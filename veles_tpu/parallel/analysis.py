"""Compiled-program communication analysis.

Used by scripts/scaling.py to report how many bytes of collective
traffic one compiled train step actually issues (the honest input to
the ICI scaling model), and handy for eyeballing sharding regressions.
"""

import re

__all__ = ["parse_collective_bytes", "parse_collective_ops",
           "collective_bytes"]

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "f64": 8, "s64": 8, "u64": 8, "pred": 1, "s8": 1,
                "u8": 1, "s16": 2, "u16": 2, "c64": 8, "c128": 16}

# XLA:TPU rewrites collectives to async -start/-done pairs in optimized
# HLO; counting the -start (plus the sync forms CPU keeps) covers both
_COLLECTIVES = ("all-reduce(", "all-reduce-start(",
                "all-gather(", "all-gather-start(",
                "reduce-scatter(",
                "all-to-all(",
                "collective-permute(", "collective-permute-start(")


def _base(kind):
    return kind.rstrip("(").replace("-start", "")


def parse_collective_ops(hlo_text, kinds=_COLLECTIVES):
    """Per-OP collective inventory of optimized HLO text: a list of
    ``{"kind", "bytes", "parts", "elems"}`` in program order,
    ``parts``/``elems`` the byte size and element count of each
    operand of a tuple-shaped op (XLA:CPU widens a bfloat16 all-reduce
    to float32, so element counts are what compares across
    platforms).  This is how the bucketed gradient all-reduce is
    audited (scripts/scaling.py, the dist smoke test): the flat path
    moves ONE gradient payload, the bucketed path one per bucket.  XLA's all-reduce combiner may still
    issue several payloads as one tuple op (XLA:CPU does, for KB-sized
    buckets) — the op count then collapses while ``parts`` keeps every
    bucket visible."""
    ops = []
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        for kind in kinds:
            if kind not in line:
                continue
            shapes_part = line.split("=", 1)[1].split(kind, 1)[0]
            parts, elems = [], []
            for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", shapes_part):
                if dt not in _DTYPE_BYTES:
                    continue
                count = 1
                for d in dims.split(","):
                    if d:
                        count *= int(d)
                elems.append(count)
                parts.append(count * _DTYPE_BYTES[dt])
            ops.append({"kind": _base(kind), "bytes": sum(parts),
                        "parts": parts, "elems": elems})
            break
    return ops


def parse_collective_bytes(hlo_text, kinds=_COLLECTIVES):
    """Sum result bytes of collective ops in optimized HLO text.

    Handles tuple-shaped results ("ar = (f32[96], f32[11,11,3,96], ...)
    all-reduce(...)").  Async -start forms count under their base kind
    ("all-reduce-start" -> "all-reduce").  Returns {kind: bytes} with a
    "total" key.
    """
    out = {_base(kind): 0 for kind in kinds}
    for op in parse_collective_ops(hlo_text, kinds):
        out[op["kind"]] += op["bytes"]
    out["total"] = sum(out.values())
    return out


def collective_bytes(jitted_fn, *example_args):
    """Compile ``jitted_fn`` for the example args and report its
    collective traffic: parse_collective_bytes of the optimized HLO."""
    compiled = jitted_fn.lower(*example_args).compile()
    return parse_collective_bytes(compiled.as_text())
