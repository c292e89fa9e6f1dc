"""Per-kernel-family search spaces for the schedule autotuner.

One family per parameterized Pallas kernel (docs/kernels.md):

- ``matmul`` — ``ops/matmul.py``'s (bm, bn, bk) tiles;
- ``conv_vjp`` — ``ops/conv_vjp.py``'s (bi, bj, bk) wgrad tiles;
- ``pool_bwd`` — ``ops/pool_bwd.py``'s output-width block (W tiling);
- ``attention`` — ``ops/attention.py``'s (bq, bk) flash tiles.

Each family owns four things the GA needs: the **search space** as
:class:`veles_tpu.genetics.config.Tune` markers (so the stock
GeneticsOptimizer drives it unchanged), **quantization** of raw genes
to MXU-legal multiples (sublane 8 on the second-minor axis, lane 128
on the minor axis — Mosaic tiles below the hardware quanta just pad
back up, so off-grid genes are pure duplicate schedules), a **VMEM
feasibility** check that rejects overflowing candidates BEFORE any
compile is paid, and a **runner builder** that turns (spec, schedule)
into the timed callable the shared measurement discipline
(``tune/measure.py``) ranks.

The ``*_spec`` builders at the bottom are the ONE definition of each
family's cache-key coordinates — the kernels' consult sites and the
MFU-attribution provenance lookups both call them, so the key a tuner
writes is byte-identical to the key a kernel later reads.

Schedules change tile/grid SCHEDULING only, never math: the precision
level and dtype are key coordinates, not genes, and the parity tests
(tests/test_tune.py) hold tuned-vs-static results bit-equal on
representable operands.
"""

import functools
import logging

from veles_tpu.genetics.config import Tune

__all__ = ["FAMILIES", "family_for", "matmul_spec", "matmul_int8_spec",
           "conv_vjp_spec", "pool_bwd_spec", "attention_spec",
           "valid_schedule", "matmul_seed_candidates",
           "current_kernel_version", "TUNE_VMEM_BUDGET_BYTES"]

logger = logging.getLogger("veles_tpu.tune")

#: per-grid-step VMEM ceiling for candidate REJECTION before compile —
#: aligned with ops/pool_bwd.POOL_VMEM_BUDGET_BYTES; the compile-time
#: Mosaic check stays the backstop for shapes that squeak past
TUNE_VMEM_BUDGET_BYTES = 12 * 2 ** 20

_warned = set()


def _warn_once(key, message, *args):
    if key not in _warned:
        _warned.add(key)
        logger.warning(message, *args)


def _ceil_mult(value, mult):
    rem = value % mult
    return value if rem == 0 else value + mult - rem


def _quant(value, mult, lo, hi):
    """Round a raw gene to the nearest legal multiple inside
    [lo, hi] — clamped duplicates collapse onto one schedule, which the
    tuner's fitness memo then serves for free."""
    q = int(round(float(value) / mult)) * mult
    if q < mult:
        q = mult
    return max(lo, min(hi, q))


def _itemsize(dtype):
    import numpy
    if str(dtype) == "bfloat16":
        return 2
    return numpy.dtype(str(dtype)).itemsize


def matmul_seed_candidates(dtype, precision_level):
    """ops/matmul.py's curated tile list — measured winners on real
    chips, kept as the GA's seed population AND the plain candidate
    sweep ``autotune_matmul`` still runs."""
    candidates = [(256, 256, 256), (512, 512, 512), (512, 512, 1024),
                  (512, 512, 2048), (256, 256, 1024), (512, 1024, 512),
                  (1024, 512, 512), (256, 512, 1024)]
    if str(dtype) == "float32" and precision_level in (0, 1):
        # taller-M / wider-N tiles for the f32 paths (level 0's three
        # bf16 dots per K-step and level 1's six-pass HIGHEST products
        # + Kahan both shift the VMEM/compute balance away from the
        # square default): a (768, 512, 512) tile measured ~1.25x over
        # (512, 512, 512) at 3001^2 on v5e for level 0
        candidates += [(768, 512, 512), (640, 512, 512),
                       (512, 640, 512), (512, 640, 640)]
    return candidates


class MatmulFamily(object):
    """(bm, bn, bk) tiles of the tiled Pallas matmul."""

    name = "matmul"

    def space(self, spec):
        mp, kp, np_ = spec["shape"]
        return {
            "bm": Tune(min(512, mp), 8, min(1024, mp)),
            "bn": Tune(min(512, np_), 128, min(2048, np_)),
            "bk": Tune(min(512, kp), 128, min(2048, kp)),
        }

    def quantize(self, spec, genes):
        mp, kp, np_ = spec["shape"]
        return {"blocks": [
            _quant(genes["bm"], 8, 8, min(1024, mp)),
            _quant(genes["bn"], 128, 128, min(2048, np_)),
            _quant(genes["bk"], 128, 128, min(2048, kp)),
        ]}

    def footprint(self, spec, schedule):
        bm, bn, bk = schedule["blocks"]
        isz = _itemsize(spec["dtype"])
        return (bm * bk * isz + bk * bn * isz   # a + b blocks
                + 2 * bm * bn * 4               # f32 acc + comp
                + bm * bn * isz)                # out block

    def feasible(self, spec, schedule):
        return self.footprint(spec, schedule) <= TUNE_VMEM_BUDGET_BYTES

    def seeds(self, spec):
        # the GA seeds at most `population` chromosomes, so the
        # dtype-specific measured winners (appended LAST in the sweep's
        # curated order) go FIRST here — a population of 8 must not
        # silently drop the known f32 best tiles
        curated = matmul_seed_candidates(spec["dtype"],
                                         spec["precision_level"])
        generic = matmul_seed_candidates("bfloat16", 2)
        specific = [c for c in curated if c not in generic]
        return [{"blocks": list(c)} for c in specific + generic]

    def default(self, spec):
        from veles_tpu.ops import matmul as _m
        return {"blocks": list(_m._DEFAULT_BLOCKS)}

    def genes_of(self, schedule):
        bm, bn, bk = schedule["blocks"]
        return {"bm": bm, "bn": bn, "bk": bk}

    def validate(self, schedule):
        blocks = schedule.get("blocks")
        if (isinstance(blocks, (list, tuple)) and len(blocks) == 3
                and all(isinstance(b, int) and b > 0 for b in blocks)
                and blocks[0] % 8 == 0 and blocks[1] % 128 == 0
                and blocks[2] % 128 == 0):
            return {"blocks": [int(b) for b in blocks]}
        return None

    def build_runner(self, spec, schedule):
        """(warm, run): ``warm()`` compiles (VMEM-overflow candidates
        raise here, before any timed chain); ``run(n)`` executes an
        n-long chain ended by a completion fetch.  Square self-multiply
        shapes chain DEPENDENTLY (matmul_benchmark's methodology);
        rectangular shapes queue n dispatches and block once."""
        import jax
        import jax.numpy as jnp
        import numpy

        from veles_tpu.ops.matmul import matmul

        m, k, n = spec.get("raw", {}).get("mkn", spec["shape"])
        rng = numpy.random.RandomState(13)
        dtype = jnp.dtype(spec["dtype"]) if spec["dtype"] != "bfloat16" \
            else jnp.bfloat16
        a = jnp.asarray((rng.rand(m, k) - 0.5) * 0.01, dtype)
        b = jnp.asarray((rng.rand(k, n) - 0.5) * 0.01, dtype)
        blocks = tuple(schedule["blocks"])
        level = spec["precision_level"]

        if k == n:
            def mm(x):
                return matmul(x, b, precision_level=level,
                              blocks=blocks)

            def run(count):
                acc = a
                for _ in range(count):
                    acc = mm(acc)
                float(acc[0, 0].astype(jnp.float32))
        else:
            def run(count):
                out = None
                for _ in range(count):
                    out = matmul(a, b, precision_level=level,
                                 blocks=blocks)
                jax.block_until_ready(out)

        def warm():
            run(1)

        return warm, run


class MatmulInt8Family(object):
    """(bm, bn, bk) tiles of the int8 quantized matmul
    (``ops/matmul_int8.py``) — its OWN family: int8 shifts the
    MXU-legal quanta (sublane 32 on M vs f32's 8, lanes still 128) and
    the VMEM balance (1-byte operand tiles vs a 4-byte int32
    accumulator), so f32-tuned tiles are off-grid here and the digest
    carries ``MATMUL_INT8_KERNEL_VERSION`` so neither family can ever
    serve the other."""

    name = "matmul_int8"

    def space(self, spec):
        mp, kp, np_ = spec["shape"]
        return {
            "bm": Tune(min(256, mp), 32, min(1024, mp)),
            "bn": Tune(min(512, np_), 128, min(2048, np_)),
            "bk": Tune(min(512, kp), 128, min(2048, kp)),
        }

    def quantize(self, spec, genes):
        mp, kp, np_ = spec["shape"]
        return {"blocks": [
            _quant(genes["bm"], 32, 32, min(1024, mp)),
            _quant(genes["bn"], 128, 128, min(2048, np_)),
            _quant(genes["bk"], 128, 128, min(2048, kp)),
        ]}

    def footprint(self, spec, schedule):
        bm, bn, bk = schedule["blocks"]
        return (bm * bk + bk * bn     # int8 a + b blocks (1 B)
                + bm * bn * 4         # int32 accumulator
                + bm * bn * 4         # f32 out block
                + 2 * bn * 4)         # scale + bias rows

    def feasible(self, spec, schedule):
        return self.footprint(spec, schedule) <= TUNE_VMEM_BUDGET_BYTES

    def seeds(self, spec):
        return [{"blocks": list(c)} for c in
                [(256, 512, 512), (512, 512, 512), (256, 256, 512),
                 (512, 512, 1024), (256, 512, 1024), (128, 512, 512)]]

    def default(self, spec):
        from veles_tpu.ops import matmul_int8 as _m
        return {"blocks": list(_m._DEFAULT_BLOCKS)}

    def genes_of(self, schedule):
        bm, bn, bk = schedule["blocks"]
        return {"bm": bm, "bn": bn, "bk": bk}

    def validate(self, schedule):
        blocks = schedule.get("blocks")
        if (isinstance(blocks, (list, tuple)) and len(blocks) == 3
                and all(isinstance(b, int) and b > 0 for b in blocks)
                and blocks[0] % 32 == 0 and blocks[1] % 128 == 0
                and blocks[2] % 128 == 0):
            return {"blocks": [int(b) for b in blocks]}
        return None

    def build_runner(self, spec, schedule):
        """Queued-dispatch runner: the int8 matmul's output is f32, so
        there is no dependent int8 chain to thread — ``run(n)`` queues
        n dispatches and blocks once, like the rectangular f32 path."""
        import jax
        import jax.numpy as jnp
        import numpy

        from veles_tpu.ops.matmul_int8 import matmul_int8

        m, k, n = spec.get("raw", {}).get("mkn", spec["shape"])
        rng = numpy.random.RandomState(17)
        a = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.randint(-127, 128, (k, n)), jnp.int8)
        scale = jnp.asarray(rng.rand(n) * 1e-3 + 1e-4, jnp.float32)
        blocks = tuple(schedule["blocks"])

        def run(count):
            out = None
            for _ in range(count):
                out = matmul_int8(a, b, scale, blocks=blocks)
            jax.block_until_ready(out)

        def warm():
            run(1)

        return warm, run


class ConvVjpFamily(object):
    """(bi, bj, bk) = (Cin, Cout, P) tiles of the fused conv-VJP
    wgrad contraction."""

    name = "conv_vjp"

    def space(self, spec):
        _taps, pp, cip, cop = spec["shape"]
        return {
            "bi": Tune(min(256, cip), 128, min(1024, cip)),
            "bj": Tune(min(256, cop), 128, min(1024, cop)),
            "bk": Tune(min(512, pp), 8, min(2048, pp)),
        }

    def quantize(self, spec, genes):
        _taps, pp, cip, cop = spec["shape"]
        return {"blocks": [
            _quant(genes["bi"], 128, 128, min(1024, cip)),
            _quant(genes["bj"], 128, 128, min(1024, cop)),
            _quant(genes["bk"], 8, 8, min(2048, pp)),
        ]}

    def footprint(self, spec, schedule):
        bi, bj, bk = schedule["blocks"]
        isz = _itemsize(spec["dtype"])
        return (bk * bi * isz          # tap-stack block
                + 2 * bk * bj * isz    # y + dy blocks
                + bk * bj * isz        # err out block
                + bi * bj * 4          # gw out block (f32)
                + 2 * bi * bj * 4      # acc + comp scratch
                + 8 * bj * 4)          # bias scratch

    def feasible(self, spec, schedule):
        return self.footprint(spec, schedule) <= TUNE_VMEM_BUDGET_BYTES

    def seeds(self, spec):
        return [{"blocks": list(c)} for c in
                [(256, 256, 512), (128, 256, 512), (256, 128, 512),
                 (256, 256, 1024), (128, 128, 256), (512, 256, 512)]]

    def default(self, spec):
        from veles_tpu.ops import conv_vjp as _c
        return {"blocks": list(_c._DEFAULT_BLOCKS)}

    def genes_of(self, schedule):
        bi, bj, bk = schedule["blocks"]
        return {"bi": bi, "bj": bj, "bk": bk}

    def validate(self, schedule):
        blocks = schedule.get("blocks")
        if (isinstance(blocks, (list, tuple)) and len(blocks) == 3
                and all(isinstance(b, int) and b > 0 for b in blocks)
                and blocks[0] % 128 == 0 and blocks[1] % 128 == 0
                and blocks[2] % 8 == 0):
            return {"blocks": [int(b) for b in blocks]}
        return None

    def build_runner(self, spec, schedule):
        import jax
        import jax.numpy as jnp
        import numpy

        from veles_tpu.ops.conv_vjp import fused_conv_vjp

        raw = spec["raw"]
        n, h, w_sp, ci = raw["x_shape"]
        oh, ow = raw["y_hw"]
        ky, kx, cout = raw["ky"], raw["kx"], raw["cout"]
        rng = numpy.random.RandomState(7)
        dtype = jnp.bfloat16 if spec["dtype"] == "bfloat16" \
            else jnp.dtype(spec["dtype"])
        x = jnp.asarray(rng.randn(n, h, w_sp, ci) * 0.1, dtype)
        w = jnp.asarray(rng.randn(ky, kx, ci, cout) * 0.1, dtype)
        y = jnp.asarray(rng.randn(n, oh, ow, cout) * 0.1, dtype)
        dy = jnp.asarray(rng.randn(n, oh, ow, cout) * 0.1, dtype)
        blocks = tuple(schedule["blocks"])

        def run(count):
            gw = None
            for _ in range(count):
                _, gw, _ = fused_conv_vjp(
                    x, w, y, dy, activation=raw["activation"],
                    padding=tuple(raw["padding"]),
                    sliding=tuple(raw["sliding"]),
                    need_err_input=False,
                    precision_level=spec["precision_level"],
                    blocks=blocks)
            jax.block_until_ready(gw)

        def warm():
            run(1)

        return warm, run


class AttentionFamily(object):
    """(bq, bk) q/k tiles of the flash-attention kernels
    (``ops/attention.py``).  bq rides sublanes of the score tile
    (quantum 8); bk rides its lanes (quantum 128).  The head dim is
    lane-padded to 128 and is a key coordinate, not a gene — the
    kernel holds a whole (padded) head row per tile."""

    name = "attention"

    def space(self, spec):
        _b, tq, tk, _dhp = spec["shape"]
        return {
            "bq": Tune(min(256, tq), 8, min(1024, tq)),
            "bk": Tune(min(256, tk), 128, min(2048, tk)),
        }

    def quantize(self, spec, genes):
        _b, tq, tk, _dhp = spec["shape"]
        return {"blocks": [
            _quant(genes["bq"], 8, 8, min(1024, tq)),
            _quant(genes["bk"], 128, 128, min(2048, tk)),
        ]}

    def footprint(self, spec, schedule):
        bq, bk = schedule["blocks"]
        dhp = spec["shape"][3]
        isz = _itemsize(spec["dtype"])
        return (bq * dhp * isz          # q block
                + 2 * bk * dhp * isz    # k + v blocks
                + bq * dhp * isz        # out block
                + bq * dhp * 4          # f32 acc scratch
                + 2 * bq * 128 * 4      # m + l scratch
                + bq * 128 * 4          # lse block
                + 2 * bq * bk * 4)      # score + prob tiles

    def feasible(self, spec, schedule):
        return self.footprint(spec, schedule) <= TUNE_VMEM_BUDGET_BYTES

    def seeds(self, spec):
        return [{"blocks": list(c)} for c in
                [(256, 256), (128, 256), (256, 512), (512, 256),
                 (128, 128), (512, 512)]]

    def default(self, spec):
        from veles_tpu.ops import attention as _a
        return {"blocks": list(_a._DEFAULT_BLOCKS)}

    def genes_of(self, schedule):
        bq, bk = schedule["blocks"]
        return {"bq": bq, "bk": bk}

    def validate(self, schedule):
        blocks = schedule.get("blocks")
        if (isinstance(blocks, (list, tuple)) and len(blocks) == 2
                and all(isinstance(b, int) and b > 0 for b in blocks)
                and blocks[0] % 8 == 0 and blocks[1] % 128 == 0):
            return {"blocks": [int(b) for b in blocks]}
        return None

    def build_runner(self, spec, schedule):
        """Queued-dispatch runner over the full custom_vjp step
        (forward + both backward kernels via jax.grad — the composition
        a train step actually pays for)."""
        import jax
        import jax.numpy as jnp
        import numpy

        from veles_tpu.ops.attention import flash_attention

        b, t, dh = spec["raw"]["btd"]
        rng = numpy.random.RandomState(23)
        dtype = jnp.bfloat16 if spec["dtype"] == "bfloat16" \
            else jnp.dtype(spec["dtype"])
        q = jnp.asarray(rng.randn(b, t, dh) * 0.1, dtype)
        k = jnp.asarray(rng.randn(b, t, dh) * 0.1, dtype)
        v = jnp.asarray(rng.randn(b, t, dh) * 0.1, dtype)
        blocks = tuple(schedule["blocks"])
        level = spec["precision_level"]

        grad = jax.grad(lambda q_, k_, v_: jnp.sum(
            flash_attention(q_, k_, v_, precision_level=level,
                            blocks=blocks).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))

        def run(count):
            out = None
            for _ in range(count):
                out = grad(q, k, v)
            jax.block_until_ready(out)

        def warm():
            run(1)

        return warm, run


class PoolBwdFamily(object):
    """Output-width block (W tiling) of the pool select-and-scatter
    backward.  Only non-overlapping windows (kx == sx, ky == sy) admit
    halo-free W tiling, so overlapping shapes are untunable.  W is the
    sublane axis of the kernel's blocks: a block is a multiple of 8
    output columns or the whole width."""

    name = "pool_bwd"

    def space(self, spec):
        _n, _h, _w, _c, _oh, ow, ky, kx, sy, sx = spec["shape"]
        if kx != sx or ky != sy or ow < 2:
            return None  # untunable: no halo-free W tiling exists
        return {"owb": Tune(ow, 1, ow)}

    def quantize(self, spec, genes):
        ow = spec["shape"][5]
        owb = int(round(float(genes["owb"]) / 8.0)) * 8
        return {"owb": ow if owb >= ow else max(8, owb)}

    def footprint(self, spec, schedule):
        # the kernel planner's OWN footprint formula — shared, so the
        # feasibility gate can never drift from what Mosaic gets
        from veles_tpu.ops.pool_bwd import pool_block_footprint
        _n, _h, _w, _c, oh, _ow, ky, kx, sy, sx = spec["shape"]
        return pool_block_footprint(
            oh, schedule["owb"], (ky, kx), (sx, sy),
            _itemsize(spec["dtype"]))

    def feasible(self, spec, schedule):
        from veles_tpu.ops.pool_bwd import POOL_VMEM_BUDGET_BYTES
        return (self.footprint(spec, schedule)
                <= POOL_VMEM_BUDGET_BYTES)

    def seeds(self, spec):
        ow = spec["shape"][5]
        owbs = {self.quantize(spec, {"owb": owb})["owb"]
                for owb in (ow, -(-ow // 2), -(-ow // 4), 8)}
        return [{"owb": owb} for owb in sorted(owbs, reverse=True)]

    def default(self, spec):
        ow = spec["shape"][5]
        return {"owb": ow}

    def genes_of(self, schedule):
        return {"owb": schedule["owb"]}

    def validate(self, schedule):
        owb = schedule.get("owb")
        if isinstance(owb, int) and owb > 0:
            return {"owb": owb}
        return None

    def build_runner(self, spec, schedule):
        import jax
        import jax.numpy as jnp
        import numpy

        from veles_tpu.models.pooling import MaxPooling
        from veles_tpu.ops.pool_bwd import max_pool_bwd

        raw = spec["raw"]
        n, h, w_sp, c = raw["x_shape"]
        window = tuple(raw["window"])
        sliding = tuple(raw["sliding"])
        rng = numpy.random.RandomState(5)
        dtype = jnp.bfloat16 if spec["dtype"] == "bfloat16" \
            else jnp.dtype(spec["dtype"])
        x = jnp.asarray(rng.randn(n, h, w_sp, c), dtype)
        y = MaxPooling.apply({}, x, window=window, sliding=sliding,
                             pallas_bwd=False)
        dy = jnp.asarray(rng.randn(*y.shape), dtype)
        owb = int(schedule["owb"])

        def run(count):
            out = None
            for _ in range(count):
                out = max_pool_bwd(x, y, dy, window=window,
                                   sliding=sliding, owb=owb)
            jax.block_until_ready(out)

        def warm():
            run(1)

        return warm, run


FAMILIES = {
    "matmul": MatmulFamily(),
    "matmul_int8": MatmulInt8Family(),
    "conv_vjp": ConvVjpFamily(),
    "pool_bwd": PoolBwdFamily(),
    "attention": AttentionFamily(),
}


def family_for(op):
    family = FAMILIES.get(op)
    if family is None:
        raise KeyError("unknown kernel family %r (have %s)" %
                       (op, sorted(FAMILIES)))
    return family


def current_kernel_version(op):
    """The family's CURRENT kernel algorithm version (the value its
    ``*_spec`` builder rides in ``extra``) or None for families without
    one — the measurement log's staleness coordinate: triples measured
    on an old algorithm must not train the cost model for a new one."""
    if op in ("matmul",):
        from veles_tpu.ops.matmul import MATMUL_KERNEL_VERSION
        return MATMUL_KERNEL_VERSION
    if op == "matmul_int8":
        from veles_tpu.ops.matmul_int8 import MATMUL_INT8_KERNEL_VERSION
        return MATMUL_INT8_KERNEL_VERSION
    if op == "conv_vjp":
        from veles_tpu.ops.conv_vjp import CONV_VJP_KERNEL_VERSION
        return CONV_VJP_KERNEL_VERSION
    if op == "attention":
        from veles_tpu.ops.attention import ATTENTION_KERNEL_VERSION
        return ATTENTION_KERNEL_VERSION
    if op == "pool_bwd":
        from veles_tpu.ops.pool_bwd import POOL_BWD_KERNEL_VERSION
        return POOL_BWD_KERNEL_VERSION
    return None


def valid_schedule(op, schedule):
    """Structural validation of a cache-served schedule: the family's
    normalized dict, or None (with ONE warning) for anything malformed
    — a stale/corrupt entry must degrade to the static tables, never
    crash a kernel call."""
    family = FAMILIES.get(op)
    if family is None or not isinstance(schedule, dict):
        return None
    normalized = family.validate(schedule)
    if normalized is None:
        _warn_once(
            ("invalid", op, str(schedule)),
            "ignoring malformed tuned schedule for %s: %r (static "
            "tables serve this shape)", op, schedule)
    return normalized


# -- cache-key spec builders (ONE definition per family) ---------------------


def matmul_spec(m, k, n, dtype, precision_level):
    """The matmul consult/tune spec: shape is PADDED to the MXU quanta
    (sublane 8 on M, lane 128 on K/N) so raw shapes that run the same
    grid share one cache entry; the kernel version rides ``extra``."""
    from veles_tpu.ops.matmul import MATMUL_KERNEL_VERSION
    return {
        "op": "matmul",
        "shape": [_ceil_mult(int(m), 8), _ceil_mult(int(k), 128),
                  _ceil_mult(int(n), 128)],
        "dtype": str(dtype),
        "precision_level": int(precision_level),
        "extra": {"kernel_version": MATMUL_KERNEL_VERSION},
        "raw": {"mkn": [int(m), int(k), int(n)]},
    }


def matmul_int8_spec(m, k, n):
    """The int8 matmul consult/tune spec: shape PADDED to the int8 MXU
    quanta (sublane 32 on M, lane 128 on K/N); dtype is pinned
    ``int8`` and the precision level 0 — the int8 level has no
    sub-ladder (integer accumulation is already exact)."""
    from veles_tpu.ops.matmul_int8 import MATMUL_INT8_KERNEL_VERSION
    return {
        "op": "matmul_int8",
        "shape": [_ceil_mult(int(m), 32), _ceil_mult(int(k), 128),
                  _ceil_mult(int(n), 128)],
        "dtype": "int8",
        "precision_level": 0,
        "extra": {"kernel_version": MATMUL_INT8_KERNEL_VERSION},
        "raw": {"mkn": [int(m), int(k), int(n)]},
    }


def conv_vjp_spec(x_shape, ky, kx, cout, y_hw, dtype, precision_level,
                  padding=(0, 0, 0, 0), sliding=(1, 1),
                  activation="linear"):
    """The fused conv-VJP consult/tune spec: shape is (taps, padded P,
    padded Cin, padded Cout) — the wgrad contraction's grid coordinates."""
    from veles_tpu.ops.conv_vjp import CONV_VJP_KERNEL_VERSION
    n, _h, _w, ci = [int(s) for s in x_shape]
    oh, ow = [int(s) for s in y_hw]
    p = n * oh * ow
    return {
        "op": "conv_vjp",
        "shape": [int(ky) * int(kx), _ceil_mult(p, 8),
                  _ceil_mult(ci, 128), _ceil_mult(int(cout), 128)],
        "dtype": str(dtype),
        "precision_level": int(precision_level),
        "extra": {"kernel_version": CONV_VJP_KERNEL_VERSION},
        "raw": {"x_shape": [int(s) for s in x_shape],
                "y_hw": [oh, ow], "ky": int(ky), "kx": int(kx),
                "cout": int(cout),
                "padding": [int(p_) for p_ in padding],
                "sliding": [int(s) for s in sliding],
                "activation": str(activation)},
    }


def attention_spec(b, t, dh, dtype, precision_level):
    """The flash-attention consult/tune spec: shape is (batch-heads,
    T padded to the q sublane quantum, T padded to the k lane quantum,
    lane-padded head dim) — the kernel grid's coordinates; the raw
    (B, T, dh) rides ``raw`` for the runner."""
    from veles_tpu.ops.attention import ATTENTION_KERNEL_VERSION
    return {
        "op": "attention",
        "shape": [int(b), _ceil_mult(int(t), 8),
                  _ceil_mult(int(t), 128), _ceil_mult(int(dh), 128)],
        "dtype": str(dtype),
        "precision_level": int(precision_level),
        "extra": {"kernel_version": ATTENTION_KERNEL_VERSION},
        "raw": {"btd": [int(b), int(t), int(dh)]},
    }


def pool_bwd_spec(x_shape, out_hw, window, sliding, dtype):
    """The pool-backward consult/tune spec: raw dims ride the key (the
    kernel's W plan depends on every one of them)."""
    from veles_tpu.ops.pool_bwd import POOL_BWD_KERNEL_VERSION
    n, h, w_sp, c = [int(s) for s in x_shape]
    oh, ow = [int(s) for s in out_hw]
    ky, kx = [int(s) for s in window]
    sx, sy = [int(s) for s in sliding]
    return {
        "op": "pool_bwd",
        "shape": [n, h, w_sp, c, oh, ow, ky, kx, sy, sx],
        "dtype": str(dtype),
        "precision_level": 0,  # pooling has no precision ladder
        "extra": {"kernel_version": POOL_BWD_KERNEL_VERSION},
        "raw": {"x_shape": [n, h, w_sp, c], "window": [ky, kx],
                "sliding": [sx, sy]},
    }
