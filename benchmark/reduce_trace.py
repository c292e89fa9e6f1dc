"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
readers use.  Read with nothing but jax (``jax.profiler.ProfileData``).

What a TPU's trace holds (looked at by hand, PERF.md section 5): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per execution of a compiled program, named
``jit_<function>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's text, shapes included), and
one plane ``/host:CPU`` whose line ``python3`` holds the Python
tracer's calls (``$<file>:<line> <function>``).

Steps are counted FROM THE TRACE: the executions of the train-step
program on the modules line.  The first one seen is dropped (the trace
may have started inside it); the window runs from the start of the next
to the start of the last, so it holds whole steps only, with everything
a step runs between two train-step programs (the loader's gather, the
decision's sums) inside it.  start_trace and stop_trace, which cost the
host tenths of a second, fall outside.
"""

import glob
import os
import re

SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")
MOSAIC = "tpu_custom_call"
HOST_LINE = "python3"


def find_xplane(directory):
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no *.xplane.pb under %s" % directory)
    return found[-1]


def load(path):
    """{"devices": {plane: {line: [(name, start_s, seconds)]}},
    "host": [(name, start_s, seconds)]} of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices[plane.name] = {}
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    lines[line.name] = events_of(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == HOST_LINE:
                    host = events_of(line)
    return {"devices": devices, "host": host}


def events_of(line):
    return sorted((event.name, event.start_ns * 1e-9,
                   event.duration_ns * 1e-9) for event in line.events)


def union_seconds(intervals):
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def clip(events, lo, hi):
    """The part of each (name, start, seconds) inside [lo, hi]."""
    out = []
    for name, start, seconds in events:
        a, b = max(start, lo), min(start + seconds, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def short_name(instruction, limit=120):
    """``<op name> <result shape>`` of an HLO instruction's text."""
    head, _, rest = instruction.partition(" = ")
    shape = SHAPE.search(rest)
    label = head.lstrip("%")
    if shape:
        label += " " + shape.group(0)
    return label[:limit]


def leading_dims(instruction):
    """The leading dimension of every shape in an instruction's text."""
    return {int(dims.split(",")[0]) for dims in SHAPE.findall(instruction)
            if dims}


def idle_gaps(ops, lo, hi):
    """[(start, end)] inside [lo, hi] in which no op ran."""
    gaps, reach = [], lo
    for _, start, seconds in sorted(ops, key=lambda e: e[1]):
        if start > reach:
            gaps.append((reach, start))
        reach = max(reach, start + seconds)
    if hi > reach:
        gaps.append((reach, hi))
    return gaps


def host_activity(host, moment):
    """The innermost traced Python call that covers ``moment``."""
    best = None
    for name, start, seconds in host:
        if start <= moment <= start + seconds and (
                best is None or seconds < best[1]):
            best = (name, seconds)
    return best[0].lstrip("$") if best else "no traced host call"


def reduce_plane(lines, host, step_module):
    """One chip's numbers, or None where no whole step was traced."""
    runs = [e for e in lines.get("XLA Modules", ())
            if e[0].split("(")[0] == step_module][1:]
    if len(runs) < 2:
        return None
    lo, hi = runs[0][1], runs[-1][1]
    steps = len(runs) - 1
    ops = clip(lines.get("XLA Ops", ()), lo, hi)
    busy = union_seconds((s, s + d) for _, s, d in ops)
    by_op = {}
    for name, _, seconds in ops:
        by_op[name] = by_op.get(name, 0.0) + seconds
    by_host = {}
    for start, end in idle_gaps(ops, lo, hi):
        what = host_activity(host, 0.5 * (start + end))
        by_host[what] = by_host.get(what, 0.0) + end - start
    return {"steps": steps, "window_s": hi - lo, "busy_s": busy,
            "op_seconds": by_op, "gap_seconds": by_host,
            "modules": sorted({e[0].split("(")[0] for e in clip(
                lines.get("XLA Modules", ()), lo, hi)})}


def reduce(path, step_module="jit_step"):
    """The trace's whole steps, averaged over the chips that ran any:
    ``steps`` and ``window_s`` (of the first chip), ``busy_s`` (mean),
    ``op_seconds`` and ``gap_seconds`` (mean per chip, by name).  None
    where no chip shows two executions of ``step_module``."""
    trace = load(path)
    planes = [reduce_plane(lines, trace["host"], step_module)
              for _, lines in sorted(trace["devices"].items())]
    planes = [p for p in planes if p is not None]
    if not planes:
        return None
    out = dict(planes[0], chips=len(planes))
    out["busy_s"] = sum(p["busy_s"] for p in planes) / len(planes)
    for key in ("op_seconds", "gap_seconds"):
        merged = {}
        for plane in planes:
            for name, seconds in plane[key].items():
                merged[name] = merged.get(name, 0.0) + seconds / len(planes)
        out[key] = merged
    return out


def op_seconds_where(trace, keep):
    """Seconds per step of the ops whose instruction text ``keep``s."""
    return sum(seconds for name, seconds in trace["op_seconds"].items()
               if keep(name)) / trace["steps"]


def breakdown(trace, top=10):
    """The run line's ``breakdown``: the device ops that took most time
    and the longest idle gaps by what the host was doing, seconds over
    the traced whole steps."""
    by_short = {}
    for name, seconds in trace["op_seconds"].items():
        key = short_name(name)
        by_short[key] = by_short.get(key, 0.0) + seconds

    def head(table):
        return [[name, seconds] for name, seconds in sorted(
            table.items(), key=lambda item: -item[1])[:top]]

    return {"device_ops": head(by_short),
            "idle_gaps": head(trace["gap_seconds"])}
