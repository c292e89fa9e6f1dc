#!/usr/bin/env python3
"""Device time by the program's own scopes from a profiler session the
program closed itself: a ``*.xplane.pb`` and the ``device_scopes.json``
that ``ProfilerHook.stop()`` wrote beside it (``VELES_PROFILE=dir``).

    python3 scripts/trace_scopes.py TRACE.xplane.pb device_scopes.json
                                    [--step-module jit_step]

``device_scopes.json`` holds, for each program the run described (the
fused step), its ``{instruction: op_name}`` table and the names of its
scopes: the parts its layer classes name and the step's own
(``veles_tpu/observe/xla_introspect.py``: ``instruction_scopes``,
``scope_names``); the
trace's whole steps and per-instruction seconds come from
``benchmark/reduce_trace.py``'s loading, as ``scripts/trace_gaps.py``'s
do.  Prints ms a step by (layer class, part, phase), leaves only (a
loop's event spans its body's), the unattributed share last — the table
the benchmark's ``*_scope_ms_per_step.train`` readers sum — and one JSON
object last."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace")
    parser.add_argument("scopes")
    parser.add_argument("--step-module", default="jit_step")
    args = parser.parse_args(argv)

    from benchmark import reduce_trace
    from veles_tpu.observe import xla_introspect
    trace = reduce_trace.reduce(args.trace, args.step_module)
    if trace is None:
        sys.stderr.write("no whole step of %s in %s\n"
                         % (args.step_module, args.trace))
        return 1
    with open(args.scopes) as fin:
        programs = json.load(fin).values()
    # name + result shape hardly repeats across programs: one table
    joined = xla_introspect.device_seconds_by_scope(
        trace["op_seconds"],
        {key: op_name for kept in programs
         for key, op_name in kept["instructions"].items()},
        sorted({part for kept in programs for part in kept["parts"]}),
        {scope: phase for kept in programs
         for scope, phase in kept["step_scopes"].items()})
    steps = trace["steps"]
    rows = sorted(((1e3 * seconds / steps, key)
                   for key, seconds in joined.items()),
                  key=lambda row: (row[1][0] is None, -row[0]))
    print("%d whole steps of %s, busy %.3f ms a step, leaves %.3f"
          % (steps, args.step_module, 1e3 * trace["busy_s"] / steps,
             sum(ms for ms, _ in rows)))
    for ms, (layer, part, phase) in rows:
        print("%10.3f  %-20s %-16s %s" % (
            ms, layer or "(no scope)", part or "-", phase or "-"))
    print(json.dumps({
        "steps": steps, "busy_ms_per_step": 1e3 * trace["busy_s"] / steps,
        "ms_per_step": [[list(key), ms] for ms, key in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
