#!/usr/bin/env python3
"""Which of the program's own spans covers each idle gap of the device,
and what the host does in a step: a kept ``*.xplane.pb``
(``benchmark/run.py --trace 1 --keep-trace DIR``) read on ONE clock.

    python3 scripts/trace_gaps.py TRACE.xplane.pb [--top 3]
                                  [--step-module jit_step]

The program's spans are the ``veles/<span>`` events of ``/host:CPU``
(``veles_tpu/observe/trace.py``: every scope is a
``jax.profiler.TraceAnnotation`` while a session is live); the device's
ops are the ``XLA Ops`` line of ``/device:TPU:0``.  The window is the
benchmark's: from the second execution of the train-step program to the
last (``benchmark/reduce_trace.py``, whose loading and interval
arithmetic this reuses).  Prints the window's idle share, the ``--top``
longest gaps with the chain of ``veles/`` spans open at each gap's
middle on every host thread, and each span's seconds per step inside
the window.  One JSON object last."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PREFIX = "veles/"


def host_threads(path):
    """[(line name, [(name, start_s, seconds)])] of ``/host:CPU``: the
    ``veles/`` spans and the step annotations of each thread."""
    from jax.profiler import ProfileData
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = sorted(
                (event.name, event.start_ns * 1e-9,
                 event.duration_ns * 1e-9) for event in line.events
                if event.name.startswith(PREFIX) or
                event.name == "train_step")
            if events:
                threads.append((line.name, events))
    return threads


def open_at(events, moment):
    """Names of the spans covering ``moment``, outermost first."""
    covering = [(seconds, name) for name, start, seconds in events
                if start <= moment <= start + seconds]
    return [name for _, name in sorted(covering, reverse=True)]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace")
    parser.add_argument("--top", type=int, default=3)
    parser.add_argument("--step-module", default="jit_step")
    args = parser.parse_args(argv)

    from benchmark import reduce_trace
    loaded = reduce_trace.load(args.trace)
    plane = sorted(loaded["devices"])[0]
    lines = loaded["devices"][plane]
    runs = [e for e in lines.get("XLA Modules", ())
            if e[0].split("(")[0] == args.step_module][1:]
    if len(runs) < 2:
        sys.stderr.write("no whole step of %s in %s\n"
                         % (args.step_module, args.trace))
        return 1
    lo, hi = runs[0][1], runs[-1][1]
    steps = len(runs) - 1
    ops = reduce_trace.clip(lines.get("XLA Ops", ()), lo, hi)
    busy = reduce_trace.union_seconds((s, s + d) for _, s, d in ops)
    gaps = sorted(reduce_trace.idle_gaps(ops, lo, hi),
                  key=lambda gap: gap[0] - gap[1])
    threads = host_threads(args.trace)
    print("%s: %d whole steps, window %.3f ms, idle %.4f %% in %d gaps"
          % (plane, steps, (hi - lo) * 1e3, 100 * (1 - busy / (hi - lo)),
             len(gaps)))
    out = {"steps": steps, "window_s": hi - lo, "busy_s": busy,
           "gaps": [], "host_s_per_step": {}}
    for start, end in gaps[:args.top]:
        middle = 0.5 * (start + end)
        covering = {"%s#%d" % (name, index): open_at(events, middle)
                    for index, (name, events) in enumerate(threads)}
        covering = {k: v for k, v in covering.items() if v}
        frame = reduce_trace.host_activity(loaded["host"], middle)
        print("  gap of %.1f us at +%.3f ms: %s (innermost Python "
              "frame: %s)" % ((end - start) * 1e6, (start - lo) * 1e3,
                              covering or "no veles/ span open", frame))
        out["gaps"].append({"seconds": end - start,
                            "at_s": start - lo, "spans": covering,
                            "python_frame": frame})
    for index, (name, events) in enumerate(threads):
        table = {}
        for span, _, seconds in reduce_trace.clip(events, lo, hi):
            row = table.setdefault(span, [0, 0.0])
            row[0] += 1
            row[1] += seconds
        print("  host thread %s#%d, per step inside the window:"
              % (name, index))
        for span, (count, seconds) in sorted(
                table.items(), key=lambda item: -item[1][1]):
            print("    %-28s %9.3f ms  (%.2f a step)"
                  % (span, 1e3 * seconds / steps, count / steps))
            out["host_s_per_step"]["%s#%d:%s" % (name, index, span)] = \
                seconds / steps
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
