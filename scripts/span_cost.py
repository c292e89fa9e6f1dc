#!/usr/bin/env python3
"""What one instrumented site costs: ``SpanTracer.scope`` against the
hand-rolled pattern it replaced (two ``perf_counter`` reads, a timer or
histogram update, a guarded ``tracer.complete``), with every listener
off, with the flight ring on (the default), with ``--trace`` on, and
inside a live ``jax.profiler`` session.

    python3 scripts/span_cost.py [--calls 50000] [--repeats 15]

Host cost only (no device op is dispatched); the smallest of
``--repeats`` loops of ``--calls`` calls, in nanoseconds per call.
Prints one JSON object last."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def per_call_ns(fn, calls, repeats):
    fn()
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        for _ in range(calls):
            fn()
        best = min(best, time.perf_counter() - start)
    return 1e9 * best / calls


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--calls", type=int, default=50000)
    parser.add_argument("--repeats", type=int, default=15)
    args = parser.parse_args(argv)

    import jax

    from veles_tpu.observe.flight import FlightRecorder
    from veles_tpu.observe.metrics import MetricsRegistry
    from veles_tpu.observe.trace import SpanTracer, step_annotation

    tracer = SpanTracer(flight=FlightRecorder(enabled=False),
                        max_events=1000)
    hist = MetricsRegistry().histogram("cost_s")
    timers = {"run": 0.0}

    def hand_rolled_timer():
        start = time.perf_counter()
        elapsed = time.perf_counter() - start
        timers["run"] += elapsed
        if tracer.active:
            tracer.complete("unit", start, elapsed, cat="unit")

    def scope_timer():
        with tracer.scope("unit", cat="unit", timers=(timers, "run")):
            pass

    def hand_rolled_histogram():
        start = time.perf_counter()
        elapsed = time.perf_counter() - start
        hist.observe(elapsed)
        if tracer.active:
            tracer.complete("step", start, elapsed, cat="step")

    def scope_histogram():
        with tracer.scope("step", cat="step", hist=hist):
            pass

    def step_mark():
        with step_annotation("train_step", 7):
            pass

    sites = [("timer.hand_rolled", hand_rolled_timer),
             ("timer.scope", scope_timer),
             ("histogram.hand_rolled", hand_rolled_histogram),
             ("histogram.scope", scope_histogram),
             ("step_annotation", step_mark)]

    def measure(calls=args.calls):
        return {name: round(per_call_ns(fn, calls, args.repeats), 1)
                for name, fn in sites}

    out = {"device": jax.devices()[0].platform, "calls": args.calls,
           "all_off": measure()}
    tracer._flight.enabled = True
    out["flight_ring_on"] = measure()
    tracer.start()  # the buffer fills, then events count as dropped
    out["trace_on"] = measure()
    tracer.stop()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as directory:
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            out["profiler_session_on"] = measure(
                calls=min(args.calls, 5000))
        finally:
            jax.profiler.stop_trace()
    for state, row in out.items():
        if isinstance(row, dict):
            print("%-20s %s" % (state, "  ".join(
                "%s %.0f ns" % item for item in row.items())))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
