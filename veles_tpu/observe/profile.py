"""Profiler hooks and the periodic JSONL heartbeat.

Two run-scoped services on top of the tracer/registry:

- :class:`ProfilerHook` starts/stops ``jax.profiler`` around a
  configurable window of fused train steps (``VELES_PROFILE=dir``
  enables it from the environment, ``VELES_PROFILE_WINDOW=start:stop``
  picks the window, default 5:25 — past the compile so the trace shows
  steady state, short so the dump stays small).  The per-step call
  (:func:`profiler_step`) is a module-global None check when no hook
  is installed — the healthy path pays nothing;
- :class:`Heartbeat` writes one JSON line every ``interval`` seconds
  (``--metrics-interval N`` / ``--metrics-path PATH``): registry
  snapshot, health counters, epoch/metrics from the decision unit, and
  samples/sec throughput derived from the ``train.samples`` counter
  delta.  web_status.py surfaces the same health block in its status
  posts; offline tools (``observe summary``) consume the file.
"""

import json
import math
import os
import threading
import time

from veles_tpu.observe.metrics import health_snapshot
from veles_tpu.observe.metrics import registry as _registry

__all__ = ["ProfilerHook", "install_profiler", "uninstall_profiler",
           "profiler_step", "Heartbeat", "validate_heartbeat",
           "HEARTBEAT_SCHEMA_VERSION", "HEARTBEAT_SCHEMAS"]

HEARTBEAT_SCHEMA_VERSION = 3

#: Schemas ``validate_heartbeat`` accepts: v2 files (pre-telemetry)
#: stay readable by ``observe summary``/``merge`` forever; v3 adds
#: the ``series`` rollup block and the ``alerts`` block.
HEARTBEAT_SCHEMAS = (2, 3)


class ProfilerHook(object):
    """Drive ``jax.profiler`` around a window of train steps."""

    def __init__(self, logdir, start_step=None, stop_step=None):
        if start_step is None or stop_step is None:
            env_start, env_stop = self._window_from_env()
            start_step = env_start if start_step is None else start_step
            stop_step = env_stop if stop_step is None else stop_step
        self.logdir = logdir
        self.start_step = max(0, int(start_step))
        self.stop_step = max(self.start_step + 1, int(stop_step))
        self.steps = 0
        self.state = "idle"  # -> "tracing" -> "done"

    @staticmethod
    def _window_from_env(environ=None):
        environ = environ if environ is not None else os.environ
        window = environ.get("VELES_PROFILE_WINDOW", "")
        try:
            start, stop = window.split(":", 1)
            return int(start), int(stop)
        except ValueError:
            return 5, 25

    @classmethod
    def from_env(cls, environ=None):
        """A hook when ``VELES_PROFILE`` names a log dir, else None."""
        environ = environ if environ is not None else os.environ
        logdir = environ.get("VELES_PROFILE", "")
        if not logdir:
            return None
        start, stop = cls._window_from_env(environ)
        return cls(logdir, start, stop)

    def step(self):
        """Account one train step; start/stop the profiler at the
        window edges.  Cheap outside the edges: one int compare."""
        self.steps += 1
        if self.state == "idle" and self.steps > self.start_step:
            self._start()
        elif self.state == "tracing" and self.steps > self.stop_step:
            self.stop()

    def _start(self):
        try:
            import jax
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
        except Exception:
            # a missing/old jax.profiler must never kill training;
            # "done" also stops the per-step retry storm
            self.state = "done"
            return
        self.state = "tracing"

    def stop(self):
        """Idempotent: stop tracing if the window is still open, and
        write ``device_scopes.json`` beside the trace it closes: which
        scope each instruction of every described program (the fused
        step) belongs to (``xla_introspect.instruction_scopes``), for
        ``scripts/trace_scopes.py`` to divide the trace's device time
        by (docs/observability.md)."""
        if self.state != "tracing":
            self.state = "done"
            return
        self.state = "done"
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            return
        self._write_device_scopes()

    def _write_device_scopes(self):
        import glob

        from veles_tpu.observe import xla_introspect
        programs = {}
        for name in xla_introspect.described():
            table = xla_introspect.instruction_scopes(name)
            if table is not None:  # else the watcher said why
                programs[name] = dict(xla_introspect.scope_names(name),
                                      instructions=table)
        if not programs:  # not a fused workflow: nothing to say
            return None
        traces = sorted(glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb")))
        path = os.path.join(
            os.path.dirname(traces[-1]) if traces else self.logdir,
            "device_scopes.json")
        try:
            with open(path, "w") as fout:
                json.dump(programs, fout)
        except OSError:
            return None
        return path


_hook = None
_hook_lock = threading.Lock()


def install_profiler(hook):
    """Make ``hook`` the process profiler (replacing and stopping any
    previous one)."""
    global _hook
    with _hook_lock:
        previous, _hook = _hook, hook
    if previous is not None:
        previous.stop()
    return hook


def uninstall_profiler():
    global _hook
    with _hook_lock:
        hook, _hook = _hook, None
    if hook is not None:
        hook.stop()
    return hook


def profiler_step():
    """Per-train-step tick (called by the fused trainer); a plain None
    check when no profiler is installed."""
    hook = _hook
    if hook is not None:
        hook.step()


# -- heartbeat ---------------------------------------------------------------

#: required keys -> allowed types of one heartbeat line.  Schema v2:
#: lines carry BOTH clocks — ``ts`` (wall, cross-host correlatable,
#: NTP-adjustable) and ``mono`` (monotonic, for in-process deltas that
#: must never go backwards) — plus the XLA ``compile`` block.
_HEARTBEAT_REQUIRED = {
    "kind": str, "schema": int, "ts": (int, float),
    "mono": (int, float), "elapsed_s": (int, float), "session": str,
    "counters": dict, "gauges": dict, "histograms": dict, "health": dict,
}


def _jsonsafe(value):
    """Recursively replace non-finite floats with None: a bare NaN
    token (json.dumps' allow_nan default) is not RFC-8259 JSON and
    breaks every non-Python consumer of the heartbeat file."""
    if isinstance(value, float):
        return value if math.isfinite(value) else None
    if isinstance(value, dict):
        return {key: _jsonsafe(item) for key, item in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonsafe(item) for item in value]
    return value


def validate_heartbeat(record):
    """Schema check for one parsed heartbeat line; raises ValueError.
    The contract tested by the observe smoke test and relied on by
    external consumers of ``--metrics-path`` files."""
    if not isinstance(record, dict):
        raise ValueError("heartbeat line is not an object")
    for key, types in _HEARTBEAT_REQUIRED.items():
        if key not in record:
            raise ValueError("heartbeat missing %r" % key)
        if not isinstance(record[key], types):
            raise ValueError("heartbeat %r has type %s" %
                             (key, type(record[key]).__name__))
    if record["kind"] != "heartbeat":
        raise ValueError("kind must be 'heartbeat'")
    if record["schema"] not in HEARTBEAT_SCHEMAS:
        raise ValueError("unknown heartbeat schema %r" % record["schema"])
    if record["schema"] >= 3:
        # v3: the telemetry-plane blocks are part of the contract
        for key in ("series", "alerts"):
            if not isinstance(record.get(key), dict):
                raise ValueError(
                    "schema 3 heartbeat needs a %r block" % key)
        if "schema" not in record["series"]:
            raise ValueError("series block lacks a schema")
    if "mfu_pct" in record and record["mfu_pct"] is not None and \
            not isinstance(record["mfu_pct"], (int, float)):
        raise ValueError("mfu_pct must be numeric or null")
    if "compile" in record and not isinstance(record["compile"], dict):
        raise ValueError("compile block must be an object")
    for name, hist in record["histograms"].items():
        if not isinstance(hist, dict) or "count" not in hist:
            raise ValueError("histogram %r lacks a count" % name)
    return record


class Heartbeat(object):
    """Append one status JSON line to ``path`` every ``interval`` s on
    a daemon thread; a final line is written at stop so even runs
    shorter than the interval leave a record."""

    def __init__(self, path, interval=5.0, workflow=None, registry=None):
        self.path = path
        self.interval = max(0.05, float(interval))
        self.workflow = workflow
        self.registry = registry if registry is not None else _registry
        self._stop = threading.Event()
        self._thread = None
        self._t0 = time.monotonic()
        self._last_sample = (self._t0, self._samples())

    def _samples(self):
        counter = self.registry.peek("train.samples")
        return counter.value if counter is not None else 0

    def line(self):
        """One heartbeat record (plain data, json-serializable)."""
        from veles_tpu import logger
        now = time.monotonic()
        # XLA introspection (docs/observability.md) refreshes FIRST so
        # the one snapshot below already carries this tick's recompile
        # counts, memory gauges and mfu — a recompile storm must show
        # on the line that observed it, not one interval late.  Gated
        # on runs that actually compiled something: a dummy/unit-test
        # heartbeat must not drag jax in.
        xla = None
        mfu = None
        if self.registry.peek("compile.count") is not None or \
                self.registry.peek("xla.step_flops") is not None:
            try:
                from veles_tpu.observe import xla_introspect as xla
                xla.poll_recompiles()
                xla.device_memory_gauges(self.registry)
                mfu = xla.mfu_snapshot(self.registry)
            except Exception:
                xla = None
        snap = self.registry.snapshot()
        record = {
            "kind": "heartbeat",
            "schema": HEARTBEAT_SCHEMA_VERSION,
            "ts": time.time(),
            "mono": now,
            "elapsed_s": round(now - self._t0, 3),
            "session": logger.session_id,
            "counters": snap["counters"],
            "gauges": snap["gauges"],
            "histograms": snap["histograms"],
            "health": health_snapshot(self.registry),
        }
        if xla is not None:
            record["compile"] = xla.compile_snapshot(self.registry)
            record["mfu_pct"] = mfu
        # the telemetry plane rides the heartbeat cadence: tick the
        # process-global series ring against the SAME wall stamp this
        # line carries, then embed the compact v3 blocks (the full
        # buckets ship over links, not the JSONL file)
        try:
            from veles_tpu.observe.alerts import alerts
            from veles_tpu.observe.timeseries import series
            series.maybe_tick(now=now, wall=record["ts"])
            if alerts.rules:
                # single-process alerting rides the heartbeat: the
                # same rules a fleet router sweeps over rollups run
                # here over the local ring (edge-triggered, so a
                # persisting breach costs one firing, not one per
                # heartbeat line)
                alerts.evaluate(series.buckets(last=32),
                                wall=record["ts"])
            record["series"] = series.heartbeat_block()
            record["alerts"] = alerts.snapshot(history=4)
        except Exception:
            record["series"] = {"schema": 0}
            record["alerts"] = {"schema": 0, "active": [],
                                "firing": [], "fired_total": 0,
                                "history": []}
        last_t, last_samples = self._last_sample
        samples = self._samples()
        if now > last_t:
            record["throughput_sps"] = round(
                (samples - last_samples) / (now - last_t), 3)
        self._last_sample = (now, samples)
        workflow = self.workflow
        if workflow is not None:
            record["workflow"] = type(workflow).__name__
            decision = getattr(workflow, "decision", None)
            if decision is not None:
                epoch = getattr(decision, "epoch_number", None)
                if epoch is not None:
                    record["epoch"] = int(epoch)
                record["metrics"] = getattr(
                    decision, "epoch_metrics", None)
        return record

    def write_line(self):
        directory = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(directory, exist_ok=True)
        record = _jsonsafe(self.line())
        with open(self.path, "a") as fout:
            fout.write(json.dumps(record, default=repr,
                                  allow_nan=False) + "\n")
        # the flight recorder keeps a condensed copy: a post-mortem
        # dump then shows throughput/health context around the failure
        from veles_tpu.observe.flight import flight
        if flight.enabled:
            flight.record(
                "heartbeat", "heartbeat", wall=record.get("ts"),
                args={key: record.get(key) for key in
                      ("elapsed_s", "throughput_sps", "epoch",
                       "health", "mfu_pct", "compile")
                      if record.get(key) is not None})

    def _loop(self):
        try:
            while not self._stop.wait(self.interval):
                try:
                    self.write_line()
                except OSError:
                    pass  # a full disk must not take training down
        finally:
            try:
                self.write_line()  # final state, even for short runs
            except OSError:
                pass

    def start(self):
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="heartbeat")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=10)
