"""XLA-layer introspection: recompiles, device memory, achieved MFU.

The runtime above XLA is otherwise blind to three failure/perf modes
the TPU-systems literature calls out as the ones that matter:

- **recompile storms** — a shape or donation mistake that silently
  recompiles the step every iteration costs orders of magnitude more
  than any kernel win.  :class:`CompileWatcher` counts backend
  compilation events via ``jax.monitoring`` (``compile.count`` /
  ``compile.seconds`` registry metrics) and tracks the jit-cache size
  of registered functions (the fused step, the eval dispatch), warning
  the first time a watched function recompiles past its expected
  signature count;
- **device-memory growth** — :func:`device_memory_gauges` publishes
  ``memory_stats()`` per device where the backend provides it (TPU),
  falling back to a live-array census (``jax.live_arrays()``) where it
  does not (CPU), as ``xla.mem.*`` gauges;
- **achieved MFU** — :func:`set_step_flops` records the XLA cost
  model's FLOP count for the compiled fused step, and
  :func:`mfu_snapshot` divides by the recent median step time and the
  chip's peak from :data:`PEAKS` to publish a live ``xla.mfu_pct``
  gauge the heartbeat and web-status health block carry.  No chip, no
  MFU: on the CPU platform the gauge stays unpublished, and a chip
  whose ``device_kind`` is not in the table is an error.

Everything here imports jax lazily and is called OFF the step path
(compile time, heartbeat thread, decision class end), preserving the
observe-package invariant that telemetry never adds a host sync.
"""

import threading

from veles_tpu.observe.metrics import percentiles
from veles_tpu.observe.metrics import registry as _registry

__all__ = ["CompileWatcher", "watcher", "ensure_installed", "watch",
           "poll_recompiles", "device_memory_gauges", "set_step_flops",
           "set_fwd_flops", "set_step_dtype", "step_dtype",
           "peak_flops", "mfu_snapshot", "bwd_snapshot",
           "compile_snapshot", "compile_delta", "PEAKS",
           "device_peaks"]

#: THE peaks table: one row per chip, keyed by the exact
#: ``device_kind`` string jax reports, with where the numbers come
#: from.  A kind that is not here has no peak — rating it is an error,
#: never a default (add the row with its source instead).  int8 runs
#: the MXU at twice the bf16 rate on v5e, so a quantized engine's steps
#: rate against ``int8`` (docs/serving.md "Quantized ladder").
PEAKS = {
    "TPU v5 lite": {
        "bf16": 197e12,     # FLOP/s
        "int8": 393e12,     # OP/s
        "hbm": 819e9,       # bytes/s
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}

_STEP_DTYPES = ("bf16", "int8")

#: the jax.monitoring duration event emitted once per XLA backend
#: compilation (jaxpr trace / MLIR lowering events are deliberately
#: not counted: only backend compiles cost real seconds at scale).
#: NOTE this event fires around ``compile_or_get_cached``, so a
#: persistent-cache HIT still bumps ``compile.count`` — the cache
#: events below are what separate "asked XLA for an executable" from
#: "actually built one" (serve engine cold/warm receipts key on it)
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"

#: jax.monitoring point events emitted by the persistent compilation
#: cache (jax/_src/compiler.py): a hit means the executable was
#: DESERIALIZED, not rebuilt, so real new compiles = count - hits
_CACHE_EVENT_COUNTERS = (
    ("/jax/compilation_cache/cache_hits", "compile.cache_hits"),
    ("/jax/compilation_cache/cache_misses", "compile.cache_misses"),
)


class CompileWatcher(object):
    """Count XLA compilations and detect per-function recompiles."""

    def __init__(self, registry=None, warn_after=2):
        self.registry = registry if registry is not None else _registry
        #: cache entries a watched function may legitimately grow to
        #: before a recompile warning (the fused step compiles once per
        #: dropout/poison signature, so 2 is the healthy ceiling)
        self.warn_after = warn_after
        self.installed = False
        self._lock = threading.Lock()
        self._watched = {}  # name -> [fn, last_size, warned]

    # -- global compile accounting ----------------------------------------

    def install(self):
        """Register the jax.monitoring listener (idempotent; a missing
        or old jax disables the counter, never the caller)."""
        with self._lock:
            if self.installed:
                return True
            try:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                monitoring.register_event_listener(self._on_event)
            except Exception:
                return False
            self.installed = True
            return True

    def _on_duration(self, event, duration, **kwargs):
        if not event.endswith(_COMPILE_EVENT_SUFFIX):
            return
        self.registry.counter("compile.count").inc()
        self.registry.counter("compile.seconds").inc(float(duration))
        from veles_tpu.observe.trace import tracer
        if tracer.active:
            tracer.instant("xla.compile", cat="xla",
                           seconds=round(float(duration), 4))

    def _on_event(self, event, **kwargs):
        for name, counter in _CACHE_EVENT_COUNTERS:
            if event == name:
                self.registry.counter(counter).inc()
                return

    # -- per-function recompile detection ----------------------------------

    def watch(self, fn, name):
        """Track a jitted function's compilation-cache size (pjit's
        ``_cache_size``); functions without one are ignored."""
        if not hasattr(fn, "_cache_size"):
            return False
        with self._lock:
            self._watched[name] = [fn, 0, False]
        return True

    def unwatch(self, name):
        with self._lock:
            self._watched.pop(name, None)

    def poll(self, warn=None):
        """Refresh watched cache sizes; returns {name: size}.  Called
        off the hot path (heartbeat thread, compile time).  The first
        time a function's cache exceeds ``warn_after`` entries a
        recompile-storm warning is logged and a ``compile.recompiles``
        counter bumped by the growth."""
        with self._lock:
            watched = list(self._watched.items())
        sizes = {}
        for name, entry in watched:
            fn, last, warned = entry
            try:
                size = int(fn._cache_size())
            except Exception:
                continue
            sizes[name] = size
            if size > last:
                if last:  # growth past the first compile = recompile
                    self.registry.counter(
                        "compile.recompiles").inc(size - last)
                entry[1] = size
            if size > self.warn_after and not warned:
                entry[2] = True
                import logging
                logging.getLogger("xla").warning(
                    "recompile storm suspected: %s has %d compiled "
                    "signatures (expected <= %d) — check for varying "
                    "shapes/dtypes or re-donated buffers",
                    name, size, self.warn_after)
                if warn is not None:
                    warn(name, size)
        return sizes


#: process-wide watcher (the fused trainer installs + registers into it)
watcher = CompileWatcher()


def ensure_installed():
    return watcher.install()


def watch(fn, name):
    return watcher.watch(fn, name)


def poll_recompiles():
    return watcher.poll()


def compile_snapshot(reg=None):
    """{"count", "seconds", "recompiles", "cache_hits", "cache_misses"}
    from the registry — always a complete dict (zeros before the first
    compile), so heartbeat consumers can rely on the keys existing.
    ``count`` includes persistent-cache hits (the backend event wraps
    the cache lookup); ``count - cache_hits`` is the number of
    executables XLA actually built, the serve engine's warm-restart
    receipt (docs/serving.md)."""
    reg = reg if reg is not None else _registry
    out = {}
    for key, name, cast in (
            ("count", "compile.count", int),
            ("seconds", "compile.seconds",
             lambda v: round(float(v), 4)),
            ("recompiles", "compile.recompiles", int),
            ("cache_hits", "compile.cache_hits", int),
            ("cache_misses", "compile.cache_misses", int)):
        metric = reg.peek(name)
        out[key] = cast(metric.value) if metric is not None else cast(0)
    return out


class compile_delta(object):
    """Context manager measuring backend-compile activity inside the
    block: ``with compile_delta() as d: ...`` then ``d.receipt`` is
    ``{"backend_compiles", "cache_hits", "new_compiles"}``.

    The decomposition mirrors the serve engine's warm-restart receipt:
    jax's monitoring event fires even on a persistent-cache hit, so
    ``new_compiles = requests - hits`` is what XLA actually built.
    Shared by ``AOTEngine.compile``, the serve hot-reload receipt (a
    same-digest reload must report 0) and the tests that assert it.
    """

    def __init__(self, reg=None):
        self._reg = reg
        self.receipt = None

    def __enter__(self):
        ensure_installed()
        self._before = compile_snapshot(self._reg)
        return self

    def __exit__(self, *exc_info):
        after = compile_snapshot(self._reg)
        requests = after["count"] - self._before["count"]
        hits = after["cache_hits"] - self._before["cache_hits"]
        self.receipt = {
            "backend_compiles": requests,
            "cache_hits": hits,
            "new_compiles": max(0, requests - hits),
        }
        return False


# -- device memory -----------------------------------------------------------


def device_memory_gauges(reg=None):
    """Publish per-device memory gauges; returns the flat dict.

    Prefers the backend's ``memory_stats()`` (TPU/GPU expose
    bytes_in_use / peak_bytes_in_use); where unavailable (CPU) falls
    back to a live-array census — the sum of ``nbytes`` over
    ``jax.live_arrays()`` — which tracks the same leak/growth signal
    with framework-side accounting."""
    reg = reg if reg is not None else _registry
    out = {}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return out
    have_stats = False
    for index, device in enumerate(devices):
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        have_stats = True
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                name = "xla.mem.%s.d%d" % (key, index)
                reg.gauge(name).set(int(stats[key]))
                out[name] = int(stats[key])
    if not have_stats:
        try:
            live = sum(int(getattr(arr, "nbytes", 0))
                       for arr in jax.live_arrays())
        except Exception:
            return out
        reg.gauge("xla.mem.live_bytes").set(live)
        out["xla.mem.live_bytes"] = live
    return out


# -- FLOPs / MFU -------------------------------------------------------------


def set_step_flops(flops, reg=None):
    """Record the cost-analysis FLOP count of ONE fused train step
    (published by the fused trainer right after compile)."""
    reg = reg if reg is not None else _registry
    reg.gauge("xla.step_flops").set(float(flops))


def set_fwd_flops(flops, reg=None):
    """Record the cost-analysis FLOP count of the FORWARD-only program
    (the fused trainer's eval dispatch — same layer composition as the
    train step's forward).  Together with ``xla.step_flops`` this is
    what lets :func:`bwd_snapshot` attribute the step between forward
    and backward+update (docs/kernels.md)."""
    reg = reg if reg is not None else _registry
    reg.gauge("xla.fwd_flops").set(float(flops))


_peak_lock = threading.Lock()
_step_dtype = ["bf16"]


def set_step_dtype(name, reg=None):
    """Record the DOMINANT arithmetic dtype of the measured step
    ("bf16" covers the f32/bf16 ladder — one MXU rate; "int8" the
    quantized level), so :func:`mfu_snapshot` divides by the matching
    peak instead of always the bf16 ceiling.  Set by the quantized
    serve engine at compile; training paths keep the default."""
    if name not in _STEP_DTYPES:
        raise ValueError("unknown step dtype %r (have %s)" %
                         (name, sorted(_STEP_DTYPES)))
    with _peak_lock:
        _step_dtype[0] = name
    reg = reg if reg is not None else _registry
    reg.gauge("xla.step_dtype_int8").set(1 if name == "int8" else 0)


def step_dtype():
    """The recorded dominant step dtype ("bf16" default)."""
    with _peak_lock:
        return _step_dtype[0]


def device_peaks(device=None):
    """The :data:`PEAKS` row of ``device`` (default: the first local
    device), None on the CPU platform — there is no device to rate
    there, so nothing may be published under a device metric's name.
    Raises LookupError for a chip the table does not know."""
    if device is None:
        import jax
        device = jax.local_devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise LookupError(
            "no peaks for device_kind %r on platform %r: add a row "
            "(with its source) to observe.xla_introspect.PEAKS" %
            (device.device_kind, device.platform)) from None


def peak_flops(dtype=None):
    """This process's peak FLOP/s for MFU at ``dtype`` (``None`` -> the
    recorded :func:`step_dtype`, so a quantized engine's steps rate
    against the int8 peak); None on the CPU platform."""
    row = device_peaks()
    return None if row is None else row[dtype or step_dtype()]


def mfu_snapshot(reg=None):
    """Live achieved-MFU percentage, or None when the inputs are not
    yet published (no compiled step, no timed steps).  Publishes the
    ``xla.mfu_pct`` gauge as a side effect so health_snapshot and the
    web-status dashboard pick it up.  Uses the p50 of the recent
    step-time window: MFU is a steady-state number and a median
    ignores the compile-step outlier by construction."""
    reg = reg if reg is not None else _registry
    # the backward attribution refreshes on the same tick (heartbeat /
    # web-status reporter both call mfu_snapshot), so the fwd/bwd
    # split can never lag the whole-step number it decomposes.  It
    # runs FIRST: bwd.step_ms needs only the train/eval histograms,
    # so it must survive this function's own early returns (no FLOPs
    # gauge, no peak rating)
    bwd_snapshot(reg)
    flops_gauge = reg.peek("xla.step_flops")
    hist = reg.peek("step.train_s")
    if flops_gauge is None or flops_gauge.value is None or hist is None:
        return None
    window = hist.window_values()
    if not window:
        return None
    step_s = percentiles(window, ps=(50,)).get("p50")
    if not step_s or step_s <= 0:
        return None
    peak = peak_flops()
    if not peak:
        return None
    mfu = 100.0 * float(flops_gauge.value) / step_s / peak
    mfu = round(mfu, 3)
    reg.gauge("xla.mfu_pct").set(mfu)
    return mfu


def bwd_snapshot(reg=None):
    """Backward+update attribution (docs/kernels.md): ``bwd.step_ms``
    and ``bwd.mfu_pct`` gauges next to the whole-step ``xla.mfu_pct``,
    so heartbeats and web_status carry the fwd/bwd split — the offline
    MFU.json ``backward_attribution`` block, live.

    Derived, no new host syncs: the eval dispatch IS the forward-only
    program and its ``step.eval_s`` histogram is already measured, so
    bwd time = p50(train step) - p50(eval step) and bwd FLOPs =
    ``xla.step_flops`` - ``xla.fwd_flops`` (both published by the
    fused trainer's one-time cost analysis).  Approximation caveat:
    the eval forward skips dropout masking and the loss tail, so the
    split attributes those few percent to the backward side.  Returns
    {"bwd_step_ms", "bwd_mfu_pct"} or None while any input is missing
    (no eval steps yet, cost analysis unavailable)."""
    reg = reg if reg is not None else _registry
    train_hist = reg.peek("step.train_s")
    eval_hist = reg.peek("step.eval_s")
    step_gauge = reg.peek("xla.step_flops")
    fwd_gauge = reg.peek("xla.fwd_flops")
    if train_hist is None or eval_hist is None:
        return None
    train_win = train_hist.window_values()
    eval_win = eval_hist.window_values()
    if not train_win or not eval_win:
        return None
    train_s = percentiles(train_win, ps=(50,)).get("p50")
    eval_s = percentiles(eval_win, ps=(50,)).get("p50")
    if not train_s or not eval_s or train_s <= eval_s:
        return None
    bwd_s = train_s - eval_s
    out = {"bwd_step_ms": round(bwd_s * 1e3, 3)}
    reg.gauge("bwd.step_ms").set(out["bwd_step_ms"])
    peak = peak_flops()
    if (peak and step_gauge is not None and fwd_gauge is not None
            and step_gauge.value and fwd_gauge.value
            and step_gauge.value > fwd_gauge.value):
        bwd_flops = float(step_gauge.value) - float(fwd_gauge.value)
        out["bwd_mfu_pct"] = round(
            100.0 * bwd_flops / bwd_s / peak, 3)
        reg.gauge("bwd.mfu_pct").set(out["bwd_mfu_pct"])
    return out
