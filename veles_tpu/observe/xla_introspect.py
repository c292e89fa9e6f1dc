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

- **device time by the program's own scopes** — the device's
  counterpart of ``tracer.scope``: every layer's ops run under
  ``jax.named_scope`` (``l<k>_<Class>``, ``loss``, ``update``, a decoder
  layer's parts), which XLA carries in each instruction's ``op_name``.
  :func:`describe` keeps the SHAPES a watched program was called with
  and the names of its scopes (none is written down here),
  :func:`instruction_scopes` asks the compiled program for its
  ``{instruction: op_name}`` table when somebody wants it (never on a
  run's own path), :func:`scope_of` reads (layer, part, phase) out of an
  ``op_name`` and :func:`device_seconds_by_scope` joins the table with a
  profiler trace's seconds by instruction (docs/observability.md).

Everything here imports jax lazily and is called OFF the step path
(compile time, heartbeat thread, decision class end), preserving the
observe-package invariant that telemetry never adds a host sync.
"""

import re
import sys
import threading

from veles_tpu.observe.metrics import percentiles
from veles_tpu.observe.metrics import registry as _registry

__all__ = ["CompileWatcher", "watcher", "ensure_installed", "watch",
           "poll_recompiles", "device_memory_gauges", "set_step_flops",
           "set_fwd_flops", "set_step_dtype", "step_dtype",
           "peak_flops", "mfu_snapshot", "bwd_snapshot",
           "compile_snapshot", "compile_delta", "PEAKS",
           "device_peaks", "describe", "described", "instruction_scopes",
           "scope_names", "instruction_key", "parse_instruction_scopes",
           "scope_of", "device_seconds_by_scope"]

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
        # name -> (abstract args, abstract kwargs, the names of its
        # scopes): shapes only, no array is kept alive
        self._described = {}
        self._scopes = {}  # name -> {instruction key: op_name} | None

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
            # another program: the old one's arguments and table go
            self._described.pop(name, None)
            self._scopes.pop(name, None)
        return True

    def unwatch(self, name):
        with self._lock:
            self._watched.pop(name, None)
            self._described.pop(name, None)
            self._scopes.pop(name, None)

    # -- what each instruction of a watched program belongs to ------------

    def describe(self, name, args, kwargs=None, parts=(),
                 step_scopes=None):
        """Keep the abstract arguments (``jax.ShapeDtypeStruct`` leaves:
        shape, dtype and, where the real array was committed to a
        device or a mesh, its sharding) the watched program ``name`` was
        called with, and the names of its scopes as :func:`scope_of`
        takes them: ``parts``, what its layers' classes name
        (``DecoderLayer.PART_SCOPES``), and ``step_scopes``, the step's
        own beside the layers' with the phase each stands for
        (``compiler.STEP_SCOPES``).  Nothing is lowered or compiled
        here; a new description drops the table of the old one."""
        with self._lock:
            self._described[name] = (
                tuple(args), dict(kwargs or {}),
                {"parts": list(parts),
                 "step_scopes": dict(step_scopes or {})})
            self._scopes.pop(name, None)

    def described(self):
        """The names of the programs that have a description."""
        with self._lock:
            return sorted(self._described)

    def scope_names(self, name):
        """``{"parts": [...], "step_scopes": {...}}`` as ``name`` was
        described: the keywords of :func:`scope_of` and
        :func:`device_seconds_by_scope`."""
        with self._lock:
            described = self._described.get(name)
        return dict(described[2]) if described else {}

    def instruction_scopes(self, name):
        """``{instruction key: op_name}`` of the watched program's
        optimised HLO (:func:`parse_instruction_scopes`), built on the
        first call from ``fn.lower(*args, **kwargs).compile()`` at the
        described arguments and kept; the text is dropped once parsed.
        The description is the real call's, so jax hands back the
        lowering and the executable it already holds: no compile
        request at all, on the CPU and on the chip (a description that
        misses the call's signature is a whole new compile: repair the
        description).
        None, and one warning line, for a program that is not watched or
        not described, a jax without ``as_text`` or a compile that
        fails: nothing here raises."""
        with self._lock:
            if name in self._scopes:
                return self._scopes[name]
            watched = self._watched.get(name)
            described = self._described.get(name)
        table = None
        try:
            if watched is None or described is None:
                raise LookupError(
                    "no program is watched and described under that name")
            args, kwargs, names = described
            text = watched[0].lower(*args, **kwargs).compile().as_text()
            table = parse_instruction_scopes(text, names["step_scopes"])
            del text
        except Exception as exc:
            import logging
            logging.getLogger("xla").warning(
                "no instruction scopes for %s: %s: %s", name,
                type(exc).__name__, exc)
        with self._lock:
            self._scopes[name] = table
        return table

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


def describe(name, args, kwargs=None, parts=(), step_scopes=None):
    return watcher.describe(name, args, kwargs, parts, step_scopes)


def described():
    return watcher.described()


def instruction_scopes(name):
    return watcher.instruction_scopes(name)


def scope_names(name):
    return watcher.scope_names(name)


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


# -- device time by the program's own scopes ---------------------------------

#: opcodes whose profiler event spans the events of a body that the same
#: line also holds: left out of every sum, so nothing is counted twice
CONTAINER_OPCODES = frozenset(("while", "conditional", "call"))

_NOT_SHAPE = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/\s*")
_LAYER = re.compile(r"l\d+_(\w+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERENCE = re.compile(r"%[\w.\-]+")
_WRAPPER = re.compile(r"\b(?:jvp|transpose|vmap)\(")
_HLO_LINE = re.compile(r"^\s*(ROOT\s+)?(%\S+ = .*)$")


def _split_instruction(text):
    """(name, result type as written, what follows it) of an HLO
    instruction's text ``%name = <type> opcode(...)...``, or None."""
    name, eq, rest = text.partition(" = ")
    if not eq or not name.startswith("%"):
        return None
    if rest.startswith("("):  # a tuple: to its closing parenthesis
        depth = 0
        for end, char in enumerate(rest):
            depth += (char == "(") - (char == ")")
            if not depth:
                break
        end += 1
    else:
        end = rest.find(" ")
        end = len(rest) if end < 0 else end
    return name, rest[:end], rest[end:].lstrip()


def instruction_key(text):
    """``%fusion.421 f32[8192,25024]``: an instruction's name and result
    shape, layouts dropped — the key the table of the program and the
    events of a trace are joined on (names repeat across the programs of
    one trace; a name with its shape hardly does).  None for a text that
    is no instruction."""
    found = _split_instruction(text)
    return None if found is None else _key(found)


def _key(found):
    # layouts and the printer's /*index=5*/ marks are not the shape
    return "%s %s" % (found[0], _NOT_SHAPE.sub("", found[1]))


def _opcode(found):
    # ``while`` of ``%while.15 = (...) while(...)``
    return found[2].split("(", 1)[0].strip()


def parse_instruction_scopes(hlo_text, step_scopes=None):
    """{:func:`instruction_key`: ``op_name``} of every instruction of
    every computation in an optimised HLO module's text — loop bodies
    and the callers of fused computations among them.

    An instruction whose own ``op_name`` names no scope inherits by the
    program's structure alone: a fusion has its fused computation's
    root's ``op_name``; an instruction of a called computation (a
    loop's body, the expansion of a ``ragged_dot`` inside it) has that
    of the instruction that calls it.  Nothing is guessed from users or
    operands: what the compiler made outside every scope (a whole
    vector's ``convert``, layout copies, zero fills) keeps the
    ``op_name`` it had, ``""`` where it had none, and a join books it
    under ``(None, None, None)``."""
    def names_a_scope(op_name):
        return scope_of(op_name, (), step_scopes)[0] is not None

    computations, caller, root = _parse_computations(hlo_text)
    resolved = {}  # instruction name -> the op_name it ends up with
    table = {}
    for name, instructions in reversed(computations):  # callers first
        inherited = resolved.get(caller.get(name), "")
        for inst, key, op_name, refs in instructions:
            if not names_a_scope(op_name):
                structural = [root[r] for r in refs if r in root]
                op_name = next(filter(
                    names_a_scope, structural + [inherited]), op_name)
            resolved[inst] = op_name
            # one string an op_name, however many instructions have it
            table[key] = sys.intern(op_name)
    return table


def _parse_computations(hlo_text):
    """([(computation, [(instruction name, key, own op_name, the names
    its text refers to)])] in the text's order, {computation: the first
    instruction that calls it}, {computation: its root's op_name})."""
    computations, caller, root = [], {}, {}
    current = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and " = " not in line.split("(", 1)[0]:
            head = line.split("(", 1)[0].split()  # [ENTRY] %name
            current = (head[-1], []) if head else None
            if current:
                computations.append(current)
            continue
        match = _HLO_LINE.match(line)
        if match is None or current is None:
            continue
        found = _split_instruction(match.group(2))
        if found is None:
            continue
        named = _OP_NAME.search(line)
        op_name = named.group(1) if named else ""
        refs = _REFERENCE.findall(found[2].split(", metadata=", 1)[0])
        current[1].append((found[0], _key(found), op_name, refs))
        if match.group(1):
            root[current[0]] = op_name
    names = {name for name, _ in computations}
    for _, instructions in computations:
        for inst, _, _, refs in instructions:
            for ref in refs:
                if ref in names:
                    caller.setdefault(ref, inst)
    return computations, caller, root


def scope_of(op_name, parts=(), step_scopes=None):
    """(layer, part, phase) of an instruction's ``op_name``, a pure
    function of the string.  ``layer``: the ``l<k>_<Class>`` component
    (inside ``jvp(...)``/``transpose(...)`` wrappers too), or one of
    ``step_scopes`` (``{scope: phase}``, the step's own scopes as its
    builder names them: ``loss``, ``grad_sync``, ``update``), else None.
    ``part``: the first component after the layer that is in ``parts``
    (the names the layer's class gives its parts), else None.
    ``phase``: what ``step_scopes`` says of its scope; where that is
    None, and under a layer, ``recompute`` (a ``rematted_computation``
    component: the forward replayed in the backward), ``backward`` (a
    ``transpose(`` wrapper and not recomputed) or ``forward``; None
    where there is no layer."""
    step_scopes = step_scopes or {}
    components = [
        c if "(" in c else c.rstrip(")")  # jit(step) stays what it is
        for c in _WRAPPER.sub("", op_name or "").split("/")]
    layer = at = None
    for index, component in enumerate(components):
        if component in step_scopes or _LAYER.fullmatch(component):
            layer, at = component, index
            break
    if layer is None:
        return None, None, None
    phase = step_scopes.get(layer)
    if phase is not None:
        return layer, None, phase
    part = next((c for c in components[at + 1:] if c in parts), None)
    if "rematted_computation" in components:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return layer, part, phase


def layer_class(layer):
    """``DecoderLayer`` of ``l3_DecoderLayer``; ``loss``, ``update`` and
    None as they are."""
    match = _LAYER.fullmatch(layer or "")
    return match.group(1) if match else layer


def device_seconds_by_scope(op_seconds, scopes, parts=(), step_scopes=None):
    """{(layer class, part, phase): seconds} of a trace's
    ``op_seconds`` ({event name = instruction text: seconds}) under the
    table :func:`instruction_scopes` gave.  Leaves only: an event whose
    opcode is in :data:`CONTAINER_OPCODES` spans its body's events and
    is left out.  An event joins the table on :func:`instruction_key`;
    what has no entry (another program's op) or an entry with no scope
    adds to the key ``(None, None, None)``."""
    out = {}
    for text, seconds in op_seconds.items():
        found = _split_instruction(text)
        if found and _opcode(found) in CONTAINER_OPCODES:
            continue
        layer, part, phase = scope_of(
            scopes.get(_key(found), "") if found else "", parts,
            step_scopes)
        key = (layer_class(layer), part, phase)
        out[key] = out.get(key, 0.0) + seconds
    return out
