"""AOT inference engine: a ladder of pre-compiled per-shape executables.

The reference's libVeles served a fixed workflow from a standalone C++
runtime: no tracing, no JIT, load-and-run.  The JAX analog is
ahead-of-time compilation — ``jax.jit(forward).lower(...).compile()``
against a small *ladder* of padded batch shapes (default 1/8/32/128),
so at serve time a request batch is padded up to the smallest fitting
rung and dispatched to an executable that already exists.  The old
``RESTfulAPI._compile`` path jit-compiled lazily on the first request
of each new batch shape, which put multi-second XLA compiles on the
latency path exactly when traffic changed — the failure mode the TPU
in-datacenter paper's latency-percentile framing punishes hardest.

Cold start is handled by the **persistent compilation cache**
(:func:`veles_tpu.backends.enable_compile_cache`: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else one fixed path inside the
checkout, with the min-compile-time/entry-size floors at zero so every
rung persists).  A restarted server then *deserializes* its ladder
instead of rebuilding it: ``compile_receipt["new_compiles"]`` is 0,
asserted via the ``compile.count`` / ``compile.cache_hits`` counters of
:mod:`veles_tpu.observe.xla_introspect` (the backend-compile monitoring
event fires even on a cache hit, so the receipt subtracts hits — see
that module).  jax's cache key covers the program, so every model
shares the one directory; :func:`model_digest` names what a fleet
serves, not where its executables live.

Numerics note (tests/test_serve.py): on XLA:CPU all rungs >= the vector
width (8 is safely past it) produce bit-identical per-row results, and
padding rows never leak into real rows (no cross-row reduction except
the per-row softmax), so continuous batching preserves bit-equality
with sequential serving *within* those rungs.  The rung-1 executable
lowers to a different vector-matrix kernel and may differ by ~1 ulp;
deployments that need strict batch-size-invariant bits should start
the ladder at 8.

Input donation is enabled only where the backend actually honors it
(TPU/GPU); XLA:CPU ignores donation with a warning, so ``donate="auto"``
skips it there.
"""

import hashlib

import numpy

from veles_tpu.logger import Logger
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer

__all__ = ["AOTEngine", "model_digest", "engine_digest_extra",
           "publish_quantized_state", "value_digest", "DEFAULT_LADDER"]


def publish_quantized_state(quantized):
    """Publish the process's served-arithmetic level: the
    ``serve.quantized`` gauge (serve_snapshot / healthz / heartbeats)
    and the MFU-ceiling dtype (``xla_introspect.set_step_dtype`` —
    int8 steps must not rate against the bf16 peak).

    Process-global, so it must track what the fleet actually SERVES:
    ``AOTEngine.compile`` publishes its own level (cold starts,
    standalone engines, new-digest reload warm-ups), and every
    transition that can change the live fleet without a compile —
    canary promote/rollback are swap-backs with 0 compiles by
    construction — republishes from the pool's live anchor engine, so
    a REJECTED quantized canary cannot leave an f32 fleet branded
    quantized (and rating MFU against the int8 peak) forever."""
    from veles_tpu.observe import xla_introspect
    _registry.gauge("serve.quantized").set(1 if quantized else 0)
    xla_introspect.set_step_dtype("int8" if quantized else "bf16")

#: default batch-shape ladder: singles stay latency-optimal, 128 is the
#: throughput rung (past it, padding waste beats batching gains for the
#: model sizes this repo serves)
DEFAULT_LADDER = (1, 8, 32, 128)


def model_digest(plans, params, sample_shape, extra=None):
    """Architecture fingerprint: the identity a fleet serves under.

    Hashes what determines the COMPILED PROGRAM — layer classes, static
    configs, parameter shapes/dtypes, the input sample shape, and the
    jax version — and deliberately NOT the weight values: retraining
    the same architecture keeps the digest (same executables, a
    zero-compile params swap), while any shape or topology change
    gets a new one (a new ladder to warm).
    """
    import jax
    digest = hashlib.sha256()
    digest.update(("jax:%s" % jax.__version__).encode())
    digest.update(repr(tuple(sample_shape)).encode())
    if extra:
        digest.update(repr(extra).encode())
    for plan, entry in zip(plans, params):
        digest.update(plan.forward_cls.__name__.encode())
        digest.update(repr(sorted(plan.static.items())).encode())
        for key in sorted(entry):
            leaf = entry[key]
            if leaf is None:
                digest.update(("%s:none" % key).encode())
            else:
                digest.update(("%s:%s:%s" % (
                    key, tuple(leaf.shape),
                    numpy.dtype(leaf.dtype).str)).encode())
    return digest.hexdigest()[:16]


def engine_digest_extra(dtype):
    """The ``extra`` an AOTEngine mixes into :func:`model_digest`: the
    ladder's INPUT dtype.  Param shapes/dtypes already ride the digest
    (so an int8-quantized spec and its f32 source can never collide —
    the regression test in tests/test_quant.py), but the input dtype
    determines the compiled program too and lives nowhere in the
    params: two engines serving the same weights at f32 vs bf16 inputs
    would otherwise share one freshness last-good identity.  Shared by
    ``AOTEngine`` and the
    router's ``reload_replicas`` so their digests agree byte-for-byte."""
    return {"input_dtype": numpy.dtype(dtype).str}


def value_digest(params):
    """Fingerprint of the parameter VALUES — the complement of
    :func:`model_digest`, which deliberately excludes them.  Two
    snapshots of the same architecture share a model digest (same
    compiled program) but differ here unless their weights are
    bit-identical; the freshness loop uses this to name *which* weights
    a fleet serves (last-good identity, rollback-restored-the-right-
    thing assertions) without holding the arrays themselves up for
    comparison."""
    digest = hashlib.sha256()
    for entry in params:
        for key in sorted(entry):
            leaf = entry[key]
            digest.update(key.encode())
            if leaf is None:
                digest.update(b"none")
            else:
                arr = numpy.ascontiguousarray(numpy.asarray(leaf))
                digest.update(arr.dtype.str.encode())
                digest.update(repr(arr.shape).encode())
                digest.update(arr.tobytes())
    return digest.hexdigest()[:16]


class AOTEngine(Logger):
    """Pre-compiled per-(model, batch-shape) executables + padded run.

    ``plans``/``params`` are the :mod:`veles_tpu.compiler` forward plan
    and the ``[{"weights", "bias"}]`` parameter list (host numpy or
    device arrays); ``sample_shape`` the per-sample input shape.  After
    :meth:`compile`, :meth:`run` dispatches a device batch on an exact
    rung and :meth:`infer` is the host-convenience (and sequential-
    reference) path: chunk, pad, run, slice.
    """

    def __init__(self, plans, params, sample_shape,
                 ladder=DEFAULT_LADDER, device=None, donate="auto",
                 dtype=numpy.float32, **kwargs):
        super(AOTEngine, self).__init__(**kwargs)
        if not plans:
            raise ValueError("AOTEngine needs a non-empty plan list")
        self.plans = list(plans)
        self.params = [dict(entry) for entry in params]
        self.sample_shape = tuple(int(s) for s in sample_shape)
        self.ladder = tuple(sorted({int(b) for b in ladder}))
        if not self.ladder or self.ladder[0] < 1:
            raise ValueError("ladder must hold positive batch sizes")
        if device is None:
            from veles_tpu.backends import Device
            device = Device()
        self.device = device
        self.dtype = numpy.dtype(dtype)
        self.donate = donate
        # int8-quantized spec (docs/serving.md "Quantized ladder"): the
        # quantization pass's artifacts in the entries are the ONLY
        # flag — no side channel through snapshots/publishes needed
        from veles_tpu.quant.forward import is_quantized_params
        self.quantized = is_quantized_params(self.params)
        self.digest = model_digest(plans, self.params, self.sample_shape,
                                   extra=engine_digest_extra(self.dtype))
        from veles_tpu.backends import enable_compile_cache
        self.cache_dir = enable_compile_cache()
        self.compile_receipt = None
        self._compiled = {}
        self._params_dev = None
        #: per-rung dispatch counters, minted on first use — lets the
        #: request-trace device segment (observe/requests.py) be
        #: correlated with WHICH executable ran when a tail shows up
        self._dispatch_counters = {}

    @classmethod
    def from_workflow(cls, sw, **kwargs):
        """Build from a trained StandardWorkflow: extracts the forward
        plan + parameters exactly like the old ``RESTfulAPI._compile``
        did, plus the loader's sample shape, and inherits the
        workflow's device."""
        from veles_tpu.compiler import extract_state, workflow_plan
        plans = workflow_plan(sw)
        state = extract_state(sw)
        params = [{"weights": s["weights"], "bias": s["bias"]}
                  for s in state]
        loader = getattr(sw, "loader", None)
        if "sample_shape" in kwargs:
            sample_shape = kwargs.pop("sample_shape")
        elif loader is not None and loader.minibatch_data:
            sample_shape = tuple(loader.minibatch_data.shape[1:])
        else:
            raise ValueError("workflow has no loader shape; pass "
                             "sample_shape=")
        kwargs.setdefault("device", getattr(sw.forwards[0], "device",
                                            None))
        return cls(plans, params, sample_shape, **kwargs)

    # -- compilation --------------------------------------------------------

    @property
    def max_batch(self):
        return self.ladder[-1]

    def _donate_argnums(self):
        if self.donate == "auto":
            # XLA:CPU ignores input-output aliasing for these programs
            # and warns per compile; donation only buys anything where
            # the backend honors it
            platform = self.device.jax_device.platform
            return (1,) if platform != "cpu" else ()
        return (1,) if self.donate else ()

    def compile(self):
        """Lower + compile every rung; returns the compile receipt.

        The receipt is the cold/warm-start proof (docs/serving.md):
        ``backend_compiles`` counts compile REQUESTS (jax's monitoring
        event fires even on a persistent-cache hit), ``cache_hits``
        the executables deserialized from disk, ``new_compiles`` their
        difference — 0 on a warm restart."""
        import time

        import jax

        from veles_tpu.compiler import build_forward
        from veles_tpu.observe import xla_introspect

        start = time.perf_counter()
        with xla_introspect.compile_delta() as delta:
            self._params_dev = self._put_params(self.params)
            if self.quantized:
                # the int8 ladder: same plans, the quantized forward
                # (quant/forward.py) over the int8 Pallas kernels —
                # "just another digest" to everything downstream
                from veles_tpu.quant.forward import \
                    build_quantized_forward
                forward = build_quantized_forward(self.plans)
            else:
                forward = self._in_model_precision(
                    build_forward(self.plans))
            donate = self._donate_argnums()
            for rung in self.ladder:
                x_aval = jax.ShapeDtypeStruct(
                    (rung,) + self.sample_shape, self.dtype)
                with _tracer.span("serve.compile", cat="serve",
                                  rung=rung):
                    jitted = jax.jit(forward, donate_argnums=donate)
                    self._compiled[rung] = jitted.lower(
                        self._params_dev, x_aval).compile()
        elapsed = time.perf_counter() - start
        requests = delta.receipt["backend_compiles"]
        hits = delta.receipt["cache_hits"]
        self.compile_receipt = dict(
            delta.receipt,
            rungs=list(self.ladder),
            seconds=round(elapsed, 4),
            cache_dir=self.cache_dir,
            quantized=self.quantized,
        )
        # the quantized-engine flag + int8 MFU-ceiling accounting
        # (docs/serving.md): serve_snapshot / healthz read the gauge,
        # and mfu_snapshot must not divide int8 steps by the bf16 peak
        publish_quantized_state(self.quantized)
        # tuned-schedule provenance beside the compile-cache receipt:
        # which road the kernel tiles took during this warm-up
        # (docs/kernels.md "Autotuning") — consult counters plus the
        # schedule-cache population
        from veles_tpu.tune.cache import tune_counters
        self.compile_receipt["tune"] = tune_counters()
        _registry.gauge("serve.aot_rungs").set(len(self.ladder))
        _registry.gauge("serve.compile_s").set(round(elapsed, 4))
        self.info(
            "AOT ladder %s compiled in %.2fs (%d compile requests, "
            "%d cache hits -> %d new backend compiles) cache=%s",
            list(self.ladder), elapsed, requests, hits,
            self.compile_receipt["new_compiles"], self.cache_dir)
        return self.compile_receipt

    def _in_model_precision(self, forward):
        """Wrap ``forward`` so the model computes in ITS precision
        whatever the wire carries: the batch arrives in the engine's
        input dtype (``self.dtype`` — a numeric numpy dtype the binary
        transport can frame, e.g. float32 or float16), is cast to the
        parameters' dtype inside the compiled program, and the
        probabilities come back float32 when the model dtype is one the
        wire cannot frame (bfloat16).  For a float32 model fed float32
        both casts are no-ops and the program is unchanged."""
        model_dtype = next(
            (numpy.dtype(entry["weights"].dtype)
             for entry in self.params
             if entry.get("weights") is not None), self.dtype)
        out_dtype = model_dtype if model_dtype.kind == "f" \
            else numpy.dtype(numpy.float32)

        def served(params, x):
            return forward(params, x.astype(model_dtype)).astype(
                out_dtype)
        return served

    def _put_params(self, params):
        put = self.device.put
        return [
            {key: (None if leaf is None else put(numpy.asarray(leaf)))
             for key, leaf in entry.items()}
            for entry in params]

    def swap_params(self, params):
        """Hot-swap the weights under the SAME architecture: new device
        buffers, zero recompiles.

        The compiled executables are parameterized by the params
        argument (``run`` passes ``self._params_dev`` per dispatch, and
        donation covers only the batch input), so replacing the device
        buffer list is the entire snapshot-reload mechanism for a
        same-digest model: the list is built complete, then swapped in
        with ONE attribute assignment — an in-flight ``run`` holds a
        reference to whichever list it started with, so batches are
        never torn between old and new weights.  A digest mismatch
        (shape/topology change) is rejected here; that case needs a new
        engine + ladder warm-up (the router's reload path).
        """
        params = [dict(entry) for entry in params]
        digest = model_digest(self.plans, params, self.sample_shape,
                              extra=engine_digest_extra(self.dtype))
        if digest != self.digest:
            raise ValueError(
                "swap_params digest mismatch (%s != %s): architecture "
                "or shapes changed — build a new engine" %
                (digest, self.digest))
        if self._params_dev is None:
            raise RuntimeError("AOTEngine.compile() not called")
        params_dev = self._put_params(params)
        self.params = params
        self._params_dev = params_dev
        return digest

    # -- dispatch -----------------------------------------------------------

    def rung_for(self, n, cap=None):
        """Smallest ladder rung holding ``n`` samples (the largest rung
        when ``n`` overflows it — callers chunk).  ``cap`` bounds the
        answer (the batcher's OOM-degrade path)."""
        top = self.ladder[-1] if cap is None else cap
        for rung in self.ladder:
            if rung > top:
                break
            if rung >= n:
                return rung
        return min(top, self.ladder[-1])

    def run(self, x_dev, rung):
        """Dispatch one pre-compiled executable on an exact-rung device
        batch; returns the device-side output (no host sync).  Bumps
        ``serve.engine.dispatches.rung<r>`` so device-segment tails in
        the request traces attribute to the executable that ran."""
        counter = self._dispatch_counters.get(rung)
        if counter is None:
            counter = self._dispatch_counters[rung] = \
                _registry.counter(
                    "serve.engine.dispatches.rung%d" % rung)
        counter.inc()
        return self._compiled[rung](self._params_dev, x_dev)

    def infer(self, x):
        """Host-side convenience: pad/chunk ``x`` through the ladder
        and return the output rows as ONE numpy array.

        This is also the sequential reference path the batching
        bit-equality test compares against: a single sample goes
        through the smallest rung, exactly like a lone queued request
        would."""
        x = numpy.ascontiguousarray(x, self.dtype)
        if x.shape == self.sample_shape:
            x = x[None]
        if x.shape[1:] != self.sample_shape:
            raise ValueError("expected sample shape %s, got %s" %
                             (self.sample_shape, x.shape[1:]))
        if self._params_dev is None:
            raise RuntimeError("AOTEngine.compile() not called")
        out, i, n = [], 0, x.shape[0]
        while i < n:
            take = min(self.max_batch, n - i)
            rung = self.rung_for(take)
            if take == rung:
                chunk = x[i:i + rung]
            else:
                chunk = numpy.zeros((rung,) + self.sample_shape,
                                    self.dtype)
                chunk[:take] = x[i:i + take]
            result = self.run(self.device.put(chunk), rung)
            out.append(numpy.asarray(result)[:take])
            i += take
        return numpy.concatenate(out) if len(out) > 1 else out[0]
