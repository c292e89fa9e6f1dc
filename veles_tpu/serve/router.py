"""Replica pool + request router: one AOT engine per chip.

The single-process serve stack (PR 7) has exactly one engine and one
batcher — fine for one chip, a hard ceiling for "millions of users".
The TensorFlow paper's serving recipe (PAPERS.md) is to replicate the
compiled function across devices behind one request stream; the TPU
in-datacenter paper adds the constraint: per-chip throughput under a
latency budget is the number that matters.  So the scale-out unit here
is a **replica** — an :class:`AOTEngine` compiled against one visible
device plus its own :class:`ContinuousBatcher` worker — and the
:class:`ReplicaPool` is the sharded front:

- **placement**: one replica per ``jax.local_devices()`` entry by
  default (``replicas=`` overrides; the CPU harness cycles devices),
  every engine compiling the SAME programs, so the persistent compile
  cache makes a warm fleet restart compile NOTHING — the cold fleet
  start is the only one that pays, and pays per device because jax's
  cache key includes the device assignment;
- **routing**: each request goes to the least-loaded replica (queue
  depth at submit); an overloaded replica cascades the request to its
  siblings before the pool sheds with a 503-shaped
  :class:`ServeOverload` whose ``retry_after`` is the fleet's best
  offer;
- **observability**: per-replica ``serve.replica.N.*`` gauges next to
  the process-shared serve counters/histograms (which therefore
  aggregate across replicas by construction), ``serve.replicas`` and
  the aggregate ``serve.queue_depth`` for heartbeats/web-status, and
  per-replica ``serve.batch`` spans (the batcher worker threads give
  each replica its own track in merged traces);
- **snapshot hot-reload** (:meth:`ReplicaPool.reload`): a same-digest
  snapshot swaps device weight buffers in place — zero recompiles,
  receipted via ``xla_introspect.compile_delta`` — while a changed
  digest AOT-warms a full new ladder per replica in the background and
  cuts over atomically between batches; either way the queue is never
  dropped.

One pool scales across one host's chips.  The next rung up is
:mod:`veles_tpu.serve.fleet`: a :class:`FleetRouter` front spanning
many serve HOSTS — each one of these pools behind its binary
transport — with the same least-loaded + cascade-then-503 semantics
lifted to host granularity, plus membership epochs and request
hedging (docs/serving.md "Multi-host tier").
"""

import threading
import time

import numpy

from veles_tpu.logger import Logger
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu.serve.batcher import ContinuousBatcher, ServeOverload
from veles_tpu.serve.engine import (
    AOTEngine, DEFAULT_LADDER, engine_digest_extra, model_digest,
    publish_quantized_state)

__all__ = ["CanaryCutover", "Replica", "ReplicaPool", "local_devices",
           "reload_replicas"]


def local_devices(count=None):
    """Device handles for a replica fleet: one :class:`backends.Device`
    per visible jax device, cycled when ``count`` asks for more
    replicas than devices (the CPU harness measures router/transport
    scaling with several replicas on one host)."""
    import jax

    from veles_tpu.backends import Device
    jax_devices = jax.local_devices()
    n = int(count) if count else len(jax_devices)
    if n < 1:
        raise ValueError("need at least one replica")
    # the platform NAME is the backend: a platform no backend is
    # registered for raises here, it is never taken for a TPU
    return [Device(backend=jax_devices[0].platform,
                   device_index=i % len(jax_devices))
            for i in range(n)]


class Replica(object):
    """One engine+batcher pair bound to one device."""

    __slots__ = ("index", "device", "engine", "batcher", "canary")

    def __init__(self, index, device, engine, batcher):
        self.index = index
        self.device = device
        self.engine = engine
        self.batcher = batcher
        #: True while this replica serves a CANDIDATE digest under
        #: canary cutover (docs/serving.md "Freshness loop"): pulled
        #: from live rotation — never a routing pick, never a cascade
        #: target — and fed only mirrored shadow traffic
        self.canary = False


def reload_replicas(replicas, params, plans=None, sample_shape=None,
                    ladder=None, engine_kwargs=None):
    """The ONE hot-reload state machine, shared by :class:`ReplicaPool`
    and the single-engine :class:`ServeService` (a list of one
    Replica-shaped entry).  Callers hold their own reload lock.

    Same digest: each entry's weights swap in place via
    ``AOTEngine.swap_params`` — zero new backend compiles, receipted
    via ``compile_delta``.  New digest (or ladder change): a full new
    engine per entry is AOT-warmed HERE, off the dispatch path, then
    each batcher cuts over between batches.  Returns the receipt."""
    from veles_tpu.observe import xla_introspect
    current = replicas[0].engine
    new_plans = list(plans) if plans is not None else current.plans
    new_shape = tuple(sample_shape) if sample_shape is not None \
        else current.sample_shape
    params = [dict(entry) for entry in params]
    # the engines' own digest recipe, input dtype included — a reload
    # that changes only the arithmetic level (f32 -> int8 spec) must
    # compare as a DIFFERENT digest and take the new-engine road
    new_digest = model_digest(new_plans, params, new_shape,
                              extra=engine_digest_extra(current.dtype))
    same = (new_digest == current.digest and
            (ladder is None or
             tuple(sorted({int(b) for b in ladder})) == current.ladder))
    mode = "params" if same else "engine"
    start = time.perf_counter()
    with _tracer.span("serve.reload", cat="serve", mode=mode,
                      digest=new_digest):
        with xla_introspect.compile_delta() as delta:
            if same:
                for rep in replicas:
                    rep.engine.swap_params(params)
            else:
                kwargs = dict(engine_kwargs or {})
                if ladder is not None:
                    kwargs["ladder"] = ladder
                fresh = []
                for rep in replicas:
                    engine = AOTEngine(new_plans, params, new_shape,
                                       device=rep.device, **kwargs)
                    engine.compile()
                    fresh.append(engine)
                # warm-up done: atomic cutover, oldest first
                for rep, engine in zip(replicas, fresh):
                    rep.batcher.swap_engine(engine)
                    rep.engine = engine
    receipt = dict(
        delta.receipt, mode=mode, digest=new_digest,
        previous_digest=current.digest, replicas=len(replicas),
        seconds=round(time.perf_counter() - start, 4))
    _registry.counter("serve.reloads").inc()
    # the fleet's served arithmetic level may have changed (f32 <->
    # int8 reload); the same-digest road compiles nothing, so the
    # flag must be republished here, from what is live now
    publish_quantized_state(replicas[0].engine.quantized)
    return receipt


class CanaryCutover(Logger):
    """The canary state machine of the train-to-serve freshness loop
    (docs/serving.md "Freshness loop"): how a candidate digest enters a
    fleet, earns (or loses) its place, and how the fleet snaps back.

    States: ``idle`` -> ``canary`` (one replica serves the candidate,
    fed only mirrored shadow traffic) -> ``promoting`` (rolling
    between-batches cutover of the live replicas) -> ``idle``; or
    ``canary``/``promoting`` -> ``idle`` via :meth:`rollback`.

    The rollback cost contract: every transition that replaces a
    replica's engine SAVES the previous engine object (still compiled)
    and every same-digest params swap SAVES the previous params list,
    so :meth:`rollback` is swap-backs only — **zero new backend
    compiles by construction**, receipted via
    ``xla_introspect.compile_delta`` and asserted by
    tests/test_freshness.py.  The driving policy (watcher, mirroring
    fraction, comparator verdicts) lives in
    :mod:`veles_tpu.serve.freshness`; this class owns only the fleet
    mechanics."""

    def __init__(self, pool):
        super(CanaryCutover, self).__init__()
        self.pool = pool
        self.state = "idle"
        self.digest = None           # candidate digest under test
        self._canary_index = None
        self._saved_engines = {}     # replica index -> pre-cutover engine
        self._saved_params = {}      # replica index -> pre-swap params
        # the POOL's reload lock, shared on purpose: a cutover
        # transition and a ReplicaPool.reload must be mutually
        # exclusive, or a reload racing begin() could clobber the
        # canary engine mid-judgment and a later rollback would
        # restore a pre-reload engine onto one replica (mixed fleet)
        self._lock = pool._reload_lock
        self._m_promotions = _registry.counter(
            "serve.freshness.promotions")
        self._m_rollbacks = _registry.counter(
            "serve.freshness.rollbacks")

    @property
    def canary_replica(self):
        if self._canary_index is None:
            return None
        return self.pool.replicas[self._canary_index]

    @staticmethod
    def _await_engine(rep, engine, timeout=10.0):
        """Block until ``rep``'s WORKER adopted ``engine``: swaps apply
        between batches, so there is a window where the replica still
        serves the previous one.  The state machine must not treat a
        swap as done inside that window — a shadow mirrored before the
        canary engine lands would be scored against the OLD model, and
        a rolled-back replica rejoining rotation early would serve the
        REJECTED model to real clients.  (The idle worker applies a
        pending swap within its 0.2s queue poll.)"""
        deadline = time.monotonic() + timeout
        while rep.batcher.engine is not engine and \
                rep.batcher.running and time.monotonic() < deadline:
            time.sleep(0.01)
        return rep.batcher.engine is engine

    def begin(self, engine):
        """Enter ``canary``: the highest-index live replica swaps to
        the (already COMPILED) candidate ``engine`` between batches and
        leaves live rotation.  Replica 0 stays live on purpose — it is
        the pool's metadata anchor."""
        with self._lock:
            if self.state != "idle":
                raise RuntimeError(
                    "canary cutover already in state %r" % self.state)
            if engine.compile_receipt is None:
                raise RuntimeError(
                    "begin() needs a COMPILED candidate engine (warm "
                    "it off the dispatch path first)")
            live = self.pool._live()
            if len(live) < 2:
                raise RuntimeError(
                    "canary cutover needs >= 2 live replicas (one "
                    "keeps serving while one tests the candidate); "
                    "use ReplicaPool.reload for a single-replica fleet")
            rep = live[-1]
            self._saved_engines = {rep.index: rep.engine}
            self._saved_params = {}
            self._canary_index = rep.index
            saved = self._saved_engines[rep.index]
            rep.canary = True
            # drain BEFORE posting the swap: the replica is out of
            # rotation now (no new routed arrivals), but requests
            # already queued were promised the LIVE model — the worker
            # applies a pending engine at the top of its loop, ahead
            # of the queue, so swapping first would answer them with
            # the unjudged candidate
            deadline = time.monotonic() + 10.0
            while (rep.batcher._q.qsize() or
                   rep.batcher._carry is not None) and \
                    time.monotonic() < deadline:
                time.sleep(0.01)  # _carry holds a popped live request
            if rep.batcher._q.qsize() or \
                    rep.batcher._carry is not None:
                rep.canary = False
                self._saved_engines = {}
                self._canary_index = None
                raise RuntimeError(
                    "canary replica %d queue never drained; aborting "
                    "begin" % rep.index)
            rep.batcher.swap_engine(engine)
            rep.engine = engine
            if not self._await_engine(rep, engine):
                # the worker never adopted the candidate (wedged past
                # the timeout): un-begin — shadows scored against the
                # OLD model would be falsely-clean evidence
                rep.batcher.swap_engine(saved)
                rep.engine = saved
                rep.canary = False
                self._saved_engines = {}
                self._canary_index = None
                raise RuntimeError(
                    "canary replica %d did not adopt the candidate "
                    "engine within the swap window; aborting begin" %
                    rep.index)
            self.digest = engine.digest
            self.state = "canary"
            _tracer.instant("serve.canary", cat="serve", phase="begin",
                            replica=rep.index, digest=engine.digest)
            self.info("canary begun on replica %d: candidate digest %s",
                      rep.index, engine.digest)
            return rep

    def shadow(self, sample, trace=None):
        """Mirror one sample to the canary replica (best-effort; see
        ``ContinuousBatcher.submit_shadow``).  Returns the shadow
        request or None.  Deliberately LOCK-FREE (atomic attribute
        reads only): promote/rollback hold the state lock across
        engine compiles, and a client thread mirroring through here
        must never stall behind them — at worst a shadow lands just as
        a verdict executes, and shadows are discardable by design.
        ``trace`` tags the mirror with the PRIMARY request's trace id
        so a merged timeline shows the shadow leg, while the shadow
        flag keeps it out of tail exemplars and served counters."""
        rep = self.canary_replica if self.state == "canary" else None
        if rep is None:
            return None
        return rep.batcher.submit_shadow(sample, trace=trace)

    def promote(self):
        """Candidate judged healthy: roll it fleet-wide.  Live replicas
        already on the candidate's DIGEST swap params in place (zero
        recompiles); a digest change AOT-warms a fresh engine per
        replica off the dispatch path, then cuts over between batches —
        rolling, one replica at a time, so the fleet never has fewer
        than N-1 replicas serving.  The canary replica rejoins rotation
        last.  Returns the promotion receipt."""
        from veles_tpu.observe import xla_introspect
        with self._lock:
            if self.state != "canary":
                raise RuntimeError(
                    "promote() from state %r (need 'canary')" %
                    self.state)
            self.state = "promoting"
            pool = self.pool
            canary = self.canary_replica
            candidate = canary.engine
            start = time.perf_counter()
            try:
                with _tracer.span("serve.canary.promote", cat="serve",
                                  digest=candidate.digest):
                    with xla_introspect.compile_delta() as delta:
                        for rep in pool.replicas:
                            if rep.index == self._canary_index:
                                continue
                            if rep.engine.digest == candidate.digest:
                                # same architecture: the previous params
                                # reference is the rollback asset; the
                                # swap is synchronous (atomic buffer-
                                # list assignment), no adoption wait
                                self._saved_params.setdefault(
                                    rep.index, rep.engine.params)
                                rep.engine.swap_params(candidate.params)
                            else:
                                engine = AOTEngine(
                                    candidate.plans, candidate.params,
                                    candidate.sample_shape,
                                    device=rep.device,
                                    **dict(pool._engine_kwargs,
                                           ladder=candidate.ladder))
                                engine.compile()
                                self._saved_engines[rep.index] = \
                                    rep.engine
                                rep.batcher.swap_engine(engine)
                                rep.engine = engine
                                # symmetric with rollback: a wedged
                                # worker still serving the OLD model
                                # behind a "promoted" receipt would be
                                # an invisible mixed fleet
                                if not self._await_engine(rep, engine):
                                    raise RuntimeError(
                                        "replica %d never adopted the "
                                        "promoted engine" % rep.index)
            except Exception:
                # a failed mid-roll promotion must not strand a mixed
                # fleet: snap every already-cut replica back
                self.exception(
                    "promotion of %s failed mid-roll; rolling back",
                    candidate.digest)
                self.rollback(reason="promotion failed")
                raise
            canary.canary = False
            self._canary_index = None
            self._saved_engines = {}
            self._saved_params = {}
            self.digest = None
            self.state = "idle"
            self._m_promotions.inc()
            # the fleet now serves the candidate's arithmetic level
            publish_quantized_state(pool.engine.quantized)
            receipt = dict(
                delta.receipt, verdict="promoted",
                digest=candidate.digest, replicas=len(pool.replicas),
                seconds=round(time.perf_counter() - start, 4))
            _tracer.instant("serve.canary", cat="serve",
                            phase="promoted", digest=candidate.digest)
            self.info("canary PROMOTED fleet-wide: %s (%d new compiles, "
                      "%.2fs)", candidate.digest,
                      receipt["new_compiles"], receipt["seconds"])
            return receipt

    def rollback(self, reason=""):
        """Candidate judged bad (or promotion failed): restore the
        last-good digest everywhere it was displaced.  Swap-backs only
        — the saved engines are already compiled and saved params swap
        in place — so the receipt's ``new_compiles`` is 0 by
        construction (the acceptance assertion of the freshness
        soak)."""
        from veles_tpu.observe import xla_introspect
        with self._lock:
            if self.state not in ("canary", "promoting"):
                raise RuntimeError(
                    "rollback() from state %r (need 'canary' or "
                    "'promoting')" % self.state)
            pool = self.pool
            bad = self.digest
            start = time.perf_counter()
            with xla_introspect.compile_delta() as delta:
                for index, engine in self._saved_engines.items():
                    rep = pool.replicas[index]
                    rep.batcher.swap_engine(engine)
                    rep.engine = engine
                for index, params in self._saved_params.items():
                    pool.replicas[index].engine.swap_params(params)
            # the restored engines must be LIVE in their workers before
            # any replica rejoins rotation: a client request served by
            # the rejected candidate after "rollback" would make the
            # canary contract a lie.  A replica whose worker never
            # adopts (wedged past the timeout) STAYS out of rotation —
            # quarantined-by-flag — rather than rejoining with the
            # rejected engine still live
            unadopted = []
            for index, engine in self._saved_engines.items():
                if not self._await_engine(pool.replicas[index], engine):
                    unadopted.append(index)
            canary = self.canary_replica
            if canary is not None and canary.index not in unadopted:
                canary.canary = False
            for index in unadopted:
                pool.replicas[index].canary = True
                self.error(
                    "replica %d never adopted the restored engine; "
                    "LEAVING it out of live rotation (restart or "
                    "reload to recover it)", index)
            self._canary_index = None
            self._saved_engines = {}
            self._saved_params = {}
            self.digest = None
            self.state = "idle"
            self._m_rollbacks.inc()
            # rollback is swap-backs only (0 compiles by construction)
            # so nothing recompiled to republish the level: a rejected
            # quantized candidate's warm-up flipped the process-global
            # flag/MFU ceiling, and the restored fleet must flip it
            # back (regression: tests/test_quant.py)
            publish_quantized_state(pool.engine.quantized)
            receipt = dict(
                delta.receipt, verdict="rolled_back", digest=bad,
                restored_digest=pool.digest, reason=reason,
                seconds=round(time.perf_counter() - start, 4))
            if unadopted:
                receipt["unadopted_replicas"] = unadopted
            _tracer.instant("serve.canary", cat="serve",
                            phase="rolled_back", digest=bad,
                            reason=reason)
            self.warning(
                "canary ROLLED BACK: candidate %s rejected (%s); fleet "
                "restored to %s with %d new compiles", bad,
                reason or "unspecified", receipt["restored_digest"],
                receipt["new_compiles"])
            return receipt

    def snapshot(self):
        """Plain-data state for /healthz and the dashboard.  Lock-free
        like :meth:`shadow` — the IO loop must never wait out a
        promotion's compiles for a health read."""
        out = {"state": self.state}
        digest, index = self.digest, self._canary_index
        if digest is not None:
            out["candidate_digest"] = digest
        if index is not None:
            out["replica"] = index
        return out


class ReplicaPool(Logger):
    """N per-device serving replicas behind one least-loaded router.

    Duck-types the :class:`ContinuousBatcher` submit surface
    (``submit``/``submit_block``/``infer``/``start``/``stop``), so
    :class:`ServeService` and the binary transport drive a pool and a
    single batcher identically."""

    def __init__(self, plans, params, sample_shape, replicas=None,
                 ladder=DEFAULT_LADDER, devices=None,
                 dtype=numpy.float32, **batcher_kwargs):
        super(ReplicaPool, self).__init__()
        if devices is None:
            devices = local_devices(replicas)
        elif replicas:
            devices = [devices[i % len(devices)]
                       for i in range(int(replicas))]
        self._engine_kwargs = dict(ladder=ladder, dtype=dtype)
        self._batcher_kwargs = dict(batcher_kwargs)
        self.replicas = []
        for i, device in enumerate(devices):
            engine = AOTEngine(plans, params, sample_shape,
                               device=device, **self._engine_kwargs)
            batcher = ContinuousBatcher(engine, replica=i,
                                        **self._batcher_kwargs)
            self.replicas.append(Replica(i, device, engine, batcher))
        self.compile_receipt = None
        # RLock: shared with CanaryCutover (see its __init__), whose
        # promote() re-enters via rollback() on a failed mid-roll
        self._reload_lock = threading.RLock()
        #: the canary state machine (docs/serving.md "Freshness loop")
        self.cutover = CanaryCutover(self)
        #: set by the freshness controller while a canary is live:
        #: called as ``hook(sample, primary_request)`` after every
        #: successful single-sample submit so a traffic slice can be
        #: mirrored to the canary replica
        self.mirror_hook = None
        self._m_replicas = _registry.gauge("serve.replicas")
        self._m_replicas.set(len(self.replicas))
        self._m_depth = _registry.gauge("serve.queue_depth")
        self._m_cascades = _registry.counter("serve.router.cascades")

    # -- workflow plumbing --------------------------------------------------

    @staticmethod
    def _workflow_spec(sw, sample_shape=None):
        from veles_tpu.compiler import workflow_plan
        plans = workflow_plan(sw)
        # read params through the HOST side, not extract_state's
        # devmem: a freshly-unpickled snapshot (restore_workflow, the
        # freshness watcher) has no device attached yet, so its Arrays'
        # devmem is None until someone re-initializes the workflow —
        # serving only needs the values, and host numpy is exactly what
        # AOTEngine wants to place per replica device anyway
        params = []
        for fwd in sw.forwards:
            entry = {}
            for key, arr in (("weights", fwd.weights),
                             ("bias", fwd.bias)):
                if arr:
                    arr.map_read()
                    entry[key] = numpy.array(arr.mem, copy=True)
                else:
                    entry[key] = None
            params.append(entry)
        if sample_shape is None:
            loader = getattr(sw, "loader", None)
            if loader is not None and loader.minibatch_data:
                sample_shape = tuple(loader.minibatch_data.shape[1:])
            else:
                raise ValueError("workflow has no loader shape; pass "
                                 "sample_shape=")
        return plans, params, tuple(sample_shape)

    @classmethod
    def from_workflow(cls, sw, **kwargs):
        """Build a pool from a trained StandardWorkflow, exactly like
        ``AOTEngine.from_workflow`` but fanned out per device."""
        plans, params, sample_shape = cls._workflow_spec(
            sw, kwargs.pop("sample_shape", None))
        return cls(plans, params, sample_shape, **kwargs)

    # -- lifecycle ----------------------------------------------------------

    @property
    def engine(self):
        """The first LIVE replica's engine: the pool's metadata anchor
        (digest, ladder, sample shape, dtype) — LIVE across hot reloads
        and canary cutovers (a replica testing a candidate digest must
        not change what /healthz says the fleet serves)."""
        for rep in self.replicas:
            if not rep.canary:
                return rep.engine
        return self.replicas[0].engine

    @property
    def digest(self):
        return self.engine.digest

    def compile(self):
        """Compile every replica's ladder; returns the aggregate
        receipt.  All replicas share the ONE persistent cache
        directory; jax's cache key includes the device assignment, so
        a cold fleet start writes one entry set per device — and a
        warm fleet RESTART deserializes every one of them:
        ``new_compiles == 0`` across all N replicas, asserted by
        tests/test_serve_router.py."""
        start = time.perf_counter()
        per = [rep.engine.compile() for rep in self.replicas]
        self.compile_receipt = {
            "replicas": len(per),
            "rungs": per[0]["rungs"],
            "backend_compiles": sum(
                r["backend_compiles"] for r in per),
            "cache_hits": sum(r["cache_hits"] for r in per),
            "new_compiles": sum(r["new_compiles"] for r in per),
            "seconds": round(time.perf_counter() - start, 4),
            "cache_dir": per[0]["cache_dir"],
            "per_replica": per,
        }
        return self.compile_receipt

    @property
    def running(self):
        return any(rep.batcher.running for rep in self.replicas)

    def start(self):
        for rep in self.replicas:
            rep.batcher.start()
        return self

    def stop(self):
        for rep in self.replicas:
            rep.batcher.stop()
        self._m_depth.set(0)

    def set_host_tag(self, tag):
        """Propagate the serving host's fleet identity to every
        replica's batcher, so request-scoped spans emitted here carry
        ``host=<tag>`` — two in-process hosts of one test fleet stay
        attributable after their traces are merged."""
        for rep in self.replicas:
            rep.batcher.set_host_tag(tag)

    # -- routing ------------------------------------------------------------

    def _update_depth(self):
        self._m_depth.set(sum(rep.batcher._q.qsize()
                              for rep in self.replicas))

    def _live(self):
        """Replicas in live rotation.  A canary replica is excluded
        from the routing pick AND from the overload cascade — mirrored
        shadow traffic is its only diet, so overflow landing there
        would both overload the measurement and serve real clients
        from an unjudged candidate — and the fleet's 503 retry_after
        is computed over the replicas that will actually serve the
        retry.  Falls back to all replicas if (impossibly) every one
        is canary."""
        live = [rep for rep in self.replicas if not rep.canary]
        return live or self.replicas

    def _submit(self, fn):
        """Least-queue-depth pick with overload cascade: try LIVE
        replicas in depth order; only when every live replica sheds
        does the pool itself shed, with the smallest retry_after any
        live replica offered (the fleet's best promise, not its
        worst)."""
        for _ in range(3):
            ranked = sorted(self._live(),
                            key=lambda rep: rep.batcher._q.qsize())
            sheds = []
            for nth, rep in enumerate(ranked):
                try:
                    req = fn(rep.batcher)
                except ServeOverload as exc:
                    sheds.append(exc)
                    continue
                if rep.canary:
                    # lost the race with CanaryCutover.begin(): the
                    # pick was live at ranking time but the replica
                    # turned canary before the enqueue landed — that
                    # request would be answered by the unjudged
                    # candidate.  Cancel it (the worker drops
                    # cancelled requests at dispatch) and re-route.
                    req.cancelled = True
                    continue
                if nth:
                    self._m_cascades.inc()
                self._update_depth()
                return req
            self._update_depth()
            if sheds:
                raise ServeOverload(
                    "all %d live replicas shedding (%s)" %
                    (len(ranked), sheds[-1]),
                    retry_after=min(exc.retry_after
                                    for exc in sheds))
            # every pick raced a cutover transition: re-rank and retry
        raise ServeOverload("fleet reconfiguring", retry_after=0.1)

    def submit(self, sample, slo_class=None, trace=None):
        req = self._submit(
            lambda batcher: batcher.submit(sample, slo_class=slo_class,
                                           trace=trace))
        hook = self.mirror_hook
        if hook is not None:
            try:
                hook(sample, req)
            except Exception:
                # mirroring is an observation: it must never fail (or
                # slow) the request it observes
                self.exception("canary mirror hook failed")
        return req

    def submit_block(self, block, slo_class=None, trace=None):
        return self._submit(
            lambda batcher: batcher.submit_block(
                block, slo_class=slo_class, trace=trace))

    def infer(self, sample, timeout=30.0, slo_class=None, trace=None):
        """Blocking submit through the router (single sample)."""
        return self._wait(
            self.submit(sample, slo_class=slo_class, trace=trace),
            timeout)

    def infer_block(self, block, timeout=30.0, slo_class=None,
                    trace=None):
        """Blocking whole-batch submit (the binary transport's path):
        one request, zero row copies, result is the 2-D block."""
        return self._wait(
            self.submit_block(block, slo_class=slo_class, trace=trace),
            timeout)

    @staticmethod
    def _wait(req, timeout):
        if not req.done.wait(timeout):
            raise TimeoutError("inference timed out after %.1fs"
                               % timeout)
        if req.error is not None:
            raise req.error
        return req.result

    # -- snapshot hot-reload ------------------------------------------------

    def reload(self, params, plans=None, sample_shape=None,
               ladder=None):
        """Swap the served model under load; returns the reload receipt.

        Same digest (retrained weights, identical architecture): each
        replica's device buffers are rebuilt and swapped in atomically
        — ZERO new backend compiles, receipted via ``compile_delta``
        (the acceptance assertion of docs/serving.md).  New digest (or
        a ladder change): a full new engine per replica is AOT-warmed
        here — off the dispatch path, requests keep batching on the old
        engines — then cut over between batches.  Either way no queued
        request is dropped or failed by the reload itself."""
        with self._reload_lock:
            # checked INSIDE the shared lock: cutover transitions hold
            # it too, so the state cannot flip between check and swap
            if self.cutover.state != "idle":
                raise RuntimeError(
                    "hot-reload refused: canary cutover in progress "
                    "(state %r) — promote or roll back first, or "
                    "route new models through the freshness loop" %
                    self.cutover.state)
            receipt = reload_replicas(
                self.replicas, params, plans=plans,
                sample_shape=sample_shape, ladder=ladder,
                engine_kwargs=self._engine_kwargs)
            # a full-fleet reload re-homogenizes every replica, so a
            # rollback-quarantined one (canary flag left True because
            # its worker never adopted the restored engine) is
            # recovered here — the quarantine error message promises
            # exactly this
            for rep in self.replicas:
                rep.canary = False
            self.info(
                "hot reload (%s): %s -> %s in %.2fs, %d new compiles",
                receipt["mode"], receipt["previous_digest"],
                receipt["digest"], receipt["seconds"],
                receipt["new_compiles"])
            return receipt

    def reload_workflow(self, sw):
        """Reload from a (re)trained workflow / restored snapshot."""
        try:
            plans, params, shape = self._workflow_spec(sw)
        except ValueError:
            plans, params, shape = self._workflow_spec(
                sw, self.engine.sample_shape)
        return self.reload(params, plans=plans, sample_shape=shape)

    # -- observability ------------------------------------------------------

    def snapshot(self):
        """Plain-data pool state for /healthz and the dashboard."""
        out = {
            "replicas": len(self.replicas),
            "digest": self.digest,
            "queue_depths": [rep.batcher._q.qsize()
                             for rep in self.replicas],
            "devices": [str(getattr(rep.device, "backend_name", "?"))
                        + ":%d" % getattr(rep.device, "device_index", 0)
                        for rep in self.replicas],
        }
        if self.cutover.state != "idle":
            out["canary"] = self.cutover.snapshot()
        # single-host serving evaluates the process-global alert
        # manager (heartbeat cadence — observe/profile.py); surface
        # what is burning next to the queue depths it burns about
        from veles_tpu.observe.alerts import alerts
        active = alerts.active()
        if alerts.rules or active:
            out["alerts_active"] = sorted(r["alert"] for r in active)
        return out
