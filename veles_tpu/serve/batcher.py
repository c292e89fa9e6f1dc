"""Continuous-batching request queue over the AOT engine.

One worker thread drains pending requests into the largest fitting
ladder rung: the first request of a batch waits at most ``max_delay_s``
for company (the latency/throughput knob), the tail is zero-padded up
to the rung, and the batch runs on **ping-pong host staging buffers**
(the PR 1 ``memory.Array.stage_init/stage_begin/stage_put`` machinery)
so the next batch's host fill overlaps the current batch's transfer.
``stage_put`` goes through ``Device.put``, which on XLA:CPU makes the
XLA-owned copy that the zero-copy ``device_put`` hazard demands (see
``CPUDevice.put``) — the staged host buffer is never aliased by a live
executable input, donated or not.

Overload protocol (mirrors the distributed server's TTL-blacklist
rejects, docs/distributed.md): past ``max_queue`` pending requests,
:meth:`ContinuousBatcher.submit` raises :class:`ServeOverload` carrying
a ``retry_after`` estimate instead of growing the queue without bound;
the HTTP front turns it into ``503 {"retry_after": ...}`` and a
well-behaved client sleeps it out, exactly like a blacklisted slave.

Degradation: an OOM-shaped engine failure (`RESOURCE_EXHAUSTED` /
``MemoryError``) permanently caps the ladder below the failing rung and
replays the batch in capped chunks — serving gets slower, not dead.
Other engine failures fail only that batch's requests and keep the
worker alive.

SLO watch: per-request end-to-end latency feeds the ``serve.latency_s``
histogram; every ``slo_check_every`` batches the recent window's
p50/p99 are compared against the configured thresholds and each breach
bumps ``serve.slo_violations`` + records a trace/flight-recorder
instant, so a post-mortem dump shows *when* the tail blew up, next to
the batch spans that did it.

Multi-tenant QoS (docs/serving.md "Multi-tenant QoS"): every request
carries an SLO class (``interactive`` / ``batch`` / ``best_effort``;
un-labelled traffic defaults to ``batch``) and the queue bound is
class-aware — a full queue evicts a pending request of STRICTLY lower
class (shed attributed to the victim's class, with a seeded per-class
jittered ``retry_after``) before it sheds the incoming one, so
interactive work starves last and is shed only when the queue is
saturated with interactive work itself.

Chaos points (docs/health.md table): ``serve.drop`` (submit-side shed),
``serve.stall`` (worker sleeps ``param`` seconds — trips the SLO
watch), ``serve.device.stall`` (sleeps at the DEVICE-dispatch edge so
request timelines attribute the stall to the device segment — the
tail-attribution chaos hook), ``serve.oom`` (simulated
RESOURCE_EXHAUSTED — exercises the degrade path),
``serve.tenant.flood`` (``param`` synthetic best-effort requests storm
the queue as real load — exercises class-ordered shedding).

Request tracing (docs/observability.md "Request tracing"): while
``VELES_REQTRACE`` is on, the worker stamps each request's segment
timeline (queue / assemble / h2d / device / d2h) on the request object
before ``done.set()``, feeds the tail-exemplar ring, and emits
request-track spans for sampled ids; an SLO-breach ENTER edge dumps
the exemplar ring with the flight recorder.
"""

import collections
import queue
import threading
import time

import numpy

from veles_tpu import chaos
from veles_tpu.logger import Logger
from veles_tpu.memory import Array
from veles_tpu.observe import requests as reqtrace
from veles_tpu.observe.metrics import percentiles
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu.serve import qos

__all__ = ["ContinuousBatcher", "ServeOverload", "serve_snapshot"]


class ServeOverload(Exception):
    """Load shed: the queue is full (or chaos dropped the request).
    ``retry_after`` (seconds) marks the rejection transient — the HTTP
    layer ships it as 503 + retry_after, like the server blacklist."""

    def __init__(self, message, retry_after=0.1):
        super(ServeOverload, self).__init__(message)
        self.retry_after = float(retry_after)


class _Request(object):
    __slots__ = ("sample", "enqueued", "done", "result", "error",
                 "cancelled", "block", "shadow", "latency", "slo_class",
                 "claimed", "trace", "marks", "child")

    def __init__(self, sample, block=False, shadow=False,
                 slo_class=None, trace=None):
        self.sample = sample
        #: canonical SLO class ("interactive" / "batch" /
        #: "best_effort") — decides shed order under overload and which
        #: serve.tenant.<class>.* series the request lands in
        self.slo_class = qos.normalize_class(slo_class)
        #: request trace id (observe/requests.py id contract) — rides
        #: the request through requeue/hedge/chunked replay unchanged
        self.trace = trace
        #: segment timeline [(segment, start_perf, dur_s)] stamped by
        #: the worker at completion, BEFORE done.set() so a transport
        #: waiter can echo it over the wire; None while VELES_REQTRACE
        #: is off (the zero-overhead kill switch)
        self.marks = None
        #: OOM-replay slice of a block request: its marks fold into the
        #: parent's timeline instead of emitting their own spans /
        #: exemplars (the parent is the request the client knows)
        self.child = False
        self.enqueued = time.perf_counter()
        self.done = threading.Event()
        self.result = None
        self.error = None
        #: set by a caller that gave up on the request (e.g. a batch
        #: payload that shed partway through submission); the worker
        #: drops it at dispatch instead of computing for nobody
        self.cancelled = False
        #: True when ``sample`` is a whole contiguous batch submitted
        #: via :meth:`ContinuousBatcher.submit_block` — the worker can
        #: hand its buffer to ``Device.put`` verbatim when it fills a
        #: rung exactly (the binary transport's zero-copy hot path)
        self.block = block
        #: canary-mirror shadow copy (docs/serving.md "Freshness
        #: loop"): computed and scored like any request but NEVER
        #: counted in the served metrics (``serve.requests`` /
        #: ``serve.latency_s``) — shadow traffic must not double-count
        #: in capacity math or skew the SLO watch
        self.shadow = shadow
        #: end-to-end seconds, stamped by the worker at completion —
        #: the canary comparator reads it off shadow/primary pairs
        #: instead of re-timing around the Event wait
        self.latency = None
        #: set by the worker when it dequeues the request: class-
        #: ordered eviction must only cancel work still WAITING — a
        #: claimed request is already being served, so evicting it
        #: would not free queue capacity
        self.claimed = False

    @property
    def rows(self):
        return self.sample.shape[0] if self.block else 1


def _oom_shaped(exc):
    return isinstance(exc, MemoryError) or \
        "RESOURCE_EXHAUSTED" in str(exc) or \
        "Out of memory" in str(exc)


class ContinuousBatcher(Logger):
    """Worker thread turning a request stream into padded-rung batches.

    ``max_delay_s`` bounds how long the OLDEST request of a forming
    batch waits for more arrivals; ``max_queue`` bounds pending
    requests before :meth:`submit` sheds; ``slo_p50_ms``/``slo_p99_ms``
    arm the SLO watch (None disables a threshold)."""

    def __init__(self, engine, max_delay_s=0.002, max_queue=256,
                 slo_p50_ms=None, slo_p99_ms=None, slo_check_every=4,
                 replica=None, retry_jitter=None, **kwargs):
        super(ContinuousBatcher, self).__init__(**kwargs)
        self.engine = engine
        self.max_delay_s = float(max_delay_s)
        self.max_queue = int(max_queue)
        #: seeded per-class retry_after jitter (satellite of the QoS
        #: layer): synchronized clients shed together must not
        #: re-stampede together
        self.retry_jitter = retry_jitter if retry_jitter is not None \
            else qos.RetryJitter()
        #: pending requests a HIGHER class may evict when the queue is
        #: full — interactive has no deque: it is never evicted, only
        #: shed at its own admission when the queue is saturated with
        #: interactive work itself (qos.SHED_ORDER contract)
        self._evictable = {cls: collections.deque()
                           for cls in qos.SHED_ORDER
                           if cls != "interactive"}
        self.slo_p50_ms = slo_p50_ms
        self.slo_p99_ms = slo_p99_ms
        self.slo_check_every = max(1, int(slo_check_every))
        #: replica index inside a ReplicaPool; scopes the GAUGES (each
        #: replica's queue depth / rung cap is its own signal) while
        #: counters and histograms stay process-shared so fleet totals
        #: and latency percentiles aggregate by construction
        self.replica = replica
        #: fleet host identity (set by BinaryTransportServer via
        #: ``set_host_tag`` when host_meta names one): rides request-
        #: span args so two in-process hosts' legs stay attributable
        #: in a shared tracer, and a merged cross-host timeline can
        #: name the slow leg
        self.host_tag = None
        self._q = queue.Queue()
        self._thread = None
        self._stop_ = False
        self._rung_cap = engine.max_batch
        self._stage = {}      # rung -> (Array, [slot])
        self._carry = None    # popped request that overflowed a batch
        self._pending_engine = None
        self._batches_since_check = 0
        self._slo_breached = False
        # metrics resolved once (docs/observability.md serve set)
        scope = "serve" if replica is None else \
            "serve.replica.%d" % replica
        self._m_depth = _registry.gauge(scope + ".queue_depth")
        self._g_rung_cap = _registry.gauge(scope + ".rung_cap")
        # published from the start: "never degraded" must read as the
        # top rung, not as a gauge nobody set
        self._g_rung_cap.set(self._rung_cap)
        self._m_batch = _registry.histogram("serve.batch_size")
        self._m_latency = _registry.histogram("serve.latency_s")
        self._m_requests = _registry.counter("serve.requests")
        self._m_batches = _registry.counter("serve.batches")
        self._m_padded = _registry.counter("serve.padded_rows")
        self._m_shed = _registry.counter("serve.shed")
        self._m_errors = _registry.counter("serve.errors")
        self._m_slo = _registry.counter("serve.slo_violations")
        # per-segment latency histograms (observe/requests.py segment
        # taxonomy); queue is per-request, the rest per-batch — fed
        # only while request tracing is enabled
        self._h_seg = {
            name: _registry.histogram("serve.segment.%s_s" % name)
            for name in ("queue", "assemble", "h2d", "device", "d2h")}
        self._m_depth.set(0)

    def set_host_tag(self, tag):
        """Name the fleet host this batcher serves (transport hello
        host_meta); request spans carry it as the leg attribution."""
        self.host_tag = tag

    # -- lifecycle ----------------------------------------------------------

    @property
    def running(self):
        return self._thread is not None

    def start(self):
        if self._thread is not None:
            return self
        self._stop_ = False
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-batcher")
        self._thread.start()
        return self

    def stop(self):
        """Stop the worker and JOIN it (the test suite's thread-leak
        fixture enforces this); pending requests fail with overload so
        no caller blocks forever on a dead queue."""
        self._stop_ = True
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=10)
        carry, self._carry = self._carry, None
        while True:
            if carry is not None:
                req, carry = carry, None
            else:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    break
            if not req.done.is_set():
                req.error = ServeOverload("server shutting down",
                                          retry_after=1.0)
                req.done.set()
        self._m_depth.set(0)

    # -- hot reload ---------------------------------------------------------

    def swap_engine(self, engine):
        """Queue an atomic engine cutover (snapshot hot-reload with a
        NEW digest): the worker applies it BETWEEN batches, so no batch
        is ever torn across engines and no queued request is dropped —
        requests keep queueing during the background compile and are
        served by whichever engine owns the batch they land in.

        Same-digest reloads never come here: ``AOTEngine.swap_params``
        swaps device buffers in place with zero recompiles."""
        if engine.compile_receipt is None:
            raise RuntimeError(
                "swap_engine needs a COMPILED engine (warm the ladder "
                "before cutover — compiling on the serving path is the "
                "failure mode the AOT design exists to avoid)")
        if self._thread is None:
            self._apply_engine(engine)  # stopped: no batch to tear
        else:
            self._pending_engine = engine

    def _apply_engine(self, engine):
        """Worker-side half of :meth:`swap_engine` (between batches)."""
        self._pending_engine = None
        old = self.engine
        self.engine = engine
        # staging buffers are shaped by the OLD engine's sample shape/
        # dtype; drop them (rebuilt lazily) and lift any OOM cap — the
        # new model's memory behavior is its own
        self._stage.clear()
        self._rung_cap = engine.max_batch
        self._g_rung_cap.set(engine.max_batch)
        if _tracer.active:
            _tracer.instant(
                "serve.reload.cutover", cat="serve",
                replica=self.replica if self.replica is not None else 0,
                old_digest=old.digest, new_digest=engine.digest)
        self.info("engine cutover: %s -> %s", old.digest, engine.digest)

    # -- submit side --------------------------------------------------------

    def _retry_after(self):
        """Transient-backoff estimate: the queue drained at the recent
        per-batch pace, bounded to something a client will tolerate."""
        window = self._m_latency.window_values()
        p50 = percentiles(window, ps=(50,)).get("p50") if window else None
        per_batch = p50 if p50 else 0.05
        depth = self._q.qsize()
        return min(5.0, max(0.05, per_batch * (
            1 + depth / float(self.engine.max_batch))))

    def _shed(self, slo_class, message):
        """Account one shed against ``slo_class`` and raise the
        overload with the class-jittered ``retry_after``."""
        self._m_shed.inc()
        qos.note_shed(slo_class)
        retry = self.retry_jitter.apply(self._retry_after(), slo_class)
        if _tracer.active:
            _tracer.instant("serve.shed", cat="serve",
                            depth=self._q.qsize(), slo_class=slo_class,
                            retry_after=round(retry, 4))
        raise ServeOverload(message, retry_after=retry)

    def _evict_lower(self, incoming_cls):
        """Cancel one pending request of STRICTLY lower class than
        ``incoming_cls`` to make room; the shed is attributed to the
        VICTIM's class.  Returns False when no lower-class work is
        pending — the incoming request must be shed instead (so a
        queue saturated with interactive work sheds interactive, and
        nothing below interactive ever evicts it)."""
        incoming_rank = qos.class_rank(incoming_cls)
        for victim_cls in qos.SHED_ORDER:
            if qos.class_rank(victim_cls) >= incoming_rank:
                return False
            dq = self._evictable[victim_cls]
            while True:
                try:
                    victim = dq.popleft()
                except IndexError:
                    break
                if victim.cancelled or victim.claimed or \
                        victim.done.is_set():
                    continue  # served, being served, or evicted
                victim.cancelled = True
                victim.error = ServeOverload(
                    "shed for %s admission (class-ordered eviction)"
                    % incoming_cls,
                    retry_after=self.retry_jitter.apply(
                        self._retry_after(), victim_cls))
                self._m_shed.inc()
                qos.note_shed(victim_cls)
                if _tracer.active:
                    _tracer.instant("serve.shed", cat="serve",
                                    depth=self._q.qsize(),
                                    slo_class=victim_cls,
                                    evicted_for=incoming_cls)
                victim.done.set()
                return True
        return False

    def _flood(self, count):
        """Chaos ``serve.tenant.flood``: enqueue ``count`` synthetic
        zero-sample best_effort requests as REAL load (no waiter) —
        the storm contends for queue capacity like any bulk tenant
        would, and rows past the bound are shed like any best_effort."""
        zero = numpy.zeros(self.engine.sample_shape, self.engine.dtype)
        for _ in range(count):
            if self._q.qsize() >= self.max_queue:
                self._m_shed.inc()
                qos.note_shed("best_effort")
                continue
            try:
                self._enqueue(_Request(zero, slo_class="best_effort"))
            except ServeOverload:
                break  # racing a stop(): the storm dies with the queue

    def _admit(self, slo_class=qos.DEFAULT_CLASS):
        """Shared admission control: running check, chaos shed, class-
        aware queue bound.  Raises :class:`ServeOverload` when the
        request must be shed."""
        if self._thread is None or self._stop_:
            raise ServeOverload("batcher not running", retry_after=1.0)
        if chaos.plan is not None:
            fault = chaos.plan.fire("serve.tenant.flood")
            if fault is not None:
                self._flood(int(fault.param) if fault.param else 32)
            fault = chaos.plan.fire("serve.drop")
            if fault is not None:
                self._m_shed.inc()
                qos.note_shed(slo_class)
                raise ServeOverload(
                    "chaos: request dropped",
                    retry_after=self.retry_jitter.apply(
                        self._retry_after(), slo_class))
        if self._q.qsize() >= self.max_queue and \
                not self._evict_lower(slo_class):
            self._shed(slo_class,
                       "queue full (%d pending)" % self._q.qsize())

    def _enqueue(self, req):
        self._q.put(req)
        if not req.shadow and req.slo_class in self._evictable:
            dq = self._evictable[req.slo_class]
            dq.append(req)
            if len(dq) > 2 * self.max_queue:
                # lazy compaction: drop served/evicted entries so the
                # deque tracks only live pending work
                live = [r for r in dq
                        if not r.cancelled and not r.done.is_set()]
                dq.clear()
                dq.extend(live)
        if self._stop_:
            # lost the race with a concurrent stop(): its drain may
            # have already run, so complete the request here — nobody
            # else will, and the caller must not block out its timeout
            req.error = ServeOverload("server shutting down",
                                      retry_after=1.0)
            req.done.set()
            raise req.error
        self._m_depth.set(self._q.qsize())
        return req

    def submit(self, sample, slo_class=None, trace=None):
        """Enqueue one sample; returns the pending request.  Raises
        :class:`ServeOverload` when shedding (full queue or chaos
        ``serve.drop``).  ``slo_class`` labels the request for the QoS
        layer (class-ordered shedding + per-class accounting);
        un-labelled callers default to ``batch``.  ``trace`` is the
        request trace id (observe/requests.py) the worker stamps its
        segment timeline against."""
        slo_class = qos.normalize_class(slo_class)
        self._admit(slo_class)
        sample = numpy.ascontiguousarray(sample, self.engine.dtype)
        if sample.shape != self.engine.sample_shape:
            raise ValueError("expected sample shape %s, got %s" %
                             (self.engine.sample_shape, sample.shape))
        return self._enqueue(_Request(sample, slo_class=slo_class,
                                      trace=trace))

    def submit_block(self, block, slo_class=None, trace=None):
        """Enqueue a whole batch as ONE request whose rows stay in
        their caller-provided buffer.

        For an already-contiguous same-dtype block — exactly what the
        binary transport decodes with ``numpy.frombuffer`` — the rows
        are NEVER copied into the ping-pong staging `memory.Array`:
        when the block fills a rung by itself the worker hands the
        buffer straight to ``Device.put`` (which on XLA:CPU makes the
        one XLA-owned copy the zero-copy ``device_put`` hazard demands
        — never raw ``jax.device_put``; see ``CPUDevice.put``), and
        when it co-batches, the fill is one vectorized slice-assign
        instead of a Python loop.  Non-conforming input falls back to
        one normalizing copy here, so callers need no special casing.
        """
        slo_class = qos.normalize_class(slo_class)
        self._admit(slo_class)
        block = numpy.asarray(block)
        if block.dtype != self.engine.dtype or \
                not block.flags["C_CONTIGUOUS"]:
            block = numpy.ascontiguousarray(block, self.engine.dtype)
        if block.ndim != len(self.engine.sample_shape) + 1 or \
                block.shape[1:] != self.engine.sample_shape:
            raise ValueError("expected a (n,) + %s block, got %s" %
                             (self.engine.sample_shape, block.shape))
        if not 1 <= block.shape[0] <= self.engine.max_batch:
            raise ValueError(
                "block of %d rows overflows the ladder (max %d); "
                "chunk at the caller" %
                (block.shape[0], self.engine.max_batch))
        return self._enqueue(_Request(block, block=True,
                                      slo_class=slo_class,
                                      trace=trace))

    def submit_shadow(self, sample, trace=None):
        """Best-effort enqueue of a canary-mirror shadow copy: never
        raises :class:`ServeOverload` — a loaded (or chaos-shedding)
        canary simply mirrors less — and returns None instead of a
        request when dropped.  Shadow requests co-batch like real ones
        but are excluded from the served counters (``serve.requests``,
        ``serve.latency_s``) and never bump the shed counter: mirrored
        traffic is an observation, not load.  A shadow KEEPS the
        primary's trace id (its spans are tagged ``shadow``) but is
        excluded from the tail-exemplar ring."""
        if self._thread is None or self._stop_ or \
                self._q.qsize() >= self.max_queue:
            return None
        sample = numpy.ascontiguousarray(sample, self.engine.dtype)
        if sample.shape != self.engine.sample_shape:
            raise ValueError("expected sample shape %s, got %s" %
                             (self.engine.sample_shape, sample.shape))
        try:
            return self._enqueue(_Request(sample, shadow=True,
                                          trace=trace))
        except ServeOverload:
            return None  # lost the race with stop(): drop the shadow

    def infer(self, sample, timeout=30.0):
        """Blocking submit: returns the output row (numpy) or raises
        the request's error."""
        req = self.submit(sample)
        if not req.done.wait(timeout):
            raise TimeoutError("inference timed out after %.1fs"
                               % timeout)
        if req.error is not None:
            raise req.error
        return req.result

    # -- worker side --------------------------------------------------------

    def _loop(self):
        while not self._stop_:
            pending = self._pending_engine
            if pending is not None:
                self._apply_engine(pending)
            first, self._carry = self._carry, None
            if first is None:
                try:
                    first = self._q.get(timeout=0.2)
                except queue.Empty:
                    continue
            first.claimed = True
            if first.cancelled:
                # evicted by a higher class while queued: drop the
                # corpse without charging it against the rung budget
                self._m_depth.set(self._q.qsize())
                continue
            batch = self._collect(first)
            self._m_depth.set(self._q.qsize())
            try:
                self._run_batch(batch)
            except Exception as exc:  # never kill the worker
                self._m_errors.inc()
                self.exception("serve batch failed")
                for req in batch:
                    if not req.done.is_set():
                        req.error = exc
                        req.done.set()

    def _collect(self, first):
        """Grow a batch around the oldest pending request: drain
        whatever is already queued instantly, then wait out the
        remaining queue-delay budget for stragglers.  Accounting is in
        ROWS (a block request carries several); a popped request that
        would overflow the rung limit becomes the head of the next
        batch via the carry slot."""
        batch = [first]
        rows = first.rows
        limit = min(self._rung_cap, self.engine.max_batch)
        deadline = first.enqueued + self.max_delay_s
        while rows < limit and not self._stop_:
            remaining = deadline - time.perf_counter()
            try:
                if remaining <= 0:
                    req = self._q.get_nowait()
                else:
                    req = self._q.get(timeout=remaining)
            except queue.Empty:
                break
            req.claimed = True
            if req.cancelled:
                continue  # evicted while queued: zero rows, skip
            if rows + req.rows > limit:
                self._carry = req
                break
            batch.append(req)
            rows += req.rows
        return batch

    def _staging(self, rung):
        arr_slot = self._stage.get(rung)
        if arr_slot is None:
            arr = Array(numpy.zeros(
                (rung,) + self.engine.sample_shape, self.engine.dtype))
            arr.stage_init(2)
            arr_slot = self._stage[rung] = [arr, 0]
        return arr_slot

    def _run_batch(self, batch):
        if chaos.plan is not None:
            fault = chaos.plan.fire("serve.stall")
            if fault is not None:
                # a stalled device/runtime: latency climbs, the SLO
                # watch must notice (tests/test_serve.py)
                time.sleep(fault.param if fault.param else 0.05)
        batch = [req for req in batch if not req.cancelled]
        if not batch:
            return
        n = sum(req.rows for req in batch)
        rung = self.engine.rung_for(n, cap=self._rung_cap)
        if n > rung:  # capped ladder (post-OOM degrade): chunk by rows
            self._run_chunked(batch, rung)
            return
        start = time.perf_counter()
        if len(batch) == 1 and batch[0].block and \
                batch[0].rows == rung:
            # zero-copy hot path: a contiguous block filling the rung
            # exactly skips the staging fill — Device.put gets the
            # caller's buffer (and on XLA:CPU makes the one hazard-safe
            # XLA-owned copy; see CPUDevice.put / submit_block)
            t_h2d = start  # no staging fill: the put IS the H2D edge
            x_dev = self.engine.device.put(batch[0].sample)
        else:
            arr, slot = self._staging(rung)
            arr.stage_begin(slot)
            self._stage[rung][1] = slot ^ 1
            mem = arr.mem
            off = 0
            for req in batch:
                if req.block:
                    mem[off:off + req.rows] = req.sample
                else:
                    mem[off] = req.sample
                off += req.rows
            if n < rung:
                # deterministic padding (bit-equality contract)
                mem[n:] = 0
                self._m_padded.inc(rung - n)
            t_h2d = time.perf_counter()
            x_dev = arr.stage_put(self.engine.device)
        t_dev = time.perf_counter()
        try:
            if chaos.plan is not None:
                fault = chaos.plan.fire("serve.device.stall")
                if fault is not None:
                    # a slow accelerator (thermal throttle, preempted
                    # chip): the stall lands INSIDE the device segment
                    # so request timelines attribute it correctly
                    time.sleep(fault.param if fault.param else 0.05)
                fault = chaos.plan.fire("serve.oom")
                if fault is not None:
                    raise MemoryError(
                        "RESOURCE_EXHAUSTED: chaos serve.oom (rung %d)"
                        % rung)
            out = self.engine.run(x_dev, rung)
            t_d2h = time.perf_counter()
            # the ONE host sync of the whole batch (the old RESTfulAPI
            # synced per request)
            host = numpy.asarray(out)
        except Exception as exc:
            self._degrade_or_fail(batch, rung, exc)
            return
        done = time.perf_counter()
        self._m_batches.inc()
        # served accounting EXCLUDES shadow (canary-mirror) rows: a
        # mirrored request must never double-count in capacity totals
        # or skew the SLO latency window (docs/serving.md)
        served = sum(req.rows for req in batch if not req.shadow)
        if served:
            self._m_requests.inc(served)
        self._m_batch.observe(n)
        stamps = reqtrace.enabled
        if stamps:
            # per-batch segment histograms (serve_snapshot "segments"
            # block); queue is per-request, observed in _note_request
            self._h_seg["assemble"].observe(t_h2d - start)
            self._h_seg["h2d"].observe(t_dev - t_h2d)
            self._h_seg["device"].observe(t_d2h - t_dev)
            self._h_seg["d2h"].observe(done - t_d2h)
        off = 0
        for req in batch:
            # hand out VIEWS of the one per-batch host block: the
            # per-request row copy (and its per-element boxing further
            # down the JSON front) is paid zero times — `host` is a
            # fresh buffer each batch, so nothing ever overwrites a
            # view a waiter still holds
            if req.block:
                req.result = host[off:off + req.rows]
            else:
                req.result = host[off]
            off += req.rows
            req.latency = done - req.enqueued
            if stamps:
                # marks must land BEFORE done.set(): a transport
                # waiter echoes them over the wire at wake-up
                self._note_request(req, start, t_h2d, t_dev, t_d2h,
                                   done, rung)
            if not req.shadow:
                self._m_latency.observe(req.latency)
                # per-class accounting (docs/serving.md "Multi-tenant
                # QoS") — shadow/mirror rows stay excluded here too
                qos.note_request(req.slo_class, req.rows)
                qos.note_latency(req.slo_class, req.latency)
            req.done.set()
        if _tracer.active:
            args = {"n": n, "rung": rung}
            if self.replica is not None:
                args["replica"] = self.replica
            _tracer.complete("serve.batch", start, done - start,
                             cat="serve", args=args)
        self._batches_since_check += 1
        if self._batches_since_check >= self.slo_check_every:
            self._batches_since_check = 0
            self._check_slo()

    def _note_request(self, req, start, t_h2d, t_dev, t_d2h, done,
                      rung):
        """Stamp one completed request's segment timeline (observe/
        requests.py taxonomy), feed the tail-exemplar ring, and emit
        request-track spans when the request is sampled."""
        queue_wait = start - req.enqueued
        marks = [("queue", req.enqueued, queue_wait),
                 ("assemble", start, t_h2d - start),
                 ("h2d", t_h2d, t_dev - t_h2d),
                 ("device", t_dev, t_d2h - t_dev),
                 ("d2h", t_d2h, done - t_d2h)]
        if req.marks:
            # a front (HTTP admit, transport wire_rx) stamped marks
            # before the queue segment began: keep them at the head
            marks = list(req.marks) + marks
        req.marks = marks
        if req.child:
            return  # the sliced parent reports for the whole request
        self._h_seg["queue"].observe(queue_wait)
        self._emit_request(req, done, rung=rung)

    def _emit_request(self, req, done, rung=None):
        reqtrace.exemplars.note(
            req.trace, req.latency, marks=req.marks or (),
            t0=req.enqueued, slo_class=req.slo_class,
            budget_s=qos.slo_budget_s(req.slo_class), kind="host",
            shadow=req.shadow)
        if req.trace and _tracer.active and reqtrace.sampled(req.trace):
            args = {"slo_class": req.slo_class, "tier": "host",
                    "rows": req.rows}
            if rung is not None:
                args["rung"] = rung
            if self.host_tag:
                args["host"] = self.host_tag
            if self.replica is not None:
                args["replica"] = self.replica
            if req.shadow:
                args["shadow"] = True
            reqtrace.emit_spans(_tracer, req.trace, req.enqueued,
                                done, req.marks or (), args=args)

    def _run_chunked(self, batch, rung):
        """Replay a too-large batch within a capped rung: requests are
        regrouped by rows; a block wider than the cap itself is sliced
        into view sub-requests (still contiguous — the zero-copy
        dispatch applies to full slices) and its result reassembled."""
        chunk, rows = [], 0
        for req in batch:
            if req.rows > rung:
                if chunk:
                    self._run_batch(chunk)
                    chunk, rows = [], 0
                self._run_block_sliced(req, rung)
                continue
            if rows + req.rows > rung:
                self._run_batch(chunk)
                chunk, rows = [], 0
            chunk.append(req)
            rows += req.rows
        if chunk:
            self._run_batch(chunk)

    def _run_block_sliced(self, req, cap):
        children = []
        for i in range(0, req.rows, cap):
            child = _Request(req.sample[i:i + cap], block=True,
                             shadow=req.shadow, slo_class=req.slo_class,
                             trace=req.trace)
            child.enqueued = req.enqueued
            child.child = True
            children.append(child)
        for child in children:
            self._run_batch([child])
        errors = [c.error for c in children if c.error is not None]
        if errors:
            req.error = errors[0]
        else:
            req.result = numpy.concatenate(
                [c.result for c in children])
        done = time.perf_counter()
        req.latency = done - req.enqueued
        if reqtrace.enabled and not errors:
            # the parent's timeline is the chunk sequence: keep only
            # the first chunk's queue mark (later "queues" would
            # overlap the earlier chunks' spans on the request track)
            marks = []
            for index, child in enumerate(children):
                for mark in (child.marks or ()):
                    if index and mark[0] == "queue":
                        continue
                    marks.append(mark)
            req.marks = marks
            self._emit_request(req, done)
        req.done.set()

    def _degrade_or_fail(self, batch, rung, exc):
        self._m_errors.inc()
        if _oom_shaped(exc) and rung > self.engine.ladder[0]:
            # permanent cap below the failing rung, replay in chunks:
            # slower beats dead, and the cap note reaches the logs +
            # health block (serve.rung_cap gauge)
            smaller = [r for r in self.engine.ladder if r < rung]
            self._rung_cap = smaller[-1]
            self._g_rung_cap.set(self._rung_cap)
            self.warning(
                "engine OOM at rung %d (%s); capping ladder at %d and "
                "replaying", rung, exc, self._rung_cap)
            if _tracer.active:
                _tracer.instant("serve.degrade", cat="serve",
                                rung=rung, cap=self._rung_cap)
            self._run_batch(batch)
            return
        self.error("engine failure at rung %d: %s", rung, exc)
        for req in batch:
            req.error = exc
            req.done.set()

    def _check_slo(self):
        if self.slo_p50_ms is None and self.slo_p99_ms is None:
            return
        window = self._m_latency.window_values()
        if not window:
            return
        ps = percentiles(window, ps=(50, 99))
        p50_ms = ps["p50"] * 1e3
        p99_ms = ps["p99"] * 1e3
        breaches = []
        if self.slo_p50_ms is not None and p50_ms > self.slo_p50_ms:
            breaches.append(("p50", p50_ms, self.slo_p50_ms))
        if self.slo_p99_ms is not None and p99_ms > self.slo_p99_ms:
            breaches.append(("p99", p99_ms, self.slo_p99_ms))
        for which, measured, budget in breaches:
            self._m_slo.inc()
            # instant -> trace AND the always-on flight ring, so a
            # post-mortem dump carries the breach next to its batches
            _tracer.instant(
                "serve.slo_violation", cat="serve", slo=which,
                measured_ms=round(measured, 3),
                budget_ms=round(budget, 3))
        if breaches and not self._slo_breached:
            # log on the ENTER edge only: the counter/instants carry
            # the per-check record, a sustained breach must not flood
            # the log at batch rate
            self.warning("SLO violation began: %s", "; ".join(
                "%s %.2fms > %.2fms budget" % b for b in breaches))
            if reqtrace.enabled:
                # the flight dump for this violation carries the tail
                # exemplars, so the breach always ships the offending
                # requests' full segment timelines (never raises)
                reqtrace.exemplars.dump("serve.slo_violation")
        elif self._slo_breached and not breaches:
            self.info("SLO recovered (window p50 %.2fms p99 %.2fms)",
                      p50_ms, p99_ms)
        self._slo_breached = bool(breaches)


#: serve health keys surfaced to web_status / heartbeats
def serve_snapshot(reg=None):
    """The serving health block as a flat plain-data dict: queue depth,
    SLO violations, shed/error counts, latency percentiles (ms) and
    mean batch size.  Empty dict when nothing ever served — dashboards
    show the block only on serving processes.

    On a multi-replica server (``serve.replicas`` gauge set by the
    ReplicaPool) the block also carries the replica count and the
    per-replica queue depths, and ``queue_depth`` becomes their sum —
    counters and histograms are process-shared, so the totals and
    percentiles already aggregate across replicas by construction."""
    reg = reg if reg is not None else _registry
    out = {}
    for name, short in (("serve.queue_depth", "queue_depth"),
                        ("serve.slo_violations", "slo_violations"),
                        ("serve.requests", "requests"),
                        ("serve.shed", "shed"),
                        ("serve.errors", "errors"),
                        ("serve.reloads", "reloads"),
                        ("serve.rung_cap", "rung_cap"),
                        # int8 quantized engine flag + calibration
                        # clip health (docs/serving.md "Quantized
                        # ladder")
                        ("serve.quantized", "quantized"),
                        ("serve.quant.clip_fraction",
                         "quant_clip_fraction"),
                        # freshness loop (docs/serving.md): the serve
                        # column shows cutover traffic next to load
                        ("serve.freshness.published",
                         "freshness_published"),
                        ("serve.freshness.candidates",
                         "freshness_candidates"),
                        ("serve.freshness.promotions", "promotions"),
                        ("serve.freshness.rollbacks", "rollbacks"),
                        ("serve.freshness.poisoned_rejected",
                         "poisoned_rejected"),
                        # multi-host tier (docs/serving.md "Multi-host
                        # tier"): the serve column shows fleet
                        # membership + hedging next to load
                        ("serve.fleet.hosts_live", "hosts_live"),
                        ("serve.fleet.membership_epoch",
                         "fleet_membership_epoch"),
                        ("serve.fleet.requeues", "fleet_requeues"),
                        ("serve.hedge.fired", "hedges_fired"),
                        ("serve.hedge.wins", "hedge_wins"),
                        ("serve.hedge.duplicates_dropped",
                         "hedge_duplicates_dropped"),
                        # multi-tenant QoS (docs/serving.md
                        # "Multi-tenant QoS"): hedge suppressions and
                        # fleet-canary verdicts next to load; the
                        # per-class detail is the "tenants" block below
                        ("serve.hedge.budget_exhausted",
                         "hedge_budget_exhausted"),
                        ("serve.fleet.canary.mirrors",
                         "fleet_canary_mirrors"),
                        ("serve.fleet.canary.promotions",
                         "fleet_canary_promotions"),
                        ("serve.fleet.canary.rollbacks",
                         "fleet_canary_rollbacks"),
                        # request tracing (docs/observability.md
                        # "Request tracing"): sampled-span and tail-
                        # exemplar volume; the per-segment breakdown
                        # is the "segments" block below
                        ("serve.reqtrace.sampled", "reqtrace_sampled"),
                        ("serve.reqtrace.exemplars",
                         "reqtrace_exemplars"),
                        # fleet telemetry plane (docs/observability.md
                        # "Fleet telemetry"): alert firings + what is
                        # burning RIGHT NOW next to load; the alert
                        # history ring is /healthz's "alerts" block
                        ("alerts.fired", "alerts_fired"),
                        ("alerts.active", "alerts_active"),
                        ("telemetry.buckets", "telemetry_buckets"),
                        ("telemetry.chunks_shipped",
                         "telemetry_chunks_shipped")):
        metric = reg.peek(name)
        if metric is not None and metric.value is not None:
            out[short] = metric.value
    replicas = reg.peek("serve.replicas")
    if replicas is not None and replicas.value:
        out["replicas"] = replicas.value
        depths = []
        for i in range(int(replicas.value)):
            gauge = reg.peek("serve.replica.%d.queue_depth" % i)
            depths.append(
                gauge.value if gauge is not None and
                gauge.value is not None else 0)
        out["replica_queue_depths"] = depths
        out["queue_depth"] = sum(depths)
    hist = reg.peek("serve.latency_s")
    if hist is not None and hist.count:
        snap = hist.snapshot()
        for p in ("p50", "p95", "p99"):
            if snap.get(p) is not None:
                out["%s_ms" % p] = round(snap[p] * 1e3, 3)
    batch = reg.peek("serve.batch_size")
    if batch is not None and batch.count:
        out["batch_mean"] = round(batch.snapshot()["mean"], 2)
    # per-segment latency breakdown (observe/requests.py taxonomy):
    # WHERE the time goes, next to the end-to-end percentiles above —
    # populated while request tracing is enabled
    segments = {}
    for name in reqtrace.SEGMENTS:
        hist = reg.peek("serve.segment.%s_s" % name)
        if hist is not None and hist.count:
            snap = hist.snapshot()
            segments[name] = {
                "count": snap["count"],
                "p50_ms": round((snap.get("p50") or 0.0) * 1e3, 3),
                "p99_ms": round((snap.get("p99") or 0.0) * 1e3, 3),
            }
    if segments:
        out["segments"] = segments
    tenants = qos.tenant_snapshot(reg)
    if tenants:
        out["tenants"] = tenants
    return out
