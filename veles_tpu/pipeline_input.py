"""Asynchronous double-buffered input pipeline for the training hot path.

The fused training loop (veles_tpu/models/fused.py) collapsed compute to
one XLA dispatch per minibatch, but each step still paid host
``fill_minibatch`` -> host->device transfer -> dispatch strictly in
sequence.  This module overlaps the three stages: while step *k* executes
on device, a single worker thread (a dedicated ``thread_pool.ThreadPool``)
serves minibatch *k+1* into a ping-pong host staging buffer
(``memory.Array.stage_begin/stage_put``) and immediately starts its async
host->device transfer, so the steady-state step time approaches
``max(fill, transfer, compute)`` instead of their sum — the TPU paper's
feed-the-MXU lesson applied to the input path.

Correctness model (full rules in docs/pipeline_input.md):

- the worker runs the loader's ORDINARY serve path (``serve_next_minibatch``
  + ``_on_successful_serve``), so shuffling, class iteration, short-tail
  padding and epoch accounting are bit-identical to the synchronous path;
- the public serving fields downstream units gate on (minibatch
  class/size/offset, ``epoch_number``, the four end-of-class Bools) are
  routed through a thread-keyed ``loader.ServeShadow`` while the worker
  serves ahead; each :class:`PrefetchItem` carries the shadow snapshot,
  which :meth:`Prefetcher.step` applies on the graph thread when the
  minibatch is consumed — downstream units always see the flags of the
  batch they are processing, never the one being prefetched;
- consumers read the minibatch through the item's device arrays (an
  async ``device_put`` of the staged host fill, or the adopted gather
  result on device-resident loaders), never through the Arrays' host
  buffers, which belong to the worker while it fills ahead.

Shutdown: ``Workflow.stop()`` reaches :meth:`shutdown` via
``Loader.stop``; a normally-finished run shuts down through the
``on_workflow_finish`` unit hook.  Both join the worker thread, so no
non-daemon threads outlive the run.  Every served-but-unconsumed
minibatch keeps its serve record in ``pending_minibatches_`` until it
is consumed; shutdown (and the standard pickling path, for mid-run
snapshots) requeues those records through ``failed_minibatches``, so
serving ahead never drops a minibatch — the same recovery path as a
dropped master-slave job.
"""

import contextlib
import queue
import threading

from veles_tpu import chaos
from veles_tpu.loader.base import ServeShadow
from veles_tpu.logger import Logger
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer

__all__ = ["Prefetcher", "PrefetchItem"]


class PrefetchItem(object):
    """One served minibatch: device arrays + the serve-time snapshot of
    the loader's public fields."""

    __slots__ = ("serial", "data", "labels", "targets", "values")

    def __init__(self, serial):
        self.serial = serial
        self.data = None
        self.labels = None
        self.targets = None
        self.values = None


class Prefetcher(Logger):
    """Serves a Loader's minibatches ``depth`` steps ahead on a worker
    thread, with ping-pong host staging and async H2D transfers.

    ``attach()`` routes ``loader.run()`` through :meth:`step`; the
    worker pool starts lazily on the first step and is recreated after
    ``shutdown()``, so one Prefetcher spans any number of runs.
    """

    def __init__(self, loader, device, depth=1, **kwargs):
        super(Prefetcher, self).__init__(**kwargs)
        self.loader = loader
        self.device = device
        self.depth = max(1, int(depth))
        self.nslots = self.depth + 1
        self.current = None
        self._pool = None
        self._results = queue.Queue()
        self._inflight = 0
        self._serial = 0
        self._shutdown = False
        # held around every worker serve; quiescent() takes it so a
        # mid-run pickle (snapshotter) never observes a half-applied
        # serve mutating pending_minibatches_/failed_minibatches
        self._serve_mutex = threading.Lock()
        # telemetry (docs/observability.md): per-stage histograms feed
        # the heartbeat/bench percentiles; resolved once, not per serve
        self._m_wait = _registry.histogram("pipeline.wait_s")
        self._m_fill = _registry.histogram("pipeline.fill_s")
        self._m_h2d = _registry.histogram("pipeline.h2d_s")
        _registry.gauge("pipeline.depth").set(self.depth)

    def _stage_scope(self, stage, hist, **args):
        """One measurement of one stage for its histogram, the
        loader's stage timer, the trace and the flight ring."""
        return _tracer.scope(
            "pipeline." + stage, cat="pipeline", hist=hist,
            timers=(self.loader.timers, "pipeline_" + stage),
            args=args or None)

    # -- lifecycle ---------------------------------------------------------

    def attach(self):
        self.loader._pipeline_ = self
        return self

    def detach(self):
        self.shutdown()
        if self.loader._pipeline_ is self:
            self.loader._pipeline_ = None

    def _start(self):
        from veles_tpu.thread_pool import ThreadPool
        self._shutdown = False
        self._inflight = 0
        self._results = queue.Queue()
        self.current = None
        # staging slots are (re-)initialized lazily per serve in
        # _serve_one_locked, so a wholesale .mem swap is always healed
        self._pool = ThreadPool(minthreads=1, maxthreads=1,
                                name="prefetch")

    def shutdown(self):
        """Stop serving ahead and JOIN the worker thread; idempotent.
        Never-consumed serves are requeued through failed_minibatches so
        no minibatch is silently dropped (same recovery path as a
        dropped master-slave job)."""
        self._shutdown = True
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        dropped = 0
        while True:  # drop never-consumed items...
            try:
                self._results.get_nowait()
                dropped += 1
            except queue.Empty:
                break
        loader = self.loader
        pending = loader.pending_minibatches_.pop(None, None)
        if pending:
            # ...but requeue their serve records (one per dropped item;
            # the worker joined, so no serve is concurrently appending).
            # Reversed because serve_next_minibatch pops failed jobs
            # LIFO: replay must preserve the original serve order
            loader.failed_minibatches.extend(reversed(pending))
            if dropped:
                self.debug("requeued %d never-consumed prefetched "
                           "minibatch(es)", len(pending))
        self._inflight = 0
        self.current = None
        loader._serve_shadow_ = None

    @contextlib.contextmanager
    def quiescent(self):
        """No serve runs while held (serves between jobs are already
        atomic w.r.t. this lock); used by ``Loader.__getstate__``."""
        with self._serve_mutex:
            yield

    def _staged_arrays(self):
        loader = self.loader
        arrays = [loader.minibatch_data, loader.minibatch_indices,
                  loader.minibatch_labels,
                  getattr(loader, "minibatch_targets", None)]
        return [a for a in arrays if a is not None and bool(a)]

    # -- graph-thread side -------------------------------------------------

    def step(self):
        """Pop the oldest served minibatch, apply its snapshot to the
        loader's public fields, and keep the worker ``depth`` serves
        ahead.  Called in place of the synchronous ``Loader.run``."""
        if self._pool is None:
            self._start()
        pool = self._pool
        if pool is not None and pool.failure is not None:
            # fail FAST on a worker serve failure: the pool keeps
            # processing queued serves, so waiting for starvation
            # (the _take path) could let a bad serve's neighbors feed
            # the graph for many more steps before anyone notices
            failure = pool.failure
            self.shutdown()
            raise failure[1].with_traceback(failure[2])
        while self._inflight < self.depth + 1 and not self._shutdown:
            self._submit()
        item = self._take()
        if item is None:  # shut down mid-wait (Workflow.stop)
            return
        self._inflight -= 1
        self._apply(item)
        self.current = item

    def _submit(self):
        pool = self._pool
        if pool is None:  # concurrent shutdown() won the race
            return
        slot = self._serial % self.nslots
        serial = self._serial
        self._serial += 1
        self._inflight += 1
        pool.callInThread(self._serve_one, serial, slot)

    def _take(self):
        """The oldest served minibatch, waited for; None when shut down
        mid-wait."""
        with self._stage_scope("wait", self._m_wait):
            while True:
                try:
                    return self._results.get(timeout=0.2)
                except queue.Empty:
                    pool = self._pool
                    if self._shutdown or pool is None:
                        return None
                    failure = pool.failure
                    if failure is not None:
                        self.shutdown()
                        raise failure[1].with_traceback(failure[2])

    def _apply(self, item):
        """Write the item's serve-time snapshot into the loader's REAL
        public fields (backing attributes directly: the property
        setters would re-derive flags from the worker-advanced global
        offset)."""
        loader = self.loader
        values = item.values
        with self._serve_mutex:
            # the oldest pending record belongs to this (FIFO) item:
            # consuming it retires its requeue obligation
            pending = loader.pending_minibatches_.get(None)
            if pending:
                pending.pop(0)
        loader._minibatch_class = values["minibatch_class"]
        loader._minibatch_size_ = values["minibatch_size"]
        loader._minibatch_offset_ = values["minibatch_offset"]
        for name in ServeShadow.FLAGS:
            flag = getattr(loader, name)
            flag <<= values[name]
        # count samples at CONSUME time (graph thread, real fields):
        # updates samples_served and epoch_number exactly like the
        # synchronous path's post-serve accounting
        loader._on_successful_serve()

    # -- worker-thread side ------------------------------------------------

    def _serve_one(self, serial, slot):
        with self._serve_mutex:
            self._serve_one_locked(serial, slot)

    def _serve_one_locked(self, serial, slot):
        if chaos.plan is not None:
            fault = chaos.plan.fire("pipeline.serve")
            if fault is not None and fault.action == "exc":
                # a worker-thread serve failure must surface on the
                # graph thread (Prefetcher._take's pool-failure path),
                # not hang the run or leak the worker
                raise RuntimeError(
                    "chaos: injected serve failure (serial %d)" % serial)
        loader = self.loader
        shadow = loader._serve_shadow_
        if shadow is None or shadow.thread is not threading.current_thread():
            # first serve of this pool: seed the worker's view from the
            # loader's live (applied) state
            shadow = ServeShadow(loader, threading.current_thread())
            loader._serve_shadow_ = shadow
        # worker-thread spans land on their own track (Perfetto's, and
        # the profiler's /host:CPU line of this thread), so the
        # fill/H2D overlap with the graph thread's step spans is
        # visible directly
        with self._stage_scope("fill", self._m_fill, serial=serial):
            for arr in self._staged_arrays():
                if not arr.staged:
                    # a wholesale .mem assignment dropped the slots
                    # (shape may have changed); re-stage around the new
                    # buffer so the in-flight-DMA protection never
                    # silently lapses
                    arr.stage_init(self.nslots)
                arr.stage_begin(slot)
            # NOTE two deviations from the synchronous Loader.run, both
            # so that serving AHEAD never miscounts: the previous
            # serve's pending record is NOT popped (every
            # served-but-unconsumed minibatch keeps its requeue record
            # until _apply retires it or shutdown moves it to
            # failed_minibatches), and _on_successful_serve runs at
            # APPLY time on the graph thread — like the master-slave
            # contract, samples are counted when consumed, so a
            # requeued serve is never counted twice
            loader.serve_next_minibatch(None)

        item = PrefetchItem(serial)
        item.values = dict(shadow.values)
        with self._stage_scope("h2d", self._m_h2d, serial=serial):
            item.data = loader.minibatch_data.staged_capture(self.device)
            if loader.minibatch_labels:
                item.labels = loader.minibatch_labels.staged_capture(
                    self.device)
            targets = getattr(loader, "minibatch_targets", None)
            if targets is not None and bool(targets):
                item.targets = targets.staged_capture(self.device)
        self._results.put(item)
