"""Decision unit — epoch bookkeeping and the stop criterion.

Znicz-equivalent decision.DecisionGD: accumulates the evaluator's
per-minibatch metrics into per-class epoch totals, tracks the best
validation error, raises ``improved`` when a new best is reached, skips
gradient descent on non-TRAIN minibatches via the shared ``gd_skip``
Bool, and sets ``complete`` when ``fail_iterations`` epochs pass without
improvement or ``max_epochs`` is reached.

Numerics health (docs/health.md): a non-finite metric is NEVER recorded
as improved/best (``NaN < best`` is silently False, and a NaN could
otherwise *become* best when no best exists yet), and the decision
doubles as the training-health watchdog — at each train-class end it
checks the consecutive-skip counters the guarded train steps maintain
and an EMA loss-spike threshold, raising ``diverged`` and invoking the
owning workflow's ``on_divergence`` hook (snapshot rollback + LR
backoff in StandardWorkflow) when training has gone off the rails.
"""

from veles_tpu.health import (
    DivergenceError, EmaSpikeWatch, is_finite_metric)
from veles_tpu.loader.base import CLASS_NAME, TRAIN, VALID
from veles_tpu.mutable import Bool
from veles_tpu.observe.flight import flight as _flight
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu.units import Unit

__all__ = ["DecisionBase", "DecisionGD", "DecisionMSE"]


class DecisionBase(Unit):
    """Epoch metric aggregation + stop control + divergence watchdog.

    Watchdog kwargs (defaults are deliberately conservative so healthy
    noisy runs never trip):

    - ``watchdog`` (True): master switch for divergence detection.
    - ``skip_budget`` (16): consecutive guarded-step skips that count
      as divergence (sustained non-finite gradients/loss).
    - ``spike_factor`` (10.0) / ``spike_floor`` (1.0) / ``ema_beta``
      (0.5): trip when the train metric exceeds ``spike_factor *
      max(EMA, spike_floor)`` — the floor keeps near-zero converged
      metrics from turning ordinary noise into "spikes".
    """

    def __init__(self, workflow, **kwargs):
        super(DecisionBase, self).__init__(workflow, **kwargs)
        self.max_epochs = kwargs.get("max_epochs", None)
        self.fail_iterations = kwargs.get("fail_iterations", 100)
        self.complete = Bool(False)
        self.improved = Bool(False)
        self.train_improved = Bool(False)
        self.gd_skip = Bool(False)
        # divergence watchdog
        self.diverged = Bool(False)
        self.watchdog = kwargs.get("watchdog", True)
        self.skip_budget = kwargs.get("skip_budget", 16)
        self.spike_factor = kwargs.get("spike_factor", 10.0)
        self.spike_floor = kwargs.get("spike_floor", 1.0)
        self.ema_beta = kwargs.get("ema_beta", 0.5)
        #: units exposing lazy skip_count / consecutive_skips counters
        #: (the gds, or the fused trainer); wired by the workflow
        self.health_sources = []
        # the ONE EMA spike discipline (health.EmaSpikeWatch), shared
        # with the serve canary comparator (docs/serving.md)
        self._spike_watch = EmaSpikeWatch(
            spike_factor=self.spike_factor,
            spike_floor=self.spike_floor, beta=self.ema_beta,
            label="train metric")
        self._skips_seen = 0
        # linked from loader:
        self.minibatch_class = None
        self.last_minibatch = None
        self.epoch_ended = None
        self.epoch_number = None
        self.class_lengths = None
        self.demand("minibatch_class", "last_minibatch", "class_lengths",
                    "epoch_ended", "epoch_number")
        self.epoch_metrics = [None, None, None]
        self.best_metric = None
        self.best_epoch = 0
        self.best_train_metric = None

    def init_unpickled(self):
        super(DecisionBase, self).init_unpickled()
        # every place this unit forces a device scalar to the host
        # (the class-end metric, the health counters): registered here,
        # so it reads 0 where no class ended
        self._m_sync_ = _registry.histogram("decision.sync_s")

    def initialize(self, **kwargs):
        super(DecisionBase, self).initialize(**kwargs)
        self._reset_epoch_accumulators()
        return True

    def _sync(self):
        """The scope around a ``float()``/``int()`` of a lazy device
        scalar: the host waits here for every step dispatched so far."""
        return _tracer.scope("decision.sync", cat="decision",
                             hist=self._m_sync_)

    def _reset_epoch_accumulators(self):
        raise NotImplementedError

    def _accumulate_minibatch(self):
        raise NotImplementedError

    def _epoch_class_metric(self, class_index):
        """Finished class -> scalar metric (lower is better)."""
        raise NotImplementedError

    def run(self):
        self.gd_skip <<= (self.minibatch_class != TRAIN)
        self._accumulate_minibatch()
        if bool(self.last_minibatch):
            self._record_class_metric(self.minibatch_class)
            self._on_class_ended(self.minibatch_class)
        if bool(self.epoch_ended):
            self._on_epoch_ended()

    def _record_class_metric(self, cls):
        """Finished class: compute the metric and publish it to the
        telemetry registry (here and in the master's
        apply_data_from_slave path — already a plain float, the
        class-end sync happened in _epoch_class_metric).  Non-finite
        metrics stay out of the gauge: the heartbeat/status files must
        remain strict JSON, and the watchdog reports the divergence
        through its own channel."""
        metric = self._epoch_class_metric(cls)
        self.epoch_metrics[cls] = metric
        if metric is not None and is_finite_metric(metric):
            _registry.gauge("metric.%s" % CLASS_NAME[cls]).set(metric)

    @staticmethod
    def _metric_improves(metric, best):
        """True when ``metric`` is a real improvement over ``best``.
        Non-finite metrics NEVER improve: ``NaN < best`` is silently
        False, but ``best is None or NaN < best`` would record NaN as
        the first best — poisoning every later comparison (nothing
        beats NaN, so ``improved`` would never fire again)."""
        if not is_finite_metric(metric):
            return False
        return best is None or metric < best

    def _on_class_ended(self, cls):
        # improvement is judged on VALID when present, else on TRAIN
        judge = VALID if self.class_lengths[VALID] > 0 else TRAIN
        if cls == judge:
            metric = self.epoch_metrics[cls]
            if self._metric_improves(metric, self.best_metric):
                self.best_metric = metric
                self.best_epoch = self.epoch_number
                self.improved <<= True
            else:
                self.improved <<= False
        if cls == TRAIN:
            metric = self.epoch_metrics[TRAIN]
            better = self._metric_improves(metric,
                                           self.best_train_metric)
            if better:
                self.best_train_metric = metric
            self.train_improved <<= better
            self._check_divergence()

    # -- divergence watchdog (docs/health.md) -------------------------------

    def _health_counters(self):
        """Sync the health sources' lazy counters (once per finished
        train class — the same cadence as the metric sync, never per
        minibatch).  Returns (total_skips, max_consecutive_skips)."""
        total = 0
        consec = 0
        for unit in self.health_sources:
            with self._sync():
                skips = int(unit.skip_count)
                unit_consec = int(unit.consecutive_skips)
            total += skips
            consec = max(consec, unit_consec)
            hook = getattr(unit, "on_health_sync", None)
            if hook is not None:
                # ride the existing sync: e.g. the fused trainer's
                # bf16-compression -> f32 fallback reacts to fresh
                # skips here without ever adding a per-step host sync
                hook(skips=skips, consec=unit_consec)
        # publish to the telemetry registry HERE — this is the existing
        # once-per-class device sync, so dashboards/heartbeats read the
        # counters as plain ints without ever touching the device
        _registry.gauge("health.skip_count").set(total)
        _registry.gauge("health.consecutive_skips").set(consec)
        return total, consec

    def _check_divergence(self):
        if not self.watchdog or bool(self.diverged):
            return
        if self.workflow is not None and \
                self.workflow.workflow_mode == "slave":
            return  # the master owns recovery; slaves just ship metrics
        reasons = []
        total, consec = self._health_counters()
        fresh = total - self._skips_seen
        self._skips_seen = total
        if consec >= self.skip_budget:
            reasons.append(
                "%d consecutive non-finite train steps skipped "
                "(budget %d)" % (consec, self.skip_budget))
        metric = self.epoch_metrics[TRAIN]
        if metric is not None:
            if not is_finite_metric(metric):
                reasons.append("non-finite train metric %r" % (metric,))
            else:
                spike = self._spike_watch.update(metric)
                if spike is not None:
                    reasons.append(spike)
        if fresh and not reasons:
            self.warning(
                "numerics guard skipped %d non-finite train step(s) "
                "this epoch (consecutive max %d, budget %d)",
                fresh, consec, self.skip_budget)
        if reasons:
            self._trip("; ".join(reasons))

    def _trip(self, reason):
        """Divergence detected: raise the flag and hand recovery to the
        owning workflow (StandardWorkflow rolls back to the last
        verified snapshot and backs off the learning rate).  Without a
        handler this FAILS LOUDLY — converging to garbage silently is
        the one outcome the watchdog exists to prevent."""
        self.diverged <<= True
        self.error("training diverged at epoch %s: %s",
                   self.epoch_number, reason)
        # black-box dump BEFORE recovery mutates anything: the ring
        # holds the step spans and heartbeats leading into divergence
        _flight.dump(reason="divergence")
        handler = getattr(self.workflow, "on_divergence", None)
        if handler is None:
            raise DivergenceError(
                "training diverged (%s) and the workflow has no "
                "on_divergence recovery hook" % reason)
        handler(reason)

    def reset_divergence(self):
        """Post-rollback reset (called by the workflow's recovery hook
        after counters were zeroed): the watchdog starts a fresh
        observation window."""
        self.diverged <<= False
        self._spike_watch.reset()
        self._skips_seen = 0

    def get_metric_names(self):
        return {"Errors", "Best metric", "Best epoch"}

    def get_metric_values(self):
        return {
            "Errors": {CLASS_NAME[i]: self.epoch_metrics[i]
                       for i in range(3)},
            "Best metric": self.best_metric,
            "Best epoch": self.best_epoch,
        }

    def _on_epoch_ended(self):
        _registry.gauge("train.epoch").set(int(self.epoch_number))
        self.info("Epoch %d metrics: test %s, validation %s, train %s",
                  self.epoch_number,
                  self.epoch_metrics[0], self.epoch_metrics[1],
                  self.epoch_metrics[2])
        stop = False
        if self.max_epochs is not None and \
                self.epoch_number >= self.max_epochs:
            stop = True
        if self.best_metric is not None and \
                self.epoch_number - self.best_epoch > self.fail_iterations:
            stop = True
        if stop:
            self.complete <<= True
        self._reset_epoch_accumulators()


class DecisionGD(DecisionBase):
    """Classification: metric = error percentage from evaluator.n_err."""

    def __init__(self, workflow, **kwargs):
        super(DecisionGD, self).__init__(workflow, **kwargs)
        self.evaluator = None  # linked: needs .n_err per minibatch
        self.demand("evaluator")
        self.epoch_n_err = [0, 0, 0]

    def _reset_epoch_accumulators(self):
        self.epoch_n_err = [0, 0, 0]

    def _accumulate_minibatch(self):
        # evaluator.n_err may be a LAZY device scalar — lazy_add keeps
        # the accumulation an async jitted dispatch; the float() below
        # is the only sync point
        from veles_tpu.models.evaluator import lazy_add
        cls = self.minibatch_class
        self.epoch_n_err[cls] = lazy_add(self.epoch_n_err[cls],
                                         self.evaluator.n_err)

    def _epoch_class_metric(self, class_index):
        length = self.class_lengths[class_index]
        if length == 0:
            return None
        # a sample may carry many targets (a row of next tokens): the
        # rate is over targets, as the errors are counted
        length *= getattr(self.evaluator, "targets_per_sample", 1)
        # forces the device sync (once per finished class, not per
        # minibatch) and normalizes to a plain float for logs/JSON
        with self._sync():
            return float(100.0 * self.epoch_n_err[class_index] / length)

    # -- master-slave contract: slaves ship per-job error counts; the
    # master merges them and performs the class/epoch-end bookkeeping
    # using its loader's flags (exact in sync mode, VELES-style
    # approximation under async pipelining).

    def generate_data_for_slave(self, slave=None):
        return {"complete": bool(self.complete)}

    def apply_data_from_master(self, data):
        self.complete <<= data.get("complete", False)

    def generate_data_for_master(self):
        # wire payload: concretize any lazy device scalars
        delta = [int(v) for v in self.epoch_n_err]
        self._reset_epoch_accumulators()
        return {"n_err": delta}

    def __getstate__(self):
        state = super(DecisionGD, self).__getstate__()
        if "epoch_n_err" in state:
            state["epoch_n_err"] = [int(v) for v in self.epoch_n_err]
        return state

    def apply_data_from_slave(self, data, slave=None):
        if not data:
            return
        for i, n in enumerate(data.get("n_err", ())):
            self.epoch_n_err[i] += n
        if bool(self.last_minibatch):
            # same class-end path as run(): the master's telemetry
            # (metric gauges, health counters) must not go dark just
            # because the hot loop runs on the slaves
            self._record_class_metric(self.minibatch_class)
            self._on_class_ended(self.minibatch_class)
        if bool(self.epoch_ended):
            self._on_epoch_ended()
        if bool(self.complete) and self.workflow is not None:
            self.workflow.on_workflow_finished()


class DecisionMSE(DecisionBase):
    """Regression: metric = epoch RMSE from evaluator.mse_sum."""

    def __init__(self, workflow, **kwargs):
        super(DecisionMSE, self).__init__(workflow, **kwargs)
        self.evaluator = None  # linked: needs .mse_sum / .n_samples
        self.demand("evaluator")
        self.epoch_sse = [0.0, 0.0, 0.0]

    def _reset_epoch_accumulators(self):
        self.epoch_sse = [0.0, 0.0, 0.0]

    def _accumulate_minibatch(self):
        from veles_tpu.models.evaluator import lazy_add
        cls = self.minibatch_class
        self.epoch_sse[cls] = lazy_add(self.epoch_sse[cls],
                                       self.evaluator.mse_sum)

    def _epoch_class_metric(self, class_index):
        import math
        length = self.class_lengths[class_index]
        if length == 0:
            return None
        # float() is the once-per-class device sync (see DecisionGD)
        with self._sync():
            return math.sqrt(float(self.epoch_sse[class_index]) / length)

    def __getstate__(self):
        state = super(DecisionMSE, self).__getstate__()
        if "epoch_sse" in state:
            state["epoch_sse"] = [float(v) for v in self.epoch_sse]
        return state
