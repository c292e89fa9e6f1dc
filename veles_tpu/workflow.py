"""Workflow — the container unit holding and executing the unit graph.

TPU-native counterpart of reference veles/workflow.py:87.  Preserved
capabilities: dependency-ordered initialization with partial re-queue,
worklist-driven run loop delimited by StartPoint/EndPoint, aggregation of
the per-unit master-slave data contract in dependency order, per-method
run-time statistics, Graphviz graph generation, run-results gathering,
source checksum, and package export for the native inference runtime.

TPU-first difference: the run loop is a flat worklist (no recursion, no
reactor); the numeric hot path is expected to be fused by
veles_tpu.compiler into jitted step functions so that a whole training
iteration is one XLA dispatch rather than a chain of kernel launches.
"""

import hashlib
import inspect
import json
import sys
import threading
from collections import deque

from veles_tpu.mutable import Bool
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu.plumbing import EndPoint, StartPoint
from veles_tpu.units import Unit

__all__ = ["Workflow", "NoMoreJobs", "AcceleratedWorkflow",
           "restore_workflow"]


class NoMoreJobs(Exception):
    """Raised by a unit when the job stream is exhausted
    (reference: workflow.py:82)."""


def restore_workflow(path, launcher=None):
    """Restore a workflow from a (manifest-verified) snapshot and
    re-home it: attach it to ``launcher`` and mark it restored so
    initialize() applies the post-restore gate fixups.  The single
    bootstrap path behind ``-w`` / ``--resume`` and programmatic
    resumes."""
    from veles_tpu.snapshotter import SnapshotterBase
    workflow = SnapshotterBase.import_file(path)
    if not isinstance(workflow, Workflow):
        from veles_tpu.snapshotter import SnapshotError
        raise SnapshotError(
            "snapshot %s holds a %s, not a Workflow" %
            (path, type(workflow).__name__))
    if launcher is not None:
        workflow.workflow = launcher
    workflow.restored_from_snapshot_ = True
    return workflow


class Workflow(Unit):
    """Container unit; nests inside a Launcher or a parent Workflow."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        self._units = []
        super(Workflow, self).__init__(workflow, **kwargs)
        self.start_point = StartPoint(self)
        self.end_point = EndPoint(self)
        self.negotiates_on_connect = True
        self._method_timers = {}
        self.result_file = kwargs.get("result_file")

    def init_unpickled(self):
        super(Workflow, self).init_unpickled()
        self._queue_lock_ = threading.Lock()
        self._worklist_ = deque()
        self._finished_ = threading.Event()
        self._running_ = False
        self._run_time_ = 0.0
        self._stop_requested_ = False
        self.restored_from_snapshot_ = False
        # stats as of the CURRENT run's start, so print_stats reports
        # per-run deltas instead of misattributing earlier runs' time
        self._stats_baseline_ = None
        # the graph thread's time BETWEEN units (worklist, gates,
        # locks): one open "workflow.hop" scope from a unit's end to
        # the next unit's start, inside this workflow's run() only
        self._hop_ = None
        self._m_hop_ = _registry.histogram("workflow.hop_s")

    # -- container behavior ------------------------------------------------

    def add_ref(self, unit):
        if unit not in self._units:
            self._units.append(unit)

    def del_ref(self, unit):
        if unit in self._units:
            self._units.remove(unit)

    @property
    def units(self):
        return list(self._units)

    @property
    def units_in_dependency_order(self):
        order = [u for u in self.start_point.dependent_units]
        rest = [u for u in self._units if u not in order]
        return order + rest

    def __getitem__(self, name):
        for unit in self._units:
            if unit.name == name:
                return unit
        raise KeyError(name)

    @property
    def workflow_mode(self):
        parent = self.workflow
        if parent is None:
            return "standalone"
        return getattr(parent, "workflow_mode", "standalone")

    @property
    def launcher(self):
        parent = self.workflow
        if isinstance(parent, Workflow):
            return parent.launcher
        return parent

    @property
    def is_running(self):
        return self._running_

    # -- initialization ----------------------------------------------------

    def initialize(self, device=None, **kwargs):
        """Initialize every unit in dependency order; units raising
        AttributeError (unsatisfied demands) get re-queued until no
        progress is made (reference: workflow.py:303,331-336)."""
        self.device = device
        if self.restored_from_snapshot_:
            # units must know they carry pickled state BEFORE their
            # initialize runs — e.g. a restored loader must NOT
            # re-shuffle (that would tear shuffled_indices away from
            # the pickled PRNG stream and break exact resume)
            for unit in self._units:
                if unit is not self:
                    unit.restored_from_snapshot = True
        queue = deque(self.units_in_dependency_order)
        deferred_errors = {}
        while queue:
            progressed = False
            requeue = deque()
            for unit in queue:
                if unit is self:
                    continue
                try:
                    unit.initialize(device=device, **kwargs)
                    progressed = True
                except AttributeError as exc:
                    requeue.append(unit)
                    deferred_errors[unit] = exc
            if not progressed and requeue:
                lines = "; ".join(
                    "%s: %s" % (u.name, deferred_errors.get(u))
                    for u in requeue)
                raise RuntimeError(
                    "workflow initialization deadlock - unsatisfied "
                    "demands: %s" % lines)
            queue = requeue
        if self.restored_from_snapshot_:
            # Units that don't remember gate state get their gates reset
            # (reference: workflow.py:338-340).
            for unit in self._units:
                if not getattr(unit, "remembers_gates", True):
                    unit.gate_block = Bool(False)
        self._is_initialized_ = True
        return True

    # -- scheduling / run loop ---------------------------------------------

    def schedule(self, dst, src):
        """Queue ``dst`` for a gate check triggered by ``src``."""
        with self._queue_lock_:
            self._worklist_.append((dst, src))

    @property
    def finished(self):
        return self._finished_.is_set()

    @property
    def stop_requested(self):
        return self._stop_requested_

    def run(self):
        """Execute the graph from start_point until end_point fires."""
        self._stopped <<= False
        self._stop_requested_ = False
        self._finished_.clear()
        self._running_ = True
        with self._queue_lock_:
            # Drop residue from a previous (stopped) run: stale worklist
            # entries and half-fired AND-gate flags would double-execute
            # units on the next run (e.g. per slave job via do_job).
            self._worklist_.clear()
        for unit in self._units:
            if unit is self:
                continue
            # a previous stop() set every unit's own stop flag; a new
            # run must clear them or the whole graph is silently
            # suppressed and the drained queue fakes a finished run
            # (non-restartable units keep it: their stop() tore down
            # resources a rerun cannot revive)
            if getattr(unit, "restartable", True):
                unit._stopped <<= False
            with unit._gate_lock_:
                for key in unit._links_from:
                    unit._links_from[key] = False
        # unit/method timers accumulate across runs; snapshot them so
        # print_stats can report THIS run (timers hold all keys a unit
        # accumulates, e.g. the input pipeline's per-stage times)
        self._stats_baseline_ = {
            "run_time": self._run_time_,
            "methods": dict(self._method_timers),
            "units": {id(u): (dict(u.timers), u.run_calls)
                      for u in self._units if u is not self},
        }
        self.event("run", "begin")
        # perf_counter (the scope's clock), not time.time: wall-clock
        # timers go backwards under NTP adjustment and disagree with
        # the deltas every other timer (units, pipeline stages) records
        span = _tracer.scope("%s.run" % self.name, cat="workflow")
        span.__enter__()
        self.begin_hop()
        try:
            self.start_point.run_dependent()
            while not self._finished_.is_set():
                with self._queue_lock_:
                    if not self._worklist_:
                        break
                    dst, src = self._worklist_.popleft()
                dst._check_gate_and_run(src)
            if not self._finished_.is_set():
                # Queue drained without reaching end_point: treat as
                # completion for open-ended graphs.
                self.on_workflow_finished()
        finally:
            self._running_ = False
            self.end_hop()
            span.__exit__(None, None, None)
            self._run_time_ += span.elapsed
            self.event("run", "end")
        return True

    def begin_hop(self):
        """A unit ended (or run() began): the graph thread is in the
        scheduler until :meth:`end_hop`.  Only inside run()."""
        if self._running_:
            self._hop_ = _tracer.scope(
                "workflow.hop", cat="sched", hist=self._m_hop_)
            self._hop_.__enter__()

    def end_hop(self):
        hop, self._hop_ = self._hop_, None
        if hop is not None:
            hop.__exit__(None, None, None)

    def on_workflow_finished(self):
        # per-unit end-of-run hook (e.g. the input pipeline joins its
        # prefetch worker so no thread outlives the run)
        for unit in self._units:
            if unit is self:
                continue
            hook = getattr(unit, "on_workflow_finish", None)
            if hook is not None:
                try:
                    hook()
                except Exception:
                    self.exception("on_workflow_finish failed for %s",
                                   unit)
        self._finished_.set()
        self._stopped <<= True
        launcher = self.launcher
        if launcher is not None and self.workflow is launcher:
            on_finished = getattr(launcher, "on_workflow_finished", None)
            if on_finished is not None:
                on_finished()

    def stop(self):
        self._stop_requested_ = True
        self._stopped <<= True
        self._finished_.set()
        for unit in self._units:
            if unit is not self:
                unit.stop()

    # -- master-slave contract (job level; see parallel/ for on-pod SPMD) --

    def _timed_method(self, name, fn, *args):
        with _tracer.scope(name, cat="distributed",
                           timers=(self._method_timers, name)):
            return fn(*args)

    def generate_data_for_master(self):
        return [self._timed_method(
            "generate_data_for_master", u.generate_data_for_master)
            for u in self._distributed_units()]

    def generate_data_for_slave(self, slave=None):
        data = []
        for unit in self._distributed_units():
            part = self._timed_method(
                "generate_data_for_slave", unit.generate_data_for_slave,
                slave)
            if part is False:
                return False  # not ready: sync point
            data.append(part)
        return data

    def apply_data_from_master(self, data):
        units = self._distributed_units()
        for unit, part in zip(units, data):
            if part is not None:
                self._timed_method(
                    "apply_data_from_master", unit.apply_data_from_master,
                    part)

    def apply_data_from_slave(self, data, slave=None):
        units = self._distributed_units()
        for unit, part in zip(units, data):
            if part is not None:
                self._timed_method(
                    "apply_data_from_slave", unit.apply_data_from_slave,
                    part, slave)
        return True

    #: how the Server validates update payloads (docs/distributed.md):
    #: "prewalk" — a standalone ``health.all_finite`` pass over the
    #: WHOLE update before any part applies (all-or-nothing; required
    #: while per-step parameter deltas ride the protocol, because a
    #: partially-applied update would break the exact-requeue
    #: guarantee); "inline" — single-traversal validate-during-apply
    #: below (the SPMD split sets this: updates are control records
    #: only, gradients ride ICI inside the compiled step).
    update_validation = "prewalk"

    def apply_update_validated(self, data, slave=None):
        """Single-traversal master update path: each unit's part is
        finiteness-validated immediately before ITS apply — one walk
        over the payload instead of the prewalk-then-apply double walk
        — raising :class:`veles_tpu.health.PoisonedUpdate` before the
        poisoned part mutates anything.

        Contract: only valid when updates carry CONTROL records
        (loader bookkeeping, decision metrics), i.e. when the SPMD
        data plane owns the gradients.  Parts applied before a later
        part's poison was found stay applied; with control-only
        payloads the server's drop + requeue recovers them exactly
        like a slave death mid-session, whereas per-step parameter
        deltas would need the all-or-nothing prewalk (see
        ``update_validation``)."""
        from veles_tpu import health
        units = self._distributed_units()
        for unit, part in zip(units, data):
            if part is None:
                continue
            if not health.all_finite(part):
                raise health.PoisonedUpdate(unit)
            self._timed_method(
                "apply_data_from_slave", unit.apply_data_from_slave,
                part, slave)
        return True

    def generate_initial_data_for_slave(self, slave=None):
        # The False "not ready" sentinel has no meaning at connect time;
        # normalise it to None so it is never applied as a payload.
        data = []
        for unit in self._distributed_units():
            if not getattr(unit, "negotiates_on_connect", False):
                continue
            part = unit.generate_data_for_slave(slave)
            data.append(None if part is False else part)
        return data

    def apply_initial_data_from_master(self, data):
        units = [u for u in self._distributed_units()
                 if getattr(u, "negotiates_on_connect", False)]
        for unit, part in zip(units, data):
            if part is not None and part is not False:
                unit.apply_data_from_master(part)

    def drop_slave(self, slave=None):
        for unit in self._distributed_units():
            unit.drop_slave(slave)

    def unserved_remainder(self):
        """Elastic resharding input (Server._reshard): how much of the
        current epoch's sample space is not yet applied.  Delegates to
        the first unit exposing the probe (the loader owns the
        class-window accounting); None = unknown."""
        for unit in self._distributed_units():
            probe = getattr(unit, "unserved_remainder", None)
            if probe is not None:
                return probe()
        return None

    def apply_reshard(self, info):
        """Slave-side reshard hook (docs/distributed.md, "Elasticity
        contract"): the master repartitioned the epoch's unserved
        remainder after a membership change.  Record the fleet view
        and forward to every unit that wants the hint (the loader
        keeps it next to its window bookkeeping).  Advisory: job
        payloads remain the authoritative work assignment."""
        self.fleet_info_ = dict(info)
        for unit in self._distributed_units():
            hook = getattr(unit, "apply_reshard", None)
            if hook is not None:
                hook(info)

    def _distributed_units(self):
        return [u for u in self.units_in_dependency_order if u is not self]

    def do_job(self, data, update, callback):
        """Slave-side job execution: apply job, merge own previous update,
        run the graph, return the new update (reference:
        workflow.py:558-574)."""
        self.apply_data_from_master(data)
        if update is not None:
            self.apply_data_from_slave(update, None)
        try:
            self.run()
        except NoMoreJobs:
            pass
        callback(self.generate_data_for_master())

    # -- introspection / reporting ----------------------------------------

    @property
    def checksum(self):
        """SHA1 of the defining source file (reference: workflow.py:851),
        used by the control plane handshake."""
        try:
            path = inspect.getsourcefile(type(self))
            with open(path, "rb") as fin:
                digest = hashlib.sha1(fin.read())
        except (TypeError, OSError):
            digest = hashlib.sha1()
        digest.update(type(self).__name__.encode())
        return digest.hexdigest()

    def generate_graph(self):
        """Return the control-flow graph as Graphviz dot text."""
        lines = ["digraph %s {" % type(self).__name__]
        index = {}
        for i, unit in enumerate(self._units):
            index[id(unit)] = "u%d" % i
            shape = "rect"
            if isinstance(unit, (StartPoint, EndPoint)):
                shape = "circle"
            lines.append('  u%d [label="%s", shape=%s];' %
                         (i, unit.name, shape))
        for unit in self._units:
            for dst in unit.links_to:
                if id(dst) in index and id(unit) in index:
                    lines.append("  %s -> %s;" %
                                 (index[id(unit)], index[id(dst)]))
        lines.append("}")
        return "\n".join(lines)

    def print_stats(self, top_number=5, out=None, cumulative=False):
        """Report where the LAST run's time went (per-run deltas
        against the snapshot taken at ``run()`` start; pass
        ``cumulative=True`` for lifetime totals)."""
        out = out or sys.stdout
        base = None if cumulative else self._stats_baseline_

        def base_unit(unit):
            if base is None:
                return {}, 0
            return base["units"].get(id(unit), ({}, 0))

        def unit_time(unit, key="run"):
            return unit.timers.get(key, 0.0) - \
                base_unit(unit)[0].get(key, 0.0)

        timed = sorted(((unit_time(u), u)
                        for u in self._units if u is not self),
                       key=lambda pair: -pair[0])
        total = sum(t for t, _ in timed) or 1e-12
        run_time = self._run_time_ - (base["run_time"] if base else 0.0)
        out.write("---- Workflow run time: %.3f s%s ----\n" % (
            run_time, "" if cumulative else " (this run)"))
        for elapsed, unit in timed[:top_number]:
            out.write("  %6.2f%%  %8.3f s  %s (%d runs)\n" % (
                100.0 * elapsed / total, elapsed, unit.name,
                unit.run_calls - base_unit(unit)[1]))
        for unit in self._units:
            # extra per-unit timer keys (e.g. the input pipeline's
            # pipeline_wait / pipeline_fill / pipeline_h2d stages)
            extra = [(k, unit_time(unit, k))
                     for k in sorted(unit.timers) if k != "run"]
            extra = [(k, v) for k, v in extra if v > 0.0]
            if extra:
                pipeline = getattr(unit, "_pipeline_", None)
                depth = ("depth %d, " % pipeline.depth
                         if pipeline is not None else "")
                out.write("  %s stage timers (%s):\n    %s\n" % (
                    unit.name, depth.rstrip(", ") or "per-run",
                    ", ".join("%s %.3f s" % (k, v)
                              for k, v in extra)))
        if self._method_timers:
            deltas = sorted(
                (name, elapsed - (base["methods"].get(name, 0.0)
                                  if base else 0.0))
                for name, elapsed in self._method_timers.items())
            deltas = [(n, e) for n, e in deltas if e > 0.0]
            if deltas:
                out.write("  distributed methods:\n")
                for name, elapsed in deltas:
                    out.write("    %8.3f s  %s\n" % (elapsed, name))

    def gather_results(self):
        """Collect metrics from every IResultProvider-like unit
        (reference: workflow.py:827-849)."""
        results = {}
        for unit in self._units:
            getter = getattr(unit, "get_metric_values", None)
            if getter is not None:
                try:
                    results.update(getter())
                except Exception:
                    self.exception("gather_results failed for %s", unit)
        return results

    def write_results(self, file=None):
        path = file or self.result_file
        if not path:
            return
        with open(path, "w") as fout:
            json.dump(self.gather_results(), fout, indent=1, default=repr,
                      sort_keys=True)

    def package_export(self, path, precision="float32"):
        """Export trained state for the native inference runtime
        (reference: workflow.py:868); see veles_tpu/package.py."""
        from veles_tpu.package import export_workflow
        return export_workflow(self, path, precision=precision)

    @property
    def computing_power(self):
        device = getattr(self, "device", None)
        return device.computing_power if device is not None else 0.0

    def __getstate__(self):
        state = super(Workflow, self).__getstate__()
        state["_workflow"] = None  # the launcher never pickles
        return state


class AcceleratedWorkflow(Workflow):
    """Workflow owning a device (reference: accelerated_units.py:827)."""

    def __init__(self, workflow, **kwargs):
        super(AcceleratedWorkflow, self).__init__(workflow, **kwargs)
        self.device = None

    def initialize(self, device=None, **kwargs):
        if device is None:
            from veles_tpu.backends import Device
            # backend=None -> VELES_BACKEND / root.common.engine
            # resolution, same as the launcher
            device = Device(backend=None)
        return super(AcceleratedWorkflow, self).initialize(
            device=device, **kwargs)
