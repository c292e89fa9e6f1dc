"""Unit — the node of the dataflow/control-flow graph.

TPU-native counterpart of reference veles/units.py:59,108.  Preserved
semantics: control links (``link_from``), the AND gate protocol with
``gate_block`` / ``gate_skip`` / ``ignores_gate``, data links
(``link_attrs`` via LinkableAttribute), required-attribute declaration
(``demand``), timed + stop-checked ``run`` wrapping, and a registry of all
unit classes for introspection.

Scheduling difference (TPU-first): successors are scheduled through the
owning workflow's scheduler (worklist + thread pool), not by recursive
calls, so arbitrarily long training loops cannot blow the stack; and
accelerated subgraphs can be fused by veles_tpu.compiler into single XLA
computations while keeping this graph as the orchestration layer.
"""

import threading
import uuid as uuid_module

from veles_tpu.config import root
from veles_tpu.distributable import Distributable
from veles_tpu.mutable import Bool, LinkableAttribute
from veles_tpu.observe.trace import tracer as _tracer

__all__ = ["Unit", "IUnit", "UnitRegistry", "RunAfterStopError",
           "nothing"]



class RunAfterStopError(RuntimeError):
    """A unit was scheduled to run after its workflow FINISHED without
    any stop request — a broken control-flow link (reference
    units.py:823-839 raised the same on post-stop runs)."""


class UnitRegistry(type):
    """Metaclass recording every Unit subclass (reference:
    veles/unit_registry.py:51)."""

    units = set()
    by_name = {}

    def __init__(cls, name, bases, namespace):
        super(UnitRegistry, cls).__init__(name, bases, namespace)
        # Classes that opt out (infrastructure like Workflow/StartPoint)
        # set hide_from_registry = True in their own namespace.
        if not namespace.get("hide_from_registry", False):
            UnitRegistry.units.add(cls)
            UnitRegistry.by_name[name] = cls
        # Merge KWATTRS / demanded hints up the MRO for introspection.
        kwattrs = set(namespace.get("KWATTRS", set()))
        for base in bases:
            kwattrs |= getattr(base, "KWATTRS", set())
        cls.KWATTRS = kwattrs
        # Units contributing CLI flags join the argparse registry (the
        # reference combined both metaclasses, cmdline.py:61-84)
        if "init_parser" in namespace or "apply_args" in namespace:
            from veles_tpu.cmdline import CommandLineArgumentsRegistry
            CommandLineArgumentsRegistry.classes.append(cls)


def nothing(*args, **kwargs):
    return None


class IUnit(object):
    """Interface contract: units must define initialize() and run()."""

    def initialize(self, **kwargs):
        """Allocate state; may be re-queued if demands are unsatisfied."""

    def run(self):
        """Do one step of work."""


class Unit(Distributable, metaclass=UnitRegistry):
    """A graph node with control gates and linked data attributes."""

    #: subclasses may set a stable UUID for the package-export factory
    #: (libVeles-parity; see veles_tpu/package.py)
    UNIT_UUID = None

    hide_from_registry = False

    def __init__(self, workflow, **kwargs):
        self.name = kwargs.pop("name", None)
        self.view_group = kwargs.pop("view_group", None)
        self.timings = kwargs.pop(
            "timings", root.common.get("timings", False))
        super(Unit, self).__init__(**kwargs)
        self._links_from = {}
        self._links_to = {}
        self._gate_block = Bool(False)
        self._gate_skip = Bool(False)
        self._ignores_gate = Bool(False)
        self._initialized = Bool(False)
        self._stopped = Bool(False)
        #: a re-run may clear this unit's stop flag; units whose stop()
        #: permanently tears down resources (sockets, server threads)
        #: set this False so a rerun leaves them suppressed instead of
        #: hanging on a dead resource
        self.restartable = True
        self._ran = False
        self._demanded = set()
        self.timers = {"run": 0.0}
        self.run_calls = 0
        self.id = str(uuid_module.uuid4())
        self._workflow = None
        self.workflow = workflow
        self.init_unpickled()

    def init_unpickled(self):
        super(Unit, self).init_unpickled()
        self._gate_lock_ = threading.RLock()
        self._run_lock_ = threading.RLock()
        self._is_initialized_ = False
        # data aliases need their class-level descriptors back when the
        # snapshot lands in a process that never built this graph
        from veles_tpu.mutable import LinkableAttribute
        LinkableAttribute.reinstall(self)

    def __repr__(self):
        return "<%s \"%s\">" % (type(self).__name__, self.name or
                                hex(id(self)))

    # -- naming / ownership ------------------------------------------------

    @property
    def name(self):
        if self._name is not None:
            return self._name
        return type(self).__name__

    @name.setter
    def name(self, value):
        self._name = value

    @property
    def workflow(self):
        return self._workflow

    @workflow.setter
    def workflow(self, value):
        if self._workflow is not None:
            self._workflow.del_ref(self)
        self._workflow = value
        if value is not None:
            value.add_ref(self)

    def detach(self):
        self.workflow = None

    @property
    def is_standalone(self):
        return self.workflow.workflow_mode == "standalone"

    @property
    def is_master(self):
        return self.workflow.workflow_mode == "master"

    @property
    def is_slave(self):
        return self.workflow.workflow_mode == "slave"

    @property
    def launcher(self):
        return self.workflow.launcher

    # -- gates & links -----------------------------------------------------

    @property
    def gate_block(self):
        return self._gate_block

    @gate_block.setter
    def gate_block(self, value):
        self._gate_block = value if isinstance(value, Bool) else Bool(value)

    @property
    def gate_skip(self):
        return self._gate_skip

    @gate_skip.setter
    def gate_skip(self, value):
        self._gate_skip = value if isinstance(value, Bool) else Bool(value)

    @property
    def ignores_gate(self):
        return self._ignores_gate

    @ignores_gate.setter
    def ignores_gate(self, value):
        self._ignores_gate = value if isinstance(value, Bool) else Bool(value)

    @property
    def links_from(self):
        return self._links_from

    @property
    def links_to(self):
        return self._links_to

    def link_from(self, *units):
        """Add control dependencies: self runs after each of ``units``."""
        with self._gate_lock_:
            for unit in units:
                self._links_from[unit] = False
                unit._links_to[self] = False
        return self

    def unlink_from(self, *units):
        with self._gate_lock_:
            for unit in units:
                self._links_from.pop(unit, None)
                unit._links_to.pop(self, None)

    def unlink_all(self):
        with self._gate_lock_:
            for unit in list(self._links_from):
                self.unlink_from(unit)
            for unit in list(self._links_to):
                unit.unlink_from(self)

    def open_gate(self, src):
        """Mark ``src`` done; True when ALL incoming links have fired
        (reference: units.py:524-543).  Resets flags on opening."""
        with self._gate_lock_:
            if bool(self._ignores_gate):
                return True
            if src in self._links_from:
                self._links_from[src] = True
            if all(self._links_from.values()):
                for key in self._links_from:
                    self._links_from[key] = False
                return True
            return False

    # -- data links --------------------------------------------------------

    def link_attrs(self, other, *names, two_way=False):
        """Alias attributes from ``other``.  Each name is either a string
        (same name both sides) or a tuple ``(mine, theirs)``."""
        for name in names:
            if isinstance(name, tuple):
                mine, theirs = name
            else:
                mine = theirs = name
            LinkableAttribute(self, mine, other, theirs, two_way=two_way)
        return self

    def demand(self, *names):
        """Declare attributes that must be set before initialize()."""
        self._demanded.update(names)

    def verify_demands(self):
        missing = []
        for name in self._demanded:
            try:
                if getattr(self, name) is None:
                    missing.append(name)
            except AttributeError:
                missing.append(name)
        return missing

    # -- lifecycle ---------------------------------------------------------

    @property
    def is_initialized(self):
        return self._is_initialized_

    def initialize(self, **kwargs):
        """Base initialize verifies demands.  Subclasses extend."""
        missing = self.verify_demands()
        if missing:
            raise AttributeError(
                "%s lacks demanded attributes: %s" % (self, missing))
        self._is_initialized_ = True
        return True

    @property
    def stopped(self):
        return bool(self._stopped)

    def stop(self):
        self._stopped <<= True

    def run(self):  # pragma: no cover - abstract
        pass

    # -- execution wrapping ------------------------------------------------

    def _timed_run(self):
        if not self._is_initialized_:
            raise RuntimeError("%s.run() before initialize()" % self)
        if self.stopped or (self.workflow is not None and
                            self.workflow.stopped):
            wf = self.workflow
            if (wf is not None and
                    getattr(wf, "finished", False) and
                    not getattr(wf, "stop_requested", True)):
                raise RunAfterStopError(
                    "%s scheduled to run after the workflow finished "
                    "— check its control links" % self)
            return False
        wf = self.workflow
        if wf is not None:
            # the graph thread leaves the scheduler: the hop that began
            # at the previous unit's end stops here
            wf.end_hop()
        # one measurement for the unit timer (print_stats), the trace
        # span and the flight ring — they cannot disagree
        with _tracer.scope(self.name, cat="unit",
                           timers=(self.timers, "run")) as span:
            self.run()
        self.run_calls += 1
        if wf is not None:
            wf.begin_hop()
        self._ran = True
        if self.timings:
            self.debug("%s ran in %.3f ms", self.name, span.elapsed * 1e3)
        return True

    def _check_gate_and_run(self, src):
        """Gate test + run + propagate (reference: units.py:782)."""
        if not self.open_gate(src):
            return
        if bool(self._gate_block):
            return
        with self._run_lock_:
            if bool(self._gate_skip):
                self.run_dependent()
                return
            if self._timed_run() is False:
                return
        self.run_dependent()

    def run_dependent(self):
        """Schedule every successor through the workflow scheduler."""
        wf = self.workflow
        if wf is None:
            for dst in list(self._links_to):
                dst._check_gate_and_run(self)
            return
        for dst in list(self._links_to):
            wf.schedule(dst, self)

    @property
    def dependent_units(self):
        """Transitive closure of links_to, including self."""
        result = []
        seen = set()
        stack = [self]
        while stack:
            unit = stack.pop()
            if id(unit) in seen:
                continue
            seen.add(id(unit))
            result.append(unit)
            stack.extend(unit._links_to)
        return result

    # -- pickling ----------------------------------------------------------

    def __getstate__(self):
        state = super(Unit, self).__getstate__()
        if self.stripped_pickle:
            state["_links_from"] = {}
            state["_links_to"] = {}
            state["_workflow"] = None
        return state
