"""Standard NN training workflow wiring.

Znicz-equivalent standard_workflow.StandardWorkflow: builds the classic
loop  repeater -> loader -> forwards -> evaluator -> decision -> gds ->
repeater  from a declarative ``layers`` list, with the stop path
decision.complete -> end_point.

A layer spec is a dict: {"type": "all2all_tanh",
"output_sample_shape": 100, ...hyperparameters...}; forward and GD
classes are looked up by their shared MAPPING name, mirroring the
reference's MappedUnitRegistry factories.
"""

from veles_tpu.models import all2all, gd as gd_module
from veles_tpu.models.decision import DecisionGD, DecisionMSE
from veles_tpu.models.evaluator import EvaluatorMSE, EvaluatorSoftmax
from veles_tpu.plumbing import Repeater
from veles_tpu.workflow import Workflow

__all__ = ["StandardWorkflow", "forward_mapping", "gd_mapping"]


def _build_mapping(module, base):
    mapping = {}
    for name in dir(module):
        cls = getattr(module, name)
        if isinstance(cls, type) and issubclass(cls, base) and \
                getattr(cls, "MAPPING", None):
            mapping[cls.MAPPING] = cls
    return mapping


def forward_mapping():
    from veles_tpu.models import (
        activation, conv, decoder, deconv, dropout, pooling, rnn,
        transformer)
    from veles_tpu.models.nn_units import ForwardBase
    mapping = {}
    for module in (all2all, conv, pooling, dropout, activation, deconv,
                   rnn, transformer, decoder):
        mapping.update(_build_mapping(module, ForwardBase))
    return mapping


def gd_mapping():
    from veles_tpu.models import (
        activation, decoder, deconv, dropout, gd_conv, gd_pooling, rnn,
        transformer)
    from veles_tpu.models.nn_units import GradientDescentBase
    mapping = {}
    for module in (gd_module, gd_conv, gd_pooling, dropout, activation,
                   deconv, rnn, transformer, decoder):
        mapping.update(_build_mapping(module, GradientDescentBase))
    return mapping


class StandardWorkflow(Workflow):
    """loader_factory(workflow) -> Loader; layers: list of layer specs.

    kwargs: loss ("softmax" | "mse"), decision_config, loader_config
    passed through to the respective units.
    """

    hide_from_registry = True

    def __init__(self, workflow, layers, loader_factory, **kwargs):
        super(StandardWorkflow, self).__init__(workflow, **kwargs)
        self.layers_config = layers
        self.loss = kwargs.get("loss", "softmax")
        decision_config = kwargs.get("decision_config", {})

        self.repeater = Repeater(self)
        self.repeater.link_from(self.start_point)

        self.loader = loader_factory(self)
        self.loader.link_from(self.repeater)

        # forwards
        fmap = forward_mapping()
        self.forwards = []
        src_unit, src_attr = self.loader, "minibatch_data"
        for spec in layers:
            spec = dict(spec)
            ltype = spec.pop("type")
            unit = fmap[ltype](self, **spec)
            unit.link_from(self.forwards[-1] if self.forwards
                           else self.loader)
            unit.link_attrs(src_unit, ("input", src_attr))
            if "minibatch_class" in unit._demanded:  # dropout et al.
                unit.link_attrs(self.loader, "minibatch_class")
            self.forwards.append(unit)
            src_unit, src_attr = unit, "output"

        # evaluator
        if self.loss == "softmax":
            self.evaluator = EvaluatorSoftmax(self)
            self.evaluator.link_attrs(self.loader,
                                      ("labels", "minibatch_labels"))
        elif self.loss == "mse":
            self.evaluator = EvaluatorMSE(self)
            self.evaluator.link_attrs(self.loader,
                                      ("target", "minibatch_targets"))
        else:
            raise ValueError("unknown loss %r" % self.loss)
        self.evaluator.link_from(self.forwards[-1])
        self.evaluator.link_attrs(self.forwards[-1], "output")
        self.evaluator.link_attrs(self.loader,
                                  ("batch_size", "minibatch_size"))

        # decision
        decision_cls = DecisionGD if self.loss == "softmax" else DecisionMSE
        self.decision = decision_cls(self, **decision_config)
        self.decision.link_from(self.evaluator)
        self.decision.link_attrs(
            self.loader, "minibatch_class", "last_minibatch", "epoch_ended",
            "epoch_number", "class_lengths")
        self.decision.evaluator = self.evaluator

        # gradient descent chain, last layer first
        gmap = gd_mapping()
        self.gds = [None] * len(layers)
        prev_gd = None
        for i in reversed(range(len(layers))):
            spec = dict(layers[i])
            ltype = spec.pop("type")
            spec.pop("output_sample_shape", None)
            spec.pop("output_shape", None)
            unit = gmap[ltype](self, need_err_input=(i > 0), **spec)
            fwd = self.forwards[i]
            unit.link_attrs(fwd, "input", "output", "weights", "bias")
            if "mask" in unit._demanded:  # dropout backward
                unit.link_attrs(fwd, "mask")
            if prev_gd is None:
                unit.link_from(self.decision)
                unit.link_attrs(self.evaluator, "err_output")
            else:
                unit.link_from(prev_gd)
                unit.link_attrs(prev_gd, ("err_output", "err_input"))
            # completion SKIPS the chain instead of blocking it: the
            # final cycle must still propagate through gds[0] to the
            # snapshotter (final improved checkpoint) and on to
            # end_point.  EVERY gd carries the complete term — if only
            # the first one did, an epoch ending on a TRAIN minibatch
            # (no-validation workflows) would skip-propagate the last
            # gd but RUN the rest against its stale err_input
            unit.gate_skip = self.decision.gd_skip | \
                self.decision.complete
            self.gds[i] = unit
            prev_gd = unit

        # the decision's divergence watchdog reads the gds' lazy skip
        # counters (the fused path rewires this to the trainer)
        self.decision.health_sources = [gd for gd in self.gds
                                        if gd is not None]
        #: LR multiplier applied by each divergence rollback
        self.divergence_lr_backoff = kwargs.get(
            "divergence_lr_backoff", 0.5)

        # close the loop and the exit path
        self.repeater.link_from(self.gds[0])
        self.end_point.link_from(self.decision)
        self.end_point.gate_block = ~self.decision.complete

        # standard snapshotting: when the config names a snapshot dir
        # (e.g. the CLI's --snapshot-dir), every improvement checkpoints
        # automatically — the reference wired a Snapshotter into every
        # standard workflow; restore with -w <file>
        self.snapshotter = None
        from veles_tpu.config import root as _root
        if _root.common.snapshot.get("dir"):
            from veles_tpu.snapshotter import Snapshotter
            self.snapshotter = Snapshotter(
                self, prefix=type(self).__name__)
            # The snapshotter runs at the QUIESCENT point of the
            # minibatch cycle — after the last gd applied its update,
            # before the repeater serves the next minibatch — so every
            # snapshot is an exact resume point (weights, loader
            # offsets, prng, decision accumulators all consistent).
            # Linking it from the decision instead would pickle TORN
            # state: the worklist interleaves it with the gd chain, so
            # some layers would carry the current minibatch's update
            # and some would not.
            self.snapshotter.link_from(self.gds[0])
            self.repeater.unlink_from(self.gds[0])
            self.repeater.link_from(self.snapshotter)
            # fire once per improved epoch: improved alone stays True
            # through the whole following epoch (it resets only at the
            # next judge-class end), which would export every minibatch
            self.snapshotter.gate_skip = ~(self.decision.improved &
                                           self.loader.epoch_ended)
            # the exit gate also waits on the snapshotter (reference
            # topology decision -> snapshotter -> end): otherwise the
            # worklist is abandoned at end_point before a queued
            # final-epoch snapshot runs
            self.end_point.link_from(self.snapshotter)

    def fuse(self, **kwargs):
        """Swap the per-unit chain for the single-dispatch fused train
        step (veles_tpu.models.fused); call before initialize().
        ``pipeline=True`` additionally overlaps host fill + H2D of the
        next minibatch with the running step."""
        from veles_tpu.models.fused import fuse_standard_workflow
        return fuse_standard_workflow(self, **kwargs)

    # -- numerics health: divergence recovery (docs/health.md) --------------

    def adopt_model_state(self, donor):
        """Copy the model state (forward params + gd solver
        accumulators) out of ``donor`` — a workflow unpickled from a
        verified snapshot — into THIS workflow's live Arrays.  Host
        copies become authoritative; device uploads happen lazily at
        the next access, and a fused trainer re-extracts its state on
        its next compile."""
        import numpy
        if len(donor.forwards) != len(self.forwards):
            raise ValueError(
                "snapshot workflow has %d forward layers, live one has "
                "%d — refusing to adopt" % (len(donor.forwards),
                                            len(self.forwards)))

        def copy_arrays(src_unit, dst_unit, names):
            for name in names:
                src = getattr(src_unit, name, None)
                dst = getattr(dst_unit, name, None)
                if src is None or dst is None or not src or not dst:
                    continue
                src.map_read()
                dst.map_invalidate()
                dst.mem = numpy.array(src.mem)

        for live, old in zip(self.forwards, donor.forwards):
            copy_arrays(old, live, ("weights", "bias"))
        for live, old in zip(self.gds, donor.gds):
            if live is None or old is None:
                continue
            copy_arrays(old, live, ("accum_weights", "accum_bias",
                                    "accum2_weights", "accum2_bias"))

    def on_divergence(self, reason):
        """The decision watchdog's recovery hook: roll the model back
        to the last verified snapshot, back off every layer's learning
        rate, reseed the fused dropout stream, and clear the health
        counters so the watchdog starts a fresh observation window.
        Without a snapshotter (or with the rollback budget spent) this
        raises — surviving bad math silently is not an option."""
        from veles_tpu.health import DivergenceError
        if self.snapshotter is None:
            raise DivergenceError(
                "training diverged (%s) and no snapshotter is attached "
                "— nothing to roll back to" % reason)
        path = self.snapshotter.rollback(reason=reason)
        backoff = self.divergence_lr_backoff
        for gd in self.gds:
            if gd is None:
                continue
            gd.learning_rate *= backoff
            gd.learning_rate_bias *= backoff
            gd.reset_health_counters()
        trainer = getattr(self, "fused_trainer", None)
        if trainer is not None:
            # recompiles against the restored Arrays and the
            # backed-off hyperparameters, with a fresh dropout stream
            trainer.reset_after_rollback(self.snapshotter.rollbacks)
        self.decision.reset_divergence()
        self.warning(
            "divergence recovery: restored %s, learning rates *= %g "
            "(rollback %d/%d); training continues", path, backoff,
            self.snapshotter.rollbacks, self.snapshotter.rollback_budget)

    def link_plotters(self):
        """Attach the standard plotter set (reference Znicz standard
        workflow behavior): per-class error curves, the confusion
        matrix, and per-layer weight histograms, all running after the
        decision each minibatch and publishing to the launcher's
        graphics server when one is attached."""
        from veles_tpu.plotting_units import (
            AccumulatingPlotter, MatrixPlotter, MultiHistogram)
        self.plotters = []
        decision = self.decision
        for cls_idx, cls_name in ((1, "validation"), (2, "train")):
            plot = AccumulatingPlotter(
                self, label="%s error %%" % cls_name)
            plot.input = decision

            def capture(plot=plot, idx=cls_idx):
                # one point per finished epoch
                if not bool(decision.epoch_ended):
                    return
                value = decision.epoch_metrics[idx]
                if value is not None:
                    plot.values.append(float(value))
            plot.capture = capture
            plot.link_from(self.decision)
            self.plotters.append(plot)
        if hasattr(self.evaluator, "confusion_matrix"):
            conf = MatrixPlotter(self)
            conf.input = self.evaluator.confusion_matrix
            conf.link_from(self.decision)
            self.plotters.append(conf)
        hist = MultiHistogram(self)
        hist.inputs = [f.weights for f in self.forwards
                       if f.weights is not None and hasattr(
                           f.weights, "map_read")]
        hist.link_from(self.decision)
        self.plotters.append(hist)
        return self.plotters

    def initialize(self, device=None, **kwargs):
        if self.workflow_mode == "slave":
            # one job = one pass: a slave must not loop the repeater; the
            # drained worklist ends the pass (master drives iteration)
            self.repeater.unlink_from(
                self.gds[0] if self.snapshotter is None
                else self.snapshotter)
        elif self.workflow_mode == "standalone":
            # standalone ONLY: in distributed runs master and slaves
            # exchange unit state by zipping their unit lists
            # positionally (workflow.py generate_data_for_slave /
            # apply_data_from_slave), so a fused master would
            # desynchronize from its unfused slaves
            device = self._maybe_auto_fuse(device)
        return super(StandardWorkflow, self).initialize(
            device=device, **kwargs)

    def _maybe_auto_fuse(self, device):
        """Fuse automatically when the resolved device is a TPU.

        The per-unit dispatch loop is the DEBUG path on TPU, so the
        product default is the fused step; ``--no-fuse`` /
        VELES_AUTO_FUSE=0 opts out.  Distributed modes never auto-fuse
        — master and slaves exchange state by zipping unit lists
        positionally, so both sides must keep the same unit graph.  A
        workflow the compiler cannot plan FAILS here: quietly running
        the per-unit path on the chip would publish its step times
        under the fused path's name.
        Returns the RESOLVED device so initialize passes it down
        without a second backend auto-selection."""
        from veles_tpu.backends import Device
        from veles_tpu.config import root
        if device is None or isinstance(device, str):
            device = Device(backend=device)
        if (getattr(self, "fused_trainer", None) is None
                and root.common.engine.get("auto_fuse", True)
                and device.BACKEND == "tpu"):
            from veles_tpu.compiler import workflow_plan
            try:
                workflow_plan(self)  # structural check only
            except Exception as exc:
                raise RuntimeError(
                    "%s cannot be fused for the TPU (%s: %s); pass "
                    "--no-fuse to run the per-unit debug path on "
                    "purpose" % (self.name, type(exc).__name__,
                                 exc)) from exc
            self.info("TPU device: fusing the train loop into one "
                      "dispatch per minibatch (--no-fuse to keep "
                      "the per-unit debug path)")
            # async input pipeline rides along by default on real
            # hardware: host fill + H2D of minibatch k+1 overlap
            # step k (VELES_PIPELINE_INPUT=0 / engine.pipeline_input
            # opts out; the trainer serves synchronously where
            # pipelining is unsupported — numpy devices, meshes)
            self.fuse(pipeline=root.common.engine.get(
                "pipeline_input", True))
        return device
