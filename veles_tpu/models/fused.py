"""FusedTrainer — run the whole forward+loss+backward+update chain as
ONE jitted dispatch inside a standard workflow.

This is the performance path promised by veles_tpu.compiler: the unit
graph keeps orchestrating (loader serves minibatches, decision stops
training, snapshotter checkpoints), but between loader and decision a
single FusedTrainer replaces forwards + evaluator + GD units.  Per
minibatch there is exactly one XLA computation and zero host transfers
besides the scalar metrics the decision unit needs.

``StandardWorkflow.fuse()`` rewires an existing workflow in place, so
every already-written config gains the fused path without changes.
"""

import numpy

from veles_tpu import chaos
from veles_tpu.loader.base import TRAIN
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.profile import profiler_step
from veles_tpu.observe.trace import step_annotation
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu.units import Unit

__all__ = ["FusedTrainer", "fuse_standard_workflow"]

#: the step's own metrics; any other key is a layer's counter
STEP_METRICS = frozenset(("loss", "n_err", "mse_sum", "grad_norm",
                          "finite", "skipped"))

#: the share of the device's memory above which the step's backward
#: recomputes its layers (FusedTrainer._backward_should_recompute): what
#: is left is for the step's temporaries and the compiler's own buffers
REMAT_ABOVE = 0.7


def kept_names():
    """What a recomputed layer keeps of its forward under the trainer's
    ``bwd_remat``: the names the flash kernels give their output and row
    statistics (ops/attention.py's ``KEPT_NAMES``, which the kernels of
    attention over a selection give theirs too), the indexer's loss its
    gradients and the selection its mask and tiles' counts
    (ops/sparse_attention.py); a name no op gives is no change to the
    program.  The selection's name comes last: the trainer keeps the
    list without it where only that fits."""
    from veles_tpu.ops.attention import KEPT_NAMES
    from veles_tpu.ops.sparse_attention import (KEPT_INDEXER_GRADS,
                                                KEPT_SELECTION)
    return KEPT_NAMES + (KEPT_INDEXER_GRADS, KEPT_SELECTION)


class FusedTrainer(Unit):
    """Wraps compiler.build_train_step over a StandardWorkflow's
    layers; exposes evaluator-compatible metrics (n_err / mse_sum) so
    the decision unit works unchanged."""

    def __init__(self, workflow, sw, **kwargs):
        super(FusedTrainer, self).__init__(workflow, **kwargs)
        self.sw = sw
        self.loss = sw.loss
        self.device = None
        self._step_fn = None
        self._state = None
        self._dropout_seed = kwargs.get("dropout_seed", 0)
        self._dropout_base_key = self._dropout_seed
        self._iteration = 0
        # numerics health (docs/health.md): per-step skip flags stay
        # lazy device scalars; the decision unit syncs them once per
        # finished class, never on the hot path
        self.skip_count = 0
        self.consecutive_skips = 0
        self.last_step_finite = True
        self.grad_norm = None
        #: async input pipeline knob (pipeline_input.Prefetcher): serve
        #: minibatch k+1 (host fill + async H2D) while step k runs
        self.pipeline = kwargs.get("pipeline", False)
        self.pipeline_depth = kwargs.get("pipeline_depth", 1)
        self._prefetcher = None
        #: SPMD data plane (docs/distributed.md): with a mesh, the
        #: step compiles as shard_map over ``data_axis`` and the
        #: gradient merge is the bucketed overlapped all-reduce
        #: (parallel/bucketed.py) instead of a flat pjit psum
        self.mesh = kwargs.get("mesh")
        self.data_axis = kwargs.get("data_axis", "data")
        self.grad_bucket_mb = kwargs.get("grad_bucket_mb")
        #: "bf16" halves gradient wire bytes; auto-falls back to f32
        #: when the health watchdog sees a skipped (non-finite) step
        self.grad_compress = kwargs.get("grad_compress")
        # evaluator-compatible surface for DecisionGD / DecisionMSE
        self.n_err = 0
        self.mse_sum = 0.0
        self.n_samples = 0
        self.last_loss = None
        #: targets a sample carries: 1 for a class label, T for a row of
        #: T next tokens (the decision's error rate is over targets)
        self.targets_per_sample = 1
        #: what the layers count for themselves (compiler.py's
        #: ``apply_with_aux`` metrics, e.g. ``moe_load``), summed over
        #: the train steps since the last publish: lazy device arrays
        self.layer_counters = {}

    def init_unpickled(self):
        super(FusedTrainer, self).init_unpickled()
        # telemetry handles (trailing underscore: transient, re-created
        # after unpickling).  The step histograms measure the graph
        # thread's dispatch wall time — the honest steady-state step
        # time under device backpressure, with zero extra host syncs
        self._m_train_step_ = _registry.histogram("step.train_s")
        #: train steps in ``layer_counters`` since the last publish
        self._counted_steps_ = 0
        self._m_eval_step_ = _registry.histogram("step.eval_s")
        # the step's anatomy on the host: picking or staging the
        # inputs, and the call of the compiled program alone; what is
        # left of step.train_s is the span's self time (the dropout
        # key, the lazy skip counters, the metrics bookkeeping)
        self._m_stage_ = _registry.histogram("step.stage_s")
        # bytes of minibatches that _stage_sharded took through the
        # host on their way to the mesh (0 where the loader gathers
        # onto the mesh, and on one chip)
        self._m_host_staged_ = _registry.counter("step.host_staged_bytes")
        # bytes a recomputed backward keeps of its layers' kernels
        # (_backward_should_recompute; 0 where it keeps every
        # activation, or none, and under a mesh, which never recomputes)
        self._m_kept_residual_ = _registry.gauge(
            "step.kept_residual_bytes")
        self._m_kept_residual_.set(0)
        # what the selection's mask and counts add to those bytes (0
        # where the backward does not keep them, or no layer selects)
        self._m_kept_selection_ = _registry.gauge(
            "step.kept_selection_bytes")
        self._m_kept_selection_.set(0)
        self._m_dispatch_ = _registry.histogram("step.dispatch_s")
        self._m_eval_dispatch_ = _registry.histogram(
            "step.eval_dispatch_s")
        self._m_steps_ = _registry.counter("train.steps")
        self._m_samples_ = _registry.counter("train.samples")
        self._m_tokens_ = _registry.counter("train.tokens")
        #: XLA cost-model FLOPs of one compiled step (None until the
        #: first step ran; 0.0 when cost analysis is unavailable)
        self._step_flops_ = None
        #: comm receipt state (SPMD mode): published once, at the
        #: first post-compile step whose wall time is clean
        self._comm_published_ = False
        #: skip count already attributed at the last health sync —
        #: growth while compression is on triggers the f32 fallback
        self._compress_skips_seen_ = 0

    def _restore_mesh(self):
        """Rebuild the SPMD mesh after unpickling (snapshots carry its
        AXES: parallel.mesh.restore_mesh)."""
        axes = getattr(self, "_spmd_axes_", None)
        if axes and self.mesh is None:
            from veles_tpu.parallel.mesh import restore_mesh
            self.mesh = restore_mesh(axes, self.warning)

    def initialize(self, device=None, **kwargs):
        self.device = device
        self._restore_mesh()
        if (self.pipeline and self._prefetcher is None
                and self.mesh is None
                and device is not None
                and getattr(device, "exists", False)
                and self.sw.workflow_mode == "standalone"):
            from veles_tpu.pipeline_input import Prefetcher
            self._prefetcher = Prefetcher(
                self.sw.loader, device,
                depth=self.pipeline_depth).attach()
        super(FusedTrainer, self).initialize(**kwargs)
        return True

    def _compile(self):
        import jax

        from veles_tpu.compiler import (
            build_forward, build_train_step, extract_state,
            step_compiler_options, workflow_plan)
        from veles_tpu.observe import xla_introspect as _xla

        # install the jax.monitoring compile listener BEFORE building,
        # so this compile (and any later recompile storm) is counted
        _xla.ensure_installed()
        plans = workflow_plan(self.sw)
        self._plans = plans
        # the step that triggers a (re)compile pays the compile in its
        # wall time; the comm receipt must be sized on a CLEAN step,
        # so publication waits two iterations past ANY compile (the
        # bf16->f32 fallback recompiles mid-run)
        self._compiled_at_iter_ = self._iteration
        if self.mesh is not None:
            from veles_tpu.parallel.bucketed import DEFAULT_BUCKET_MB
            bucket_mb = (self.grad_bucket_mb
                         if self.grad_bucket_mb is not None
                         else DEFAULT_BUCKET_MB)
            self._step_fn = build_train_step(
                plans, loss=self.loss, mesh=self.mesh,
                data_axis=self.data_axis, grad_bucket_mb=bucket_mb,
                grad_compress=self.grad_compress, donate=True,
                compiler_options=step_compiler_options())
        else:
            remat = self._backward_should_recompute(plans)
            self._publish_scan_gauges(plans, remat)
            self._step_fn = build_train_step(
                plans, loss=self.loss, donate=True, bwd_remat=remat,
                compiler_options=step_compiler_options())
        #: adamw's bias correction wants the step's number
        self._counts_steps = any(p.solver == "adamw" for p in plans)
        #: the layers' own metric names, known after the first step
        self._layer_metrics = None
        if self.loss == "softmax":
            self.targets_per_sample = int(numpy.prod(
                self.sw.loader.minibatch_labels.shape[1:]))
        forward = build_forward(plans)

        # eval metrics fused INTO the forward dispatch: one async call
        # per eval minibatch, no eager ops (each eager op is a
        # dispatch of its own)
        import jax.numpy as jnp
        if self.loss == "softmax":
            def eval_metrics(params, x, labels):
                out = forward(params, x)
                valid = labels >= 0
                pred = jnp.argmax(out, axis=-1)
                return ((pred != labels) & valid).sum()
        else:
            def eval_metrics(params, x, target, batch_size):
                out = forward(params, x)
                diff = (out.reshape(out.shape[0], -1) -
                        target.reshape(target.shape[0], -1))
                mask = jnp.arange(out.shape[0]) < batch_size
                return jnp.sum(jnp.mean(diff * diff, axis=1) * mask)
        self._eval_metrics = jax.jit(eval_metrics)
        self._state = extract_state(self.sw)
        if self.mesh is not None:
            # replicate over the WHOLE mesh (copies — the unit Arrays
            # stay authoritative on host); eval reuses these replicated
            # params, so its jit runs on the same device set
            from veles_tpu.parallel.api import replicate
            self._state = replicate(self.mesh, self._state)
        self._has_dropout = any(
            p.static.get("dropout_ratio") is not None for p in plans)
        # recompile detection (docs/observability.md): each of these
        # should settle on a handful of signatures — growth past that
        # is the recompile storm the watcher warns about
        _xla.watch(self._step_fn, "fused.step")
        _xla.watch(self._eval_metrics, "fused.eval")

    def _backward_should_recompute(self, plans):
        """What the step's backward holds of the forward, as
        ``build_train_step``'s ``bwd_remat``: False, every activation;
        :func:`kept_names`, each layer recomputed but for what its
        kernels named (the flash forward's output and row statistics, so
        that the kernel runs once a layer; the indexer's loss's
        gradients; the selection's mask and tiles' counts, so that the
        selection runs once a layer); that list without the selection's
        name; True, each layer recomputed whole.  Decided from what can
        be observed — the bytes autodiff would save for the backward at
        this minibatch's shape (abstract traces, nothing runs), beside
        what the device already holds and one more copy of the
        parameters for their gradients, against ``REMAT_ABOVE`` of the
        device's memory: the first of the four that fits, and the last
        where the layers name nothing.  A device that does not report its
        memory (the CPU) keeps the activations."""
        import jax

        from veles_tpu.compiler import _forward_for_loss
        from veles_tpu.observe import xla_introspect as _xla
        from veles_tpu.ops.sparse_attention import KEPT_SELECTION
        named = kept_names()
        memory = _xla.device_memory_gauges()
        limit = memory.get("xla.mem.bytes_limit.d0")
        if not limit:
            self._m_kept_residual_.set(0)
            self._m_kept_selection_.set(0)
            return False
        in_use = memory.get("xla.mem.bytes_in_use.d0", 0)
        loader = self.sw.loader
        x = loader.minibatch_data
        x = jax.ShapeDtypeStruct(x.shape, x.dtype)
        params = [{k: None if s[k] is None else
                   jax.ShapeDtypeStruct(s[k].shape, s[k].dtype)
                   for k in ("weights", "bias")}
                  for s in self._abstract_state()]

        def nbytes(tree):
            return sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(tree))

        def saved_bytes(remat):
            """What the backward holds of the forward under ``remat``
            (the parameters among it)."""
            def saved(p, x_):
                return jax.vjp(lambda q: _forward_for_loss(
                    plans, q, x_, remat=remat), p)[1]
            return nbytes(jax.eval_shape(saved, params, x))

        param_bytes = nbytes(params)
        # the residuals hold the parameters too: they are there already
        held = max(0, saved_bytes(False) - param_bytes)
        room = REMAT_ABOVE * limit - in_use - param_bytes
        remat, kept, selection, what = False, 0, 0, "activations are kept"
        if held > room:
            # what the named values add to a recomputed layer's inputs:
            # with the selection's mask and counts, else without them
            bare = saved_bytes(True)
            without = tuple(n for n in named if n != KEPT_SELECTION)
            remat, kept = named, saved_bytes(named) - bare
            if 0 < kept <= room:
                selection = kept - (saved_bytes(without) - bare)
            else:
                remat, kept = without, saved_bytes(without) - bare
            if 0 < kept <= room:
                what = ("each layer is recomputed in the backward but "
                        "for %.2f GB that its kernels named, which are "
                        "kept" % (kept / 1e9))
                if selection:
                    what += (", the selection's %.2f GB among them, so "
                             "the replay does not select again"
                             % (selection / 1e9))
            else:
                remat, kept = True, 0
                what = "each layer is recomputed in the backward"
        self._m_kept_residual_.set(kept)
        self._m_kept_selection_.set(selection)
        self.info("backward: %.2f GB of activations to hold, %.2f GB in "
                  "use, %.2f GB of gradients, device %.2f GB: %s",
                  held / 1e9, in_use / 1e9,
                  param_bytes / 1e9, limit / 1e9, what)
        return remat

    def _publish_scan_gauges(self, plans, remat):
        """The state-space layers' gauges, once a build: ``ssm.chunks``,
        the chunks a layer's scan walks a sequence, ``ssm.carry_blocks``,
        the blocks of ``decoder.CARRY_BLOCK`` chunks its states pass
        through a sequence (above 1 the recurrence from block to block
        runs), and ``ssm.kept_state_bytes``, the float32 states that the
        chunks start from which the backward holds of the forward (0
        where the layers are recomputed: the replay scans again).
        Nothing where no layer scans."""
        scans = [plan.static for plan in plans
                 if plan.static.get("ssm_chunk")]
        if not scans:
            return
        from veles_tpu.models import decoder
        batch, tokens = self.sw.loader.minibatch_data.shape[:2]
        chunks = -(-tokens // scans[0]["ssm_chunk"])
        kept = 0 if remat is not False else sum(
            4 * batch * chunks * s["ssm_heads"] * s["ssm_head_width"]
            * s["ssm_state"] for s in scans)
        _registry.gauge("ssm.chunks").set(chunks)
        _registry.gauge("ssm.carry_blocks").set(
            -(-chunks // decoder.CARRY_BLOCK))
        _registry.gauge("ssm.kept_state_bytes").set(kept)

    def _abstract_state(self):
        return [{"weights": fwd.weights if fwd.weights else None,
                 "bias": fwd.bias if fwd.bias and fwd.include_bias
                 else None} for fwd in self.sw.forwards]

    def _publish_step_flops(self, x, target, batch_size, key, poisons):
        """XLA's own cost model for ONE fused step, from abstract
        avals of the arguments the step was just called with, feeding
        the live ``mfu_pct`` gauge.  One-time at the first train step,
        entirely off the per-step path afterwards.  A cost model that
        cannot rate the program leaves MFU unpublished and SAYS so at
        warning level (0.0 is recorded so the attempt is never retried
        per step)."""
        import jax

        from veles_tpu.compiler import STEP_SCOPES
        from veles_tpu.observe import xla_introspect as _xla
        self._step_flops_ = 0.0

        def aval(leaf):
            if leaf is None:
                return None
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                # a committed array's sharding is part of the lowering
                # (and of the key jax caches it under): with it the
                # description is the call's own
                return jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype,
                    sharding=leaf.sharding if getattr(
                        leaf, "committed", False) else None)
            return leaf

        args = [jax.tree.map(aval, self._state,
                             is_leaf=lambda v: v is None),
                aval(x), aval(target), aval(batch_size)]
        kwargs = {k: aval(v) for k, v in poisons.items()}
        if key is not None or poisons:
            args.append(aval(key))
        params = [{"weights": aval(s["weights"]),
                   "bias": aval(s["bias"])} for s in self._state]
        eval_args = [params, aval(x), aval(target)]
        if self.loss != "softmax":
            eval_args.append(aval(batch_size))
        # shapes only: the table of what each instruction belongs to is
        # built when somebody asks (xla_introspect.instruction_scopes)
        _xla.describe("fused.step", args, kwargs, parts=sorted(
            {part for plan in self._plans
             for part in getattr(plan.forward_cls, "PART_SCOPES", ())}),
            step_scopes=STEP_SCOPES)
        try:
            # pre-compile estimate ONLY: a .compile() here would
            # synchronously rebuild a step that takes minutes on a
            # real chip and log a phantom compile.count entry
            cost = self._step_fn.lower(*args, **kwargs).cost_analysis()
            # forward-only FLOPs from the eval dispatch's lowering (the
            # same layer composition as the step's forward): feeds the
            # live fwd/bwd attribution — bwd.step_ms / bwd.mfu_pct
            # gauges next to mfu_pct (xla_introspect.bwd_snapshot,
            # docs/kernels.md)
            fwd_cost = self._eval_metrics.lower(
                *eval_args).cost_analysis()
        except Exception as exc:
            self.warning("step cost analysis failed, MFU will not be "
                         "published: %s: %s", type(exc).__name__, exc)
            return
        flops = float((cost or {}).get("flops", 0.0))
        fwd_flops = float((fwd_cost or {}).get("flops", 0.0))
        if flops <= 0:
            self.warning("XLA's cost model reports no FLOPs for the "
                         "fused step; MFU will not be published")
            return
        self._step_flops_ = flops
        _xla.set_step_flops(flops)
        if 0 < fwd_flops < flops:
            _xla.set_fwd_flops(fwd_flops)

    def _stage_sharded(self, arr):
        """Stage one minibatch Array onto the mesh THROUGH THE HOST,
        leading dim over ``data_axis``: for a minibatch that is not on
        the mesh already (``_stage``), and the only place that reads one
        back.  Multi-host processes stitch their local slice
        (parallel.shard_host_batch); single-process meshes device_put
        the full batch.  The host buffer is COPIED first: XLA:CPU's
        device_put adopts host memory zero-copy, and the loader refills
        ``mem`` on the next serve (the PR 1 hazard)."""
        from veles_tpu.parallel.api import shard_host_batch
        arr.map_read()
        host = numpy.array(arr.mem)
        self._m_host_staged_.inc(host.nbytes)
        if host.shape[0] % self.mesh.shape[self.data_axis]:
            raise ValueError(
                "minibatch rows %d not divisible by mesh axis %r=%d"
                % (host.shape[0], self.data_axis,
                   self.mesh.shape[self.data_axis]))
        return shard_host_batch(self.mesh, host, self.data_axis)

    def _publish_comm(self, step_seconds):
        """One-time comm receipt (SPMD mode): the exact bucket
        partition the compiled step runs (plan_buckets is
        deterministic) plus the modeled overlap schedule, published as
        ``comm.*`` gauges and per-bucket spans (docs/observability.md).
        ``step_seconds`` is the first clean post-compile step wall."""
        import jax

        from veles_tpu.parallel import bucketed as _bucketed
        self._comm_published_ = True
        try:
            grads_like = [{"weights": s["weights"], "bias": s["bias"]}
                          for s in self._state]
            leaves = jax.tree_util.tree_leaves(grads_like)
            receipt = _bucketed.comm_receipt(
                leaves, self.mesh.shape[self.data_axis],
                bucket_bytes=getattr(self._step_fn, "bucket_bytes",
                                     None),
                step_seconds=step_seconds,
                compress=self.grad_compress)
            _bucketed.publish_comm_receipt(receipt)
            self.info(
                "SPMD comm: %d bucket(s), %.1f MB gradients, modeled "
                "overlap %.1f%%",
                len(receipt["bucket_bytes"]),
                receipt["allreduce_bytes"] / 2.0 ** 20,
                receipt["model"]["overlap_pct"])
        except Exception as exc:
            self.warning("comm receipt unavailable: %s: %s",
                         type(exc).__name__, exc)

    def on_health_sync(self, skips, consec):
        """Health-watchdog hook (decision._health_counters, the
        existing once-per-class device sync): a skipped step while
        bf16 gradient compression is on means the compressed wire
        format may have produced the non-finite — fall back to f32
        (drop the compiled step; the next run() recompiles) rather
        than risk skipping every step of a run that f32 would carry.
        The skipped update itself was already discarded bit-exactly by
        the in-graph guard, so the fallback costs one recompile and
        nothing else (docs/health.md)."""
        if (self.grad_compress is not None
                and skips > self._compress_skips_seen_):
            self.warning(
                "non-finite step under %s gradient compression; "
                "falling back to f32 all-reduce (recompile)",
                self.grad_compress)
            _registry.counter("comm.compress_fallbacks").inc()
            # write the live fused state back into the unit Arrays
            # BEFORE dropping it: the recompile re-extracts from the
            # Arrays, whose old device buffers were donated into the
            # compressed step and no longer exist
            self.sync()
            self.grad_compress = None
            self._step_fn = None
            self._state = None
            self._comm_published_ = False
        self._compress_skips_seen_ = skips
        self.publish_layer_counters()

    def publish_layer_counters(self):
        """Read the layers' counters (one wait for the device) and add
        them to the registry under the names their layer class declares
        (``AUX_COUNTERS``: {the step's metric: the registry's name}): a
        scalar a layer adds up into the counter ``<name>``, a vector a
        layer into ``<name>.l<i>.e<j>`` (element ``j`` of the ``i``-th
        layer that counts it); a float (a layer's loss term) sets the
        gauge ``<name>`` to its sum over the layers, averaged over the
        train steps since the last publish.  Called where the decision
        already syncs (``on_health_sync``, a train class's end) and by
        whoever wants the counts sooner (the benchmark, at its window's
        edges).  Returns what it added."""
        import jax
        counters = getattr(self, "layer_counters", None)
        if not counters:  # also a trainer from before the counters
            return {}
        self.layer_counters = {}
        steps = getattr(self, "_counted_steps_", 0)
        self._counted_steps_ = 0
        names = {}
        for plan in self._plans or ():
            names.update(getattr(plan.forward_cls, "AUX_COUNTERS", {}))
        added = {name: numpy.asarray(value)
                 for name, value in jax.device_get(counters).items()}
        for name, value in added.items():
            target = names.get(name)
            if target is None:
                continue
            if value.dtype.kind == "f":
                _registry.gauge(target).set(
                    float(value.sum()) / max(steps, 1))
                continue
            if value.ndim < 2:  # one scalar a layer
                _registry.counter(target).inc(int(value.sum()))
                continue
            for layer, row in enumerate(value):
                for element, count in enumerate(row):
                    _registry.counter("%s.l%d.e%d" % (
                        target, layer, element)).inc(int(count))
        return added

    def sync(self):
        """Write the fused state back into the unit Arrays (on demand:
        snapshots, plotting, package export)."""
        from veles_tpu.compiler import adopt_state
        if self._state is not None:
            adopt_state(self.sw, self._state, self.device)

    _sync_state_to_units = sync

    def _stage(self, loader):
        """(x, target) on the device: split over the mesh, the
        Prefetcher's already-transferred arrays, or the loader's.  A
        loader that gathers onto the mesh (``Loader.lay_over_mesh``)
        hands over arrays that are split as the step takes them, and
        they pass as they are; any other minibatch goes through the
        host."""
        targets = (loader.minibatch_labels if self.loss == "softmax"
                   else loader.minibatch_targets)
        if self.mesh is not None:
            return tuple(self._on_mesh(arr)
                         for arr in (loader.minibatch_data, targets))
        prefetched = (self._prefetcher.current
                      if self._prefetcher is not None else None)
        if prefetched is not None:
            # pipelined path: the worker already filled + H2D'd this
            # minibatch one step ahead; its device arrays ARE the input
            return prefetched.data, (
                prefetched.labels if self.loss == "softmax"
                else prefetched.targets)
        return (loader.minibatch_data.device_array(self.device),
                targets.device_array(self.device))

    def _on_mesh(self, arr):
        from veles_tpu.parallel.api import batch_sharding
        held = arr.resident()
        if held is not None and held.sharding.is_equivalent_to(
                batch_sharding(self.mesh, self.data_axis), held.ndim):
            return held
        return self._stage_sharded(arr)

    def run(self):
        loader = self.sw.loader
        is_train = loader.minibatch_class == TRAIN
        # one measurement feeds the step histogram, the trace span and
        # the flight ring (which keeps the last N step spans for
        # post-mortem dumps even when full tracing is off)
        with _tracer.scope(
                "fused.train_step" if is_train else "fused.eval_step",
                cat="step", hist=(self._m_train_step_ if is_train
                                  else self._m_eval_step_),
                args={"iteration": self._iteration +
                      (1 if is_train else 0)}) as span:
            if self._step_fn is None:
                self._compile()
            with _tracer.scope("fused.stage", cat="step",
                               hist=self._m_stage_):
                x, target = self._stage(loader)
            batch_size = numpy.float32(loader.minibatch_size)
            if is_train:
                self._train_step(x, target, batch_size)
            else:
                self._eval_step(x, target, batch_size)
            self.n_samples = int(batch_size)
        if not is_train:
            return
        if (self.mesh is not None and not self._comm_published_
                and self._iteration >=
                getattr(self, "_compiled_at_iter_", 0) + 2):
            # the first post-compile step's wall includes the compile;
            # this one is the first clean step time the overlap model
            # can be sized on
            self._publish_comm(span.elapsed)
        self._m_steps_.inc()
        self._m_samples_.inc(self.n_samples)
        if getattr(self, "targets_per_sample", 1) > 1:
            self._m_tokens_.inc(self.n_samples * self.targets_per_sample)
        profiler_step()

    def _train_step(self, x, target, batch_size):
        import jax

        self._iteration += 1
        key = None
        if self._has_dropout:
            key = jax.random.fold_in(
                jax.random.PRNGKey(self._dropout_base_key),
                self._iteration)
        poisons = {}
        if chaos.plan is not None:
            # nan-injection rides INSIDE the jitted step as traced
            # scalars (compiler.py); the healthy path never pays
            for point, kwarg in (("step.grad", "grad_poison"),
                                 ("step.loss", "loss_poison")):
                fault = chaos.plan.fire(point)
                if fault is not None:
                    poisons[kwarg] = numpy.float32(
                        numpy.nan if fault.param is None
                        else fault.param)
        if self._counts_steps:  # one more traced scalar of the step
            poisons["step_count"] = numpy.int32(self._iteration)
        # the call of the compiled program ALONE; in a profiler trace
        # the step annotation groups the device's ops by train step
        with step_annotation("train_step", self._iteration), \
                _tracer.scope("fused.dispatch", cat="step",
                              hist=self._m_dispatch_):
            if key is not None or poisons:
                self._state, metrics = self._step_fn(
                    self._state, x, target, batch_size, key, **poisons)
            else:
                self._state, metrics = self._step_fn(
                    self._state, x, target, batch_size)
        # all lazy device scalars: the decision unit forces the
        # sync once per finished class, so the fused path stays
        # one async dispatch per step
        self.last_loss = metrics["loss"]
        self.n_err = metrics["n_err"]
        self.grad_norm = metrics["grad_norm"]
        self.last_step_finite = metrics["finite"]
        from veles_tpu.models.evaluator import lazy_add, lazy_consec
        self.skip_count = lazy_add(self.skip_count,
                                   metrics["skipped"])
        self.consecutive_skips = lazy_consec(
            self.consecutive_skips, metrics["skipped"])
        if self._layer_metrics is None:  # the first step since compile
            self._layer_metrics = tuple(metrics.keys() - STEP_METRICS)
        for name in self._layer_metrics:
            self.layer_counters[name] = lazy_add(
                self.layer_counters.get(name, 0), metrics[name])
        self._counted_steps_ += 1
        # mse_sum from the step's aux metric matches EvaluatorMSE's
        # definition (per-feature mean, summed over samples); the
        # scalar loss is SSE/batch over ALL elements and would
        # inflate epoch RMSE by sqrt(num_features).  The fallback
        # product only exists inside the conditional — an eager
        # default arg would dispatch one more op per step
        if "mse_sum" in metrics:
            self.mse_sum = metrics["mse_sum"]
        elif self.loss != "softmax":
            self.mse_sum = metrics["loss"] * batch_size
        if self._step_flops_ is None:
            self._publish_step_flops(
                x, target, batch_size, key, poisons)

    def _eval_step(self, x, target, batch_size):
        """Eval minibatch: ONE jitted forward+metrics dispatch, result
        stays lazy on device until class end."""
        params = [{"weights": s["weights"], "bias": s["bias"]}
                  for s in self._state]
        with _tracer.scope("fused.dispatch", cat="step",
                           hist=self._m_eval_dispatch_):
            if self.loss == "softmax":
                self.n_err = self._eval_metrics(params, x, target)
            else:
                self.mse_sum = self._eval_metrics(
                    params, x, target, batch_size)

    def reset_health_counters(self):
        """Zero the skip accounting (after the decision's divergence
        handler finished a rollback, so the next epoch's check starts
        clean)."""
        self.skip_count = 0
        self.consecutive_skips = 0
        self.last_step_finite = True

    def reset_after_rollback(self, rollbacks):
        """Post-rollback reset: drop the compiled step and the fused
        device state so the next run re-reads the (restored) unit
        Arrays AND the (backed-off) gd hyperparameters, and reseed the
        dropout stream — replaying the exact noise that accompanied a
        divergence wastes one retry of the bounded budget."""
        self._step_fn = None
        self._state = None
        self._eval_metrics = None
        # deterministic but distinct per rollback (golden-ratio hash
        # increment keeps streams well separated for small seeds)
        self._dropout_base_key = (
            self._dropout_seed + rollbacks * 0x9E3779B1) & 0x7FFFFFFF
        self.reset_health_counters()

    def __getstate__(self):
        # state lives in the unit Arrays for snapshots
        self._sync_state_to_units()
        state = super(FusedTrainer, self).__getstate__()
        state["_step_fn"] = None
        state["_state"] = None
        state["_eval_metrics"] = None
        state["_plans"] = None
        # a Mesh holds live device handles, so only its AXES pickle;
        # initialize() -> _restore_mesh rebuilds it on resume
        state["mesh"] = None
        state["_spmd_axes_"] = (dict(self.mesh.shape)
                                if self.mesh is not None else None)
        # re-created (and re-attached to the loader) at initialize
        state["_prefetcher"] = None
        # concretize lazy device metrics for the pickle
        state["n_err"] = int(self.n_err)
        state["layer_counters"] = {}
        state["mse_sum"] = float(self.mse_sum)
        if self.last_loss is not None:
            state["last_loss"] = float(self.last_loss)
        state["skip_count"] = int(self.skip_count)
        state["consecutive_skips"] = int(self.consecutive_skips)
        state["last_step_finite"] = bool(self.last_step_finite)
        state["grad_norm"] = (None if self.grad_norm is None
                              else float(self.grad_norm))
        return state


def fuse_standard_workflow(sw, dropout_seed=0, pipeline=False,
                           pipeline_depth=1, mesh=None, data_axis="data",
                           grad_bucket_mb=None, grad_compress=None):
    """Rewire a StandardWorkflow: loader -> FusedTrainer -> decision.

    The forward/GD units stay constructed (they own the param Arrays and
    the snapshot format) but leave the control graph.  ``pipeline=True``
    additionally overlaps host fill + H2D of minibatch k+1 with step k
    (pipeline_input.Prefetcher); it falls back to the synchronous serve
    on devices without real hardware or in distributed modes.

    ``mesh`` switches the trainer to the SPMD data plane: the step
    compiles as shard_map over ``data_axis`` with the bucketed
    overlapped gradient all-reduce (``grad_bucket_mb``, default ~25 MB
    via ``--grad-bucket-mb``; ``grad_compress="bf16"`` via
    ``--grad-compress``).  With a mesh the master-slave protocol
    carries CONTROL records only — per-step gradients ride ICI — so
    the workflow flips to the single-traversal inline update
    validation (docs/distributed.md, ``Workflow.update_validation``),
    and the loader is told the mesh: a dataset resident on the device
    then lives, and is gathered, over ``data_axis``
    (``Loader.lay_over_mesh``).
    """
    from veles_tpu.config import root
    train_cfg = root.common.train
    if grad_bucket_mb is None:
        grad_bucket_mb = train_cfg.get("grad_bucket_mb")
    if grad_compress is None:
        grad_compress = train_cfg.get("grad_compress")
    trainer = FusedTrainer(sw, sw, dropout_seed=dropout_seed,
                           pipeline=pipeline,
                           pipeline_depth=pipeline_depth,
                           mesh=mesh, data_axis=data_axis,
                           grad_bucket_mb=grad_bucket_mb,
                           grad_compress=grad_compress)
    if mesh is not None:
        sw.update_validation = "inline"
        sw.loader.lay_over_mesh(mesh, data_axis)
    # detach the old chain from control flow
    for unit in sw.forwards + [sw.evaluator] + sw.gds:
        unit.unlink_all()
    trainer.link_from(sw.loader)
    sw.decision.link_from(trainer)
    # decision reads its metrics from the trainer now
    sw.decision.evaluator = trainer
    # ...and its numerics-health counters (skip_count /
    # consecutive_skips) from the trainer instead of the severed gds
    sw.decision.health_sources = [trainer]
    snapshotter = getattr(sw, "snapshotter", None)
    if snapshotter is not None:
        # the fused step is atomic, so post-decision state is already
        # quiescent: ride decision -> snapshotter -> repeater (the
        # per-unit graph hangs it off gds[0] instead, which fuse just
        # severed); gate unchanged — once per improved epoch
        snapshotter.unlink_all()
        snapshotter.link_from(sw.decision)
        sw.repeater.link_from(snapshotter)
        sw.end_point.link_from(snapshotter)
        snapshotter.gate_skip = ~(sw.decision.improved &
                                  sw.loader.epoch_ended)
    else:
        sw.repeater.link_from(sw.decision)
    sw.end_point.link_from(sw.decision)
    sw.end_point.gate_block = ~sw.decision.complete
    sw.fused_trainer = trainer
    return trainer
