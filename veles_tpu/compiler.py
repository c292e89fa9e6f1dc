"""Workflow compiler — fuse the training loop body into ONE jitted step.

The reference executed each iteration as a chain of per-unit kernel
launches with host scheduling in between (SURVEY.md section 3.2).  The
TPU-idiomatic replacement: trace the forward units' pure ``apply``
functions, differentiate the loss with ``jax.grad``, and apply the
per-layer solver updates — all inside a single XLA computation, so one
training iteration is one device dispatch with zero host round-trips.

The unit graph stays as orchestration (loader/decision/services); a
:class:`veles_tpu.models.fused.FusedTrainer` unit swaps itself in for the
forward+evaluator+GD chain.  Parity between the fused step and the
per-unit path is covered by tests/test_compiler.py.

Sharding: pass ``mesh`` + ``state_shardings``/``batch_sharding`` and the
step is jitted with those shardings; XLA inserts the ICI collectives
(psum for the data-parallel gradient merge) automatically — the
scaling-book recipe replacing the reference's ZMQ parameter-server data
plane.
"""

import functools

import numpy

from veles_tpu.models.nn_units import GradientDescentBase

__all__ = ["LayerPlan", "build_train_step", "build_train_epoch",
           "build_eval_epoch", "build_forward", "workflow_plan",
           "extract_state", "adopt_state"]


class LayerPlan(object):
    """Static per-layer compile info: forward class, solver, hyper."""

    def __init__(self, forward_cls, solver="momentum", hyper=None,
                 include_bias=True, static=None):
        self.forward_cls = forward_cls
        self.solver = solver
        self.hyper = hyper or {}
        self.include_bias = include_bias
        self.static = static or {}

    def hyper_full(self):
        base = {
            "learning_rate": 0.01, "learning_rate_bias": None,
            "weights_decay": 0.0, "weights_decay_bias": 0.0,
            "l1_vs_l2": 0.0, "gradient_moment": 0.0,
            "gradient_moment_bias": None, "adadelta_rho": 0.95,
            "solver_epsilon": 1e-6,
        }
        base.update(self.hyper)
        if base["learning_rate_bias"] is None:
            base["learning_rate_bias"] = base["learning_rate"]
        if base["gradient_moment_bias"] is None:
            base["gradient_moment_bias"] = base["gradient_moment"]
        return base


def workflow_plan(sw):
    """Extract LayerPlans from a StandardWorkflow."""
    plans = []
    for fwd, gd in zip(sw.forwards, sw.gds):
        plans.append(LayerPlan(
            type(fwd), solver=gd.solver, hyper=gd.hyper_dict(),
            include_bias=fwd.include_bias, static=fwd.static_config()))
    return plans


def extract_state(sw):
    """Pull per-layer param+solver-state pytree out of workflow Arrays."""
    state = []
    for fwd, gd in zip(sw.forwards, sw.gds):
        entry = {}
        for key, arr in (("weights", fwd.weights), ("bias", fwd.bias),
                         ("accum_weights", gd.accum_weights),
                         ("accum_bias", gd.accum_bias),
                         ("accum2_weights", gd.accum2_weights),
                         ("accum2_bias", gd.accum2_bias)):
            entry[key] = arr.devmem if arr else None
        state.append(entry)
    return state


def adopt_state(sw, new_state, device=None):
    """Stage a fused-step result back into the workflow's Arrays.

    The fused step donates its input state buffers (donate_argnums),
    so the Arrays must not keep references to ``new_state``'s leaves —
    the next step would delete them under the Arrays' feet.  Values
    are copied to host with overlapped async transfers and the device
    side detached; host is authoritative afterwards."""
    adopted = []
    for (fwd, gd), entry in zip(zip(sw.forwards, sw.gds), new_state):
        for key, arr in (("weights", fwd.weights), ("bias", fwd.bias),
                         ("accum_weights", gd.accum_weights),
                         ("accum_bias", gd.accum_bias),
                         ("accum2_weights", gd.accum2_weights),
                         ("accum2_bias", gd.accum2_bias)):
            if entry.get(key) is not None and arr:
                arr.set_device_array(entry[key], device or fwd.device)
                adopted.append(arr)
    for arr in adopted:
        arr.prefetch_host()   # start all transfers...
    for arr in adopted:
        arr.detach_device()   # ...then collect, dropping references


def layer_scope(index, plan):
    """``l<index>_<layer type>``: the ``jax.named_scope`` of one layer
    of the plan, as device traces and ``compiled.as_text()`` show it."""
    return "l%d_%s" % (index, plan.forward_cls.__name__)


def tied_plans(plans):
    """{layer index: index of the layer whose ``weights`` it reads} for
    the plans tied to another layer's parameters (``static["tied_to"]``:
    a decoder head on the embedding's table)."""
    return {i: plan.static["tied_to"] for i, plan in enumerate(plans)
            if plan.static.get("tied_to") is not None}


def refuse_tied_plans(plans, who):
    """Raise for a model with tied layers: ``who`` walks a slice of the
    layers, or updates them shard by shard, and would run the tied one
    without the array it shares (or leave its own parameters out)."""
    tied = tied_plans(plans)
    if tied:
        raise ValueError(
            "%s cannot honour tied parameters (layer %s reads the "
            "weights of layer %s): train this model with the "
            "single-device or the data-parallel fused step, or untie it"
            % (who, *next(iter(tied.items()))))


def _forward_for_loss(plans, params, x, key=None, remat=False,
                      layer_fn=None, fold_offset=0, aux=None):
    """Forward pass; returns (pre-softmax logits | final output).

    ``key``: dropout rng; None (inference / keyless step) makes dropout
    layers identity (inverted dropout needs no eval-time rescale).

    ``remat=True`` wraps each layer's apply in ``jax.checkpoint``: the
    backward recomputes the layer forward instead of holding its
    activations live across the whole gradient graph — part of the
    backward-decongestion set (docs/kernels.md).  Recomputation replays
    identical ops, so gradients stay bit-identical; it trades MXU time
    for activation HBM pressure and is off by default.
    ``remat=KEPT_NAMES`` (any tuple of ``checkpoint_name`` names)
    recomputes the same way but keeps what the layer's ops gave those
    names — the flash forward's output and row statistics — so the
    ops that made them are not run again; a layer that names nothing
    lowers as under ``True``.

    ``layer_fn(i, plan, p, h, key)``: optional per-layer override hook
    (the model-parallel builders swap a sharded apply in for specific
    layers); returning None falls through to the stock walk.
    ``fold_offset`` shifts the dropout key-fold index — a caller
    walking a SLICE of a larger model (the pipeline step's tail) must
    key dropout on the global layer index to match the fused step.

    ``aux``: a list that collects ``(layer index, {name: array})`` from
    the layers whose class has ``apply_with_aux`` (a routed layer's
    per-expert load); None leaves them out.

    A plan whose static config names ``tied_to`` (a decoder head tied to
    the embedding's table) gets that layer's ``weights`` beside its own
    parameters, as ``params["tied"]``: ONE array in ``params``, read
    twice, so its gradient is autodiff's sum of both uses.  The walk
    must then hold the whole model (:func:`refuse_tied_plans`).
    """
    from veles_tpu.models.all2all import All2All, All2AllSoftmax
    from veles_tpu.models.dropout import DropoutForward
    import jax

    def layer(fn):
        if not remat:
            return fn
        if remat is True:
            return jax.checkpoint(fn)
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                *remat))

    h = x
    for i, (plan, p) in enumerate(zip(plans, params)):
        tied_to = plan.static.get("tied_to")
        if tied_to is not None:
            p = dict(p, tied=params[tied_to]["weights"])
        # metadata only: the layer's forward ops AND their transposes in
        # the backward carry the scope in ``op_name``; instructions,
        # shapes and numerics are those of the unscoped program
        with jax.named_scope(layer_scope(i + fold_offset, plan)):
            if layer_fn is not None:
                override = layer_fn(i, plan, p, h, key)
                if override is not None:
                    h = override
                    continue
            if plan.forward_cls is All2AllSoftmax:
                # keep logits for a numerically-stable CE
                h = layer(All2All.apply)(p, h)
            elif issubclass(plan.forward_cls, DropoutForward):
                if key is not None:
                    mask = DropoutForward.make_mask(
                        jax.random.fold_in(key, i + fold_offset),
                        h.shape, plan.static.get("dropout_ratio", 0.5),
                        h.dtype)
                    h = h * mask
            elif aux is not None and hasattr(plan.forward_cls,
                                             "apply_with_aux"):
                h, extra = layer(functools.partial(
                    plan.forward_cls.apply_with_aux, **plan.static))(p, h)
                if extra:
                    aux.append((i + fold_offset, extra))
            else:
                h = layer(functools.partial(
                    plan.forward_cls.apply, **plan.static))(p, h)
    return h


def _stack_layer_aux(collected):
    """[(layer, {name: array})] -> {name: array stacked over the layers
    that gave it, in layer order}: what the step adds to its metrics."""
    import jax.numpy as jnp
    names = sorted({name for _, extra in collected for name in extra})
    return {name: jnp.stack([extra[name] for _, extra in collected
                             if name in extra]) for name in names}


def _chain_grad_barriers(grads):
    """Backward-decongestion scheduling hint (docs/kernels.md): thread
    the per-layer gradient dicts through ``lax.optimization_barrier``
    in backward PRODUCTION order (last layer first — its grads exist
    first), so XLA cannot hoist every layer's wgrad to the end of the
    schedule and pile them onto the MXU at once.  The barrier is an
    identity — results are bit-identical with or without the chain
    (tests/test_pallas_bwd.py proves it); only the schedule changes.
    Mirrors parallel/bucketed.py's collective chaining."""
    import jax
    from jax import lax

    barrier = lax.optimization_barrier
    out = list(grads)
    token = None
    for idx in range(len(out) - 1, -1, -1):
        leaves, treedef = jax.tree_util.tree_flatten(out[idx])
        if not leaves:
            continue
        if token is None:
            chained = barrier(tuple(leaves))
        else:
            chained = barrier(tuple(leaves) + (token,))[:-1]
        token = chained[0]
        out[idx] = jax.tree_util.tree_unflatten(treedef, list(chained))
    return out


def build_forward(plans):
    """Pure inference fn(params_list, x) -> output (probs for softmax)."""
    def forward(params, x):
        import jax
        from veles_tpu.models.all2all import All2AllSoftmax
        h = _forward_for_loss(plans, params, x)
        if plans and plans[-1].forward_cls is All2AllSoftmax:
            h = jax.nn.softmax(h, axis=-1)
        return h
    return forward


def _build_step_fn(plans, loss, grad_sync=None, metric_sync=None,
                   row_offset_fn=None, bwd_schedule=None,
                   bwd_remat=False, forward_fn=None, gsq_fn=None,
                   zero_update=None):
    """The raw (unjitted) train-step function shared by
    build_train_step (which jits one minibatch per dispatch) and
    build_train_epoch (which lax.scans it — one dispatch per epoch).

    SPMD hooks (used by the shard_map data plane, None elsewhere):
    ``grad_sync(grads)`` runs right after the backward — the bucketed
    cross-device all-reduce slots in here, BEFORE the numerics guard,
    so a poisoned gradient on ANY shard makes every replica skip the
    same step bit-exactly.  ``metric_sync(scalar)`` globalizes the
    loss/aux scalars (psum over the data axis).  ``row_offset_fn()``
    returns this shard's global row offset so the mse tail mask keys
    on GLOBAL row indices (a short minibatch's padded rows live in the
    last shard).

    Backward decongestion (docs/kernels.md): ``bwd_schedule`` (None ->
    follow the VELES_PALLAS_BWD knob) threads the per-layer gradients
    through an optimization_barrier chain in backward production order
    — a pure scheduling hint, bit-identical results; ``bwd_remat``
    checkpoints each layer's forward to cut activation pressure (True:
    the layer whole; a tuple of ``checkpoint_name`` names: but for what
    its ops gave those names, :func:`_forward_for_loss`).

    Model-parallel hooks (parallel/tensor.py, parallel/pipeline.py):
    ``forward_fn(params, x, key, remat)`` replaces the stock layer walk
    (a tensor-parallel forward slices local shards and psums; a
    pipeline forward runs the stage wavefront) and ``gsq_fn(grads)``
    replaces the flat squared-sum for the numerics guard (sharded
    leaves need a model-axis psum so every shard sees the SAME global
    norm and a poisoned step skips uniformly).

    ZeRO hook (:func:`_build_zero1_spmd_train_step`):
    ``zero_update(state, grads)`` replaces the grad_sync + squared-sum
    + update loop as one unit — the gradient merge (reduce-scatter),
    the sharded solver update, and the param all-gather are coupled,
    and the global grad-norm falls out of the owned shards.  Returns
    ``(new_state, gsq)``; the finiteness guard and the skip-select
    still run here so the skip contract has exactly one definition."""
    import jax
    import jax.numpy as jnp

    if bwd_schedule is None:
        from veles_tpu.ops.common import pallas_bwd_enabled
        bwd_schedule = pallas_bwd_enabled()

    hypers = [p.hyper_full() for p in plans]

    def loss_fn(params, x, target, batch_size, key):
        collected = []
        if forward_fn is not None:
            out = forward_fn(params, x, key, bwd_remat)
        else:
            out = _forward_for_loss(plans, params, x, key,
                                    remat=bwd_remat, aux=collected)
        with jax.named_scope(SCOPE_LOSS):
            value, metric = loss_of(out, target, batch_size)
        return value, (metric, _stack_layer_aux(collected))

    def loss_of(out, target, batch_size):
        if loss == "softmax":
            labels = target
            if out.ndim == 3:
                # (B, T, V) logits against (B, T) next tokens: every
                # position is a sample, the mean is over tokens
                batch_size = batch_size * out.shape[1]
                out = out.reshape(-1, out.shape[-1])
                labels = labels.reshape(-1)
            valid = labels >= 0
            safe = jnp.where(valid, labels, 0)
            logp = jax.nn.log_softmax(out)
            picked = logp[jnp.arange(out.shape[0]), safe]
            total = -jnp.sum(picked * valid)
            pred = jnp.argmax(out, axis=-1)
            n_err = jnp.sum((pred != safe) & valid)
            return total / batch_size, n_err
        # mse
        out2 = out.reshape(out.shape[0], -1)
        t2 = target.reshape(target.shape[0], -1)
        rows = jnp.arange(out2.shape[0])
        if row_offset_fn is not None:
            rows = rows + row_offset_fn()
        mask = (rows < batch_size).astype(out2.dtype)[:, None]
        diff = (out2 - t2) * mask
        # aux: per-sample mean over features, summed over samples — the
        # same definition EvaluatorMSE uses, so train and eval epoch
        # RMSE (DecisionMSE) accumulate commensurate terms
        mse_sum = jnp.sum(jnp.sum(diff * diff, axis=1) / out2.shape[1])
        return jnp.sum(diff * diff) / batch_size, mse_sum

    def step(state, x, target, batch_size, step_key=None,
             grad_poison=None, loss_poison=None, step_count=None):
        params = [{"weights": s["weights"], "bias": s["bias"]}
                  for s in state]
        (loss_value, (aux, layer_aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x, target, batch_size,
                                   step_key)
        # chaos nan-injection (docs/health.md): the poisons are traced
        # scalars, so the injection happens INSIDE the compiled step —
        # exactly where a real numeric fault would appear — and the
        # non-poisoned trace carries zero overhead (poison args are
        # None at trace time on the healthy path)
        if grad_poison is not None:
            grads = jax.tree_util.tree_map(
                lambda g: g + grad_poison.astype(g.dtype), grads)
        if loss_poison is not None:
            loss_value = loss_value + loss_poison
        if bwd_schedule:
            # scheduling hint only — identity on values (see
            # _chain_grad_barriers); sits before the all-reduce so the
            # buckets also issue in production order
            grads = _chain_grad_barriers(grads)
        if grad_sync is not None:
            # SPMD data plane: bucketed all-reduce of the LOCAL grads.
            # Poisons inject before the sync so a chaos fault on one
            # shard spreads (like a real bad chip) and the finiteness
            # guard below skips the step uniformly on every replica.
            # Under its own scope: the merge ends the backward, and a
            # device trace must not book it to the guard that reads it.
            with jax.named_scope(SCOPE_GRAD_SYNC):
                grads = grad_sync(grads)
        if metric_sync is not None:
            loss_value = metric_sync(loss_value)
            aux = metric_sync(aux)
            layer_aux = {name: metric_sync(value)
                         for name, value in layer_aux.items()}

        # numerics guard: one all-isfinite reduction over the loss and
        # the global grad-norm.  A single inf/nan anywhere in the
        # gradients makes the squared-sum non-finite, so isfinite of
        # the norm covers every leaf; both flags stay LAZY device
        # scalars riding the existing metrics result — no host sync
        with jax.named_scope(SCOPE_UPDATE):
            if zero_update is not None:
                # ZeRO-1: reduce-scatter + sharded update + all-gather in
                # one coupled unit; the grad-norm's squared-sum comes back
                # from the owned shards (psum over the data axis, so the
                # skip verdict below is uniform across ranks).  The
                # poisons above inject BEFORE the reduce-scatter, so a
                # fault on one shard still spreads like a real bad chip.
                new_state, gsq = zero_update(state, grads)
            elif gsq_fn is not None:
                gsq = gsq_fn(grads)
            else:
                gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree_util.tree_leaves(grads))
            grad_norm = jnp.sqrt(gsq)
            step_finite = jnp.isfinite(loss_value) & jnp.isfinite(grad_norm)

            new_state = new_state if zero_update is not None else \
                _apply_solver(plans, hypers, state, grads, step_count)
            # a non-finite update is SKIPPED, not applied: every state leaf
            # falls back to its pre-step value, so one poisoned minibatch
            # leaves params (and solver accumulators) bit-identical to
            # never having served it (tests/test_health.py proves equality)
            new_state = [GradientDescentBase.select_state(step_finite,
                                                          entry, old)
                         for entry, old in zip(new_state, state)]
        if loss == "softmax":
            metrics = {"loss": loss_value, "n_err": aux}
        else:
            metrics = {"loss": loss_value,
                       "n_err": jnp.zeros((), jnp.int32),
                       "mse_sum": aux}
        # what the layers count for themselves (a routed layer's
        # per-expert load), stacked over layers: lazy like the rest
        metrics.update(layer_aux)
        metrics["grad_norm"] = grad_norm
        metrics["finite"] = step_finite
        metrics["skipped"] = (~step_finite).astype(jnp.int32)
        return new_state, metrics

    def _apply_solver(plans, hypers, state, grads, step_count=None):
        def solver_grad(plan, decay, hyper, grad, param):
            """(the gradient the solver takes, its extra keywords):
            adamw decays the parameter, not the gradient, and wants the
            count of this step (1 for the first)."""
            grad = grad.astype(param.dtype)
            if plan.solver == "adamw":
                return grad, {"step": step_count, "decay": decay}
            return GradientDescentBase.regularized(
                grad, param, decay, hyper["l1_vs_l2"]), {}

        new_state = []
        for plan, hyper, s, g in zip(plans, hypers, state, grads):
            updates_bias = plan.include_bias and s["bias"] is not None
            if s["weights"] is None and not updates_bias:
                new_state.append(dict(s))  # param-less (pooling, ...)
                continue
            # a tied layer's matrix is another layer's: its gain alone
            new_w, acc_w, acc2_w = None, None, None
            if s["weights"] is not None:
                W = s["weights"]
                gw, extra = solver_grad(plan, hyper["weights_decay"],
                                        hyper, g["weights"], W)
                new_w, acc_w, acc2_w = GradientDescentBase.solver_update(
                    plan.solver, W, gw, s["accum_weights"],
                    s["accum2_weights"], hyper["learning_rate"],
                    hyper["gradient_moment"], hyper["adadelta_rho"],
                    hyper["solver_epsilon"], **extra)
            entry = {"weights": new_w, "accum_weights": acc_w,
                     "accum2_weights": acc2_w,
                     "bias": s["bias"], "accum_bias": s["accum_bias"],
                     "accum2_bias": s["accum2_bias"]}
            if updates_bias:
                b = s["bias"]
                gb, extra = solver_grad(plan, hyper["weights_decay_bias"],
                                        hyper, g["bias"], b)
                new_b, acc_b, acc2_b = GradientDescentBase.solver_update(
                    plan.solver, b, gb, s["accum_bias"], s["accum2_bias"],
                    hyper["learning_rate_bias"],
                    hyper["gradient_moment_bias"], hyper["adadelta_rho"],
                    hyper["solver_epsilon"], **extra)
                entry.update({"bias": new_b, "accum_bias": acc_b,
                              "accum2_bias": acc2_b})
            new_state.append(entry)
        return new_state

    return step


def step_compiler_options():
    """Per-chip XLA options for the fused step, from the autotune DB
    (None when the device kind has no tuned entry — e.g. CPU tests).

    Currently one knob: ``train_step:scoped_vmem_kib`` ->
    ``xla_tpu_scoped_vmem_limit_kib``, shipped per device kind in
    devices/device_infos.json rather than as a blanket flag.  An
    option the installed libtpu does not take fails the step's compile
    — it is not dropped."""
    import jax

    from veles_tpu.backends import DeviceInfo
    vmem = DeviceInfo(jax.devices()[0].device_kind).get(
        "train_step:scoped_vmem_kib")
    if not vmem:
        return None
    return {"xla_tpu_scoped_vmem_limit_kib": str(int(vmem))}


def build_train_step(plans, loss="softmax", mesh=None, data_axis="data",
                     state_shardings=None, batch_sharding=None,
                     donate=True, compiler_options=None,
                     grad_bucket_mb=None, grad_compress=None,
                     grad_allreduce_impl="psum", bwd_schedule=None,
                     bwd_remat=False, zero=None, zero_shards=None):
    """Compile fn(state, x, labels_or_targets, batch_size) ->
    (new_state, metrics).

    state: list of dicts (weights/bias/accum*); metrics: {"loss", "n_err"}
    (classification) or {"loss"} (mse), plus the numerics-health trio
    {"grad_norm", "finite", "skipped"} — all lazy device scalars.  A
    step whose loss or global grad-norm is non-finite does NOT update
    the state (``skipped`` = 1; params and solver accumulators keep
    their pre-step values bit-exactly); see docs/health.md.  The
    optional ``grad_poison`` / ``loss_poison`` keyword scalars are the
    chaos harness's in-graph nan-injection hooks (None costs nothing).
    batch_size is a traced scalar so
    short minibatches don't retrigger compilation.  (B, T, V) logits
    against (B, T) integer targets are the next-token loss: the mean and
    ``n_err`` are over tokens.  The ``adamw`` solver wants the optional
    ``step_count`` keyword (this step's number, from 1; a traced scalar),
    which only the single-device step takes: the shard_map builders do
    not pass it on yet.  Layers with ``apply_with_aux`` add their own
    counters to the metrics, stacked over layers (``moe_load`` ...).
    ``compiler_options``: per-program XLA options (see
    :func:`step_compiler_options` for the tuned per-chip set).

    Distributed variants (docs/distributed.md):

    - ``mesh`` + ``state_shardings``: the annotation (pjit) path — XLA
      inserts the data-parallel gradient psum from the shardings.
    - ``mesh`` + ``grad_bucket_mb``: the SPMD shard_map path — the
      inner loop is explicit per-device code and the gradient merge is
      a BUCKETED all-reduce (parallel/bucketed.py): one collective per
      ~``grad_bucket_mb`` MB of gradients, issued in backward
      production order so the wire time overlaps the remaining
      backward.  ``float("inf")`` means one flat bucket (the
      bit-equality reference).  ``grad_compress="bf16"`` halves the
      wire bytes (numerics-guard + trainer fallback own the risk);
      ``grad_allreduce_impl`` picks ``"psum"`` (default) or ``"ring"``
      (explicit ppermute ring from parallel/ring.py).

    Backward scheduling (docs/kernels.md): ``bwd_schedule`` (None ->
    the VELES_PALLAS_BWD knob) chains per-layer gradients through
    optimization_barriers in backward production order — bit-identical
    values, decongested MXU schedule; ``bwd_remat`` checkpoints layer
    forwards (recompute-over-store).

    ``zero=1`` (with ``mesh``) selects the ZeRO-1 shard_map path
    (docs/distributed.md, "Elastic mesh contract"): the gradient merge
    is a reduce-scatter in backward production order, the solver
    update runs on each device's OWNED shards only (optimizer state —
    the accum leaves — lives sharded over the data axis, ~1/N per
    device), and an all-gather re-replicates the updated params.
    Bit-identical params to the flat all-reduce path on a fixed mesh
    (``psum_scatter`` sums like ``psum``; tests/test_mesh.py); only
    the ``grad_norm`` metric may differ in last-ULP digits (its
    squared-sum associates per-shard).  State must be in ZeRO form
    (:func:`veles_tpu.parallel.mesh.zero_state`): accum leaves shaped
    (n_slots, shard_elems) and a replicated int32 ``zero_slots`` table
    per layer mapping device slots to the ``zero_shards`` logical
    shards (default: one shard per device).  The table is a RUNTIME
    input — moving shards between devices never recompiles.
    """
    import jax

    if zero:
        if int(zero) != 1:
            raise ValueError("only the ZeRO-1 rung is implemented, "
                             "got zero=%r" % (zero,))
        if mesh is None:
            raise ValueError("zero=1 needs a mesh (the optimizer "
                             "state shards over its data axis)")
        if grad_compress:
            raise ValueError("zero=1 does not take grad_compress "
                             "(the reduce-scatter is the wire format)")
        return _build_zero1_spmd_train_step(
            plans, loss, mesh, data_axis,
            zero_shards or mesh.shape[data_axis], donate,
            compiler_options, bwd_schedule, bwd_remat)
    if mesh is not None and grad_bucket_mb is not None:
        return _build_spmd_train_step(
            plans, loss, mesh, data_axis, grad_bucket_mb, grad_compress,
            grad_allreduce_impl, donate, compiler_options,
            bwd_schedule, bwd_remat)

    step = _build_step_fn(plans, loss, bwd_schedule=bwd_schedule,
                          bwd_remat=bwd_remat)

    jit_kwargs = {}
    if compiler_options:
        jit_kwargs["compiler_options"] = compiler_options
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    if mesh is not None and state_shardings is not None:
        # 7-tuple: the optional step_key (dropout PRNG) and the chaos
        # poison scalars all ride replicated.  Everything is passed
        # POSITIONALLY — pjit rejects kwargs once in_shardings is
        # specified — with fixed arity so the spec always matches
        # (None args are empty pytrees)
        jit_kwargs["in_shardings"] = (
            state_shardings, batch_sharding, batch_sharding and
            _labels_sharding(mesh, data_axis, loss), None, None,
            None, None)
        jit_kwargs["out_shardings"] = (state_shardings, None)
        jitted = jax.jit(step, **jit_kwargs)

        def sharded_step(state, x, target, batch_size, step_key=None,
                         grad_poison=None, loss_poison=None):
            return jitted(state, x, target, batch_size, step_key,
                          grad_poison, loss_poison)
        sharded_step.lower = _fixed_arity_lower(jitted)
        sharded_step._cache_size = jitted._cache_size
        return sharded_step
    return jax.jit(step, **jit_kwargs)


def _fixed_arity_lower(jitted):
    """A ``.lower`` for the fixed-arity step wrappers, so callers that
    introspect the compiled program (step-FLOPs publication, the
    collective-bytes receipts) work on the wrapped paths too."""
    def lower(state, x, target, batch_size, step_key=None,
              grad_poison=None, loss_poison=None):
        return jitted.lower(state, x, target, batch_size, step_key,
                            grad_poison, loss_poison)
    return lower


def _finalize_step(fn, donate, compiler_options, **attrs):
    """The ONE jit + fixed-arity-wrapper + ``.lower`` scaffold shared
    by every shard_map step builder (the SPMD path here,
    parallel/tensor.py, parallel/pipeline.py) — extra ``attrs`` land
    on the returned step (mesh, axes, bucket sizes) for callers that
    introspect it."""
    import jax

    jit_kwargs = {}
    if compiler_options:
        jit_kwargs["compiler_options"] = compiler_options
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    jitted = jax.jit(fn, **jit_kwargs)

    def step(state, x, target, batch_size, step_key=None,
             grad_poison=None, loss_poison=None):
        return jitted(state, x, target, batch_size, step_key,
                      grad_poison, loss_poison)
    step.lower = _fixed_arity_lower(jitted)
    # the recompile watcher (observe/xla_introspect.py) reads this
    step._cache_size = jitted._cache_size
    for key, value in attrs.items():
        setattr(step, key, value)
    return step


def _build_spmd_train_step(plans, loss, mesh, data_axis, grad_bucket_mb,
                           grad_compress, grad_allreduce_impl, donate,
                           compiler_options, bwd_schedule=None,
                           bwd_remat=False):
    """The pure-SPMD data plane: shard_map over ``mesh``'s data axis,
    per-device backward on the local batch shard, bucketed gradient
    all-reduce (parallel/bucketed.py), replicated update.  State and
    metrics ride replicated; batch/targets are sharded on the leading
    dim.  Returns the same fixed-arity step the other paths do."""
    import math as _math

    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from veles_tpu.parallel import bucketed as _bucketed
    from veles_tpu.parallel.mesh import shard_map

    n = mesh.shape[data_axis]
    bucket_bytes = (float("inf") if _math.isinf(float(grad_bucket_mb))
                    else float(grad_bucket_mb) * 2.0 ** 20)

    def grad_sync(grads):
        return _bucketed.bucketed_all_reduce(
            grads, data_axis, bucket_bytes=bucket_bytes,
            impl=grad_allreduce_impl, compress=grad_compress,
            axis_size=n)

    def metric_sync(value):
        return lax.psum(value, data_axis)

    def row_offset_fn():
        # recomputed lazily inside the traced step: local row count is
        # not known until the batch shard's shape is
        return lax.axis_index(data_axis) * _local_rows[0]

    _local_rows = [0]
    raw = _build_step_fn(plans, loss, grad_sync=grad_sync,
                         metric_sync=metric_sync,
                         row_offset_fn=row_offset_fn,
                         bwd_schedule=bwd_schedule,
                         bwd_remat=bwd_remat)

    def local_step(state, x, target, batch_size, step_key,
                   grad_poison, loss_poison):
        _local_rows[0] = x.shape[0]
        if step_key is not None:
            # distinct dropout stream per shard: the pjit path draws
            # ONE mask over the global batch; the SPMD shards must not
            # all reuse the same per-row noise
            step_key = jax.random.fold_in(
                step_key, lax.axis_index(data_axis))
        return raw(state, x, target, batch_size, step_key,
                   grad_poison, loss_poison)

    spmd = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis), P(), P(), P(), P()),
        out_specs=(P(), P()), check_vma=False)
    return _finalize_step(spmd, donate, compiler_options, mesh=mesh,
                          data_axis=data_axis,
                          bucket_bytes=bucket_bytes)


def _build_zero1_spmd_train_step(plans, loss, mesh, data_axis, n_shards,
                                 donate, compiler_options,
                                 bwd_schedule=None, bwd_remat=False):
    """The ZeRO-1 shard_map data plane (docs/distributed.md, "Elastic
    mesh contract"): per-device backward on the local batch shard, the
    gradient merge as a chained reduce-scatter in backward production
    order, the solver update on each device's OWNED logical shards
    only (accum leaves live sharded over ``data_axis`` — per-device
    optimizer memory is ~1/N), and an all-gather re-replicating the
    updated params.  Shard placement is the runtime ``zero_slots``
    table (parallel/bucketed.py slot helpers), so the compiled program
    depends on the mesh SIZE but never on which device owns which
    shard — the MeshManager moves shards without recompiling."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from veles_tpu.parallel import bucketed as _bucketed
    from veles_tpu.parallel.mesh import shard_map

    refuse_tied_plans(plans, "the ZeRO-1 step (its update walks the "
                      "layers that own a matrix)")
    n = mesh.shape[data_axis]
    m = int(n_shards)
    k = -(-m // n)  # device slots; table pads with the zero-row id m
    hypers = [p.hyper_full() for p in plans]
    _local_rows = [0]

    def metric_sync(value):
        return lax.psum(value, data_axis)

    def row_offset_fn():
        return lax.axis_index(data_axis) * _local_rows[0]

    # (tensor key, accum keys, hyper keys) — the two per-layer tensors
    # the solver walks, same hyper wiring as the flat update loop
    _TENSORS = (
        ("weights", "accum_weights", "accum2_weights", "learning_rate",
         "gradient_moment", "weights_decay"),
        ("bias", "accum_bias", "accum2_bias", "learning_rate_bias",
         "gradient_moment_bias", "weights_decay_bias"),
    )

    def zero_update(state, grads):
        slots = next(s["zero_slots"] for s in state
                     if s.get("zero_slots") is not None)
        rank = lax.axis_index(data_axis)
        # backward PRODUCTION order (last layer first, weights before
        # bias — grads of a layer exist together), so each
        # reduce-scatter can issue while earlier layers' backward runs
        jobs = []
        for idx in range(len(plans) - 1, -1, -1):
            s = state[idx]
            if s["weights"] is None:
                continue
            jobs.append((idx, "weights"))
            if plans[idx].include_bias and s["bias"] is not None:
                jobs.append((idx, "bias"))
        mats = []
        for idx, tensor in jobs:
            g = grads[idx][tensor]
            e = _bucketed.shard_elems(g.size, m)
            mats.append(_bucketed.slot_matrix(g, slots, m, e))
        parts = _bucketed.chained_reduce_scatter(mats, data_axis)
        shard_of = dict(zip(jobs, parts))
        # global grad-norm from the owned shards: every element of the
        # summed gradient lives in exactly one shard (pad rows are
        # zero), so the psum'd squared-sum covers every leaf and the
        # skip verdict is uniform across ranks — association differs
        # from the flat path's, so grad_norm may differ in last ULPs
        gsq = lax.psum(
            sum(jnp.sum(jnp.square(p.astype(jnp.float32)))
                for p in parts), data_axis)
        my_slots = lax.dynamic_slice(slots, (rank * k,), (k,))
        new_state = []
        for idx, s in enumerate(state):
            if s["weights"] is None:  # param-less layer passthrough
                new_state.append(dict(s))
                continue
            plan, hyper = plans[idx], hypers[idx]
            entry = dict(s)
            for (tensor, acc_key, acc2_key, lr_key, mom_key,
                 dec_key) in _TENSORS:
                g_my = shard_of.get((idx, tensor))
                if g_my is None:
                    continue
                w = s[tensor]
                e = _bucketed.shard_elems(w.size, m)
                w_rows = _bucketed.slot_matrix(w, slots, m, e)
                w_my = lax.dynamic_slice(w_rows, (rank * k, 0), (k, e))
                gw = GradientDescentBase.regularized(
                    g_my.astype(w.dtype), w_my, hyper[dec_key],
                    hyper["l1_vs_l2"])
                # elementwise solver with per-layer SCALAR hypers: the
                # sharded update is the full-tensor update restricted
                # to owned elements — bit-identical per element
                new_my, new_acc, new_acc2 = \
                    GradientDescentBase.solver_update(
                        plan.solver, w_my, gw, s[acc_key], s[acc2_key],
                        hyper[lr_key], hyper[mom_key],
                        hyper["adadelta_rho"], hyper["solver_epsilon"])
                w_all = _bucketed.gather_slots(new_my, data_axis)
                entry[tensor] = _bucketed.unslot_matrix(
                    w_all, slots, m, w.size, w.shape, w.dtype)
                entry[acc_key] = new_acc
                entry[acc2_key] = new_acc2
            new_state.append(entry)
        return new_state, gsq

    raw = _build_step_fn(plans, loss, metric_sync=metric_sync,
                         row_offset_fn=row_offset_fn,
                         bwd_schedule=bwd_schedule, bwd_remat=bwd_remat,
                         zero_update=zero_update)

    def local_step(state, x, target, batch_size, step_key,
                   grad_poison, loss_poison):
        _local_rows[0] = x.shape[0]
        if step_key is not None:
            step_key = jax.random.fold_in(
                step_key, lax.axis_index(data_axis))
        return raw(state, x, target, batch_size, step_key,
                   grad_poison, loss_poison)

    _SHARDED = ("accum_weights", "accum_bias", "accum2_weights",
                "accum2_bias")

    def state_specs(state):
        # accum leaves ride sharded on the leading (slot) dim; params,
        # slot tables and None leaves ride replicated.  Built from the
        # traced state at trace time, so the one builder serves any
        # solver's state structure
        return [{key: (None if value is None else
                       P(data_axis) if key in _SHARDED else P())
                 for key, value in entry.items()} for entry in state]

    def spmd_fn(state, x, target, batch_size, step_key, grad_poison,
                loss_poison):
        specs = state_specs(state)
        fn = shard_map(
            local_step, mesh=mesh,
            in_specs=(specs, P(data_axis), P(data_axis), P(), P(), P(),
                      P()),
            out_specs=(specs, P()), check_vma=False)
        return fn(state, x, target, batch_size, step_key, grad_poison,
                  loss_poison)

    return _finalize_step(spmd_fn, donate, compiler_options, mesh=mesh,
                          data_axis=data_axis, zero=1, n_shards=m,
                          slots_per_device=k)


def _labels_sharding(mesh, data_axis, loss):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(data_axis))


def _tail_schedule(order, batch, what):
    """Static tail plan shared by the train/eval epoch scans:
    ceil-div step count, edge-padded order (padded slots are masked
    out by the callers), per-step valid-row counts."""
    import jax.numpy as jnp

    n = order.shape[0]
    n_steps = -(-n // batch)
    if n_steps == 0:
        # a zero-iteration scan would return empty metrics with the
        # state silently unchanged
        raise ValueError("%s: order is empty (batch %d)" % (what, batch))
    pad = n_steps * batch - n
    if pad:
        order = jnp.pad(order, (0, pad), mode="edge")
    sizes = jnp.full((n_steps,), batch, jnp.int32)
    if pad:
        sizes = sizes.at[n_steps - 1].set(batch - pad)
    return order, sizes, n_steps, n


def _epoch_gathers(dataset, targets, loss):
    """(idx -> x, idx -> y) for an epoch scan's body.  The row stores
    (ops/gather.py) are built HERE, once an epoch and outside the scan:
    a pass over each table, which the body must not repeat per step."""
    from veles_tpu.ops import gather

    def rows(table):
        return functools.partial(
            gather.gather_rows, gather.build_store(table),
            sample_shape=table.shape[1:])

    if loss == "softmax":
        return rows(dataset), functools.partial(
            gather.gather_labels, gather.build_label_store(targets))
    return rows(dataset), rows(targets)


def build_train_epoch(plans, batch, loss="softmax", donate=True,
                      compiler_options=None):
    """Compile fn(state, dataset, targets, order, key=None) ->
    (new_state, epoch_metrics): the WHOLE epoch as one XLA dispatch.

    ``lax.scan`` walks ``order`` in ``batch``-sized windows, gathering
    each minibatch from the HBM-resident dataset (Pallas gather) and
    applying the same train step build_train_step compiles — so on a
    dispatch-bound model (small MLPs) per-step cost collapses to pure
    compute.  The per-step path remains the product default because
    the decision unit gates per minibatch; this is the turbo path for
    epoch-granular control (no Workflow reaches it:
    ``examples/digits_turbo.py`` and ``tests/test_epoch.py`` are its
    callers).

    ``targets``: int labels (softmax) or a float target array indexed
    like the dataset (mse).  ``order`` (int32 (N,)) defines epoch
    order; ceil(N / batch) steps run — a tail shorter than ``batch``
    executes as one masked step (padded slots carry sentinel labels /
    zeroed residuals, so they contribute nothing to gradients or
    metrics), giving exact N-sample coverage like the unit path.
    metrics: {"loss_mean", "n_err"} (+"mse_sum" for mse); loss_mean is
    the sample-weighted epoch mean.
    """
    import jax
    import jax.numpy as jnp

    step = _build_step_fn(plans, loss)

    def epoch(state, dataset, targets, order, key=None):
        order, sizes, n_steps, n = _tail_schedule(
            order, batch, "build_train_epoch")
        sizes = sizes.astype(jnp.float32)  # step's batch_size arg
        gather_x, gather_y = _epoch_gathers(dataset, targets, loss)

        def body(carry, scans):
            st = carry
            i, size = scans
            idx = jax.lax.dynamic_slice(order, (i * batch,), (batch,))
            x = gather_x(idx)
            y = gather_y(idx)
            if loss == "softmax":
                # padded slots -> sentinel label: excluded from the CE
                # sum, n_err, and gradients by the loss's valid mask
                # (mse loss masks rows >= batch_size itself)
                y = jnp.where(jnp.arange(batch) < size, y, -1)
            k = None if key is None else jax.random.fold_in(key, i)
            st, m = step(st, x, y, size, k)
            return st, m

        state, ms = jax.lax.scan(body, state,
                                 (jnp.arange(n_steps), sizes))
        totals = {"loss_mean": jnp.sum(ms["loss"] * sizes) / n,
                  "n_err": ms["n_err"].sum(),
                  # steps whose update the numerics guard refused to
                  # apply (non-finite loss/grads); callers treat > 0 as
                  # a health signal (docs/health.md)
                  "skipped": ms["skipped"].sum()}
        if "mse_sum" in ms:
            totals["mse_sum"] = ms["mse_sum"].sum()
        return state, totals

    jit_kwargs = {"donate_argnums": (0,)} if donate else {}
    if compiler_options:
        jit_kwargs["compiler_options"] = compiler_options
    return jax.jit(epoch, **jit_kwargs)


def build_eval_epoch(plans, batch, loss="softmax",
                     compiler_options=None):
    """Compile fn(params, dataset, targets, order) -> metrics: the
    whole evaluation pass as one XLA dispatch.

    The eval twin of :func:`build_train_epoch` — scans ``order`` in
    ``batch``-sized windows, gathers each minibatch, runs the forward
    (dropout layers are identity at eval), and accumulates metrics on
    device: {"n_err", "samples"} for softmax, {"mse_sum", "samples"}
    for mse (same definitions the evaluator units use, so epoch error
    rates and RMSE are commensurate with the unit path).  ``params``
    is the [{"weights", "bias"}] list build_forward consumes.  A tail
    shorter than ``batch`` runs as one masked step, so metrics cover
    all N samples exactly; ``samples`` counts the rows that actually
    entered the metric (valid labels for softmax), making
    n_err/samples an undiluted error rate even with sentinel labels.
    """
    import jax
    import jax.numpy as jnp

    def epoch(params, dataset, targets, order):
        order, sizes, n_steps, _ = _tail_schedule(
            order, batch, "build_eval_epoch")
        gather_x, gather_y = _epoch_gathers(dataset, targets, loss)

        def body(carry, scans):
            total, count = carry
            i, size = scans
            idx = jax.lax.dynamic_slice(order, (i * batch,), (batch,))
            x = gather_x(idx)
            out = _forward_for_loss(plans, params, x)
            slot = jnp.arange(batch) < size
            if loss == "softmax":
                y = gather_y(idx)
                valid = (y >= 0) & slot
                pred = jnp.argmax(out, axis=-1)
                m = jnp.sum((pred != y) & valid).astype(jnp.int32)
                c = jnp.sum(valid).astype(jnp.int32)
            else:
                t = gather_y(idx)
                diff = (out.reshape(out.shape[0], -1)
                        - t.reshape(t.shape[0], -1))
                diff = diff * slot[:, None].astype(diff.dtype)
                m = jnp.sum(jnp.mean(diff * diff, axis=1))
                c = size
            return (total + m, count + c), None

        init = ((jnp.zeros((), jnp.int32) if loss == "softmax"
                 else jnp.zeros((), jnp.float32)),
                jnp.zeros((), jnp.int32))
        (total, count), _ = jax.lax.scan(
            body, init, (jnp.arange(n_steps), sizes))
        name = "n_err" if loss == "softmax" else "mse_sum"
        return {name: total, "samples": count}

    return jax.jit(epoch, compiler_options=compiler_options or None)


#: the step's own ``jax.named_scope`` names beside the layers', each with
#: the phase of the step everything under it is (``xla_introspect.
#: scope_of``); None: the loss is differentiated, its wrappers say.
#: (At the END of the file: a Pallas kernel's serialized body holds the
#: line numbers of the frames that called it, so a line added above
#: ``_forward_for_loss`` changes the lowered step's text.)
SCOPE_LOSS = "loss"
SCOPE_GRAD_SYNC = "grad_sync"
SCOPE_UPDATE = "update"
STEP_SCOPES = {SCOPE_LOSS: None, SCOPE_GRAD_SYNC: "backward",
               SCOPE_UPDATE: "update"}
