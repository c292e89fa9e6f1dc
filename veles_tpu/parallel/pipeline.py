"""Pipeline parallelism over a mesh axis (GPipe-style microbatching).

No reference behavior to match (the 2015 platform had only
parameter-server DP); this is a native capability of the parallel
layer.  Stage parameters live stacked with a leading stage dimension
sharded over the ``pipe`` axis — each device holds ONE stage.  The
schedule is the classic skewed wavefront: at tick t, device p runs
microbatch (t - p); activations hop to the next stage via
``lax.ppermute`` over ICI each tick; total ticks = M + P - 1 for M
microbatches over P stages.  Autodiff through the scan gives the
backward pipeline for free (tested against the sequential oracle).

Constraint (classic GPipe): every stage maps activations to the SAME
shape, so the rotating buffer is well-formed — which is exactly the
transformer-block contract ((B, T, D) -> (B, T, D)), making the block
stack the natural stage payload: :func:`build_pipeline_train_step`
splits a transformer model's homogeneous block run into contiguous
stage groups over the axis and keeps the head (and any prefix) layers
replicated, trained off the psum-replicated final activations.  With
``microbatches=1`` every stage executes the EXACT op sequence of the
single-device fused step on the same values (stage hops and the
replication psum move exact bytes; discarded warm-up/drain ticks
contribute exact-zero gradients), so the split step is BIT-IDENTICAL
to the unsplit one — the receipt tests/test_transformer.py pins.
``microbatches>1`` accumulates per-microbatch wgrads inside the scan
(a different f32 grouping than the whole-batch contraction):
documented-ULP-bounded, same as the tensor-parallel bound.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.parallel.mesh import shard_map

__all__ = ["pipeline_forward", "stack_stage_params",
           "stage_param_sharding", "build_pipeline_train_step",
           "stack_pipeline_state", "unstack_pipeline_state"]


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] -> tree with leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *per_stage_params)


def stage_param_sharding(mesh, params_stacked, axis="pipe"):
    """Shard the leading (stage) dimension over the pipe axis."""
    def spec(leaf):
        return NamedSharding(mesh, P(axis))
    return jax.tree.map(
        lambda leaf: jax.device_put(leaf, spec(leaf)), params_stacked)


def pipeline_forward(stage_fn, params_stacked, x, mesh, microbatches,
                     axis="pipe", data_axis=None):
    """Run x (B, ...) through P pipelined stages; returns (B, ...).

    stage_fn(stage_params, activation) -> activation (same shape).
    params_stacked: pytree, leading dim = number of stages, sharded
    over ``axis`` (see stage_param_sharding).
    ``data_axis``: optionally shard the batch dim over a second mesh
    axis — each data-parallel row runs its own wavefront (dp x pp);
    stage params replicate across rows.
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if data_axis is not None:
        rows = mesh.shape[data_axis]
        if batch % rows:
            raise ValueError("batch %d %% %s rows %d != 0" %
                             (batch, data_axis, rows))
        batch //= rows  # per-row batch, as seen inside shard_map
    if batch % microbatches:
        raise ValueError("batch %d %% microbatches %d != 0" %
                         (batch, microbatches))

    def sharded(params_local, x_full):
        # params_local: leading dim 1 (this device's stage)
        p = lax.axis_index(axis)
        my_params = jax.tree.map(lambda l: l[0], params_local)
        result = _wavefront(stage_fn, my_params, x_full, p, axis,
                            n_stages, microbatches, batch)
        # replicate the final activations to every pipe rank
        return lax.psum(
            jnp.where(p == n_stages - 1, result, jnp.zeros_like(result)),
            axis)

    fn = shard_map(
        sharded, mesh=mesh,
        in_specs=(P(axis), P(data_axis)), out_specs=P(data_axis),
        check_vma=False)
    return fn(params_stacked, x)


def _wavefront(stage_fn, my_params, x_full, p, axis, n_stages,
               microbatches, batch):
    """The skewed-wavefront scan shared by :func:`pipeline_forward`
    and the train step: returns the (batch, ...) result as produced on
    the LAST stage (garbage elsewhere — callers mask + replicate).
    Warm-up/drain ticks process finite garbage whose outputs get zero
    cotangents, so their gradient contributions are exact zeros."""
    if batch % microbatches:
        # a clear trace-time error, not a reshape failure deep in jit
        raise ValueError("batch %d %% microbatches %d != 0"
                         % (batch, microbatches))
    mbs = x_full.reshape((microbatches, batch // microbatches) +
                         x_full.shape[1:])
    ticks = microbatches + n_stages - 1
    buf = jnp.zeros_like(mbs[0])
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def tick(buf, t):
        mb_idx = t - p
        inject = mbs[jnp.clip(mb_idx, 0, microbatches - 1)]
        current = jnp.where(p == 0, inject, buf)
        out = stage_fn(my_params, current)
        nxt = lax.ppermute(out, axis, perm)
        return nxt, out

    _, outs = lax.scan(tick, buf, jnp.arange(ticks))
    # last stage emits microbatch m at tick m + (P-1)
    tail = lax.dynamic_slice_in_dim(outs, n_stages - 1, microbatches,
                                    axis=0)
    return tail.reshape((batch,) + x_full.shape[1:])


# -- the pipeline-parallel train step ---------------------------------------


def _stage_split(plans):
    """(prefix, blocks, tail) indices: the contiguous run of
    TransformerBlock plans is the stage payload; everything before /
    after stays replicated."""
    from veles_tpu.models.transformer import TransformerBlock
    flags = [p.forward_cls is TransformerBlock for p in plans]
    if not any(flags):
        raise ValueError("no transformer-block layers to stage-split")
    start = flags.index(True)
    stop = len(flags) - flags[::-1].index(True)
    if not all(flags[start:stop]):
        raise ValueError("transformer blocks must be contiguous for "
                         "the stage split")
    return start, stop


def stack_pipeline_state(mesh, plans, state, axis="pipe"):
    """Host state -> pipeline-placed device state: the block entries
    regroup as ``blocks_per_stage`` entries whose leaves stack a
    leading stage dim sharded over ``axis`` (stack_stage_params'
    layout); prefix/tail entries replicate.  Returns (placed_state,
    layout) where ``layout`` feeds :func:`unstack_pipeline_state`."""
    import numpy

    start, stop = _stage_split(plans)
    n_stages = mesh.shape[axis]
    n_blocks = stop - start
    if n_blocks % n_stages:
        raise ValueError("%d transformer blocks %% %d stages != 0"
                         % (n_blocks, n_stages))
    per_stage = n_blocks // n_stages
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(axis))

    def put_repl(entry):
        return {k: (None if v is None else jax.device_put(v, repl))
                for k, v in entry.items()}

    placed = [put_repl(e) for e in state[:start]]
    for j in range(per_stage):
        # entry j stacks block (stage*per_stage + j) over stages
        rows = [state[start + s * per_stage + j]
                for s in range(n_stages)]
        stacked = {}
        for key in rows[0]:
            if rows[0][key] is None:
                stacked[key] = None
            else:
                stacked[key] = jax.device_put(
                    numpy.stack([numpy.asarray(r[key])
                                 for r in rows]), shard)
        placed.append(stacked)
    placed += [put_repl(e) for e in state[stop:]]
    layout = {"start": start, "stop": stop, "per_stage": per_stage,
              "n_stages": n_stages}
    return placed, layout


def unstack_pipeline_state(placed, layout):
    """Inverse of :func:`stack_pipeline_state` -> global host state."""
    import numpy

    start, per_stage = layout["start"], layout["per_stage"]
    n_stages = layout["n_stages"]

    def host(entry):
        return {k: (None if v is None else numpy.asarray(v))
                for k, v in entry.items()}

    state = [host(e) for e in placed[:start]]
    stacked = placed[start:start + per_stage]
    blocks = []
    for s in range(n_stages):
        for j in range(per_stage):
            entry = stacked[j]
            blocks.append({
                k: (None if v is None else numpy.asarray(v)[s])
                for k, v in entry.items()})
    state += blocks
    state += [host(e) for e in placed[start + per_stage:]]
    return state


def build_pipeline_train_step(plans, loss="softmax", mesh=None,
                              axis="pipe", microbatches=1,
                              donate=True, compiler_options=None):
    """Compile the pipeline-parallel fused train step: the model's
    contiguous transformer-block run splits into ``mesh.shape[axis]``
    contiguous stage groups driven through the shared skewed wavefront
    (:func:`_wavefront`); prefix/tail layers run replicated off the
    stage stack's psum-replicated output.  State must be placed with
    :func:`stack_pipeline_state`.

    The replication step is a psum-forward/identity-backward
    custom_vjp (``parallel.tensor.psum_conjugates``): differentiating
    a plain ``lax.psum`` inside shard_map inflates cotangents by the
    axis size (see parallel/tensor.py), and identity IS the correct
    transpose here — each rank's tail consumes its own replicated
    copy.  The numerics guard psums the stage-shard grad-norm over the
    axis so a poisoned step skips uniformly on every stage.

    Same fixed-arity contract as ``compiler.build_train_step`` with
    ``.lower`` exposed for step-FLOPs introspection."""
    from veles_tpu import compiler as _compiler
    from veles_tpu.parallel.tensor import psum_conjugates

    if mesh is None:
        raise ValueError("build_pipeline_train_step needs a mesh")
    _compiler.refuse_tied_plans(
        plans, "the pipeline step (each stage walks a slice of the "
        "layers)")
    start, stop = _stage_split(plans)
    n_stages = mesh.shape[axis]
    n_blocks = stop - start
    if n_blocks % n_stages:
        raise ValueError("%d transformer blocks %% %d stages != 0"
                         % (n_blocks, n_stages))
    per_stage = n_blocks // n_stages
    block_plans = plans[start:stop]
    for p in block_plans[1:]:
        if p.hyper_full() != block_plans[0].hyper_full() or \
                p.static != block_plans[0].static:
            raise ValueError(
                "stage-split blocks must share hyper/static config "
                "(stacked entries update under one plan)")
    # the step's reduced plan list: one entry per STACKED block slot
    step_plans = (plans[:start] + block_plans[:per_stage] +
                  plans[stop:])
    enter, leave = psum_conjugates(axis)

    def forward_fn(params, x, key, remat):
        p = lax.axis_index(axis)
        prefix, stacked = params[:start], params[start:start +
                                                 per_stage]
        tail = params[start + per_stage:]
        h = x
        if prefix:
            h = _compiler._forward_for_loss(
                plans[:start], prefix, h, key, remat=remat)
            # the wavefront consumes h only on stage 0 (the where-
            # injection), so the raw cotangent reaching the prefix is
            # zero on every other rank; the enter conjugate psums it,
            # making the prefix backward — and thus the 'replicated'
            # prefix updates and their share of the finiteness norm —
            # bit-identical on every rank (the replication invariant
            # out_specs P() promises)
            h = enter(h)
        my_blocks = [jax.tree.map(lambda l: l[0], e) for e in stacked]
        statics = [pl.static for pl in block_plans[:per_stage]]

        def stage_fn(block_params, a):
            from veles_tpu.models.transformer import TransformerBlock
            for bp, static in zip(block_params, statics):
                a = TransformerBlock.apply(bp, a, **static)
            return a

        result = _wavefront(stage_fn, my_blocks, h, p, axis, n_stages,
                            microbatches, h.shape[0])
        h = leave(jnp.where(p == n_stages - 1, result,
                            jnp.zeros_like(result)))
        if tail:
            # fold_offset: dropout layers after the block run must key
            # on their GLOBAL layer index, exactly like the fused step
            h = _compiler._forward_for_loss(
                plans[stop:], tail, h, key, remat=remat,
                fold_offset=stop)
        return h

    staged = set(range(start, start + per_stage))

    def gsq_fn(grads):
        # stage shards see only their own wgrads; the psum makes the
        # guard's norm global so poisoned steps skip on every stage
        from veles_tpu.parallel.tensor import sharded_gsq
        return sharded_gsq(grads, staged, axis)

    raw = _compiler._build_step_fn(step_plans, loss,
                                   forward_fn=forward_fn,
                                   gsq_fn=gsq_fn)

    state_spec = ([P()] * start + [P(axis)] * per_stage +
                  [P()] * (len(plans) - stop))
    spmd = shard_map(
        raw, mesh=mesh,
        in_specs=(state_spec, P(), P(), P(), P(), P(), P()),
        out_specs=(state_spec, P()), check_vma=False)
    return _compiler._finalize_step(
        spmd, donate, compiler_options, mesh=mesh, pipe_axis=axis,
        microbatches=microbatches)
