"""Tensor parallelism: transformer blocks sharded over a ``model``
mesh axis (Megatron-style head/column splits).

Going past pure data-parallel for models that don't fit one chip
("TensorFlow: A system for large-scale machine learning", PAPERS.md):
attention heads shard over the axis (each device projects, attends and
output-projects ITS heads), the position-wise MLP column-splits W1 /
row-splits W2 — so each block pays exactly TWO activation psums per
direction (one per sub-layer), placed on the residual trunk where they
compose with the bucketed data-axis gradient plane
(parallel/bucketed.py): activation psums ride the ``model`` axis inside
the step, gradient buckets ride the ``data`` axis after the backward,
and the numerics guard sees the model-axis-psummed global grad norm so
a poisoned step skips uniformly on every shard.

Autodiff caveat (empirically pinned, tests/test_transformer.py): with
``check_vma=False``, differentiating THROUGH ``lax.psum`` inside
``shard_map`` multiplies cotangents by the axis size (the documented
psum-transpose asymmetry).  The forward therefore uses the conjugate
custom_vjp pair :func:`psum_conjugates` — ``enter`` (identity forward /
psum backward) where a replicated activation enters a sharded region,
``leave`` (psum forward / identity backward) where partial results
merge — the f/g operators of the Megatron formulation, which make every
parameter gradient correct by construction: sharded params get their
complete local slice gradients, replicated params get bit-identical
full gradients on every model rank.

Parity contract: the TP step is ULP-BOUNDED against the single-device
fused step (the output projection becomes a psum of per-shard partial
contractions — a different f32 reduction grouping), receipted by the
3-chained-step bound in tests/test_parallel_transformer.py; a 1-sized
model axis stays within absolute float noise (only program-structure
fusion differences remain).
"""

import functools

import numpy

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.models.transformer import (TransformerBlock,
                                          _unpack, layer_norm)
from veles_tpu.parallel.mesh import shard_map

__all__ = ["psum_conjugates", "sharded_gsq",
           "block_param_sizes_local", "split_block_shards",
           "merge_block_shards", "place_tp_state", "gather_tp_state",
           "tp_block_apply", "build_tp_train_step"]


@functools.lru_cache(maxsize=None)
def psum_conjugates(axis):
    """(enter, leave): the Megatron f/g conjugate pair for ``axis``.

    ``enter`` — identity forward, psum backward: wraps a REPLICATED
    activation entering a sharded region, so the partial cotangents the
    region produces merge back into the full gradient.
    ``leave`` — psum forward, identity backward: merges the region's
    partial outputs; the replicated cotangent passes through unchanged
    (each shard's partial has coefficient 1 in the sum).
    """

    @jax.custom_vjp
    def enter(x):
        return x

    def enter_fwd(x):
        return x, None

    def enter_bwd(_, ct):
        return (lax.psum(ct, axis),)

    enter.defvjp(enter_fwd, enter_bwd)

    @jax.custom_vjp
    def leave(x):
        return lax.psum(x, axis)

    def leave_fwd(x):
        return lax.psum(x, axis), None

    def leave_bwd(_, ct):
        return (ct,)

    leave.defvjp(leave_fwd, leave_bwd)
    return enter, leave


def sharded_gsq(grads, sharded, axis):
    """The model-parallel numerics-guard norm: squared-sum of the
    gradient leaves with the SHARDED entries (``sharded`` = set of
    layer indices whose leaves live sliced on this rank) psummed over
    ``axis``, so every shard computes the SAME global norm and a
    poisoned step skips uniformly.  Replicated entries add locally —
    their leaves are bit-identical across ranks by construction.  One
    definition, shared by the TP and pipeline step builders."""
    shard_sq = jnp.zeros((), jnp.float32)
    repl_sq = jnp.zeros((), jnp.float32)
    for i, g in enumerate(grads):
        for leaf in jax.tree_util.tree_leaves(g):
            sq = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
            if i in sharded:
                shard_sq = shard_sq + sq
            else:
                repl_sq = repl_sq + sq
    return lax.psum(shard_sq, axis) + repl_sq


# -- packed-layout shard plumbing -------------------------------------------


def block_param_sizes_local(d, hidden, n_shards):
    """Per-shard (name, shape) layout of one TP transformer block —
    the local counterpart of ``transformer.block_param_sizes``:
    Wq/Wk/Wv keep their head-slice columns, Wo its head-slice rows,
    W1 its hidden columns, W2 its hidden rows; LN gains and the
    post-psum biases (b_o, b2) replicate."""
    dl, hl = d // n_shards, hidden // n_shards
    weights = [("ln1_gamma", (d,)), ("w_qkv", (d, 3 * dl)),
               ("w_o", (dl, d)), ("ln2_gamma", (d,)),
               ("w1", (d, hl)), ("w2", (hl, d))]
    bias = [("ln1_beta", (d,)), ("b_qkv", (3 * dl,)), ("b_o", (d,)),
            ("ln2_beta", (d,)), ("b1", (hl,)), ("b2", (d,))]
    return weights, bias


def _pack(pieces, layout):
    return numpy.concatenate(
        [numpy.asarray(pieces[name]).ravel() for name, _ in layout])


def split_block_shards(weights, bias, d, heads, hidden, n_shards):
    """Global packed (weights, bias) -> (n_shards, L_local) stacked
    arrays, head-aligned: shard s owns heads [s*H/n, (s+1)*H/n)."""
    from veles_tpu.models.transformer import split_block_params
    if heads % n_shards or hidden % n_shards:
        raise ValueError("heads %d / hidden %d not divisible by "
                         "model shards %d" % (heads, hidden, n_shards))
    wp, bp = split_block_params(numpy.asarray(weights),
                                numpy.asarray(bias), d, hidden)
    dl, hl = d // n_shards, hidden // n_shards
    layout_w, layout_b = block_param_sizes_local(d, hidden, n_shards)
    w_rows, b_rows = [], []
    wq, wk, wv = (wp["w_qkv"][:, :d], wp["w_qkv"][:, d:2 * d],
                  wp["w_qkv"][:, 2 * d:])
    bq, bk, bv = bp["b_qkv"][:d], bp["b_qkv"][d:2 * d], bp["b_qkv"][2 * d:]
    for s in range(n_shards):
        cols = slice(s * dl, (s + 1) * dl)
        hcols = slice(s * hl, (s + 1) * hl)
        w_rows.append(_pack({
            "ln1_gamma": wp["ln1_gamma"],
            "w_qkv": numpy.concatenate(
                [wq[:, cols], wk[:, cols], wv[:, cols]], axis=1),
            "w_o": wp["w_o"][cols, :],
            "ln2_gamma": wp["ln2_gamma"],
            "w1": wp["w1"][:, hcols],
            "w2": wp["w2"][hcols, :],
        }, layout_w))
        b_rows.append(_pack({
            "ln1_beta": bp["ln1_beta"],
            "b_qkv": numpy.concatenate([bq[cols], bk[cols], bv[cols]]),
            "b_o": bp["b_o"],
            "ln2_beta": bp["ln2_beta"],
            "b1": bp["b1"][hcols],
            "b2": bp["b2"],
        }, layout_b))
    return numpy.stack(w_rows), numpy.stack(b_rows)


def merge_block_shards(w_stacked, b_stacked, d, heads, hidden):
    """Inverse of :func:`split_block_shards`: (n, L_local) stacks back
    to the global packed (weights, bias).  Replicated pieces (LN
    gains/betas, b_o, b2) are taken from shard 0 — the TP step keeps
    them bit-identical across shards by construction."""
    from veles_tpu.models.transformer import block_param_sizes
    n = w_stacked.shape[0]
    dl, hl = d // n, hidden // n
    layout_w, layout_b = block_param_sizes_local(d, hidden, n)
    locals_w = [_unpack(numpy.asarray(w_stacked[s]), layout_w)
                for s in range(n)]
    locals_b = [_unpack(numpy.asarray(b_stacked[s]), layout_b)
                for s in range(n)]
    wq = numpy.concatenate([lw["w_qkv"][:, :dl] for lw in locals_w], 1)
    wk = numpy.concatenate([lw["w_qkv"][:, dl:2 * dl]
                            for lw in locals_w], 1)
    wv = numpy.concatenate([lw["w_qkv"][:, 2 * dl:]
                            for lw in locals_w], 1)
    merged_w = {
        "ln1_gamma": locals_w[0]["ln1_gamma"],
        "w_qkv": numpy.concatenate([wq, wk, wv], axis=1),
        "w_o": numpy.concatenate([lw["w_o"] for lw in locals_w], 0),
        "ln2_gamma": locals_w[0]["ln2_gamma"],
        "w1": numpy.concatenate([lw["w1"] for lw in locals_w], 1),
        "w2": numpy.concatenate([lw["w2"] for lw in locals_w], 0),
    }
    merged_b = {
        "ln1_beta": locals_b[0]["ln1_beta"],
        "b_qkv": numpy.concatenate(
            [numpy.concatenate([lb["b_qkv"][i * dl:(i + 1) * dl]
                                for lb in locals_b])
             for i in range(3)]),
        "b_o": locals_b[0]["b_o"],
        "ln2_beta": locals_b[0]["ln2_beta"],
        "b1": numpy.concatenate([lb["b1"] for lb in locals_b]),
        "b2": locals_b[0]["b2"],
    }
    layout_gw, layout_gb = block_param_sizes(d, hidden)
    return _pack(merged_w, layout_gw), _pack(merged_b, layout_gb)


def _tp_plan(plan):
    return plan.forward_cls is TransformerBlock


def place_tp_state(mesh, plans, state, model_axis="model"):
    """Host state -> TP-placed device state: transformer-block entries
    split per shard and stacked (n, L_local) with the leading dim over
    ``model_axis`` (the pipeline stack_stage_params idiom); everything
    else replicates over the whole mesh."""
    n = mesh.shape[model_axis]
    shard = NamedSharding(mesh, P(model_axis))
    repl = NamedSharding(mesh, P())
    placed = []
    for plan, entry in zip(plans, state):
        if not _tp_plan(plan):
            placed.append({k: (None if v is None
                               else jax.device_put(v, repl))
                           for k, v in entry.items()})
            continue
        heads = plan.static["heads"]
        hidden = plan.static["hidden"]
        d = _packed_d(int(numpy.prod(numpy.shape(entry["weights"]))),
                      hidden)
        out = {}
        for wkey, bkey in (("weights", "bias"),
                           ("accum_weights", "accum_bias"),
                           ("accum2_weights", "accum2_bias")):
            wv, bv = entry.get(wkey), entry.get(bkey)
            if wv is None:
                out[wkey], out[bkey] = None, None
                continue
            ws, bs = split_block_shards(wv, bv, d, heads, hidden, n)
            out[wkey] = jax.device_put(ws, shard)
            out[bkey] = jax.device_put(bs, shard)
        placed.append(out)
    return placed


def gather_tp_state(plans, tp_state):
    """TP-placed state back to global host state (for adoption,
    snapshots, and the parity receipts)."""
    merged = []
    for plan, entry in zip(plans, tp_state):
        if not _tp_plan(plan):
            merged.append({k: (None if v is None else numpy.asarray(v))
                           for k, v in entry.items()})
            continue
        heads = plan.static["heads"]
        hidden = plan.static["hidden"]
        ws = numpy.asarray(entry["weights"])
        n = ws.shape[0]
        d = _packed_d(ws.shape[1], hidden, local=True, n=n)
        out = {}
        for wkey, bkey in (("weights", "bias"),
                           ("accum_weights", "accum_bias"),
                           ("accum2_weights", "accum2_bias")):
            wv, bv = entry.get(wkey), entry.get(bkey)
            if wv is None:
                out[wkey], out[bkey] = None, None
                continue
            gw, gb = merge_block_shards(
                numpy.asarray(wv), numpy.asarray(bv), d, heads, hidden)
            out[wkey], out[bkey] = gw, gb
        merged.append(out)
    return merged


def _packed_d(packed_len, hidden, local=False, n=1):
    """Solve the packed length for the feature dim d.

    Global: L = 2d + 4d^2 + 2*d*hidden.
    Local (per shard): L = 2d + d*(3d/n) + (d/n)*d + d*h/n + (h/n)*d
                         = 2d + 4d^2/n + 2*d*hidden/n.
    """
    for d in range(1, 1 << 16):
        if local:
            if n * (2 * d) + 4 * d * d + 2 * d * hidden == \
                    packed_len * n:
                return d
        elif 2 * d + 4 * d * d + 2 * d * hidden == packed_len:
            return d
    raise ValueError("packed length %d matches no feature dim"
                     % packed_len)


# -- the sharded forward -----------------------------------------------------


def tp_block_apply(w_local, b_local, x, *, heads, hidden, n_shards,
                   axis, eps=1e-5, pallas_bwd=None):
    """One pre-LN block over LOCAL packed params: LN and residuals run
    replicated; QKV/attention/W1 run on this shard's heads/columns via
    the SAME sub-layer cores the single-device block uses
    (``transformer.attention_heads`` / ``position_wise_mlp`` — one
    definition, the shard passes its column/row slices and local head
    count); the two ``leave`` psums merge the output projections and
    the post-psum biases (b_o, b2) add replicated.  The conjugate ops
    make the backward correct (module docstring)."""
    from veles_tpu.models.transformer import (attention_heads,
                                              position_wise_mlp)
    d = x.shape[-1]
    heads_l = heads // n_shards
    layout_w, layout_b = block_param_sizes_local(d, hidden, n_shards)
    wp = _unpack(w_local, layout_w)
    bp = _unpack(b_local, layout_b)
    enter, leave = psum_conjugates(axis)

    ln1 = layer_norm(x, wp["ln1_gamma"], bp["ln1_beta"], eps)
    o = attention_heads(enter(ln1), wp["w_qkv"], bp["b_qkv"], heads_l,
                        pallas_bwd)
    partial = jnp.einsum("btf,fg->btg", o, wp["w_o"],
                         preferred_element_type=jnp.float32)
    attn = leave(partial) + bp["b_o"]
    h = x + attn.astype(x.dtype)

    ln2 = layer_norm(h, wp["ln2_gamma"], bp["ln2_beta"], eps)
    part2 = position_wise_mlp(enter(ln2), wp["w1"], bp["b1"],
                              wp["w2"])
    return (h + (leave(part2) + bp["b2"]).astype(x.dtype)).astype(
        x.dtype)


def build_tp_train_step(plans, loss="softmax", mesh=None,
                        model_axis="model", data_axis=None,
                        grad_bucket_mb=None, grad_compress=None,
                        grad_allreduce_impl="psum", donate=True,
                        compiler_options=None):
    """Compile the tensor-parallel fused train step: shard_map over
    ``mesh`` with transformer-block entries stacked (n, L_local) over
    ``model_axis`` (see :func:`place_tp_state`) and, when ``data_axis``
    is given, the batch sharded over it with the BUCKETED gradient
    all-reduce (parallel/bucketed.py) merging grads across data rows —
    activation psums on the model axis, gradient buckets on the data
    axis, one shard_map program.

    Same fixed-arity contract as ``compiler.build_train_step``:
    fn(state, x, target, batch_size, step_key=None, grad_poison=None,
    loss_poison=None) -> (new_state, metrics), with ``.lower`` exposed
    for step-FLOPs introspection (live MFU attribution)."""
    import math as _math

    from veles_tpu import compiler as _compiler
    from veles_tpu.parallel import bucketed as _bucketed

    if mesh is None:
        raise ValueError("build_tp_train_step needs a mesh")
    _compiler.refuse_tied_plans(plans, "the tensor-parallel step")
    n = mesh.shape[model_axis]
    tp_flags = [_tp_plan(p) for p in plans]
    if not any(tp_flags):
        raise ValueError("no transformer-block layers to shard over "
                         "%r" % model_axis)

    grad_sync = metric_sync = row_offset_fn = None
    _local_rows = [0]
    if data_axis is not None:
        bucket_bytes = (
            float("inf") if grad_bucket_mb is None
            or _math.isinf(float(grad_bucket_mb))
            else float(grad_bucket_mb) * 2.0 ** 20)

        def grad_sync(grads):
            return _bucketed.bucketed_all_reduce(
                grads, data_axis, bucket_bytes=bucket_bytes,
                impl=grad_allreduce_impl, compress=grad_compress,
                axis_size=mesh.shape[data_axis])

        def metric_sync(value):
            return lax.psum(value, data_axis)

        def row_offset_fn():
            return lax.axis_index(data_axis) * _local_rows[0]

    tp_indices = {i for i, flag in enumerate(tp_flags) if flag}

    def gsq_fn(grads):
        return sharded_gsq(grads, tp_indices, model_axis)

    def layer_fn(i, plan, p, h, key):
        if not tp_flags[i]:
            return None  # default layer walk
        return tp_block_apply(
            p["weights"][0], p["bias"][0], h,
            heads=plan.static["heads"], hidden=plan.static["hidden"],
            n_shards=n, axis=model_axis,
            eps=plan.static.get("eps", 1e-5))

    def forward_fn(params, x, key, remat):
        return _compiler._forward_for_loss(plans, params, x, key,
                                           remat=remat,
                                           layer_fn=layer_fn)

    raw = _compiler._build_step_fn(
        plans, loss, grad_sync=grad_sync, metric_sync=metric_sync,
        row_offset_fn=row_offset_fn, forward_fn=forward_fn,
        gsq_fn=gsq_fn)

    def local_step(state, x, target, batch_size, step_key,
                   grad_poison, loss_poison):
        _local_rows[0] = x.shape[0]
        if step_key is not None and data_axis is not None:
            # distinct dropout stream per DATA shard; model ranks share
            # the stream (their activations are replicated)
            step_key = jax.random.fold_in(
                step_key, lax.axis_index(data_axis))
        return raw(state, x, target, batch_size, step_key,
                   grad_poison, loss_poison)

    # one PREFIX spec per layer entry: every leaf of a TP entry rides
    # the stacked (n, L_local) layout, so the entry-level prefix covers
    # the dict (and sidesteps None-leaf structure mismatches)
    state_spec = [P(model_axis) if flag else P() for flag in tp_flags]
    batch_spec = P(data_axis) if data_axis is not None else P()
    spmd = shard_map(
        local_step, mesh=mesh,
        in_specs=(state_spec, batch_spec, batch_spec, P(), P(), P(),
                  P()),
        out_specs=(state_spec, P()), check_vma=False)
    return _compiler._finalize_step(
        spmd, donate, compiler_options, mesh=mesh,
        model_axis=model_axis, data_axis=data_axis)
