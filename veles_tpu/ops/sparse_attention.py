"""Attention over a learned token selection — the indexer's pass and
flash attention over the keys it keeps (docs/kernels.md "Attention over
a selection").

A lightning indexer scores every causal pair: ``I[t, s] = sum_j w[t, j]
relu(q[t, j] . k[s])`` over its ``H_I`` heads, in float32.  Query ``t``
keeps ``S[t] = {s <= t : I[t, s] >= tau[t]}``, ``tau[t]`` the
``min(t + 1, topk)``-th largest of its row: ``topk`` keys, all of them
while the row is shorter, more only on an exact tie.  One selection
serves every attention head of the row.

- **The selection** (``veles_indexer_select``): a grid step takes a
  block of queries, computes its scores against every key tile the
  block's causal pairs reach into a VMEM scratch (the scores as int32
  keys whose order is the floats' order, so no (T, T) array ever exists
  in HBM), and finds each row's threshold EXACTLY by building the
  ``min(t + 1, topk)``-th largest key bit by bit from the top: 32 counts
  over the scratch, each a compare and an add.  It writes the selection
  as an int8 mask ``(B, T, T)`` (1 kept; the form the attention kernels
  read a tile of: reading it costs a tile nothing measurable on the
  chip, docs/kernels.md), the row's max and sum of ``exp(I - max)`` over
  ``S[t]`` and ``|S[t]|``, and the kept pairs of every (query block, key
  tile).  The mask and those counts are named (``KEPT_SELECTION``), so a
  layer's checkpoint that keeps them does not run the selection again
  in the backward.
- **Attention over the selection** (``veles_sparse_fwd``, ``_dq``,
  ``_dkv``): the causal flash kernels' online softmax and recomputing
  backward (ops/attention.py), grouped heads through the block index
  maps, with the mask's tile in place of the causal mask.  A (q tile,
  k tile) in which no query kept a key runs nothing, and its block index
  names the tile fetched last, so nothing is fetched for it (scalar
  tables, prefetched: which tiles are occupied, which tile to hold).
  The forward names its output and row statistics with the flash
  kernels' names (``attention.KEPT_NAMES``), so a layer's checkpoint
  keeps them as it keeps the flash kernels'.
- **The indexer's loss** (``veles_indexer_kl``): ``p[t, s]``, the
  attention probabilities summed over the heads / heads (recomputed from
  the forward's row statistics, every head a grid step), against the
  indexer's softmax over ``S[t]``: ``L_I = mean_t sum_S p (log p - log
  softmax_S(I))``.  One pass gives its three sums a row and its gradient
  by the indexer's query, key and weights: ``dL/dI = (softmax_S(I) - p)
  / tokens`` over the kept pairs; the key's gradient comes back per
  (query block, key tile) and is summed outside.  ``p`` passes no
  gradient: this loss trains the indexer alone.

Why not a per-query gather at the benchmark's shape: a query's 2,048
keys and values (4 KV heads x 128, bfloat16) are 4 MB; 16,384 queries
move 68.7 GB a layer's forward, 84 ms at 819 GB/s, while the causal
product over every pair of a 16,384-token row costs less than that
(docs/kernels.md).  Masked tiles move the keys once a tile.

Interpret mode on CPU as the other families (``common.interpret_for``).
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.attention import (KEPT_OUT, KEPT_ROW_MAX, KEPT_ROW_SUM,
                                     _MASK_FLOOR, _cotangent, _narrow,
                                     _probabilities)
from veles_tpu.ops.common import (ceil_mult, interpret_for, mxu_partial_dot,
                                  pad_to, unpad)

__all__ = ["select", "attend", "indexer_loss", "Selection", "counters",
           "selection_of", "select_reference", "attend_reference",
           "indexer_loss_reference", "sparse_blocks", "causal_tiles"]

#: the kernels' names in compiled HLO and device traces (``%<name>``)
SELECT_KERNEL_NAME = "veles_indexer_select"
KL_KERNEL_NAME = "veles_indexer_kl"
FWD_KERNEL_NAME = "veles_sparse_fwd"
DQ_KERNEL_NAME = "veles_sparse_dq"
DKV_KERNEL_NAME = "veles_sparse_dkv"

#: what the indexer's loss names of its gradients: a layer's checkpoint
#: that keeps it (``fused.FusedTrainer._backward_should_recompute``)
#: does not run ``veles_indexer_kl`` again in the backward
KEPT_INDEXER_GRADS = "veles_indexer_grads"
#: what the selection names of its results: the mask and the tiles' counts
#: that the attention's ``fwd`` rule keeps for its backward kernels; a
#: layer's checkpoint that keeps them does not run ``veles_indexer_select``
#: again in the backward
KEPT_SELECTION = "veles_indexer_selection"

#: a tile of the attention kernels, long sequences
_TILE = 512
#: at most this many query rows a selection step (its scratch holds a
#: row's keys: 256 x 16,384 int32 is 16 MB)
_SELECT_ROWS = 256
_INT_MIN = -2 ** 31
_VMEM_LIMIT = 96 * 2 ** 20
#: the selection's mask in HBM: 1 where a pair is kept (int8: a float32
#: mask made the backward kernels slower on the chip, docs/kernels.md)
_MASK_DTYPE = jnp.int8


class Selection(NamedTuple):
    """What :func:`select` hands the attention and the loss: ``mask``
    (B, Tq, Tk) int8, 1 where a pair is kept; ``rows`` (B, Tq, 128)
    float32, lanes 1-3 a row's max and sum of ``exp(I - max)`` over the
    kept keys and how many it kept; ``tiles`` (B, query blocks, key
    tiles) int32, the kept pairs of each."""
    mask: jax.Array
    rows: jax.Array
    tiles: jax.Array


def sparse_blocks(t, blocks=None):
    """(bq, bk, bq_select): the attention kernels' tile and the
    selection's query block (which divides bq) for ``t`` tokens."""
    bq, bk = blocks or (_TILE, _TILE)
    bq, bk = min(bq, ceil_mult(t, 32)), min(bk, ceil_mult(t, 128))
    if bq <= _SELECT_ROWS:
        return bq, bk, bq
    bq = ceil_mult(bq, 64)
    return bq, bk, bq // 2


def causal_tiles(t, bq, bk):
    """(q tile, k tile) pairs of ``t`` tokens that hold a causal pair."""
    return sum(min((i * bq + bq - 1) // bk, (t - 1) // bk) + 1
               for i in range(-(-t // bq)))


def _flip(bits):
    """A float32's bits as int32 <-> an int32 key in the floats' order
    (the negative floats' order turned around; an involution)."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _sortable(x):
    return _flip(lax.bitcast_convert_type(x, jnp.int32))


def _float_of(key):
    return lax.bitcast_convert_type(_flip(key), jnp.float32)


def _lane(shape):
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _kept(mask_ref):
    """The (bq, bk) tile of kept pairs, as a predicate (a narrow int
    widens first: Mosaic compares 32-bit lanes)."""
    return mask_ref[0].astype(jnp.int32) != 0


# -- the selection -----------------------------------------------------------


def _index_scores(qi_ref, kt, w, heads):
    """(bq, bk) float32 ``sum_j w_j relu(q_j . k)`` of a query block's
    heads ``qi_ref[0, j]`` (bq, 128) against a key tile ``kt`` (128,
    bk), heads in order."""
    acc = None
    for j in range(heads):
        s = mxu_partial_dot(qi_ref[0, j], kt, 0)
        part = w[:, j:j + 1] * jnp.maximum(s, 0.0)
        acc = part if acc is None else acc + part
    return acc


def _select_kernel(qi_ref, wi_ref, ki_ref, mask_ref, rows_ref, tiles_ref,
                   keys_ref, *, heads, topk, t_real, bq, bk, n_k):
    i = pl.program_id(1)
    row = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    real = row < t_real
    reach = jnp.minimum(((i + 1) * bq - 1) // bk + 1, n_k)
    w = wi_ref[0]

    def at(kk):
        return pl.ds(pl.multiple_of(kk * bk, bk), bk)

    def scores(kk, carry):
        kt = ki_ref[0, at(kk), :].T
        col = kk * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = (col <= row) & (col < t_real)
        keys_ref[:, at(kk)] = jnp.where(
            keep, _sortable(_index_scores(qi_ref, kt, w, heads)), _INT_MIN)
        return carry

    lax.fori_loop(0, reach, scores, 0)
    wanted = jnp.minimum(row + 1, topk)

    def count_at_least(trial):
        def tile(kk, acc):
            hit = jnp.where(keys_ref[:, at(kk)] >= trial, 1, 0)
            for c in range(bk // 128):
                acc = acc + hit[:, c * 128:(c + 1) * 128]
            return acc
        acc = lax.fori_loop(0, reach, tile, jnp.zeros((bq, 128), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def bit(n, found):
        # the largest key with ``wanted`` keys at or above it, one bit at
        # a time from the top, counted as unsigned (the sign bit flipped)
        flip = jnp.int32(_INT_MIN)
        trial = ((found ^ flip) | jnp.left_shift(jnp.int32(1), 31 - n)) ^ flip
        return jnp.where(count_at_least(trial) >= wanted, trial, found)

    tau = lax.fori_loop(0, 32, bit, jnp.full((bq, 1), _INT_MIN, jnp.int32))

    def row_max(kk, top):
        return jnp.maximum(top, jnp.max(keys_ref[:, at(kk)], axis=1,
                                        keepdims=True))

    top = _float_of(lax.fori_loop(0, reach, row_max,
                                  jnp.full((bq, 1), _INT_MIN, jnp.int32)))
    mask_ref[0] = jnp.zeros(mask_ref.shape[1:], mask_ref.dtype)
    tile_lane = _lane((8, 128))
    first_sublane = lax.broadcasted_iota(jnp.int32, (8, 128), 0) == 0

    def kept(kk, carry):
        total, count, tiles = carry
        keys = keys_ref[:, at(kk)]
        chosen = (keys >= tau) & real
        mask_ref[0, :, at(kk)] = jnp.where(chosen, 1, 0).astype(
            mask_ref.dtype)
        total = total + jnp.sum(jnp.where(
            chosen, jnp.exp(_float_of(keys) - top), 0.0), axis=1,
            keepdims=True)
        ones = jnp.where(chosen, 1, 0)
        in_row = jnp.sum(ones, axis=1, keepdims=True)
        in_tile = jnp.sum(in_row, axis=0, keepdims=True)
        tiles = tiles + jnp.where((tile_lane == kk) & first_sublane,
                                  in_tile, 0)
        return total, count + in_row, tiles

    total, count, tiles = lax.fori_loop(0, reach, kept, (
        jnp.zeros((bq, 1), jnp.float32), jnp.zeros((bq, 1), jnp.int32),
        jnp.zeros((8, 128), jnp.int32)))
    lane = _lane((bq, 128))
    rows_ref[0] = jnp.where(lane == 1, top, jnp.where(
        lane == 2, total, jnp.where(lane == 3, count.astype(jnp.float32),
                                    0.0)))
    tiles_ref[0, 0] = tiles


@functools.partial(jax.jit, static_argnames=("topk", "blocks", "interpret"))
def _select_jit(q_i, k_i, w_i, topk, blocks, interpret):
    b, t, heads, _ = q_i.shape
    bq, bk, bs = sparse_blocks(t, blocks)
    qi = pad_to(jnp.swapaxes(q_i, 1, 2), (None, None, bs, 128))
    ki = pad_to(k_i, (None, bk, 128))
    wi = pad_to(w_i.astype(jnp.float32), (None, bs, 128))
    tq, tk = ceil_mult(t, bq), ki.shape[1]
    qi, wi = pad_to(qi, (None, None, tq, None)), pad_to(wi, (None, tq, None))
    n_k = tk // bk
    if n_k > 128:
        raise ValueError("the selection counts at most 128 key tiles a "
                         "row: %d tokens in tiles of %d" % (t, bk))
    mask, rows, tiles = pl.pallas_call(
        functools.partial(_select_kernel, heads=heads, topk=topk,
                          t_real=t, bq=bs, bk=bk, n_k=n_k),
        name=SELECT_KERNEL_NAME,
        grid=(b, tq // bs),
        in_specs=[
            pl.BlockSpec((1, heads, bs, 128), lambda bb, i: (bb, 0, i, 0)),
            pl.BlockSpec((1, bs, 128), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, tk, 128), lambda bb, i: (bb, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, tk), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, bs, 128), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, 1, 8, 128), lambda bb, i: (bb, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tq, tk), _MASK_DTYPE),
            jax.ShapeDtypeStruct((b, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, tq // bs, 8, 128), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bs, tk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(qi, wi, ki)
    return Selection(mask, rows, tiles[:, :, 0, :n_k])


def select(q_i, k_i, w_i, topk, blocks=None):
    """The selection of ``q_i`` (B, T, H_I, d) against ``k_i`` (B, T, d)
    under weights ``w_i`` (B, T, H_I), through the kernel: a
    :class:`Selection` (padded to the tiles; ``rows[:, :T]`` are real).
    Passes no gradient.  The mask and the tiles' counts, what the
    attention's backward reads of the selection, are named
    ``KEPT_SELECTION`` (the tiles as the kernel's result cut to them, so
    nothing the backward reads descends from the raw result); ``rows``
    is read by the indexer's loss alone, whose gradients are kept."""
    q_i, k_i, w_i = (lax.stop_gradient(x) for x in (q_i, k_i, w_i))
    mask, rows, tiles = _select_jit(q_i, k_i, w_i, int(topk),
                                    None if blocks is None else tuple(blocks),
                                    interpret_for(q_i, k_i, w_i))
    return Selection(checkpoint_name(mask, KEPT_SELECTION), rows,
                     checkpoint_name(tiles, KEPT_SELECTION))


def select_reference(q_i, k_i, w_i, topk):
    """(scores (B, T, T) float32, kept (B, T, T) bool): the selection's
    definition in plain ``jax.numpy``, the scores of every causal pair
    with the float32 products of the operands as given."""
    t = q_i.shape[1]
    s = jnp.einsum("btjd,bsd->btjs", q_i.astype(jnp.float32),
                   k_i.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    scores = jnp.einsum("btj,btjs->bts", w_i.astype(jnp.float32),
                        jnp.maximum(s, 0.0),
                        precision=lax.Precision.HIGHEST)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    wanted = jnp.minimum(jnp.arange(t) + 1, topk)
    top = lax.top_k(masked, min(topk, t))[0]
    tau = jnp.take_along_axis(top, (wanted - 1)[None, :, None], axis=-1)
    return scores, causal & (masked >= tau)


def selection_of(kept, blocks=None):
    """A :class:`Selection` of ``kept`` (B, T, T) bool pairs (the plain
    path's): the mask, each row's count (lane 3) and the tiles' counts,
    laid out as the kernel lays them."""
    b, t = kept.shape[:2]
    bq, bk, bs = sparse_blocks(t, blocks)
    mask = pad_to(kept.astype(_MASK_DTYPE), (None, bq, bk))
    tq, tk = mask.shape[1:]
    count = pad_to(jnp.sum(kept, axis=-1).astype(jnp.float32), (None, bq))
    rows = jnp.where(jnp.arange(128) == 3, count[..., None], 0.0)
    tiles = jnp.sum(mask.astype(jnp.int32).reshape(
        b, tq // bs, bs, tk // bk, bk), axis=(2, 4))
    return Selection(mask, rows, tiles)


def counters(selection, t, blocks=None):
    """What a layer counts of its selection a step: the kept pairs by
    attention query tile (a vector: one int32 each stays exact over
    thousands of steps), the (query tile, key tile) pairs that keep one,
    and those that hold a causal pair."""
    bq, bk, bs = sparse_blocks(t, blocks)
    b = selection.mask.shape[0]
    per_row = pad_to(selection.rows[:, :t, 3], (None, bq))
    pairs = jnp.sum(per_row.reshape(b, -1, bq), axis=(0, 2))
    occupied = jnp.sum(_occupied(selection.tiles, bq, bs) > 0)
    return {"sparse_selected_pairs": pairs.astype(jnp.int32),
            "sparse_occupied_tiles": occupied.astype(jnp.int32),
            "sparse_causal_tiles": jnp.int32(b * causal_tiles(t, bq, bk))}


# -- attention over the selection -------------------------------------------


def _occupied(tiles, bq, bs):
    """Kept pairs of each (attention q tile, k tile): the selection's
    query blocks summed in pairs where a tile holds two."""
    b, n, k = tiles.shape
    return tiles.reshape(b, n // (bq // bs), bq // bs, k).sum(axis=2)


def _held(occupied):
    """[..., n] int32 tables: at step j, the tile to fetch — j where
    occupied, else the last occupied before it (else the first after):
    a step that keeps nothing names the block already there."""
    n = occupied.shape[-1]
    steps = jnp.arange(n, dtype=jnp.int32)
    last = lax.cummax(jnp.where(occupied, steps, -1), axis=occupied.ndim - 1)
    first = jnp.argmax(occupied, axis=-1).astype(jnp.int32)[..., None]
    return jnp.where(last < 0, first, last)


def _sparse_fwd_kernel(occ_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref,
                       o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref,
                       *, n_q, n_k, heads, scale, product_dtype):
    """The flash forward's (b, i, kk) step with the selection's mask
    tile for the causal mask, run only where the tile keeps a pair."""
    bb, i, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _MASK_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(occ_ref[(bb // heads * n_q + i) * n_k + kk] > 0)
    def _tile():
        s = mxu_partial_dot(q_ref[0], k_ref[0].T, 0) * scale
        s = jnp.where(_kept(mask_ref), s, _MASK_FLOOR)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + mxu_partial_dot(
            _narrow(p, product_dtype), v_ref[0], 0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kk == n_k - 1)
    def _store():
        l_fin = l_ref[:, :1]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = jnp.broadcast_to(l_safe, l_out_ref.shape[1:])


def _pair_kernel_probabilities(q_ref, k_ref, mask_ref, m_ref, l_ref, scale):
    s = mxu_partial_dot(q_ref[0], k_ref[0].T, 0) * scale
    s = jnp.where(_kept(mask_ref), s, _MASK_FLOOR)
    return _probabilities(s, m_ref, l_ref)


def _sparse_dq_kernel(occ_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref,
                      m_ref, l_ref, delta_ref, mask_ref, dq_ref, acc_ref,
                      *, n_q, n_k, heads, scale, product_dtype):
    bb, i, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[(bb // heads * n_q + i) * n_k + kk] > 0)
    def _tile():
        p = _pair_kernel_probabilities(q_ref, k_ref, mask_ref, m_ref,
                                       l_ref, scale)
        dp = mxu_partial_dot(_cotangent(do_ref, product_dtype),
                             v_ref[0].T, 0)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        acc_ref[:] += mxu_partial_dot(_narrow(ds, product_dtype),
                                      k_ref[0], 0)

    @pl.when(kk == n_k - 1)
    def _store():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _sparse_dkv_kernel(occ_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref,
                       m_ref, l_ref, delta_ref, mask_ref, dk_ref, dv_ref,
                       dk_acc_ref, dv_acc_ref, *, n_q, n_k, kv_heads, group,
                       scale, product_dtype):
    bb, kk = pl.program_id(0), pl.program_id(1)
    head, i = pl.program_id(2), pl.program_id(3)

    @pl.when((i == 0) & (head == 0))
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @pl.when(occ_ref[(bb // kv_heads * n_q + i) * n_k + kk] > 0)
    def _tile():
        p = _pair_kernel_probabilities(q_ref, k_ref, mask_ref, m_ref,
                                       l_ref, scale)
        do = _cotangent(do_ref, product_dtype)
        dv_acc_ref[:] += mxu_partial_dot(_narrow(p, product_dtype).T, do, 0)
        dp = mxu_partial_dot(do, v_ref[0].T, 0)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_acc_ref[:] += mxu_partial_dot(_narrow(ds, product_dtype).T,
                                         q_ref[0], 0)

    @pl.when((i == n_q - 1) & (head == group - 1))
    def _store():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _tables(mask_tiles, bq, bs):
    """(occupied, k tile to hold along a q row, q tile to hold along a k
    column), flat int32 for the scalar prefetch."""
    occupied = _occupied(mask_tiles, bq, bs)
    taken = occupied > 0
    return (occupied.reshape(-1).astype(jnp.int32), _held(taken).reshape(-1),
            _held(jnp.swapaxes(taken, 1, 2)).reshape(-1))


def _compiler_params(parallel, arbitrary=1):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * parallel
        + ("arbitrary",) * arbitrary, vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("scale", "blocks",
                                             "interpret", "product_dtype"))
def _sparse_fwd_jit(q, k, v, mask, tiles, scale, blocks, interpret,
                    product_dtype):
    bh, t, _ = q.shape
    b = mask.shape[0]
    heads = bh // b
    dv = v.shape[-1]
    bq, bk, bs = sparse_blocks(t, blocks)
    qp = pad_to(q, (None, bq, 128))
    kp = pad_to(k, (None, bk, 128))
    vp = pad_to(v, (None, bk, 128))
    _, tq, dhp = qp.shape
    dvp, tk = vp.shape[-1], kp.shape[1]
    n_q, n_k = tq // bq, tk // bk
    occ, along_k, _ = _tables(tiles, bq, bs)
    kv = _group_row(bh // k.shape[0])

    def step(bb, i, kk, occ_ref, fetch_ref):
        return fetch_ref[(bb // heads * n_q + i) * n_k + kk]

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk, *_: (bb, i, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, i, kk, *r: (
                kv(bb), step(bb, i, kk, *r), 0)),
            pl.BlockSpec((1, bk, dvp), lambda bb, i, kk, *r: (
                kv(bb), step(bb, i, kk, *r), 0)),
            pl.BlockSpec((1, bq, bk), lambda bb, i, kk, *r: (
                bb // heads, i, step(bb, i, kk, *r))),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dvp), lambda bb, i, kk, *_: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk, *_: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk, *_: (bb, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dvp), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ])
    out, row_max, row_sum = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, n_q=n_q, n_k=n_k,
                          heads=heads, scale=scale,
                          product_dtype=product_dtype),
        name=FWD_KERNEL_NAME,
        grid_spec=grid,
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dvp), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(2),
        interpret=interpret,
    )(occ, along_k, qp, kp, vp, mask)
    return unpad(out, (bh, t, dv)), (row_max, row_sum)


def _group_row(group):
    return (lambda bb: bb) if group == 1 else (lambda bb: bb // group)


@functools.partial(jax.jit, static_argnames=("scale", "blocks",
                                             "interpret", "product_dtype"))
def _sparse_bwd_jit(q, k, v, out, stats, do, mask, tiles, scale, blocks,
                    interpret, product_dtype):
    bh, t, dh = q.shape
    b_kv = k.shape[0]
    b = mask.shape[0]
    heads, kv_heads = bh // b, b_kv // b
    group = bh // b_kv
    dv_width = v.shape[-1]
    row_max, row_sum = (jnp.broadcast_to(s[:, :, None], s.shape + (128,))
                        for s in stats)
    bq, bk, bs = sparse_blocks(t, blocks)
    qp = pad_to(q, (None, bq, 128))
    kp = pad_to(k, (None, bk, 128))
    vp = pad_to(v, (None, bk, 128))
    dop = pad_to(do, (None, bq, 128))
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = pad_to(jnp.broadcast_to(delta, (bh, t, 128)), (None, bq, None))
    _, tq, dhp = qp.shape
    dvp, tk = vp.shape[-1], kp.shape[1]
    n_q, n_k = tq // bq, tk // bk
    occ, along_k, along_q = _tables(tiles, bq, bs)
    kv = _group_row(group)
    static = dict(n_q=n_q, n_k=n_k, scale=scale, product_dtype=product_dtype)

    def k_step(bb, i, kk, occ_ref, fetch_ref):
        return fetch_ref[(bb // heads * n_q + i) * n_k + kk]

    def q_row(bb, i, kk, *_):
        return (bb, i, 0)

    dq = pl.pallas_call(
        functools.partial(_sparse_dq_kernel, heads=heads, **static),
        name=DQ_KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, bq, dhp), q_row),
                pl.BlockSpec((1, bk, dhp), lambda bb, i, kk, *r: (
                    kv(bb), k_step(bb, i, kk, *r), 0)),
                pl.BlockSpec((1, bk, dvp), lambda bb, i, kk, *r: (
                    kv(bb), k_step(bb, i, kk, *r), 0)),
                pl.BlockSpec((1, bq, dvp), q_row),
                pl.BlockSpec((1, bq, 128), q_row),
                pl.BlockSpec((1, bq, 128), q_row),
                pl.BlockSpec((1, bq, 128), q_row),
                pl.BlockSpec((1, bq, bk), lambda bb, i, kk, *r: (
                    bb // heads, i, k_step(bb, i, kk, *r))),
            ],
            out_specs=pl.BlockSpec((1, bq, dhp), q_row),
            scratch_shapes=[pltpu.VMEM((bq, dhp), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, tq, dhp), q.dtype),
        compiler_params=_compiler_params(2),
        interpret=interpret,
    )(occ, along_k, qp, kp, vp, dop, row_max, row_sum, delta, mask)

    # (KV head, k tile, query head of the group, q tile), as the flash
    # dk/dv kernel walks grouped heads: a group sums into one accumulator
    def q_step(bb, kk, head, i, occ_ref, fetch_ref):
        return fetch_ref[(bb // kv_heads * n_k + kk) * n_q + i]

    def q_side(bb, kk, head, i, *r):
        return (bb * group + head, q_step(bb, kk, head, i, *r), 0)

    def k_side(bb, kk, *_):
        return (bb, kk, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_sparse_dkv_kernel, kv_heads=kv_heads,
                          group=group, **static),
        name=DKV_KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b_kv, n_k, group, n_q),
            in_specs=[
                pl.BlockSpec((1, bq, dhp), q_side),
                pl.BlockSpec((1, bk, dhp), k_side),
                pl.BlockSpec((1, bk, dvp), k_side),
                pl.BlockSpec((1, bq, dvp), q_side),
                pl.BlockSpec((1, bq, 128), q_side),
                pl.BlockSpec((1, bq, 128), q_side),
                pl.BlockSpec((1, bq, 128), q_side),
                pl.BlockSpec((1, bq, bk), lambda bb, kk, head, i, *r: (
                    bb // kv_heads, q_step(bb, kk, head, i, *r), kk)),
            ],
            out_specs=[pl.BlockSpec((1, bk, dhp), k_side),
                       pl.BlockSpec((1, bk, dvp), k_side)],
            scratch_shapes=[pltpu.VMEM((bk, dhp), jnp.float32),
                            pltpu.VMEM((bk, dvp), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b_kv, tk, dhp), q.dtype),
                   jax.ShapeDtypeStruct((b_kv, tk, dvp), q.dtype)],
        compiler_params=_compiler_params(2, 2),
        interpret=interpret,
    )(occ, along_q, qp, kp, vp, dop, row_max, row_sum, delta, mask)
    return (unpad(dq, (bh, t, dh)), unpad(dk, (b_kv, t, dh)),
            unpad(dv, (b_kv, t, dv_width)))


@functools.lru_cache(maxsize=None)
def _sparse_fn(scale, blocks, product_dtype):
    """Per-static-config custom_vjp over (q, k, v, mask, tiles) ->
    (out, (row max, row sum)), the statistics one float a row, (B x H,
    Tq padded)."""
    form = dict(scale=scale, blocks=blocks, product_dtype=product_dtype)

    def run(q, k, v, mask, tiles):
        out, (row_max, row_sum) = _sparse_fwd_jit(
            q, k, v, mask, tiles, interpret=interpret_for(q, k, v), **form)
        return out, (row_max[:, :, 0], row_sum[:, :, 0])

    @jax.custom_vjp
    def f(q, k, v, mask, tiles):
        return run(q, k, v, mask, tiles)

    def fwd(q, k, v, mask, tiles):
        out, (row_max, row_sum) = run(q, k, v, mask, tiles)
        # named as the flash kernels name theirs: kept across a layer's
        # checkpoint by the same policy, so the kernel runs once a layer
        out = checkpoint_name(out, KEPT_OUT)
        stats = (checkpoint_name(row_max, KEPT_ROW_MAX),
                 checkpoint_name(row_sum, KEPT_ROW_SUM))
        return (out, stats), (q, k, v, out, stats, mask, tiles)

    def bwd(res, cotangents):
        q, k, v, out, stats, mask, tiles = res
        do = cotangents[0]   # the statistics pass no gradient
        dq, dk, dv = _sparse_bwd_jit(
            q, k, v, out, stats, do, mask, tiles,
            interpret=interpret_for(q, k, v), **form)
        return dq, dk, dv, None, None

    f.defvjp(fwd, bwd)
    return f


def attend(q, k, v, selection, scale=None, blocks=None):
    """Attention of (B x H, T, d) ``q`` over the keys ``selection`` kept,
    ``k``/``v`` (B x H_kv, T, d) grouped heads read by ``H / H_kv``
    query heads each: (out, (row max, row sum)), the statistics (B x H,
    Tq padded) float32 for :func:`indexer_loss` (they pass no
    gradient).  bfloat16 operands take bfloat16 products, float32
    accumulation, as the flash kernels do."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    narrow = jnp.dtype(q.dtype).name if q.dtype == jnp.bfloat16 else None
    return _sparse_fn(float(scale), None if blocks is None
                      else tuple(blocks), narrow)(
                          q, k, v, selection.mask, selection.tiles)


def attend_reference(q, k, v, kept, scale=None):
    """Plain softmax attention over ``kept`` (B, T, T) bool pairs, the
    heads folded as :func:`attend` takes them: (out, probabilities (B x
    H, T, T))."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b = kept.shape[0]
    heads, group = q.shape[0] // b, q.shape[0] // k.shape[0]
    rows = jnp.arange(q.shape[0])
    k, v = k[rows // group], v[rows // group]
    s = jnp.einsum("nqd,nkd->nqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * scale
    s = jnp.where(kept[rows // heads], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("nqk,nkd->nqd", p, v.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    return out.astype(q.dtype), p


# -- the indexer's loss ------------------------------------------------------


def _kl_kernel(occ_ref, fetch_ref, q_ref, k_ref, am_ref, al_ref, qi_ref,
               ki_ref, wi_ref, rows_ref, mask_ref, dqi_ref, sums_ref,
               dki_ref, dqi_acc, sums_acc, *, n_q, n_k, heads, group,
               index_heads, scale, per_token):
    bb, i, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk = mask_ref.shape[1], mask_ref.shape[2]

    @pl.when(kk == 0)
    def _init():
        dqi_acc[:] = jnp.zeros_like(dqi_acc)
        sums_acc[:] = jnp.zeros_like(sums_acc)

    dki_ref[0, 0] = jnp.zeros(dki_ref.shape[2:], dki_ref.dtype)

    @pl.when(occ_ref[(bb * n_q + i) * n_k + kk] > 0)
    def _tile():
        keep = _kept(mask_ref)
        am, al = am_ref[0], al_ref[0]
        p = None
        for n in range(heads):
            s = mxu_partial_dot(q_ref[0, n], k_ref[0, n // group].T, 0) \
                * scale
            part = jnp.exp(s - am[:, n:n + 1]) * (1.0 / al[:, n:n + 1])
            p = part if p is None else p + part
        p = jnp.where(keep, p * (1.0 / heads), 0.0)
        ki = ki_ref[0]
        kt = ki.T
        w = wi_ref[0]
        score = _index_scores(qi_ref, kt, w, index_heads)
        rows = rows_ref[0]
        top = rows[:, 1:2]
        mine = jnp.where(keep, jnp.exp(score - top) * (1.0 / rows[:, 2:3]),
                         0.0)
        g = (mine - p) * per_token
        log_p = jnp.log(jnp.where(p > 0.0, p, 1.0))
        lane = _lane((bq, 128))
        sums = jnp.where(lane == index_heads,
                         jnp.sum(p * log_p, axis=1, keepdims=True), 0.0)
        sums += jnp.where(lane == index_heads + 1, jnp.sum(
            p * jnp.where(keep, score - top, 0.0), axis=1, keepdims=True),
            0.0)
        sums += jnp.where(lane == index_heads + 2,
                          jnp.sum(p, axis=1, keepdims=True), 0.0)
        dk = jnp.zeros(dki_ref.shape[2:], jnp.float32)
        for j in range(index_heads):
            s = mxu_partial_dot(qi_ref[0, j], kt, 0)
            sums += jnp.where(lane == j, jnp.sum(
                g * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
            g_j = (jnp.where(s > 0.0, g, 0.0) * w[:, j:j + 1]).astype(
                ki.dtype)
            dqi_acc[j] += mxu_partial_dot(g_j, ki, 0)
            dk = dk + mxu_partial_dot(g_j.T, qi_ref[0, j], 0)
        sums_acc[:] += sums
        dki_ref[0, 0] = dk

    @pl.when(kk == n_k - 1)
    def _store():
        dqi_ref[0] = dqi_acc[:]
        sums_ref[0] = sums_acc[:]


@functools.partial(jax.jit, static_argnames=("scale", "blocks",
                                             "interpret"))
def _kl_jit(q, k, stats, q_i, k_i, w_i, selection, scale, blocks,
            interpret):
    b, t, index_heads, di = q_i.shape
    bh, _, dh = q.shape
    heads, kv_heads = bh // b, k.shape[0] // b
    bq, bk, bs = sparse_blocks(t, blocks)
    tq, tk = selection.mask.shape[1:]
    n_q, n_k = tq // bs, tk // bk
    q4 = pad_to(q.reshape(b, heads, t, dh), (None, None, tq, 128))
    q4 = q4[:, :, :tq]
    k4 = pad_to(k.reshape(b, kv_heads, t, dh), (None, None, tk, 128))
    am, al = (pad_to(jnp.swapaxes(s[:, :t].reshape(b, heads, t), 1, 2),
                     (None, tq, 128))[:, :tq] for s in stats)
    qi = pad_to(jnp.swapaxes(q_i, 1, 2), (None, None, tq, 128))[:, :, :tq]
    ki = pad_to(k_i, (None, tk, 128))
    wi = pad_to(w_i.astype(jnp.float32), (None, tq, 128))[:, :tq]
    taken = selection.tiles > 0
    occ = selection.tiles.reshape(-1)
    along_k = _held(taken).reshape(-1)

    def k_step(bb, i, kk, occ_ref, fetch_ref):
        return fetch_ref[(bb * n_q + i) * n_k + kk]

    def row(bb, i, kk, *_):
        return (bb, i, 0)

    def heads_row(bb, i, kk, *_):
        return (bb, 0, i, 0)

    dqi, sums, dki = pl.pallas_call(
        functools.partial(_kl_kernel, n_q=n_q, n_k=n_k, heads=heads,
                          group=heads // kv_heads, index_heads=index_heads,
                          scale=scale, per_token=1.0 / (b * t)),
        name=KL_KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, heads, bs, q4.shape[-1]), heads_row),
                pl.BlockSpec((1, kv_heads, bk, k4.shape[-1]),
                             lambda bb, i, kk, *r: (
                                 bb, 0, k_step(bb, i, kk, *r), 0)),
                pl.BlockSpec((1, bs, 128), row),
                pl.BlockSpec((1, bs, 128), row),
                pl.BlockSpec((1, index_heads, bs, 128), heads_row),
                pl.BlockSpec((1, bk, 128), lambda bb, i, kk, *r: (
                    bb, k_step(bb, i, kk, *r), 0)),
                pl.BlockSpec((1, bs, 128), row),
                pl.BlockSpec((1, bs, 128), row),
                pl.BlockSpec((1, bs, bk), lambda bb, i, kk, *r: (
                    bb, i, k_step(bb, i, kk, *r))),
            ],
            out_specs=[
                pl.BlockSpec((1, index_heads, bs, 128), heads_row),
                pl.BlockSpec((1, bs, 128), row),
                pl.BlockSpec((1, 1, bk, 128),
                             lambda bb, i, kk, *_: (bb, i, kk, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((index_heads, bs, 128), jnp.float32),
                            pltpu.VMEM((bs, 128), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, index_heads, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, n_q, tk, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(2),
        interpret=interpret,
    )(occ, along_k, q4, k4, am, al, qi, ki, wi, selection.rows,
      selection.mask)
    sums = sums[:, :t]
    kl = (sums[..., index_heads] - sums[..., index_heads + 1]
          + sums[..., index_heads + 2] * jnp.log(selection.rows[:, :t, 2]))
    grads = (jnp.swapaxes(dqi[:, :, :t, :di], 1, 2),
             jnp.sum(dki, axis=1)[:, :t, :di], sums[..., :index_heads])
    return jnp.sum(kl) / (b * t), grads


def indexer_loss(q, k, stats, q_i, k_i, w_i, selection, scale=None,
                 blocks=None):
    """(L_I, its gradients by ``q_i``, ``k_i``, ``w_i`` as float32) through
    the kernel: ``q``/``k`` and ``stats`` are :func:`attend`'s operands
    and statistics.  Nothing here is differentiated: the gradients are
    the loss's own, written out (module docstring)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    args = [lax.stop_gradient(x) for x in (q, k, q_i, k_i, w_i)]
    stats = tuple(lax.stop_gradient(s) for s in stats)
    return _kl_jit(args[0], args[1], stats, *args[2:], selection,
                   float(scale), None if blocks is None else tuple(blocks),
                   interpret_for(q, k))


def indexer_loss_reference(probabilities, kept, q_i, k_i, w_i):
    """L_I in plain ``jax.numpy`` from the attention's probabilities
    (B x H, T, T) and the kept pairs: differentiable in ``q_i``, ``k_i``,
    ``w_i``; ``p`` passes no gradient."""
    b, t = kept.shape[:2]
    p = lax.stop_gradient(jnp.mean(
        probabilities.reshape((b, -1) + probabilities.shape[1:]), axis=1))
    s = jnp.einsum("btjd,bsd->btjs", q_i.astype(jnp.float32),
                   k_i.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    scores = jnp.einsum("btj,btjs->bts", w_i.astype(jnp.float32),
                        jnp.maximum(s, 0.0),
                        precision=lax.Precision.HIGHEST)
    log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    terms = jnp.where(kept & (p > 0), p * (jnp.log(
        jnp.where(p > 0, p, 1.0)) - jnp.where(kept, log_q, 0.0)), 0.0)
    return jnp.sum(terms) / (b * t)
