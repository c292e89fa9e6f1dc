"""Flash-style Pallas attention — the transformer workload's MXU
kernel (docs/kernels.md "The attention kernel").

No reference behavior to match (the 2015 platform predates attention);
this is the ops layer's third hand-scheduled family after matmul and
conv-VJP, built to the same contracts:

- **Forward** is the online-softmax tiled formulation: the grid walks
  (batch-head, q-tile, k-tile) with the k loop innermost; an f32
  scoped-VMEM accumulator carries the running (max, sum, output) triple
  and each k-tile rescales it by ``exp(m_prev - m_new)`` — softmax
  without ever materializing the (T, T) score matrix in HBM.  The
  PRODUCT steps (q@k^T and p@v, plus every backward contraction) are
  the shared :func:`veles_tpu.ops.common.mxu_partial_dot`, so precision
  levels 0-2 mean exactly what they mean in matmul/conv-VJP: level 0
  bf16x3 decomposition for f32 operands, levels 1/2 true-f32 HIGHEST
  products.  (The ACCUMULATION is the online-softmax rescale chain —
  there is no Kahan ladder here; the rescale IS the accumulation
  algorithm, and the levels only change the product precision.)
- **Backward** is a custom_vjp over two more Pallas kernels (the
  ``conv_vjp.py`` pattern): dq accumulates over k-tiles, dk/dv over
  q-tiles, both recomputing the probability tiles from the saved row
  statistics instead of storing them — flash attention's
  recompute-over-store memory shape.  The statistics are the row max
  ``m`` and the row sum ``l`` themselves, NOT their logsumexp: the
  chip's float32 ``log`` is good to ~1e-4 absolute (measured on a v5e,
  PR 21), and ``exp(s - (m + log l))`` would carry that into every
  recomputed probability — ``exp(s - m) / l`` does not.
- **Interpret mode on CPU** (``common.interpret_for``), so tier-1
  parity runs everywhere; masking uses a -1e30 finite floor (never
  -inf), so padded rows/columns contribute EXACT zeros to every
  gradient instead of NaN-poisoning the accumulators.
- ``blocks=None`` consults the ``attention`` ScheduleCache family
  (tune/spec.py) exactly like matmul's consult — tiles change the
  SCHEDULE, never the math.
- **Causal** (``causal=True``): key ``j`` counts for query ``i`` only
  where ``j <= i``.  All three kernels skip the tiles that lie wholly
  above the diagonal — the body runs under ``pl.when`` and the block
  index of the skipped side is clamped to the last tile needed, so a
  skipped grid step fetches nothing — and mask the tiles the diagonal
  or the band's edge crosses with the same finite floor.  The FORWARD
  masks those and only those: a tile that lies wholly below the
  diagonal (under a window wholly inside the band) and holds no padded
  key is a WHOLE tile (:func:`_tile_classes`), every pair of it is
  kept, and the forward hands its scores on as they are, the mask
  under ``lax.cond(whole, keep, mask)`` — the select would return its
  first operand, so the numbers are the masked body's bit for bit.
  What that gains on the chip is NOT the mask's work (a v5e issues it
  in slots the tile leaves spare: with no mask on any tile the three
  kernels are 0.4 % faster) but the ``scf.if``'s boundary: the scores
  leave a region as a value, so the product q k^T is finished before
  the row reductions begin — 14 % of the forward at 8,192 tokens
  (docs/kernels.md).  The backward kernels reduce nothing, gain
  nothing from a boundary, and mask every needed tile as before.
  :func:`tile_census` counts the classes a head's grid holds.
- **Keys wider than values** (latent attention: 192-wide ``q``/``k``
  against 128-wide ``v``): ``q``/``k``/``dq``/``dk`` tiles carry the
  key width, ``v``/``out``/``do``/``dv`` tiles and the output
  accumulator the value width, each padded to whole lanes by itself.
- **Grouped key/value heads** (``k``/``v`` with fewer rows than ``q``:
  (B x H_kv, T, .) against (B x H, T, .)): query head ``n`` reads the
  tiles of KV head ``n // group`` through the block index maps — K and
  V are never repeated to the query heads in HBM, forward or backward —
  and the dk/dv kernel's grid runs over the KV heads with the group as
  an inner axis, so a group's query heads sum into one float32
  accumulator and each dk/dv tile is written once.
- **A window** (``window=W``, causal only): key ``j`` counts for query
  ``i`` only where ``0 <= i - j < W``.  The windowed form's grid spans
  the band alone — ``W / bk + 1`` key steps a query tile (and as many
  query steps a key tile in the dk/dv kernel), the block index offset
  by the first tile the band crosses — so its cost grows with T x W,
  not T x T; the few steps a tile at the sequence's start does not
  need run nothing and fetch nothing, as in the causal form, and of
  the band's tiles the forward masks the tiles the diagonal or the
  band's edge crosses, and only those.  These kernels carry their own
  names (``veles_flash_win_*``).  A window of T or more is the causal
  form, and runs as it.
- ``product_dtype`` (None keeps the v2 behaviour): the dtype EVERY
  product's operands are rounded to, the probability and cotangent
  tiles included.  With bfloat16 ``q``/``k``/``v`` the v2 kernels hand
  the MXU float32 probability tiles, which costs the level's float32
  product (three passes at level 0); ``product_dtype=bfloat16`` is the
  usual mixed-precision contract — bfloat16 operands, float32
  accumulation — in one pass.

The ``VELES_PALLAS_BWD`` contract (docs/kernels.md): the model layer
(models/transformer.py) routes to :func:`flash_attention` only when the
knob resolves on; knob off runs :func:`attention_reference` — plain jnp
softmax attention over the same ``mxu_partial_dot`` product step — with
stock autodiff, which IS the fallback path (bit-exact by construction).
On single-tile shapes the kernel executes the reference's exact op
sequence; the two are still different programs (zero-padding to the
lane width regroups XLA's reductions), so they agree within a few ULP
(< 1e-6 absolute at |out| ~ 1), not bit for bit — and ULP-bounded on
multi-tile shapes (tile accumulation order; tests/test_transformer.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops import common as _common
from veles_tpu.ops.common import (ceil_mult, interpret_for,
                                   mxu_partial_dot, pad_to, unpad)

__all__ = ["flash_attention", "attention_reference", "tile_census",
           "ATTENTION_KERNEL_VERSION"]

#: the kernels' names in compiled HLO and device traces (``%<name>``)
FWD_KERNEL_NAME = "veles_flash_fwd"
DQ_KERNEL_NAME = "veles_flash_dq"
DKV_KERNEL_NAME = "veles_flash_dkv"
#: the same three under a window, whose grid spans the band only
WIN_FWD_KERNEL_NAME = "veles_flash_win_fwd"
WIN_DQ_KERNEL_NAME = "veles_flash_win_dq"
WIN_DKV_KERNEL_NAME = "veles_flash_win_dkv"

#: what the ``fwd`` rule names (``jax.ad_checkpoint.checkpoint_name``)
#: of the forward kernel's results: the output, and the row max and row
#: sum as one float32 a row.  They are all the backward reads of the
#: forward kernel, so a layer's ``jax.checkpoint`` whose policy saves
#: these names (compiler._forward_for_loss) recomputes the layer
#: without calling the forward kernel again; outside such a policy a
#: name is an identity
KEPT_OUT, KEPT_ROW_MAX, KEPT_ROW_SUM = KEPT_NAMES = (
    "veles_flash_out", "veles_flash_row_max", "veles_flash_row_sum")

#: bump when the kernel's algorithm changes: tuned schedules in the
#: cache are only valid for the algorithm they were measured on
#: (v2: the backward reads (m, l) row statistics, not a logsumexp;
#: v3: causal tile skipping, key width apart from value width,
#: ``product_dtype`` — the non-causal equal-width float32 program is
#: v2's, op for op; v4: grouped key/value heads and a window with a
#: band-only grid; v5: the causal and windowed FORWARD masks only the
#: tiles a mask can change, under a ``lax.cond`` — the plain form's
#: program is still v2's)
ATTENTION_KERNEL_VERSION = 5

_DEFAULT_BLOCKS = (256, 256)  # (bq, bk)
#: causal sequences of a thousand tokens and more: a (256, 256) tile is
#: a quarter of a microsecond of MXU work, about what a grid step costs
_CAUSAL_LONG_BLOCKS = (512, 512)

#: finite -inf stand-in for score masking: exp(-1e30 - m) underflows to
#: an exact 0.0 for any realistic row max m, while (-1e30) - (-1e30)
#: stays 0 — so fully-masked (padded) rows produce finite garbage that
#: the unpad slices away, and padded contributions to dk/dv are exact
#: zeros instead of inf - inf = NaN
_MASK_FLOOR = -1e30


def _col_ids(bq, bk):
    return jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)


def _row_ids(bq, bk):
    return jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)


def _masked_scores(s, i, kk, *, bq, bk, t_real, causal, window=None):
    """Padded key columns — and, causal, the keys after each query and,
    under a window, those ``window`` and more before it — to the finite
    floor, never -inf."""
    col = kk * bk + _col_ids(*s.shape)
    keep = col < t_real
    if causal:
        row = i * bq + _row_ids(*s.shape)
        keep = keep & (col <= row)
        if window is not None:
            keep = keep & (row - col < window)
    return jnp.where(keep, s, _MASK_FLOOR)


def _tile_classes(i, kk, bq, bk, window, t_real):
    """(needed, whole) of (q tile ``i``, k tile ``kk``) under the
    causal mask, from what a kernel can see of its place — Python ints
    (:func:`tile_census`) or a grid step's scalars alike.

    NEEDED is a tile that holds a pair with key <= query (and, under a
    window, one with query - key < window, among the sequence's own
    tiles: a band step may name a tile past its end).  A needed tile is
    WHOLE where every pair of it is kept: its last column is at or
    before its first row, under a window its last row is within
    ``window`` of its first column, and it holds no padded key (which
    the first already says of a causal tile; written out, so that the
    class reads as what it is).  Padded QUERY rows want no mask: they
    are sliced away.  Every other needed tile is an EDGE: the diagonal
    or the window's far edge crosses it, or it holds padded keys."""
    needed = kk * bk < (i + 1) * bq
    whole = ((kk + 1) * bk - 1 <= i * bq) & ((kk + 1) * bk <= t_real)
    if window is not None:
        needed = (needed & (i * bq < (kk + 1) * bk + window - 1)
                  & (i * bq < t_real) & (kk * bk < t_real))
        whole = whole & ((i + 1) * bq - 1 - kk * bk < window)
    return needed, needed & whole


def _when_needed(causal, i, kk, bq, bk, window=None, t_real=None):
    """Decorator running a kernel body only where (q tile ``i``, k tile
    ``kk``) is needed (:func:`_tile_classes`); always, when not
    causal."""
    if not causal:
        return lambda body: body()
    return pl.when(_tile_classes(i, kk, bq, bk, window, t_real)[0])


def _scores_masked_on_an_edge(s, i, kk, *, bq, bk, t_real, causal,
                              window=None):
    """The forward's :func:`_masked_scores`: causal, the mask sits
    under a ``lax.cond`` that hands a whole tile's ``s`` on as it is
    (bit for bit what the select would return).  The gain is the
    boundary's, not the mask's (docs/kernels.md): ``s`` leaves an
    ``scf.if`` as a value, so q k^T is finished before the row maxima's
    lane reductions begin.  It holds only while the branch that hands
    ``s`` on is the TRUE one, on a predicate that is no negation:
    Mosaic makes it the ``then`` of the ``scf.if`` (and turns a negated
    predicate's branches around), and an ``else`` that yields ``s``
    unchanged copies the tile — 24.0 against 21.3 ms a call at the
    kanana cell's shape (tests/test_attention_tiles.py pins both)."""
    place = dict(bq=bq, bk=bk, t_real=t_real, causal=causal, window=window)
    if not causal:
        return _masked_scores(s, i, kk, **place)
    whole = _tile_classes(i, kk, bq, bk, window, t_real)[1]
    return jax.lax.cond(
        whole, lambda s: s, lambda s: _masked_scores(s, i, kk, **place), s)


def tile_census(t, bq, bk, window=None):
    """(whole, edge, skipped) grid steps of ONE head of the causal
    (``window=None``) or windowed forward and dq kernels over ``t``
    tokens in (``bq``, ``bk``) tiles: how often the forward hands a
    tile's scores on as they are, masks them, and runs nothing —
    static, so counted here with the kernels' own predicates rather
    than sampled from a run."""
    n_q, n_k = -(-t // bq), -(-t // bk)
    k_steps = n_k if window is None else _band_steps(t, bq, bk, window)[0]
    counts = [0, 0, 0]
    for i in range(n_q):
        first = 0 if window is None else max(i * bq - window + 1, 0) // bk
        for kk in range(first, first + k_steps):
            needed, whole = _tile_classes(i, kk, bq, bk, window, t)
            counts[0 if whole else 1 if needed else 2] += 1
    return tuple(counts)


def _first_k(i, bq, bk, window):
    """The first key tile q tile ``i``'s band crosses."""
    return jnp.maximum(i * bq - (window - 1), 0) // bk


def _narrow(x, product_dtype):
    return x if product_dtype is None else x.astype(product_dtype)


# -- forward kernel ----------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                acc_ref, m_ref, l_ref, *, n_k, scale, t_real, bq, bk,
                precision_level, causal, product_dtype, window=None):
    """One (b, i, step) grid step of the online-softmax forward: step
    ``kk`` of ``n_k`` is key tile ``kk``, or under a window the
    ``kk``-th tile of q tile ``i``'s band.

    ``acc_ref`` (bq, value width) f32 carries the running unnormalized
    output; ``m_ref``/``l_ref`` (bq, 128) carry the running row max and
    row sum, lane-broadcast so the scratch tiles stay MXU-shaped.
    """
    i = pl.program_id(1)
    step = kk = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _MASK_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)

    if window is not None:
        kk = step + _first_k(i, bq, bk, window)

    @_when_needed(causal, i, kk, bq, bk, window, t_real)
    def _tile():
        q = q_ref[0]
        s = mxu_partial_dot(q, k_ref[0].T, precision_level) * scale
        s = _scores_masked_on_an_edge(s, i, kk, bq=bq, bk=bk, t_real=t_real,
                                      causal=causal, window=window)

        m_prev = m_ref[:, :1]                      # (bq, 1)
        s_max = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, s_max)
        p = jnp.exp(s - m_new)                     # (bq, bk) f32
        alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + mxu_partial_dot(
            _narrow(p, product_dtype), v_ref[0], precision_level)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(step == n_k - 1)
    def _store():
        l_fin = l_ref[:, :1]
        # fully-masked (padded) q rows have l == 0; divide by 1 so the
        # garbage rows stay finite for the unpad slice
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = jnp.broadcast_to(l_safe, l_out_ref.shape[1:])


def _last_k(causal, bq, bk):
    """Block index of the k side for grid step (i, kk): causal, a
    skipped step names the last tile the q tile needs, which is the
    block already there, so nothing is fetched for it."""
    if not causal:
        return lambda i, kk: kk
    return lambda i, kk: jnp.minimum(kk, ((i + 1) * bq - 1) // bk)


def _first_q(causal, bq, bk):
    """The same for the q side of the dk/dv kernel's (kk, i) steps."""
    if not causal:
        return lambda kk, i: i
    return lambda kk, i: jnp.maximum(i, (kk * bk) // bq)


def _band_k(bq, bk, window, t):
    """Block index of the k side for step ``kk`` of q tile ``i``'s
    band; a step past the band's end (a tile at the sequence's start
    has a shorter one) names the band's last tile again."""
    def at(i, kk):
        return jnp.minimum(_first_k(i, bq, bk, window) + kk, jnp.minimum(
            ((i + 1) * bq - 1) // bk, (t - 1) // bk))
    return at


def _band_q(bq, bk, window, t):
    """The same for the q side of the dk/dv kernel: step ``i`` of the
    q tiles that k tile ``kk``'s keys are in the window of."""
    def at(kk, i):
        return jnp.minimum((kk * bk) // bq + i, jnp.minimum(
            ((kk + 1) * bk + window - 2) // bq, (t - 1) // bq))
    return at


def _band_steps(t, bq, bk, window):
    """(key steps a q tile, q steps a k tile) of the windowed grids:
    the most tiles of the other side any tile's band crosses —
    ``window / bk + 1`` where the tiles are square and divide it."""
    n_q, n_k = -(-t // bq), -(-t // bk)
    k_steps = max(min(((i + 1) * bq - 1) // bk, n_k - 1)
                  - max(i * bq - window + 1, 0) // bk + 1
                  for i in range(n_q))
    q_steps = max(min(((kk + 1) * bk + window - 2) // bq, n_q - 1)
                  - (kk * bk) // bq + 1 for kk in range(n_k))
    return k_steps, q_steps


def _kv_head(group):
    """Row of ``k``/``v`` that row ``bb`` of ``q`` reads: with
    ``group`` query heads a KV head, head ``n`` reads ``n // group``."""
    if group == 1:
        return lambda bb: bb
    return lambda bb: bb // group


@functools.partial(
    jax.jit, static_argnames=("scale", "precision_level", "blocks",
                              "interpret", "causal", "product_dtype",
                              "window"))
def _flash_fwd_jit(q, k, v, scale, precision_level, blocks, interpret,
                   causal=False, product_dtype=None, window=None):
    """(out, (m, l)): the tiled forward.  q is (B, T, key width), k
    (B / group, T, key width), v (B / group, T, value width); the row
    statistics come back (B, Tq_padded, 128) f32 each, lane-broadcast
    (the backward kernels read the same layout)."""
    b, t, _ = q.shape
    dv = v.shape[-1]
    bq, bk = _clamped_blocks(blocks, t)
    qp = pad_to(q, (None, bq, 128))
    kp = pad_to(k, (None, bk, 128))
    vp = pad_to(v, (None, bk, 128))
    _, tq, dhp = qp.shape
    dvp = vp.shape[-1]
    tk = kp.shape[1]
    n_k = tk // bk
    k_at = _last_k(causal, bq, bk)
    if window is not None:
        n_k = _band_steps(t, bq, bk, window)[0]
        k_at = _band_k(bq, bk, window, t)
    grid = (b, tq // bq, n_k)
    kv = _kv_head(b // k.shape[0])

    out, row_max, row_sum = pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=n_k, scale=scale,
                          t_real=t, bq=bq, bk=bk,
                          precision_level=precision_level,
                          causal=causal, product_dtype=product_dtype,
                          window=window),
        name=FWD_KERNEL_NAME if window is None else WIN_FWD_KERNEL_NAME,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bk, dhp),
                         lambda bb, i, kk: (kv(bb), k_at(i, kk), 0)),
            pl.BlockSpec((1, bk, dvp),
                         lambda bb, i, kk: (kv(bb), k_at(i, kk), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dvp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tq, dvp), q.dtype),
            jax.ShapeDtypeStruct((b, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dvp), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return unpad(out, (b, t, dv)), (row_max, row_sum)


# -- backward kernels --------------------------------------------------------


def _probabilities(s, m_ref, l_ref):
    """The forward's probability tile again, from its saved row max
    and row sum (see the module docstring on why not a logsumexp)."""
    return jnp.exp(s - m_ref[0][:, :1]) * (1.0 / l_ref[0][:, :1])


def _cotangent(do_ref, product_dtype):
    """The output cotangent tile as a product operand: float32 as in
    v2, or as it is stored where the products are narrowed."""
    if product_dtype is None:
        return do_ref[0].astype(jnp.float32)
    return do_ref[0].astype(product_dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                   dq_ref, acc_ref, *, n_k, scale, t_real, bq, bk,
                   precision_level, causal, product_dtype, window=None):
    """dq for one q-tile, accumulated over k-tiles (under a window,
    over the tiles of its band, as the forward walks them): the
    probability tile is recomputed from the saved row statistics
    (recompute-over-store), then ds = p * (dp - delta) and
    dq += ds @ k * scale."""
    i = pl.program_id(1)
    step = kk = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if window is not None:
        kk = step + _first_k(i, bq, bk, window)

    @_when_needed(causal, i, kk, bq, bk, window, t_real)
    def _tile():
        s = mxu_partial_dot(q_ref[0], k_ref[0].T, precision_level) * scale
        s = _masked_scores(s, i, kk, bq=bq, bk=bk, t_real=t_real,
                           causal=causal, window=window)
        p = _probabilities(s, m_ref, l_ref)
        dp = mxu_partial_dot(_cotangent(do_ref, product_dtype),
                             v_ref[0].T, precision_level)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        acc_ref[:] += mxu_partial_dot(_narrow(ds, product_dtype),
                                      k_ref[0], precision_level)

    @pl.when(step == n_k - 1)
    def _store():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, n_q, scale, t_real, bq, bk, precision_level,
                    causal, product_dtype, window=None, group=1):
    """dk/dv for one k-tile, accumulated over q-tiles (under a window,
    over the q tiles whose band holds it) and, with grouped heads
    (grid (KV head, k tile, query head of the group, q step)), over the
    group's query heads.  Padded key columns are masked to exact-zero
    probabilities, so their dk/dv rows come out 0 and the unpad slices
    them away."""
    step = qq = pl.program_id(2 if group == 1 else 3)
    head = None if group == 1 else pl.program_id(2)

    def at(step_, head_):
        here = step == step_
        return here if head is None else here & (head == head_)

    @pl.when(at(0, 0))
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    kk = pl.program_id(1)
    if window is not None:
        qq = step + (kk * bk) // bq

    @_when_needed(causal, qq, kk, bq, bk, window, t_real)
    def _tile():
        s = mxu_partial_dot(q_ref[0], k_ref[0].T, precision_level) * scale
        s = _masked_scores(s, qq, kk, bq=bq, bk=bk, t_real=t_real,
                           causal=causal, window=window)
        p = _probabilities(s, m_ref, l_ref)
        do = _cotangent(do_ref, product_dtype)
        dv_acc_ref[:] += mxu_partial_dot(_narrow(p, product_dtype).T, do,
                                         precision_level)
        dp = mxu_partial_dot(do, v_ref[0].T, precision_level)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_acc_ref[:] += mxu_partial_dot(_narrow(ds, product_dtype).T,
                                         q_ref[0], precision_level)

    @pl.when(at(n_q - 1, group - 1))
    def _store():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "precision_level", "blocks",
                              "interpret", "causal", "product_dtype",
                              "window"))
def _flash_bwd_jit(q, k, v, out, stats, do, scale, precision_level,
                   blocks, interpret, causal=False, product_dtype=None,
                   window=None):
    """(dq, dk, dv) via the two tiled backward kernels.  ``delta`` =
    rowsum(do * out) is the standard flash-backward precompute — one
    elementwise pass, kept outside the kernels like conv-VJP keeps its
    dgrad as a lax conv."""
    b, t, dh = q.shape
    b_kv = k.shape[0]
    group = b // b_kv
    dv_width = v.shape[-1]
    # (B, Tq_padded) each, as the ``fwd`` rule keeps them: back to the
    # lane-broadcast layout the two kernels read
    row_max, row_sum = (
        jnp.broadcast_to(s[:, :, None], s.shape + (128,)) for s in stats)
    bq, bk = _clamped_blocks(blocks, t)
    qp = pad_to(q, (None, bq, 128))
    kp = pad_to(k, (None, bk, 128))
    vp = pad_to(v, (None, bk, 128))
    dop = pad_to(do, (None, bq, 128))
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (B, T, 1)
    delta = pad_to(jnp.broadcast_to(delta, (b, t, 128)), (None, bq,
                                                          None))
    _, tq, dhp = qp.shape
    dvp = vp.shape[-1]
    tk = kp.shape[1]
    n_q, n_k = tq // bq, tk // bk
    k_steps, q_steps = n_k, n_q
    k_at = _last_k(causal, bq, bk)
    q_at = _first_q(causal, bq, bk)
    static = dict(scale=scale, t_real=t, bq=bq, bk=bk,
                  precision_level=precision_level, causal=causal,
                  product_dtype=product_dtype, window=window)
    if window is not None:
        k_steps, q_steps = _band_steps(t, bq, bk, window)
        k_at = _band_k(bq, bk, window, t)
        q_at = _band_q(bq, bk, window, t)
    kv = _kv_head(group)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=k_steps, **static),
        name=DQ_KERNEL_NAME if window is None else WIN_DQ_KERNEL_NAME,
        grid=(b, n_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bk, dhp),
                         lambda bb, i, kk: (kv(bb), k_at(i, kk), 0)),
            pl.BlockSpec((1, bk, dvp),
                         lambda bb, i, kk: (kv(bb), k_at(i, kk), 0)),
            pl.BlockSpec((1, bq, dvp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dhp),
                               lambda bb, i, kk: (bb, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, tq, dhp), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dhp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, row_max, row_sum, delta)

    # the dk/dv grid: (KV head, k tile, q step), and with grouped heads
    # the group's query heads as one more inner axis that sums into the
    # same accumulator (two index maps, so that the ungrouped one stays
    # the one it was: no ``bb * 1 + 0`` in its program)
    if group == 1:
        def q_side(bb, kk, i):
            return (bb, q_at(kk, i), 0)
        inner, static_dkv = (q_steps,), static
    else:
        def q_side(bb, kk, head, i):
            return (bb * group + head, q_at(kk, i), 0)
        inner, static_dkv = (group, q_steps), dict(static, group=group)

    def k_side(bb, kk, *_):
        return (bb, kk, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=q_steps, **static_dkv),
        name=DKV_KERNEL_NAME if window is None else WIN_DKV_KERNEL_NAME,
        grid=(b_kv, n_k) + inner,
        in_specs=[
            pl.BlockSpec((1, bq, dhp), q_side),
            pl.BlockSpec((1, bk, dhp), k_side),
            pl.BlockSpec((1, bk, dvp), k_side),
            pl.BlockSpec((1, bq, dvp), q_side),
            pl.BlockSpec((1, bq, 128), q_side),
            pl.BlockSpec((1, bq, 128), q_side),
            pl.BlockSpec((1, bq, 128), q_side),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dhp), k_side),
            pl.BlockSpec((1, bk, dvp), k_side),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_kv, tk, dhp), q.dtype),
            jax.ShapeDtypeStruct((b_kv, tk, dvp), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dhp), jnp.float32),
            pltpu.VMEM((bk, dvp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
            + ("arbitrary",) * len(inner)),
        interpret=interpret,
    )(qp, kp, vp, dop, row_max, row_sum, delta)

    return (unpad(dq, (b, t, dh)), unpad(dk, (b_kv, t, dh)),
            unpad(dv, (b_kv, t, dv_width)))


# -- the custom_vjp entry ----------------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_fn(scale, precision_level, blocks, causal=False,
              product_dtype=None, window=None):
    """Per-static-config custom_vjp, cached so jit tracing sees one
    stable callable per (scale, level, schedule, form) — the conv_act
    pattern."""
    form = dict(causal=causal, product_dtype=product_dtype, window=window)

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _flash_fwd_jit(q, k, v, scale, precision_level,
                                blocks, interpret_for(q, k, v), **form)
        return out

    def fwd(q, k, v):
        out, (row_max, row_sum) = _flash_fwd_jit(
            q, k, v, scale, precision_level, blocks,
            interpret_for(q, k, v), **form)
        # every lane of a row's statistic is the same float: one a row
        # is kept ((B, Tq), which the chip does not tile back to 128
        # lanes as it would (B, Tq, 1)).  The primal output and every
        # residual below descend from the NAMED values only; one that
        # descended from the kernel's raw result would bring the kernel
        # back into a recomputed layer
        out = checkpoint_name(out, KEPT_OUT)
        stats = (checkpoint_name(row_max[:, :, 0], KEPT_ROW_MAX),
                 checkpoint_name(row_sum[:, :, 0], KEPT_ROW_SUM))
        return out, (q, k, v, out, stats)

    def bwd(res, do):
        q, k, v, out, stats = res
        return _flash_bwd_jit(q, k, v, out, stats, do, scale,
                              precision_level, blocks,
                              interpret_for(q, k, v), **form)

    f.defvjp(fwd, bwd)
    return f


def _check_shapes(q, k, v, causal, window):
    if (q.ndim != 3 or k.ndim != 3 or k.shape[1:] != q.shape[1:]
            or v.ndim != 3 or v.shape[:2] != k.shape[:2]
            or not k.shape[0] or q.shape[0] % k.shape[0]):
        raise ValueError("attention expects (B, T, dk) q and k and a "
                         "(B, T, dv) v (k and v may have B / group rows: "
                         "grouped heads), got %s %s %s" %
                         (q.shape, k.shape, v.shape))
    if window is not None and (not causal or window < 1):
        raise ValueError("a window (%r) counts keys back from the query: "
                         "it wants causal=True and at least 1" % (window,))


def flash_attention(q, k, v, scale=None, precision_level=0,
                    blocks=None, causal=False, product_dtype=None,
                    window=None):
    """Tiled online-softmax attention with the Pallas backward
    attached: ``softmax(q @ k^T * scale) @ v`` over (B, T, key width)
    ``q``/``k`` and (B, T, value width) ``v`` (B = batch x heads; the
    model layer folds heads in); ``causal=True`` keeps key ``j`` for
    query ``i`` only where ``j <= i``, and ``window=W`` with it only
    where ``i - j < W``.  ``k``/``v`` with B / group rows are grouped
    heads: row ``n`` of ``q`` reads row ``n // group`` (module
    docstring).

    ``precision_level`` follows the matmul ladder for every product
    step (docs/kernels.md); ``product_dtype`` rounds every product's
    operands, probability and cotangent tiles included (module
    docstring).  ``blocks=None`` consults the ``attention``
    schedule-cache family before the static default.
    """
    _check_shapes(q, k, v, causal, window)
    if window is not None and window >= q.shape[1]:
        window = None  # every earlier key is inside it: the causal form
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    plain = not causal and v.shape == q.shape == k.shape
    if blocks is None:
        # the tuned schedules were measured on the plain form
        blocks = (plain and _tuned_blocks(q, precision_level)) or (
            _CAUSAL_LONG_BLOCKS if causal and q.shape[1] >= 1024
            else _DEFAULT_BLOCKS)
    if product_dtype is not None:
        product_dtype = jnp.dtype(product_dtype).name
    # the plain form keeps the call (and the cache key) it had in v2
    form = (bool(causal), product_dtype) if causal or product_dtype else ()
    if window is not None:
        form += (int(window),)
    out = _flash_fn(float(scale), int(precision_level), tuple(blocks),
                    *form)(q, k, v)
    if _common.DEBUG_NONFINITE and not isinstance(out, jax.core.Tracer):
        _debug_check(q, k, v, out, precision_level)
    return out


def attention_reference(q, k, v, scale=None, precision_level=1,
                        causal=False, window=None):
    """Stock softmax attention in the kernel's exact op order — the
    ``VELES_PALLAS_BWD=0`` fallback (plain jnp, stock autodiff) AND
    the parity oracle: on shapes that fit one (bq, bk) tile the flash
    kernel executes this sequence verbatim AT THE SAME LEVEL, so the
    two agree within a few ULP there (module docstring); multi-tile
    shapes differ only by the online rescale's accumulation order
    (ULP-bounded, tests/test_transformer.py).  ``v`` may be narrower
    or wider than ``q``/``k``; ``causal=True`` floors the keys after
    each query as the kernels do, ``window`` those that far and more
    before it; ``k``/``v`` with B / group rows are grouped heads, each
    read by ``group`` rows of ``q`` (indexed, so stock autodiff sums a
    group's gradients).

    The DEFAULT level is 1 (true-f32 HIGHEST products): stock model-
    layer math is full f32 everywhere else in the zoo (the gd units'
    jnp.dot with preferred_element_type), and autodiff THROUGH the
    level-0 bf16x3 decomposition computes the gradient of the
    approximation with bf16-ROUNDED operand jacobians — ~1e-2 relative
    off the true gradient, where the flash kernel's hand-written
    level-0 backward applies the exact-gradient FORMULA with bf16x3
    products: ~2e-5 on unstructured operands, but the softmax
    backward cancels (``dp - delta``, ``sum_j ds_ij = 0``), and inside
    a transformer block at T=512, D=512 the 16-bit products leave the
    block's weight gradient 3.8e-3 off the true-f32 one (PR 21:
    identical in the interpreter and under Mosaic on a v5e; level 1
    is within 5e-7).  Pass ``precision_level=0`` explicitly only to
    parity-test the kernel's level-0 op sequence."""
    _check_shapes(q, k, v, causal, window)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[0] // k.shape[0]
    if group > 1:
        of_head = jnp.arange(q.shape[0]) // group
        k, v = k[of_head], v[of_head]

    def one(qb, kb, vb):
        s = mxu_partial_dot(qb, kb.T, precision_level) * scale
        if causal:
            back = _row_ids(*s.shape) - _col_ids(*s.shape)
            keep = back >= 0
            if window is not None:
                keep = keep & (back < window)
            s = jnp.where(keep, s, _MASK_FLOOR)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        return (mxu_partial_dot(p, vb, precision_level) / l).astype(
            qb.dtype)

    return jax.vmap(one)(q, k, v)


def _clamped_blocks(blocks, t):
    bq, bk = blocks or _DEFAULT_BLOCKS
    return min(bq, ceil_mult(t, 8)), min(bk, ceil_mult(t, 128))


def _tuned_blocks(q, precision_level):
    """Schedule-cache consult for a ``blocks=None`` call (tracer-safe:
    shapes/dtypes only, so the consult fires at trace time inside the
    fused step — which is how ``tune/walk.py`` records it)."""
    b, t, dh = q.shape
    if not (b and t and dh):
        return None
    from veles_tpu.tune.cache import schedule_for
    from veles_tpu.tune.spec import attention_spec, valid_schedule
    spec = attention_spec(b, t, dh, jnp.dtype(q.dtype).name,
                          precision_level)
    schedule = schedule_for(spec["op"], spec["shape"], spec["dtype"],
                            spec["precision_level"], spec["extra"],
                            raw=spec["raw"])
    if schedule is None:
        return None
    normalized = valid_schedule("attention", schedule)
    return tuple(normalized["blocks"]) if normalized else None


def _debug_check(q, k, v, out, precision_level):
    """VELES_DEBUG_NONFINITE guard, matmul's contract: eager calls
    only, raise with operand stats on a non-finite output."""
    if not bool(jnp.isfinite(out).all()):
        from veles_tpu.ops.matmul import _operand_stats
        raise FloatingPointError(
            "flash_attention produced non-finite output (%s; "
            "precision_level=%d — level 0's bf16x3 domain excludes "
            "|x| >= bf16-max)" % (
                "; ".join((_operand_stats("q", q),
                           _operand_stats("k", k),
                           _operand_stats("v", v))), precision_level))
