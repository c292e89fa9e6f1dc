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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops import common as _common
from veles_tpu.ops.common import (ceil_mult, interpret_for,
                                   mxu_partial_dot, pad_to, unpad)

__all__ = ["flash_attention", "attention_reference",
           "ATTENTION_KERNEL_VERSION"]

#: the kernels' names in compiled HLO and device traces (``%<name>``)
FWD_KERNEL_NAME = "veles_flash_fwd"
DQ_KERNEL_NAME = "veles_flash_dq"
DKV_KERNEL_NAME = "veles_flash_dkv"

#: bump when the kernel's algorithm changes: tuned schedules in the
#: cache are only valid for the algorithm they were measured on
#: (v2: the backward reads (m, l) row statistics, not a logsumexp)
ATTENTION_KERNEL_VERSION = 2

_DEFAULT_BLOCKS = (256, 256)  # (bq, bk)

#: finite -inf stand-in for score masking: exp(-1e30 - m) underflows to
#: an exact 0.0 for any realistic row max m, while (-1e30) - (-1e30)
#: stays 0 — so fully-masked (padded) rows produce finite garbage that
#: the unpad slices away, and padded contributions to dk/dv are exact
#: zeros instead of inf - inf = NaN
_MASK_FLOOR = -1e30


def _col_ids(bq, bk):
    return jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)


# -- forward kernel ----------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                acc_ref, m_ref, l_ref, *, n_k, scale, t_real, bk,
                precision_level):
    """One (b, i, kk) grid step of the online-softmax forward.

    ``acc_ref`` (bq, dh) f32 carries the running unnormalized output;
    ``m_ref``/``l_ref`` (bq, 128) carry the running row max and row
    sum, lane-broadcast so the scratch tiles stay MXU-shaped.
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _MASK_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]
    s = mxu_partial_dot(q, k_ref[0].T, precision_level) * scale
    # mask padded key columns to the finite floor, never -inf
    col = kk * bk + _col_ids(*s.shape)
    s = jnp.where(col < t_real, s, _MASK_FLOOR)

    m_prev = m_ref[:, :1]                      # (bq, 1)
    s_max = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
    m_new = jnp.maximum(m_prev, s_max)
    p = jnp.exp(s - m_new)                     # (bq, bk) f32
    alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + mxu_partial_dot(
        p, v_ref[0], precision_level)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kk == n_k - 1)
    def _store():
        l_fin = l_ref[:, :1]
        # fully-masked (padded) q rows have l == 0; divide by 1 so the
        # garbage rows stay finite for the unpad slice
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = jnp.broadcast_to(l_safe, l_out_ref.shape[1:])


@functools.partial(
    jax.jit, static_argnames=("scale", "precision_level", "blocks",
                              "interpret"))
def _flash_fwd_jit(q, k, v, scale, precision_level, blocks, interpret):
    """(out, (m, l)): the tiled forward.  q/k/v are (B, T, dh); the
    row statistics come back (B, Tq_padded, 128) f32 each,
    lane-broadcast (the backward kernels read the same layout)."""
    b, t, dh = q.shape
    bq, bk = _clamped_blocks(blocks, t)
    qp = pad_to(q, (None, bq, 128))
    kp = pad_to(k, (None, bk, 128))
    vp = pad_to(v, (None, bk, 128))
    _, tq, dhp = qp.shape
    tk = kp.shape[1]
    n_k = tk // bk
    grid = (b, tq // bq, n_k)

    out, row_max, row_sum = pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=n_k, scale=scale,
                          t_real=t, bk=bk,
                          precision_level=precision_level),
        name=FWD_KERNEL_NAME,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, i, kk: (bb, kk, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, i, kk: (bb, kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, i, kk: (bb, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tq, dhp), q.dtype),
            jax.ShapeDtypeStruct((b, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dhp), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return unpad(out, (b, t, dh)), (row_max, row_sum)


# -- backward kernels --------------------------------------------------------


def _probabilities(s, m_ref, l_ref):
    """The forward's probability tile again, from its saved row max
    and row sum (see the module docstring on why not a logsumexp)."""
    return jnp.exp(s - m_ref[0][:, :1]) * (1.0 / l_ref[0][:, :1])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                   dq_ref, acc_ref, *, n_k, scale, t_real, bk,
                   precision_level):
    """dq for one q-tile, accumulated over k-tiles: the probability
    tile is recomputed from the saved row statistics
    (recompute-over-store), then ds = p * (dp - delta) and
    dq += ds @ k * scale."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = mxu_partial_dot(q_ref[0], k_ref[0].T, precision_level) * scale
    col = kk * bk + _col_ids(*s.shape)
    s = jnp.where(col < t_real, s, _MASK_FLOOR)
    p = _probabilities(s, m_ref, l_ref)
    dp = mxu_partial_dot(do_ref[0].astype(jnp.float32), v_ref[0].T,
                         precision_level)
    ds = p * (dp - delta_ref[0][:, :1]) * scale
    acc_ref[:] += mxu_partial_dot(ds, k_ref[0], precision_level)

    @pl.when(kk == n_k - 1)
    def _store():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, n_q, scale, t_real, bk, precision_level):
    """dk/dv for one k-tile, accumulated over q-tiles.  Padded key
    columns are masked to exact-zero probabilities, so their dk/dv
    rows come out 0 and the unpad slices them away."""
    qq = pl.program_id(2)

    @pl.when(qq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    kk = pl.program_id(1)
    s = mxu_partial_dot(q_ref[0], k_ref[0].T, precision_level) * scale
    col = kk * bk + _col_ids(*s.shape)
    s = jnp.where(col < t_real, s, _MASK_FLOOR)
    p = _probabilities(s, m_ref, l_ref)
    do = do_ref[0].astype(jnp.float32)
    dv_acc_ref[:] += mxu_partial_dot(p.T, do, precision_level)
    dp = mxu_partial_dot(do, v_ref[0].T, precision_level)
    ds = p * (dp - delta_ref[0][:, :1]) * scale
    dk_acc_ref[:] += mxu_partial_dot(ds.T, q_ref[0], precision_level)

    @pl.when(qq == n_q - 1)
    def _store():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "precision_level", "blocks",
                              "interpret"))
def _flash_bwd_jit(q, k, v, out, stats, do, scale, precision_level,
                   blocks, interpret):
    """(dq, dk, dv) via the two tiled backward kernels.  ``delta`` =
    rowsum(do * out) is the standard flash-backward precompute — one
    elementwise pass, kept outside the kernels like conv-VJP keeps its
    dgrad as a lax conv."""
    b, t, dh = q.shape
    row_max, row_sum = stats
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
    tk = kp.shape[1]
    n_q, n_k = tq // bq, tk // bk

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, scale=scale,
                          t_real=t, bk=bk,
                          precision_level=precision_level),
        name=DQ_KERNEL_NAME,
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk: (bb, i, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, i, kk: (bb, kk, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, i, kk: (bb, kk, 0)),
            pl.BlockSpec((1, bq, dhp), lambda bb, i, kk: (bb, i, 0)),
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

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, scale=scale,
                          t_real=t, bk=bk,
                          precision_level=precision_level),
        name=DKV_KERNEL_NAME,
        grid=(b, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, dhp), lambda bb, kk, i: (bb, i, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, kk, i: (bb, kk, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, kk, i: (bb, kk, 0)),
            pl.BlockSpec((1, bq, dhp), lambda bb, kk, i: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, kk, i: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, kk, i: (bb, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda bb, kk, i: (bb, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dhp), lambda bb, kk, i: (bb, kk, 0)),
            pl.BlockSpec((1, bk, dhp), lambda bb, kk, i: (bb, kk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tk, dhp), q.dtype),
            jax.ShapeDtypeStruct((b, tk, dhp), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dhp), jnp.float32),
            pltpu.VMEM((bk, dhp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, row_max, row_sum, delta)

    return (unpad(dq, (b, t, dh)), unpad(dk, (b, t, dh)),
            unpad(dv, (b, t, dh)))


# -- the custom_vjp entry ----------------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_fn(scale, precision_level, blocks):
    """Per-static-config custom_vjp, cached so jit tracing sees one
    stable callable per (scale, level, schedule) — the conv_act
    pattern."""

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _flash_fwd_jit(q, k, v, scale, precision_level,
                                blocks, interpret_for(q, k, v))
        return out

    def fwd(q, k, v):
        out, stats = _flash_fwd_jit(q, k, v, scale, precision_level,
                                    blocks, interpret_for(q, k, v))
        return out, (q, k, v, out, stats)

    def bwd(res, do):
        q, k, v, out, stats = res
        return _flash_bwd_jit(q, k, v, out, stats, do, scale,
                              precision_level, blocks,
                              interpret_for(q, k, v))

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, scale=None, precision_level=0,
                    blocks=None):
    """Tiled online-softmax attention with the Pallas backward
    attached: ``softmax(q @ k^T * scale) @ v`` over (B, T, dh)
    operands (B = batch x heads; the model layer folds heads in).

    ``precision_level`` follows the matmul ladder for every product
    step (docs/kernels.md); ``blocks=None`` consults the ``attention``
    schedule-cache family before the static ``_DEFAULT_BLOCKS``.
    """
    if q.ndim != 3 or k.shape != q.shape or v.shape != q.shape:
        raise ValueError("flash_attention expects matching (B, T, dh) "
                         "operands, got %s %s %s" %
                         (q.shape, k.shape, v.shape))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if blocks is None:
        blocks = _tuned_blocks(q, precision_level) or _DEFAULT_BLOCKS
    out = _flash_fn(float(scale), int(precision_level),
                    tuple(blocks))(q, k, v)
    if _common.DEBUG_NONFINITE and not isinstance(out, jax.core.Tracer):
        _debug_check(q, k, v, out, precision_level)
    return out


def attention_reference(q, k, v, scale=None, precision_level=1):
    """Stock softmax attention in the kernel's exact op order — the
    ``VELES_PALLAS_BWD=0`` fallback (plain jnp, stock autodiff) AND
    the parity oracle: on shapes that fit one (bq, bk) tile the flash
    kernel executes this sequence verbatim AT THE SAME LEVEL, so the
    two agree within a few ULP there (module docstring); multi-tile
    shapes differ only by the online rescale's accumulation order
    (ULP-bounded, tests/test_transformer.py).

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
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def one(qb, kb, vb):
        s = mxu_partial_dot(qb, kb.T, precision_level) * scale
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
