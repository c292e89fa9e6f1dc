"""Fused conv-VJP Pallas kernel family — the hand-scheduled backward
for the conv layers (docs/kernels.md).

The thesis (docs/kernels.md; not measured on today's code — ROADMAP
S1/D1 holds the A/B that decides it): a fused step's backward loses
MXU time to COMPOSITION, not to any single op — the step interleaves
every layer's dgrad/wgrad/epilogue/bias ops freely.  This module
replaces the autodiff conv backward with a scheduled composition:

- **wgrad** as a batch-contraction matmul over per-tap strided slices
  of the (padded) input — ONE Pallas kernel whose grid walks
  (Cout-tiles, taps, Cin-tiles, P-tiles) with an f32 scoped-VMEM
  accumulator, following the ``ops/matmul.py`` kernel/interpret/
  precision-level pattern (the PRODUCT step is the shared
  ``common.mxu_partial_dot``, so level 0 runs the bf16x3 decomposition
  for f32 operands and bf16 operands take single-pass MXU products).
- the **elementwise epilogue fused into the matmul tiles**: the
  activation backward (in terms of the forward OUTPUT y, exactly like
  the gd units) and the bias-grad reduction both happen on the (P, Cout)
  tiles the wgrad contraction already streams through VMEM — no
  separate elementwise pass over the cotangent, no extra HBM round
  trip for ``err``.  The kernel emits ``err`` as a third output for the
  dgrad to consume.
- **dgrad** as the explicit lhs-dilated conv (transposed conv: dilate
  ``err`` by the forward stride, convolve with the spatially-flipped
  I/O-swapped kernel) — the formulation XLA's own transpose rule uses,
  kept as a lax conv; it consumes the fused ``err`` instead of
  recomputing the epilogue.

Traffic note: the per-tap slices materialize ~taps x input bytes in
HBM, like im2col (AlexNet conv2 at batch 256 in bf16: 25 taps x 186,624
rows x 128 padded lanes = 1.2 GB).  Kernels with more than
``MAX_FUSED_TAPS`` taps (AlexNet's 11x11 layer 0) keep the stock
autodiff VJP; :func:`conv_vjp_route` names the road.

Parity contract (tests/test_pallas_bwd.py, ``pallas`` marker): dgrad
is bit-exact vs autodiff; wgrad/bias-grad are bit-exact on
exactly-representable cotangents and within a documented ULP bound
(~1e-6 rel for f32 level>=1, ~5e-7 products + tile-order accumulation
for level 0 bf16x3) on random ones — tile-parallel f32 accumulation
cannot reproduce XLA's reduction order bit-for-bit.  The
``VELES_PALLAS_BWD=0`` fallback restores the autodiff backward
bit-exactly (it IS the stock code path).
"""

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops import common as _common
from veles_tpu.ops.common import (ceil_mult, interpret_for,
                                   mxu_partial_dot, pad_to, unpad)

__all__ = ["fused_conv_vjp", "conv_act", "activation_grad",
           "ACTIVATIONS", "MAX_FUSED_TAPS", "conv_vjp_route",
           "CONV_VJP_KERNEL_VERSION"]

#: the kernel's name in compiled HLO and device traces (``%veles_conv_wgrad``)
KERNEL_NAME = "veles_conv_wgrad"

#: bump when the wgrad kernel's algorithm changes: tuned schedules in
#: the cache are only valid for the algorithm they were measured on
#: (the version rides the schedule-cache digest, so old entries become
#: misses, never silently-served stale tiles)
CONV_VJP_KERNEL_VERSION = 1

#: kernels with more taps than this keep the autodiff VJP: the per-tap
#: slice stack would multiply activation traffic past any MXU cover
#: (AlexNet layer 0's 11x11 = 121 taps is the motivating case)
MAX_FUSED_TAPS = 32

_DEFAULT_BLOCKS = (256, 256, 512)  # (bi=Cin, bj=Cout, bk=P) tile sizes


def conv_vjp_route(ky, kx):
    """"pallas" or "autodiff": the road :func:`fused_conv_vjp` takes
    for a ``ky x kx`` kernel."""
    return "autodiff" if ky * kx > MAX_FUSED_TAPS else "pallas"


# -- activation epilogues ----------------------------------------------------
# Derivatives in terms of the forward OUTPUT y (no pre-activation state
# stored) — the same closed forms the gd units use (models/gd.py), kept
# here as (name -> grad(y, err)) so the kernel can fuse them by name.

def _grad_linear(y, err):
    return err


def _grad_strict_relu(y, err):
    return err * (y > 0)


def _grad_relu_log(y, err):
    # y = log(1+exp(x))  =>  dy/dx = 1 - exp(-y)
    return err * (1.0 - jnp.exp(-y))


def _grad_tanh(y, err):
    # y = A*tanh(B x)  =>  dy/dx = (B/A)*(A^2 - y^2); A/B come from the
    # forward's own class so the closed form can never desynchronize
    from veles_tpu.models.all2all import All2AllTanh
    a, b = All2AllTanh.A, All2AllTanh.B
    return err * ((b / a) * (a * a - y * y))


def _grad_sigmoid(y, err):
    return err * (y * (1.0 - y))


ACTIVATIONS = {
    "linear": _grad_linear,
    "strict_relu": _grad_strict_relu,
    "relu_log": _grad_relu_log,
    "tanh": _grad_tanh,
    "sigmoid": _grad_sigmoid,
}


def activation_grad(activation, y, err):
    """err * d(activation)/dz expressed via the forward output y."""
    return ACTIVATIONS[activation](y, err)


# -- the fused epilogue + wgrad + bias kernel --------------------------------


def _wgrad_kernel(xt_ref, y_ref, dy_ref, gw_ref, gb_ref, err_ref,
                  acc_ref, comp_ref, bias_ref, *, n_k,
                  precision_level, activation, err_dtype):
    """One (j, t, i, k) grid step of the batch-contraction wgrad.

    Grid order is (Cout-tile j, tap t, Cin-tile i, P-tile k) with k
    innermost, so ``acc_ref`` (f32 scoped VMEM) accumulates one
    (bi, bj) weight-gradient tile over the full P sweep.  The epilogue
    — activation backward + bias reduction — runs on the (bk, bj)
    err tile the contraction streams anyway; ``err`` is stored for the
    dgrad, and the bias sum accumulates once (on the t==0, i==0
    sweep), landing in ``gb_ref`` whose block index is constant per j
    so the window stays VMEM-resident until j advances.
    """
    t = pl.program_id(1)
    i = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if precision_level > 0:
            comp_ref[:] = jnp.zeros_like(comp_ref)

    first_sweep = (t == 0) & (i == 0)

    @pl.when(first_sweep & (k == 0))
    def _init_bias():
        bias_ref[:] = jnp.zeros_like(bias_ref)

    # fused elementwise epilogue: activation backward on the forward
    # OUTPUT tile + the incoming cotangent tile, in f32 on the VPU
    err_f32 = activation_grad(activation, y_ref[:].astype(jnp.float32),
                              dy_ref[:].astype(jnp.float32))
    err = err_f32.astype(err_dtype)
    # written every visit (recomputed per (t, i) anyway — idempotent),
    # so output-window revisits never flush stale data
    err_ref[:] = err

    @pl.when(first_sweep)
    def _bias():
        bias_ref[0:1, :] += jnp.sum(err_f32, axis=0, keepdims=True)

    partial = mxu_partial_dot(xt_ref[0].T, err, precision_level)
    if precision_level == 0:
        acc_ref[:] += partial
    elif precision_level == 1:
        # Kahan across P-tile partial sums (matmul.py's ladder)
        y_c = partial - comp_ref[:]
        t_c = acc_ref[:] + y_c
        comp_ref[:] = (t_c - acc_ref[:]) - y_c
        acc_ref[:] = t_c
    else:
        acc = acc_ref[:]
        t_c = acc + partial
        big = jnp.abs(acc) >= jnp.abs(partial)
        comp_ref[:] += jnp.where(big, (acc - t_c) + partial,
                                 (partial - t_c) + acc)
        acc_ref[:] = t_c

    @pl.when(k == n_k - 1)
    def _store():
        total = acc_ref[:]
        if precision_level == 2:
            total = total + comp_ref[:]
        gw_ref[0] = total

    @pl.when(first_sweep & (k == n_k - 1))
    def _store_bias():
        gb_ref[:] = bias_ref[0:1, :]


def _build_tap_stack(x, ky, kx, out_hw, padding, sliding):
    """(taps, N*OH*OW, Ci) strided-slice stack of the padded input:
    tap (kh, kw)'s matrix row p = (n, oh, ow) is
    x_pad[n, oh*sy + kh, ow*sx + kw, ci].  ``lax.pad`` handles the
    possibly-negative high padding (stride may leave the bottom/right
    input rows uncovered by any window)."""
    from jax import lax
    left, top, _right, _bottom = padding
    sx, sy = sliding
    oh, ow = out_hw
    n, h, w_sp, ci = x.shape
    need_h = (oh - 1) * sy + ky
    need_w = (ow - 1) * sx + kx
    zero = jnp.zeros((), x.dtype)
    xp = lax.pad(x, zero,
                 [(0, 0, 0), (top, need_h - h - top, 0),
                  (left, need_w - w_sp - left, 0), (0, 0, 0)])
    taps = []
    for kh in range(ky):
        for kw in range(kx):
            sl = lax.slice(
                xp, (0, kh, kw, 0),
                (n, kh + (oh - 1) * sy + 1, kw + (ow - 1) * sx + 1, ci),
                (1, sy, sx, 1))
            taps.append(sl.reshape(n * oh * ow, ci))
    return jnp.stack(taps)


@functools.partial(
    jax.jit, static_argnames=("activation", "ky", "kx", "out_hw",
                              "padding", "sliding", "precision_level",
                              "blocks", "interpret"))
def _fused_wgrad_jit(x, y, dy, activation, ky, kx, out_hw, padding,
                     sliding, precision_level, blocks, interpret):
    """(grad_w f32 (ky,kx,Ci,Cout), grad_b f32 (Cout,), err x.dtype) —
    the Pallas-scheduled half of the conv VJP."""
    n, _h, _w, ci = x.shape
    oh, ow = out_hw
    cout = y.shape[-1]
    p = n * oh * ow

    xt = _build_tap_stack(x, ky, kx, out_hw, padding, sliding)
    ym = y.reshape(p, cout)
    dym = dy.reshape(p, cout)

    bi, bj, bk = blocks or _DEFAULT_BLOCKS
    # Cin rides the LANE axis of the tap stack and the sublane axis of
    # the weight tile, so it pads to 128; Cout is lanes everywhere
    bi = min(bi, ceil_mult(ci, 128))
    bj = min(bj, ceil_mult(cout, 128))
    bk = min(bk, ceil_mult(p, 8))
    xt = pad_to(xt, (None, bk, bi))
    ym = pad_to(ym, (bk, bj))
    dym = pad_to(dym, (bk, bj))
    n_taps, pp, cip = xt.shape
    cop = ym.shape[1]
    n_k = pp // bk
    grid = (cop // bj, n_taps, cip // bi, n_k)

    gw, gb, err = pl.pallas_call(
        functools.partial(_wgrad_kernel, n_k=n_k,
                          precision_level=precision_level,
                          activation=activation, err_dtype=x.dtype),
        name=KERNEL_NAME,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, bi), lambda j, t, i, k: (t, k, i)),
            pl.BlockSpec((bk, bj), lambda j, t, i, k: (k, j)),
            pl.BlockSpec((bk, bj), lambda j, t, i, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bi, bj), lambda j, t, i, k: (t, i, j)),
            pl.BlockSpec((1, bj), lambda j, t, i, k: (0, j)),
            pl.BlockSpec((bk, bj), lambda j, t, i, k: (k, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_taps, cip, cop), jnp.float32),
            jax.ShapeDtypeStruct((1, cop), jnp.float32),
            jax.ShapeDtypeStruct((pp, cop), x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bi, bj), jnp.float32),
            pltpu.VMEM((bi, bj), jnp.float32),
            pltpu.VMEM((8, bj), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(xt, ym, dym)

    grad_w = unpad(gw, (n_taps, ci, cout)).reshape(ky, kx, ci, cout)
    grad_b = unpad(gb, (1, cout))[0]
    err = unpad(err, (p, cout)).reshape(n, oh, ow, cout)
    return grad_w, grad_b, err


def _dgrad_lhs_dilated(err, w, x_shape, padding, sliding):
    """dX via the transposed conv: dilate err by the forward stride and
    convolve with the spatially-flipped, I/O-swapped kernel — the same
    lhs-dilated formulation XLA's own conv transpose rule emits, so it
    is bit-identical to the autodiff dgrad (tests prove it)."""
    from jax import lax
    ky, kx = w.shape[0], w.shape[1]
    left, top, _right, _bottom = padding
    sx, sy = sliding
    h, w_sp = x_shape[1], x_shape[2]
    oh, ow = err.shape[1], err.shape[2]
    lo_h, hi_h = ky - 1 - top, h + top - (oh - 1) * sy - 1
    lo_w, hi_w = kx - 1 - left, w_sp + left - (ow - 1) * sx - 1
    w_t = w[::-1, ::-1].swapaxes(2, 3)
    pet = jnp.float32 if err.dtype == jnp.float32 else None
    return lax.conv_general_dilated(
        err, w_t, window_strides=(1, 1),
        padding=((lo_h, hi_h), (lo_w, hi_w)),
        lhs_dilation=(sy, sx),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=pet).astype(err.dtype)


def fused_conv_vjp(x, w, y, err_output, *, activation="linear",
                   padding=(0, 0, 0, 0), sliding=(1, 1),
                   include_bias=True, need_err_input=True,
                   precision_level=0, blocks=None):
    """The hand-scheduled conv backward: (err_input, grad_w, grad_b).

    ``x``/``w``/``y`` are the forward operands and OUTPUT (activation
    included), ``err_output`` the incoming cotangent.  grad_w/grad_b
    come back f32 (callers cast); err_input in ``x.dtype`` or None.

    ``precision_level`` follows the matmul ladder for the wgrad
    contraction: 0 = bf16x3 products for f32 operands (fastest; safe
    under the PR 3 step-level finite guard, which skips a poisoned
    update bit-exactly), 1/2 = true-f32 products + Kahan/Neumaier.
    Falls back to the stock autodiff VJP when the tap count exceeds
    ``MAX_FUSED_TAPS`` (see module docstring).
    """
    ky, kx = int(w.shape[0]), int(w.shape[1])
    oh, ow = int(err_output.shape[1]), int(err_output.shape[2])
    if conv_vjp_route(ky, kx) == "autodiff":
        # once per trace, never per step: the shape routes, out loud
        logging.getLogger("veles_tpu.ops").info(
            "conv_vjp: %dx%d kernel (%d taps > MAX_FUSED_TAPS=%d) "
            "keeps the stock autodiff backward", ky, kx, ky * kx,
            MAX_FUSED_TAPS)
        return _autodiff_conv_vjp(
            x, w, y, err_output, activation=activation, padding=padding,
            sliding=sliding, include_bias=include_bias,
            need_err_input=need_err_input)
    if blocks is None:
        blocks = _tuned_blocks(x, ky, kx, oh, ow, err_output,
                               precision_level, activation, padding,
                               sliding)
    grad_w, grad_b, err = _fused_wgrad_jit(
        x, y, err_output, activation, ky, kx, (oh, ow),
        tuple(padding), tuple(sliding), precision_level, blocks,
        interpret_for(x, err_output))
    err_input = (_dgrad_lhs_dilated(err, w, x.shape, padding, sliding)
                 if need_err_input else None)
    if not include_bias:
        grad_b = None
    if _common.DEBUG_NONFINITE and not isinstance(grad_w, jax.core.Tracer):
        # eager calls only, like matmul's guard: the check concretizes
        # values, which would crash a jit trace (the fused train step
        # reaches here as tracers — its finite_guard owns that path)
        _debug_check(x, w, err_output, grad_w, grad_b, err_input,
                     precision_level)
    return err_input, grad_w, grad_b


def _tuned_blocks(x, ky, kx, oh, ow, err_output, precision_level,
                  activation, padding, sliding):
    """Schedule-cache consult for a ``blocks=None`` call: the tuned
    (bi, bj, bk) wgrad tiles for this (taps, padded P/Cin/Cout, dtype,
    precision, device) or None (-> ``_DEFAULT_BLOCKS``).  Padding/
    sliding/activation ride the recorded raw context only — the wgrad
    contraction's grid depends on the padded shape alone.  Tracer-safe
    (shapes/dtypes only), so the consult fires at trace time inside
    the fused step — which is how ``tune/walk.py`` records it."""
    from veles_tpu.tune.cache import schedule_for
    from veles_tpu.tune.spec import conv_vjp_spec, valid_schedule
    spec = conv_vjp_spec(
        x.shape, ky, kx, err_output.shape[-1], (oh, ow),
        jnp.dtype(x.dtype).name, precision_level, padding, sliding,
        activation)
    schedule = schedule_for(spec["op"], spec["shape"], spec["dtype"],
                            spec["precision_level"], spec["extra"],
                            raw=spec["raw"])
    if schedule is None:
        return None
    normalized = valid_schedule("conv_vjp", schedule)
    return tuple(normalized["blocks"]) if normalized else None


def _autodiff_conv_vjp(x, w, y, err_output, *, activation, padding,
                       sliding, include_bias, need_err_input):
    """The stock formulation (what gd_conv runs with the knob off),
    used as the many-tap fallback so the call-site contract is one
    function either way."""
    from veles_tpu.models.conv import Conv
    err = activation_grad(activation, y, err_output).astype(x.dtype)

    def lin(w_, x_):
        return Conv.apply({"weights": w_, "bias": None}, x_,
                          padding=padding, sliding=sliding,
                          pallas_bwd=False)

    _, vjp = jax.vjp(lin, w, x)
    grad_w, err_input = vjp(err)
    grad_b = (err.astype(jnp.float32).sum(axis=(0, 1, 2))
              if include_bias else None)
    return (err_input if need_err_input else None,
            grad_w.astype(jnp.float32), grad_b)


def _debug_check(x, w, dy, grad_w, grad_b, err_input, precision_level):
    """VELES_DEBUG_NONFINITE guard, same contract as matmul's: raise
    with operand stats when a finite input produced a non-finite
    gradient (the level-0 bf16x3 domain limit being the usual cause)."""
    outs = [("grad_w", grad_w)]
    if grad_b is not None:
        outs.append(("grad_b", grad_b))
    if err_input is not None:
        outs.append(("err_input", err_input))
    for name, out in outs:
        if not bool(jnp.isfinite(out).all()):
            from veles_tpu.ops.matmul import _operand_stats
            raise FloatingPointError(
                "fused_conv_vjp produced non-finite %s (%s; "
                "precision_level=%d — level 0's bf16x3 domain excludes "
                "|x| >= bf16-max)" % (
                    name, "; ".join((_operand_stats("x", x),
                                     _operand_stats("w", w),
                                     _operand_stats("dy", dy))),
                    precision_level))


# -- custom_vjp forward wrapper ---------------------------------------------


@functools.lru_cache(maxsize=None)
def _conv_act_fn(activation, padding, sliding, include_bias,
                 precision_level):
    """Per-static-config custom_vjp of act(conv(x, w) + b): the
    forward is EXACTLY models/conv.py's composition (bit-identical
    HLO), the backward is the fused family above.  Cached per config so
    jit tracing sees one stable callable per layer."""
    from veles_tpu.models.conv import conv2d

    left, top, right, bottom = padding
    sx, sy = sliding
    act = _forward_act(activation)

    def raw(x, w, *b):
        pet = jnp.float32 if x.dtype == jnp.float32 else None
        z = conv2d(x, w, (sy, sx), ((top, bottom), (left, right)), pet)
        if include_bias:
            z = z + b[0]
        return act(z).astype(x.dtype)

    @jax.custom_vjp
    def f(x, w, *b):
        return raw(x, w, *b)

    def fwd(x, w, *b):
        y = raw(x, w, *b)
        return y, (x, w, y) + b

    def bwd(res, dy):
        x, w, y = res[:3]
        err_input, grad_w, grad_b = fused_conv_vjp(
            x, w, y, dy, activation=activation, padding=padding,
            sliding=sliding, include_bias=include_bias,
            need_err_input=True, precision_level=precision_level)
        grads = (err_input, grad_w.astype(w.dtype))
        if include_bias:
            grads += (grad_b.astype(res[3].dtype),)
        return grads

    f.defvjp(fwd, bwd)
    return f


def conv_act(x, w, b, *, activation, padding, sliding,
             precision_level=0):
    """act(conv(x, w) + b) with the hand-scheduled backward attached
    (the entry models/conv.py routes through when VELES_PALLAS_BWD is
    on).  ``b`` may be None."""
    fn = _conv_act_fn(activation, tuple(padding), tuple(sliding),
                      b is not None, precision_level)
    return fn(x, w, b) if b is not None else fn(x, w)


def _forward_act(activation):
    """The forward activation by epilogue name — resolved to THE
    models/all2all.py staticmethod (the conv classes' _activate), not a
    local copy, so the knob-on forward is bit-identical to the knob-off
    forward by construction (lazy import: models import this module)."""
    from veles_tpu.models import all2all
    cls = {
        "linear": all2all.All2All,
        "strict_relu": all2all.All2AllStrictRELU,
        "relu_log": all2all.All2AllRELU,
        "tanh": all2all.All2AllTanh,
        "sigmoid": all2all.All2AllSigmoid,
    }[activation]
    return cls._activate
