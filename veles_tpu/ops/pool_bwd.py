"""Max-pool select-and-scatter backward Pallas kernel
(docs/kernels.md).

Formulation (one image x one 128-lane channel tile x one W tile per
grid step): for each tap (kh, kw) of the window, in row-major window
order, a tap element is SELECTED iff it equals the window max (the
forward output ``y``, which the unit already holds — no recompute) and
no earlier tap matched (first-match tie-break, the same scan order
XLA's SelectAndScatter folds ge-select in).  The selected cotangent is
accumulated back at the tap's input coordinates.

Both the tap read and the scatter are STRIDED REF accesses
(``ref[pl.ds(kh, oh, stride=sy), pl.ds(kw, ow, stride=sx), :]``) on f32
VMEM scratch: Mosaic lowers those to strided loads/stores, where a
strided slice or an ``.at[].add`` of a VALUE has no lowering (scatter).
H is a major dim and W the sublane dim of the (H, W, 128) blocks, so
neither stride touches the lane axis.

Ceil-mode partial windows (models/pooling.py pads bottom/right) are
covered by padding the input block with -inf: padded cells never equal
a real window max, exactly reduce_window's -inf init semantics.

Parity (tests/test_pallas_bwd.py): routing is bit-exact vs the
``jax.vjp(lax.reduce_window)`` reference on exactly-representable
cotangents (including ties and ceil-mode tails); random cotangents
agree within ~1 ULP where >= 2 overlapping windows sum in a different
order.  Blocks larger than the VMEM budget tile the W axis when the
windows do not overlap (kx == sx, ky == sy — the VGG 2x2/2 case) and
fall back to autodiff when they do; :func:`pool_bwd_route` names the
road a shape takes.
"""

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import ceil_mult, interpret_for, pad_to, unpad

__all__ = ["max_pool_bwd", "max_pool", "POOL_VMEM_BUDGET_BYTES",
           "POOL_BWD_KERNEL_VERSION", "pool_block_footprint",
           "pool_bwd_route"]

#: the kernel's name in compiled HLO and device traces (``%veles_pool_bwd``)
KERNEL_NAME = "veles_pool_bwd"

#: bump when the select-and-scatter kernel's algorithm changes: tuned
#: W-tilings in the schedule cache are keyed to the algorithm they
#: were measured on (stale versions miss, never serve).  v2 = strided
#: ref accesses + 128-lane channel tiles (the form Mosaic compiles).
POOL_BWD_KERNEL_VERSION = 2

#: per-grid-step VMEM budget for the pool blocks (double-buffered
#: x/y/dy/out windows + the two f32 scratch planes + tap temporaries),
#: under Mosaic's 16 MiB default scoped-VMEM limit (the footprint
#: formula below runs ~15-20 % over what Mosaic reports allocating);
#: overlapping-window shapes that exceed it keep the autodiff backward
POOL_VMEM_BUDGET_BYTES = 14 * 2 ** 20

#: channels ride the lane axis one 128-wide tile per grid step
_LANES = 128


def _pool_bwd_kernel(x_ref, y_ref, dy_ref, out_ref, xf_ref, acc_ref, *,
                     window, sliding, out_h, out_w):
    """One (n, c-tile, w-tile) grid step of the routed scatter."""
    ky, kx = window
    sx, sy = sliding
    # f32 planes: strided accesses need 32-bit rows (bf16 packs two
    # rows per sublane), and the upcast is exact so the equality below
    # routes exactly like the storage dtype would
    xf_ref[...] = x_ref[0].astype(jnp.float32)   # (Hp, Wb, 128), -inf pad
    acc_ref[...] = jnp.zeros_like(acc_ref)
    yv = y_ref[0].astype(jnp.float32)            # (OH, OWb, 128)
    dyv = dy_ref[0].astype(jnp.float32)
    unmatched = jnp.ones(yv.shape, jnp.float32)
    for kh in range(ky):
        for kw in range(kx):
            tap = (pl.ds(kh, out_h, stride=sy),
                   pl.ds(kw, out_w, stride=sx), slice(None))
            sel = jnp.where(xf_ref[tap] == yv, unmatched, 0.0)
            unmatched = unmatched - sel
            acc_ref[tap] = acc_ref[tap] + sel * dyv
    out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def pool_block_footprint(oh, owb, window, sliding, itemsize):
    """VMEM bytes of one grid step over ``oh x owb`` output cells: the
    double-buffered x/out and y/dy windows in the storage dtype, the
    two f32 scratch planes, and the three f32 tap temporaries.  The
    channel count does not enter — channels tile at 128 lanes whatever
    it is.  The ONE footprint formula — the
    kernel's planner below and the autotuner's feasibility gate
    (tune/spec.py) both call it, so they cannot drift when the block
    layout changes."""
    ky, kx = window
    sx, sy = sliding
    sublane = 8 * max(1, 4 // itemsize)
    in_elems = ((oh - 1) * sy + ky) * ceil_mult(
        (owb - 1) * sx + kx, sublane)
    out_elems = oh * ceil_mult(owb, sublane)
    return _LANES * (2 * itemsize * (2 * in_elems + 2 * out_elems)
                     + 4 * (2 * in_elems + 3 * out_elems))


def _plan_blocks(oh, ow, window, sliding, itemsize, owb_override=None):
    """(w-tiles, ow-block) fitting POOL_VMEM_BUDGET_BYTES, or None when
    the shape cannot tile (overlapping windows need the full W span).
    A W block is a multiple of 8 output columns (W is the sublane axis
    of the blocks) or the whole width.

    ``owb_override`` is a TUNED W block (docs/kernels.md
    "Autotuning"): honored only where halo-free tiling exists
    (kx == sx, ky == sy), the block is sublane-aligned and the
    footprint fits the budget; an infeasible/stale override logs a
    warning and falls back to the static plan — it can never overflow
    VMEM or crash the call."""
    ky, kx = window
    sx, sy = sliding

    def footprint(owb):
        return pool_block_footprint(oh, owb, window, sliding, itemsize)

    if (owb_override and 0 < owb_override < ow
            and kx == sx and ky == sy):
        if (owb_override % 8 == 0
                and footprint(owb_override) <= POOL_VMEM_BUDGET_BYTES):
            return -(-ow // owb_override), owb_override
        import logging
        logging.getLogger("veles_tpu.tune").warning(
            "tuned pool W block owb=%d is unaligned or exceeds the "
            "VMEM budget for this shape; using the static plan",
            owb_override)
    if footprint(ow) <= POOL_VMEM_BUDGET_BYTES:
        return 1, ow
    if kx != sx or ky != sy:
        return None  # overlapping windows: no halo-free W tiling
    for owb in range((ow - 1) // 8 * 8, 0, -8):
        if footprint(owb) <= POOL_VMEM_BUDGET_BYTES:
            return -(-ow // owb), owb
    return None


def pool_bwd_route(x_shape, window, sliding, dtype):
    """"pallas" or "autodiff": the road :func:`max_pool_bwd` takes for
    this input shape (the static plan; a tuned W block changes the
    tiling, never the road)."""
    from veles_tpu.models.pooling import _out_len
    ky, kx = window
    sx, sy = sliding
    _n, h, w_sp, _c = x_shape
    plan = _plan_blocks(_out_len(h, ky, sy), _out_len(w_sp, kx, sx),
                        window, sliding, jnp.dtype(dtype).itemsize)
    return "autodiff" if plan is None else "pallas"


@functools.partial(
    jax.jit, static_argnames=("window", "sliding", "interpret", "owb"))
def _max_pool_bwd_jit(x, y, dy, window, sliding, interpret, owb=None):
    from jax import lax
    ky, kx = window
    sx, sy = sliding
    n, h, w_sp, c = x.shape
    oh, ow = y.shape[1], y.shape[2]

    plan = _plan_blocks(oh, ow, window, sliding,
                        jnp.dtype(x.dtype).itemsize, owb_override=owb)
    if plan is None:
        # VMEM-infeasible overlapping shape: stock autodiff routing —
        # said once per trace, never per step
        logging.getLogger("veles_tpu.ops").info(
            "pool_bwd: %s input with a %s window / %s stride exceeds "
            "POOL_VMEM_BUDGET_BYTES untiled and cannot tile; keeping "
            "the stock autodiff backward", x.shape, window, sliding)
        from veles_tpu.models.pooling import MaxPooling

        def pool(x_):
            return MaxPooling.apply({}, x_, window=window,
                                    sliding=sliding, pallas_bwd=False)

        _, vjp = jax.vjp(pool, x)
        (err_input,) = vjp(dy.astype(x.dtype))
        return err_input
    n_wtiles, owb = plan

    need_h = (oh - 1) * sy + ky
    # W coverage: full need_w when untiled; owb*sx per tile when tiled
    # (tiling only happens for kx == sx, where need_w == ow*sx exactly,
    # so block offsets are exact multiples of the block width)
    bwx = (ow - 1) * sx + kx
    if n_wtiles > 1:
        bwx = owb * sx
    xw_total = n_wtiles * bwx
    neg_inf = jnp.asarray(-jnp.inf, x.dtype)
    # -inf padding everywhere a real (ceil-mode) window can peek past
    # the input — reduce_window's init semantics, so a padded cell can
    # never be selected over a real window max.  Channel padding is
    # plain zeros: a zero can only "match" a zero-padded y cell, whose
    # cotangent is the zero pad_to wrote (contributes nothing).
    xp = lax.pad(x, neg_inf,
                 [(0, 0, 0), (0, need_h - h, 0),
                  (0, xw_total - w_sp, 0), (0, 0, 0)])
    xp = pad_to(xp, (None, None, None, _LANES))
    y_p = pad_to(y, (None, None, owb, _LANES))
    dy_p = pad_to(dy, (None, None, owb, _LANES))
    cp = xp.shape[3]

    def block(rows, cols):
        return pl.BlockSpec((1, rows, cols, _LANES),
                            lambda i, j, k: (i, 0, k, j))

    out = pl.pallas_call(
        functools.partial(
            _pool_bwd_kernel, window=window, sliding=sliding,
            out_h=oh, out_w=owb),
        name=KERNEL_NAME,
        grid=(n, cp // _LANES, n_wtiles),
        in_specs=[block(need_h, bwx), block(oh, owb), block(oh, owb)],
        out_specs=block(need_h, bwx),
        out_shape=jax.ShapeDtypeStruct((n, need_h, xw_total, cp),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((need_h, bwx, _LANES), jnp.float32),
                        pltpu.VMEM((need_h, bwx, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xp, y_p, dy_p)
    return unpad(out, (n, h, w_sp, c))


def max_pool_bwd(x, y, err_output, *, window, sliding, owb=None):
    """err_input for max pooling via the scheduled select-and-scatter
    kernel: ``x`` the forward input, ``y`` the forward output (the
    window maxima — no recompute), ``err_output`` the incoming
    cotangent.  Returns err_input in ``x.dtype``.

    ``owb=None`` consults the tuned schedule cache for a W-tiling
    override (docs/kernels.md "Autotuning"); an explicit ``owb``
    bypasses the consult (the tuner's own candidate measurements)."""
    window = (int(window[0]), int(window[1]))
    sliding = (int(sliding[0]), int(sliding[1]))
    if owb is None:
        owb = _tuned_owb(x, y, window, sliding)
    return _max_pool_bwd_jit(x, y, err_output.astype(x.dtype),
                             window, sliding,
                             interpret_for(x, err_output), owb)


def _tuned_owb(x, y, window, sliding):
    """Schedule-cache consult: the tuned output-width block for this
    pool shape or None (-> the static ``_plan_blocks`` plan).
    Tracer-safe — shapes only — so it fires at trace time inside the
    fused step (``tune/walk.py`` records it there)."""
    from veles_tpu.tune.cache import schedule_for
    from veles_tpu.tune.spec import pool_bwd_spec, valid_schedule
    spec = pool_bwd_spec(x.shape, (y.shape[1], y.shape[2]), window,
                         sliding, jnp.dtype(x.dtype).name)
    schedule = schedule_for(spec["op"], spec["shape"], spec["dtype"],
                            spec["precision_level"], spec["extra"],
                            raw=spec["raw"])
    if schedule is None:
        return None
    normalized = valid_schedule("pool_bwd", schedule)
    return normalized["owb"] if normalized else None


# -- custom_vjp forward wrapper ---------------------------------------------


@functools.lru_cache(maxsize=None)
def _max_pool_fn(window, sliding):
    """Per-config custom_vjp of the max-pool forward: forward is
    EXACTLY models/pooling.py's reduce_window composition, backward is
    the kernel above."""
    from veles_tpu.models.pooling import MaxPooling

    def raw(x):
        return MaxPooling.apply({}, x, window=window, sliding=sliding,
                                pallas_bwd=False)

    @jax.custom_vjp
    def f(x):
        return raw(x)

    def fwd(x):
        y = raw(x)
        return y, (x, y)

    def bwd(res, dy):
        x, y = res
        return (max_pool_bwd(x, y, dy, window=window,
                             sliding=sliding),)

    f.defvjp(fwd, bwd)
    return f


def max_pool(x, *, window, sliding):
    """Max pooling with the select-and-scatter Pallas backward attached
    (models/pooling.py routes here when VELES_PALLAS_BWD is on)."""
    return _max_pool_fn((int(window[0]), int(window[1])),
                        (int(sliding[0]), int(sliding[1])))(x)
