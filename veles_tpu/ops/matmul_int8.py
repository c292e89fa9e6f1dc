"""Int8 quantized matmul + conv forward: the MXU's native 8-bit level.

"In-Datacenter Performance Analysis of a TPU" (PAPERS.md) is the
motivation: production inference is a hard-latency, throughput-per-chip
game the MXU wins with 8-bit multipliers — the original TPU's 92 TOPS
were *int8* TOPS.  This module is that level of the precision ladder
(docs/kernels.md): int8 operands, **int32 accumulation** (exact — no
Kahan/Neumaier machinery needed, integer sums cannot lose digits), and
a **fused dequant-rescale epilogue** in the same kernel store that
writes the output tile, so the f32 result never round-trips through
HBM as raw int32.

Layout mirrors ``ops/matmul.py``: the grid walks (M/bm, N/bn) with the
K loop innermost accumulating into an int32 VMEM scratch; the PRODUCT
step is the shared :func:`veles_tpu.ops.common.mxu_int8_dot` (this
kernel and the conv forward cannot drift on it).  Int8 changes the
MXU-legal tile quanta: the minimum native tile is (32, 128) — sublane
32 on the second-minor axis vs f32's 8 — so tiles and padding here
quantize to 32/128 multiples, and the schedule-cache family
(``tune/spec.py`` ``matmul_int8``) carries its own ``kernel_version``
so f32 tiles can never serve an int8 call.

``conv2d_int8`` lowers the conv forward onto the SAME kernel: per-tap
strided slices of the zero-padded input (pure data movement, exact in
the int8 domain) stack into an im2col patch matrix, one
``matmul_int8`` contraction produces the (P, Cout) output, and the
per-output-channel dequant scales + bias ride the shared epilogue.

Numerics contract (tests/test_quant.py): integer accumulation is exact
and the epilogue is the same f32 expression as
:func:`matmul_int8_reference`, so the Pallas kernel (interpret mode on
CPU, Mosaic on TPU) matches the reference **bit-exactly** — the
acceptance bound the quantized serve engine's parity receipt
(QUANT.json) is anchored to.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import (ceil_mult, interpret_for,
                                   mxu_int8_dot, pad_to, unpad)

__all__ = ["matmul_int8", "matmul_int8_reference", "conv2d_int8",
           "MATMUL_INT8_KERNEL_VERSION", "INT8_SUBLANE"]

#: the kernel's name in compiled HLO and device traces (``%veles_matmul_int8``)
KERNEL_NAME = "veles_matmul_int8"

#: int8's native MXU tile is (32, 128): the sublane quantum is 32 (vs
#: f32's 8) because four int8 rows pack one 32-bit sublane register
INT8_SUBLANE = 32

#: smaller default M-tile than the f32 kernel: int8 operand tiles are
#: 4x denser per byte, so the VMEM balance shifts toward the f32/int32
#: accumulator, which scales with bm*bn only
_DEFAULT_BLOCKS = (256, 512, 512)

#: bump when the kernel's algorithm changes — persisted tuned schedules
#: are only valid for the algorithm they were measured on (the same
#: contract as MATMUL_KERNEL_VERSION, docs/kernels.md "Autotuning")
MATMUL_INT8_KERNEL_VERSION = 1


def _matmul_int8_kernel(a_ref, b_ref, scale_ref, bias_ref, out_ref,
                        acc_ref, *, n_k):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j] in int32; the
    last K step dequantizes: out = f32(acc) * scale[j] + bias[j].

    ``scale_ref``/``bias_ref`` are (1, bn) blocks of the per-output-
    channel dequant scale (activation scale x per-channel weight
    scale) and the f32 bias — fused into the store so the int32
    accumulator never leaves VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += mxu_int8_dot(a_ref[:], b_ref[:])

    @pl.when(k == n_k - 1)
    def _store():
        total = acc_ref[:].astype(jnp.float32) * scale_ref[:]
        total = total + bias_ref[:]
        out_ref[:] = total.astype(out_ref.dtype)


def matmul_int8(a, b, scale, bias=None, blocks=None,
                out_dtype=jnp.float32):
    """``dequant(a @ b)`` through the int8 Pallas kernel.

    a: (M, K) int8, b: (K, N) int8.  ``scale`` is the combined dequant
    factor — a scalar or an (N,) per-output-channel vector (activation
    scale x per-channel weight scale); ``bias`` an optional (N,) f32
    vector added AFTER dequant (biases stay f32 in post-training
    quantization: they are tiny and quantizing them buys nothing).
    Products accumulate in int32 (exact); the epilogue computes
    ``f32(acc) * scale + bias`` and casts to ``out_dtype``.

    ``blocks=None`` consults the tuned schedule cache under the
    ``matmul_int8`` family (its own kernel version and int8 tile
    quanta — an f32 schedule can never serve this kernel) before the
    static default.  Like :func:`veles_tpu.ops.matmul.matmul` this is
    a thin eager wrapper: the interpret-mode decision needs concrete
    operand placement, so CPU tests run the identical kernel through
    the Pallas interpreter.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.dtype != jnp.int8 or b.dtype != jnp.int8:
        raise TypeError("matmul_int8 expects int8 operands, got %s @ %s"
                        % (a.dtype, b.dtype))
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("matmul_int8 expects 2-D operands")
    n = b.shape[1]
    scale = jnp.asarray(scale, jnp.float32)
    if scale.ndim == 0:
        scale = jnp.full((n,), scale, jnp.float32)
    if scale.shape != (n,):
        raise ValueError("scale must be scalar or (N,)=(%d,), got %s"
                         % (n, scale.shape))
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    else:
        bias = jnp.asarray(bias, jnp.float32)
        if bias.shape != (n,):
            raise ValueError("bias must be (N,)=(%d,), got %s"
                             % (n, bias.shape))
    if blocks is None:
        blocks = _tuned_blocks(a, b)
    return _matmul_int8_jit(a, b, scale, bias, blocks,
                            jnp.dtype(out_dtype).name,
                            interpret_for(a, b))


def _tuned_blocks(a, b):
    """Schedule-cache consult for a ``blocks=None`` call (tracer-safe:
    shapes only) — the tuned (bm, bn, bk) for this padded int8 shape
    or None (-> ``_DEFAULT_BLOCKS``)."""
    if (getattr(a, "ndim", None) != 2 or getattr(b, "ndim", None) != 2
            or a.shape[1] != b.shape[0]):
        return None
    m, k = a.shape
    n = b.shape[1]
    if not (m and k and n):
        return None
    from veles_tpu.tune.cache import schedule_for
    from veles_tpu.tune.spec import matmul_int8_spec, valid_schedule
    spec = matmul_int8_spec(m, k, n)
    schedule = schedule_for(spec["op"], spec["shape"], spec["dtype"],
                            spec["precision_level"], spec["extra"],
                            raw=spec["raw"])
    if schedule is None:
        return None
    normalized = valid_schedule("matmul_int8", schedule)
    return tuple(normalized["blocks"]) if normalized else None


@functools.partial(
    jax.jit, static_argnames=("blocks", "out_dtype", "interpret"))
def _matmul_int8_jit(a, b, scale, bias, blocks, out_dtype, interpret):
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError("shape mismatch: %s @ %s" % (a.shape, b.shape))
    if m == 0 or n == 0 or k == 0:
        return jnp.broadcast_to(bias[None, :], (m, n)).astype(out_dtype)
    bm, bn, bk = blocks or _DEFAULT_BLOCKS
    bm = min(bm, ceil_mult(m, INT8_SUBLANE))
    bn = min(bn, ceil_mult(n, 128))
    bk = min(bk, ceil_mult(k, 128))
    a = pad_to(a, (bm, bk))
    b = pad_to(b, (bk, bn))
    scale2 = pad_to(scale[None, :], (None, bn))
    bias2 = pad_to(bias[None, :], (None, bn))
    mp, kp = a.shape
    _, np_ = b.shape
    n_k = kp // bk
    grid = (mp // bm, np_ // bn, n_k)

    out = pl.pallas_call(
        functools.partial(_matmul_int8_kernel, n_k=n_k),
        name=KERNEL_NAME,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, scale2, bias2)
    return unpad(out, (m, n))


def matmul_int8_reference(a, b, scale, bias=None,
                          out_dtype=jnp.float32):
    """The untiled reference the kernel must match BIT-exactly: one
    int32 dot, the identical f32 dequant expression.  Integer
    accumulation is exact under any tile grouping and the epilogue
    applies the same elementwise ops in the same order, so equality is
    bitwise, not a ULP bound (tests/test_quant.py asserts it).

    Compare under ``jax.jit``: XLA contracts the epilogue's mul+add
    into an FMA inside compiled programs (the kernel always runs
    compiled), so the JITTED reference is the bit-exact twin; the
    eager reference can differ by 1 ulp where the FMA rounds once."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    n = b.shape[1]
    scale = jnp.asarray(scale, jnp.float32)
    if scale.ndim == 0:
        scale = jnp.full((n,), scale, jnp.float32)
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    acc = mxu_int8_dot(a, b)
    total = acc.astype(jnp.float32) * scale[None, :]
    total = total + jnp.asarray(bias, jnp.float32)[None, :]
    return total.astype(out_dtype)


def conv2d_int8(x, w, scale, bias=None, padding=(0, 0, 0, 0),
                sliding=(1, 1), blocks=None, out_dtype=jnp.float32):
    """Int8 conv forward through the SAME shared product step: per-tap
    strided slices of the zero-padded input stack into an im2col patch
    matrix (pure data movement — exact in the int8 domain; the f32
    conv's zero padding quantizes to int8 zero, so semantics match),
    then ONE ``matmul_int8`` contraction with the per-Cout dequant
    scales and bias fused into its epilogue.

    x: (N, H, W, Cin) int8, w: (ky, kx, Cin, Cout) int8 (HWIO, the
    layout ``models/conv.py`` trains in); ``scale`` scalar or (Cout,);
    ``padding`` = (left, top, right, bottom), ``sliding`` = (sx, sy) —
    the Conv unit's static config, verbatim.  Returns (N, OH, OW,
    Cout) in ``out_dtype``.  The tap loop unrolls at trace time into
    ky*kx slices, mirroring how ``ops/conv_vjp.py`` walks taps in its
    wgrad grid."""
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    if x.ndim == 3:
        x = x[..., None]
    if x.dtype != jnp.int8 or w.dtype != jnp.int8:
        raise TypeError("conv2d_int8 expects int8 operands, got %s / %s"
                        % (x.dtype, w.dtype))
    n, h, w_sp, ci = x.shape
    ky, kx, ci2, cout = w.shape
    if ci != ci2:
        raise ValueError("channel mismatch: x %s vs w %s" %
                         (x.shape, w.shape))
    left, top, right, bottom = padding
    sx, sy = sliding
    xp = jnp.pad(x, ((0, 0), (top, bottom), (left, right), (0, 0)))
    oh = (h + top + bottom - ky) // sy + 1
    ow = (w_sp + left + right - kx) // sx + 1
    taps = []
    for dy in range(ky):
        for dx in range(kx):
            taps.append(xp[:, dy:dy + (oh - 1) * sy + 1:sy,
                           dx:dx + (ow - 1) * sx + 1:sx, :])
    patches = jnp.concatenate(taps, axis=-1)      # tap-major, then Cin
    patches = patches.reshape(n * oh * ow, ky * kx * ci)
    z = matmul_int8(patches, w.reshape(ky * kx * ci, cout), scale,
                    bias=bias, blocks=blocks, out_dtype=out_dtype)
    return z.reshape(n, oh, ow, cout)
