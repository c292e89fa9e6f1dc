"""Shared helpers for the Pallas kernel set.

This module is also the kernels' ONE env contract: the interpret-mode
decision (``interpret_mode``/``interpret_for``), the opt-in non-finite
debug guard (``DEBUG_NONFINITE`` <- ``VELES_DEBUG_NONFINITE``), and the
hand-scheduled-backward knob (``PALLAS_BWD_ENV`` <-
``VELES_PALLAS_BWD``) all live here so matmul, conv-VJP and pool-bwd
kernels cannot drift apart on how they read the environment.  All env
vars are read ONCE at import; tests monkeypatch the module flags
directly.
"""

import functools
import os

import jax
import jax.numpy as jnp

__all__ = ["interpret_mode", "interpret_for", "pad_to", "unpad", "kernel_cast",
           "ceil_mult", "mxu_partial_dot", "mxu_int8_dot",
           "pallas_bwd_enabled", "DEBUG_NONFINITE", "PALLAS_BWD_ENV"]

#: opt-in per-call output validation (docs/health.md); the check forces
#: a device sync per eager kernel call, so it is for debugging only
DEBUG_NONFINITE = os.environ.get(
    "VELES_DEBUG_NONFINITE", "") not in ("", "0")

#: VELES_PALLAS_BWD: "" / "auto" -> hand-scheduled backward on real TPU
#: backends only; "0" -> always the stock autodiff backward (bit-exact
#: fallback contract, docs/kernels.md); anything else -> always on
#: (CPU parity tests run the kernels through the Pallas interpreter)
PALLAS_BWD_ENV = os.environ.get("VELES_PALLAS_BWD", "")


def pallas_bwd_enabled():
    """One resolution of the VELES_PALLAS_BWD knob for every caller
    (models/conv.py, models/pooling.py, the gd units, compiler.py).

    Reads the module flag, not the environment — the env was read once
    at import, and tests flip ``common.PALLAS_BWD_ENV`` directly."""
    env = PALLAS_BWD_ENV
    if env in ("", "auto"):
        return jax.default_backend() == "tpu"
    return env != "0"


def kernel_cast(x, dtype):
    """dtype cast safe inside Mosaic kernels: narrow ints widen to int32
    first (Mosaic has no direct narrow-int -> float lowering)."""
    if (jnp.issubdtype(x.dtype, jnp.integer) and
            jnp.issubdtype(dtype, jnp.floating) and
            x.dtype.itemsize < 4):
        x = x.astype(jnp.int32)
    return x.astype(dtype)


@functools.lru_cache(maxsize=None)
def interpret_mode():
    """True when jax's default backend has no Mosaic (CPU tests):
    Pallas kernels then execute in interpreter mode, same numerics.
    ``backends.TPUDevice`` refuses to exist beside a non-TPU default
    backend, so a run that asked for the chip never gets here with the
    interpreter on."""
    return jax.default_backend() == "cpu"


def interpret_for(*arrays):
    """Per-call interpret decision: Pallas needs the interpreter whenever
    the operand actually lives on CPU, whatever the process default
    backend is (a TPU host can still run CPU-device workflows).  Tracers
    carry no placement — they take the default-backend rule."""
    for x in arrays:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return any(d.platform == "cpu" for d in x.devices())
    return interpret_mode()


def mxu_partial_dot(a, b, precision_level):
    """One MXU tile product ``a @ b`` -> f32 partial, the single
    definition of the precision ladder's PRODUCT step shared by the
    matmul kernel and the conv-VJP wgrad kernel (the ACCUMULATION step
    — plain / Kahan / Neumaier — stays with each kernel's scratch).

    Level 0 on f32 inputs runs the bf16x3 decomposition (a_hi@b_hi +
    a_hi@b_lo + a_lo@b_hi): ~5e-7 max rel err vs an f64 oracle at ~2x
    the MXU's 6-pass true-f32 throughput.  |x| >= bf16-max (~3.39e38)
    and inf map to NaN — out of the decomposition's domain.  Levels
    1/2 pay for HIGHEST (true-f32) products.  bf16 inputs always take
    single-pass DEFAULT products (Mosaic rejects HIGHEST for bf16)."""
    if a.dtype == jnp.float32 and precision_level == 0:
        a_hi = a.astype(jnp.bfloat16)
        b_hi = b.astype(jnp.bfloat16)
        a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)

        def bf16_dot(x, y):
            return jnp.dot(x, y, preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.DEFAULT)

        return (bf16_dot(a_hi, b_hi) + bf16_dot(a_hi, b_lo)
                + bf16_dot(a_lo, b_hi))
    precision = (jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision)


def mxu_int8_dot(a, b):
    """One MXU tile product ``a @ b`` for int8 operands -> int32
    partial: the quantized level BELOW the f32/bf16 precision ladder
    (docs/kernels.md), shared by the int8 matmul kernel and the int8
    conv forward exactly like :func:`mxu_partial_dot` is shared by the
    f32/bf16 kernels.

    Integer products and sums are exact, so — unlike the float levels —
    tile grouping can never change the result: any schedule of this
    product step accumulates to bit-identical int32 totals, which is
    what makes the int8 kernels' tuned-vs-static and Pallas-vs-
    reference parity contracts *bit*-equalities rather than ULP
    bounds."""
    return jnp.dot(a, b, preferred_element_type=jnp.int32)


def ceil_mult(value, mult):
    """Round ``value`` up to the next multiple of ``mult``."""
    rem = value % mult
    return value if rem == 0 else value + mult - rem


def pad_to(x, multiples):
    """Zero-pad trailing dims of ``x`` up to the given multiples."""
    pads = []
    for dim, mult in zip(x.shape, multiples):
        if mult is None:
            pads.append((0, 0))
        else:
            rem = dim % mult
            pads.append((0, 0 if rem == 0 else mult - rem))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def unpad(x, shape):
    if x.shape == tuple(shape):
        return x
    return x[tuple(slice(0, s) for s in shape)]
