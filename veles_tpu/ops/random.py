"""Device-side random number generation.

TPU-native counterpart of reference ocl/random.cl:42-125 /
cuda/random.cu — the xorshift128+ and xorshift1024* generators (16 u64
words of state per stream, interleaved output) used by the Uniform
accelerated unit and, downstream, dropout.

TPUs have no native uint64, so the generators run on (hi, lo) uint32
pairs with explicit carry emulation — bit-exact against the u64
reference semantics (tests compare against a numpy u64 oracle, the same
role the reference's numpy fallback plays at prng/uniform.py:129-163).

For new code the idiomatic path is ``hardware_uniform`` (Pallas
``pltpu.prng_random_bits``) or ``jax.random``; the xorshift family is
kept for reference-parity workloads.
"""

import functools

import jax
import jax.numpy as jnp
import numpy
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import interpret_mode

__all__ = ["xorshift128plus", "xorshift1024star", "uniform_from_bits",
           "hardware_uniform", "numpy_xorshift128plus",
           "numpy_xorshift1024star"]

#: the kernel's name in compiled HLO and device traces (``%veles_random``)
KERNEL_NAME = "veles_random"

U32 = jnp.uint32


# -- u64 emulation on (hi, lo) uint32 pairs -------------------------------

def _shl(hi, lo, k):
    if k == 0:
        return hi, lo
    if k >= 32:
        return (lo << (k - 32)).astype(U32), jnp.zeros_like(lo)
    return ((hi << k) | (lo >> (32 - k))).astype(U32), (lo << k).astype(U32)


def _shr(hi, lo, k):
    if k == 0:
        return hi, lo
    if k >= 32:
        return jnp.zeros_like(hi), (hi >> (k - 32)).astype(U32)
    return (hi >> k).astype(U32), ((lo >> k) | (hi << (32 - k))).astype(U32)


def _xor(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _add(a, b):
    lo = (a[1] + b[1]).astype(U32)
    carry = (lo < a[1]).astype(U32)
    hi = (a[0] + b[0] + carry).astype(U32)
    return hi, lo


def _mul(a, konst):
    """(hi, lo) * constant mod 2**64 via 16-bit limbs (products fit u32)."""
    a_limbs = [(a[1] & 0xffff), (a[1] >> 16), (a[0] & 0xffff),
               (a[0] >> 16)]
    k_limbs = [U32((konst >> (16 * i)) & 0xffff) for i in range(4)]
    r = [jnp.zeros_like(a[1]) for _ in range(4)]
    for i in range(4):
        for j in range(4 - i):
            r[i + j] = (r[i + j] + a_limbs[i] * k_limbs[j]).astype(U32)
            # carry into the next limb (r slots hold up to 32 bits)
            if i + j + 1 < 4:
                carry = r[i + j] >> 16
                r[i + j] = r[i + j] & 0xffff
                r[i + j + 1] = (r[i + j + 1] + carry).astype(U32)
    lo = (r[0] | (r[1] << 16)).astype(U32)
    hi = ((r[2] & 0xffff) | (r[3] << 16)).astype(U32)
    return hi, lo


# -- xorshift128+ ----------------------------------------------------------

def _xs128_step(state):
    """xorshift128+ with the reference's constants 23/17/26
    (ocl/random.cl:104-112): x <- s[0], y <- s[1]; s' = (y, new);
    out = new + y.  state: ((hi, lo), (hi, lo)); returns (state, out64)."""
    x, y = state[0], state[1]
    x = _xor(x, _shl(*x, 23))
    new1 = _xor(_xor(x, y), _xor(_shr(*x, 17), _shr(*y, 26)))
    out = _add(new1, y)
    return (y, new1), out


@functools.partial(jax.jit, static_argnames=("count",))
def xorshift128plus(state, count):
    """Generate ``count`` u64 outputs per stream.

    state: uint32 array (2, 2, S) = (word, hi/lo, streams).
    Returns (new_state, bits) with bits uint32 (count, 2, S).
    """
    def body(carry, _):
        st, out = _xs128_step(((carry[0, 0], carry[0, 1]),
                               (carry[1, 0], carry[1, 1])))
        new = jnp.stack([jnp.stack(st[0]), jnp.stack(st[1])])
        return new, jnp.stack(out)

    new_state, outs = jax.lax.scan(body, state, None, length=count)
    return new_state, outs


def numpy_xorshift128plus(state, count):
    """u64 oracle with identical bitstream (host fallback)."""
    s = (state[:, 0].astype(numpy.uint64) << numpy.uint64(32)) | \
        state[:, 1].astype(numpy.uint64)
    outs = numpy.empty((count,) + s.shape[1:], dtype=numpy.uint64)
    with numpy.errstate(over="ignore"):
        for i in range(count):
            x, y = s[0], s[1]
            x = x ^ ((x << numpy.uint64(23)) & numpy.uint64(0xffffffffffffffff))
            new1 = x ^ y ^ (x >> numpy.uint64(17)) ^ (y >> numpy.uint64(26))
            outs[i] = (new1 + y) & numpy.uint64(0xffffffffffffffff)
            s = numpy.stack([y, new1])
    hi = (s >> numpy.uint64(32)).astype(numpy.uint32)
    lo = (s & numpy.uint64(0xffffffff)).astype(numpy.uint32)
    return numpy.stack([hi, lo], axis=1), outs


# -- xorshift1024* ---------------------------------------------------------

_XS1024_MULT = 1181783497276652981


def _xs1024_step(state_hi, state_lo, p):
    """One step over (16, S) hi/lo state arrays; returns new arrays,
    new p, and the (hi, lo) output."""
    s0 = (state_hi[p], state_lo[p])
    p1 = (p + 1) & 15
    s1 = (state_hi[p1], state_lo[p1])
    s1 = _xor(s1, _shl(*s1, 31))
    new = _xor(_xor(s1, s0), _xor(_shr(*s1, 11), _shr(*s0, 30)))
    state_hi = state_hi.at[p1].set(new[0])
    state_lo = state_lo.at[p1].set(new[1])
    out = _mul(new, _XS1024_MULT)
    return state_hi, state_lo, p1, out


@functools.partial(jax.jit, static_argnames=("count",))
def xorshift1024star(state_hi, state_lo, p, count):
    """state_hi/lo: uint32 (16, S); p: int32 scalar; count outputs."""
    def body(carry, _):
        hi, lo, pp = carry
        hi, lo, pp, out = _xs1024_step(hi, lo, pp)
        return (hi, lo, pp), jnp.stack(out)

    (state_hi, state_lo, p), outs = jax.lax.scan(
        body, (state_hi, state_lo, p), None, length=count)
    return state_hi, state_lo, p, outs


def numpy_xorshift1024star(state, p, count):
    """u64 oracle: state uint64 (16, S)."""
    s = state.astype(numpy.uint64).copy()
    outs = numpy.empty((count,) + s.shape[1:], dtype=numpy.uint64)
    mask = numpy.uint64(0xffffffffffffffff)
    with numpy.errstate(over="ignore"):
        for i in range(count):
            s0 = s[p]
            p = (p + 1) & 15
            s1 = s[p]
            s1 = s1 ^ ((s1 << numpy.uint64(31)) & mask)
            new = s1 ^ s0 ^ (s1 >> numpy.uint64(11)) ^ \
                (s0 >> numpy.uint64(30))
            s[p] = new
            outs[i] = (new * numpy.uint64(_XS1024_MULT)) & mask
    return s, p, outs


# -- bits -> floats --------------------------------------------------------

@jax.jit
def uniform_from_bits(hi_bits, vmin=0.0, vmax=1.0):
    """Map uint32 bits to floats in [vmin, vmax) using the top 24 bits
    (exactly representable in float32)."""
    u = (hi_bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    return vmin + u * (vmax - vmin)


# -- idiomatic hardware PRNG path -----------------------------------------

def _hw_uniform_kernel(seed_ref, out_ref):
    pltpu.prng_seed(seed_ref[0])
    bits = pltpu.bitcast(pltpu.prng_random_bits(out_ref.shape),
                         jnp.uint32)
    # top 24 bits; values < 2**24 fit int32, which Mosaic can cast to
    # float (unsigned -> float is not lowerable directly)
    top = (bits >> 8).astype(jnp.int32)
    out_ref[:] = top.astype(jnp.float32) * (1.0 / (1 << 24))


@functools.partial(jax.jit, static_argnames=("shape",))
def hardware_uniform(seed, shape):
    """Uniform [0,1) floats from the TPU hardware PRNG (Pallas).

    Falls back to jax.random on the CPU interpreter (where the hardware
    generator doesn't exist); both paths are deterministic per seed.
    """
    if interpret_mode():
        return jax.random.uniform(jax.random.PRNGKey(seed), shape)
    return pl.pallas_call(
        _hw_uniform_kernel,
        name=KERNEL_NAME,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
    )(jnp.asarray([seed], jnp.int32))
