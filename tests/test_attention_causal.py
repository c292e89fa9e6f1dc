"""The flash kernels' causal and unequal-width forms (interpret mode),
and the plain form (non-causal, equal widths, ``product_dtype=None``)
against the reference.

The plain form is the program ATTENTION_KERNEL_VERSION 2 ran: PR 29
proved that bit for bit against SHA-256 digests of the old module's
output and gradients, on the machines the digests were recorded on
(the ledger's PR 29 test run).  A digest cannot be compared on a CPU
that rounds differently, so what stays is the contract any machine can
hold: forward and the three gradients within stated bounds of
``attention_reference``, float32 and bfloat16, levels 0 and 1.
"""

import jax
import jax.numpy as jnp
import numpy
import pytest

from tests.test_transformer import _maxrel as maxrel
from veles_tpu.ops import attention
from veles_tpu.ops.attention import attention_reference, flash_attention

#: bfloat16 keeps 8 bits of significand: one rounding is off by at most
#: half of this, relative to the value.
BFLOAT16_EPS = 2.0 ** -7


def operands(seed, b, t, dk, dv, dtype=jnp.float32):
    rng = numpy.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, d).astype(numpy.float32),
                             dtype) for d in (dk, dk, dv))


def loss_of(fn):
    def loss(q, k, v):
        out = fn(q, k, v)
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                          ).reshape(out.shape)
        return jnp.sum(out.astype(jnp.float32) * weights)
    return loss


def test_version_is_bumped():
    assert attention.ATTENTION_KERNEL_VERSION == 5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("level, bound", [(0, 1e-5), (1, 5e-6)])
def test_plain_form_against_the_reference(level, bound, dtype):
    """Three q tiles by three k tiles with a ragged tail, so padding,
    the online rescale and both backward accumulations all run.

    float32: the output within the bound
    ``test_transformer.py::test_flash_ulp_bound_on_multi_tile_shapes``
    holds that level to; the gradients within the tolerances of
    ``test_causal_unequal_width_against_the_reference`` of the level-1
    reference's (autodiff through the level-0 reference differentiates
    the approximation, ~4e-3 off; the kernel's backward applies the
    exact formula).  bfloat16: against the float32 reference on the
    same rounded operands, the output is one rounding away and a
    gradient two (its cotangent, then itself), each at most half an
    epsilon of the largest value: one epsilon bounds both."""
    q, k, v = operands(3, 2, 300, 48, 48, jnp.dtype(dtype))
    wide = tuple(a.astype(jnp.float32) for a in (q, k, v))
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=level, blocks=(104, 128))
    out = flash(q, k, v)
    assert out.dtype == q.dtype and out.shape == (2, 300, 48)
    want = attention_reference(*wide, precision_level=level)
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(lambda *a: attention_reference(
        *a, precision_level=1)), argnums=(0, 1, 2))(*wide)
    if dtype == "bfloat16":
        assert maxrel(want, out) < BFLOAT16_EPS
        for got, wanted in zip(grads, wants):
            assert got.dtype == jnp.bfloat16
            assert maxrel(wanted, got) < BFLOAT16_EPS
    else:
        assert maxrel(want, out) < bound
        for got, wanted in zip(grads, wants):
            numpy.testing.assert_allclose(got, wanted,
                                          rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("t, blocks", [
    (300, (104, 128)),   # the diagonal crosses tiles; a ragged tail
    (384, (128, 128)),   # square tiles on the diagonal
    (512, (64, 256)),    # k tiles wider than q tiles
    (512, (256, 128)),   # q tiles wider than k tiles
    (96, None),          # one tile
])
@pytest.mark.parametrize("dk, dv", [(192, 128), (64, 64), (40, 72)])
def test_causal_unequal_width_against_the_reference(t, blocks, dk, dv):
    q, k, v = operands(t + dk, 3, t, dk, dv)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=1, blocks=blocks, causal=True)
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True)
    out, want = flash(q, k, v), reference(q, k, v)
    assert out.shape == want.shape == (3, t, dv)
    numpy.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    # a query's output depends on no later key: the first rows of the
    # long sequence equal the whole of the short one
    half = t // 2
    numpy.testing.assert_allclose(
        out[:, :half], flash(q[:, :half], k[:, :half], v[:, :half]),
        rtol=2e-5, atol=2e-6)
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(q, k, v)
    for got, want, width in zip(grads, wants, (dk, dk, dv)):
        assert got.shape == (3, t, width)
        numpy.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_non_causal_unequal_width_against_the_reference():
    q, k, v = operands(9, 2, 260, 192, 128)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=1, blocks=(128, 128))
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1)
    numpy.testing.assert_allclose(flash(q, k, v), reference(q, k, v),
                                  rtol=2e-5, atol=2e-6)
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads, wants):
        numpy.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_narrowed_products_stay_within_bfloat16_of_the_reference():
    """bfloat16 operands, probability and cotangent tiles rounded to
    bfloat16 for their products: within a few bfloat16 roundings of
    the float32 reference on the same (rounded) operands."""
    q, k, v = operands(17, 2, 384, 192, 128, jnp.bfloat16)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, blocks=(128, 128), causal=True,
        product_dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in (q, k, v))
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True)
    out = numpy.asarray(flash(q, k, v), numpy.float32)
    want = numpy.asarray(reference(*wide))
    assert numpy.abs(out - want).max() < 0.03 * numpy.abs(want).max()
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(*wide)
    for got, want in zip(grads, wants):
        got = numpy.asarray(got, numpy.float32)
        assert numpy.abs(got - want).max() < 0.05 * numpy.abs(want).max()


def test_shape_check_names_what_it_wants():
    q, k, v = operands(1, 2, 16, 8, 8)
    with pytest.raises(ValueError, match=r"\(B, T, dk\) q and k"):
        flash_attention(q, k[:, :8], v)
    with pytest.raises(ValueError, match=r"\(B, T, dv\) v"):
        flash_attention(q, k, v[:, :8])
