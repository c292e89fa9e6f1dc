"""The flash kernels' causal and unequal-width forms (interpret mode),
and the plain form against the kernels it replaced.

``tests/data/attention_v2_digests.json`` holds SHA-256 digests of what
``ops/attention.py`` gave before the causal form
(ATTENTION_KERNEL_VERSION 2) on seeded operands — output and the three
gradients as float32 bytes: the non-causal equal-width program must
still give those, bit for bit, forward and backward.  The digests were
recorded on this repository's CPU test machines; ``reference`` (plain
``jax.numpy``, no kernel) is recorded beside them, and where IT reads
otherwise the machine rounds differently and the comparison says
nothing: the test skips.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import attention
from veles_tpu.ops.attention import attention_reference, flash_attention

HERE = os.path.dirname(os.path.abspath(__file__))


def digest(x):
    return hashlib.sha256(numpy.ascontiguousarray(
        numpy.asarray(x, numpy.float32)).tobytes()).hexdigest()


def v2_digests():
    with open(os.path.join(HERE, "data",
                           "attention_v2_digests.json")) as fin:
        return json.load(fin)


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
    assert attention.ATTENTION_KERNEL_VERSION == 3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("level", [0, 1])
def test_plain_form_is_the_v2_program_bit_for_bit(level, dtype):
    """Three q tiles by three k tiles with a ragged tail, so padding,
    the online rescale and both backward accumulations all run."""
    was = v2_digests()["%d-%s" % (level, dtype)]
    q, k, v = operands(3, 2, 300, 48, 48, jnp.dtype(dtype))
    if digest(attention_reference(q, k, v)) != was["reference"]:
        pytest.skip("plain jax.numpy rounds differently here than where "
                    "the digests were recorded")
    fn = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=level, blocks=(104, 128))
    assert digest(fn(q, k, v)) == was["out"]
    grads = jax.grad(loss_of(fn), argnums=(0, 1, 2))(q, k, v)
    assert [digest(g) for g in grads] == [was["dq"], was["dk"], was["dv"]]


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
