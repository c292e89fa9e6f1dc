"""The flash kernels' grouped-head and windowed forms (interpret mode)
against ``attention_reference`` and against a plain numpy softmax:
forward and the three gradients over windows smaller than a tile, at a
tile's edge, crossing tiles and no shorter than the sequence, groups of
1, 2 and 8 query heads a KV head, sequences that are no multiple of the
tile, bfloat16 products.  What the forms promise beyond numbers is read
from the traced program: K and V enter the kernels with their own
(fewer) rows, a windowed grid spans the band's tiles only, the windowed
kernels carry their own names, and a call with neither a group nor a
window traces to the program the kernels were before they learned
either."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy
import pytest

from tests.test_attention_causal import BFLOAT16_EPS, loss_of
from veles_tpu.ops import attention
from veles_tpu.ops.attention import attention_reference, flash_attention


def operands(seed, b, group, t, dk, dv, dtype=jnp.float32):
    rng = numpy.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(n, t, d).astype(numpy.float32),
                             dtype)
                 for n, d in ((b, dk), (b // group, dk), (b // group, dv)))


def plain_softmax(q, k, v, window):
    """Written apart from both: float64, one query head at a time."""
    q, k, v = (numpy.asarray(a, numpy.float64) for a in (q, k, v))
    group, t = q.shape[0] // k.shape[0], q.shape[1]
    back = numpy.arange(t)[:, None] - numpy.arange(t)[None, :]
    allowed = (back >= 0) & (back < (window or t))
    out = numpy.zeros(q.shape[:2] + v.shape[-1:])
    for n in range(q.shape[0]):
        s = q[n] @ k[n // group].T / numpy.sqrt(q.shape[-1])
        s = numpy.where(allowed, s, -numpy.inf)
        p = numpy.exp(s - s.max(axis=1, keepdims=True))
        out[n] = p / p.sum(axis=1, keepdims=True) @ v[n // group]
    return out


#: (tokens, (bq, bk)): a ragged tail and the band crossing tiles of
#: unequal sides; square tiles; q tiles wider than k tiles
SHAPES = [(300, (104, 128)), (512, (128, 128)), (512, (256, 128))]
#: smaller than a tile; a tile's edge; crossing tiles; not under T
WINDOWS = [40, 128, 200, 600]


@pytest.mark.parametrize("group", [1, 2, 8])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("t, blocks", SHAPES)
def test_windowed_grouped_forms_against_the_reference(t, blocks, window,
                                                      group):
    q, k, v = operands(t + window + group, 8, group, t, 64, 48)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=1, blocks=blocks, causal=True, window=window)
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True, window=window)
    out, want = flash(q, k, v), reference(q, k, v)
    assert out.shape == want.shape == (8, t, 48)
    numpy.testing.assert_allclose(want, plain_softmax(q, k, v, window),
                                  rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(q, k, v)
    for got, wanted, operand in zip(grads, wants, (q, k, v)):
        # dk and dv have the KV heads' rows: a group's sum, made once
        assert got.shape == operand.shape
        numpy.testing.assert_allclose(got, wanted, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("window", [None, 200])
def test_grouped_full_causal_and_default_tiles(window):
    """``blocks=None`` at a length that is no multiple of the default
    tile; no window is the grouped full-causal form."""
    q, k, v = operands(5, 8, 4, 700, 32, 32)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=1, causal=True, window=window)
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True, window=window)
    numpy.testing.assert_allclose(flash(q, k, v), reference(q, k, v),
                                  rtol=2e-5, atol=2e-6)
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(q, k, v)
    for got, wanted in zip(grads, wants):
        numpy.testing.assert_allclose(got, wanted, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("window", [None, 200])
def test_narrowed_products_stay_within_bfloat16_of_the_reference(window):
    """bfloat16 operands, probability and cotangent tiles rounded to
    bfloat16 for their products, eight query heads a KV head: within a
    few bfloat16 roundings of the float32 reference on the same
    (rounded) operands; dk/dv sum a group in float32 and round once."""
    q, k, v = operands(17, 8, 8, 384, 128, 128, jnp.bfloat16)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, blocks=(128, 128), causal=True, window=window,
        product_dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in (q, k, v))
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True, window=window)
    out = numpy.asarray(flash(q, k, v), numpy.float32)
    want = numpy.asarray(reference(*wide))
    assert numpy.abs(out - want).max() < 4 * BFLOAT16_EPS * numpy.abs(
        want).max()
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(*wide)
    for got, want in zip(grads, wants):
        assert got.dtype == jnp.bfloat16
        got = numpy.asarray(got, numpy.float32)
        assert numpy.abs(got - want).max() < 0.05 * numpy.abs(want).max()


def pallas_calls(jaxpr, found=None):
    """[(kernel name, grid, input shapes)] of a jaxpr and all it
    encloses."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"],
                          tuple(eqn.params["grid_mapping"].grid),
                          [tuple(var.aval.shape) for var in eqn.invars]))
        else:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                pallas_calls(inner, found)
    return found


def traced(t, b, group, window, blocks, width=128):
    q = jax.ShapeDtypeStruct((b, t, width), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b // group, t, width), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, blocks=blocks, causal=True, window=window,
            product_dtype=jnp.bfloat16).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    return pallas_calls(jax.make_jaxpr(grads)(q, k, k).jaxpr)


def test_a_windowed_grid_spans_the_band_and_kv_is_never_repeated():
    """The cell's shapes: 8,192 tokens, a 2,048-token window, (512, 512)
    tiles, 32 query heads on 4 KV heads.  16 q tiles x 5 key steps where
    the causal form walks 16 x 16; the dk/dv kernel's grid runs over the
    4 KV heads with the 8 query heads of a group inside; K and V enter
    every kernel with 4 rows, and dk/dv leave with 4."""
    calls = traced(8192, 32, 8, 2048, None)
    names = [name for name, _, _ in calls]
    assert names == [attention.WIN_FWD_KERNEL_NAME,
                     attention.WIN_DQ_KERNEL_NAME,
                     attention.WIN_DKV_KERNEL_NAME]
    grids = {name: grid for name, grid, _ in calls}
    assert grids[attention.WIN_FWD_KERNEL_NAME] == (32, 16, 5)
    assert grids[attention.WIN_DQ_KERNEL_NAME] == (32, 16, 5)
    assert grids[attention.WIN_DKV_KERNEL_NAME] == (4, 16, 8, 5)
    for _, _, shapes in calls:
        assert shapes[0] == (32, 8192, 128)      # q
        assert shapes[1] == shapes[2] == (4, 8192, 128)   # k, v
    # the same heads without a window: every causal tile is a grid step
    full = {name: grid for name, grid, _ in traced(8192, 32, 8, None, None)}
    assert full == {attention.FWD_KERNEL_NAME: (32, 16, 16),
                    attention.DQ_KERNEL_NAME: (32, 16, 16),
                    attention.DKV_KERNEL_NAME: (4, 16, 8, 16)}
    # 70 of the band's 80 steps hold an allowed pair (ROADMAP S10 (b):
    # the causal form's 256 hold 136)
    assert attention._band_steps(8192, 512, 512, 2048) == (5, 5)
    assert sum(min(i + 1, 5) for i in range(16)) == 70


def test_band_steps_of_unequal_tiles_cover_every_needed_tile():
    """Whatever the tiles, the steps a tile is given reach from the
    first to the last tile of the other side that holds an allowed
    pair."""
    for t, bq, bk, window in [(300, 104, 128, 40), (512, 256, 128, 200),
                              (512, 64, 256, 257), (1000, 128, 384, 129)]:
        k_steps, q_steps = attention._band_steps(t, bq, bk, window)
        back = numpy.arange(t)[:, None] - numpy.arange(t)[None, :]
        allowed = (back >= 0) & (back < window)
        n_q, n_k = -(-t // bq), -(-t // bk)
        tiles = numpy.array([[allowed[i * bq:(i + 1) * bq,
                                      j * bk:(j + 1) * bk].any()
                              for j in range(n_k)] for i in range(n_q)])
        assert tiles.sum(axis=1).max() == k_steps
        assert tiles.sum(axis=0).max() == q_steps


def test_a_window_no_shorter_than_the_sequence_is_the_causal_form():
    calls = traced(256, 4, 2, 256, (128, 128))
    assert [name for name, _, _ in calls] == [
        attention.FWD_KERNEL_NAME, attention.DQ_KERNEL_NAME,
        attention.DKV_KERNEL_NAME]
    assert [name for name, _, _ in traced(256, 4, 2, 255, (128, 128))] == [
        attention.WIN_FWD_KERNEL_NAME, attention.WIN_DQ_KERNEL_NAME,
        attention.WIN_DKV_KERNEL_NAME]


def test_shape_and_window_checks_name_what_they_want():
    q, k, v = operands(1, 6, 3, 16, 8, 8)
    with pytest.raises(ValueError, match="B / group rows"):
        flash_attention(jnp.concatenate([q, q[:2]]), jnp.concatenate(
            [k, k[:1]]), jnp.concatenate([v, v[:1]]))  # 8 heads on 3
    with pytest.raises(ValueError, match=r"\(B, T, dv\) v"):
        attention_reference(q, k, v[:1])     # v's heads are not k's
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match="at least 1"):
        attention_reference(q, k, v, causal=True, window=0)


#: SHA-256 of the traced programs below, source paths and line numbers
#: taken out — recorded under the jax named beside them: a jaxpr's text
#: is the tracer's, whatever CPU runs it.  ``plain_float32`` is the
#: program PR 32's ``ops/attention.py`` traced, before grouped heads,
#: the window and the tile classes: no form since has touched it.
#: ``latent_causal_bfloat16`` pinned that file's causal program until
#: ATTENTION_KERNEL_VERSION 5 put the causal forward's mask under a
#: ``lax.cond`` on the tile's class (tests/test_attention_tiles.py
#: holds v5's bits to v4's); it now pins v5's causal program, against
#: a later form that is to leave it alone
RECORDED_UNDER_JAX = "0.9.0"
PROGRAMS_BEFORE = {
    "latent_causal_bfloat16":
        "7a6720b39a8f78685900da15b4447446ac6a02ed385df6ea0334ba03bb2fcd4e",
    "plain_float32":
        "adec35508ac7cb95367491c5b26273f4bad979e72aa3a6b55dafbb50130014e6",
}


def program_text(fn, *avals):
    text = str(jax.make_jaxpr(fn)(*avals))
    text = re.sub(r"/[^ \]]*/veles_tpu/", "PATH/veles_tpu/", text)
    text = re.sub(r"attention\.py:\d+", "attention.py:N", text)
    return re.sub(r"0x[0-9a-f]+", "ADDR", text)


@pytest.mark.parametrize("form", sorted(PROGRAMS_BEFORE))
def test_neither_group_nor_window_traces_to_the_program_it_was(form):
    """The decoder cell with latent attention (every head its own
    192-wide keys, no window) and the plain form: kernel bodies, grids,
    block index maps and names, forward and both backward kernels, are
    those of the kernels before this form — the whole traced program's
    text, not a number a rounding could hide in."""
    if jax.__version__ != RECORDED_UNDER_JAX:
        pytest.skip("the digests were recorded under jax %s"
                    % RECORDED_UNDER_JAX)
    if form == "latent_causal_bfloat16":
        q = jax.ShapeDtypeStruct((4, 2048, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((4, 2048, 128), jnp.bfloat16)

        def fn(q, k, v):
            return jax.value_and_grad(lambda *a: flash_attention(
                *a, causal=True, product_dtype=jnp.bfloat16).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
        text = program_text(fn, q, q, v)
    else:
        q = jax.ShapeDtypeStruct((2, 300, 48), jnp.float32)

        def fn(q, k, v):
            return jax.value_and_grad(lambda *a: flash_attention(
                *a, blocks=(104, 128)).sum(), argnums=(0, 1, 2))(q, k, v)
        text = program_text(fn, q, q, q)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PROGRAMS_BEFORE[form]


@pytest.mark.parametrize("t, blocks", [(300, (104, 128)), (256, None)])
def test_heads_narrower_than_a_lane_tile_32_on_8(t, blocks):
    """64-wide heads, 32 query heads on 8 KV heads (groups of 4), the
    full-causal form: each tile pads to 128 lanes by itself, and the
    padding's lanes add nothing to the scores, the output or any of the
    three gradients."""
    q, k, v = operands(64 + t, 32, 4, t, 64, 64)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, precision_level=1, blocks=blocks, causal=True)
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True)
    out, want = flash(q, k, v), reference(q, k, v)
    assert out.shape == want.shape == (32, t, 64)
    numpy.testing.assert_allclose(want, plain_softmax(q, k, v, None),
                                  rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(q, k, v)
    for name, got, wanted, operand in zip(("dq", "dk", "dv"), grads,
                                          wants, (q, k, v)):
        assert got.shape == operand.shape, name  # dk, dv: the 8 KV heads'
        numpy.testing.assert_allclose(got, wanted, rtol=1e-4, atol=2e-5,
                                      err_msg=name)


def test_narrow_heads_with_bfloat16_products():
    """The cell's operand dtype at the narrow width: within a few
    bfloat16 roundings of the float32 reference on the same operands."""
    q, k, v = operands(23, 32, 4, 256, 64, 64, jnp.bfloat16)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, blocks=(128, 128), causal=True, product_dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in (q, k, v))
    reference = lambda *a: attention_reference(  # noqa: E731
        *a, precision_level=1, causal=True)
    out = numpy.asarray(flash(q, k, v), numpy.float32)
    want = numpy.asarray(reference(*wide))
    assert numpy.abs(out - want).max() < 4 * BFLOAT16_EPS * numpy.abs(
        want).max()
    grads = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss_of(reference), argnums=(0, 1, 2))(*wide)
    for got, wanted in zip(grads, wants):
        wanted = numpy.asarray(wanted)
        assert numpy.abs(numpy.asarray(got, numpy.float32)
                         - wanted).max() < 16 * BFLOAT16_EPS * numpy.abs(
                             wanted).max()
