"""Hand-scheduled backward kernels (docs/kernels.md), interpret-mode
parity on CPU: the fused conv-VJP family (``ops/conv_vjp.py``), the
pool select-and-scatter backward (``ops/pool_bwd.py``), the compiler's
backward-decongestion hints (barrier chain / remat — bit-identical by
contract), and the ``VELES_PALLAS_BWD`` knob's autodiff-fallback
bit-equality.  Every test runs the kernels through the Pallas
interpreter (``JAX_PLATFORMS=cpu``), same numerics as Mosaic."""

import numpy
import pytest

pytestmark = pytest.mark.pallas

NAN = float("nan")


@pytest.fixture
def pallas_on(monkeypatch):
    """Force the hand-scheduled backward on (the CPU default is off);
    the env was read once at import, so tests flip the module flag."""
    from veles_tpu.ops import common
    monkeypatch.setattr(common, "PALLAS_BWD_ENV", "1")


@pytest.fixture
def pallas_off(monkeypatch):
    from veles_tpu.ops import common
    monkeypatch.setattr(common, "PALLAS_BWD_ENV", "0")


def _conv_reference(x, w, y, dy, activation, padding, sliding):
    """The stock formulation: activation backward (via the forward
    output, like the gd units), then jax.vjp of the pure conv."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.models.conv import Conv
    from veles_tpu.ops.conv_vjp import activation_grad

    err = activation_grad(activation, y.astype(jnp.float32),
                          dy.astype(jnp.float32)).astype(x.dtype)

    def lin(w_, x_):
        return Conv.apply({"weights": w_, "bias": None}, x_,
                          padding=padding, sliding=sliding,
                          pallas_bwd=False)

    _, vjp = jax.vjp(lin, w, x)
    gw, gx = vjp(err)
    gb = err.astype(jnp.float32).sum(axis=(0, 1, 2))
    return gx, gw.astype(jnp.float32), gb


def _max_rel(a, b):
    a = numpy.asarray(a, numpy.float64)
    b = numpy.asarray(b, numpy.float64)
    return float(numpy.abs(a - b).max() /
                 max(numpy.abs(b).max(), 1e-12))


def _conv_case(shape, co, kyx, padding, sliding, activation, dtype,
               seed=0):
    import jax.numpy as jnp

    from veles_tpu.models.conv import Conv
    from veles_tpu.ops.conv_vjp import _forward_act

    rng = numpy.random.RandomState(seed)
    n, h, w_sp, ci = shape
    ky, kx = kyx
    x = jnp.asarray(rng.randn(n, h, w_sp, ci), dtype)
    w = jnp.asarray(rng.randn(ky, kx, ci, co) * 0.1, dtype)
    z = Conv.apply({"weights": w, "bias": None}, x, padding=padding,
                   sliding=sliding, pallas_bwd=False)
    y = _forward_act(activation)(z.astype(jnp.float32)).astype(dtype)
    dy = jnp.asarray(rng.randn(*y.shape), dtype)
    return x, w, y, dy


# -- conv-VJP parity ---------------------------------------------------------


@pytest.mark.parametrize("activation,padding,sliding", [
    ("linear", (0, 0, 0, 0), (1, 1)),
    ("strict_relu", (1, 1, 1, 1), (2, 2)),
    ("relu_log", (0, 0, 0, 0), (1, 1)),
    ("tanh", (2, 1, 2, 1), (2, 3)),
    ("sigmoid", (1, 1, 1, 1), (1, 1)),
])
def test_conv_vjp_parity_f32(activation, padding, sliding):
    """Fused wgrad/bias/err vs the autodiff reference, f32 level 1
    (true-f32 products + Kahan): within the documented ~1e-6 rel band
    for the tile-parallel contraction; dgrad BIT-exact (it is the same
    lhs-dilated lax conv XLA's transpose rule emits)."""
    from veles_tpu.ops.conv_vjp import fused_conv_vjp
    import jax.numpy as jnp

    x, w, y, dy = _conv_case((2, 9, 10, 4), 8, (3, 3), padding,
                             sliding, activation, jnp.float32)
    gx, gw, gb = fused_conv_vjp(
        x, w, y, dy, activation=activation, padding=padding,
        sliding=sliding, precision_level=1)
    rgx, rgw, rgb = _conv_reference(x, w, y, dy, activation, padding,
                                    sliding)
    assert _max_rel(gw, rgw) < 1e-5
    assert _max_rel(gb, rgb) < 1e-5
    # dgrad consumes the kernel's fused err; activation backwards that
    # are exact in f32 (linear/strict_relu) stay bit-exact end to end
    if activation in ("linear", "strict_relu"):
        numpy.testing.assert_array_equal(numpy.asarray(gx),
                                         numpy.asarray(rgx))
    else:
        assert _max_rel(gx, rgx) < 1e-5


def test_conv_vjp_bit_exact_on_representable():
    """On exactly-representable operands (small integers) every f32
    product and sum is exact, so tile order cannot matter: the fused
    wgrad/bias/dgrad must be BIT-identical to autodiff."""
    import jax.numpy as jnp

    from veles_tpu.ops.conv_vjp import fused_conv_vjp

    rng = numpy.random.RandomState(3)
    x = jnp.asarray(rng.randint(-4, 5, (2, 8, 8, 3)), jnp.float32)
    w = jnp.asarray(rng.randint(-3, 4, (3, 3, 3, 8)), jnp.float32)
    y = jnp.zeros((2, 6, 6, 8), jnp.float32)  # linear epilogue: unused
    dy = jnp.asarray(rng.randint(-4, 5, (2, 6, 6, 8)), jnp.float32)
    gx, gw, gb = fused_conv_vjp(
        x, w, y, dy, activation="linear", padding=(0, 0, 0, 0),
        sliding=(1, 1), precision_level=1)
    rgx, rgw, rgb = _conv_reference(x, w, y, dy, "linear",
                                    (0, 0, 0, 0), (1, 1))
    numpy.testing.assert_array_equal(numpy.asarray(gw),
                                     numpy.asarray(rgw))
    numpy.testing.assert_array_equal(numpy.asarray(gb),
                                     numpy.asarray(rgb))
    numpy.testing.assert_array_equal(numpy.asarray(gx),
                                     numpy.asarray(rgx))


def test_conv_vjp_bf16x3_ulp_bound():
    """Level 0's bf16x3 decomposition: f32-class products (~5e-7 rel)
    plus tile-order accumulation — the documented bound is 1e-5 rel vs
    the true-f32 reference (docs/kernels.md)."""
    import jax.numpy as jnp

    from veles_tpu.ops.conv_vjp import fused_conv_vjp

    x, w, y, dy = _conv_case((2, 8, 8, 3), 16, (3, 3), (0, 0, 0, 0),
                             (1, 1), "linear", jnp.float32, seed=7)
    _, gw0, gb0 = fused_conv_vjp(
        x, w, y, dy, activation="linear", padding=(0, 0, 0, 0),
        sliding=(1, 1), precision_level=0)
    _, rgw, rgb = _conv_reference(x, w, y, dy, "linear", (0, 0, 0, 0),
                                   (1, 1))
    assert _max_rel(gw0, rgw) < 1e-5
    assert _max_rel(gb0, rgb) < 1e-5


def test_conv_vjp_bf16():
    """bf16 operands take single-pass MXU products with f32
    accumulation; parity vs autodiff is bounded by the reference's own
    bf16 output rounding (eps ~7.8e-3)."""
    import jax.numpy as jnp

    from veles_tpu.ops.conv_vjp import fused_conv_vjp

    x, w, y, dy = _conv_case((2, 8, 8, 4), 16, (3, 3), (1, 1, 1, 1),
                             (1, 1), "strict_relu", jnp.bfloat16)
    gx, gw, gb = fused_conv_vjp(
        x, w, y, dy, activation="strict_relu", padding=(1, 1, 1, 1),
        sliding=(1, 1), precision_level=1)
    rgx, rgw, rgb = _conv_reference(x, w, y, dy, "strict_relu",
                                     (1, 1, 1, 1), (1, 1))
    assert _max_rel(gw, rgw) < 1.6e-2
    assert _max_rel(gb, rgb) < 1.6e-2
    assert _max_rel(gx, rgx) < 1.6e-2


def test_conv_vjp_many_taps_falls_back():
    """Kernels past MAX_FUSED_TAPS (AlexNet's 11x11) keep the stock
    autodiff VJP — bit-identical to the reference, same call-site
    contract."""
    import jax.numpy as jnp

    from veles_tpu.ops.conv_vjp import MAX_FUSED_TAPS, fused_conv_vjp

    ky = kx = 6
    assert ky * kx > MAX_FUSED_TAPS
    x, w, y, dy = _conv_case((1, 14, 14, 2), 4, (ky, kx),
                             (0, 0, 0, 0), (2, 2), "strict_relu",
                             jnp.float32, seed=5)
    gx, gw, gb = fused_conv_vjp(
        x, w, y, dy, activation="strict_relu", padding=(0, 0, 0, 0),
        sliding=(2, 2), precision_level=0)
    rgx, rgw, rgb = _conv_reference(x, w, y, dy, "strict_relu",
                                     (0, 0, 0, 0), (2, 2))
    numpy.testing.assert_array_equal(numpy.asarray(gw),
                                     numpy.asarray(rgw))
    numpy.testing.assert_array_equal(numpy.asarray(gx),
                                     numpy.asarray(rgx))
    numpy.testing.assert_array_equal(numpy.asarray(gb),
                                     numpy.asarray(rgb))


# -- pool select-and-scatter backward ---------------------------------------


def _pool_reference(x, dy, window, sliding):
    import jax

    from veles_tpu.models.pooling import MaxPooling

    def pool(x_):
        return MaxPooling.apply({}, x_, window=window, sliding=sliding,
                                pallas_bwd=False)

    _, vjp = jax.vjp(pool, x)
    (ref,) = vjp(dy.astype(x.dtype))
    return ref


@pytest.mark.parametrize("shape,window,sliding,exact", [
    ((2, 8, 8, 3), (2, 2), (2, 2), True),     # VGG-style non-overlap
    ((2, 9, 9, 3), (3, 3), (2, 2), False),    # AlexNet overlap + ceil
    ((1, 5, 5, 2), (2, 2), (2, 2), True),     # odd input, ceil tail
    ((2, 6, 6, 130), (2, 2), (2, 2), True),   # channels past one lane
    ((1, 4, 4, 1), (4, 4), (4, 4), True),     # window == input
    ((2, 7, 7, 5), (3, 3), (1, 1), False),    # dense overlap
])
def test_pool_bwd_parity(shape, window, sliding, exact):
    """Routed scatter vs jax.vjp(reduce_window): bit-exact for
    non-overlapping windows (each input cell receives at most one
    contribution); OVERLAPPING windows agree within ~1 ULP where >= 2
    selected contributions sum into one cell in a different order
    (docs/kernels.md)."""
    import jax.numpy as jnp

    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops.pool_bwd import max_pool_bwd

    rng = numpy.random.RandomState(11)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    y = MaxPooling.apply({}, x, window=window, sliding=sliding,
                         pallas_bwd=False)
    dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    out = max_pool_bwd(x, y, dy, window=window, sliding=sliding)
    ref = _pool_reference(x, dy, window, sliding)
    assert out.shape == x.shape
    if exact:
        numpy.testing.assert_array_equal(numpy.asarray(out),
                                         numpy.asarray(ref))
    else:
        numpy.testing.assert_allclose(
            numpy.asarray(out), numpy.asarray(ref), rtol=1e-6,
            atol=1e-6)


def test_pool_bwd_ties_bit_exact():
    """All-equal windows: the kernel's first-match tie-break must
    reproduce XLA's select-and-scatter routing exactly."""
    import jax.numpy as jnp

    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops.pool_bwd import max_pool_bwd

    rng = numpy.random.RandomState(2)
    x = jnp.ones((1, 6, 6, 2), jnp.float32)
    y = MaxPooling.apply({}, x, window=(3, 3), sliding=(2, 2),
                         pallas_bwd=False)
    dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    out = max_pool_bwd(x, y, dy, window=(3, 3), sliding=(2, 2))
    ref = _pool_reference(x, dy, (3, 3), (2, 2))
    numpy.testing.assert_array_equal(numpy.asarray(out),
                                     numpy.asarray(ref))


def test_pool_bwd_w_tiling_and_vmem_fallback(monkeypatch):
    """Shrinking POOL_VMEM_BUDGET_BYTES (a) tiles the W axis for
    non-overlapping windows and (b) falls back to autodiff for
    overlapping ones — both bit-exact vs the reference."""
    import jax.numpy as jnp

    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops import pool_bwd

    rng = numpy.random.RandomState(4)

    # (a) non-overlap: find a budget that forces > 1 W tile
    x = jnp.asarray(rng.randn(1, 6, 64, 3), jnp.float32)
    y = MaxPooling.apply({}, x, window=(2, 2), sliding=(2, 2),
                         pallas_bwd=False)
    dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    full = pool_bwd._plan_blocks(y.shape[1], y.shape[2],
                                 (2, 2), (2, 2), 4)
    assert full == (1, y.shape[2])
    budget = pool_bwd.POOL_VMEM_BUDGET_BYTES
    while True:
        budget //= 2
        monkeypatch.setattr(pool_bwd, "POOL_VMEM_BUDGET_BYTES", budget)
        plan = pool_bwd._plan_blocks(y.shape[1], y.shape[2],
                                     (2, 2), (2, 2), 4)
        assert plan is not None, "non-overlap must always tile"
        if plan[0] > 1:
            break
    out = pool_bwd.max_pool_bwd(x, y, dy, window=(2, 2),
                                sliding=(2, 2))
    ref = _pool_reference(x, dy, (2, 2), (2, 2))
    numpy.testing.assert_array_equal(numpy.asarray(out),
                                     numpy.asarray(ref))

    # (b) overlapping window + impossible budget -> autodiff fallback
    monkeypatch.setattr(pool_bwd, "POOL_VMEM_BUDGET_BYTES", 1)
    x2 = jnp.asarray(rng.randn(1, 9, 9, 2), jnp.float32)
    y2 = MaxPooling.apply({}, x2, window=(3, 3), sliding=(2, 2),
                          pallas_bwd=False)
    dy2 = jnp.asarray(rng.randn(*y2.shape), jnp.float32)
    out2 = pool_bwd.max_pool_bwd(x2, y2, dy2, window=(3, 3),
                                 sliding=(2, 2))
    ref2 = _pool_reference(x2, dy2, (3, 3), (2, 2))
    numpy.testing.assert_array_equal(numpy.asarray(out2),
                                     numpy.asarray(ref2))


# -- custom_vjp wrappers: forward bit-identity + end-to-end grads -----------


def test_knob_forward_bit_identical():
    """The knob must never change the forward: conv_act / max_pool
    custom_vjp forwards are the SAME composition as the stock apply."""
    import jax.numpy as jnp

    from veles_tpu.models.conv import ConvStrictRELU, ConvTanh
    from veles_tpu.models.pooling import MaxPooling

    rng = numpy.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 8, 8, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 8) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(8) * 0.1, jnp.float32)
    for cls in (ConvStrictRELU, ConvTanh):
        on = cls.apply({"weights": w, "bias": b}, x,
                       padding=(1, 1, 1, 1), sliding=(1, 1),
                       pallas_bwd=True)
        off = cls.apply({"weights": w, "bias": b}, x,
                        padding=(1, 1, 1, 1), sliding=(1, 1),
                        pallas_bwd=False)
        numpy.testing.assert_array_equal(numpy.asarray(on),
                                         numpy.asarray(off))
    p_on = MaxPooling.apply({}, x, window=(2, 2), sliding=(2, 2),
                            pallas_bwd=True)
    p_off = MaxPooling.apply({}, x, window=(2, 2), sliding=(2, 2),
                             pallas_bwd=False)
    numpy.testing.assert_array_equal(numpy.asarray(p_on),
                                     numpy.asarray(p_off))


def test_wrapper_grads_match_autodiff():
    """jax.grad through the knob-on custom_vjp composition (conv ->
    pool -> scalar loss) matches the stock path within the kernel
    band — the end-to-end cascade, not just per-op parity."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.models.conv import ConvStrictRELU
    from veles_tpu.models.pooling import MaxPooling

    rng = numpy.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 8, 8, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 8) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(8) * 0.1, jnp.float32)

    def loss(w_, b_, pallas_bwd):
        h = ConvStrictRELU.apply(
            {"weights": w_, "bias": b_}, x, padding=(1, 1, 1, 1),
            sliding=(1, 1), pallas_bwd=pallas_bwd)
        h = MaxPooling.apply({}, h, window=(2, 2), sliding=(2, 2),
                             pallas_bwd=pallas_bwd)
        return (h * h).sum()

    g_on = jax.grad(loss, argnums=(0, 1))(w, b, True)
    g_off = jax.grad(loss, argnums=(0, 1))(w, b, False)
    assert _max_rel(g_on[0], g_off[0]) < 1e-5
    assert _max_rel(g_on[1], g_off[1]) < 1e-5


# -- compiler scheduling hints: bit-identical by contract -------------------


def _conv_step_fixture(loss="softmax"):
    """A conv+pool+conv+pool+softmax fused-step setup on synthetic
    images — the smallest model exercising every new kernel."""
    from veles_tpu.models.zoo import build_plans_and_state

    specs = [
        {"type": "conv_str", "n_kernels": 4, "kx": 3, "ky": 3,
         "padding": 1, "learning_rate": 0.05, "gradient_moment": 0.9},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "conv_tanh", "n_kernels": 4, "kx": 3, "ky": 3,
         "padding": 1, "learning_rate": 0.05, "gradient_moment": 0.9},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "softmax", "output_sample_shape": 5,
         "learning_rate": 0.05, "gradient_moment": 0.9},
    ]
    plans, state, _ = build_plans_and_state(specs, (12, 12, 3), seed=2)
    rng = numpy.random.RandomState(1)
    batches = [(rng.randn(16, 12, 12, 3).astype(numpy.float32),
                rng.randint(0, 5, 16).astype(numpy.int32))
               for _ in range(4)]
    return plans, state, batches


def _run_steps(step, state, batches, indices, **kwargs):
    out = state
    m = None
    for i in indices:
        out, m = step(out, batches[i][0], batches[i][1],
                      numpy.float32(16), **kwargs)
    return out, m


def _assert_states_equal(sa, sb):
    for ea, eb in zip(sa, sb):
        for key in ea:
            if ea[key] is None:
                assert eb[key] is None
                continue
            numpy.testing.assert_array_equal(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]))


def test_barrier_chain_is_identity():
    """_chain_grad_barriers is a scheduling hint ONLY: values out ==
    values in, leaf for leaf."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.compiler import _chain_grad_barriers

    rng = numpy.random.RandomState(0)
    grads = [
        {"weights": jnp.asarray(rng.randn(4, 3), jnp.float32),
         "bias": jnp.asarray(rng.randn(3), jnp.float32)},
        {},  # a param-less layer (pooling) must pass through
        {"weights": jnp.asarray(rng.randn(3, 2), jnp.float32),
         "bias": None},
    ]
    chained = _chain_grad_barriers(grads)
    assert len(chained) == len(grads)
    for orig, out in zip(grads, chained):
        assert set(orig) == set(out)
        for leaves in (jax.tree_util.tree_leaves(orig),
                       jax.tree_util.tree_leaves(out)):
            pass
        for ka in orig:
            if orig[ka] is None:
                assert out[ka] is None
            else:
                numpy.testing.assert_array_equal(
                    numpy.asarray(orig[ka]), numpy.asarray(out[ka]))


def test_step_bwd_schedule_and_remat_bit_identical(pallas_off):
    """The decongestion hints (optimization_barrier chain, per-layer
    remat) change the SCHEDULE, never the values: 3 chained steps are
    bit-identical with and without them."""
    from veles_tpu.compiler import build_train_step

    plans, state, batches = _conv_step_fixture()
    base = build_train_step(plans, donate=False, bwd_schedule=False)
    hinted = build_train_step(plans, donate=False, bwd_schedule=True)
    remat = build_train_step(plans, donate=False, bwd_schedule=True,
                             bwd_remat=True)
    s_base, _ = _run_steps(base, state, batches, (0, 1, 2))
    s_hint, _ = _run_steps(hinted, state, batches, (0, 1, 2))
    s_remat, _ = _run_steps(remat, state, batches, (0, 1, 2))
    _assert_states_equal(s_base, s_hint)
    _assert_states_equal(s_base, s_remat)


# -- the VELES_PALLAS_BWD knob end to end -----------------------------------


def test_env_knob_resolution(monkeypatch):
    from veles_tpu.ops import common

    for env, expect_cpu in (("0", False), ("1", True), ("on", True),
                            ("", False), ("auto", False)):
        monkeypatch.setattr(common, "PALLAS_BWD_ENV", env)
        # CPU backend: ""/"auto" resolve off (TPU-only default)
        assert common.pallas_bwd_enabled() is expect_cpu


def test_fused_step_knob_parity(pallas_on):
    """The whole fused train step with the hand-scheduled backward:
    losses bit-identical to autodiff (same forward), updated state
    within the documented kernel band over chained steps."""
    from veles_tpu.compiler import build_train_step
    from veles_tpu.ops import common

    plans, state, batches = _conv_step_fixture()
    step_on = build_train_step(plans, donate=False)
    s_on, m_on = _run_steps(step_on, state, batches, (0, 1, 2))

    common.PALLAS_BWD_ENV = "0"
    step_off = build_train_step(plans, donate=False)
    s_off, m_off = _run_steps(step_off, state, batches, (0, 1, 2))

    # first-step forward is identical => first loss identical; after
    # the first update states differ within the kernel parity band
    assert numpy.isfinite(float(m_on["loss"]))
    for ea, eb in zip(s_on, s_off):
        for key in ea:
            if ea[key] is None:
                assert eb[key] is None
                continue
            assert _max_rel(ea[key], eb[key]) < 1e-4, key


def test_poisoned_step_skips_bit_exactly_through_fused_bwd(pallas_on):
    """PR 3's guard contract survives the hand-scheduled backward: a
    NaN-poisoned step leaves params AND solver accumulators
    bit-identical to never having served that minibatch."""
    import math

    from veles_tpu.compiler import build_train_step

    plans, state, batches = _conv_step_fixture()
    step = build_train_step(plans, donate=False)

    ref, m = _run_steps(step, state, batches, (0, 1, 3))
    assert bool(m["finite"]) and int(m["skipped"]) == 0

    got, _ = _run_steps(step, state, batches, (0, 1))
    got, m = _run_steps(step, got, batches, (2,),
                        grad_poison=numpy.float32(NAN))
    assert not bool(m["finite"]) and int(m["skipped"]) == 1
    assert not math.isfinite(float(m["grad_norm"]))
    got, _ = _run_steps(step, got, batches, (3,))
    _assert_states_equal(ref, got)


def test_knob_off_never_calls_kernels(pallas_off, monkeypatch):
    """The tier-1 fallback smoke: with VELES_PALLAS_BWD=0 the fused
    step must take the stock autodiff path — the Pallas kernels are
    poisoned to raise, and the result matches an unpatched knob-off
    run bit-exactly (the fallback IS the stock code path)."""
    from veles_tpu.compiler import build_train_step
    from veles_tpu.ops import conv_vjp, pool_bwd

    plans, state, batches = _conv_step_fixture()
    baseline = build_train_step(plans, donate=False)
    s_ref, _ = _run_steps(baseline, state, batches, (0, 1))

    def boom(*args, **kwargs):
        raise AssertionError("VELES_PALLAS_BWD=0 must not reach the "
                             "Pallas backward kernels")

    monkeypatch.setattr(conv_vjp, "fused_conv_vjp", boom)
    monkeypatch.setattr(conv_vjp, "conv_act", boom)
    monkeypatch.setattr(pool_bwd, "max_pool_bwd", boom)
    monkeypatch.setattr(pool_bwd, "max_pool", boom)
    step = build_train_step(plans, donate=False)
    s_got, _ = _run_steps(step, state, batches, (0, 1))
    _assert_states_equal(s_ref, s_got)


def test_gd_units_route_through_kernels(pallas_on):
    """The per-unit gd chain (non-fused path) takes the same kernels:
    GDConv/GDMaxPooling backwards match their stock formulations."""
    import jax.numpy as jnp

    from veles_tpu.models.gd_conv import GDConvStrictRELU
    from veles_tpu.models.gd_pooling import GDMaxPooling
    from veles_tpu.models.conv import ConvStrictRELU
    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops import common

    rng = numpy.random.RandomState(8)
    x = jnp.asarray(rng.randn(2, 8, 8, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 4) * 0.1, jnp.float32)
    b = jnp.zeros((4,), jnp.float32)
    state = {"weights": w, "bias": b,
             "accum_weights": jnp.zeros_like(w),
             "accum_bias": jnp.zeros_like(b),
             "accum2_weights": None, "accum2_bias": None}
    hyper = {"learning_rate": 0.1, "learning_rate_bias": 0.1,
             "weights_decay": 0.0, "weights_decay_bias": 0.0,
             "l1_vs_l2": 0.0, "gradient_moment": 0.9,
             "gradient_moment_bias": 0.9, "adadelta_rho": 0.9,
             "solver_epsilon": 1e-8}
    y = ConvStrictRELU.apply({"weights": w, "bias": b}, x,
                             padding=(1, 1, 1, 1), sliding=(1, 1),
                             pallas_bwd=False)
    dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    err_on, new_on = GDConvStrictRELU.backward(
        state, hyper, x, y, dy, solver="momentum", include_bias=True,
        need_err_input=True, padding=(1, 1, 1, 1), sliding=(1, 1))

    common.PALLAS_BWD_ENV = "0"
    err_off, new_off = GDConvStrictRELU.backward(
        state, hyper, x, y, dy, solver="momentum", include_bias=True,
        need_err_input=True, padding=(1, 1, 1, 1), sliding=(1, 1))
    assert _max_rel(err_on, err_off) < 1e-5
    for key in new_on:
        if new_on[key] is None:
            assert new_off[key] is None
            continue
        assert _max_rel(new_on[key], new_off[key]) < 1e-5, key

    # pooling: routing is value-exact, so bit-equality holds
    common.PALLAS_BWD_ENV = "1"
    yp = MaxPooling.apply({}, x, window=(2, 2), sliding=(2, 2),
                          pallas_bwd=False)
    dyp = jnp.asarray(rng.randn(*yp.shape), jnp.float32)
    p_on, _ = GDMaxPooling.backward(
        {}, hyper, x, yp, dyp, solver="momentum", include_bias=False,
        need_err_input=True, window=(2, 2), sliding=(2, 2))
    common.PALLAS_BWD_ENV = "0"
    p_off, _ = GDMaxPooling.backward(
        {}, hyper, x, yp, dyp, solver="momentum", include_bias=False,
        need_err_input=True, window=(2, 2), sliding=(2, 2))
    numpy.testing.assert_array_equal(numpy.asarray(p_on),
                                     numpy.asarray(p_off))


# -- observe: live fwd/bwd attribution --------------------------------------


def test_bwd_snapshot_attribution(monkeypatch):
    """bwd.step_ms / bwd.mfu_pct derive from the existing step
    histograms + the two flops gauges, and ride health_snapshot so
    heartbeats and web_status carry the split (docs/kernels.md).
    Rated against a stand-in chip row: the CPU has no peak."""
    from veles_tpu.observe.metrics import MetricsRegistry, health_snapshot
    from veles_tpu.observe import xla_introspect as xla

    monkeypatch.setattr(xla, "peak_flops", lambda dtype=None: 197e12)
    reg = MetricsRegistry()
    # missing inputs -> None, never a crash
    assert xla.bwd_snapshot(reg) is None
    train = reg.histogram("step.train_s")
    ev = reg.histogram("step.eval_s")
    assert xla.bwd_snapshot(reg) is None  # histograms empty
    for _ in range(8):
        train.observe(0.016)
        ev.observe(0.004)
    out = xla.bwd_snapshot(reg)
    assert out == {"bwd_step_ms": 12.0}  # no flops yet: time only

    reg.gauge("xla.step_flops").set(1.5e12)
    reg.gauge("xla.fwd_flops").set(0.5e12)
    out = xla.bwd_snapshot(reg)
    assert out["bwd_step_ms"] == 12.0
    assert out["bwd_mfu_pct"] > 0
    health = health_snapshot(reg)
    assert health["bwd_step_ms"] == 12.0
    assert health["bwd_mfu_pct"] == out["bwd_mfu_pct"]

    # eval slower than train (mis-ordered windows) -> attribution
    # withheld rather than a negative time published
    reg2 = MetricsRegistry()
    t2, e2 = reg2.histogram("step.train_s"), reg2.histogram("step.eval_s")
    for _ in range(4):
        t2.observe(0.002)
        e2.observe(0.004)
    assert xla.bwd_snapshot(reg2) is None
