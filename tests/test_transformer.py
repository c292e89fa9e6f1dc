"""Transformer workload: flash-attention kernel parity, the
LayerNorm/MultiHeadAttention/TransformerBlock unit chain, the fused
train step, and model sharding beyond data-parallel (tensor-parallel
head sharding + pipeline-parallel stage split) — docs/kernels.md "The
attention kernel", docs/distributed.md "Model parallelism"."""

import numpy
import pytest

pytestmark = pytest.mark.transformer

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from veles_tpu.ops import common as _ops_common  # noqa: E402
from veles_tpu.ops.attention import (  # noqa: E402
    attention_reference, flash_attention)


def _qkv(rng, b, t, dh, dtype=numpy.float32, scale=1.0):
    return tuple(jnp.asarray(rng.randn(b, t, dh) * scale, dtype)
                 for _ in range(3))


def _maxrel(a, b):
    a, b = numpy.asarray(a, numpy.float64), numpy.asarray(
        b, numpy.float64)
    return float(numpy.abs(a - b).max() / max(numpy.abs(a).max(),
                                              1e-9))


# -- kernel parity ----------------------------------------------------------


#: single-tile flash vs reference: the same op sequence, but two
#: different programs — the interpreted kernel pads to the 128 lane
#: width, which regroups XLA:CPU's reduce tree.  The XLA:CPU these
#: tests were first pinned on happened to give bit-equality at some
#: lengths; the installed one (jax 0.9.0) gives a few ULP at every
#: length (measured 6e-8 .. 6e-7 at |out| ~ 1.6).  So the assertion is
#: the ULP bound docs/kernels.md states, which a real difference (a
#: wrong mask, a dropped tile: 1e-2 and up) cannot hide behind.
SINGLE_TILE_ATOL = 1e-6


@pytest.mark.parametrize("level", [0, 1, 2])
def test_flash_bit_exact_on_single_tile_shapes(level):
    """One (bq, bk) tile = the kernel executes the reference's exact
    op sequence (same shared mxu_partial_dot products): within a few
    ULP (name kept from when XLA:CPU made it bit-exact)."""
    rng = numpy.random.RandomState(0)
    q, k, v = _qkv(rng, 3, 16, 8)
    ref = attention_reference(q, k, v, precision_level=level)
    out = flash_attention(q, k, v, precision_level=level,
                          blocks=(256, 256))
    assert float(numpy.abs(numpy.asarray(ref) - numpy.asarray(out))
                 .max()) < SINGLE_TILE_ATOL


def test_flash_padding_boundary_pinned():
    """Zero-padding a length to the 128 lane width regroups XLA's
    reduce tree: every length — below, at and between the lane-width
    fractions — stays inside the single-tile ULP bound
    (docs/kernels.md)."""
    rng = numpy.random.RandomState(9)
    for t in (32, 64, 40):
        q, k, v = _qkv(rng, 2, t, 8)
        a = numpy.asarray(flash_attention(q, k, v, precision_level=1,
                                          blocks=(256, 256)))
        b = numpy.asarray(attention_reference(q, k, v,
                                              precision_level=1))
        assert float(numpy.abs(a - b).max()) < SINGLE_TILE_ATOL, t


@pytest.mark.parametrize("level,bound", [(1, 5e-6), (0, 1e-5)])
def test_flash_ulp_bound_on_multi_tile_shapes(level, bound):
    """Multi-tile shapes differ only by the online rescale's
    accumulation order: ULP-bounded (measured ~3e-7 level 1 / ~2e-6
    level 0 on this shape)."""
    rng = numpy.random.RandomState(1)
    q, k, v = _qkv(rng, 2, 300, 16)
    ref = attention_reference(q, k, v, precision_level=level)
    out = flash_attention(q, k, v, precision_level=level,
                          blocks=(64, 128))
    assert _maxrel(ref, out) < bound
    assert bool(jnp.isfinite(out).all())


def test_flash_backward_matches_stock_autodiff():
    """The Pallas backward pair vs jax.grad through the reference —
    including padded rows/columns (T=37 forces both paddings), whose
    contributions must be EXACT zeros, not NaN."""
    rng = numpy.random.RandomState(2)
    q, k, v = _qkv(rng, 2, 37, 8)

    def loss(fn):
        def f(q_, k_, v_):
            return jnp.sum(fn(q_, k_, v_) ** 2)
        return f

    flash = loss(lambda *a: flash_attention(
        *a, precision_level=1, blocks=(16, 128)))
    ref = loss(lambda *a: attention_reference(*a, precision_level=1))
    gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert bool(jnp.isfinite(a).all())
        assert _maxrel(b, a) < 5e-6


def test_flash_bf16_operands():
    rng = numpy.random.RandomState(3)
    q, k, v = _qkv(rng, 2, 24, 8, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, blocks=(256, 256))
    ref = attention_reference(q, k, v)
    assert out.dtype == jnp.bfloat16
    numpy.testing.assert_allclose(
        numpy.asarray(out, numpy.float32),
        numpy.asarray(ref, numpy.float32), rtol=0.05, atol=0.05)


def test_knob_off_runs_stock_reference_bit_exactly(monkeypatch):
    """VELES_PALLAS_BWD=0: the model layer's attention IS
    attention_reference (stock autodiff), bit-exact by construction."""
    from veles_tpu.models.transformer import MultiHeadAttention
    rng = numpy.random.RandomState(4)
    d, heads = 8, 2
    x = jnp.asarray(rng.randn(3, 5, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, 4 * d) * 0.3, jnp.float32)
    b = jnp.asarray(rng.randn(4 * d) * 0.1, jnp.float32)
    monkeypatch.setattr(_ops_common, "PALLAS_BWD_ENV", "0")
    off = MultiHeadAttention.apply({"weights": w, "bias": b}, x,
                                   heads=heads)
    monkeypatch.setattr(_ops_common, "PALLAS_BWD_ENV", "1")
    on = MultiHeadAttention.apply({"weights": w, "bias": b}, x,
                                  heads=heads)
    # the stock path twice = bit-stable; flash vs stock stays in band
    monkeypatch.setattr(_ops_common, "PALLAS_BWD_ENV", "0")
    off2 = MultiHeadAttention.apply({"weights": w, "bias": b}, x,
                                    heads=heads)
    numpy.testing.assert_array_equal(numpy.asarray(off),
                                     numpy.asarray(off2))
    assert _maxrel(off, on) < 1e-5


def test_debug_nonfinite_guard(monkeypatch):
    monkeypatch.setattr(_ops_common, "DEBUG_NONFINITE", True)
    rng = numpy.random.RandomState(5)
    q, k, v = _qkv(rng, 1, 8, 8)
    q = q.at[0, 0, 0].set(jnp.nan)
    with pytest.raises(FloatingPointError):
        flash_attention(q, k, v, blocks=(256, 256))


# -- schedule-cache family --------------------------------------------------


@pytest.mark.tune
def test_attention_schedule_cache_consult_loads_tuned_blocks():
    """A planted cache entry demonstrably changes the tiles a
    blocks=None call runs — with BIT-equal results in interpret mode
    when the planted tile covers the whole shape."""
    from veles_tpu.tune.cache import cache_for, schedule_key
    from veles_tpu.tune.spec import attention_spec
    rng = numpy.random.RandomState(6)
    q, k, v = _qkv(rng, 2, 48, 8)
    spec = attention_spec(2, 48, 8, "float32", 1)
    kind = jax.devices()[0].device_kind
    digest, payload = schedule_key(
        spec["op"], spec["shape"], spec["dtype"],
        spec["precision_level"], kind, spec["extra"])
    cache = cache_for()
    cache.put(digest, payload, {"blocks": [16, 128]}, source="test")
    consulted = flash_attention(q, k, v, precision_level=1)
    explicit = flash_attention(q, k, v, precision_level=1,
                               blocks=(16, 128))
    numpy.testing.assert_array_equal(numpy.asarray(consulted),
                                     numpy.asarray(explicit))
    # malformed entry degrades to the static default, never crashes
    cache.put(digest, payload, {"blocks": [7, 100, 3]}, source="test")
    fallback = flash_attention(q, k, v, precision_level=1)
    default = flash_attention(q, k, v, precision_level=1,
                              blocks=(256, 256))
    numpy.testing.assert_array_equal(numpy.asarray(fallback),
                                     numpy.asarray(default))


@pytest.mark.tune
def test_attention_family_quantization_and_feasibility():
    from veles_tpu.tune.spec import attention_spec, family_for
    family = family_for("attention")
    spec = attention_spec(4, 513, 64, "float32", 0)
    sched = family.quantize(spec, {"bq": 100, "bk": 300})
    bq, bk = sched["blocks"]
    assert bq % 8 == 0 and bk % 128 == 0
    assert family.feasible(spec, {"blocks": [128, 256]})
    assert not family.feasible(spec, {"blocks": [1024, 2048]})
    assert family.validate({"blocks": [8, 128]})
    assert family.validate({"blocks": [7, 128]}) is None
    assert family.space(spec) is not None


# -- the unit chain ---------------------------------------------------------


def test_layer_norm_apply_and_gd_matches_autodiff():
    from veles_tpu.models.transformer import GDLayerNorm, LayerNorm
    rng = numpy.random.RandomState(7)
    x = jnp.asarray(rng.randn(4, 6, 8), jnp.float32)
    gamma = jnp.asarray(rng.rand(8) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(8) * 0.1, jnp.float32)
    y = LayerNorm.apply({"weights": gamma, "bias": beta}, x)
    xn = (numpy.asarray(y) - numpy.asarray(beta)) / numpy.asarray(
        gamma)
    numpy.testing.assert_allclose(xn.mean(-1), 0.0, atol=1e-5)
    numpy.testing.assert_allclose(xn.std(-1), 1.0, atol=1e-3)

    err = jnp.asarray(rng.randn(4, 6, 8), jnp.float32)

    def loss(g_, b_):
        return jnp.sum(LayerNorm.apply(
            {"weights": g_, "bias": b_}, x) * err)

    gw, gb = jax.grad(loss, argnums=(0, 1))(gamma, beta)
    state = {"weights": gamma, "bias": beta,
             "accum_weights": jnp.zeros_like(gamma),
             "accum_bias": jnp.zeros_like(beta),
             "accum2_weights": None, "accum2_bias": None}
    hyper = {"learning_rate": 1.0, "learning_rate_bias": 1.0,
             "weights_decay": 0.0, "weights_decay_bias": 0.0,
             "l1_vs_l2": 0.0, "gradient_moment": 0.0,
             "gradient_moment_bias": 0.0, "adadelta_rho": 0.95,
             "solver_epsilon": 1e-6}
    _, new_state = GDLayerNorm.backward(
        state, hyper, x, y, err, solver="momentum", include_bias=True,
        need_err_input=False, eps=1e-5)
    numpy.testing.assert_allclose(
        numpy.asarray(gamma) - numpy.asarray(new_state["weights"]),
        numpy.asarray(gw), rtol=1e-4, atol=1e-5)
    numpy.testing.assert_allclose(
        numpy.asarray(beta) - numpy.asarray(new_state["bias"]),
        numpy.asarray(gb), rtol=1e-4, atol=1e-5)


def test_transformer_block_shapes_and_gd_guard():
    """Block keeps (B, T, D); a poisoned cotangent skips the update
    bit-exactly and cascades a non-finite err_input upstream."""
    from veles_tpu.models.transformer import (GDTransformerBlock,
                                              TransformerBlock,
                                              init_block_params)
    rng = numpy.random.RandomState(8)
    d, hidden = 8, 16
    w, b = init_block_params(d, hidden, rng)
    x = jnp.asarray(rng.randn(3, 5, d), jnp.float32)
    y = TransformerBlock.apply({"weights": w, "bias": b}, x, heads=2,
                               hidden=hidden)
    assert y.shape == x.shape

    state = {"weights": jnp.asarray(w), "bias": jnp.asarray(b),
             "accum_weights": jnp.zeros_like(jnp.asarray(w)),
             "accum_bias": jnp.zeros_like(jnp.asarray(b)),
             "accum2_weights": None, "accum2_bias": None}
    hyper = {"learning_rate": 0.1, "learning_rate_bias": 0.1,
             "weights_decay": 0.0, "weights_decay_bias": 0.0,
             "l1_vs_l2": 0.0, "gradient_moment": 0.0,
             "gradient_moment_bias": 0.0, "adadelta_rho": 0.95,
             "solver_epsilon": 1e-6}
    err = jnp.full(y.shape, jnp.nan, jnp.float32)
    err_input, new_state = GDTransformerBlock.backward(
        state, hyper, x, y, err, solver="momentum", include_bias=True,
        need_err_input=True, heads=2, hidden=hidden)
    assert int(new_state.pop("skipped")) == 1
    numpy.testing.assert_array_equal(
        numpy.asarray(new_state["weights"]), numpy.asarray(w))
    assert not bool(jnp.isfinite(err_input).all())


def test_workflow_trains_per_unit_chain(cpu_device):
    """The unit chain end to end (per-unit jit path) on digit-row-like
    synthetic sequences: error drops well below chance."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.loader import FullBatchLoader
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.prng import RandomGenerator

    class SeqLoader(FullBatchLoader):
        def load_data(self):
            self.class_lengths[:] = [0, 32, 96]
            self._calc_class_end_offsets()
            self.create_originals((8, 8))
            rng = numpy.random.RandomState(7)
            t = numpy.arange(8)
            for i in range(self.total_samples):
                label = i % 2
                freq = 0.3 if label == 0 else 0.9
                sig = numpy.sin(freq * t)[:, None].repeat(8, 1)
                self.original_data.mem[i] = (
                    sig + rng.randn(8, 8) * 0.1)
                self.original_labels[i] = label

    wf = DummyWorkflow()
    sw = StandardWorkflow(
        wf.workflow,
        layers=[
            {"type": "transformer", "heads": 2, "hidden": 16,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 2,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: SeqLoader(
            w, minibatch_size=32,
            prng=RandomGenerator("tfm", seed=5)),
        decision_config=dict(max_epochs=8),
    )
    sw.initialize(device=cpu_device)
    sw.run()
    assert sw.decision.epoch_metrics[1] < 25.0


def test_workflow_trains_fused_with_mfu_attribution(cpu_device):
    """StandardWorkflow.fuse over the transformer chain: the fused
    step trains AND publishes its cost-model FLOPs, so mfu_snapshot /
    bwd_snapshot attribute the new workload like conv/MLP."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.loader import FullBatchLoader
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.observe import xla_introspect
    from veles_tpu.observe.metrics import registry as _registry
    from veles_tpu.prng import RandomGenerator

    class SeqLoader(FullBatchLoader):
        def load_data(self):
            self.class_lengths[:] = [0, 16, 48]
            self._calc_class_end_offsets()
            self.create_originals((8, 8))
            rng = numpy.random.RandomState(9)
            for i in range(self.total_samples):
                label = i % 2
                base = numpy.full((8, 8), label, numpy.float32)
                self.original_data.mem[i] = (
                    base + rng.randn(8, 8) * 0.2)
                self.original_labels[i] = label

    wf = DummyWorkflow()
    sw = StandardWorkflow(
        wf.workflow,
        layers=[
            {"type": "transformer", "heads": 2, "hidden": 16,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 2,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: SeqLoader(
            w, minibatch_size=16,
            prng=RandomGenerator("tfm-fused", seed=6)),
        decision_config=dict(max_epochs=3),
    )
    trainer = sw.fuse()
    sw.initialize(device=cpu_device)
    sw.run()
    assert sw.decision.epoch_metrics[1] is not None
    assert trainer._step_flops_ is not None
    if trainer._step_flops_ > 0:  # cost analysis available on this jax
        assert _registry.peek("xla.step_flops").value > 0
        # fwd flops from the eval lowering -> bwd attribution feeds
        snap = xla_introspect.bwd_snapshot()
        assert snap is None or "bwd_step_ms" in snap
