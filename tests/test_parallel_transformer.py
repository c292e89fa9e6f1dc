"""Model sharding beyond data-parallel, driving the NEW transformer
blocks: tensor-parallel head/column sharding (parallel/tensor.py) and
the pipeline-parallel stage split (parallel/pipeline.py), both against
the single-device fused step over 3 chained train steps on the
8-device CPU mesh — plus pipeline_forward/moe_apply compositions over
real TransformerBlock stages (the pre-existing pipeline-MoE tests use
synthetic stages).  docs/distributed.md "Model parallelism"."""

import numpy
import pytest

pytestmark = [pytest.mark.transformer, pytest.mark.dist]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from veles_tpu.compiler import build_train_step  # noqa: E402
from veles_tpu.models.zoo import (  # noqa: E402
    build_plans_and_state, transformer_layers)
from veles_tpu.parallel.mesh import make_mesh  # noqa: E402
from veles_tpu.parallel.pipeline import (  # noqa: E402
    build_pipeline_train_step, stack_pipeline_state,
    unstack_pipeline_state)
from veles_tpu.parallel.tensor import (  # noqa: E402
    build_tp_train_step, gather_tp_state, place_tp_state)

#: the receipted ULP bound for the model-parallel paths: the TP output
#: projection is a psum of per-shard partial contractions and the
#: microbatched pipeline accumulates per-microbatch wgrads — different
#: f32 reduction groupings than the single-device step, compounded
#: through 3 momentum steps.  Measured 1.5e-4 (TP) / 9.1e-5 (mb=2
#: pipeline) on this model; the bound gives ~6x headroom.
ULP_BOUND_3_STEPS = 1e-3

#: the microbatches=1 pipeline split executes the single-device op
#: sequence on the same values, but it is a DIFFERENT program (a
#: shard_map over the pipe axis), and the installed XLA:CPU (jax
#: 0.9.0) no longer compiles the two to bit-equal arithmetic: the
#: losses and every rank's buffers still agree bit for bit, the state
#: differs from the unsplit step's by 1-2 ULP after 3 momentum steps
#: (measured 3.0e-7 / 2.6e-7 max rel on these two models).  The bound
#: sits 100x under the regrouped-reduction band above, so a lost
#: wavefront tick or a divergent rank (1e-2 and up) cannot hide in it.
SPLIT_ULP_BOUND_3_STEPS = 1e-5


def _setup(seed=3, heads=4):
    specs = transformer_layers(blocks=2, heads=heads, hidden=16,
                               classes=10)
    plans, state, _ = build_plans_and_state(specs, (8, 8), seed=seed)
    rng = numpy.random.RandomState(5)
    x = jnp.asarray(rng.rand(16, 8, 8), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, 16), jnp.int32)
    return plans, state, x, y, numpy.float32(16)


def _run3(step, state, x, y, bs, **kw):
    losses = []
    for _ in range(3):
        state, m = step(state, x, y, bs, **kw)
        losses.append(float(m["loss"]))
    return state, losses, m


def _host(state):
    return [{k: (None if v is None else numpy.asarray(v))
             for k, v in e.items()} for e in state]


def _maxrel(ref, got):
    worst = 0.0
    for re, ge in zip(ref, got):
        for key in re:
            if re[key] is None or ge.get(key) is None:
                continue
            a = numpy.asarray(re[key], numpy.float64)
            b = numpy.asarray(ge[key], numpy.float64)
            worst = max(worst, float(
                numpy.abs(a - b).max() / max(numpy.abs(a).max(),
                                             1e-9)))
    return worst


def _assert_bit_identical(ref, got):
    for re, ge in zip(ref, got):
        for key in re:
            if re[key] is None:
                continue
            numpy.testing.assert_array_equal(
                numpy.asarray(re[key]), numpy.asarray(ge[key]),
                err_msg="leaf %r" % key)


def _reference(plans, state, x, y, bs):
    step = build_train_step(plans, donate=False)
    s = [dict(e) for e in state]
    s, losses, m = _run3(step, s, x, y, bs)
    return _host(s), losses


# -- tensor parallel --------------------------------------------------------


def test_tp_step_matches_single_device_over_3_chained_steps():
    """Acceptance: head-sharded QKV + column/row-split MLP over
    model=2, ULP-bounded (receipted) against the single-device fused
    step — loss AND weights/accumulators."""
    plans, state, x, y, bs = _setup()
    ref_state, ref_losses = _reference(plans, state, x, y, bs)

    mesh = make_mesh({"model": 2}, devices=jax.devices()[:2])
    ts = place_tp_state(mesh, plans, state)
    step = build_tp_train_step(plans, mesh=mesh, donate=False)
    ts, losses, m = _run3(step, ts, x, y, bs)
    for a, b in zip(ref_losses, losses):
        assert abs(a - b) / abs(a) < 1e-5
    measured = _maxrel(ref_state, gather_tp_state(plans, ts))
    assert measured < ULP_BOUND_3_STEPS, \
        "TP drift %.3g exceeds the receipted bound" % measured


def test_tp_single_shard_stays_in_tight_ulp_band():
    """model axis of size 1 = no partial contractions to regroup; the
    residual drift (measured 2.5e-3 rel on near-zero bias
    accumulators, ~4e-7 absolute) is pure program-structure noise —
    XLA fuses the shard_map program differently from the plain one,
    regrouping the bias-grad reductions — an order of magnitude under
    the multi-shard bound."""
    plans, state, x, y, bs = _setup(heads=2)
    ref_state, ref_losses = _reference(plans, state, x, y, bs)
    mesh = make_mesh({"model": 1}, devices=jax.devices()[:1])
    ts = place_tp_state(mesh, plans, state)
    step = build_tp_train_step(plans, mesh=mesh, donate=False)
    ts, losses, _ = _run3(step, ts, x, y, bs)
    for a, b in zip(ref_losses, losses):
        assert abs(a - b) / abs(a) < 1e-6
    got = gather_tp_state(plans, ts)
    for re, ge in zip(ref_state, got):
        for key in re:
            if re[key] is None:
                continue
            a = numpy.asarray(re[key], numpy.float64)
            b = numpy.asarray(ge[key], numpy.float64)
            assert float(numpy.abs(a - b).max()) < 1e-6, key


def test_tp_composes_with_bucketed_data_axis():
    """dp x tp on one mesh: batch shards over data, heads over model,
    gradients merge through the bucketed all-reduce — same result as
    TP alone (the data-axis merge is exact for a replicated batch
    split + psum'd metrics)."""
    plans, state, x, y, bs = _setup()
    mesh_tp = make_mesh({"model": 2}, devices=jax.devices()[:2])
    ts = place_tp_state(mesh_tp, plans, state)
    step_tp = build_tp_train_step(plans, mesh=mesh_tp, donate=False)
    ts, tp_losses, _ = _run3(step_tp, ts, x, y, bs)

    mesh = make_mesh({"data": 2, "model": 2},
                     devices=jax.devices()[:4])
    ts2 = place_tp_state(mesh, plans, state)
    step = build_tp_train_step(plans, mesh=mesh, data_axis="data",
                               grad_bucket_mb=0.001, donate=False)
    ts2, losses, m = _run3(step, ts2, x, y, bs)
    assert bool(m["finite"])
    for a, b in zip(tp_losses, losses):
        assert abs(a - b) / abs(a) < 1e-5
    assert _maxrel(gather_tp_state(plans, ts),
                   gather_tp_state(plans, ts2)) < ULP_BOUND_3_STEPS


def test_tp_poisoned_step_skips_uniformly():
    """A poisoned gradient leaves EVERY shard's state bit-identical to
    never having served the step (the guard's grad-norm is psummed
    over the model axis, so all shards see the same verdict)."""
    plans, state, x, y, bs = _setup()
    mesh = make_mesh({"model": 2}, devices=jax.devices()[:2])
    ts = place_tp_state(mesh, plans, state)
    step = build_tp_train_step(plans, mesh=mesh, donate=False)
    before = gather_tp_state(plans, ts)
    ts, m = step(ts, x, y, bs, None, numpy.float32(numpy.nan), None)
    assert int(m["skipped"]) == 1 and not bool(m["finite"])
    _assert_bit_identical(before, gather_tp_state(plans, ts))


def test_tp_step_flops_feed_mfu_attribution():
    """The TP step exposes .lower like the fused step, so the live MFU
    pipeline (xla.step_flops -> mfu_snapshot) attributes the sharded
    workload too."""
    from veles_tpu.observe import xla_introspect
    from veles_tpu.observe.metrics import MetricsRegistry
    plans, state, x, y, bs = _setup()
    mesh = make_mesh({"model": 2}, devices=jax.devices()[:2])
    ts = place_tp_state(mesh, plans, state)
    step = build_tp_train_step(plans, mesh=mesh, donate=False)
    cost = step.lower(ts, x, y, bs).cost_analysis()
    flops = (sum(float(c.get("flops", 0.0)) for c in cost
                 if isinstance(c, dict))
             if isinstance(cost, (list, tuple))
             else float((cost or {}).get("flops", 0.0)))
    assert flops > 0
    reg = MetricsRegistry()
    xla_introspect.set_step_flops(flops, reg)
    assert reg.peek("xla.step_flops").value == flops


# -- pipeline parallel ------------------------------------------------------


def test_pipeline_2_stage_split_bit_identical_over_3_steps():
    """Acceptance (satellite): the 2-stage pipeline split of the
    2-block transformer matches the unsplit fused step over 3 chained
    train steps — losses bit for bit, state within
    SPLIT_ULP_BOUND_3_STEPS (microbatches=1: every stage executes the
    single-device op sequence; discarded wavefront ticks contribute
    exact-zero gradients).  Name kept from when XLA:CPU compiled the
    two programs to bit-equal arithmetic."""
    plans, state, x, y, bs = _setup(heads=2)
    ref_state, ref_losses = _reference(plans, state, x, y, bs)

    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    ps, layout = stack_pipeline_state(mesh, plans, state)
    step = build_pipeline_train_step(plans, mesh=mesh, microbatches=1,
                                     donate=False)
    ps, losses, _ = _run3(step, ps, x, y, bs)
    assert losses == ref_losses, "loss must be bit-identical"
    assert _maxrel(ref_state, unstack_pipeline_state(ps, layout)) < \
        SPLIT_ULP_BOUND_3_STEPS


def test_pipeline_microbatches_ulp_bounded():
    """microbatches=2 accumulates per-microbatch wgrads (a different
    f32 grouping): receipted-ULP-bounded, not bit-equal."""
    plans, state, x, y, bs = _setup(heads=2)
    ref_state, _ = _reference(plans, state, x, y, bs)
    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    ps, layout = stack_pipeline_state(mesh, plans, state)
    step = build_pipeline_train_step(plans, mesh=mesh, microbatches=2,
                                     donate=False)
    ps, _, m = _run3(step, ps, x, y, bs)
    assert bool(m["finite"])
    measured = _maxrel(ref_state, unstack_pipeline_state(ps, layout))
    assert 0 < measured < ULP_BOUND_3_STEPS


def test_pipeline_poisoned_step_skips_uniformly():
    plans, state, x, y, bs = _setup(heads=2)
    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    ps, layout = stack_pipeline_state(mesh, plans, state)
    step = build_pipeline_train_step(plans, mesh=mesh, microbatches=1,
                                     donate=False)
    before = unstack_pipeline_state(ps, layout)
    ps, m = step(ps, x, y, bs, None, numpy.float32(numpy.nan), None)
    assert int(m["skipped"]) == 1
    _assert_bit_identical(before, unstack_pipeline_state(ps, layout))


def test_pipeline_prefix_layer_grads_replicate_bit_identically():
    """Regression: layers BEFORE the block run feed the wavefront only
    through stage 0's injection, so their raw cotangent is zero on
    every other rank — without the enter conjugate's psum, 'replicated'
    prefix updates silently diverge per rank (rank 0 trains, the rest
    momentum-decay) and the finiteness guard fires non-uniformly.
    With it, every rank's buffers stay BIT-identical to each other
    and the prefix-bearing split stays within SPLIT_ULP_BOUND_3_STEPS
    of the unsplit step over 3 chained steps."""
    specs = ([{"type": "layer_norm", "learning_rate": 0.05,
               "gradient_moment": 0.9}] +
             transformer_layers(blocks=2, heads=2, hidden=16,
                                classes=10, lr=0.05))
    plans, state, _ = build_plans_and_state(specs, (8, 8), seed=4)
    rng = numpy.random.RandomState(6)
    x = jnp.asarray(rng.rand(16, 8, 8), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, 16), jnp.int32)
    bs = numpy.float32(16)
    ref_state, ref_losses = _reference(plans, state, x, y, bs)

    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    ps, layout = stack_pipeline_state(mesh, plans, state)
    step = build_pipeline_train_step(plans, mesh=mesh, microbatches=1,
                                     donate=False)
    ps, losses, _ = _run3(step, ps, x, y, bs)
    assert losses == ref_losses
    # the REAL uniformity check: the assembled logical array can hide a
    # divergent rank (jax picks one shard for a 'replicated' leaf), so
    # compare every rank's device buffer bit-for-bit
    for key in ("weights", "accum_weights", "bias", "accum_bias"):
        leaf = ps[0][key]
        shards = [numpy.asarray(s.data)
                  for s in leaf.addressable_shards]
        for other in shards[1:]:
            numpy.testing.assert_array_equal(shards[0], other,
                                             err_msg=key)
    got = unstack_pipeline_state(ps, layout)
    assert _maxrel(ref_state, got) < SPLIT_ULP_BOUND_3_STEPS
    # the trained prefix must actually have MOVED (a zero-grad prefix
    # that merely matched the reference would mean the reference broke)
    assert not numpy.array_equal(numpy.asarray(got[0]["weights"]),
                                 numpy.asarray(state[0]["weights"]))


def test_pipeline_rejects_uneven_or_scattered_blocks():
    plans, state, x, y, bs = _setup(heads=2)
    mesh3 = make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    with pytest.raises(ValueError):
        build_pipeline_train_step(plans, mesh=mesh3)
    no_blocks, _, _ = build_plans_and_state(
        [{"type": "softmax", "output_sample_shape": 4}], (8,), seed=0)
    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError):
        build_pipeline_train_step(no_blocks, mesh=mesh)


# -- pipeline_forward / moe over REAL transformer blocks --------------------


def test_pipeline_forward_drives_transformer_block_stages():
    """pipeline_forward with TransformerBlock.apply as the stage fn
    (4 real blocks over 4 stages) vs the sequential composition."""
    from veles_tpu.models.transformer import (TransformerBlock,
                                              init_block_params)
    from veles_tpu.parallel.pipeline import (pipeline_forward,
                                             stack_stage_params,
                                             stage_param_sharding)
    rng = numpy.random.RandomState(11)
    d, hidden, n_stages = 8, 16, 4
    stages = []
    for _ in range(n_stages):
        w, b = init_block_params(d, hidden, rng)
        stages.append({"weights": jnp.asarray(w),
                       "bias": jnp.asarray(b)})
    x = jnp.asarray(rng.randn(8, 6, d), jnp.float32)

    def stage_fn(params, a):
        return TransformerBlock.apply(params, a, heads=2,
                                      hidden=hidden)

    want = x
    for s in stages:
        want = stage_fn(s, want)

    mesh = make_mesh({"pipe": n_stages}, devices=jax.devices()[:4])
    stacked = stage_param_sharding(mesh, stack_stage_params(stages))
    got = pipeline_forward(stage_fn, stacked, x, mesh, microbatches=2)
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(want), rtol=1e-5,
                                  atol=1e-5)


def test_moe_ffn_drives_transformer_attention_sublayer():
    """A transformer block whose position-wise FFN is the
    expert-parallel MoE layer: attention sub-layer (real
    MultiHeadAttention math) -> LN -> moe_apply over the expert axis,
    vs the moe_reference composition."""
    from veles_tpu.models.transformer import (layer_norm,
                                              multi_head_attention)
    from veles_tpu.parallel.moe import (init_moe_params, moe_apply,
                                        moe_reference,
                                        shard_moe_params)
    rng = numpy.random.RandomState(12)
    d, heads = 8, 2
    w_qkv = jnp.asarray(rng.randn(d, 3 * d) * 0.3, jnp.float32)
    w_o = jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)
    gamma = jnp.ones((d,), jnp.float32)
    beta = jnp.zeros((d,), jnp.float32)
    x = jnp.asarray(rng.randn(6, 5, d), jnp.float32)

    h = x + multi_head_attention(layer_norm(x, gamma, beta), w_qkv,
                                 None, w_o, None, heads)
    tokens = layer_norm(h, gamma, beta).reshape(-1, d)
    moe = init_moe_params(rng, n_experts=4, features=d, hidden=16,
                          out_features=d)
    want = numpy.asarray(h) + numpy.asarray(
        moe_reference(moe, tokens, top_k=2)).reshape(h.shape)

    mesh = make_mesh({"expert": 4}, devices=jax.devices()[:4])
    sharded = shard_moe_params(mesh, moe)
    got = numpy.asarray(h) + numpy.asarray(
        moe_apply(sharded, tokens, mesh, top_k=2)).reshape(h.shape)
    numpy.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
