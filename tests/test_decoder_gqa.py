"""The grouped-query decoder with window and full layers mixed, sandwich
norms and routed experts at toy width on the CPU, seeded weights: the
program against the benchmark's plain reference
(``benchmark/references/gqa_window_moe_decoder.py``) on logits, loss,
every gradient and one AdamW step, with a pattern window-window-full and
a window shorter than T; the shares of an expert-parallel deployment add
up to the uncut layer; the rotate-half rotary; which layers know
positions; the recomputed backward keeps both kernel forms' results."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import gqa_window_moe_decoder as reference  # noqa: E402,E501

from tests.test_checkpoint_keeps import kernel_calls  # noqa: E402
from tests.test_decoder import ToyTokens, T, VOCAB  # noqa: E402
from veles_tpu import prng  # noqa: E402
from veles_tpu.backends import Device  # noqa: E402
from veles_tpu.compiler import (  # noqa: E402
    _forward_for_loss, build_forward, build_train_step, extract_state,
    workflow_plan)
from veles_tpu.config import root  # noqa: E402
from veles_tpu.dummy import DummyLauncher  # noqa: E402
from veles_tpu.models import decoder, fused, zoo  # noqa: E402
from veles_tpu.models.nn_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.observe.metrics import registry  # noqa: E402
from veles_tpu.ops import attention  # noqa: E402
from veles_tpu.ops.attention import KEPT_NAMES  # noqa: E402

WINDOW = 12
ARGUMENTS = dict(
    vocab=VOCAB, width=64, layer_types=["window", "window", "full"],
    dense_layers=1, heads=8, kv_heads=2, head_width=16, window=WINDOW,
    ffn=96, experts=16, experts_held=4, first_expert=4, top_k=3,
    expert_width=32, shared_width=32, routed_scale=2.826, route_eps=1e-20,
    theta=100.0, eps=1e-5, embed_scale=8.0, lr=3e-3, router_bias_std=0.05,
    post_norm_gain=0.5)


@pytest.fixture
def _precision(monkeypatch):
    def set_to(name):
        monkeypatch.setattr(root.common.engine, "precision_type", name)
    set_to("float32")
    return set_to


def toy_workflow(seed=5, batch=4, max_epochs=2, **arguments):
    prng.get().seed(seed)
    layers = zoo.gqa_moe_decoder_layers(**dict(ARGUMENTS, **arguments))
    sw = StandardWorkflow(
        DummyLauncher(), layers=layers,
        loader_factory=lambda w: ToyTokens(w, minibatch_size=batch),
        decision_config=dict(max_epochs=max_epochs))
    sw.fuse()
    sw.initialize(device=Device(backend="cpu"))
    return sw, layers


def program_and_batch(**arguments):
    sw, layers = toy_workflow(**arguments)
    plans, state = workflow_plan(sw), extract_state(sw)
    rows = numpy.array(sw.loader.original_data.mem[:4])
    return sw, layers, plans, state, rows[:, :-1], rows[:, 1:]


def weights_and_gains(state):
    return [{"weights": s["weights"], "bias": s["bias"]} for s in state]


def test_program_against_reference_logits_loss_gradients_and_a_step(
        _precision):
    sw, layers, plans, state, x, y = program_and_batch()
    assert [spec.get("window") for spec in layers[1:-1]] == [
        WINDOW, WINDOW, None] and WINDOW < T
    assert [spec["rope"] for spec in layers[1:-1]] == [True, True, False]
    params = weights_and_gains(state)
    with jax.default_matmul_precision("highest"):
        got = numpy.asarray(jax.jit(build_forward(plans))(params, x))
    want, loads = reference.forward(layers, params, x, query_block=8,
                                    token_block=16, with_load=True)
    assert got.shape == (4, T, VOCAB)
    numpy.testing.assert_allclose(got, want, atol=5e-6)
    # the post-norms' gains start from the factory's value, the rest at 1
    names = [n for n, _ in reference.layer_pieces(layers[2], 64)[1]]
    gains = reference.split(numpy.asarray(state[2]["bias"]),
                            reference.layer_pieces(layers[2], 64)[1])
    assert names == ["attn_gain", "q_gain", "k_gain", "post_attn_gain",
                     "ffn_gain", "post_ffn_gain", "router_bias"]
    for name in names[:-1]:
        assert float(gains[name][0]) == (0.5 if "post" in name else 1.0)

    step = build_train_step(plans, donate=False)
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, x, y, numpy.float32(4),
                                  step_count=numpy.int32(1))
    ref_loss, ref_grads = reference.loss_and_gradients(
        layers, params, x, y, query_block=8, token_block=16)
    assert float(metrics["loss"]) == pytest.approx(float(ref_loss),
                                                   rel=1e-6)
    numpy.testing.assert_array_equal(metrics["moe_load"],
                                     numpy.stack(loads))
    assert metrics["moe_dropped"].tolist() == [0, 0]
    assert metrics["moe_assignments"].tolist() == [
        int(load.sum()) for load in loads]
    grads = jax.grad(lambda p: reference.loss(
        build_forward(plans)(p, x), y))(params)
    hyper = dict(lr=3e-3, beta1=0.9, beta2=0.95, eps=1e-8)
    for i, (old, new) in enumerate(zip(state, new_state)):
        for key, decay in (("weights", 0.1), ("bias", 0.0)):
            if old[key] is None:
                continue
            g_ref = numpy.asarray(ref_grads[i][key])
            g = numpy.asarray(grads[i][key])
            scale = max(numpy.abs(g_ref).max(), 1e-12)
            assert numpy.abs(g - g_ref).max() < 2e-4 * scale, (i, key)
            p, m, v = reference.adamw_step(
                numpy.asarray(old[key]), g_ref, 0.0, 0.0, 1, decay=decay,
                **hyper)
            moved = numpy.abs(g_ref) > 1e-3 * scale  # sign(g) is settled
            numpy.testing.assert_allclose(
                numpy.asarray(new[key])[moved], p[moved], atol=1e-6)
            numpy.testing.assert_allclose(
                numpy.asarray(new["accum_" + key])[moved], m[moved],
                rtol=2e-3, atol=1e-9)


def test_the_control_runs_the_references_own_programs(_precision):
    """``lowered=False`` is the float32 reference through the programs
    compiled for the control's operand, their rounding switched off;
    switched on, an 8-bit float is a rounding away."""
    sw, layers, plans, state, x, y = program_and_batch()
    params = weights_and_gains(state)
    how = dict(query_block=8, token_block=16)
    want = numpy.asarray(reference.forward(layers, params, x, **how))
    off = numpy.asarray(reference.forward(
        layers, params, x, operand="float8_e4m3fn", lowered=False, **how))
    numpy.testing.assert_allclose(off, want, rtol=1e-5, atol=2e-6)
    low = numpy.asarray(reference.forward(
        layers, params, x, operand="float8_e4m3fn", **how))
    apart = numpy.linalg.norm(low - want) / numpy.linalg.norm(want)
    assert 0.01 < apart < 0.5, apart
    half = numpy.asarray(reference.forward(
        layers, params, x, operand="bfloat16", **how))
    assert numpy.linalg.norm(half - want) < 0.2 * numpy.linalg.norm(
        low - want)


def test_the_shares_add_up_to_the_uncut_layer(_precision):
    """Sixteen ranks of one expert each: the routed parts the ranks give
    plus the shared expert once equal the uncut reference layer's ``f``
    BEFORE ``g_post_ffn`` — the post-norm is not additive (the norm of a
    sum is not the sum of norms), so the shares are added up in front of
    it, as the exchange between ranks would add them."""
    rng = numpy.random.RandomState(11)
    dims = dict(heads=8, kv_heads=2, head_width=16, window=WINDOW,
                rope=True, post_norms=True, experts=16, top_k=3,
                expert_width=32, shared_width=32, routed_scale=2.826,
                route_eps=1e-20, theta=100.0)
    width, ranks = 64, 16
    whole = dict(dims, experts_held=16, first_expert=0)
    pieces, gain_pieces = reference.layer_pieces(whole, width)
    full = {name: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
            for name, shape in pieces}
    gains = {name: jnp.asarray(1 + 0.1 * rng.randn(*shape), jnp.float32)
             for name, shape in gain_pieces}
    gains["router_bias"] = jnp.asarray(0.05 * rng.randn(16), jnp.float32)
    m = jnp.asarray(rng.randn(2 * T, width), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, load = reference.feed_forward_sum(m, whole, full, gains,
                                                 "float32")
        shared = reference.gated(m, full["s_gate"], full["s_up"],
                                 full["s_down"], "float32")
        chosen, gate = reference.route(
            m, full["w_router"], gains["router_bias"], 3, 2.826, 1e-20)
        total, seen = numpy.zeros((2 * T, width), numpy.float32), 0
        for rank in range(ranks):
            held = slice(rank, rank + 1)
            out, aux = decoder.routed_experts(
                m, chosen.astype(jnp.int32), gate, full["e_gate"][held],
                full["e_up"][held], full["e_down"][held],
                first_expert=rank, capacity=None)
            assert int(aux["moe_dropped"]) == 0
            numpy.testing.assert_array_equal(aux["moe_load"], load[held])
            seen += int(aux["moe_assignments"])
            total += numpy.asarray(out)
    assert seen == 2 * T * 3  # every assignment is some rank's
    numpy.testing.assert_allclose(total + numpy.asarray(shared), uncut,
                                  atol=5e-6)
    assert numpy.abs(total).max() > 1e-3
    # and the norm in front of which they add up is not additive
    normed = reference.rms_norm(uncut, gains["post_ffn_gain"], 1e-5)
    parts = reference.rms_norm(jnp.asarray(total), gains["post_ffn_gain"],
                               1e-5) + reference.rms_norm(
        shared, gains["post_ffn_gain"], 1e-5)
    assert numpy.abs(numpy.asarray(normed - parts)).max() > 0.1


def test_the_program_layer_is_the_reference_layer_on_its_share(_precision):
    """One routed layer of the program, holding experts 4-7 of 16,
    against the reference layer given the same share."""
    rng = numpy.random.RandomState(3)
    dims = dict(heads=8, kv_heads=2, head_width=16, window=WINDOW,
                rope=True, post_norms=True, experts=16, experts_held=4,
                first_expert=4, top_k=3, expert_width=32, shared_width=32,
                routed_scale=2.826, route_eps=1e-20, theta=100.0)
    pieces, gain_pieces = reference.layer_pieces(dims, 64)
    w = {name: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
         for name, shape in pieces}
    gains = {name: jnp.asarray(1 + 0.1 * rng.randn(*shape), jnp.float32)
             for name, shape in gain_pieces}
    h = jnp.asarray(rng.randn(2, T, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, load = reference.layer(h, dims, w, gains, eps=1e-5,
                                     operand="float32", query_block=8,
                                     token_block=16)
        got, aux = decoder.decoder_layer(
            h, reference._flat(w, pieces), reference._flat(gains,
                                                           gain_pieces),
            compute_dtype="float32", eps=1e-5, **dims)
    numpy.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    numpy.testing.assert_array_equal(aux["moe_load"], load)


def test_rotary_turns_the_two_halves():
    x = numpy.random.RandomState(0).randn(2, 5, 3, 8).astype(numpy.float32)
    got = numpy.asarray(decoder.rotary(jnp.asarray(x), 100.0, halves=True))
    for t in range(5):
        for i in range(4):
            angle = t * 100.0 ** (-2 * i / 8)
            a, b = x[:, t, :, i], x[:, t, :, i + 4]
            numpy.testing.assert_allclose(
                got[:, t, :, i],
                a * numpy.cos(angle) - b * numpy.sin(angle), atol=1e-5)
            numpy.testing.assert_allclose(
                got[:, t, :, i + 4],
                a * numpy.sin(angle) + b * numpy.cos(angle), atol=1e-5)
    # the reference's, written apart, is the same turn
    numpy.testing.assert_allclose(
        numpy.asarray(reference.rotary(jnp.asarray(x[0]), 100.0)), got[0],
        atol=1e-5)


@pytest.mark.parametrize("kind, knows_positions", [("full", False),
                                                   ("window", True)])
def test_only_a_windowed_layer_knows_positions(_precision, kind,
                                               knows_positions):
    """A full layer alone is position-free: swapping two earlier tokens
    leaves a later query's output as it was (no rotary, and causal
    softmax sums over a SET of keys).  A windowed layer alone is not:
    its rotary tells the two apart."""
    rng = numpy.random.RandomState(7)
    dims = dict(heads=8, kv_heads=2, head_width=16, ffn=96,
                post_norms=True, theta=100.0,
                window=WINDOW if kind == "window" else None,
                rope=kind == "window")
    w_layout, b_layout = decoder.layer_layout(64, **dims)
    weights = jnp.asarray(rng.randn(sum(
        decoder._size(s) for _, s in w_layout)) * 0.05, jnp.float32)
    bias = jnp.ones((sum(decoder._size(s) for _, s in b_layout),),
                    jnp.float32)
    h = rng.randn(1, 10, 64).astype(numpy.float32)
    swapped = h.copy()
    swapped[0, [2, 5]] = h[0, [5, 2]]
    with jax.default_matmul_precision("highest"):
        out, other = (numpy.asarray(decoder.decoder_layer(
            jnp.asarray(a), weights, bias, compute_dtype="float32",
            eps=1e-5, **dims)[0]) for a in (h, swapped))
    moved = numpy.abs(out[0, 6:] - other[0, 6:]).max()
    assert (moved > 1e-3) == knows_positions, moved
    if not knows_positions:
        assert moved < 1e-5


def test_flash_path_matches_the_stock_path_in_the_layers(_precision):
    """``pallas_bwd`` on routes the layers' attention through the
    grouped flash kernels, windowed and full (interpret mode here): the
    same output and gradients as the stock reference path."""
    sw, layers, plans, state, x, y = program_and_batch()
    params = weights_and_gains(state)

    def loss(p, flash):
        for plan in plans:
            if plan.forward_cls is decoder.DecoderLayer:
                plan.static["pallas_bwd"] = flash
        return reference.loss(build_forward(plans)(p, x), y)

    stock, stock_grads = jax.value_and_grad(loss)(params, False)
    flash, flash_grads = jax.value_and_grad(loss)(params, True)
    assert float(flash) == pytest.approx(float(stock), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(flash_grads),
                    jax.tree_util.tree_leaves(stock_grads)):
        assert numpy.abs(numpy.asarray(a - b)).max() < 1e-4 * max(
            numpy.abs(numpy.asarray(b)).max(), 1e-9)


def test_recomputed_backward_keeps_both_kernel_forms_named_results(
        _precision, monkeypatch):
    """Two kernel forms in one step (two windowed layers, one full): the
    decision keeps what BOTH named, the step under that policy runs each
    forward kernel once — two ``veles_flash_win_fwd``, one
    ``veles_flash_fwd``, not four and two as the bare checkpoint does —
    and gives the same step as the one that keeps every activation."""
    sw, layers, plans, state, x, y = program_and_batch()
    trainer = sw.fused_trainer
    for plan in plans:
        if plan.forward_cls is decoder.DecoderLayer:
            plan.static["pallas_bwd"] = True
    params = weights_and_gains(state)

    def forwards(remat):
        def loss(p):
            return reference.loss(_forward_for_loss(
                plans, p, x, remat=remat), y)
        calls = kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        assert {name: calls[name] for name in (
            attention.DQ_KERNEL_NAME, attention.DKV_KERNEL_NAME,
            attention.WIN_DQ_KERNEL_NAME,
            attention.WIN_DKV_KERNEL_NAME)} == {
                attention.DQ_KERNEL_NAME: 1, attention.DKV_KERNEL_NAME: 1,
                attention.WIN_DQ_KERNEL_NAME: 2,
                attention.WIN_DKV_KERNEL_NAME: 2}
        assert len(calls) == 6
        return (calls[attention.WIN_FWD_KERNEL_NAME],
                calls[attention.FWD_KERNEL_NAME])

    assert forwards(False) == (2, 1)
    assert forwards(True) == (4, 2)
    assert forwards(KEPT_NAMES) == (2, 1)

    # the decision, told a device on which only the named results fit:
    # 3 layers of 4 rows x 8 heads x 32 tokens x (16 wide + 2) float32
    class Told(object):
        def __init__(self, limit):
            self.stats = {"bytes_limit": limit, "bytes_in_use": 0}

        def memory_stats(self):
            return self.stats

    seen = []
    monkeypatch.setattr(trainer, "info",
                        lambda fmt, *args: seen.append(fmt % args))
    named = 3 * 4 * 8 * T * (16 + 2) * 4
    held = sum(a.nbytes for s in state
               for a in (s["weights"], s["bias"]) if a is not None)
    limit = int((held + named + 4096) / fused.REMAT_ABOVE) + 1
    monkeypatch.setattr(jax, "local_devices", lambda *a: [Told(limit)])
    assert trainer._backward_should_recompute(plans) == fused.kept_names()
    assert "recomputed in the backward but for" in seen[-1]
    assert registry.peek("step.kept_residual_bytes").value == named
    # no layer selects: the residuals under kept_names() are those under
    # the list without the selection's name, which adds nothing
    assert registry.peek("step.kept_selection_bytes").value == 0

    kept = build_train_step(plans, donate=False)(
        state, x, y, numpy.float32(4), step_count=numpy.int32(1))
    again = build_train_step(plans, donate=False, bwd_remat=KEPT_NAMES)(
        state, x, y, numpy.float32(4), step_count=numpy.int32(1))
    assert float(kept[1]["loss"]) == float(again[1]["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(kept[0]),
                    jax.tree_util.tree_leaves(again[0])):
        numpy.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6)


def test_bfloat16_operands_float32_state_trains(_precision):
    _precision("bfloat16")
    sw, _ = toy_workflow(max_epochs=3)
    assert all(f.weights.dtype == numpy.float32 for f in sw.forwards)
    before = {name: registry.counter(name).value for name in (
        "moe.dropped_assignments", "moe.assignments", "train.tokens")}
    sw.run()
    trainer = sw.fused_trainer
    assert float(trainer.last_loss) < 4.3 < numpy.log(VOCAB)
    assert int(trainer.skip_count) == 0
    assert registry.counter("moe.dropped_assignments").value == \
        before["moe.dropped_assignments"]
    assert registry.counter("moe.assignments").value > \
        before["moe.assignments"]
    assert registry.counter("train.tokens").value > before["train.tokens"]
    loads = [name for name in registry.snapshot()["counters"]
             if name.startswith("moe.load.l")]
    assert len(loads) >= 2 * 4  # 2 routed layers x 4 held experts


def test_the_factory_refuses_an_unknown_kind_of_layer():
    with pytest.raises(ValueError, match="window.*full"):
        zoo.gqa_moe_decoder_layers(**dict(
            ARGUMENTS, layer_types=["window", "sliding"]))
