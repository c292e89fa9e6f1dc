"""The decoder whose token mixer is a gated short convolution in most
layers at toy width on the CPU, seeded weights: the short convolution
against a token-by-token numpy loop and against the benchmark's plain
reference (``benchmark/references/conv_gqa_moe_decoder.py``), forward
and gradients, causal and at sequences shorter than the filter; a conv
layer, an ungated rotary attention layer and a routed layer with no
shared expert, each against the reference; the whole model on logits,
loss, every gradient and one AdamW step; the four shares of an
expert-parallel deployment add up to the uncut layer; the head tied to
the embedding's table — one array in the state and in a snapshot, its
gradient the sum of both uses, a restore reproduces the loss, the
builders that cannot honour the tie refuse it, and an untied plan's
program is the text it was."""

import hashlib
import os
import pickle
import re
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import conv_gqa_moe_decoder as reference  # noqa: E402,E501

from tests.test_checkpoint_keeps import kernel_calls  # noqa: E402
from tests.test_decoder import ToyTokens, T, VOCAB  # noqa: E402
from veles_tpu import compiler, prng  # noqa: E402
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

WIDTH = 64
ARGUMENTS = dict(
    vocab=VOCAB, width=WIDTH, layer_types=["conv", "attention", "conv"],
    dense_layers=1, heads=8, kv_heads=2, head_width=8, conv_taps=3,
    ffn=96, experts=16, experts_held=4, first_expert=4, top_k=3,
    expert_width=32, route_eps=1e-6, theta=100.0, eps=1e-5, lr=3e-3,
    router_bias_std=0.05, out_init_std=0.01)
BLOCKS = dict(query_block=8, token_block=16)


@pytest.fixture
def _precision(monkeypatch):
    def set_to(name):
        monkeypatch.setattr(root.common.engine, "precision_type", name)
    set_to("float32")
    return set_to


def toy_workflow(seed=5, batch=4, max_epochs=2, **arguments):
    prng.get().seed(seed)
    layers = zoo.conv_gqa_moe_decoder_layers(**dict(ARGUMENTS, **arguments))
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


# -- the short convolution ----------------------------------------------------


def conv_operands(seed, t, width=16, taps=3):
    rng = numpy.random.RandomState(seed)
    return (rng.randn(2, t, width).astype(numpy.float32),
            (rng.randn(width, 3 * width) * 0.3).astype(numpy.float32),
            rng.uniform(-0.6, 0.6, (width, taps)).astype(numpy.float32),
            (rng.randn(width, width) * 0.3).astype(numpy.float32))


def token_by_token(a, w_in, taps, w_out):
    """The equations one token and one tap at a time, float64."""
    a, w_in, taps, w_out = (numpy.asarray(x, numpy.float64)
                            for x in (a, w_in, taps, w_out))
    width, length = taps.shape
    out = numpy.zeros(a.shape[:2] + (w_out.shape[1],))
    for row in range(a.shape[0]):
        bcx = a[row] @ w_in
        u = bcx[:, :width] * bcx[:, 2 * width:]
        for t in range(a.shape[1]):
            c = numpy.zeros(width)
            for j in range(length):
                back = t - (length - 1) + j
                if back >= 0:
                    c += taps[:, j] * u[back]
            out[row, t] = (bcx[t, width:2 * width] * c) @ w_out
    return out


@pytest.mark.parametrize("t", [1, 2, 3, 7, T])
@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_against_a_token_by_token_loop(t, taps):
    """Sequences shorter than the filter too (T < 3): what lies before
    position 0 is nothing."""
    a, w_in, k, w_out = conv_operands(t + taps, t, taps=taps)
    with jax.default_matmul_precision("highest"):
        got = decoder.short_conv(*(jnp.asarray(x)
                                   for x in (a, w_in, k, w_out)))
    assert got.shape == a.shape and got.dtype == jnp.float32
    numpy.testing.assert_allclose(got, token_by_token(a, w_in, k, w_out),
                                  rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [2, T])
def test_short_conv_against_the_reference_forward_and_gradients(t):
    a, w_in, k, w_out = (jnp.asarray(x) for x in conv_operands(3, t))
    weigh = jnp.asarray(numpy.random.RandomState(4).randn(
        *a.shape).astype(numpy.float32))

    def program(a, w_in, k, w_out):
        return jnp.sum(decoder.short_conv(a, w_in, k, w_out) * weigh)

    def plain(a, w_in, k, w_out):
        w = {"w_in": w_in, "conv_k": k, "w_out": w_out}
        return jnp.sum(jnp.stack([reference.short_conv(row, w, "float32")
                                  for row in a]) * weigh)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(program, argnums=(0, 1, 2, 3))(
            a, w_in, k, w_out)
        want = jax.value_and_grad(plain, argnums=(0, 1, 2, 3))(
            a, w_in, k, w_out)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, g, g_ref in zip(("a", "w_in", "conv_k", "w_out"), got[1],
                              want[1]):
        assert numpy.abs(numpy.asarray(g_ref)).max() > 1e-3, name
        numpy.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-5,
                                      err_msg=name)


@pytest.mark.parametrize("changed", [0, 5, T - 1])
def test_short_conv_is_causal_and_short(changed):
    """Changing token t moves no output before t, and none after
    t + taps - 1: the filter looks taps - 1 tokens back and no
    further."""
    a, w_in, k, w_out = conv_operands(9, T)
    other = a.copy()
    other[:, changed] += 1.0
    with jax.default_matmul_precision("highest"):
        out, moved = (numpy.asarray(decoder.short_conv(
            jnp.asarray(x), jnp.asarray(w_in), jnp.asarray(k),
            jnp.asarray(w_out))) for x in (a, other))
    apart = numpy.abs(out - moved).max(axis=(0, 2))
    assert (apart[:changed] == 0).all()
    assert apart[changed] > 1e-3
    assert (apart[changed + 3:] == 0).all()


def test_bfloat16_operands_keep_float32_sums():
    a, w_in, k, w_out = conv_operands(2, T)
    low = decoder.short_conv(*(jnp.asarray(x, jnp.bfloat16)
                               for x in (a, w_in, k, w_out)))
    assert low.dtype == jnp.bfloat16
    want = token_by_token(a, w_in, k, w_out)
    off = numpy.linalg.norm(numpy.asarray(low, numpy.float64) - want) \
        / numpy.linalg.norm(want)
    assert 1e-4 < off < 3e-2, off


# -- the layers, each against the reference ----------------------------------


ROUTED = dict(experts=16, experts_held=4, first_expert=4, top_k=3,
              expert_width=32, shared_width=0, routed_scale=1.0,
              route_eps=1e-6)
MIXERS = {"conv": dict(conv_taps=3),
          "attention": dict(heads=8, kv_heads=2, head_width=8, rope=True,
                            out_gate=False, theta=100.0)}


def layer_on_seeded_pieces(dims, seed):
    rng = numpy.random.RandomState(seed)
    pieces, gain_pieces = reference.layer_pieces(dims, WIDTH)
    w = {name: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
         for name, shape in pieces}
    gains = {name: jnp.asarray(1 + 0.1 * rng.randn(*shape), jnp.float32)
             for name, shape in gain_pieces}
    h = jnp.asarray(rng.randn(2, T, WIDTH), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, load = reference.layer(h, dims, w, gains, eps=1e-5,
                                     operand="float32", **BLOCKS)
        got, aux = decoder.decoder_layer(
            h, reference._flat(w, pieces),
            reference._flat(gains, gain_pieces), compute_dtype="float32",
            eps=1e-5, **dims)
    return pieces, gain_pieces, got, want, aux, load


@pytest.mark.parametrize("mixer", ["conv", "attention"])
@pytest.mark.parametrize("body", ["dense", "routed"])
def test_a_layer_is_the_reference_layer(_precision, mixer, body):
    """A conv layer and an ungated rotary attention layer, dense and
    routed with NO shared expert, holding experts 4-7 of 16."""
    dims = dict(MIXERS[mixer], **({"ffn": 96} if body == "dense"
                                  else ROUTED))
    pieces, gain_pieces, got, want, aux, load = layer_on_seeded_pieces(
        dims, 3)
    names = [name for name, _ in pieces]
    assert "w_z" not in names and "s_gate" not in names
    assert ("conv_k" in names) == (mixer == "conv")
    assert decoder.layer_layout(WIDTH, **dims) == (pieces, gain_pieces)
    numpy.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if body == "routed":
        numpy.testing.assert_array_equal(aux["moe_load"], load)
        assert int(aux["moe_dropped"]) == 0
    else:
        assert aux == {} and load is None


def test_the_gate_and_the_shared_expert_are_parts():
    """``out_gate`` and ``shared_width`` add their pieces to the layout
    and leave with them; the accepted layouts are as they were."""
    base = dict(heads=8, kv_heads=2, head_width=8, experts=16,
                experts_held=4, expert_width=32)
    gated = [n for n, _ in decoder.layer_layout(
        WIDTH, shared_width=32, **base)[0]]
    assert gated == ["w_q", "w_k", "w_v", "w_z", "w_o", "w_router",
                     "e_gate", "e_up", "e_down", "s_gate", "s_up",
                     "s_down"]
    bare = [n for n, _ in decoder.layer_layout(
        WIDTH, shared_width=0, out_gate=False, **base)[0]]
    assert bare == [n for n in gated if n != "w_z"
                    and not n.startswith("s_")]
    assert "w_out" in decoder.DecoderLayer.RESIDUAL_WRITERS
    assert {"out_gate", "conv_taps"} <= set(decoder.DecoderLayer.DIMS)


def test_the_four_shares_add_up_to_the_uncut_layer(_precision):
    """The configuration's deployment at toy width: four ranks of 8
    experts each (0-7, 8-15, 16-23, 24-31 of 32, top 4, no shared
    expert).  What the ranks' routed layers give adds up to the uncut
    reference's output for the whole layer, every assignment is some
    rank's, and nothing is counted once beside them: there is no shared
    expert and no norm after the sub-layer."""
    rng = numpy.random.RandomState(11)
    dims = dict(MIXERS["conv"], experts=32, top_k=4, expert_width=32,
                shared_width=0, routed_scale=1.0, route_eps=1e-6)
    whole = dict(dims, experts_held=32, first_expert=0)
    pieces, gain_pieces = reference.layer_pieces(whole, WIDTH)
    full = {name: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
            for name, shape in pieces}
    gains = {name: jnp.asarray(1 + 0.1 * rng.randn(*shape), jnp.float32)
             for name, shape in gain_pieces}
    gains["router_bias"] = jnp.asarray(0.05 * rng.randn(32), jnp.float32)
    h = jnp.asarray(rng.randn(2, T, WIDTH), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, load = reference.layer(h, whole, full, gains, eps=1e-5,
                                      operand="float32", **BLOCKS)
        # what every rank computes alike: the stream after the mixer
        mixed = jnp.stack([row + reference.short_conv(reference.rms_norm(
            row, gains["conv_gain"], 1e-5), full, "float32") for row in h])
        total, seen = numpy.zeros(h.shape, numpy.float32), 0
        for rank in range(4):
            held = slice(8 * rank, 8 * rank + 8)
            share = dict(dims, experts_held=8, first_expert=8 * rank)
            w = dict(full, **{name: full[name][held]
                              for name in ("e_gate", "e_up", "e_down")})
            names, _ = reference.layer_pieces(share, WIDTH)
            out, aux = decoder.decoder_layer(
                h, reference._flat(w, names),
                reference._flat(gains, gain_pieces),
                compute_dtype="float32", eps=1e-5, **share)
            assert int(aux["moe_dropped"]) == 0
            numpy.testing.assert_array_equal(aux["moe_load"], load[held])
            seen += int(aux["moe_assignments"])
            total += numpy.asarray(out - mixed)
    assert seen == 2 * T * 4  # every assignment is some rank's
    numpy.testing.assert_allclose(numpy.asarray(mixed) + total, uncut,
                                  atol=5e-6)
    assert numpy.abs(total).max() > 1e-3


# -- the whole model ---------------------------------------------------------


def test_program_against_reference_logits_loss_gradients_and_a_step(
        _precision):
    sw, layers, plans, state, x, y = program_and_batch()
    assert [bool(spec.get("conv_taps")) for spec in layers[1:-1]] == [
        True, False, True]
    assert layers[2]["rope"] and not layers[2]["out_gate"]
    assert layers[2]["shared_width"] == 0 and layers[-1]["tied_to"] == 0
    params = weights_and_gains(state)
    assert params[-1]["weights"] is None
    with jax.default_matmul_precision("highest"):
        got = numpy.asarray(jax.jit(build_forward(plans))(params, x))
    want, loads = reference.forward(layers, params, x, with_load=True,
                                    **BLOCKS)
    assert got.shape == (4, T, VOCAB)
    numpy.testing.assert_allclose(got, want, atol=5e-6)
    # the filter starts within 1 / sqrt(taps), the writers at their std
    w = reference.split(numpy.asarray(state[1]["weights"]),
                        reference.layer_pieces(layers[1], WIDTH)[0])
    assert 0.5 < float(jnp.abs(w["conv_k"]).max()) <= 3 ** -0.5
    assert float(jnp.std(w["w_out"])) == pytest.approx(0.01, rel=0.1)
    assert float(jnp.std(w["w_in"])) == pytest.approx(0.02, rel=0.1)

    step = build_train_step(plans, donate=False)
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, x, y, numpy.float32(4),
                                  step_count=numpy.int32(1))
    ref_loss, ref_grads = reference.loss_and_gradients(
        layers, params, x, y, **BLOCKS)
    assert float(metrics["loss"]) == pytest.approx(float(ref_loss),
                                                   rel=1e-6)
    numpy.testing.assert_array_equal(metrics["moe_load"],
                                     numpy.stack(loads))
    assert metrics["moe_dropped"].tolist() == [0, 0]
    assert ref_grads[-1]["weights"] is None
    hyper = dict(lr=3e-3, beta1=0.9, beta2=0.95, eps=1e-8)
    for i, (old, new) in enumerate(zip(state, new_state)):
        for key, decay in (("weights", 0.1), ("bias", 0.0)):
            if old[key] is None:
                assert new[key] is None and new["accum_" + key] is None
                continue
            g_ref = numpy.asarray(ref_grads[i][key]).reshape(
                old[key].shape)
            g = numpy.asarray(new["accum_" + key]) / 0.1
            scale = max(numpy.abs(g_ref).max(), 1e-12)
            assert numpy.abs(g - g_ref).max() < 2e-4 * scale, (i, key)
            p, m, v = reference.adamw_step(
                numpy.asarray(old[key]), g_ref, 0.0, 0.0, 1, decay=decay,
                **hyper)
            moved = numpy.abs(g_ref) > 1e-3 * scale  # sign(g) is settled
            numpy.testing.assert_allclose(
                numpy.asarray(new[key])[moved], p[moved], atol=1e-6)
    # the head's gain is updated though the head owns no matrix
    assert numpy.abs(numpy.asarray(new_state[-1]["bias"])
                     - numpy.asarray(state[-1]["bias"])).max() > 1e-4


def test_the_factory_refuses_an_unknown_kind_of_layer():
    with pytest.raises(ValueError, match='"conv" or "attention".*window'):
        zoo.conv_gqa_moe_decoder_layers(**dict(
            ARGUMENTS, layer_types=["conv", "window"]))


def test_bfloat16_operands_float32_state_trains(_precision):
    _precision("bfloat16")
    sw, _ = toy_workflow(max_epochs=3)
    assert all(f.weights.dtype == numpy.float32 for f in sw.forwards
               if f.weights)
    before = {name: registry.counter(name).value for name in (
        "moe.dropped_assignments", "moe.assignments", "train.tokens")}
    sw.run()
    trainer = sw.fused_trainer
    assert float(trainer.last_loss) < 4.4 < numpy.log(VOCAB)
    assert int(trainer.skip_count) == 0
    assert registry.counter("moe.dropped_assignments").value == \
        before["moe.dropped_assignments"]
    assert registry.counter("moe.assignments").value > \
        before["moe.assignments"]
    assert registry.counter("train.tokens").value > before["train.tokens"]
    # the 4 held of 16 in each of the two routed layers
    counters = registry.snapshot()["counters"]
    assert {"moe.load.l%d.e%d" % (layer, expert) for layer in (0, 1)
            for expert in range(4)} <= set(counters)


# -- the tied head -----------------------------------------------------------


def test_the_tables_gradient_is_the_sum_of_both_uses(_precision):
    """One array read twice: the gradient the step hands AdamW for the
    table is what an untied model gives for its embedding PLUS (the
    transpose of) what it gives for its head, on the same numbers."""
    sw, layers, plans, state, x, y = program_and_batch()
    params = weights_and_gains(state)
    untied = [compiler.LayerPlan(p.forward_cls, p.solver, p.hyper,
                                 p.include_bias, dict(p.static))
              for p in plans]
    del untied[-1].static["tied_to"]
    apart = [dict(p) for p in params]
    apart[-1]["weights"] = params[0]["weights"].T

    def loss(plans_):
        return lambda p: reference.loss(build_forward(plans_)(p, x), y)

    with jax.default_matmul_precision("highest"):
        tied = jax.grad(loss(plans))(params)
        two = jax.grad(loss(untied))(apart)
    assert tied[-1]["weights"] is None
    both = numpy.asarray(two[0]["weights"] + two[-1]["weights"].T)
    assert numpy.abs(numpy.asarray(two[-1]["weights"])).max() > 1e-4
    numpy.testing.assert_allclose(tied[0]["weights"], both, rtol=1e-4,
                                  atol=1e-7)
    numpy.testing.assert_allclose(tied[-1]["bias"], two[-1]["bias"],
                                  rtol=1e-4, atol=1e-7)


def tables_of(sw):
    """The (vocab, width)-sized arrays a workflow's units hold."""
    arrays = []
    for unit in list(sw.forwards) + [gd for gd in sw.gds if gd is not None]:
        for name in ("weights", "accum_weights", "accum2_weights"):
            array = getattr(unit, name, None)
            if array is not None and array and array.size == VOCAB * WIDTH:
                arrays.append((type(unit).__name__, name))
    return arrays


def test_state_and_snapshot_hold_one_table_and_a_restore_reproduces_the_loss(
        _precision):
    sw, layers = toy_workflow(max_epochs=2)
    head = sw.forwards[-1]
    assert head.tied_to == 0 and not head.weights and head.bias
    assert [s["weights"] is None for s in extract_state(sw)] == [
        False, False, False, False, True]
    # one table, with its two moments; the gd units link the forwards'
    assert sorted(set(tables_of(sw))) == [
        ("DecoderEmbedding", "weights"),
        ("GDDecoderEmbedding", "accum2_weights"),
        ("GDDecoderEmbedding", "accum_weights"),
        ("GDDecoderEmbedding", "weights")]
    sw.run()
    trainer = sw.fused_trainer
    trainer.sync()
    blob = pickle.dumps(sw)
    untied, _ = toy_workflow(max_epochs=2, tied_head=False)
    assert len(pickle.dumps(untied)) - len(blob) > 0.9 * 3 * 4 * VOCAB * WIDTH

    def next_loss(workflow):
        """The loss of the next train step from the workflow's state."""
        plans, state = workflow_plan(workflow), extract_state(workflow)
        rows = numpy.array(workflow.loader.original_data.mem[8:12])
        _, metrics = build_train_step(plans, donate=False)(
            state, rows[:, :-1], rows[:, 1:], numpy.float32(4),
            step_count=numpy.int32(9))
        return float(metrics["loss"])

    want = next_loss(sw)
    restored = pickle.loads(blob)
    restored.workflow = DummyLauncher()
    restored.restored_from_snapshot_ = True
    restored.initialize(device=Device(backend="cpu"))
    assert not restored.forwards[-1].weights
    assert sorted(set(tables_of(restored))) == sorted(set(tables_of(sw)))
    assert next_loss(restored) == want < numpy.log(VOCAB)


def test_a_tied_head_runs_inside_the_fused_step_only(_precision):
    prng.get().seed(5)
    sw = StandardWorkflow(
        DummyLauncher(),
        layers=zoo.conv_gqa_moe_decoder_layers(**ARGUMENTS),
        loader_factory=lambda w: ToyTokens(w, minibatch_size=4),
        decision_config=dict(max_epochs=1))
    sw.initialize(device=Device(backend="cpu"))  # not fused
    with pytest.raises(RuntimeError, match="tied to layer 0's table"):
        sw.forwards[-1].run()


@pytest.mark.parametrize("builder", ["tensor", "pipeline", "zero"])
def test_builders_that_cannot_honour_the_tie_refuse_it(_precision,
                                                       builder):
    """The model-parallel builders walk a slice of the layers (or swap a
    layer's apply), and ZeRO-1 updates the layers that own a matrix:
    each says so rather than run the head untied or leave its gain
    out."""
    from veles_tpu.parallel import pipeline, tensor
    from veles_tpu.parallel.mesh import auto_mesh
    sw, layers, plans, state, x, y = program_and_batch()
    assert compiler.tied_plans(plans) == {4: 0}
    with pytest.raises(ValueError, match="cannot honour tied parameters "
                       r"\(layer 4 reads the weights of layer 0\)"):
        if builder == "tensor":
            tensor.build_tp_train_step(plans, mesh=auto_mesh("model"))
        elif builder == "pipeline":
            pipeline.build_pipeline_train_step(plans,
                                               mesh=auto_mesh("pipe"))
        else:
            build_train_step(plans, mesh=auto_mesh("data"), zero=1)
    # untied, nothing is refused on this account
    assert compiler.tied_plans(
        workflow_plan(toy_workflow(tied_head=False)[0])) == {}


#: sha256 of the lowered train step of ``tests/test_decoder_gqa.py``'s
#: toy (an UNTIED plan: window, window, full; gate, shared expert,
#: sandwich norms).  PR 35 wrote down the parent's
#: (``9a997158...c09c7``) to show that the walk's new branch, the solver
#: loop's new shape and the layer's new parts left an untied plan's
#: program text for text what it was.  PR 36 moved it: the toy has two
#: routed layers, whose stretch from the tokens to the sum over a
#: token's slots became ``decoder._expert_rows``' loops over the filled
#: rows (the parent's text is 332,306 characters, this one 370,872: a
#: loop body in each rule).  The programs without a routed layer did
#: not move: the MNIST MLP's lowered step has the parent's sha256
#: (``PERF.md`` section 6).  PR 38 moved it again: each piece of a
#: layer's packed vectors passes an ``optimization_barrier`` before its
#: reshape and cast (``decoder._unpacker``; 374,409 characters); the
#: AlexNet and MLP steps kept the parent's sha256
UNTIED_STEP_DIGEST = (
    "8b7a2eddb67dd5bbc7c0d082ac09063ef3a47a1992add1485c3d1b07479d1440")


def test_an_untied_plans_program_text_is_unchanged(_precision):
    from tests import test_decoder_gqa as accepted
    sw, layers, plans, state, x, y = accepted.program_and_batch()
    assert compiler.tied_plans(plans) == {}
    text = jax.jit(compiler._build_step_fn(plans, "softmax")).lower(
        state, x, y, numpy.float32(4), None,
        step_count=numpy.int32(1)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == UNTIED_STEP_DIGEST


# -- scopes, counters, what the backward keeps --------------------------------


def test_the_short_conv_scope_rides_in_the_programs_metadata(_precision):
    """``op_name`` of the compiled step's instructions: a conv layer's
    mixer ops, forward and transposed, carry ``short_conv`` inside the
    layer's scope, where an attention layer's carry ``attention``."""
    sw, layers, plans, state, x, y = program_and_batch()
    text = jax.jit(compiler._build_step_fn(plans, "softmax")).lower(
        state, x, y, numpy.float32(4), None,
        step_count=numpy.int32(1)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))

    def scoped(layer, scope):
        return any(re.search(r"[/(]l%d_DecoderLayer\)*/%s/" % (layer, scope),
                             name) for name in op_names)

    for layer, scope in ((1, "short_conv"), (2, "attention"),
                         (3, "short_conv"), (2, "router"),
                         (3, "routed_experts"), (1, "dense_ffn")):
        assert scoped(layer, scope), (layer, scope)
    for layer, scope in ((1, "attention"), (2, "short_conv"),
                         (2, "shared_experts"), (3, "shared_experts")):
        assert not scoped(layer, scope), (layer, scope)
    assert any("transpose(jvp(l3_DecoderLayer))/short_conv/" in name
               for name in op_names)


def test_the_recomputed_backward_keeps_one_attention_layers_results(
        _precision, monkeypatch):
    """Three kinds of layer in one step: the conv layers name nothing
    and are recomputed whole, the attention layer keeps what its kernel
    named — ``KEPT_NAMES`` and the decision are as they were, the flash
    forward runs once, and ``step.kept_residual_bytes`` reads ONE
    layer's output and two row statistics."""
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
        assert calls[attention.DQ_KERNEL_NAME] == 1
        assert calls[attention.DKV_KERNEL_NAME] == 1 and len(calls) == 3
        return calls[attention.FWD_KERNEL_NAME]

    assert (forwards(False), forwards(True), forwards(KEPT_NAMES)) == (
        1, 2, 1)

    class Told(object):
        def __init__(self, limit):
            self.stats = {"bytes_limit": limit, "bytes_in_use": 0}

        def memory_stats(self):
            return self.stats

    seen = []
    monkeypatch.setattr(trainer, "info",
                        lambda fmt, *args: seen.append(fmt % args))
    # 1 layer of 4 rows x 8 heads x 32 tokens x (8 wide + 2) float32
    named = 4 * 8 * T * (8 + 2) * 4
    held = sum(a.nbytes for s in state
               for a in (s["weights"], s["bias"]) if a is not None)
    limit = int((held + named + 4096) / fused.REMAT_ABOVE) + 1
    monkeypatch.setattr(jax, "local_devices", lambda *a: [Told(limit)])
    assert trainer._backward_should_recompute(plans) == fused.kept_names()
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
