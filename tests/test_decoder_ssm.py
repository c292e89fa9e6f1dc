"""The decoder whose layers are each one part — a state-space scan,
grouped-query attention with no norm of q or k and no position signal,
or routed relu² experts beside a shared one — at toy width on the CPU,
seeded weights: the chunked scan against the token-by-token recurrence
of the benchmark's plain reference
(``benchmark/references/ssm_moe_decoder.py``), forward and gradients by
every operand, at chunks of 4, 8 and 16 with sequences a whole number of
chunks and not; the mixer with its filter, skip, gate and grouped norm;
one layer of each kind; the ``MEMEM*EME`` model through the fused
trainer on logits, loss, every gradient and one AdamW step; the shares
of an expert-parallel deployment, the shared expert counted once, add up
to the uncut layer; the parts' layouts; the scopes and gauges."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import ssm_moe_decoder as reference  # noqa: E402

from tests.test_decoder import ToyTokens, T, VOCAB  # noqa: E402
from veles_tpu import compiler, prng  # noqa: E402
from veles_tpu.backends import Device  # noqa: E402
from veles_tpu.compiler import (  # noqa: E402
    build_forward, build_train_step, extract_state, workflow_plan)
from veles_tpu.config import root  # noqa: E402
from veles_tpu.dummy import DummyLauncher  # noqa: E402
from veles_tpu.models import decoder, zoo  # noqa: E402
from veles_tpu.models.nn_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.observe.metrics import registry  # noqa: E402

WIDTH = 64
PATTERN = "MEMEM*EME"
KINDS = {"M": "ssm", "E": "routed", "*": "attention"}
ARGUMENTS = dict(
    vocab=VOCAB, width=WIDTH, layer_types=[KINDS[k] for k in PATTERN],
    heads=8, kv_heads=2, head_width=8, ssm_heads=4, ssm_head_width=16,
    ssm_groups=2, ssm_state=8, ssm_chunk=8, conv_taps=4, experts=16,
    experts_held=4, first_expert=4, top_k=3, expert_width=32,
    shared_width=48, routed_scale=2.5, eps=1e-5, lr=3e-3,
    out_init_std=0.01)
SSM = dict(ssm_heads=4, ssm_head_width=16, ssm_groups=2, ssm_state=8,
           ssm_chunk=8, conv_taps=4)
ATTENTION = dict(heads=8, kv_heads=2, head_width=8, rope=False,
                 out_gate=False, qk_norm=False)
ROUTED = dict(experts=16, experts_held=4, first_expert=4, top_k=3,
              expert_width=32, shared_width=48, routed_scale=2.5,
              expert_act="relu2")
BLOCKS = dict(query_block=8, token_block=16, scan_block=8)


@pytest.fixture
def _precision(monkeypatch):
    def set_to(name):
        monkeypatch.setattr(root.common.engine, "precision_type", name)
    set_to("float32")
    return set_to


def toy_workflow(seed=5, batch=4, max_epochs=2, **arguments):
    prng.get().seed(seed)
    layers = zoo.hybrid_moe_decoder_layers(**dict(ARGUMENTS, **arguments))
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


# -- the chunked scan ---------------------------------------------------------


def scan_operands(seed, t, heads=4, width=3, groups=2, state=5):
    rng = numpy.random.RandomState(seed)
    return (rng.randn(t, heads, width).astype(numpy.float32),
            numpy.log1p(numpy.exp(rng.randn(t, heads))).astype(
                numpy.float32),
            rng.uniform(0.0, 1.5, heads).astype(numpy.float32),
            rng.randn(t, groups, state).astype(numpy.float32),
            rng.randn(t, groups, state).astype(numpy.float32))


@pytest.mark.parametrize("chunk", [4, 8, 16])
@pytest.mark.parametrize("t", [32, 37])
def test_the_chunked_scan_is_the_recurrence(chunk, t):
    """Forward and the gradients by x, dt, log(-A), B and C: the
    program's chunked form against the reference's one step a token,
    with T a whole number of chunks (32) and not (37: the end padded)."""
    x, dt, a_log, b, c = scan_operands(1, t)
    weigh = jnp.asarray(numpy.random.RandomState(2).randn(t, 4, 3),
                        jnp.float32)

    def program(x, dt, a_log, b, c):
        return jnp.sum(decoder.ssd_scan(
            x[None], dt[None], -jnp.exp(a_log), b[None], c[None],
            chunk)[0] * weigh)

    def plain(x, dt, a_log, b, c):
        return jnp.sum(reference.recurrence(
            x, dt, -jnp.exp(a_log), b, c, "float32", 8) * weigh)

    with jax.default_matmul_precision("highest"):
        got = decoder.ssd_scan(x[None], dt[None], -numpy.exp(a_log),
                               b[None], c[None], chunk)[0]
        want = reference.recurrence(x, dt, -numpy.exp(a_log), b, c,
                                    "float32", 8)
        numpy.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)
        g_got = jax.grad(program, argnums=range(5))(x, dt, a_log, b, c)
        g_want = jax.grad(plain, argnums=range(5))(x, dt, a_log, b, c)
    for name, g, w in zip("x dt a_log b c".split(), g_got, g_want):
        scale = float(numpy.abs(w).max())
        assert scale > 1e-3, name
        numpy.testing.assert_allclose(g, w, atol=2e-5 * max(scale, 1),
                                      err_msg=name)


def test_the_scan_is_causal_and_its_chunks_carry_the_state():
    """A later token moves no earlier output; an early token moves the
    outputs of every chunk after its own (the recurrence carries it),
    and a scan whose chunks start from zero is another result."""
    x, dt, a_log, b, c = scan_operands(3, 37)
    a = -numpy.exp(a_log) * 0.05  # slow decays: the state lives long

    def scan(x):
        return numpy.asarray(decoder.ssd_scan(
            x[None], dt[None], a, b[None], c[None], 8)[0])

    with jax.default_matmul_precision("highest"):
        base = scan(x)
        later = x.copy()
        later[20] += 1.0
        moved = numpy.abs(scan(later) - base).max(axis=(1, 2))
        assert moved[:20].max() == 0 and moved[20] > 0
        early = x.copy()
        early[1] += 1.0
        moved = numpy.abs(scan(early) - base).max(axis=(1, 2))
        assert moved[8:].min() > 1e-4  # every later chunk

    def reset(decay, ends):
        return jnp.zeros_like(ends)

    with jax.default_matmul_precision("highest"):
        try:
            carried, decoder._carried = decoder._carried, reset
            apart = scan(x)
        finally:
            decoder._carried = carried
    assert numpy.abs(apart[:8] - base[:8]).max() < 1e-5
    assert numpy.abs(apart[8:] - base[8:]).max() > 1e-2


def test_the_scan_keeps_float32_sums_from_bfloat16_operands():
    x, dt, a_log, b, c = scan_operands(4, 64, heads=4, width=16,
                                       groups=2, state=8)
    with jax.default_matmul_precision("highest"):
        want = reference.recurrence(x, dt, -numpy.exp(a_log), b, c,
                                    "float32", 16)
        low = decoder.ssd_scan(
            jnp.asarray(x, jnp.bfloat16)[None], dt[None],
            -numpy.exp(a_log), jnp.asarray(b, jnp.bfloat16)[None],
            jnp.asarray(c, jnp.bfloat16)[None], 16)[0]
    assert low.dtype == jnp.float32
    off = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 1e-4 < off < 2e-2


def sequential_carry(log_decay, ends):
    """The states the chunks start from, one chunk a step:
    ``S_0 = 0, S_c = exp(log_decay_{c-1}) S_{c-1} + ends_{c-1}``."""
    def step(state, chunk):
        decay, end = chunk
        return jnp.exp(decay)[..., None, None] * state + end, state

    return jax.lax.scan(step, jnp.zeros_like(ends[0]), (log_decay, ends))[1]


@pytest.mark.parametrize("n, low, high, block", [
    (128, -0.05, 0.0, None),   # slow: a state lives across every chunk
    (128, -3.0, -0.5, None),   # fast
    (128, -205.0, -150.0, None),  # every chunk's decay underflows to 0
    (37, -0.2, 0.0, 4),        # blocks of 4, the last one short
    (37, -205.0, -150.0, 4),
])
def test_the_chunks_pass_their_states_as_the_recurrence_does(
        monkeypatch, n, low, high, block):
    """``_carried`` against the recurrence one chunk a step, outputs and
    gradients by the ends and by the log decays, at the precision a
    product has by default (no ``highest`` context here: the matrix
    product asks for float32 itself); where the decays underflow, every
    number finite; with ``CARRY_BLOCK`` smaller than the chunks, the
    blocks passing their states by the recurrence."""
    if block:
        monkeypatch.setattr(decoder, "CARRY_BLOCK", block)
    rng = numpy.random.RandomState(n)
    log_decay = jnp.asarray(rng.uniform(low, high, (n, 2, 3)), jnp.float32)
    ends = jnp.asarray(rng.randn(n, 2, 3, 4, 5), jnp.float32)
    weigh = jnp.asarray(rng.randn(n, 2, 3, 4, 5), jnp.float32)

    def weighed(carry):
        return lambda lam, e: jnp.sum(carry(lam, e) * weigh)

    got = decoder._carried(log_decay, ends)
    want = sequential_carry(log_decay, ends)
    scale = float(jnp.abs(want).max())
    numpy.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale)
    g_got = jax.grad(weighed(decoder._carried), (0, 1))(log_decay, ends)
    g_want = jax.grad(weighed(sequential_carry), (0, 1))(log_decay, ends)
    for name, g, w in zip(("log_decay", "ends"), g_got, g_want):
        assert bool(jnp.isfinite(g).all()), name
        numpy.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=name)


def test_the_scan_of_128_chunks_holds_no_loop_over_them():
    """``ssd_scan`` over 128 chunks, forward and backward: the only loop
    is the map over the groups of heads, and the chunk states pass in a
    product at float32 precision."""
    x, dt, a_log, b, c = scan_operands(6, 256, heads=4, width=3, groups=2,
                                       state=5)

    def scan(x, dt, b, c):
        return jnp.sum(decoder.ssd_scan(x[None], dt[None],
                                        -jnp.exp(a_log), b[None], c[None],
                                        2))

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    for f in (scan, jax.grad(scan, argnums=(0, 1, 2, 3))):
        found = list(equations(jax.make_jaxpr(f)(x, dt, b, c).jaxpr))
        loops = {e.params["length"] for e in found
                 if e.primitive.name == "scan"}
        assert loops == {2}, loops  # the 2 groups
        assert not any(e.primitive.name == "while" for e in found)
        passing = [e for e in found if e.primitive.name == "dot_general"
                   and e.params["precision"] is not None]
        assert passing and all(
            e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
            for e in passing)


# -- the layers ---------------------------------------------------------------


def seeded_pieces(dims, seed):
    rng = numpy.random.RandomState(seed)
    pieces, gain_pieces = reference.layer_pieces(dims, WIDTH)
    w = {name: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
         for name, shape in pieces}
    gains = {name: jnp.asarray(1 + 0.1 * rng.randn(*shape), jnp.float32)
             for name, shape in gain_pieces}
    if "a_log" in gains:
        gains["a_log"] = jnp.asarray(rng.uniform(0, 2.7, dims["ssm_heads"]),
                                     jnp.float32)
        gains["dt_bias"] = jnp.asarray(rng.uniform(-4, 0, dims["ssm_heads"]),
                                       jnp.float32)
    if "router_bias" in gains:
        gains["router_bias"] = jnp.asarray(
            0.05 * rng.randn(dims["experts"]), jnp.float32)
    return pieces, gain_pieces, w, gains


def reference_layer(h, dims, w, gains):
    outs = [reference.sequence_layer(row, dims, w, gains, 1e-5, "float32",
                                     **BLOCKS) for row in h]
    return jnp.stack([o for o, _ in outs]), sum(load for _, load in outs)


@pytest.mark.parametrize("kind", ["ssm", "attention", "routed"])
def test_a_layer_of_one_part_is_the_reference_layer(_precision, kind):
    """Each kind alone: its layout is the reference's, listed by hand;
    the layer's output and its gradients by the input and every piece
    are the reference's."""
    dims = {"ssm": SSM, "attention": ATTENTION, "routed": ROUTED}[kind]
    pieces, gain_pieces, w, gains = seeded_pieces(dims, 3)
    assert decoder.layer_layout(WIDTH, **dims) == (pieces, gain_pieces)
    h = jnp.asarray(numpy.random.RandomState(4).randn(2, T, WIDTH),
                    jnp.float32)
    weigh = jnp.asarray(numpy.random.RandomState(5).randn(2, T, WIDTH),
                        jnp.float32)

    def program(h, vec, gvec):
        out, aux = decoder.decoder_layer(h, vec, gvec,
                                         compute_dtype="float32", eps=1e-5,
                                         **dims)
        return jnp.sum(out * weigh), (out, aux)

    def plain(h, w, gains):
        out, load = reference_layer(h, dims, w, gains)
        return jnp.sum(out * weigh), (out, load)

    with jax.default_matmul_precision("highest"):
        (_, (got, aux)), g_got = jax.value_and_grad(
            program, argnums=(0, 1, 2), has_aux=True)(
                h, reference._flat(w, pieces),
                reference._flat(gains, gain_pieces))
        (_, (want, load)), g_want = jax.value_and_grad(
            plain, argnums=(0, 1, 2), has_aux=True)(h, w, gains)
    numpy.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    numpy.testing.assert_allclose(g_got[0], g_want[0], atol=1e-4)
    for got_vec, want_tree, names in ((g_got[1], g_want[1], pieces),
                                      (g_got[2], g_want[2], gain_pieces)):
        for name, part in reference.split(numpy.asarray(got_vec),
                                          names).items():
            if name == "router_bias":  # selection only: no gradient
                continue
            scale = float(jnp.abs(want_tree[name]).max())
            numpy.testing.assert_allclose(part, want_tree[name],
                                          atol=2e-4 * max(scale, 1e-3),
                                          err_msg=name)
    if kind == "routed":
        numpy.testing.assert_array_equal(aux["moe_load"], load)
        assert int(aux["moe_dropped"]) == 0
    else:
        assert aux == {}


def test_each_part_leaves_the_layout_with_its_dims():
    """A mixer alone has no feed-forward norm or pieces, a routed layer
    alone no mixer; relu² experts and shared expert have no gate; no
    q/k gains without ``qk_norm``; the accepted layouts are as they
    were."""
    names = {kind: [n for n, _ in sum(decoder.layer_layout(
        WIDTH, **dims), [])] for kind, dims in (
            ("ssm", SSM), ("attention", ATTENTION), ("routed", ROUTED))}
    assert names["ssm"] == ["w_in", "conv_k", "w_out", "ssm_gain",
                            "conv_b", "dt_bias", "a_log", "d_skip",
                            "ssm_norm_gain"]
    assert names["attention"] == ["w_q", "w_k", "w_v", "w_o", "attn_gain"]
    assert names["routed"] == ["w_router", "e_up", "e_down", "s_up",
                               "s_down", "ffn_gain", "router_bias"]
    gated = [n for n, _ in sum(decoder.layer_layout(
        WIDTH, **dict(ATTENTION, qk_norm=True, out_gate=True,
                      **dict(ROUTED, expert_act="silu"))), [])]
    assert gated == ["w_q", "w_k", "w_v", "w_z", "w_o", "w_router",
                     "e_gate", "e_up", "e_down", "s_gate", "s_up",
                     "s_down", "attn_gain", "q_gain", "k_gain", "ffn_gain",
                     "router_bias"]
    # the filter's channels are x, B and C: 64 + 2 x 2 x 8
    weights = dict(decoder.layer_layout(WIDTH, **SSM)[0])
    assert weights["w_in"] == (WIDTH, 64 + 96 + 4)
    assert weights["conv_k"] == (96, 4) and weights["w_out"] == (64, WIDTH)
    assert {"ssm_heads", "ssm_chunk", "expert_act", "qk_norm"} <= set(
        decoder.DecoderLayer.DIMS)
    assert {"ssm_mixer", "ssm_scan"} <= set(decoder.DecoderLayer.PART_SCOPES)


def test_the_shares_add_up_to_the_uncut_layer(_precision):
    """Four ranks of 4 experts each (0-3, ..., 12-15 of 16, top 3) and
    the shared expert whole on every rank: the ranks' routed parts, with
    the shared expert counted ONCE, add up to the uncut reference's
    layer with relu² experts, and every assignment is some rank's."""
    whole = dict(ROUTED, experts_held=16, first_expert=0)
    pieces, gain_pieces, full, gains = seeded_pieces(whole, 11)
    h = jnp.asarray(numpy.random.RandomState(12).randn(2, T, WIDTH),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, load = reference_layer(h, whole, full, gains)
        shared = jnp.stack([reference.relu2(reference.rms_norm(
            row, gains["ffn_gain"], 1e-5), full["s_up"], full["s_down"],
            "float32") for row in h])
        total, seen = numpy.zeros(h.shape, numpy.float32), 0
        for rank in range(4):
            held = slice(4 * rank, 4 * rank + 4)
            share = dict(ROUTED, experts_held=4, first_expert=4 * rank)
            w = dict(full, **{name: full[name][held]
                              for name in ("e_up", "e_down")})
            names, _ = reference.layer_pieces(share, WIDTH)
            out, aux = decoder.decoder_layer(
                h, reference._flat(w, names),
                reference._flat(gains, gain_pieces),
                compute_dtype="float32", eps=1e-5, **share)
            assert int(aux["moe_dropped"]) == 0
            numpy.testing.assert_array_equal(aux["moe_load"], load[held])
            seen += int(aux["moe_assignments"])
            total += numpy.asarray(out - h - shared)
    assert seen == 2 * T * 3
    numpy.testing.assert_allclose(numpy.asarray(h + shared) + total, uncut,
                                  atol=5e-6)
    assert numpy.abs(total).max() > 1e-3 and numpy.abs(shared).max() > 1e-3


# -- the whole model ---------------------------------------------------------


def test_the_pattern_through_the_fused_trainer_against_the_reference(
        _precision):
    """``MEMEM*EME`` through Launcher -> StandardWorkflow -> fuse: the
    program's logits, the step's loss, every gradient (from AdamW's first
    moment) and the step's change against the reference's."""
    sw, layers, plans, state, x, y = program_and_batch()
    assert sw.fused_trainer is not None
    kinds = ["ssm" if s.get("ssm_heads") else "attention"
             if s.get("kv_heads") else "routed" for s in layers[1:-1]]
    assert kinds == [KINDS[k] for k in PATTERN]
    assert layers[-1].get("tied_to") is None
    params = weights_and_gains(state)
    with jax.default_matmul_precision("highest"):
        got = numpy.asarray(jax.jit(build_forward(plans))(params, x))
    want, loads = reference.forward(layers, params, x, with_load=True,
                                    **BLOCKS)
    assert got.shape == (4, T, VOCAB) and len(loads) == 4
    numpy.testing.assert_allclose(got, want, atol=5e-6)

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
    hyper = dict(lr=3e-3, beta1=0.9, beta2=0.95, eps=1e-8)
    for i, (old, new) in enumerate(zip(state, new_state)):
        for key, decay in (("weights", 0.1), ("bias", 0.0)):
            if old[key] is None:
                continue
            g_ref = numpy.asarray(ref_grads[i][key]).reshape(
                old[key].shape)
            g = numpy.asarray(new["accum_" + key]) / 0.1
            scale = max(numpy.abs(g_ref).max(), 1e-12)
            assert numpy.abs(g - g_ref).max() < 2e-4 * scale, (i, key)
            p, _, _ = reference.adamw_step(
                numpy.asarray(old[key]), g_ref, 0.0, 0.0, 1, decay=decay,
                **hyper)
            moved = numpy.abs(g_ref) > 1e-3 * scale  # sign(g) is settled
            numpy.testing.assert_allclose(
                numpy.asarray(new[key])[moved], p[moved], atol=1e-6)
    # the scan's own pieces take their gradient in every state-space layer
    for i in (1, 3, 5, 8):
        gains = reference.split(numpy.asarray(ref_grads[i]["bias"]),
                                reference.layer_pieces(layers[i], WIDTH)[1])
        for name in ("a_log", "dt_bias", "d_skip", "conv_b",
                     "ssm_norm_gain"):
            assert float(jnp.abs(gains[name]).max()) > 0, (i, name)


def test_the_pieces_start_as_mamba_2_does(_precision):
    """Every weight matrix at its std (the residual writers at
    ``out_init_std``), the filter and its bias within 1 / sqrt(taps),
    A in [1, 16], dt in [0.001, 0.1] through the softplus, the skip and
    the gains 1, the correction bias 0."""
    sw, layers, plans, state, x, y = program_and_batch()
    w, g = (reference.split(numpy.asarray(state[1][key]), pieces)
            for key, pieces in zip(("weights", "bias"),
                                   reference.layer_pieces(layers[1],
                                                          WIDTH)))
    assert float(jnp.std(w["w_in"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(w["w_out"])) == pytest.approx(0.01, rel=0.1)
    for name in ("conv_k", "conv_b"):
        assert 0.3 < float(jnp.abs(g.get(name, w.get(name))).max()) <= 0.5
    a = numpy.exp(numpy.asarray(g["a_log"]))
    assert 1 <= a.min() and a.max() <= 16
    dt = numpy.log1p(numpy.exp(numpy.asarray(g["dt_bias"])))
    assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert (numpy.asarray(g["d_skip"]) == 1).all()
    assert (numpy.asarray(g["ssm_norm_gain"]) == 1).all()
    assert decoder.DecoderLayer.SSM_DT_INIT == (0.001, 0.1, 1e-4)
    assert decoder.DecoderLayer.SSM_A_INIT == (1.0, 16.0)
    routed = reference.split(numpy.asarray(state[2]["weights"]),
                             reference.layer_pieces(layers[2], WIDTH)[0])
    assert float(jnp.std(routed["e_down"])) == pytest.approx(0.01, rel=0.1)
    assert float(jnp.std(routed["s_up"])) == pytest.approx(0.02, rel=0.1)


def test_bfloat16_operands_float32_state_trains(_precision):
    _precision("bfloat16")
    sw, _ = toy_workflow(max_epochs=3)
    assert all(f.weights.dtype == numpy.float32 for f in sw.forwards
               if f.weights)
    before = {name: registry.counter(name).value for name in (
        "moe.dropped_assignments", "moe.assignments")}
    sw.run()
    trainer = sw.fused_trainer
    assert float(trainer.last_loss) < 4.4 < numpy.log(VOCAB)
    assert int(trainer.skip_count) == 0
    assert registry.counter("moe.dropped_assignments").value == \
        before["moe.dropped_assignments"]
    assert registry.counter("moe.assignments").value > \
        before["moe.assignments"]


def test_the_factory_refuses_an_unknown_kind_of_layer():
    with pytest.raises(ValueError, match='"ssm" or "attention" or "routed"'):
        zoo.hybrid_moe_decoder_layers(**dict(
            ARGUMENTS, layer_types=["ssm", "conv"]))


# -- scopes and gauges -------------------------------------------------------


def test_the_two_scopes_are_siblings_in_the_programs_metadata(_precision):
    """The scan's ops carry ``ssm_scan`` and the rest of the mixer's
    ``ssm_mixer``, inside the layer's scope and never one inside the
    other, forward and transposed; a routed layer carries neither."""
    sw, layers, plans, state, x, y = program_and_batch()
    text = jax.jit(compiler._build_step_fn(plans, "softmax")).lower(
        state, x, y, numpy.float32(4), None,
        step_count=numpy.int32(1)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))

    def scoped(layer, scope):
        return any(re.search(r"[/(]l%d_DecoderLayer\)*/%s/" % (layer, scope),
                             name) for name in op_names)

    for layer in (1, 3, 5, 8):
        assert scoped(layer, "ssm_mixer") and scoped(layer, "ssm_scan")
    for layer, scope in ((2, "router"), (2, "routed_experts"),
                         (2, "shared_experts"), (6, "attention")):
        assert scoped(layer, scope), (layer, scope)
    for layer in (2, 6):
        assert not scoped(layer, "ssm_mixer")
    assert not any("ssm_mixer/ssm_scan" in n or "ssm_scan/ssm_mixer" in n
                   for n in op_names)
    assert any("transpose(jvp(l1_DecoderLayer))/ssm_scan/" in name
               for name in op_names)


def test_the_gauges_count_chunks_and_the_states_kept(_precision,
                                                     monkeypatch):
    """``ssm.chunks``: 32 tokens in chunks of 8 are 4 (and 12 make 3);
    ``ssm.kept_state_bytes``: where the backward keeps every activation,
    the float32 states 4 rows x 4 chunks x 4 heads x 16 x 8 of each of
    the four layers; where the layers are recomputed, 0;
    ``ssm.carry_blocks``: the 3 chunks are one block, and 2 with blocks
    of 2."""
    sw, layers, plans, state, x, y = program_and_batch()
    trainer = sw.fused_trainer
    assert registry.peek("ssm.chunks").value == 4
    assert registry.peek("ssm.carry_blocks").value == 1
    assert registry.peek("ssm.kept_state_bytes").value == \
        4 * 4 * 4 * 4 * 16 * 8 * 4
    trainer._publish_scan_gauges(plans, True)
    assert registry.peek("ssm.kept_state_bytes").value == 0
    for plan in plans:
        if plan.static.get("ssm_chunk"):
            plan.static["ssm_chunk"] = 12
    trainer._publish_scan_gauges(plans, False)
    assert registry.peek("ssm.chunks").value == 3
    assert registry.peek("ssm.carry_blocks").value == 1
    monkeypatch.setattr(decoder, "CARRY_BLOCK", 2)
    trainer._publish_scan_gauges(plans, False)
    assert registry.peek("ssm.carry_blocks").value == 2
