"""The causal MLA + routed-experts decoder at toy width on the CPU,
seeded weights: the program against the benchmark's plain reference
(``benchmark/references/mla_moe_decoder.py``) on logits, loss, every
gradient and one AdamW step; the shares of an expert-parallel deployment
add up to the uncut layer; the routed layer counts what it drops; the
token loader, the per-token error rate, AdamW and the recompute decision
each on their own."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import mla_moe_decoder as reference  # noqa: E402

from veles_tpu import prng  # noqa: E402
from veles_tpu.backends import Device  # noqa: E402
from veles_tpu.compiler import (  # noqa: E402
    build_forward, build_train_step, extract_state, workflow_plan)
from veles_tpu.config import root  # noqa: E402
from veles_tpu.dummy import DummyLauncher  # noqa: E402
from veles_tpu.loader.tokens import TokenRowLoader  # noqa: E402
from veles_tpu.models import decoder, fused, zoo  # noqa: E402
from veles_tpu.models.nn_units import GradientDescentBase  # noqa: E402
from veles_tpu.models.nn_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.observe.metrics import registry  # noqa: E402
from veles_tpu.ops.attention import KEPT_NAMES  # noqa: E402

VOCAB, T = 96, 32
ARGUMENTS = dict(
    vocab=VOCAB, width=64, layers=3, heads=4, qk_nope=16, qk_rope=8,
    v_head=16, kv_rank=24, ffn=96, experts=16, experts_held=4,
    first_expert=4, top_k=3, expert_width=32, shared_width=64,
    routed_scale=2.448, lr=3e-3, router_bias_std=0.05)


class ToyTokens(TokenRowLoader):
    """72 seeded Zipf rows of T + 1 ids: 8 validation, 64 train."""

    def load_data(self):
        self.class_lengths[:] = (0, 8, 64)
        self._calc_class_end_offsets()
        self.create_originals((T + 1,), labels=False)
        rng = numpy.random.RandomState(3)
        p = 1.0 / numpy.arange(1, VOCAB + 1)
        self.original_data.mem[...] = rng.choice(
            VOCAB, size=(72, T + 1), p=p / p.sum())


@pytest.fixture
def _precision(monkeypatch):
    def set_to(name):
        monkeypatch.setattr(root.common.engine, "precision_type", name)
    set_to("float32")
    return set_to


def toy_workflow(seed=5, batch=4, fuse=True, max_epochs=2, **arguments):
    prng.get().seed(seed)
    layers = zoo.mla_moe_decoder_layers(**dict(ARGUMENTS, **arguments))
    sw = StandardWorkflow(
        DummyLauncher(), layers=layers,
        loader_factory=lambda w: ToyTokens(w, minibatch_size=batch),
        decision_config=dict(max_epochs=max_epochs))
    if fuse:
        sw.fuse()
    sw.initialize(device=Device(backend="cpu"))
    return sw, layers


def program_and_batch(**arguments):
    sw, layers = toy_workflow(**arguments)
    plans, state = workflow_plan(sw), extract_state(sw)
    rows = numpy.array(sw.loader.original_data.mem[:4])
    return sw, layers, plans, state, rows[:, :-1], rows[:, 1:]


def test_program_against_reference_logits_loss_gradients_and_a_step(
        _precision):
    sw, layers, plans, state, x, y = program_and_batch()
    params = [{"weights": s["weights"], "bias": s["bias"]} for s in state]
    with jax.default_matmul_precision("highest"):
        got = numpy.asarray(jax.jit(build_forward(plans))(params, x))
    want, loads = reference.forward(layers, params, x, query_block=8,
                                    token_block=48, with_load=True)
    assert got.shape == (4, T, VOCAB)
    numpy.testing.assert_allclose(got, want, atol=2e-6)
    # float32 state whatever the operands; packed one pair a layer
    assert all(s["weights"].dtype == jnp.float32 for s in state)
    assert state[2]["weights"].ndim == 1 and state[2]["bias"].ndim == 1

    step = build_train_step(plans, donate=False)
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, x, y, numpy.float32(4),
                                  step_count=numpy.int32(1))
    ref_loss, ref_grads = reference.loss_and_gradients(layers, params, x, y)
    assert float(metrics["loss"]) == pytest.approx(float(ref_loss),
                                                   rel=1e-6)
    # the routed layers' load is the reference's count, nothing dropped
    numpy.testing.assert_array_equal(metrics["moe_load"],
                                     numpy.stack(loads))
    assert metrics["moe_dropped"].tolist() == [0, 0]
    assert metrics["moe_assignments"].tolist() == [
        int(load.sum()) for load in loads]
    # every gradient, through one AdamW step of the reference's own:
    # step 1 moves each parameter by lr * (sign(g) + decay * p), so the
    # gradients are compared themselves too
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
    # the router's correction bias takes no gradient and does not move
    bias_layout = decoder.layer_layout(64, **{
        k: v for k, v in layers[2].items()
        if k in decoder.DecoderLayer.DIMS})[1]
    offset = sum(int(numpy.prod(s)) for n, s in bias_layout[:-1])
    assert bias_layout[-1][0] == "router_bias"
    numpy.testing.assert_array_equal(
        numpy.asarray(new_state[2]["bias"])[offset:],
        numpy.asarray(state[2]["bias"])[offset:])
    assert numpy.abs(numpy.asarray(state[2]["bias"])[offset:]).max() > 0


def test_the_control_runs_the_references_own_programs(_precision):
    """``lowered=False`` is the float32 reference through the programs
    compiled for the control's operand, their rounding switched off;
    switched on, an 8-bit float (each tensor scaled to the format's
    range) is a rounding away, not an underflow away."""
    sw, layers, plans, state, x, y = program_and_batch()
    params = [{"weights": s["weights"], "bias": s["bias"]} for s in state]
    how = dict(query_block=8, token_block=48)
    want = numpy.asarray(reference.forward(layers, params, x, **how))
    off = numpy.asarray(reference.forward(
        layers, params, x, operand="float8_e4m3fn", lowered=False, **how))
    numpy.testing.assert_allclose(off, want, rtol=1e-5, atol=1e-6)
    low = numpy.asarray(reference.forward(
        layers, params, x, operand="float8_e4m3fn", **how))
    apart = numpy.linalg.norm(low - want) / numpy.linalg.norm(want)
    assert 0.01 < apart < 0.5, apart
    half = numpy.asarray(reference.forward(
        layers, params, x, operand="bfloat16", **how))
    assert numpy.linalg.norm(half - want) < 0.2 * numpy.linalg.norm(
        low - want)


def test_the_shares_add_up_to_the_uncut_layer(_precision):
    """Eight ranks of two experts each: the routed parts the ranks give,
    with what every rank computes alike (attention, the shared expert)
    counted once, equal the uncut reference layer."""
    rng = numpy.random.RandomState(11)
    dims = dict(heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_rank=24,
                experts=16, top_k=3, expert_width=32, shared_width=64,
                routed_scale=2.448)
    width, ranks = 64, 8
    whole = dict(dims, experts_held=16, first_expert=0)
    pieces, gain_pieces = reference.layer_pieces(whole, width)
    full = {name: (rng.randn(*shape) * 0.05).astype(numpy.float32)
            for name, shape in pieces}
    gains = {name: (1 + 0.1 * rng.randn(*shape)).astype(numpy.float32)
             for name, shape in gain_pieces}
    gains["router_bias"] = (0.05 * rng.randn(16)).astype(numpy.float32)
    h = rng.randn(2, T, width).astype(numpy.float32)
    how = dict(eps=1e-6, operand="float32", query_block=8, token_block=64)
    uncut, load = reference.layer(jnp.asarray(h), whole, full, gains, **how)
    # what every rank computes alike: a rank that holds no expert
    alike, _ = reference.layer(
        jnp.asarray(h), dict(whole, experts_held=0), full, gains, **how)
    bias = numpy.concatenate([gains[n].ravel() for n, _ in gain_pieces])
    total, seen = numpy.zeros_like(h), 0
    for rank in range(ranks):
        held = slice(2 * rank, 2 * rank + 2)
        mine = dict(full, e_gate=full["e_gate"][held],
                    e_up=full["e_up"][held], e_down=full["e_down"][held])
        weights = numpy.concatenate([mine[n].ravel() for n, _ in pieces])
        with jax.default_matmul_precision("highest"):
            out, aux = decoder.decoder_layer(
                jnp.asarray(h), jnp.asarray(weights), jnp.asarray(bias),
                compute_dtype="float32", experts_held=2,
                first_expert=2 * rank, capacity=2 * T * 3, **dims)
        assert int(aux["moe_dropped"]) == 0
        numpy.testing.assert_array_equal(aux["moe_load"], load[held])
        seen += int(aux["moe_assignments"])
        total += numpy.asarray(out) - numpy.asarray(alike)
    assert seen == 2 * T * 3  # every assignment is some rank's
    numpy.testing.assert_allclose(total + numpy.asarray(alike), uncut,
                                  atol=5e-6)
    assert numpy.abs(numpy.asarray(uncut - alike)).max() > 1e-3


def test_a_full_buffer_drops_and_counts(_precision):
    rng = numpy.random.RandomState(2)
    m = jnp.asarray(rng.randn(40, 16).astype(numpy.float32))
    idx = jnp.asarray(rng.randint(0, 8, (40, 2)).astype(numpy.int32))
    gate = jnp.asarray(rng.rand(40, 2).astype(numpy.float32))
    experts = [jnp.asarray(rng.randn(3, *s).astype(numpy.float32) * 0.2)
               for s in ((16, 8), (16, 8), (8, 16))]
    args = (m, idx, gate) + tuple(experts)
    roomy, aux = decoder.routed_experts(*args, first_expert=2, capacity=80)
    held = int(((idx >= 2) & (idx < 5)).sum())
    assert (int(aux["moe_assignments"]), int(aux["moe_dropped"])) == (
        held, 0)
    assert int(aux["moe_load"].sum()) == held
    tight, aux = decoder.routed_experts(*args, first_expert=2,
                                        capacity=held - 5)
    assert int(aux["moe_dropped"]) == 5
    assert int(aux["moe_assignments"]) == held  # counted before the cut
    # the rows that fit are computed as before; five terms are missing
    assert numpy.abs(numpy.asarray(tight - roomy)).max() > 1e-4
    changed = (numpy.abs(numpy.asarray(tight - roomy)).max(axis=1)
               > 0).sum()
    assert 1 <= changed <= 5
    # gradients flow through dispatch and combine as gathers
    grads = jax.grad(lambda x, g: decoder.routed_experts(
        x, idx, g, *experts, first_expert=2, capacity=80)[0].sum(),
        argnums=(0, 1))(m, gate)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    not_held = ~(((idx >= 2) & (idx < 5)))
    assert float(jnp.abs(grads[1][not_held]).max()) == 0.0


def straight_line_routed(m, idx, weights, e_gate, e_up, e_down, *,
                         first_expert, capacity):
    """The routed layer as it stood before the chunked walk (PR 29's
    body, a stable sort for its counting sort and stock autodiff for its
    paired gathers; relu² experts where ``e_gate`` is None): every pass
    over all ``capacity`` rows, a token's rows, each rounded to ``m``'s
    dtype, summed by a float32 scatter-add."""
    from jax import lax
    n, k = idx.shape
    held = e_up.shape[0]
    local = idx - first_expert
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)
    load = jnp.bincount(key, length=held + 1)[:held]
    kept = jnp.sum(load)
    sizes = jnp.diff(jnp.minimum(jnp.cumsum(load), capacity), prepend=0)
    slot = jnp.argsort(key, stable=True)[:capacity]  # row -> assignment
    xs = m[slot // k]
    product = functools.partial(lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=jnp.float32)
    if e_gate is None:
        hidden = jnp.square(jax.nn.relu(product(xs, e_up)))
    else:
        hidden = jax.nn.silu(product(xs, e_gate)) * product(xs, e_up)
    y = product(hidden.astype(m.dtype), e_down)
    row_valid = jnp.arange(capacity) < jnp.minimum(kept, capacity)
    y = jnp.where(row_valid[:, None],
                  y * weights.reshape(-1)[slot][:, None], 0.0)
    out = jnp.zeros(m.shape, jnp.float32).at[slot // k].add(
        y.astype(m.dtype).astype(jnp.float32))
    return out.astype(m.dtype), {
        "moe_load": load, "moe_assignments": kept,
        "moe_dropped": jnp.maximum(kept - capacity, 0)}


#: tokens x 2 slots of the fills' toy: a buffer of one chunk and 80 rows
FILL_TOKENS = decoder.CHUNK // 2 + 40


@pytest.mark.parametrize("fill, capacity, gated, twice, dtype", [
    (0, None, True, False, "float32"), (1, None, True, False, "float32"),
    (decoder.CHUNK, None, True, False, "float32"),
    (decoder.CHUNK + 1, None, True, False, "float32"),
    (2 * FILL_TOKENS, None, True, False, "float32"),
    (decoder.CHUNK + 40, decoder.CHUNK + 16, True, False, "float32"),
    (2, None, True, True, "float32"),
    (decoder.CHUNK + 1, None, False, False, "float32"),
    (2 * FILL_TOKENS, None, True, False, "bfloat16")],
    ids=["nothing", "one_row", "one_chunk", "one_chunk_and_a_row",
         "every_row", "more_than_a_reduced_buffer",
         "a_token_twice_in_one_chunk", "relu2_experts",
         "every_row_bfloat16"])
def test_the_walk_over_filled_chunks_is_the_straight_line_body(
        _precision, fill, capacity, gated, twice, dtype):
    """``fill`` assignments to the 3 held experts of 8: output, every
    gradient and the counters are the whole-buffer body's, whatever
    share of the buffer's chunks the loops visit.  ``twice``: the
    assignments are one token's two slots, on two held experts, so one
    chunk's scatter-add meets that token twice; ``gated`` False: relu²
    experts, no ``e_gate``.  In bfloat16 the output is the reference's to
    the last bit: a token's two rows are each rounded to bfloat16 and
    added in float32 on both sides (two terms add the same in either
    order), so a rounding moved or left out fails here."""
    rng = numpy.random.RandomState(fill % 97)
    n, k, width, inner = FILL_TOKENS, 2, 16, 8
    if capacity is None:
        capacity = n * k
    m = jnp.asarray(rng.randn(n, width).astype(numpy.float32), dtype)
    gate = jnp.asarray(rng.rand(n, k).astype(numpy.float32))
    experts = [jnp.asarray(rng.randn(3, *s).astype(numpy.float32) * 0.2,
                           dtype)
               for s in ((width, inner), (width, inner), (inner, width))]
    if not gated:
        experts[0] = None
    idx = rng.choice([0, 1, 5, 6, 7], size=n * k)     # held: 2, 3, 4
    if twice:
        chosen = k * rng.randint(n) + numpy.arange(k)
        idx[chosen] = rng.permutation([2, 3, 4])[:k]
    else:
        chosen = rng.permutation(n * k)[:fill]
        idx[chosen] = rng.randint(2, 5, size=fill)
    idx = jnp.asarray(idx.reshape(n, k).astype(numpy.int32))
    cotangent = jnp.asarray(rng.randn(n, width).astype(numpy.float32))

    def both(layer):
        def loss(m, gate, *experts):
            out, aux = layer(m, idx, gate, *experts, first_expert=2,
                             capacity=capacity)
            return jnp.sum(out * cotangent), (out, aux)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                    m, gate, *experts)

    (_, (out, aux)), grads = both(decoder.routed_experts)
    (_, (want, want_aux)), want_grads = both(straight_line_routed)
    out, want = out.astype(jnp.float32), want.astype(jnp.float32)
    if dtype == "bfloat16":
        numpy.testing.assert_array_equal(out, want)
        # the reference's stock autodiff rounds its intermediate
        # products elsewhere: two ulps at the largest gradient
        tolerance = 2.0 ** -7
    else:
        numpy.testing.assert_allclose(out, want, atol=2e-6)
        tolerance = 2e-6
    for name, g, g_want in zip(("m", "weights", "e_gate", "e_up", "e_down"),
                               grads, want_grads):
        if g_want is None:                            # relu²: no e_gate
            assert g is None
            continue
        numpy.testing.assert_allclose(
            g.astype(jnp.float32), g_want.astype(jnp.float32),
            atol=tolerance * max(1.0, float(jnp.abs(g_want).max())),
            err_msg=name)
    for name, value in want_aux.items():
        numpy.testing.assert_array_equal(aux[name], value, err_msg=name)
    assert int(aux["moe_assignments"]) == fill
    assert int(aux["moe_dropped"]) == max(fill - capacity, 0)
    visited = -(-min(fill, capacity) // decoder.CHUNK) * decoder.CHUNK
    assert int(aux["moe_visited_rows"]) == visited
    assert fill == 0 or float(jnp.abs(want).max()) > 1e-3


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_routed_stretch_is_one_loop_over_chunks(_precision,
                                                    monkeypatch):
    """The toy's forward holds one ``while`` a routed layer and its
    gradient one more; nothing in the forward or the backward has the
    buffer's rows x D (rows are walked a chunk at a time and never
    stored whole), and each loop carries one (N, D) float32 sum that
    its chunks scatter-add their rows into."""
    _precision("bfloat16")
    monkeypatch.setattr(decoder, "CHUNK", 64)
    sw, layers, plans, state, x, y = program_and_batch()
    params = [{"weights": s["weights"], "bias": s["bias"]} for s in state]
    text = jax.jit(build_forward(plans)).lower(params, x).as_text()
    assert text.count("stablehlo.while") == 2     # the toy's routed layers
    capacity = 4 * T * 3                          # tokens x top_k (3 < 4)
    assert capacity % 64 == 0 and capacity != 4 * T * 4
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: build_forward(plans)(
        p, x).astype(jnp.float32).sum()))(params)
    equations = list(_equations(jaxpr.jaxpr))
    # the buffer's index vectors (a row's token, weight, position) are
    # all that has its rows
    whole = {(eqn.primitive.name, var.aval.shape) for eqn in equations
             for var in eqn.outvars
             if getattr(var.aval, "shape", ())[:1] == (capacity,)
             and numpy.prod(var.aval.shape[1:]) > 1}
    assert not whole, whole
    loops = [eqn for eqn in equations if eqn.primitive.name == "while"]
    assert len(loops) == 4                        # forward and backward
    def is_sum(var):                              # (N, D) float32
        return (getattr(var.aval, "shape", ()), var.aval.dtype) == (
            (4 * T, 64), jnp.float32)
    for loop in loops:
        assert sum(map(is_sum, loop.outvars)) == 1, loop.outvars
        assert "scatter-add" in {
            eqn.primitive.name
            for eqn in _equations(loop.params["body_jaxpr"].jaxpr)
            if any(map(is_sum, eqn.outvars))}
    chunked = {eqn.primitive.name for eqn in equations
               for var in eqn.outvars
               if getattr(var.aval, "shape", ())[:1] == (64,)}
    assert {"ragged_dot_general", "gather", "logistic"} <= chunked, chunked


def test_visited_rows_are_counted_by_whole_chunks(_precision, monkeypatch):
    """``moe.visited_rows`` reaches the registry with the layer's other
    counters: whole chunks, no fewer rows than were assigned and less
    than a chunk more a routed layer a step."""
    monkeypatch.setattr(decoder, "CHUNK", 32)
    sw, _ = toy_workflow(max_epochs=2)
    names = ("moe.visited_rows", "moe.assignments",
             "moe.dropped_assignments", "train.steps")
    before = {name: registry.counter(name).value for name in names}
    sw.run()
    sw.fused_trainer.publish_layer_counters()
    visited, assigned, dropped, steps = (
        registry.counter(name).value - before[name] for name in names)
    assert steps > 0 and dropped == 0
    assert visited % 32 == 0
    assert assigned <= visited < assigned + 32 * 2 * steps


def test_flash_path_matches_the_stock_path_in_the_layer(_precision):
    """``pallas_bwd`` on routes the layer's attention through the causal
    192/128-style flash kernels (interpret mode here): same output and
    gradients as the stock reference path."""
    sw, layers, plans, state, x, y = program_and_batch(layers=2)
    params = [{"weights": s["weights"], "bias": s["bias"]} for s in state]

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


def test_bfloat16_operands_float32_state_trains(_precision):
    _precision("bfloat16")
    sw, _ = toy_workflow(max_epochs=3)
    assert all(f.weights.dtype == numpy.float32 for f in sw.forwards)
    assert all(g.accum2_weights.dtype == numpy.float32 for g in sw.gds)
    assert sw.loader.minibatch_data.dtype == numpy.int32
    from veles_tpu.observe.metrics import registry
    before = {name: registry.counter(name).value for name in (
        "moe.dropped_assignments", "moe.assignments")}
    sw.run()
    trainer = sw.fused_trainer
    assert float(trainer.last_loss) < 4.2 < numpy.log(VOCAB)
    assert int(trainer.skip_count) == 0
    # errors are counted a token, and the rate is over tokens
    assert trainer.targets_per_sample == T
    assert 0 < sw.decision.epoch_metrics[2] < 100
    assert registry.counter("moe.dropped_assignments").value == \
        before["moe.dropped_assignments"]
    assert registry.counter("moe.assignments").value > \
        before["moe.assignments"]
    loads = [name for name in registry.snapshot()["counters"]
             if name.startswith("moe.load.l")]
    assert len(loads) >= 2 * 4  # 2 routed layers x 4 held experts


def test_rotary_turns_adjacent_pairs():
    x = numpy.random.RandomState(0).randn(2, 5, 3, 8).astype(numpy.float32)
    got = numpy.asarray(decoder.rotary(jnp.asarray(x), 100.0))
    for t in range(5):
        for i in range(4):
            angle = t * 100.0 ** (-2 * i / 8)
            a, b = x[:, t, :, 2 * i], x[:, t, :, 2 * i + 1]
            numpy.testing.assert_allclose(
                got[:, t, :, 2 * i],
                a * numpy.cos(angle) - b * numpy.sin(angle), atol=1e-5)
            numpy.testing.assert_allclose(
                got[:, t, :, 2 * i + 1],
                a * numpy.sin(angle) + b * numpy.cos(angle), atol=1e-5)


def test_adamw_against_the_written_formula():
    rng = numpy.random.RandomState(4)
    p, g = rng.randn(50).astype(numpy.float32), rng.randn(50).astype(
        numpy.float32)
    m, v = numpy.zeros(50, numpy.float32), numpy.zeros(50, numpy.float32)
    q = p.copy()
    mq, vq = m.copy(), v.copy()
    for step in (1, 2, 3):
        p, m, v = (numpy.asarray(a) for a in
                   GradientDescentBase.solver_update(
                       "adamw", jnp.asarray(p), jnp.asarray(g),
                       jnp.asarray(m), jnp.asarray(v), 1e-2, 0.9, 0.95,
                       1e-8, step=numpy.int32(step), decay=0.1))
        q, mq, vq = reference.adamw_step(q, g, mq, vq, step, lr=1e-2,
                                         beta1=0.9, beta2=0.95, eps=1e-8,
                                         decay=0.1)
        numpy.testing.assert_allclose(p, q, rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError, match="needs the step count"):
        GradientDescentBase.solver_update(
            "adamw", p, g, m, v, 1e-2, 0.9, 0.95, 1e-8)


def test_token_loader_serves_the_row_and_the_row_shifted(_precision):
    for fuse in (True, False):  # the device path, and the host's
        sw, _ = toy_workflow(fuse=False, batch=5)
        loader = sw.loader
        if not fuse:
            loader.on_device = False
        loader.run()
        for array in (loader.minibatch_data, loader.minibatch_labels,
                      loader.minibatch_indices):
            array.map_read()
        size = loader.minibatch_size
        idx = loader.minibatch_indices.mem[:size]
        rows = numpy.asarray(loader.original_data.mem)[idx]
        assert loader.minibatch_data.mem.shape == (5, T)
        numpy.testing.assert_array_equal(
            loader.minibatch_data.mem[:size], rows[:, :-1])
        numpy.testing.assert_array_equal(
            loader.minibatch_labels.mem[:size], rows[:, 1:])
        # 8 validation rows in fives: the second serve is short
        loader.run()
        for array in (loader.minibatch_data, loader.minibatch_labels):
            array.map_read()
        assert loader.minibatch_size == 3
        assert (loader.minibatch_labels.mem[3:] == -1).all()
        assert (loader.minibatch_data.mem[3:] == 0).all()
        assert (loader.minibatch_labels.mem[:3] >= 0).all()
    # the store pads 33 ids to 128 lanes, 4-byte rows one sublane deep
    assert loader.shape == (T + 1,) and loader.tokens == T
    assert not loader.has_labels


def test_recompute_is_decided_from_the_devices_memory(_precision,
                                                      monkeypatch):
    sw, _ = toy_workflow()
    trainer = sw.fused_trainer
    plans = workflow_plan(sw)
    # a device that does not say (the CPU): keep the activations
    assert trainer._backward_should_recompute(plans) is False

    class Told(object):
        def __init__(self, limit, used=0):
            self.stats = {"bytes_limit": limit, "bytes_in_use": used}

        def memory_stats(self):
            return self.stats

    seen = []
    monkeypatch.setattr(trainer, "info",
                        lambda fmt, *args: seen.append(fmt % args))
    monkeypatch.setattr(jax, "local_devices", lambda *a: [Told(1 << 40)])
    assert trainer._backward_should_recompute(plans) is False
    assert "activations are kept" in seen[-1]
    # the toy's backward would hold a few MB: a device of 1 MB recomputes
    monkeypatch.setattr(jax, "local_devices", lambda *a: [Told(1 << 20)])
    assert trainer._backward_should_recompute(plans) is True
    assert "recomputed in the backward" in seen[-1]
    # what is in use counts: a roomy device that is nearly full
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a: [Told(1 << 30, used=(1 << 30) - 4096)])
    assert trainer._backward_should_recompute(plans) is True
    assert 0 < fused.REMAT_ABOVE < 1
    # the CPU's layers run the stock attention, which names nothing: a
    # recomputed layer keeps nothing
    gauge = registry.peek("step.kept_residual_bytes")
    assert gauge.value == 0
    # through the flash kernels a layer names its attention's output and
    # two floats a row (ops/attention.KEPT_NAMES): 3 layers of 4 rows x
    # 4 heads x 32 tokens x (16 wide + 2) float32
    for plan in plans:
        if plan.forward_cls is decoder.DecoderLayer:
            plan.static["pallas_bwd"] = True
    named = 3 * 4 * 4 * T * (16 + 2) * 4
    state = sum(a.nbytes for s in extract_state(sw)
                for a in (s["weights"], s["bias"]) if a is not None)

    def told(room, used=0):
        """A device with ``room`` bytes under the line once the state's
        gradients are counted."""
        limit = int((state + used + room) / fused.REMAT_ABOVE) + 1
        monkeypatch.setattr(jax, "local_devices",
                            lambda *a: [Told(limit, used)])
        return trainer._backward_should_recompute(plans)

    # the activations do not fit, what the layers named does
    assert told(named + 4096) == fused.kept_names()
    assert "recomputed in the backward but for" in seen[-1]
    assert "%.2f GB" % (named / 1e9) in seen[-1]
    assert gauge.value == named
    # no layer selects: the residuals under kept_names() are those under
    # the list without the selection's name, which adds nothing
    assert registry.peek("step.kept_selection_bytes").value == 0
    assert told(named) == fused.kept_names() \
        and told(named, used=1 << 20) == fused.kept_names()
    # neither fits: the bare checkpoint, as before
    assert told(named - 4096) is True
    assert seen[-1].endswith("each layer is recomputed in the backward")
    assert gauge.value == 0
    # all of it fits
    assert told(1 << 30) is False and gauge.value == 0
    assert "activations are kept" in seen[-1]
    # the CPU, which does not say
    gauge.set(named)
    cpu = jax.devices("cpu")
    monkeypatch.setattr(jax, "local_devices", lambda *a: cpu)
    assert trainer._backward_should_recompute(plans) is False
    assert gauge.value == 0


def test_recomputed_backward_gives_the_same_step(_precision):
    sw, layers, plans, state, x, y = program_and_batch()
    kept = build_train_step(plans, donate=False)(
        state, x, y, numpy.float32(4), step_count=numpy.int32(1))
    again = build_train_step(plans, donate=False, bwd_remat=True)(
        state, x, y, numpy.float32(4), step_count=numpy.int32(1))
    assert float(kept[1]["loss"]) == float(again[1]["loss"])
    # AdamW divides the first moment by the second's root + 1e-8, so
    # where a gradient is itself of that epsilon's size the quotient
    # turns its rounding (one routed weight's reads 1.16e-8 against
    # 1.17e-8 in the two programs) into 3e-3 of the rate, 6e-6 of the
    # parameter.  The parameters whose gradient is under twice
    # the epsilon (a first moment under 0.1 x 2e-8; 22 of 153,000 here)
    # are held by their moments alone, every other one as before
    near_epsilon = 0
    for layer, other in zip(kept[0], again[0]):
        for name in layer:
            if layer[name] is None:  # the embedding has no bias
                assert other[name] is None
                continue
            a, b = numpy.asarray(layer[name]), numpy.asarray(other[name])
            if not name.startswith("accum"):
                first = numpy.abs(numpy.asarray(layer["accum_" + name]))
                tiny = (first > 0) & (first < 2e-9)
                near_epsilon += int(tiny.sum())
                a = numpy.where(tiny, b, a)
            numpy.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6,
                                          err_msg=name)
    assert near_epsilon <= 32
