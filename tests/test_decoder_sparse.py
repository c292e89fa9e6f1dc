"""Grouped-query attention over a learned top-k selection (a lightning
indexer trained by its own loss) over softmax-routed experts, at toy
width on the CPU, seeded weights: the program against the benchmark's
plain reference (``benchmark/references/sparse_gqa_moe_decoder.py``) on
logits, loss, every gradient (the indexer's included) and one AdamW
step, in float32 and bfloat16, with T well above top-k; the selection's
definition; the new kernels in interpret mode against their masked
references at a T that is not a multiple of the tile; where each loss's
gradient goes; the shares of an expert-parallel deployment under the
softmax router; the counters; what the recomputed backward keeps; and
the accepted decoders' lowered steps, which are the parent's."""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import sparse_gqa_moe_decoder as reference  # noqa: E402,E501

from tests.test_checkpoint_keeps import kernel_calls  # noqa: E402
from veles_tpu import compiler, prng  # noqa: E402
from veles_tpu.backends import Device  # noqa: E402
from veles_tpu.compiler import (  # noqa: E402
    _forward_for_loss, build_forward, build_train_step, extract_state,
    workflow_plan)
from veles_tpu.config import root  # noqa: E402
from veles_tpu.dummy import DummyLauncher  # noqa: E402
from veles_tpu.loader import TokenRowLoader  # noqa: E402
from veles_tpu.models import decoder, fused, zoo  # noqa: E402
from veles_tpu.models.nn_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.observe.metrics import registry  # noqa: E402
from veles_tpu.ops import sparse_attention as sparse  # noqa: E402

VOCAB, T, TOPK, WIDTH = 96, 256, 64, 64
ARGUMENTS = dict(
    vocab=VOCAB, width=WIDTH, layer_types=["selected", "selected"],
    heads=8, kv_heads=2, head_width=16, window=None, ffn=None,
    experts=16, experts_held=4, first_expert=4, top_k=3, expert_width=32,
    shared_width=0, dense_layers=0, theta=1e4, eps=1e-6, lr=3e-3,
    rope=[True, True], out_gate=False, post_norms=False, router="softmax",
    index_heads=2, index_width=16, index_topk=TOPK, out_init_std=0.01)
BLOCKS = dict(query_block=64, token_block=256)
KEPT = fused.kept_names()


class Tokens(TokenRowLoader):
    """12 seeded Zipf rows of T + 1 ids: 4 validation, 8 train."""

    def load_data(self):
        self.class_lengths[:] = (0, 4, 8)
        self._calc_class_end_offsets()
        self.create_originals((T + 1,), labels=False)
        rng = numpy.random.RandomState(3)
        p = 1.0 / numpy.arange(1, VOCAB + 1)
        self.original_data.mem[...] = rng.choice(
            VOCAB, size=(12, T + 1), p=p / p.sum())


@pytest.fixture
def _precision(monkeypatch):
    def set_to(name):
        monkeypatch.setattr(root.common.engine, "precision_type", name)
    set_to("float32")
    return set_to


def program_and_batch(seed=5, batch=2, max_epochs=1, **arguments):
    prng.get().seed(seed)
    layers = zoo.gqa_moe_decoder_layers(**dict(ARGUMENTS, **arguments))
    sw = StandardWorkflow(
        DummyLauncher(), layers=layers,
        loader_factory=lambda w: Tokens(w, minibatch_size=batch),
        decision_config=dict(max_epochs=max_epochs))
    sw.fuse()
    sw.initialize(device=Device(backend="cpu"))
    plans, state = workflow_plan(sw), extract_state(sw)
    rows = numpy.array(sw.loader.original_data.mem[:batch])
    return sw, layers, plans, state, rows[:, :-1], rows[:, 1:]


def weights_and_gains(state):
    return [{"weights": s["weights"], "bias": s["bias"]} for s in state]


def with_kernels(plans, on=True):
    for plan in plans:
        if plan.forward_cls is decoder.DecoderLayer:
            plan.static["pallas_bwd"] = on
    return plans


def relative(got, want):
    got, want = numpy.asarray(got, numpy.float64), numpy.asarray(
        want, numpy.float64)
    return numpy.linalg.norm(got - want) / numpy.linalg.norm(want)


# -- the whole model against the reference -----------------------------------


#: (logits rms, loss, the worst gradient array, the parameters' change)
#: of the program against the float32 reference: float32 (the plain
#: path and the kernels in the interpreter) to its rounding, bfloat16
#: to what its operands' rounding gives at this width
LIMITS = {"float32": (2e-6, 1e-6, 3e-5, 1e-3),
          "float32-kernels": (2e-5, 1e-6, 1e-4, 1e-3),
          "bfloat16": (5e-2, 5e-3, 0.35, 0.6)}


@pytest.mark.parametrize("form", sorted(LIMITS))
def test_program_against_reference_logits_loss_gradients_and_a_step(
        _precision, form):
    _precision(form.split("-")[0])
    sw, layers, plans, state, x, y = program_and_batch()
    with_kernels(plans, form.endswith("kernels"))
    assert [spec.get("index_topk") for spec in layers[1:-1]] == [TOPK] * 2
    params = weights_and_gains(state)
    limit_rms, limit_loss, limit_grad, limit_step = LIMITS[form]
    with jax.default_matmul_precision("highest"):
        got = numpy.asarray(jax.jit(build_forward(plans))(params, x))
        new_state, metrics = build_train_step(plans, donate=False)(
            state, x, y, numpy.float32(2), step_count=numpy.int32(1))
    want, _, kls = reference.forward(layers, params, x, with_load=True,
                                     **BLOCKS)
    assert relative(got, want) < limit_rms
    ref_loss, ref_grads = reference.loss_and_gradients(
        layers, params, x, y, **BLOCKS)
    # the step's loss is the next-token loss; L_I is a layer's metric
    assert float(metrics["loss"]) == pytest.approx(float(ref_loss),
                                                   rel=limit_loss)
    numpy.testing.assert_allclose(
        metrics["indexer_kl"], [float(k) / (2 * T) for k in kls],
        rtol=max(limit_loss, 1e-5) * 10)
    hyper = dict(lr=3e-3, beta1=0.9, beta2=0.95, eps=1e-8)
    moved = numpy.zeros(2)
    for i, (old, new) in enumerate(zip(state, new_state)):
        for key, decay in (("weights", 0.1), ("bias", 0.0)):
            if old[key] is None:
                continue
            g_ref = numpy.asarray(ref_grads[i][key]).reshape(
                old[key].shape)
            g = numpy.asarray(new["accum_" + key]) / 0.1
            assert relative(g, g_ref) < limit_grad, (i, key)
            p, _, _ = reference.adamw_step(
                numpy.asarray(old[key]), g_ref, 0.0, 0.0, 1, decay=decay,
                **hyper)
            step = numpy.asarray(new[key]) - numpy.asarray(old[key])
            moved += [numpy.sum(numpy.square(step - (p - old[key]))),
                      numpy.sum(numpy.square(p - old[key]))]
    assert numpy.sqrt(moved[0] / moved[1]) < limit_step
    # the indexer's own pieces, each against the reference's
    names, _ = reference.layer_pieces(layers[1], WIDTH)
    mine = reference.split(numpy.asarray(new_state[1]["accum_weights"])
                           / 0.1, names)
    theirs = reference.split(numpy.asarray(ref_grads[1]["weights"]), names)
    for name in ("w_iq", "w_ik", "w_iw"):
        assert numpy.abs(theirs[name]).max() > 1e-6, name
        assert relative(mine[name], theirs[name]) < limit_grad, name


# -- the selection ---------------------------------------------------------


def index_operands(seed, t, heads=2, width=16, positive=False):
    rng = numpy.random.RandomState(seed)
    q = rng.randn(1, t, heads, width).astype(numpy.float32)
    k = rng.randn(1, t, width).astype(numpy.float32)
    w = rng.randn(1, t, heads).astype(numpy.float32)
    if positive:  # every product above 0: scores are continuous, no tie
        q, k, w = numpy.abs(q), numpy.abs(k), numpy.abs(w)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(w)


def kept_by(path, operands, topk, t):
    if path == "kernel":
        return numpy.asarray(sparse.select(*operands, topk,
                                           blocks=(64, 128)).mask[
                                               0, :t, :t]) != 0
    return numpy.asarray(sparse.select_reference(*operands, topk)[1][0])


@pytest.mark.parametrize("path", ["kernel", "plain"])
@pytest.mark.parametrize("t", [100, 300])
def test_the_selection_keeps_every_key_then_exactly_topk(path, t):
    """All earlier keys while t < k, exactly k after, never a later key,
    and they are the k highest of the row."""
    topk = 48
    operands = index_operands(t, t, positive=True)
    kept = kept_by(path, operands, topk, t)
    counts = kept.sum(axis=1)
    numpy.testing.assert_array_equal(counts,
                                     numpy.minimum(numpy.arange(t) + 1, topk))
    assert not numpy.triu(kept, 1).any()
    scores = numpy.asarray(sparse.select_reference(*operands, topk)[0][0])
    for row in (topk - 1, topk, t - 1):
        best = numpy.argsort(-scores[row, :row + 1])[:topk]
        assert set(numpy.flatnonzero(kept[row])) == set(best)


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_a_planted_tie_keeps_both_keys(path):
    """Two keys that score alike for every query (the same key vector)
    straddle the threshold of some rows: those rows keep k + 1."""
    t, topk = 160, 32
    q, k, w = index_operands(7, t, positive=True)
    k = k.at[0, 20].set(k[0, 90])
    kept = kept_by(path, (q, k, w), topk, t)
    counts = kept.sum(axis=1)
    over = numpy.flatnonzero(counts > numpy.minimum(numpy.arange(t) + 1,
                                                    topk))
    assert len(over) > 0 and (counts[over] == topk + 1).all()
    assert kept[over, 20].all() and kept[over, 90].all()
    assert not numpy.triu(kept, 1).any()
    numpy.testing.assert_array_equal(kept, kept_by(
        "plain" if path == "kernel" else "kernel", (q, k, w), topk, t))


@pytest.mark.parametrize("topk", [TOPK, T])
def test_the_references_selection_and_its_every_key_form(_precision, topk):
    """The reference's kept pairs of the first layer are the program's
    (float32: the same definition on the same operands, computed apart);
    where the selection keeps every earlier key, attending over it is
    the reference's every-key form, and elsewhere it is not."""
    sw, layers, plans, state, x, y = program_and_batch(index_topk=topk)
    params = weights_and_gains(state)
    kept = numpy.asarray(reference.selection(layers, params, x[0],
                                             query_block=64))
    spec = layers[1]
    dims = {k: spec[k] for k in decoder.DecoderLayer.DIMS
            if spec.get(k) is not None}
    w_layout, b_layout = decoder.layer_layout(WIDTH, **dims)
    w = decoder.unpack(params[1]["weights"], w_layout, "float32")
    g = decoder.unpack(params[1]["bias"], b_layout, jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params[0]["weights"], jnp.asarray(x[:1]), axis=0)
        index = decoder.index_inputs(
            decoder.rms_norm(h, g["attn_gain"], spec["eps"]), w, g,
            index_heads=spec["index_heads"],
            index_width=spec["index_width"], theta=spec["theta"],
            eps=spec["eps"])
        mine = numpy.asarray(sparse.select_reference(*index, topk)[1][0])
    numpy.testing.assert_array_equal(kept, mine)
    assert kept.sum() >= numpy.minimum(numpy.arange(T) + 1, topk).sum()
    selected = reference.forward(layers, params, x[:1], **BLOCKS)
    every = reference.forward(layers, params, x[:1], every_key=True,
                              **BLOCKS)
    apart = relative(every, selected)
    assert apart < 1e-6 if topk == T else apart > 1e-3


# -- the kernels in the interpreter ------------------------------------------


@pytest.mark.parametrize("heads, kv_heads", [(4, 2), (4, 4)])
def test_kernels_against_the_masked_reference(heads, kv_heads):
    """T = 300, tiles (64, 128): the selection, attention over it forward
    and its three gradients, and the indexer's loss with its gradient by
    the indexer's three operands against ``jax.grad`` of the plain
    loss."""
    t, topk, d = 300, 48, 32
    operands = index_operands(11, t)
    blocks = (64, 128)
    selection = sparse.select(*operands, topk, blocks=blocks)
    _, kept = sparse.select_reference(*operands, topk)
    numpy.testing.assert_array_equal(
        numpy.asarray(selection.mask[:, :t, :t]) != 0, numpy.asarray(kept))
    rng = numpy.random.RandomState(12)
    q = jnp.asarray(rng.randn(heads, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(kv_heads, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(kv_heads, t, d), jnp.float32)
    out, stats = sparse.attend(q, k, v, selection, blocks=blocks)
    want, probabilities = sparse.attend_reference(q, k, v, kept)
    numpy.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    weigh = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(sparse.attend(
        *a, selection, blocks=blocks)[0] * weigh), argnums=(0, 1, 2))(q, k, v)
    grads = jax.grad(lambda *a: jnp.sum(sparse.attend_reference(
        *a, kept)[0] * weigh), argnums=(0, 1, 2))(q, k, v)
    for name, g, g_ref in zip("qkv", got, grads):
        assert relative(g, g_ref) < 1e-4, name
    value, index_grads = sparse.indexer_loss(q, k, stats, *operands,
                                             selection, blocks=blocks)
    want, want_grads = jax.value_and_grad(
        lambda *a: sparse.indexer_loss_reference(probabilities, kept, *a),
        argnums=(0, 1, 2))(*operands)
    assert float(value) == pytest.approx(float(want), rel=1e-4)
    for name, g, g_ref in zip(("q_i", "k_i", "w_i"), index_grads,
                              want_grads):
        assert numpy.abs(numpy.asarray(g_ref)).max() > 1e-6, name
        assert relative(g, g_ref) < 1e-4, name


def test_the_mask_skips_tiles_that_keep_nothing():
    """A selection that keeps only the diagonal tiles: the attention's
    tables name the held tile for every other step, and the result is
    the masked reference's."""
    t, topk = 256, 8
    rng = numpy.random.RandomState(3)
    # the key that scores highest for each query is its own neighbourhood
    q = numpy.zeros((1, t, 1, 16), numpy.float32)
    k = numpy.zeros((1, t, 16), numpy.float32)
    angle = numpy.arange(t) * 0.001
    q[0, :, 0, 0], q[0, :, 0, 1] = numpy.cos(angle), numpy.sin(angle)
    k[0, :, 0], k[0, :, 1] = numpy.cos(angle), numpy.sin(angle)
    k[0, :, 2] = numpy.arange(t) * 1e-3  # later keys score higher
    q[0, :, 0, 2] = 1.0
    operands = (jnp.asarray(q), jnp.asarray(k), jnp.ones((1, t, 1)))
    selection = sparse.select(*operands, topk, blocks=(64, 128))
    _, kept = sparse.select_reference(*operands, topk)
    occupied = numpy.asarray(sparse._occupied(selection.tiles, 64, 64))
    causal = sparse.causal_tiles(t, 64, 128)
    assert 0 < (occupied > 0).sum() < causal
    x = jnp.asarray(rng.randn(2, t, 16), jnp.float32)
    out, _ = sparse.attend(x, x[:1], x[:1], selection, blocks=(64, 128))
    numpy.testing.assert_allclose(
        out, sparse.attend_reference(x, x[:1], x[:1], kept)[0],
        rtol=1e-4, atol=1e-4)


# -- where each loss's gradient goes -----------------------------------------


def test_each_loss_reaches_its_own_pieces(_precision, monkeypatch):
    """In the program and in the reference alike: the next-token loss
    puts nothing on the indexer's pieces, and the indexer's loss nothing
    on any other piece — its gradient is what the reference's L_I gives
    with the indexer's input detached."""
    sw, layers, plans, state, x, y = program_and_batch()
    params = weights_and_gains(state)
    names, gain_names = reference.layer_pieces(layers[1], WIDTH)
    mine = {"w_iq", "w_ik", "w_iw", "index_k_gain", "index_k_bias"}

    def program_grads():
        def loss(p):
            return reference.loss(_forward_for_loss(plans, p, x), y)
        with jax.default_matmul_precision("highest"):
            return jax.grad(loss)(params)

    total = program_grads()
    # the L_I gradients handed over, the next-token loss's alone
    monkeypatch.setattr(decoder, "_gradients_in",
                        lambda: lambda x_, inputs, grads: x_)
    plain = program_grads()
    ref_total = reference.loss_and_gradients(layers, params, x, y,
                                             **BLOCKS)[1]
    ref_plain = reference.loss_and_gradients(
        layers, params, x, y, indexer_loss=False, **BLOCKS)[1]
    for layer in (1, 2):
        pieces = {}
        for key, listed in (("weights", names), ("bias", gain_names)):
            for name, grad in (("total", total), ("plain", plain),
                               ("ref_total", ref_total),
                               ("ref_plain", ref_plain)):
                pieces.setdefault(name, {}).update(reference.split(
                    numpy.asarray(grad[layer][key]), listed))
        for piece in pieces["total"]:
            only_kl = pieces["total"][piece] - pieces["plain"][piece]
            ref_kl = pieces["ref_total"][piece] - pieces["ref_plain"][piece]
            if piece in mine:
                assert not numpy.asarray(pieces["plain"][piece]).any()
                assert not numpy.asarray(pieces["ref_plain"][piece]).any()
                assert numpy.abs(ref_kl).max() > 1e-6, piece
                assert relative(only_kl, ref_kl) < 1e-4, piece
            else:
                assert not numpy.asarray(only_kl).any(), piece
                assert not numpy.asarray(ref_kl).any(), piece


# -- the share ---------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(_precision):
    """The configuration's deployment at toy width: eight ranks of 2
    experts each (0-1, ..., 14-15 of 16, top 3, softmax-routed, no shared
    expert).  What the ranks' routed layers give adds up to the uncut
    reference's output for the whole layer, every assignment is some
    rank's, and what every rank computes alike (the attention over the
    selection) is counted once."""
    rng = numpy.random.RandomState(11)
    dims = dict(heads=8, kv_heads=2, head_width=16, rope=True,
                out_gate=False, theta=1e4, index_heads=2, index_width=16,
                index_topk=TOPK, experts=16, top_k=3, expert_width=32,
                shared_width=0, router="softmax")
    whole = dict(dims, experts_held=16, first_expert=0)
    pieces, gain_pieces = reference.layer_pieces(whole, WIDTH)
    full = {name: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
            for name, shape in pieces}
    gains = {name: jnp.asarray(1 + 0.1 * rng.randn(*shape), jnp.float32)
             for name, shape in gain_pieces}
    h = jnp.asarray(rng.randn(1, T, WIDTH), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, load, _ = reference.sequence_layer(
            h[0], whole, full, gains, 1e-6, "float32", 64, 256)
        mixed = h[0] + reference.attention(
            reference.rms_norm(h[0], gains["attn_gain"], 1e-6), full,
            gains, whole, 1e-6, "float32", 64, False)[0]
        total, seen = numpy.zeros(h.shape[1:], numpy.float32), 0
        for rank in range(8):
            held = slice(2 * rank, 2 * rank + 2)
            share = dict(dims, experts_held=2, first_expert=2 * rank)
            w = dict(full, **{name: full[name][held]
                              for name in ("e_gate", "e_up", "e_down")})
            names, _ = reference.layer_pieces(share, WIDTH)
            out, aux = decoder.decoder_layer(
                h, reference._flat(w, names),
                reference._flat(gains, gain_pieces), compute_dtype="float32",
                eps=1e-6, pallas_bwd=False, **share)
            assert int(aux["moe_dropped"]) == 0
            numpy.testing.assert_array_equal(aux["moe_load"], load[held])
            seen += int(aux["moe_assignments"])
            total += numpy.asarray(out[0] - mixed)
    assert seen == T * 3
    numpy.testing.assert_allclose(numpy.asarray(mixed) + total, uncut,
                                  atol=2e-5)
    assert numpy.abs(total).max() > 1e-3


# -- the layout, the scopes, the counters ------------------------------------


def test_the_indexer_and_the_softmax_router_are_parts():
    """``index_heads`` adds the indexer's pieces and the key norm's gain
    and bias; a softmax router takes no correction bias; the accepted
    layouts are as they were; the names the trainer and the trace read
    are declared."""
    base = dict(heads=8, kv_heads=2, head_width=16, out_gate=False,
                experts=16, experts_held=4, expert_width=32,
                shared_width=0)
    plain, plain_bias = decoder.layer_layout(WIDTH, **base)
    indexed, indexed_bias = decoder.layer_layout(
        WIDTH, index_heads=2, index_width=16, router="softmax", **base)
    assert [n for n, _ in indexed] == [n for n, _ in plain][:4] + [
        "w_iq", "w_ik", "w_iw"] + [n for n, _ in plain][4:]
    assert dict(indexed)["w_iq"] == (WIDTH, 32)
    assert [n for n, _ in indexed_bias] == [
        "attn_gain", "q_gain", "k_gain", "index_k_gain", "index_k_bias",
        "ffn_gain"]
    assert [n for n, _ in plain_bias][-1] == "router_bias"
    assert {"index_heads", "index_width", "index_topk", "router"} <= set(
        decoder.DecoderLayer.DIMS)
    assert decoder.DecoderLayer.PART_SCOPES[-1] == "indexer"
    assert decoder.DecoderLayer.AUX_COUNTERS["indexer_kl"] == \
        "sparse.indexer_kl"
    with pytest.raises(ValueError, match='"selected"'):
        zoo.gqa_moe_decoder_layers(**dict(ARGUMENTS,
                                          layer_types=["sparse"]))


def test_the_initialiser_starts_the_key_norm_at_gain_1_bias_0(_precision):
    sw, layers, plans, state, x, y = program_and_batch()
    _, gain_names = reference.layer_pieces(layers[1], WIDTH)
    gains = reference.split(numpy.asarray(state[1]["bias"]), gain_names)
    assert (numpy.asarray(gains["index_k_gain"]) == 1).all()
    assert (numpy.asarray(gains["index_k_bias"]) == 0).all()
    names, _ = reference.layer_pieces(layers[1], WIDTH)
    w = reference.split(numpy.asarray(state[1]["weights"]), names)
    assert float(jnp.std(w["w_iq"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(w["w_o"])) == pytest.approx(0.01, rel=0.1)


def test_the_indexer_scope_rides_in_the_programs_metadata(_precision):
    """``op_name`` of the compiled step: the indexer's ops, forward and
    transposed, under ``indexer`` in the layer's scope and not under
    ``attention``; the selection's kernel there."""
    sw, layers, plans, state, x, y = program_and_batch()
    with_kernels(plans)
    text = jax.jit(compiler._build_step_fn(plans, "softmax")).lower(
        state, x, y, numpy.float32(2), None,
        step_count=numpy.int32(1)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for layer in (1, 2):
        assert any(re.search(r"[/(]l%d_DecoderLayer\)*/indexer/" % layer,
                             name) for name in op_names), layer
        assert any(re.search(r"[/(]l%d_DecoderLayer\)*/attention/" % layer,
                             name) for name in op_names), layer
    assert not any("attention/indexer" in name or "indexer/attention" in name
                   for name in op_names)
    assert any("transpose(jvp(l2_DecoderLayer))/indexer/" in name
               for name in op_names)


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_the_counters_at_toy_size(path):
    """Kept pairs by query tile, the tiles that keep one and the causal
    ones, with no tie: sum_t min(t + 1, k)."""
    t, topk = 300, 40
    operands = index_operands(5, t, positive=True)
    if path == "kernel":
        selection = sparse.select(*operands, topk, blocks=(64, 128))
    else:
        selection = sparse.selection_of(
            sparse.select_reference(*operands, topk)[1], blocks=(64, 128))
    counts = sparse.counters(selection, t, blocks=(64, 128))
    want = numpy.minimum(numpy.arange(t) + 1, topk)
    per_tile = [want[i:i + 64].sum() for i in range(0, t, 64)]
    numpy.testing.assert_array_equal(counts["sparse_selected_pairs"],
                                     per_tile)
    assert int(counts["sparse_causal_tiles"]) == sparse.causal_tiles(
        t, 64, 128) == 1 + 1 + 2 + 2 + 3
    assert 0 < int(counts["sparse_occupied_tiles"]) <= 9


def test_the_trainer_publishes_the_counters_and_the_loss_gauge(_precision):
    sw, layers, plans, state, x, y = program_and_batch(max_epochs=2)
    before = {name: registry.counter(name).value for name in (
        "sparse.occupied_tiles", "sparse.causal_tiles")}
    sw.run()
    trainer = sw.fused_trainer
    assert int(trainer.skip_count) == 0
    trainer.publish_layer_counters()
    snapshot = registry.snapshot()
    causal = snapshot["counters"]["sparse.causal_tiles"] \
        - before["sparse.causal_tiles"]
    assert causal > 0 and causal % (2 * sparse.causal_tiles(T, 256, 256)) \
        == 0
    assert snapshot["counters"]["sparse.occupied_tiles"] \
        - before["sparse.occupied_tiles"] == causal
    assert {name for name in snapshot["counters"]
            if name.startswith("sparse.selected_pairs.")} == {
                "sparse.selected_pairs.l0.e0", "sparse.selected_pairs.l1.e0"}
    # the gauge: the 2 layers' L_I a step, a few hundredths at the seed
    assert 0 < snapshot["gauges"]["sparse.indexer_kl"] < 1


def test_the_recomputed_backward_keeps_what_the_kernels_named(_precision):
    """Under the trainer's keep-list the selection, the sparse forward
    and the indexer's loss run once a layer (the selection's mask and
    tiles' counts are kept, not recomputed); without the selection's
    name the selection runs twice; with every layer recomputed whole
    each runs twice."""
    sw, layers, plans, state, x, y = program_and_batch()
    with_kernels(plans)
    params = weights_and_gains(state)

    def calls(remat):
        def loss(p):  # as the step has it: the layers' counters kept
            collected = []
            out = _forward_for_loss(plans, p, x, remat=remat,
                                    aux=collected)
            return reference.loss(out, y), compiler._stack_layer_aux(
                collected)
        return kernel_calls(jax.make_jaxpr(jax.value_and_grad(
            loss, has_aux=True))(params).jaxpr)

    kept, whole = calls(KEPT), calls(True)
    assert KEPT[-1] == sparse.KEPT_SELECTION
    assert kept[sparse.FWD_KERNEL_NAME] == 2 \
        and whole[sparse.FWD_KERNEL_NAME] == 4
    assert kept[sparse.KL_KERNEL_NAME] == 2 \
        and whole[sparse.KL_KERNEL_NAME] == 4
    assert kept[sparse.SELECT_KERNEL_NAME] == 2 \
        and whole[sparse.SELECT_KERNEL_NAME] == 4
    assert calls(KEPT[:-1]) == dict(kept, **{sparse.SELECT_KERNEL_NAME: 4})
    assert kept == calls(False)
    assert kept[sparse.DQ_KERNEL_NAME] == kept[sparse.DKV_KERNEL_NAME] == 2
    with jax.default_matmul_precision("highest"):
        one = build_train_step(plans, donate=False, bwd_remat=KEPT)(
            state, x, y, numpy.float32(2), step_count=numpy.int32(1))
        two = build_train_step(plans, donate=False)(
            state, x, y, numpy.float32(2), step_count=numpy.int32(1))
    assert float(one[1]["loss"]) == float(two[1]["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(one[0]),
                    jax.tree_util.tree_leaves(two[0])):
        numpy.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6)
    assert fused.REMAT_ABOVE > 0


def test_the_selection_is_kept_where_its_bytes_fit(_precision, monkeypatch):
    """The decision, told devices of made-up sizes: where the kernels'
    names and the selection's fit, both are kept and
    ``step.kept_selection_bytes`` reads the toy's masks and tiles'
    counts; where only the kernels' names fit, the keep-list without the
    selection's name and 0; where neither fits, the bare checkpoint."""
    sw, layers, plans, state, x, y = program_and_batch()
    with_kernels(plans)
    trainer = sw.fused_trainer
    params = weights_and_gains(state)

    def saved(remat):
        """What the backward holds under ``remat``, as the decision
        sizes it (an abstract trace)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(jax.eval_shape(
                       lambda p: jax.vjp(lambda q: _forward_for_loss(
                           plans, q, x, remat=remat), p)[1], params)))

    # a layer's selection at T 256 takes one (256, 256) tile: the mask
    # (2 rows x 256 x 256 int8) and one int32 count a row
    selection = 2 * (2 * T * T + 2 * 4)
    bare = saved(True)
    both, kernels = saved(KEPT) - bare, saved(KEPT[:-1]) - bare
    assert both - kernels == selection and kernels > 0
    state_bytes = sum(a.nbytes for s in state
                      for a in (s["weights"], s["bias"]) if a is not None)
    assert saved(False) - state_bytes > both

    class Told(object):
        def __init__(self, limit):
            self.stats = {"bytes_limit": limit, "bytes_in_use": 0}

        def memory_stats(self):
            return self.stats

    seen = []
    monkeypatch.setattr(trainer, "info",
                        lambda fmt, *args: seen.append(fmt % args))

    def told(room):
        limit = int((state_bytes + room) / fused.REMAT_ABOVE) + 1
        monkeypatch.setattr(jax, "local_devices", lambda *a: [Told(limit)])
        return trainer._backward_should_recompute(plans), (
            registry.peek("step.kept_residual_bytes").value,
            registry.peek("step.kept_selection_bytes").value)

    assert told(both) == (KEPT, (both, selection))
    assert "the replay does not select again" in seen[-1]
    assert told(both - 4096) == (KEPT[:-1], (kernels, 0))
    assert "recomputed in the backward but for" in seen[-1] \
        and "select again" not in seen[-1]
    assert told(kernels - 4096) == (True, (0, 0))
    assert seen[-1].endswith("each layer is recomputed in the backward")


# -- the accepted decoders' programs ----------------------------------------


RECORDED_UNDER_JAX = "0.9.0"
#: sha256 of the lowered train step of the accepted decoders' toys
#: (``tests/test_decoder.py``: latent attention; ``test_decoder_gqa.py``:
#: window and full layers; ``test_decoder_conv.py``: short convolutions
#: and 8-wide heads), the kernels on (the interpreter's lowering), each
#: with every activation kept and with the trainer's keep-list — the
#: parent's digests, with the parent's keep-list: the new parts, the
#: router kind and the longer keep-list leave them as they were.  The
#: text carries no source location (no debug info, and the interpreter
#: leaves no Mosaic payload), so where code sits in a module moves none
#: of them; on a described v5e the same steps, each kernel's payload
#: printed without its locations, are the parent's too (PERF.md
#: section 6)
ACCEPTED_STEPS = {
    ("test_decoder", False):
        "111cd2490c64977bc3e65d541bcd88657c8c79352245b5d3ef2b4d16390ca284",
    ("test_decoder", True):
        "6727295992c7d9c16977ff4e2d624d22f262100e8be6b2d7e898252132581500",
    ("test_decoder_gqa", False):
        "480863b0a4431cb77bbf5e2ad7d89f3e84f31b9f2248821fb37696a794419ca6",
    ("test_decoder_gqa", True):
        "b2434d593e5229d839fdc5b6c109b93ddd240395adc54b9f27aa6066c6e13a64",
    ("test_decoder_conv", False):
        "eb39b03aed0458622ed0f2831db9b59d8ec6774fd7a7d274ad0c30b8ed733139",
    ("test_decoder_conv", True):
        "9c21f21004b684190ee833eb80a420f073cfd08aea821a005fd8f902bbc27f41",
}


@pytest.mark.parametrize("family, keep", sorted(ACCEPTED_STEPS))
def test_the_accepted_decoders_lowered_steps_are_the_parents(
        _precision, family, keep):
    if jax.__version__ != RECORDED_UNDER_JAX:
        pytest.skip("the digests were recorded under jax %s"
                    % RECORDED_UNDER_JAX)
    import importlib
    module = importlib.import_module("tests." + family)
    sw, layers, plans, state, x, y = module.program_and_batch()
    with_kernels(plans)
    text = jax.jit(compiler._build_step_fn(
        plans, "softmax", bwd_remat=KEPT if keep else False)).lower(
            state, x, y, numpy.float32(4), None,
            step_count=numpy.int32(1)).as_text(debug_info=False)
    assert not re.search(r"loc\(|\.py:\d", text)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        ACCEPTED_STEPS[family, keep]
