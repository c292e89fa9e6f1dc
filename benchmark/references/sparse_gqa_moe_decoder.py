"""The plain reference of the causal decoder whose grouped-query attention
runs over a learned top-k selection of keys, over softmax-routed experts
with no shared one (configuration ``keye_vl2_30b_a3b``): the layer
equations in ``jax.numpy``, float32, true-float32 products
(``jax.default_matmul_precision("highest")``), no kernel, no cache, and
nothing imported from the program (what no model's equations differ in —
the operand rounding, a product, RMSNorm, the gated feed-forward, the
loss, AdamW, the arithmetic on gradient lists — is the sibling
reference's, ``mla_moe_decoder.py``, imported, not copied; the
rotate-half rotary and the count of allowed pairs are the other
sibling's, ``gqa_window_moe_decoder.py``).  Written from the published
``config.json`` (its keys in brackets) and, where that has no key, from
the family's convention (marked †: DeepSeek-V3.2's published lightning
indexer for the selection, the Qwen3-MoE decoder for the rest), and from
the layer specs the zoo factory returns; the packed parameter layout is
listed here again, by hand, so a program that packed differently would
disagree.

``h`` is the residual stream, ``rms_norm(x; g) = x / sqrt(mean(x^2) +
eps) * g`` (rms_norm_eps)::

    h = E[ids]                                 (tie_word_embeddings false)
    every layer:  a = rms_norm(h; g_in)                           (pre-norm †)
      the indexer, over a detached (sa_config; DeepSeek-V3.2's indexer †):
        qI[t, j] = a[t] W_iq[j]    j < index_heads, index_width wide
        kI[s] = layer_norm(a[s] W_ik; g, b)          (one key head; LN †)
        wI[t, j] = a[t] W_iw[j] / sqrt(index_heads index_width)  (scales †)
        rotary on the first half of qI's and kI's width, rotate-half †
        I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])      (s <= t)
        tau[t] = the min(t + 1, topk)-th largest of I[t, 0..t]      (topk)
        S[t] = {s <= t : I[t, s] >= tau[t]}         (more only on a tie)
      attention over the selection:
        q = a W_q -> heads x head_dim;  k, v = a W_k, a W_v -> kv_heads x
        head_dim         (num_attention_heads, num_key_value_heads, head_dim)
        q, k = rms_norm over each head's head_dim, one gain each  (QK-norm †)
        rotary on q and k, rotate-half, every layer              (rope_theta)
        P[t, n, s] = softmax_{s in S[t]}(q[t, n] . k[s, n // group]
                                         / sqrt(head_dim))
        h = h + (sum_s P[t, n, s] v[s, n // group])_n W_o    (no gate, bias)
      the indexer's loss (DeepSeek-V3.2's sparse stage †):
        p[t, s] = stop_grad(mean_n P[t, n, s])
        L_I = mean_t sum_{s in S[t]} p (log p - log softmax_{S[t]} I[t, .])
      the routed feed-forward (no leading dense layer: mlp_only_layers []):
        m = rms_norm(h; g_post)                    (post-attention norm †)
        z = m W_r, float32, num_experts outputs
        chosen = the top_k largest; w_i = softmax(z)_i / sum_chosen
        softmax(z)                                        (norm_topk_prob)
        h = h + sum_{i chosen AND held} w_i Expert_i(m)      (no shared)
    logits = rms_norm(h; g_final) W_head                     (final norm †)
    objective = next-token loss + sum_layers L_I

**The selection and L_I in query blocks.**  A block of queries at a time
against every key: its scores, its threshold (``lax.top_k`` of the
row), its attention over the kept keys and its part of L_I, computed
again in a backward pass (``jax.checkpoint``), so nothing (T, T) is
alive at once.  The selection passes no gradient; L_I's reaches only
``W_iq``, ``W_ik``, ``W_iw`` and the key norm's gain and bias (``a`` is
detached), and the next-token loss's never reaches them.
``row_gradients`` returns the sum of both: each layer's L_I counted
over the row's tokens, as the program counts it over the step's.

**The share.**  ``forward`` is given the experts held (a routed layer's
``first_expert``, ``experts_held``) and the vocabulary rows held: it
routes over all ``experts`` and adds only the held experts' terms —
every held expert on every token, weighted by the router's choice, no
buffer, so nothing can be dropped here.  With no shared expert and no
norm after the sub-layer, the shares' routed parts add up to the whole
layer's.

``every_key`` (of :func:`forward` and :func:`row_gradients`): attention
over every causal key instead of the selection, the variant that shows a
limit refusing a program which ignored the selection.  ``operand`` and
the blocking as in the siblings.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from benchmark.references.gqa_window_moe_decoder import (  # noqa: F401
    allowed_pairs, rotary)
from benchmark.references.mla_moe_decoder import (  # noqa: F401
    _blocks, _flat, _hashable, _jitted_head, _rounded, adamw_step,
    add_gradients, gated, loss, product, rms_norm, scale_gradients, silu,
    split)


# -- the packed layout, listed by hand ----------------------------------------


def layer_pieces(spec, width):
    """([(name, shape)] of a layer's packed weights, of its packed bias),
    in packing order."""
    heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
        spec["head_width"]
    index_heads, index_wide = spec["index_heads"], spec["index_width"]
    weights = [("w_q", (width, heads * wide)),
               ("w_k", (width, kv_heads * wide)),
               ("w_v", (width, kv_heads * wide)),
               ("w_o", (heads * wide, width)),
               ("w_iq", (width, index_heads * index_wide)),
               ("w_ik", (width, index_wide)),
               ("w_iw", (width, index_heads))]
    bias = [("attn_gain", (width,)), ("q_gain", (wide,)),
            ("k_gain", (wide,)), ("index_k_gain", (index_wide,)),
            ("index_k_bias", (index_wide,)), ("ffn_gain", (width,))]
    held, expert = spec["experts_held"], spec["expert_width"]
    weights += [("w_router", (width, spec["experts"])),
                ("e_gate", (held, width, expert)),
                ("e_up", (held, width, expert)),
                ("e_down", (held, expert, width))]
    return weights, bias


# -- the equations ----------------------------------------------------------


def layer_norm(x, gain, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(centred * centred, axis=-1,
                                       keepdims=True) + eps) * gain + bias


def indexer(a, w, gains, spec, eps, operand):
    """One sequence's (qI (T, heads, width), kI (T, width), wI (T,
    heads)) from a normalised, DETACHED ``a`` (T, width)."""
    heads, wide = spec["index_heads"], spec["index_width"]
    theta, half = spec.get("theta", 1e4), spec["index_width"] // 2
    t = a.shape[0]
    q = product(a, w["w_iq"], operand).reshape(t, heads, wide)
    k = layer_norm(product(a, w["w_ik"], operand), gains["index_k_gain"],
                   gains["index_k_bias"], eps)
    q = jnp.concatenate([rotary(q[..., :half], theta), q[..., half:]], -1)
    k = jnp.concatenate([rotary(k[..., :half], theta), k[..., half:]], -1)
    return (_rounded(q, operand), _rounded(k, operand),
            product(a, w["w_iw"], operand) / numpy.sqrt(heads * wide))


def index_scores(q_i, k_i, w_i):
    """I (queries, keys) of a block of the indexer's queries against
    every key, float32; the causal mask is the caller's."""
    s = jnp.einsum("qjd,kd->qjk", q_i, k_i)
    return jnp.einsum("qj,qjk->qk", w_i, jnp.maximum(s, 0.0))


def kept_pairs(scores, at, keys, topk):
    """The block's selection: keys at or before each query whose score
    is at least the row's ``min(t + 1, topk)``-th largest."""
    causal = keys[None, :] <= at[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    top = jax.lax.top_k(masked, min(topk, keys.shape[0]))[0]
    wanted = jnp.minimum(at + 1, topk)
    tau = jnp.take_along_axis(top, (wanted - 1)[:, None], axis=-1)
    return causal & (masked >= tau)


def attention(a, w, gains, spec, eps, operand, query_block, every_key):
    """One sequence: a (T, width) normalised input -> (T, width) and the
    sequence's L_I summed over its tokens.  A block of queries at a time
    against every key."""
    heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
        spec["head_width"]
    group = heads // kv_heads
    theta, topk = spec.get("theta", 1e4), spec["index_topk"]
    t = a.shape[0]
    q_i, k_i, w_i = indexer(jax.lax.stop_gradient(a), w, gains, spec, eps,
                            operand)
    q = product(a, w["w_q"], operand).reshape(t, heads, wide)
    k = product(a, w["w_k"], operand).reshape(t, kv_heads, wide)
    v = product(a, w["w_v"], operand).reshape(t, kv_heads, wide)
    q = rotary(rms_norm(q, gains["q_gain"], eps), theta)
    k = rotary(rms_norm(k, gains["k_gain"], eps), theta)
    # query head n = g * group + j reads KV head g
    q = _rounded(q, operand).reshape(t, kv_heads, group, wide)
    k, v = _rounded(k, operand), _rounded(v, operand)
    scale = 1.0 / numpy.sqrt(wide)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(part):
        q_block, qi_block, wi_block, at = part
        scores = index_scores(qi_block, k_i, wi_block)
        kept = kept_pairs(jax.lax.stop_gradient(scores), at, keys, topk)
        allowed = keys[None, :] <= at[:, None] if every_key else kept
        s = jnp.einsum("qgjd,kgd->gjqk", q_block, k) * scale
        p = jax.nn.softmax(jnp.where(allowed[None, None], s, -jnp.inf),
                           axis=-1)
        out = jnp.einsum("gjqk,kgd->qgjd", _rounded(p, operand), v)
        mean = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf),
                                   axis=-1)
        terms = jnp.where(kept & (mean > 0), mean * (jnp.log(
            jnp.where(mean > 0, mean, 1.0)) - jnp.where(kept, log_q, 0.0)),
            0.0)
        return out, jnp.sum(terms)

    o, kl = jax.lax.map(block, (
        _blocks(q, query_block), _blocks(q_i, query_block),
        _blocks(w_i, query_block), _blocks(keys, query_block)))
    return product(o.reshape(t, heads * wide), w["w_o"], operand), \
        jnp.sum(kl)


def route(m, w_router, top_k):
    """(experts chosen (N, top_k), their weights (N, top_k)): a softmax
    over every output, renormalised over the chosen; float32 products
    whatever ``operand``."""
    p = jax.nn.softmax(jnp.matmul(m, w_router), axis=-1)
    chosen = jnp.argsort(-p, axis=-1)[:, :top_k]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed(m, w, spec, operand):
    """sum over the HELD experts of w_i Expert_i(m), every held expert on
    every token, one after the other, and the tokens each was chosen
    for."""
    chosen, weight = route(m, w["w_router"], spec["top_k"])
    weight = weight * spec.get("routed_scale", 1.0)
    held = spec["experts_held"]

    @jax.checkpoint
    def add_expert(out, expert):
        index, w_gate, w_up, w_down = expert
        share = jnp.sum(jnp.where(chosen == index, weight, 0.0), axis=-1)
        return (out + share[:, None] * gated(m, w_gate, w_up, w_down,
                                             operand),
                jnp.sum(chosen == index))

    return jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (spec.get("first_expert", 0) + jnp.arange(held),
         w["e_gate"][:held], w["e_up"][:held], w["e_down"][:held]))


def sequence_layer(h, spec, w, gains, eps, operand, query_block,
                   token_block, every_key=False):
    """One sequence (T, width) -> (T, width), the routed load (experts
    held,) and the layer's L_I summed over the sequence's tokens."""
    attended, kl = attention(rms_norm(h, gains["attn_gain"], eps), w, gains,
                             spec, eps, operand, query_block, every_key)
    h = h + attended

    @jax.checkpoint
    def feed_forward(tokens):
        return routed(rms_norm(tokens, gains["ffn_gain"], eps), w, spec,
                      operand)

    out, load = jax.lax.map(feed_forward, _blocks(h, token_block))
    return h + out.reshape(h.shape), jnp.sum(load, axis=0), kl


@functools.lru_cache(maxsize=None)
def _jitted_layer(spec, operand, query_block, token_block, every_key):
    """(forward, backward) of one sequence through one layer, jitted;
    ``on`` is :func:`_rounded`'s flag.  ``backward(on, h, w, gains,
    d_out, d_kl)`` computes the forward again and returns the gradients
    by h, w and gains of <d_out, out> + d_kl L_I-sum."""
    def run(on, h, w, gains):
        return sequence_layer(h, spec, w, gains, spec.get("eps", 1e-6),
                              (operand, on), query_block, token_block,
                              every_key)

    def backward(on, h, w, gains, d_out, d_kl):
        def both(*args):
            out, _, kl = run(on, *args)
            return out, kl
        _, pull = jax.vjp(both, h, w, gains)
        return pull((d_out, d_kl))

    return jax.jit(run), jax.jit(backward)


def _layer_params(layers, params, width):
    """[(spec, pieces)] of the layers between embedding and head;
    ``pieces()`` slices the layer's (w, gains) out of its packed
    vectors when they are wanted, so one layer's copy is alive at a
    time."""
    def of(spec, entry):
        names, gain_names = layer_pieces(spec, width)
        return lambda: (split(entry["weights"], names),
                        split(entry["bias"], gain_names))
    return [(_hashable(spec), of(spec, entry))
            for spec, entry in zip(layers[1:-1], params[1:-1])]


def forward(layers, params, x, operand="float32", query_block=256,
            token_block=4096, with_load=False, lowered=True,
            every_key=False):
    """Logits (B, T, vocab held) of token ids ``x`` (B, T).  ``layers``
    are the zoo factory's specs, ``params`` one ``{"weights", "bias"}``
    a spec as the program packs them (host or device arrays).  A
    sequence and a layer at a time.  ``lowered`` False computes in
    float32 through the programs compiled for ``operand``; with
    ``with_load`` also the routed loads and each layer's L_I, summed
    over the rows' tokens."""
    how = (operand, query_block, token_block, every_key)
    on = jnp.asarray(bool(lowered))
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        head = _jitted_head(layers[-1].get("eps", 1e-6), operand)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        w_head = jnp.asarray(params[-1]["weights"], jnp.float32)
        logits, loads, kls = [], [0] * len(inner), [0.0] * len(inner)
        for row in numpy.asarray(x):
            h = table[jnp.asarray(row)]
            for i, (spec, pieces) in enumerate(inner):
                h, load, kl = _jitted_layer(spec, *how)[0](on, h,
                                                           *pieces())
                loads[i], kls[i] = loads[i] + load, kls[i] + kl
            logits.append(head(h, gain, w_head,
                               jnp.zeros(row.shape, jnp.int32), on)[0][1])
    logits = jnp.stack(logits)
    return (logits, loads, kls) if with_load else logits


def row_gradients(layers, params, row, targets, operand="float32",
                  query_block=256, token_block=4096, lowered=True,
                  every_key=False, indexer_loss=True):
    """One sequence's part of a step: (its next-token loss SUMMED over
    its targets, how many they are, its logits (T, vocab), the gradients
    of that sum plus the layers' L_I (each its mean over the row's
    tokens, times the targets counted: summed over the rows and divided
    by the targets, as the runner adds them, it is the objective's) as
    one ``{"weights", "bias"}`` of float32 arrays a spec, the routed
    loads).  Backward by hand, a layer at a time from the head down,
    each layer's forward computed again from its kept input.
    ``lowered`` as in :func:`forward`; ``indexer_loss`` False leaves the
    layers' L_I out (the next-token loss's gradients alone)."""
    how = (operand, query_block, token_block, every_key)
    on = jnp.asarray(bool(lowered))
    row, targets = numpy.asarray(row), numpy.asarray(targets)
    counted = int((targets >= 0).sum())
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        inputs, loads = [table[jnp.asarray(row)]], []
        for spec, pieces in inner:
            h, load, _ = _jitted_layer(spec, *how)[0](on, inputs[-1],
                                                      *pieces())
            inputs.append(h)
            loads.append(load)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        w_head = jnp.asarray(params[-1]["weights"], jnp.float32)
        (total, logits), (d_h, d_gain, d_w) = _jitted_head(
            layers[-1].get("eps", 1e-6), operand)(
                inputs.pop(), gain, w_head, jnp.asarray(targets), on)
        grads = [{"weights": d_w, "bias": d_gain}]
        del d_w
        d_kl = jnp.float32(counted / len(row) if indexer_loss else 0.0)
        for spec, pieces in inner[::-1]:
            d_h, d_w, d_gains = _jitted_layer(spec, *how)[1](
                on, inputs.pop(), *pieces(), d_h, d_kl)
            names, gain_names = layer_pieces(spec, table.shape[-1])
            grads.append({"weights": _flat(d_w, names),
                          "bias": _flat(d_gains, gain_names)})
            del d_w, d_gains
        grads.append({"weights": jnp.zeros_like(table).at[
            jnp.asarray(row)].add(d_h), "bias": None})
    return float(total), counted, logits, grads[::-1], loads


def loss_and_gradients(layers, params, x, targets, **how):
    """(mean next-token loss over every target of the minibatch, the
    objective's gradients as :func:`row_gradients` gives them): the
    rows' sums, added up."""
    total = count = 0
    grads = None
    for row, wanted in zip(numpy.asarray(x), numpy.asarray(targets)):
        part, n, _, mine, _ = row_gradients(layers, params, row, wanted,
                                            **how)
        total, count = total + part, count + n
        grads = mine if grads is None else add_gradients(grads, mine)
    return total / count, scale_gradients(grads, 1.0 / count)


def selection(layers, params, row, layer=0, query_block=256):
    """The reference's kept pairs (T, T) bool of one sequence in layer
    ``layer`` (of the layers between embedding and head), float32, from
    the seed's weights: what the program's selection is compared with."""
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        h = table[jnp.asarray(numpy.asarray(row))]
        how = ("float32", query_block, 4096, False)
        on = jnp.asarray(False)
        for spec, pieces in inner[:layer]:
            h = _jitted_layer(spec, *how)[0](on, h, *pieces())[0]
        spec, pieces = inner[layer]
        return _jitted_selection(spec, query_block)(h, *pieces())


@functools.lru_cache(maxsize=None)
def _jitted_selection(spec, query_block):
    def kept(h, w, gains):
        eps = spec.get("eps", 1e-6)
        q_i, k_i, w_i = indexer(rms_norm(h, gains["attn_gain"], eps), w,
                                gains, spec, eps, "float32")
        keys = jnp.arange(h.shape[0])
        return jax.lax.map(lambda part: kept_pairs(
            index_scores(part[0], k_i, part[1]), part[2], keys,
            spec["index_topk"]), (_blocks(q_i, query_block),
                                  _blocks(w_i, query_block),
                                  _blocks(keys, query_block))).reshape(
                                      h.shape[0], h.shape[0])
    return jax.jit(kept)


# -- operations and bytes, from shapes ----------------------------------------


def parameter_counts(arguments):
    """Matrix parameters held here, by part, from the factory's
    arguments (the norms' gains and the key norm's bias, a few thousand a
    layer, are left out)."""
    a = arguments
    width = a["width"]
    q_wide = a["heads"] * a["head_width"]
    kv_wide = a["kv_heads"] * a["head_width"]
    return {"attention": width * (q_wide + 2 * kv_wide) + q_wide * width,
            "indexer": width * (a["index_heads"] * a["index_width"]
                                + a["index_width"] + a["index_heads"]),
            "router": width * a["experts"],
            "expert": 3 * width * a["expert_width"],
            "vocabulary": a["vocab"] * width}


def selected_pairs(t, topk):
    """Pairs the selection keeps of a sequence of ``t`` tokens, ties
    aside: ``sum_t min(t + 1, topk)``."""
    return allowed_pairs(t, topk)


def step_cost(config, batch):
    """Operations and least bytes of one train step of ``batch`` rows,
    from shapes alone.  Operations are the MODEL's: 2 a multiply-add,
    forward + weight gradient + input gradient = 3 x the forward's.
    Attention counts the SELECTED pairs only, ``sum_t min(t + 1, topk)``
    a sequence, at the published head width for the query heads
    (``sparse_attention_flops``); the indexer (``indexer_flops``, all of
    what its scope runs) its three projections and their weights'
    gradient (no input gradient: its input is detached, so 2 x the
    forward), its scores over every causal pair (``index_heads``
    products ``index_width`` deep a pair) and their gradient by its
    query and key over the selected pairs (two such products a pair);
    a routed layer the
    assignments its held experts get when the router spreads evenly
    (tokens x top_k x held / experts).  Never the padded, masked,
    skipped-tile or recomputed work, so no share of a peak can read over
    100 % whatever implements it.  Bytes: the float32 state read and
    written once (weights, two moments, gradient)."""
    a = config["model"]["arguments"]
    t = config["input_shape"][0] - 1
    tokens = batch * t
    n = parameter_counts(a)
    layers = len(a["layer_types"])
    dense_layers = a.get("dense_layers", 0)
    routed_layers = layers - dense_layers
    assignments = tokens * a["top_k"] * a["experts_held"] / a["experts"]
    kept = batch * selected_pairs(t, a["index_topk"])
    index_product = 2 * a["index_heads"] * a["index_width"]
    sparse_flops = layers * kept * 3 * a["heads"] * 2 * 2 * a["head_width"]
    indexer_flops = layers * (2 * 2 * tokens * n["indexer"]
                              + batch * allowed_pairs(t) * index_product
                              + kept * 2 * index_product)
    routed_flops = 3 * routed_layers * assignments * 2 * n["expert"]
    matrix_flops = 3 * 2 * tokens * (
        layers * n["attention"] + routed_layers * n["router"]
        + n["vocabulary"]) + routed_flops
    held = (layers * (n["attention"] + n["indexer"])
            + routed_layers * (n["router"] + a["experts_held"] * n["expert"])
            + 2 * n["vocabulary"])
    flops = matrix_flops + sparse_flops + indexer_flops
    return {"flops": flops, "flops_per_image": flops / batch,
            "bytes": 7 * 4 * held, "parameters": held, "tokens": tokens,
            "attention_flops": sparse_flops,
            "sparse_attention_flops": sparse_flops,
            "indexer_flops": indexer_flops,
            "selected_pairs": layers * kept,
            "routed_flops": routed_flops,
            "routed_assignments": routed_layers * assignments}
