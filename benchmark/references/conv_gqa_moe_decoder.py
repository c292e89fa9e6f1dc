"""The plain reference of the causal decoder whose token mixer is a
gated short convolution in most layers and grouped-query attention in
the rest, over routed experts with no shared one and a head tied to the
embedding (configuration ``lfm2_8b_a1b``): the layer equations of the
``lfm2_moe`` family in ``jax.numpy``, float32, true-float32 products
(``jax.default_matmul_precision("highest")``), no kernel, no cache, and
nothing imported from the program (what no model's equations differ in —
the operand rounding, a product, RMSNorm, the gated feed-forward, the
loss, AdamW, the arithmetic on gradient lists — is the sibling
reference's, ``mla_moe_decoder.py``, imported, not copied; the
rotate-half rotary, the sigmoid, the router and the held experts' sum
are the other sibling's, ``gqa_window_moe_decoder.py``).  Written from
the published ``config.json`` (its keys in brackets) and, where that has
no key, from the family's published modelling code (marked †:
``modeling_lfm2_moe.py``), and from the layer specs the zoo factory
returns; the packed parameter layout is listed here again, by hand, so a
program that packed differently would disagree.

``h`` is the residual stream, ``rms_norm(x; g) = x / sqrt(mean(x^2) +
eps) * g`` (norm_eps)::

    h = E[ids]
    every layer: a = rms_norm(h; g_operator)               (operator_norm) †
    conv layer (layer_types "conv"):
        [B | C | x] = a W_in       W_in (width, 3 width), no bias (conv_bias)
        u = B * x
        c[t] = sum_{j < L} k[:, j] * u[t - (L - 1) + j],  u[t < 0] = 0
                (conv_L_cache L = 3: depthwise, one filter a channel, causal)
        h = h + (C * c) W_out
    attention layer ("full_attention"): head width = width / heads †
        q = a W_q -> heads x head_width;  k = a W_k, v = a W_v -> kv_heads
        x head_width               (num_attention_heads, num_key_value_heads)
        q = rms_norm(q; g_q), k = rms_norm(k; g_k) over each head's width,
            one gain each, shared by the heads                   (QK-norm) †
        rotary on q and k in EVERY attention layer, pair (i, i + head_width
            / 2) of each head, positions from 0                (rope_theta)
        query head n reads KV head n // (heads / kv_heads)
        s_ij = q_i . k_j / sqrt(head_width), allowed where j <= i
        h = h + softmax_j(s) v W_o                       (no gate, no bias)
    m = rms_norm(h; g_ffn)                                       (ffn_norm) †
    dense layer (index < num_dense_layers):
        h = h + (silu(m W_1) * (m W_3)) W_2              (intermediate_size)
    routed layer: p = sigmoid(m W_r), float32                 (num_experts)
        chosen = the top_k largest of p + b  (use_expert_bias; b takes no
                                              gradient)
        w_i = p_i / (sum_chosen p + 1e-6) * routed_scale   (norm_topk_prob,
                                     routed_scaling_factor; the 1e-6 †)
        h = h + sum_{i chosen AND held} w_i Expert_i(m)     (NO shared expert)
    logits = rms_norm(h; g_final) E^T   (the final norm †; the head is the
                                         embedding's table †)

**The share.**  ``forward`` is given the experts held (a routed layer's
``first_expert``, ``experts_held``) and the vocabulary rows held (the
embedding's ``vocab``) in the layer specs: it routes over all
``experts`` and adds only the held experts' terms — EVERY held expert is
evaluated on every token and weighted by the router's choice, no
sorting, no buffer, so no assignment can be dropped here.  With no
shared expert and no norm after the sub-layer, the shares' routed parts
add up to the whole layer's.

**The tie.**  The head spec's ``tied_to`` names the embedding: ONE table
serves both uses, the head's entry of ``params`` holds the final norm's
gain only (its ``weights`` is None), and the table's gradient is the sum
of the embedding's scatter and the head's product — returned in the
embedding's entry; the head's entry carries None for the matrix.

``operand`` and the blocking as in the siblings.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from benchmark.references.gqa_window_moe_decoder import (  # noqa: F401
    allowed_pairs, rotary, route, routed, sigmoid)
from benchmark.references.mla_moe_decoder import (  # noqa: F401
    _blocks, _flat, _hashable, _jitted_head, _rounded, adamw_step,
    add_gradients, gated, loss, product, rms_norm, scale_gradients, silu,
    split)


# -- the packed layout, listed by hand ----------------------------------------


def layer_pieces(spec, width):
    """([(name, shape)] of a layer's packed weights, of its packed bias),
    in packing order."""
    if spec.get("conv_taps"):
        weights = [("w_in", (width, 3 * width)),
                   ("conv_k", (width, spec["conv_taps"])),
                   ("w_out", (width, width))]
        bias = [("conv_gain", (width,))]
    else:
        heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
            spec["head_width"]
        weights = [("w_q", (width, heads * wide)),
                   ("w_k", (width, kv_heads * wide)),
                   ("w_v", (width, kv_heads * wide)),
                   ("w_o", (heads * wide, width))]
        bias = [("attn_gain", (width,)), ("q_gain", (wide,)),
                ("k_gain", (wide,))]
    bias += [("ffn_gain", (width,))]
    if spec.get("ffn"):
        weights += [("w_gate", (width, spec["ffn"])),
                    ("w_up", (width, spec["ffn"])),
                    ("w_down", (spec["ffn"], width))]
    else:
        held, expert = spec["experts_held"], spec["expert_width"]
        weights += [("w_router", (width, spec["experts"])),
                    ("e_gate", (held, width, expert)),
                    ("e_up", (held, width, expert)),
                    ("e_down", (held, expert, width))]
        bias += [("router_bias", (spec["experts"],))]
    return weights, bias


# -- the equations ----------------------------------------------------------


def short_conv(a, w, operand):
    """One sequence: a (T, width) normalised input -> (T, width).  The
    filter is elementwise (no product's operands to round): tap j of
    channel d weighs the gated input L - 1 - j positions back, and
    nothing lies before position 0."""
    t, width = a.shape
    taps = w["conv_k"]
    length = taps.shape[1]
    bcx = product(a, w["w_in"], operand)
    gate_in, gate_out, x = (bcx[:, :width], bcx[:, width:2 * width],
                            bcx[:, 2 * width:])
    u = jnp.concatenate(
        [jnp.zeros((length - 1, width), jnp.float32), gate_in * x])
    c = jnp.zeros((t, width), jnp.float32)
    for j in range(length):
        c = c + taps[:, j][None, :] * u[j:j + t]
    return product(gate_out * c, w["w_out"], operand)


def attention(a, w, gains, spec, eps, operand, query_block):
    """One sequence: a (T, width) normalised input -> (T, width).  A
    block of queries at a time against every key, the keys after a query
    masked; a block's scores are computed again in a backward pass, not
    kept (``jax.checkpoint``)."""
    heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
        spec["head_width"]
    group = heads // kv_heads
    t = a.shape[0]
    q = product(a, w["w_q"], operand).reshape(t, heads, wide)
    k = product(a, w["w_k"], operand).reshape(t, kv_heads, wide)
    v = product(a, w["w_v"], operand).reshape(t, kv_heads, wide)
    q = rotary(rms_norm(q, gains["q_gain"], eps), spec.get("theta", 1e6))
    k = rotary(rms_norm(k, gains["k_gain"], eps), spec.get("theta", 1e6))
    # query head n = g * group + j reads KV head g
    q = _rounded(q, operand).reshape(t, kv_heads, group, wide)
    k, v = _rounded(k, operand), _rounded(v, operand)
    scale = 1.0 / numpy.sqrt(wide)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(part):
        q_block, at = part
        s = jnp.einsum("qgjd,kgd->gjqk", q_block, k) * scale
        s = jnp.where((keys[None, :] <= at[:, None])[None, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gjqk,kgd->qgjd", _rounded(p, operand), v)

    o = jax.lax.map(block, (_blocks(q, query_block),
                            _blocks(keys, query_block)))
    return product(o.reshape(t, heads * wide), w["w_o"], operand)


def sequence_layer(h, spec, w, gains, eps, operand, query_block,
                   token_block):
    """One sequence (T, width) -> (T, width), and the routed load
    ((experts held,); (0,) for a dense layer)."""
    if spec.get("conv_taps"):
        h = h + short_conv(rms_norm(h, gains["conv_gain"], eps), w,
                           operand)
    else:
        h = h + attention(rms_norm(h, gains["attn_gain"], eps), w, gains,
                          spec, eps, operand, query_block)

    @jax.checkpoint
    def feed_forward(tokens):
        m = rms_norm(tokens, gains["ffn_gain"], eps)
        if spec.get("ffn"):
            return (gated(m, w["w_gate"], w["w_up"], w["w_down"], operand),
                    jnp.zeros((0,), jnp.int32))
        return routed(m, w, gains, spec, operand)

    out, load = jax.lax.map(feed_forward, _blocks(h, token_block))
    return h + out.reshape(h.shape), jnp.sum(load, axis=0)


def layer(h, spec, w, gains, eps, operand, query_block, token_block):
    """(B, T, width) -> (B, T, width), and the routed load (or None)."""
    rows = [sequence_layer(row, spec, w, gains, eps, operand, query_block,
                           token_block) for row in h]
    load = sum(load for _, load in rows)
    return jnp.stack([out for out, _ in rows]), \
        (None if spec.get("ffn") else load)


@functools.lru_cache(maxsize=None)
def _jitted_layer(spec, operand, query_block, token_block):
    """(forward, backward) of one sequence through one layer, jitted;
    ``on`` is :func:`_rounded`'s flag.  ``backward(on, h, w, gains,
    d_out)`` computes the forward again and returns the gradients by h,
    w and gains."""
    def run(on, h, w, gains):
        return sequence_layer(h, spec, w, gains, spec.get("eps", 1e-5),
                              (operand, on), query_block, token_block)

    def backward(on, h, w, gains, d_out):
        _, pull = jax.vjp(lambda *args: run(on, *args)[0], h, w, gains)
        return pull(d_out)

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


def head_matrix(layers, params, table):
    """The head's (width, vocab) matrix: the table's transpose where the
    head spec is tied to the embedding, else the head's own."""
    if layers[-1].get("tied_to") is None:
        return jnp.asarray(params[-1]["weights"], jnp.float32)
    assert layers[-1]["tied_to"] == 0 and params[-1]["weights"] is None
    return table.T


def forward(layers, params, x, operand="float32", query_block=512,
            token_block=4096, with_load=False, lowered=True):
    """Logits (B, T, vocab held) of token ids ``x`` (B, T).  ``layers``
    are the zoo factory's specs, ``params`` one ``{"weights", "bias"}``
    a spec as the program packs them (host or device arrays; a tied
    head's ``weights`` is None).  A sequence and a layer at a time.
    ``lowered`` False computes in float32 through the programs compiled
    for ``operand``."""
    how = (operand, query_block, token_block)
    on = jnp.asarray(bool(lowered))
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        head = _jitted_head(layers[-1].get("eps", 1e-5), operand)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        w_head = head_matrix(layers, params, table)
        logits, loads = [], [0] * len(inner)
        for row in numpy.asarray(x):
            h = table[jnp.asarray(row)]
            for i, (spec, pieces) in enumerate(inner):
                h, load = _jitted_layer(spec, *how)[0](on, h, *pieces())
                loads[i] = loads[i] + load
            logits.append(head(h, gain, w_head,
                               jnp.zeros(row.shape, jnp.int32), on)[0][1])
    logits = jnp.stack(logits)
    loads = [load for load, (spec, _) in zip(loads, inner)
             if not spec.get("ffn")]
    return (logits, loads) if with_load else logits


def row_gradients(layers, params, row, targets, operand="float32",
                  query_block=512, token_block=4096, lowered=True):
    """One sequence's part of a step: (its loss SUMMED over its targets,
    how many they are, its logits (T, vocab), the gradients of that sum
    as one ``{"weights", "bias"}`` of float32 arrays a spec, shaped as
    the parameters are and left on the device, the routed loads).
    Backward by hand, a layer at a time from the head down, each layer's
    forward computed again from its kept input.  Tied, the table's
    gradient is the embedding's scatter PLUS the head's product
    (transposed), and the head's own matrix gradient is None.
    ``lowered`` as in :func:`forward`."""
    how = (operand, query_block, token_block)
    on = jnp.asarray(bool(lowered))
    row, targets = numpy.asarray(row), numpy.asarray(targets)
    tied = layers[-1].get("tied_to") is not None
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        inputs, loads = [table[jnp.asarray(row)]], []
        for spec, pieces in inner:
            h, load = _jitted_layer(spec, *how)[0](on, inputs[-1],
                                                   *pieces())
            inputs.append(h)
            if not spec.get("ffn"):
                loads.append(load)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        (total, logits), (d_h, d_gain, d_w) = _jitted_head(
            layers[-1].get("eps", 1e-5), operand)(
                inputs.pop(), gain, head_matrix(layers, params, table),
                jnp.asarray(targets), on)
        grads = [{"weights": None if tied else d_w, "bias": d_gain}]
        d_table = d_w.T if tied else jnp.zeros_like(table)
        del d_w
        for spec, pieces in inner[::-1]:
            d_h, d_w, d_gains = _jitted_layer(spec, *how)[1](
                on, inputs.pop(), *pieces(), d_h)
            names, gain_names = layer_pieces(spec, table.shape[-1])
            grads.append({"weights": _flat(d_w, names),
                          "bias": _flat(d_gains, gain_names)})
            del d_w, d_gains
        grads.append({"weights": d_table.at[jnp.asarray(row)].add(d_h),
                      "bias": None})
    return (float(total), int((targets >= 0).sum()), logits, grads[::-1],
            loads)


def loss_and_gradients(layers, params, x, targets, **how):
    """(mean loss over every target of the minibatch, its gradients as
    :func:`row_gradients` gives them): the rows' sums, added up."""
    total = count = 0
    grads = None
    for row, wanted in zip(numpy.asarray(x), numpy.asarray(targets)):
        part, n, _, mine, _ = row_gradients(layers, params, row, wanted,
                                            **how)
        total, count = total + part, count + n
        grads = mine if grads is None else add_gradients(grads, mine)
    return total / count, scale_gradients(grads, 1.0 / count)


# -- operations and bytes, from shapes ----------------------------------------


def parameter_counts(arguments):
    """Matrix parameters held here, by part, from the factory's
    arguments (the norms' gains, a few thousand a layer, are left out;
    the filter's ``width x conv_taps`` is counted with its mixer).  The
    vocabulary's table counts ONCE where the head is tied."""
    a = arguments
    width = a["width"]
    q_wide = a["heads"] * a["head_width"]
    kv_wide = a["kv_heads"] * a["head_width"]
    return {"conv": width * 3 * width + width * a["conv_taps"]
            + width * width,
            "attention": width * (q_wide + 2 * kv_wide) + q_wide * width,
            "dense_ffn": 3 * width * a["ffn"],
            "router": width * a["experts"],
            "expert": 3 * width * a["expert_width"],
            "vocabulary": a["vocab"] * width,
            "tables": 1 if a.get("tied_head", True) else 2}


def step_cost(config, batch):
    """Operations and least bytes of one train step of ``batch`` rows,
    from shapes alone.  Operations are the MODEL's: 2 a multiply-add,
    forward + weight gradient + input gradient = 3 x the forward's;
    attention counts the causal pairs only, T (T + 1) / 2 a sequence, at
    the PUBLISHED head width (64: never the lane tile a kernel pads it
    to) for the 32 query heads; the filter 2 x ``conv_taps`` operations
    a channel a token and the two gates one each; the table twice (the
    embedding is a gather, the head a product: tied or not, the head's
    product is one); a routed layer counts the assignments its held
    experts get when the router spreads evenly (tokens x top_k x held /
    experts).  Never the padded, masked or recomputed work, so no share
    of a peak can read over 100 % whatever implements it.  Bytes: the
    float32 state read and written once (weights, two moments,
    gradient)."""
    a = config["model"]["arguments"]
    t = config["input_shape"][0] - 1
    tokens = batch * t
    n = parameter_counts(a)
    kinds = a["layer_types"]
    conv_layers = sum(kind == "conv" for kind in kinds)
    attention_layers = len(kinds) - conv_layers
    dense_layers = a.get("dense_layers", 1)
    routed_layers = len(kinds) - dense_layers
    assignments = tokens * a["top_k"] * a["experts_held"] / a["experts"]
    full_flops = (attention_layers * allowed_pairs(t) * 3 * batch
                  * a["heads"] * 2 * 2 * a["head_width"])
    filter_flops = 3 * conv_layers * tokens * a["width"] * (
        2 * a["conv_taps"] + 2)
    conv_matrix = n["conv"] - a["width"] * a["conv_taps"]
    routed_flops = 3 * routed_layers * assignments * 2 * n["expert"]
    matrix_flops = 3 * 2 * tokens * (
        conv_layers * conv_matrix + attention_layers * n["attention"]
        + dense_layers * n["dense_ffn"] + routed_layers * n["router"]
        + n["vocabulary"]) + routed_flops
    held = (conv_layers * n["conv"] + attention_layers * n["attention"]
            + dense_layers * n["dense_ffn"]
            + routed_layers * (n["router"]
                               + a["experts_held"] * n["expert"])
            + n["tables"] * n["vocabulary"])
    flops = matrix_flops + full_flops + filter_flops
    return {"flops": flops, "flops_per_image": flops / batch,
            "bytes": 7 * 4 * held, "parameters": held, "tokens": tokens,
            "attention_flops": full_flops,
            "full_attention_flops": full_flops,
            "short_conv_flops": filter_flops
            + 3 * 2 * tokens * conv_layers * conv_matrix,
            "routed_flops": routed_flops,
            "routed_assignments": routed_layers * assignments}
