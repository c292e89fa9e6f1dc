"""The plain reference of the causal grouped-query decoder with window
and full layers mixed, sandwich norms and routed experts (configuration
``trinity_mini``): the layer equations of the ``afmoe`` family in
``jax.numpy``, float32, true-float32 products
(``jax.default_matmul_precision("highest")``), no kernel, no cache, and
nothing imported from the program (what no model's equations differ in —
the operand rounding, a product, RMSNorm, the gated feed-forward, the
loss, AdamW, the arithmetic on gradient lists — is the sibling
reference's, ``mla_moe_decoder.py``, imported, not copied).  Written
from the published ``config.json`` (its keys in brackets) and, where
that has no key, from
the family's published modelling code (marked †: ``modeling_afmoe.py``),
and from the layer specs the zoo factory returns; the packed parameter
layout is listed here again, by hand, so a program that packed
differently would disagree.

``h`` is the residual stream, ``rms_norm(x; g) = x / sqrt(mean(x^2) +
eps) * g`` (rms_norm_eps)::

    h = E[ids] * embed_scale          (mup_enabled: sqrt(hidden_size)) †
    a = rms_norm(h; g_attn)
    q = a W_q -> heads x head_width;  k = a W_k, v = a W_v -> kv_heads x
    head_width (num_attention_heads, num_key_value_heads, head_dim)
    z = a W_z -> heads x head_width            (gate_proj: the output gate) †
    q = rms_norm(q; g_q), k = rms_norm(k; g_k) over each head's width,
        one gain each, shared by the heads                     (QK-norm) †
    layers with ``rope`` (the windowed ones) only: rotary on q and k,
        pair (i, i + head_width / 2) of each head (rotate-half),
        positions from 0 (rope_theta; rope_scaling null);
        the full layers get no position signal at all                   †
    query head n reads KV head n // (heads / kv_heads)
    s_ij = q_i . k_j / sqrt(head_width), allowed where j <= i and, in a
        windowed layer, i - j < window        (layer_types, sliding_window)
    o = softmax_j(s) v * sigmoid(z)                                     †
    h = h + rms_norm(o W_o; g_post_attn)                 (sandwich norm) †
    m = rms_norm(h; g_ffn)
    dense layer:  f = (silu(m W_g) * (m W_u)) W_d
    routed layer: p = sigmoid(m W_r)                          (score_func)
                  chosen = the top_k largest of p + b   (one group; b takes
                                                         no gradient)
                  w_i = p_i / (sum_chosen p + 1e-20) * routed_scale
                                                  (route_norm, route_scale)
                  f = sum_{i chosen AND held} w_i Expert_i(m) + Shared(m)
    h = h + rms_norm(f; g_post_ffn)                      (sandwich norm) †
    logits = rms_norm(h; g_final) W_head

**The share.**  ``forward`` is given the experts held (a routed layer's
``first_expert``, ``experts_held``) and the vocabulary rows held (the
embedding's and the head's ``vocab``) in the layer specs: it routes over
all ``experts`` and adds only the held experts' terms — EVERY held expert
is evaluated on every token and weighted by the router's choice, no
sorting, no buffer, so no assignment can be dropped here.  The shares'
routed parts add up BEFORE ``g_post_ffn``: the norm is not additive.

``operand`` rounds both operands of every product but the router's
(float32 in the family's own code) to that dtype first: "float32" is the
reference, "bfloat16" what the program computes in, "float8_e4m3fn" the
control one precision below it (each tensor scaled to the format's
range), which the cell's tolerance must refuse.  Everything is computed
a sequence and a layer at a time, in blocks (``query_block`` queries of
attention, ``token_block`` tokens of the feed-forwards, one held expert
after the other), and a block's intermediates are computed again in the
backward pass: the float32 step of 8,192 tokens — :func:`row_gradients`,
:func:`adamw_step` — fits on the chip the program ran on.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

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
    weights = [
        ("w_q", (width, heads * wide)),
        ("w_k", (width, kv_heads * wide)),
        ("w_v", (width, kv_heads * wide)),
        ("w_z", (width, heads * wide)),
        ("w_o", (heads * wide, width)),
    ]
    bias = [("attn_gain", (width,)), ("q_gain", (wide,)),
            ("k_gain", (wide,)), ("post_attn_gain", (width,)),
            ("ffn_gain", (width,)), ("post_ffn_gain", (width,))]
    if spec.get("ffn"):
        weights += [("w_gate", (width, spec["ffn"])),
                    ("w_up", (width, spec["ffn"])),
                    ("w_down", (spec["ffn"], width))]
    else:
        held, expert = spec["experts_held"], spec["expert_width"]
        weights += [("w_router", (width, spec["experts"])),
                    ("e_gate", (held, width, expert)),
                    ("e_up", (held, width, expert)),
                    ("e_down", (held, expert, width)),
                    ("s_gate", (width, spec["shared_width"])),
                    ("s_up", (width, spec["shared_width"])),
                    ("s_down", (spec["shared_width"], width))]
        bias += [("router_bias", (spec["experts"],))]
    return weights, bias


# -- the equations ----------------------------------------------------------


def rotary(x, theta, first=0):
    """x (T, ..., width): pairs (x[i], x[i + width / 2]) of position t
    turn by t * theta ** (-2i / width) (the rotate-half pairing);
    positions count from ``first``."""
    t, width = x.shape[0], x.shape[-1]
    half = width // 2
    inverse = theta ** (-numpy.arange(0, width, 2) / width)
    angle = (first + numpy.arange(t))[:, None] * inverse[None, :]
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    cos = jnp.asarray(numpy.cos(angle), jnp.float32).reshape(shape)
    sin = jnp.asarray(numpy.sin(angle), jnp.float32).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def attention(a, w, gains, spec, eps, operand, query_block):
    """One sequence: a (T, width) normalised input -> (T, width), before
    the post-norm.  A block of queries at a time against every key, the
    keys after a query and those ``window`` and more before it masked; a
    block's scores are computed again in a backward pass, not kept
    (``jax.checkpoint``)."""
    heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
        spec["head_width"]
    group = heads // kv_heads
    window, theta = spec.get("window"), spec.get("theta", 1e4)
    t = a.shape[0]
    q = product(a, w["w_q"], operand).reshape(t, heads, wide)
    k = product(a, w["w_k"], operand).reshape(t, kv_heads, wide)
    v = product(a, w["w_v"], operand).reshape(t, kv_heads, wide)
    z = product(a, w["w_z"], operand)
    q = rms_norm(q, gains["q_gain"], eps)
    k = rms_norm(k, gains["k_gain"], eps)
    if spec.get("rope"):
        q, k = rotary(q, theta), rotary(k, theta)
    # query head n = g * group + j reads KV head g
    q = _rounded(q, operand).reshape(t, kv_heads, group, wide)
    k, v = _rounded(k, operand), _rounded(v, operand)
    scale = 1.0 / numpy.sqrt(wide)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(part):
        q_block, at = part
        s = jnp.einsum("qgjd,kgd->gjqk", q_block, k) * scale
        back = at[:, None] - keys[None, :]
        allowed = back >= 0
        if window:
            allowed = allowed & (back < window)
        s = jnp.where(allowed[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gjqk,kgd->qgjd", _rounded(p, operand), v)

    o = jax.lax.map(block, (_blocks(q, query_block),
                            _blocks(keys, query_block)))
    o = o.reshape(t, heads * wide) * sigmoid(z)
    return product(o, w["w_o"], operand)


def route(m, w_router, router_bias, top_k, scale, route_eps):
    """(experts chosen (N, top_k), their weights (N, top_k)): the
    router's products are float32 whatever ``operand``."""
    p = sigmoid(jnp.matmul(m, w_router))
    chosen = jnp.argsort(-(p + router_bias), axis=-1)[:, :top_k]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, axis=-1, keepdims=True)
                             + route_eps) * scale


def routed(m, w, gains, spec, operand):
    """sum over the HELD experts of w_i Expert_i(m), every held expert on
    every token, one after the other, and the tokens each was chosen
    for."""
    chosen, weight = route(m, w["w_router"], gains["router_bias"],
                           spec["top_k"], spec.get("routed_scale", 1.0),
                           spec.get("route_eps", 0.0))
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


def feed_forward_sum(m, spec, w, gains, operand):
    """``f`` of the equations, BEFORE the post-norm: the dense
    feed-forward, or the held experts' terms plus the shared expert; and
    the routed load ((0,) for a dense layer)."""
    if spec.get("ffn"):
        return (gated(m, w["w_gate"], w["w_up"], w["w_down"], operand),
                jnp.zeros((0,), jnp.int32))
    part, load = routed(m, w, gains, spec, operand)
    return part + gated(m, w["s_gate"], w["s_up"], w["s_down"],
                        operand), load


def sequence_layer(h, spec, w, gains, eps, operand, query_block,
                   token_block):
    """One sequence (T, width) -> (T, width), and the routed load
    ((experts held,); (0,) for a dense layer)."""
    h = h + rms_norm(
        attention(rms_norm(h, gains["attn_gain"], eps), w, gains, spec,
                  eps, operand, query_block), gains["post_attn_gain"], eps)

    @jax.checkpoint
    def feed_forward(tokens):
        f, load = feed_forward_sum(
            rms_norm(tokens, gains["ffn_gain"], eps), spec, w, gains,
            operand)
        return rms_norm(f, gains["post_ffn_gain"], eps), load

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


def embedded(layers, table, row):
    """E[ids] times the embedding's input multiplier."""
    return table[jnp.asarray(row)] * jnp.float32(
        layers[0].get("scale", 1.0))


def forward(layers, params, x, operand="float32", query_block=512,
            token_block=4096, with_load=False, lowered=True):
    """Logits (B, T, vocab held) of token ids ``x`` (B, T).  ``layers``
    are the zoo factory's specs, ``params`` one ``{"weights", "bias"}``
    a spec as the program packs them (host or device arrays).  A
    sequence and a layer at a time.  ``lowered`` False computes in
    float32 through the programs compiled for ``operand``."""
    how = (operand, query_block, token_block)
    on = jnp.asarray(bool(lowered))
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        head = _jitted_head(layers[-1].get("eps", 1e-5), operand)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        w_head = jnp.asarray(params[-1]["weights"], jnp.float32)
        logits, loads = [], [0] * len(inner)
        for row in numpy.asarray(x):
            h = embedded(layers, table, row)
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
    forward computed again from its kept input: what is alive on the
    device is one layer's pieces, their gradients and one block's
    scores, so the float32 backward of 8,192 tokens fits the chip.
    ``lowered`` as in :func:`forward`."""
    how = (operand, query_block, token_block)
    on = jnp.asarray(bool(lowered))
    row, targets = numpy.asarray(row), numpy.asarray(targets)
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        inputs, loads = [embedded(layers, table, row)], []
        for spec, pieces in inner:
            h, load = _jitted_layer(spec, *how)[0](on, inputs[-1],
                                                   *pieces())
            inputs.append(h)
            if not spec.get("ffn"):
                loads.append(load)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        w_head = jnp.asarray(params[-1]["weights"], jnp.float32)
        (total, logits), (d_h, d_gain, d_w) = _jitted_head(
            layers[-1].get("eps", 1e-5), operand)(
                inputs.pop(), gain, w_head, jnp.asarray(targets), on)
        grads = [{"weights": d_w, "bias": d_gain}]
        del d_w
        for spec, pieces in inner[::-1]:
            d_h, d_w, d_gains = _jitted_layer(spec, *how)[1](
                on, inputs.pop(), *pieces(), d_h)
            names, gain_names = layer_pieces(spec, table.shape[-1])
            grads.append({"weights": _flat(d_w, names),
                          "bias": _flat(d_gains, gain_names)})
            del d_w, d_gains
        grads.append({"weights": jnp.zeros_like(table).at[
            jnp.asarray(row)].add(d_h * jnp.float32(
                layers[0].get("scale", 1.0))), "bias": None})
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
    arguments (the norms' gains, a few thousand a layer, are left
    out)."""
    a = arguments
    width = a["width"]
    q_wide = a["heads"] * a["head_width"]
    kv_wide = a["kv_heads"] * a["head_width"]
    return {"attention": width * (2 * q_wide + 2 * kv_wide)
            + q_wide * width,
            "dense_ffn": 3 * width * a["ffn"],
            "router": width * a["experts"],
            "expert": 3 * width * a["expert_width"],
            "shared": 3 * width * a["shared_width"],
            "vocabulary": a["vocab"] * width}


def allowed_pairs(t, window=None):
    """(query, key) pairs a causal sequence of ``t`` tokens attends
    over: ``sum_i min(i + 1, window)``, every earlier key without
    one."""
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def step_cost(config, batch):
    """Operations and least bytes of one train step of ``batch`` rows,
    from shapes alone.  Operations are the MODEL's: 2 a multiply-add,
    forward + weight gradient + input gradient = 3 x the forward's;
    attention counts the allowed pairs only — T (T + 1) / 2 a sequence
    in a full layer, W (W + 1) / 2 + (T - W) W in a windowed one — at
    the published head width, for the 32 query heads (the grouped keys
    change what is read, not what is multiplied); a routed layer counts
    the assignments its held experts get when the router spreads evenly
    (tokens x top_k x held / experts).  Never the padded, masked or
    recomputed work, so no share of a peak can read over 100 % whatever
    implements it.  Bytes: the float32 state read and written once
    (weights, two moments, gradient)."""
    a = config["model"]["arguments"]
    t = config["input_shape"][0] - 1
    tokens = batch * t
    n = parameter_counts(a)
    layers = len(a["layer_types"])
    windowed = sum(kind == "window" for kind in a["layer_types"])
    dense_layers = a.get("dense_layers", 1)
    routed_layers = layers - dense_layers
    assignments = tokens * a["top_k"] * a["experts_held"] / a["experts"]
    per_pair = 3 * batch * a["heads"] * 2 * 2 * a["head_width"]
    window_flops = windowed * allowed_pairs(t, a["window"]) * per_pair
    full_flops = (layers - windowed) * allowed_pairs(t) * per_pair
    routed_flops = 3 * routed_layers * assignments * 2 * n["expert"]
    matrix_flops = 3 * 2 * tokens * (
        layers * n["attention"] + dense_layers * n["dense_ffn"]
        + routed_layers * (n["router"] + n["shared"])
        + n["vocabulary"]) + routed_flops
    held = (layers * n["attention"] + dense_layers * n["dense_ffn"]
            + routed_layers * (n["router"] + n["shared"]
                               + a["experts_held"] * n["expert"])
            + 2 * n["vocabulary"])
    flops = matrix_flops + window_flops + full_flops
    return {"flops": flops, "flops_per_image": flops / batch,
            "bytes": 7 * 4 * held, "parameters": held, "tokens": tokens,
            "attention_flops": window_flops + full_flops,
            "window_attention_flops": window_flops,
            "full_attention_flops": full_flops,
            "routed_flops": routed_flops,
            "routed_assignments": routed_layers * assignments}
