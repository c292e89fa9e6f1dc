"""The plain reference of the causal MLA + routed-experts decoder
(configuration ``kanana2_30b_a3b``): the DeepSeek-V3 layer equations in
``jax.numpy``, float32, true-float32 products
(``jax.default_matmul_precision("highest")``), no kernel, no cache, and
nothing imported from the program.  Written from the published
description (config.json keys in brackets) and from the layer specs the
zoo factory returns; the packed parameter layout is listed here again,
by hand, so a program that packed differently would disagree.

A layer, ``h`` the residual stream::

    a = rms_norm(h) * g_attn                          (rms_norm_eps)
    q = a W_q  -> heads x [nope | rope]               (q_lora_rank null)
    [c | k_rope] = a W_kva; c = rms_norm(c) * g_kv    (kv_lora_rank)
    [k_nope | v] = c W_kvb -> heads x [nope | v_head]
    rotary on q_rope (per head) and k_rope (shared), adjacent pairs
                                          (rope_interleave, rope_theta)
    s = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope), causal
    h += softmax(s) v W_o
    m = rms_norm(h) * g_ffn
    dense layer:  h += (silu(m W_g) * (m W_u)) W_d
    routed layer: p = sigmoid(m W_r)                  (scoring_func)
                  chosen = the top_k largest of p + b (noaux_tc; one group)
                  w_i = p_i / sum_chosen p * routed_scale (norm_topk_prob)
                  h += sum_{i chosen AND held} w_i Expert_i(m) + Shared(m)

**The share.**  ``forward`` is given the experts held (a routed layer's
``first_expert``, ``experts_held``) and the vocabulary rows held (the
embedding's and the head's ``vocab``) in the layer specs: it routes over
all ``experts`` and adds only the held experts' terms — EVERY held expert
is evaluated on every token and weighted by the router's choice, no
sorting, no buffer, so no assignment can be dropped here.

``operand`` rounds both operands of every product but the router's
(float32 in the family's own code) to that dtype first: "float32" is the
reference, "bfloat16" what the program computes in, "float8_e4m3fn" the
control one precision below it (each tensor scaled to the format's
range), which the cell's tolerance must refuse.  Everything is computed
a sequence and a layer at a time, in blocks (``query_block`` queries of
attention, ``token_block`` tokens of the feed-forwards, one held expert
after the other), and a block's intermediates are computed again in the
backward pass: the float32 step of 2 x 8,192 tokens —
:func:`row_gradients`, :func:`adamw_step` — fits on the chip the program
ran on.
"""

import functools

import jax
import jax.numpy as jnp
import numpy


# -- the packed layout, listed by hand ----------------------------------------


def layer_pieces(spec, width):
    """([(name, shape)] of a layer's packed weights, of its packed bias),
    in packing order."""
    heads = spec["heads"]
    weights = [
        ("w_q", (width, heads * (spec["qk_nope"] + spec["qk_rope"]))),
        ("w_kva", (width, spec["kv_rank"] + spec["qk_rope"])),
        ("w_kvb", (spec["kv_rank"],
                   heads * (spec["qk_nope"] + spec["v_head"]))),
        ("w_o", (heads * spec["v_head"], width)),
    ]
    bias = [("attn_gain", (width,)), ("kv_gain", (spec["kv_rank"],)),
            ("ffn_gain", (width,))]
    if spec.get("ffn"):
        weights += [("w_gate", (width, spec["ffn"])),
                    ("w_up", (width, spec["ffn"])),
                    ("w_down", (spec["ffn"], width))]
    else:
        held, wide = spec["experts_held"], spec["expert_width"]
        weights += [("w_router", (width, spec["experts"])),
                    ("e_gate", (held, width, wide)),
                    ("e_up", (held, width, wide)),
                    ("e_down", (held, wide, width)),
                    ("s_gate", (width, spec["shared_width"])),
                    ("s_up", (width, spec["shared_width"])),
                    ("s_down", (spec["shared_width"], width))]
        bias += [("router_bias", (spec["experts"],))]
    return weights, bias


def split(vector, pieces):
    out, offset = {}, 0
    for name, shape in pieces:
        size = int(numpy.prod(shape))
        out[name] = jnp.asarray(vector[offset:offset + size],
                                jnp.float32).reshape(shape)
        offset += size
    assert offset == vector.shape[0], (offset, vector.shape)
    return out


# -- the equations ----------------------------------------------------------


def _rounded(x, operand):
    """``x`` as a product's operand of dtype ``operand``, back in
    float32.  An 8-bit float is scaled to the format's range first, one
    scale a tensor, as 8-bit products are run: unscaled, weights of std
    0.02 would fall among its subnormals and the control would measure
    underflow, not rounding.  ``operand`` may be ``(dtype, on)`` with
    ``on`` a traced flag: the rounding is in the program and applies
    where the flag is set, so the float32 reference and its control are
    ONE compiled program (a float32 product costs the chip's compiler
    seconds a shape)."""
    on = True
    if not isinstance(operand, str):
        operand, on = operand
    if operand == "float32":
        return x
    dtype = jnp.dtype(operand)
    if dtype.itemsize > 1:
        low = x.astype(dtype).astype(jnp.float32)
    else:
        scale = float(jnp.finfo(dtype).max) / jnp.maximum(
            jnp.max(jnp.abs(x)), 1e-30)
        low = (x * scale).astype(dtype).astype(jnp.float32) / scale
    return low if on is True else jnp.where(on, low, x)


def product(a, b, operand):
    """a @ b with both operands rounded to ``operand``, float32 sums."""
    return jnp.matmul(_rounded(a, operand), _rounded(b, operand))


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def rotary(x, theta, first=0):
    """x (T, ..., width): pairs (x[2i], x[2i+1]) of position t turn by
    t * theta ** (-2i / width); positions count from ``first``."""
    t, width = x.shape[0], x.shape[-1]
    pairs = x.reshape(x.shape[:-1] + (width // 2, 2))
    inverse = theta ** (-numpy.arange(0, width, 2) / width)
    angle = (first + numpy.arange(t))[:, None] * inverse[None, :]
    shape = (t,) + (1,) * (x.ndim - 2) + (width // 2,)
    cos = jnp.asarray(numpy.cos(angle), jnp.float32).reshape(shape)
    sin = jnp.asarray(numpy.sin(angle), jnp.float32).reshape(shape)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gated(m, w_gate, w_up, w_down, operand):
    return product(silu(product(m, w_gate, operand))
                   * product(m, w_up, operand), w_down, operand)


def _blocks(x, block):
    """(N, ...) -> (N / block, block, ...); one block if N does not
    divide."""
    if x.shape[0] % block:
        block = x.shape[0]
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def attention(a, w, gains, spec, eps, operand, query_block):
    """One sequence: a (T, width) normalised input -> (T, width).  A
    block of queries at a time against every key, the keys after a query
    masked; a block's scores are computed again in a backward pass, not
    kept (``jax.checkpoint``)."""
    heads, nope, rope = spec["heads"], spec["qk_nope"], spec["qk_rope"]
    v_head, rank = spec["v_head"], spec["kv_rank"]
    theta = spec.get("theta", 1e6)
    t = a.shape[0]
    q = product(a, w["w_q"], operand).reshape(t, heads, nope + rope)
    kva = product(a, w["w_kva"], operand)
    c = rms_norm(kva[:, :rank], gains["kv_gain"], eps)
    k_rope = _rounded(rotary(kva[:, rank:], theta), operand)  # (T, rope)
    kv = product(c, w["w_kvb"], operand).reshape(t, heads, nope + v_head)
    k_nope = _rounded(kv[..., :nope], operand)
    v = _rounded(kv[..., nope:], operand)
    q_nope = _rounded(q[..., :nope], operand)
    q_rope = _rounded(rotary(q[..., nope:], theta), operand)
    scale = 1.0 / numpy.sqrt(nope + rope)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(part):
        q_n, q_r, at = part
        s = (jnp.einsum("qhd,khd->hqk", q_n, k_nope)
             + jnp.einsum("qhd,kd->hqk", q_r, k_rope)) * scale
        s = jnp.where(keys[None, None, :] <= at[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _rounded(p, operand), v)

    o = jax.lax.map(block, (_blocks(q_nope, query_block),
                            _blocks(q_rope, query_block),
                            _blocks(keys, query_block)))
    return product(o.reshape(t, heads * v_head), w["w_o"], operand)


def route(m, w_router, router_bias, top_k, scale):
    """(experts chosen (N, top_k), their weights (N, top_k)): the
    router's products are float32 whatever ``operand``."""
    p = 1.0 / (1.0 + jnp.exp(-jnp.matmul(m, w_router)))
    chosen = jnp.argsort(-(p + router_bias), axis=-1)[:, :top_k]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True) * scale


def routed(m, w, gains, spec, operand):
    """sum over the HELD experts of w_i Expert_i(m), every held expert on
    every token, one after the other, and the tokens each was chosen
    for."""
    chosen, weight = route(m, w["w_router"], gains["router_bias"],
                           spec["top_k"], spec.get("routed_scale", 1.0))
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
                   token_block):
    """One sequence (T, width) -> (T, width), and the routed load
    ((experts held,); (0,) for a dense layer)."""
    h = h + attention(rms_norm(h, gains["attn_gain"], eps), w, gains,
                      spec, eps, operand, query_block)

    @jax.checkpoint
    def feed_forward(tokens):
        m = rms_norm(tokens, gains["ffn_gain"], eps)
        if spec.get("ffn"):
            return (gated(m, w["w_gate"], w["w_up"], w["w_down"], operand),
                    jnp.zeros((0,), jnp.int32))
        part, load = routed(m, w, gains, spec, operand)
        return part + gated(m, w["s_gate"], w["s_up"], w["s_down"],
                            operand), load

    out, load = jax.lax.map(feed_forward, _blocks(h, token_block))
    return h + out.reshape(h.shape), jnp.sum(load, axis=0)


def layer(h, spec, w, gains, eps, operand, query_block, token_block):
    """(B, T, width) -> (B, T, width), and the routed load (or None)."""
    rows = [sequence_layer(row, spec, w, gains, eps, operand, query_block,
                           token_block) for row in h]
    load = sum(load for _, load in rows)
    return jnp.stack([out for out, _ in rows]), \
        (None if spec.get("ffn") else load)


class _hashable(dict):
    """A layer spec as the key of its jitted layer: the routed layers
    of one model share one compiled program."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


@functools.lru_cache(maxsize=None)
def _jitted_layer(spec, operand, query_block, token_block):
    """(forward, backward) of one sequence through one layer, jitted;
    ``on`` is :func:`_rounded`'s flag.  ``backward(on, h, w, gains,
    d_out)`` computes the forward again and returns the gradients by h,
    w and gains."""
    def run(on, h, w, gains):
        return sequence_layer(h, spec, w, gains, spec.get("eps", 1e-6),
                              (operand, on), query_block, token_block)

    def backward(on, h, w, gains, d_out):
        _, pull = jax.vjp(lambda *args: run(on, *args)[0], h, w, gains)
        return pull(d_out)

    return jax.jit(run), jax.jit(backward)


@functools.lru_cache(maxsize=None)
def _jitted_head(eps, operand):
    """((loss summed over the row's tokens, logits), their gradients by
    (h, gain, w)): one program for the forward's logits and the
    backward's start."""
    def head(h, gain, w, targets, on):
        logits = product(rms_norm(h, gain, eps), w, (operand, on))
        return loss(logits, targets) * jnp.sum(targets >= 0), logits

    return jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2),
                                      has_aux=True))


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
        head = _jitted_head(layers[-1].get("eps", 1e-6), operand)
        gain = jnp.asarray(params[-1]["bias"], jnp.float32)
        w_head = jnp.asarray(params[-1]["weights"], jnp.float32)
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


def loss(logits, targets):
    """Mean next-token cross-entropy over the targets >= 0."""
    logits = logits.reshape(-1, logits.shape[-1])
    targets = jnp.asarray(targets).reshape(-1)
    valid = targets >= 0
    log_p = logits - jax.scipy.special.logsumexp(
        logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(
        log_p, jnp.where(valid, targets, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def _flat(tree, pieces):
    return jnp.concatenate([tree[name].ravel() for name, _ in pieces])


def row_gradients(layers, params, row, targets, operand="float32",
                  query_block=512, token_block=4096, lowered=True):
    """One sequence's part of a step: (its loss SUMMED over its targets,
    how many they are, its logits (T, vocab), the gradients of that sum
    as one ``{"weights", "bias"}`` of float32 arrays a spec, shaped as
    the parameters are and left on the device, the routed loads).  Backward by hand, a layer at a time from the
    head down, each layer's forward computed again from its kept input:
    what is alive on the device is one layer's pieces, their gradients
    and one block's scores, so the float32 backward of 8,192 tokens
    fits the chip.  ``lowered`` as in :func:`forward`."""
    how = (operand, query_block, token_block)
    on = jnp.asarray(bool(lowered))
    row, targets = numpy.asarray(row), numpy.asarray(targets)
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
        w_head = jnp.asarray(params[-1]["weights"], jnp.float32)
        (total, logits), (d_h, d_gain, d_w) = _jitted_head(
            layers[-1].get("eps", 1e-6), operand)(
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
            jnp.asarray(row)].add(d_h), "bias": None})
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


def add_gradients(a, b):
    return [{k: None if v is None else v + other[k]
             for k, v in entry.items()} for entry, other in zip(a, b)]


def scale_gradients(grads, factor):
    return [{k: None if v is None else v * jnp.float32(factor)
             for k, v in entry.items()} for entry in grads]


def adamw_step(param, grad, m, v, step, *, lr, beta1, beta2, eps, decay):
    """One AdamW step (Loshchilov & Hutter 2019), ``step`` from 1:
    (param, m, v) after it.  Host or device arrays."""
    m = beta1 * m + (1 - beta1) * grad
    v = beta2 * v + (1 - beta2) * grad * grad
    m_hat = m / (1 - beta1 ** step)
    v_hat = v / (1 - beta2 ** step)
    return param - lr * (m_hat / (v_hat ** 0.5 + eps)
                         + decay * param), m, v


# -- operations and bytes, from shapes ----------------------------------------


def parameter_counts(arguments):
    """Parameters held here, by part, from the factory's arguments."""
    a = arguments
    width, heads = a["width"], a["heads"]
    attention_ = (width * heads * (a["qk_nope"] + a["qk_rope"])
                  + width * (a["kv_rank"] + a["qk_rope"])
                  + a["kv_rank"] * heads * (a["qk_nope"] + a["v_head"])
                  + heads * a["v_head"] * width)
    expert = 3 * width * a["expert_width"]
    return {"attention": attention_, "dense_ffn": 3 * width * a["ffn"],
            "router": width * a["experts"], "expert": expert,
            "shared": 3 * width * a["shared_width"],
            "vocabulary": a["vocab"] * width}


def step_cost(config, batch):
    """Operations and least bytes of one train step of ``batch`` rows,
    from shapes alone.  Operations are the MODEL's: 2 a multiply-add,
    forward + weight gradient + input gradient = 3 x the forward's;
    attention counts the causal pairs only, T (T + 1) / 2 a sequence,
    keys ``qk_nope + qk_rope`` and values ``v_head`` wide as published;
    a routed layer counts the assignments its held experts get when the
    router spreads evenly (tokens x top_k x held / experts).  Never the
    padded, masked or recomputed work, so no share of a peak can read
    over 100 % whatever implements it.  Bytes: the float32 state read
    and written once (weights, two moments, gradient)."""
    a = config["model"]["arguments"]
    t = config["input_shape"][0] - 1
    tokens = batch * t
    n = parameter_counts(a)
    dense_layers = a.get("dense_layers", 1)
    routed_layers = a["layers"] - dense_layers
    assignments = tokens * a["top_k"] * a["experts_held"] / a["experts"]
    pairs = batch * t * (t + 1) / 2
    attention_flops = 3 * a["layers"] * pairs * a["heads"] * 2 * (
        a["qk_nope"] + a["qk_rope"] + a["v_head"])
    routed_flops = 3 * routed_layers * assignments * 2 * n["expert"]
    matrix_flops = 3 * 2 * tokens * (
        a["layers"] * n["attention"] + dense_layers * n["dense_ffn"]
        + routed_layers * (n["router"] + n["shared"])
        + n["vocabulary"]) + routed_flops
    held = (a["layers"] * n["attention"] + dense_layers * n["dense_ffn"]
            + routed_layers * (n["router"] + n["shared"]
                               + a["experts_held"] * n["expert"])
            + 2 * n["vocabulary"])
    flops = matrix_flops + attention_flops
    return {"flops": flops, "flops_per_image": flops / batch,
            "bytes": 7 * 4 * held, "parameters": held, "tokens": tokens,
            "attention_flops": attention_flops,
            "routed_flops": routed_flops,
            "routed_assignments": routed_layers * assignments}
