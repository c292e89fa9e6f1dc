"""The plain reference of the causal decoder whose layers are each one
part — a Mamba-2 state-space mixer, grouped-query attention with no
position signal, or routed relu² experts beside a shared one — under an
untied head (configuration ``nemotron_twotower_30b_a3b``): the layer
equations of the ``nemotron_h`` family in ``jax.numpy``, float32,
true-float32 products (``jax.default_matmul_precision("highest")``), no
kernel, no cache, and nothing imported from the program (what no
model's equations differ in — the operand rounding, a product, RMSNorm,
the loss, the head, AdamW, the arithmetic on gradient lists — is the
sibling reference's, ``mla_moe_decoder.py``, imported, not copied; the
sigmoid router with its correction bias and the count of causal pairs
are the other sibling's, ``gqa_window_moe_decoder.py``).  Written from
the published ``config.json`` (its keys in brackets) and, where that has
no key, from the family's published modelling code (marked †:
``modeling_nemotron_h.py``), and from the layer specs the zoo factory
returns; the packed parameter layout is listed here again, by hand, so
a program that packed differently would disagree.

``h`` is the residual stream, ``rms_norm(x; g) = x / sqrt(mean(x^2) +
eps) * g`` (layer_norm_epsilon)::

    h = E[ids]
    layer kind by hybrid_override_pattern[i], each ONE part, pre-norm †:
    M:  a = rms_norm(h; g_ssm)
        [z | xBC | dt] = a W_in      (mamba_num_heads x mamba_head_dim = d,
                                      n_groups x ssm_state_size; no bias:
                                      mamba_proj_bias false)
        xBC[t] = silu(sum_{j < L} k[:, j] xBC[t - (L - 1) + j] + conv_b)
                (conv_kernel L, depthwise and causal; use_conv_bias)
        [x | B | C] = xBC;  head n reads group n // (heads / n_groups)
        dt = softplus(dt + dt_bias);  A = -exp(a_log)        (per head)
        s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T,  s_{-1} = 0
        y_t = s_t C_t + d_skip x_t          (TOKEN BY TOKEN, below)
        y = rms_norm over each of n_groups groups of d / n_groups (y *
            silu(z)) * g_norm              (gated norm after the gate †)
        h = h + y W_out
    *:  a = rms_norm(h; g_attn)
        q = a W_q -> heads x head_dim;  k, v = a W_k, a W_v -> kv_heads x
        head_dim         (num_attention_heads, num_key_value_heads, head_dim)
        NO q/k norm, NO rotary, no gate, no bias (attention_bias false) †
        h = h + causal_softmax(q . k[n // group] / sqrt(head_dim)) v W_o
    E:  m = rms_norm(h; g_ffn)
        p = sigmoid(m W_r), float32                    (n_routed_experts)
        chosen = the top_k largest of p + b    (b takes no gradient; n_group
                                                = topk_group = 1)
        w_i = p_i / sum_chosen p * routed_scale     (norm_topk_prob,
                                                     routed_scaling_factor)
        h = h + sum_{i chosen AND held} w_i relu(m U_i)^2 D_i
              + relu(m U_s)^2 D_s        (mlp_hidden_act relu2; one shared
                                          expert, moe_shared_expert_...)
    logits = rms_norm(h; g_final) W_head      (tie_word_embeddings false)

**The scan, token by token.**  The state-space layer is the recurrence
itself, one ``lax.scan`` step a token — not the program's chunked form,
so agreement means something.  Its backward keeps the state only at the
edges of blocks of ``scan_block`` tokens (``jax.checkpoint`` over a
block's steps), so at 16,384 tokens 128 states of (heads, head_dim,
state) float32 are kept and a block's are computed again.

**The share.**  ``forward`` is given the experts held (a routed layer's
``first_expert``, ``experts_held``) and the vocabulary rows held: it
routes over all ``experts`` and adds only the held experts' terms —
every held expert on every token, weighted by the router's choice, no
buffer, so nothing can be dropped here.  The shared expert is whole on
every rank: the shares' routed parts plus ONE shared expert add up to
the whole layer's.

``operand`` and the blocking of attention and the feed-forward as in the
siblings; ``operand`` also rounds the scan's operands ``dt x``, B and C.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from benchmark.references.gqa_window_moe_decoder import (  # noqa: F401
    allowed_pairs, route)
from benchmark.references.mla_moe_decoder import (  # noqa: F401
    _blocks, _flat, _hashable, _jitted_head, _rounded, adamw_step,
    add_gradients, loss, product, rms_norm, scale_gradients, silu, split)


# -- the packed layout, listed by hand ----------------------------------------


def layer_pieces(spec, width):
    """([(name, shape)] of a layer's packed weights, of its packed bias),
    in packing order."""
    if spec.get("ssm_heads"):
        heads, state = spec["ssm_heads"], spec["ssm_state"]
        inner = heads * spec["ssm_head_width"]
        filtered = inner + 2 * spec["ssm_groups"] * state
        return ([("w_in", (width, inner + filtered + heads)),
                 ("conv_k", (filtered, spec["conv_taps"])),
                 ("w_out", (inner, width))],
                [("ssm_gain", (width,)), ("conv_b", (filtered,)),
                 ("dt_bias", (heads,)), ("a_log", (heads,)),
                 ("d_skip", (heads,)), ("ssm_norm_gain", (inner,))])
    if spec.get("kv_heads"):
        heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
            spec["head_width"]
        return ([("w_q", (width, heads * wide)),
                 ("w_k", (width, kv_heads * wide)),
                 ("w_v", (width, kv_heads * wide)),
                 ("w_o", (heads * wide, width))],
                [("attn_gain", (width,))])
    held, expert = spec["experts_held"], spec["expert_width"]
    shared = spec["shared_width"]
    return ([("w_router", (width, spec["experts"])),
             ("e_up", (held, width, expert)),
             ("e_down", (held, expert, width)),
             ("s_up", (width, shared)), ("s_down", (shared, width))],
            [("ffn_gain", (width,)), ("router_bias", (spec["experts"],))])


# -- the equations ----------------------------------------------------------


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def relu2(m, w_up, w_down, operand):
    """relu(m W_u)^2 W_d."""
    return product(jnp.square(jnp.maximum(product(m, w_up, operand), 0.0)),
                   w_down, operand)


def causal_filter(u, taps):
    """c[t] = sum_j taps[:, j] u[t - (L - 1) + j], nothing before 0."""
    t, length = u.shape[0], taps.shape[1]
    u = jnp.concatenate([jnp.zeros((length - 1, u.shape[1]), u.dtype), u])
    c = jnp.zeros((t, u.shape[1]), jnp.float32)
    for j in range(length):
        c = c + taps[:, j][None, :] * u[j:j + t]
    return c


def recurrence(x, dt, a, b, c, operand, scan_block):
    """One sequence's y_t = s_t C_t (T, heads, head_dim) of
    s_t = exp(dt_t a) s_{t-1} + dt_t x_t B_t^T, one step a token; x
    (T, heads, head_dim), dt (T, heads), a (heads,), b and c (T, groups,
    state).  The backward keeps the state at the edges of blocks of
    ``scan_block`` tokens only."""
    t, heads, width = x.shape
    groups, state = b.shape[1:]
    per = heads // groups
    xs = _rounded(x * dt[:, :, None], operand)
    b, c = _rounded(b, operand), _rounded(c, operand)
    decay = jnp.exp(dt * a[None, :])

    def step(s, token):
        d, x_t, b_t, c_t = token
        b_t, c_t = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        s = d[:, None, None] * s + x_t[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(s, tokens):
        return jax.lax.scan(step, s, tokens)

    pad = -t % scan_block

    def blocked(v):
        v = jnp.concatenate([v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
        return v.reshape((-1, scan_block) + v.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((heads, width, state), jnp.float32),
                        tuple(blocked(v) for v in (decay, xs, b, c)))
    return y.reshape(-1, heads, width)[:t]


def ssm(a, w, gains, spec, eps, operand, scan_block):
    """One sequence: a (T, width) normalised input -> (T, width)."""
    heads, wide = spec["ssm_heads"], spec["ssm_head_width"]
    groups, state = spec["ssm_groups"], spec["ssm_state"]
    t = a.shape[0]
    inner = heads * wide
    filtered = inner + 2 * groups * state
    proj = product(a, w["w_in"], operand)
    xbc = silu(causal_filter(proj[:, inner:inner + filtered], w["conv_k"])
               + gains["conv_b"])
    x = xbc[:, :inner].reshape(t, heads, wide)
    b = xbc[:, inner:inner + groups * state].reshape(t, groups, state)
    c = xbc[:, inner + groups * state:].reshape(t, groups, state)
    dt = softplus(proj[:, inner + filtered:] + gains["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(gains["a_log"]), b, c, operand,
                   scan_block) + gains["d_skip"][None, :, None] * x
    y = y.reshape(t, inner) * silu(proj[:, :inner])
    y = rms_norm(y.reshape(t, groups, inner // groups),
                 gains["ssm_norm_gain"].reshape(groups, -1), eps)
    return product(y.reshape(t, inner), w["w_out"], operand)


def attention(a, w, spec, operand, query_block):
    """One sequence: a (T, width) normalised input -> (T, width); no
    norm of q or k, no position signal, no gate.  A block of queries at a
    time against every key, the keys after a query masked; a block's
    scores are computed again in a backward pass (``jax.checkpoint``)."""
    heads, kv_heads, wide = spec["heads"], spec["kv_heads"], \
        spec["head_width"]
    group = heads // kv_heads
    t = a.shape[0]
    # query head n = g * group + j reads KV head g
    q = _rounded(product(a, w["w_q"], operand), operand).reshape(
        t, kv_heads, group, wide)
    k = _rounded(product(a, w["w_k"], operand), operand).reshape(
        t, kv_heads, wide)
    v = _rounded(product(a, w["w_v"], operand), operand).reshape(
        t, kv_heads, wide)
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


def routed(m, w, gains, spec, operand):
    """sum over the HELD experts of w_i relu(m U_i)^2 D_i, every held
    expert on every token, one after the other, and the tokens each was
    chosen for."""
    chosen, weight = route(m, w["w_router"], gains["router_bias"],
                           spec["top_k"], spec.get("routed_scale", 1.0),
                           spec.get("route_eps", 0.0))
    held = spec["experts_held"]

    @jax.checkpoint
    def add_expert(out, expert):
        index, w_up, w_down = expert
        share = jnp.sum(jnp.where(chosen == index, weight, 0.0), axis=-1)
        return (out + share[:, None] * relu2(m, w_up, w_down, operand),
                jnp.sum(chosen == index))

    return jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (spec.get("first_expert", 0) + jnp.arange(held),
         w["e_up"][:held], w["e_down"][:held]))


def sequence_layer(h, spec, w, gains, eps, operand, query_block,
                   token_block, scan_block):
    """One sequence (T, width) -> (T, width), and the routed load
    ((experts held,); (0,) for a layer of another kind)."""
    none = jnp.zeros((0,), jnp.int32)
    if spec.get("ssm_heads"):
        return h + ssm(rms_norm(h, gains["ssm_gain"], eps), w, gains, spec,
                       eps, operand, scan_block), none
    if spec.get("kv_heads"):
        return h + attention(rms_norm(h, gains["attn_gain"], eps), w, spec,
                             operand, query_block), none

    @jax.checkpoint
    def feed_forward(tokens):
        m = rms_norm(tokens, gains["ffn_gain"], eps)
        part, load = routed(m, w, gains, spec, operand)
        return part + relu2(m, w["s_up"], w["s_down"], operand), load

    out, load = jax.lax.map(feed_forward, _blocks(h, token_block))
    return h + out.reshape(h.shape), jnp.sum(load, axis=0)


@functools.lru_cache(maxsize=None)
def _jitted_layer(spec, operand, query_block, token_block, scan_block):
    """(forward, backward) of one sequence through one layer, jitted;
    ``on`` is :func:`_rounded`'s flag.  ``backward(on, h, w, gains,
    d_out)`` computes the forward again and returns the gradients by h,
    w and gains."""
    def run(on, h, w, gains):
        return sequence_layer(h, spec, w, gains, spec.get("eps", 1e-5),
                              (operand, on), query_block, token_block,
                              scan_block)

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


def forward(layers, params, x, operand="float32", query_block=256,
            token_block=4096, scan_block=128, with_load=False,
            lowered=True):
    """Logits (B, T, vocab held) of token ids ``x`` (B, T).  ``layers``
    are the zoo factory's specs, ``params`` one ``{"weights", "bias"}``
    a spec as the program packs them (host or device arrays).  A
    sequence and a layer at a time.  ``lowered`` False computes in
    float32 through the programs compiled for ``operand``."""
    how = (operand, query_block, token_block, scan_block)
    on = jnp.asarray(bool(lowered))
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(params[0]["weights"], jnp.float32)
        inner = _layer_params(layers, params, table.shape[-1])
        head = _jitted_head(layers[-1].get("eps", 1e-5), operand)
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
             if spec.get("experts")]
    return (logits, loads) if with_load else logits


def row_gradients(layers, params, row, targets, operand="float32",
                  query_block=256, token_block=4096, scan_block=128,
                  lowered=True):
    """One sequence's part of a step: (its loss SUMMED over its targets,
    how many they are, its logits (T, vocab), the gradients of that sum
    as one ``{"weights", "bias"}`` of float32 arrays a spec, shaped as
    the parameters are and left on the device, the routed loads).
    Backward by hand, a layer at a time from the head down, each layer's
    forward computed again from its kept input.  ``lowered`` as in
    :func:`forward`."""
    how = (operand, query_block, token_block, scan_block)
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
            if spec.get("experts"):
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


# -- operations and bytes, from shapes ----------------------------------------


def parameter_counts(arguments):
    """Matrix parameters held here, by part, from the factory's
    arguments (the norms' gains, the scan's per-head pieces and its
    filter's bias, a few thousand a layer, are left out; the filter's
    taps are counted with its mixer)."""
    a = arguments
    width = a["width"]
    inner = a["ssm_heads"] * a["ssm_head_width"]
    filtered = inner + 2 * a["ssm_groups"] * a["ssm_state"]
    q_wide = a["heads"] * a["head_width"]
    kv_wide = a["kv_heads"] * a["head_width"]
    return {"ssm": width * (inner + filtered + a["ssm_heads"])
            + filtered * a["conv_taps"] + inner * width,
            "attention": width * (q_wide + 2 * kv_wide) + q_wide * width,
            "router": width * a["experts"],
            "expert": 2 * width * a["expert_width"],
            "shared": 2 * width * a["shared_width"],
            "vocabulary": a["vocab"] * width}


def scan_cost(t, arguments):
    """(operations, bytes) of one sequence's chunked scan through one
    layer, forward: the products of the chunked form at ``ssm_chunk``,
    over the causal pairs within a chunk only — C B^T (a group's, ``N``
    deep), its decayed product with dt x (a head's, ``P`` wide), the
    chunk-end states B^T (dt x) and the states read through C — never a
    padded token; and x, B, C (2 bytes each), dt, y and the chunk states
    (float32), each read or written once."""
    a = arguments
    heads, wide = a["ssm_heads"], a["ssm_head_width"]
    groups, state, chunk = a["ssm_groups"], a["ssm_state"], a["ssm_chunk"]
    flops = 0
    for start in range(0, t, chunk):
        q = min(chunk, t - start)
        pairs = q * (q + 1) // 2
        flops += (2 * groups * state * pairs + 2 * heads * wide * pairs
                  + 2 * 2 * heads * wide * state * q)
    chunks = -(-t // chunk)
    data = t * (2 * heads * wide + 2 * 2 * groups * state + 4 * heads
                + 4 * heads * wide) + 4 * chunks * heads * wide * state
    return flops, data


def step_cost(config, batch):
    """Operations and least bytes of one train step of ``batch`` rows,
    from shapes alone.  Operations are the MODEL's: 2 a multiply-add,
    forward + weight gradient + input gradient = 3 x the forward's;
    attention counts the causal pairs only, T (T + 1) / 2 a sequence, at
    the published head width for the query heads; the state-space
    layers their projections, their filter (2 x ``conv_taps`` a channel
    a token) and their scan (:func:`scan_cost`, ``ssm_scan_flops``, 3 x
    its forward as every part is counted; ``ssm_scan_bytes`` 3 x its
    forward's data: the backward reads what the forward read and wrote
    and writes as much again); a routed layer counts the assignments its
    held experts get when the router spreads evenly (tokens x top_k x
    held / experts), each expert and the shared one two matrices.
    Never the padded, masked or recomputed work, so no share of a peak
    can read over 100 % whatever implements it.  Bytes: the float32
    state read and written once (weights, two moments, gradient)."""
    a = config["model"]["arguments"]
    t = config["input_shape"][0] - 1
    tokens = batch * t
    n = parameter_counts(a)
    kinds = a["layer_types"]
    ssm_layers = kinds.count("ssm")
    attention_layers = kinds.count("attention")
    routed_layers = kinds.count("routed")
    inner = a["ssm_heads"] * a["ssm_head_width"]
    filtered = inner + 2 * a["ssm_groups"] * a["ssm_state"]
    assignments = tokens * a["top_k"] * a["experts_held"] / a["experts"]
    attention_flops = (attention_layers * allowed_pairs(t) * 3 * batch
                       * a["heads"] * 2 * 2 * a["head_width"])
    scan_flops, scan_bytes = scan_cost(t, a)
    scan_flops *= 3 * batch * ssm_layers
    scan_bytes *= 3 * batch * ssm_layers
    filter_flops = 3 * ssm_layers * tokens * filtered * 2 * a["conv_taps"]
    ssm_matrix = n["ssm"] - filtered * a["conv_taps"]
    routed_flops = 3 * routed_layers * assignments * 2 * n["expert"]
    matrix_flops = 3 * 2 * tokens * (
        ssm_layers * ssm_matrix + attention_layers * n["attention"]
        + routed_layers * (n["router"] + n["shared"])
        + n["vocabulary"]) + routed_flops
    held = (ssm_layers * n["ssm"] + attention_layers * n["attention"]
            + routed_layers * (n["router"] + n["shared"]
                               + a["experts_held"] * n["expert"])
            + 2 * n["vocabulary"])
    flops = matrix_flops + attention_flops + filter_flops + scan_flops
    return {"flops": flops, "flops_per_image": flops / batch,
            "bytes": 7 * 4 * held, "parameters": held, "tokens": tokens,
            "attention_flops": attention_flops,
            "full_attention_flops": attention_flops,
            "ssm_flops": 3 * 2 * tokens * ssm_layers * ssm_matrix
            + filter_flops + scan_flops,
            "ssm_scan_flops": scan_flops, "ssm_scan_bytes": scan_bytes,
            "routed_flops": routed_flops,
            "routed_assignments": routed_layers * assignments}
