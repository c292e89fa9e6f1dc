"""Causal decoder units: token embedding, one layer made of parts — a
token mixer that is latent attention (MLA), grouped-query attention (over
every earlier key, a window of them, or a learned selection of them), a
gated short convolution or a state-space scan (Mamba-2's), norms before
each sub-layer or around it, a gated SiLU feed-forward or a routed expert
layer — and the output head, with a matrix of its own or tied to the
embedding's table (docs/model_layer.md "Decoder units").  The parts are
chosen by the layer's own dims (``kv_rank`` makes the mixer latent
attention, ``kv_heads`` grouped-query attention, ``index_heads`` puts an
indexer's selection in front of it, ``conv_taps`` makes it a short
convolution, ``ssm_heads`` a state-space scan; a layer with none of them
has no mixer; ``out_gate`` False takes the sigmoid gate off grouped
attention's output, ``qk_norm`` False its norms of q and k;
``post_norms`` puts a norm after each sub-layer too; ``ffn`` makes the
feed-forward dense, ``experts`` routed, and a layer with neither has no
feed-forward part; a routed layer carries a shared expert where
``shared_width`` is not 0, routes by a sigmoid with a correction bias
or, ``router`` "softmax", by a softmax, and with ``expert_act``
"relu2" its experts are not gated), never by a model's name: the
DeepSeek-V3 family is one choice of them, the window/full grouped-query
family with sandwich norms another, the hybrid of short convolutions and
grouped-query attention a third, grouped-query attention over a
lightning indexer's selection a fourth, layers of one part each —
state-space scans, grouped-query attention and relu² experts — a fifth.

Built on the contracts of ``transformer.py``: the math is in pure
functions and ``apply(params, x, **static)`` class methods; a layer's
many matrices pack into the ONE ``(weights, bias)`` pair every unit has
— one flat float32 vector each, static offsets (:func:`layer_layout`) —
so ``compiler.py``, the snapshotter and ``parallel/`` see a layer like
any other.  ``weights`` holds the matrices (decayed by the solver),
``bias`` what is not decayed: the norms' gains (and the indexer's key
norm's bias) and the router's correction bias, which takes no gradient.
The state is float32 whatever ``root.common.engine.precision_type``
says (``STATE_DTYPE``); that setting is the dtype of the operands and
activations, and every product accumulates in float32.

The layer with latent attention, ``h`` the residual stream (config.json
keys of the DeepSeek-V3 family in brackets)::

    a = rms_norm(h)
    q = a W_q                      -> heads x (nope | rope)
    [c | k_rope] = a W_kva         -> kv_rank + rope   (kv_lora_rank)
    [k_nope | v] = rms_norm(c) W_kvb -> heads x (nope + v_head)
    rotary on q_rope per head and on the one k_rope all heads share,
    adjacent pairs (rope_interleave)
    h += causal_softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(nope+rope)) v W_o
    m = rms_norm(h)
    dense:  h += (silu(m W_g) * m W_u) W_d
    routed: p = sigmoid(m W_r); the top_k largest of p + b;
            w_i = p_i / sum_chosen p * routed_scale
            (softmax: z = m W_r, the top_k largest z, w = softmax(z_chosen))
            h += sum_i w_i Expert_i(m) [+ Shared(m)]

``Shared`` is a part: a routed layer whose ``shared_width`` is 0 has
none, and its ``s_*`` pieces and ``shared_experts`` scope leave with it.

The grouped-query attention sub-layer (:func:`grouped_attention`), in
place of the first five lines::

    q = a W_q -> heads x head_width;  k, v = a W_k, a W_v -> kv_heads x
    head_width;  z = a W_z -> heads x head_width  (the output gate, a
    part: with ``out_gate`` False ``w_z`` leaves the layout)
    q, k = rms_norm(q), rms_norm(k)  over each head, one gain each
    rotary on q and k, the pairing (i, i + head_width / 2), where the
    layer has ``rope``; no position signal where it has not
    query head n reads KV head n // (heads / kv_heads); key j counts for
    query i where j <= i and, with ``window``, i - j < window
    h += [rms_norm](softmax(q.k / sqrt(head_width)) v [* sigmoid(z)]) W_o

With ``index_heads`` key j counts for query i where j is in S[i], what a
lightning indexer over stop_grad(a) keeps (:func:`key_selection`, the
``attend`` that :func:`grouped_attention` is handed)::

    qI = a W_iq -> index_heads x index_width;  kI = layer_norm(a W_ik)
    wI = a W_iw / sqrt(index_heads index_width); rotary on the first half
    I[i, j] = sum_n wI[i, n] relu(qI[i, n] . kI[j])  (j <= i, float32)
    S[i] = {j <= i : I[i, j] >= the min(i + 1, index_topk)-th largest}
    L_I = mean_i KL(mean_heads P[i, :] || softmax_{S[i]} I[i, :]), whose
    gradient reaches the indexer's pieces alone

The gated short convolution (:func:`short_conv`), the mixer of a layer
with ``conv_taps`` = L, in place of attention::

    [B | C | x] = a W_in              W_in (width, 3 width), no bias
    u = B * x
    c[t] = sum_{j < L} k[:, j] * u[t - (L - 1) + j],  u[t < 0] = 0
                        (depthwise: one L-tap filter a channel, causal)
    h += (C * c) W_out                W_out (width, width)

and with ``post_norms`` each sub-layer's output is normalised before it
is added (``h += rms_norm(f)``: the sandwich placement).

The state-space mixer (:func:`ssm_mixer`, Mamba-2's), the mixer of a
layer with ``ssm_heads`` = H heads ``ssm_head_width`` = P wide, its B and
C in ``ssm_groups`` = G groups of ``ssm_state`` = N, d = H P::

    [z | xBC | dt] = a W_in           W_in (width, 2 d + 2 G N + H), no bias
    xBC = silu(c + conv_b), c the causal filter above over xBC, L taps
    [x | B | C] = xBC                 x: H heads x P; B, C: G groups x N;
                                      head n reads group n // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(a_log)   per head, float32
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T  (per head, P x N, float32)
    y_t = s_t C_t + d_skip * x_t
    h += rms_norm_G(y * silu(z)) W_out      (the norm over each of G
                                              groups of d / G, then a gain)

The scan (:func:`ssd_scan`, its own scope) is the chunked form: within
a chunk of ``ssm_chunk`` tokens the products ``(L o C B^T) X`` over its
decays' segment sums, the chunk-end states from ``B^T X``, the states
the chunks start from passed by one float32 matrix product over the
chunks' segment sums of log decays (:func:`_carried`, no loop over the
chunks within a block of :data:`CARRY_BLOCK`), and those states read
back through ``C``; operands in the compute dtype, decays, sums and states
float32.  A sequence that is not a whole number of chunks is padded at
its end, which changes no earlier output.

A layer of one part: with no mixer ``h += f(rms_norm(h))`` for its
feed-forward ``f`` alone, with no feed-forward part the mixer alone.  A
routed layer's experts and shared expert with ``expert_act`` "relu2"
are ``relu(m W_u)^2 W_d``: no gate, and ``e_gate``/``s_gate`` leave the
layout.

**The share.**  A routed layer is told which experts it holds
(``first_expert``, ``experts_held``): it routes over ALL ``experts``,
sorts the step's token-expert assignments by expert, keeps those of its
own experts in a buffer, and runs grouped products
(``lax.ragged_dot``) over them.  What the absent experts would add is
left out — that partial result goes on — and nothing stands in for the
chips that hold them or for the exchange.  The buffer holds the most a
step can send: tokens x min(top_k, experts held) rows, so nothing is
ever dropped, and a step pays for the rows it filled: everything after
the sort walks them in chunks of ``CHUNK`` rows and stops at the fill,
each chunk adding its rows to their tokens' sums in a float32 carry
(:func:`_expert_rows`).  A layer built with a smaller
``capacity`` (no factory or configuration sets one) DROPS the
assignments that do not fit and counts them (``moe_dropped``): the layer
never drops one silently.

**Initialisation.**  Normal, ``weights_stddev`` every matrix but those
that write into the residual stream (``w_o``, ``w_out`` and every
``*_down``), which take ``out_stddev`` (default: the same), and the
short convolutions' filters and the scan's filter bias, uniform within
1 / sqrt(L) (what a depthwise ``Conv1d`` starts from).  The scan's
``a_log`` starts as log U(1, 16), ``dt_bias`` as the inverse softplus of
a dt drawn log-uniform in [0.001, 0.1] and floored at 1e-4 (Mamba-2's
starts, ``DecoderLayer.SSM_A_INIT`` and ``SSM_DT_INIT``), ``d_skip`` at
1.  With one std everywhere
the first layer's attention output — an average of values over the
prefix, so nearly the same vector at every position — outweighs the
embedding four to one, every token looks alike to the router and all
choose the same experts; the scaled form (std / sqrt(2 x layers), as
GPT-2 and Megatron-LM initialise these projections) keeps tokens apart.
Under ``post_norms`` a norm follows each of those matrices, so their
std does not reach the stream; what does is the post-norms' gains, which
start from ``post_gain`` (default 1, as every other gain).  The indexer's
key norm starts as every norm does: gain 1, bias 0.
"""

import functools

import numpy

from veles_tpu.models.transformer import _GDAutodiff, _SequenceUnit

__all__ = ["DecoderEmbedding", "DecoderLayer", "DecoderHead",
           "GDDecoderEmbedding", "GDDecoderLayer", "GDDecoderHead",
           "rms_norm", "rotary", "latent_attention", "grouped_attention",
           "short_conv", "ssm_mixer", "ssd_scan", "gated_ffn", "relu2_ffn",
           "routed_experts", "layer_layout", "unpack", "decoder_layer"]

#: ``jax.named_scope`` names inside each ``l<k>_DecoderLayer``
SCOPE_ATTENTION = "attention"
SCOPE_CONV = "short_conv"
SCOPE_ROUTER = "router"
SCOPE_ROUTED = "routed_experts"
SCOPE_SHARED = "shared_experts"
SCOPE_FFN = "dense_ffn"
SCOPE_INDEXER = "indexer"
#: the state-space mixer's scopes, siblings (``scope_of`` reads the first
#: part a name holds, so a scope inside another would vanish into it):
#: its projections, filter, gate and norm, and the chunked scan alone
SCOPE_SSM = "ssm_mixer"
SCOPE_SCAN = "ssm_scan"


# -- pure math ---------------------------------------------------------------


def rms_norm(x, gain, eps=1e-6):
    """x / sqrt(mean(x^2) + eps) * gain over the last axis, float32
    statistics, in x's dtype."""
    import jax.numpy as jnp
    from jax import lax
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                      + eps)
    return (xf * scale * gain.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta, halves=False):
    """Rotary positions on pairs of the last axis: pair ``i`` of
    position ``t`` turns by ``t * theta ** (-2 i / width)``.  The pairs
    are ADJACENT elements (2i, 2i + 1), or with ``halves`` element ``i``
    and element ``i + width / 2`` (the rotate-half pairing).  ``x`` is
    (B, T, ..., width); positions count from 0 along axis 1."""
    import jax.numpy as jnp
    width = x.shape[-1]
    t = x.shape[1]
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, t) + (1,) * (x.ndim - 3) + (width,)
    xf = x.astype(jnp.float32)
    if halves:
        cos = jnp.tile(jnp.cos(angle), 2)              # (T, width)
        sin = jnp.tile(jnp.sin(angle), 2)
        a, b = xf[..., :width // 2], xf[..., width // 2:]
        other = jnp.concatenate([-b, a], axis=-1)
    else:
        cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)   # (T, width)
        sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)
        # the pair's other element: (a, b) -> (-b, a)
        even = (jnp.arange(width) % 2 == 0)
        other = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                          jnp.roll(xf, 1, axis=-1))
    return (xf * cos.reshape(shape) + other * sin.reshape(shape)).astype(
        x.dtype)


def _dense(x, w):
    import jax.numpy as jnp
    return jnp.einsum("...f,fg->...g", x, w,
                      preferred_element_type=jnp.float32)


def _attend(q, k, v, scale, pallas_bwd, window=None):
    """(B*H, T, .) causal attention — ``k``/``v`` (B*H_kv, T, .) where
    the heads are grouped, ``window`` keys back where given — through
    the flash kernels or the stock reference, per the VELES_PALLAS_BWD
    contract.  Attention over a learned selection of keys is
    :func:`key_selection`'s ``attend``, which :func:`grouped_attention`
    calls in this one's place."""
    import jax.numpy as jnp

    from veles_tpu.ops.attention import (attention_reference,
                                         flash_attention)
    if pallas_bwd is None:
        from veles_tpu.ops.common import pallas_bwd_enabled
        pallas_bwd = pallas_bwd_enabled()
    if not pallas_bwd:
        return attention_reference(q, k, v, scale=scale, causal=True,
                                   window=window)
    narrow = q.dtype if q.dtype == jnp.bfloat16 else None
    return flash_attention(q, k, v, scale=scale, causal=True,
                           product_dtype=narrow, window=window)


def _fold_heads(x):
    """(B, T, H, w) -> (B*H, T, w)."""
    b, t, heads, width = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * heads, t, width)


def latent_attention(a, w, *, heads, qk_nope, qk_rope, v_head, kv_rank,
                     kv_gain, theta, eps, pallas_bwd=None):
    """The MLA sub-layer over normalised ``a`` (B, T, D), before the
    residual add.  ``w`` holds ``w_q``, ``w_kva``, ``w_kvb``, ``w_o``."""
    import jax.numpy as jnp
    b, t, _ = a.shape
    dtype = a.dtype
    q = _dense(a, w["w_q"]).astype(dtype).reshape(
        b, t, heads, qk_nope + qk_rope)
    kva = _dense(a, w["w_kva"]).astype(dtype)
    c = rms_norm(kva[..., :kv_rank], kv_gain, eps)
    k_rope = rotary(kva[..., kv_rank:], theta)           # (B, T, rope)
    kv = _dense(c, w["w_kvb"]).astype(dtype).reshape(
        b, t, heads, qk_nope + v_head)
    q = jnp.concatenate(
        [q[..., :qk_nope], rotary(q[..., qk_nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :qk_nope],
         jnp.broadcast_to(k_rope[:, :, None, :], (b, t, heads, qk_rope))],
        axis=-1)
    v = kv[..., qk_nope:]
    o = _attend(_fold_heads(q), _fold_heads(k), _fold_heads(v),
                1.0 / float(numpy.sqrt(qk_nope + qk_rope)), pallas_bwd)
    o = o.reshape(b, heads, t, v_head).transpose(0, 2, 1, 3).reshape(
        b, t, heads * v_head)
    return _dense(o, w["w_o"]).astype(dtype)


def grouped_attention(a, w, *, heads, kv_heads, head_width, window, rope,
                      theta, eps, q_gain, k_gain, out_gate=True,
                      pallas_bwd=None, attend=None):
    """The grouped-query sub-layer over normalised ``a`` (B, T, D),
    before the residual add: ``heads`` query heads read ``kv_heads``
    key/value heads (never repeated: the kernels index them), each
    head's q and k RMS-normalised (one ``head_width`` gain each, shared
    by the heads; not where the gains are None), rotary in the
    rotate-half pairing where ``rope``, keys ``window`` back where
    given, and with ``out_gate`` a sigmoid gate on the output before
    ``w_o``.  ``w`` holds ``w_q``, ``w_k``,
    ``w_v``, ``w_o`` and, gated, ``w_z``.  ``attend(q, k, v, scale)``
    over the folded heads, where given, takes :func:`_attend`'s place:
    a learned selection's keys (:func:`key_selection`)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = a.shape
    dtype = a.dtype
    q = _dense(a, w["w_q"]).astype(dtype).reshape(b, t, heads, head_width)
    k = _dense(a, w["w_k"]).astype(dtype).reshape(
        b, t, kv_heads, head_width)
    v = _dense(a, w["w_v"]).astype(dtype).reshape(
        b, t, kv_heads, head_width)
    if out_gate:
        z = _dense(a, w["w_z"])
    if q_gain is not None:
        q, k = rms_norm(q, q_gain, eps), rms_norm(k, k_gain, eps)
    if rope:
        q, k = rotary(q, theta, halves=True), rotary(k, theta, halves=True)
    q, k, v = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    scale = 1.0 / float(numpy.sqrt(head_width))
    if attend is None:
        o = _attend(q, k, v, scale, pallas_bwd, window)
    else:
        o = attend(q, k, v, scale)
    o = o.reshape(b, heads, t, head_width).transpose(0, 2, 1, 3).reshape(
        b, t, heads * head_width)
    if out_gate:
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(z)).astype(dtype)
    return _dense(o, w["w_o"]).astype(dtype)


def _layer_norm(x, gain, bias, eps):
    """(x - mean) / sqrt(var + eps) * gain + bias over the last axis, in
    float32."""
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred / jnp.sqrt(var + eps) * gain + bias


def index_inputs(a, w, g, *, index_heads, index_width, theta, eps):
    """The lightning indexer's operands over (B, T, D) ``a``: its queries
    (B, T, index_heads, index_width) and its one key head (B, T,
    index_width) in ``a``'s dtype, rotary on the first half of each
    (the rotate-half pairing), and its head weights (B, T, index_heads)
    float32, scaled by 1 / sqrt(index_heads x index_width)."""
    import jax.numpy as jnp
    b, t, _ = a.shape
    half = index_width // 2
    q = _dense(a, w["w_iq"]).astype(a.dtype).reshape(
        b, t, index_heads, index_width)
    k = _layer_norm(_dense(a, w["w_ik"]), g["index_k_gain"],
                    g["index_k_bias"], eps).astype(a.dtype)
    q = jnp.concatenate([rotary(q[..., :half], theta, halves=True),
                         q[..., half:]], axis=-1)
    k = jnp.concatenate([rotary(k[..., :half], theta, halves=True),
                         k[..., half:]], axis=-1)
    scale = 1.0 / float(numpy.sqrt(index_heads * index_width))
    return q, k, _dense(a, w["w_iw"]) * scale


@functools.lru_cache(maxsize=None)
def _gradients_in():
    """``attach(x, inputs, grads)`` -> ``x``, whose backward hands
    ``grads`` to ``inputs`` (cast to their dtypes) whatever ``x``'s
    cotangent: a loss term's gradient written out, applied where its
    inputs are.  The gradients are named (``KEPT_INDEXER_GRADS``), so a
    layer's checkpoint that keeps them does not compute them again."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    from veles_tpu.ops.sparse_attention import KEPT_INDEXER_GRADS

    @jax.custom_vjp
    def attach(x, inputs, grads):
        return x

    def fwd(x, inputs, grads):
        grads = tuple(checkpoint_name(g.astype(i.dtype), KEPT_INDEXER_GRADS)
                      for g, i in zip(grads, inputs))
        return x, grads

    def bwd(grads, g_x):
        return g_x, grads, None

    attach.defvjp(fwd, bwd)
    return attach


def key_selection(a, w, g, *, index_heads, index_width, index_topk, theta,
                  eps, pallas_bwd=None):
    """A layer's learned selection of keys over normalised ``a`` (B, T,
    D): ``(attend, trained)``.  The indexer (its own scope) reads ``a``
    detached and keeps ``index_topk`` keys a query
    (ops/sparse_attention.py).  ``attend(q, k, v, scale)`` is
    :func:`grouped_attention`'s hook: the folded heads over the kept keys
    alone, one selection for every head.  ``trained(attended)`` ->
    ``(attended, aux)`` computes the indexer's loss L_I from what
    ``attend`` saw and hands its gradient to the indexer's operands
    (:func:`_gradients_in`), so that it trains the indexer's pieces and
    nothing else; ``aux`` counts the kept pairs by query tile, the
    (query tile, key tile) pairs that hold one beside those a causal
    mask holds, and L_I.  Kernels where ``pallas_bwd`` (the knob as
    :func:`_attend` reads it), else their plain ``jax.numpy``
    definitions."""
    import jax
    from jax import lax

    from veles_tpu.ops import sparse_attention as sparse
    if pallas_bwd is None:
        from veles_tpu.ops.common import pallas_bwd_enabled
        pallas_bwd = pallas_bwd_enabled()
    with jax.named_scope(SCOPE_INDEXER):
        index = index_inputs(lax.stop_gradient(a), w, g,
                             index_heads=index_heads,
                             index_width=index_width, theta=theta, eps=eps)
        if pallas_bwd:
            selection = sparse.select(*index, index_topk)
        else:
            _, kept = sparse.select_reference(*index, index_topk)
            selection = sparse.selection_of(kept)
    seen = {}

    def attend(q, k, v, scale):
        seen.update(q=q, k=k, scale=scale)
        if pallas_bwd:
            o, seen["stats"] = sparse.attend(q, k, v, selection, scale)
        else:
            o, seen["probabilities"] = sparse.attend_reference(
                q, k, v, kept, scale)
        return o

    def trained(attended):
        with jax.named_scope(SCOPE_INDEXER):
            if pallas_bwd:
                kl, grads = sparse.indexer_loss(
                    seen["q"], seen["k"], seen["stats"], *index, selection,
                    seen["scale"])
            else:
                kl, grads = jax.value_and_grad(
                    lambda *x: sparse.indexer_loss_reference(
                        seen["probabilities"], kept, *x),
                    argnums=(0, 1, 2))(
                        *(lax.stop_gradient(x) for x in index))
            attended = _gradients_in()(attended, index, grads)
            aux = dict(sparse.counters(selection, a.shape[1]),
                       indexer_kl=kl)
        return attended, aux

    return attend, trained


def short_conv(a, w_in, taps, w_out):
    """The gated short convolution over normalised ``a`` (B, T, D),
    before the residual add: ``[B | C | x] = a W_in``, the gated input
    ``u = B * x``, a depthwise causal filter along the sequence —
    ``c[t] = sum_j taps[:, j] * u[t - (L - 1) + j]`` with ``taps`` (D,
    L) and nothing before position 0 — and ``(C * c) W_out``.  The
    filter (:func:`_causal_filter`) is L shifted multiply-adds in
    float32, which XLA fuses with both gates into one pass over the (B,
    T, 3 D) projection."""
    import jax.numpy as jnp
    dtype = a.dtype
    width = taps.shape[0]
    bcx = _dense(a, w_in).astype(dtype)
    gate_in, gate_out, x = (bcx[..., :width], bcx[..., width:2 * width],
                            bcx[..., 2 * width:])
    c = _causal_filter((gate_in * x).astype(jnp.float32), taps)
    return _dense((gate_out.astype(jnp.float32) * c).astype(dtype),
                  w_out).astype(dtype)


def _causal_filter(u, taps):
    """The depthwise causal filter over float32 ``u`` (B, T, D):
    ``c[t] = sum_j taps[:, j] * u[t - (L - 1) + j]`` with ``taps`` (D, L)
    and nothing before position 0, float32."""
    import jax.numpy as jnp
    length = taps.shape[1]
    t = u.shape[1]
    u = jnp.pad(u, ((0, 0), (length - 1, 0), (0, 0)))
    k = taps.astype(jnp.float32)
    return sum(k[:, j] * u[:, j:j + t] for j in range(length))


# The chunks whose states pass in one matrix product (:func:`_carried`).
# The product's operations grow with the block (each of n chunks sums a
# block of P x N states), the recurrence across blocks with its n / block
# sequential steps, each a few microseconds of loop and small launches.
# At 128, 16,384 tokens in chunks of 128 are one block and no loop: a
# group of 8 heads of 64 x 128 passes its states in 2.1 GFLOP, six
# bfloat16 passes at float32 precision, against 128 steps of the loop;
# 131,072 tokens take 8 steps, not one (1,024 x 1,024) product of 8 x
# the operations.
CARRY_BLOCK = 128


def _passing(log_decay):
    """The weights by which chunk j's end state reaches the start of
    chunk c, ``W[c, j] = exp(sum_{j < k < c} log_decay_k)`` for j < c and
    0 on and above the diagonal, from ``log_decay`` (..., K) -> (..., K,
    K).  Each entry is the cumulative sum of its own segment's terms
    alone (Mamba-2's ``segsum``), never the difference of two sums over
    the whole block, whose float32 rounding would move every decay that
    survives."""
    import jax.numpy as jnp
    k = log_decay.shape[-1]
    c, j = numpy.arange(k)[:, None], numpy.arange(k)[None, :]
    entering = jnp.pad(log_decay[..., :-1],
                       [(0, 0)] * (log_decay.ndim - 1) + [(1, 0)])
    terms = jnp.where(j < c - 1, entering[..., :, None], 0.0)
    return jnp.where(j < c, jnp.exp(jnp.cumsum(terms, axis=-2)), 0.0)


def _carried(log_decay, ends):
    """The states a sequence's chunks start from, float32: ``log_decay``
    (n, B, H) the log of each chunk's decay from its start to its end,
    ``ends`` (n, B, H, P, N) the state each chunk ends in from a zero
    start -> (n, B, H, P, N) ``S_c = sum_{j < c} W[c, j] ends_j``, which
    is ``S_0 = 0, S_c = exp(log_decay_{c-1}) S_{c-1} + ends_{c-1}``.
    Within a block of :data:`CARRY_BLOCK` chunks one matrix product by
    the weights of :func:`_passing`, at float32 precision (``HIGHEST``:
    the default is one bfloat16 pass on the TPU); from block to block the
    recurrence, each block's end state decayed by its whole decay.  The
    log decays, not their ``exp``: a chunk's decay underflows to 0 where
    its log is below about -104, and a log of that would be -inf."""
    import jax.numpy as jnp
    from jax import lax
    n = ends.shape[0]
    block = min(CARRY_BLOCK, n)
    pad = -n % block
    blocks = (n + pad) // block
    lam = jnp.pad(log_decay, [(0, pad)] + [(0, 0)] * (log_decay.ndim - 1))
    ends = jnp.pad(ends, [(0, pad)] + [(0, 0)] * (ends.ndim - 1))
    lam = lam.reshape((blocks, block) + lam.shape[1:])
    ends = ends.reshape((blocks, block) + ends.shape[1:])
    weights = _passing(jnp.moveaxis(lam, 1, -1))    # (blocks, B, H, K, K)
    starts = jnp.einsum("gbhcj,gjbhpn->gcbhpn", weights, ends,
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    if blocks > 1:
        def step(state, block):
            decay, end = block
            return decay[..., None, None] * state + end, state

        block_ends = (jnp.exp(lam[:, -1])[..., None, None] * starts[:, -1]
                      + ends[:, -1])
        entering = lax.scan(step, jnp.zeros_like(block_ends[0]),
                            (jnp.exp(lam.sum(axis=1)), block_ends))[1]
        into = jnp.exp(jnp.cumsum(jnp.pad(
            lam[:, :-1], [(0, 0), (1, 0)] + [(0, 0)] * (lam.ndim - 2)),
            axis=1))
        starts = starts + into[..., None, None] * entering[:, None]
    return starts.reshape((blocks * block,) + starts.shape[2:])[:n]


def ssd_scan(x, dt, a, b, c, chunk):
    """The selective state's outputs ``y_t = s_t C_t`` of
    ``s_t = exp(dt_t a) s_{t-1} + dt_t x_t B_t^T`` (``s_{-1} = 0``) in
    the chunked form, (B, T, H, P) float32: ``x`` (B, T, H, P) in the
    compute dtype, ``dt`` (B, T, H) and ``a`` (H,) float32, ``b``/``c``
    (B, T, G, N) in the compute dtype, head n reading group n // (H / G).
    Within a chunk of ``chunk`` tokens ``y = (L o C B^T)(dt x)`` with
    ``L[l, s] = exp(sum_{s < j <= l} dt_j a)`` (s <= l), plus the state
    the chunk starts from read through ``C`` and decayed to each token;
    the chunk-end states are ``B^T (dt x)`` decayed to the chunk's end,
    passed on to the chunks after them by :func:`_carried` (a matrix
    product, no loop over the chunks within a block).  The products'
    operands are in the compute dtype, the decays, sums and states
    float32.  A group of heads at a time (no head reads another group's
    B and C), each computed again in the backward (``jax.checkpoint``),
    so that one group's decays and states alone are alive at once; the
    end is padded to a whole chunk (dt 0 and x 0: no earlier output
    moves)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    f32 = jnp.float32
    dtype = x.dtype
    bsz, t, heads, width = x.shape
    groups, state = b.shape[2:]
    per = heads // groups
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunked(v):
        """(B, T, ...) -> (B n, chunk, ...)."""
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape((bsz * n, chunk) + v.shape[2:])

    # a group at a time, a head's tokens last: (G, B n, per, chunk[, P])
    xs = chunked((x.astype(f32) * dt[..., None]).astype(dtype)).reshape(
        bsz * n, chunk, groups, per, width).transpose(2, 0, 3, 1, 4)
    cum = jnp.cumsum(chunked(dt * a).reshape(bsz * n, chunk, groups, per),
                     axis=1).transpose(2, 0, 3, 1)
    bs, cs = (chunked(v).transpose(2, 0, 1, 3) for v in (b, c))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def in_order(v):
        """(B n, ...) -> (n, B, ...): a sequence's chunks in order."""
        return v.reshape((bsz, n) + v.shape[1:]).swapaxes(0, 1)

    @jax.checkpoint
    def group(part):
        """A group's outputs (B n, per, chunk, P): within each chunk and
        from the state the chunk starts from."""
        xg, cumg, bg, cg = part
        to_end = jnp.exp(cumg[..., -1:] - cumg)
        ends = jnp.einsum(
            "chlp,cln->chpn",
            (xg.astype(f32) * to_end[..., None]).astype(dtype), bg,
            preferred_element_type=f32)
        starts = _carried(in_order(cumg[..., -1]),
                          in_order(ends)).swapaxes(0, 1).reshape(ends.shape)
        seg = cumg[..., :, None] - cumg[..., None, :]
        decays = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        scores = jnp.einsum("cln,csn->cls", cg, bg,
                            preferred_element_type=f32)
        within = jnp.einsum("chls,chsp->chlp",
                            (decays * scores[:, None]).astype(dtype), xg,
                            preferred_element_type=f32)
        carried = jnp.einsum("cln,chpn->chlp", cg, starts.astype(dtype),
                             preferred_element_type=f32)
        return within + carried * jnp.exp(cumg)[..., None]

    y = lax.map(group, (xs, cum, bs, cs))
    y = y.reshape(groups, bsz, n, per, chunk, width).transpose(
        1, 2, 4, 0, 3, 5).reshape(bsz, n * chunk, heads, width)
    return y[:, :t]


def ssm_mixer(a, w, g, *, heads, head_width, groups, state, chunk, eps):
    """Mamba-2's state-space sub-layer over normalised ``a`` (B, T, D),
    before the residual add: ``[z | xBC | dt] = a W_in``, the causal
    filter over ``xBC`` with its bias and a SiLU, the chunked scan of
    ``x`` by ``B`` and ``C`` (:func:`ssd_scan`, in its own scope), the
    skip ``d_skip * x``, the gate ``silu(z)``, an RMS norm over each of
    ``groups`` groups of the ``heads * head_width`` channels and
    ``W_out``.  ``w`` holds ``w_in``, ``conv_k``, ``w_out``; ``g`` the
    float32 ``conv_b``, ``dt_bias``, ``a_log``, ``d_skip`` and
    ``ssm_norm_gain``.  The float32 stretches between the products —
    the filter and its SiLU, the skip, gate and norm — are computed
    again in the backward from their operands (``jax.checkpoint``):
    kept, their float32 intermediates would outweigh the layer's
    operands several times."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dtype = a.dtype
    bsz, t, _ = a.shape
    inner = heads * head_width
    wide = inner + 2 * groups * state

    @jax.checkpoint
    def filtered(u, taps, bias):
        return jax.nn.silu(_causal_filter(u.astype(f32), taps)
                           + bias).astype(dtype)

    @jax.checkpoint
    def gated(y, x, z, skip, gain):
        y = (y + skip[:, None] * x.astype(f32)).reshape(bsz, t, inner) \
            * jax.nn.silu(z.astype(f32))
        return rms_norm(y.reshape(bsz, t, groups, inner // groups),
                        gain.reshape(groups, -1), eps).reshape(
                            bsz, t, inner).astype(dtype)

    with jax.named_scope(SCOPE_SSM):
        proj = _dense(a, w["w_in"]).astype(dtype)
        xbc = filtered(proj[..., inner:inner + wide], w["conv_k"],
                       g["conv_b"])
        x = xbc[..., :inner].reshape(bsz, t, heads, head_width)
        b, c = (xbc[..., inner + i * groups * state:
                    inner + (i + 1) * groups * state].reshape(
                        bsz, t, groups, state) for i in (0, 1))
        dt = jax.nn.softplus(proj[..., inner + wide:].astype(f32)
                             + g["dt_bias"])
        a_decay = -jnp.exp(g["a_log"])
    with jax.named_scope(SCOPE_SCAN):
        y = ssd_scan(x, dt, a_decay, b, c, chunk)
    with jax.named_scope(SCOPE_SSM):
        return _dense(gated(y, x, proj[..., :inner], g["d_skip"],
                            g["ssm_norm_gain"]), w["w_out"]).astype(dtype)


def gated_ffn(m, w_gate, w_up, w_down):
    """(silu(m W_g) * m W_u) W_d, float32 accumulation, in m's dtype."""
    import jax
    gate = _dense(m, w_gate)
    up = _dense(m, w_up)
    return _dense((jax.nn.silu(gate) * up).astype(m.dtype),
                  w_down).astype(m.dtype)


def relu2_ffn(m, w_up, w_down):
    """relu(m W_u)^2 W_d, float32 accumulation, in m's dtype."""
    import jax
    import jax.numpy as jnp
    return _dense(jnp.square(jax.nn.relu(_dense(m, w_up))).astype(m.dtype),
                  w_down).astype(m.dtype)


#: rows of the routed buffer one step of :func:`routed_experts`' loops
#: works over (a multiple of the 512-row tile; ``PERF.md`` section 6 has
#: the chip readings that chose it).  A smaller buffer is one chunk.
CHUNK = 8192


@functools.lru_cache(maxsize=None)
def _expert_rows(chunk, gated=True):
    """``expert_rows(m, w_row, e_in, e_down, token_of, reach, filled)``
    -> the (N, D) weighted sum of the held experts' outputs: the stretch
    of :func:`routed_experts` from the tokens to the sum, walked over the
    buffer's rows ``[0, filled)`` in chunks of ``chunk`` rows and over no
    chunk beyond them.  ``e_in`` holds the experts' input matrices:
    ``(e_gate, e_up)`` of gated SiLU experts, ``(e_up,)`` of relu² ones
    (``gated`` False).  Row ``r`` holds token ``token_of[r]`` under
    weight ``w_row[r]`` and expert ``e``'s rows end at ``reach[e]``.
    Each chunk adds its filled rows, rounded to ``m``'s dtype, to their
    tokens' sums in a float32 ``(N, D)`` carry (a scatter-add at
    ``token_of``), so the sum follows the fill and no array has the
    buffer's rows x D.  The loops' bound is a device scalar, so they
    lower to ``while`` and the backward is written, not derived: the
    dispatch gather ``m[token_of]`` and that scatter-add are each the
    other's transpose."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    f32 = jnp.float32

    def grouped(rows, experts, sizes):
        return lax.ragged_dot(rows, experts, sizes,
                              preferred_element_type=f32)

    def back(g, experts, sizes):
        """``grouped``'s transpose in its rows."""
        return grouped(g, jnp.swapaxes(experts, 1, 2), sizes)

    #: rows (R, A) x cotangents (R, B) -> (E, A, B): ``grouped``'s
    #: transpose in the experts, in float32 for the carries
    into_experts = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def weight_grad(rows, g, sizes):
        return lax.ragged_dot_general(rows, g, sizes, into_experts,
                                      preferred_element_type=f32)

    def act(pre, dtype):
        """The hidden rows from the pre-activations."""
        if gated:
            gate, up = pre
            return (jax.nn.silu(gate) * up).astype(dtype)
        return jnp.square(jax.nn.relu(pre[0])).astype(dtype)

    def chunk_of(i, token_of, w_row, reach, filled):
        """Chunk ``i``: (its first row, its rows' tokens and weights,
        which of them are filled, the rows of it each expert owns)."""
        r0 = i * chunk
        tok = lax.dynamic_slice(token_of, (r0,), (chunk,))
        w = lax.dynamic_slice(w_row, (r0,), (chunk,))
        live = r0 + jnp.arange(chunk, dtype=jnp.int32) < filled
        edge = jnp.clip(reach, r0, r0 + chunk)
        sizes = edge - jnp.concatenate([r0[None], edge[:-1]])
        return r0, tok, w[:, None], live[:, None], sizes

    def trips(filled):
        return (filled + chunk - 1) // chunk

    def rows_in(m, tok, e_in, sizes):
        """A chunk's tokens and their pre-activations."""
        xs = m[tok]
        return xs, [grouped(xs, e, sizes) for e in e_in]

    def forward(m, w_row, e_in, e_down, token_of, reach, filled):
        def step(i, out):
            _, tok, w, live, sizes = chunk_of(
                i, token_of, w_row, reach, filled)
            _, pre = rows_in(m, tok, e_in, sizes)
            y_c = grouped(act(pre, m.dtype), e_down, sizes)
            return out.at[tok].add(jnp.where(
                live, y_c * w, 0.0).astype(m.dtype).astype(f32))

        return lax.fori_loop(0, trips(filled), step,
                             jnp.zeros(m.shape, f32)).astype(m.dtype)

    def fwd(*args):
        # no row is kept for the backward, whose loop computes a chunk's
        # ``xs`` and pre-activations again: under a layer's checkpoint
        # this rule is the recomputation, and where the layer's output
        # is only added to the stream its loop is then dead code (the
        # chip, PERF.md section 6: cheaper than filling three more
        # whole buffers, even where the loop stays)
        return forward(*args), args

    def bwd(res, g_out):
        m, w_row, e_in, e_down, token_of, reach, filled = res
        dtype = m.dtype

        def step(i, carry):
            g_m, g_w, *sums = carry
            r0, tok, w, live, sizes = chunk_of(
                i, token_of, w_row, reach, filled)
            xs, pre = rows_in(m, tok, e_in, sizes)
            hidden, act_back = jax.vjp(lambda *pre: act(pre, dtype), *pre)
            g_y = jnp.where(live, g_out[tok], 0)
            # <g_y, hidden E_down> = <g_y E_down^T, hidden>: the weight's
            # gradient without the down product's output
            g_hidden = back(g_y, e_down, sizes)
            g_w_c = jnp.where(live, jnp.sum(
                g_hidden * hidden.astype(f32), axis=1, keepdims=True), 0)
            g_pre = [g.astype(dtype)
                     for g in act_back((g_hidden * w).astype(dtype))]
            g_xs_c = back(g_pre[0], e_in[0], sizes)
            for g, e in zip(g_pre[1:], e_in[1:]):
                g_xs_c = g_xs_c + back(g, e, sizes)
            g_xs_c = jnp.where(live, g_xs_c, 0)
            grads = [weight_grad(xs, g, sizes) for g in g_pre] + [
                weight_grad(hidden, (g_y.astype(f32) * w).astype(dtype),
                            sizes)]
            return (g_m.at[tok].add(g_xs_c.astype(dtype).astype(f32)),
                    lax.dynamic_update_slice(g_w, g_w_c[:, 0], (r0,)),
                    *(total + g for total, g in zip(sums, grads)))

        g_m, g_w, *g_in, g_down = lax.fori_loop(
            0, trips(filled), step, (
                jnp.zeros(m.shape, f32), jnp.zeros(w_row.shape, f32),
                *(jnp.zeros(e.shape, f32) for e in e_in + (e_down,))))
        return (g_m.astype(dtype), g_w.astype(w_row.dtype),
                tuple(g.astype(e.dtype) for g, e in zip(g_in, e_in)),
                g_down.astype(e_down.dtype), None, None, None)

    expert_rows = jax.custom_vjp(forward)
    expert_rows.defvjp(fwd, bwd)
    return expert_rows


def routed_experts(m, idx, weights, e_gate, e_up, e_down, *, first_expert,
                   capacity):
    """The held experts' part of ``sum_i w_i Expert_i(m)``.

    ``m`` (N, D) tokens, ``idx`` (N, K) chosen experts out of all,
    ``weights`` (N, K) float32; ``e_gate``/``e_up`` (E_held, D, F) and
    ``e_down`` (E_held, F, D) the experts ``first_expert ..
    first_expert + E_held - 1``, gated SiLU experts, or with ``e_gate``
    None relu² ones, ``relu(m e_up)^2 e_down``.  Assignments are sorted
    by expert and those of the held experts are the rows of a
    ``capacity``-row buffer (None: the N x min(K, E_held) rows a step
    can send at most), a token and a weight a row; the grouped products
    and every pass over rows walk the rows a step FILLED, in chunks of
    ``CHUNK``, and each chunk adds its rows to their tokens' float32
    sums inside the loop (:func:`_expert_rows`).  Returns
    (out (N, D), aux) with aux ``moe_load`` (E_held,) tokens routed to
    each held expert, ``moe_assignments`` their sum, ``moe_dropped`` how
    many did not fit the buffer and ``moe_visited_rows`` the rows of the
    chunks walked, by the grouped products and the combine alike."""
    import jax.numpy as jnp
    n, k = idx.shape
    held = e_up.shape[0]
    if capacity is None:
        capacity = n * min(k, held)
    local = idx - first_expert
    is_held = (local >= 0) & (local < held)
    # one key an assignment; the experts not held come last.  A counting
    # sort (the keys are few): an assignment's row is its expert's first
    # row plus how many of that expert's came before it — a running
    # count, no sort (the chip's compiler takes 20 s a sort)
    key = jnp.where(is_held, local, held).reshape(-1).astype(jnp.int32)
    mine = jnp.arange(held + 1, dtype=jnp.int32)[:, None] == key[None, :]
    before = jnp.cumsum(mine, axis=1, dtype=jnp.int32)
    count = before[:, -1]                    # tokens an expert gets
    first = jnp.cumsum(count) - count
    pos = jnp.sum(jnp.where(mine, before - 1 + first[:, None], 0),
                  axis=0)                           # assignment -> row
    order = jnp.zeros_like(pos).at[pos].set(
        jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
    load = count[:held]
    kept = jnp.sum(load)
    filled = jnp.minimum(kept, capacity)
    reach = jnp.minimum(jnp.cumsum(load), capacity)
    # whole chunks: the rows past ``capacity`` are never filled
    rows = min(CHUNK, capacity)
    slot = jnp.pad(order[:capacity],                # row -> assignment
                   (0, -capacity % rows))
    token_of = (slot // k).astype(jnp.int32)
    gated = e_gate is not None
    out = _expert_rows(rows, gated)(
        m, weights.reshape(-1)[slot], (e_gate, e_up) if gated else (e_up,),
        e_down, token_of, reach, filled)
    aux = {"moe_load": load, "moe_assignments": kept,
           "moe_dropped": jnp.maximum(kept - capacity, 0),
           "moe_visited_rows": (filled + rows - 1) // rows * rows}
    return out, aux


# -- the packed layer --------------------------------------------------------


def layer_layout(d, *, heads=None, qk_nope=None, qk_rope=None,
                 v_head=None, kv_rank=None, kv_heads=None, head_width=None,
                 out_gate=True, qk_norm=True, conv_taps=None,
                 ssm_heads=None, ssm_head_width=None, ssm_groups=None,
                 ssm_state=None, post_norms=False, ffn=None, experts=None,
                 experts_held=None, expert_width=None, shared_width=None,
                 router="sigmoid", expert_act="silu", index_heads=None,
                 index_width=None, **_):
    """((name, shape) of the packed ``weights``, of the packed ``bias``):
    the ONE definition the initialiser and the apply read.  ``ssm_heads``
    makes the mixer a state-space scan (its filter ``conv_taps`` long),
    else ``conv_taps`` a short convolution, ``kv_rank`` latent attention,
    ``kv_heads`` grouped attention (its gate's ``w_z`` unless
    ``out_gate`` is False, its q and k norms' gains unless ``qk_norm``
    is; an indexer's pieces where ``index_heads``), and none of them no
    mixer; ``post_norms`` adds the gains of a norm after each sub-layer;
    ``ffn`` makes the layer dense, ``experts`` routed (a correction bias
    unless the ``router`` is a softmax; no gates where ``expert_act`` is
    "relu2"), beside a shared expert where ``shared_width`` is not 0,
    and neither leaves the feed-forward part out."""
    mixer = bool(ssm_heads or conv_taps or kv_rank or kv_heads)
    if ssm_heads:
        inner = ssm_heads * ssm_head_width
        filtered = inner + 2 * ssm_groups * ssm_state
        weights = [("w_in", (d, inner + filtered + ssm_heads)),
                   ("conv_k", (filtered, conv_taps)),
                   ("w_out", (inner, d))]
        bias = [("ssm_gain", (d,)), ("conv_b", (filtered,)),
                ("dt_bias", (ssm_heads,)), ("a_log", (ssm_heads,)),
                ("d_skip", (ssm_heads,)), ("ssm_norm_gain", (inner,))]
    elif conv_taps:
        weights = [("w_in", (d, 3 * d)), ("conv_k", (d, conv_taps)),
                   ("w_out", (d, d))]
        bias = [("conv_gain", (d,))]
    elif kv_rank:
        weights = [("w_q", (d, heads * (qk_nope + qk_rope))),
                   ("w_kva", (d, kv_rank + qk_rope)),
                   ("w_kvb", (kv_rank, heads * (qk_nope + v_head))),
                   ("w_o", (heads * v_head, d))]
        bias = [("attn_gain", (d,)), ("kv_gain", (kv_rank,))]
    elif kv_heads:
        weights = [("w_q", (d, heads * head_width)),
                   ("w_k", (d, kv_heads * head_width)),
                   ("w_v", (d, kv_heads * head_width))]
        if out_gate:
            weights += [("w_z", (d, heads * head_width))]
        weights += [("w_o", (heads * head_width, d))]
        bias = [("attn_gain", (d,))]
        if qk_norm:
            bias += [("q_gain", (head_width,)), ("k_gain", (head_width,))]
        if index_heads:
            weights += [("w_iq", (d, index_heads * index_width)),
                        ("w_ik", (d, index_width)), ("w_iw", (d, index_heads))]
            bias += [("index_k_gain", (index_width,)),
                     ("index_k_bias", (index_width,))]
    else:
        weights, bias = [], []
    feed_forward = bool(ffn or experts)
    if post_norms and mixer:
        bias += [("post_attn_gain", (d,))]
    if feed_forward:
        bias += [("ffn_gain", (d,))]
    if post_norms and feed_forward:
        bias += [("post_ffn_gain", (d,))]
    if ffn:
        weights += [("w_gate", (d, ffn)), ("w_up", (d, ffn)),
                    ("w_down", (ffn, d))]
    elif experts:
        gated = expert_act != "relu2"
        weights += [("w_router", (d, experts))]
        if gated:
            weights += [("e_gate", (experts_held, d, expert_width))]
        weights += [("e_up", (experts_held, d, expert_width)),
                    ("e_down", (experts_held, expert_width, d))]
        if shared_width:
            if gated:
                weights += [("s_gate", (d, shared_width))]
            weights += [("s_up", (d, shared_width)),
                        ("s_down", (shared_width, d))]
        if router != "softmax":
            bias += [("router_bias", (experts,))]
    return weights, bias


def _size(shape):
    return int(numpy.prod(shape))


#: pieces kept float32 whatever the operands' dtype: the router scores
#: in float32 (a rounded score would move a token between experts, not
#: its output by a rounding), as the family's own code does
FLOAT32_PIECES = ("w_router",)


@functools.lru_cache(maxsize=None)
def _unpacker(layout):
    """vec -> tuple of pieces, each cast to its dtype, whose gradient is
    ONE concatenate of the pieces' (autodiff's sum of padded slices
    would pass over the vector once a piece).  Each piece is cut from
    the vector BEFORE it is reshaped or cast, behind a barrier: left to
    itself the compiler casts the whole vector and moves each reshape
    ahead of its slice, so that every distinct minor width costs a
    relayout of the whole vector (a 32-wide float32 router piece padded
    to 128 lanes: four times its bytes).  Cut first, a piece costs one
    pass over its own bytes; slice, reshape and cast commute exactly,
    so the pieces are the same bits."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def pieces(vec):
        out, offset = [], 0
        for _, shape, dtype in layout:
            size = _size(shape)
            piece = lax.optimization_barrier(vec[offset:offset + size])
            out.append(piece.reshape(shape).astype(dtype))
            offset += size
        return tuple(out)

    @jax.custom_vjp
    def unpack(vec):
        return pieces(vec)

    def fwd(vec):
        return pieces(vec), None

    def bwd(_, grads):
        return (jnp.concatenate(
            [g.astype(jnp.float32).ravel() for g in grads]),)

    unpack.defvjp(fwd, bwd)
    return unpack


def unpack(vec, layout, dtype):
    """Packed flat float32 ``vec`` -> {name: array}: of ``dtype``, the
    ``FLOAT32_PIECES`` of float32."""
    layout = tuple(
        (name, tuple(shape), "float32" if name in FLOAT32_PIECES
         else numpy.dtype(dtype).name) for name, shape in layout)
    pieces = _unpacker(layout)(vec)
    return {entry[0]: piece for entry, piece in zip(layout, pieces)}


def decoder_layer(h, weights, bias, *, compute_dtype, heads=None,
                  qk_nope=None, qk_rope=None, v_head=None, kv_rank=None,
                  kv_heads=None, head_width=None, window=None, rope=True,
                  out_gate=True, qk_norm=True, conv_taps=None,
                  ssm_heads=None, ssm_head_width=None, ssm_groups=None,
                  ssm_state=None, ssm_chunk=None, post_norms=False,
                  ffn=None, index_heads=None, index_width=None,
                  index_topk=None, experts=None, router="sigmoid",
                  experts_held=None, first_expert=0, top_k=None,
                  expert_width=None, shared_width=None, routed_scale=1.0,
                  route_eps=0.0, expert_act="silu", capacity=None,
                  theta=1e6, eps=1e-6, pallas_bwd=None):
    """One layer over packed params: (h, aux).  ``aux`` is empty for a
    layer with no routed part and no selection."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    dims = dict(heads=heads, qk_nope=qk_nope, qk_rope=qk_rope,
                v_head=v_head, kv_rank=kv_rank, kv_heads=kv_heads,
                head_width=head_width, out_gate=out_gate, qk_norm=qk_norm,
                conv_taps=conv_taps, ssm_heads=ssm_heads,
                ssm_head_width=ssm_head_width, ssm_groups=ssm_groups,
                ssm_state=ssm_state, post_norms=post_norms, ffn=ffn,
                experts=experts, experts_held=experts_held, router=router,
                expert_width=expert_width, shared_width=shared_width,
                expert_act=expert_act, index_heads=index_heads,
                index_width=index_width)
    w_layout, b_layout = layer_layout(h.shape[-1], **dims)
    w = unpack(weights, w_layout, compute_dtype)
    g = unpack(bias, b_layout, jnp.float32)
    h, extra = h.astype(compute_dtype), {}

    def added(h, f, gain):
        """The residual stream after a sub-layer's output ``f``."""
        return h + (rms_norm(f, g[gain], eps) if post_norms else f)

    if index_heads:
        # attention over the indexer's selection: the indexer's scope
        # beside attention's, not inside it
        with jax.named_scope(SCOPE_ATTENTION):
            a = rms_norm(h, g["attn_gain"], eps)
        attend, trained = key_selection(
            a, w, g, index_heads=index_heads, index_width=index_width,
            index_topk=index_topk, theta=theta, eps=eps,
            pallas_bwd=pallas_bwd)
        with jax.named_scope(SCOPE_ATTENTION):
            attended = grouped_attention(
                a, w, heads=heads, kv_heads=kv_heads, head_width=head_width,
                window=None, rope=rope, theta=theta, eps=eps,
                q_gain=g.get("q_gain"), k_gain=g.get("k_gain"),
                out_gate=out_gate, pallas_bwd=pallas_bwd, attend=attend)
        attended, extra = trained(attended)
        h = added(h, attended, "post_attn_gain")
    elif ssm_heads:
        with jax.named_scope(SCOPE_SSM):
            a = rms_norm(h, g["ssm_gain"], eps)
        mixed = ssm_mixer(a, w, g, heads=ssm_heads, head_width=ssm_head_width,
                          groups=ssm_groups, state=ssm_state,
                          chunk=ssm_chunk, eps=eps)
        with jax.named_scope(SCOPE_SSM):
            h = added(h, mixed, "post_attn_gain")
    elif conv_taps:
        with jax.named_scope(SCOPE_CONV):
            mixed = short_conv(rms_norm(h, g["conv_gain"], eps),
                               w["w_in"], w["conv_k"], w["w_out"])
            h = added(h, mixed, "post_attn_gain")
    elif kv_rank or kv_heads:
        with jax.named_scope(SCOPE_ATTENTION):
            a = rms_norm(h, g["attn_gain"], eps)
            if kv_rank:
                attended = latent_attention(
                    a, w, heads=heads, qk_nope=qk_nope, qk_rope=qk_rope,
                    v_head=v_head, kv_rank=kv_rank, kv_gain=g["kv_gain"],
                    theta=theta, eps=eps, pallas_bwd=pallas_bwd)
            else:
                attended = grouped_attention(
                    a, w, heads=heads, kv_heads=kv_heads,
                    head_width=head_width, window=window, rope=rope,
                    theta=theta, eps=eps, q_gain=g.get("q_gain"),
                    k_gain=g.get("k_gain"), out_gate=out_gate,
                    pallas_bwd=pallas_bwd)
            h = added(h, attended, "post_attn_gain")
    if not (ffn or experts):
        return h, extra
    m = rms_norm(h, g["ffn_gain"], eps)
    if ffn:
        with jax.named_scope(SCOPE_FFN):
            return added(h, gated_ffn(m, w["w_gate"], w["w_up"],
                                      w["w_down"]), "post_ffn_gain"), extra
    b, t, d = m.shape
    tokens = m.reshape(b * t, d)
    with jax.named_scope(SCOPE_ROUTER):
        # float32 scores from the float32 router (FLOAT32_PIECES)
        z = jnp.dot(tokens.astype(jnp.float32), w["w_router"],
                    precision=lax.Precision.HIGHEST)
        from veles_tpu.parallel.moe import top_k_route
        if router == "softmax":
            # the softmax over all, renormalised over the top_k largest,
            # is the softmax over the chosen scores
            _, idx = top_k_route(z, top_k)
            gate = jax.nn.softmax(jnp.take_along_axis(z, idx, axis=-1),
                                  axis=-1) * routed_scale
        else:
            p = jax.nn.sigmoid(z)
            _, idx = top_k_route(
                p + lax.stop_gradient(g["router_bias"]), top_k)
            chosen = jnp.take_along_axis(p, idx, axis=-1)
            total = jnp.sum(chosen, axis=-1, keepdims=True)
            if route_eps:
                total = total + route_eps
            gate = chosen / total * routed_scale
    with jax.named_scope(SCOPE_ROUTED):
        routed, aux = routed_experts(
            tokens, idx, gate, w.get("e_gate"), w["e_up"], w["e_down"],
            first_expert=first_expert, capacity=capacity)
        aux.update(extra)
    shared = None
    if shared_width:
        with jax.named_scope(SCOPE_SHARED):
            if expert_act == "relu2":
                shared = relu2_ffn(m, w["s_up"], w["s_down"])
            else:
                shared = gated_ffn(m, w["s_gate"], w["s_up"], w["s_down"])
    routed = routed.reshape(b, t, d)
    if shared is None:
        return added(h, routed, "post_ffn_gain"), aux
    if post_norms:
        return added(h, routed + shared, "post_ffn_gain"), aux
    # pre-norm only: the sum in the order it always had (the same bits)
    return h + routed + shared, aux


# -- units -------------------------------------------------------------------


def _compute_dtype():
    from veles_tpu.config import precision_dtype
    return precision_dtype().name


class _DecoderUnit(_SequenceUnit):
    """State is float32 whatever the engine's precision says; that
    setting is the operands' dtype (``compute_dtype``)."""

    STATE_DTYPE = numpy.float32

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("weights_filling", "gaussian")
        kwargs.setdefault("weights_stddev", 0.02)
        super(_DecoderUnit, self).__init__(workflow, **kwargs)
        self.eps = kwargs.get("eps", 1e-6)

    def _gaussian(self, shape, stddev=None):
        arr = numpy.zeros(shape, numpy.float32)
        self.fill_array(arr, self.weights_filling,
                        stddev or self.weights_stddev, shape[0])
        return arr


def _embedding_static(scale):
    static = {"compute_dtype": _compute_dtype()}
    if scale != 1.0:
        static["scale"] = scale
    return static


class DecoderEmbedding(_DecoderUnit):
    """Token ids (B, T) -> (B, T, width): ``weights`` is the (vocab,
    width) table, no bias; ``scale`` multiplies the rows (an input
    multiplier such as sqrt(width))."""

    MAPPING = "decoder_embedding"

    def __init__(self, workflow, **kwargs):
        kwargs["include_bias"] = False
        super(DecoderEmbedding, self).__init__(workflow, **kwargs)
        self.vocab = int(kwargs["vocab"])
        self.width = int(kwargs["width"])
        self.scale = float(kwargs.get("scale", 1.0))

    def static_config(self):
        return _embedding_static(self.scale)

    def create_params(self):
        shape = tuple(self.input.shape)
        if len(shape) != 2:
            raise ValueError("%s expects (batch, tokens) ids, got %s"
                             % (type(self).__name__, (shape,)))
        self._ensure_output(shape + (self.width,))
        if not self.weights:
            self.weights.mem = self._gaussian((self.vocab, self.width))

    @classmethod
    def apply(cls, params, x, *, compute_dtype="float32", scale=1.0):
        import jax.numpy as jnp
        rows = jnp.take(params["weights"], x, axis=0)
        if scale != 1.0:
            rows = rows * scale
        return rows.astype(compute_dtype)


class DecoderLayer(_DecoderUnit):
    """One layer — a latent-attention, grouped-attention (over a
    selection where it has an indexer), short-convolution or state-space
    mixer or none, norms before or around each sub-layer, dense, routed
    or neither — packed (:func:`layer_layout`)."""

    MAPPING = "decoder_layer"
    DIMS = ("heads", "qk_nope", "qk_rope", "v_head", "kv_rank", "kv_heads",
            "head_width", "window", "rope", "out_gate", "conv_taps",
            "post_norms", "ffn", "index_heads", "index_width", "index_topk",
            "experts", "experts_held", "first_expert", "top_k", "router",
            "expert_width", "shared_width", "routed_scale", "route_eps",
            "capacity", "theta", "qk_norm", "ssm_heads", "ssm_head_width",
            "ssm_groups", "ssm_state", "ssm_chunk", "expert_act")
    #: the gains of the norms AFTER a sub-layer (``post_gain``)
    POST_GAINS = ("post_attn_gain", "post_ffn_gain")

    #: the scan's starts, Mamba-2's: dt's (min, max, floor) and A's range
    SSM_DT_INIT = (0.001, 0.1, 1e-4)
    SSM_A_INIT = (1.0, 16.0)

    #: the pieces that write into the residual stream (``out_stddev``)
    RESIDUAL_WRITERS = ("w_o", "w_out", "w_down", "e_down", "s_down")
    #: registry names of the counters ``apply_with_aux`` emits: the
    #: trainer publishes a scalar a layer as ``<name>`` and a vector a
    #: layer as ``<name>.l<layer>.e<element>``; a float, as a gauge
    AUX_COUNTERS = {"moe_assignments": "moe.assignments",
                    "moe_dropped": "moe.dropped_assignments",
                    "moe_load": "moe.load",
                    "moe_visited_rows": "moe.visited_rows",
                    "sparse_selected_pairs": "sparse.selected_pairs",
                    "sparse_occupied_tiles": "sparse.occupied_tiles",
                    "sparse_causal_tiles": "sparse.causal_tiles",
                    "indexer_kl": "sparse.indexer_kl"}

    def __init__(self, workflow, **kwargs):
        super(DecoderLayer, self).__init__(workflow, **kwargs)
        self.dims = {name: kwargs[name] for name in self.DIMS
                     if kwargs.get(name) is not None}
        self.router_bias_stddev = kwargs.get("router_bias_stddev", 0.0)
        self.out_stddev = kwargs.get("out_stddev")
        self.post_gain = kwargs.get("post_gain", 1.0)

    def static_config(self):
        return dict(self.dims, eps=self.eps,
                    compute_dtype=_compute_dtype())

    def create_params(self):
        shape = self._seq_shape()
        self._ensure_output(shape)
        if self.weights:
            return  # restored from a snapshot
        w_layout, b_layout = layer_layout(shape[-1], **self.dims)
        self.weights.mem = numpy.concatenate(
            [self._filled(name, piece).ravel()
             for name, piece in w_layout])
        self.bias.mem = numpy.concatenate(
            [self._bias_filled(name, piece) for name, piece in b_layout])

    def _uniform(self, shape, low, high):
        value = numpy.zeros(shape, numpy.float32)
        self.prng.fill(value, low, high)
        return value

    def _bias_filled(self, name, shape):
        """A piece of the packed bias as initialised."""
        if name in ("router_bias", "index_k_bias"):
            value = numpy.zeros(shape, numpy.float32)
            if self.router_bias_stddev and name == "router_bias":
                self.prng.fill_normal(value, 0.0, self.router_bias_stddev)
            return value
        if name == "conv_b":
            bound = 1.0 / numpy.sqrt(self.dims["conv_taps"])
            return self._uniform(shape, -bound, bound)
        if name == "a_log":
            return numpy.log(self._uniform(shape, *self.SSM_A_INIT))
        if name == "dt_bias":
            low, high, floor = self.SSM_DT_INIT
            dt = numpy.maximum(numpy.exp(self._uniform(
                shape, numpy.log(low), numpy.log(high))), floor)
            # softplus's inverse, so that softplus(dt_bias) = dt
            return (dt + numpy.log(-numpy.expm1(-dt))).astype(numpy.float32)
        return numpy.full(shape, self.post_gain if name in self.POST_GAINS
                          else 1.0, numpy.float32)

    def _filled(self, name, shape):
        """A piece of the packed weights as initialised."""
        if name == "conv_k":
            # a depthwise Conv1d's start: uniform within 1 / sqrt(taps)
            bound = 1.0 / numpy.sqrt(shape[-1])
            return self._uniform(shape, -bound, bound)
        return self._gaussian(shape, self.out_stddev
                              if name in self.RESIDUAL_WRITERS else None)

    @classmethod
    def apply_with_aux(cls, params, x, **static):
        return decoder_layer(x, params["weights"], params["bias"],
                             **static)

    @classmethod
    def apply(cls, params, x, **static):
        return cls.apply_with_aux(params, x, **static)[0]

    #: the ``jax.named_scope`` names of a layer's parts: what
    #: ``xla_introspect.scope_of`` reads as the ``part`` of an
    #: instruction under an ``l<k>_DecoderLayer`` scope
    PART_SCOPES = (SCOPE_ATTENTION, SCOPE_CONV, SCOPE_ROUTER, SCOPE_ROUTED,
                   SCOPE_SHARED, SCOPE_FFN, SCOPE_SSM, SCOPE_SCAN,
                   SCOPE_INDEXER)


class DecoderHead(_DecoderUnit):
    """rms_norm, then float32 logits over the vocabulary rows held:
    ``weights`` (width, vocab), ``bias`` the norm's gain.  With
    ``tied_to`` = the embedding's place in the model the head has no
    matrix of its own: the logits are taken against that unit's (vocab,
    width) table, which the fused step hands to ``apply`` as
    ``params["tied"]`` (``compiler._forward_for_loss``) — one array in
    the state, the moments and a snapshot, its gradient the sum of both
    uses.  A tied head runs inside the fused step only."""

    MAPPING = "decoder_head"

    def __init__(self, workflow, **kwargs):
        super(DecoderHead, self).__init__(workflow, **kwargs)
        self.vocab = int(kwargs["vocab"])
        self.tied_to = kwargs.get("tied_to")

    def static_config(self):
        static = {"eps": self.eps, "compute_dtype": _compute_dtype()}
        if self.tied_to is not None:
            static["tied_to"] = int(self.tied_to)
        return static

    def create_params(self):
        shape = self._seq_shape()
        self._ensure_output(shape[:2] + (self.vocab,))
        if self.bias:
            return  # restored from a snapshot
        if self.tied_to is None:
            self.weights.mem = self._gaussian((shape[-1], self.vocab))
        self.bias.mem = numpy.ones((shape[-1],), numpy.float32)

    def run(self):
        if self.tied_to is not None:
            raise RuntimeError(
                "%s is tied to layer %d's table, which only the fused "
                "step hands it: fuse the workflow (the per-unit path "
                "runs an untied head)" % (self.name, self.tied_to))
        super(DecoderHead, self).run()

    @classmethod
    def apply(cls, params, x, *, eps=1e-6, compute_dtype="float32",
              tied_to=None):
        import jax.numpy as jnp
        h = rms_norm(x.astype(compute_dtype), params["bias"], eps)
        if tied_to is None:
            return _dense(h, params["weights"].astype(compute_dtype))
        return jnp.einsum("...f,vf->...v", h,
                          params["tied"].astype(compute_dtype),
                          preferred_element_type=jnp.float32)


# -- gradient-descent units (the per-unit debug path) ------------------------


class _GDDecoder(_GDAutodiff):
    """Stock vjp over the forward's apply; the static config is the
    forward unit's (linked by the workflow as ``forward_unit``)."""

    MAPPING = None

    def __init__(self, workflow, **kwargs):
        super(_GDDecoder, self).__init__(workflow, **kwargs)
        self._static = {k: v for k, v in kwargs.items()
                        if k in DecoderLayer.DIMS and v is not None}
        self.eps = kwargs.get("eps", 1e-6)


class GDDecoderEmbedding(_GDDecoder):
    MAPPING = "decoder_embedding"
    FORWARD_CLS = DecoderEmbedding

    def __init__(self, workflow, **kwargs):
        super(GDDecoderEmbedding, self).__init__(workflow, **kwargs)
        self.scale = float(kwargs.get("scale", 1.0))

    def backward_static(self):
        return _embedding_static(self.scale)


class GDDecoderLayer(_GDDecoder):
    MAPPING = "decoder_layer"
    FORWARD_CLS = DecoderLayer

    def backward_static(self):
        return dict(self._static, eps=self.eps,
                    compute_dtype=_compute_dtype())


class GDDecoderHead(_GDDecoder):
    MAPPING = "decoder_head"
    FORWARD_CLS = DecoderHead

    def backward_static(self):
        return {"eps": self.eps, "compute_dtype": _compute_dtype()}
