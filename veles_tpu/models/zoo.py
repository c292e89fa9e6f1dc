"""Reference model zoo: AlexNet and VGG layer specs
(manualrst_veles_algorithms.rst:157 names AlexNet & VGG as the
reference models).

Each builder returns a ``layers`` list for StandardWorkflow; the
benchmark's cells (``benchmark/configs/``) build the same specs.
bf16-friendly: all the FLOPs sit in conv/fc layers that the compiler
lowers onto the MXU.
"""

__all__ = ["alexnet_layers", "vgg_layers", "mnist_mlp_layers",
           "autoencoder_layers", "transformer_layers",
           "mla_moe_decoder_layers", "hybrid_moe_decoder_layers",
           "build_plans_and_state"]


def build_plans_and_state(specs, input_shape, seed=0):
    """Compile LayerPlans + an initial fused-step state for a spec list
    WITHOUT building the unit graph (used by the graft entry, the
    tuner's walk and the receipt scripts, where no loader exists).
    input_shape excludes batch."""
    import numpy

    from veles_tpu.compiler import LayerPlan
    from veles_tpu.models.nn_workflow import forward_mapping

    fmap = forward_mapping()
    rng = numpy.random.RandomState(seed)
    plans, state = [], []
    shape = tuple(input_shape)

    def entry(w_shape, b_shape):
        fan_in = int(numpy.prod(w_shape[:-1]))
        weights = (rng.uniform(-1, 1, w_shape) /
                   numpy.sqrt(fan_in)).astype(numpy.float32)
        return {
            "weights": weights,
            "bias": numpy.zeros(b_shape, numpy.float32),
            "accum_weights": numpy.zeros(w_shape, numpy.float32),
            "accum_bias": numpy.zeros(b_shape, numpy.float32),
            "accum2_weights": None, "accum2_bias": None}

    def none_entry():
        return {"weights": None, "bias": None, "accum_weights": None,
                "accum_bias": None, "accum2_weights": None,
                "accum2_bias": None}

    for spec in specs:
        spec = dict(spec)
        ltype = spec.pop("type")
        cls = fmap[ltype]
        hyper = {k: spec[k] for k in
                 ("learning_rate", "gradient_moment", "weights_decay",
                  "l1_vs_l2") if k in spec}
        if ltype in ("conv", "conv_tanh", "conv_relu", "conv_str",
                     "conv_sigmoid"):
            from veles_tpu.models.conv import _norm_padding
            k = spec["kx"]
            n = spec["n_kernels"]
            sx, sy = spec.get("sliding", (1, 1))
            left, top, right, bottom = _norm_padding(
                spec.get("padding", 0))
            h, w = shape[0], shape[1]
            ch = shape[2] if len(shape) > 2 else 1
            out_h = (h + top + bottom - spec["ky"]) // sy + 1
            out_w = (w + left + right - k) // sx + 1
            plans.append(LayerPlan(
                cls, hyper=hyper,
                static={"padding": (left, top, right, bottom),
                        "sliding": (sx, sy)}))
            state.append(entry((spec["ky"], k, ch, n), (n,)))
            shape = (out_h, out_w, n)
        elif ltype in ("max_pooling", "avg_pooling", "maxabs_pooling"):
            from veles_tpu.models.pooling import _out_len
            kx, ky = spec["kx"], spec["ky"]
            sx, sy = spec.get("sliding", (kx, ky))
            plans.append(LayerPlan(
                cls, include_bias=False,
                static={"window": (ky, kx), "sliding": (sx, sy)}))
            state.append(none_entry())
            shape = (_out_len(shape[0], ky, sy),
                     _out_len(shape[1], kx, sx),
                     shape[2] if len(shape) > 2 else 1)
        elif ltype == "dropout":
            plans.append(LayerPlan(
                cls, include_bias=False,
                static={"dropout_ratio": spec.get("dropout_ratio",
                                                  0.5)}))
            state.append(none_entry())
        elif ltype == "transformer":
            from veles_tpu.models.transformer import init_block_params
            d = shape[-1]
            heads = spec.get("heads", 1)
            if d % heads:
                # the unit path's clear error, not a deep-jit reshape
                # failure at first trace
                raise ValueError("features %d %% heads %d != 0"
                                 % (d, heads))
            hidden = spec.get("hidden") or 4 * d
            plans.append(LayerPlan(
                cls, hyper=hyper,
                static={"heads": heads, "hidden": hidden,
                        "eps": spec.get("eps", 1e-5)}))
            weights, bias = init_block_params(d, hidden, rng)
            state.append({
                "weights": weights, "bias": bias,
                "accum_weights": numpy.zeros_like(weights),
                "accum_bias": numpy.zeros_like(bias),
                "accum2_weights": None, "accum2_bias": None})
        elif ltype == "attention":
            d = shape[-1]
            heads = spec.get("heads", 1)
            if d % heads:
                raise ValueError("features %d %% heads %d != 0"
                                 % (d, heads))
            plans.append(LayerPlan(
                cls, hyper=hyper, static={"heads": heads}))
            state.append(entry((d, 4 * d), (4 * d,)))
        elif ltype == "layer_norm":
            d = shape[-1]
            plans.append(LayerPlan(
                cls, hyper=hyper,
                static={"eps": spec.get("eps", 1e-5)}))
            gamma = numpy.ones((d,), numpy.float32)
            state.append({
                "weights": gamma,
                "bias": numpy.zeros((d,), numpy.float32),
                "accum_weights": numpy.zeros_like(gamma),
                "accum_bias": numpy.zeros((d,), numpy.float32),
                "accum2_weights": None, "accum2_bias": None})
        else:  # all2all family
            fan_in = int(numpy.prod(shape))
            out = spec["output_sample_shape"]
            out = int(numpy.prod(out)) if not isinstance(out, int) \
                else out
            plans.append(LayerPlan(cls, hyper=hyper))
            state.append(entry((fan_in, out), (out,)))
            shape = (out,)
    return plans, state, shape


def transformer_layers(blocks=2, heads=2, hidden=None, classes=10,
                       lr=0.05, moment=0.9):
    """Sequence-classification transformer: a homogeneous pre-LN block
    stack over (B, T, D) input with a softmax head flattening the
    final sequence — the workload the flash-attention kernel, the
    tensor-parallel head sharding, and the pipeline stage split all
    drive (docs/distributed.md "Model parallelism")."""
    spec = [{"type": "transformer", "heads": heads, "hidden": hidden,
             "learning_rate": lr, "gradient_moment": moment}
            for _ in range(blocks)]
    spec.append({"type": "softmax", "output_sample_shape": classes,
                 "learning_rate": lr, "gradient_moment": moment})
    return spec


def _adamw_spec(lr, beta1, beta2, adam_eps, decay, init_std, eps):
    """What every unit of a decoder takes: AdamW (decay on the matrices
    only), the initialisation's std and the norms' epsilon."""
    return {"solver": "adamw", "learning_rate": lr,
            "gradient_moment": beta1, "adadelta_rho": beta2,
            "solver_epsilon": adam_eps, "weights_decay": decay,
            "weights_decay_bias": 0.0, "weights_stddev": init_std,
            "eps": eps}


def mla_moe_decoder_layers(vocab, width, layers, heads, qk_nope, qk_rope,
                           v_head, kv_rank, ffn, experts, experts_held,
                           top_k, expert_width, shared_width,
                           dense_layers=1, first_expert=0,
                           routed_scale=1.0, theta=1e6, eps=1e-6,
                           lr=1e-4, beta1=0.9, beta2=0.95, adam_eps=1e-8,
                           decay=0.1, init_std=0.02, out_init_std=None,
                           router_bias_std=0.0):
    """A causal decoder of the DeepSeek-V3 layer family
    (models/decoder.py): token embedding, ``layers`` latent-attention
    layers — the first ``dense_layers`` with a gated feed-forward ``ffn``
    wide, the rest routed over ``experts`` of which this program holds
    ``experts_held`` from ``first_expert`` on, ``top_k`` a token, beside a
    shared expert ``shared_width`` wide — and the output head over the
    ``vocab`` rows held.  ``out_init_std`` is the std of the matrices
    that write into the residual stream (None: ``init_std``, like the
    rest).  Trained with AdamW; the loader serves (B, T) ids and (B, T)
    next ids (``loader.TokenRowLoader``)."""
    solver = _adamw_spec(lr, beta1, beta2, adam_eps, decay, init_std, eps)
    attention = {"heads": heads, "qk_nope": qk_nope, "qk_rope": qk_rope,
                 "v_head": v_head, "kv_rank": kv_rank, "theta": theta}
    routed = {"experts": experts, "experts_held": experts_held,
              "first_expert": first_expert, "top_k": top_k,
              "expert_width": expert_width, "shared_width": shared_width,
              "routed_scale": routed_scale,
              "router_bias_stddev": router_bias_std}
    spec = [dict(solver, type="decoder_embedding", vocab=vocab,
                 width=width)]
    for index in range(layers):
        body = {"ffn": ffn} if index < dense_layers else routed
        spec.append(dict(solver, type="decoder_layer",
                         out_stddev=out_init_std, **attention, **body))
    spec.append(dict(solver, type="decoder_head", vocab=vocab))
    return spec


def _layer_kind(kind, known):
    if kind not in known:
        raise ValueError("layer_types holds %s, got %r" % (
            " or ".join("\"%s\"" % name for name in known), kind))
    return kind


def gqa_moe_decoder_layers(vocab, width, layer_types, heads, kv_heads,
                           head_width, window, ffn, experts, experts_held,
                           top_k, expert_width, shared_width,
                           dense_layers=1, first_expert=0,
                           routed_scale=1.0, route_eps=0.0, theta=1e4,
                           eps=1e-5, embed_scale=1.0, lr=1e-4, beta1=0.9,
                           beta2=0.95, adam_eps=1e-8, decay=0.1,
                           init_std=0.02, router_bias_std=0.0,
                           post_norm_gain=1.0, rope=None, out_gate=True,
                           post_norms=True, router="sigmoid",
                           index_heads=None, index_width=None,
                           index_topk=None, out_init_std=None):
    """A causal decoder of grouped-query attention layers
    (models/decoder.py): token embedding times ``embed_scale``, one layer
    for each entry of ``layer_types`` — ``"window"`` (keys at most
    ``window`` back), ``"full"`` (every earlier key) or ``"selected"``
    (the ``index_topk`` earlier keys a lightning indexer of
    ``index_heads`` heads ``index_width`` wide keeps: an indexer's pieces
    in the layer, trained by its own loss) — ``heads`` query heads
    reading ``kv_heads`` key/value heads ``head_width`` wide, with an
    output gate unless ``out_gate`` is False and a norm after each
    sub-layer as well as before it unless ``post_norms`` is False (the
    post-norms' gains start from ``post_norm_gain``).  ``rope`` says,
    layer by layer, which layers rotate q and k; by default the
    ``"window"`` layers do and the others get no position signal.  The
    first ``dense_layers`` have a gated feed-forward ``ffn`` wide (0: none
    is dense), the rest are routed as :func:`mla_moe_decoder_layers`
    routes — by a sigmoid with a correction bias, or with ``router``
    ``"softmax"`` by a softmax renormalised over the chosen — beside a
    shared expert where ``shared_width`` is not 0; the output head is
    over the ``vocab`` rows held.  Solver, initialisation
    (``out_init_std`` for the matrices that write into the residual
    stream) and loader as there."""
    solver = _adamw_spec(lr, beta1, beta2, adam_eps, decay, init_std, eps)
    routed = {"experts": experts, "experts_held": experts_held,
              "first_expert": first_expert, "top_k": top_k,
              "expert_width": expert_width, "shared_width": shared_width,
              "routed_scale": routed_scale, "route_eps": route_eps,
              "router_bias_stddev": router_bias_std}
    if router != "sigmoid":
        routed["router"] = router
    kinds = ("window", "full", "selected")
    norms = {"post_norms": True, "post_gain": post_norm_gain} \
        if post_norms else {"post_norms": False}
    spec = [dict(solver, type="decoder_embedding", vocab=vocab,
                 width=width, scale=embed_scale)]
    for index, kind in enumerate(layer_types):
        kind = _layer_kind(kind, kinds)
        windowed = kind == "window"
        body = {"ffn": ffn} if index < dense_layers else routed
        mixer = {} if out_gate else {"out_gate": False}
        if kind == "selected":
            mixer.update(index_heads=index_heads, index_width=index_width,
                         index_topk=index_topk)
        if out_init_std is not None:
            mixer["out_stddev"] = out_init_std
        spec.append(dict(
            solver, type="decoder_layer", heads=heads,
            kv_heads=kv_heads, head_width=head_width,
            window=window if windowed else None,
            rope=windowed if rope is None else bool(rope[index]),
            theta=theta, **norms, **mixer, **body))
    spec.append(dict(solver, type="decoder_head", vocab=vocab))
    return spec


def conv_gqa_moe_decoder_layers(vocab, width, layer_types, heads, kv_heads,
                                head_width, conv_taps, ffn, experts,
                                experts_held, top_k, expert_width,
                                dense_layers=1, first_expert=0,
                                routed_scale=1.0, route_eps=0.0, theta=1e6,
                                eps=1e-5, tied_head=True, lr=1e-4,
                                beta1=0.9, beta2=0.95, adam_eps=1e-8,
                                decay=0.1, init_std=0.02,
                                out_init_std=None, router_bias_std=0.0):
    """A causal decoder whose token mixer is a gated short convolution
    in most layers (models/decoder.py): token embedding, one pre-norm
    layer for each entry of ``layer_types`` — ``"conv"`` (a depthwise
    causal filter of ``conv_taps`` taps between two gates) or
    ``"attention"`` (``heads`` query heads reading ``kv_heads``
    key/value heads ``head_width`` wide over every earlier key, rotary
    positions, QK-norm, no output gate) — the first ``dense_layers``
    with a gated feed-forward ``ffn`` wide, the rest routed as
    :func:`mla_moe_decoder_layers` routes but with NO shared expert,
    and the output head over the ``vocab`` rows held, tied to the
    embedding's table unless ``tied_head`` is False.  Solver,
    initialisation (``out_init_std`` for the matrices that write into
    the residual stream) and loader as there."""
    solver = _adamw_spec(lr, beta1, beta2, adam_eps, decay, init_std, eps)
    mixers = {"conv": {"conv_taps": conv_taps},
              "attention": {"heads": heads, "kv_heads": kv_heads,
                            "head_width": head_width, "rope": True,
                            "out_gate": False, "theta": theta}}
    routed = {"experts": experts, "experts_held": experts_held,
              "first_expert": first_expert, "top_k": top_k,
              "expert_width": expert_width, "shared_width": 0,
              "routed_scale": routed_scale, "route_eps": route_eps,
              "router_bias_stddev": router_bias_std}
    spec = [dict(solver, type="decoder_embedding", vocab=vocab,
                 width=width)]
    for index, kind in enumerate(layer_types):
        body = {"ffn": ffn} if index < dense_layers else routed
        spec.append(dict(solver, type="decoder_layer",
                         out_stddev=out_init_std,
                         **mixers[_layer_kind(kind, tuple(mixers))],
                         **body))
    head = {"tied_to": 0} if tied_head else {}
    spec.append(dict(solver, type="decoder_head", vocab=vocab, **head))
    return spec


def hybrid_moe_decoder_layers(vocab, width, layer_types, heads, kv_heads,
                              head_width, ssm_heads, ssm_head_width,
                              ssm_groups, ssm_state, ssm_chunk, conv_taps,
                              experts, experts_held, top_k, expert_width,
                              shared_width, first_expert=0,
                              routed_scale=1.0, eps=1e-5, lr=1e-4,
                              beta1=0.9, beta2=0.95, adam_eps=1e-8,
                              decay=0.1, init_std=0.02, out_init_std=None):
    """A causal decoder whose layers are each ONE part
    (models/decoder.py), one for each entry of ``layer_types``:
    ``"ssm"`` (Mamba-2's state-space mixer: ``ssm_heads`` heads
    ``ssm_head_width`` wide, B and C in ``ssm_groups`` groups of
    ``ssm_state``, scanned in chunks of ``ssm_chunk`` tokens, its filter
    ``conv_taps`` long), ``"attention"`` (``heads`` query heads reading
    ``kv_heads`` key/value heads ``head_width`` wide over every earlier
    key: no position signal, no norm of q or k, no output gate) or
    ``"routed"`` (``experts`` routed by a sigmoid with a correction
    bias, ``top_k`` a token, of which this program holds
    ``experts_held`` from ``first_expert`` on, relu² experts
    ``expert_width`` wide beside a relu² shared expert ``shared_width``
    wide); pre-norms only; token embedding and an untied output head over
    the ``vocab`` rows held.  Solver, initialisation (``out_init_std`` for
    the matrices that write into the residual stream) and loader as
    :func:`mla_moe_decoder_layers`'."""
    solver = _adamw_spec(lr, beta1, beta2, adam_eps, decay, init_std, eps)
    parts = {
        "ssm": {"ssm_heads": ssm_heads, "ssm_head_width": ssm_head_width,
                "ssm_groups": ssm_groups, "ssm_state": ssm_state,
                "ssm_chunk": ssm_chunk, "conv_taps": conv_taps},
        "attention": {"heads": heads, "kv_heads": kv_heads,
                      "head_width": head_width, "rope": False,
                      "out_gate": False, "qk_norm": False},
        "routed": {"experts": experts, "experts_held": experts_held,
                   "first_expert": first_expert, "top_k": top_k,
                   "expert_width": expert_width,
                   "shared_width": shared_width,
                   "routed_scale": routed_scale, "expert_act": "relu2"}}
    spec = [dict(solver, type="decoder_embedding", vocab=vocab,
                 width=width)]
    for kind in layer_types:
        spec.append(dict(solver, type="decoder_layer",
                         out_stddev=out_init_std,
                         **parts[_layer_kind(kind, tuple(parts))]))
    spec.append(dict(solver, type="decoder_head", vocab=vocab))
    return spec


def mnist_mlp_layers(hidden=100, classes=10, lr=0.1, moment=0.9):
    """BASELINE config 1: the 784-hidden-10 fully-connected net."""
    return [
        {"type": "all2all_tanh", "output_sample_shape": hidden,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "softmax", "output_sample_shape": classes,
         "learning_rate": lr, "gradient_moment": moment},
    ]


def autoencoder_layers(bottleneck=16, hidden=64, out_features=None,
                       lr=0.01, moment=0.9):
    """MNIST-style MLP autoencoder (validation RMSE baseline 0.5478)."""
    spec = [
        {"type": "all2all_tanh", "output_sample_shape": hidden,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "all2all_tanh", "output_sample_shape": bottleneck,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "all2all_tanh", "output_sample_shape": hidden,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "all2all", "output_sample_shape": out_features,
         "learning_rate": lr, "gradient_moment": moment},
    ]
    return spec


def _conv(n, k, lr, moment, stride=1, pad=None, act="conv_str"):
    spec = {"type": act, "n_kernels": n, "kx": k, "ky": k,
            "learning_rate": lr, "gradient_moment": moment}
    if stride != 1:
        spec["sliding"] = (stride, stride)
    spec["padding"] = (k // 2) if pad is None else pad
    return spec


def _pool(k=3, stride=2):
    return {"type": "max_pooling", "kx": k, "ky": k,
            "sliding": (stride, stride)}


def alexnet_layers(classes=1000, lr=0.01, moment=0.9, dropout=0.5):
    """AlexNet (227x227x3 input)."""
    return [
        _conv(96, 11, lr, moment, stride=4, pad=0),
        _pool(),
        _conv(256, 5, lr, moment),
        _pool(),
        _conv(384, 3, lr, moment),
        _conv(384, 3, lr, moment),
        _conv(256, 3, lr, moment),
        _pool(),
        {"type": "all2all_str", "output_sample_shape": 4096,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "all2all_str", "output_sample_shape": 4096,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "softmax", "output_sample_shape": classes,
         "learning_rate": lr, "gradient_moment": moment},
    ]


def vgg_layers(classes=1000, lr=0.01, moment=0.9, dropout=0.5,
               config="D"):
    """VGG (224x224x3).  config "A"=VGG11, "D"=VGG16, "E"=VGG19."""
    plan = {
        "A": [(64, 1), (128, 1), (256, 2), (512, 2), (512, 2)],
        "D": [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)],
        "E": [(64, 2), (128, 2), (256, 4), (512, 4), (512, 4)],
    }[config]
    layers = []
    for channels, repeats in plan:
        for _ in range(repeats):
            layers.append(_conv(channels, 3, lr, moment))
        layers.append(_pool(k=2, stride=2))
    layers += [
        {"type": "all2all_str", "output_sample_shape": 4096,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "all2all_str", "output_sample_shape": 4096,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "softmax", "output_sample_shape": classes,
         "learning_rate": lr, "gradient_moment": moment},
    ]
    return layers
