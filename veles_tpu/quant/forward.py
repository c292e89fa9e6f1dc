"""The quantized forward builder — ``compiler.build_forward``'s int8
twin.

Per layer: quantize the f32 activation onto the calibrated per-tensor
grid (``clip(round(x / act_scale), -127, 127)``), run the int8 Pallas
kernel (matmul for all2all layers, the im2col conv for conv layers —
both over the shared :func:`veles_tpu.ops.common.mxu_int8_dot` product
step) with int32 accumulation and the fused dequant epilogue
(``f32(acc) * (act_scale * weights_scale[c]) + bias``), then the
layer's own f32 activation function.  Activations carry f32 between
layers — the w8a8 recipe with an f32 spine, which keeps softmax /
tanh / pooling semantics untouched and lets non-quantized layers mix
freely in one ladder.

The builder consumes the entry layout :func:`veles_tpu.quant.ptq.
quantize_model_spec` produces; :func:`is_quantized_params` is how
:class:`~veles_tpu.serve.engine.AOTEngine` decides which forward to
compile — presence of ``weights_scale`` in any entry, nothing else,
so a quantized spec needs no side-channel flag through the snapshot /
publish / watcher pipeline.
"""

import functools

__all__ = ["build_quantized_forward", "f32_layer_apply",
           "is_quantized_entry", "is_quantized_params",
           "quantize_activation", "walk_forward"]


def is_quantized_entry(entry):
    """One layer's params are int8-quantized (pass artifacts present)."""
    return entry is not None and entry.get("weights_scale") is not None


def is_quantized_params(params):
    """True when ANY layer entry carries quantization artifacts — the
    AOTEngine's forward-selection predicate."""
    return any(is_quantized_entry(entry) for entry in params)


def quantize_activation(x, act_scale):
    """On-device activation quantization onto the calibrated symmetric
    grid.  ``jnp.round`` is round-half-even, the same rule as the
    host-side ``numpy.rint`` in ptq.py — one rounding rule everywhere."""
    import jax.numpy as jnp
    from veles_tpu.quant.ptq import QMAX
    q = jnp.round(x / act_scale)
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def _apply_quantized(plan, entry, h):
    """One quantized layer: quantize input, int8 kernel with fused
    dequant+bias, f32 activation."""
    import jax.numpy as jnp

    from veles_tpu.models.conv import Conv
    from veles_tpu.ops.matmul_int8 import conv2d_int8, matmul_int8

    act_scale = entry["act_scale"].astype(jnp.float32)
    # combined dequant factor: activation scale x per-channel weight
    # scale, folded HERE so the kernel epilogue is one multiply
    scale = act_scale * entry["weights_scale"].astype(jnp.float32)
    bias = entry.get("bias")
    if issubclass(plan.forward_cls, Conv):
        x = h
        if x.ndim == 3:
            x = x[..., None]
        z = conv2d_int8(
            quantize_activation(x, act_scale), entry["weights"],
            scale, bias=bias,
            padding=plan.static.get("padding", (0, 0, 0, 0)),
            sliding=plan.static.get("sliding", (1, 1)))
    else:
        x2 = h.reshape(h.shape[0], -1)
        z = matmul_int8(quantize_activation(x2, act_scale),
                        entry["weights"], scale, bias=bias)
    return z


def walk_forward(plans, params, x, layer_fn):
    """The ONE inference layer walk the quantized forward AND the
    calibration pass share — mirroring ``compiler.build_forward``'s
    semantics (dropout is identity at inference, softmax applied only
    at the tail) so the walk rules cannot drift between the f32
    reference, the int8 twin and the statistics the scales are solved
    from.  ``layer_fn(i, plan, entry, h) -> h`` owns the per-layer
    arithmetic; dropout layers never reach it."""
    import jax

    from veles_tpu.compiler import refuse_tied_plans
    from veles_tpu.models.all2all import All2AllSoftmax
    from veles_tpu.models.dropout import DropoutForward

    refuse_tied_plans(plans, "the quantized forward")
    h = x
    for i, (plan, entry) in enumerate(zip(plans, params)):
        if issubclass(plan.forward_cls, DropoutForward):
            continue  # identity at inference (inverted dropout)
        h = layer_fn(i, plan, entry, h)
    if plans and plans[-1].forward_cls is All2AllSoftmax:
        h = jax.nn.softmax(h, axis=-1)
    return h


def f32_layer_apply(plan, entry, h):
    """One f32 layer step with ``build_forward``'s semantics: an
    All2AllSoftmax layer keeps its LOGITS (the tail softmax belongs to
    the walk), everything else runs its stock ``apply`` with the
    plan's static config."""
    from veles_tpu.models.all2all import All2All, All2AllSoftmax
    if plan.forward_cls is All2AllSoftmax:
        return All2All.apply(entry, h)
    return functools.partial(plan.forward_cls.apply,
                             **plan.static)(entry, h)


def build_quantized_forward(plans):
    """Pure inference fn(params_list, x) -> output, the int8 mirror of
    ``compiler.build_forward``: same layer walk (:func:`walk_forward`),
    same softmax tail, same dropout-is-identity rule — only the
    parameterized layers' arithmetic runs on the int8 level.  Entries
    without quantization artifacts run their stock f32 ``apply``, so
    partially-quantized specs work layer by layer."""
    def forward(params, x):
        import jax.numpy as jnp

        from veles_tpu.models.all2all import All2AllSoftmax

        def layer(i, plan, entry, h):
            if not is_quantized_entry(entry):
                return f32_layer_apply(plan, entry, h)
            z = _apply_quantized(plan, entry, h)
            if plan.forward_cls is All2AllSoftmax:
                return z  # keep logits; softmax applied at the tail
            return plan.forward_cls._activate(z).astype(jnp.float32)

        return walk_forward(plans, params, x, layer)
    return forward
