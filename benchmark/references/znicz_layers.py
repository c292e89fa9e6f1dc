"""The plain reference of the configurations built from Znicz layer
specs (``alexnet``, ``mnist_mlp``): each layer type's forward pass in
``jax.numpy`` and ``lax``, float32, true-float32 products, written from
the layer specs (``zoo.*_layers()`` dicts).  No kernels, no cache, no
batching; nothing is imported from ``veles_tpu.models``.  A configuration
names its reference in its file (``reference.module``); one with other
layers brings a module of its own, with ``forward`` and ``step_cost``.

Layer types (Znicz names): ``conv_str`` / ``all2all_str`` end in
max(x, 0); ``all2all_tanh`` in LeCun's 1.7159 * tanh(0.6666 x);
``softmax`` is a dense layer under a softmax; ``max_pooling`` covers the
whole input (a partial window at the edge counts); ``dropout`` is the
identity outside training.  Weights are HWIO for a conv and (inputs,
outputs) for a dense layer, over NHWC activations flattened row-major.
"""

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.flops import padding4, pooled_length
from benchmark.flops import step_cost  # noqa: F401  (counts these layers)


def _conv(spec, params, x, activate):
    if x.ndim == 3:
        x = x[..., None]
    left, top, right, bottom = padding4(spec.get("padding", 0))
    sx, sy = spec.get("sliding", (1, 1))
    z = lax.conv_general_dilated(
        x, params["weights"], window_strides=(sy, sx),
        padding=((top, bottom), (left, right)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if params.get("bias") is not None:
        z = z + params["bias"]
    return activate(z)


def _dense(spec, params, x, activate):
    z = x.reshape(x.shape[0], -1) @ params["weights"]
    if params.get("bias") is not None:
        z = z + params["bias"]
    return activate(z)


def _covering(length, window, stride):
    """Padding after the last element so that windows cover it all."""
    return ((pooled_length(length, window, stride) - 1) * stride + window
            - length)


def _max_pooling(spec, params, x, activate):
    if x.ndim == 3:
        x = x[..., None]
    ky, kx = spec["ky"], spec["kx"]
    sx, sy = spec.get("sliding", (kx, ky))
    return lax.reduce_window(
        x, -jnp.inf, lax.max, window_dimensions=(1, ky, kx, 1),
        window_strides=(1, sy, sx, 1),
        padding=((0, 0), (0, _covering(x.shape[1], ky, sy)),
                 (0, _covering(x.shape[2], kx, sx)), (0, 0)))


def _relu(z):
    return jnp.maximum(z, 0)


def _tanh(z):
    return 1.7159 * jnp.tanh(0.6666 * z)


def _softmax(z):
    return jax.nn.softmax(z, axis=-1)


LAYERS = {
    "conv_str": (_conv, _relu),
    "all2all_str": (_dense, _relu),
    "all2all_tanh": (_dense, _tanh),
    "all2all": (_dense, lambda z: z),
    "softmax": (_dense, _softmax),
    "max_pooling": (_max_pooling, None),
    "dropout": (lambda spec, params, x, activate: x, None),
}


def forward(layers, params, x):
    """Outputs (probabilities under a softmax head) of ``layers`` with
    one ``{"weights", "bias"}`` entry of ``params`` per layer."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(x, jnp.float32)
        for spec, entry in zip(layers, params):
            layer, activate = LAYERS[spec["type"]]
            entry = {k: None if v is None else jnp.asarray(v, jnp.float32)
                     for k, v in entry.items()}
            h = layer(spec, entry, h, activate)
        return h
