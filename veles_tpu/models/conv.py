"""Convolutional forward units.

Znicz-equivalent conv family (manualrst_veles_algorithms.rst: conv with
padding and "sliding" stride, plus activation fusions).  Layout is NHWC
with HWIO kernels — the layout XLA:TPU prefers for feeding the MXU — and
the conv itself is ``lax.conv_general_dilated`` with f32 accumulation;
the activation fuses into the same XLA computation.

kwargs: n_kernels, kx, ky (kernel width/height), sliding=(sx, sy),
padding=(left, top, right, bottom) or int, plus the ForwardBase
weight-init kwargs.
"""


import numpy

from veles_tpu.models.all2all import (
    All2AllRELU, All2AllSigmoid, All2AllStrictRELU, All2AllTanh)
from veles_tpu.models.nn_units import ForwardBase

__all__ = ["Conv", "ConvTanh", "ConvRELU", "ConvStrictRELU", "ConvSigmoid"]


def _norm_padding(padding):
    if isinstance(padding, int):
        return (padding, padding, padding, padding)
    if len(padding) == 2:
        return (padding[0], padding[1], padding[0], padding[1])
    return tuple(padding)


def conv2d(x, w, strides, padding, pet=None):
    """The one conv entry point (autodiff gradients: stock autodiff
    keeps forward-mode AD usable; the hand-scheduled backward attaches
    one level up, in ``Conv.apply``)."""
    from jax import lax
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=pet)


class Conv(ForwardBase):
    """y = activation(conv2d(x, W) + b).

    With the ``VELES_PALLAS_BWD`` knob on (docs/kernels.md), ``apply``
    routes through the ``ops.conv_vjp.conv_act`` custom_vjp: the
    forward HLO is bit-identical (same conv + bias + activation
    composition), but the backward the fused step differentiates is
    the hand-scheduled family — fused activation-backward/bias-grad
    epilogue in the Pallas wgrad tiles, dgrad as the explicit
    lhs-dilated conv.  ``ACTIVATION`` names the epilogue.
    """

    MAPPING = "conv"
    ACTIVATION = "linear"

    def __init__(self, workflow, **kwargs):
        super(Conv, self).__init__(workflow, **kwargs)
        self.n_kernels = kwargs["n_kernels"]
        self.kx = kwargs["kx"]
        self.ky = kwargs["ky"]
        self.sliding = tuple(kwargs.get("sliding", (1, 1)))
        self.padding = _norm_padding(kwargs.get("padding", 0))

    @staticmethod
    def _activate(z):
        return z

    @classmethod
    def apply(cls, params, x, *, padding=(0, 0, 0, 0), sliding=(1, 1),
              pallas_bwd=None):
        import jax.numpy as jnp
        W = params["weights"]
        if x.ndim == 3:
            x = x[..., None]
        if pallas_bwd is None:
            from veles_tpu.ops.common import pallas_bwd_enabled
            pallas_bwd = pallas_bwd_enabled()
        if pallas_bwd:
            # forward-identical custom_vjp carrying the hand-scheduled
            # backward (ops/conv_vjp.py); pallas_bwd=False restores
            # the stock autodiff path below bit-exactly
            from veles_tpu.ops.conv_vjp import conv_act
            return conv_act(x, W, params.get("bias"),
                            activation=cls.ACTIVATION, padding=padding,
                            sliding=sliding)
        left, top, right, bottom = padding
        sx, sy = sliding
        # preferred_element_type=f32 + cast breaks the conv transpose
        # rule for bf16 (mixed-dtype cotangent); the MXU accumulates
        # bf16 convs in f32 in hardware regardless, so only request a
        # wider output when the input is already f32.
        pet = jnp.float32 if x.dtype == jnp.float32 else None
        z = conv2d(x, W, (sy, sx), ((top, bottom), (left, right)),
                   pet)
        if params.get("bias") is not None:
            z = z + params["bias"]
        return cls._activate(z).astype(x.dtype)

    def static_config(self):
        return {"padding": self.padding, "sliding": self.sliding}

    def output_spatial(self, in_h, in_w):
        left, top, right, bottom = self.padding
        sx, sy = self.sliding
        out_h = (in_h + top + bottom - self.ky) // sy + 1
        out_w = (in_w + left + right - self.kx) // sx + 1
        return out_h, out_w

    def create_params(self):
        if not self.input or self.input.sample_size == 0:
            raise AttributeError(
                "%s: input shape unknown at initialize" % self.name)
        shape = self.input.shape
        if len(shape) == 3:
            batch, in_h, in_w, in_ch = shape + (1,)
        else:
            batch, in_h, in_w, in_ch = shape
        fan_in = self.kx * self.ky * in_ch
        if not self.output:
            out_h, out_w = self.output_spatial(in_h, in_w)
            self.output.mem = numpy.zeros(
                (batch, out_h, out_w, self.n_kernels), numpy.float32)
        if self.weights:
            return
        weights = numpy.zeros(
            (self.ky, self.kx, in_ch, self.n_kernels), numpy.float32)
        self.fill_array(weights, self.weights_filling, self.weights_stddev,
                        fan_in)
        self.weights.mem = weights
        if self.include_bias:
            bias = numpy.zeros((self.n_kernels,), numpy.float32)
            self.fill_array(bias, self.bias_filling, self.bias_stddev,
                            fan_in)
            self.bias.mem = bias


class ConvTanh(Conv):
    MAPPING = "conv_tanh"
    ACTIVATION = "tanh"
    _activate = staticmethod(All2AllTanh._activate)


class ConvRELU(Conv):
    MAPPING = "conv_relu"
    ACTIVATION = "relu_log"
    _activate = staticmethod(All2AllRELU._activate)


class ConvStrictRELU(Conv):
    MAPPING = "conv_str"
    ACTIVATION = "strict_relu"
    _activate = staticmethod(All2AllStrictRELU._activate)


class ConvSigmoid(Conv):
    MAPPING = "conv_sigmoid"
    ACTIVATION = "sigmoid"
    _activate = staticmethod(All2AllSigmoid._activate)
