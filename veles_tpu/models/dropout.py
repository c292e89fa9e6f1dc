"""Dropout forward/backward units (Znicz-equivalent dropout).

The reference generated the mask with the device xorshift PRNG
(veles/prng/uniform.py) and multiplied activations by it.  Here the mask
comes from the counter-based ``jax.random`` (threefry) keyed off the
reproducible host PRNG — same reproducibility guarantee, no mutable
device RNG state to checkpoint (veles_tpu.ops.random keeps the bit-exact
xorshift kernels for anyone needing stream parity).

Inverted dropout: kept activations are scaled by 1/(1-p) at train time so
inference needs no rescale.  Dropout only applies on TRAIN minibatches
(``minibatch_class`` linked from the loader); evaluation passes through.
"""

import numpy

from veles_tpu import prng
from veles_tpu.loader.base import TRAIN
from veles_tpu.memory import Array
from veles_tpu.models.nn_units import ForwardBase, GradientDescentBase

__all__ = ["DropoutForward", "DropoutBackward"]


class DropoutForward(ForwardBase):
    """kwargs: dropout_ratio (probability of DROPPING a unit)."""

    MAPPING = "dropout"

    def __init__(self, workflow, **kwargs):
        super(DropoutForward, self).__init__(workflow, **kwargs)
        self.dropout_ratio = kwargs.get("dropout_ratio", 0.5)
        self.minibatch_class = None  # linked from loader
        self.mask = Array()
        self.prng = kwargs.get("prng", prng.get())
        self.demand("minibatch_class")
        self._step = 0

    def static_config(self):
        return {"dropout_ratio": self.dropout_ratio}

    def create_params(self):
        if not self.input or self.input.sample_size == 0:
            raise AttributeError(
                "%s: input shape unknown at initialize" % self.name)
        if not self.output:
            self.output.mem = numpy.zeros(self.input.shape, numpy.float32)

    def param_arrays(self):
        return []

    @staticmethod
    def make_mask(key, shape, ratio, dtype):
        import jax
        keep = 1.0 - ratio
        bern = jax.random.bernoulli(key, keep, shape)
        return bern.astype(dtype) / keep

    def run(self):
        import jax
        self._step += 1
        if self.minibatch_class != TRAIN:
            # pass-through on eval minibatches
            if self.on_device():
                self.output.set_device_array(self.input.devmem, self.device)
            else:
                self.input.map_read()
                self.output.map_invalidate()
                self.output.mem = numpy.array(self.input.mem)
            self.mask.reset()
            return
        seed = numpy.uint32((self.prng.seed_value or 0) & 0xffffffff)
        step = numpy.uint32(self._step & 0xffffffff)
        if self.on_device():
            if self._jit_fn_ is None:
                # seed/step ride as jit ARGUMENTS and the key is built
                # inside the program: eager PRNGKey+fold_in per
                # minibatch would be two more dispatches each
                def fwd(seed, step, x, ratio):
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(seed), step)
                    mask = DropoutForward.make_mask(
                        key, x.shape, ratio, x.dtype)
                    return x * mask, mask
                self._jit_fn_ = jax.jit(fwd, static_argnums=(3,))
            out, mask = self._jit_fn_(seed, step, self.input.devmem,
                                      self.dropout_ratio)
            self.output.set_device_array(out, self.device)
            self.mask.set_device_array(mask, self.device)
        else:
            from veles_tpu.backends import host_compute_context
            self.input.map_read()
            with host_compute_context(self.device):
                key = jax.random.fold_in(
                    jax.random.PRNGKey(seed), step)
                mask = numpy.asarray(DropoutForward.make_mask(
                    key, self.input.mem.shape, self.dropout_ratio,
                    self.input.mem.dtype))
            self.output.map_invalidate()
            self.output.mem = self.input.mem * mask
            self.mask.map_invalidate()
            self.mask.mem = mask


class DropoutBackward(GradientDescentBase):
    """err_input = err_output * mask (identity on eval minibatches)."""

    MAPPING = "dropout"

    def __init__(self, workflow, **kwargs):
        super(DropoutBackward, self).__init__(workflow, **kwargs)
        self.mask = None  # linked from DropoutForward
        self._demanded -= {"weights", "output", "input"}
        self.demand("mask")

    def _init_solver_state(self):
        pass

    def run(self):
        if not self.mask:  # eval minibatch: mask was reset
            if self.on_device() and self.err_output.devmem is not None:
                self.err_input.set_device_array(
                    self.err_output.devmem, self.device)
            else:
                self.err_output.map_read()
                self.err_input.map_invalidate()
                self.err_input.mem = numpy.array(self.err_output.mem)
            return
        if self.on_device():
            import jax
            if self._jit_fn_ is None:
                self._jit_fn_ = jax.jit(lambda e, m: e * m)
            self.err_input.set_device_array(
                self._jit_fn_(self.err_output.devmem, self.mask.devmem),
                self.device)
        else:
            self.err_output.map_read()
            self.mask.map_read()
            self.err_input.map_invalidate()
            self.err_input.mem = self.err_output.mem * self.mask.mem
