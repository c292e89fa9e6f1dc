"""Evaluator units: loss gradients + per-minibatch metrics.

Znicz-equivalent evaluator_softmax / evaluator_mse
(manualrst_veles_algorithms.rst: softmax & MSE losses).

Design notes:
- ``err_output`` is the MEAN-loss gradient (divided by the current
  minibatch size), so learning rates are batch-size invariant — a
  deliberate departure from the reference's summed gradient, documented
  here for anyone porting configs.
- short (padded) minibatches are masked by ``labels >= 0`` /
  an explicit sample mask, matching the loader's padding convention;
- metrics (n_err, confusion, loss sums) are computed in the same jitted
  call and stay LAZY on device (jax scalars): the decision unit
  accumulates them asynchronously and forces a host sync only at
  class/epoch boundaries.  A per-minibatch ``int(n_err)`` blocks the
  host on the device and drains the dispatch queue, so nothing here
  synchronizes.
"""

import numpy

from veles_tpu.backends import NumpyDevice
from veles_tpu.memory import Array
from veles_tpu.units import Unit

__all__ = ["EvaluatorBase", "EvaluatorSoftmax", "EvaluatorMSE",
           "lazy_add", "lazy_consec"]

_JIT_ADD = None
_JIT_CONSEC = None


def lazy_add(a, b):
    """a + b for metric accumulation without eager-op overhead.

    Eager jax ops dispatch one program each, so accumulating lazy
    metrics with plain ``+`` silently re-serializes training on the
    host.
    Jitted when either side is a jax array; plain Python + otherwise
    (numpy-backend workflows never touch jax here)."""
    if not (hasattr(a, "aval") or hasattr(b, "aval")):
        return a + b
    global _JIT_ADD
    if _JIT_ADD is None:
        import jax

        def lazy_add(p, q):  # a host trace shows PjitFunction(lazy_add)
            return p + q
        _JIT_ADD = jax.jit(lazy_add)
    return _JIT_ADD(a, b)


def lazy_consec(prev, skipped):
    """Consecutive-skip counter update for the numerics watchdog
    (docs/health.md) without a host sync: ``skipped`` is a lazy 0/1
    scalar, so ``(prev + s) * s`` increments on a skipped step and
    resets to 0 on any applied one.  Jitted like :func:`lazy_add`;
    plain arithmetic for host-side (numpy-backend) callers."""
    if not (hasattr(prev, "aval") or hasattr(skipped, "aval")):
        return (prev + skipped) * skipped
    global _JIT_CONSEC
    if _JIT_CONSEC is None:
        import jax

        def lazy_consec(p, s):  # PjitFunction(lazy_consec) in a trace
            return (p + s) * s
        _JIT_CONSEC = jax.jit(lazy_consec)
    return _JIT_CONSEC(prev, skipped)


class EvaluatorBase(Unit):
    """Common plumbing: demands output + batch_size, owns err_output."""

    def __init__(self, workflow, **kwargs):
        super(EvaluatorBase, self).__init__(workflow, **kwargs)
        self.output = None          # linked from the last forward unit
        self.batch_size = None      # linked from loader.minibatch_size
        self.err_output = Array()
        self.device = None
        self._jit_fn_ = None
        self.demand("output", "batch_size")

    def init_unpickled(self):
        super(EvaluatorBase, self).init_unpickled()
        self._jit_fn_ = None

    def on_device(self):
        return (self.device is not None and self.device.exists and
                not isinstance(self.device, NumpyDevice))

    def initialize(self, device=None, **kwargs):
        self.device = device
        return super(EvaluatorBase, self).initialize(**kwargs)


class EvaluatorSoftmax(EvaluatorBase):
    """Cross-entropy on softmax probabilities.

    err_output = (probs - onehot(label)) / batch_size, zero for padded
    samples; metrics: n_err (misclassifications), confusion_matrix row =
    truth, column = prediction.
    """

    def __init__(self, workflow, **kwargs):
        super(EvaluatorSoftmax, self).__init__(workflow, **kwargs)
        self.labels = None          # linked from loader.minibatch_labels
        self.n_err = 0              # per-minibatch, read by decision
        self.confusion_matrix = Array()
        self.compute_confusion = kwargs.get("compute_confusion", True)
        self.demand("labels")

    @staticmethod
    def compute(probs, labels, batch_size, n_classes):
        import jax.numpy as jnp
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        onehot = jnp.zeros_like(probs).at[
            jnp.arange(probs.shape[0]), safe].set(1.0)
        err = (probs - onehot) * valid[:, None] / batch_size
        pred = jnp.argmax(probs, axis=-1)
        n_err = jnp.sum((pred != safe) & valid)
        confusion = jnp.zeros((n_classes, n_classes), jnp.int32).at[
            safe, pred].add(valid.astype(jnp.int32))
        return err.astype(probs.dtype), n_err, confusion

    def init_unpickled(self):
        super(EvaluatorSoftmax, self).init_unpickled()
        self._confusion_acc_ = None

    def run(self):
        n_classes = self.output.shape[-1]
        if self.on_device():
            import functools
            import jax
            import jax.numpy as jnp
            if self._jit_fn_ is None:
                self._jit_fn_ = jax.jit(functools.partial(
                    EvaluatorSoftmax.compute, n_classes=n_classes))
            err, n_err, confusion = self._jit_fn_(
                self.output.device_array(self.device),
                self.labels.device_array(self.device),
                numpy.float32(self.batch_size))
            self.err_output.set_device_array(err, self.device)
            # lazy: the decision unit syncs at class end, not per step
            self.n_err = n_err
            if self.compute_confusion:
                acc = self._confusion_acc_
                if acc is None and self.confusion_matrix:
                    # snapshot-restored history seeds the accumulator
                    acc = jnp.asarray(self.confusion_matrix.mem)
                self._confusion_acc_ = (confusion if acc is None
                                        else lazy_add(acc, confusion))
                self.confusion_matrix.set_device_array(
                    self._confusion_acc_, self.device)
            return
        from veles_tpu.backends import host_compute_context
        self.output.map_read()
        self.labels.map_read()
        with host_compute_context(self.device):
            err, n_err, confusion = EvaluatorSoftmax.compute(
                self.output.mem, self.labels.mem,
                numpy.float32(self.batch_size), n_classes)
        self.err_output.map_invalidate()
        self.err_output.mem = numpy.asarray(err)
        self.n_err = int(n_err)
        conf = numpy.asarray(confusion)
        if self.compute_confusion:
            if not self.confusion_matrix:
                self.confusion_matrix.mem = numpy.zeros_like(conf)
            self.confusion_matrix.map_write()
            self.confusion_matrix.mem += conf

    def __getstate__(self):
        # snapshots must carry plain scalars, not device handles
        state = super(EvaluatorSoftmax, self).__getstate__()
        if "n_err" in state:
            state["n_err"] = int(self.n_err)
        return state


class EvaluatorMSE(EvaluatorBase):
    """Mean-squared-error: err_output = 2*(y - target)/batch (masked),
    metric: summed squared error for RMSE aggregation."""

    def __init__(self, workflow, **kwargs):
        super(EvaluatorMSE, self).__init__(workflow, **kwargs)
        self.target = None          # linked from loader.minibatch_targets
        self.mse_sum = 0.0          # per-minibatch sum of sample MSEs
        self.n_samples = 0
        self.demand("target")

    @staticmethod
    def compute(y, target, batch_size, max_batch):
        import jax.numpy as jnp
        y2 = y.reshape(y.shape[0], -1)
        t2 = target.reshape(target.shape[0], -1)
        mask = (jnp.arange(y2.shape[0]) < batch_size).astype(y2.dtype)
        diff = (y2 - t2) * mask[:, None]
        err = (2.0 * diff / batch_size).astype(y.dtype).reshape(y.shape)
        mse_sum = jnp.sum(jnp.mean(diff * diff, axis=1))
        return err, mse_sum

    def run(self):
        if self.on_device():
            import jax
            if self._jit_fn_ is None:
                self._jit_fn_ = jax.jit(EvaluatorMSE.compute)
            err, mse_sum = self._jit_fn_(
                self.output.device_array(self.device),
                self.target.device_array(self.device),
                numpy.float32(self.batch_size),
                self.output.shape[0])
            self.err_output.set_device_array(err, self.device)
            # lazy (see module docstring): synced at class end
            self.mse_sum = mse_sum
            self.n_samples = int(self.batch_size)
            return
        from veles_tpu.backends import host_compute_context
        self.output.map_read()
        self.target.map_read()
        with host_compute_context(self.device):
            err, mse_sum = EvaluatorMSE.compute(
                self.output.mem, self.target.mem,
                numpy.float32(self.batch_size), self.output.shape[0])
        self.err_output.map_invalidate()
        self.err_output.mem = numpy.asarray(err)
        self.mse_sum = float(mse_sum)
        self.n_samples = int(self.batch_size)

    def __getstate__(self):
        state = super(EvaluatorMSE, self).__getstate__()
        if "mse_sum" in state:
            state["mse_sum"] = float(self.mse_sum)
        return state
