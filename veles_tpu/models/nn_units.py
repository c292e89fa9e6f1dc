"""Base classes for forward and gradient-descent units.

Counterpart of Znicz's nn_units.Forward / nn_units.GradientDescentBase
(empty submodule; capabilities per docs/source/manualrst_veles_algorithms
.rst:150-165 — weight-init schemes, per-layer hyperparameters, L1/L2
regularization, solvers).

Design: parameters (weights/bias + solver state) are veles_tpu Arrays
shared BY OBJECT between the forward unit and its GD unit, so a device-side
update by one is immediately visible to the other with no host traffic.
Forward math lives in pure static methods over (params, x) so the same
code serves three paths: per-unit jit (here), the fused whole-step
compiler, and the numpy fallback backend.
"""

import numpy

from veles_tpu import prng
from veles_tpu.backends import NumpyDevice
from veles_tpu.config import precision_dtype, root
from veles_tpu.memory import Array
from veles_tpu.units import Unit

__all__ = ["ForwardBase", "GradientDescentBase"]


def _is_jax_device(device):
    return device is not None and device.exists and \
        not isinstance(device, NumpyDevice)


class ForwardBase(Unit):
    """Forward propagation unit: input -> output with trainable params.

    kwargs (per-layer hyperparameters):
      weights_filling: "uniform" | "gaussian" | "constant"
      weights_stddev: spread; default 1/sqrt(fan_in) for uniform
      bias_filling / bias_stddev: likewise for bias
      include_bias: bool (default True)
      weights_transposed: kept for reference-parity introspection; this
        build always stores (fan_in, fan_out) which is the natural MXU
        layout (the reference stored (fan_out, fan_in)).
    """

    #: dtype of the unit's parameters (and so of its solver state); None
    #: follows ``root.common.engine.precision_type``.  A unit that keeps
    #: float32 state under lower-precision operands names it here and
    #: casts inside ``apply``
    STATE_DTYPE = None

    def __init__(self, workflow, **kwargs):
        super(ForwardBase, self).__init__(workflow, **kwargs)
        self.input = None  # linked from loader/previous unit (Array)
        # an activation, not state: a snapshot keeps its shape only
        # (AlexNet at batch 256 would carry 0.8 GB of them)
        self.output = Array(shallow_pickle=True)
        self.weights = Array()
        self.bias = Array()
        self.include_bias = kwargs.get("include_bias", True)
        self.weights_filling = kwargs.get("weights_filling", "uniform")
        self.weights_stddev = kwargs.get("weights_stddev", None)
        self.bias_filling = kwargs.get("bias_filling", "uniform")
        self.bias_stddev = kwargs.get("bias_stddev", None)
        self.prng = kwargs.get("prng", prng.get())
        self.device = None
        self._jit_fn_ = None
        self.demand("input")

    def init_unpickled(self):
        super(ForwardBase, self).init_unpickled()
        self._jit_fn_ = None

    # -- parameter creation -------------------------------------------------

    def fill_array(self, arr, filling, stddev, fan_in):
        """Weight-init schemes (manualrst_veles_algorithms.rst:150-165)."""
        if stddev is None:
            stddev = 1.0 / numpy.sqrt(fan_in) if fan_in else 0.01
        if filling == "uniform":
            self.prng.fill(arr, -stddev, stddev)
        elif filling == "gaussian":
            self.prng.fill_normal(arr, 0.0, stddev)
        elif filling == "constant":
            arr[:] = stddev
        else:
            raise ValueError("unknown filling %r" % filling)

    # -- device plumbing ----------------------------------------------------

    def on_device(self):
        return _is_jax_device(self.device)

    def initialize(self, device=None, **kwargs):
        self.device = device
        super(ForwardBase, self).initialize(**kwargs)
        self.create_params()
        dtype = numpy.dtype(self.STATE_DTYPE or precision_dtype())
        for arr in self.param_arrays():
            if arr:
                if arr.dtype != dtype:
                    # create_params fills float32; the configured
                    # precision (root.common.engine.precision_type)
                    # is what the model computes and snapshots in
                    arr.map_write()
                    arr.mem = arr.mem.astype(dtype)
                arr.initialize(self.device)
        return True

    def create_params(self):
        """Allocate weights/bias from the input shape; idempotent on
        snapshot restore."""
        raise NotImplementedError

    def param_arrays(self):
        return [self.weights, self.bias]

    # -- the pure functions -------------------------------------------------

    @staticmethod
    def apply(params, x, **static):
        """params dict, x device array -> output device array.  ``static``
        holds compile-time layer config (strides, padding, ...)."""
        raise NotImplementedError

    def static_config(self):
        """Compile-time kwargs baked into the jitted apply."""
        return {}

    def params_dict(self):
        return {"weights": self.weights.devmem,
                "bias": self.bias.devmem if self.include_bias else None}

    def params_numpy(self):
        self.weights.map_read()
        if self.include_bias:
            self.bias.map_read()
        return {"weights": self.weights.mem,
                "bias": self.bias.mem if self.include_bias else None}

    # -- execution ----------------------------------------------------------

    def run(self):
        if self.on_device():
            self._device_run()
        else:
            self._numpy_run()

    def _device_run(self):
        import functools
        import jax
        if self._jit_fn_ is None:
            self._jit_fn_ = jax.jit(functools.partial(
                type(self).apply, **self.static_config()))
        out = self._jit_fn_(self.params_dict(),
                            self.input.device_array(self.device))
        self.output.set_device_array(out, self.device)
        if root.common.get("sync_run", False):
            # honest per-unit timings (reference --sync-run,
            # accelerated_units.py:186-193)
            jax.block_until_ready(out)

    def _numpy_run(self):
        from veles_tpu.backends import host_compute_context
        params = self.params_numpy()
        self.input.map_read()
        with host_compute_context(self.device):
            out = numpy.asarray(type(self).apply(
                params, self.input.mem, **self.static_config()))
        self.output.map_invalidate()
        self.output.mem = out

    # -- master-slave contract (job-farming DP, SURVEY.md section 2.6) -----
    #
    # Master ships canonical params with each job; the slave trains on its
    # minibatch and returns the param DELTA; the master merges deltas
    # additively (Downpour-style async SGD).  On-pod DP does NOT use this
    # path — it rides ICI psum via veles_tpu.parallel.

    def generate_data_for_slave(self, slave=None):
        payload = {}
        for name, arr in (("weights", self.weights), ("bias", self.bias)):
            if arr:
                arr.map_read()
                payload[name] = numpy.array(arr.mem)
        return payload or None

    def apply_data_from_master(self, data):
        if not data:
            return
        self._job_start_params_ = {}
        for name, arr in (("weights", self.weights), ("bias", self.bias)):
            value = data.get(name)
            if value is not None and arr:
                arr.map_invalidate()
                arr.mem = numpy.array(value)
                self._job_start_params_[name] = numpy.array(value)

    def generate_data_for_master(self):
        start = getattr(self, "_job_start_params_", None)
        if not start:
            return None
        delta = {}
        for name, arr in (("weights", self.weights), ("bias", self.bias)):
            if name in start and arr:
                arr.map_read()
                delta[name] = arr.mem - start[name]
        return delta or None

    def apply_data_from_slave(self, data, slave=None):
        if not data:
            return
        for name, arr in (("weights", self.weights), ("bias", self.bias)):
            value = data.get(name)
            if value is not None and arr:
                arr.map_write()
                arr.mem += value


class GradientDescentBase(Unit):
    """Backward + parameter update for one forward unit.

    kwargs: learning_rate, learning_rate_bias, weights_decay (L2/L1 per
    l1_vs_l2 blend), gradient_moment (momentum; adamw's beta1), solver
    ("momentum" | "adagrad" | "adadelta" | "adamw"), adadelta_rho
    (adamw's beta2), solver_epsilon.

    Reference-parity semantics: err_output is dL/d(output) arriving from
    the NEXT unit (or the evaluator); run() produces err_input =
    dL/d(input) for the PREVIOUS unit and applies the update in the same
    fused jitted call.
    """

    def __init__(self, workflow, **kwargs):
        super(GradientDescentBase, self).__init__(workflow, **kwargs)
        self.input = None
        self.output = None
        self.err_output = None   # linked: next gd's err_input / evaluator
        self.err_input = Array(shallow_pickle=True)  # transient too
        self.weights = None      # linked BY OBJECT from the forward unit
        self.bias = None
        self.include_bias = kwargs.get("include_bias", True)
        self.learning_rate = kwargs.get("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.get(
            "learning_rate_bias", kwargs.get("learning_rate", 0.01))
        self.weights_decay = kwargs.get("weights_decay", 0.0)
        self.weights_decay_bias = kwargs.get("weights_decay_bias", 0.0)
        self.l1_vs_l2 = kwargs.get("l1_vs_l2", 0.0)
        self.gradient_moment = kwargs.get("gradient_moment", 0.0)
        self.gradient_moment_bias = kwargs.get(
            "gradient_moment_bias", kwargs.get("gradient_moment", 0.0))
        self.solver = kwargs.get("solver", "momentum")
        self.adadelta_rho = kwargs.get("adadelta_rho", 0.95)
        self.solver_epsilon = kwargs.get("solver_epsilon", 1e-6)
        self.need_err_input = kwargs.get("need_err_input", True)
        self.device = None
        self._jit_fn_ = None
        # solver state (velocity / grad accumulators), created lazily
        self.accum_weights = Array()
        self.accum_bias = Array()
        self.accum2_weights = Array()
        self.accum2_bias = Array()
        # numerics health (docs/health.md): updates whose gradients
        # were non-finite are SKIPPED; both counters stay lazy device
        # scalars, synced by the decision once per finished class
        self.skip_count = 0
        self.consecutive_skips = 0
        self.demand("input", "output", "err_output", "weights")

    def init_unpickled(self):
        super(GradientDescentBase, self).init_unpickled()
        self._jit_fn_ = None

    def on_device(self):
        return _is_jax_device(self.device)

    def initialize(self, device=None, **kwargs):
        self.device = device
        super(GradientDescentBase, self).initialize(**kwargs)
        self._init_solver_state()
        return True

    def _init_solver_state(self):
        pairs = [(self.accum_weights, self.weights),
                 (self.accum_bias,
                  self.bias if self.include_bias else None)]
        if self.solver in ("adadelta", "adamw"):
            pairs += [(self.accum2_weights, self.weights),
                      (self.accum2_bias,
                       self.bias if self.include_bias else None)]
        for accum, param in pairs:
            if param and not accum:
                accum.mem = numpy.zeros(param.shape, param.dtype)
            if accum:  # (re)attach, incl. after snapshot restore
                accum.initialize(self.device)

    # -- hyperparameters bundled for the pure function ----------------------

    def hyper_dict(self):
        return {
            "learning_rate": self.learning_rate,
            "learning_rate_bias": self.learning_rate_bias,
            "weights_decay": self.weights_decay,
            "weights_decay_bias": self.weights_decay_bias,
            "l1_vs_l2": self.l1_vs_l2,
            "gradient_moment": self.gradient_moment,
            "gradient_moment_bias": self.gradient_moment_bias,
            "adadelta_rho": self.adadelta_rho,
            "solver_epsilon": self.solver_epsilon,
        }

    @staticmethod
    def regularized(grad, param, decay, l1_vs_l2):
        """L1/L2-blended weight decay gradient term."""
        import jax.numpy as jnp
        return grad + decay * ((1.0 - l1_vs_l2) * param +
                               l1_vs_l2 * jnp.sign(param))

    @staticmethod
    def select_state(finite, new_state, old_state):
        """``where(finite, new, old)`` over one state dict's leaves —
        the single definition of the skip-step fallback, shared by the
        per-unit guard below and the fused step (compiler.py) so the
        two paths can never drift apart.  ``None`` leaves and leaves
        that ARE the old object (param-less passthroughs) are kept
        as-is."""
        import jax.numpy as jnp
        selected = {}
        for key, value in new_state.items():
            old = old_state.get(key)
            selected[key] = value if (value is None or old is None or
                                      value is old) else \
                jnp.where(finite, value, old)
        return selected

    @staticmethod
    def finite_guard(state, new_state, *grads):
        """Skip-step guard shared by every guarded backward: when any
        gradient in ``grads`` carries a non-finite value, every leaf of
        ``new_state`` falls back to its pre-step value in ``state`` —
        params AND solver accumulators stay bit-identical to never
        having run the step.  Adds the int32 ``"skipped"`` flag (0/1)
        to the returned dict; callers pop it for their lazy skip
        accounting (it never reaches ``_adopt_state``'s fixed key
        set)."""
        import jax.numpy as jnp
        finite = jnp.asarray(True)
        for grad in grads:
            if grad is not None:
                finite = finite & jnp.isfinite(grad).all()
        guarded = GradientDescentBase.select_state(finite, new_state,
                                                   state)
        guarded["skipped"] = (~finite).astype(jnp.int32)
        return guarded

    @staticmethod
    def solver_update(solver, param, grad, accum, accum2, lr, moment,
                      rho, eps, step=None, decay=0.0):
        """One solver step; returns (new_param, new_accum, new_accum2).

        momentum:  v = moment*v + lr*g;            p -= v
        adagrad:   a += g*g;                       p -= lr*g/sqrt(a+eps)
        adadelta:  a  = rho*a + (1-rho)*g*g
                   d  = g*sqrt(a2+eps)/sqrt(a+eps); p -= lr*d
                   a2 = rho*a2 + (1-rho)*d*d
        adamw:     m  = moment*m + (1-moment)*g;  v = rho*v + (1-rho)*g*g
                   p -= lr*(m/(1-moment^t) / (sqrt(v/(1-rho^t))+eps)
                            + decay*p)
        (manualrst_veles_algorithms.rst solver list: SGD+momentum /
        AdaGrad / AdaDelta; AdamW is Loshchilov & Hutter 2019.)  adamw
        keeps its two moments in ``accum``/``accum2``, takes ``step``
        (t: 1 for the first step, a traced scalar) and decays the
        parameter by ``decay`` itself: its callers do not fold the
        decay into ``grad``.
        """
        import jax.numpy as jnp
        if solver == "momentum":
            v = moment * accum + lr * grad
            return param - v, v, accum2
        if solver == "adagrad":
            a = accum + grad * grad
            return param - lr * grad / jnp.sqrt(a + eps), a, accum2
        if solver == "adadelta":
            a = rho * accum + (1.0 - rho) * grad * grad
            d = grad * jnp.sqrt(accum2 + eps) / jnp.sqrt(a + eps)
            a2 = rho * accum2 + (1.0 - rho) * d * d
            return param - lr * d, a, a2
        if solver == "adamw":
            if step is None:
                raise ValueError(
                    "the adamw solver needs the step count for its bias "
                    "correction, and this train step was built without "
                    "one (the shard_map builders do not pass it)")
            t = jnp.asarray(step, jnp.float32)
            m = moment * accum + (1.0 - moment) * grad
            v = rho * accum2 + (1.0 - rho) * grad * grad
            m_hat = m / (1.0 - moment ** t)
            v_hat = v / (1.0 - rho ** t)
            return (param - lr * (m_hat / (jnp.sqrt(v_hat) + eps)
                                  + decay * param), m, v)
        raise ValueError("unknown solver %r" % solver)

    # -- the pure backward --------------------------------------------------

    @staticmethod
    def backward(state, hyper, x, y, err_output, *, solver, include_bias,
                 need_err_input, **static):
        """state dict (weights/bias/accums) -> (err_input, new_state)."""
        raise NotImplementedError

    def backward_static(self):
        """Compile-time kwargs baked into the jitted backward."""
        return {}

    def state_dict(self):
        d = {"weights": self.weights.devmem,
             "accum_weights": self.accum_weights.devmem,
             "accum2_weights": (self.accum2_weights.devmem
                                if self.accum2_weights else None)}
        if self.include_bias and self.bias:
            d["bias"] = self.bias.devmem
            d["accum_bias"] = self.accum_bias.devmem
            d["accum2_bias"] = (self.accum2_bias.devmem
                                if self.accum2_bias else None)
        else:
            d["bias"] = d["accum_bias"] = d["accum2_bias"] = None
        return d

    def state_numpy(self):
        arrays = [self.weights, self.accum_weights, self.accum2_weights,
                  self.bias, self.accum_bias, self.accum2_bias]
        for arr in arrays:
            if arr:
                arr.map_read()
        return {
            "weights": self.weights.mem,
            "accum_weights": self.accum_weights.mem,
            "accum2_weights": (self.accum2_weights.mem
                               if self.accum2_weights else None),
            "bias": self.bias.mem if self.include_bias and self.bias
            else None,
            "accum_bias": (self.accum_bias.mem
                           if self.include_bias and self.accum_bias
                           else None),
            "accum2_bias": (self.accum2_bias.mem
                            if self.accum2_bias else None),
        }

    # -- master-slave contract (job-farming DP, SURVEY.md section 2.6) -----
    #
    # The forward unit ships canonical PARAMS per job; this unit ships
    # canonical SOLVER STATE (momentum velocity / adagrad / adadelta
    # accumulators) the same way and merges the slave's accumulator
    # deltas additively — so a momentum run farms out bit-faithfully
    # instead of every slave re-warming velocity from zero on each job.

    def _accum_pairs(self):
        return (("accum_weights", self.accum_weights),
                ("accum_bias", self.accum_bias),
                ("accum2_weights", self.accum2_weights),
                ("accum2_bias", self.accum2_bias))

    def generate_data_for_slave(self, slave=None):
        payload = {}
        for name, arr in self._accum_pairs():
            if arr:
                arr.map_read()
                payload[name] = numpy.array(arr.mem)
        return payload or None

    def apply_data_from_master(self, data):
        if not data:
            return
        self._job_start_accums_ = {}
        for name, arr in self._accum_pairs():
            value = data.get(name)
            if value is not None and arr:
                arr.map_invalidate()
                arr.mem = numpy.array(value)
                self._job_start_accums_[name] = numpy.array(value)

    def generate_data_for_master(self):
        start = getattr(self, "_job_start_accums_", None)
        if not start:
            return None
        delta = {}
        for name, arr in self._accum_pairs():
            if name in start and arr:
                arr.map_read()
                delta[name] = arr.mem - start[name]
        return delta or None

    def apply_data_from_slave(self, data, slave=None):
        if not data:
            return
        for name, arr in self._accum_pairs():
            value = data.get(name)
            if value is not None and arr:
                arr.map_write()
                arr.mem += value

    def __getstate__(self):
        # snapshots carry plain ints, not lazy device scalars
        state = super(GradientDescentBase, self).__getstate__()
        if "skip_count" in state:
            state["skip_count"] = int(self.skip_count)
        if "consecutive_skips" in state:
            state["consecutive_skips"] = int(self.consecutive_skips)
        return state

    def _adopt_state(self, new_state, device_side):
        pairs = (("weights", self.weights),
                 ("accum_weights", self.accum_weights),
                 ("accum2_weights", self.accum2_weights),
                 ("bias", self.bias),
                 ("accum_bias", self.accum_bias),
                 ("accum2_bias", self.accum2_bias))
        for key, arr in pairs:
            value = new_state.get(key)
            if value is None or arr is None or not arr:
                continue
            if device_side:
                arr.set_device_array(value, self.device)
            else:
                arr.map_invalidate()
                arr.mem = numpy.asarray(value)

    # -- execution ----------------------------------------------------------

    def run(self):
        from veles_tpu import chaos
        poison = None
        if chaos.plan is not None:
            # nan-injection (docs/health.md): poisoning err_output
            # makes this layer's gradients non-finite AND propagates a
            # non-finite err_input upstream, so the whole chain skips
            # the step — the same blast radius a real NaN has
            fault = chaos.plan.fire("step.grad")
            if fault is not None:
                poison = numpy.float32(
                    numpy.nan if fault.param is None else fault.param)
        if self.on_device():
            self._device_run(poison)
        else:
            self._numpy_run(poison)

    def _account_skip(self, skipped):
        """Lazy skip accounting; ``skipped`` is the guarded backward's
        0/1 flag (popped before _adopt_state sees the dict)."""
        from veles_tpu.models.evaluator import lazy_add, lazy_consec
        self.skip_count = lazy_add(self.skip_count, skipped)
        self.consecutive_skips = lazy_consec(self.consecutive_skips,
                                             skipped)

    def reset_health_counters(self):
        self.skip_count = 0
        self.consecutive_skips = 0

    def _device_run(self, poison=None):
        import functools
        import jax
        if self._jit_fn_ is None:
            self._jit_fn_ = jax.jit(functools.partial(
                type(self).backward, solver=self.solver,
                include_bias=self.include_bias and bool(self.bias),
                need_err_input=self.need_err_input,
                **self.backward_static()))
        err_output = self.err_output.devmem
        if poison is not None:
            err_output = err_output + poison
        err_input, new_state = self._jit_fn_(
            self.state_dict(), self.hyper_dict(),
            self.input.devmem, self.output.devmem, err_output)
        skipped = new_state.pop("skipped", None)
        if skipped is not None:
            self._account_skip(skipped)
        if self.need_err_input and err_input is not None:
            self.err_input.set_device_array(err_input, self.device)
        self._adopt_state(new_state, device_side=True)
        if root.common.get("sync_run", False):
            import jax
            jax.block_until_ready(new_state)

    def _numpy_run(self, poison=None):
        from veles_tpu.backends import host_compute_context
        for arr in (self.input, self.output, self.err_output):
            arr.map_read()
        err_output = self.err_output.mem
        if poison is not None:
            err_output = err_output + poison
        with host_compute_context(self.device):
            err_input, new_state = type(self).backward(
                self.state_numpy(), self.hyper_dict(),
                self.input.mem, self.output.mem, err_output,
                solver=self.solver,
                include_bias=self.include_bias and bool(self.bias),
                need_err_input=self.need_err_input,
                **self.backward_static())
        skipped = new_state.pop("skipped", None)
        if skipped is not None:
            self._account_skip(int(numpy.asarray(skipped)))
        if self.need_err_input and err_input is not None:
            self.err_input.map_invalidate()
            self.err_input.mem = numpy.asarray(err_input)
        self._adopt_state(new_state, device_side=False)
