"""Model-zoo tests: autoencoder (MSE), deconv/depool oracle checks,
RNN/LSTM vs autodiff, Kohonen convergence, RBM reconstruction,
AlexNet/VGG construction + one fused step on tiny shapes."""

import os
import sys

import numpy
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples"))

import jax
import jax.numpy as jnp

from veles_tpu.dummy import DummyWorkflow
from veles_tpu.loader import FullBatchLoaderMSE
from veles_tpu.models.nn_workflow import StandardWorkflow
from veles_tpu.prng import RandomGenerator


# ------------------------------------------------------------ autoencoder

class AutoencoderLoader(FullBatchLoaderMSE):
    """targets = inputs (reconstruction)."""

    def load_data(self):
        self.class_lengths[:] = [0, 32, 128]
        self._calc_class_end_offsets()
        self.create_originals((12,), labels=False)
        rng = numpy.random.RandomState(3)
        base = rng.rand(4, 12).astype(numpy.float32)
        for i in range(self.total_samples):
            self.original_data.mem[i] = (
                base[i % 4] + rng.randn(12) * 0.05)
        self.original_targets.mem = numpy.array(self.original_data.mem)


def test_autoencoder_trains(cpu_device):
    from veles_tpu.models.zoo import autoencoder_layers
    wf = DummyWorkflow()
    sw = StandardWorkflow(
        wf.workflow,
        layers=autoencoder_layers(bottleneck=4, hidden=16,
                                  out_features=12, lr=0.02),
        loader_factory=lambda w: AutoencoderLoader(
            w, minibatch_size=32, prng=RandomGenerator("ae", seed=2)),
        loss="mse",
        decision_config=dict(max_epochs=15),
    )
    sw.initialize(device=cpu_device)
    sw.run()
    rmse = sw.decision.epoch_metrics[1]
    assert rmse is not None and rmse < 0.6, "val RMSE %s" % rmse


# ---------------------------------------------------------- deconv/depool

def test_deconv_inverts_conv_shape():
    from veles_tpu.models.deconv import Deconv
    rng = numpy.random.RandomState(0)
    x = rng.randn(2, 4, 4, 3).astype(numpy.float32)
    W = rng.randn(3, 3, 5, 3).astype(numpy.float32)  # (ky,kx,out,in)
    y = numpy.asarray(Deconv.apply(
        {"weights": W, "bias": None}, x, padding=(0, 0, 0, 0),
        sliding=(1, 1)))
    assert y.shape == (2, 6, 6, 5)


def test_gd_deconv_matches_autodiff():
    from veles_tpu.models.deconv import Deconv, GDDeconv
    rng = numpy.random.RandomState(1)
    x = rng.randn(2, 4, 4, 2).astype(numpy.float32)
    W = (rng.randn(3, 3, 3, 2) * 0.3).astype(numpy.float32)
    y = numpy.asarray(Deconv.apply(
        {"weights": W, "bias": None}, x, padding=(0, 0, 0, 0),
        sliding=(1, 1)))
    err = rng.randn(*y.shape).astype(numpy.float32)

    def loss(W_, x_):
        return jnp.sum(Deconv.apply(
            {"weights": W_, "bias": None}, x_, padding=(0, 0, 0, 0),
            sliding=(1, 1)) * err)

    gw, gx = jax.grad(loss, argnums=(0, 1))(W, x)
    state = {"weights": W, "bias": None,
             "accum_weights": numpy.zeros_like(W), "accum_bias": None,
             "accum2_weights": None, "accum2_bias": None}
    hyper = {"learning_rate": 0.1, "learning_rate_bias": 0.1,
             "weights_decay": 0.0, "weights_decay_bias": 0.0,
             "l1_vs_l2": 0.0, "gradient_moment": 0.0,
             "gradient_moment_bias": 0.0, "adadelta_rho": 0.95,
             "solver_epsilon": 1e-6}
    err_input, new_state = GDDeconv.backward(
        state, hyper, x, y, err, solver="momentum", include_bias=False,
        need_err_input=True, padding=(0, 0, 0, 0), sliding=(1, 1))
    numpy.testing.assert_allclose(
        numpy.asarray(new_state["weights"]),
        W - 0.1 * numpy.asarray(gw), rtol=1e-3, atol=1e-4)
    numpy.testing.assert_allclose(numpy.asarray(err_input),
                                  numpy.asarray(gx), rtol=1e-3,
                                  atol=1e-4)


def test_depooling_upsamples():
    from veles_tpu.models.deconv import Depooling
    x = numpy.arange(4, dtype=numpy.float32).reshape(1, 2, 2, 1)
    y = numpy.asarray(Depooling.apply({}, x, window=(2, 2)))
    assert y.shape == (1, 4, 4, 1)
    assert (y[0, :2, :2, 0] == 0).all()
    assert (y[0, 2:, 2:, 0] == 3).all()


# ------------------------------------------------------------- recurrent

def test_rnn_lstm_forward_shapes():
    from veles_tpu.models.rnn import LSTM, RNN
    rng = numpy.random.RandomState(2)
    x = rng.randn(3, 7, 5).astype(numpy.float32)
    w_rnn = rng.randn(5 + 4, 4).astype(numpy.float32) * 0.2
    y = numpy.asarray(RNN.apply(
        {"weights": w_rnn, "bias": numpy.zeros(4, numpy.float32)}, x))
    assert y.shape == (3, 7, 4)
    assert numpy.abs(y).max() <= 1.0
    w_lstm = rng.randn(5 + 4, 16).astype(numpy.float32) * 0.2
    y2 = numpy.asarray(LSTM.apply(
        {"weights": w_lstm, "bias": numpy.zeros(16, numpy.float32)}, x,
        return_sequences=False))
    assert y2.shape == (3, 4)


def test_gd_lstm_matches_autodiff():
    from veles_tpu.models.rnn import GDLSTM, LSTM
    rng = numpy.random.RandomState(4)
    x = rng.randn(2, 5, 3).astype(numpy.float32)
    W = (rng.randn(3 + 4, 16) * 0.3).astype(numpy.float32)
    b = numpy.zeros(16, numpy.float32)
    y = numpy.asarray(LSTM.apply({"weights": W, "bias": b}, x))
    err = rng.randn(*y.shape).astype(numpy.float32)

    def loss(W_, b_):
        return jnp.sum(LSTM.apply({"weights": W_, "bias": b_}, x) * err)

    gw, gb = jax.grad(loss, argnums=(0, 1))(W, b)
    state = {"weights": W, "bias": b,
             "accum_weights": numpy.zeros_like(W),
             "accum_bias": numpy.zeros_like(b),
             "accum2_weights": None, "accum2_bias": None}
    hyper = {"learning_rate": 1.0, "learning_rate_bias": 1.0,
             "weights_decay": 0.0, "weights_decay_bias": 0.0,
             "l1_vs_l2": 0.0, "gradient_moment": 0.0,
             "gradient_moment_bias": 0.0, "adadelta_rho": 0.95,
             "solver_epsilon": 1e-6}
    _, new_state = GDLSTM.backward(
        state, hyper, x, y, err, solver="momentum", include_bias=True,
        need_err_input=False)
    numpy.testing.assert_allclose(
        W - numpy.asarray(new_state["weights"]), numpy.asarray(gw),
        rtol=1e-3, atol=1e-4)


def test_rnn_workflow_trains_sequence_classification(cpu_device):
    """Classify which of 2 frequencies dominates a sequence."""
    from veles_tpu.loader import FullBatchLoader

    class SeqLoader(FullBatchLoader):
        def load_data(self):
            self.class_lengths[:] = [0, 32, 96]
            self._calc_class_end_offsets()
            self.create_originals((16, 2))
            rng = numpy.random.RandomState(7)
            t = numpy.arange(16)
            for i in range(self.total_samples):
                label = i % 2
                freq = 0.2 if label == 0 else 0.8
                sig = numpy.sin(freq * t)[:, None].repeat(2, 1)
                self.original_data.mem[i] = (
                    sig + rng.randn(16, 2) * 0.1)
                self.original_labels[i] = label

    wf = DummyWorkflow()
    sw = StandardWorkflow(
        wf.workflow,
        layers=[
            {"type": "lstm", "hidden_size": 8,
             "return_sequences": False, "learning_rate": 0.05,
             "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 2,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: SeqLoader(
            w, minibatch_size=32, prng=RandomGenerator("seq", seed=5)),
        decision_config=dict(max_epochs=10),
    )
    sw.initialize(device=cpu_device)
    sw.run()
    assert sw.decision.epoch_metrics[1] < 15.0


# ---------------------------------------------------------------- kohonen

def test_kohonen_organizes(cpu_device):
    from veles_tpu.memory import Array
    from veles_tpu.models.kohonen import KohonenForward, KohonenTrainer
    wf = DummyWorkflow()
    rng = numpy.random.RandomState(6)
    centers = numpy.array([[0, 0], [1, 1], [0, 1], [1, 0]],
                          numpy.float32)
    data = numpy.concatenate([
        centers[i] + rng.randn(50, 2).astype(numpy.float32) * 0.05
        for i in range(4)])
    trainer = KohonenTrainer(wf, shape=(4, 4),
                             prng=RandomGenerator("koh", seed=4))
    trainer.input = Array(data)
    trainer.initialize(device=cpu_device)
    for _ in range(40):
        trainer.run()
    fwd = KohonenForward(wf, shape=(4, 4))
    fwd.input = Array(data)
    fwd.weights = trainer.weights
    fwd.initialize(device=cpu_device)
    fwd.run()
    winners = fwd.output.mem
    # each cluster maps to a (mostly) distinct dominant neuron
    dominant = set()
    for i in range(4):
        counts = numpy.bincount(winners[i * 50:(i + 1) * 50],
                                minlength=16)
        dominant.add(int(counts.argmax()))
    assert len(dominant) >= 3


# -------------------------------------------------------------------- rbm

def test_rbm_reduces_reconstruction_error(cpu_device):
    from veles_tpu.memory import Array
    from veles_tpu.models.rbm import RBM
    wf = DummyWorkflow()
    rng = numpy.random.RandomState(8)
    patterns = (rng.rand(4, 20) > 0.5).astype(numpy.float32)
    data = patterns[rng.randint(0, 4, 128)]
    rbm = RBM(wf, hidden_size=12, learning_rate=0.2,
              prng=RandomGenerator("rbm", seed=6))
    rbm.input = Array(data)
    rbm.initialize(device=cpu_device)
    errors = []
    for _ in range(200):
        rbm.run()
        errors.append(rbm.reconstruction_error)
    assert errors[-1] < errors[0] * 0.6, (errors[0], errors[-1])


# ------------------------------------------------------------ alexnet/vgg

def test_alexnet_vgg_fused_step_tiny():
    """Full AlexNet/VGG specs compile + execute one fused train step on
    scaled-down input (the real shapes run in the benchmark's
    ``alexnet_train_*`` cells on the chip)."""
    from veles_tpu.compiler import build_train_step
    from veles_tpu.models.zoo import (
        alexnet_layers, build_plans_and_state, vgg_layers)

    rng = numpy.random.RandomState(0)
    for name, specs, input_shape in (
            ("alexnet", alexnet_layers(classes=10), (67, 67, 3)),
            ("vgg11", vgg_layers(classes=10, config="A"), (32, 32, 3))):
        plans, state, out_shape = build_plans_and_state(
            specs, input_shape, seed=1)
        assert out_shape == (10,), name
        step = build_train_step(plans, donate=False)
        x = rng.rand(2, *input_shape).astype(numpy.float32)
        labels = rng.randint(0, 10, 2).astype(numpy.int32)
        new_state, metrics = step(
            state, x, labels, numpy.float32(2),
            jax.random.PRNGKey(0))
        assert numpy.isfinite(float(metrics["loss"])), name


def test_alexnet_workflow_constructs(cpu_device):
    """AlexNet spec builds through StandardWorkflow (tiny input)."""
    from veles_tpu.loader import FullBatchLoader
    from veles_tpu.models.zoo import alexnet_layers

    class TinyImages(FullBatchLoader):
        def load_data(self):
            self.class_lengths[:] = [0, 4, 8]
            self._calc_class_end_offsets()
            self.create_originals((67, 67, 3))
            rng = numpy.random.RandomState(1)
            for i in range(self.total_samples):
                self.original_data.mem[i] = rng.rand(67, 67, 3)
                self.original_labels[i] = i % 2

    wf = DummyWorkflow()
    sw = StandardWorkflow(
        wf.workflow,
        layers=alexnet_layers(classes=2, lr=0.01),
        loader_factory=lambda w: TinyImages(
            w, minibatch_size=4, prng=RandomGenerator("ax", seed=3)),
        decision_config=dict(max_epochs=1),
    )
    sw.initialize(device=cpu_device)
    assert len(sw.forwards) == 13
    assert sw.forwards[0].weights.shape == (11, 11, 3, 96)


def test_kohonen_example_workflow(cpu_device):
    """The SOM example drives the real graph engine loop
    (repeater -> trainer -> counter gate) on real digits and reaches
    useful unsupervised structure (winner purity well above the 10%
    chance level)."""
    import importlib
    module = importlib.import_module("kohonen")
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    saved_epochs = root.kohonen.epochs
    root.kohonen.epochs = 40  # keep the test fast; purity ~70%
    try:
        launcher = Launcher()
        wf = module.KohonenWorkflow(launcher)
        launcher.initialize(device=cpu_device)
        launcher.run()
        assert wf.purity is not None and wf.purity > 0.5, wf.purity
    finally:
        root.kohonen.epochs = saved_epochs


def test_rbm_example_workflow(cpu_device):
    """The RBM example pretrains on real digits through the graph
    engine loop and reconstructs held-out digits well below the
    untrained error."""
    import importlib
    module = importlib.import_module("rbm")
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    saved = root.rbm.epochs
    root.rbm.epochs = 25
    try:
        launcher = Launcher()
        wf = module.RBMWorkflow(launcher)
        untrained = None
        launcher.initialize(device=cpu_device)
        untrained = wf.rbm.reconstruct_error(wf.valid_x)
        launcher.run()
        assert wf.holdout_error is not None
        assert wf.holdout_error < untrained * 0.7, (
            wf.holdout_error, untrained)
    finally:
        root.rbm.epochs = saved
