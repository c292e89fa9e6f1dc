"""Data layer tests (reference test model: veles/tests/test_loader.py,
SURVEY.md section 4): normalizers, minibatch contract, fullbatch device
gather parity across backends, distributed index-window protocol."""

import numpy
import pytest

from veles_tpu.dummy import DummyWorkflow
from veles_tpu.loader import (
    FullBatchLoader, FullBatchLoaderMSE, TEST, VALID, TRAIN)
from veles_tpu.normalization import NormalizerRegistry
from veles_tpu.ops import gather as gather_module


# ---------------------------------------------------------------- normalizers

def test_normalizer_registry_knows_all_mappings():
    for name in ("none", "linear", "range_linear", "mean_disp", "exp",
                 "pointwise", "external_mean", "internal_mean"):
        assert name in NormalizerRegistry.normalizers


def test_mean_disp_normalizer_roundtrip():
    n = NormalizerRegistry.get("mean_disp")
    data = numpy.random.RandomState(7).rand(100, 12).astype(numpy.float32)
    n.analyze(data)
    normalized = n.normalize(data.copy())
    assert abs(normalized.mean()) < 0.1
    restored = n.denormalize(normalized.copy())
    assert numpy.allclose(restored, data, atol=1e-5)


def test_range_linear_normalizer_interval():
    n = NormalizerRegistry.get("range_linear", interval=(0, 1))
    data = numpy.random.RandomState(3).rand(50, 4) * 9 - 3
    n.analyze(data)
    out = n.normalize(data.copy())
    assert out.min() >= -1e-9 and out.max() <= 1 + 1e-9
    back = n.denormalize(out.copy())
    assert numpy.allclose(back, data, atol=1e-9)


def test_pointwise_normalizer():
    n = NormalizerRegistry.get("pointwise")
    data = numpy.random.RandomState(5).rand(40, 6) * 10
    n.analyze(data)
    out = n.normalize(data.copy())
    assert out.min() >= -1 - 1e-9 and out.max() <= 1 + 1e-9
    back = n.denormalize(out.copy())
    assert numpy.allclose(back, data, atol=1e-9)


def test_external_mean_normalizer():
    mean = numpy.full(8, 2.0, numpy.float32)
    n = NormalizerRegistry.get("external_mean", mean_source=mean)
    n.analyze(None)
    data = numpy.full((3, 8), 5.0, numpy.float32)
    out = n.normalize(data.copy())
    assert numpy.allclose(out, 3.0)


def test_internal_mean_normalizer():
    n = NormalizerRegistry.get("internal_mean")
    data = numpy.random.RandomState(1).rand(30, 5)
    n.analyze(data)
    out = n.normalize(data.copy())
    assert numpy.allclose(out.mean(axis=0), 0, atol=1e-9)


# ---------------------------------------------------------------- the loader

class SyntheticLoader(FullBatchLoader):
    """10-class blobs: deterministic, learnable; 3-class split."""

    def __init__(self, workflow, n_test=32, n_valid=32, n_train=128,
                 features=16, classes=4, **kwargs):
        self._counts = (n_test, n_valid, n_train)
        self._features = features
        self._classes = classes
        super(SyntheticLoader, self).__init__(workflow, **kwargs)

    def load_data(self):
        self.class_lengths[:] = self._counts
        self._calc_class_end_offsets()
        self.create_originals((self._features,))
        rng = numpy.random.RandomState(42)
        centers = rng.rand(self._classes, self._features) * 4
        for i in range(self.total_samples):
            label = i % self._classes
            self.original_data.mem[i] = (
                centers[label] + rng.randn(self._features) * 0.1)
            self.original_labels[i] = "class%d" % label


def make_loader(device=None, **kwargs):
    from veles_tpu.prng import RandomGenerator
    wf = DummyWorkflow()
    kwargs.setdefault("prng", RandomGenerator("test_loader", seed=1234))
    loader = SyntheticLoader(wf, minibatch_size=32, **kwargs)
    loader.initialize(device=device)
    return loader


def test_loader_initialize_host():
    loader = make_loader(device=None)
    assert loader.total_samples == 192
    assert loader.class_end_offsets == [32, 64, 192]
    assert loader.has_labels
    assert loader.unique_labels_count == 4
    assert loader.minibatch_data.shape == (32, 16)


def test_loader_epoch_iteration_host():
    loader = make_loader(device=None)
    classes_seen = []
    epoch_ended_at = []
    for i in range(6):  # 32/32 + 32/32 + 128/32=4 -> 6 minibatches/epoch
        loader.run()
        classes_seen.append(loader.minibatch_class)
        if bool(loader.epoch_ended):
            epoch_ended_at.append(i)
        assert loader.minibatch_size == 32
    assert classes_seen == [TEST, VALID, TRAIN, TRAIN, TRAIN, TRAIN]
    # reference semantics (loader/base.py:861-869): epoch_ended fires when
    # the VALIDATION class completes (eval done), train_ended after TRAIN
    assert epoch_ended_at == [1]
    assert bool(loader.train_ended)
    assert loader.epoch_number == 1


def test_loader_minibatch_content_matches_indices_host():
    loader = make_loader(device=None)
    loader.run()
    idx = loader.minibatch_indices.mem[:loader.minibatch_size]
    loader.original_data.map_read()
    expected = loader.original_data.mem[idx]
    numpy.testing.assert_allclose(
        loader.minibatch_data.mem[:loader.minibatch_size], expected,
        rtol=1e-6)


def test_loader_device_gather_parity(cpu_device):
    host = make_loader(device=None)
    dev = make_loader(device=cpu_device)
    for _ in range(6):
        host.run()
        dev.run()
        dev.minibatch_data.map_read()
        numpy.testing.assert_allclose(
            dev.minibatch_data.mem[:dev.minibatch_size],
            host.minibatch_data.mem[:host.minibatch_size], rtol=1e-5)
        dev.minibatch_labels.map_read()
        numpy.testing.assert_array_equal(
            dev.minibatch_labels.mem[:dev.minibatch_size],
            host.minibatch_labels.mem[:host.minibatch_size])


class RowsLoader(FullBatchLoader):
    """Rows handed in; ``how`` says which way they reach
    ``original_data``: through ``create_originals`` (a window on the
    store's own buffer) or assigned as a plain ndarray (one host copy)."""

    def __init__(self, workflow, rows=None, how="create", **kwargs):
        self._rows, self._how = rows, how
        super(RowsLoader, self).__init__(workflow, **kwargs)

    def load_data(self):
        self.class_lengths[:] = [0, 20, len(self._rows) - 20]
        self._calc_class_end_offsets()
        if self._how == "create":
            self.create_originals(self._rows.shape[1:])
            self.original_data.mem[:] = self._rows
        else:
            self.original_data = self._rows.copy()
            self.original_labels = [None] * len(self._rows)
        self.original_labels[:] = [i % 5 for i in range(len(self._rows))]


def rows_loader(device, rows, how, dtype):
    from veles_tpu.prng import RandomGenerator
    loader = RowsLoader(
        DummyWorkflow(), rows=rows, how=how, minibatch_size=16,
        dtype=dtype, prng=RandomGenerator("rows_loader", seed=77))
    loader.initialize(device=device)
    return loader


@pytest.mark.parametrize("how,shape,stored,dtype", [
    ("create", (67, 784), "float32", "float32"),
    ("ndarray", (67, 784), "float32", "float32"),
    ("create", (67, 10), "float32", "float32"),
    ("ndarray", (67, 10), "float32", "float32"),
    ("create", (67, 9, 7, 3), "bfloat16", "bfloat16"),
    ("ndarray", (67, 9, 7, 3), "bfloat16", "bfloat16"),
    # create_originals allocates in the loader's own dtype
    ("ndarray", (67, 6, 5), "uint8", "float32"),
])
def test_device_minibatches_are_the_rows_bit_for_bit(
        cpu_device, how, shape, stored, dtype):
    """The device path serves ``rows[idx]`` in the loader's dtype, bit
    for bit what the host path (and so the parent's gather) serves for
    the same seed: widths off 128, a short last minibatch with its tail
    zeroed (20 and 47 rows in batches of 16), a narrower stored type."""
    import jax.numpy as jnp
    rng = numpy.random.RandomState(3)
    rows = (rng.rand(*shape) * 200).astype(jnp.dtype(stored))
    dev = rows_loader(cpu_device, rows, how, jnp.dtype(dtype))
    host = rows_loader(None, rows, how, jnp.dtype(dtype))
    assert dev._use_device_path() and not host._use_device_path()
    assert dev.original_data.shape == shape
    assert dev.original_data.mem.tobytes() == rows.tobytes()
    # the device holds the table once, as the store the host buffer is
    store = dev._stores_["data"]
    assert gather_module.host_store_of(dev.original_data.mem).shape \
        == store.shape
    assert dev.original_data.device is None
    short = 0
    for _ in range(7):  # 2 validation + 3 train minibatches, and again
        dev.run()
        host.run()
        count = dev.minibatch_size
        assert count == host.minibatch_size
        short += count < 16
        idx = dev.minibatch_indices.mem[:count]
        numpy.testing.assert_array_equal(
            idx, host.minibatch_indices.mem[:count])
        dev.minibatch_data.map_read()
        got = dev.minibatch_data.mem
        assert got.dtype == jnp.dtype(dtype)
        want = rows[idx].astype(jnp.dtype(dtype))
        assert got[:count].tobytes() == want.tobytes()
        assert got[:count].tobytes() == \
            host.minibatch_data.mem[:count].tobytes()
        assert not numpy.asarray(got[count:], numpy.float32).any()
        dev.minibatch_labels.map_read()
        numpy.testing.assert_array_equal(
            dev.minibatch_labels.mem[:count], idx % 5)
        assert (dev.minibatch_labels.mem[count:] == -1).all()
    assert short >= 2


def test_store_is_derived_state_rebuilt_by_initialize(cpu_device):
    """Not pickled; ``original_data`` pickles as the plain rows; a
    restored loader's ``initialize`` builds the store again."""
    import pickle
    from veles_tpu.observe.metrics import registry
    rows = numpy.random.RandomState(4).rand(67, 10).astype(numpy.float32)
    loader = rows_loader(cpu_device, rows, "create", numpy.float32)
    assert registry.peek("loader.store_s").count >= 2  # data, labels
    assert registry.peek("loader.store_bytes").value == sum(
        store.nbytes for store in loader._stores_.values())
    assert loader._stores_["data"].shape == (67, 1, 128)
    state = loader.__getstate__()
    assert not [key for key in state if "store" in key]
    kept = pickle.loads(pickle.dumps(loader.original_data))
    assert kept.mem.flags.c_contiguous and kept.shape == (67, 10)
    assert kept.mem.tobytes() == rows.tobytes()
    # a restored loader: plain rows, no store, until it initializes
    loader.original_data = kept
    loader._stores_ = {}
    loader._how = "kept"
    loader.load_data = lambda: None
    loader.initialize(device=cpu_device)
    assert loader._stores_["data"].shape == (67, 1, 128)
    assert gather_module.host_store_of(loader.original_data.mem) is not None
    loader.run()
    loader.minibatch_data.map_read()
    idx = loader.minibatch_indices.mem[:loader.minibatch_size]
    assert loader.minibatch_data.mem[:len(idx)].tobytes() == \
        rows[idx].tobytes()


def test_loader_train_shuffled_between_epochs():
    loader = make_loader(device=None)
    first = None
    for _ in range(6):
        loader.run()
    first = loader.shuffled_indices.mem[64:].copy()
    for _ in range(6):
        loader.run()
    second = loader.shuffled_indices.mem[64:]
    assert not numpy.array_equal(first, second)
    # test/valid windows never shuffled
    numpy.testing.assert_array_equal(
        loader.shuffled_indices.mem[:64], numpy.arange(64))


def test_loader_normalization_applied_to_originals():
    loader = make_loader(device=None, normalization_type="mean_disp")
    data = loader.original_data.mem
    train = data[loader.class_end_offsets[VALID]:]
    assert abs(train.mean()) < 0.2


# ------------------------------------------------- distributed index protocol

class _FakeSlave(object):
    def __init__(self, sid):
        self.id = sid


def test_master_slave_index_window_protocol():
    master = make_loader(device=None)
    master.workflow.workflow.workflow_mode = "master"
    slave = make_loader(device=None)
    slave.workflow.workflow.workflow_mode = "slave"

    s = _FakeSlave("s1")
    job = master.generate_data_for_slave(s)
    assert job["minibatch_size"] == 32
    assert master.pending_minibatches_count == 1

    slave.apply_data_from_master(job)
    slave.serve_next_minibatch(None)
    numpy.testing.assert_array_equal(
        slave.minibatch_indices.mem[:32], job["indices"])
    # slave filled its minibatch from its local copy of the dataset
    expected = slave.original_data.mem[job["indices"]]
    numpy.testing.assert_allclose(
        slave.minibatch_data.mem[:32], expected, rtol=1e-6)

    master.apply_data_from_slave(True, s)
    assert master.pending_minibatches_count == 0
    assert master.samples_served == 32


def test_drop_slave_requeues_failed_minibatches():
    master = make_loader(device=None)
    master.workflow.workflow.workflow_mode = "master"
    s = _FakeSlave("dead")
    job = master.generate_data_for_slave(s)
    assert master.pending_minibatches_count == 1
    master.drop_slave(s)
    assert master.pending_minibatches_count == 0
    assert len(master.failed_minibatches) == 1
    assert master.total_failed == 1
    # next serve must re-serve the failed window first
    s2 = _FakeSlave("alive")
    job2 = master.generate_data_for_slave(s2)
    assert job2["minibatch_offset"] == job["minibatch_offset"]
    numpy.testing.assert_array_equal(job2["indices"], job["indices"])


def test_pickle_moves_pending_to_failed():
    import pickle
    master = make_loader(device=None)
    master.workflow.workflow.workflow_mode = "master"
    master.generate_data_for_slave(_FakeSlave("s1"))
    state = master.__getstate__()
    assert len(state["failed_minibatches"]) == 1


# ------------------------------------------------------------------- MSE

class SyntheticMSELoader(FullBatchLoaderMSE):
    def load_data(self):
        self.class_lengths[:] = [0, 16, 64]
        self._calc_class_end_offsets()
        self.create_originals((8,), labels=False)
        rng = numpy.random.RandomState(0)
        self.original_data.mem[:] = rng.rand(80, 8)
        self.original_targets.mem = (
            self.original_data.mem @ rng.rand(8, 3)).astype(numpy.float32)


@pytest.mark.parametrize("batch", [16, 24])
def test_mse_loader_targets(cpu_device, batch):
    """Targets 3 wide go through the row store too, bit for bit; at
    batch 24 the 16 validation rows are a short minibatch."""
    wf = DummyWorkflow()
    loader = SyntheticMSELoader(wf, minibatch_size=batch)
    loader.initialize(device=cpu_device)
    assert loader._stores_["targets"].shape == (80, 1, 128)
    for _ in range(3):
        loader.run()
        count = loader.minibatch_size
        loader.minibatch_targets.map_read()
        idx = loader.minibatch_indices.mem[:count]
        loader.original_targets.map_read()
        assert loader.minibatch_targets.mem[:count].tobytes() == \
            loader.original_targets.mem[idx].tobytes()
        assert not loader.minibatch_targets.mem[count:].any()
