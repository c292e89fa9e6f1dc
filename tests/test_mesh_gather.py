"""The resident dataset under a mesh (ISSUE 30): the row stores laid
over the data axis, the minibatch gathered onto the mesh as the
data-parallel step takes it, nothing of it through the host.

Tier-1, on the eight virtual CPU devices (Pallas in interpret mode): the
mesh gather against the one-chip gather of the same window bit for bit,
what each device holds of a store, and the whole stack — a workflow fused
with a mesh over a ``FullBatchLoader`` on the CPU device path — against
the same workflow forced through ``FusedTrainer._stage_sharded``.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.dummy import DummyWorkflow
from veles_tpu.loader import FullBatchLoader, FullBatchLoaderMSE
from veles_tpu.memory import Array
from veles_tpu.models.nn_workflow import StandardWorkflow
from veles_tpu.observe.metrics import registry
from veles_tpu.ops import gather
from veles_tpu.parallel import batch_sharding, make_mesh
from veles_tpu.prng import RandomGenerator

pytestmark = pytest.mark.dist

BATCH = 16
SAMPLE = (5, 7)


def mesh_of(chips):
    return make_mesh({"data": chips}, jax.devices()[:chips])


# -- (a) the gather ----------------------------------------------------------

@pytest.mark.parametrize("count", [BATCH, 11], ids=["full", "short"])
@pytest.mark.parametrize("rows", [64, 61],
                         ids=["rows_multiple", "rows_not_multiple"])
@pytest.mark.parametrize("chips", [2, 4, 8])
@pytest.mark.parametrize("kind", ["float32", "bfloat16", "labels"])
def test_mesh_gather_is_the_one_chip_gather(kind, chips, rows, count):
    """The same rows in the same order as the one-chip gather of the
    same window, the short minibatch's zero tail and -1 labels
    included, already split over the axis as a batch is."""
    rng = numpy.random.default_rng([chips, rows, count])
    mesh = mesh_of(chips)
    window = numpy.zeros(BATCH, numpy.int32)
    window[:count] = rng.permutation(rows)[:count]
    live = numpy.arange(BATCH) < count
    over = dict(mesh=mesh, data_axis="data")
    if kind == "labels":
        table = rng.integers(0, 1000, rows).astype(numpy.int32)
        buf = gather.build_label_store(table)
        want = numpy.where(live, numpy.asarray(gather.gather_labels(
            jnp.asarray(buf), jnp.asarray(window))), -1)
        got = gather.mesh_gather_labels(
            gather.shard_store(buf, mesh, "data"), window,
            numpy.int32(count), **over)
        assert want[:count].tolist() == table[window[:count]].tolist()
    else:
        table = rng.standard_normal((rows,) + SAMPLE).astype(
            jnp.dtype(kind))
        buf = gather.build_store(table)
        want = numpy.asarray(gather.gather_minibatch(
            jnp.asarray(buf), jnp.asarray(window), sample_shape=SAMPLE))
        want = want * live.reshape(-1, 1, 1).astype(want.dtype)
        got = gather.mesh_gather_minibatch(
            gather.shard_store(buf, mesh, "data"), window,
            numpy.int32(count), sample_shape=SAMPLE, **over)
    assert got.dtype == want.dtype
    assert numpy.array_equal(numpy.asarray(got), want)
    assert got.sharding.is_equivalent_to(batch_sharding(mesh), got.ndim)
    assert {s.data.shape[0] for s in got.addressable_shards} == {
        BATCH // chips}


def test_mesh_gather_refuses_a_window_the_axis_does_not_divide():
    mesh = mesh_of(4)
    store = gather.shard_store(
        gather.build_store(numpy.zeros((8, 3), numpy.float32)), mesh,
        "data")
    with pytest.raises(ValueError, match="not divisible by mesh axis"):
        gather.mesh_gather_minibatch(
            store, numpy.zeros(6, numpy.int32), numpy.int32(6),
            mesh=mesh, data_axis="data", sample_shape=(3,))


# -- the loaders the whole-stack tests run -----------------------------------

class Blobs(FullBatchLoader):
    """4-class Gaussian blobs; 314 rows, which 4 and 8 do not divide,
    and a short last minibatch in both classes at batch 48."""

    def load_data(self):
        self.class_lengths[:] = [0, 64, 250]
        self._calc_class_end_offsets()
        self.create_originals((16,))
        rng = numpy.random.RandomState(99)
        centers = rng.randn(4, 16) * 2.0
        for i in range(self.total_samples):
            self.original_data.mem[i] = (
                centers[i % 4] + rng.randn(16) * 0.3)
            self.original_labels[i] = i % 4


class BlobsMSE(FullBatchLoaderMSE):
    def load_data(self):
        self.class_lengths[:] = [0, 64, 250]
        self._calc_class_end_offsets()
        self.create_originals((16,), labels=False)
        rng = numpy.random.RandomState(5)
        self.original_data.mem[:] = rng.rand(314, 16)
        self.original_targets.mem = (
            self.original_data.mem @ rng.rand(16, 4)).astype(numpy.float32)


def workflow(loss, mesh, device, epochs=2):
    from veles_tpu import prng
    prng.get().seed(7)
    last = {"softmax": "softmax", "mse": "all2all"}[loss]
    sw = StandardWorkflow(
        DummyWorkflow().workflow,
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": last, "output_sample_shape": 4,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: (Blobs if loss == "softmax" else BlobsMSE)(
            w, minibatch_size=48, prng=RandomGenerator("blobs", seed=3)),
        loss=loss, decision_config=dict(max_epochs=epochs))
    sw.fuse(mesh=mesh, grad_bucket_mb=0.001)
    if device is not None:
        sw.initialize(device=device)
    return sw


def record_steps(trainer, monkeypatch):
    """The arrays every step receives and each train step's loss."""
    seen = {"shardings": [], "losses": []}
    train, evaluate = trainer._train_step, trainer._eval_step

    def train_step(x, target, batch_size):
        seen["shardings"] += [x.sharding, target.sharding]
        train(x, target, batch_size)
        seen["losses"].append(trainer.last_loss)

    def eval_step(x, target, batch_size):
        seen["shardings"] += [x.sharding, target.sharding]
        evaluate(x, target, batch_size)

    monkeypatch.setattr(trainer, "_train_step", train_step)
    monkeypatch.setattr(trainer, "_eval_step", eval_step)
    return seen


# -- (b) what each device holds ----------------------------------------------

@pytest.mark.parametrize("chips", [2, 4, 8])
def test_each_chip_holds_its_rows_and_none_the_table(cpu_device, chips):
    sw = workflow("mse", mesh_of(chips), cpu_device)
    loader = sw.loader
    assert sorted(loader._stores_) == ["data", "targets"]
    for name, store in loader._stores_.items():
        shards = store.addressable_shards
        assert len(shards) == chips
        assert len({shard.device for shard in shards}) == chips
        per_chip = -(-314 // chips)
        assert {shard.data.shape[0] for shard in shards} == {per_chip}
        assert per_chip < 314 <= store.shape[0] == chips * per_chip
        # the rows in place, the pad behind them zero
        held = numpy.asarray(store)
        rows = getattr(loader, "original_" + name).mem
        assert numpy.array_equal(
            held[:314].reshape(314, -1)[:, :rows.shape[1]], rows)
        assert not held[314:].any()
    assert registry.peek("loader.store_bytes").value == sum(
        store.nbytes for store in loader._stores_.values())


# -- (c) the whole stack -----------------------------------------------------

@pytest.mark.parametrize("loss", ["softmax", "mse"])
def test_minibatch_never_leaves_the_mesh(cpu_device, monkeypatch, loss):
    """Fused with a mesh over a FullBatchLoader on the device path: no
    byte of a minibatch through the host, a mesh gather every step, the
    step's arrays split as a batch is — and the losses those of the
    same workflow whose loader was never told the mesh, every minibatch
    through ``_stage_sharded``."""
    mesh = mesh_of(4)
    registry.reset()
    sw = workflow(loss, mesh, cpu_device)
    trainer = sw.fused_trainer
    seen = record_steps(trainer, monkeypatch)
    fetched = []
    fetch = Array.map_read

    def map_read(self):
        if self is sw.loader.minibatch_data and self.resident() is not None:
            fetched.append(self)
        fetch(self)

    monkeypatch.setattr(Array, "map_read", map_read)
    staged = []
    monkeypatch.setattr(
        trainer, "_stage_sharded", lambda arr: staged.append(arr))
    sw.run()
    steps = registry.peek("train.steps").value
    # 6 train minibatches of 250 rows an epoch, 2 of validation's 64 a pass
    assert steps == 12 and len(seen["losses"]) == 12
    served = len(seen["shardings"]) // 2
    assert served > steps and not (served - steps) % 2
    assert registry.peek("loader.mesh_gathers").value == served
    assert registry.peek("step.host_staged_bytes").value == 0
    assert not staged and not fetched
    for sharding in seen["shardings"]:
        assert sharding.is_equivalent_to(batch_sharding(mesh), 2)

    monkeypatch.undo()
    registry.reset()
    sw_host = workflow(loss, mesh, None)
    sw_host.loader.mesh_axes = sw_host.loader._mesh_ = None
    sw_host.initialize(device=cpu_device)
    assert {len(store.sharding.device_set)
            for store in sw_host.loader._stores_.values()} == {1}
    seen_host = record_steps(sw_host.fused_trainer, monkeypatch)
    sw_host.run()
    assert registry.peek("loader.mesh_gathers").value == 0
    width = {"softmax": 16 * 4 + 4, "mse": (16 + 4) * 4}[loss]
    assert registry.peek("step.host_staged_bytes").value == \
        served * 48 * width
    assert [float(loss) for loss in seen["losses"]] == [
        float(loss) for loss in seen_host["losses"]]
    assert sw.decision.epoch_metrics == sw_host.decision.epoch_metrics


# -- (d) one chip: as it was -------------------------------------------------

def test_without_a_mesh_nothing_changes(cpu_device):
    """No mesh: the stores sit on the loader's one device, no counter
    moves, and ``jit_gather_minibatch`` lowers to the text of the
    program as it was before a mesh was known here (PR 26's, kept
    below as the reference)."""
    def gather_minibatch(dataset, indices, out_dtype=None,
                         sample_shape=None):
        with jax.named_scope(gather.SCOPE):
            batch = indices.shape[0]
            indices = jnp.clip(indices.astype(jnp.int32), 0,
                               dataset.shape[0] - 1)
            out = gather._kernel_rows(dataset, indices)
            out = out.reshape(batch, -1)[:, :int(numpy.prod(sample_shape))]
            return out.astype(out_dtype or dataset.dtype).reshape(
                (batch,) + tuple(sample_shape))

    was = jax.jit(gather_minibatch,
                  static_argnames=("out_dtype", "sample_shape"))
    for rows, sample_shape, dtype, out_dtype, batch in (
            (1450, (784,), "float32", "float32", 100),
            (128, (27, 27, 3), "bfloat16", "bfloat16", 16),
            (500, (32, 32, 3), "uint8", "float32", 8)):
        avals = (jax.ShapeDtypeStruct(gather.store_shape(
                     rows, int(numpy.prod(sample_shape)), dtype), dtype),
                 jax.ShapeDtypeStruct((batch,), jnp.int32))
        static = dict(out_dtype=numpy.dtype(out_dtype),
                      sample_shape=sample_shape)
        text = gather.gather_minibatch.trace(
            *avals, **static).lower().as_text()
        assert "jit_gather_minibatch" in text
        assert text == was.trace(*avals, **static).lower().as_text()

    registry.reset()
    wf = DummyWorkflow()
    loader = Blobs(wf, minibatch_size=48)
    loader.initialize(device=cpu_device)
    assert loader.mesh_axes is None and loader._mesh_ is None
    for store in loader._stores_.values():
        assert store.sharding.device_set == {cpu_device.jax_device}
        assert store.shape[0] in (314, 3)
    for _ in range(3):
        loader.run()
    held = loader.minibatch_data.resident()
    assert held.sharding.device_set == {cpu_device.jax_device}
    assert registry.peek("loader.mesh_gathers").value == 0


# -- (e) a pickle ------------------------------------------------------------

def test_pickled_mesh_workflow_restores_and_places_its_stores(
        cpu_device, monkeypatch):
    """The loader's pickle carries the mesh's axes, not the Mesh; the
    restored workflow lays its stores over the mesh again and its
    minibatches still never pass through the host."""
    mesh = mesh_of(8)
    sw = workflow("softmax", mesh, cpu_device, epochs=1)
    sw.run()
    state = sw.loader.__getstate__()
    assert state["mesh_axes"] == {"data": 8}
    assert state["data_axis"] == "data"
    assert not [key for key in state if key.endswith("_")]
    registry.reset()
    restored = pickle.loads(pickle.dumps(sw))
    assert restored.loader._mesh_ is None and not restored.loader._stores_
    restored.workflow = DummyWorkflow().workflow
    restored.decision.max_epochs = 2
    restored.decision.complete <<= False
    restored.initialize(device=cpu_device)
    assert restored.loader._mesh_ == mesh
    assert restored.fused_trainer.mesh == mesh
    for store in restored.loader._stores_.values():
        assert len(store.sharding.device_set) == 8
        assert {s.data.shape[0] for s in store.addressable_shards} == {
            -(-store.shape[0] // 8)}
    seen = record_steps(restored.fused_trainer, monkeypatch)
    restored.run()
    assert seen["losses"]
    assert registry.peek("loader.mesh_gathers").value > 0
    assert registry.peek("step.host_staged_bytes").value == 0
    for sharding in seen["shardings"]:
        assert sharding.is_equivalent_to(batch_sharding(mesh), 2)


def test_token_rows_take_the_same_gather(cpu_device):
    """``TokenRowLoader`` serves through the one helper: told a mesh,
    its rows live and are gathered over it, and inputs and next-token
    targets are those of the one-chip loader."""
    from veles_tpu.loader.tokens import TokenRowLoader

    class Tokens(TokenRowLoader):
        def load_data(self):
            self.class_lengths[:] = [0, 8, 22]
            self._calc_class_end_offsets()
            self.create_originals((9,), labels=False)
            self.original_data.mem[:] = numpy.random.RandomState(
                1).randint(0, 50, (30, 9))

    def serve(mesh):
        loader = Tokens(DummyWorkflow(), minibatch_size=8,
                        prng=RandomGenerator("tokens", seed=4))
        if mesh is not None:
            loader.lay_over_mesh(mesh, "data")
        loader.initialize(device=cpu_device)
        served = []
        for _ in range(5):  # 8 of validation, then 8, 8 and a short 6
            loader.run()
            held = (loader.minibatch_data.resident(),
                    loader.minibatch_labels.resident())
            served.append([numpy.asarray(part) for part in held])
        return loader, held, served

    registry.reset()
    loader, held, served = serve(mesh_of(4))
    assert len(loader._stores_["data"].sharding.device_set) == 4
    assert registry.peek("loader.mesh_gathers").value == 5
    for part in held:
        assert len(part.sharding.device_set) == 4
    _, _, want = serve(None)
    for got_step, want_step in zip(served, want):
        for got_part, want_part in zip(got_step, want_step):
            assert numpy.array_equal(got_part, want_part)
    assert (served[3][1][6:] == -1).all() and not served[3][0][6:].any()
