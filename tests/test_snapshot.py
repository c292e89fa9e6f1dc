"""Snapshot -> resume end-to-end (reference: snapshotter.py:522 +
workflow.py:338-340 + SURVEY.md section 3.4): training state, RNG, and
epoch counters survive the pickle round-trip and training continues;
plus the crash-consistency layer — atomic writes, sidecar manifests,
verification + previous-good fallback, retention, run gating, and the
snapshot-db failure path (ISSUE 2)."""

import gzip
import os
import pickle
import time

import numpy
import pytest

from veles_tpu.config import root
from veles_tpu.dummy import DummyLauncher, DummyWorkflow
from veles_tpu.models.nn_workflow import StandardWorkflow
from veles_tpu.prng import RandomGenerator
from veles_tpu.snapshotter import (
    MANIFEST_SUFFIX, SnapshotError, Snapshotter, SnapshotterBase)
from tests.test_models import BlobsLoader


def _build(device, max_epochs):
    wf = DummyWorkflow()
    sw = StandardWorkflow(
        wf.workflow,
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 32,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 4,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: BlobsLoader(
            w, minibatch_size=64, prng=RandomGenerator("snap", seed=9)),
        decision_config=dict(max_epochs=max_epochs),
    )
    sw.initialize(device=device)
    return sw


def test_snapshot_resume_continues_training(tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=2)
    sw.run()
    assert bool(sw.decision.complete)
    epoch_before = sw.decision.epoch_number
    sw.forwards[0].weights.map_read()
    weights_before = numpy.array(sw.forwards[0].weights.mem)

    blob = pickle.dumps(sw, protocol=pickle.HIGHEST_PROTOCOL)
    restored = pickle.loads(blob)

    # reattach to a fresh launcher and continue for 2 more epochs
    restored.workflow = DummyLauncher()
    restored.restored_from_snapshot_ = True
    restored.decision.max_epochs = 4
    restored.decision.complete <<= False
    restored.initialize(device=cpu_device)

    # weights survived the round trip
    restored.forwards[0].weights.map_read()
    numpy.testing.assert_array_equal(
        restored.forwards[0].weights.mem, weights_before)
    # epoch counter continued, not reset
    assert restored.loader.epoch_number == epoch_before

    restored.run()
    assert bool(restored.decision.complete)
    assert restored.decision.epoch_number >= 4
    assert restored.decision.epoch_metrics[1] < 5.0


def test_snapshot_keeps_state_not_activations(cpu_device):
    """Weights and solver state travel; a forward's output and a GD's
    err_input keep shape and dtype only (zeros after the restore), so a
    snapshot does not grow with the minibatch."""
    sw = _build(cpu_device, max_epochs=1)
    sw.run()
    fwd, gd = sw.forwards[0], sw.gds[0]
    fwd.output.map_read()
    assert numpy.abs(fwd.output.mem).max() > 0
    restored = pickle.loads(pickle.dumps(
        sw, protocol=pickle.HIGHEST_PROTOCOL))
    back = restored.forwards[0]
    assert back.output.shape == fwd.output.shape
    assert back.output.dtype == fwd.output.dtype
    assert not back.output.mem.any()
    assert restored.gds[0].err_input.shape == gd.err_input.shape
    fwd.weights.map_read()
    numpy.testing.assert_array_equal(back.weights.mem, fwd.weights.mem)
    gd.accum_weights.map_read()
    numpy.testing.assert_array_equal(restored.gds[0].accum_weights.mem,
                                     gd.accum_weights.mem)


def test_shallow_array_keeps_bfloat16_and_reads_no_device(cpu_device):
    """Shape and dtype survive by the dtype object (bfloat16's ``.str``
    is the void '<V2'), and dropping the bytes costs no device read."""
    import jax.numpy as jnp
    from veles_tpu.memory import Array
    arr = Array(shallow_pickle=True)
    arr.set_device_array(jnp.ones((3, 5), jnp.bfloat16), cpu_device)
    back = pickle.loads(pickle.dumps(arr))
    assert back.shape == (3, 5) and back.dtype == jnp.bfloat16
    assert not back.mem.astype(numpy.float32).any()
    # the host copy of the original is still the stale placeholder
    assert not arr.mem.astype(numpy.float32).any()


def test_snapshotter_unit_writes_and_imports(tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="t",
                       interval=1, time_interval=0, compression="gz")
    snap.initialize()
    sw.run()
    snap.run()
    assert snap.destination and os.path.exists(snap.destination)
    # _current symlink maintained (reference :388-409)
    link = os.path.join(str(tmp_path), "t_current")
    assert os.path.islink(link)

    restored = SnapshotterBase.import_file(snap.destination)
    assert type(restored).__name__ == "StandardWorkflow"
    restored.workflow = DummyLauncher()
    restored.initialize(device=cpu_device)
    restored.forwards[0].weights.map_read()
    sw.forwards[0].weights.map_read()
    numpy.testing.assert_array_equal(
        restored.forwards[0].weights.mem, sw.forwards[0].weights.mem)


def test_snapshotter_codecs(tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    for codec in ("", "gz", "bz2", "xz"):
        snap = Snapshotter(sw, directory=str(tmp_path),
                           prefix="c%s" % (codec or "raw"), interval=1,
                           time_interval=0, compression=codec)
        snap.initialize()
        snap.export()
        restored = SnapshotterBase.import_file(snap.destination)
        assert restored is not None


def test_slave_never_snapshots(tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    sw.workflow.workflow_mode = "slave"
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="s",
                       interval=1, time_interval=0)
    snap.initialize()
    snap.run()
    assert snap.destination is None


# -- run gating (interval / time_interval / skip) -------------------------


class _RecordingSnapshotter(SnapshotterBase):
    """Counts exports without paying for a real workflow pickle."""

    def __init__(self, *args, **kwargs):
        super(_RecordingSnapshotter, self).__init__(*args, **kwargs)
        self.exports = 0

    def export(self):
        self.exports += 1
        self.destination = os.path.join(
            self.directory, "%s_fake%d" % (self.prefix, self.exports))


def test_run_gating_interval(tmp_path):
    snap = _RecordingSnapshotter(
        DummyWorkflow(), directory=str(tmp_path), interval=2,
        time_interval=0)
    snap.initialize()
    snap.run()
    assert snap.exports == 0, "counter 1 is not a multiple of 2"
    snap.run()
    assert snap.exports == 1
    snap.run()
    snap.run()
    assert snap.exports == 2


def test_run_gating_time_interval_first_snapshot_exempt(tmp_path):
    """The throttle only applies to REPEAT snapshots: a short run (or
    an early crash) must still leave one snapshot on disk."""
    snap = _RecordingSnapshotter(
        DummyWorkflow(), directory=str(tmp_path), interval=1,
        time_interval=3600)
    snap.initialize()
    snap.run()
    assert snap.exports == 1, "first snapshot must ignore time_interval"
    snap.run()
    assert snap.exports == 1, "repeat within time_interval throttled"


def test_run_gating_skip_bool(tmp_path):
    snap = _RecordingSnapshotter(
        DummyWorkflow(), directory=str(tmp_path), interval=1,
        time_interval=0)
    snap.initialize()
    snap.skip <<= True
    snap.run()
    snap.run()
    assert snap.exports == 0
    snap.skip <<= False
    snap.run()
    assert snap.exports == 1


def test_run_gating_disable_config(tmp_path):
    snap = _RecordingSnapshotter(
        DummyWorkflow(), directory=str(tmp_path), interval=1,
        time_interval=0)
    snap.initialize()
    root.common.disable.update({"snapshotting": True})
    try:
        snap.run()
        assert snap.exports == 0
    finally:
        root.common.disable.update({"snapshotting": False})
    snap.run()
    assert snap.exports == 1


# -- import_file: codec sniffing on damaged files -------------------------


def test_import_file_zero_byte(tmp_path):
    path = tmp_path / "empty.pickle"
    path.write_bytes(b"")
    with pytest.raises(SnapshotError) as err:
        SnapshotterBase.import_file(str(path))
    assert "no usable snapshot" in str(err.value)


def test_import_file_truncated_gz(tmp_path):
    blob = gzip.compress(pickle.dumps({"k": list(range(1000))}))
    path = tmp_path / "cut.pickle.gz"
    path.write_bytes(blob[:len(blob) // 2])  # valid magic, torn body
    with pytest.raises(SnapshotError):
        SnapshotterBase.import_file(str(path))


def test_import_file_truncated_plain_pickle(tmp_path):
    blob = pickle.dumps({"k": 1})
    path = tmp_path / "cut.pickle"
    path.write_bytes(blob[:-3])
    with pytest.raises(SnapshotError):
        SnapshotterBase.import_file(str(path))


def test_import_file_sniffs_extensionless(tmp_path):
    """The _current symlink carries no extension: the codec must come
    from the magic bytes."""
    path = tmp_path / "no_extension"
    path.write_bytes(gzip.compress(pickle.dumps({"ok": 42})))
    assert SnapshotterBase.import_file(str(path)) == {"ok": 42}


# -- manifest / atomicity / retention -------------------------------------


def test_export_writes_verified_manifest(tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    sw.run()
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="m",
                       interval=1, time_interval=0, compression="gz")
    snap.initialize()
    snap.export()
    dest = snap.destination
    assert os.path.exists(dest + MANIFEST_SUFFIX)
    assert not os.path.exists(dest + ".tmp"), "tmp residue after export"
    ok, manifest = SnapshotterBase.verify_snapshot(dest)
    assert ok is True
    assert manifest["nbytes"] == os.path.getsize(dest)
    assert manifest["codec"] == "gz"
    assert manifest["workflow"] == "StandardWorkflow"
    assert manifest["checksum"] == sw.checksum
    # the _current link verifies through to the same manifest
    link = os.path.join(str(tmp_path), "m_current")
    ok, _ = SnapshotterBase.verify_snapshot(link)
    assert ok is True


def test_verify_snapshot_detects_truncation_and_corruption(
        tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="v",
                       interval=1, time_interval=0, compression="")
    snap.initialize()
    snap.export()
    dest = snap.destination
    original = open(dest, "rb").read()
    # truncation -> size mismatch
    with open(dest, "wb") as fout:
        fout.write(original[:-10])
    ok, reason = SnapshotterBase.verify_snapshot(dest)
    assert ok is False and "size mismatch" in reason
    # same-size corruption -> sha mismatch
    with open(dest, "wb") as fout:
        fout.write(original[:-1] + bytes([original[-1] ^ 0xFF]))
    ok, reason = SnapshotterBase.verify_snapshot(dest)
    assert ok is False and "sha256" in reason
    # restored bytes verify again
    with open(dest, "wb") as fout:
        fout.write(original)
    assert SnapshotterBase.verify_snapshot(dest)[0] is True


def test_legacy_snapshot_without_manifest_still_imports(tmp_path,
                                                        cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="l",
                       interval=1, time_interval=0, compression="gz")
    snap.initialize()
    snap.export()
    os.remove(snap.destination + MANIFEST_SUFFIX)
    ok, reason = SnapshotterBase.verify_snapshot(snap.destination)
    assert ok is None and reason == "no manifest"
    assert SnapshotterBase.import_file(snap.destination) is not None


def test_retention_keeps_newest_and_current(tmp_path, cpu_device):
    sw = _build(cpu_device, max_epochs=1)
    sw.run()
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="r",
                       interval=1, time_interval=0, compression="gz",
                       keep=2)
    snap.initialize()
    for i in range(5):
        snap.suffix = "e%d" % i
        snap.export()
        time.sleep(0.02)  # distinct mtimes for the retention sort
    pickles = sorted(f for f in os.listdir(str(tmp_path))
                     if ".pickle" in f and not f.endswith(MANIFEST_SUFFIX)
                     and not f.endswith(".tmp"))
    # keep=2 (+ best-by-metric may add one more)
    assert len(pickles) <= 3
    assert any("e4" in f for f in pickles), "newest must survive"
    assert any("e3" in f for f in pickles)
    link = os.path.join(str(tmp_path), "r_current")
    target = os.path.realpath(link)
    assert os.path.exists(target), "_current target must never be pruned"
    # manifests of pruned snapshots are pruned with them
    manifests = [f for f in os.listdir(str(tmp_path))
                 if f.endswith(MANIFEST_SUFFIX)]
    assert len(manifests) == len(pickles)


def test_resolve_resume(tmp_path, cpu_device):
    assert SnapshotterBase.resolve_resume("") is None
    assert SnapshotterBase.resolve_resume(
        "auto", directory=str(tmp_path / "missing")) is None
    with pytest.raises(SnapshotError):
        SnapshotterBase.resolve_resume(str(tmp_path / "nope.pickle"))
    sw = _build(cpu_device, max_epochs=1)
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="a",
                       interval=1, time_interval=0, compression="gz")
    snap.initialize()
    snap.suffix = "one"
    snap.export()
    resolved = SnapshotterBase.resolve_resume(
        "auto", directory=str(tmp_path))
    assert resolved == os.path.realpath(
        os.path.join(str(tmp_path), "a_current"))
    # explicit path resolves to itself
    assert SnapshotterBase.resolve_resume(snap.destination) == \
        snap.destination


# -- satellite regressions ------------------------------------------------


def test_record_in_db_failure_warns_not_raises(tmp_path, cpu_device,
                                               caplog):
    """A locked/readonly/unopenable snapshot DB must never abort the
    training step after a successful snapshot write."""
    sw = _build(cpu_device, max_epochs=1)
    bad_db = os.path.join(str(tmp_path), "no_such_dir", "snap.sqlite")
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="db",
                       interval=1, time_interval=0, compression="gz",
                       db_path=bad_db)
    snap.initialize()
    snap.export()  # must not raise
    assert snap.destination and os.path.exists(snap.destination)
    assert any("snapshot db record failed" in r.message
               for r in caplog.records)


def test_failed_current_link_flip_warns(tmp_path, cpu_device,
                                        monkeypatch, caplog):
    """A failed _current flip silently strands resume on an OLD
    snapshot — it must at least be visible in the log."""
    sw = _build(cpu_device, max_epochs=1)
    snap = Snapshotter(sw, directory=str(tmp_path), prefix="ln",
                       interval=1, time_interval=0, compression="gz")
    snap.initialize()

    def broken_symlink(*args, **kwargs):
        raise OSError("symlinks unavailable")

    monkeypatch.setattr(os, "symlink", broken_symlink)
    snap.export()  # must not raise
    assert snap.destination and os.path.exists(snap.destination)
    assert any("failed to update snapshot link" in r.message
               for r in caplog.records)


class OtherWorkflow(StandardWorkflow):
    """A second model snapshotting into the same directory."""

    hide_from_registry = True


def test_fallback_never_crosses_workflows(tmp_path, cpu_device, caplog):
    """A shared snapshot directory holds several models' histories; a
    corrupted snapshot must fall back to ITS OWN workflow's previous
    good snapshot, never to a newer snapshot of a different one."""
    sw = _build(cpu_device, max_epochs=1)
    mine = Snapshotter(sw, directory=str(tmp_path), prefix="mine",
                       interval=1, time_interval=0, compression="gz")
    mine.initialize()
    mine.suffix = "old"
    mine.export()
    my_old = mine.destination
    time.sleep(0.02)
    mine.suffix = "new"
    mine.export()
    my_new = mine.destination

    time.sleep(0.02)
    other_sw = _build(cpu_device, max_epochs=1)
    object.__setattr__(other_sw, "__class__", OtherWorkflow)
    other = Snapshotter(other_sw, directory=str(tmp_path),
                        prefix="other", interval=1, time_interval=0,
                        compression="gz")
    other.initialize()
    other.export()  # newest file in the directory, wrong workflow

    with open(my_new, "r+b") as fout:  # corrupt my newest
        fout.seek(os.path.getsize(my_new) // 2)
        byte = fout.read(1)
        fout.seek(-1, os.SEEK_CUR)
        fout.write(bytes([byte[0] ^ 0xFF]))

    restored = SnapshotterBase.import_file(
        os.path.join(str(tmp_path), "mine_current"))
    assert type(restored).__name__ == "StandardWorkflow", \
        "fell back to a different workflow's snapshot"
    assert any(os.path.basename(my_old) in r.message and
               "previous-good" in r.message for r in caplog.records)
