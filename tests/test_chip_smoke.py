"""chip_smoke.py on the CPU: the refusals hold, the phase functions run
at toy width through their arguments with interpreter kernels, and the
compile-cache contract holds both ways.  The chip itself is reached only
through the chip tool (README "Quick start")."""

import os
import sys

import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

TOY_LAYERS = [
    {"type": "conv_str", "n_kernels": 8, "kx": 3, "ky": 3, "padding": 1,
     "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
    {"type": "all2all_str", "output_sample_shape": 32,
     "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "dropout", "dropout_ratio": 0.5},
    {"type": "softmax", "output_sample_shape": 10,
     "learning_rate": 0.01, "gradient_moment": 0.9},
]

TOY_KERNELS = dict(
    matmul=130, int8_matmul=(40, 200, 130),
    int8_conv=(2, 6, 6, 5, 7, 3), attention=(2, 40, 16, 2),
    conv_vjp=(2, 7, 7, 3, 5, 3, 1),
    pools=(((2, 9, 9, 3), (3, 3), (2, 2)),
           ((2, 8, 8, 3), (2, 2), (2, 2))),
    reduce=(100, 70), normalize=(30, 50),
    join=((4, 6), (4, 7), (4, 1)), gather=(50, 16, (5, 31)),
    uniform=(64, 128))


def test_refuses_to_run_without_a_tpu(capsys):
    """Exit code 2, the platform found is named, no result line."""
    assert chip_smoke.main() == 2
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err and "no TPU" in captured.err
    assert captured.out == ""


def test_tpu_device_by_name_raises_on_cpu():
    from veles_tpu.backends import Device, TPUDevice
    assert not TPUDevice.available()
    with pytest.raises(RuntimeError, match="default backend is 'cpu'"):
        Device(backend="tpu")


@pytest.fixture
def _toy_config(tmp_path, monkeypatch):
    """bfloat16 like the chip run, the Pallas backward on (interpreter),
    and the snapshot settings the train phase writes put back after."""
    from veles_tpu.ops import common
    saved = dict(root.common.snapshot.__dict__)
    monkeypatch.setattr(root.common.engine, "precision_type", "bfloat16")
    monkeypatch.setattr(common, "PALLAS_BWD_ENV", "1")
    yield str(tmp_path / "snapshots")
    root.common.snapshot.__dict__.clear()
    root.common.snapshot.__dict__.update(saved)


@pytest.mark.parametrize("chips", [1, 8])
def test_train_and_serve_phases_at_toy_width(_toy_config, chips):
    """The same phase functions the chip runs, through their size
    arguments: one device by the default entry (auto-fuse + input
    pipeline), the eight virtual devices over the data mesh."""
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    sw = chip_smoke.train_phase(
        device, TOY_LAYERS, (16, 16, 3), 8 * chips, _toy_config,
        chips=chips, train_batches=4, valid_batches=2, epochs=3,
        label_kinds=4, expect_mosaic=False)
    assert sw.forwards[0].weights.dtype == numpy.dtype("bfloat16")
    assert (sw.fused_trainer.mesh is not None) == (chips > 1)
    receipt = chip_smoke.serve_phase(sw, ladder=(1, 8), blocks=(1, 5, 8))
    assert receipt["rungs"] == [1, 8]


def test_smoke_snapshot_carries_the_seed_not_the_dataset(_toy_config):
    """What the train phase exports: weights and solver state.  The
    seeded dataset and the activations come back by initialize."""
    from veles_tpu.snapshotter import SnapshotterBase
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"
    sw = chip_smoke.train_phase(
        device, TOY_LAYERS, (16, 16, 3), 8, _toy_config,
        train_batches=4, valid_batches=2, epochs=2, label_kinds=4,
        expect_mosaic=False)
    assert sw.loader.original_data.nbytes > 0
    restored = SnapshotterBase.import_file(sw.snapshotter.destination,
                                           fallback=False)
    assert not restored.loader.original_data
    assert restored.loader.data_seed == sw.loader.data_seed
    assert not restored.forwards[0].output.mem.any()
    assert restored.gds[-1].accum_weights.nbytes > 0


def test_file_cap_probe_sees_a_real_limit(tmp_path):
    """``ulimit -f`` below the size asked for: False, and nothing left
    behind.  (The soft limit is lowered around the probe only.)"""
    import resource
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    assert chip_smoke.file_cap_allows(str(tmp_path), 1 << 22)
    resource.setrlimit(resource.RLIMIT_FSIZE, (1 << 20, hard))
    try:
        refused = chip_smoke.file_cap_allows(str(tmp_path), 1 << 22)
        allowed = chip_smoke.file_cap_allows(str(tmp_path), 1 << 19)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    assert refused is False and allowed is True
    assert os.listdir(str(tmp_path)) == []


def test_train_phase_under_a_file_cap_says_so(_toy_config, monkeypatch,
                                              capsys):
    """A machine that caps one file below the snapshot: the snapshotter
    is turned off out loud, the round trip is checked in memory, and
    no file is written."""
    monkeypatch.setattr(chip_smoke, "file_cap_allows",
                        lambda directory, nbytes: False)
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"
    sw = chip_smoke.train_phase(
        device, TOY_LAYERS, (16, 16, 3), 8, _toy_config,
        train_batches=4, valid_batches=2, epochs=2, label_kinds=4,
        expect_mosaic=False)
    out = capsys.readouterr().out
    assert "caps one file below" in out and "RLIMIT_FSIZE" in out
    assert "snapshot in memory" in out
    assert sw.snapshotter.destination is None
    assert not os.path.isdir(_toy_config) or not os.listdir(_toy_config)


def test_kernels_phase_at_toy_width():
    done = chip_smoke.kernels_phase(TOY_KERNELS, expect_mosaic=False)
    assert len(done) == 16, done


@pytest.fixture
def _fresh_cache_decision(monkeypatch):
    """An undecided cache, and a dict standing in for
    ``jax.config.update`` so the test neither moves the session's real
    cache nor misses a call."""
    import jax
    calls = {}
    monkeypatch.setattr(backends, "_COMPILE_CACHE_DIR", None)
    monkeypatch.setattr(jax.config, "update", calls.__setitem__)
    return calls


def test_compile_cache_placed_from_outside(_fresh_cache_decision,
                                           monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and no code sets
    another; only the two floors are touched."""
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert backends.enable_compile_cache() == placed
    assert _fresh_cache_decision == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1}
    # idempotent: the decision is taken once per process
    _fresh_cache_decision.clear()
    assert backends.enable_compile_cache() == placed
    assert _fresh_cache_decision == {}


def test_compile_cache_default_is_inside_the_checkout(
        _fresh_cache_decision, monkeypatch):
    """Unset: one fixed git-ignored path inside the checkout."""
    from veles_tpu import config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert config._DEFAULT_CACHE == os.path.join(REPO, ".veles_cache")
    with open(os.path.join(REPO, ".gitignore")) as fin:
        assert ".veles_cache/" in fin.read().split()
    monkeypatch.setattr(root.common.dirs, "cache", config._DEFAULT_CACHE)
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    expected = os.path.join(REPO, ".veles_cache", "jax_cache")
    assert backends.enable_compile_cache() == expected
    assert _fresh_cache_decision["jax_compilation_cache_dir"] == expected
