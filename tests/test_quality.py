"""Real-data model quality (reference test model: the Znicz sample
workflows pinned to the quality table in
manualrst_veles_algorithms.rst:31,50).

Offline anchor: sklearn's bundled real handwritten digits through the
FULL loader->workflow->decision->snapshotter graph.  MNIST/CIFAR runs
execute when their datasets are cached (no network in CI)."""

import gzip
import os
import struct
import sys

import numpy
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))

from veles_tpu.datasets import (
    DatasetNotFound, DigitsLoader, digits_arrays, load_idx, mnist_arrays)


def test_load_idx_roundtrip(tmp_path):
    arr = numpy.arange(24, dtype=numpy.uint8).reshape(2, 3, 4)
    raw = struct.pack(">HBB", 0, 0x08, 3)
    raw += struct.pack(">III", 2, 3, 4) + arr.tobytes()
    p = tmp_path / "t.idx"
    p.write_bytes(raw)
    numpy.testing.assert_array_equal(load_idx(str(p)), arr)
    gz = tmp_path / "t.idx.gz"
    gz.write_bytes(gzip.compress(raw))
    numpy.testing.assert_array_equal(load_idx(str(gz)), arr)
    # int32 big-endian payload
    arr32 = numpy.array([[1, -2], [300000, 4]], dtype=">i4")
    raw32 = struct.pack(">HBB", 0, 0x0C, 2) + struct.pack(
        ">II", 2, 2) + arr32.tobytes()
    p32 = tmp_path / "t32.idx"
    p32.write_bytes(raw32)
    numpy.testing.assert_array_equal(load_idx(str(p32)), arr32)


def _write_idx(path, arr, dtype_code=0x08):
    raw = struct.pack(">HBB", 0, dtype_code, arr.ndim)
    raw += struct.pack(">" + "I" * arr.ndim, *arr.shape) + arr.tobytes()
    path.write_bytes(gzip.compress(raw) if str(path).endswith(".gz")
                     else raw)


def _write_stl10_drop(data_dir, rng):
    """Canonical-shaped synthetic STL-10 binaries under data_dir."""
    base = data_dir / "stl10_binary"
    base.mkdir(exist_ok=True)
    for x_name, y_name, count in (("train_X.bin", "train_y.bin", 5000),
                                  ("test_X.bin", "test_y.bin", 8000)):
        (base / x_name).write_bytes(
            rng.randint(0, 256, count * 3 * 96 * 96,
                        dtype=numpy.uint8).tobytes())
        (base / y_name).write_bytes(
            rng.randint(1, 11, count, dtype=numpy.uint8).tobytes())
    return base


def _write_cifar10_drop(data_dir, rng):
    """Canonical-shaped synthetic CIFAR-10 python batches."""
    import pickle
    base = data_dir / "cifar-10-batches-py"
    base.mkdir(exist_ok=True)
    for name in ["data_batch_%d" % i for i in range(1, 6)] + [
            "test_batch"]:
        with open(base / name, "wb") as fout:
            pickle.dump({
                b"data": rng.randint(0, 256, (10000, 3072),
                                     dtype=numpy.uint8),
                b"labels": rng.randint(0, 10, 10000).tolist(),
            }, fout)
    return base


def _write_mnist_drop(data_dir, rng=None):
    """Canonical-shaped synthetic MNIST idx files (uncompressed names;
    _fetch accepts the .gz name minus .gz).  ``rng=None`` writes
    all-zero files — same shapes, much faster for ingest tests."""
    from veles_tpu.datasets import MNIST_FILES
    for key, filename in MNIST_FILES.items():
        count = 60000 if key.startswith("train") else 10000
        shape = (count, 28, 28) if key.endswith("images") else (count,)
        if rng is None:
            arr = numpy.zeros(shape, numpy.uint8)
        elif key.endswith("images"):
            arr = rng.randint(0, 256, shape, dtype=numpy.uint8)
        else:
            arr = rng.randint(0, 10, count, dtype=numpy.uint8)
        _write_idx(data_dir / filename[:-3], arr)


def test_mnist_selfcheck_rejects_wrong_drop(tmp_path):
    """A data drop with non-canonical shapes must fail the self-check
    with a clear message, not surface as a training-time shape error
    (round-3 verdict item 5)."""
    from veles_tpu.datasets import MNIST_FILES
    wrong = numpy.zeros((5, 28, 28), numpy.uint8)
    labels = numpy.zeros(5, numpy.uint8)
    for key, filename in MNIST_FILES.items():
        _write_idx(tmp_path / filename,
                   wrong if key.endswith("images") else labels)
    with pytest.raises(DatasetNotFound, match="self-check failed"):
        mnist_arrays(str(tmp_path))


def test_cifar_selfcheck_rejects_truncated_drop(tmp_path):
    """Truncated CIFAR batches fail the shape self-check loudly."""
    import pickle
    from veles_tpu.datasets import cifar10_arrays
    base = tmp_path / "cifar-10-batches-py"
    base.mkdir()
    batch = {b"data": numpy.zeros((7, 3072), numpy.uint8),
             b"labels": [0] * 7}
    for name in ["data_batch_%d" % i for i in range(1, 6)] + [
            "test_batch"]:
        with open(base / name, "wb") as fout:
            pickle.dump(batch, fout)
    with pytest.raises(DatasetNotFound, match="self-check failed"):
        cifar10_arrays(str(tmp_path))


def test_selfcheck_reports_missing_when_no_drop(tmp_path):
    from veles_tpu.datasets import selfcheck
    report = selfcheck(str(tmp_path))
    assert report["mnist"]["status"] == "missing"
    assert report["cifar10"]["status"] == "missing"
    assert report["stl10"]["status"] == "missing"


def test_ingest_stages_drop_and_selfchecks(tmp_path):
    """The one-command data drop (VERDICT r04 task 3): canonical-format
    files anywhere under a directory land in the cache, parse, and
    come back checksummed in the report."""
    import pickle

    from veles_tpu.datasets import ingest, mnist_arrays

    drop = tmp_path / "drop" / "nested"
    drop.mkdir(parents=True)
    cache = tmp_path / "cache"
    cache.mkdir()
    _write_mnist_drop(drop)
    cdir = drop / "cifar-10-batches-py"
    cdir.mkdir()
    batch = {b"data": numpy.zeros((10000, 3072), numpy.uint8),
             b"labels": [0] * 10000}
    for name in ["data_batch_%d" % i for i in range(1, 6)] + [
            "test_batch"]:
        with open(cdir / name, "wb") as fout:
            pickle.dump(batch, fout)

    report = ingest(str(tmp_path / "drop"), str(cache))
    assert report["mnist"]["status"] == "ok"
    assert report["cifar10"]["status"] == "ok"
    assert report["stl10"]["status"] == "missing"
    assert len(report["cifar10"]["files"]) == 6  # checksummed
    assert len(report["ingested"]["files"]) == 10
    # the staged data actually trains: arrays load from the cache
    tx, ty, vx, vy = mnist_arrays(str(cache))
    assert tx.shape == (60000, 784) and vx.shape == (10000, 784)


def test_ingest_cli_command(tmp_path):
    """python -m veles_tpu.datasets ingest <dir> prints the JSON
    report and exits 0 when something validated."""
    import json
    import subprocess
    import sys

    drop = tmp_path / "drop"
    drop.mkdir()
    cache = tmp_path / "cache"
    cache.mkdir()
    _write_mnist_drop(drop)
    env = dict(os.environ, JAX_PLATFORMS="cpu", VELES_BACKEND="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "veles_tpu.datasets", "ingest",
         str(drop), "--data-dir", str(cache)],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-1500:]
    report = json.loads(proc.stdout)
    assert report["mnist"]["status"] == "ok"
    assert report["mnist"]["source"] == "idx"


@pytest.mark.slow
def test_stl10_drop_parses_and_selfchecks(tmp_path):
    """A canonical-shaped STL-10 drop parses (channel-major,
    column-major layout; 1-indexed labels) and passes the self-check;
    wrong sizes fail loudly.  (slow: writes + reloads a full-size
    360 MB drop)"""
    from veles_tpu.datasets import stl10_arrays

    base = _write_stl10_drop(tmp_path, numpy.random.RandomState(0))

    tx, ty, vx, vy = stl10_arrays(str(tmp_path))
    assert tx.shape == (5000, 96, 96, 3) and vx.shape == (8000, 96, 96, 3)
    assert 0.0 <= tx.min() and tx.max() <= 1.0
    assert ty.min() >= 0 and ty.max() <= 9  # rebased from 1..10

    # layout: byte b of image 0 channel 0 lands at [col, row] transposed
    raw = numpy.fromfile(base / "train_X.bin", numpy.uint8)
    img0 = raw[:3 * 96 * 96].reshape(3, 96, 96)
    numpy.testing.assert_allclose(
        tx[0, 5, 7, 2], img0[2, 7, 5] / 255.0, rtol=1e-6)

    # truncated drop fails the self-check with a clear message
    (base / "test_X.bin").write_bytes(b"\0" * 1000)
    with pytest.raises(DatasetNotFound, match="self-check failed"):
        stl10_arrays(str(tmp_path))


def test_digits_arrays_deterministic_real_data():
    tx, ty, vx, vy = digits_arrays()
    assert tx.shape == (1437, 64) and vx.shape == (360, 64)
    assert tx.dtype == numpy.float32 and ty.dtype == numpy.int32
    assert 0.0 <= tx.min() and tx.max() <= 1.0
    assert set(numpy.unique(vy)) <= set(range(10))
    tx2, ty2, _, _ = digits_arrays()
    numpy.testing.assert_array_equal(tx, tx2)
    numpy.testing.assert_array_equal(ty, ty2)


def test_digits_loader_contract(cpu_device):
    from veles_tpu.dummy import DummyWorkflow
    wf = DummyWorkflow()
    loader = DigitsLoader(wf.workflow, minibatch_size=48)
    loader.initialize(device=cpu_device)
    assert loader.class_lengths[1] == 360
    assert loader.class_lengths[2] == 1437
    assert loader.shape == (64,)


@pytest.mark.slow
def test_digits_quality_via_full_graph(cpu_device):
    """The committed QUALITY.json number stays reached: <= 2.5 %
    validation error on real digits through the full graph (measured
    1.39 % — see scripts/quality.py)."""
    import digits as digits_example
    from veles_tpu.launcher import Launcher

    launcher = Launcher()
    workflow = digits_example.build(launcher)
    launcher.initialize(device="cpu")
    launcher.run()
    best = workflow.decision.best_metric
    assert best is not None and best <= 2.5, \
        "digits validation error regressed: %s%%" % best


@pytest.mark.slow
def test_mnist_quality_via_full_graph():
    """BASELINE parity: 784-100-10 to the reference's 1.48 % table value
    (manualrst_veles_algorithms.rst:31).  Runs only where the MNIST idx
    files are cached or downloadable (no network in CI)."""
    try:
        mnist_arrays()
    except DatasetNotFound:
        pytest.skip("MNIST dataset unavailable offline")
    import mnist as mnist_example
    from veles_tpu.launcher import Launcher

    launcher = Launcher()
    workflow = mnist_example.build(launcher)
    launcher.initialize(device=os.environ.get("VELES_BACKEND", "cpu"))
    launcher.run()
    best = workflow.decision.best_metric
    # 1.48 is the table value; allow seed variance headroom
    assert best is not None and best <= 1.8, \
        "MNIST validation error %s%% (reference table: 1.48%%)" % best


@pytest.mark.slow
def test_digits_conv_classification_quality(cpu_device):
    """Conv *classification* anchor (round-3 verdict: conv quality was
    pinned only by reconstruction RMSE): digits through the conv/pool
    stack reach the committed QUALITY.json error."""
    import importlib

    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher

    module = importlib.import_module("digits_conv")
    saved = root.digits_conv.max_epochs
    root.digits_conv.max_epochs = 40  # converges ~1.7 % at epoch 36
    try:
        launcher = Launcher()
        wf = module.build(launcher)
        launcher.initialize(device=cpu_device)
        launcher.run()
        best = wf.decision.best_metric
        assert best is not None and best <= 2.5, \
            "digits_conv validation error regressed: %s%%" % best
    finally:
        root.digits_conv.max_epochs = saved


@pytest.mark.slow
def test_mnist_drop_rehearsal(tmp_path, cpu_device):
    """A canonical-shaped MNIST drop starts the parity workflow with
    ZERO code changes (round-3 verdict item 5): synthesize idx files
    with the real shapes (random pixels — quality is meaningless,
    execution is the point), point the datasets dir at them, and run
    the real examples/mnist.py workflow end to end."""
    import importlib

    from veles_tpu.config import root
    from veles_tpu.datasets import selfcheck
    from veles_tpu.launcher import Launcher

    _write_mnist_drop(tmp_path, numpy.random.RandomState(0))
    report = selfcheck(str(tmp_path))
    assert report["mnist"]["status"] == "ok"
    # synthetic files are structurally canonical but not THE files
    # (uncompressed names have no published md5 -> canonical None)
    assert all(f["canonical"] is not True
               for f in report["mnist"]["files"].values())

    saved_dir = root.common.dirs.datasets
    module = importlib.import_module("mnist")
    saved_epochs = root.mnist.max_epochs
    root.common.dirs.datasets = str(tmp_path)
    root.mnist.max_epochs = 1
    try:
        launcher = Launcher()
        wf = module.build(launcher)
        launcher.initialize(device=cpu_device)
        launcher.run()
        # random labels: anything finite proves the pipeline ran
        assert wf.decision.best_metric is not None
        assert 0.0 <= wf.decision.best_metric <= 100.0
        assert int(wf.loader.epoch_number) >= 1
    finally:
        root.common.dirs.datasets = saved_dir
        root.mnist.max_epochs = saved_epochs


@pytest.mark.slow
def test_stl10_and_mnist_ae_drop_rehearsal(tmp_path, cpu_device):
    """The dataset-gated parity configs (CIFAR-10 17.21 %, STL-10
    35.10 %, MNIST AE RMSE 0.5478) execute end to end on
    canonical-shaped synthetic drops: one fused eval + train step
    each through the real example workflows."""
    import importlib

    from veles_tpu.config import root
    from veles_tpu.loader.base import TRAIN

    rng = numpy.random.RandomState(0)
    _write_stl10_drop(tmp_path, rng)
    _write_mnist_drop(tmp_path, rng)
    _write_cifar10_drop(tmp_path, rng)

    saved_dir = root.common.dirs.datasets
    root.common.dirs.datasets = str(tmp_path)
    try:
        for module_name in ("cifar10", "stl10", "mnist_autoencoder"):
            module = importlib.import_module(module_name)
            from veles_tpu.launcher import Launcher
            launcher = Launcher()
            sw = module.build(launcher)
            sw.fuse()
            sw.initialize(device=cpu_device)
            # one eval dispatch on the first served minibatch, then
            # rehearse the TRAIN program on the same batch (walking
            # the whole 8k-image validation epoch at 96px on CPU
            # would take tens of minutes and prove nothing extra)
            sw.loader.run()
            sw.fused_trainer.run()
            sw.loader.minibatch_class = TRAIN
            sw.fused_trainer.run()
            loss = float(sw.fused_trainer.last_loss)
            assert numpy.isfinite(loss), (module_name, loss)
    finally:
        root.common.dirs.datasets = saved_dir


@pytest.mark.slow
def test_digits_quality_on_real_tpu():
    """On-chip end-to-end proof (round-3 verdict item 2): the FULL
    unit-graph product (loader -> per-unit jitted forwards/GD ->
    decision -> snapshot path) trains to the same quality on the real
    TPU as on CPU.  Subprocess because conftest pins this process to
    the virtual CPU mesh.  Skipped when no TPU is attached."""
    import json
    import subprocess
    import sys

    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "VELES_BACKEND")}
    env["XLA_FLAGS"] = ""  # no virtual-device forcing in the child
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; d = jax.devices(); "
             "print(int(bool(d) and d[0].platform == 'tpu'))"],
            env=env, capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired:
        pytest.skip("TPU probe timed out (runtime unresponsive)")
    if probe.returncode != 0 or probe.stdout.strip() != "1":
        pytest.skip("no real TPU attached")

    # run the maintained harness, not a re-implementation: the same
    # path that records QUALITY.json rows (incl. the snapshot-restore
    # proof for digits).  --fuse: one compiled program instead of the
    # per-unit walk's one compile per unit method
    out = os.path.join(tempfile.mkdtemp(prefix="quality_tpu_"),
                       "q.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "quality.py"),
         "--backend", "tpu", "--anchors", "digits", "--fuse",
         "--out", out],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.load(open(out))["results_tpu_fused"]["digits"]
    assert row.get("snapshot_restored"), row
    # same bar as the CPU anchor (measured 1.39% on both backends)
    assert row["best_error_pct"] <= 2.5, row


@pytest.mark.slow
def test_autoencoder_reconstructs_digits(cpu_device):
    """Autoencoder quality anchor (reference MNIST AE RMSE 0.5478,
    manualrst_veles_algorithms.rst:69; offline stand-in reconstructs
    the 8x8 digits): the committed QUALITY.json RMSE stays reached."""
    import importlib

    module = importlib.import_module("autoencoder")
    from veles_tpu.launcher import Launcher
    launcher = Launcher()
    workflow = module.build(launcher)
    launcher.initialize(device=cpu_device)
    launcher.run()
    best = workflow.decision.best_metric
    assert best is not None
    # measured 0.1256 on plain CPU; generous headroom for backend and
    # mesh-size numeric drift, still far under the reference MNIST 0.5478
    assert best < 0.2, best


@pytest.mark.slow
def test_lstm_sequence_classification(cpu_device):
    """LSTM over digit-row sequences (the reference shipped RNN/LSTM
    untested; this pins our recurrent training path on real data)."""
    import importlib

    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher

    module = importlib.import_module("sequence")
    saved = root.sequence.max_epochs
    root.sequence.max_epochs = 25
    try:
        launcher = Launcher()
        wf = module.build(launcher)
        launcher.initialize(device=cpu_device)
        launcher.run()
        best = wf.decision.best_metric
        assert best is not None and best < 5.0, best
    finally:
        root.sequence.max_epochs = saved


@pytest.mark.slow
@pytest.mark.transformer
def test_transformer_sequence_classification(cpu_device):
    """Transformer over digit-row sequences (examples/transformer.py):
    the pre-LN block chain + flash-attention path trained end to end
    through the unit graph into the receipted accuracy band (measured
    1.67 % best validation error at 25 epochs — the LSTM anchor's
    band)."""
    import importlib

    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher

    module = importlib.import_module("transformer")
    saved = root.transformer.max_epochs
    root.transformer.max_epochs = 25
    try:
        launcher = Launcher()
        wf = module.build(launcher)
        launcher.initialize(device=cpu_device)
        launcher.run()
        best = wf.decision.best_metric
        assert best is not None and best < 5.0, best
    finally:
        root.transformer.max_epochs = saved


@pytest.mark.slow
def test_conv_autoencoder_reconstructs_digits(cpu_device):
    """Convolutional autoencoder (reference family: conv autoencoders):
    conv encode + deconv decode on real digits, pinned well below the
    MLP autoencoder's RMSE."""
    import importlib

    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher

    module = importlib.import_module("conv_autoencoder")
    saved = root.conv_ae.max_epochs
    root.conv_ae.max_epochs = 15
    try:
        launcher = Launcher()
        wf = module.build(launcher)
        launcher.initialize(device=cpu_device)
        launcher.run()
        best = wf.decision.best_metric
        # 4x spatial bottleneck: measured 0.114 at full epochs
        assert best is not None and best < 0.2, best
    finally:
        root.conv_ae.max_epochs = saved
