"""The benchmark on the CPU: the manifest and the files it names agree,
``run.py`` refuses to run without a TPU, the train runner yields every
declared metric at toy width on 1 and on 4 virtual devices, a cell
dropped in as files is found with no edit to a file that is there, and
the yardstick's own arithmetic (``flops.py``, ``reduce_trace.py``,
``references/``, ``datasets.py``) is checked against hand counts.

A CPU run says what the program counts and whether results are right;
every speed in PERF.md comes from the chip."""

import copy
import json
import os
import re
import shutil
import sys
import time
import types

import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import datasets, flops, reduce_trace  # noqa: E402
from benchmark.references import znicz_layers as reference  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.runners import train as train_runner  # noqa: E402

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
#: a refused PR's chip run of this program at this size (2026-09-27, one
#: TPU v5 lite): alexnet_train_b256, five train steps traced
TRACE = os.path.join(HERE, "data", "alexnet_train_b256.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}

MANIFEST = bench_run.load_manifest()
CELLS = [cell["name"] for cell in MANIFEST["workloads"]]
PER_LAYER = [metric["name"] for metric in MANIFEST["per_layer"]]


# -- the manifest and the files it names -------------------------------------


def test_manifest_has_the_contracts_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.isfile(os.path.join(REPO, MANIFEST["command"][1]))
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(REPO, path)), path
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
            for key in ("why", "source", "layer"):
                if key in entry and not (group in (
                        "end_to_end", "per_layer") and key == "source"):
                    assert 1 <= len(entry[key]) <= 200, (entry, key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names)), "a name appears twice"
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    assert all(c["chips"] in (1, 4) for c in MANIFEST["workloads"])


def test_metrics_are_well_formed_and_move_what_their_cells_report():
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in end_to_end
    for metric in MANIFEST["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound",
                               "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
        assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    for metric in MANIFEST["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source",
                               "layer", "moves", "workloads"}
        moved = end_to_end[metric["moves"]]
        assert set(metric.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS)), metric["name"]
    for cell in CELLS:
        reported = [m["name"] for m in bench_run.cell_metrics(
            MANIFEST, "end_to_end", cell)]
        assert "setup_s" in reported and len(reported) >= 2
        assert bench_run.cell_metrics(MANIFEST, "per_layer", cell)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_exist_and_agree(cell_name):
    cell, config, traffic = bench_run.load_cell(MANIFEST, cell_name)
    entry = bench_run.find(MANIFEST["configs"], cell["config"], "config")
    assert entry["file"].startswith(tuple(
        p + "/" for p in MANIFEST["paths"]))
    assert config["name"] == cell["config"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert traffic["name"] == cell["traffic"]
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "runners", traffic["runner"] + ".py"))
    # sizes live in the files, not in the harness
    data = config["dataset"]
    assert data["train_rows"] % traffic["batch"] == 0
    assert data["validation_rows"] > 0 and data["label_kinds"] > 1
    assert config["dtype"] in flops.ITEMSIZE
    assert config["reference"]["max_rel_diff"] > 0
    assert config["reference"]["reason"]
    module = train_runner.reference_of(config)
    assert callable(module.forward)
    cost = module.step_cost(config, traffic["batch"])
    assert cost["flops"] > 0 and cost["bytes"] > 0


@pytest.mark.parametrize("name", PER_LAYER)
def test_layer_metric_file_agrees_with_the_manifest(name):
    metric = bench_run.find(MANIFEST["per_layer"], name, "metric")
    reader = bench_run.load_reader(name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"],
        metric["source"])
    assert callable(reader.read)


def test_every_file_under_paths_is_named_from_a_names_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), REPO)
                assert allowed.match(rel), rel


def test_peaks_table_names_its_source_and_refuses_an_unknown_kind():
    peak = flops.peaks("TPU v5 lite")
    assert peak["flops_per_s"]["bfloat16"] == 197e12
    assert peak["bytes_per_s"] == 819e9 and "v5e" in peak["source"]
    with pytest.raises(KeyError, match="no entry for device kind"):
        flops.peaks("cpu")


# -- run.py without a TPU -----------------------------------------------------


def test_run_refuses_without_a_tpu(capsys):
    """Exit code 2, the platform found is named, no result line."""
    code = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert code == 2
    assert "'cpu'" in captured.err and "no result" in captured.err
    assert captured.out == ""


def test_run_names_the_cells_it_has_for_an_unknown_one():
    with pytest.raises(KeyError, match=CELLS[0]):
        bench_run.load_cell(MANIFEST, "no_such_cell")


# -- the train runner at toy width -------------------------------------------

TOY_CONFIG = {
    "name": "toy_mlp", "source": "tests", "reduced": [],
    "model": {"factory": "mnist_mlp_layers",
              "arguments": {"hidden": 16, "classes": 4}},
    "input_shape": [32], "dtype": "float32",
    "dataset": {"train_rows": 256, "validation_rows": 64,
                "label_kinds": 4},
    "reference": {"module": "znicz_layers", "max_rel_diff": 1e-4,
                  "reason": "float32 on the CPU"},
}
TOY_TRAFFIC = {
    "name": "toy_train", "runner": "train", "batch": 8,
    "warmup_train_steps": 6, "interval_stride": 2,
    "trace_after_steps": 2, "trace_steps": 2,
    "snapshot": {"compression": "", "interval": 1, "time_interval": 600,
                 "keep": 1},
    "decision": {},
}


@pytest.fixture
def _settings_put_back(monkeypatch):
    """The runner writes the engine's precision and the snapshot
    settings, as a CLI run would: put them back after."""
    saved = dict(root.common.snapshot.__dict__)
    monkeypatch.setattr(root.common.engine, "precision_type",
                        root.common.engine.precision_type)
    yield
    root.common.snapshot.__dict__.clear()
    root.common.snapshot.__dict__.update(saved)


def toy_context(chips, seed=20260928, seconds=0.4, **traffic):
    import time

    import jax
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    lines = []
    mix = dict(TOY_TRAFFIC, batch=8 * chips, **traffic)
    return types.SimpleNamespace(
        # under the name of the cell that reports every end-to-end metric
        cell={"name": "mnist_mlp_train_b100", "config": "toy_mlp",
              "traffic": "toy_train", "chips": chips},
        config=copy.deepcopy(TOY_CONFIG), traffic=mix, seed=seed,
        seconds=seconds, trace=False, keep_trace="",
        started=time.perf_counter(),
        say=lambda fmt, *args: lines.append(fmt % args if args else fmt),
        chips=chips, devices=jax.devices()[:chips],
        device_kind="TPU v5 lite", device=device, lines=lines)


@pytest.mark.parametrize("chips", [1, 4])
def test_train_runner_yields_every_declared_metric(_settings_put_back,
                                                   monkeypatch, chips):
    """The runner the chip runs, through the product's normal path: one
    device by the default entry (auto-fuse, Prefetcher), four virtual
    devices over ``auto_mesh("data")``.  The trace-read metrics are fed
    the recorded trace: a CPU has no device plane.  A toy window holds
    too few samples for a percentile: the floor is lifted here and
    tested on its own below."""
    monkeypatch.setattr(train_runner, "MIN_BEYOND_P95", 0)
    ctx = toy_context(chips)
    result = train_runner.run(ctx)
    assert result["correct"], ctx.lines
    assert result["failed"] == 0 and result["attempted"] >= 4
    for metric in MANIFEST["end_to_end"]:
        assert result["metrics"][metric["name"]] > 0, metric["name"]
    layers = result["layers"]
    assert layers["steps"] == result["attempted"]
    assert layers["trace"] is None
    untraced = bench_run.read_layer_metrics(MANIFEST, CELLS[0], layers)
    host_read = {"units_host_ms_per_step.train",
                 "trainer_ms_per_step.train",
                 "snapshot_ms_per_save.train"}
    if chips == 1:  # a mesh runs no Prefetcher: that reader finds nothing
        host_read.add("pipeline_wait_us_per_step.train")
    assert set(untraced) == host_read
    layers["trace"] = reduce_trace.reduce(TRACE)
    layers["dataset_rows"] = 12288
    traced = bench_run.read_layer_metrics(MANIFEST, CELLS[0], layers)
    assert set(traced) == {m["name"] for m in bench_run.cell_metrics(
        MANIFEST, "per_layer", CELLS[0])} - (
            set() if chips == 1 else {"pipeline_wait_us_per_step.train"})
    assert all(numpy.isfinite(v) and v >= 0 for v in traced.values())
    assert traced["step_peak_pct.train"] <= 100
    # the one save of the run fell into set-up, whatever the seed
    assert layers["registry_whole_run"]["snapshot.exports"] == 1
    assert layers["registry"].get("snapshot.exports", 0) == 0
    line = bench_run.result_line(MANIFEST, ctx, result, ctx.devices)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert set(line["compared"]) == {
        "compiles_in_window", "failed_steps", "loss_last_below_first",
        "output_rel_diff"}
    assert all(len(pair) == 2 for pair in line["compared"].values())
    assert set(line["metrics"]) == {m["name"]
                                    for m in MANIFEST["end_to_end"]}
    assert line["device"]["count"] == chips
    json.dumps(line)


def test_a_compile_inside_the_window_is_not_correct(_settings_put_back,
                                                    monkeypatch):
    """Nothing may compile inside the measured window: a run in which
    something did says so and is not correct."""
    import jax
    plain_run = train_runner.WindowUnit.run

    def run_and_compile_once(self):
        plain_run(self)
        if self.open is not None and not getattr(self, "compiled", False):
            self.compiled = True
            jax.jit(lambda x: x * 3 + 1)(numpy.float32(len(self.stamps)))

    monkeypatch.setattr(train_runner.WindowUnit, "run",
                        run_and_compile_once)
    ctx = toy_context(1, seconds=0.2)
    result = train_runner.run(ctx)
    assert not result["correct"]
    assert any("1 compile request(s) inside the window" in line
               for line in ctx.lines), ctx.lines


def test_the_window_keeps_few_device_scalars_alive(_settings_put_back,
                                                   monkeypatch):
    """Thousands of live device scalars slow the program's step (PERF.md
    section 6, PR 28): the window checks EVERY step's loss and flag, but
    holds at most a chunk of them, whatever the number of steps -- one
    count a chunk, compiled before the window opens -- and keeps the
    loss of the first and of the last ``LOSS_STEPS`` train steps."""
    seen = {"most": 0}
    plain_judge = train_runner.judge
    plain_watch = train_runner.WindowUnit._watch_step

    def judge(ctx, sw, window):
        seen["window"], seen["trainer"] = window, sw.fused_trainer
        return plain_judge(ctx, sw, window)

    def watch_step(self, trainer):
        plain_watch(self, trainer)
        seen["most"] = max(seen["most"], len(self.pending[0]))

    monkeypatch.setattr(train_runner, "judge", judge)
    monkeypatch.setattr(train_runner.WindowUnit, "_watch_step", watch_step)
    monkeypatch.setattr(train_runner, "CHECK_CHUNK", 8)
    ctx = toy_context(1, seconds=0.3)
    result = train_runner.run(ctx)
    assert result["correct"], ctx.lines  # so: no compile in the window
    window = seen["window"]
    kept = train_runner.LOSS_STEPS
    assert window.train_steps - window.opened_at_step >= \
        result["attempted"] > 2 * kept
    assert len(window.first_losses) == len(window.last_losses) == kept
    assert not hasattr(window, "losses") and not hasattr(window, "finite")
    # every step of the window is in a chunk's count or still pending
    assert seen["most"] == 7
    assert 8 * len(window.failed_in_chunks) + len(window.pending[0]) == \
        result["attempted"]
    assert result["failed"] == int(seen["trainer"].skip_count) == 0
    assert result["compared"]["failed_steps"] == [0, 0]
    head, tail = result["compared"]["loss_last_below_first"][::-1]
    assert head > tail > 0
    assert any("checking each step's loss and flag took the host" in line
               for line in ctx.lines), ctx.lines


@pytest.mark.parametrize("fault, planted_at", [
    ("nan_loss_flag_true", 2 * train_runner.LOSS_STEPS + 5),
    ("flag_false_loss_finite", 2 * train_runner.LOSS_STEPS + 5),
    ("nan_loss_flag_true", -1)])  # the window's last step: in no chunk
def test_one_bad_step_in_mid_window_is_not_correct(
        _settings_put_back, monkeypatch, fault, planted_at):
    """The runner reads every step of the window itself: one step in
    the middle whose loss is NaN while its ``finite`` flag says true --
    or whose flag is false while the program's counter did not count --
    is a failed step and a run that is not correct, though the 40
    losses kept for "the loss fell" and the program's ``skip_count``
    show nothing."""
    plain_run = train_runner.WindowUnit.run
    monkeypatch.setattr(train_runner, "CHECK_CHUNK", 16)

    def run_with_one_bad_step(self):
        trainer = self.workflow.fused_trainer
        if self.workflow.loader.minibatch_class == train_runner.TRAIN:
            if not hasattr(self, "bad"):
                # made in set-up from the step's own scalars, so that
                # nothing compiles for it inside the window
                self.bad = {
                    "nan_loss_flag_true": (
                        "last_loss",
                        trainer.last_loss * numpy.float32("nan")),
                    "flag_false_loss_finite": (
                        "last_step_finite", ~trainer.last_step_finite),
                }[fault]
            due = len(self.stamps) == planted_at if planted_at >= 0 else (
                self.open is not None and time.perf_counter()
                - self.open["clock"] >= self.seconds)
            if due and self.bad is not None:
                setattr(trainer, *self.bad)
                self.bad = None
        plain_run(self)

    monkeypatch.setattr(train_runner.WindowUnit, "run",
                        run_with_one_bad_step)
    ctx = toy_context(1, seconds=0.4)
    result = train_runner.run(ctx)
    assert result["attempted"] > planted_at + train_runner.LOSS_STEPS
    assert not result["correct"] and result["failed"] == 1
    assert result["compared"]["failed_steps"] == [1, 0]
    if planted_at >= 0:
        assert all(numpy.isfinite(
            result["compared"]["loss_last_below_first"]))
    assert "  NOT CORRECT: 1 skipped or non-finite step(s)" in ctx.lines
    assert not any("compile request(s) inside" in line
                   for line in ctx.lines), ctx.lines


def test_a_step_the_program_skipped_is_a_failed_step(_settings_put_back,
                                                     monkeypatch):
    """``failed`` is also the program's own count of non-finite steps,
    where that is more than the runner read itself: one more on the
    counter is one failed step and a run that is not correct."""
    plain_judge = train_runner.judge

    def judge(ctx, sw, window):
        sw.fused_trainer.skip_count = sw.fused_trainer.skip_count + 1
        return plain_judge(ctx, sw, window)

    monkeypatch.setattr(train_runner, "judge", judge)
    ctx = toy_context(1, seconds=0.1)
    result = train_runner.run(ctx)
    assert not result["correct"] and result["failed"] == 1
    assert any("1 skipped or non-finite step(s)" in line
               for line in ctx.lines), ctx.lines


def test_same_seed_same_inputs_and_weights(_settings_put_back):
    """Data and initial weights are functions of --seed."""
    def first_losses(seed):
        ctx = toy_context(1, seed=seed, seconds=0.05)
        train_runner.run(ctx)
        line = next(l for l in ctx.lines if "mean loss of the first" in l)
        return line
    big = (1 << 31) + 12345  # the driver's seeds are large
    assert first_losses(big).split("of the last")[0] == \
        first_losses(big).split("of the last")[0]
    assert first_losses(big).split("of the last")[0] != \
        first_losses(7).split("of the last")[0]


# -- the step interval's percentile --------------------------------------------

#: the per-step values of 401 samples; by linear interpolation between
#: closest ranks the 95th percentile is rank 380 of 400: 2.95 exactly
TAIL_VALUES = numpy.linspace(2.0, 3.0, 401)


def seeded_stamps(stride, values=TAIL_VALUES, seed=20261001):
    """(opening edge, stamps): every ``stride``-th completion lies a
    sample's ``stride * value`` ms after the last, the samples in a
    seeded order, the steps inside a sample at seeded uneven places --
    so only the stride the stamps were built for reads the values."""
    rng = numpy.random.RandomState(seed)
    samples = rng.permutation(values) * stride / 1e3
    inside = rng.uniform(0.2, 1.8, (len(samples), stride))
    steps = inside / inside.sum(1, keepdims=True) * samples[:, None]
    opened = 1234.5
    return opened, (opened + numpy.cumsum(steps.ravel())).tolist()


@pytest.mark.parametrize("stride", [1, 5, 20, 50, 100])
def test_interval_p95_reads_the_known_tail_per_step(stride):
    opened, stamps = seeded_stamps(stride)
    spans = train_runner.step_intervals(opened, stamps, stride)
    p95, samples, beyond = train_runner.interval_p95(spans)
    assert samples == 401 and beyond == 20
    assert p95 == pytest.approx(2.95, rel=1e-9)
    assert numpy.median(spans) == pytest.approx(2.5, rel=1e-9)
    # a window that ends inside a sample drops the part, not the sample
    spans = train_runner.step_intervals(opened, stamps[:-1], stride)
    assert len(spans) == (401 if stride == 1 else 400) - (stride == 1)
    if stride > 1:  # another stride reads other samples, not these values
        other = train_runner.interval_p95(
            train_runner.step_intervals(opened, stamps, 1))[0]
        assert abs(other - 2.95) > 0.05


@pytest.mark.parametrize("stride", [1, 5, 20, 50, 100])
def test_a_window_with_under_ten_samples_beyond_gives_no_p95(stride):
    """180 samples leave 9 beyond the percentile: the runner leaves the
    metric out and says why (and ``run.py`` gives a cell that reports
    it no line: below); 201 leave 10, and the metric is there."""
    def metrics_of(samples):
        opened, stamps = seeded_stamps(
            stride, numpy.linspace(2.0, 3.0, samples))
        lines = []
        return lines, train_runner.window_metrics(
            lambda fmt, *args: lines.append(fmt % args), opened,
            stamps[-1], stamps, 100, stride)

    lines, short = metrics_of(180)
    assert set(short) == {"train_images_per_s"}
    assert short["train_images_per_s"] == pytest.approx(
        100 / 2.5e-3, rel=1e-9)
    assert any("no train_step_ms_p95: 9 sample(s) beyond" in line
               for line in lines), lines
    lines, enough = metrics_of(201)
    assert enough["train_step_ms_p95"] == pytest.approx(2.95, rel=1e-9)
    assert any("201 step-interval samples (every %d step(s))" % stride
               in line and "(10 beyond it)" in line for line in lines)


@pytest.mark.parametrize("cell_name, metrics, missing", [
    (CELLS[0], {"setup_s": 1.0}, "train_images_per_s"),
    ("mnist_mlp_train_b100",
     {"setup_s": 1.0, "train_images_per_s": 2.0}, "train_step_ms_p95"),
])
def test_a_run_that_lacks_an_end_to_end_metric_gets_no_line(
        cell_name, metrics, missing):
    import jax
    ctx = types.SimpleNamespace(cell={"name": cell_name}, trace=False)
    result = {"correct": True, "attempted": 9, "failed": 0,
              "metrics": metrics, "compared": {},
              "layers": {"trace": None}}
    with pytest.raises(RuntimeError, match="gave no " + missing):
        bench_run.result_line(MANIFEST, ctx, result, jax.devices()[:1])


#: ms a train step, where a cell reports ``train_step_ms_p95`` (my chip
#: runs, PR 28: PERF.md section 5)
RECORDED_STEP_MS = {"mnist_mlp_train_b100": 2.6}


@pytest.mark.parametrize("cell_name", bench_run.find(
    MANIFEST["end_to_end"], "train_step_ms_p95", "metric")["workloads"])
def test_the_traffic_files_stride_leaves_ten_samples_beyond(cell_name):
    """At the cell's recorded step time a window of ``run_seconds``
    holds, at the traffic file's stride, at least twice the ten samples
    beyond the 95th percentile that a run needs."""
    _, _, traffic = bench_run.load_cell(MANIFEST, cell_name)
    steps = MANIFEST["run_seconds"] * 1e3 / RECORDED_STEP_MS[cell_name]
    samples = int(steps // traffic["interval_stride"])
    beyond = samples - 1 - int(0.95 * (samples - 1))
    assert beyond >= 2 * train_runner.MIN_BEYOND_P95, (samples, beyond)


def test_cpu_anatomy_places_a_stall_in_which_the_process_stood_still():
    marks, lines = [], []
    clock = steps = cpu = 0.0
    for i in range(11):  # ten stretches of 400 steps; the sixth lasts 3 s
        marks.append((clock, int(steps), cpu))
        clock += 3.0 if i == 5 else 1.0
        cpu += 2.5  # and gets no more CPU than the others: 2 s frozen
        steps += 400
    train_runner.cpu_anatomy(
        lambda fmt, *args: lines.append(fmt % args), marks, 100)
    assert lines == [
        "  the process used 25.00 s of CPU over the window's 12.00 s",
        "  slow stretch: train steps 2000-2400 in 3.000 s, 13333 images/s "
        "(median stretch 40000), 2.50 s of CPU"]


# -- a cell dropped in as files ----------------------------------------------


def test_a_new_cell_config_and_layer_metric_are_found_as_files(tmp_path):
    """What a later PR does: new files, new manifest entries, and no
    edit to a file that is there."""
    repo = str(tmp_path / "repo")
    os.makedirs(repo)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(repo, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for folder, _, files in os.walk(repo):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as fin:
                before[path] = fin.read()

    def write(rel, text):
        with open(os.path.join(repo, rel), "w") as fout:
            fout.write(text)

    write("benchmark/configs/toy_mlp.json", json.dumps(TOY_CONFIG))
    write("benchmark/traffic/toy_train.json", json.dumps(TOY_TRAFFIC))
    write("benchmark/layer_metrics/eval_steps.train.py",
          'LAYER = "Entry"\nUNIT = "steps"\n'
          'MOVES = "train_images_per_s"\nSOURCE = "program_counter"\n\n\n'
          'def read(context):\n    return context["eval_steps"]\n')
    manifest = copy.deepcopy(MANIFEST)
    manifest["configs"].append({
        "name": "toy_mlp", "source": "tests", "reduced": [],
        "file": "benchmark/configs/toy_mlp.json", "why": "a test"})
    manifest["workloads"].append({
        "name": "toy_mlp_toy_train", "config": "toy_mlp",
        "traffic": "toy_train", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "eval_steps.train", "unit": "steps", "better": "lower",
        "source": "program_counter", "layer": "Entry",
        "moves": "train_images_per_s",
        "workloads": ["toy_mlp_toy_train"]})
    write("BENCHMARK.json", json.dumps(manifest))

    manifest = bench_run.load_manifest(repo)
    cell, config, traffic = bench_run.load_cell(
        manifest, "toy_mlp_toy_train", repo)
    assert (cell["chips"], config["name"], traffic["runner"]) == (
        1, "toy_mlp", "train")
    context = {"eval_steps": 3, "steps": 10, "trace": None,
               "units": {}, "units_whole_run": {}, "registry": {},
               "registry_whole_run": {}, "trainer_unit": "FusedTrainer",
               "snapshotter_unit": "Snapshotter", "benchmark_units": []}
    assert bench_run.read_layer_metrics(
        manifest, "toy_mlp_toy_train", context, repo) == {
            "eval_steps.train": 3.0}
    # the old cells do not report the new metric, and nothing changed
    assert "eval_steps.train" not in [
        m["name"] for m in bench_run.cell_metrics(
            manifest, "per_layer", CELLS[0])]
    for path, content in before.items():
        with open(path, "rb") as fin:
            assert fin.read() == content, path


# -- flops.py against hand counts --------------------------------------------


def test_flops_against_hand_counts():
    from veles_tpu.models import zoo
    # multiply-adds per image forward, by hand from the published shapes
    alexnet = (55 * 55 * 11 * 11 * 3 * 96 + 27 * 27 * 5 * 5 * 96 * 256 +
               13 * 13 * 3 * 3 * 256 * 384 + 13 * 13 * 3 * 3 * 384 * 384 +
               13 * 13 * 3 * 3 * 384 * 256 + 6 * 6 * 256 * 4096 +
               4096 * 4096 + 4096 * 1000)
    first = 55 * 55 * 11 * 11 * 3 * 96
    got = flops.train_flops_per_image(zoo.alexnet_layers(), (227, 227, 3))
    assert got == 6 * alexnet - 2 * first
    assert abs(got / 1e9 - 6.6) < 0.01  # the issue's 6.6 GFLOP an image
    # the MLP: 79,400 multiply-adds; 6 x 79,400 = 0.476 MFLOP counts the
    # first layer's input gradient, which the step does not compute
    got = flops.train_flops_per_image(zoo.mnist_mlp_layers(), (784,))
    assert got == 6 * 79400 - 2 * 78400 == 319600
    nbytes = flops.train_bytes_per_step(
        zoo.mnist_mlp_layers(), (784,), 100, "float32")
    assert nbytes == 4 * (100 * 784 + 2 * 100 * 110 + 4 * 79510)
    peak = flops.peaks("TPU v5 lite")
    seconds, bound = flops.floor_seconds(
        256 * 6600706176, 1.4e9, peak, "bfloat16")
    assert bound == "compute" and abs(seconds * 1e3 - 8.578) < 0.001
    seconds, bound = flops.floor_seconds(100 * 319600, nbytes, peak,
                                         "float32")
    assert bound == "bytes" and seconds < 3e-6
    with pytest.raises(ValueError, match="cannot count"):
        flops.layer_costs([{"type": "transformer"}], (8, 8))


# -- reduce_trace.py on a small recorded trace --------------------------------


def test_interval_arithmetic():
    assert reduce_trace.union_seconds(
        [(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4
    assert reduce_trace.clip([("a", 0.0, 2.0), ("b", 3.0, 1.0)], 1, 3.5) \
        == [("a", 1, 1.0), ("b", 3.0, 0.5)]
    assert reduce_trace.idle_gaps(
        [("a", 1.0, 1.0), ("b", 1.5, 1.0), ("c", 4.0, 0.5)], 0, 5) == [
            (0, 1.0), (2.5, 4.0), (4.5, 5)]
    text = ("%copy.2 = bf16[12288,227,227,3]{0,3,2,1:T(4,128)(2,1)} "
            "copy(bf16[12288,227,227,3]{0,2,3,1:T(8,128)(2,1)} %dataset.1)")
    assert reduce_trace.short_name(text) == "copy.2 bf16[12288,227,227,3]"
    assert reduce_trace.leading_dims(text) == {12288}
    assert reduce_trace.leading_dims(
        "%x = (f32[9,512]{1,0}, u32[]{:S(2)}) fusion(s32[100]{0} %y)") == {
            9, 100}
    host = [("$a.py:1 outer", 0.0, 10.0), ("$b.py:2 inner", 2.0, 1.0)]
    assert reduce_trace.host_activity(host, 2.5) == "b.py:2 inner"
    assert reduce_trace.host_activity(host, 5.0) == "a.py:1 outer"
    assert reduce_trace.host_activity(host, 11.0) == "no traced host call"


# -- reference.py and datasets.py ---------------------------------------------

TOY_CNN = [
    {"type": "conv_str", "n_kernels": 8, "kx": 3, "ky": 3, "padding": 1,
     "sliding": (2, 2), "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
    {"type": "all2all_str", "output_sample_shape": 32,
     "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "dropout", "dropout_ratio": 0.5},
    {"type": "all2all_tanh", "output_sample_shape": 16,
     "learning_rate": 0.01, "gradient_moment": 0.9},
    {"type": "softmax", "output_sample_shape": 10,
     "learning_rate": 0.01, "gradient_moment": 0.9},
]


def test_reference_agrees_with_the_programs_forward():
    """Every layer type the two configurations use, at toy width: the
    plain float32 reference (nothing imported from veles_tpu.models)
    against ``compiler.build_forward`` on the same seeded weights.  The
    input is 14 wide so the pooling window hangs over the edge."""
    import jax

    from veles_tpu.compiler import build_forward
    from veles_tpu.models.zoo import build_plans_and_state
    plans, state, _ = build_plans_and_state(TOY_CNN, (14, 14, 3), seed=5)
    rng = numpy.random.RandomState(11)
    for entry in state:
        if entry["bias"] is not None:
            entry["bias"] = rng.randn(*entry["bias"].shape).astype(
                numpy.float32) * 0.1
    params = [{"weights": s["weights"], "bias": s["bias"]} for s in state]
    x = rng.randn(6, 14, 14, 3).astype(numpy.float32)
    with jax.default_matmul_precision("highest"):
        got = numpy.asarray(jax.jit(build_forward(plans))(params, x))
    want = numpy.asarray(reference.forward(TOY_CNN, params, x))
    assert got.shape == want.shape == (6, 10)
    numpy.testing.assert_allclose(want.sum(axis=1), 1.0, atol=1e-5)
    # float32 on both sides: rounding order is all that differs
    assert numpy.abs(got - want).max() < 1e-5
    with open(reference.__file__) as source:
        assert "veles_tpu" not in source.read().split('"""', 2)[2]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dataset_is_a_function_of_the_seed(dtype):
    import ml_dtypes
    kind = numpy.dtype(getattr(ml_dtypes, dtype, dtype))

    def make(seed, rows=96):
        out = numpy.zeros((rows, 5, 4, 3), kind)
        return out, datasets.fill_rows(out, 8, seed)

    big = (1 << 31) + 977
    a, la = make(big)
    b, lb = make(big)
    c, lc = make(big + 1)
    assert a.tobytes() == b.tobytes() and (la == lb).all()
    assert a.tobytes() != c.tobytes() and (la != lc).any()
    # every class present, equally often, for every seed
    assert (numpy.bincount(la, minlength=8) == 12).all()
    assert (numpy.bincount(lc, minlength=8) == 12).all()
    values = a.astype(numpy.float32)
    assert -0.5 <= values.min() and values.max() <= 0.5
    # rows of one class share a pattern, rows of two classes do not
    flat = values.reshape(len(a), -1)
    same = flat[la == la[0]]
    other = flat[la != la[0]]
    assert numpy.abs(same - same[0]).max() <= 0.26
    assert numpy.abs(other - same[0]).max() > 0.3


def test_recorded_trace_reduces_to_values_worked_out_by_hand():
    """The modules line of the recorded trace shows the train-step
    program (``jit_step``) starting at 0.048994266, 0.170508337,
    0.292055707, 0.413587590 and 0.535135077 s.  The first is dropped
    (the trace may have begun inside it): three whole steps from
    0.170508337 to 0.535135077 s."""
    trace = reduce_trace.reduce(TRACE)
    assert trace["steps"] == 3 and trace["chips"] == 1
    assert trace["window_s"] == pytest.approx(0.535135077 - 0.170508337,
                                              abs=1e-12)
    assert "jit_gather_minibatch" in trace["modules"]
    # %copy.2, the re-layout of the whole resident dataset, ran four
    # times (0.118459170, 0.240000510, 0.361534237, 0.483074070 s); the
    # first lies before the window
    copies = [seconds for name, seconds in trace["op_seconds"].items()
              if name.startswith("%copy.2 = bf16[12288,227,227,3]")]
    assert copies == [pytest.approx(
        0.013881713 + 0.013880340 + 0.013884406, abs=1e-12)]
    # the ops line never overlaps itself, so its union is its sum; the
    # 1,993 op events inside the window add up to 0.364434159 s
    raw = reduce_trace.load(TRACE)["devices"]["/device:TPU:0"]
    inside = reduce_trace.clip(raw["XLA Ops"], 0.170508337, 0.535135077)
    assert len(inside) == 1993
    assert sum(d for _, _, d in inside) == pytest.approx(0.364434159,
                                                         abs=1e-9)
    assert trace["busy_s"] == pytest.approx(0.364434159, abs=1e-9)
    assert sum(trace["gap_seconds"].values()) == pytest.approx(
        0.364626740 - 0.364434159, abs=1e-9)
    # through the readers: 121.478 ms a step on the device, 0.0528 %
    # idle, 51.06 ms of it over the 12,288-row dataset, 24.98 ms in
    # Mosaic kernels, 8.578 ms / 121.478 ms = 7.06 % of the chip's peak
    context = {"trace": trace, "dataset_rows": 12288, "chips": 1,
               "device_kind": "TPU v5 lite",
               "config": {"dtype": "bfloat16"},
               "step_cost": {"flops": 256 * 6600706176, "bytes": 1.4e9}}
    want = {"device_ms_per_step.train": 121.478053,
            "device_idle_pct.train": 0.052816,
            "data_device_ms_per_step.train": 51.062551,
            "mosaic_ms_per_step.train": 24.978409,
            "step_peak_pct.train": 7.0612}
    for name, value in want.items():
        assert bench_run.load_reader(name).read(context) == pytest.approx(
            value, rel=1e-4), name
    shown = reduce_trace.breakdown(trace)
    assert shown["device_ops"][0] == [
        "copy.2 bf16[12288,227,227,3]", pytest.approx(0.041646459)]
    assert len(shown["device_ops"]) == 10
    assert all(len(name) <= 120 for name, _ in shown["device_ops"])
    # what a trace without two whole executions gives: nothing
    assert reduce_trace.reduce(TRACE, step_module="jit_no_such") is None
