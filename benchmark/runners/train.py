"""The training runner: one cell's job through the product's normal path.

``Launcher`` -> ``StandardWorkflow`` -> auto-fuse -> ``FusedTrainer``,
``Prefetcher`` attached, snapshotter wired, the dataset resident in HBM:
the set-up and the checks are copied from ``chip_smoke.train_phase``.
What this file adds is the measured window.  A ``WindowUnit`` linked
after the trainer (as ``chip_smoke.StepRecorder``) warms up by STEPS,
opens the window at the step the traffic file names, stamps every train
step's completion on the host's clock where the program itself waits,
and after ``seconds`` ends the run the way the product ends one:
``decision.complete``.

``run(ctx)`` returns a dict with ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end values) and ``layers`` (what the per-layer
readers read): unit timers and the registry as deltas over the window,
the reduced trace, the step count, the configuration and the traffic.
"""

import errno
import importlib
import os
import pickle
import shutil
import tempfile
import time

import numpy

from veles_tpu.loader.base import TRAIN
from veles_tpu.units import Unit

from benchmark import flops, reduce_trace
from benchmark.datasets import SeededDataset

#: train steps averaged at each end of the run for "the loss fell"
LOSS_STEPS = 20
TRAIN_STEP_MODULE = "jit_step"


def reference_of(config):
    """The configuration's plain reference, found by the name in its
    file: ``forward(layers, params, x)`` and ``step_cost(config,
    batch)``."""
    return importlib.import_module(
        "benchmark.references." + config["reference"]["module"])


def check(cond, fmt, *args):
    """The runner's assertion on the path it drives: survives -O."""
    if not cond:
        raise AssertionError(fmt % args if args else fmt)


def compile_counts():
    from veles_tpu.observe import xla_introspect
    xla_introspect.ensure_installed()
    return xla_introspect.compile_snapshot()


def registry_totals():
    """{name: value} of every counter, and of every histogram its
    ``.count`` and ``.sum``: plain numbers, so two of them subtract."""
    from veles_tpu.observe.metrics import Counter, Histogram, registry
    out = {}
    for name, metric in registry.items():
        if isinstance(metric, Counter):
            out[name] = metric.value
        elif isinstance(metric, Histogram):
            out[name + ".count"] = metric.count
            out[name + ".sum"] = metric.total
    return out


def unit_totals(workflow):
    """{unit name: {"runs": n, timer key: seconds}} summed by name."""
    out = {}
    for unit in workflow.units:
        if unit is workflow:
            continue
        row = out.setdefault(unit.name, {"runs": 0})
        row["runs"] += unit.run_calls
        for key, value in unit.timers.items():
            row[key] = row.get(key, 0.0) + value
    return out


def delta(after, before):
    """after - before, for flat or one-level-nested dicts of numbers."""
    out = {}
    for key, value in after.items():
        if isinstance(value, dict):
            out[key] = delta(value, before.get(key, {}))
        else:
            out[key] = value - before.get(key, 0)
    return out


def file_cap_allows(directory, nbytes):
    """Whether one file in ``directory`` may grow to ``nbytes`` (a
    ``ulimit -f`` or the file system's ceiling): a sparse probe."""
    os.makedirs(directory, exist_ok=True)
    with tempfile.TemporaryFile(dir=directory) as probe:
        try:
            os.truncate(probe.fileno(), nbytes)
        except OSError as exc:
            if exc.errno != errno.EFBIG:
                raise
            return False
    return True


class WindowUnit(Unit):
    """Runs after the fused trainer on every minibatch.  Keeps each
    train step's lazy device scalars (no host sync on the step path),
    opens the window after ``warmup_steps`` train steps, and closes it
    ``seconds`` later.  The device is waited for at the two edges of the
    window and nowhere inside an untraced one."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(WindowUnit, self).__init__(workflow, **kwargs)
        self.seconds = float(kwargs["seconds"])
        self.warmup_steps = int(kwargs["warmup_steps"])
        self.process_started = kwargs["process_started"]
        #: (directory, first step of the window to trace, whole steps)
        self.trace_plan = kwargs.get("trace_plan")
        self.losses, self.finite = [], []
        self.stamps = []
        self.eval_steps = 0
        self.opened_at_step = None
        self.open = self.close = None
        self.tracing = False
        self.trace_seconds = 0.0

    def _wait_for_device(self):
        import jax
        trainer = self.workflow.fused_trainer
        jax.block_until_ready((trainer.last_loss, trainer.n_err))

    def _edge(self):
        sw = self.workflow
        self._wait_for_device()
        return {"compiles": compile_counts(), "units": unit_totals(sw),
                "registry": registry_totals(),
                "clock": time.perf_counter()}

    def run(self):
        sw = self.workflow
        is_train = sw.loader.minibatch_class == TRAIN
        if is_train:
            trainer = sw.fused_trainer
            self.losses.append(trainer.last_loss)
            self.finite.append(trainer.last_step_finite)
        if self.close is not None:
            return
        if self.open is None:
            if is_train and len(self.losses) >= self.warmup_steps:
                self.opened_at_step = len(self.losses)
                self.open = self._edge()
                self.setup_s = self.open["clock"] - self.process_started
            return
        now = time.perf_counter()
        if is_train:
            self.stamps.append(now)
            if self.trace_plan is not None:
                self._drive_trace()
        else:
            self.eval_steps += 1
        if now - self.open["clock"] >= self.seconds:
            self.close = self._edge()
            sw.decision.complete <<= True

    def _drive_trace(self):
        import jax
        directory, first, steps = self.trace_plan
        done = len(self.stamps)
        started = time.perf_counter()
        if not self.tracing and done == first:
            jax.profiler.start_trace(directory)
            self.tracing = True
        elif self.tracing and done == first + steps + 2:
            # the device runs behind the host: wait, so that the steps
            # dispatched since start_trace are whole in the trace
            self._wait_for_device()
            jax.profiler.stop_trace()
            self.tracing = False
            self.trace_plan = None
        self.trace_seconds += time.perf_counter() - started


def build(ctx, snapshot_dir):
    """The workflow under its launcher, initialised: chip_smoke's
    ``train_phase`` set-up with the sizes taken from the files."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.models import zoo
    from veles_tpu.models.nn_workflow import StandardWorkflow

    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    model = config["model"]
    layers = getattr(zoo, model["factory"])(**model.get("arguments", {}))
    root.common.engine.precision_type = config["dtype"]
    prng.get().seed(seed)
    settings = dict(traffic["snapshot"])
    settings.pop("why", None)
    settings["dir"] = snapshot_dir
    root.common.snapshot.update(settings)
    data = config["dataset"]
    batch = int(traffic["batch"])
    launcher = Launcher()
    sw = StandardWorkflow(
        launcher, layers=layers,
        loader_factory=lambda workflow: SeededDataset(
            workflow, minibatch_size=batch,
            prng=prng.RandomGenerator("benchmark", seed=seed),
            sample_shape=config["input_shape"],
            label_kinds=data["label_kinds"],
            lengths=(0, data["validation_rows"], data["train_rows"]),
            data_seed=seed),
        decision_config=dict(traffic.get("decision", {})))
    check(sw.snapshotter is not None, "the snapshotter is not wired")
    if ctx.chips > 1:
        from veles_tpu.parallel import auto_mesh
        mesh = auto_mesh("data", ctx.devices)
        check(mesh.shape["data"] == ctx.chips, "mesh %s over %d chips",
              dict(mesh.shape), ctx.chips)
        sw.fuse(mesh=mesh)
    launcher.initialize(device=ctx.device)
    trainer = getattr(sw, "fused_trainer", None)
    check(trainer is not None, "auto-fuse did not happen: the run would "
          "take the per-unit path on the chip")
    if ctx.chips == 1:
        check(trainer._prefetcher is not None,
              "the Prefetcher is not attached on one chip")
    check(sw.loader._use_device_path(), "the dataset is not HBM-resident")
    # where the machine caps one file below a snapshot's weight, say so
    # and run with the snapshotter off
    snapshot_bytes = len(pickle.dumps(sw, protocol=pickle.HIGHEST_PROTOCOL))
    if not file_cap_allows(snapshot_dir, snapshot_bytes + (1 << 20)):
        sw.snapshotter.skip <<= True
        ctx.say("  this machine caps one file below the %.1f MB a "
                "snapshot weighs: the snapshotter is off for this run",
                snapshot_bytes / 1e6)
    original = sw.loader.original_data
    ctx.say("  workflow initialised %.1f s after start: dataset %s %s, "
            "%.2f GB on the device; snapshot %.1f MB",
            time.perf_counter() - ctx.started, original.shape,
            original.dtype, original.nbytes / 1e9, snapshot_bytes / 1e6)
    return launcher, sw


def run(ctx):
    traffic = ctx.traffic
    ctx.compiles_at_start = compile_counts()
    ctx.registry_at_start = registry_totals()
    snapshot_dir = tempfile.mkdtemp(prefix="benchmark_snapshots_")
    trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_") \
        if ctx.trace else None
    try:
        launcher, sw = build(ctx, snapshot_dir)
        window = WindowUnit(
            sw, seconds=ctx.seconds,
            warmup_steps=traffic["warmup_train_steps"],
            process_started=ctx.started,
            trace_plan=(trace_dir, traffic["trace_after_steps"],
                        traffic["trace_steps"]) if ctx.trace else None)
        window.link_from(sw.fused_trainer)
        window.initialize()
        launcher.run()
        check(bool(sw.decision.complete), "the decision never completed")
        check(window.close is not None, "the decision completed by itself "
              "before the window closed")
        check(not window.tracing, "the run ended inside the trace")
        result = judge(ctx, sw, window)
        result["layers"] = layer_context(ctx, sw, window, trace_dir)
        return result
    finally:
        shutil.rmtree(snapshot_dir, ignore_errors=True)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def judge(ctx, sw, window):
    """correct / attempted / failed and the end-to-end metrics."""
    import jax
    traffic = ctx.traffic
    batch = int(traffic["batch"])
    stride = int(traffic["interval_stride"])
    opened, closed = window.open, window.close
    seconds = closed["clock"] - opened["clock"]
    steps = len(window.stamps)
    compiles = delta(closed["compiles"], opened["compiles"])
    setup = delta(opened["compiles"], ctx.compiles_at_start)
    ctx.say("  set-up %.2f s (compile requests %d, cache hits %d, misses "
            "%d, %.1f s in the compiler); window %.3f s: %d train + %d "
            "eval steps from train step %d on", window.setup_s,
            setup["count"], setup["cache_hits"], setup["cache_misses"],
            setup["seconds"], seconds, steps, window.eval_steps,
            window.opened_at_step)
    problems = []
    if compiles["count"]:
        problems.append("%d compile request(s) inside the window"
                        % compiles["count"])

    losses = numpy.asarray(jax.device_get(window.losses), numpy.float64)
    finite = numpy.asarray(jax.device_get(window.finite), bool)
    in_window = slice(window.opened_at_step,
                      window.opened_at_step + steps)
    bad = ~(finite[in_window] & numpy.isfinite(losses[in_window]))
    failed = max(int(bad.sum()), int(sw.fused_trainer.skip_count))
    if failed:
        problems.append("%d skipped or non-finite step(s)" % failed)
    head, tail = losses[:LOSS_STEPS].mean(), losses[-LOSS_STEPS:].mean()
    ctx.say("  mean loss of the first %d train steps from initialisation "
            "%.4f, of the last %d %.4f", LOSS_STEPS, head, LOSS_STEPS,
            tail)
    if not tail < head:
        problems.append("the loss did not fall: %.4f -> %.4f"
                        % (head, tail))
    problems += against_reference(ctx, sw)

    marks = numpy.asarray([opened["clock"]] + window.stamps)[::stride]
    spans = numpy.diff(marks) * 1e3 / stride
    check(len(spans) > 0, "no step interval in a window of %d steps",
          steps)
    ctx.say("  %d step-interval samples (every %d step(s)); median "
            "%.4f ms, p95 %.4f ms, max %.4f ms", len(spans), stride,
            numpy.median(spans), numpy.percentile(spans, 95), spans.max())
    for problem in problems:
        ctx.say("  NOT CORRECT: %s", problem)
    return {
        "correct": not problems, "attempted": steps, "failed": failed,
        "metrics": {
            "train_images_per_s": steps * batch / seconds,
            # linear interpolation between closest ranks
            "train_step_ms_p95": float(numpy.percentile(spans, 95)),
            "setup_s": window.setup_s,
        }}


def against_reference(ctx, sw):
    """One validation minibatch through the program's forward, against
    the plain float32 reference on the same weights."""
    import jax

    from veles_tpu.compiler import build_forward
    batch = int(ctx.traffic["batch"])
    tolerance = ctx.config["reference"]
    trainer = sw.fused_trainer
    loader = sw.loader
    # the loader keeps its host copy in step with the device's: no
    # device op over the resident dataset, so the peak stays the run's
    for array in (loader.original_data, loader._mapped_original_labels_):
        array.map_read()
    x = numpy.array(loader.original_data.mem[:batch])
    labels = numpy.array(loader._mapped_original_labels_.mem[:batch])
    params = [{"weights": s["weights"], "bias": s["bias"]}
              for s in trainer._state]
    got = numpy.asarray(jax.jit(build_forward(trainer._plans))(params, x),
                        numpy.float32)
    host = [{k: None if v is None else numpy.asarray(v, numpy.float32)
             for k, v in p.items()} for p in jax.device_get(params)]
    want = numpy.asarray(reference_of(ctx.config).forward(
        sw.layers_config, host, numpy.asarray(x, numpy.float32)))
    # max|out - ref| / max|ref|, the bound tier-1's kernel tests use: a
    # 1000-way softmax's probabilities are small numbers
    diff = float(numpy.abs(got - want).max() / numpy.abs(want).max())
    errors = int((got.argmax(-1) != labels).sum())
    ref_errors = int((want.argmax(-1) != labels).sum())
    ctx.say("  one validation minibatch of %d: the program's output is "
            "within %.3g of the float32 reference's, relative to its "
            "largest (tolerance %g); it "
            "counts %d errors, the reference %d", batch, diff,
            tolerance["max_rel_diff"], errors, ref_errors)
    problems = []
    if not numpy.isfinite(got).all() or diff > tolerance["max_rel_diff"]:
        problems.append("output off the reference by %.3g (tolerance %g)"
                        % (diff, tolerance["max_rel_diff"]))
    return problems


def layer_context(ctx, sw, window, trace_dir):
    """What the per-layer readers read."""
    opened, closed = window.open, window.close
    steps = len(window.stamps)
    data = ctx.config["dataset"]
    context = {
        "steps": steps,
        "eval_steps": window.eval_steps,
        "seconds": closed["clock"] - opened["clock"],
        "units": delta(closed["units"], opened["units"]),
        "units_whole_run": closed["units"],
        "registry": delta(closed["registry"], opened["registry"]),
        "registry_whole_run": delta(closed["registry"],
                                    ctx.registry_at_start),
        "trainer_unit": sw.fused_trainer.name,
        "snapshotter_unit": sw.snapshotter.name,
        "benchmark_units": [window.name],
        "config": ctx.config, "traffic": ctx.traffic,
        "chips": ctx.chips, "device_kind": ctx.device_kind,
        "dataset_rows": data["validation_rows"] + data["train_rows"],
        "step_cost": reference_of(ctx.config).step_cost(
            ctx.config, int(ctx.traffic["batch"])),
        "trace": None,
    }
    cost = context["step_cost"]
    floor, bound = flops.floor_seconds(
        cost["flops"], cost["bytes"], flops.peaks(ctx.device_kind),
        ctx.config["dtype"], ctx.chips)
    ctx.say("  one step needs %.3f MFLOP an image forward and backward "
            "and moves at least %.3f MB; its floor on %d %s is %.4f ms, "
            "set by %s", cost["flops_per_image"] / 1e6, cost["bytes"] / 1e6,
            ctx.chips, ctx.device_kind, floor * 1e3, bound)
    if trace_dir is not None:
        path = reduce_trace.find_xplane(trace_dir)
        if ctx.keep_trace:
            os.makedirs(ctx.keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(
                ctx.keep_trace, ctx.cell["name"] + ".xplane.pb"))
        context["trace"] = reduce_trace.reduce(
            path, step_module=ctx.traffic.get(
                "train_step_module", TRAIN_STEP_MODULE))
        ctx.say("  the trace cost the window %.3f s in start_trace and "
                "stop_trace", window.trace_seconds)
    return context
