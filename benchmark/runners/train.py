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

import collections
import errno
import importlib
import math
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
#: samples a run needs beyond its 95th percentile (choosing-metrics, 1)
MIN_BEYOND_P95 = 10
#: train steps of the window whose loss and ``finite`` flag the runner
#: holds before one program on the device counts the failed among them
CHECK_CHUNK = 64


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


def count_failed(losses, flags):
    """How many of these steps have a loss that is not finite or a
    ``finite`` flag that is false: one device scalar."""
    import jax.numpy as jnp
    fine = jnp.stack(flags) & jnp.isfinite(jnp.stack(losses))
    return jnp.sum(~fine)


class WindowUnit(Unit):
    """Runs after the fused trainer on every minibatch.  Opens the
    window after ``warmup_steps`` train steps and closes it ``seconds``
    later; the device is waited for at the two edges and nowhere inside
    an untraced window.  Every train step of the window is checked, as
    before PR 28, but its loss and ``finite`` flag are not kept until
    the end: every ``CHECK_CHUNK`` steps one jitted call counts the
    failed among them on the device, and the runner keeps that count.
    Every step's pair kept over a 30 s window (11,500 pairs) slowed the
    MNIST cell's step by 3 % by the window's end, and a copy of each to
    the host cost 235 us a step (PERF.md section 6, PR 28).  The loss of
    the first and of the last ``LOSS_STEPS`` train steps is kept for
    "the loss fell"."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(WindowUnit, self).__init__(workflow, **kwargs)
        self.seconds = float(kwargs["seconds"])
        self.warmup_steps = int(kwargs["warmup_steps"])
        self.process_started = kwargs["process_started"]
        #: (directory, first step of the window to trace, whole steps)
        self.trace_plan = kwargs.get("trace_plan")
        self.train_steps = 0
        self.first_losses = []
        self.last_losses = collections.deque(maxlen=LOSS_STEPS)
        self.stamps = []
        import jax
        self._count_failed_ = jax.jit(count_failed)  # not pickled
        #: (losses, flags) of the window's newest steps, not yet counted
        self.pending = ([], [])
        #: one device scalar a chunk: the failed steps in it
        self.failed_in_chunks = []
        self.check_seconds = 0.0
        #: (clock, train steps stamped, this process's CPU seconds),
        #: about once a second
        self.cpu_marks = []
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
            self.train_steps += 1
            if len(self.first_losses) < LOSS_STEPS:
                self.first_losses.append(trainer.last_loss)
            self.last_losses.append(trainer.last_loss)
        if self.close is not None:
            return
        if self.open is None:
            if is_train and self.train_steps >= self.warmup_steps:
                self.opened_at_step = self.train_steps
                # compiles the chunk's program (or finds it in the
                # cache) inside set-up
                self._count_failed_(
                    (trainer.last_loss,) * CHECK_CHUNK,
                    (trainer.last_step_finite,) * CHECK_CHUNK)
                self.open = self._edge()
                self.setup_s = self.open["clock"] - self.process_started
                self.cpu_marks.append((self.open["clock"], 0,
                                       time.process_time()))
            return
        now = time.perf_counter()
        if is_train:
            self.stamps.append(now)
            self._watch_step(trainer)
            if self.trace_plan is not None:
                self._drive_trace()
        else:
            self.eval_steps += 1
        closing = now - self.open["clock"] >= self.seconds
        if closing or now - self.cpu_marks[-1][0] >= 1.0:
            self.cpu_marks.append((now, len(self.stamps),
                                   time.process_time()))
        if closing:
            self.close = self._edge()
            sw.decision.complete <<= True

    def _watch_step(self, trainer):
        """Holds this step's loss and flag; every ``CHECK_CHUNK``-th
        step hands the chunk to the device to count its failed steps."""
        started = time.perf_counter()
        losses, flags = self.pending
        losses.append(trainer.last_loss)
        flags.append(trainer.last_step_finite)
        if len(losses) == CHECK_CHUNK:
            self.failed_in_chunks.append(
                self._count_failed_(tuple(losses), tuple(flags)))
            self.pending = ([], [])
        self.check_seconds += time.perf_counter() - started

    def failed_steps(self):
        """The failed steps of the window: the chunks' counts, and the
        steps since the last chunk read one by one.  Called after the
        window has closed."""
        import jax
        losses, flags = jax.device_get(self.pending)
        rest = sum(not (bool(flag) and math.isfinite(float(loss)))
                   for loss, flag in zip(losses, flags))
        return rest + sum(
            int(count) for count in jax.device_get(self.failed_in_chunks))

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
    #: every number compared, beside its limit: {name: [number, limit]}
    compared = {"compiles_in_window": [compiles["count"], 0]}
    if compiles["count"]:
        problems.append("%d compile request(s) inside the window"
                        % compiles["count"])

    first, last = (
        numpy.asarray(jax.device_get(list(losses)), numpy.float64)
        for losses in (window.first_losses, window.last_losses))
    # every step of the window, read by the runner itself, or the
    # program's own count over the whole run if that is more
    failed = max(window.failed_steps(), int(sw.fused_trainer.skip_count))
    compared["failed_steps"] = [failed, 0]
    if failed:
        problems.append("%d skipped or non-finite step(s)" % failed)
    head, tail = first.mean(), last.mean()
    ctx.say("  mean loss of the first %d train steps from initialisation "
            "%.4f, of the last %d %.4f", LOSS_STEPS, head, LOSS_STEPS,
            tail)
    compared["loss_last_below_first"] = [float(tail), float(head)]
    if not tail < head:
        problems.append("the loss did not fall: %.4f -> %.4f"
                        % (head, tail))
    diff, off = against_reference(ctx, sw)
    compared["output_rel_diff"] = [
        diff, ctx.config["reference"]["max_rel_diff"]]
    problems += off

    ctx.say("  the window: %d train + %d eval steps, %d save(s), %.3f s",
            steps, window.eval_steps,
            delta(closed["registry"], opened["registry"]).get(
                "snapshot.exports", 0), seconds)
    ctx.say("  checking each step's loss and flag took the host %.1f us a "
            "step (%d chunk(s) of %d)", window.check_seconds * 1e6 / max(
                steps, 1), len(window.failed_in_chunks), CHECK_CHUNK)
    metrics = window_metrics(ctx.say, opened["clock"], closed["clock"],
                             window.stamps, batch, stride)
    metrics["setup_s"] = window.setup_s
    cpu_anatomy(ctx.say, window.cpu_marks, batch)
    for problem in problems:
        ctx.say("  NOT CORRECT: %s", problem)
    return {"correct": not problems, "attempted": steps, "failed": failed,
            "metrics": metrics, "compared": compared}


def window_metrics(say, opened_clock, closed_clock, stamps, batch, stride):
    """The end-to-end metrics the stamps give, and the lines that place
    an off run.  ``train_step_ms_p95`` is left out where fewer than
    ``MIN_BEYOND_P95`` samples lie beyond the percentile: a cell that
    reports it then gets no result line."""
    steps = len(stamps)
    rates, longest = window_anatomy(opened_clock, stamps, batch)
    say("  images/s in each whole second: %s",
        " ".join("%d" % rate for rate in rates))
    say("  the five longest step intervals (ms @ train step of the "
        "window): %s", ", ".join("%.3f @ %d" % pair for pair in longest))
    spans = step_intervals(opened_clock, stamps, stride)
    check(len(spans) > 0, "no step interval in a window of %d steps",
          steps)
    p95, _, beyond = interval_p95(spans)
    say("  %d step-interval samples (every %d step(s)); median %.4f ms, "
        "p95 %.4f ms (%d beyond it), max %.4f ms", len(spans), stride,
        numpy.median(spans), p95, beyond, spans.max())
    metrics = {"train_images_per_s":
               steps * batch / (closed_clock - opened_clock)}
    if beyond >= MIN_BEYOND_P95:
        metrics["train_step_ms_p95"] = p95
    else:
        say("  no train_step_ms_p95: %d sample(s) beyond the percentile, "
            "and a percentile wants %d", beyond, MIN_BEYOND_P95)
    return metrics


def step_intervals(opened_clock, stamps, stride):
    """ms a step between every ``stride``-th train-step completion,
    from the window's opening edge on: many samples, not long ones
    (``time.perf_counter`` resolves far below a step)."""
    marks = numpy.asarray([opened_clock] + list(stamps))[::stride]
    return numpy.diff(marks) * 1e3 / stride


def interval_p95(spans):
    """(95th percentile by linear interpolation between closest ranks,
    samples, samples beyond the percentile)."""
    if not len(spans):
        return float("nan"), 0, 0
    p95 = float(numpy.percentile(spans, 95))
    return p95, len(spans), int((spans > p95).sum())


def window_anatomy(opened_clock, stamps, batch):
    """What places an off run: (train images completed in each whole
    second of the window, the five longest single-step intervals with
    the window's train step each ended on)."""
    since = numpy.asarray(stamps) - opened_clock
    whole = int(since[-1]) if len(since) else 0
    rates = numpy.bincount(since.astype(int), minlength=whole)[:whole]
    single = step_intervals(opened_clock, stamps, 1)
    longest = numpy.argsort(single)[::-1][:5]
    return ((rates * batch).tolist(),
            [(float(single[i]), int(i) + 1) for i in longest])


def cpu_anatomy(say, marks, batch):
    """Whose a stall is, as far as a process can see: the CPU time of
    all its threads (the TPU runtime's spin, so it is more than one
    core) over the window, and over each stretch between two marks
    (about a second; a stall makes it longer) that ran more than 2 %
    under the median stretch's rate, the five slowest of them.  A
    stretch in which every thread stood still lacks the stall's length
    in CPU seconds on each core: the process was not running, whatever
    its Python was about to do."""
    if len(marks) < 2:
        return
    say("  the process used %.2f s of CPU over the window's %.2f s",
        marks[-1][2] - marks[0][2], marks[-1][0] - marks[0][0])
    pairs = list(zip(marks, marks[1:]))
    rates = [(b[1] - a[1]) * batch / (b[0] - a[0]) for a, b in pairs]
    median = numpy.median(rates)
    slow = sorted((i for i, rate in enumerate(rates)
                   if rate < 0.98 * median), key=rates.__getitem__)
    for i in slow[:5]:
        a, b = pairs[i]
        say("  slow stretch: train steps %d-%d in %.3f s, %d images/s "
            "(median stretch %d), %.2f s of CPU", a[1], b[1], b[0] - a[0],
            rates[i], median, b[2] - a[2])


def against_reference(ctx, sw):
    """One validation minibatch through the program's forward, against
    the plain float32 reference on the same weights: (the largest
    difference relative to the reference's largest output, problems)."""
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
    return diff, problems


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
