"""The language-model training runner: a decoder cell's job through the
product's normal path.

What ``train.py`` is for image rows with one label each, this is for
rows of token ids whose target is the row shifted by one: the same
``Launcher`` -> ``StandardWorkflow`` -> auto-fuse -> ``FusedTrainer``
path, ``Prefetcher`` attached, snapshotter wired, the rows resident in
HBM (each checked, as there), the same ``WindowUnit`` window, stamps and
end-to-end arithmetic — imported from ``train.py``, which is not edited.
A row is the cell's "image": ``train_images_per_s`` counts sequences,
and the run also prints tokens a second.

Traffic file parameters (``benchmark/traffic/<traffic>.json``, ``runner``
``train_lm``): ``batch`` (rows a step), ``warmup_train_steps``,
``interval_stride``, ``trace_after_steps``, ``trace_steps``, ``snapshot``
and ``decision`` as for ``train``; ``loss_steps`` (k: the mean loss of
the run's last k train steps must lie below that of its first k).
Configuration file: ``model`` (a zoo factory returning decoder specs),
``input_shape`` ``[T + 1]``, ``dataset`` (``train_rows``,
``validation_rows``, ``label_kinds`` = the vocabulary rows held,
``zipf_exponent``), ``reference`` (``module``, and the limits below).

``correct`` is all of: no failed step (every step of the window read, as
``train.py`` reads them); no compile request inside the window; the loss
fell; no routed assignment dropped in the whole run
(``moe.dropped_assignments`` = 0); and, after the window, the FIRST
TRAIN STEP from the seed, followed at the timed size: the weights the
run started from (kept on the host) and one train minibatch (``batch``
x T) go through the trainer's own ``_step_fn`` — the window's compiled
program: forward, recomputing backward, flash kernels, AdamW with its
step count, float32 state — and, a sequence and a layer at a time,
through the configuration's plain float32 reference: its forward, its
backward by hand, a plain AdamW step.  Compared (``reference.*`` holds
the limits):

- ``first_step_loss_diff`` = |the step's loss - ref loss| / ref loss at
  most ``max_loss_diff``.
- ``first_step_grad_diff`` = the largest, over the parameter arrays, of
  ||g - g_ref|| / ||g_ref||, at most ``max_grad_diff``; g is read back
  from AdamW's first moment after the step (m = (1 - beta1) g).
- ``first_step_update_diff`` = ||dp - dp_ref|| / ||dp_ref|| over all
  parameters, dp the step's change of them, at most ``max_update_diff``;
  a state left unchanged reads 1.  (AdamW's first step moves every
  element by the rate times its gradient's SIGN, so each sign a
  rounding flips counts whole: it reads tenths where the gradient
  reads hundredths.)
- ``logits_rms_diff`` = ||out - ref|| / ||ref|| (Frobenius) of the same
  minibatch's logits from the program's own forward
  (``compiler.build_forward`` on the trainer's plans) at most
  ``max_rms_diff``; ``logits_max_diff`` = max|out - ref| / max|ref| at
  most ``max_rel_diff`` — looser by design: a token whose 6th and 7th
  router scores lie within a rounding of each other goes to another
  expert in the program than in the reference, and its logits move by a
  whole expert's term, not by a rounding.
- the FAULT, a step that trains on half the minibatch: the reference's
  gradient of the first sequence alone, read as the program's is, must
  FAIL ``max_grad_diff`` (``half_batch_grad_diff_above``).
- the CONTROL, one precision down, once: the reference's logits of the
  first sequence with the operands of every product rounded to
  ``control_operand`` (each tensor scaled to the format's range; the
  same compiled programs, their rounding switched on) against its own
  in float32 must FAIL ``max_rms_diff`` (``control_rms_diff_above``).  A run in which the fault or the control
  passes says a limit has gone slack, and is not correct.

Every number is printed beside its limit, in the run's lines, on
standard error and under ``compared``.  The per-layer context adds to
``train.py``'s keys: ``tokens_per_step``, ``routed_rows`` (the rows of
the routed layers' buffer of kept assignments, tokens x top_k, whose
count marks their ops in the trace), and ``step_cost``'s
``attention_flops``/``routed_flops``.
"""

import functools
import gc
import os
import shutil
import tempfile
import time

import numpy

from benchmark import flops, reduce_trace
from benchmark.runners import train
from benchmark.runners.train import (
    check, compile_counts, delta, file_cap_allows, reference_of,
    registry_totals, window_metrics)
from benchmark.token_datasets import SeededTokens


class LMWindowUnit(train.WindowUnit):
    """``WindowUnit`` whose edges also publish the layers' counters
    (``FusedTrainer.publish_layer_counters``: the routed layers' load):
    the edge waits for the device anyway, so the registry's deltas over
    the window are the window's."""

    hide_from_registry = True

    def _edge(self):
        self._wait_for_device()
        self.workflow.fused_trainer.publish_layer_counters()
        return super(LMWindowUnit, self)._edge()


def state_bytes(sw):
    """What a snapshot of ``sw`` weighs, about: its parameters and
    solver state (``pickle.dumps`` of 7 GB would double it in memory)."""
    arrays = []
    for unit in sw.forwards:
        arrays += [unit.weights, unit.bias]
    for unit in sw.gds:
        if unit is not None:
            arrays += [unit.accum_weights, unit.accum_bias,
                       unit.accum2_weights, unit.accum2_bias]
    return sum(array.nbytes for array in arrays
               if array is not None and array)


def build(ctx, snapshot_dir):
    """The workflow under its launcher, initialised: ``train.build``
    with token rows."""
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
        loader_factory=lambda workflow: SeededTokens(
            workflow, minibatch_size=batch,
            prng=prng.RandomGenerator("benchmark", seed=seed),
            row_ids=config["input_shape"][0],
            vocabulary=data["label_kinds"],
            exponent=data.get("zipf_exponent", 1.0),
            lengths=(0, data["validation_rows"], data["train_rows"]),
            data_seed=seed),
        decision_config=dict(traffic.get("decision", {})))
    check(sw.snapshotter is not None, "the snapshotter is not wired")
    check(ctx.chips == 1, "the decoder's step takes one chip (adamw's "
          "step count does not pass the shard_map builders yet)")
    launcher.initialize(device=ctx.device)
    trainer = getattr(sw, "fused_trainer", None)
    check(trainer is not None, "auto-fuse did not happen: the run would "
          "take the per-unit path on the chip")
    check(trainer._prefetcher is not None,
          "the Prefetcher is not attached on one chip")
    check(sw.loader._use_device_path(), "the rows are not HBM-resident")
    snapshot_bytes = state_bytes(sw)
    if not file_cap_allows(snapshot_dir, snapshot_bytes + (1 << 20)):
        sw.snapshotter.skip <<= True
        ctx.say("  this machine caps one file below the %.1f MB a "
                "snapshot weighs: the snapshotter is off for this run",
                snapshot_bytes / 1e6)
    original = sw.loader.original_data
    ctx.say("  workflow initialised %.1f s after start: rows %s %s, "
            "%.3f GB on the device; model state %.2f GB",
            time.perf_counter() - ctx.started, original.shape,
            original.dtype,
            sum(s.nbytes for s in sw.loader._stores_.values()) / 1e9,
            snapshot_bytes / 1e9)
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
        initial = initial_parameters(sw)
        window = LMWindowUnit(
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
        result = judge(ctx, sw, window, initial)
        result["layers"] = layer_context(ctx, sw, window, trace_dir)
        return result
    finally:
        shutil.rmtree(snapshot_dir, ignore_errors=True)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def judge(ctx, sw, window, initial):
    """correct / attempted / failed and the end-to-end metrics."""
    import jax
    traffic = ctx.traffic
    batch = int(traffic["batch"])
    keep = int(traffic["loss_steps"])
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
    compared = {"compiles_in_window": [compiles["count"], 0]}
    if compiles["count"]:
        problems.append("%d compile request(s) inside the window"
                        % compiles["count"])
    failed = max(window.failed_steps(), int(sw.fused_trainer.skip_count))
    compared["failed_steps"] = [failed, 0]
    if failed:
        problems.append("%d skipped or non-finite step(s)" % failed)

    first = numpy.asarray(jax.device_get(
        list(window.first_losses)[:keep]), numpy.float64)
    last = numpy.asarray(jax.device_get(
        list(window.last_losses)[-keep:]), numpy.float64)
    head, tail = first.mean(), last.mean()
    ctx.say("  mean loss of the first %d train steps from initialisation "
            "%.4f, of the last %d %.4f (ln of the %d ids is %.4f)", keep,
            head, keep, tail, ctx.config["dataset"]["label_kinds"],
            numpy.log(ctx.config["dataset"]["label_kinds"]))
    compared["loss_last_below_first"] = [float(tail), float(head)]
    if not tail < head:
        problems.append("the loss did not fall: %.4f -> %.4f"
                        % (head, tail))

    whole = delta(closed["registry"], ctx.registry_at_start)
    dropped = int(whole.get("moe.dropped_assignments", 0))
    routed = int(whole.get("moe.assignments", 0))
    compared["dropped_assignments"] = [dropped, 0]
    even = (whole.get("train.steps", 0)
            * reference_of(ctx.config).step_cost(
                ctx.config, batch)["routed_assignments"])
    ctx.say("  routed layers: %d assignments to held experts in the whole "
            "run (%.2f x an even router's %d), %d dropped of the %d rows a "
            "layer's buffer holds a step", routed, routed / max(even, 1),
            even, dropped, routed_rows(ctx.config, batch))
    if dropped:
        problems.append("%d routed assignment(s) dropped" % dropped)

    numbers, off = against_reference(ctx, sw, initial)
    compared.update(numbers)
    problems += off

    ctx.say("  the window: %d train + %d eval steps, %d save(s), %.3f s",
            steps, window.eval_steps,
            delta(closed["registry"], opened["registry"]).get(
                "snapshot.exports", 0), seconds)
    metrics = window_metrics(ctx.say, opened["clock"], closed["clock"],
                             window.stamps, batch,
                             int(traffic["interval_stride"]))
    metrics["setup_s"] = window.setup_s
    tokens = batch * (ctx.config["input_shape"][0] - 1)
    ctx.say("  %.1f tokens/s (%d tokens a step, %.1f ms a step)",
            steps * tokens / seconds, tokens, 1e3 * seconds / max(steps, 1))
    train.cpu_anatomy(ctx.say, window.cpu_marks, batch)
    for problem in problems:
        ctx.say("  NOT CORRECT: %s", problem)
    return {"correct": not problems, "attempted": steps, "failed": failed,
            "metrics": metrics, "compared": compared}


def routed_rows(config, batch):
    """Rows of a routed layer's buffer of kept assignments: the most a
    step can send to the held experts."""
    a = config["model"]["arguments"]
    return batch * (config["input_shape"][0] - 1) * min(
        a["top_k"], a["experts_held"])


def initial_parameters(sw):
    """Host copies of every unit's parameters as initialised from the
    seed: what the first train step starts from."""
    out = []
    for unit in sw.forwards:
        entry = {}
        for key, array in (("weights", unit.weights), ("bias", unit.bias)):
            if array:
                array.map_read()
                entry[key] = numpy.array(array.mem, numpy.float32)
            else:
                entry[key] = None
        out.append(entry)
    return out


def first_step_of_the_program(trainer, initial, x, targets):
    """The trainer's own compiled step on the initial weights and zero
    moments: (loss, parameters after it, first moments after it, compile
    requests it cost — 0 where it is the window's program)."""
    import jax
    import jax.numpy as jnp
    shapes = [{key: None if leaf is None else (leaf.shape, leaf.dtype,
                                               leaf.sharding)
               for key, leaf in entry.items()} for entry in trainer._state]
    device = jax.devices()[0]
    # the trained state is done with: the fresh one wants its room
    trainer._state = None
    gc.collect()
    state = []
    for entry, start in zip(shapes, initial):
        fresh = {}
        for key, leaf in entry.items():
            if leaf is None:
                fresh[key] = None
            elif key in start:
                fresh[key] = jax.device_put(
                    start[key].astype(leaf[1]), leaf[2])
            else:
                fresh[key] = jax.device_put(
                    jnp.zeros(leaf[0], leaf[1]), leaf[2])
        state.append(fresh)
    before = compile_counts()["count"]
    state, metrics = trainer._step_fn(
        state, jax.device_put(x, device), jax.device_put(targets, device),
        numpy.float32(len(x)), None, step_count=numpy.int32(1))
    loss = float(metrics["loss"])
    after = [{key: None if entry[key] is None
              else numpy.asarray(entry[key], numpy.float32)
              for key in ("weights", "bias")} for entry in state]
    moments = [{key: None if entry["accum_" + key] is None
                else numpy.asarray(entry["accum_" + key], numpy.float32)
                for key in ("weights", "bias")} for entry in state]
    del state, metrics
    gc.collect()
    return loss, after, moments, compile_counts()["count"] - before


@functools.lru_cache(maxsize=None)
def _on_device():
    """(moments of a difference, a parameter array's numbers): the
    comparisons' arithmetic as jitted programs — 576 M elements a pass
    are the device's work, not numpy's."""
    import jax
    import jax.numpy as jnp

    def square(x):
        return jnp.sum(jnp.square(x))

    def moments(got, want):
        got = got.astype(jnp.float32)
        return (square(got - want), square(want),
                jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want)))

    def array_numbers(adamw_step, start, end, moment, g_ref, g_half, adam):
        """|g - g_ref|^2, |g_half - g_ref|^2, |g_ref|^2, and of the
        array's change |dp - dp_ref|^2, |dp_half - dp_ref|^2,
        |dp_ref|^2; g from AdamW's first moment."""
        def change(grad):
            return adamw_step(start, grad, 0.0, 0.0, 1, **adam)[0] - start
        g = moment / (1 - adam["beta1"])
        ref, half = change(g_ref), change(g_half)
        return jnp.stack([square(g - g_ref), square(g_half - g_ref),
                          square(g_ref), square(end - start - ref),
                          square(half - ref), square(ref)])
    return jax.jit(moments), jax.jit(array_numbers, static_argnums=0)


def against_reference(ctx, sw, initial):
    """The first train step from the seed through the trainer's own
    step and through the plain reference, the fault and the control
    ({name: [number, limit]}, problems)."""
    import jax

    from veles_tpu.compiler import build_forward
    batch = int(ctx.traffic["batch"])
    limits = ctx.config["reference"]
    reference = reference_of(ctx.config)
    trainer, loader = sw.fused_trainer, sw.loader
    layers = sw.layers_config
    moments_of, array_numbers = _on_device()
    loader.original_data.map_read()
    first_train = ctx.config["dataset"]["validation_rows"]
    rows = numpy.array(
        loader.original_data.mem[first_train:first_train + batch])
    x, targets = rows[:, :-1], rows[:, 1:]
    clock = [time.perf_counter()]

    def lap():
        clock.append(time.perf_counter())
        return clock[-1] - clock[-2]

    loss, after, moments, compiled = first_step_of_the_program(
        trainer, initial, x, targets)
    ctx.say("  the first train step from the seed, %d x %d tokens, through "
            "the trainer's own step (%d compile request(s): 0 says it is "
            "the window's program): loss %.5f; %.1f s with its state's way "
            "to the device and back", batch, x.shape[1], compiled, loss,
            lap())
    params = [{key: None if value is None else jax.device_put(value)
               for key, value in entry.items()} for entry in initial]
    got = jax.jit(build_forward(trainer._plans))(params, x)
    finite = bool(numpy.isfinite(numpy.asarray(got[:, -1])).all())
    forward_s = lap()

    # the reference: a sequence at a time, the first alone is the fault
    total = count = 0
    sums = numpy.zeros(2)
    largest = numpy.zeros(2)
    grads = half = loads = None
    for i in range(batch):
        part, n, logits, mine, load = reference.row_gradients(
            layers, params, x[i], targets[i], lowered=False,
            operand=limits["control_operand"])
        total, count = total + part, count + n
        parts = jax.device_get(moments_of(got[i], logits))
        sums += parts[:2]
        largest = numpy.maximum(largest, parts[2:])
        if i == 0:
            plain = logits  # the control's other side
            half = reference.scale_gradients(mine, 1.0 / n)
        del logits
        loads = load if loads is None else [
            a + b for a, b in zip(loads, load)]
        grads = mine if grads is None else reference.add_gradients(
            grads, mine)
        del mine
    del got
    grads = reference.scale_gradients(grads, 1.0 / count)
    want_loss = total / count
    rms = float(numpy.sqrt(sums[0] / sums[1]))
    worst = float(largest[0] / largest[1])
    loss_diff = abs(loss - want_loss) / want_loss
    even = reference.step_cost(ctx.config, batch)["routed_assignments"] \
        / max(len(loads), 1)
    for layer, load in enumerate(numpy.asarray(jax.device_get(loads))):
        ctx.say("  the reference's routed layer %d at the seed's weights: "
                "%d assignments to the held experts (an even router sends "
                "%d), the fullest held expert %.2f x their mean: %s",
                layer, load.sum(), even,
                load.max() / max(load.mean(), 1e-9), load.tolist())
    reference_s = lap()

    # gradients, from AdamW's first moment; the change of the parameters
    grad_diff, half_diff = {}, {}
    moved = numpy.zeros(3)  # |dp - dp_ref|^2, |dp_half - ..|^2, |dp_ref|^2
    for i, (end, moment) in enumerate(zip(after, moments)):
        spec = layers[i]  # the solver's settings, as the factory set them
        for key, decay in (("weights", spec["weights_decay"]),
                           ("bias", spec["weights_decay_bias"])):
            if end[key] is None:
                continue
            adam = dict(lr=spec["learning_rate"],
                        beta1=spec["gradient_moment"],
                        beta2=spec["adadelta_rho"],
                        eps=spec["solver_epsilon"], decay=decay)
            numbers = numpy.asarray(array_numbers(
                reference.adamw_step, params[i][key], end[key],
                moment[key], grads[i][key].reshape(end[key].shape),
                half[i][key].reshape(end[key].shape), adam), numpy.float64)
            if numbers[2]:
                name = "%d.%s" % (i, key)
                grad_diff[name] = float(numpy.sqrt(numbers[0] / numbers[2]))
                half_diff[name] = float(numpy.sqrt(numbers[1] / numbers[2]))
            moved += numbers[3:]
    worst_grad = max(grad_diff, key=grad_diff.get)
    least_half = min(half_diff, key=half_diff.get)
    update_diff = float(numpy.sqrt(moved[0] / moved[2]))
    half_update = float(numpy.sqrt(moved[1] / moved[2]))
    half_grad = max(half_diff.values())
    ctx.say("  against the float32 reference's step: loss %.5f against "
            "%.5f (%.3g apart, limit %g); gradients within %.3g of the "
            "reference's (array %s; limit %g; all: %s); the parameters' "
            "change %.3g off the reference's AdamW step (limit %g; a state "
            "left unchanged reads 1)", loss, want_loss, loss_diff,
            limits["max_loss_diff"], grad_diff[worst_grad], worst_grad,
            limits["max_grad_diff"],
            " ".join("%s %.3g" % item for item in grad_diff.items()),
            update_diff, limits["max_update_diff"])
    ctx.say("  the same minibatch's logits from the program's forward: "
            "within %.3g (rms) and %.3g (largest) of the reference's, "
            "limits %g and %g", rms, worst, limits["max_rms_diff"],
            limits["max_rel_diff"])
    ctx.say("  the fault, a step on half the minibatch: gradients %.3g off "
            "(the least of any array %.3g, %s), the parameters' change "
            "%.3g off; the gradients' limit must refuse it", half_grad,
            half_diff[least_half], least_half, half_update)
    del grads, half
    compare_s = lap()

    # the control, one precision down, once: the first sequence through
    # the reference's own programs with their rounding switched on
    control = reference.forward(layers, params, x[:1],
                                operand=limits["control_operand"])[0]
    parts = jax.device_get(moments_of(control, plain))
    control_rms = float(numpy.sqrt(parts[0] / parts[1]))
    ctx.say("  the control, the reference in %s operands on the first "
            "sequence: %.3g (rms) and %.3g (largest) off itself in "
            "float32; the rms limit must refuse it",
            limits["control_operand"], control_rms,
            float(parts[2] / parts[3]))
    control_s = lap()
    ctx.say("  the check took %.1f s: the program's forward %.1f, the "
            "reference's sequences forward and backward %.1f, the arrays' "
            "comparison %.1f, the control %.1f", clock[-1] - clock[0],
            forward_s, reference_s, compare_s, control_s)
    numbers = {
        "first_step_loss_diff": [loss_diff, limits["max_loss_diff"]],
        "first_step_grad_diff": [grad_diff[worst_grad],
                                 limits["max_grad_diff"]],
        "first_step_update_diff": [update_diff,
                                   limits["max_update_diff"]],
        "logits_rms_diff": [rms, limits["max_rms_diff"]],
        "logits_max_diff": [worst, limits["max_rel_diff"]],
        "half_batch_grad_diff_above": [-half_grad,
                                       -limits["max_grad_diff"]],
        "control_rms_diff_above": [-control_rms,
                                   -limits["max_rms_diff"]]}
    problems = []
    if not finite:
        problems.append("the program's logits are not finite")
    for name, (number, limit) in numbers.items():
        if not number <= limit:
            problems.append("%s %.3g beyond its limit %g"
                            % (name, number, limit))
    return numbers, problems


def layer_context(ctx, sw, window, trace_dir):
    """What the per-layer readers read: ``train.py``'s keys and the
    decoder's."""
    opened, closed = window.open, window.close
    steps = len(window.stamps)
    data = ctx.config["dataset"]
    batch = int(ctx.traffic["batch"])
    cost = reference_of(ctx.config).step_cost(ctx.config, batch)
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
        "step_cost": cost,
        "tokens_per_step": cost["tokens"],
        "routed_rows": routed_rows(ctx.config, batch),
        "trace": None,
    }
    floor, bound = flops.floor_seconds(
        cost["flops"], cost["bytes"], flops.peaks(ctx.device_kind),
        ctx.config["dtype"], ctx.chips)
    ctx.say("  one step needs %.1f TFLOP forward and backward (%.0f %% of "
            "it causal attention, %.0f %% the held experts) and moves at "
            "least %.2f GB; its floor on %d %s is %.1f ms, set by %s",
            cost["flops"] / 1e12,
            100.0 * cost["attention_flops"] / cost["flops"],
            100.0 * cost["routed_flops"] / cost["flops"],
            cost["bytes"] / 1e9, ctx.chips, ctx.device_kind, floor * 1e3,
            bound)
    if trace_dir is not None:
        path = reduce_trace.find_xplane(trace_dir)
        if ctx.keep_trace:
            os.makedirs(ctx.keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(
                ctx.keep_trace, ctx.cell["name"] + ".xplane.pb"))
        context["trace"] = reduce_trace.reduce(
            path, step_module=ctx.traffic.get(
                "train_step_module", train.TRAIN_STEP_MODULE))
        ctx.say("  the trace cost the window %.3f s in start_trace and "
                "stop_trace", window.trace_seconds)
    return context
