"""The language-model runner on the CPU at toy width: it yields every
metric the decoder cell declares through the product's normal path, a
dropped assignment or a bad step reads not ``correct``, the control one
precision down is refused, and the new cells' files and readers agree
with the manifest.  A CPU run says what the program counts and whether
results are right; every speed in PERF.md comes from the chip."""

import copy
import os
import sys
import time
import types

import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark import token_datasets  # noqa: E402
from benchmark.references import mla_moe_decoder as reference  # noqa: E402
from benchmark.runners import train as train_runner  # noqa: E402
from benchmark.runners import train_lm  # noqa: E402

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

MANIFEST = bench_run.load_manifest()
CELL = "kanana2_train_t8k_b2"
DP4 = "alexnet_train_dp4_b1024"
NEW_METRICS = {
    "collective_ms_per_step.train": [DP4],
    "input_stage_ms_per_step.train": [DP4],
    "mla_attention_ms_per_step.train": [CELL],
    "mla_attention_roofline_pct.train": [CELL],
    "moe_routed_ms_per_step.train": [CELL],
    "moe_expert_load_max_over_mean.train": [CELL],
}

TOY_ARGUMENTS = {
    "vocab": 96, "width": 64, "layers": 3, "dense_layers": 1, "heads": 4,
    "qk_nope": 16, "qk_rope": 8, "v_head": 16, "kv_rank": 24, "ffn": 96,
    "experts": 16, "experts_held": 4, "first_expert": 4, "top_k": 3,
    "expert_width": 32, "shared_width": 64, "routed_scale": 2.448,
    "lr": 3e-3, "router_bias_std": 0.01, "out_init_std": 0.008}
TOY_CONFIG = {
    "name": "toy_decoder", "source": "tests", "reduced": [],
    "model": {"factory": "mla_moe_decoder_layers",
              "arguments": TOY_ARGUMENTS},
    "input_shape": [33], "dtype": "float32",
    "dataset": {"train_rows": 256, "validation_rows": 8,
                "label_kinds": 96, "zipf_exponent": 1.0},
    "reference": {"module": "mla_moe_decoder", "max_rel_diff": 1e-4,
                  "max_rms_diff": 1e-5, "max_loss_diff": 1e-5,
                  "max_grad_diff": 1e-3, "max_update_diff": 0.05,
                  "control_operand": "bfloat16",
                  "reason": "float32 on the CPU; bfloat16 is the "
                            "precision below"},
}
TOY_TRAFFIC = {
    "name": "toy_train_lm", "runner": "train_lm", "batch": 4,
    "warmup_train_steps": 3, "interval_stride": 1, "loss_steps": 4,
    "trace_after_steps": 1, "trace_steps": 2,
    "snapshot": {"compression": "", "interval": 1, "time_interval": 600,
                 "keep": 1},
    "decision": {},
}


@pytest.fixture
def _settings_put_back(monkeypatch):
    saved = dict(root.common.snapshot.__dict__)
    monkeypatch.setattr(root.common.engine, "precision_type",
                        root.common.engine.precision_type)
    yield
    root.common.snapshot.__dict__.clear()
    root.common.snapshot.__dict__.update(saved)


def toy_context(seed=20261002, seconds=0.6, **arguments):
    import jax
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    lines = []
    config = copy.deepcopy(TOY_CONFIG)
    config["model"]["arguments"].update(arguments)
    return types.SimpleNamespace(
        cell={"name": CELL, "config": "toy_decoder",
              "traffic": "toy_train_lm", "chips": 1},
        config=config, traffic=dict(TOY_TRAFFIC), seed=seed,
        seconds=seconds, trace=False, keep_trace="",
        started=time.perf_counter(),
        say=lambda fmt, *args: lines.append(fmt % args if args else fmt),
        chips=1, devices=jax.devices()[:1], device_kind="TPU v5 lite",
        device=device, lines=lines)


#: what a traced chip run's op names look like: the flash kernels by
#: name, ops over the kept-assignment buffer by its rows
def fake_trace(capacity):
    rows = "bf16[%d,64]{1,0}" % capacity
    ops = {
        "%veles_flash_fwd.3 = bf16[8,32,128]{2,1,0} custom-call()": 0.010,
        "%veles_flash_dq = bf16[8,32,128]{2,1,0} custom-call()": 0.012,
        "%veles_flash_dkv.1 = (bf16[8,32,128]) custom-call()": 0.018,
        "%fusion.7 = " + rows + " fusion(bf16[128,64]{1,0} %p)": 0.006,
        "%ragged-dot.2 = f32[" + str(capacity) + ",32]{1,0} ragged-dot("
        + rows + " %x)": 0.004,
        "%fusion.9 = bf16[128,64]{1,0} fusion(" + rows + " %y)": 0.002,
        "%fusion.1 = f32[128,96]{1,0} fusion()": 0.5}
    return {"steps": 2, "window_s": 1.0, "busy_s": 0.9, "chips": 1,
            "gap_seconds": {}, "modules": ["jit_step"], "op_seconds": ops}


def test_lm_runner_yields_every_declared_metric(_settings_put_back):
    ctx = toy_context()
    result = train_lm.run(ctx)
    assert result["correct"], ctx.lines
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert result["metrics"]["train_images_per_s"] > 0
    assert result["metrics"]["setup_s"] > 0
    assert set(result["compared"]) == {
        "compiles_in_window", "failed_steps", "loss_last_below_first",
        "dropped_assignments", "first_step_loss_diff",
        "first_step_grad_diff", "first_step_update_diff",
        "logits_rms_diff", "logits_max_diff",
        "half_batch_grad_diff_above", "control_rms_diff_above"}
    assert all(len(pair) == 2 for pair in result["compared"].values())
    tail, head = result["compared"]["loss_last_below_first"]
    assert tail < head < numpy.log(96) + 0.2
    # the program is the reference to rounding, in its logits and in
    # its first step; the control and the fault are not
    assert result["compared"]["logits_rms_diff"][0] < 1e-5
    assert result["compared"]["first_step_loss_diff"][0] < 1e-6
    assert result["compared"]["first_step_grad_diff"][0] < 1e-4
    assert result["compared"]["first_step_update_diff"][0] < 0.05
    assert -result["compared"]["control_rms_diff_above"][0] > 1e-4
    assert -result["compared"]["half_batch_grad_diff_above"][0] > 0.1
    # the check ran the window's compiled step, not one more
    assert any("(0 compile request(s)" in line for line in ctx.lines), \
        ctx.lines
    layers = result["layers"]
    assert layers["trace"] is None
    assert layers["tokens_per_step"] == 4 * 32
    assert layers["routed_rows"] == 4 * 32 * 3 == 384
    # the window's counts, from the program's counters: 2 routed layers
    # of 4 held experts, and as many assignments as the load adds up to
    loads = {name: value for name, value in layers["registry"].items()
             if name.startswith("moe.load.")}
    assert len(loads) == 8 and min(loads.values()) >= 0
    assert sum(loads.values()) == layers["registry"]["moe.assignments"]
    assert layers["registry"]["moe.dropped_assignments"] == 0
    assert layers["registry"]["train.tokens"] == \
        result["attempted"] * 4 * 32
    assert bench_run.read_layer_metrics(MANIFEST, CELL, layers) == {}
    layers["trace"] = fake_trace(384)
    traced = bench_run.read_layer_metrics(MANIFEST, CELL, layers)
    assert set(traced) == {m["name"] for m in bench_run.cell_metrics(
        MANIFEST, "per_layer", CELL)} == set(
            name for name, cells in NEW_METRICS.items() if CELL in cells)
    assert traced["mla_attention_ms_per_step.train"] == pytest.approx(20.0)
    assert traced["moe_routed_ms_per_step.train"] == pytest.approx(6.0)
    cost = layers["step_cost"]
    assert traced["mla_attention_roofline_pct.train"] == pytest.approx(
        100 * cost["attention_flops"] / 197e12 / 0.020)
    assert traced["moe_expert_load_max_over_mean.train"] == pytest.approx(
        max(loads.values()) * 8 / sum(loads.values()))
    assert traced["moe_expert_load_max_over_mean.train"] >= 1.0
    line = bench_run.result_line(MANIFEST, ctx, result, ctx.devices)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["correct"] and line["device"]["count"] == 1
    # the normal path: one save, in set-up
    assert layers["registry_whole_run"]["snapshot.exports"] == 1
    assert layers["registry"].get("snapshot.exports", 0) == 0


def test_a_dropped_assignment_is_not_correct(_settings_put_back,
                                             monkeypatch):
    """A layer built with a buffer of 8 rows (no factory sets one) where
    a step sends some 90: the layer counts what it drops, and the run
    says so."""
    from veles_tpu.models import zoo
    factory = zoo.mla_moe_decoder_layers
    monkeypatch.setattr(zoo, "mla_moe_decoder_layers", lambda **kw: [
        dict(spec, capacity=8) if spec.get("experts") else spec
        for spec in factory(**kw)])
    ctx = toy_context(seconds=0.2)
    result = train_lm.run(ctx)
    assert not result["correct"]
    dropped, limit = result["compared"]["dropped_assignments"]
    assert dropped > 0 and limit == 0
    assert any("routed assignment(s) dropped" in line and "NOT CORRECT"
               in line for line in ctx.lines), ctx.lines
    # and the reference, which drops nothing, now disagrees
    number, limit = result["compared"]["logits_rms_diff"]
    assert number > 10 * limit


def test_one_bad_step_is_not_correct(_settings_put_back, monkeypatch):
    plain_run = train_lm.LMWindowUnit.run

    def run_with_one_bad_step(self):
        trainer = self.workflow.fused_trainer
        # the window's first train step: in it however slow the steps
        if (self.workflow.loader.minibatch_class == train_runner.TRAIN
                and self.open is not None and not self.stamps
                and not getattr(self, "planted", False)):
            self.planted = True
            trainer.last_step_finite = self.bad_flag
        elif not hasattr(self, "bad_flag") and \
                trainer.last_step_finite is not True:
            # made in set-up, so nothing compiles for it in the window
            self.bad_flag = ~trainer.last_step_finite
        plain_run(self)

    monkeypatch.setattr(train_lm.LMWindowUnit, "run", run_with_one_bad_step)
    ctx = toy_context(seconds=0.4)
    result = train_lm.run(ctx)
    assert not result["correct"] and result["failed"] == 1
    assert result["compared"]["failed_steps"] == [1, 0]
    assert "  NOT CORRECT: 1 skipped or non-finite step(s)" in ctx.lines


def test_a_slack_limit_is_not_correct(_settings_put_back):
    """Limits wide enough for the control or the fault to pass are
    refused too."""
    ctx = toy_context(seconds=0.1)
    ctx.config["reference"].update(max_rms_diff=0.5, max_rel_diff=0.9,
                                   max_grad_diff=5.0)
    result = train_lm.run(ctx)
    assert not result["correct"]
    for name in ("control_rms_diff_above", "half_batch_grad_diff_above"):
        assert any(name in line and "NOT CORRECT" in line
                   for line in ctx.lines), ctx.lines


@pytest.mark.parametrize("fault, caught_by", [
    ("half_batch", "first_step_grad_diff"),
    ("no_bias_correction", "first_step_update_diff"),
    ("no_update", "first_step_update_diff")])
def test_a_wrong_first_step_is_not_correct(_settings_put_back, monkeypatch,
                                           fault, caught_by):
    """What the loss falling cannot see: a step that trains on half its
    minibatch, an AdamW without its bias correction, a step that leaves
    the parameters where they were."""
    import jax
    import jax.numpy as jnp
    plain = train_lm.first_step_of_the_program

    def faulty(trainer, initial, x, targets):
        step = trainer._step_fn

        def wrong(state, x_, y_, size, key, step_count):
            if fault == "half_batch":
                half = len(x_) // 2
                x_ = jnp.concatenate([x_[:half], x_[:half]])
                y_ = jnp.concatenate([y_[:half], y_[:half]])
            if fault == "no_bias_correction":
                step_count = numpy.int32(1000)
            before = jax.tree_util.tree_map(jnp.copy, state)
            new, metrics = step(state, x_, y_, size, key,
                                step_count=step_count)
            if fault == "no_update":
                new = [dict(entry, weights=old["weights"],
                            bias=old["bias"])
                       for entry, old in zip(new, before)]
            return new, metrics
        monkeypatch.setattr(trainer, "_step_fn", wrong)
        return plain(trainer, initial, x, targets)

    monkeypatch.setattr(train_lm, "first_step_of_the_program", faulty)
    ctx = toy_context(seconds=0.1)
    result = train_lm.run(ctx)
    assert not result["correct"]
    number, limit = result["compared"][caught_by]
    assert number > limit, result["compared"]
    assert any(caught_by in line and "NOT CORRECT" in line
               for line in ctx.lines), ctx.lines


# -- the new cells' files and readers against the manifest --------------------


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_declared_for_its_cells(name):
    metric = bench_run.find(MANIFEST["per_layer"], name, "metric")
    assert metric["workloads"] == NEW_METRICS[name]
    assert metric["moves"] == "train_images_per_s"
    module = bench_run.load_reader(name)
    assert module.__doc__.startswith(module.LAYER + ":")
    # an untraced run, and a program that has none of it: nothing
    bare = {"trace": None, "registry": {}, "steps": 3}
    assert module.read(bare) is None


def test_accepted_entries_keep_their_order_and_new_ones_follow():
    """What the driver holds a program PR to: the per-layer metrics the
    benchmark had stay where they were, and this PR's come after them
    all (``test_span_metrics.py``'s frozen form of this pins PR 25's
    seven as the last and is marked stale in ``tests/conftest.py``)."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    new = [n for n in names if n in NEW_METRICS]
    assert names[-len(new):] == new and len(new) == len(NEW_METRICS)
    assert names[:-len(new)][-7:] == [
        "trainer_dispatch_ms_per_step.train",
        "trainer_stage_us_per_step.train",
        "decision_sync_ms_per_step.train", "sched_hop_us_per_step.train",
        "loader_gather_us_per_step.train", "conv_wgrad_ms_per_step.train",
        "pool_bwd_ms_per_step.train"]


def test_readers_find_nothing_in_a_parent_that_lacks_the_decoder():
    """The parent commit has no flash kernel in a step, no buffer, no
    load counters: each reader returns nothing or 0.0, never raises."""
    context = {"trace": {"steps": 2, "op_seconds": {
        "%fusion.1 = f32[128,96]{1,0} fusion()": 0.5}}, "registry": {},
        "steps": 5, "step_cost": {"flops": 1.0, "bytes": 1.0},
        "config": {"dtype": "bfloat16"}, "chips": 1,
        "device_kind": "TPU v5 lite"}
    read = {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS}
    assert read == {"collective_ms_per_step.train": 0.0,
                    "input_stage_ms_per_step.train": None,
                    "mla_attention_ms_per_step.train": 0.0,
                    "mla_attention_roofline_pct.train": None,
                    "moe_routed_ms_per_step.train": None,
                    "moe_expert_load_max_over_mean.train": None}


def test_collective_reader_counts_collectives_only():
    ops = {"%all-reduce.3 = f32[1024]{0} all-reduce(f32[1024]{0} %x)": 0.4,
           "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%y)": 0.1,
           "%all-reduce-done.1 = f32[8]{0} all-reduce-done(%z)": 0.3,
           "%collective-permute.2 = f32[8]{0} collective-permute()": 0.2,
           # XLA names an all-reduce after the primitive that made it
           "%psum.43 = f32[6553600]{0:T(1024)S(1)} all-reduce("
           "f32[6553600]{0:T(1024)S(1)} %pad_maximum_fusion.1), "
           "channel_id=1": 0.5,
           "%psum.44 = (f32[8]{0}, f32[]) all-reduce-start(%a, %b)": 0.5,
           "%fusion.4 = f32[8]{0} fusion(%all-reduce.3)": 9.0,
           "%all-reduce-scatter-fusion = f32[8]{0} fusion()": 7.0}
    context = {"trace": {"steps": 4, "op_seconds": ops}}
    assert bench_run.load_reader("collective_ms_per_step.train").read(
        context) == pytest.approx(1e3 * 2.0 / 4)


def test_input_stage_reader_reads_the_stage_span_per_train_step():
    """``step.stage_s`` over the window (train and eval minibatches) ÷
    the window's train steps, in ms; nothing untraced."""
    context = {"trace": {"steps": 3}, "steps": 4, "registry": {
        "step.stage_s.sum": 2.0, "step.stage_s.count": 10}}
    read = bench_run.load_reader("input_stage_ms_per_step.train").read
    assert read(context) == pytest.approx(500.0)
    assert read(dict(context, trace=None)) is None


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every number of the catalog's row under its own key; the three
    reduced keys, and only they, differ; the factory's arguments repeat
    the widths; the file states the deployment."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "topk_group": 1,
        "v_head_dim": 128, "vocab_size": 128256}
    differs = sorted(key for key, value in published.items()
                     if config[key] != value)
    assert differs == sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size"])
    assert (config["q_lora_rank"], config["rope_scaling"]) == (None, None)
    assert (config["scoring_func"], config["topk_method"],
            config["norm_topk_prob"], config["rope_interleave"],
            config["tie_word_embeddings"], config["attention_bias"]) == (
                "sigmoid", "noaux_tc", True, True, False, False)
    a = config["model"]["arguments"]
    assert (a["width"], a["heads"], a["qk_nope"], a["qk_rope"],
            a["v_head"], a["kv_rank"], a["ffn"], a["experts"], a["top_k"],
            a["expert_width"], a["shared_width"], a["routed_scale"],
            a["theta"], a["eps"]) == (
                2048, 32, 128, 64, 128, 512, 6144, 128, 6, 768, 2 * 768,
                2.448, 1e6, 1e-6)
    assert (a["layers"], a["dense_layers"], a["experts_held"],
            a["vocab"]) == (config["num_hidden_layers"], 1,
                            config["n_routed_experts"],
                            config["vocab_size"]) == (5, 1, 16, 16032)
    assert "8 chips share each layer" in config["deployment"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    # the buffer holds the most a step can send, and no file sets one
    assert "capacity" not in a
    assert train_lm.routed_rows(config, traffic["batch"]) == 98304
    # the projections into the residual stream: std / sqrt(2 x 48)
    assert a["out_init_std"] == pytest.approx(
        a["init_std"] / numpy.sqrt(2 * 48), rel=1e-3)
    # the limits order as they must
    limits = config["reference"]
    assert 0 < limits["max_rms_diff"] < limits["max_rel_diff"] < 1
    assert 0 < limits["max_grad_diff"] < limits["max_update_diff"] < 1


def test_step_cost_against_hand_counts():
    """The issue's arithmetic: 576 M parameters held, 46 TFLOP a step,
    45 % of it causal attention."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    cost = reference.step_cost(config, traffic["batch"])
    n = reference.parameter_counts(config["model"]["arguments"])
    assert n["attention"] == 2048 * 6144 + 2048 * 576 + 512 * 8192 \
        + 4096 * 2048 == 26345472
    assert (n["expert"], n["shared"], n["router"]) == (
        4718592, 9437184, 262144)
    assert cost["parameters"] == 575930368
    assert cost["tokens"] == 16384
    pairs = 2 * 8192 * 8193 // 2
    assert cost["attention_flops"] == 3 * 5 * pairs * 32 * 2 * 320
    assert cost["routed_assignments"] == 4 * 12288
    assert cost["routed_flops"] == 3 * 4 * 12288 * 2 * 4718592
    assert 45e12 < cost["flops"] < 47e12
    assert 0.44 < cost["attention_flops"] / cost["flops"] < 0.46


def test_token_rows_are_a_function_of_the_seed():
    def make(seed):
        out = numpy.zeros((300, 65), numpy.int32)
        return token_datasets.fill_ids(out, 500, 1.0, seed)
    big = (1 << 31) + 4242
    a, b, c = make(big), make(big), make(big + 1)
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 500
    # Zipf: the commonest id takes about 1 / H(500) = 14.7 % of the draws
    share = numpy.bincount(a.ravel(), minlength=500).max() / a.size
    assert 0.12 < share < 0.18
