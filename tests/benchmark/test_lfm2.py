"""The ``lfm2_8b_a1b`` configuration, its cell
``lfm2_8b_a1b_train_t8k_b2`` and its three per-layer readers, on the CPU:
the file is the published configuration cut as it says, ``step_cost``
agrees with counts made by hand (the published head width, never the
lane tile), each reader reads a made-up trace and finds nothing in a
program that lacks what it reads, no share can pass 100 %, the
manifest's accepted entries are still a prefix with the new ones after
them, and the accepted runner ``train_lm`` yields the cell's metrics at
toy width and two rows a step through the product's normal path —
``correct``, the fault and the control refused.  A CPU run says what the
program counts and whether results are right; every speed in PERF.md
comes from the chip."""

import copy
import os
import re
import sys
import time
import types

import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.references import conv_gqa_moe_decoder as reference  # noqa: E402,E501
from benchmark.runners import train_lm  # noqa: E402

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

MANIFEST = bench_run.load_manifest()
CELL = "lfm2_8b_a1b_train_t8k_b2"
NEW_METRICS = ["short_conv_ms_per_step.train",
               "narrow_attention_ms_per_step.train",
               "narrow_attention_roofline_pct.train"]
#: the per-layer metrics the benchmark had before this cell, in the
#: order it had them: PR 24-25's sixteen, PR 29's six, PR 33's five
ACCEPTED = [
    "units_host_ms_per_step.train", "pipeline_wait_us_per_step.train",
    "data_device_ms_per_step.train", "trainer_ms_per_step.train",
    "device_ms_per_step.train", "mosaic_ms_per_step.train",
    "step_peak_pct.train", "snapshot_ms_per_save.train",
    "device_idle_pct.train", "trainer_dispatch_ms_per_step.train",
    "trainer_stage_us_per_step.train", "decision_sync_ms_per_step.train",
    "sched_hop_us_per_step.train", "loader_gather_us_per_step.train",
    "conv_wgrad_ms_per_step.train", "pool_bwd_ms_per_step.train",
    "collective_ms_per_step.train", "input_stage_ms_per_step.train",
    "mla_attention_ms_per_step.train", "mla_attention_roofline_pct.train",
    "moe_routed_ms_per_step.train", "moe_expert_load_max_over_mean.train",
    "window_attention_ms_per_step.train",
    "window_attention_roofline_pct.train",
    "gqa_attention_ms_per_step.train", "gqa_attention_roofline_pct.train",
    "moe_buffer_fill_pct.train"]
ACCEPTED_CONFIGS = ["alexnet", "mnist_mlp", "kanana2_30b_a3b",
                    "trinity_mini"]
ACCEPTED_CELLS = ["alexnet_train_b256", "mnist_mlp_train_b100",
                  "alexnet_train_dp4_b1024", "kanana2_train_t8k_b2",
                  "trinity_mini_train_t8k_b1"]

#: what this PR appended to ``BENCHMARK.json``, each list's entries
#: after all the list had
ADDED = {"configs": ["lfm2_8b_a1b"], "workloads": [CELL],
         "per_layer": NEW_METRICS}

#: the catalog row ``LFM2-8B-A1B`` of the model-configs guide
#: (https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json),
#: every number of its ``config``, written here by hand
PUBLISHED = {
    "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
    "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "vocab_size": 65536}
PUBLISHED_LAYER_TYPES = (
    ["conv", "conv", "full_attention"] + ["conv", "conv", "conv",
                                          "full_attention"] * 4
    + ["conv", "conv", "full_attention", "conv", "conv"])


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every number of the catalog's row under its own key; the reduced
    keys, and only they, differ; the factory's arguments repeat the
    widths; the file states the deployment and what it assumed."""
    cell, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_8b_a1b", "train_t8k_b2", 1)
    assert len(PUBLISHED_LAYER_TYPES) == 24 and \
        PUBLISHED_LAYER_TYPES.count("full_attention") == 6
    published = dict(PUBLISHED, layer_types=PUBLISHED_LAYER_TYPES)
    differs = sorted(key for key, value in published.items()
                     if config[key] != value)
    entry = bench_run.find(MANIFEST["configs"], "lfm2_8b_a1b", "config")
    assert differs == sorted(config["reduced"]) == sorted(
        entry["reduced"]) == sorted([
            "num_hidden_layers", "num_dense_layers", "layer_types",
            "num_experts", "vocab_size"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/"
        "config.json")
    assert (config["model_type"], config["conv_bias"],
            config["norm_topk_prob"], config["use_expert_bias"]) == (
                "lfm2_moe", False, True, True)
    # the dense layer (published 0|1: both conv), then published 2-5
    assert config["layer_types"] == ["conv"] + PUBLISHED_LAYER_TYPES[2:6] \
        == ["conv", "full_attention", "conv", "conv", "conv"]
    a = config["model"]["arguments"]
    assert config["model"]["factory"] == "conv_gqa_moe_decoder_layers"
    assert (a["width"], a["heads"], a["kv_heads"], a["head_width"],
            a["conv_taps"], a["ffn"], a["experts"], a["top_k"],
            a["expert_width"], a["routed_scale"], a["theta"], a["eps"]) == (
                2048, 32, 8, 2048 // 32, 3, 7168, 32, 4, 1792, 1.0, 1e6,
                1e-5)
    assert a["route_eps"] == 1e-6 and a["tied_head"] is True
    assert "shared_width" not in a
    # the writers into the stream start at 0.02 / sqrt(2 x 24 layers)
    assert a["init_std"] == 0.02
    assert a["out_init_std"] == pytest.approx(0.02 / 48 ** 0.5, rel=1e-4)
    assert a["layer_types"] == ["conv", "attention", "conv", "conv", "conv"]
    assert (len(a["layer_types"]), a["dense_layers"], a["experts_held"],
            a["first_expert"], a["vocab"]) == (
                config["num_hidden_layers"], config["num_dense_layers"],
                config["num_experts"], 0, config["vocab_size"]) == (
                    5, 1, 8, 0, 16384)
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts held, at least an eighth of the vocabulary
    assert a["vocab"] * 4 == 65536
    assert "4 chips share each layer" in config["deployment"]
    for item in ("norms", "head_width", "qk_norm", "positions",
                 "short_conv", "attention", "router", "tied_head",
                 "router_bias", "auxiliary_loss", "solver",
                 "initialisation", "data"):
        assert config["assumed"][item]
    assert "8.34 B" in config["assumed"]["tied_head"]
    # the buffer holds the most a step can send, and no file sets one
    assert "capacity" not in a
    assert traffic["batch"] == 2 and config["input_shape"] == [8193]
    assert traffic["runner"] == "train_lm"
    assert train_lm.routed_rows(config, traffic["batch"]) == 65536
    assert "65,536 rows" in config["buffer"]
    data = config["dataset"]
    assert (data["train_rows"], data["validation_rows"],
            data["label_kinds"]) == (4096, 16, 16384)
    assert "status" not in config
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the limits order as they must
    limits = config["reference"]
    assert limits["module"] == "conv_gqa_moe_decoder"
    assert limits["control_operand"] == "float8_e4m3fn"
    assert 0 < limits["max_rms_diff"] < limits["max_rel_diff"] < 1
    assert 0 < limits["max_grad_diff"] < limits["max_update_diff"] < 1
    assert "PLACEHOLDER" not in repr(config)


def test_step_cost_against_hand_counts():
    """The issue's arithmetic: 507.8 M parameters held, the table once;
    at 8,192 tokens a sequence's attention layer multiplies 33,558,528
    causal pairs at width 64; the conv mixers' products are the largest
    part of 21 TFLOP, attention 8 %."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    cost = reference.step_cost(config, traffic["batch"])
    n = reference.parameter_counts(config["model"]["arguments"])
    assert n["conv"] == 2048 * 6144 + 2048 * 3 + 2048 * 2048 == 16783360
    assert n["attention"] == 2048 * (2048 + 2 * 512) + 2048 * 2048 \
        == 10485760
    assert (n["dense_ffn"], n["expert"], n["router"], n["vocabulary"],
            n["tables"]) == (44040192, 11010048, 65536, 33554432, 1)
    assert cost["parameters"] == 4 * 16783360 + 10485760 + 44040192 \
        + 4 * (65536 + 8 * 11010048) + 33554432 == 507797504
    assert cost["bytes"] == 28 * cost["parameters"]
    assert cost["tokens"] == 16384
    assert reference.allowed_pairs(8192) == 33558528
    # the PUBLISHED head width: q.k and p.v at 64, not a 128-lane tile
    per_pair = 3 * 32 * 2 * (64 + 64)
    assert cost["full_attention_flops"] == cost["attention_flops"] \
        == 2 * 33558528 * per_pair
    assert cost["routed_assignments"] == 4 * 16384 * 4 * 8 / 32 == 4 * 16384
    assert cost["routed_flops"] == 3 * 4 * 16384 * 2 * 11010048
    filter_flops = 3 * 4 * 16384 * 2048 * (2 * 3 + 2)
    assert cost["short_conv_flops"] == filter_flops \
        + 3 * 2 * 16384 * 4 * (2048 * 6144 + 2048 * 2048)
    assert 20e12 < cost["flops"] < 22e12
    share = {key: cost[key] / cost["flops"] for key in (
        "short_conv_flops", "routed_flops", "attention_flops")}
    assert 0.30 < share["short_conv_flops"] < 0.33
    assert 0.19 < share["routed_flops"] < 0.22
    assert 0.07 < share["attention_flops"] < 0.09
    assert filter_flops < 0.001 * cost["flops"]
    assert cost["flops_per_image"] == cost["flops"] / 2
    # untied, the table is held twice and the operations are the same
    untied = copy.deepcopy(config)
    untied["model"]["arguments"]["tied_head"] = False
    two = reference.step_cost(untied, 2)
    assert two["parameters"] == cost["parameters"] + 33554432
    assert two["flops"] == cost["flops"]
    # a row fewer halves the tokens, the pairs, the assignments
    half = reference.step_cost(config, 1)
    for key in ("flops", "tokens", "full_attention_flops", "routed_flops",
                "routed_assignments", "short_conv_flops"):
        assert 2 * half[key] == cost[key], key


# -- the readers --------------------------------------------------------------


def fake_context(steps=2):
    """What a traced chip run's op names look like: the full-causal
    kernels by name, once, the short convolution's passes by their
    6,144-wide shapes, and look-alikes that are neither."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    ops = {
        "%veles_flash_fwd.9 = bf16[64,8192,64]{2,1,0} custom-call()": 0.030,
        "%veles_flash_dq = bf16[64,8192,64]{2,1,0} custom-call()": 0.030,
        "%veles_flash_dkv.4 = (bf16[16,8192,64]) custom-call()": 0.040,
        # the input projection, the gate-filter-gate pass, its backward
        "%fusion.7 = bf16[2,8192,6144]{2,1,0} fusion(bf16[2,8192,2048]"
        "{2,1,0} %a, bf16[2048,6144]{1,0} %w)": 0.012,
        "%fusion.8 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,6144]"
        "{2,1,0} %bcx, f32[2048,3]{1,0} %k)": 0.004,
        "%fusion.9 = f32[2048,6144]{1,0} fusion(bf16[16384,2048]{1,0} "
        "%a, bf16[16384,6144]{1,0} %d)": 0.014,
        # neither: 6,144 LEADING, another width, a mention by name
        "%fusion.1 = f32[6144,2048]{1,0} fusion(f32[12582912]{0} %w)": 0.5,
        "%fusion.2 = bf16[2,8192,7168]{2,1,0} fusion()": 0.5,
        "%fusion.3 = f32[16384,16384]{1,0} fusion(%veles_flash_fwd)": 0.5}
    registry = {"train.steps": 10, "moe.assignments": 10 * 4 * 16384,
                "moe.dropped_assignments": 0}
    return {
        "trace": {"steps": steps, "window_s": 1.0, "busy_s": 0.9,
                  "chips": 1, "gap_seconds": {}, "modules": ["jit_step"],
                  "op_seconds": ops},
        "registry": registry, "steps": 10, "config": config,
        "traffic": traffic, "chips": 1, "device_kind": "TPU v5 lite",
        "step_cost": reference.step_cost(config, traffic["batch"]),
        "routed_rows": train_lm.routed_rows(config, traffic["batch"])}


def test_each_reader_on_a_made_up_trace():
    context = fake_context()
    read = bench_run.read_layer_metrics(MANIFEST, CELL, context)
    assert set(read) == set(NEW_METRICS) == {
        m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer",
                                                  CELL)}
    assert read["short_conv_ms_per_step.train"] == pytest.approx(
        1e3 * 0.030 / 2)
    assert read["narrow_attention_ms_per_step.train"] == pytest.approx(
        1e3 * 0.100 / 2)
    cost = context["step_cost"]
    assert read["narrow_attention_roofline_pct.train"] == pytest.approx(
        100 * cost["full_attention_flops"] / 197e12 / 0.050)
    # the twins ARE the accepted readers' readings
    for twin, accepted in (
            ("narrow_attention_ms_per_step.train",
             "gqa_attention_ms_per_step.train"),
            ("narrow_attention_roofline_pct.train",
             "gqa_attention_roofline_pct.train")):
        assert read[twin] == bench_run.load_reader(accepted).read(context)
    # the width comes from the configuration, not from a literal
    context["config"] = dict(context["config"], hidden_size=1024)
    assert bench_run.load_reader("short_conv_ms_per_step.train").read(
        context) == 0.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_its_cell(name):
    metric = bench_run.find(MANIFEST["per_layer"], name, "metric")
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "train_images_per_s"
    module = bench_run.load_reader(name)
    assert module.__doc__.startswith(module.LAYER + ":")
    assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"],
        metric["source"])
    # an untraced run: nothing
    bare = {"trace": None, "registry": {}, "steps": 3, "config": {}}
    assert module.read(bare) is None


def test_readers_find_nothing_in_a_program_that_lacks_what_they_read():
    """A configuration with no short convolution (every accepted one)
    gives the conv reader nothing; a trace without the kernels reads
    0.0 ms and no share; none raises."""
    _, accepted, _ = bench_run.load_cell(MANIFEST, "kanana2_train_t8k_b2")
    context = {"trace": {"steps": 2, "op_seconds": {
        "%fusion.1 = f32[128,6144]{1,0} fusion()": 0.5}}, "registry": {},
        "steps": 5, "step_cost": {"flops": 1.0, "bytes": 1.0},
        "config": accepted, "chips": 1, "device_kind": "TPU v5 lite"}
    read = {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS}
    assert read == {"short_conv_ms_per_step.train": None,
                    "narrow_attention_ms_per_step.train": 0.0,
                    "narrow_attention_roofline_pct.train": None}


def test_the_share_cannot_pass_100_percent_by_construction():
    """The operations are the model's pairs at width 64.  Kernels that
    did nothing but multiply them at the chip's peak would read 100 %;
    kernels that multiply every pair of the 136 visited tiles a head at
    a width padded to 128 lanes do 2 x 1.06 x the model's work — at the
    peak, 47 %."""
    context = fake_context(steps=1)
    cost, ops = context["step_cost"], context["trace"]["op_seconds"]
    at_peak = cost["full_attention_flops"] / 197e12
    for name in list(ops):
        ops[name] = at_peak / 3 if "%veles_flash" in name[:14] else 0.0
    reader = bench_run.load_reader("narrow_attention_roofline_pct.train")
    assert reader.read(context) == pytest.approx(100.0)
    padded = 2 * 136 * 512 * 512 / reference.allowed_pairs(8192)
    for name in list(ops):
        ops[name] *= padded
    assert reader.read(context) == pytest.approx(100 / padded)
    assert 46 < 100 / padded < 48


def test_accepted_entries_are_a_prefix_and_new_ones_follow():
    """What the driver holds a program PR to, in the form the next
    append survives: what the benchmark had, in the order it had it, is
    a PREFIX of each list, and this PR's entries follow it all."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + 3] == NEW_METRICS
    assert len(set(names)) == len(names)
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs[:4] == ACCEPTED_CONFIGS and configs[4] == "lfm2_8b_a1b"
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[:5] == ACCEPTED_CELLS and cells[5] == CELL
    # the accepted lists name the cells they named
    for metric in MANIFEST["per_layer"][:len(ACCEPTED)]:
        assert CELL not in metric["workloads"]
    # one four-chip cell, as before
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["chips"] == 4] == ["alexnet_train_dp4_b1024"]


def test_the_new_entries_keep_the_manifests_form():
    """Names of at most 64 letters, digits, ``_``, ``.`` and ``-``; a
    ``why`` and a ``source`` of at most 200 characters on one line; just
    the keys the manifest's accepted entries have."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    added = {group: [bench_run.find(MANIFEST[group], n, group)
                     for n in names] for group, names in ADDED.items()}
    for group, entries in added.items():
        for entry in entries:
            assert set(entry) == set(MANIFEST[group][0]), entry["name"]
            assert name.match(entry["name"])
            for key in ("why", "source", "layer"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text, (entry["name"], key)
    config, = added["configs"]
    cell, = added["workloads"]
    assert all(name.match(key) for key in config["reduced"])
    assert name.match(cell["traffic"]) and cell["chips"] == 1
    assert os.path.isfile(os.path.join(REPO, config["file"]))
    assert [c["file"] for c in MANIFEST["configs"]].count(
        config["file"]) == 1
    accepted = MANIFEST["per_layer"][:len(ACCEPTED)]
    for metric in added["per_layer"]:
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] == "device_trace"
        assert metric["layer"] in {m["layer"] for m in accepted}
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", metric["name"] + ".py"))
    # the traffic mix and its runner are the accepted files
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


# -- the runner at toy width --------------------------------------------------


TOY_CONFIG = {
    "name": "toy_conv_decoder", "source": "tests", "reduced": [],
    "hidden_size": 64, "conv_L_cache": 3,
    "model": {"factory": "conv_gqa_moe_decoder_layers", "arguments": {
        "vocab": 96, "width": 64,
        "layer_types": ["conv", "attention", "conv"], "dense_layers": 1,
        "heads": 8, "kv_heads": 2, "head_width": 8, "conv_taps": 3,
        "ffn": 96, "experts": 16, "experts_held": 4, "first_expert": 4,
        "top_k": 3, "expert_width": 32, "route_eps": 1e-6, "theta": 100.0,
        "eps": 1e-5, "lr": 3e-3, "out_init_std": 0.01,
        "router_bias_std": 0.01}},
    "input_shape": [33], "dtype": "float32",
    "dataset": {"train_rows": 256, "validation_rows": 8,
                "label_kinds": 96, "zipf_exponent": 1.0},
    "reference": {"module": "conv_gqa_moe_decoder", "max_rel_diff": 1e-4,
                  "max_rms_diff": 1e-5, "max_loss_diff": 1e-5,
                  "max_grad_diff": 1e-3, "max_update_diff": 0.05,
                  "control_operand": "bfloat16",
                  "reason": "float32 on the CPU; bfloat16 is the "
                            "precision below"},
}
TOY_TRAFFIC = {
    "name": "toy_train_lm", "runner": "train_lm", "batch": 2,
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


def toy_context(batch=2, seed=(1 << 31) + 20261004, seconds=0.6):
    import jax
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    lines = []
    return types.SimpleNamespace(
        cell={"name": CELL, "config": "toy_conv_decoder",
              "traffic": "toy_train_lm", "chips": 1},
        config=copy.deepcopy(TOY_CONFIG),
        traffic=dict(TOY_TRAFFIC, batch=batch),
        seed=seed, seconds=seconds, trace=False, keep_trace="",
        started=time.perf_counter(),
        say=lambda fmt, *args: lines.append(fmt % args if args else fmt),
        chips=1, devices=jax.devices()[:1], device_kind="TPU v5 lite",
        device=device, lines=lines)


def test_the_accepted_runner_at_toy_width_and_two_rows(_settings_put_back):
    """The third decoder family through the language-model runner as it
    is: Launcher -> StandardWorkflow -> auto-fuse -> FusedTrainer with
    the Prefetcher, the snapshotter and the rows resident (it checks
    each), a seed beyond 31 bits, the first train step against this
    family's reference with ONE table for both uses, the half-minibatch
    fault and the control refused."""
    ctx = toy_context()
    result = train_lm.run(ctx)
    compared = result["compared"]
    beyond = [name for name, (number, limit) in compared.items()
              if not number <= limit]
    assert beyond == [] and result["correct"], ctx.lines
    assert not any("NOT CORRECT" in line for line in ctx.lines)
    assert -compared["half_batch_grad_diff_above"][0] > 0.1
    assert compared["half_batch_grad_diff_above"][1] == \
        -ctx.config["reference"]["max_grad_diff"]
    assert -compared["control_rms_diff_above"][0] > 1e-4
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert result["metrics"]["train_images_per_s"] > 0
    assert result["metrics"]["setup_s"] > 0
    assert compared["dropped_assignments"] == [0, 0]
    assert compared["compiles_in_window"] == [0, 0]
    assert compared["logits_rms_diff"][0] < 1e-5
    assert compared["first_step_loss_diff"][0] < 1e-6
    assert compared["first_step_grad_diff"][0] < 1e-4
    assert compared["first_step_update_diff"][0] < 0.05
    assert any("(0 compile request(s)" in line for line in ctx.lines), \
        ctx.lines
    # every array's gradient was compared but the head's matrix, which
    # the head does not own: the table's is the sum of both uses
    line, = [line for line in ctx.lines if "gradients within" in line]
    compared_arrays = set(re.findall(r"(\d\.(?:weights|bias)) ", line))
    assert compared_arrays == {"0.weights", "1.weights", "1.bias",
                               "2.weights", "2.bias", "3.weights",
                               "3.bias", "4.bias"}
    layers = result["layers"]
    assert layers["tokens_per_step"] == 2 * 32
    assert layers["routed_rows"] == 2 * 32 * 3
    assert layers["registry"]["train.tokens"] == \
        result["attempted"] * 2 * 32
    assert layers["registry"]["moe.dropped_assignments"] == 0
    assert {name for name in layers["registry_whole_run"]
            if name.startswith("moe.load.")} == {
                "moe.load.l%d.e%d" % (layer, expert)
                for layer in (0, 1) for expert in range(4)}
    assert layers["step_cost"]["full_attention_flops"] > 0
    # untraced: no per-layer metric; traced, the three
    assert bench_run.read_layer_metrics(MANIFEST, CELL, layers) == {}
    layers["trace"] = fake_context()["trace"]
    traced = bench_run.read_layer_metrics(MANIFEST, CELL, layers)
    assert set(traced) == set(NEW_METRICS)
    line = bench_run.result_line(MANIFEST, ctx, result, ctx.devices)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert layers["registry_whole_run"]["snapshot.exports"] == 1
    assert numpy.isfinite(result["metrics"]["train_images_per_s"])


def test_a_reference_that_leaves_the_heads_use_out_is_refused(
        _settings_put_back, monkeypatch):
    """What the comparison is for.  A reference whose table took no
    gradient from the head on the rows the sequence never reads (what a
    second, untied table would give the embedding) is off the tied
    program's by the head's use of those rows, and the run is not
    correct."""
    ctx = toy_context(seconds=0.3)
    plain = reference.row_gradients

    def one_use(*args, **kwargs):
        total, count, logits, grads, loads = plain(*args, **kwargs)
        row = numpy.asarray(args[2])
        table = numpy.asarray(args[1][0]["weights"])
        kept = numpy.zeros_like(table)
        kept[numpy.unique(row)] = numpy.asarray(
            grads[0]["weights"])[numpy.unique(row)]
        grads[0] = dict(grads[0], weights=kept)
        return total, count, logits, grads, loads

    monkeypatch.setattr(reference, "row_gradients", one_use)
    result = train_lm.run(ctx)
    assert not result["correct"]
    assert result["compared"]["first_step_grad_diff"][0] > 0.05
    assert any("first_step_grad_diff" in line and "NOT CORRECT" in line
               for line in ctx.lines), ctx.lines
