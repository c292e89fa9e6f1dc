"""The ``trinity_mini`` configuration, its cell
``trinity_mini_train_t8k_b1`` and its five per-layer readers, on the CPU:
the file is the published configuration cut as it says, ``step_cost``
agrees with counts made by hand, each reader reads a made-up trace and
registry and finds nothing in a parent that lacks the kernels, no share
can pass 100 %, the manifest's new entries follow all it had, and the
cell's runner yields its metrics at toy width through the product's
normal path.

The cell trains on ONE sequence a step.  ``runners/train_lm.py``'s fault
("a step on half the minibatch") is the first sequence's gradient, the
whole step's at batch 1, so through that runner no batch-1 run reads
``correct`` (``test_the_runners_at_toy_width[train_lm-1]`` shows it).
The traffic file therefore names ``runners/train_lm_b1.py``: the same
runner, imported and unedited, its fault cut from the first half of the
row's targets.  A CPU run says what the program counts and whether
results are right; every speed in PERF.md comes from the chip."""

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
from benchmark.references import gqa_window_moe_decoder as reference  # noqa: E402,E501
from benchmark.runners import train_lm, train_lm_b1  # noqa: E402

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

MANIFEST = bench_run.load_manifest()
CELL = "trinity_mini_train_t8k_b1"
NEW_METRICS = ["window_attention_ms_per_step.train",
               "window_attention_roofline_pct.train",
               "gqa_attention_ms_per_step.train",
               "gqa_attention_roofline_pct.train",
               "moe_buffer_fill_pct.train"]
#: the per-layer metrics the benchmark had before this cell, in the
#: order it had them: PR 24-25's sixteen, PR 29's six
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
    "moe_routed_ms_per_step.train", "moe_expert_load_max_over_mean.train"]

#: what PR 33 appended to ``BENCHMARK.json``, each list's entries after
#: all the list had
ADDED = {"configs": ["trinity_mini"], "workloads": [CELL],
         "per_layer": NEW_METRICS}

#: the catalog row ``Trinity-Mini`` of the model-configs guide
#: (https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json),
#: every number of its ``config``, written here by hand
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "moe_intermediate_size": 1024,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.826,
    "sliding_window": 2048, "topk_group": 1, "vocab_size": 200192}


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every number of the catalog's row under its own key; the reduced
    keys, and only they, differ; the factory's arguments repeat the
    widths; the file states the deployment and what it assumed."""
    cell, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_mini", "train_t8k_b1", 1)
    published = dict(PUBLISHED, layer_types=(
        ["sliding_attention"] * 3 + ["full_attention"]) * 8)
    differs = sorted(key for key, value in published.items()
                     if config[key] != value)
    entry = bench_run.find(MANIFEST["configs"], "trinity_mini", "config")
    assert differs == sorted(config["reduced"]) == sorted(
        entry["reduced"]) == sorted([
            "num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size", "layer_types"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/"
        "config.json")
    assert (config["model_type"], config["hidden_act"],
            config["score_func"], config["rope_scaling"],
            config["mup_enabled"], config["route_norm"],
            config["tie_word_embeddings"], config["use_grouped_mm"]) == (
                "afmoe", "silu", "sigmoid", None, True, True, False, True)
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    a = config["model"]["arguments"]
    assert config["model"]["factory"] == "gqa_moe_decoder_layers"
    assert (a["width"], a["heads"], a["kv_heads"], a["head_width"],
            a["window"], a["ffn"], a["experts"], a["top_k"],
            a["expert_width"], a["shared_width"], a["routed_scale"],
            a["theta"], a["eps"]) == (
                2048, 32, 4, 128, 2048, 6144, 128, 8, 1024, 1 * 1024,
                2.826, 1e4, 1e-5)
    assert a["embed_scale"] == pytest.approx(2048 ** 0.5, rel=1e-12)
    assert a["route_eps"] == 1e-20
    # the post-norms' gains start at 1 / sqrt(2 x 32 published layers)
    assert a["post_norm_gain"] == (2 * 32) ** -0.5 == 0.125
    assert a["layer_types"] == ["window"] * 4 + ["full"]
    assert (len(a["layer_types"]), a["dense_layers"], a["experts_held"],
            a["first_expert"], a["vocab"]) == (
                config["num_hidden_layers"], config["num_dense_layers"],
                config["num_experts"], 0, config["vocab_size"]) == (
                    5, 1, 8, 0, 25024)
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts held, an eighth of the vocabulary
    assert a["vocab"] * 8 == 200192
    assert "16 chips share each layer" in config["deployment"]
    for item in ("input_multiplier", "qk_norm", "output_gate", "positions",
                 "sandwich_norms", "router", "router_bias", "solver",
                 "initialisation", "auxiliary_loss", "data"):
        assert config["assumed"][item]
    # the buffer holds the most a step can send, and no file sets one
    assert "capacity" not in a
    assert traffic["batch"] == 1 and config["input_shape"] == [8193]
    assert train_lm.routed_rows(config, traffic["batch"]) == 65536
    assert "65,536 rows" in config["buffer"]
    data = config["dataset"]
    assert (data["train_rows"], data["validation_rows"],
            data["label_kinds"]) == (4096, 8, 25024)
    assert (traffic["runner"], traffic["warmup_train_steps"],
            traffic["interval_stride"], traffic["loss_steps"],
            traffic["trace_after_steps"], traffic["trace_steps"]) == (
                "train_lm_b1", 4, 1, 8, 2, 4)
    assert traffic["runner_why"]
    shared = bench_run.load_json(REPO, "benchmark", "traffic",
                                 "train_t8k_b2.json")
    assert traffic["snapshot"]["why"] and {
        key: value for key, value in traffic["snapshot"].items()
        if key != "why"} == {
            key: value for key, value in shared["snapshot"].items()
            if key != "why"}
    assert traffic["decision"] == shared["decision"]
    assert "status" not in traffic and "status" not in config
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the limits order as they must
    limits = config["reference"]
    assert limits["module"] == "gqa_window_moe_decoder"
    assert limits["control_operand"] == "float8_e4m3fn"
    assert 0 < limits["max_rms_diff"] < limits["max_rel_diff"] < 1
    assert 0 < limits["max_grad_diff"] < limits["max_update_diff"] < 1


def test_step_cost_against_hand_counts():
    """The issue's arithmetic: 504 M parameters held; at 8,192 tokens a
    windowed layer attends over 14,681,088 pairs and a full one over
    33,558,528; 4.5 of a sequence's 17.5 TFLOP are attention; the cell's
    step is one sequence."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    cost = reference.step_cost(config, traffic["batch"])
    n = reference.parameter_counts(config["model"]["arguments"])
    assert n["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27262976
    assert (n["dense_ffn"], n["expert"], n["shared"], n["router"]) == (
        37748736, 6291456, 6291456, 262144)
    assert cost["parameters"] == 5 * 27262976 + 37748736 + 4 * (
        262144 + 6291456 + 8 * 6291456) + 2 * 25024 * 2048 == 504102912
    assert cost["tokens"] == 8192
    assert reference.allowed_pairs(8192, 2048) == 14681088 == sum(
        min(i + 1, 2048) for i in range(8192))
    assert reference.allowed_pairs(8192) == 33558528
    assert reference.allowed_pairs(2048, 2048) == 2048 * 2049 // 2
    per_pair = 3 * 32 * 2 * (128 + 128)
    assert cost["window_attention_flops"] == 4 * 14681088 * per_pair
    assert cost["full_attention_flops"] == 33558528 * per_pair
    assert cost["attention_flops"] == cost["window_attention_flops"] \
        + cost["full_attention_flops"]
    assert cost["routed_assignments"] == 4 * 4096
    assert cost["routed_flops"] == 3 * 4 * 4096 * 2 * 6291456
    assert 17e12 < cost["flops"] < 18e12
    assert 0.25 < cost["attention_flops"] / cost["flops"] < 0.27
    assert cost["flops_per_image"] == cost["flops"]
    assert cost["bytes"] == 28 * cost["parameters"]
    # a second row doubles the tokens, the pairs, the assignments
    double = reference.step_cost(config, 2)
    for key in ("flops", "tokens", "window_attention_flops",
                "full_attention_flops", "routed_flops",
                "routed_assignments"):
        assert double[key] == 2 * cost[key], key
    assert double["flops_per_image"] == cost["flops"]
    assert double["parameters"] == cost["parameters"]


# -- the readers --------------------------------------------------------------


def fake_context(steps=2):
    """What a traced chip run's op names look like: the windowed and the
    full kernels by name, once a layer, and the program's counters."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    ops = {
        "%veles_flash_win_fwd.3 = bf16[32,8192,128]{2,1,0} custom-call()":
            0.010,
        "%veles_flash_win_fwd = bf16[32,8192,128]{2,1,0} custom-call()":
            0.010,
        "%veles_flash_win_dq.1 = bf16[32,8192,128]{2,1,0} custom-call()":
            0.020,
        "%veles_flash_win_dkv.2 = (bf16[4,8192,128]) custom-call()": 0.020,
        "%veles_flash_fwd.9 = bf16[32,8192,128]{2,1,0} custom-call()": 0.030,
        "%veles_flash_dq = bf16[32,8192,128]{2,1,0} custom-call()": 0.030,
        "%veles_flash_dkv.4 = (bf16[4,8192,128]) custom-call()": 0.040,
        # neither: a fusion that only mentions a kernel
        "%fusion.1 = f32[8192,25024]{1,0} fusion(%veles_flash_win_fwd)":
            0.5}
    registry = {"train.steps": 10, "moe.assignments": 10 * 4 * 4096,
                "moe.dropped_assignments": 0}
    for layer in (2, 3, 4, 5):
        for expert in range(8):
            registry["moe.load.l%d.e%d" % (layer, expert)] = 10 * 512
    return {
        "trace": {"steps": steps, "window_s": 1.0, "busy_s": 0.9,
                  "chips": 1, "gap_seconds": {}, "modules": ["jit_step"],
                  "op_seconds": ops},
        "registry": registry, "steps": 10, "config": config,
        "traffic": traffic, "chips": 1, "device_kind": "TPU v5 lite",
        "step_cost": reference.step_cost(config, traffic["batch"]),
        "routed_rows": train_lm.routed_rows(config, traffic["batch"])}


def test_each_reader_on_a_made_up_trace_and_registry():
    context = fake_context()
    read = bench_run.read_layer_metrics(MANIFEST, CELL, context)
    assert set(read) == set(NEW_METRICS) == {
        m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer",
                                                  CELL)}
    assert read["window_attention_ms_per_step.train"] == pytest.approx(
        1e3 * 0.060 / 2)
    assert read["gqa_attention_ms_per_step.train"] == pytest.approx(
        1e3 * 0.100 / 2)
    cost = context["step_cost"]
    assert read["window_attention_roofline_pct.train"] == pytest.approx(
        100 * cost["window_attention_flops"] / 197e12 / 0.030)
    assert read["gqa_attention_roofline_pct.train"] == pytest.approx(
        100 * cost["full_attention_flops"] / 197e12 / 0.050)
    # an even router fills a sixteenth of the buffer
    assert read["moe_buffer_fill_pct.train"] == pytest.approx(100 / 16)
    # the window's train steps stand in where the counter is missing
    del context["registry"]["train.steps"]
    assert bench_run.load_reader("moe_buffer_fill_pct.train").read(
        context) == pytest.approx(100 / 16)


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
    # an untraced run, and a program that has none of it: nothing
    bare = {"trace": None, "registry": {}, "steps": 3}
    assert module.read(bare) is None


def test_readers_find_nothing_in_a_parent_that_lacks_the_kernels():
    """The parent commit runs no windowed kernel and this cell not at
    all; in the latent-attention cell the readers are not listed.  On
    any trace without the names each reader returns nothing or 0.0 and
    never raises."""
    context = {"trace": {"steps": 2, "op_seconds": {
        "%fusion.1 = f32[128,96]{1,0} fusion()": 0.5}}, "registry": {},
        "steps": 5, "step_cost": {"flops": 1.0, "bytes": 1.0},
        "config": {"dtype": "bfloat16"}, "chips": 1,
        "device_kind": "TPU v5 lite"}
    read = {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS}
    assert read == {"window_attention_ms_per_step.train": 0.0,
                    "window_attention_roofline_pct.train": None,
                    "gqa_attention_ms_per_step.train": 0.0,
                    "gqa_attention_roofline_pct.train": None,
                    "moe_buffer_fill_pct.train": None}
    # a reference whose step_cost does not split its attention (the
    # latent-attention cell's) gives the grouped share nothing to read
    context["trace"]["op_seconds"][
        "%veles_flash_fwd.1 = bf16[64,8192,128] custom-call()"] = 0.2
    context["step_cost"]["attention_flops"] = 1e12
    assert bench_run.load_reader(
        "gqa_attention_roofline_pct.train").read(context) is None


def test_the_shares_cannot_pass_100_percent_by_construction():
    """The operations are the model's pairs only.  A kernel that did
    nothing but multiply them at the chip's peak would read 100 %; the
    kernels multiply every pair of the band's 70 tiles a head (18.4 M
    against the model's 14.7 M) — at the peak, 80 %."""
    context = fake_context(steps=1)
    cost, ops = context["step_cost"], context["trace"]["op_seconds"]
    at_peak = cost["window_attention_flops"] / 197e12
    for name in list(ops):
        ops[name] = at_peak / 4 if "veles_flash_win" in name else 0.0
    reader = bench_run.load_reader("window_attention_roofline_pct.train")
    assert reader.read(context) == pytest.approx(100.0)
    visited = 70 * 512 * 512 / reference.allowed_pairs(8192, 2048)
    for name in list(ops):
        ops[name] *= visited
    assert reader.read(context) == pytest.approx(100 / visited)
    assert 79 < 100 / visited < 81
    # the buffer's fill: every row filled is 100 %
    context["registry"]["moe.assignments"] = 10 * 4 * 65536
    assert bench_run.load_reader("moe_buffer_fill_pct.train").read(
        context) == pytest.approx(100.0)


def test_accepted_entries_are_a_prefix_and_new_ones_follow():
    """What the driver holds a program PR to, in a form the next append
    survives: the per-layer metrics the benchmark had, in the order it
    had them, are a PREFIX of the list, and this PR's five follow them
    all; so with the configurations and the cells.  (The frozen forms of
    this — ``test_span_metrics.py``'s and ``test_train_lm.py``'s — pin
    some metrics as the LAST ones, which every append ends: both are
    marked stale in ``tests/conftest.py``.)"""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + 5] == NEW_METRICS
    assert len(set(names)) == len(names)
    assert [c["name"] for c in MANIFEST["configs"]][:4] == [
        "alexnet", "mnist_mlp", "kanana2_30b_a3b", "trinity_mini"]
    assert [w["name"] for w in MANIFEST["workloads"]][:5] == [
        "alexnet_train_b256", "mnist_mlp_train_b100",
        "alexnet_train_dp4_b1024", "kanana2_train_t8k_b2", CELL]
    # the accepted lists name the cells they named
    for metric in MANIFEST["per_layer"][:len(ACCEPTED)]:
        assert CELL not in metric["workloads"]


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
        assert metric["source"] in ("device_trace", "program_counter")
        assert metric["layer"] in {m["layer"] for m in accepted}
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", metric["name"] + ".py"))
    # the runner the traffic file names is a file beside the others
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "runners", "train_lm_b1.py"))


# -- the runner at toy width --------------------------------------------------


TOY_CONFIG = {
    "name": "toy_gqa_decoder", "source": "tests", "reduced": [],
    "model": {"factory": "gqa_moe_decoder_layers", "arguments": {
        "vocab": 96, "width": 64,
        "layer_types": ["window", "window", "full"], "dense_layers": 1,
        "heads": 8, "kv_heads": 2, "head_width": 16, "window": 12,
        "ffn": 96, "experts": 16, "experts_held": 4, "first_expert": 4,
        "top_k": 3, "expert_width": 32, "shared_width": 32,
        "routed_scale": 2.826, "route_eps": 1e-20, "theta": 100.0,
        "eps": 1e-5, "embed_scale": 8.0, "lr": 3e-3,
        "router_bias_std": 0.01}},
    "input_shape": [33], "dtype": "float32",
    "dataset": {"train_rows": 256, "validation_rows": 8,
                "label_kinds": 96, "zipf_exponent": 1.0},
    "reference": {"module": "gqa_window_moe_decoder", "max_rel_diff": 1e-4,
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


def toy_context(batch, seed=(1 << 31) + 20261003, seconds=0.6):
    import jax
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    lines = []
    return types.SimpleNamespace(
        cell={"name": CELL, "config": "toy_gqa_decoder",
              "traffic": "toy_train_lm", "chips": 1},
        config=copy.deepcopy(TOY_CONFIG),
        traffic=dict(TOY_TRAFFIC, batch=batch),
        seed=seed, seconds=seconds, trace=False, keep_trace="",
        started=time.perf_counter(),
        say=lambda fmt, *args: lines.append(fmt % args if args else fmt),
        chips=1, devices=jax.devices()[:1], device_kind="TPU v5 lite",
        device=device, lines=lines)


@pytest.mark.parametrize("runner,batch", [
    ("train_lm", 2), ("train_lm", 1), ("train_lm_b1", 1)])
def test_the_runners_at_toy_width(_settings_put_back, runner, batch):
    """The second decoder family through the language-model runner:
    Launcher -> StandardWorkflow -> auto-fuse -> FusedTrainer with the
    Prefetcher, the snapshotter and the rows resident (it checks each),
    a seed beyond 31 bits, the first train step against this family's
    reference, the fault and the control refused.  ``train_lm.py`` with
    two sequences a step is ``correct``.  With ONE, the cell's batch,
    every number lies inside its limit but the fault's: its "step on half
    the minibatch" is the first sequence's gradient, the whole step's
    here, so it reads 0 off and the runner says a limit has gone slack.
    ``train_lm_b1.py``, the cell's runner, is that runner with the fault
    cut from the first half of the row's targets: ``correct``, every
    other number the same function's."""
    ctx = toy_context(batch)
    ctx.traffic["runner"] = runner
    module = {"train_lm": train_lm, "train_lm_b1": train_lm_b1}[runner]
    accepted_check = train_lm.against_reference
    result = module.run(ctx)
    assert train_lm.against_reference is accepted_check
    compared = result["compared"]
    beyond = [name for name, (number, limit) in compared.items()
              if not number <= limit]
    if (runner, batch) == ("train_lm", 1):
        assert beyond == ["half_batch_grad_diff_above"] \
            and not result["correct"], ctx.lines
        assert compared["half_batch_grad_diff_above"][0] == 0
    else:
        assert beyond == [] and result["correct"], ctx.lines
        assert -compared["half_batch_grad_diff_above"][0] > 0.1
        assert not any("NOT CORRECT" in line for line in ctx.lines)
    assert compared["half_batch_grad_diff_above"][1] == \
        -ctx.config["reference"]["max_grad_diff"]
    assert any("the first half of its targets" in line
               for line in ctx.lines) == (runner == "train_lm_b1")
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert result["metrics"]["train_images_per_s"] > 0
    assert result["metrics"]["setup_s"] > 0
    assert compared["dropped_assignments"] == [0, 0]
    assert compared["compiles_in_window"] == [0, 0]
    assert compared["logits_rms_diff"][0] < 1e-5
    assert compared["first_step_loss_diff"][0] < 1e-6
    assert compared["first_step_grad_diff"][0] < 1e-4
    assert compared["first_step_update_diff"][0] < 0.05
    assert -compared["control_rms_diff_above"][0] > 1e-4
    assert any("(0 compile request(s)" in line for line in ctx.lines), \
        ctx.lines
    layers = result["layers"]
    assert layers["tokens_per_step"] == batch * 32
    assert layers["routed_rows"] == batch * 32 * 3
    assert layers["registry"]["train.tokens"] == \
        result["attempted"] * batch * 32
    assert layers["registry"]["moe.dropped_assignments"] == 0
    assert layers["step_cost"]["window_attention_flops"] > 0
    # untraced: no per-layer metric; traced, the five, the fill from the
    # program's own counters
    assert bench_run.read_layer_metrics(MANIFEST, CELL, layers) == {}
    layers["trace"] = fake_context()["trace"]
    traced = bench_run.read_layer_metrics(MANIFEST, CELL, layers)
    assert set(traced) == set(NEW_METRICS)
    assert traced["moe_buffer_fill_pct.train"] == pytest.approx(
        100.0 * layers["registry"]["moe.assignments"]
        / (batch * 96 * 2 * layers["registry"]["train.steps"]))
    assert 0 < traced["moe_buffer_fill_pct.train"] < 100
    line = bench_run.result_line(MANIFEST, ctx, result, ctx.devices)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert layers["registry_whole_run"]["snapshot.exports"] == 1
    assert numpy.isfinite(result["metrics"]["train_images_per_s"])


def test_the_one_row_runner_refuses_a_larger_minibatch():
    """A cell of two rows a step has ``train_lm.py``'s own fault."""
    ctx = toy_context(2)
    with pytest.raises(AssertionError, match="one row a step"):
        train_lm_b1.run(ctx)
    assert train_lm.against_reference is train_lm_b1._accepted_check
