"""The ``nemotron_twotower_30b_a3b`` configuration, its cell
``nemotron_tt_train_t16k_b1`` and its three per-layer readers, on the
CPU: the file is the published configuration cut as it says (667 M
parameters held), ``step_cost`` counts the chunked scan's products over
the causal pairs within a chunk and its data once, the readers read a
made-up table of scopes and find nothing in a program that lacks what
they read, the manifest's accepted entries are still a prefix with this
cell's after them, and the cell's runner ``train_lm_pieces`` yields the
cell's metrics at toy width through the product's normal path —
``correct``, the scan's pieces compared one by one, every fault and the
control refused.  A CPU run says what the program counts and whether
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
from benchmark.references import ssm_moe_decoder as reference  # noqa: E402
from benchmark.runners import (  # noqa: E402
    train_lm, train_lm_b1, train_lm_pieces)

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

MANIFEST = bench_run.load_manifest()
CELL = "nemotron_tt_train_t16k_b1"
CONFIG = "nemotron_twotower_30b_a3b"
NEW_METRICS = ["ssm_mixer_scope_ms_per_step.train",
               "ssm_scan_ms_per_step.train",
               "ssm_scan_roofline_pct.train"]
#: the benchmark before this cell, in its order (the earlier files pin
#: what each of their PRs added)
ACCEPTED_CONFIGS = ["alexnet", "mnist_mlp", "kanana2_30b_a3b",
                    "trinity_mini", "lfm2_8b_a1b", "keye_vl2_30b_a3b"]
ACCEPTED_CELLS = ["alexnet_train_b256", "mnist_mlp_train_b100",
                  "alexnet_train_dp4_b1024", "kanana2_train_t8k_b2",
                  "trinity_mini_train_t8k_b1", "lfm2_8b_a1b_train_t8k_b2",
                  "keye_vl2_train_t16k_b1"]
ACCEPTED_METRICS = (47, "sparse_tile_occupancy_pct.train")

#: the catalog row ``Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`` of the
#: model-configs guide (https://huggingface.co/nvidia/
#: Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json), every
#: key of its ``config``, written here by hand
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
KINDS = {"M": "ssm", "E": "routed", "*": "attention"}


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every key of the catalog's row under its own name; the reduced
    keys, and only they, differ; the factory's arguments repeat every
    width; the file states the deployment and what it assumed."""
    cell, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_t16k_b1", 1)
    differs = sorted(key for key, value in PUBLISHED.items()
                     if config[key] != value)
    entry = bench_run.find(MANIFEST["configs"], CONFIG, "config")
    assert differs == sorted(config["reduced"]) == sorted(
        entry["reduced"]) == sorted([
            "num_hidden_layers", "hybrid_override_pattern",
            "n_routed_experts", "vocab_size"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-"
        "Base-BF16/blob/main/config.json")
    # the first nine layers of the published pattern
    pattern = config["hybrid_override_pattern"]
    assert pattern == PUBLISHED["hybrid_override_pattern"][:9] \
        == "MEMEM*EME"
    a = config["model"]["arguments"]
    assert config["model"]["factory"] == "hybrid_moe_decoder_layers"
    assert a["layer_types"] == [KINDS[k] for k in pattern]
    assert len(a["layer_types"]) == config["num_hidden_layers"] == 9
    assert (a["width"], a["heads"], a["kv_heads"], a["head_width"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"])
    assert (a["ssm_heads"], a["ssm_head_width"], a["ssm_groups"],
            a["ssm_state"], a["ssm_chunk"], a["conv_taps"]) == (
        config["mamba_num_heads"], config["mamba_head_dim"],
        config["n_groups"], config["ssm_state_size"],
        config["chunk_size"], config["conv_kernel"]) == (
            64, 64, 8, 128, 128, 4)
    assert (a["experts"], a["top_k"], a["expert_width"],
            a["shared_width"], a["routed_scale"], a["eps"]) == (
        PUBLISHED["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"],
        config["moe_shared_expert_intermediate_size"],
        config["routed_scaling_factor"], config["layer_norm_epsilon"])
    # what the factory fixes: relu² experts, a sigmoid router, attention
    # with no position signal, no q/k norm and no gate, an untied head,
    # and Mamba-2's starts, which are the configuration's time steps
    from veles_tpu.models import decoder, zoo
    specs = zoo.hybrid_moe_decoder_layers(**a)
    routed = specs[1 + a["layer_types"].index("routed")]
    attention = specs[1 + a["layer_types"].index("attention")]
    assert routed["expert_act"] == "relu2" == config["mlp_hidden_act"]
    assert routed.get("router", "sigmoid") == "sigmoid"
    assert not routed.get("route_eps")
    assert (attention["rope"], attention["qk_norm"],
            attention["out_gate"]) == (False, False, False)
    assert specs[-1].get("tied_to") is None
    assert decoder.DecoderLayer.SSM_DT_INIT == (
        config["time_step_min"], config["time_step_max"],
        config["time_step_floor"])
    assert decoder.DecoderLayer.SSM_A_INIT == (1.0, 16.0)
    assert a["init_std"] == 0.02
    assert a["out_init_std"] == pytest.approx(0.02 / 52 ** 0.5, rel=1e-4)
    # the guide's floors: four routed layers, 8 or more experts held, at
    # least an eighth of the vocabulary
    assert a["layer_types"].count("routed") == 4
    assert a["experts_held"] == config["n_routed_experts"] == 8
    assert a["first_expert"] == 0
    assert a["vocab"] * 8 == PUBLISHED["vocab_size"] == 8 * config[
        "vocab_size"]
    assert "16 chips share each layer" in config["deployment"]
    for item in ("towers", "norms", "positions", "attention", "mamba",
                 "router", "experts", "auxiliary_loss", "solver",
                 "initialisation", "data"):
        assert config["assumed"][item]
    assert "rope_theta 10,000" in config["assumed"]["positions"]
    assert "capacity" not in a
    assert traffic["batch"] == 1 and config["input_shape"] == [16385]
    assert traffic["runner"] == "train_lm_pieces"
    assert train_lm.routed_rows(config, traffic["batch"]) == 98304
    assert "98,304 rows" in config["buffer"]
    data = config["dataset"]
    assert (data["train_rows"], data["validation_rows"],
            data["label_kinds"]) == (2048, 8, 16384)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    limits = config["reference"]
    assert limits["module"] == "ssm_moe_decoder"
    assert limits["control_operand"] == "float8_e4m3fn"
    assert 0 < limits["max_rms_diff"] < limits["max_rel_diff"] < 1
    assert 0 < limits["max_grad_diff"] < limits["max_update_diff"] < 1
    # the scan's small pieces one by one, the decay's among the fault's
    weights, bias = reference.layer_pieces(
        dict(a, ssm_heads=64), a["width"])
    names = {name for name, _ in weights + bias}
    assert sorted(limits["pieces"]) == sorted(
        ["a_log", "dt_bias", "d_skip", "conv_k", "conv_b",
         "ssm_norm_gain"]) and set(limits["pieces"]) <= names
    assert sorted(limits["piece_fault"]) == ["a_log", "dt_bias"]
    assert limits["max_piece_grad_diff"] < 1


def test_the_parameters_held_and_the_step_cost():
    """667 M parameters held (the issue's table), 38.45 TFLOP a step of
    which the state-space layers are 41 %; the chunked scan's products
    over the causal pairs within a chunk, three times the forward's, and
    its data once."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    cost = reference.step_cost(config, traffic["batch"])
    n = reference.parameter_counts(config["model"]["arguments"])
    assert n["ssm"] == 2688 * 10304 + 6144 * 4 + 4096 * 2688 == 38731776
    assert n["attention"] == 2688 * (4096 + 2 * 256) + 4096 * 2688 \
        == 23396352
    assert (n["router"], n["expert"], n["shared"], n["vocabulary"]) == (
        2688 * 128, 2 * 2688 * 1856, 2 * 2688 * 3712, 16384 * 2688)
    assert cost["parameters"] == 4 * 38731776 + 23396352 + 4 * (
        344064 + 8 * 9977856 + 19955712) + 2 * 44040192 == 666894336
    assert abs(cost["parameters"] - 667e6) < 1e6
    assert cost["bytes"] == 28 * cost["parameters"]
    # a chunk of 128: C B^T of 8 groups over its 8,256 causal pairs 128
    # deep, their decayed product with dt x for 64 heads 64 wide, the
    # chunk-end states and their read-back, 64 x 64 x 128 a token each
    per_chunk = (2 * 8 * 128 * 8256 + 2 * 64 * 64 * 8256
                 + 4 * 64 * 64 * 128 * 128)
    assert cost["ssm_scan_flops"] == 3 * 4 * 128 * per_chunk
    assert 0.54e12 < cost["ssm_scan_flops"] < 0.55e12
    # x, B, C bfloat16; dt, y and the 128 chunk states float32
    data = (16384 * (2 * 4096 + 2 * 2 * 1024 + 4 * 64 + 4 * 4096)
            + 4 * 128 * 64 * 64 * 128)
    assert cost["ssm_scan_bytes"] == 3 * 4 * data
    assert cost["attention_flops"] == 134225920 * 3 * 32 * 2 * 2 * 128
    assert cost["routed_assignments"] == 4 * 16384 * 6 * 8 / 128
    assert cost["routed_flops"] == 3 * 4 * 16384 * 6 * 8 / 128 * 2 \
        * 9977856
    assert 38.4e12 < cost["flops"] < 38.5e12
    assert 0.40 < cost["ssm_flops"] / cost["flops"] < 0.42
    assert cost["flops_per_image"] == cost["flops"]
    two = reference.step_cost(config, 2)
    for key in ("flops", "tokens", "ssm_scan_flops", "ssm_scan_bytes",
                "attention_flops", "routed_flops", "routed_assignments"):
        assert two[key] == 2 * cost[key], key


@pytest.mark.parametrize("t, chunk", [(10, 4), (8, 4), (3, 8)])
def test_the_scan_counts_no_padded_token(t, chunk):
    a = dict(ssm_heads=2, ssm_head_width=3, ssm_groups=1, ssm_state=5,
             ssm_chunk=chunk)
    flops, data = reference.scan_cost(t, a)
    lengths = [min(chunk, t - s) for s in range(0, t, chunk)]
    assert flops == sum(2 * 5 * q * (q + 1) // 2 + 2 * 6 * q * (q + 1) // 2
                        + 4 * 6 * 5 * q for q in lengths)
    assert data == t * (2 * 6 + 4 * 5 + 4 * 2 + 4 * 6) \
        + 4 * len(lengths) * 6 * 5


# -- the readers --------------------------------------------------------------


def fake_context(steps=2):
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    return {
        "trace": {"steps": steps, "window_s": 1.0, "busy_s": 0.9,
                  "chips": 1, "gap_seconds": {}, "modules": ["jit_step"],
                  "op_seconds": {"%fusion.1 = f32[8]{0} fusion()": 0.1}},
        "registry": {}, "steps": 10, "config": config, "traffic": traffic,
        "chips": 1, "device_kind": "TPU v5 lite",
        "step_cost": reference.step_cost(config, traffic["batch"]),
        "routed_rows": train_lm.routed_rows(config, traffic["batch"])}


#: the program's table of scopes for a made-up trace: the mixer's leaves,
#: the scan's, attention's, and ones of no scope
JOINED = {("DecoderLayer", "ssm_mixer", "forward"): 0.03,
          ("DecoderLayer", "ssm_mixer", "backward"): 0.05,
          ("DecoderLayer", "ssm_scan", "forward"): 0.02,
          ("DecoderLayer", "ssm_scan", "recompute"): 0.02,
          ("DecoderLayer", "ssm_scan", "backward"): 0.06,
          ("DecoderLayer", "attention", "forward"): 0.6,
          (None, None, None): 0.1}


def test_each_reader_on_a_made_up_table_of_scopes(monkeypatch):
    from benchmark import scope_metrics
    context = fake_context()
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: JOINED)
    read = {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS}
    assert read["ssm_mixer_scope_ms_per_step.train"] == pytest.approx(
        1e3 * 0.08 / 2)
    assert read["ssm_scan_ms_per_step.train"] == pytest.approx(
        1e3 * 0.10 / 2)
    cost = context["step_cost"]
    # bound by the bytes: 8.9 GB at 819 GB/s is longer than 0.54 TFLOP
    # at 197 TFLOP/s
    least = cost["ssm_scan_bytes"] / 819e9
    assert least > cost["ssm_scan_flops"] / 197e12
    assert read["ssm_scan_roofline_pct.train"] == pytest.approx(
        100 * least / 0.05)
    # the cell's lists: each metric of this PR reads in its cell
    declared = {m["name"] for m in bench_run.cell_metrics(
        MANIFEST, "per_layer", CELL)}
    assert set(NEW_METRICS) <= declared


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
    bare = {"trace": None, "registry": {}, "steps": 3, "config": {}}
    assert module.read(bare) is None


def test_readers_find_nothing_in_a_program_that_lacks_what_they_read(
        monkeypatch):
    """The parent's program (no ``ssm_*`` scope) and one from before the
    scopes' API: every reader returns None and none raises."""
    from benchmark import scope_metrics
    context = fake_context()
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: {
        ("DecoderLayer", "attention", "forward"): 0.5})
    assert {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: None)
    assert {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)


def test_the_roofline_share_cannot_pass_100_percent_by_construction(
        monkeypatch):
    """A scan scope that took the least time the counted work allows
    reads 100 %; one that took longer reads less."""
    from benchmark import scope_metrics
    context = fake_context(steps=1)
    cost = context["step_cost"]
    least = max(cost["ssm_scan_flops"] / 197e12,
                cost["ssm_scan_bytes"] / 819e9)
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: {
        ("DecoderLayer", "ssm_scan", "backward"): least})
    reader = bench_run.load_reader("ssm_scan_roofline_pct.train")
    assert reader.read(context) == pytest.approx(100.0)
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: {
        ("DecoderLayer", "ssm_scan", "backward"): 4 * least})
    assert reader.read(context) == pytest.approx(25.0)


# -- the manifest ------------------------------------------------------------


def test_accepted_entries_are_a_true_prefix_and_this_cells_follow():
    """What the benchmark had, in the order it had it, is a PREFIX of
    each list, and this PR's entries follow it; nothing is asserted of
    what later PRs append."""
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs[:len(ACCEPTED_CONFIGS) + 1] == ACCEPTED_CONFIGS + [
        CONFIG]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[:len(ACCEPTED_CELLS) + 1] == ACCEPTED_CELLS + [CELL]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    count, last = ACCEPTED_METRICS
    assert names[count - 1] == last
    assert names[count:count + len(NEW_METRICS)] == NEW_METRICS
    assert len(set(names)) == len(names)
    for metric in MANIFEST["per_layer"][:count]:
        assert CELL not in metric.get("workloads", [])
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["chips"] == 4] == ["alexnet_train_dp4_b1024"]


def test_the_new_entries_keep_the_manifests_form():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    added = {"configs": [bench_run.find(MANIFEST["configs"], CONFIG, "c")],
             "workloads": [bench_run.find(MANIFEST["workloads"], CELL, "w")],
             "per_layer": [bench_run.find(MANIFEST["per_layer"], n, "m")
                           for n in NEW_METRICS]}
    for group, entries in added.items():
        for entry in entries:
            assert set(entry) == set(MANIFEST[group][0]) | (
                {"workloads"} if group == "per_layer" else set()), \
                entry["name"]
            assert name.match(entry["name"])
            for key in ("why", "source", "layer"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text, (entry["name"], key)
    config, = added["configs"]
    assert all(name.match(key) for key in config["reduced"])
    assert [c["file"] for c in MANIFEST["configs"]].count(
        config["file"]) == 1
    layers = {m["layer"] for m in MANIFEST["per_layer"][
        :ACCEPTED_METRICS[0]]}
    for metric in added["per_layer"]:
        assert metric["layer"] in layers
        assert metric["better"] in ("lower", "higher")
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    cells = len(MANIFEST["workloads"])
    assert (2 + 14 * cells) * (MANIFEST["run_seconds"] + 60) \
        + 180 * cells + 1200 <= 43200


# -- the runner at toy width --------------------------------------------------


TOY_CONFIG = {
    "name": "toy_ssm_decoder", "source": "tests", "reduced": [],
    "model": {"factory": "hybrid_moe_decoder_layers", "arguments": {
        "vocab": 96, "width": 64,
        "layer_types": ["ssm", "routed", "attention", "ssm", "routed"],
        "heads": 8, "kv_heads": 2, "head_width": 16, "ssm_heads": 4,
        "ssm_head_width": 16, "ssm_groups": 2, "ssm_state": 8,
        "ssm_chunk": 16, "conv_taps": 4, "experts": 16, "experts_held": 4,
        "first_expert": 4, "top_k": 3, "expert_width": 32,
        "shared_width": 48, "routed_scale": 2.5, "eps": 1e-5, "lr": 3e-3,
        "out_init_std": 0.01}},
    "input_shape": [65], "dtype": "float32",
    "dataset": {"train_rows": 256, "validation_rows": 8,
                "label_kinds": 96, "zipf_exponent": 1.0},
    "reference": {"module": "ssm_moe_decoder", "max_rel_diff": 1e-4,
                  "max_rms_diff": 1e-5, "max_loss_diff": 1e-5,
                  "max_grad_diff": 1e-3, "max_update_diff": 0.05,
                  "control_operand": "bfloat16",
                  "pieces": ["a_log", "dt_bias", "d_skip", "conv_k",
                             "conv_b", "ssm_norm_gain"],
                  "piece_fault": ["a_log", "dt_bias"],
                  "max_piece_grad_diff": 1e-3,
                  "reason": "float32 on the CPU; bfloat16 is the "
                            "precision below"},
}
TOY_TRAFFIC = {
    "name": "toy_train_t64_b1", "runner": "train_lm_pieces", "batch": 1,
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


def toy_context(seed=(1 << 31) + 20261018, seconds=0.6):
    import jax
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    lines = []
    return types.SimpleNamespace(
        cell={"name": CELL, "config": "toy_ssm_decoder",
              "traffic": "toy_train_t64_b1", "chips": 1},
        config=copy.deepcopy(TOY_CONFIG), traffic=dict(TOY_TRAFFIC),
        seed=seed, seconds=seconds, trace=False, keep_trace="",
        started=time.perf_counter(),
        say=lambda fmt, *args: lines.append(fmt % args if args else fmt),
        chips=1, devices=jax.devices()[:1], device_kind="TPU v5 lite",
        device=device, lines=lines)


def test_the_cells_runner_at_toy_width(_settings_put_back):
    """The one-row runner that compares named pieces, as it is:
    Launcher -> StandardWorkflow -> auto-fuse -> FusedTrainer with the
    Prefetcher, the snapshotter and the rows resident, a seed beyond 31
    bits, the first train step against the sequential reference, the
    half-row fault and the control refused, the scan's pieces each
    within their limit and their fault refused; the gauges reach the
    registry."""
    ctx = toy_context()
    result = train_lm_pieces.run(ctx)
    compared = result["compared"]
    beyond = [name for name, (number, limit) in compared.items()
              if not number <= limit]
    assert beyond == [] and result["correct"], ctx.lines
    assert compared["logits_rms_diff"][0] < 1e-5
    assert compared["first_step_grad_diff"][0] < 1e-4
    assert 0 < compared["piece_grad_diff"][0] < 1e-4
    assert compared["piece_fault_grad_diff_above"] == [-1.0, -1e-3]
    assert -compared["half_batch_grad_diff_above"][0] > 0.05
    assert -compared["control_rms_diff_above"][0] > 1e-4
    assert result["failed"] == 0 and result["attempted"] >= 8
    line, = [line for line in ctx.lines if "one by one" in line]
    for layer in (1, 4):
        for piece in TOY_CONFIG["reference"]["pieces"]:
            assert "%d.%s " % (layer, piece) in line
    layers = result["layers"]
    assert layers["tokens_per_step"] == 64
    assert layers["registry_whole_run"]["moe.assignments"] > 0
    from veles_tpu.observe.metrics import registry
    assert registry.peek("ssm.chunks").value == 4
    assert bench_run.read_layer_metrics(MANIFEST, CELL, layers) == {}
    out = bench_run.result_line(MANIFEST, ctx, result, ctx.devices)
    assert set(out["metrics"]) == {"train_images_per_s", "setup_s"}
    assert numpy.isfinite(result["metrics"]["train_images_per_s"])
    # the accepted runners' patches are taken back
    assert train_lm.against_reference is train_lm_b1._accepted_check


def test_the_cells_runner_refuses_a_scan_that_loses_its_decays_gradient(
        _settings_put_back, monkeypatch):
    """The fault the whole arrays' reading cannot see, planted in the
    program: the decay's gradient stops at A (``a_log`` and, through
    dt's share of it, part of ``dt_bias``'s), so those pieces read far
    off one by one."""
    import jax
    from veles_tpu.models import decoder
    accepted = decoder.ssd_scan

    def detached(x, dt, a, b, c, chunk):
        return accepted(x, dt, jax.lax.stop_gradient(a), b, c, chunk)

    monkeypatch.setattr(decoder, "ssd_scan", detached)
    ctx = toy_context(seed=(1 << 31) + 20261019)
    result = train_lm_pieces.run(ctx)
    assert not result["correct"]
    assert result["compared"]["piece_grad_diff"][0] > 0.1
    problem, = [line for line in ctx.lines if "NOT CORRECT" in line
                and "piece_grad_diff" in line]
