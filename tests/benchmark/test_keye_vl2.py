"""The ``keye_vl2_30b_a3b`` configuration, its cell
``keye_vl2_train_t16k_b1`` and its five per-layer readers, on the CPU:
the file is the published configuration cut as it says, ``step_cost``
agrees with counts made by hand (the SELECTED pairs at the published
head width, the indexer's scores over every causal pair), each reader
reads a made-up trace and registry and finds nothing in a program that
lacks what it reads, no share can pass 100 %, the manifest's accepted
entries are still a prefix with the new ones after them, and the
accepted runner ``train_lm_b1`` yields the cell's metrics at toy width
through the product's normal path — ``correct``, the fault and the
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
from benchmark.references import sparse_gqa_moe_decoder as reference  # noqa: E402,E501
from benchmark.runners import (  # noqa: E402
    train_lm, train_lm_b1, train_lm_pieces)

from veles_tpu import backends  # noqa: E402
from veles_tpu.config import root  # noqa: E402

MANIFEST = bench_run.load_manifest()
CELL = "keye_vl2_train_t16k_b1"
CONFIG = "keye_vl2_30b_a3b"
NEW_METRICS = ["sparse_attention_ms_per_step.train",
               "sparse_attention_roofline_pct.train",
               "indexer_scope_ms_per_step.train",
               "indexer_roofline_pct.train",
               "sparse_tile_occupancy_pct.train"]
#: how many per-layer metrics, configurations and cells the benchmark had
#: before this cell, and the last of each (``test_lfm2.py`` and the
#: earlier files pin the order of what comes before)
ACCEPTED = (42, "head_loss_ms_per_step.train")
ACCEPTED_CONFIGS = (5, "lfm2_8b_a1b")
ACCEPTED_CELLS = (6, "lfm2_8b_a1b_train_t8k_b2")

#: the catalog row ``Keye-VL-2.0-30B-A3B`` of the model-configs guide
#: (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/
#: config.json), every number of its ``config``, written here by hand
PUBLISHED = {
    "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "moe_intermediate_size": 768,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000, "vocab_size": 151936}
PUBLISHED_GROUPS = {
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "mlp_only_layers": [], "tie_word_embeddings": False,
    "use_sliding_window": False, "sliding_window": None,
    "attention_bias": False, "norm_topk_prob": True}


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every number of the catalog's row under its own key; the reduced
    keys, and only they, differ; the nested groups whole; the factory's
    arguments repeat the widths; the file states the deployment and
    what it assumed."""
    cell, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_t16k_b1", 1)
    differs = sorted(key for key, value in PUBLISHED.items()
                     if config[key] != value)
    entry = bench_run.find(MANIFEST["configs"], CONFIG, "config")
    assert differs == sorted(config["reduced"]) == sorted(
        entry["reduced"]) == sorted([
            "num_hidden_layers", "num_experts", "num_local_experts",
            "vocab_size"])
    for key, value in PUBLISHED_GROUPS.items():
        assert config[key] == value, key
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert config["model_type"] == "KeyeVL2"
    a = config["model"]["arguments"]
    sa = config["sa_config"]
    assert config["model"]["factory"] == "gqa_moe_decoder_layers"
    assert (a["width"], a["heads"], a["kv_heads"], a["head_width"],
            a["experts"], a["top_k"], a["expert_width"], a["theta"],
            a["eps"]) == (2048, 32, 4, 128, 128, 8, 768, 1e7, 1e-6)
    assert (a["index_heads"], a["index_width"], a["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert a["router"] == "softmax" and a["shared_width"] == 0
    assert a["dense_layers"] == 0 and a["rope"] == [True] * 4
    assert a["out_gate"] is False and a["post_norms"] is False
    assert a["layer_types"] == ["selected"] * 4
    assert a["init_std"] == 0.02
    assert a["out_init_std"] == pytest.approx(0.02 / 96 ** 0.5, rel=1e-4)
    assert (len(a["layer_types"]), a["experts_held"], a["first_expert"],
            a["vocab"]) == (config["num_hidden_layers"],
                            config["num_experts"], 0,
                            config["vocab_size"]) == (4, 16, 0, 18992)
    # the guide's floors: four routed layers, 8 or more experts held, at
    # least an eighth of the vocabulary
    assert a["vocab"] * 8 == 151936 and a["experts_held"] >= 8
    assert "8 chips share each layer" in config["deployment"]
    for item in ("norms", "qk_norm", "positions", "attention", "indexer",
                 "selection", "indexer_loss", "router", "auxiliary_loss",
                 "solver", "initialisation", "data", "vision_tower"):
        assert config["assumed"][item]
    assert "capacity" not in a
    assert traffic["batch"] == 1 and config["input_shape"] == [16385]
    assert traffic["runner"] == "train_lm_pieces"
    assert train_lm.routed_rows(config, traffic["batch"]) == 131072
    assert "131,072 rows" in config["buffer"]
    data = config["dataset"]
    assert (data["train_rows"], data["validation_rows"],
            data["label_kinds"]) == (2048, 8, 18992)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    limits = config["reference"]
    assert limits["module"] == "sparse_gqa_moe_decoder"
    assert limits["control_operand"] == "float8_e4m3fn"
    assert 0 < limits["max_rms_diff"] < limits["max_rel_diff"] < 1
    assert 0 < limits["max_grad_diff"] < limits["max_update_diff"] < 1
    # the indexer's pieces one by one: every one of them, the fault's
    # among them, and a limit a zeroed piece (1.0) exceeds
    weights, bias = reference.layer_pieces(a, a["width"])
    indexer = [name for name, _ in weights + bias if "index" in name
               or name in ("w_iq", "w_ik", "w_iw")]
    assert sorted(limits["pieces"]) == sorted(indexer)
    assert set(limits["piece_fault"]) <= set(limits["pieces"])
    assert limits["max_grad_diff"] < limits["max_piece_grad_diff"] < 1


def test_step_cost_against_hand_counts():
    """The issue's arithmetic: 465.4 M parameters held; a layer keeps
    31,458,304 of 134,225,920 causal pairs at 16,384 tokens; the
    attention over them is 6.18 TFLOP a step and the indexer 2.21: its
    projections and their weights' gradient 0.59, its scores 1.10, their
    gradient over the selected pairs 0.52."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    cost = reference.step_cost(config, traffic["batch"])
    n = reference.parameter_counts(config["model"]["arguments"])
    assert n["attention"] == 2048 * (4096 + 2 * 512) + 4096 * 2048 \
        == 18874368
    assert n["indexer"] == 2048 * (16 * 64 + 64 + 16) == 2260992
    assert (n["router"], n["expert"], n["vocabulary"]) == (
        262144, 3 * 2048 * 768, 18992 * 2048)
    assert cost["parameters"] == 4 * (18874368 + 2260992 + 262144
                                      + 16 * 4718592) + 2 * 38895616
    assert 465.3e6 < cost["parameters"] < 465.5e6
    assert cost["bytes"] == 28 * cost["parameters"]
    assert cost["tokens"] == 16384
    assert reference.selected_pairs(16384, 2048) == 31458304
    assert reference.allowed_pairs(16384) == 134225920
    assert cost["selected_pairs"] == 4 * 31458304 == 125833216
    # the PUBLISHED head width, the selected pairs, 3 x the forward
    assert cost["sparse_attention_flops"] == cost["attention_flops"] \
        == 4 * 31458304 * 3 * 32 * 2 * (128 + 128)
    assert 6.18e12 < cost["sparse_attention_flops"] < 6.19e12
    forward, backward = 134225920 * 2048, 31458304 * 4096
    # the input is detached: forward and the weights' gradient only
    projections = 2 * 2 * 16384 * 2260992
    assert cost["indexer_flops"] == 4 * (projections + forward + backward)
    assert forward == pytest.approx(274.9e9, rel=1e-3)
    assert backward == pytest.approx(128.9e9, rel=1e-3)
    assert projections == pytest.approx(148.2e9, rel=1e-3)
    assert 2.20e12 < cost["indexer_flops"] < 2.21e12
    # every matrix once: the indexer's in indexer_flops, not twice
    assert cost["flops"] == 3 * 2 * 16384 * (
        4 * 18874368 + 4 * 262144 + 18992 * 2048) \
        + cost["routed_flops"] + cost["sparse_attention_flops"] \
        + cost["indexer_flops"]
    assert cost["routed_assignments"] == 4 * 16384 * 8 * 16 / 128
    assert cost["routed_flops"] == 3 * 4 * 16384 * 2 * 4718592
    assert 21e12 < cost["flops"] < 23e12
    assert cost["flops_per_image"] == cost["flops"]
    two = reference.step_cost(config, 2)
    for key in ("flops", "tokens", "sparse_attention_flops",
                "indexer_flops", "routed_flops", "routed_assignments"):
        assert two[key] == 2 * cost[key], key


# -- the readers --------------------------------------------------------------


def fake_context(steps=2):
    """What a traced chip run's op names and counters look like: the
    sparse kernels by name and a look-alike, the occupied tiles beside
    the causal ones."""
    _, config, traffic = bench_run.load_cell(MANIFEST, CELL)
    ops = {
        "%veles_sparse_fwd.3 = bf16[32,16384,128]{2,1,0} custom-call()":
            0.2,
        "%veles_sparse_dq = bf16[32,16384,128]{2,1,0} custom-call()": 0.2,
        "%veles_sparse_dkv.1 = (bf16[4,16384,128]) custom-call()": 0.2,
        "%veles_flash_fwd = bf16[32,16384,128]{2,1,0} custom-call()": 0.5,
        "%fusion.3 = f32[16384,16384]{1,0} fusion(%veles_sparse_fwd)": 0.5}
    registry = {"train.steps": 10, "sparse.occupied_tiles": 4 * 10 * 500,
                "sparse.causal_tiles": 4 * 10 * 528}
    return {
        "trace": {"steps": steps, "window_s": 1.0, "busy_s": 0.9,
                  "chips": 1, "gap_seconds": {}, "modules": ["jit_step"],
                  "op_seconds": ops},
        "registry": registry, "steps": 10, "config": config,
        "traffic": traffic, "chips": 1, "device_kind": "TPU v5 lite",
        "step_cost": reference.step_cost(config, traffic["batch"]),
        "routed_rows": train_lm.routed_rows(config, traffic["batch"])}


def test_each_reader_on_a_made_up_trace(monkeypatch):
    from benchmark import scope_metrics
    context = fake_context()
    # the program's table of scopes for the made-up trace: the indexer's
    # leaves, attention's, and ones of no scope
    joined = {("DecoderLayer", "indexer", "forward"): 0.03,
              ("DecoderLayer", "indexer", "backward"): 0.05,
              ("DecoderLayer", "attention", "forward"): 0.6,
              (None, None, None): 0.1}
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: joined)
    read = bench_run.read_layer_metrics(MANIFEST, CELL, context)
    assert set(read) == set(NEW_METRICS) == {
        m["name"] for m in bench_run.cell_metrics(MANIFEST, "per_layer",
                                                  CELL)}
    assert read["sparse_attention_ms_per_step.train"] == pytest.approx(
        1e3 * 0.6 / 2)
    cost = context["step_cost"]
    assert read["sparse_attention_roofline_pct.train"] == pytest.approx(
        100 * cost["sparse_attention_flops"] / 197e12 / 0.3)
    assert read["indexer_scope_ms_per_step.train"] == pytest.approx(
        1e3 * 0.08 / 2)
    assert read["indexer_roofline_pct.train"] == pytest.approx(
        100 * cost["indexer_flops"] / 197e12 / 0.04)
    assert read["sparse_tile_occupancy_pct.train"] == pytest.approx(
        100 * 500 / 528)


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
    """The parent's program (no sparse kernel, no ``indexer`` scope, no
    tile counters) and one from before the scopes' API: every reader
    returns None and none raises."""
    from benchmark import scope_metrics
    _, accepted, _ = bench_run.load_cell(MANIFEST, "trinity_mini_train_t8k_b1")
    context = {"trace": {"steps": 2, "op_seconds": {
        "%veles_flash_fwd = bf16[32,8192,128]{2,1,0} custom-call()": 0.5}},
        "registry": {"moe.assignments": 5}, "steps": 5,
        "step_cost": {"flops": 1.0, "bytes": 1.0}, "config": accepted,
        "chips": 1, "device_kind": "TPU v5 lite"}
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: {
        ("DecoderLayer", "attention", "forward"): 0.5})
    assert {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    monkeypatch.setattr(scope_metrics, "by_scope", lambda ctx: None)
    assert {name: bench_run.load_reader(name).read(context)
            for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)


def test_the_shares_cannot_pass_100_percent_by_construction():
    """The operations are the model's: kernels that did nothing but
    multiply the selected pairs at the chip's peak read 100 %; kernels
    that multiply every pair of every causal tile read the selection's
    share of them at most."""
    context = fake_context(steps=1)
    cost, ops = context["step_cost"], context["trace"]["op_seconds"]
    at_peak = cost["sparse_attention_flops"] / 197e12
    for name in list(ops):
        ops[name] = at_peak / 3 if "%veles_sparse" in name[:15] else 0.0
    reader = bench_run.load_reader("sparse_attention_roofline_pct.train")
    assert reader.read(context) == pytest.approx(100.0)
    causal = 32 * 33 // 2 * 512 * 512  # every pair of the causal tiles
    every = causal / reference.selected_pairs(16384, 2048)
    for name in list(ops):
        ops[name] *= every
    assert reader.read(context) == pytest.approx(100 / every)
    assert 22 < 100 / every < 23
    # the occupancy is a share of the causal tiles: never above them
    assert bench_run.load_reader("sparse_tile_occupancy_pct.train").read(
        dict(context, registry={"sparse.occupied_tiles": 528,
                                "sparse.causal_tiles": 528})) == 100.0


def test_accepted_entries_are_a_prefix_and_new_ones_follow():
    """What the benchmark had, in the order it had it, is a PREFIX of
    each list, and this PR's entries follow it all; the four-chip cells
    are as many as before."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    count, last = ACCEPTED
    assert names[count - 1] == last
    assert names[count:] == NEW_METRICS
    assert len(set(names)) == len(names)
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs[ACCEPTED_CONFIGS[0] - 1] == ACCEPTED_CONFIGS[1]
    assert configs[ACCEPTED_CONFIGS[0]:] == [CONFIG]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[ACCEPTED_CELLS[0] - 1] == ACCEPTED_CELLS[1]
    assert cells[ACCEPTED_CELLS[0]:] == [CELL]
    for metric in MANIFEST["per_layer"][:count]:
        assert CELL not in metric["workloads"]
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
            assert set(entry) == set(MANIFEST[group][-len(entries) - 1]) \
                | ({"workloads"} if group == "per_layer" else set()), \
                entry["name"]
            assert name.match(entry["name"])
            for key in ("why", "source", "layer"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text, (entry["name"], key)
    config, = added["configs"]
    cell, = added["workloads"]
    assert all(name.match(key) for key in config["reduced"])
    assert name.match(cell["traffic"]) and cell["chips"] == 1
    assert [c["file"] for c in MANIFEST["configs"]].count(
        config["file"]) == 1
    layers = {m["layer"] for m in MANIFEST["per_layer"][:ACCEPTED[0]]}
    for metric in added["per_layer"]:
        assert metric["better"] in ("lower", "higher")
        assert metric["layer"] in layers
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert metric["source"] in ("device_trace", "program_counter")
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    # a full check still fits: 2 + 14 runs a cell of run_seconds + 60,
    # 180 s more a cell and 1,200 spare, within 43,200 s
    cells = len(MANIFEST["workloads"])
    assert (2 + 14 * cells) * (MANIFEST["run_seconds"] + 60) \
        + 180 * cells + 1200 <= 43200


# -- the runner at toy width --------------------------------------------------


TOY_CONFIG = {
    "name": "toy_sparse_decoder", "source": "tests", "reduced": [],
    "model": {"factory": "gqa_moe_decoder_layers", "arguments": {
        "vocab": 96, "width": 64, "layer_types": ["selected"] * 2,
        "heads": 8, "kv_heads": 2, "head_width": 16, "window": None,
        "ffn": None, "experts": 16, "experts_held": 4, "first_expert": 4,
        "top_k": 3, "expert_width": 32, "shared_width": 0,
        "dense_layers": 0, "theta": 1e4, "eps": 1e-6, "lr": 3e-3,
        "rope": [True, True], "out_gate": False, "post_norms": False,
        "router": "softmax", "index_heads": 2, "index_width": 16,
        "index_topk": 16, "out_init_std": 0.01}},
    "input_shape": [65], "dtype": "float32",
    "dataset": {"train_rows": 256, "validation_rows": 8,
                "label_kinds": 96, "zipf_exponent": 1.0},
    "reference": {"module": "sparse_gqa_moe_decoder", "max_rel_diff": 1e-4,
                  "max_rms_diff": 1e-5, "max_loss_diff": 1e-5,
                  "max_grad_diff": 1e-3, "max_update_diff": 0.05,
                  "control_operand": "bfloat16",
                  "pieces": ["w_iq", "w_ik", "w_iw", "index_k_gain",
                             "index_k_bias"],
                  "piece_fault": ["w_iq", "w_iw"],
                  "max_piece_grad_diff": 1e-3,
                  "reason": "float32 on the CPU; bfloat16 is the "
                            "precision below"},
}
TOY_TRAFFIC = {
    "name": "toy_train_t64_b1", "runner": "train_lm_b1", "batch": 1,
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


def toy_context(seed=(1 << 31) + 20261015, seconds=0.6):
    import jax
    device = backends.Device(backend="cpu")
    device.BACKEND = "tpu"  # instance attr: claims the TPU's entry path
    lines = []
    return types.SimpleNamespace(
        cell={"name": CELL, "config": "toy_sparse_decoder",
              "traffic": "toy_train_t64_b1", "chips": 1},
        config=copy.deepcopy(TOY_CONFIG), traffic=dict(TOY_TRAFFIC),
        seed=seed, seconds=seconds, trace=False, keep_trace="",
        started=time.perf_counter(),
        say=lambda fmt, *args: lines.append(fmt % args if args else fmt),
        chips=1, devices=jax.devices()[:1], device_kind="TPU v5 lite",
        device=device, lines=lines)


def test_the_accepted_runner_at_toy_width(_settings_put_back):
    """The fourth decoder family through the one-row runner as it is:
    Launcher -> StandardWorkflow -> auto-fuse -> FusedTrainer with the
    Prefetcher, the snapshotter and the rows resident (it checks each),
    a seed beyond 31 bits, the first train step against this family's
    reference with the indexer's loss in both, the half-row fault and
    the control refused; the layers' counters reach the registry."""
    ctx = toy_context()
    result = train_lm_b1.run(ctx)
    compared = result["compared"]
    beyond = [name for name, (number, limit) in compared.items()
              if not number <= limit]
    assert beyond == [] and result["correct"], ctx.lines
    assert -compared["half_batch_grad_diff_above"][0] > 0.05
    assert -compared["control_rms_diff_above"][0] > 1e-4
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert compared["logits_rms_diff"][0] < 1e-5
    assert compared["first_step_grad_diff"][0] < 1e-4
    layers = result["layers"]
    assert layers["tokens_per_step"] == 64
    registry = layers["registry"]
    steps = registry["train.steps"]
    pairs = sum(value for name, value in registry.items()
                if name.startswith("sparse.selected_pairs."))
    # 64 tokens, top 16: sum_t min(t + 1, 16) = 904 a layer, ties aside
    assert pairs >= 2 * steps * 904
    assert registry["sparse.causal_tiles"] == 2 * steps
    assert registry["sparse.occupied_tiles"] == 2 * steps
    # the step's loss is the next-token loss; L_I is the gauge's
    line, = [line for line in ctx.lines if "gradients within" in line]
    assert "1.weights" in line and "2.weights" in line
    assert bench_run.read_layer_metrics(MANIFEST, CELL, layers) == {}
    traced = dict(layers, trace=fake_context()["trace"])
    assert bench_run.load_reader("sparse_tile_occupancy_pct.train").read(
        traced) == 100.0
    out = bench_run.result_line(MANIFEST, ctx, result, ctx.devices)
    assert set(out["metrics"]) == {"train_images_per_s", "setup_s"}
    assert numpy.isfinite(result["metrics"]["train_images_per_s"])


def test_the_cells_runner_compares_the_indexers_pieces_at_toy_width(
        _settings_put_back):
    """``train_lm_pieces``: the accepted one-row runner's comparisons as
    they are, then each of the indexer's pieces in each layer against
    the reference's alone, its fault (the pieces ``piece_fault`` names
    zeroed) refused, and the loss of the control and of the one-row
    fault printed."""
    ctx = toy_context(seed=(1 << 31) + 20261016)
    ctx.traffic["runner"] = "train_lm_pieces"
    result = train_lm_pieces.run(ctx)
    compared = result["compared"]
    assert result["correct"], ctx.lines
    assert 0 < compared["piece_grad_diff"][0] < 1e-4
    assert compared["piece_fault_grad_diff_above"] == [-1.0, -1e-3]
    line, = [line for line in ctx.lines if "one by one" in line]
    for layer in (1, 2):
        for piece in TOY_CONFIG["reference"]["pieces"]:
            assert "%d.%s " % (layer, piece) in line
    line, = [line for line in ctx.lines if "the control (" in line]
    assert "the one-row fault" in line
    # the accepted runner's patches are taken back
    assert train_lm.against_reference is train_lm_b1._accepted_check
    assert train_lm.first_step_of_the_program \
        is train_lm_pieces._accepted_first_step


def test_the_cells_runner_refuses_a_program_whose_indexer_learns_nothing(
        _settings_put_back, monkeypatch):
    """The fault the whole arrays' reading cannot see at the cell's size,
    planted in the program: the indexer's loss hands its gradient to
    nothing, so its pieces take none.  Piece by piece they read 1."""
    from veles_tpu.models import decoder
    monkeypatch.setattr(decoder, "_gradients_in",
                        lambda: lambda x, inputs, grads: x)
    ctx = toy_context(seed=(1 << 31) + 20261017)
    result = train_lm_pieces.run(ctx)
    assert not result["correct"]
    assert result["compared"]["piece_grad_diff"][0] == pytest.approx(1.0)
    problem, = [line for line in ctx.lines if "NOT CORRECT" in line
                and "piece_grad_diff" in line]
