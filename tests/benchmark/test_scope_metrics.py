"""The twelve readers of device time by the program's own scopes
(``benchmark/scope_metrics.py``) on made-up contexts: nothing untraced,
nothing — and never an exception — where the program's API is absent,
gives None or raises, a hand-checked number where it gives a table; the
manifest's accepted entries are still a prefix and the twelve follow it.
And, in a form an append survives, ALL that seven frozen tests of this
directory assert (they compare their cell's list, or what its readers
find in a made-up trace, with one PR's metrics, which every later append
to the cell ends: ``tests/conftest.py`` marks the seven stale): each is
run as it stands, on the manifest's entries its PR knew.  A CPU run
says what the readers compute; every time in PERF.md comes from the
chip."""

import contextlib
import importlib
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark import scope_metrics  # noqa: E402

from veles_tpu.compiler import STEP_SCOPES  # noqa: E402
from veles_tpu.models.decoder import DecoderLayer  # noqa: E402
from veles_tpu.observe import xla_introspect  # noqa: E402

MANIFEST = bench_run.load_manifest()
ALEXNET, MLP, DP4, KANANA, TRINITY, LFM2 = (
    "alexnet_train_b256", "mnist_mlp_train_b100", "alexnet_train_dp4_b1024",
    "kanana2_train_t8k_b2", "trinity_mini_train_t8k_b1",
    "lfm2_8b_a1b_train_t8k_b2")
DEVICE_PACED = [ALEXNET, DP4, KANANA, TRINITY, LFM2]
DECODERS = [KANANA, TRINITY, LFM2]

#: the per-layer metrics the benchmark had, in the order it had them
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
    "moe_buffer_fill_pct.train", "short_conv_ms_per_step.train",
    "narrow_attention_ms_per_step.train",
    "narrow_attention_roofline_pct.train"]

#: this PR's twelve, in ISSUE 37's order: (name, unit, cells, what the
#: reader gives on ``made_up_context``)
READERS = [
    ("forward_ms_per_step.train", "ms", DEVICE_PACED, 1 + 2 + 64),
    ("backward_ms_per_step.train", "ms", DEVICE_PACED, 8 + 16 + 128 + 512),
    ("recompute_ms_per_step.train", "ms", DECODERS, 4 + 32 + 256),
    ("update_ms_per_step.train", "ms", DEVICE_PACED, 1024),
    ("scope_unattributed_pct.train", "%", DEVICE_PACED,
     100 * 6144 / 8191.0),
    ("attention_scope_ms_per_step.train", "ms", DECODERS, 1 + 4 + 8),
    ("dense_ffn_scope_ms_per_step.train", "ms", DECODERS, 16),
    ("router_scope_ms_per_step.train", "ms", DECODERS, 32),
    ("routed_experts_scope_ms_per_step.train", "ms", DECODERS, 128),
    ("shared_experts_scope_ms_per_step.train", "ms", [KANANA, TRINITY],
     256),
    ("layer_glue_ms_per_step.train", "ms", DECODERS, 2),
    ("head_loss_ms_per_step.train", "ms", DECODERS, 64 + 512),
]
NAMES = [row[0] for row in READERS]

L3 = "jit(step)/transpose(jvp(l3_DecoderLayer))/jvp(l3_DecoderLayer)/"
#: (instruction, its op_name in the made-up table, ms a step): powers of
#: two, so every sum says which leaves it holds
LEAVES = [
    ("%fusion.1 = f32[8,4]{1,0} fusion(%p)",
     "jit(step)/jvp(l3_DecoderLayer)/attention/dot_general", 1),
    ("%fusion.2 = f32[8,4]{1,0} fusion(%p)",
     "jit(step)/jvp(l3_DecoderLayer)/rsqrt", 2),
    ("%veles_flash_fwd.3 = (bf16[8,4]{1,0}, f32[8]{0}) custom-call(%q)",
     L3 + "checkpoint/rematted_computation/attention/veles_flash_fwd", 4),
    ("%fusion.4 = f32[8,4]{1,0} fusion(%p)",
     L3 + "checkpoint/attention/transpose", 8),
    ("%fusion.5 = f32[8,4]{1,0} fusion(%p)",
     L3 + "checkpoint/dense_ffn/dot_general", 16),
    ("%fusion.6 = f32[8,4]{1,0} fusion(%p)",
     L3 + "checkpoint/rematted_computation/router/top_k", 32),
    ("%fusion.7 = f32[8,96]{1,0} fusion(%p)",
     "jit(step)/jvp(l5_DecoderHead)/dot_general", 64),
    ("%fusion.8 = f32[8,4]{1,0} fusion(%p)",
     L3 + "checkpoint/routed_experts/while/body/ragged_dot", 128),
    ("%fusion.9 = f32[8,4]{1,0} fusion(%p)",
     L3 + "checkpoint/rematted_computation/shared_experts/mul", 256),
    ("%fusion.10 = f32[8,96]{1,0} fusion(%p)",
     "jit(step)/transpose(jvp(loss))/mul", 512),
    ("%fusion.11 = f32[64]{0} fusion(%p)", "jit(step)/update/sub", 1024),
    # in the table with no scope; another program's op under a name of
    # the step's; a loop, whose body's ops the trace also holds
    ("%copy.12 = f32[64]{0} copy(%p)", "", 2048),
    ("%fusion.1 = s32[100]{0} fusion(%rows)", None, 4096),
    ("%while.13 = (s32[], f32[8,4]{1,0}) while(%t), body=%b",
     L3 + "checkpoint/routed_experts/while", 8192),
]


def made_up_context(steps=2):
    return {"trace": {
        "steps": steps, "window_s": 1.0, "busy_s": 0.9,
        "op_seconds": {text: steps * ms / 1e3 for text, _, ms in LEAVES}},
        "registry": {}, "steps": 10}


def made_up_table():
    return {xla_introspect.instruction_key(text): op_name
            for text, op_name, _ in LEAVES if op_name is not None}


@pytest.fixture
def asked_anew():
    """``scope_metrics`` says each thing once a process: each test is a
    process of its own."""
    scope_metrics._said.clear()
    yield
    scope_metrics._said.clear()


@pytest.fixture
def program_with_a_table(asked_anew, monkeypatch):
    monkeypatch.setattr(xla_introspect, "instruction_scopes",
                        lambda name: made_up_table())
    monkeypatch.setattr(xla_introspect, "scope_names", lambda name: {
        "parts": list(DecoderLayer.PART_SCOPES),
        "step_scopes": STEP_SCOPES})


def raises(*args, **kwargs):
    raise RuntimeError("the program's API fell over")


# -- each reader -------------------------------------------------------------


@pytest.mark.parametrize("name,unit,cells,expected", READERS)
def test_a_reader_reads_its_leaves(program_with_a_table, name, unit, cells,
                                   expected):
    reader = bench_run.load_reader(name)
    assert reader.read(made_up_context()) == pytest.approx(expected)
    # per traced step, whatever their number
    assert reader.read(made_up_context(steps=5)) == pytest.approx(expected)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_finds_nothing_in_an_untraced_run(program_with_a_table,
                                                   name):
    bare = {"trace": None, "registry": {}, "steps": 3}
    assert bench_run.load_reader(name).read(bare) is None


@pytest.mark.parametrize("how", ["absent", "none", "raises", "join_raises",
                                 "names_raise"])
@pytest.mark.parametrize("name", NAMES)
def test_a_reader_never_raises(asked_anew, monkeypatch, capsys, name, how):
    """A program from before the API, one that has no table to give, an
    API that falls over at any of its three calls: the reader returns
    None."""
    if how == "absent":
        monkeypatch.delattr(xla_introspect, "instruction_scopes")
    elif how == "none":
        monkeypatch.setattr(xla_introspect, "instruction_scopes",
                            lambda name: None)
    elif how == "raises":
        monkeypatch.setattr(xla_introspect, "instruction_scopes", raises)
    elif how == "join_raises":
        monkeypatch.setattr(xla_introspect, "instruction_scopes",
                            lambda name: made_up_table())
        monkeypatch.setattr(xla_introspect, "device_seconds_by_scope",
                            raises)
    else:
        monkeypatch.setattr(xla_introspect, "instruction_scopes",
                            lambda name: made_up_table())
        monkeypatch.setattr(xla_introspect, "scope_names", raises)
    assert bench_run.load_reader(name).read(made_up_context()) is None
    assert capsys.readouterr().err.count(
        "benchmark: no device time by scope") == 1


def test_the_partitions_add_up(program_with_a_table):
    """forward + recompute + backward + update + the unattributed are the
    leaves; the parts and the glue are the ``DecoderLayer`` scopes'."""
    context = made_up_context()
    read = {name: bench_run.load_reader(name).read(context)
            for name in NAMES}
    leaves = sum(ms for _, _, ms in LEAVES) - 8192  # less the loop
    assert sum(read[n] for n in NAMES[:4]) == pytest.approx(
        leaves * (1 - read["scope_unattributed_pct.train"] / 100))
    joined = scope_metrics.by_scope(context)
    layers = 1e3 * sum(seconds for (layer, _, _), seconds in joined.items()
                       if layer == "DecoderLayer") / 2
    assert sum(read[n] for n in NAMES[5:11]) == pytest.approx(layers)
    assert ("DecoderLayer", "routed_experts", "backward") in joined
    assert joined[(None, None, None)] == pytest.approx(2 * 6.144)


def test_another_programs_trace_reads_as_unattributed(
        asked_anew, monkeypatch, capsys):
    """A table none of the trace's ops is an instruction of (a recorded
    trace fed to a toy run): whatever has no entry is under ``(None,
    None, None)``, so the times read 0.0 and the blind spot 100 %."""
    monkeypatch.setattr(
        xla_introspect, "instruction_scopes",
        lambda name: {"%fusion.99 f32[3]": "jit(step)/jvp(l0_Conv)/x"})
    context = made_up_context()
    for name, unit, _, _ in READERS:
        assert bench_run.load_reader(name).read(context) == (
            100.0 if unit == "%" else 0.0), name
    assert not capsys.readouterr().err


def test_a_program_described_later_is_read(asked_anew, monkeypatch):
    """An ask that found no program does not stand for the process: the
    next one, once a program is there, reads it."""
    monkeypatch.setattr(xla_introspect, "instruction_scopes",
                        lambda name: None)
    reader = bench_run.load_reader(NAMES[0])
    assert reader.read(made_up_context()) is None
    monkeypatch.setattr(xla_introspect, "instruction_scopes",
                        lambda name: made_up_table())
    monkeypatch.setattr(xla_introspect, "scope_names", lambda name: {
        "parts": list(DecoderLayer.PART_SCOPES),
        "step_scopes": STEP_SCOPES})
    assert reader.read(made_up_context()) == pytest.approx(READERS[0][3])


@pytest.mark.parametrize("cell", DEVICE_PACED + [MLP])
def test_an_exception_in_the_programs_api_cannot_reach_run_py(
        asked_anew, monkeypatch, capsys, cell):
    """``run.py::read_layer_metrics`` over the twelve as the cell lists
    them, the program's API falling over: they are left out of the line,
    nothing raises, one line on standard error says why."""
    monkeypatch.setattr(xla_introspect, "instruction_scopes", raises)
    twelve = dict(MANIFEST, per_layer=[
        m for m in MANIFEST["per_layer"] if m["name"] in NAMES])
    listed = bench_run.cell_metrics(twelve, "per_layer", cell)
    assert bool(listed) == (cell != MLP)
    assert bench_run.read_layer_metrics(
        twelve, cell, made_up_context()) == {}
    assert capsys.readouterr().err.count(
        "no device time by scope: RuntimeError") == bool(listed)


# -- the manifest ------------------------------------------------------------


def test_accepted_entries_are_a_prefix_and_the_twelve_follow():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + 12] == NAMES
    assert len(set(names)) == len(names)
    assert [c["name"] for c in MANIFEST["configs"]][:5] == [
        "alexnet", "mnist_mlp", "kanana2_30b_a3b", "trinity_mini",
        "lfm2_8b_a1b"]
    assert [w["name"] for w in MANIFEST["workloads"]][:6] == [
        ALEXNET, MLP, DP4, KANANA, TRINITY, LFM2]
    # this PR brings no configuration and no cell
    assert len(MANIFEST["configs"]) == 5 and len(MANIFEST["workloads"]) == 6


@pytest.mark.parametrize("name,unit,cells,expected", READERS)
def test_a_new_metric_is_declared_as_its_file_says(name, unit, cells,
                                                   expected):
    metric = bench_run.find(MANIFEST["per_layer"], name, "metric")
    assert metric == {
        "name": name, "unit": unit, "better": "lower",
        "source": "device_trace", "layer": "Fused step (device)",
        "moves": "train_images_per_s", "workloads": cells}
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$", name)
    assert MLP not in cells
    module = bench_run.load_reader(name)
    assert module.__doc__.startswith(module.LAYER + ":")
    assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"])


# -- what seven frozen tests assert, on the entries their PRs knew -----------


def on_the_entries_its_pr_knew(frozen, monkeypatch, cell):
    """The frozen module sees the per-layer entries its PR brought or
    listed its cell in, so its literal comparison of the cell's list
    holds whatever later PRs appended; that the full manifest still
    lists them for the cell, in the accepted order, is checked here."""
    new = frozen.NEW_METRICS  # names, or {name: the cells it lists}
    known = [name for name in new
             if not isinstance(new, dict) or cell in new[name]]
    listed = [m["name"] for m in bench_run.cell_metrics(
        MANIFEST, "per_layer", cell)]
    assert [n for n in listed if n in known] == [
        n for n in ACCEPTED if n in known]
    assert len(listed) > len(known)  # why the frozen test is stale
    monkeypatch.setattr(frozen, "MANIFEST", dict(MANIFEST, per_layer=[
        m for m in MANIFEST["per_layer"] if m["name"] in known]))


@contextlib.contextmanager
def settings_put_back(frozen):
    """What the frozen modules' ``_settings_put_back`` fixture does."""
    saved = dict(frozen.root.common.snapshot.__dict__)
    precision = frozen.root.common.engine.precision_type
    try:
        yield
    finally:
        frozen.root.common.engine.precision_type = precision
        frozen.root.common.snapshot.__dict__.clear()
        frozen.root.common.snapshot.__dict__.update(saved)


def test_all_the_frozen_lfm2_reader_test_asserts(asked_anew, monkeypatch):
    frozen = importlib.import_module("tests.benchmark.test_lfm2")
    on_the_entries_its_pr_knew(frozen, monkeypatch, LFM2)
    frozen.test_each_reader_on_a_made_up_trace()


def test_all_the_frozen_lfm2_runner_test_asserts(asked_anew, monkeypatch):
    """A whole toy run, then the cell's readers on a made-up trace: the
    frozen test wants PR 35's three and no other, and the twelve read
    that trace too (all of it unattributed)."""
    frozen = importlib.import_module("tests.benchmark.test_lfm2")
    on_the_entries_its_pr_knew(frozen, monkeypatch, LFM2)
    with settings_put_back(frozen):
        frozen.test_the_accepted_runner_at_toy_width_and_two_rows(None)


def test_all_the_frozen_trinity_reader_test_asserts(asked_anew, monkeypatch):
    frozen = importlib.import_module("tests.benchmark.test_trinity_mini")
    on_the_entries_its_pr_knew(frozen, monkeypatch, TRINITY)
    frozen.test_each_reader_on_a_made_up_trace_and_registry()


@pytest.mark.parametrize("runner,batch", [
    ("train_lm", 2), ("train_lm", 1), ("train_lm_b1", 1)])
def test_all_the_frozen_trinity_runner_test_asserts(
        asked_anew, monkeypatch, runner, batch):
    frozen = importlib.import_module("tests.benchmark.test_trinity_mini")
    on_the_entries_its_pr_knew(frozen, monkeypatch, TRINITY)
    with settings_put_back(frozen):
        frozen.test_the_runners_at_toy_width(None, runner, batch)


def test_all_the_frozen_kanana_test_asserts_and_the_twelve_on_its_program(
        asked_anew, monkeypatch, capsys):
    """``test_train_lm.py::test_lm_runner_yields_every_declared_metric``
    as it stands: a whole toy run through the runner, then the cell's
    readers on a made-up trace.  The run described its program, so the
    twelve then join the made-up trace with the toy's OWN table: no op
    of the one is an instruction of the other, all of it unattributed."""
    frozen = importlib.import_module("tests.benchmark.test_train_lm")
    on_the_entries_its_pr_knew(frozen, monkeypatch, KANANA)
    with settings_put_back(frozen):
        frozen.test_lm_runner_yields_every_declared_metric(None)
    table = xla_introspect.instruction_scopes(scope_metrics.PROGRAM)
    assert table and not set(table) & set(made_up_table())
    twelve = dict(MANIFEST, per_layer=[
        m for m in MANIFEST["per_layer"] if m["name"] in NAMES])
    assert bench_run.read_layer_metrics(
        twelve, KANANA, made_up_context()) == {
            name: 100.0 if unit == "%" else 0.0
            for name, unit, cells, _ in READERS if KANANA in cells}
    assert "no device time by scope" not in capsys.readouterr().err
