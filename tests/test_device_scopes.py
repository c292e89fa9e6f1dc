"""Device time by the program's own scopes, on the CPU: ``scope_of``
over the forms an ``op_name`` takes, the table the compiled step of a
toy decoder of each family gives (every part, all four phases, ``loss``
and ``update``), the join with a made-up trace (a loop and its body,
two programs sharing a name, a name the table lacks), what it costs a
run in which nobody asks (nothing), and that the scopes are metadata
only.  A CPU run says what the program names; every time in PERF.md
comes from the chip."""

import importlib
import os
import sys

import jax
import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from veles_tpu import compiler  # noqa: E402
from veles_tpu.config import root  # noqa: E402
from veles_tpu.models import decoder  # noqa: E402
from veles_tpu.observe import xla_introspect as xla  # noqa: E402
from veles_tpu.observe.metrics import registry  # noqa: E402

PARTS = decoder.DecoderLayer.PART_SCOPES
STEP = compiler.STEP_SCOPES
L1 = "jit(step)/transpose(jvp(l1_DecoderLayer))/jvp(l1_DecoderLayer)/"


@pytest.fixture
def _precision(monkeypatch):
    monkeypatch.setattr(root.common.engine, "precision_type", "float32")


# -- scope_of ----------------------------------------------------------------


@pytest.mark.parametrize("op_name,expected", [
    # the forms a checkpointed layer with two parts and a loop gives
    ("jit(step)/jvp(l1_DecoderLayer)/attention/dot_general",
     ("l1_DecoderLayer", "attention", "forward")),
    (L1 + "checkpoint/attention/dot_general",
     ("l1_DecoderLayer", "attention", "backward")),
    (L1 + "checkpoint/rematted_computation/attention/tanh",
     ("l1_DecoderLayer", "attention", "recompute")),
    (L1 + "checkpoint/rematted_computation/dense_ffn/while",
     ("l1_DecoderLayer", "dense_ffn", "recompute")),
    (L1 + "checkpoint/rematted_computation/dense_ffn/while/body/"
     "closed_call/sin", ("l1_DecoderLayer", "dense_ffn", "recompute")),
    ("jit(step)/jvp(l0_DecoderLayer)/dense_ffn/closed_call/while/cond/lt",
     ("l0_DecoderLayer", "dense_ffn", "forward")),
    # a layer that keeps its activations: the part follows the wrapper
    ("jit(step)/transpose(jvp(l3_DecoderLayer))/short_conv/mul",
     ("l3_DecoderLayer", "short_conv", "backward")),
    # no part: the layer's glue
    ("jit(step)/jvp(l2_DecoderLayer)/rsqrt",
     ("l2_DecoderLayer", None, "forward")),
    (L1 + "checkpoint/concatenate", ("l1_DecoderLayer", None, "backward")),
    # the step's own scopes
    ("jit(step)/jvp(loss)/reduce_sum", ("loss", None, "forward")),
    ("jit(step)/transpose(jvp(loss))/mul", ("loss", None, "backward")),
    ("jit(step)/update/sub", ("update", None, "update")),
    ("jit(local_step)/shard_map/update/jit(_where)/select_n",
     ("update", None, "update")),
    # a mesh's gradient merge ends the backward
    ("jit(local_step)/shard_map/grad_sync/psum",
     ("grad_sync", None, "backward")),
    ("jit(local_step)/shard_map/grad_sync/concatenate",
     ("grad_sync", None, "backward")),
    # a nested jit under a part, a Pallas kernel and its custom_vjp rule
    ("jit(step)/jvp(l1_DecoderLayer)/attention/jit(_flash_fwd_jit)/"
     "veles_flash_win_fwd/while/body/cond/branch_1_fun/dot_general",
     ("l1_DecoderLayer", "attention", "forward")),
    (L1 + "checkpoint/attention/jit(_flash_bwd_jit)/veles_flash_win_dkv",
     ("l1_DecoderLayer", "attention", "backward")),
    ("jit(step)/jvp(l4_DecoderLayer)/routed_experts/custom_vjp_call/"
     "while/body/ragged_dot",
     ("l4_DecoderLayer", "routed_experts", "forward")),
    # other layer classes, a mesh's step
    ("jit(local_step)/shard_map/transpose(jvp(l7_All2AllSoftmax))/"
     "dot_general", ("l7_All2AllSoftmax", None, "backward")),
    ("jit(step)/jvp(l0_Conv)/conv_general_dilated",
     ("l0_Conv", None, "forward")),
    # no scope: a parameter, a reducer's body, the step's own glue, a
    # function that only shares a scope's name, nothing
    ("state[0]['weights']", (None, None, None)),
    ("reduce_sum", (None, None, None)),
    ("jit(step)/convert_element_type", (None, None, None)),
    ("jit(update)/sub", (None, None, None)),
    ("", (None, None, None)),
    (None, (None, None, None)),
])
def test_scope_of(op_name, expected):
    assert xla.scope_of(op_name, PARTS, STEP) == expected


def test_the_parts_are_the_layer_classs_not_the_parsers():
    name = "jit(step)/jvp(l1_DecoderLayer)/attention/dot_general"
    assert xla.scope_of(name) == ("l1_DecoderLayer", None, "forward")
    assert xla.scope_of(name, ("dot_general",))[1] == "dot_general"
    # nor are the step's own scopes: its builder names them
    assert xla.scope_of("jit(step)/update/sub") == (None, None, None)
    assert xla.scope_of("jit(step)/solver/sub", (), {"solver": "update"}) == (
        "solver", None, "update")
    assert set(STEP) == {compiler.SCOPE_LOSS, compiler.SCOPE_GRAD_SYNC,
                         compiler.SCOPE_UPDATE}
    assert set(PARTS) == {
        decoder.SCOPE_ATTENTION, decoder.SCOPE_CONV, decoder.SCOPE_ROUTER,
        decoder.SCOPE_ROUTED, decoder.SCOPE_SHARED, decoder.SCOPE_FFN,
        decoder.SCOPE_INDEXER, decoder.SCOPE_SSM, decoder.SCOPE_SCAN}
    assert xla.layer_class("l12_DecoderHead") == "DecoderHead"
    assert xla.layer_class("loss") == "loss"
    assert xla.layer_class(None) is None


# -- the key and the table ---------------------------------------------------


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.3 (param_0.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0:T(8,128)} parameter(0)
  ROOT %tanh.2 = f32[8,4]{1,0:T(8,128)} tanh(%param_0.1), metadata={op_name="jit(step)/jvp(l1_DecoderLayer)/attention/tanh" source_file="a.py" source_line=3}
}

%body.7 (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %arg = (s32[], f32[8,4]{1,0}) parameter(0)
  %sin.4 = f32[8,4]{1,0} sine(%gte.1), metadata={op_name="jit(step)/jvp(l1_DecoderLayer)/dense_ffn/while/body/sin"}
  ROOT %tuple.9 = (s32[], f32[8,4]{1,0}) tuple(%add.1, %sin.4)
}

ENTRY %main.40 (Arg_0.1: f32[8,4]) -> f32[8,4] {
  %Arg_0.1 = f32[8,4]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.421 = f32[8,4]{1,0:T(8,128)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
  %while.15 = (s32[], f32[8,4]{1,0}, /*index=2*/f32[2]{0}) while(%tuple.1), condition=%cond.2, body=%body.7, metadata={op_name="jit(step)/jvp(l1_DecoderLayer)/dense_ffn/while"}
  ROOT %veles_flash_dkv.14 = (bf16[4,8]{1,0:T(8,128)(2,1)}, bf16[4,8]{1,0}) custom-call(%fusion.421), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(l1_DecoderLayer))/attention/veles_flash_dkv"}, backend_config={"a": {"b": 1}}
}
"""


def test_the_table_of_an_optimised_module():
    table = xla.parse_instruction_scopes(HLO)
    assert table["%sin.4 f32[8,4]"].endswith("dense_ffn/while/body/sin")
    loop = table["%while.15 (s32[], f32[8,4], f32[2])"]
    assert loop.endswith("dense_ffn/while")
    assert table["%veles_flash_dkv.14 (bf16[4,8], bf16[4,8])"].endswith(
        "veles_flash_dkv")
    # a fusion with no metadata of its own has its root's
    tanh = "jit(step)/jvp(l1_DecoderLayer)/attention/tanh"
    assert table["%fusion.421 f32[8,4]"] == table["%tanh.2 f32[8,4]"] == tanh
    # what names no scope: a loop's body belongs to the loop, a fused
    # computation to its fusion; the step's parameter keeps what it had
    assert table["%arg (s32[], f32[8,4])"] == loop
    assert table["%tuple.9 (s32[], f32[8,4])"] == loop
    assert table["%param_0.1 f32[8,4]"] == tanh
    assert table["%Arg_0.1 f32[8,4]"] == "x"
    assert len(table) == 9


COMPILER_MADE = """HloModule jit_step, is_scheduled=true

%wide.body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %ragged-dot-none.3 = f32[8,4]{1,0} custom-call(%gte.5), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %t = (s32[], f32[8,4]{1,0}) tuple(%gte.4, %ragged-dot-none.3)
}

ENTRY %main (w: f32[32], x: f32[8,4]) -> f32[8,4] {
  %w = f32[32]{0} parameter(0), metadata={op_name="state[1][\'weights\']"}
  %x = f32[8,4]{1,0} parameter(1), metadata={op_name="x"}
  %convert.9 = bf16[32]{0} convert(%w)
  %reshape.2 = bf16[8,4]{1,0} reshape(%convert.9)
  %copy-start.1 = (bf16[8,4]{1,0:S(1)}, bf16[8,4]{1,0}, u32[]) copy-start(%reshape.2)
  %copy-done.1 = bf16[8,4]{1,0:S(1)} copy-done(%copy-start.1)
  %fusion.7 = f32[8,4]{1,0} fusion(%copy-done.1, %x), kind=kOutput, calls=%f.7, metadata={op_name="jit(step)/jvp(l1_DecoderLayer)/attention/dot_general"}
  %fusion.8 = f32[8,4]{1,0} fusion(%reshape.2), kind=kLoop, calls=%f.8, metadata={op_name="jit(step)/transpose(jvp(l1_DecoderLayer))/jvp(l1_DecoderLayer)/checkpoint/rematted_computation/attention/dot_general"}
  %while.2 = (s32[], f32[8,4]{1,0}) while(%tuple.1), condition=%c, body=%wide.body, metadata={op_name="jit(step)/transpose(jvp(l1_DecoderLayer))/jvp(l1_DecoderLayer)/checkpoint/routed_experts/while"}
  %broadcast.5 = f32[64]{0} broadcast(%constant.1), dimensions={}
  %scatter.6 = f32[64]{0} fusion(%broadcast.5, %fusion.7), kind=kLoop, calls=%f.6
  %reshape.9 = f32[8,8]{1,0} reshape(%scatter.6)
  %fusion.10 = f32[8,4]{1,0} fusion(%reshape.9), kind=kLoop, calls=%f.10, metadata={op_name="jit(step)/transpose(jvp(loss))/mul"}
  %copy.11 = f32[8,4]{0,1} copy(%fusion.10)
  ROOT %out = f32[8,4]{0,1} bitcast(%copy.11)
}
"""


def test_what_the_compiler_made_inherits_by_structure_and_no_guess():
    """On the chip part of a decoder step is in instructions the
    compiler made (PERF.md section 6, PR 37): a ragged product's
    expansion inside a routed loop, which the loop's scope covers, and
    whole vectors' converts, layout copies and zero fills outside every
    scope, which stay unattributed: ``scope_unattributed_pct.train``
    says how much, and nothing is guessed from who reads them."""
    table = xla.parse_instruction_scopes(COMPILER_MADE, STEP)

    def scope(key):
        return xla.scope_of(table[key], PARTS, STEP)

    # a loop's body belongs to the loop, whatever its own op_name says
    assert scope("%ragged-dot-none.3 f32[8,4]") == (
        "l1_DecoderLayer", "routed_experts", "backward")
    assert scope("%p (s32[], f32[8,4])") == (
        "l1_DecoderLayer", "routed_experts", "backward")
    # not the first user's, not the first operand's
    for key in ("%convert.9 bf16[32]", "%reshape.2 bf16[8,4]",
                "%copy-start.1 (bf16[8,4], bf16[8,4], u32[])",
                "%copy-done.1 bf16[8,4]", "%w f32[32]", "%x f32[8,4]",
                "%broadcast.5 f32[64]", "%scatter.6 f32[64]",
                "%reshape.9 f32[8,8]", "%copy.11 f32[8,4]",
                "%out f32[8,4]"):
        assert scope(key) == (None, None, None), key
    assert table["%convert.9 bf16[32]"] == ""
    assert table["%w f32[32]"] == "state[1]['weights']"


def test_an_instruction_no_scope_reaches_keeps_what_it_had():
    text = """HloModule jit_gather

ENTRY %main (rows: s32[100]) -> s32[100] {
  %rows = s32[100]{0} parameter(0), metadata={op_name="rows"}
  ROOT %copy.1 = s32[100]{0} copy(%rows)
}
"""
    assert xla.parse_instruction_scopes(text) == {
        "%rows s32[100]": "rows", "%copy.1 s32[100]": ""}


@pytest.mark.parametrize("text,key", [
    ("%fusion.421 = f32[8192,25024]{1,0:T(8,128)} fusion(f32[8192]{0} %p), "
     "kind=kLoop, calls=%fused_computation.3",
     "%fusion.421 f32[8192,25024]"),
    ("%while.10 = (s32[], bf16[98304,2048]{1,0:T(8,128)(2,1)}, "
     "/*index=2*/s32[16]{0:T(128)S(1)}) while((s32[]) %tuple.3), "
     "condition=%c, body=%b",
     "%while.10 (s32[], bf16[98304,2048], s32[16])"),
    ("%copy-start.3 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) "
     "copy-start(f32[8]{0} %p)",
     "%copy-start.3 (f32[8], f32[8], u32[])"),
    ("%constant.1 = f32[] constant(0)", "%constant.1 f32[]"),
    ("$train.py:12 run", None),
])
def test_the_join_key(text, key):
    assert xla.instruction_key(text) == key


def test_seconds_by_scope_on_a_made_up_trace():
    scopes = {
        "%while.15 (s32[], f32[8,4])": L1 + "checkpoint/routed_experts/while",
        "%fusion.1 f32[8,4]": L1 + "checkpoint/routed_experts/while/body/mul",
        "%fusion.2 f32[8,4]":
            L1 + "checkpoint/rematted_computation/attention/tanh",
        "%fusion.3 f32[16,4]": "jit(step)/jvp(l5_DecoderHead)/dot_general",
        "%fusion.4 f32[]": "jit(step)/update/sub",
        "%copy.1 f32[8]": "",
    }
    t = "{1,0:T(8,128)}"
    op_seconds = {
        # a loop and its body's op: the loop's event spans the op's
        "%while.15 = (s32[], f32[8,4]" + t + ") while(%t), body=%b": 0.5,
        "%fusion.1 = f32[8,4]" + t + " fusion(%p), kind=kLoop": 0.25,
        "%fusion.2 = f32[8,4]" + t + " fusion(%p), kind=kLoop": 0.125,
        # the gather program's op shares a name with the step's, not a
        # shape: it is another program's
        "%fusion.3 = f32[16,4]" + t + " fusion(%q)": 1.0,
        "%fusion.3 = f32[100,784]" + t + " fusion(%rows)": 2.0,
        "%fusion.4 = f32[] fusion(%g)": 4.0,
        # in the table with no scope, and not in the table at all
        "%copy.1 = f32[8]{0} copy(%p)": 8.0,
        "%veles_gather_rows = f32[100,784]{1,0} custom-call(%s)": 16.0,
        "%call.2 = f32[8]{0} call(%p), to_apply=%f": 32.0,
        "%conditional.1 = f32[8]{0} conditional(%i, %a, %b)": 64.0,
    }
    got = xla.device_seconds_by_scope(op_seconds, scopes, PARTS, STEP)
    assert got == {
        ("DecoderLayer", "routed_experts", "backward"): 0.25,
        ("DecoderLayer", "attention", "recompute"): 0.125,
        ("DecoderHead", None, "forward"): 1.0,
        ("update", None, "update"): 4.0,
        (None, None, None): 2.0 + 8.0 + 16.0}
    # nothing twice, nothing lost: the leaves add up to the leaves
    assert sum(got.values()) == sum(op_seconds.values()) - 0.5 - 32 - 64


# -- the program's own table -------------------------------------------------


FAMILIES = {
    # module of the family's toy, the parts it has
    "mla": ("tests.test_decoder", {
        "attention", "dense_ffn", "router", "routed_experts",
        "shared_experts"}),
    "gqa": ("tests.test_decoder_gqa", {
        "attention", "dense_ffn", "router", "routed_experts",
        "shared_experts"}),
    "conv": ("tests.test_decoder_conv", {
        "attention", "short_conv", "dense_ffn", "router",
        "routed_experts"}),
}


def ran_toy(family, recompute=True, **arguments):
    """A toy decoder of the family trained one epoch through its
    ``FusedTrainer``, each layer recomputed in the backward."""
    toy = importlib.import_module(FAMILIES[family][0])
    sw, _ = toy.toy_workflow(max_epochs=1, **arguments)
    if recompute:
        sw.fused_trainer._backward_should_recompute = lambda plans: True
    sw.run()
    return sw


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_compiled_step_names_parts_phases_loss_and_update(
        _precision, family):
    ran_toy(family)
    assert xla.described() == ["fused.step"]
    names = xla.scope_names("fused.step")
    assert set(names["parts"]) == set(PARTS)
    assert names["step_scopes"] == STEP
    with xla.compile_delta() as asked:
        table = xla.instruction_scopes("fused.step")
    # the description is the call's own: jax hands back what it compiled
    assert asked.receipt["new_compiles"] == 0
    assert asked.receipt["backend_compiles"] == 0
    assert xla.instruction_scopes("fused.step") is table
    seen = {xla.scope_of(name, **names) for name in table.values()}
    layers = {layer for layer, _, _ in seen}
    assert {"l0_DecoderEmbedding", "l1_DecoderLayer", "loss",
            "update"} <= layers
    assert any(xla.layer_class(layer) == "DecoderHead" for layer in layers)
    parts = {(part, phase) for layer, part, phase in seen
             if xla.layer_class(layer) == "DecoderLayer"}
    for part in FAMILIES[family][1]:
        for phase in ("forward", "recompute", "backward"):
            assert (part, phase) in parts, (part, phase)
    assert {part for part, _ in parts} == FAMILIES[family][1] | {None}
    assert {phase for _, _, phase in seen} - {None} == {
        "forward", "recompute", "backward", "update"}
    assert ("loss", None, "forward") in seen
    assert ("loss", None, "backward") in seen
    # a loop is in the table beside the ops of its body
    assert any(key.startswith("%while") and "routed_experts" in name
               for key, name in table.items())
    assert any("routed_experts/" in name and "/while/body/" in name
               for name in table.values())


def test_a_step_that_keeps_its_activations_has_no_recompute(_precision):
    ran_toy("mla", recompute=False)
    seen = {xla.scope_of(name, PARTS, STEP)[2]
            for name in xla.instruction_scopes("fused.step").values()}
    assert seen - {None} == {"forward", "backward", "update"}


def test_no_description_no_table_and_nothing_raises(caplog):
    watcher = xla.CompileWatcher(registry=registry)
    assert watcher.instruction_scopes("fused.step") is None
    assert "no instruction scopes for fused.step" in caplog.text
    caplog.clear()
    assert watcher.instruction_scopes("fused.step") is None  # asked once
    assert not caplog.text

    class Refuses(object):
        def _cache_size(self):
            return 1

        def lower(self, *args, **kwargs):
            raise RuntimeError("a compile that fails")

    watcher.watch(Refuses(), "fused.step")
    watcher.describe("fused.step", (1,), {}, parts=("attention",))
    assert watcher.scope_names("fused.step") == {
        "parts": ["attention"], "step_scopes": {}}
    assert watcher.described() == ["fused.step"]
    assert watcher.instruction_scopes("fused.step") is None
    assert "a compile that fails" in caplog.text

    class NoText(Refuses):
        def lower(self, *args, **kwargs):
            return self

        def compile(self):
            return object()  # a jax without as_text

    # another program under the name: the old one's arguments go with it
    watcher.watch(NoText(), "fused.step")
    assert watcher.described() == []
    caplog.clear()
    assert watcher.instruction_scopes("fused.step") is None
    assert "no program is watched and described" in caplog.text
    watcher.describe("fused.step", (1,))
    assert watcher.instruction_scopes("fused.step") is None
    assert "as_text" in caplog.text
    watcher.unwatch("fused.step")
    assert watcher.scope_names("fused.step") == {}


def test_only_shapes_are_kept_of_what_the_step_was_called_with(_precision):
    sw = ran_toy("mla", recompute=False)
    args, kwargs, _ = xla.watcher._described["fused.step"]
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    assert leaves and all(isinstance(leaf, jax.ShapeDtypeStruct)
                          for leaf in leaves)
    assert set(kwargs) == {"step_count"}  # adamw's, as the call's
    state = sw.fused_trainer._state
    assert args[0][1]["weights"].shape == state[1]["weights"].shape
    assert args[0][1]["weights"].sharding == state[1]["weights"].sharding


# -- what it costs a run in which nobody asks --------------------------------


def counted_run(monkeypatch, described):
    """(compile requests, lowerings to MLIR) of one whole toy run."""
    from jax import monitoring
    if not described:  # the parent: nothing is handed over
        monkeypatch.setattr(xla, "describe", lambda *a, **k: None)
    monkeypatch.setattr(
        xla.watcher, "instruction_scopes",
        lambda name: pytest.fail("somebody asked for the table"))
    lowered = []

    def listener(event, duration, **kwargs):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        with xla.compile_delta() as counted:
            ran_toy("mla", recompute=False)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    monkeypatch.undo()
    return counted.receipt["backend_compiles"], len(lowered)


def test_a_run_in_which_nobody_asks_compiles_and_lowers_what_the_parents_does(
        _precision, monkeypatch):
    # once for what a process compiles one time only (the loader's
    # programs, the initialisers): after it a run costs its own step
    ran_toy("mla", recompute=False)
    parent = counted_run(monkeypatch, described=False)
    monkeypatch.setattr(root.common.engine, "precision_type", "float32")
    change = counted_run(monkeypatch, described=True)
    assert parent[0] > 0 and parent[1] > 0
    assert change == parent


# -- scopes are metadata only ------------------------------------------------


def lowered_step(debug_info):
    toy = importlib.import_module(FAMILIES["gqa"][0])
    sw, layers, plans, state, x, y = toy.program_and_batch()
    return jax.jit(compiler._build_step_fn(
        plans, "softmax", bwd_remat=True,
        # a mesh's merge, as far as one device can stand in for it
        grad_sync=lambda grads: jax.tree.map(lambda g: g + g, grads))).lower(
            state, x, y, numpy.float32(4), None,
            step_count=numpy.int32(1)).as_text(debug_info=debug_info)


def test_the_lowered_step_is_the_same_text_but_for_metadata(
        _precision, monkeypatch):
    import contextlib
    scoped, scoped_debug = lowered_step(False), lowered_step(True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, bare_debug = lowered_step(False), lowered_step(True)
    assert scoped == bare
    for scope in ("/routed_experts/", "/grad_sync/", "/update/", "(loss)/"):
        assert scope in scoped_debug and scope not in bare_debug


# -- an operator's profiler session ------------------------------------------


def test_the_profiler_hook_writes_the_table_beside_the_trace_it_closes(
        tmp_path, monkeypatch):
    import json

    from veles_tpu.observe.profile import ProfilerHook
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    session = tmp_path / "plugins" / "profile" / "2026_10_05"
    session.mkdir(parents=True)
    (session / "host.xplane.pb").write_bytes(b"")
    table = {"%fusion.1 f32[8,4]": "jit(step)/update/sub"}
    names = {"parts": ["attention"], "step_scopes": {"update": "update"}}
    monkeypatch.setattr(xla, "described", lambda: ["fused.step"])
    monkeypatch.setattr(xla, "instruction_scopes", lambda name: table)
    monkeypatch.setattr(xla, "scope_names", lambda name: names)
    hook = ProfilerHook(str(tmp_path), 0, 1)
    hook.state = "tracing"
    hook.stop()
    assert hook.state == "done"
    assert json.loads((session / "device_scopes.json").read_text()) == {
        "fused.step": dict(names, instructions=table)}
    # a session that was never open, a program with no table, a workflow
    # that is not fused (nothing described, nobody asked): no file
    (session / "device_scopes.json").unlink()
    hook.stop()
    monkeypatch.setattr(xla, "instruction_scopes", lambda name: None)
    hook.state = "tracing"
    hook.stop()
    monkeypatch.setattr(xla, "described", lambda: [])
    monkeypatch.setattr(
        xla, "instruction_scopes",
        lambda name: pytest.fail("asked for a program nobody described"))
    hook.state = "tracing"
    hook.stop()
    assert not (session / "device_scopes.json").exists()


def test_trace_scopes_prints_the_recorded_traces_time_by_scope(
        tmp_path, capsys):
    """``scripts/trace_scopes.py`` on the benchmark's recorded TPU trace
    and a table that names two of its instructions."""
    import importlib.util
    import json

    from benchmark import reduce_trace
    recorded = os.path.join(REPO, "tests", "benchmark", "data",
                            "alexnet_train_b256.xplane.pb")
    trace = reduce_trace.reduce(recorded)
    leaves = {text: seconds for text, seconds in trace["op_seconds"].items()
              if not text.split(" = ")[0].startswith(
                  ("%while", "%conditional", "%call"))}
    first, second = sorted(leaves, key=leaves.get)[-2:]
    scopes = tmp_path / "device_scopes.json"
    scopes.write_text(json.dumps({"fused.step": {
        "parts": [], "step_scopes": STEP, "instructions": {
            xla.instruction_key(first): "jit(step)/jvp(l0_Conv)/conv",
            xla.instruction_key(second):
                "jit(step)/transpose(jvp(l0_Conv))/conv"}}}))
    spec = importlib.util.spec_from_file_location(
        "trace_scopes", os.path.join(REPO, "scripts", "trace_scopes.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert script.main([recorded, str(scopes)]) == 0
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rows = {tuple(key): ms for key, ms in printed["ms_per_step"]}
    steps = printed["steps"]
    assert rows[("Conv", None, "forward")] == pytest.approx(
        1e3 * leaves[first] / steps)
    assert rows[("Conv", None, "backward")] == pytest.approx(
        1e3 * leaves[second] / steps)
    assert sum(rows.values()) == pytest.approx(
        1e3 * sum(leaves.values()) / steps)
    assert script.main([recorded, str(scopes), "--step-module",
                        "jit_nothing"]) == 1
