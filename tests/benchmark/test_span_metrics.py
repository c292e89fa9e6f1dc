"""The readers of the program's own spans and kernel names
(``benchmark/span_metrics.py`` and the seven ``layer_metrics`` files that
use it), each on a hand-made context: nothing in an untraced run, the
registry arithmetic, which instruction names count as a kernel's, 0.0 on
the recorded pre-name trace, and nothing — without raising — against a
program that has no such histogram."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import reduce_trace  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import span_metrics  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "alexnet_train_b256.xplane.pb")
MANIFEST = bench_run.load_manifest()

#: reader -> (histogram it reads, what one second per step reads as,
#: whether it divides by the histogram's count instead of train steps)
HOST_READERS = {
    "trainer_dispatch_ms_per_step.train": ("step.dispatch_s", 1e3, True),
    "trainer_stage_us_per_step.train": ("step.stage_s", 1e6, False),
    "decision_sync_ms_per_step.train": ("decision.sync_s", 1e3, False),
    "sched_hop_us_per_step.train": ("workflow.hop_s", 1e6, False),
    "loader_gather_us_per_step.train": ("loader.gather_s", 1e6, False),
}
KERNEL_READERS = {
    "conv_wgrad_ms_per_step.train": "veles_conv_wgrad",
    "pool_bwd_ms_per_step.train": "veles_pool_bwd",
}
BOTH_CELLS = ["alexnet_train_b256", "mnist_mlp_train_b100"]

#: what the names look like in a trace: the instruction's text
WGRAD = ("%veles_conv_wgrad.7 = (f32[25,128,256]{2,1,0:T(8,128)}, "
         "f32[1,256]{1,0:T(1,128)}, bf16[186624,256]{1,0:T(8,128)(2,1)}) "
         "custom-call(bf16[25,186624,128]{2,1,0:T(8,128)(2,1)} %fusion.9)"
         ", custom_call_target=\"tpu_custom_call\"")
WGRAD_PLAIN = "%veles_conv_wgrad = (f32[9,128,128]{2,1,0}) custom-call()"
POOL = ("%veles_pool_bwd.2 = bf16[256,56,64,128]{3,2,1,0:T(8,128)(2,1)} "
        "custom-call(bf16[256,56,64,128]{3,2,1,0} %pad.3), "
        "custom_call_target=\"tpu_custom_call\"")
#: the parent's name for conv2's wgrad, and two near misses
OLD_WGRAD = ("%transpose(jvp(jit(_fused_wgrad_jit))).7 = (f32[25,128,256]"
             "{2,1,0}) custom-call(), custom_call_target=\"tpu_custom_call\"")
LOOKALIKE = "%veles_conv_wgrad_tail.1 = f32[8]{0} fusion(f32[8]{0} %p)"
CONSUMER = "%fusion.3 = f32[25,128,256]{2,1,0} fusion(%veles_conv_wgrad.7)"


def context(registry=None, trace=True, steps=10, op_seconds=None):
    return {"steps": steps, "eval_steps": 2, "registry": registry or {},
            "trace": {"steps": 4, "op_seconds": op_seconds or {}}
            if trace else None}


def reader(name):
    return bench_run.load_reader(name)


@pytest.mark.parametrize("name", sorted(HOST_READERS) +
                         sorted(KERNEL_READERS))
def test_reader_reads_nothing_in_an_untraced_run(name):
    """Per-layer metrics are read in the traced run only."""
    histogram = HOST_READERS.get(name, ("none",))[0]
    registry = {histogram + ".sum": 3.0, histogram + ".count": 5}
    assert reader(name).read(context(registry, trace=False)) is None


@pytest.mark.parametrize("name", sorted(HOST_READERS))
def test_host_reader_arithmetic(name):
    histogram, scale, by_count = HOST_READERS[name]
    registry = {histogram + ".sum": 0.5, histogram + ".count": 20,
                "other.sum": 99.0, "other.count": 1}
    got = reader(name).read(context(registry, steps=10))
    assert got == pytest.approx(scale * 0.5 / (20 if by_count else 10))
    # registered at initialise and never observed in the window: 0
    idle = {histogram + ".sum": 0.0, histogram + ".count": 0}
    assert reader(name).read(context(idle)) == 0.0


@pytest.mark.parametrize("name", sorted(HOST_READERS))
def test_host_reader_finds_nothing_in_a_program_without_the_span(name):
    """The parent commit has no such histogram: nothing, and no raise."""
    assert reader(name).read(context({"step.train_s.sum": 1.0,
                                      "step.train_s.count": 8})) is None


def test_per_step_readers_need_a_train_step():
    registry = {"step.stage_s.sum": 1.0, "step.stage_s.count": 3}
    assert span_metrics.per_train_step(
        context(registry, steps=0), "step.stage_s", 1e6) is None
    assert span_metrics.histogram_sum(
        context(registry), "step.stage_s") == (1.0, 3)


@pytest.mark.parametrize("name", sorted(KERNEL_READERS))
def test_kernel_reader_counts_its_instruction_names_only(name):
    ops = {WGRAD: 0.080, WGRAD_PLAIN: 0.004, POOL: 0.012,
           OLD_WGRAD: 0.5, LOOKALIKE: 0.7, CONSUMER: 0.9}
    want = {"veles_conv_wgrad": 1e3 * (0.080 + 0.004) / 4,
            "veles_pool_bwd": 1e3 * 0.012 / 4}[KERNEL_READERS[name]]
    assert reader(name).read(context(op_seconds=ops)) == \
        pytest.approx(want)
    # a trace that holds none of the kernel: 0.0, not nothing
    assert reader(name).read(context(op_seconds={OLD_WGRAD: 0.5})) == 0.0


def test_kernel_names_are_the_programs_constants():
    from veles_tpu.ops import conv_vjp, pool_bwd
    assert KERNEL_READERS == {
        "conv_wgrad_ms_per_step.train": conv_vjp.KERNEL_NAME,
        "pool_bwd_ms_per_step.train": pool_bwd.KERNEL_NAME}


def test_recorded_pre_name_trace_reads_zero_for_the_named_kernels():
    """The recorded trace is of a program from before the kernels had
    names: its Mosaic time (24.98 ms a step) is in no ``%veles_*``
    instruction."""
    trace = reduce_trace.reduce(TRACE)
    ctx = {"trace": trace, "steps": 3, "registry": {}}
    for name in KERNEL_READERS:
        assert reader(name).read(ctx) == 0.0
    assert reader("mosaic_ms_per_step.train").read(ctx) == \
        pytest.approx(24.978409, rel=1e-4)
    assert not [text for text in trace["op_seconds"]
                if text.startswith("%veles_")]


@pytest.mark.parametrize("name", sorted(HOST_READERS) +
                         sorted(KERNEL_READERS))
def test_new_metric_is_declared_for_the_cells_that_can_read_it(name):
    metric = bench_run.find(MANIFEST["per_layer"], name, "metric")
    assert metric["moves"] == "train_images_per_s"
    assert metric["better"] == "lower"
    assert metric["workloads"] == (
        BOTH_CELLS if name in HOST_READERS else BOTH_CELLS[:1])
    module = reader(name)
    assert module.SOURCE == ("program_span" if name in HOST_READERS
                             else "device_trace")
    assert module.__doc__.startswith(module.LAYER + ":")


def test_new_entries_come_after_the_accepted_ones():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    new = [n for n in names if n in HOST_READERS or n in KERNEL_READERS]
    assert names[-len(new):] == new and len(new) == 7
