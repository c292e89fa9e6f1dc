"""Kernels: the step's floor on this chip over the device time it took.
Floor = max(FLOPs / peak FLOP/s, bytes / peak bytes/s) from
``benchmark/flops.py`` and ``benchmark/peaks.json``; the device time is
``device_ms_per_step.train``'s, so the dataset's re-layout counts
against the step."""

from benchmark import flops

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    if trace is None:
        return None
    cost = context["step_cost"]
    floor, _ = flops.floor_seconds(
        cost["flops"], cost["bytes"], flops.peaks(context["device_kind"]),
        context["config"]["dtype"], context["chips"])
    return 100.0 * floor / (trace["busy_s"] / trace["steps"])
