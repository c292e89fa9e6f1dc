"""Kernels: the windowed flash-attention kernels' share of their
roofline — the least time the chip could take for the step's windowed
attention over the time the three kernels took
(``window_attention_ms_per_step.train``).  The least time is compute's:
the operations ``step_cost`` counts as ``window_attention_flops`` (the
in-window causal pairs only, W (W + 1) / 2 + (T - W) W a sequence, the
query heads at the published head width, forward + backward = 3 x the
forward; never the masked, padded or recomputed work) over the chip's
peak for the configuration's dtype; the bytes' floor is far below it.
The share cannot pass 100 %: the kernels multiply every pair of the
tiles the band crosses, the model's pairs among them.  Nothing where
the reference counts no windowed attention or the trace holds no such
kernel."""

from benchmark import flops
from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    if context["trace"] is None:
        return None
    operations = context.get("step_cost", {}).get("window_attention_flops")
    took = load_reader("window_attention_ms_per_step.train").read(context)
    if not operations or not took:
        return None
    peak = flops.peaks(context["device_kind"])["flops_per_s"][
        context["config"]["dtype"]] * context["chips"]
    return 100.0 * (operations / peak) / (took * 1e-3)
