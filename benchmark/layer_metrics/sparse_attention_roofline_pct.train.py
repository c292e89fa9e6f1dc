"""Kernels: the share of their roofline that the three kernels of
attention over a learned selection reach — the operations ``step_cost``
counts as ``sparse_attention_flops`` (the SELECTED pairs only, sum_t
min(t + 1, topk) a sequence, the query heads at the published head
width, 3 x the forward; never the masked pairs of an occupied tile, the
padded or the recomputed work) over the chip's peak for the
configuration's dtype, over the time the kernels took
(``sparse_attention_ms_per_step.train``).  A kernel that masks inside
its tiles reads low by what it masks: that is the reading.  Nothing
where the reference counts no such operations or the trace holds no
such kernel."""

from benchmark import flops
from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    if context["trace"] is None:
        return None
    operations = context.get("step_cost", {}).get("sparse_attention_flops")
    took = load_reader("sparse_attention_ms_per_step.train").read(context)
    if not operations or not took:
        return None
    peak = flops.peaks(context["device_kind"])["flops_per_s"][
        context["config"]["dtype"]] * context["chips"]
    return 100.0 * (operations / peak) / (took * 1e-3)
