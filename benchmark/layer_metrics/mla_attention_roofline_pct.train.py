"""Kernels: the flash-attention kernels' share of their roofline — the
least time the chip could take for the step's attention over the time
the three kernels took.  The least time is compute's: the operations
``step_cost`` counts for attention (causal pairs only, T (T + 1) / 2 a
sequence, keys and values as wide as published, forward + backward = 3 x
the forward; never the padded, masked or recomputed work) over the
chip's peak for the configuration's dtype; at these lengths the bytes'
floor is far below it.  The kernels' time includes the forward they run
again where the backward recomputes the layer, so the share cannot pass
100 %.  Nothing where the reference counts no attention or the trace
holds no such kernel."""

from benchmark import flops
from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    if context["trace"] is None:
        return None
    operations = context.get("step_cost", {}).get("attention_flops")
    took = load_reader("mla_attention_ms_per_step.train").read(context)
    if not operations or not took:
        return None
    peak = flops.peaks(context["device_kind"])["flops_per_s"][
        context["config"]["dtype"]] * context["chips"]
    return 100.0 * (operations / peak) / (took * 1e-3)
