"""Kernels: the full-causal grouped-head flash kernels' share of their
roofline — the operations ``step_cost`` counts as
``full_attention_flops`` (the full layers' causal pairs, T (T + 1) / 2 a
sequence, the query heads at the published head width, 3 x the forward;
never the masked, padded or recomputed work) over the chip's peak for
the configuration's dtype, over the time the three kernels took
(``gqa_attention_ms_per_step.train``).  Compute sets the floor; the
grouped keys lower what is read, not what is multiplied.  Nothing where
the reference counts no such operations (a configuration whose
``step_cost`` does not split its attention) or the trace holds no such
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
    operations = context.get("step_cost", {}).get("full_attention_flops")
    took = load_reader("gqa_attention_ms_per_step.train").read(context)
    if not operations or not took:
        return None
    peak = flops.peaks(context["device_kind"])["flops_per_s"][
        context["config"]["dtype"]] * context["chips"]
    return 100.0 * (operations / peak) / (took * 1e-3)
