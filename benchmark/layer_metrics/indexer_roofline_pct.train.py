"""Kernels: the indexer's share of its roofline — the operations
``step_cost`` counts as ``indexer_flops`` (all that its scope runs: its
three projections and their weights' gradient, its scores over every
causal pair, ``index_heads`` products ``index_width`` deep a pair, and
their gradient by its query and key over the SELECTED pairs; the same
work whatever implements it, never the recomputed) over the chip's peak for
the configuration's dtype, over the device time of the ``indexer``
scope (``indexer_scope_ms_per_step.train``).  Nothing where the
reference counts no such operations or no leaf is in that scope."""

from benchmark import flops
from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    if context["trace"] is None:
        return None
    operations = context.get("step_cost", {}).get("indexer_flops")
    took = load_reader("indexer_scope_ms_per_step.train").read(context)
    if not operations or not took:
        return None
    peak = flops.peaks(context["device_kind"])["flops_per_s"][
        context["config"]["dtype"]] * context["chips"]
    return 100.0 * (operations / peak) / (took * 1e-3)
