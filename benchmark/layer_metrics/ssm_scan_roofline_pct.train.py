"""Kernels: the chunked scan's share of its roofline — the least time
the chip could take for the work ``step_cost`` counts, the larger of
``ssm_scan_flops`` (the chunked form's products over the causal pairs
within a chunk, three times the forward's, never a padded token or a
recomputed pass) over the peak for the configuration's dtype and
``ssm_scan_bytes`` (x, B, C, dt, y and the chunk states read or written
once, three times the forward's) over the peak bandwidth, over the
device time of the ``ssm_scan`` scope (``ssm_scan_ms_per_step.train``).
A scan that does only the counted work reads at most 100 %.  Nothing
where the reference counts no scan or no leaf is in that scope."""

from benchmark import flops
from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    if context["trace"] is None:
        return None
    cost = context.get("step_cost", {})
    operations, data = cost.get("ssm_scan_flops"), cost.get("ssm_scan_bytes")
    took = load_reader("ssm_scan_ms_per_step.train").read(context)
    if not operations or not data or not took:
        return None
    peak = flops.peaks(context["device_kind"])
    least = max(operations / (peak["flops_per_s"][context["config"]["dtype"]]
                              * context["chips"]),
                data / (peak["bytes_per_s"] * context["chips"]))
    return 100.0 * least / (took * 1e-3)
