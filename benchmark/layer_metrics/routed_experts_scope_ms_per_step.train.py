"""Fused step (device): device ms per traced train step in the leaf
instructions under a decoder layer's ``routed_experts`` part, every
phase: the counting sort, the loops' bodies (the loops themselves are
containers and left out, so nothing is counted twice), the (N, K)
gathers, the zero fills (``benchmark/scope_metrics.py``).  Beside
``moe_routed_ms_per_step.train``, which finds the layer by its shapes."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: part == "routed_experts")
