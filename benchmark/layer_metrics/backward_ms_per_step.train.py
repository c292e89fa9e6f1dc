"""Fused step (device): device ms per traced train step in the leaf
instructions whose scope is in the BACKWARD phase: under a
``transpose(...)`` wrapper of a layer's or the ``loss``'s scope and not
a recomputation, or under ``grad_sync``, a mesh's gradient merge, which
ends the backward (``benchmark/scope_metrics.py``)."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: phase == "backward")
