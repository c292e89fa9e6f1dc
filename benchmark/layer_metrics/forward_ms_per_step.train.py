"""Fused step (device): device ms per traced train step in the leaf
instructions of the compiled step whose scope is in the FORWARD phase:
a layer's (``l<k>_<Class>``) or the ``loss``'s ops with no
``transpose(`` in their ``op_name`` (``benchmark/scope_metrics.py``).
With ``recompute``, ``backward``, ``update`` and the unattributed share
it partitions a step's leaf time."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: phase == "forward")
