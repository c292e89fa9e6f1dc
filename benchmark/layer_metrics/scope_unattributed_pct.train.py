"""Fused step (device): the instrument's own blind spot — the share,
per cent, of the traced window's leaf device time that
``benchmark/scope_metrics.py`` can give to no scope: instructions whose
``op_name`` names no layer, ``loss`` or ``update`` (parameters' copies,
what the compiler added), and the ops of the window's other programs
(the loader's gather, the eval step)."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.share_pct_where(
        context, lambda layer, part, phase: layer is None)
