"""Fused step (device): device ms per traced train step in the leaf
instructions under a decoder layer's ``dense_ffn`` part (the dense
layer's gated feed-forward), every phase
(``benchmark/scope_metrics.py``)."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: part == "dense_ffn")
