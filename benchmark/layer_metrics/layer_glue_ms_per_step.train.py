"""Fused step (device): device ms per traced train step in the leaf
instructions under an ``l<k>_DecoderLayer`` scope and NO part:
``unpack``'s slices, casts and retilings of the packed vectors, the feed-forward's
norm, the residual sums (``benchmark/scope_metrics.py``).  With the six
part metrics it partitions the ``DecoderLayer`` scopes' time."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: (
            layer == "DecoderLayer" and part is None))
