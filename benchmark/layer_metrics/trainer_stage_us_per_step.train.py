"""Fused step (host side): picking or staging the minibatch's ``x`` and
``target`` (span ``fused.stage``, histogram ``step.stage_s``: the
Prefetcher's arrays, the loader's, or the mesh's device -> host -> devices
copy), train and eval minibatches, per train step of the window."""

from benchmark import span_metrics

LAYER = "Fused step (host side)"
UNIT = "us"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    return span_metrics.per_train_step(context, "step.stage_s", 1e6)
