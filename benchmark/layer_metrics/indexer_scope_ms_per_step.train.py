"""Fused step (device): device ms per traced train step in the leaf
instructions under a decoder layer's ``indexer`` part, every phase: the
indexer's projections, key norm and rotary, the selection's kernel, the
indexer's loss and its gradient's way back to its pieces
(``benchmark/scope_metrics.py``).  Nothing where no leaf is under that
part (a program whose layers have no indexer)."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

PART = "indexer"


def read(context):
    joined = scope_metrics.by_scope(context)
    if joined is None or not any(part == PART for _, part, _ in joined):
        return None
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: part == PART)
