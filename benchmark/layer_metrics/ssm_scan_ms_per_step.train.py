"""Fused step (device): device ms per traced train step in the leaf
instructions under a decoder layer's ``ssm_scan`` part, every phase: the
chunked scan of the selective state alone — within a chunk the decays'
segment sums and ``(L o C B^T) X``, the chunk-end states, the recurrence
over them and their read-back through ``C`` — forward, replayed and
backward (``benchmark/scope_metrics.py``).  Nothing where no leaf is
under that part (a program whose layers have no state-space mixer)."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

PART = "ssm_scan"


def read(context):
    joined = scope_metrics.by_scope(context)
    if joined is None or not any(part == PART for _, part, _ in joined):
        return None
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: part == PART)
