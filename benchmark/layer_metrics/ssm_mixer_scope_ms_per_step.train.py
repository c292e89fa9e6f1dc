"""Fused step (device): device ms per traced train step in the leaf
instructions under a decoder layer's ``ssm_mixer`` part, every phase:
the state-space mixer's pre-norm, its input projection, the causal
filter with its bias and SiLU, dt's softplus, the skip, the gate, the
grouped norm and the output projection, and their backward — all of the
mixer but the chunked scan, which is ``ssm_scan``'s
(``benchmark/scope_metrics.py``).  Nothing where no leaf is under that
part (a program whose layers have no state-space mixer)."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

PART = "ssm_mixer"


def read(context):
    joined = scope_metrics.by_scope(context)
    if joined is None or not any(part == PART for _, part, _ in joined):
        return None
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: part == PART)
