"""Fused step (device): device ms per traced train step in the leaf
instructions under a ``rematted_computation`` component: the forward
that a checkpointed layer replays in the backward — what the keep-policy
of ``compiler._forward_for_loss`` / ``FusedTrainer.
_backward_should_recompute`` costs (``benchmark/scope_metrics.py``).
0.0 where the step keeps its activations."""

from benchmark import scope_metrics

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_metrics.ms_per_step_where(
        context, lambda layer, part, phase: phase == "recompute")
