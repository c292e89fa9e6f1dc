"""Fused step (host side): the call of the compiled train-step program
alone (span ``fused.dispatch`` of a train step, histogram
``step.dispatch_s``), mean over the window's train dispatches.  The call
returns once the program is enqueued, so what exceeds microseconds here
is the host blocked on a full device queue."""

from benchmark import span_metrics

LAYER = "Fused step (host side)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    return span_metrics.per_observation(context, "step.dispatch_s", 1e3)
