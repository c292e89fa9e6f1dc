"""Entry: the graph thread's time BETWEEN units (span ``workflow.hop``,
histogram ``workflow.hop_s``: worklist, gates, locks, from one unit's end
to the next unit's start), per train step of the window."""

from benchmark import span_metrics

LAYER = "Entry"
UNIT = "us"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    return span_metrics.per_train_step(context, "workflow.hop_s", 1e6)
