"""Entry: where the decision unit forces a device scalar to the host
(span ``decision.sync``, histogram ``decision.sync_s``: the class-end
metric's ``float()``, the health counters' ``int()``s), per train step of
the window.  0 where no class ended in the window."""

from benchmark import span_metrics

LAYER = "Entry"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    return span_metrics.per_train_step(context, "decision.sync_s", 1e3)
