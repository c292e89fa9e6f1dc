"""Distribution: host ms per train step of the window spent staging the
minibatch for the mesh — span ``fused.stage``, histogram
``step.stage_s``, which under a mesh is ``FusedTrainer._stage_sharded``:
the gathered rows fetched from their device to the host and handed to
every chip's shard (the ``Prefetcher`` is off there), train and eval
minibatches.  On the data-parallel cell this is the input path its
``why`` names, and what the chips wait for; on one chip the same span is
``trainer_stage_us_per_step.train``'s, microseconds of picking the
Prefetcher's arrays.  Nothing in an untraced run or where the program
has no such histogram."""

from benchmark import span_metrics

LAYER = "Distribution"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    return span_metrics.per_train_step(context, "step.stage_s", 1e3)
