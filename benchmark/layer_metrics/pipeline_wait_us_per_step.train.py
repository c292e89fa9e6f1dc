"""Data (host): how long the graph thread waited for the Prefetcher's
next minibatch (registry histogram ``pipeline.wait_s``), over the
window, per train step.  Nothing where no Prefetcher ran (a mesh)."""

LAYER = "Data (host)"
UNIT = "us"
MOVES = "train_images_per_s"
SOURCE = "program_counter"


def read(context):
    waits = context["registry"].get("pipeline.wait_s.count", 0)
    if not waits or not context["steps"]:
        return None
    return 1e6 * context["registry"]["pipeline.wait_s.sum"] / \
        context["steps"]
