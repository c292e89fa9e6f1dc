"""Fused step (device): the share of the routed layers' buffer of kept
assignments that a step fills — the program's ``moe.assignments``
counter over the window ÷ (``routed_rows``, the rows of one layer's
buffer, x the routed layers x the window's train steps).  The grouped
products run over the rows that are filled; the gathers and elementwise
passes run over the whole buffer, so the rest is what they waste
(``moe_routed_ms_per_step.train`` is their time).  The buffer holds the
most a step can send, tokens x min(top_k, experts held): with 8 of 128
experts held and 8 a token an even router fills 1/16 of it.  The routed
layers are counted from the ``moe.load.l<i>.e<j>`` counters' layer
indices.  Nothing in an untraced run, or where the program counts no
assignments or the context names no buffer."""

LAYER = "Fused step (device)"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "program_counter"


def read(context):
    if context["trace"] is None:
        return None
    registry = context["registry"]
    rows = context.get("routed_rows")
    steps = registry.get("train.steps") or context.get("steps")
    layers = {name.split(".")[2] for name in registry
              if name.startswith("moe.load.l")}
    if not rows or not steps or not layers \
            or "moe.assignments" not in registry:
        return None
    return 100.0 * registry["moe.assignments"] / (
        rows * len(layers) * steps)
