"""Fused step (device): the fullest held expert's tokens over the mean
held expert's, over the window and all routed layers — from the
program's ``moe.load.l<i>.e<j>`` counters (accumulated on the device,
published at the window's edges).  1.0 is an even router; the grouped
products' time follows the sum, the buffer's headroom the fullest.
Nothing in an untraced run, or where the program counts no load."""

LAYER = "Fused step (device)"
UNIT = "ratio"
MOVES = "train_images_per_s"
SOURCE = "program_counter"


def read(context):
    if context["trace"] is None:
        return None
    loads = [value for name, value in context["registry"].items()
             if name.startswith("moe.load.")]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
