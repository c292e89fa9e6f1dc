"""Fused step (device): union of the device-op intervals over the
traced whole steps, per step; the steps are counted from the trace."""

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    if trace is None:
        return None
    return 1e3 * trace["busy_s"] / trace["steps"]
