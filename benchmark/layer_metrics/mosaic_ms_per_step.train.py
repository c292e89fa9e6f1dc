"""Kernels: device time per traced step in Mosaic (Pallas) kernels, the
``tpu_custom_call`` instructions."""

from benchmark import reduce_trace

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    if trace is None:
        return None
    return 1e3 * reduce_trace.op_seconds_where(
        trace, lambda text: reduce_trace.MOSAIC in text)
