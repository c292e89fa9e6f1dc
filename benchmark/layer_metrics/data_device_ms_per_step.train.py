"""Data (device): device time per traced step of the ops that touch the
whole resident dataset -- an HLO instruction whose result or operand has
the dataset's row count as its leading dimension."""

from benchmark import reduce_trace

LAYER = "Data (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    trace, rows = context["trace"], context["dataset_rows"]
    if trace is None:
        return None
    return 1e3 * reduce_trace.op_seconds_where(
        trace, lambda text: rows in reduce_trace.leading_dims(text))
