"""Fused step (device): device ms per traced train step in the routed
expert layers' ops (``models/decoder.py``'s ``routed_experts``): the
dispatch gather into the kept-assignment buffer, the grouped products
over it, the gate and the combine, forward and backward.  An op counts
where its result or an operand has the buffer's row count
(``routed_rows``, 98,304 = tokens x top_k in the cell: the only
other arrays of that length are the layer's own sort keys and orders)
as leading dimension — the way
``data_device_ms_per_step`` reads the dataset's.  The router and the
shared experts are not in it.  Nothing where the context names no
buffer."""

from benchmark import reduce_trace

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    rows = context.get("routed_rows")
    if trace is None or not rows:
        return None
    return 1e3 * reduce_trace.op_seconds_where(
        trace, lambda text: rows in reduce_trace.leading_dims(text))
