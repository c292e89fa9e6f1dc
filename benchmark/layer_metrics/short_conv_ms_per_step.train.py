"""Fused step (device): device ms per traced train step in the gated
short convolution's ops (``models/decoder.py``'s ``short_conv``): the
input projection ``[B | C | x] = a W_in``, the gate-filter-gate passes
over it and their backward, the projection's weight and input
gradients.  An op counts where its result or an operand is 3 x the
hidden size wide in its LAST dimension (6,144 in the cell: the
projection's output and ``W_in`` itself; no other array of a
configuration whose feed-forward and expert widths differ from it is
that wide) — the way ``moe_routed_ms_per_step`` reads the routed
buffer's rows by their leading dimension.  The output projection
``W_out`` (hidden x hidden) is not in it.  Nothing where the
configuration has no short convolution (no ``conv_L_cache``)."""

from benchmark import reduce_trace

LAYER = "Fused step (device)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def last_dims(instruction):
    """The last dimension of every shape in an instruction's text."""
    return {int(dims.split(",")[-1])
            for dims in reduce_trace.SHAPE.findall(instruction) if dims}


def read(context):
    trace = context["trace"]
    config = context["config"]
    if trace is None or not config.get("conv_L_cache"):
        return None
    wide = 3 * config["hidden_size"]
    return 1e3 * reduce_trace.op_seconds_where(
        trace, lambda text: wide in last_dims(text))
