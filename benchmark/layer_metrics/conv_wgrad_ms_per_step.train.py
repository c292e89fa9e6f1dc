"""Kernels: device time per traced step in the conv-VJP weight-gradient
kernel, the instructions named ``%veles_conv_wgrad``
(``veles_tpu/ops/conv_vjp.py``'s ``KERNEL_NAME``).  0 where the trace
holds none: a model without convolutions, or a program from before the
kernels had names."""

from benchmark import span_metrics

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return span_metrics.kernel_ms_per_step(context, "veles_conv_wgrad")
