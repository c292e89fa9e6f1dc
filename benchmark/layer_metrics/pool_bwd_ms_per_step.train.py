"""Kernels: device time per traced step in the max-pooling backward
kernel, the instructions named ``%veles_pool_bwd``
(``veles_tpu/ops/pool_bwd.py``'s ``KERNEL_NAME``).  0 where the trace
holds none."""

from benchmark import span_metrics

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return span_metrics.kernel_ms_per_step(context, "veles_pool_bwd")
