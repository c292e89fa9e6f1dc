"""Kernels: device ms per traced train step in the three full-causal
flash-attention kernels (``%veles_flash_fwd``, ``%veles_flash_dq``,
``%veles_flash_dkv`` by instruction name) in a cell whose full layers
have grouped key/value heads: query head n reads the tiles of KV head
n // group through the kernels' block index maps (K and V are never
repeated in HBM) and the dk/dv kernel sums a group's query heads in one
accumulator.  The names are the ones ``mla_attention_ms_per_step.train``
reads in the latent-attention cell; which cell lists which metric tells
the two apart.  A program without the kernels in the trace's steps
reads 0.0."""

from benchmark import span_metrics

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

KERNELS = ("veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv")


def read(context):
    if context["trace"] is None:
        return None
    return sum(span_metrics.kernel_ms_per_step(context, kernel)
               for kernel in KERNELS)
