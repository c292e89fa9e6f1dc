"""Kernels: device ms per traced train step in the three flash-attention
kernels (``ops/attention.py``: ``%veles_flash_fwd``, ``%veles_flash_dq``,
``%veles_flash_dkv`` by instruction name) — the decoder's causal latent
attention, forward, the forward again where the backward recomputes the
layer, and both backward kernels.  Nothing where the program has no such
kernel in the trace's steps reads 0.0."""

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
