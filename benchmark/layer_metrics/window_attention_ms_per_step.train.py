"""Kernels: device ms per traced train step in the three windowed
flash-attention kernels (``ops/attention.py``: ``%veles_flash_win_fwd``,
``%veles_flash_win_dq``, ``%veles_flash_win_dkv`` by instruction name) —
the decoder's layers that attend over a window of the causal prefix,
grouped key/value heads, forward (once a layer where the recomputed
backward keeps what the kernel named) and both backward kernels.  Their
grids span the band of tiles the window crosses, so this time grows with
T x W, not T x T.  A program without such a kernel in the trace's steps
(the parent commit, a cell whose model has no window) reads 0.0."""

from benchmark import span_metrics

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

KERNELS = ("veles_flash_win_fwd", "veles_flash_win_dq",
           "veles_flash_win_dkv")


def read(context):
    if context["trace"] is None:
        return None
    return sum(span_metrics.kernel_ms_per_step(context, kernel)
               for kernel in KERNELS)
