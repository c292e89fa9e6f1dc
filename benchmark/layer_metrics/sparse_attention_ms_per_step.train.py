"""Kernels: device ms per traced train step in the three kernels of
attention over a learned selection (``%veles_sparse_fwd``,
``%veles_sparse_dq``, ``%veles_sparse_dkv`` by instruction name,
``veles_tpu/ops/sparse_attention.py``): the flash kernels' tiles with
the selection's mask for the causal one, every (q tile, k tile) in which
no query kept a key skipped.  Nothing where the trace holds none of the
three (a program without them, or one that attends over every key)."""

from benchmark import span_metrics

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

KERNELS = ("veles_sparse_fwd", "veles_sparse_dq", "veles_sparse_dkv")


def read(context):
    if context["trace"] is None:
        return None
    took = sum(span_metrics.kernel_ms_per_step(context, kernel)
               for kernel in KERNELS)
    return took or None
