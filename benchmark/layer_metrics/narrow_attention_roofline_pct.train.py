"""Kernels: the full-causal flash kernels' share of their roofline at
heads narrower than a lane tile — ``step_cost``'s
``full_attention_flops`` (the causal pairs at the PUBLISHED head width,
64, never the 128 lanes a tile pads to, 3 x the forward) over the chip's
peak over the three kernels' time: ``gqa_attention_roofline_pct.train``'s
reading (its ``read``, called, not copied).  What the padding costs
shows as a share about half of what the same kernels read at 128-wide
heads."""

from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return load_reader("gqa_attention_roofline_pct.train").read(context)
