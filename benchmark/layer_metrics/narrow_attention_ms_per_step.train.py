"""Kernels: device ms per traced train step in the three full-causal
flash-attention kernels (``%veles_flash_fwd``, ``%veles_flash_dq``,
``%veles_flash_dkv``) in a cell whose heads are NARROWER than a lane
tile: 64-wide heads, 32 query heads on 8 KV heads.  Each tile pads to
128 lanes by itself, so the MXU and HBM do twice the model's work; the
time is ``gqa_attention_ms_per_step.train``'s reading (its ``read``,
called, not copied), and which cell lists which metric tells the two
apart."""

from benchmark.run import load_reader

LAYER = "Kernels"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    return load_reader("gqa_attention_ms_per_step.train").read(context)
