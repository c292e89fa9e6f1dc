"""Mean/dispersion normalization kernel.

TPU-native counterpart of reference ocl/mean_disp_normalizer.cl:12-20 /
cuda equivalent: ``out = (x - mean) * rdisp`` broadcast over samples,
with an on-the-fly cast from the storage dtype (the reference normalises
uint8 image data straight out of the dataset).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from veles_tpu.ops.common import (ceil_mult, interpret_for, kernel_cast,
                                   pad_to)

__all__ = ["mean_disp_normalize"]

#: the kernel's name in compiled HLO and device traces (``%veles_normalize``)
KERNEL_NAME = "veles_normalize"


def _normalize_kernel(x_ref, mean_ref, rdisp_ref, out_ref):
    x = kernel_cast(x_ref[:], out_ref.dtype)
    out_ref[:] = (x - mean_ref[:]) * rdisp_ref[:]


#: lanes per grid step: a (256, 2048) f32 output block is 2 MiB, so the
#: double-buffered windows stay far under Mosaic's scoped-VMEM limit
#: at any sample width (one block per full-width row OOMs VMEM at
#: image widths)
_BLOCK_LANES = 2048


@functools.partial(jax.jit, static_argnames=("out_dtype", "block"))
def mean_disp_normalize(x, mean, rdisp, out_dtype=jnp.float32, block=256):
    """(B, F) storage-dtype x, (F,) mean, (F,) reciprocal dispersion."""
    batch = x.shape[0]
    sample_shape = x.shape[1:]
    flat = x.reshape(batch, -1)
    width = flat.shape[1]
    mean = mean.reshape(1, width).astype(out_dtype)
    rdisp = rdisp.reshape(1, width).astype(out_dtype)
    # rows per block in the STORAGE dtype's sublane quantum (uint8
    # packs four rows per sublane: 32-row tiles)
    rows = 8 * max(1, 4 // flat.dtype.itemsize)
    bm = min(ceil_mult(block, rows), ceil_mult(batch, rows))
    bw = min(_BLOCK_LANES, ceil_mult(width, 128))
    flat = pad_to(flat, (bm, bw))
    mean = pad_to(mean, (None, bw))
    rdisp = pad_to(rdisp, (None, bw))
    mp, wp = flat.shape
    out = pl.pallas_call(
        _normalize_kernel,
        name=KERNEL_NAME,
        grid=(mp // bm, wp // bw),
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
            pl.BlockSpec((1, bw), lambda i, j: (0, j)),
            pl.BlockSpec((1, bw), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, wp), out_dtype),
        interpret=interpret_for(flat),
    )(flat, mean, rdisp)
    return out[:batch, :width].reshape((batch,) + sample_shape)
