"""Minibatch gather from a device-resident dataset.

TPU-native counterpart of reference ocl/fullbatch_loader.cl:5-50 /
cuda/fullbatch_loader.cu: ``minibatch[i] = dataset[indices[i]]`` with an
on-the-fly dtype cast, plus label gathering.  Implemented with
``PrefetchScalarGridSpec`` — the shuffled indices are scalar-prefetched so
the BlockSpec index_map can route each grid step's DMA straight to the
right dataset row, which is the idiomatic TPU version of the reference's
index-chasing kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import interpret_for, kernel_cast

__all__ = ["gather_minibatch", "gather_labels"]

#: every op of the two gather programs carries this scope in its
#: ``op_name`` metadata (``jit(gather_minibatch)/loader_gather/...``)
SCOPE = "loader_gather"

#: the kernel's name in compiled HLO and device traces (``%veles_gather_rows``)
KERNEL_NAME = "veles_gather_rows"


def _gather_kernel(idx_ref, data_ref, out_ref):
    out_ref[:] = kernel_cast(data_ref[:], out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def gather_minibatch(dataset, indices, out_dtype=None):
    """Gather rows: (N, F...) x (B,) -> (B, F...) with dtype cast.

    ``dataset`` stays in HBM/ANY; each grid step DMAs one sample row into
    VMEM addressed by the prefetched index.
    """
    with jax.named_scope(SCOPE):
        return _gather_rows(dataset, indices,
                            out_dtype or dataset.dtype)


def _gather_rows(dataset, indices, out_dtype):
    batch = indices.shape[0]
    sample_shape = dataset.shape[1:]
    flat = dataset.reshape(dataset.shape[0], -1)
    width = flat.shape[1]
    if width % 128:
        # Padding the whole dataset per call would be an O(N*F) copy per
        # step; lane-unaligned sample widths take XLA's native gather
        # instead.  FullBatchLoader stores its dataset lane-aligned so
        # the DMA path below is the common case.
        return jnp.take(flat, indices, axis=0).astype(out_dtype).reshape(
            (batch,) + sample_shape)
    wp = width
    flat = flat.reshape(flat.shape[0], 1, wp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, 1, wp),
                         lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, wp), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, wp), out_dtype),
        interpret=interpret_for(flat),
    )(indices.astype(jnp.int32), flat)
    return out[:, 0, :width].reshape((batch,) + sample_shape)


@jax.jit
def gather_labels(labels, indices):
    """Label gather; labels are small, XLA's native gather is optimal."""
    with jax.named_scope(SCOPE):
        return jnp.take(labels, indices, axis=0)
