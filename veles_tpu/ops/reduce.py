"""Matrix row/column reductions.

TPU-native counterpart of reference ocl/matrix_reduce.cl:1-69 (shared-
memory tree reduction templated over row/column mode).  On TPU the VPU
reduces a VMEM block natively; the kernel tiles the reduced axis and
accumulates partials in scratch, which is the same two-stage tree the
reference builds by hand.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import ceil_mult, interpret_for, pad_to

__all__ = ["reduce_rows", "reduce_cols"]

#: the kernels' names in compiled HLO and device traces (``%<name>``)
COLS_KERNEL_NAME = "veles_reduce_cols"
ROWS_KERNEL_NAME = "veles_reduce_rows"


def _reduce_cols_kernel(in_ref, out_ref, acc_ref, *, n_k):
    """Sum over rows (axis 0): out[j] = sum_i in[i, j]."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.sum(in_ref[:], axis=0, keepdims=True,
                          dtype=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block",))
def reduce_cols(x, block=512):
    """Column sums: (M, N) -> (1, N)."""
    m, n = x.shape
    bm = min(block, ceil_mult(m, 8))
    x = pad_to(x, (bm, 128))
    mp, np_ = x.shape
    n_k = mp // bm
    out = pl.pallas_call(
        functools.partial(_reduce_cols_kernel, n_k=n_k),
        name=COLS_KERNEL_NAME,
        grid=(n_k,),
        in_specs=[pl.BlockSpec((bm, np_), lambda k: (k, 0))],
        out_specs=pl.BlockSpec((1, np_), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, np_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_for(x),
    )(x)
    return out[:, :n]


def _reduce_rows_kernel(in_ref, out_ref, acc_ref, *, n_k):
    """Sum over columns (axis 1): out[i] = sum_j in[i, j]."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.sum(in_ref[:], axis=1, keepdims=True,
                          dtype=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block",))
def reduce_rows(x, block=512):
    """Row sums: (M, N) -> (M, 1)."""
    m, n = x.shape
    bn = min(block, ceil_mult(n, 128))
    x = pad_to(x, (8, bn))
    mp, np_ = x.shape
    n_k = np_ // bn
    out = pl.pallas_call(
        functools.partial(_reduce_rows_kernel, n_k=n_k),
        name=ROWS_KERNEL_NAME,
        grid=(n_k,),
        in_specs=[pl.BlockSpec((mp, bn), lambda k: (0, k))],
        out_specs=pl.BlockSpec((mp, 1), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), x.dtype),
        scratch_shapes=[pltpu.VMEM((mp, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_for(x),
    )(x)
    return out[:m]


