"""Minibatch gather from a device-resident dataset.

TPU-native counterpart of reference ocl/fullbatch_loader.cl:5-50 /
cuda/fullbatch_loader.cu: ``minibatch[i] = dataset[indices[i]]`` with an
on-the-fly dtype cast, plus label gathering.

Two halves.  A **row store** is the dataset in the one layout a row can
be DMA'd from: ``(N, S, L)`` with every row a whole number of the chip's
memory tiles, so that the chip's default layout for that shape is
row-major and row ``i`` is one contiguous run of HBM.  (The default
layout of an ``(N, F...)`` array puts the ROW INDEX on the lanes: a row
is scattered over the whole table, and any gather from it first
transposes the table.)  The store is built once — by
``FullBatchLoader.initialize``, on the host, in the buffer the dataset
is loaded into — and its shape follows from what can be observed, the
row's width and the element's size (:func:`store_shape`).  The **gather**
is one Pallas kernel, ``veles_gather_rows``: the shuffled indices are
scalar-prefetched into SMEM and every row goes HBM -> HBM by its own
DMA, a window of them in flight.  The program around it touches the
``B`` gathered rows only: slice off the pad, cast, reshape.
"""

import functools

import jax
import jax.numpy as jnp
import numpy
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import ceil_mult, interpret_for

__all__ = ["gather_minibatch", "gather_labels", "gather_rows",
           "store_shape", "build_store", "build_label_store", "host_store",
           "host_store_of", "rows_of"]

#: every op of the two gather programs carries this scope in its
#: ``op_name`` metadata (``jit(gather_minibatch)/loader_gather/...``)
SCOPE = "loader_gather"

#: the kernel's name in compiled HLO and device traces (``%veles_gather_rows``)
KERNEL_NAME = "veles_gather_rows"

LANES = 128
#: row DMAs the kernel keeps in flight (one DMA semaphore each)
DMA_WINDOW = 16


# -- the row store -----------------------------------------------------------

def store_shape(rows, width, dtype):
    """Shape ``(rows, S, L)`` of the store of ``rows`` rows of ``width``
    elements of ``dtype``: a row is whole memory tiles.

    4-byte elements tile one sublane deep in a ``(N, 1, L)`` array, so a
    row pads to whole lanes: ``(rows, 1, ceil(width / 128) * 128)``.
    Narrower elements pack 2 or 4 to a sublane word and tile 16 or 32
    sublanes deep, so a row pads to whole ``(32 / itemsize * 4, 128)``
    tiles: ``(rows, S, 128)``.  (An ``(N, 1, L)`` bfloat16 array and an
    ``(N, 7, 128)`` float32 one both compile to a copy of the table.)"""
    itemsize = numpy.dtype(dtype).itemsize
    width = max(width, 1)
    if itemsize >= 4:
        return (rows, 1, ceil_mult(width, LANES))
    sublanes = 8 * (4 // itemsize)
    return (rows, ceil_mult(width, sublanes * LANES) // LANES, LANES)


def _width(sample_shape):
    return int(numpy.prod(sample_shape, dtype=numpy.int64))


def rows_of(buf, sample_shape):
    """The ``(N,) + sample_shape`` window on the numpy store ``buf``: a
    strided view, so what is written through it is in the store."""
    sample_shape = tuple(sample_shape)
    view = buf.reshape(len(buf), -1)[:, :_width(sample_shape)].reshape(
        (len(buf),) + sample_shape)
    if view.size and host_store_of(view) is not buf:
        raise ValueError("numpy copied the %s window of a %s store" % (
            view.shape, buf.shape))
    return view


def host_store(rows, sample_shape, dtype):
    """A zeroed host buffer in store shape, and its window."""
    buf = numpy.zeros(
        store_shape(rows, _width(tuple(sample_shape)), dtype), dtype)
    return buf, rows_of(buf, sample_shape)


def host_store_of(view):
    """The store buffer that ``view`` is the :func:`rows_of` window of,
    or None (a plain array: the store takes one host copy)."""
    base = view.base
    if not isinstance(base, numpy.ndarray) or view.ndim < 1:
        return None
    is_window = (
        base.dtype == view.dtype and base.flags.c_contiguous and
        base.shape == store_shape(len(view), _width(view.shape[1:]),
                                  view.dtype) and
        base.ctypes.data == view.ctypes.data and
        (len(view) < 2 or view.strides[0] == base.strides[0]))
    return base if is_window else None


def build_store(data):
    """``(N, F...)`` rows as a store.  A numpy array gives a numpy
    store (one host copy), a jax array or tracer a padded, reshaped one
    (a pass over the table on the device: build once, not per step)."""
    rows, sample_shape = data.shape[0], tuple(data.shape[1:])
    if isinstance(data, numpy.ndarray):
        buf, view = host_store(rows, sample_shape, data.dtype)
        view[...] = data
        return buf
    shape = store_shape(rows, _width(sample_shape), data.dtype)
    flat = data.reshape(rows, -1)
    flat = jnp.pad(flat, ((0, 0), (0, shape[1] * shape[2] - flat.shape[1])))
    return flat.reshape(shape)


def build_label_store(labels):
    """``(N,)`` labels, 128 to a row, as a store: the row kernel fetches
    row ``i // 128`` and the program picks lane ``i % 128``."""
    xp = numpy if isinstance(labels, numpy.ndarray) else jnp
    padded = ceil_mult(labels.shape[0], LANES)
    return build_store(xp.pad(labels, (0, padded - labels.shape[0])).reshape(
        padded // LANES, LANES))


# -- the gather --------------------------------------------------------------

def _gather_kernel(idx_ref, store_ref, out_ref, sems):
    """Row ``idx[i]`` of the store to row ``i`` of the output, HBM to
    HBM, DMA_WINDOW copies in flight."""
    batch = out_ref.shape[0]

    def row_copy(i):
        return pltpu.make_async_copy(
            store_ref.at[idx_ref[i]], out_ref.at[i],
            sems.at[i % DMA_WINDOW])

    def issue(i, carry):
        @pl.when(i >= DMA_WINDOW)
        def _():
            row_copy(i - DMA_WINDOW).wait()
        row_copy(i).start()
        return carry

    def drain(i, carry):
        row_copy(i).wait()
        return carry

    jax.lax.fori_loop(0, batch, issue, 0)
    jax.lax.fori_loop(max(0, batch - DMA_WINDOW), batch, drain, 0)


def gather_rows(store, indices, sample_shape, out_dtype=None):
    """store x (B,) -> ``(B,) + sample_shape``: the kernel over the
    store as it is, then ops over the B gathered rows.  Traceable (the
    epoch scans call it in their body).  Indices are clamped into range:
    a DMA from a row that is not there would fault the chip."""
    batch = indices.shape[0]
    indices = jnp.clip(indices.astype(jnp.int32), 0, store.shape[0] - 1)
    sample_shape = tuple(sample_shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_WINDOW,))],
    )
    out = pl.pallas_call(
        _gather_kernel,
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch,) + store.shape[1:],
                                       store.dtype),
        interpret=interpret_for(store),
    )(indices, store)
    out = out.reshape(batch, -1)[:, :_width(sample_shape)]
    return out.astype(out_dtype or store.dtype).reshape(
        (batch,) + sample_shape)


@functools.partial(jax.jit, static_argnames=("out_dtype", "sample_shape"))
def gather_minibatch(dataset, indices, out_dtype=None, sample_shape=None):
    """Gather rows: (N, F...) x (B,) -> (B, F...) with dtype cast.

    With ``sample_shape`` given, ``dataset`` is the row store of such
    rows (:func:`build_store`) and the program holds the kernel and ops
    over the B rows, nothing over the table: how ``FullBatchLoader``
    calls it every step.  A raw ``(N, F...)`` array pays the build —
    a pass over the whole table — in every call."""
    with jax.named_scope(SCOPE):
        if sample_shape is None:
            sample_shape = dataset.shape[1:]
            dataset = build_store(dataset)
        return gather_rows(dataset, indices, sample_shape, out_dtype)


@jax.jit
def gather_labels(labels, indices):
    """Label gather: (N,) x (B,) -> (B,).  ``labels`` is the label
    store (:func:`build_label_store`, 3-D) or the raw 1-D vector, which
    pays the build in every call."""
    with jax.named_scope(SCOPE):
        if labels.ndim == 1:
            labels = build_label_store(labels)
        indices = indices.astype(jnp.int32)
        rows = gather_rows(labels, indices // LANES, (LANES,))
        return jnp.take_along_axis(
            rows, (indices % LANES)[:, None], axis=1)[:, 0]
