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

Under a mesh the same two halves are laid over its data axis
(:func:`shard_store`, :func:`mesh_gather_minibatch`,
:func:`mesh_gather_labels`): chip ``k`` of ``n`` holds rows
``[k R, (k + 1) R)``, ``R = ceil(N / n)``, runs the same kernel on its
own shard for the rows of the index window that it owns, and one
reduce-scatter leaves it with its ``B / n`` rows of the window, in the
window's order: the batch sharding the data-parallel step takes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec

from veles_tpu.ops.common import ceil_mult, interpret_for

__all__ = ["gather_minibatch", "gather_labels", "gather_rows",
           "mesh_gather_minibatch", "mesh_gather_labels", "shard_store",
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


def shard_store(buf, mesh, data_axis):
    """The numpy store ``buf`` as ONE array laid over ``data_axis`` by
    rows: chip ``k`` of ``n`` holds rows ``[k R, (k + 1) R)``,
    ``R = ceil(N / n)``, the last shard padded with zero rows that no
    index names.  Every chip is sent its own rows straight from the
    host buffer: the table is never whole on one chip, and the host
    makes no copy of it (but the last shard's, where it is padded)."""
    chips = mesh.shape[data_axis]
    shard_rows = -(-len(buf) // chips)

    def shard(index):
        rows = buf[index[0]]
        pad = shard_rows - len(rows)
        if pad:
            rows = numpy.concatenate(
                [rows, numpy.zeros((pad,) + rows.shape[1:], rows.dtype)])
        return rows

    return jax.make_array_from_callback(
        (chips * shard_rows,) + buf.shape[1:],
        NamedSharding(mesh, PartitionSpec(data_axis)), shard)


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


def _kernel_rows(store, indices):
    """store x (B,) int32 indices, each in range -> (B, S, L): the
    kernel alone."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_WINDOW,))],
    )
    return pl.pallas_call(
        _gather_kernel,
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (indices.shape[0],) + store.shape[1:], store.dtype),
        interpret=interpret_for(store),
    )(indices, store)


def _samples(rows, sample_shape, out_dtype):
    """(B, S, L) gathered rows -> ``(B,) + sample_shape``: slice off
    the pad, cast, reshape."""
    batch = rows.shape[0]
    out = rows.reshape(batch, -1)[:, :_width(sample_shape)]
    return out.astype(out_dtype or rows.dtype).reshape(
        (batch,) + sample_shape)


def gather_rows(store, indices, sample_shape, out_dtype=None):
    """store x (B,) -> ``(B,) + sample_shape``: the kernel over the
    store as it is, then ops over the B gathered rows.  Traceable (the
    epoch scans call it in their body).  Indices are clamped into range:
    a DMA from a row that is not there would fault the chip."""
    indices = jnp.clip(indices.astype(jnp.int32), 0, store.shape[0] - 1)
    return _samples(_kernel_rows(store, indices), tuple(sample_shape),
                    out_dtype)


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
        return _lanes(gather_rows(labels, indices // LANES, (LANES,)),
                      indices)


def _lanes(rows, indices):
    """Label ``indices[i]`` out of row ``i`` of the (B, 128) label rows."""
    return jnp.take_along_axis(
        rows, (indices % LANES)[:, None], axis=1)[:, 0]


# -- the gather under a mesh --------------------------------------------------

def _own_rows(shard, rows, count, data_axis, pick=None):
    """Inside a ``shard_map`` over ``data_axis``: this chip's ``shard``
    of a :func:`shard_store` x the window's (B,) store rows -> this
    chip's ``B / n`` rows of the window, in the window's order, zero
    from the window's ``count``-th on.  Every chip runs the kernel over
    the whole window, on row 0 where the row is another chip's or past
    ``count``, zeroes those, and a reduce-scatter adds the chips'
    windows up: each row is one chip's row plus zeros, so no value
    changes.  ``pick`` maps the (B, S, L) gathered rows to what is
    exchanged."""
    local = rows - lax.axis_index(data_axis) * shard.shape[0]
    owned = ((jnp.arange(rows.shape[0]) < count) & (local >= 0) &
             (local < shard.shape[0]))
    got = _kernel_rows(shard, jnp.where(owned, local, 0))
    if pick is not None:
        got = pick(got)
    owned = owned.reshape((-1,) + (1,) * (got.ndim - 1))
    return lax.psum_scatter(jnp.where(owned, got, 0), data_axis,
                            scatter_dimension=0, tiled=True)


def _over_mesh(local_gather, mesh, data_axis, store, indices, count):
    chips = mesh.shape[data_axis]
    if indices.shape[0] % chips:
        raise ValueError(
            "minibatch rows %d not divisible by mesh axis %r=%d"
            % (indices.shape[0], data_axis, chips))
    whole = PartitionSpec()
    return jax.shard_map(
        local_gather, mesh=mesh,
        in_specs=(PartitionSpec(data_axis), whole, whole),
        out_specs=PartitionSpec(data_axis), check_vma=False)(
            store, indices.astype(jnp.int32), count)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "data_axis", "out_dtype", "sample_shape"))
def mesh_gather_minibatch(store, indices, count, *, mesh, data_axis,
                          out_dtype=None, sample_shape):
    """:func:`gather_minibatch` where ``store`` is a
    :func:`shard_store` over ``data_axis``: the same rows in the same
    order, split over the axis as ``parallel.api.batch_sharding`` splits
    a batch; the rows from ``count`` on are zero.  The ops over the
    gathered rows run on each chip's ``B / n``."""
    sample_shape = tuple(sample_shape)

    def local_gather(shard, indices, count):
        return _samples(_own_rows(shard, indices, count, data_axis),
                        sample_shape, out_dtype)

    with jax.named_scope(SCOPE):
        return _over_mesh(local_gather, mesh, data_axis, store, indices,
                          count)


@functools.partial(jax.jit, static_argnames=("mesh", "data_axis"))
def mesh_gather_labels(store, indices, count, *, mesh, data_axis):
    """:func:`gather_labels` where ``store`` is the label store as a
    :func:`shard_store`; the labels from ``count`` on are -1."""
    def local_gather(shard, indices, count):
        labels = _own_rows(
            shard, indices // LANES, count, data_axis,
            pick=lambda rows: _lanes(rows.reshape(-1, LANES), indices))
        part = labels.shape[0]
        mine = lax.axis_index(data_axis) * part + jnp.arange(part)
        return jnp.where(mine < count, labels, -1)

    with jax.named_scope(SCOPE):
        return _over_mesh(local_gather, mesh, data_axis, store, indices,
                          count)
