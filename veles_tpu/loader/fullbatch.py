"""FullBatchLoader — whole dataset resident on device (HBM).

TPU-native counterpart of reference veles/loader/fullbatch.py:79,467.
Preserved semantics: `create_originals` host allocation, validation
re-split by ratio, normalization applied ONCE to the original dataset at
initialize (reference fullbatch.py:336-347), minibatch gather by shuffled
index window, zero-padding of short minibatches, labels mapped to ints up
front.

TPU redesign (reference's GPU path was a per-step __global gather kernel,
ocl/fullbatch_loader.cl:5-50): at `initialize` the dataset goes into HBM
once, as a ROW STORE (ops/gather.py: every row whole memory tiles, so a
row is one contiguous run of HBM).  The host buffer `create_originals`
allocates IS that store, and `original_data.mem` a strided
`(N,) + sample_shape` window on it, so `load_data()` and the normalizer
write store layout with no copy and the upload is the buffer as it is; a
dataset assigned as a plain ndarray takes one host copy.  Each serve step
runs ops.gather.gather_minibatch on the store — one Pallas kernel whose
scalar-prefetched index window routes a DMA per sample, then ops over
the gathered rows only — and adopts the result as the device-side
minibatch with NO host round-trip (Array.set_device_array).  The stores
(data, labels, MSE targets) are derived state: not pickled, rebuilt by
`initialize`, which reads the host rows as they are then — rows written
later reach the device at the next `initialize`.  `original_data` itself
never goes to the device: the table is there once.  On the numpy backend
the same contract runs through the host path, which is what the test
base uses for parity checks.

Under a mesh (the trainer was fused with one: `lay_over_mesh`) the same
stores are laid over its data axis by rows, each chip holding
`ceil(N / chips)` of them and none the table, and the minibatch is
gathered onto the mesh already split as the data-parallel step takes it
(ops/gather.py, "the gather under a mesh"): the shuffle stays global, the
rows and their order are the one-chip gather's, and nothing of a
minibatch passes through the host.
"""

import numpy

from veles_tpu.backends import NumpyDevice
from veles_tpu.config import precision_dtype
from veles_tpu.loader.base import (
    Loader, LoaderError, LoaderMSEMixin, TRAIN, VALID)
from veles_tpu.memory import Array
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu import ops
from veles_tpu.ops import gather

__all__ = ["FullBatchLoader", "FullBatchLoaderMSE"]


class FullBatchLoader(Loader):
    """Dataset in one Array; minibatches gathered on device."""

    def __init__(self, workflow, **kwargs):
        super(FullBatchLoader, self).__init__(workflow, **kwargs)
        self.validation_ratio = kwargs.get("validation_ratio", None)
        self.on_device = kwargs.get("on_device", True)
        self.original_data = Array()
        self.original_labels = []
        self.device = None
        #: storage dtype of the dataset and its minibatches; follows
        #: the configured model precision so the two meet in one dtype
        self.dtype = numpy.dtype(kwargs.get("dtype", precision_dtype()))

    @staticmethod
    def _coerce_array(value):
        """Accept `loader.original_data = ndarray` (the natural user
        assignment) as well as a prepared Array."""
        if isinstance(value, Array):
            return value
        arr = Array()
        if value is not None:
            arr.mem = numpy.ascontiguousarray(value)
        return arr

    @property
    def original_data(self):
        return self._original_data

    @original_data.setter
    def original_data(self, value):
        self._original_data = self._coerce_array(value)

    @property
    def original_labels(self):
        return self._original_labels

    @original_labels.setter
    def original_labels(self, value):
        # ndarray assignment is the natural user move; the mapping pass
        # below needs a plain list (labels may be any hashable)
        if isinstance(value, numpy.ndarray):
            value = value.tolist()
        self._original_labels = [] if value is None else value

    def init_unpickled(self):
        super(FullBatchLoader, self).init_unpickled()
        # trailing-underscore attrs are not pickled; the mapped labels
        # are rebuilt from original_labels by _map_original_labels()
        self._mapped_original_labels_ = Array()
        # the device row stores by what they hold ("data", "labels",
        # "targets"): derived state, rebuilt by initialize
        self._stores_ = {}
        # what a step gathers, in store order: (store, its rows' shape
        # or None for labels, the minibatch Array that adopts them)
        self._served_ = []
        # the device path of fill_indices: the upload of the index
        # window and the dispatch of the two gather programs
        self._m_gather_ = _registry.histogram("loader.gather_s")
        # building and uploading the stores, once an initialize
        self._m_store_ = _registry.histogram("loader.store_s")
        # minibatches gathered over a mesh (0 on one chip)
        self._m_mesh_gathers_ = _registry.counter("loader.mesh_gathers")

    @property
    def shape(self):
        if not self.original_data:
            raise LoaderError("load_data() has not created original_data")
        return self.original_data.shape[1:]

    def create_originals(self, dshape, labels=True):
        """Allocate original_data (+labels) for load_data() to fill: on
        the device path a window on a host buffer in row-store layout,
        which ``_store_rows`` uploads as it is."""
        if self._use_device_path():
            self.original_data.set_host_view(gather.host_store(
                self.total_samples, dshape, self.dtype)[1])
        else:
            self.original_data.mem = numpy.zeros(
                (self.total_samples,) + tuple(dshape), self.dtype)
        if labels:
            self._mapped_original_labels_.mem = numpy.zeros(
                self.total_samples, Loader.LABEL_DTYPE)
            self.original_labels[:] = [None] * self.total_samples

    def initialize(self, device=None, **kwargs):
        self.device = device
        # the device lets go of the last initialize's table first
        self._stores_ = {}
        self._served_ = []
        axes = getattr(self, "mesh_axes", None)
        if axes and self._mesh_ is None:  # unpickled
            from veles_tpu.parallel.mesh import restore_mesh
            self._mesh_ = restore_mesh(axes, self.warning)
        if self._mesh_ is not None and self._mesh_.is_multi_process:
            # every process's loader serves its own rows there, and the
            # trainer stitches them (parallel.shard_host_batch)
            self._mesh_ = None
        result = super(FullBatchLoader, self).initialize(**kwargs)
        self.analyze_original_dataset()
        self._map_original_labels()
        if self._use_device_path():
            # one-time HBM residency; per-step gathers read from here
            self._store_rows("data", self.original_data,
                             self.minibatch_data)
            if self.has_labels:
                self._mapped_original_labels_.map_read()
                self._store_rows(
                    "labels", self._mapped_original_labels_,
                    self.minibatch_labels, build=gather.build_label_store)
            self.shuffled_indices.initialize(self.device)
        return result

    def _use_device_path(self):
        return (self.on_device and self.device is not None and
                not isinstance(self.device, NumpyDevice) and
                self.device.exists)

    def _store_rows(self, name, array, minibatch, build=None):
        """Upload ``array``'s rows as the row store ``name``, from which
        every step gathers ``minibatch`` (the upload is asynchronous:
        the span times the host's part).  Rows that ``create_originals``
        made are in store layout already; any other take one host copy,
        after which ``array.mem`` is the window on that copy and the
        host, too, holds the rows once.  Under a mesh the store goes
        over its data axis, each chip its own rows."""
        with _tracer.scope("loader.store", cat="loader",
                           hist=self._m_store_):
            if build is not None:
                buf, sample_shape = build(array.mem), None
            else:
                sample_shape = array.mem.shape[1:]
                buf = gather.host_store_of(array.mem)
                if buf is None:
                    buf = gather.build_store(array.mem)
                    array.set_host_view(gather.rows_of(buf, sample_shape))
            self._stores_[name] = (
                self.device.put(buf) if self._mesh_ is None else
                gather.shard_store(buf, self._mesh_, self.data_axis))
        self._served_.append((name, sample_shape, minibatch))
        _registry.gauge("loader.store_bytes").set(
            sum(store.nbytes for store in self._stores_.values()))

    def create_minibatch_data(self):
        self.minibatch_data.mem = numpy.zeros(
            (self.max_minibatch_size,) + self.shape, self.dtype)

    # -- analysis (once, on originals) --------------------------------------

    def analyze_dataset(self):
        pass  # replaced by analyze_original_dataset after super().initialize

    def normalize_minibatch(self):
        pass  # originals are already normalized

    def analyze_original_dataset(self):
        if self.class_lengths[TRAIN] > 0:
            self.normalizer.analyze(
                self.original_data.mem[self.class_end_offsets[VALID]:])
        elif not self.normalizer.initialized:
            raise LoaderError(
                "no train samples and the normalizer is uninitialized")
        self.normalizer.normalize(self.original_data.mem)

    def _map_original_labels(self):
        if not self.original_labels or all(
                l is None for l in self.original_labels):
            self.original_labels = []
            return
        if not self.labels_mapping:
            uniques = sorted(set(self.original_labels))
            self.labels_mapping.update(
                (lbl, i) for i, lbl in enumerate(uniques))
        if self._mapped_original_labels_.mem is None:
            # labels assigned directly (no create_originals call)
            self._mapped_original_labels_.mem = numpy.zeros(
                len(self.original_labels), Loader.LABEL_DTYPE)
        self._mapped_original_labels_.map_write()
        for i, raw in enumerate(self.original_labels):
            self._mapped_original_labels_[i] = self.labels_mapping[raw]
        self.minibatch_labels.mem = numpy.zeros(
            self.max_minibatch_size, Loader.LABEL_DTYPE)

    def _build_labels_mapping_if_needed(self):
        self._map_original_labels()

    # -- validation re-split (reference fullbatch.py:349) --------------------

    def resize_validation(self, ratio=None):
        """Move a random train slice into validation (index rearrange)."""
        ratio = self.validation_ratio if ratio is None else ratio
        if ratio is None:
            return
        if ratio <= 0:
            self.class_lengths[TRAIN] += self.class_lengths[VALID]
            self.class_lengths[VALID] = 0
            self._calc_class_end_offsets()
            return
        total = self.class_lengths[VALID] + self.class_lengths[TRAIN]
        want_valid = int(numpy.round(ratio * total))
        offset = self.class_end_offsets[VALID] - self.class_lengths[VALID]
        window = numpy.arange(offset, offset + total)
        self.prng.shuffle(window)
        order = numpy.concatenate([
            numpy.sort(window[:want_valid]),
            numpy.sort(window[want_valid:])])
        self.original_data.map_write()
        self.original_data.mem[offset:offset + total] = \
            self.original_data.mem[order]
        if self.original_labels:
            self.original_labels[offset:offset + total] = [
                self.original_labels[i] for i in order]
        self.class_lengths[VALID] = want_valid
        self.class_lengths[TRAIN] = total - want_valid
        self._calc_class_end_offsets()

    # -- serving -------------------------------------------------------------

    def fill_indices(self, start_offset, count):
        if not self._use_device_path():
            return super(FullBatchLoader, self).fill_indices(
                start_offset, count)
        window = self._index_window(start_offset, count)
        with _tracer.scope("loader.gather", cat="loader",
                           hist=self._m_gather_):
            for (_, _, minibatch), rows in zip(
                    self._served_, self._gather(window, count)):
                minibatch.set_device_array(rows, self.device)
        return True

    def _gather(self, window, count):
        """The ``window``'s rows out of every store, in store order, as
        device arrays: the rows from ``count`` on zero, their labels -1.
        On one chip the two gather programs on the loader's device;
        under a mesh the same gather over its data axis, each chip left
        with its part of the window (ops/gather.py): the arrays carry
        the batch sharding the trainer's step takes."""
        mesh = self._mesh_
        short = count < self.max_minibatch_size
        if mesh is None:
            idx_dev = self.device.put(window)
        else:
            from veles_tpu.parallel.api import replicate
            self._m_mesh_gathers_.inc()
            over = dict(mesh=mesh, data_axis=self.data_axis)
            window, count = replicate(mesh, (window, numpy.int32(count)))
        for name, sample_shape, _ in self._served_:
            store = self._stores_[name]
            if mesh is not None:
                yield (gather.mesh_gather_labels(
                           store, window, count, **over)
                       if sample_shape is None else
                       gather.mesh_gather_minibatch(
                           store, window, count, out_dtype=self.dtype,
                           sample_shape=sample_shape, **over))
            elif sample_shape is None:
                labels = ops.gather_labels(store, idx_dev)
                yield (self._mask_tail_labels(labels, count) if short
                       else labels)
            else:
                rows = ops.gather_minibatch(
                    store, idx_dev, out_dtype=self.dtype,
                    sample_shape=sample_shape)
                yield self._zero_tail(rows, count) if short else rows

    def _index_window(self, start_offset, count):
        """The minibatch's ``count`` shuffled indices in a window of the
        full minibatch size (0 past ``count``: a row that exists), and
        the same in ``minibatch_indices`` (-1 past ``count``)."""
        self.shuffled_indices.map_read()
        window = numpy.zeros(self.max_minibatch_size, Loader.INDEX_DTYPE)
        window[:count] = \
            self.shuffled_indices.mem[start_offset:start_offset + count]
        self.minibatch_indices.mem[:count] = window[:count]
        self.minibatch_indices.mem[count:] = -1
        return window

    @staticmethod
    def _zero_tail(data, count):
        import jax.numpy as jnp
        mask = (jnp.arange(data.shape[0]) < count)
        return data * mask.astype(data.dtype).reshape(
            (-1,) + (1,) * (data.ndim - 1))

    @staticmethod
    def _mask_tail_labels(labels, count):
        import jax.numpy as jnp
        return jnp.where(jnp.arange(labels.shape[0]) < count, labels, -1)

    def fill_minibatch(self):
        idx = self.minibatch_indices.mem[:self.minibatch_size]
        self.minibatch_data.map_write()
        self.original_data.map_read()
        self.minibatch_data.mem[:self.minibatch_size] = \
            self.original_data.mem[idx]
        if self.has_labels:
            self._mapped_original_labels_.map_read()
            self.minibatch_labels.map_write()
            self.minibatch_labels.mem[:self.minibatch_size] = \
                self._mapped_original_labels_.mem[idx]

    def map_minibatch_labels(self):
        pass  # labels were mapped once in _map_original_labels


class FullBatchLoaderMSE(LoaderMSEMixin, FullBatchLoader):
    """FullBatch variant serving (data, target) pairs
    (reference: fullbatch.py:467-566)."""

    def __init__(self, workflow, **kwargs):
        super(FullBatchLoaderMSE, self).__init__(workflow, **kwargs)
        self.original_targets = Array()

    @property
    def original_targets(self):
        return self._original_targets

    @original_targets.setter
    def original_targets(self, value):
        self._original_targets = self._coerce_array(value)

    def create_minibatch_data(self):
        super(FullBatchLoaderMSE, self).create_minibatch_data()
        self.minibatch_targets.mem = numpy.zeros(
            (self.max_minibatch_size,) + self.original_targets.shape[1:],
            self.dtype)

    def initialize(self, device=None, **kwargs):
        result = super(FullBatchLoaderMSE, self).initialize(
            device=device, **kwargs)
        if self.class_lengths[TRAIN] > 0:
            self.target_normalizer.analyze(self.original_targets.mem)
        self.target_normalizer.normalize(self.original_targets.mem)
        if self._use_device_path():
            self._store_rows("targets", self.original_targets,
                             self.minibatch_targets)
        return result

    def fill_minibatch(self):
        super(FullBatchLoaderMSE, self).fill_minibatch()
        idx = self.minibatch_indices.mem[:self.minibatch_size]
        self.original_targets.map_read()
        self.minibatch_targets.map_write()
        self.minibatch_targets.mem[:self.minibatch_size] = \
            self.original_targets.mem[idx]
