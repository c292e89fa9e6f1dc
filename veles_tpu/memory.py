"""Host/device tensor abstraction.

TPU-native counterpart of reference veles/memory.py:110 (``Array`` with the
explicit ``map_read / map_write / map_invalidate / unmap`` coherence
protocol).  The protocol's *names and semantics* are preserved so unit code
ports unchanged, but the mechanics map onto JAX placement:

==================  =====================================================
reference call       TPU meaning
==================  =====================================================
``map_read``        ensure ``mem`` (numpy) reflects device state
                    (blocking ``numpy.asarray(devmem)`` if device-fresher)
``map_write``       like map_read, then mark host copy dirty
``map_invalidate``  mark host dirty WITHOUT reading device back
``unmap``           if host dirty, ``device_put`` the numpy buffer;
                    ``devmem`` becomes the fresh jax.Array
==================  =====================================================

jax.Arrays are immutable, so there is no zero-copy aliasing; instead the
dirty-bit state machine minimises transfers exactly like the reference's
OpenCL map/unmap path minimised them.  A :class:`Watcher` counts
HBM-resident bytes (reference: memory.py:56).  ``shallow_pickle`` ships
only shape+dtype over the wire (reference: memory.py:477-511).
"""

import threading

import numpy

from veles_tpu.distributable import Pickleable

__all__ = ["Array", "Watcher", "roundup"]


def roundup(num, align):
    rem = num % align
    return num if rem == 0 else num + (align - rem)


class Watcher(object):
    """Tracks bytes resident on devices across all Arrays."""

    _lock = threading.Lock()
    bytes_on_device = 0
    arrays_on_device = 0

    @classmethod
    def add(cls, nbytes):
        with cls._lock:
            cls.bytes_on_device += nbytes
            cls.arrays_on_device += 1

    @classmethod
    def remove(cls, nbytes):
        with cls._lock:
            cls.bytes_on_device -= nbytes
            cls.arrays_on_device -= 1


# coherence states
_HOST_ONLY = 0      # no device buffer
_IN_SYNC = 1        # host == device
_HOST_DIRTY = 2     # host newer than device
_DEVICE_DIRTY = 3   # device newer than host


class Array(Pickleable):
    """A named tensor with a host numpy buffer and an optional device
    (jax) buffer, synchronised through the map/unmap protocol."""

    def __init__(self, data=None, shallow_pickle=False):
        super(Array, self).__init__()
        self._mem = None
        self.shallow_pickle = shallow_pickle
        if data is not None:
            self.mem = data

    def init_unpickled(self):
        super(Array, self).init_unpickled()
        self._device_ = None
        self._devmem_ = None
        self._state_ = _HOST_ONLY
        self._lock_ = threading.RLock()
        self._watched_nbytes_ = 0  # exactly what we told Watcher.add
        # ping-pong host staging (see stage_init); transient by design:
        # a restored Array re-stages lazily on the first pipelined serve
        self._stage_bufs_ = None
        self._stage_pending_ = None
        self._stage_slot_ = 0

    # -- basic container behaviour ----------------------------------------

    @property
    def mem(self):
        return self._mem

    @mem.setter
    def mem(self, value):
        if value is None:
            self.reset()
            return
        self.set_host_view(numpy.ascontiguousarray(value))

    def set_host_view(self, view):
        """Take ``view`` as the host buffer AS IT IS: a strided window
        on a larger buffer stays a window on it (``mem = view`` would
        copy it contiguous).  FullBatchLoader's originals are such
        windows on the row store the device holds."""
        self._mem = view
        # a wholesale buffer swap invalidates the staging slots (their
        # shape/identity no longer matches); re-staged lazily
        self._stage_bufs_ = None
        self._stage_pending_ = None
        if self._device_ is not None:
            self._state_ = _HOST_DIRTY

    @property
    def devmem(self):
        """Current device buffer (jax.Array), pushing host changes first."""
        self.unmap()
        return self._devmem_

    def device_array(self, device):
        """devmem, first attaching ``device`` when the Array is still
        host-only.  Streaming loaders (zmq/restful/interactive feeds)
        hand consumers unattached host Arrays; consumer units pass
        their own device here instead of crashing on a None devmem."""
        with self._lock_:
            if self._device_ is None and device is not None \
                    and device.exists and self._mem is not None:
                self._device_ = device
                self._state_ = _HOST_DIRTY
        return self.devmem

    def resident(self):
        """The device buffer where it holds what the Array holds — a
        result adopted by ``set_device_array`` or an upload the host has
        not written since — else None.  Moves nothing: no upload as
        ``devmem`` makes, no fetch."""
        with self._lock_:
            if self._state_ in (_DEVICE_DIRTY, _IN_SYNC):
                return self._devmem_
        return None

    def __bool__(self):
        return self._mem is not None and self._mem.size > 0

    def __len__(self):
        return 0 if self._mem is None else len(self._mem)

    def __getitem__(self, key):
        self.map_read()
        return self._mem[key]

    def __setitem__(self, key, value):
        self.map_write()
        self._mem[key] = value

    @property
    def shape(self):
        return None if self._mem is None else self._mem.shape

    @property
    def size(self):
        return 0 if self._mem is None else self._mem.size

    @property
    def dtype(self):
        return None if self._mem is None else self._mem.dtype

    @property
    def nbytes(self):
        return 0 if self._mem is None else self._mem.nbytes

    @property
    def sample_size(self):
        """Elements per sample (all dims but the first)."""
        if self._mem is None or self._mem.ndim == 0:
            return 0
        return self._mem.size // self._mem.shape[0]

    def reshape(self, shape):
        self.map_write()
        self._mem = self._mem.reshape(shape)

    def plain(self):
        self.map_read()
        return self._mem.ravel()

    # -- device lifecycle --------------------------------------------------

    @property
    def device(self):
        return self._device_

    def initialize(self, device):
        """Attach to ``device``; the first ``unmap`` uploads the data."""
        with self._lock_:
            if device is None or not device.exists:
                self._device_ = None
                self._state_ = _HOST_ONLY
                return
            if self._device_ is device and self._state_ != _HOST_ONLY:
                return
            self._device_ = device
            if self._mem is not None:
                self._state_ = _HOST_DIRTY

    def reset(self):
        with self._lock_:
            if self._watched_nbytes_:
                Watcher.remove(self._watched_nbytes_)
                self._watched_nbytes_ = 0
            self._mem = None
            self._devmem_ = None
            self._state_ = _HOST_ONLY
            self._stage_bufs_ = None
            self._stage_pending_ = None

    # -- ping-pong host staging (async input pipeline) ----------------------
    #
    # Ownership rules (docs/pipeline_input.md): between stage_begin(slot)
    # and the next stage_begin on the SAME slot, that slot's host buffer
    # belongs to the producer thread; consumers must read the minibatch
    # through the device array returned by stage_put / staged_capture,
    # never through ``mem``.

    @property
    def staged(self):
        return self._stage_bufs_ is not None

    def stage_init(self, nslots=2):
        """Allocate ``nslots`` host staging buffers; slot 0 adopts the
        existing host buffer, the rest are fresh allocations of the
        same shape/dtype."""
        with self._lock_:
            if self._mem is None:
                raise ValueError("stage_init() before mem is allocated")
            self._stage_bufs_ = [self._mem] + [
                numpy.empty_like(self._mem) for _ in range(nslots - 1)]
            self._stage_pending_ = [None] * nslots
            self._stage_slot_ = 0

    def stage_begin(self, slot):
        """Point ``mem`` at ``slot``'s host buffer for a staged fill
        (producer thread).  Blocks until the slot's previous async
        host->device transfer has finished reading the buffer, so an
        in-flight DMA is never overwritten.  No-op when unstaged."""
        with self._lock_:
            if self._stage_bufs_ is None:
                return
            pending = self._stage_pending_[slot]
            self._stage_pending_[slot] = None
        if pending is not None and hasattr(pending, "block_until_ready"):
            try:
                pending.block_until_ready()
            except Exception:
                pass  # a deleted/donated buffer cannot be in flight
        with self._lock_:
            if self._stage_bufs_ is None:
                return
            self._mem = self._stage_bufs_[slot]
            self._stage_slot_ = slot
            # the upcoming fill makes the host buffer authoritative; it
            # also guarantees map_read/map_write cannot replace _mem
            # with a device fetch mid-fill
            self._state_ = (_HOST_DIRTY if self._device_ is not None
                            else _HOST_ONLY)

    def stage_put(self, device):
        """Start the async host->device transfer of the CURRENT host
        buffer and return the resulting device array immediately (JAX
        transfers are asynchronous).  The coherence state is NOT
        touched: the caller owns the returned array, and the host
        buffer must not be refilled before ``stage_begin`` is called
        again on the same slot."""
        with self._lock_:
            dev = device.put(self._mem)
            if self._stage_bufs_ is not None:
                self._stage_pending_[self._stage_slot_] = dev
            self._track_device_bytes(self._mem.nbytes)
            return dev

    def staged_capture(self, device):
        """Device-side array for the just-served minibatch: the adopted
        device buffer when a device path already produced one
        (set_device_array), else an async ``stage_put`` of the staged
        host fill."""
        with self._lock_:
            if self._state_ == _DEVICE_DIRTY and self._devmem_ is not None:
                return self._devmem_
        return self.stage_put(device)

    # -- coherence protocol ------------------------------------------------

    def map_read(self):
        with self._lock_:
            if self._state_ == _DEVICE_DIRTY:
                self._mem = numpy.asarray(self._devmem_)
                self._state_ = _IN_SYNC

    def map_write(self):
        with self._lock_:
            self.map_read()
            if self._state_ != _HOST_ONLY:
                self._state_ = _HOST_DIRTY

    def map_invalidate(self):
        with self._lock_:
            if self._state_ != _HOST_ONLY:
                self._state_ = _HOST_DIRTY

    def unmap(self):
        with self._lock_:
            if self._state_ == _HOST_DIRTY or (
                    self._state_ == _IN_SYNC and self._devmem_ is None):
                if self._device_ is None:
                    return
                self._devmem_ = self._device_.put(self._mem)
                self._track_device_bytes(self._mem.nbytes)
                self._state_ = _IN_SYNC

    def _track_device_bytes(self, nbytes):
        """Keep Watcher in sync with exactly what this Array contributed."""
        if nbytes != self._watched_nbytes_:
            if self._watched_nbytes_:
                Watcher.remove(self._watched_nbytes_)
            if nbytes:
                Watcher.add(nbytes)
            self._watched_nbytes_ = nbytes

    def set_device_array(self, jax_array, device=None):
        """Adopt a fresh device-side result (the output of a jitted step)
        without a host round-trip; host copy becomes stale."""
        with self._lock_:
            if device is not None:
                self._device_ = device
            self._devmem_ = jax_array
            self._state_ = _DEVICE_DIRTY
            if self._mem is None:
                # keep shape/dtype metadata without materialising
                self._mem = numpy.zeros(jax_array.shape, jax_array.dtype)
            self._track_device_bytes(self._mem.nbytes)

    def detach_device(self):
        """Materialise the host copy and DROP the device reference.

        For adopting buffers another computation is about to donate
        (the fused train step donates its input state): keeping the
        reference would hand later devmem readers a deleted jax.Array.
        Host becomes authoritative; a future unmap re-uploads."""
        with self._lock_:
            self.map_read()
            if self._devmem_ is not None:
                self._devmem_ = None
                self._track_device_bytes(0)
                if self._device_ is not None:
                    self._state_ = _HOST_DIRTY

    def prefetch_host(self):
        """Start an async device->host copy when the device copy is
        authoritative.  A later map_read finds the bytes already local,
        so N arrays wait for one transfer window instead of N in
        sequence (a whole-workflow snapshot reads every parameter)."""
        with self._lock_:
            if self._state_ != _DEVICE_DIRTY:
                return
            if hasattr(self._devmem_, "copy_to_host_async"):
                try:
                    self._devmem_.copy_to_host_async()
                    return
                except Exception:
                    pass  # fall through to the eager fetch
            # backend without async D2H (or a failed async start): fetch
            # eagerly NOW so the caller's later map_read is still local
            # instead of silently degrading to N sequential round trips
            self._mem = numpy.asarray(self._devmem_)
            self._state_ = _IN_SYNC

    # -- pickling ----------------------------------------------------------

    def __getstate__(self):
        shallow = self.shallow_pickle or getattr(
            self, "stripped_pickle", False)
        if not shallow:
            self.map_read()
        state = super(Array, self).__getstate__()
        if shallow:
            # shape and dtype only: no device read for bytes that are
            # dropped (the dtype object, not ``.str`` — bfloat16's is
            # the void '<V2')
            like = (self._devmem_ if self._state_ == _DEVICE_DIRTY
                    else self._mem)
            state["_mem"] = None
            state["_shallow_shape"] = (
                None if like is None
                else (tuple(like.shape), numpy.dtype(like.dtype)))
        return state

    def __setstate__(self, state):
        shallow = state.pop("_shallow_shape", None)
        super(Array, self).__setstate__(state)
        if shallow is not None and self._mem is None:
            shape, dtype = shallow
            self._mem = numpy.zeros(shape, numpy.dtype(dtype))

    def __repr__(self):
        return "<Array shape=%s dtype=%s state=%d>" % (
            self.shape, self.dtype, self._state_)
