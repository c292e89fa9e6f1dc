"""Device backends.

TPU-native counterpart of reference veles/backends.py:166,184,190-197.
The registry/priority/auto-selection design is preserved; the devices are:

- :class:`TPUDevice` — JAX on TPU.  The unit of execution is a jitted XLA
  computation, not a hand-launched kernel; ``device`` here mostly carries
  placement (which ``jax.Device`` / mesh), dtype policy, and the autotune
  table for Pallas kernels.
- :class:`CPUDevice` — JAX on host CPU.  Same code path as TPU (XLA:CPU +
  Pallas interpreter), used for tests.
- :class:`NumpyDevice` — pure-numpy pseudo-device, always available;
  units run their ``numpy_*`` methods (reference: backends.py:918).

Selection: ``Device(backend="tpu"|"cpu"|"numpy"|"auto")`` or the
``VELES_BACKEND`` env var / ``root.common.engine.backend`` config.  ``auto``
picks the highest-priority available backend (tpu 30 > cpu 20 > numpy 10),
mirroring the reference's cuda 30 > ocl 20 > numpy 10 ladder, and warns
once when it settles below the top.  A backend asked for BY NAME is never
substituted: ``Device(backend="tpu")`` raises unless JAX's default backend
is ``tpu``.
"""

import json
import logging
import os
import threading

import numpy

from veles_tpu.config import root
from veles_tpu.distributable import Pickleable

__all__ = ["Device", "TPUDevice", "CPUDevice", "NumpyDevice",
           "BackendRegistry"]


class BackendRegistry(type):
    backends = {}
    _demotion_warned = False

    def __init__(cls, name, bases, namespace):
        super(BackendRegistry, cls).__init__(name, bases, namespace)
        backend = namespace.get("BACKEND")
        if backend is not None:
            BackendRegistry.backends[backend] = cls


class Device(Pickleable, metaclass=BackendRegistry):
    """Base device; ``Device(backend=...)`` dispatches to a subclass."""

    BACKEND = None
    PRIORITY = 0

    def __new__(cls, *args, **kwargs):
        if cls is not Device:
            return super(Device, cls).__new__(cls)
        backend = kwargs.get("backend")
        if backend is None:
            backend = os.environ.get("VELES_BACKEND") or \
                root.common.engine.get("backend", "auto")
        if backend == "auto":
            chosen = None
            skipped = []
            for sub in sorted(BackendRegistry.backends.values(),
                              key=lambda c: -c.PRIORITY):
                if sub.available():
                    chosen = sub
                    break
                skipped.append(sub.__name__)
            if chosen is None:
                raise RuntimeError("no available backend")
            if skipped and not BackendRegistry._demotion_warned:
                # a missing accelerator must not demote the run
                # silently; once per process — a CPU-only host would
                # otherwise repeat this for every Device() and drown
                # the signal
                BackendRegistry._demotion_warned = True
                logging.getLogger("Device").warning(
                    "auto backend selected %s; higher-priority "
                    "backend(s) unavailable: %s", chosen.__name__,
                    ", ".join(skipped))
            return super(Device, chosen).__new__(chosen)
        try:
            sub = BackendRegistry.backends[backend]
        except KeyError:
            raise ValueError("unknown backend %r (known: %s)" % (
                backend, sorted(BackendRegistry.backends)))
        return super(Device, sub).__new__(sub)

    def __init__(self, **kwargs):
        kwargs.pop("backend", None)
        super(Device, self).__init__(**kwargs)
        self._computing_power = None

    @classmethod
    def available(cls):
        return False

    @property
    def backend_name(self):
        return self.BACKEND

    @property
    def exists(self):
        """True when real accelerated hardware backs this device."""
        return False

    @property
    def is_async(self):
        """True when execution is asynchronous (needs explicit sync for
        honest timings — the reference's --sync-run concern)."""
        return False

    def sync(self):
        pass

    def thread_pool_attach(self, pool):
        """Per-thread attach hook (reference pushes CUDA contexts here;
        JAX needs nothing, kept for unit-compat)."""

    def thread_pool_detach(self):
        pass

    @property
    def max_group_size(self):
        return 1

    @property
    def computing_power(self):
        """Benchmark-derived rating used for job load balancing
        (reference: accelerated_units.py:768-778)."""
        if self._computing_power is None:
            self._computing_power = self._measure_power()
        return self._computing_power

    def _measure_power(self):
        import time
        size = 1024
        a = numpy.random.RandomState(13).rand(size, size).astype(numpy.float32)
        fn = self.matmul_fn()
        fn(a, a)  # warm-up / compile
        # perf_counter: this rating feeds the master's load balancing;
        # a wall-clock NTP step here would misweight the slave for the
        # whole session
        start = time.perf_counter()
        for _ in range(3):
            result = fn(a, a)
        self.sync_result(result)
        elapsed = (time.perf_counter() - start) / 3
        return 1000.0 / max(elapsed, 1e-9)

    def matmul_fn(self):
        return lambda a, b: numpy.dot(a, b)

    def sync_result(self, result):
        pass

    def __repr__(self):
        return "<%s backend=%s>" % (type(self).__name__, self.BACKEND)


_HOST_CPU_DEVICE = None


def host_compute_context(device=None):
    """Context manager pinning jax ops to the in-process host CPU.

    The numpy backend's unit fallbacks evaluate the same jax math the
    device path jits — but an unpinned eager op (or jit dispatch) runs
    on jax's DEFAULT backend, which on a TPU host is the chip: every
    small host-side op would pay a transfer and a dispatch there.
    Every numpy-path call site wraps itself in this context so "numpy
    backend" really means "this host".

    Pins when ``device`` is None or the numpy backend.  No-op for
    real accelerator devices: the nn-unit call sites then take their
    device-array paths instead, while host-array units (Kohonen, RBM)
    deliberately dispatch to the accelerator and pay a transfer per
    call — that is their accelerated mode, not an oversight.
    """
    import contextlib
    global _HOST_CPU_DEVICE
    if device is not None and not isinstance(device, NumpyDevice):
        return contextlib.nullcontext()
    import jax
    if _HOST_CPU_DEVICE is None:
        _HOST_CPU_DEVICE = jax.local_devices(backend="cpu")[0]
    return jax.default_device(_HOST_CPU_DEVICE)


_COMPILE_CACHE_DIR = None


def enable_compile_cache():
    """THE decision on where XLA's persistent compile cache lives;
    idempotent, returns the directory.  Every jax-backed ``Device``
    and the serve engines call it before their first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, the
    program uses that directory and never sets another.  Unset: the
    fixed ``<checkout>/.veles_cache/jax_cache`` (config.py) — the path
    is part of the cache key, so a directory that moves never hits.
    The min-compile-time/entry-size floors drop to zero either way: a
    serve ladder's sub-second executables are exactly what a restarted
    server needs back.  jax's own key covers program, options, jax
    version and device assignment, so one directory serves every
    model (analog of the reference's kernel binary cache,
    accelerated_units.py:605-636)."""
    global _COMPILE_CACHE_DIR
    if _COMPILE_CACHE_DIR is not None:
        return _COMPILE_CACHE_DIR
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root.common.dirs.cache, "jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _COMPILE_CACHE_DIR = path
    return path


class _JaxDevice(Device):
    """Shared implementation for JAX-backed devices."""

    PLATFORM = None

    def __init__(self, **kwargs):
        self.device_index = kwargs.pop("device_index", 0)
        super(_JaxDevice, self).__init__(**kwargs)
        enable_compile_cache()
        self.init_unpickled()

    def init_unpickled(self):
        super(_JaxDevice, self).init_unpickled()
        self._jax_device_ = None

    @classmethod
    def available(cls):
        import jax
        try:
            return len(jax.devices(cls.PLATFORM)) > 0
        except RuntimeError:  # jax: "Unknown backend" / none present
            return False

    @property
    def jax_device(self):
        if self._jax_device_ is None:
            import jax
            self._jax_device_ = jax.devices(self.PLATFORM)[self.device_index]
        return self._jax_device_

    @property
    def exists(self):
        return True

    @property
    def is_async(self):
        return True

    def sync(self):
        import jax
        jax.effects_barrier()

    def sync_result(self, result):
        if hasattr(result, "block_until_ready"):
            result.block_until_ready()

    def matmul_fn(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def mm(a, b):
            return jnp.dot(a, b)

        def run(a, b):
            return mm(jax.device_put(a, self.jax_device),
                      jax.device_put(b, self.jax_device))
        return run

    def put(self, array):
        import jax
        return jax.device_put(array, self.jax_device)

    def __getstate__(self):
        state = super(_JaxDevice, self).__getstate__()
        state["_computing_power"] = None
        return state


class TPUDevice(_JaxDevice):
    """JAX on TPU.  Fulfils the north-star role of BASELINE.json: the
    backend that compiles accelerated units to XLA computations.

    The TPU must be jax's DEFAULT backend: the kernels decide between
    Mosaic and the Pallas interpreter, and the fused step between the
    Pallas and the stock backward, from ``jax.default_backend()``
    (ops/common.py) — a TPU device beside a CPU default would run the
    interpreter on the chip's data without a word."""

    BACKEND = "tpu"
    PRIORITY = 30
    PLATFORM = "tpu"
    _one_of_n_logged = False

    def __init__(self, **kwargs):
        import jax
        found = jax.default_backend()
        if found != "tpu":
            raise RuntimeError(
                "the tpu backend was asked for but jax's default "
                "backend is %r (devices: %s); refusing to run the "
                "TPU path on it" % (found, jax.devices()))
        super(TPUDevice, self).__init__(**kwargs)
        chips = jax.local_device_count()
        if chips > 1 and not TPUDevice._one_of_n_logged:
            # a Device is ONE chip; spanning the host takes an explicit
            # mesh (sw.fuse(mesh=auto_mesh("data"))) or one serve
            # replica per chip (ReplicaPool) — ROADMAP S6
            TPUDevice._one_of_n_logged = True
            logging.getLogger("Device").info(
                "using 1 of %d local chips (device %d)", chips,
                self.device_index)

    @classmethod
    def available(cls):
        import jax
        return jax.default_backend() == "tpu"


class CPUDevice(_JaxDevice):
    """JAX on host CPU — test/interpreter backend, same code path."""

    BACKEND = "cpu"
    PRIORITY = 20
    PLATFORM = "cpu"

    def put(self, array):
        """XLA:CPU ``device_put`` adopts aligned host buffers ZERO-COPY
        with immutable semantics, and does NOT keep them valid against
        later reuse (measured: a post-put write to the numpy buffer
        changes the jax.Array's contents, and training over recycled
        gather-window/minibatch buffers was nondeterministic).  Take a
        device-side copy and block until it has read the source, so the
        returned array is XLA-owned and the caller may reuse or free
        its buffer immediately — matching real-transfer backends.
        (Handing ``device_put`` a TEMPORARY numpy copy instead
        reproducibly corrupted the process heap — glibc "corrupted
        double-linked list" — so the source must stay alive, which the
        caller guarantees for the duration of this call.)"""
        import jax
        dev = jax.device_put(array, self.jax_device)
        if isinstance(array, numpy.ndarray):
            dev = jax.numpy.copy(dev)
            dev.block_until_ready()
        return dev


class NumpyDevice(Device):
    """Pure numpy pseudo-device; always available."""

    BACKEND = "numpy"
    PRIORITY = 10

    @classmethod
    def available(cls):
        return True


class DeviceInfo(object):
    """Per-chip autotune table for Pallas kernel tile sizes.

    TPU analog of the reference's ``devices/device_infos.json`` block-size
    database (reference: backends.py:88-143).  Keyed by device kind and
    op signature; persisted under the cache dir.
    """

    _lock = threading.Lock()

    def __init__(self, device_kind):
        self.device_kind = device_kind
        self.table = {}
        self._path = os.path.join(root.common.dirs.cache,
                                  "device_infos.json")
        self._load()

    #: shipped autotune tables (analog of the reference's checked-in
    #: devices/device_infos.json) — consulted when the cache is cold
    SHIPPED_PATH = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "devices", "device_infos.json")

    def _load(self):
        """The shipped table (part of the checkout — missing is an
        error), overlaid by what this checkout's own autotune runs
        persisted (a cache: absent on a clean export, and a corrupt
        one is reported and ignored)."""
        with open(self.SHIPPED_PATH) as fin:
            self.table = dict(json.load(fin).get(self.device_kind, {}))
        try:
            with open(self._path) as fin:
                overlay = json.load(fin)
        except FileNotFoundError:
            return
        except ValueError as exc:
            logging.getLogger("Device").warning(
                "ignoring corrupt autotune overlay %s: %s",
                self._path, exc)
            return
        self.table.update(overlay.get(self.device_kind, {}))

    def get(self, op_key, default=None):
        return self.table.get(op_key, default)

    def put(self, op_key, value):
        self.table[op_key] = value
        self._save()

    def _save(self):
        with DeviceInfo._lock:
            data = {}
            try:
                with open(self._path) as fin:
                    data = json.load(fin)
            except (OSError, ValueError):
                pass
            data[self.device_kind] = self.table
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            tmp = self._path + ".tmp"
            with open(tmp, "w") as fout:
                json.dump(data, fout, indent=1, sort_keys=True)
            os.replace(tmp, self._path)
