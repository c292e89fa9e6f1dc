"""ctypes bindings + on-demand build for the native inference runtime.

Counterpart of the reference's libVeles consumption path: a package
exported by Workflow.package_export is loaded and executed by the C++
runtime (native/src/), with the greedy strip-packing arena planner and
the batch-sharding thread-pool engine.  Build uses cmake+make the first
time and caches the shared library under the program's cache root
(``root.common.dirs.cache``: a git-ignored directory of the checkout,
so a clean export builds it from ``native/src`` and nothing else).
"""

import ctypes
import os
import subprocess
import threading

import numpy

__all__ = ["NativeWorkflow", "build_native", "native_available",
           "source_digest"]

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_ROOT, "native")


def source_digest():
    """Hash of every native source file: the cache key (computed once,
    on first use — importing this module must not walk the source
    tree).  An existence-only check against a shared cache dir would
    keep serving a stale .so across source changes and checkouts."""
    global _digest
    if _digest is None:
        import hashlib
        digest = hashlib.sha256()
        for dirpath, _, filenames in sorted(os.walk(_NATIVE_DIR)):
            for filename in sorted(filenames):
                if filename.endswith((".cc", ".h", ".txt")):
                    path = os.path.join(dirpath, filename)
                    digest.update(filename.encode())
                    with open(path, "rb") as fin:
                        digest.update(fin.read())
        _digest = digest.hexdigest()[:16]
    return _digest


def _lib_path():
    """Digest-keyed build dir + library path, resolved lazily."""
    from veles_tpu.config import root
    build_dir = os.path.join(root.common.dirs.cache, "native_build",
                             source_digest())
    return build_dir, os.path.join(build_dir, "libveles_tpu_native.so")


_digest = None
_build_lock = threading.Lock()
_lib = None


def build_native(force=False):
    """Build (or rebuild) the shared library; returns its path."""
    with _build_lock:
        build_dir, lib_path = _lib_path()
        if os.path.exists(lib_path) and not force:
            return lib_path
        os.makedirs(build_dir, exist_ok=True)
        subprocess.run(
            ["cmake", "-DCMAKE_BUILD_TYPE=Release", _NATIVE_DIR],
            cwd=build_dir, check=True, capture_output=True)
        subprocess.run(
            ["cmake", "--build", ".", "-j"],
            cwd=build_dir, check=True, capture_output=True)
        return lib_path


def native_available():
    try:
        _load_lib()
        return True
    except Exception:
        return False


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    path = build_native()
    lib = ctypes.CDLL(path)
    lib.veles_workflow_load.restype = ctypes.c_void_p
    lib.veles_workflow_load.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
    lib.veles_workflow_destroy.argtypes = [ctypes.c_void_p]
    lib.veles_workflow_input_size.restype = ctypes.c_longlong
    lib.veles_workflow_input_size.argtypes = [ctypes.c_void_p]
    lib.veles_workflow_output_size.restype = ctypes.c_longlong
    lib.veles_workflow_output_size.argtypes = [ctypes.c_void_p]
    lib.veles_workflow_unit_count.restype = ctypes.c_longlong
    lib.veles_workflow_unit_count.argtypes = [ctypes.c_void_p]
    lib.veles_workflow_arena_size.restype = ctypes.c_longlong
    lib.veles_workflow_arena_size.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int]
    lib.veles_workflow_run.restype = ctypes.c_int
    lib.veles_workflow_run.argtypes = [
        ctypes.c_void_p,
        numpy.ctypeslib.ndpointer(numpy.float32, flags="C_CONTIGUOUS"),
        numpy.ctypeslib.ndpointer(numpy.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    _lib = lib
    return lib


class NativeWorkflow(object):
    """Loads a package and runs batched inference natively."""

    def __init__(self, package_path):
        self._lib = _load_lib()
        err = ctypes.create_string_buffer(1024)
        self._handle = self._lib.veles_workflow_load(
            package_path.encode(), err, len(err))
        if not self._handle:
            raise RuntimeError(
                "native load failed: %s" % err.value.decode())

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.veles_workflow_destroy(handle)
            self._handle = None

    @property
    def input_size(self):
        return int(self._lib.veles_workflow_input_size(self._handle))

    @property
    def output_size(self):
        return int(self._lib.veles_workflow_output_size(self._handle))

    @property
    def unit_count(self):
        return int(self._lib.veles_workflow_unit_count(self._handle))

    def arena_size(self, batch):
        size = int(self._lib.veles_workflow_arena_size(
            self._handle, batch))
        if size < 0:
            raise RuntimeError("arena planning failed")
        return size

    def run(self, batch_data):
        """batch_data: (B, *input_shape) float array -> (B, output_size)."""
        x = numpy.ascontiguousarray(batch_data, numpy.float32)
        batch = x.shape[0]
        if x.size != batch * self.input_size:
            raise ValueError(
                "expected %d floats/sample, got %d" %
                (self.input_size, x.size // max(batch, 1)))
        out = numpy.zeros((batch, self.output_size), numpy.float32)
        err = ctypes.create_string_buffer(1024)
        rc = self._lib.veles_workflow_run(
            self._handle, x.reshape(-1), out.reshape(-1), batch, err,
            len(err))
        if rc != 0:
            raise RuntimeError("native run failed: %s" %
                               err.value.decode())
        return out
