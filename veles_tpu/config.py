"""Global configuration tree.

TPU-native equivalent of the reference's autovivifying config system
(reference: veles/config.py:60,152,165). A :class:`Config` node creates child
nodes on attribute access, can be called to update leaves in bulk, supports
per-key protection against accidental overwrite, and renders itself as a tree.

Site overrides load in this order (later wins):
``/etc/default/veles_tpu`` -> ``~/.veles_tpu`` -> ``./site_config.py``.
Each is a Python file executed with ``root`` in scope.
"""

import os
import runpy
import threading

__all__ = ["Config", "root", "get", "validate_kwargs", "precision_dtype"]


class Config(object):
    """A node in the configuration tree.

    Attribute access auto-creates child ``Config`` nodes, so
    ``root.common.engine.precision = "float32"`` just works.  Calling a node
    with a mapping (or keyword arguments) updates the subtree recursively.
    """

    def __init__(self, path):
        self.__dict__["_path_"] = path
        self.__dict__["_protected_"] = set()

    @property
    def path(self):
        return self.__dict__["_path_"]

    def __call__(self, *args, **kwargs):
        if len(args) > 1:
            raise TypeError("Config accepts at most one positional mapping")
        if args:
            self.update(args[0])
        if kwargs:
            self.update(kwargs)
        return self

    def update(self, mapping):
        """Recursively merge ``mapping`` into this subtree."""
        if isinstance(mapping, Config):
            mapping = mapping.as_dict()
        if not isinstance(mapping, dict):
            raise TypeError("Config.update requires a dict, got %s" %
                            type(mapping))
        for key, value in mapping.items():
            if isinstance(value, dict):
                node = getattr(self, key)
                if not isinstance(node, Config):
                    node = Config("%s.%s" % (self.path, key))
                    setattr(self, key, node)
                node.update(value)
            else:
                setattr(self, key, value)
        return self

    def protect(self, *names):
        """Forbid future reassignment of the given child keys."""
        self.__dict__["_protected_"].update(names)

    def __getattr__(self, name):
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        node = Config("%s.%s" % (self.__dict__["_path_"], name))
        self.__dict__[name] = node
        return node

    def __setattr__(self, name, value):
        if name in self.__dict__["_protected_"]:
            raise AttributeError(
                "Config key %s.%s is protected" % (self.path, name))
        self.__dict__[name] = value

    def __contains__(self, name):
        return name in self.__dict__ and not name.endswith("_")

    def get(self, name, default=None):
        """Return the leaf value if it was explicitly set, else ``default``."""
        value = self.__dict__.get(name, default)
        if isinstance(value, Config):
            return default
        return value

    def as_dict(self):
        out = {}
        for key, value in self.__dict__.items():
            if key.endswith("_"):
                continue
            if isinstance(value, Config):
                sub = value.as_dict()
                if sub:
                    out[key] = sub
            else:
                out[key] = value
        return out

    def print_(self, indent=0, out=None):
        import sys
        out = out or sys.stdout
        for key, value in sorted(self.__dict__.items()):
            if key.endswith("_"):
                continue
            if isinstance(value, Config):
                out.write("%s%s:\n" % ("  " * indent, key))
                value.print_(indent + 1, out)
            else:
                out.write("%s%s: %r\n" % ("  " * indent, key, value))

    def __repr__(self):
        return "<Config %s: %s>" % (self.path, self.as_dict())

    # Pickle support: Config participates in workflow snapshots.
    def __getstate__(self):
        return {"path": self.path, "tree": self.as_dict(),
                "protected": sorted(self.__dict__["_protected_"])}

    def __setstate__(self, state):
        self.__dict__["_path_"] = state["path"]
        self.__dict__["_protected_"] = set()
        self.update(state["tree"])
        self.__dict__["_protected_"].update(state.get("protected", ()))


def get(node, default=None):
    """Return ``node`` unless it is an unset Config placeholder."""
    if isinstance(node, Config):
        return default
    return node


def validate_kwargs(caller, **kwargs):
    """Warn about keyword arguments that are unset Config placeholders."""
    for name, value in kwargs.items():
        if isinstance(value, Config):
            import warnings
            warnings.warn(
                "%s: keyword argument %r is an unset config key %s" %
                (type(caller).__name__, name, value.path))


#: The global configuration tree.
root = Config("root")

#: everything the program caches — the XLA compile cache (unless
#: JAX_COMPILATION_CACHE_DIR places it elsewhere), tuned schedules, the
#: native build, datasets, snapshots — lives under ONE git-ignored
#: directory of the checkout.  The path is part of the compile cache's
#: key, so it must not move between runs of one checkout.
_DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".veles_cache")

root.common.update({
    "dirs": {
        "cache": _DEFAULT_CACHE,
        "datasets": os.environ.get(
            "VELES_DATA", os.path.join(_DEFAULT_CACHE, "datasets")),
        "snapshots": os.path.join(_DEFAULT_CACHE, "snapshots"),
        "user": os.path.expanduser("~/.veles_tpu_dir"),
    },
    "engine": {
        # Numeric precision for model math (precision_dtype() below):
        # the dtype of parameters, solver state and full-batch datasets.
        # bfloat16 keeps the MXU fed; float32 is the reference-compatible
        # default for parity tests.
        "precision_type": os.environ.get("VELES_PRECISION", "float32"),
        # Speed/digits ladder (reference PRECISION_LEVEL analog):
        # 0 (default): fastest — f32 matmul products run a bf16x3 MXU
        #    decomposition (~5e-7 max rel err; |x| >= ~3.39e38 or inf
        #    is out of domain and yields NaN) with plain f32
        #    accumulation;
        # 1: true-f32 (HIGHEST) products + Kahan-compensated sums;
        # 2: level 1 plus Neumaier compensation (most digits, ~2x
        #    slower than level 1).  See ops/matmul.py.
        "precision_level": int(os.environ.get("VELES_PRECISION_LEVEL", "0")),
        "backend": os.environ.get("VELES_BACKEND", "auto"),
        # On TPU the per-unit dispatch loop is 8-25x slower than the
        # fused single-dispatch train step (QUALITY.json results_tpu
        # history), so StandardWorkflow fuses automatically when the
        # resolved device is a TPU.  Set VELES_AUTO_FUSE=0 (or the CLI
        # --no-fuse) to keep the per-unit graph for debugging.
        "auto_fuse": os.environ.get("VELES_AUTO_FUSE", "1") != "0",
        # Async double-buffered input pipeline riding on the fused
        # step (pipeline_input.Prefetcher): host fill + H2D of
        # minibatch k+1 overlap step k.  Applies to the auto-fused
        # path; VELES_PIPELINE_INPUT=0 opts out.
        "pipeline_input": os.environ.get(
            "VELES_PIPELINE_INPUT", "1") != "0",
    },
    "snapshot": {
        # --resume auto|PATH: restore the validated _current target (or
        # the given snapshot) before initialize; empty = fresh start
        "resume": "",
        # retention: keep only the newest N snapshots (+ best-by-metric
        # and the _current target); 0 = unlimited, reference parity
        "keep": 0,
    },
    "trace": {
        "run": False,
        "event_file": None,
    },
    "timings": False,
    "disable": {
        "plotting": False,
        "snapshotting": False,
        "publishing": False,
    },
    "test_dataset_root": os.environ.get("VELES_TEST_DATA", "/tmp/veles_tpu"),
    "web": {
        "host": "localhost",
        "port": 8090,
        "notification_interval": 1,
    },
    "graphics": {"multicast_address": "239.192.1.1"},
})

def precision_dtype():
    """``root.common.engine.precision_type`` as a numpy dtype: the
    dtype forward units create their parameters in and full-batch
    loaders store their dataset in (solver state follows the
    parameters).  "bfloat16" is the ml_dtypes extension type jax
    itself uses, so host buffers and device arrays agree."""
    import numpy
    name = root.common.engine.precision_type
    if name == "bfloat16":
        import ml_dtypes
        return numpy.dtype(ml_dtypes.bfloat16)
    return numpy.dtype(name)


_site_lock = threading.Lock()
_site_loaded = False


def load_site_configs():
    """Execute site override files (idempotent)."""
    global _site_loaded
    with _site_lock:
        if _site_loaded:
            return
        _site_loaded = True
        for path in ("/etc/default/veles_tpu",
                     os.path.expanduser("~/.veles_tpu"),
                     os.path.join(os.getcwd(), "site_config.py")):
            if os.path.exists(path):
                try:
                    runpy.run_path(path, init_globals={"root": root})
                except Exception as exc:  # pragma: no cover
                    import warnings
                    warnings.warn("failed to load site config %s: %s" %
                                  (path, exc))
