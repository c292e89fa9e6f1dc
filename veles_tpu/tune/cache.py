"""Digest-keyed persistent schedule cache (docs/kernels.md,
"Autotuning").

The tuner's winners live beside the XLA compile cache: one JSON table
under ``<cache-dir>/schedule_cache/`` (``VELES_SCHEDULE_CACHE``
overrides the directory) mapping a sha256 digest of

    (op name, padded shape tuple, dtype, precision level,
     device kind, jax version, kernel version)

to the winning schedule — tile/grid parameters ONLY, never anything
that changes math (the precision level is part of the KEY: a schedule
tuned at level 0 can never serve a level-1 call).  The kernel version
rides the digest so optima measured on an old algorithm are a MISS for
a new one, exactly like ``MATMUL_KERNEL_VERSION`` gated the old
DeviceInfo table.

``schedule_for`` is the kernels' consult hook (``ops/matmul.py``,
``ops/conv_vjp.py``, ``ops/pool_bwd.py``): an in-memory table lookup
after one lazy disk load, counted as ``tune.cache_hits`` /
``tune.cache_misses``.  A corrupt or stale entry is a logged WARNING
and a miss — the static ``_DEFAULT_BLOCKS`` tables stay the fallback,
a bad cache can never crash a kernel call.  Under a
:func:`record_specs` context every consult also records its full spec,
which is how ``tune/walk.py`` harvests the shapes a fused step's
lowering actually uses.
"""

import functools
import hashlib
import json
import logging
import os
import threading

__all__ = ["ScheduleCache", "schedule_key", "schedule_for",
           "provenance", "cache_for", "default_cache_dir",
           "record_specs", "tune_counters", "SCHEDULE_CACHE_SCHEMA",
           "MeasurementLog", "measurement_log", "record_measurement",
           "load_bank", "BANK_FILE_NAME"]

logger = logging.getLogger("veles_tpu.tune")

#: bump when the cache FILE layout changes (entry payloads carry their
#: own per-kernel versions inside the digest)
SCHEDULE_CACHE_SCHEMA = 1

_FILE_NAME = "schedules.json"

#: the measured-triple sidecar beside ``schedules.json`` — the cost
#: model's training data (docs/kernels.md, "Autotuning")
_MEASUREMENTS_NAME = "measurements.jsonl"

#: rewrite threshold: when the sidecar exceeds this byte size an append
#: compacts it to the newest ``_MEASUREMENTS_KEEP`` rows (append-only
#: in the common case, bounded in the limit)
_MEASUREMENTS_MAX_BYTES = 8 * 2 ** 20
_MEASUREMENTS_KEEP = 10000

#: the portable fleet-bank file name used by the publish channel
BANK_FILE_NAME = "schedule_bank.json"


def default_cache_dir():
    """``$VELES_SCHEDULE_CACHE`` or ``<root cache dir>/schedule_cache``
    — resolved per call so tests can redirect via the environment."""
    env = os.environ.get("VELES_SCHEDULE_CACHE", "")
    if env:
        return env
    from veles_tpu.config import root
    return os.path.join(root.common.dirs.get("cache", "/tmp"),
                        "schedule_cache")


@functools.lru_cache(maxsize=4096)
def _digest(payload_json):
    return hashlib.sha256(payload_json.encode("utf-8")).hexdigest()


def schedule_key(op, shape, dtype, precision_level, device_kind,
                 extra=None):
    """(digest, payload) for one schedule-cache entry.

    ``shape`` is the PADDED shape tuple (MXU sublane/lane multiples):
    two raw shapes that pad identically run the identical kernel grid,
    so they share one entry.  ``extra`` carries per-family versioning
    (e.g. the kernel algorithm version)."""
    payload = {
        "op": str(op),
        "shape": [int(s) for s in shape],
        "dtype": str(dtype),
        "precision_level": int(precision_level),
        "device_kind": str(device_kind),
        "jax": _jax_version(),
    }
    if extra:
        payload.update({str(k): extra[k] for k in sorted(extra)})
    return _digest(json.dumps(payload, sort_keys=True)), payload


@functools.lru_cache(maxsize=1)
def _jax_version():
    import jax
    return jax.__version__


@functools.lru_cache(maxsize=1)
def _device_kind_cached():
    import jax
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def device_kind():
    """The default device's kind string — the cache-key coordinate that
    keeps a v5e's tiles from serving a v4 (or a CPU test host)."""
    return _device_kind_cached()


class ScheduleCache(object):
    """One on-disk schedule table: lazy load, atomic save, tolerant of
    corruption (a broken file logs a warning and reads as empty — it
    is a CACHE; the static tables are the source of truth)."""

    def __init__(self, path=None):
        self.path = path or os.path.join(default_cache_dir(),
                                         _FILE_NAME)
        self._lock = threading.Lock()
        self._entries = None
        self._warned = set()

    # -- load/save -----------------------------------------------------------

    def _read_disk(self):
        """The on-disk table, or {} (with ONE warning when corrupt)."""
        try:
            with open(self.path) as fin:
                data = json.load(fin)
            if (not isinstance(data, dict)
                    or data.get("schema") != SCHEDULE_CACHE_SCHEMA
                    or not isinstance(data.get("entries"), dict)):
                raise ValueError("unrecognized schedule cache layout")
            return data["entries"]
        except FileNotFoundError:
            return {}
        except (OSError, ValueError) as exc:
            self._warn_once(
                "corrupt", "schedule cache %s unreadable (%s); "
                "falling back to static tables" % (self.path, exc))
            return {}

    def _load(self):
        if self._entries is None:
            self._entries = self._read_disk()
        return self._entries

    def _save(self):
        data = {"schema": SCHEDULE_CACHE_SCHEMA,
                "entries": self._entries or {}}
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as fout:
            json.dump(data, fout, indent=1, sort_keys=True)
            fout.flush()
        os.replace(tmp, self.path)

    def _warn_once(self, key, message):
        if key not in self._warned:
            self._warned.add(key)
            logger.warning(message)

    # -- table API -----------------------------------------------------------

    def get(self, digest):
        """The full entry dict for ``digest`` or None.  A structurally
        invalid entry (no ``schedule`` dict) warns and misses."""
        with self._lock:
            entry = self._load().get(digest)
        if entry is None:
            return None
        if (not isinstance(entry, dict)
                or not isinstance(entry.get("schedule"), dict)):
            self._warn_once(
                digest, "schedule cache entry %s malformed; ignoring "
                "(static tables serve this shape)" % digest[:12])
            return None
        return entry

    def put(self, digest, payload, schedule, fitness=None,
            source="ga", evals=None):
        """Persist one winner.  ``schedule`` is the family's
        tile/grid dict; ``fitness`` the GA's (negative seconds)."""
        entry = dict(payload)
        entry["schedule"] = dict(schedule)
        entry["source"] = source
        if fitness is not None:
            entry["fitness"] = float(fitness)
        if evals is not None:
            entry["evals"] = int(evals)
        with self._lock:
            # re-read the file before the read-modify-write: another
            # process (a fleet pre-tune, a concurrent sweep) may have
            # added OR re-tuned entries since our lazy load — the
            # fresher disk state wins for every digest except the one
            # we are writing right now (a stale in-memory snapshot
            # must neither wipe nor revert them)
            merged = self._read_disk()
            merged[digest] = entry
            self._entries = merged
            self._save()
        return entry

    def entries(self):
        with self._lock:
            return dict(self._load())

    def __len__(self):
        with self._lock:
            return len(self._load())

    # -- fleet bank ----------------------------------------------------------

    def export_bank(self, path):
        """Write the whole table as one portable bank file (atomic
        write): entries verbatim plus per-entry ``host`` provenance so
        a merged fleet bank can still say which host tuned what.
        Returns the entry count."""
        import socket
        host = socket.gethostname()
        with self._lock:
            entries = self._read_disk()
            self._entries = entries
        exported = {}
        for digest, entry in sorted(entries.items()):
            entry = dict(entry)
            entry.setdefault("host", host)
            exported[digest] = entry
        bank = {"schema": SCHEDULE_CACHE_SCHEMA,
                "kind": "schedule_bank", "host": host,
                "jax": _jax_version(), "entries": exported}
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fout:
            json.dump(bank, fout, indent=1, sort_keys=True)
            fout.flush()
        os.replace(tmp, path)
        return len(exported)

    def merge_bank(self, bank):
        """Merge a fleet bank (dict or path) into the table under the
        same re-read-before-write discipline as :meth:`put`.

        Per-digest policy is **disk wins except newer fitness**: a bank
        entry is adopted only when the digest is absent locally or the
        bank's measured fitness is strictly better (fitness = negative
        seconds, so higher wins).  Entries whose digest does not match
        a recompute over their own key coordinates are STALE (tampered,
        or written by a different schedule_key discipline) and
        rejected; structurally invalid schedules are rejected the same
        way the kernels' consult would reject them.  Returns the count
        dict ``{"adopted", "kept", "stale", "invalid", "total"}``."""
        from veles_tpu.tune.spec import valid_schedule
        if not isinstance(bank, dict):
            bank = load_bank(bank)
        entries = bank.get("entries") or {}
        counts = {"adopted": 0, "kept": 0, "stale": 0, "invalid": 0,
                  "total": len(entries)}
        adoptable = {}
        for digest, entry in entries.items():
            if not isinstance(entry, dict):
                counts["invalid"] += 1
                continue
            payload = {k: v for k, v in entry.items()
                       if k not in _NON_KEY_FIELDS}
            if _digest(json.dumps(payload, sort_keys=True)) != digest:
                counts["stale"] += 1
                continue
            if valid_schedule(entry.get("op"),
                              entry.get("schedule")) is None:
                counts["invalid"] += 1
                continue
            adoptable[digest] = entry
        with self._lock:
            merged = self._read_disk()
            for digest, entry in adoptable.items():
                local = merged.get(digest)
                if local is not None and not _fitter(entry, local):
                    counts["kept"] += 1
                    continue
                merged[digest] = dict(entry)
                counts["adopted"] += 1
            self._entries = merged
            if counts["adopted"]:
                self._save()
        reg = _counters()
        reg.counter("tune.bank_merged").inc()
        if counts["adopted"]:
            reg.counter("tune.bank_entries").inc(counts["adopted"])
        return counts


#: entry fields that ride ALONGSIDE the key payload (everything else
#: in an entry is a schedule_key coordinate, so a digest recompute over
#: the remainder must reproduce the entry's own digest)
_NON_KEY_FIELDS = frozenset(
    ("schedule", "source", "fitness", "evals", "host"))


def _fitter(challenger, incumbent):
    """True when the challenger's measured fitness strictly beats the
    incumbent's (an unmeasured challenger never displaces anything; an
    unmeasured incumbent yields to any measured challenger)."""
    cf = challenger.get("fitness")
    if cf is None:
        return False
    inf = incumbent.get("fitness")
    return inf is None or float(cf) > float(inf)


def load_bank(path):
    """Read + structurally verify one bank file; raises ValueError on
    anything that is not a schedule bank of the current schema."""
    with open(path) as fin:
        bank = json.load(fin)
    if (not isinstance(bank, dict)
            or bank.get("kind") != "schedule_bank"
            or bank.get("schema") != SCHEDULE_CACHE_SCHEMA
            or not isinstance(bank.get("entries"), dict)):
        raise ValueError("%s is not a schedule bank (schema %s)"
                         % (path, SCHEDULE_CACHE_SCHEMA))
    return bank


class MeasurementLog(object):
    """The ``measurements.jsonl`` sidecar: every measured
    (spec, schedule, slope) triple the tuner ever ranks, one JSON row
    per line — the cost model's training set.

    Append-only in the common case; an append that finds the file past
    ``_MEASUREMENTS_MAX_BYTES`` compacts it to the newest
    ``_MEASUREMENTS_KEEP`` rows (atomic replace).  Rows carry the full
    digest payload, so loads can filter to the CURRENT jax version /
    device kind / kernel version — a version bump strands old rows
    exactly like it strands old cache entries."""

    def __init__(self, path=None):
        self.path = path or os.path.join(default_cache_dir(),
                                         _MEASUREMENTS_NAME)
        self._lock = threading.Lock()
        self._warned = False

    def append(self, digest, payload, schedule, slope, mode="measure"):
        row = {"digest": str(digest), "payload": dict(payload),
               "schedule": dict(schedule), "slope": float(slope),
               "mode": str(mode)}
        line = json.dumps(row, sort_keys=True) + "\n"
        with self._lock:
            os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                        exist_ok=True)
            with open(self.path, "a") as fout:
                fout.write(line)
            try:
                oversized = (os.path.getsize(self.path)
                             > _MEASUREMENTS_MAX_BYTES)
            except OSError:
                oversized = False
            if oversized:
                self._compact()

    def _compact(self):
        with open(self.path) as fin:
            lines = fin.readlines()
        tmp = self.path + ".tmp"
        with open(tmp, "w") as fout:
            fout.writelines(lines[-_MEASUREMENTS_KEEP:])
            fout.flush()
        os.replace(tmp, self.path)

    def rows(self, op=None, mode=None, current_only=True):
        """The parsed rows, newest last.  ``current_only`` keeps only
        rows whose payload matches the CURRENT jax version and device
        kind AND whose digest recompute matches (a jax/kernel-version
        bump invalidates training data like it invalidates cache
        entries).  Unparseable lines are skipped (one warning)."""
        try:
            with open(self.path) as fin:
                lines = fin.readlines()
        except FileNotFoundError:
            return []
        except OSError as exc:
            self._warn("measurement log %s unreadable (%s)"
                       % (self.path, exc))
            return []
        jax_now = _jax_version() if current_only else None
        kind_now = device_kind() if current_only else None
        kernel_now = {}
        if current_only:
            from veles_tpu.tune.spec import current_kernel_version
        out = []
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
                payload = row["payload"]
                digest = row["digest"]
                float(row["slope"])
                row["schedule"], row["mode"]
            except (ValueError, KeyError, TypeError):
                self._warn("measurement log %s has unparseable rows; "
                           "skipping them" % self.path)
                continue
            if op is not None and payload.get("op") != op:
                continue
            if mode is not None and row.get("mode") != mode:
                continue
            if current_only:
                if (payload.get("jax") != jax_now
                        or payload.get("device_kind") != kind_now):
                    continue
                row_op = payload.get("op")
                if row_op not in kernel_now:
                    kernel_now[row_op] = current_kernel_version(row_op)
                if (kernel_now[row_op] is not None
                        and payload.get("kernel_version")
                        != kernel_now[row_op]):
                    continue
                recomputed = _digest(json.dumps(payload,
                                                sort_keys=True))
                if recomputed != digest:
                    continue
            out.append(row)
        return out

    def count_by_family(self, mode=None, current_only=True):
        counts = {}
        for row in self.rows(mode=mode, current_only=current_only):
            op = row["payload"].get("op", "?")
            counts[op] = counts.get(op, 0) + 1
        return counts

    def _warn(self, message):
        if not self._warned:
            self._warned = True
            logger.warning(message)


# -- process-wide consult hook ----------------------------------------------

_instances_lock = threading.Lock()
_instances = {}


def cache_for(path=None):
    """The ScheduleCache singleton for ``path`` (default: the resolved
    cache dir).  Keyed by resolved path so tests that redirect
    ``VELES_SCHEDULE_CACHE`` get a fresh table, not a stale singleton."""
    resolved = path or os.path.join(default_cache_dir(), _FILE_NAME)
    with _instances_lock:
        inst = _instances.get(resolved)
        if inst is None:
            inst = _instances[resolved] = ScheduleCache(resolved)
        return inst


_log_instances = {}


def measurement_log(path=None):
    """The MeasurementLog singleton for ``path`` — same resolved-path
    keying as :func:`cache_for`, so the conftest tmp-redirect that
    isolates ``schedules.json`` isolates the sidecar too."""
    resolved = path or os.path.join(default_cache_dir(),
                                    _MEASUREMENTS_NAME)
    with _instances_lock:
        inst = _log_instances.get(resolved)
        if inst is None:
            inst = _log_instances[resolved] = MeasurementLog(resolved)
        return inst


def record_measurement(digest, payload, schedule, slope,
                       mode="measure"):
    """Append one measured triple to the sidecar; never raises (a
    read-only cache dir must not break a tune run)."""
    try:
        measurement_log().append(digest, payload, schedule, slope,
                                 mode=mode)
    except Exception as exc:
        logger.warning("measurement log append failed (%s); triple "
                       "dropped", exc)


#: active recording sink (tune/walk.py) — a plain list; consults append
#: their spec dicts.  Guarded by the GIL like every other module flag.
_recording = None


class record_specs(object):
    """Context manager: while active, every ``schedule_for`` consult
    appends ``{"op", "shape", "dtype", "precision_level", "extra",
    "raw", "digest"}`` to the returned list (dedup by digest) — the
    walk's harvest of what a lowering actually consulted."""

    def __enter__(self):
        global _recording
        self._saved = _recording
        self._sink = []
        self._seen = set()
        _recording = self
        return self._sink

    def __exit__(self, *exc):
        global _recording
        _recording = self._saved
        return False

    def add(self, spec):
        if spec["digest"] not in self._seen:
            self._seen.add(spec["digest"])
            self._sink.append(spec)


def _counters():
    from veles_tpu.observe.metrics import registry
    return registry


def schedule_for(op, shape, dtype, precision_level, extra=None,
                 raw=None):
    """The kernels' consult: the cached ``schedule`` dict for this
    (op, padded shape, dtype, precision level, device kind) or None.

    Counts ``tune.cache_hits`` / ``tune.cache_misses``; under an
    active :class:`record_specs` context also records the spec.  Never
    raises — a broken cache is a warning plus the static fallback."""
    try:
        kind = device_kind()
        digest, payload = schedule_key(op, shape, dtype,
                                       precision_level, kind, extra)
        if _recording is not None:
            _recording.add({
                "op": str(op), "shape": [int(s) for s in shape],
                "dtype": str(dtype),
                "precision_level": int(precision_level),
                "device_kind": kind, "extra": dict(extra or {}),
                "raw": dict(raw or {}), "digest": digest})
        entry = cache_for().get(digest)
        reg = _counters()
        if entry is None:
            reg.counter("tune.cache_misses").inc()
            return None
        reg.counter("tune.cache_hits").inc()
        return entry["schedule"]
    except Exception as exc:  # never let the cache break a kernel call
        logger.warning("schedule cache consult failed (%s); using "
                       "static tables", exc)
        return None


def provenance(op, shape, dtype, precision_level, extra=None):
    """"tuned" when a cache entry would ACTUALLY serve this spec —
    same structural validation as the kernels' consult, so an entry
    the consult rejects (and serves statically) is never attributed as
    tuned — else "static".  No counters, no recording."""
    try:
        digest, _ = schedule_key(op, shape, dtype, precision_level,
                                 device_kind(), extra)
        entry = cache_for().get(digest)
        if entry is None:
            return "static"
        from veles_tpu.tune.spec import valid_schedule
        return ("tuned" if valid_schedule(op, entry["schedule"])
                else "static")
    except Exception:
        return "static"


def tune_counters():
    """Snapshot of the tune metric set + cache population for receipts
    (the serve engine's compile receipt, the CLI's TUNE.json)."""
    reg = _counters()
    out = {}
    for name in ("tune.cache_hits", "tune.cache_misses", "tune.evals",
                 "tune.bank_published", "tune.bank_merged",
                 "tune.bank_entries"):
        metric = reg.peek(name)
        if metric is not None:
            out[name.split(".", 1)[1]] = metric.value
    try:
        out["entries"] = len(cache_for())
    except Exception:
        pass
    return out
