"""Device-mesh construction.

Replaces the reference Launcher's socket handshake + SSH node discovery
(launcher.py:808-906) with JAX topology discovery: ``jax.devices()``
enumerates the slice; multi-host processes call
``jax.distributed.initialize`` (veles_tpu.launcher does this when
VELES_COORDINATOR is set) and get the same global view.
"""

import numpy

import jax
from jax import shard_map
from jax.sharding import Mesh

__all__ = ["make_mesh", "auto_mesh", "restore_mesh", "shard_map",
           "zero_slot_table",
           "zero_state", "unzero_state", "MeshManager", "mesh_snapshot"]


def make_mesh(axes, devices=None):
    """axes: dict name -> size, e.g. {"data": 4, "model": 2}.

    Sizes must multiply to the device count; -1 once means "the rest".
    """
    devices = list(devices if devices is not None else jax.devices())
    names = list(axes)
    sizes = [axes[n] for n in names]
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis size may be -1")
    known = int(numpy.prod([s for s in sizes if s != -1]))
    if -1 in sizes:
        if len(devices) % known:
            raise ValueError(
                "cannot infer -1 axis: %d devices over %d" %
                (len(devices), known))
        sizes[sizes.index(-1)] = len(devices) // known
    total = int(numpy.prod(sizes))
    if total != len(devices):
        raise ValueError("mesh %s needs %d devices, have %d" %
                         (axes, total, len(devices)))
    grid = numpy.array(devices, dtype=object).reshape(sizes)
    return Mesh(grid, names)


def auto_mesh(data_axis="data", devices=None):
    """All devices on one data-parallel axis — the reference's only
    tensor-level strategy (parameter-server DP, SURVEY.md section 2.6)."""
    devices = list(devices if devices is not None else jax.devices())
    return make_mesh({data_axis: len(devices)}, devices)


def restore_mesh(axes, warn):
    """The mesh a pickle's ``axes`` (``dict(mesh.shape)``: a Mesh holds
    live device handles, so snapshots carry its axes instead) stand
    for, on the devices this host has now: the same shape where it
    fits; a single-axis (pure-DP) mesh re-spans whatever devices exist,
    with a word to ``warn``; a multi-axis shape that no longer fits
    fails LOUDLY rather than degrade to a single-device run."""
    try:
        return make_mesh(dict(axes))
    except ValueError as exc:
        if len(axes) != 1:
            raise ValueError(
                "cannot rebuild the resumed SPMD mesh %s on this "
                "host: %s — re-fuse with an explicit mesh"
                % (dict(axes), exc))
        axis = next(iter(axes))
        mesh = auto_mesh(axis)
        warn("resumed SPMD mesh %s does not fit this host (%s); "
             "re-spanning the data axis over %d devices",
             dict(axes), exc, mesh.shape[axis])
        return mesh


# -- ZeRO-1 state layout (docs/distributed.md, "Elastic mesh contract") ---
#
# Optimizer state (the accum leaves) is split into ``n_shards``
# LOGICAL shards per tensor; each device hosts ``ceil(n_shards/N)``
# slots, and an int32 ``zero_slots`` table maps device slots to
# logical shard ids (the id ``n_shards`` marks a padding slot backed
# by an all-zero row).  The table is a runtime input of the compiled
# step, so shard OWNERSHIP (elastic.shard_owners) can change without
# recompiling — the elastic-mesh property the MeshManager builds on.

#: accum leaves — the state entries that live sharded in ZeRO form
ZERO_SHARDED_KEYS = ("accum_weights", "accum_bias", "accum2_weights",
                     "accum2_bias")


def _zero_ref_key(key):
    """The param tensor an accum leaf shadows (its shape source)."""
    return "bias" if key.endswith("bias") else "weights"


def zero_slot_table(n_shards, n_devices, owners=None):
    """Build the int32 ``(n_devices * ceil(n_shards/n_devices),)``
    slot table for an ownership map ``{shard: device_index}`` (default
    round-robin).  Device d's slots are ``[d*k, (d+1)*k)``, filled with
    its owned shard ids ascending and padded with the id ``n_shards``
    (the all-zero row the slot helpers append)."""
    m, n = int(n_shards), int(n_devices)
    k = -(-m // n)
    table = numpy.full((n * k,), m, numpy.int32)
    owned = {d: [] for d in range(n)}
    if owners is None:
        for shard in range(m):
            owned[shard % n].append(shard)
    else:
        for shard, d in owners.items():
            owned[int(d)].append(int(shard))
    for d in range(n):
        shards = sorted(owned[d])
        if len(shards) > k:
            raise ValueError(
                "device %d owns %d shards, capacity %d (n_shards=%d "
                "over %d devices)" % (d, len(shards), k, m, n))
        table[d * k:d * k + len(shards)] = shards
    return table


def zero_state(state, n_devices, n_shards=None, slots=None):
    """Pack a canonical state (full accum arrays) into ZeRO-1 form for
    ``compiler.build_train_step(zero=1)``: accum leaves become
    ``(n_slots, shard_elems)`` slot matrices (host numpy — the step's
    in_specs place them sharded on first dispatch) and every layer
    entry gains the replicated ``zero_slots`` table.  Params stay
    full/replicated.  ``n_shards`` defaults to one shard per device."""
    from veles_tpu.parallel.bucketed import shard_elems

    m = int(n_shards or n_devices)
    if slots is None:
        slots = zero_slot_table(m, n_devices)
    slots = numpy.asarray(slots, numpy.int32)
    out = []
    for entry in state:
        packed = {key: value for key, value in entry.items()}
        packed["zero_slots"] = slots
        for key in ZERO_SHARDED_KEYS:
            arr = entry.get(key)
            if arr is None:
                continue
            arr = numpy.asarray(arr)
            e = shard_elems(arr.size, m)
            flat = numpy.zeros(((m + 1) * e,), arr.dtype)
            flat[:arr.size] = arr.reshape((-1,))
            packed[key] = numpy.ascontiguousarray(
                flat.reshape((m + 1, e))[slots])
        out.append(packed)
    return out


def unzero_state(state, n_shards):
    """Invert :func:`zero_state`: reassemble full canonical accum
    arrays (host numpy) from the slot matrices by each entry's
    ``zero_slots`` table.  The round-trip is exact — rows move, bits
    never change — which is what makes reshard state movement safe."""
    m = int(n_shards)
    out = []
    for entry in state:
        slots = numpy.asarray(entry["zero_slots"])
        # every leaf comes back as HOST numpy — canonical state must
        # not stay committed to the old mesh's devices, or the next
        # mesh's step would refuse the placement
        plain = {key: None if value is None else numpy.asarray(value)
                 for key, value in entry.items()
                 if key != "zero_slots"}
        for key in ZERO_SHARDED_KEYS:
            rows = plain.get(key)
            if rows is None:
                continue
            rows = numpy.asarray(rows)
            ref = numpy.asarray(plain[_zero_ref_key(key)])
            e = rows.shape[-1]
            full = numpy.zeros((m + 1, e), rows.dtype)
            full[slots] = rows
            plain[key] = full[:m].reshape((-1,))[:ref.size].reshape(
                ref.shape)
        out.append(plain)
    return out


#: Mesh keys surfaced to dashboards/heartbeats: registry name -> short
#: name (the elastic-mesh mirror of observe.metrics._HEALTH_KEYS).
_MESH_KEYS = (
    ("mesh.size", "size"),
    ("mesh.epoch", "epoch"),
    ("mesh.reshards", "reshards"),
    ("mesh.bytes_moved", "bytes_moved"),
    ("mesh.coalesced_events", "coalesced_events"),
    ("mesh.compile_hits", "compile_hits"),
    ("mesh.compile_misses", "compile_misses"),
)


def mesh_snapshot(reg=None):
    """The elastic-mesh counters as a flat dict for the web-status
    mesh column and post-mortems: mesh size/epoch, reshard and
    bytes-moved accounting, compile-cache traffic, plus the
    ``mesh.reshard_s`` time-to-recover histogram.  {} on processes
    that never built a MeshManager."""
    from veles_tpu.observe.metrics import registry as _registry
    from veles_tpu.observe.metrics import snapshot_keys
    reg = reg if reg is not None else _registry
    out = snapshot_keys(_MESH_KEYS, reg)
    hist = reg.peek("mesh.reshard_s")
    if hist is not None and getattr(hist, "count", 0):
        out["reshard_s"] = hist.snapshot()
    return out


def _device_key(device):
    """Stable consistent-hash key for a jax device — id-based, so the
    same physical device hashes identically across reshards and
    process restarts (the property HRW ownership stability needs)."""
    return "d%d" % device.id


class MeshManager(object):
    """Elastic ZeRO-1 training mesh (docs/distributed.md, "Elastic
    mesh contract").

    Owns the live train state in ZeRO-1 form over a data-parallel mesh
    and survives membership churn: on a join/leave (``submit_membership``
    — fed by ``elastic.FleetView`` epochs via :meth:`sync_fleet`) the
    manager *quiesces at the step boundary* (events only mark a pending
    membership; :meth:`step` applies the newest one before touching the
    data plane, so back-to-back events coalesce into ONE reshard),
    takes a manifest-verified safety snapshot, recomputes consistent-
    hash shard ownership (:func:`veles_tpu.elastic.shard_owners`),
    moves ONLY the shards whose owner changed (on a single-host mesh
    the movement is a host-side row reassembly; ``bytes_moved``
    accounts the changed-owner rows that would cross the interconnect
    on a pod — the full-gather reference is ``n_shards`` rows), and
    resumes with a step from the digest-keyed compile cache (rejoining
    a previously-seen device set recompiles nothing).

    A crash mid-reshard (chaos point ``mesh.reshard=crash``, fired
    after the safety snapshot, before destructive movement) recovers
    via :meth:`resume` — the ``--resume auto`` semantics over
    ``snapshotter.latest_state_snapshot``.
    """

    def __init__(self, plans, state, loss="softmax", devices=None,
                 n_shards=None, data_axis="data", snapshot_dir=None,
                 donate=True, compiler_options=None, bwd_schedule=None,
                 bwd_remat=False):
        from veles_tpu.observe.metrics import registry as _registry
        self.plans = plans
        self.loss = loss
        self.data_axis = data_axis
        self.snapshot_dir = snapshot_dir
        self.donate = donate
        self.compiler_options = compiler_options
        self.bwd_schedule = bwd_schedule
        self.bwd_remat = bwd_remat
        self._devices = self._order(
            devices if devices is not None else jax.devices())
        if not self._devices:
            raise ValueError("MeshManager needs at least one device")
        #: logical shard count — the movement granularity.  Defaults to
        #: 4x the initial mesh so a single leave moves ~1/N of the
        #: optimizer state in ~4 row-sized pieces, and shrinking below
        #: the initial size never runs out of shards to spread.
        self.n_shards = int(n_shards or 4 * len(self._devices))
        if self.n_shards < len(self._devices):
            raise ValueError(
                "n_shards=%d < %d devices: every device needs at least "
                "one logical shard" % (self.n_shards,
                                       len(self._devices)))
        self.mesh_epoch = 0
        self.applied_steps = 0
        self._pending = None          # (devices, source_epoch) | None
        self._fleet_epoch_seen = None
        self._steps = {}              # digest -> compiled step fn
        self._owners = None
        #: per-reshard receipt rows (movement plan, bytes, timings)
        self.reshard_log = []
        self._reg = _registry
        self._adopt(state)
        self._publish_gauges()

    # -- membership ----------------------------------------------------

    @staticmethod
    def _order(devices):
        return tuple(sorted(devices, key=lambda d: d.id))

    @property
    def devices(self):
        return self._devices

    @property
    def size(self):
        return len(self._devices)

    def submit_membership(self, devices, epoch=None):
        """Queue a membership change (join/leave/swap).  Applied at
        the NEXT step boundary; a newer event before that boundary
        replaces the pending one — back-to-back churn coalesces into a
        single reshard (the counter ``mesh.coalesced_events`` audits
        it)."""
        devices = self._order(devices)
        if not devices:
            raise ValueError("membership event with zero devices")
        if self._pending is not None:
            self._reg.counter("mesh.coalesced_events").inc()
        self._pending = (devices, epoch)

    def sync_fleet(self, fleet, devices_for):
        """Feed membership from an :class:`veles_tpu.elastic.FleetView`:
        when its ``membership_epoch`` moved since the last sync, the
        union of ``devices_for(sid)`` over live members becomes the
        pending device set.  Returns True when an event was queued."""
        epoch = fleet.membership_epoch
        if epoch == self._fleet_epoch_seen:
            return False
        self._fleet_epoch_seen = epoch
        devices = []
        seen = set()
        for sid in fleet.members:
            for dev in devices_for(sid):
                if dev.id not in seen:
                    seen.add(dev.id)
                    devices.append(dev)
        self.submit_membership(devices, epoch=epoch)
        return True

    # -- state layout ---------------------------------------------------

    def _keys(self, devices=None):
        return [_device_key(d) for d in (devices or self._devices)]

    def _adopt(self, state, owners=None):
        """(Re)pack canonical state for the current device set."""
        from veles_tpu.elastic import shard_owners
        keys = self._keys()
        self._owners = shard_owners(self.n_shards, keys,
                                    previous=owners)
        index = {key: i for i, key in enumerate(keys)}
        slots = zero_slot_table(
            self.n_shards, len(keys),
            owners={s: index[m] for s, m in self._owners.items()})
        self._state = zero_state(state, len(keys),
                                 n_shards=self.n_shards, slots=slots)

    def canonical_state(self):
        """The full (unsharded) state as host numpy — snapshot /
        inspection form; the ZeRO round-trip is bit-exact."""
        return unzero_state(self._state, self.n_shards)

    def shard_bytes(self):
        """Bytes of optimizer state per logical shard (all layers, all
        accum leaves) — the unit ``bytes_moved`` accounts in."""
        from veles_tpu.parallel.bucketed import shard_elems
        total = 0
        for entry in self._state:
            for key in ZERO_SHARDED_KEYS:
                rows = entry.get(key)
                if rows is None:
                    continue
                rows = numpy.asarray(rows) if not hasattr(rows, "dtype") \
                    else rows
                total += int(rows.shape[-1]) * rows.dtype.itemsize
        return total

    # -- compile cache --------------------------------------------------

    def _digest(self):
        import hashlib
        meta = [(p.forward_cls.__name__, p.solver, p.include_bias,
                 tuple(sorted(p.hyper_full().items())),
                 tuple(sorted(p.static.items())))
                for p in self.plans]
        blob = repr((self._keys(), self.n_shards, self.loss,
                     self.data_axis, self.bwd_schedule, self.bwd_remat,
                     meta)).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def _get_step(self):
        from veles_tpu import compiler
        digest = self._digest()
        step = self._steps.get(digest)
        if step is not None:
            self._reg.counter("mesh.compile_hits").inc()
            return step
        self._reg.counter("mesh.compile_misses").inc()
        mesh = auto_mesh(self.data_axis, self._devices)
        step = compiler.build_train_step(
            self.plans, loss=self.loss, mesh=mesh,
            data_axis=self.data_axis, zero=1, zero_shards=self.n_shards,
            donate=self.donate, compiler_options=self.compiler_options,
            bwd_schedule=self.bwd_schedule, bwd_remat=self.bwd_remat)
        self._steps[digest] = step
        return step

    # -- reshard --------------------------------------------------------

    def maybe_reshard(self):
        """Apply the newest pending membership event (if any) at this
        step boundary; returns the reshard receipt row or None."""
        if self._pending is None:
            return None
        devices, epoch = self._pending
        self._pending = None
        if devices == self._devices:
            return None  # no-op churn (leave+rejoin of the same set)
        return self._reshard(devices, epoch)

    def _reshard(self, devices, source_epoch):
        import time as _time

        from veles_tpu import chaos
        from veles_tpu.elastic import movement_plan
        from veles_tpu.observe.trace import tracer as _tracer
        t0 = _time.perf_counter()
        canonical = self.canonical_state()
        snapshot_path = self.snapshot(reason="pre_reshard",
                                      state=canonical)
        if chaos.plan is not None:
            fault = chaos.plan.fire("mesh.reshard")
            if fault is not None and fault.action == "crash":
                # after the safety snapshot, before destructive
                # movement — the window a real crash would hit
                raise chaos.ChaosCrash("simulated crash mid-reshard")
        old_owners = self._owners
        old_size = len(self._devices)
        self._devices = devices
        self._adopt(canonical, owners=old_owners)
        plan = movement_plan(old_owners, self._owners)
        per_shard = self.shard_bytes()
        bytes_moved = plan["n_moved"] * per_shard
        self.mesh_epoch += 1
        cached = self._digest() in self._steps
        self._get_step()  # time-to-recover includes the (re)compile
        elapsed = _time.perf_counter() - t0
        event = {
            "mesh_epoch": self.mesh_epoch,
            "source_epoch": source_epoch,
            "step": self.applied_steps,
            "from_size": old_size,
            "to_size": len(self._devices),
            "n_shards": self.n_shards,
            "moved_shards": plan["n_moved"],
            "changed_fraction": plan["changed_fraction"],
            "bytes_moved": bytes_moved,
            "full_gather_bytes": self.n_shards * per_shard,
            "reshard_s": elapsed,
            "compile_cached": cached,
            "snapshot": snapshot_path,
        }
        self.reshard_log.append(event)
        self._reg.counter("mesh.reshards").inc()
        self._reg.counter("mesh.bytes_moved").inc(bytes_moved)
        self._reg.histogram("mesh.reshard_s").observe(elapsed)
        self._publish_gauges()
        if _tracer.active:
            _tracer.instant("mesh.resharded", cat="mesh", **{
                k: event[k] for k in ("mesh_epoch", "from_size",
                                      "to_size", "moved_shards",
                                      "bytes_moved", "reshard_s")})
        return event

    def _publish_gauges(self):
        self._reg.gauge("mesh.size").set(len(self._devices))
        self._reg.gauge("mesh.epoch").set(self.mesh_epoch)

    # -- snapshots ------------------------------------------------------

    def snapshot(self, reason="manual", state=None):
        """Manifest-verified safety snapshot of the canonical state
        (+ progress counters) via the snapshotter atomics; returns the
        path, or None when no ``snapshot_dir`` is configured."""
        if not self.snapshot_dir:
            return None
        import os

        from veles_tpu import snapshotter
        os.makedirs(self.snapshot_dir, exist_ok=True)
        path = os.path.join(
            self.snapshot_dir, "mesh_%s_e%d_s%d.pickle" %
            (reason, self.mesh_epoch, self.applied_steps))
        payload = {
            "state": state if state is not None
            else self.canonical_state(),
            "applied_steps": self.applied_steps,
            "mesh_epoch": self.mesh_epoch,
            "n_shards": self.n_shards,
        }
        snapshotter.write_state_snapshot(
            path, payload, workflow_name="MeshManager",
            epoch=self.mesh_epoch)
        return path

    @classmethod
    def resume(cls, snapshot_dir, plans, **kwargs):
        """Rebuild a manager from the newest verified safety snapshot
        in ``snapshot_dir`` (the ``--resume auto`` path) over whatever
        devices are live now.  State is bit-exact: the snapshot holds
        the canonical form, the repack moves rows, never values."""
        from veles_tpu import snapshotter
        snap = snapshotter.latest_state_snapshot(snapshot_dir)
        if snap is None:
            raise snapshotter.SnapshotError(
                "no verified mesh snapshot under %s" % snapshot_dir)
        payload = snapshotter.load_state_snapshot(snap)
        kwargs.setdefault("n_shards", payload.get("n_shards"))
        manager = cls(plans, payload["state"],
                      snapshot_dir=snapshot_dir, **kwargs)
        manager.applied_steps = int(payload.get("applied_steps", 0))
        manager.mesh_epoch = int(payload.get("mesh_epoch", 0))
        manager._publish_gauges()
        return manager

    # -- stepping -------------------------------------------------------

    def step(self, x, target, batch_size=None, step_key=None,
             grad_poison=None, loss_poison=None):
        """Run one train step on the current mesh, applying any
        pending membership event FIRST (the step-boundary quiesce).
        Returns the step metrics; state advances in place.  The global
        batch's leading dim must divide by the mesh size (the soak
        picks batch sizes divisible by every size in its schedule)."""
        self.maybe_reshard()
        n = len(self._devices)
        if x.shape[0] % n:
            raise ValueError(
                "global batch %d does not divide over %d devices — "
                "pick a batch size divisible by every mesh size the "
                "membership schedule can reach" % (x.shape[0], n))
        if batch_size is None:
            batch_size = numpy.float32(x.shape[0])
        step = self._get_step()
        self._state, metrics = step(self._state, x, target, batch_size,
                                    step_key, grad_poison, loss_poison)
        self.applied_steps += 1
        return metrics
