"""Bucketed gradient all-reduce overlapped with the backward pass.

SCALING.json's round-5 receipt: every distributed step all-reduced one
flat ~250 MB gradient pytree with no overlap credited — the comm sat
serially behind the whole backward.  This module is the SPMD data
plane's fix (the TensorFlow-paper split, PAPERS.md: dataflow inner
loop, control-plane outer loop):

- :func:`plan_buckets` partitions the gradient pytree into
  size-targeted buckets (default ~25 MB, ``--grad-bucket-mb``),
  walking the leaves in REVERSE layer order — the order the backward
  pass produces them — so bucket 0 is ready while most of the
  backward is still running.  Leaves larger than a bucket are split at
  exact element boundaries (a leaf may straddle a bucket edge).
- :func:`bucketed_all_reduce` issues one collective per bucket inside
  a ``shard_map``-ed step, chained through
  ``lax.optimization_barrier`` so XLA's all-reduce combiner cannot
  re-fuse them into the flat monolith and the latency-hiding scheduler
  (async ``all-reduce-start``/``-done`` on TPU) can overlap each
  bucket's wire time with the remaining backward + update compute.
  Bit-identical to the flat single-tensor all-reduce: ``psum`` is
  elementwise and the concatenate/slice round-trip is exact
  (tests/test_bucketed.py proves every boundary case).
- optional ``compress="bf16"`` halves the wire bytes; the step-level
  numerics guard (docs/health.md) covers the rounding: a compressed
  step whose grads go non-finite is SKIPPED bit-exactly and the
  trainer auto-falls back to f32 (``FusedTrainer.on_health_sync``).
- :func:`overlap_model` / :func:`comm_receipt` /
  :func:`publish_comm_receipt` are the observability half: an
  analytic overlap-credited schedule (shared with scripts/scaling.py)
  published as ``comm.*`` gauges and per-bucket spans through the
  PR 4-5 observe stack.
"""

import math
import time

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["DEFAULT_BUCKET_MB", "Bucket", "BucketPlan", "plan_buckets",
           "bucketed_all_reduce", "flat_all_reduce", "overlap_model",
           "comm_receipt", "publish_comm_receipt", "shard_elems",
           "slot_matrix", "unslot_matrix", "chained_reduce_scatter",
           "gather_slots"]

#: default bucket size target.  25 MB rides the knee of the v5e ring
#: model: big enough that per-hop launch latency stays < 3 % of a
#: bucket's wire time, small enough that a ~250 MB AlexNet gradient
#: splits into ~10 buckets and the first all-reduce issues while ~90 %
#: of the backward is still outstanding.
DEFAULT_BUCKET_MB = 25.0

class Bucket(object):
    """One all-reduce payload: contiguous element spans of flattened
    gradient leaves.  ``slices`` holds ``(leaf_index, start, stop)``
    element ranges (into the leaf's 1-D view)."""

    __slots__ = ("slices", "elems", "nbytes")

    def __init__(self):
        self.slices = []
        self.elems = 0
        self.nbytes = 0

    def __repr__(self):
        return "<Bucket %d leaves %d elems %.2f MB>" % (
            len(self.slices), self.elems, self.nbytes / 2.0 ** 20)


class BucketPlan(object):
    """Static partition of a gradient pytree's leaves into buckets,
    ordered by backward-pass production (last layer first)."""

    __slots__ = ("buckets", "n_leaves", "total_elems", "total_bytes",
                 "bucket_bytes")

    def __init__(self, buckets, n_leaves, bucket_bytes):
        self.buckets = buckets
        self.n_leaves = n_leaves
        self.total_elems = sum(b.elems for b in buckets)
        self.total_bytes = sum(b.nbytes for b in buckets)
        self.bucket_bytes = bucket_bytes

    def __repr__(self):
        return "<BucketPlan %d buckets / %d leaves / %.1f MB>" % (
            len(self.buckets), self.n_leaves,
            self.total_bytes / 2.0 ** 20)


def _leaf_meta(leaf):
    """(n_elements, itemsize) for an array / ShapeDtypeStruct leaf."""
    size = int(math.prod(leaf.shape)) if leaf.shape else 1
    return size, int(jnp.dtype(leaf.dtype).itemsize)


def plan_buckets(leaves, bucket_bytes=None):
    """Partition ``leaves`` (arrays or ShapeDtypeStructs, in pytree
    order) into size-targeted buckets.

    Leaves are walked in REVERSE order — the backward pass produces
    the LAST layer's gradients first, so bucket 0 holds the grads that
    exist earliest and its all-reduce can overlap the rest of the
    backward.  A leaf that does not fit the current bucket's remaining
    capacity is split at the exact element boundary; an oversized leaf
    therefore spans several buckets.  ``bucket_bytes=None`` means the
    :data:`DEFAULT_BUCKET_MB` target; ``inf`` (or any target >= the
    total) yields ONE bucket — the flat single-tensor all-reduce,
    which doubles as the bit-equality reference.
    """
    if bucket_bytes is None:
        bucket_bytes = DEFAULT_BUCKET_MB * 2.0 ** 20
    elif bucket_bytes <= 0:
        raise ValueError("bucket_bytes must be positive, got %r"
                         % (bucket_bytes,))
    buckets = []
    cur = Bucket()
    for i in reversed(range(len(leaves))):
        size, item = _leaf_meta(leaves[i])
        pos = 0
        while pos < size:
            room = bucket_bytes - cur.nbytes
            if room < item and cur.slices:
                buckets.append(cur)
                cur = Bucket()
                room = bucket_bytes
            take = size - pos
            if room < take * item:
                # at least one element per span, so a bucket target
                # smaller than one element still makes progress
                take = max(int(room // item), 1)
            cur.slices.append((i, pos, pos + take))
            cur.elems += take
            cur.nbytes += take * item
            pos += take
            if cur.nbytes >= bucket_bytes:
                buckets.append(cur)
                cur = Bucket()
    if cur.slices:
        buckets.append(cur)
    return BucketPlan(buckets, len(leaves), bucket_bytes)


def _reduce_one(vec, axis_name, impl, compress, axis_size):
    """All-reduce ONE bucket vector over ``axis_name``."""
    wire = vec
    if compress == "bf16" and vec.dtype == jnp.float32:
        # lossy wire format; the step-level finiteness guard plus the
        # trainer's f32 fallback (docs/health.md) own the failure mode
        wire = vec.astype(jnp.bfloat16)
    elif compress not in (None, "bf16"):
        raise ValueError("unknown gradient compression %r" % (compress,))
    if impl == "ring":
        from veles_tpu.parallel.ring import ring_all_reduce
        if axis_size is None:
            raise ValueError("impl='ring' needs axis_size")
        out = ring_all_reduce(wire, axis_name, axis_size)
    elif impl == "psum":
        out = lax.psum(wire, axis_name)
    else:
        raise ValueError("unknown all-reduce impl %r" % (impl,))
    return out.astype(vec.dtype)


def bucketed_all_reduce(grads, axis_name, bucket_bytes=None, plan=None,
                        impl="psum", compress=None, axis_size=None,
                        chain=True):
    """Sum a gradient pytree over a mesh axis, one collective per
    bucket, inside a ``shard_map``-ed computation.

    ``chain=True`` threads each bucket's input through an
    ``optimization_barrier`` on the previous bucket's RESULT: the
    collectives stay distinct ops in the optimized HLO (XLA's
    all-reduce combiner would otherwise glue them back into the flat
    monolith) and issue in production order, which is what lets the
    latency-hiding scheduler overlap bucket k's wire time with the
    compute that produces buckets k+1.. .

    Bit-identity: ``psum`` is elementwise, the bucket concatenate /
    slice round-trip is exact, and dtypes never change (without
    ``compress``), so ANY bucketing — including pathological splits —
    produces results bit-identical to the flat single-tensor
    all-reduce.  ``impl="ring"`` (ppermute reduce-scatter +
    all-gather, parallel/ring.py) changes the summation ORDER and is
    therefore only ULP-close, not bit-equal, to psum.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if plan is None:
        plan = plan_buckets(leaves, bucket_bytes)
    flats = [leaf.reshape((-1,)) for leaf in leaves]
    pieces = [[] for _ in leaves]
    token = None
    for bucket in plan.buckets:
        parts = [flats[i][start:stop]
                 for (i, start, stop) in bucket.slices]
        vec = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if chain and token is not None:
            vec, _ = lax.optimization_barrier((vec, token))
        vec = _reduce_one(vec, axis_name, impl, compress, axis_size)
        token = vec
        offset = 0
        for (i, start, stop) in bucket.slices:
            n = stop - start
            pieces[i].append((start, vec[offset:offset + n]))
            offset += n
    out = []
    for i, leaf in enumerate(leaves):
        spans = sorted(pieces[i], key=lambda item: item[0])
        flat = (spans[0][1] if len(spans) == 1 else
                jnp.concatenate([piece for _, piece in spans]))
        out.append(flat.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def flat_all_reduce(grads, axis_name, impl="psum", compress=None,
                    axis_size=None):
    """The flat single-tensor reference: ONE bucket spanning the whole
    pytree (what every distributed step did before bucketing)."""
    return bucketed_all_reduce(
        grads, axis_name, bucket_bytes=float("inf"), impl=impl,
        compress=compress, axis_size=axis_size, chain=False)


# -- ZeRO-1 reduce-scatter + all-gather (docs/distributed.md, "Elastic
#    mesh contract") ------------------------------------------------------
#
# The sharded-optimizer data plane replaces the flat all-reduce with
# the two halves it is made of: a reduce-scatter hands each device the
# SUMMED gradient rows of the shards it owns (where the solver update
# runs on 1/N of the state), and an all-gather re-replicates the
# updated params.  ``lax.psum_scatter(tiled=True)`` is bit-identical to
# ``psum`` + slice on every row (tests/test_mesh.py proves it), so the
# split costs no numerics.  Shard-to-device placement is a runtime
# *slot table* (int32, one logical-shard id per device slot, the pad
# id pointing at an all-zero row), so the compiled step is independent
# of WHICH device owns which shard — a reshard changes only the table,
# and the digest-keyed compile cache stays warm.

def shard_elems(size, n_shards):
    """Per-shard element count for a tensor of ``size`` elements split
    into ``n_shards`` logical shards (ceil-div; the last shard pads)."""
    return -(-int(size) // max(int(n_shards), 1))


def slot_matrix(flat, slots, n_shards, elems):
    """Arrange a flattened tensor into per-slot rows: pad ``flat`` to
    ``n_shards * elems``, reshape to (n_shards, elems), append one
    all-zero pad row (logical id ``n_shards``), and gather rows by the
    ``slots`` table — the (n_slots, elems) matrix whose row i is the
    shard device ``i // slots_per_device`` hosts in slot ``i``."""
    flat = flat.reshape((-1,))
    pad = n_shards * elems - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    mat = flat.reshape((n_shards, elems))
    mat = jnp.concatenate([mat, jnp.zeros((1, elems), mat.dtype)])
    return jnp.take(mat, slots, axis=0)


def unslot_matrix(rows, slots, n_shards, size, shape, dtype):
    """Invert :func:`slot_matrix`: scatter slot rows back to canonical
    shard order (pad slots all target the dropped row ``n_shards``),
    strip the padding, and reshape to the tensor's ``shape``."""
    elems = rows.shape[-1]
    full = jnp.zeros((n_shards + 1, elems), dtype).at[slots].set(
        rows.astype(dtype))
    return full[:n_shards].reshape((-1,))[:size].reshape(shape)


def chained_reduce_scatter(mats, axis_name, chain=True):
    """Reduce-scatter each (n_slots, elems) slot matrix over
    ``axis_name``; device r receives the summed rows
    ``[r*k, (r+1)*k)`` (k = n_slots / axis size) — its owned shards.

    ``mats`` arrive in backward PRODUCTION order (last layer first) and
    ``chain=True`` threads each input through an
    ``optimization_barrier`` on the previous result, the same
    scheduling contract as :func:`bucketed_all_reduce`: collectives
    stay distinct and issue while the backward still runs.  Returns
    the per-device (k, elems) shard matrices, same order.
    ``psum_scatter`` sums in ``psum``'s order, so every returned row is
    bit-identical to the matching rows of a flat all-reduce."""
    out = []
    token = None
    for mat in mats:
        if chain and token is not None:
            mat, _ = lax.optimization_barrier((mat, token))
        part = lax.psum_scatter(mat, axis_name, scatter_dimension=0,
                                tiled=True)
        token = part
        out.append(part)
    return out


def gather_slots(part, axis_name):
    """All-gather the per-device (k, elems) shard rows back to the full
    (n_slots, elems) slot matrix — the replication half of the ZeRO-1
    update (params come back identical on every device)."""
    return lax.all_gather(part, axis_name, axis=0, tiled=True)


# -- analytic overlap model (shared with scripts/scaling.py) --------------

def overlap_model(grad_bytes, n_buckets, n_devices, step_seconds=None,
                  ici_gbps=100.0, hop_latency_s=1e-6, bwd_fraction=0.6):
    """Overlap-credited ring all-reduce schedule for one train step.

    Wire time is the standard ring bound 2(n-1)/n * bytes / bw; launch
    latency is paid PER BUCKET (2(n-1) hops each — reduce-scatter +
    all-gather), so more buckets buy overlap at a latency premium.
    Bucket k's all-reduce can hide behind the backward compute that
    produces buckets k+1.., i.e. behind ``bwd_fraction`` of the
    single-chip step scaled by (B-1)/B; the LAST bucket is never
    hidable (nothing runs behind it), so exposed comm is at least one
    bucket's share.  ``bwd_fraction`` defaults to 0.6 from MFU.json's
    round-5 attribution (backward+update dominates the step at 42 %
    MFU vs the forward's 71 %).  ``step_seconds=None`` (no measured
    step time yet) credits NO overlap — the model never invents a
    window it cannot size.
    """
    n = max(int(n_devices), 1)
    n_buckets = max(int(n_buckets), 1)
    bw = ici_gbps * 1e9
    t_wire = (2.0 * (n - 1) / n) * grad_bytes / bw if n > 1 else 0.0
    t_lat = n_buckets * 2 * (n - 1) * hop_latency_s
    t_comm = t_wire + t_lat
    if step_seconds and n_buckets > 1:
        window = (bwd_fraction * step_seconds *
                  (n_buckets - 1.0) / n_buckets)
    else:
        window = 0.0
    tail = t_comm / n_buckets
    hidden = min(max(t_comm - tail, 0.0), window)
    exposed = t_comm - hidden
    return {
        "n_devices": n,
        "n_buckets": n_buckets,
        "t_comm_s": t_comm,
        "t_comm_hidden_s": hidden,
        "t_comm_exposed_s": exposed,
        "overlap_pct": round(100.0 * hidden / t_comm, 2) if t_comm
        else 0.0,
        "bwd_fraction": bwd_fraction,
        "ici_usable_gbps": ici_gbps,
        "hop_latency_s": hop_latency_s,
    }


def comm_receipt(grad_leaves, n_devices, bucket_bytes=None,
                 step_seconds=None, compress=None, ici_gbps=100.0,
                 hop_latency_s=1e-6, bwd_fraction=0.6):
    """Build the per-step communication receipt for a gradient pytree:
    the exact bucket partition (``plan_buckets`` is deterministic, so
    this is the same plan the compiled step runs) plus the modeled
    overlap schedule.  ``compress="bf16"`` halves the wire bytes."""
    plan = plan_buckets(grad_leaves, bucket_bytes)
    bucket_sizes = [b.nbytes for b in plan.buckets]
    wire_bytes = plan.total_bytes
    if compress == "bf16":
        wire_bytes //= 2
    model = overlap_model(
        wire_bytes, len(bucket_sizes), n_devices,
        step_seconds=step_seconds, ici_gbps=ici_gbps,
        hop_latency_s=hop_latency_s, bwd_fraction=bwd_fraction)
    return {
        "allreduce_bytes": plan.total_bytes,
        "wire_bytes": wire_bytes,
        "compress": compress,
        "bucket_bytes": bucket_sizes,
        "bucket_target_bytes": (None if math.isinf(plan.bucket_bytes)
                                else int(plan.bucket_bytes)),
        "model": model,
    }


def publish_comm_receipt(receipt, tracer=None, registry=None):
    """Flow a :func:`comm_receipt` through the observe stack:
    ``comm.allreduce_bytes`` / ``comm.overlap_pct`` / ``comm.buckets``
    gauges, plus one ``comm.bucket`` span per bucket on the caller's
    trace track (the MODELED schedule, stamped as such in the span
    args — per-bucket device timing is not host-visible from inside
    one XLA dispatch; the compile-only collective-bytes receipts in
    SCALING.json are the measured half)."""
    from veles_tpu.observe.metrics import registry as _registry
    from veles_tpu.observe.trace import tracer as _tracer
    reg = registry if registry is not None else _registry
    model = receipt["model"]
    reg.gauge("comm.allreduce_bytes").set(receipt["allreduce_bytes"])
    reg.gauge("comm.buckets").set(len(receipt["bucket_bytes"]))
    reg.gauge("comm.overlap_pct").set(model["overlap_pct"])
    tr = tracer if tracer is not None else _tracer
    if not tr.active:
        return
    total = max(sum(receipt["bucket_bytes"]), 1)
    cursor = time.perf_counter()
    for index, nbytes in enumerate(receipt["bucket_bytes"]):
        dur = model["t_comm_s"] * nbytes / total
        tr.complete("comm.bucket", cursor, dur, cat="comm",
                    args={"index": index, "bytes": nbytes,
                          "modeled": True})
        cursor += dur
    tr.instant("comm.receipt", cat="comm",
               buckets=len(receipt["bucket_bytes"]),
               allreduce_bytes=receipt["allreduce_bytes"],
               overlap_pct=model["overlap_pct"],
               compress=receipt.get("compress") or "none")
