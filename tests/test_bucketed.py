"""SPMD data plane: bucketed gradient all-reduce overlapped with the
backward (veles_tpu/parallel/bucketed.py, compiler SPMD path).

Three tiers:

- plan/partition unit tests (pure host logic, every boundary case);
- bit-equality on the virtual CPU mesh: bucketed+overlapped ==
  flat single-tensor all-reduce for bucket > pytree, bucket of one
  leaf, and a leaf straddling a bucket edge;
- the tier-1-safe ``dist`` smoke: a 2-device compile-only
  collective-bytes audit (SCALING.json methodology) proving the
  bucketed path can never silently regress to the flat all-reduce,
  plus the control-plane demotion (inline update validation) and the
  comm observability receipts.
"""

import math

import numpy
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from veles_tpu.compiler import LayerPlan, build_train_step
from veles_tpu.models.all2all import All2AllSoftmax, All2AllTanh
from veles_tpu.parallel import make_mesh
from veles_tpu.parallel.analysis import parse_collective_ops
from veles_tpu.parallel.bucketed import (
    DEFAULT_BUCKET_MB, bucketed_all_reduce, comm_receipt, overlap_model,
    plan_buckets, publish_comm_receipt)
from veles_tpu.parallel.mesh import shard_map
from veles_tpu.parallel.ring import ring_all_reduce


def _sds(*shapes):
    return [jax.ShapeDtypeStruct(s, numpy.float32) for s in shapes]


def _plan_coverage(plan, leaves):
    """Every element of every leaf covered exactly once, in order."""
    for i, leaf in enumerate(leaves):
        size = int(math.prod(leaf.shape)) if leaf.shape else 1
        spans = sorted((s, e) for b in plan.buckets
                       for (j, s, e) in b.slices if j == i)
        pos = 0
        for s, e in spans:
            assert s == pos and e > s
            pos = e
        assert pos == size, "leaf %d covered %d/%d" % (i, pos, size)


# -- bucket planning (pure host logic) ------------------------------------

class TestPlanBuckets(object):

    def test_bucket_larger_than_pytree_is_flat(self):
        leaves = _sds((100, 10), (10,), (10, 4), (4,))
        for target in (float("inf"), 10 * 2 ** 20):
            plan = plan_buckets(leaves, target)
            assert len(plan.buckets) == 1
            _plan_coverage(plan, leaves)
            assert plan.total_bytes == 4 * (1000 + 10 + 40 + 4)

    def test_bucket_of_exactly_one_leaf(self):
        # target == every leaf's byte size -> one bucket per leaf
        leaves = _sds((64,), (64,), (64,))
        plan = plan_buckets(leaves, 64 * 4)
        assert len(plan.buckets) == 3
        assert all(len(b.slices) == 1 and b.elems == 64
                   for b in plan.buckets)
        _plan_coverage(plan, leaves)

    def test_leaf_straddles_bucket_edge(self):
        # 100-element leaf against a 64-element bucket: the leaf must
        # split at the exact element boundary, spanning two buckets
        leaves = _sds((100,))
        plan = plan_buckets(leaves, 64 * 4)
        assert len(plan.buckets) == 2
        assert plan.buckets[0].slices == [(0, 0, 64)]
        assert plan.buckets[1].slices == [(0, 64, 100)]
        _plan_coverage(plan, leaves)

    def test_reverse_production_order(self):
        # bucket 0 must hold the LAST leaf's gradients — the first the
        # backward pass produces — so its all-reduce can overlap the
        # rest of the backward
        leaves = _sds((8,), (8,), (8,))
        plan = plan_buckets(leaves, 8 * 4)
        assert [b.slices[0][0] for b in plan.buckets] == [2, 1, 0]

    def test_mixed_spans_fill_to_target(self):
        leaves = _sds((10,), (30,), (10,))
        plan = plan_buckets(leaves, 25 * 4)
        _plan_coverage(plan, leaves)
        assert sum(b.elems for b in plan.buckets) == 50
        # no bucket exceeds the target
        assert all(b.nbytes <= 25 * 4 for b in plan.buckets)

    def test_default_target(self):
        leaves = _sds((1000,))
        plan = plan_buckets(leaves, None)
        assert len(plan.buckets) == 1  # 4 KB << 25 MB
        assert plan.bucket_bytes == DEFAULT_BUCKET_MB * 2 ** 20

    def test_invalid_target_rejected(self):
        with pytest.raises(ValueError):
            plan_buckets(_sds((8,)), 0)


# -- bit-equality on the virtual mesh -------------------------------------

def _mlp_state(rng, dims):
    out = []
    for fi, fo in zip(dims[:-1], dims[1:]):
        out.append({
            "weights": rng.randn(fi, fo).astype(numpy.float32) * 0.1,
            "bias": numpy.zeros(fo, numpy.float32),
            "accum_weights": numpy.zeros((fi, fo), numpy.float32),
            "accum_bias": numpy.zeros(fo, numpy.float32),
            "accum2_weights": None, "accum2_bias": None})
    return out


def _plans(lr=0.1):
    hyper = {"learning_rate": lr, "gradient_moment": 0.9}
    return [LayerPlan(All2AllTanh, hyper=hyper),
            LayerPlan(All2AllSoftmax, hyper=hyper)]


def _batch(rng, n=64, fan_in=16, classes=4):
    labels = (numpy.arange(n) % classes).astype(numpy.int32)
    centers = rng.randn(classes, fan_in).astype(numpy.float32) * 2
    x = (centers[labels] +
         rng.randn(n, fan_in).astype(numpy.float32) * 0.2)
    return x, labels


def _run_steps(step, state, x, labels, n_steps=3):
    for _ in range(n_steps):
        state, metrics = step(state, x, labels, numpy.float32(len(x)))
    return state, metrics


def _assert_bit_equal(sa, sb):
    la = jax.tree_util.tree_leaves(sa)
    lb = jax.tree_util.tree_leaves(sb)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert numpy.array_equal(numpy.asarray(a), numpy.asarray(b)), \
            "bucketed result is not bit-identical to the flat all-reduce"


# grad pytree here: 16x32 w (2048 B), 32 b (128 B), 32x4 w (512 B),
# 4 b (16 B) -> 2704 bytes total.  The parametrized targets hit every
# boundary case from the issue checklist.
_BUCKET_CASES = {
    "bucket_gt_pytree": 1.0,                    # 1 MB >> 2.7 KB: flat
    "bucket_of_one_leaf": 2048 / 2.0 ** 20,     # largest leaf alone
    "leaf_straddles_edge": 1000 / 2.0 ** 20,    # splits both weights
    "one_bucket_per_element_ish": 64 / 2.0 ** 20,
}


@pytest.mark.dist
@pytest.mark.parametrize("case", sorted(_BUCKET_CASES))
def test_bucketed_bit_identical_to_flat(case):
    """Acceptance: the bucketed+overlapped all-reduce produces the
    same update as the flat single-tensor all-reduce, bit for bit,
    for every bucket-size boundary case, over several chained steps."""
    rng = numpy.random.RandomState(5)
    state = _mlp_state(rng, (16, 32, 4))
    x, labels = _batch(rng)
    mesh = make_mesh({"data": 8})

    flat = build_train_step(_plans(), mesh=mesh,
                            grad_bucket_mb=float("inf"), donate=False)
    buck = build_train_step(_plans(), mesh=mesh,
                            grad_bucket_mb=_BUCKET_CASES[case],
                            donate=False)
    sf, mf = _run_steps(flat, [dict(s) for s in state], x, labels)
    sb, mb = _run_steps(buck, [dict(s) for s in state], x, labels)
    _assert_bit_equal(sf, sb)
    assert float(mf["loss"]) == float(mb["loss"])
    assert int(mf["n_err"]) == int(mb["n_err"])


@pytest.mark.dist
def test_spmd_step_matches_single_device_and_pjit():
    """The SPMD shard_map plane agrees with the single-device step and
    the pjit annotation path (same math, different collectives)."""
    from veles_tpu.parallel import (auto_mesh, batch_sharding,
                                    mlp_state_shardings)
    rng = numpy.random.RandomState(7)
    state = _mlp_state(rng, (16, 32, 4))
    x, labels = _batch(rng)

    ref_step = build_train_step(_plans(), donate=False)
    sr, mr = _run_steps(ref_step, [dict(s) for s in state], x, labels)

    mesh = auto_mesh()
    spmd = build_train_step(_plans(), mesh=mesh, grad_bucket_mb=0.001,
                            donate=False)
    sb, mb = _run_steps(spmd, [dict(s) for s in state], x, labels)

    pjit_step = build_train_step(
        _plans(), mesh=mesh,
        state_shardings=mlp_state_shardings(mesh, state),
        batch_sharding=batch_sharding(mesh), donate=False)
    sp, mp = _run_steps(pjit_step, [dict(s) for s in state], x, labels)

    for a, b, c in zip(jax.tree_util.tree_leaves(sr),
                       jax.tree_util.tree_leaves(sb),
                       jax.tree_util.tree_leaves(sp)):
        numpy.testing.assert_allclose(numpy.asarray(a), numpy.asarray(b),
                                      rtol=1e-4, atol=1e-6)
        numpy.testing.assert_allclose(numpy.asarray(b), numpy.asarray(c),
                                      rtol=1e-4, atol=1e-6)
    assert abs(float(mr["loss"]) - float(mb["loss"])) < 1e-5
    assert abs(float(mp["loss"]) - float(mb["loss"])) < 1e-5


@pytest.mark.dist
def test_short_minibatch_mse_mask_is_global():
    """A short (padded) minibatch's masked tail lives in the LAST
    shard under SPMD; the mse mask must key on GLOBAL row indices or
    the pad rows of every shard but the first would leak into the
    loss.  Equality vs the single-device step proves it."""
    from veles_tpu.models.all2all import All2AllTanh as Tanh
    plans = [LayerPlan(Tanh, hyper={"learning_rate": 0.1})]
    rng = numpy.random.RandomState(9)
    state = [{"weights": rng.randn(8, 8).astype(numpy.float32) * 0.1,
              "bias": numpy.zeros(8, numpy.float32),
              "accum_weights": numpy.zeros((8, 8), numpy.float32),
              "accum_bias": numpy.zeros(8, numpy.float32),
              "accum2_weights": None, "accum2_bias": None}]
    x = rng.randn(16, 8).astype(numpy.float32)
    t = rng.randn(16, 8).astype(numpy.float32)
    # only 11 of 16 rows are real; rows 11.. are loader padding
    bs = numpy.float32(11)

    ref = build_train_step(plans, loss="mse", donate=False)
    sr, mr = ref([dict(s) for s in state], x, t, bs)

    mesh = make_mesh({"data": 8})
    spmd = build_train_step(plans, loss="mse", mesh=mesh,
                            grad_bucket_mb=0.001, donate=False)
    sb, mb = spmd([dict(s) for s in state], x, t, bs)
    numpy.testing.assert_allclose(
        numpy.asarray(sr[0]["weights"]), numpy.asarray(sb[0]["weights"]),
        rtol=1e-5, atol=1e-7)
    assert abs(float(mr["mse_sum"]) - float(mb["mse_sum"])) < 1e-4


@pytest.mark.dist
def test_ring_all_reduce_matches_sum():
    """The explicit ppermute ring (reduce-scatter + all-gather) sums
    correctly, including lengths not divisible by the ring size."""
    mesh = make_mesh({"data": 8})
    rng = numpy.random.RandomState(2)
    for length in (1000, 1001, 7):  # pad path and tiny vectors
        rows = rng.randn(8, length).astype(numpy.float32)

        fn = shard_map(
            lambda v: ring_all_reduce(v.reshape(-1), "data", 8),
            mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False)
        got = numpy.asarray(fn(rows))
        numpy.testing.assert_allclose(got, rows.sum(axis=0),
                                      rtol=1e-5, atol=1e-5)


@pytest.mark.dist
def test_ring_impl_step_close_to_psum():
    """impl='ring' changes summation order (ULP-close, not bit-equal);
    the trained step still agrees to float tolerance."""
    rng = numpy.random.RandomState(11)
    state = _mlp_state(rng, (16, 32, 4))
    x, labels = _batch(rng)
    mesh = make_mesh({"data": 8})
    psum_step = build_train_step(_plans(), mesh=mesh,
                                 grad_bucket_mb=0.001, donate=False)
    ring_step = build_train_step(_plans(), mesh=mesh,
                                 grad_bucket_mb=0.001,
                                 grad_allreduce_impl="ring",
                                 donate=False)
    sp, _ = _run_steps(psum_step, [dict(s) for s in state], x, labels)
    sr, _ = _run_steps(ring_step, [dict(s) for s in state], x, labels)
    for a, b in zip(jax.tree_util.tree_leaves(sp),
                    jax.tree_util.tree_leaves(sr)):
        numpy.testing.assert_allclose(numpy.asarray(a), numpy.asarray(b),
                                      rtol=1e-4, atol=1e-6)


# -- bf16 compression + health gating -------------------------------------

@pytest.mark.dist
@pytest.mark.health
def test_bf16_compression_trains_and_skips_poison_bit_exactly():
    rng = numpy.random.RandomState(13)
    state = _mlp_state(rng, (16, 32, 4))
    x, labels = _batch(rng)
    mesh = make_mesh({"data": 8})
    step = build_train_step(_plans(), mesh=mesh, grad_bucket_mb=0.001,
                            grad_compress="bf16", donate=False)
    s1, m1 = step([dict(s) for s in state], x, labels,
                  numpy.float32(64))
    assert bool(m1["finite"])
    assert numpy.isfinite(float(m1["loss"]))
    # compressed grads still descend
    ref = build_train_step(_plans(), donate=False)
    sr, _ = ref([dict(s) for s in state], x, labels, numpy.float32(64))
    numpy.testing.assert_allclose(
        numpy.asarray(s1[0]["weights"]), numpy.asarray(sr[0]["weights"]),
        rtol=2e-2, atol=2e-3)

    # a poisoned step under compression is SKIPPED bit-exactly: psum
    # spreads the NaN to every replica, the guard refuses the update
    s2, m2 = step([dict(s) for s in state], x, labels,
                  numpy.float32(64), None, numpy.float32(numpy.nan))
    assert not bool(m2["finite"]) and int(m2["skipped"]) == 1
    for before, after in zip(jax.tree_util.tree_leaves(state),
                             jax.tree_util.tree_leaves(s2)):
        assert numpy.array_equal(numpy.asarray(before),
                                 numpy.asarray(after))


@pytest.mark.health
def test_trainer_compression_fallback_on_health_sync():
    """FusedTrainer.on_health_sync: fresh skips while bf16 compression
    is on -> drop the compiled step and fall back to f32 (the PR 3
    watchdog gate riding the existing class-end sync)."""
    from veles_tpu.models.fused import FusedTrainer
    from veles_tpu.observe.metrics import registry

    trainer = FusedTrainer.__new__(FusedTrainer)
    trainer.grad_compress = "bf16"
    trainer._compress_skips_seen_ = 0
    trainer._step_fn = object()
    trainer._state = None  # sync() is a no-op without live fused state
    trainer._comm_published_ = True
    trainer.warning = lambda *a, **k: None
    before = registry.counter("comm.compress_fallbacks").value

    trainer.on_health_sync(skips=0, consec=0)   # no skips: no change
    assert trainer.grad_compress == "bf16"
    trainer.on_health_sync(skips=2, consec=1)   # fresh skips: fall back
    assert trainer.grad_compress is None
    assert trainer._step_fn is None
    assert not trainer._comm_published_
    assert registry.counter("comm.compress_fallbacks").value == before + 1
    trainer.on_health_sync(skips=2, consec=0)   # stale count: no-op
    assert trainer._step_fn is None


# -- the tier-1 dist smoke: compile-only collective-bytes audit -----------

@pytest.mark.dist
def test_two_device_spmd_smoke_collective_bytes():
    """Tier-1-safe 2-device virtual-CPU SPMD smoke (SCALING.json
    methodology, compile-only): the bucketed step must move one
    gradient payload PER BUCKET, their sizes must match the plan, and
    their sum must equal the flat path's single gradient payload — so
    the overlap path can never silently regress to the flat monolith.

    Pinned on what holds by construction.  The LOWERED program carries
    one all_reduce per bucket (plus the two metric psums), chained by
    optimization barriers; in the OPTIMIZED HLO every bucket is still
    its own operand.  Whether XLA then ISSUES them as separate ops is
    the compiler's: the installed XLA:CPU (jax 0.9.0) combines these
    KB-sized all-reduces — and the 4-byte loss psum with them, the
    2708-vs-2704 of the old pin — into one tuple op, so the optimized
    op count is not asserted here; chip_smoke.py reports it on a
    four-chip host."""
    rng = numpy.random.RandomState(3)
    state = _mlp_state(rng, (16, 32, 4))
    x, labels = _batch(rng, n=16)
    mesh = make_mesh({"data": 2}, jax.devices()[:2])
    args = (state, x, labels, numpy.float32(16))

    grad_bytes = 4 * (16 * 32 + 32 + 32 * 4 + 4)  # 2704
    bucket_mb = 1024 / 2.0 ** 20                  # 1 KB buckets

    grads_like = [{"weights": s["weights"], "bias": s["bias"]}
                  for s in state]
    plan = plan_buckets(jax.tree_util.tree_leaves(grads_like),
                        1024)
    assert len(plan.buckets) >= 3

    def grad_payloads(step):
        """(lowered all_reduce count, gradient payload bytes): the
        metric psums are 4-byte scalars, a gradient bucket never is."""
        lowered = step.lower(*args)
        payloads = [part for op in parse_collective_ops(
                        lowered.compile().as_text())
                    if op["kind"] == "all-reduce"
                    for part in op["parts"] if part >= 512]
        return lowered.as_text().count("stablehlo.all_reduce"), payloads

    buck = build_train_step(_plans(), mesh=mesh,
                            grad_bucket_mb=bucket_mb, donate=False)
    flat = build_train_step(_plans(), mesh=mesh,
                            grad_bucket_mb=float("inf"), donate=False)
    bucket_lowered, bucket_payloads = grad_payloads(buck)
    flat_lowered, flat_payloads = grad_payloads(flat)
    metric_psums = 2  # loss + error count

    assert flat_lowered == 1 + metric_psums
    assert flat_payloads == [grad_bytes]
    assert bucket_lowered == len(plan.buckets) + metric_psums, \
        "bucketed step regressed: %d collective(s) for %d buckets" % (
            bucket_lowered - metric_psums, len(plan.buckets))
    assert sum(bucket_payloads) == grad_bytes
    assert sorted(bucket_payloads) == sorted(
        b.nbytes for b in plan.buckets)


# -- overlap model + comm receipts ----------------------------------------

class TestOverlapModel(object):

    def test_no_step_time_credits_nothing(self):
        m = overlap_model(250e6, 10, 8, step_seconds=None)
        assert m["overlap_pct"] == 0.0
        assert m["t_comm_exposed_s"] == m["t_comm_s"]

    def test_single_bucket_cannot_hide(self):
        m = overlap_model(250e6, 1, 8, step_seconds=1.0)
        assert m["overlap_pct"] == 0.0

    def test_more_buckets_more_overlap_until_window_bound(self):
        prev = -1.0
        for buckets in (2, 5, 10):
            m = overlap_model(250e6, buckets, 8, step_seconds=0.015)
            assert m["overlap_pct"] >= prev
            prev = m["overlap_pct"]
        # the tail bucket is never hidable
        assert m["t_comm_exposed_s"] >= m["t_comm_s"] / 10 - 1e-12

    def test_window_bound(self):
        # tiny step: the backward window, not the bucket count, limits
        # the hidable fraction
        m = overlap_model(250e6, 10, 8, step_seconds=1e-4,
                          bwd_fraction=0.5)
        assert m["t_comm_hidden_s"] <= 0.5 * 1e-4 * 0.9 + 1e-12


def test_comm_receipt_publishes_gauges_and_bucket_spans():
    from veles_tpu.observe.metrics import MetricsRegistry
    from veles_tpu.observe.trace import SpanTracer

    leaves = _sds((1000, 100), (100,))
    receipt = comm_receipt(leaves, 8, bucket_bytes=100 * 1000,
                           step_seconds=0.02)
    assert receipt["allreduce_bytes"] == 4 * (100000 + 100)
    assert len(receipt["bucket_bytes"]) == len(
        plan_buckets(leaves, 100 * 1000).buckets)

    reg = MetricsRegistry()
    tr = SpanTracer()
    tr.start()
    publish_comm_receipt(receipt, tracer=tr, registry=reg)
    tr.stop()
    assert reg.peek("comm.allreduce_bytes").value == \
        receipt["allreduce_bytes"]
    assert reg.peek("comm.buckets").value == len(receipt["bucket_bytes"])
    assert reg.peek("comm.overlap_pct").value == \
        receipt["model"]["overlap_pct"]
    spans = [e for e in tr.events
             if e.get("name") == "comm.bucket" and e.get("ph") == "X"]
    assert len(spans) == len(receipt["bucket_bytes"])
    assert [s["args"]["index"] for s in spans] == \
        list(range(len(spans)))
    assert all(s["args"]["modeled"] for s in spans)
    assert any(e.get("name") == "comm.receipt" for e in tr.events)


# -- control-plane demotion: single-traversal update validation ----------

class _RecordingUnit(object):
    def __init__(self, name):
        self.name = name
        self.applied = []

    def apply_data_from_slave(self, part, slave=None):
        self.applied.append(part)


class _StubControlWorkflow(object):
    """Bare workflow-contract stand-in exposing the pieces the inline
    validator touches."""
    update_validation = "inline"

    def __init__(self, units):
        self.units = units
        self._method_timers = {}

    def _distributed_units(self):
        return self.units

    # borrow the REAL implementations under test
    from veles_tpu.workflow import Workflow as _W
    apply_update_validated = _W.apply_update_validated
    apply_data_from_slave = _W.apply_data_from_slave
    _timed_method = _W._timed_method


def test_apply_update_validated_single_pass_and_poison_stops():
    from veles_tpu.health import PoisonedUpdate

    units = [_RecordingUnit("a"), _RecordingUnit("b"),
             _RecordingUnit("c")]
    wf = _StubControlWorkflow(units)
    ok = [numpy.arange(4, dtype=numpy.float32),
          {"n": 3, "loss": 0.5},
          None]
    assert wf.apply_update_validated(ok, None) is True
    assert units[0].applied and units[1].applied
    assert not units[2].applied  # None part skipped

    poisoned = [numpy.arange(4, dtype=numpy.float32),
                {"delta": numpy.array([1.0, numpy.nan])},
                {"n": 1}]
    units2 = [_RecordingUnit("a"), _RecordingUnit("b"),
              _RecordingUnit("c")]
    wf2 = _StubControlWorkflow(units2)
    with pytest.raises(PoisonedUpdate) as err:
        wf2.apply_update_validated(poisoned, None)
    # the poisoned part never applied, nor anything after it; the
    # finite part BEFORE it did (control records: recovered by the
    # drop/requeue path, docs/distributed.md)
    assert units2[0].applied
    assert not units2[1].applied
    assert not units2[2].applied
    assert "_RecordingUnit" in str(err.value)


def test_server_quarantines_inline_poisoned_update(cpu_device):
    """End-to-end over the real Server/Client sockets: a workflow in
    inline-validation mode (the SPMD control plane) still quarantines
    a poisoned update — single traversal, same drop + TTL-blacklist
    semantics (counted via server.quarantined and the blacklist)."""
    import time as _time

    from veles_tpu.jobfarm import JobFarm

    farm = JobFarm("bucketed-inline", blacklist_ttl=0.4)

    calls = []

    def runner(spec):
        calls.append(spec)
        if spec == "poison" and calls.count("poison") == 1:
            return {"delta": numpy.array([numpy.nan], numpy.float32)}
        return {"delta": numpy.array([float(len(calls))],
                                     numpy.float32)}

    farm.start(runner=runner, local_slaves=1)
    try:
        # flip the farm master to the inline single-traversal mode:
        # results are control-record dicts here, so the demoted
        # validation path applies
        farm._master.update_validation = "inline"
        results = farm.submit(["ok1", "poison", "ok2"], timeout=30)
        assert len(results) == 3
        # the poisoned result was dropped and its job re-run after the
        # quarantine TTL, so every slot holds a finite value
        for r in results:
            assert numpy.isfinite(r["delta"]).all()
        assert farm.server.quarantined == 1
    finally:
        farm.shutdown()
        _time.sleep(0)


def test_legacy_prewalk_unchanged_all_or_nothing():
    """Workflows that still ship per-step deltas keep the
    all-or-nothing prewalk (update_validation default)."""
    from veles_tpu.workflow import Workflow
    assert Workflow.update_validation == "prewalk"
    from veles_tpu.jobfarm import _FarmMaster
    assert _FarmMaster.update_validation == "prewalk"


# -- e2e: SPMD fused workflow + demoted control plane + merged trace ------

def _blobs_workflow(seed_name, mesh=None, bucket=None, compress=None,
                    device=None, max_epochs=3):
    from tests.test_models import BlobsLoader
    from veles_tpu import prng
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.prng import RandomGenerator

    prng.get().seed(7)
    sw = StandardWorkflow(
        DummyWorkflow().workflow,
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 4,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: BlobsLoader(
            w, minibatch_size=32,
            prng=RandomGenerator(seed_name, seed=3)),
        decision_config=dict(max_epochs=max_epochs))
    sw.fuse(mesh=mesh, grad_bucket_mb=bucket, grad_compress=compress)
    sw.initialize(device=device)
    return sw


@pytest.mark.dist
def test_fused_spmd_workflow_trains_and_publishes_comm(cpu_device):
    """The whole stack: StandardWorkflow.fuse(mesh=...) runs the SPMD
    bucketed inner loop, matches the single-device fused run, demotes
    the protocol (inline validation), and publishes the comm
    receipt."""
    from veles_tpu.observe.metrics import registry
    from veles_tpu.parallel import auto_mesh

    registry.reset()
    ref = _blobs_workflow("dist_e2e", device=cpu_device)
    ref.run()
    ref.fused_trainer.sync()

    mesh = auto_mesh()
    got = _blobs_workflow("dist_e2e", mesh=mesh, bucket=0.001,
                          device=cpu_device)
    assert got.update_validation == "inline"
    assert ref.update_validation == "prewalk"
    got.run()
    got.fused_trainer.sync()

    assert bool(ref.decision.complete) and bool(got.decision.complete)
    for fr, fg in zip(ref.forwards, got.forwards):
        fr.weights.map_read()
        fg.weights.map_read()
        numpy.testing.assert_allclose(fr.weights.mem, fg.weights.mem,
                                      rtol=1e-4, atol=1e-6)
    assert registry.peek("comm.allreduce_bytes").value > 0
    assert registry.peek("comm.buckets").value >= 2
    assert registry.peek("comm.overlap_pct").value is not None


@pytest.mark.dist
def test_spmd_mesh_survives_pickle_resume(cpu_device):
    """A Mesh holds live device handles, so snapshots carry its AXES;
    initialize() must rebuild it on resume instead of silently
    degrading the resumed run to a single-device step."""
    from veles_tpu.models.fused import FusedTrainer
    from veles_tpu.parallel import auto_mesh

    sw = _blobs_workflow("dist_resume", mesh=auto_mesh(), bucket=0.001,
                         device=cpu_device, max_epochs=1)
    state = sw.fused_trainer.__getstate__()
    assert state["mesh"] is None
    assert state["_spmd_axes_"] == {"data": 8}

    def bare(axes):
        t = FusedTrainer.__new__(FusedTrainer)
        t.mesh = None
        t._spmd_axes_ = axes
        t.warning = lambda *a, **k: None
        return t

    resumed = bare({"data": 8})
    resumed._restore_mesh()
    assert resumed.mesh is not None
    assert dict(resumed.mesh.shape) == {"data": 8}

    # a pure-DP mesh that no longer fits re-spans the current devices
    refit = bare({"data": 16})
    refit._restore_mesh()
    assert dict(refit.mesh.shape) == {"data": 8}

    # a multi-axis shape that cannot be rebuilt fails LOUDLY
    with pytest.raises(ValueError, match="re-fuse"):
        bare({"data": 5, "model": 3})._restore_mesh()


@pytest.mark.dist
@pytest.mark.chaos
def test_two_node_chaos_merged_trace_carries_comm_spans(
        cpu_device, tmp_path):
    """Acceptance: a 2-process-track chaos run (in-proc master +
    slave, injected poisoned update) produces a merged Perfetto trace
    in which the SPMD data plane's per-bucket comm spans and the
    ``comm.overlap_pct`` gauge are visible alongside the control
    plane's protocol events."""
    from tests.test_network import _build, _start_server
    from veles_tpu import chaos
    from veles_tpu.chaos import FaultPlan
    from veles_tpu.client import Client
    from veles_tpu.observe.merge import merge_run
    from veles_tpu.observe.metrics import registry
    from veles_tpu.observe.trace import tracer, validate_trace
    from veles_tpu.parallel import auto_mesh

    registry.reset()
    tracer.start()
    tracer.label = "master"
    try:
        # the master's data plane: an SPMD bucketed run records the
        # per-bucket comm spans on the master track while the control
        # plane serves jobs below
        spmd = _blobs_workflow("dist_chaos_spmd", mesh=auto_mesh(),
                               bucket=0.001, device=cpu_device,
                               max_epochs=2)
        spmd.run()

        master = _build("master", "dist_chaos_m", cpu_device)
        slave = _build("slave", "dist_chaos_s", cpu_device)
        server, _ = _start_server(master, blacklist_ttl=0.6)
        client = Client("127.0.0.1:%d" % server.port, slave,
                        trace_scope="threads")
        plan = chaos.install(FaultPlan().add("net.update", "nan",
                                             nth=2))
        try:
            client.run()
        finally:
            chaos.uninstall()
        assert server._done.wait(15)
        assert plan.fired("net.update") == 1
        assert server.quarantined == 1

        import json as _json
        trace_path = str(tmp_path / "master.json")
        tracer.save(trace_path)
        with open(trace_path) as fin:
            master_doc = _json.load(fin)
        merged = merge_run(master_doc, server.trace_collector,
                           trace_id=server.trace_id)
        validate_trace(merged)
    finally:
        tracer.stop()

    events = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    buckets = [e for e in events if e["name"] == "comm.bucket"]
    assert len(buckets) >= 2, \
        "per-bucket comm spans missing from the merged trace"
    assert {b["args"]["index"] for b in buckets} >= {0, 1}
    assert any(e["name"] == "comm.receipt" for e in events)
    assert any(e["name"] == "proto.quarantine" for e in events)
    assert registry.peek("comm.overlap_pct").value is not None
    assert registry.peek("comm.allreduce_bytes").value > 0
