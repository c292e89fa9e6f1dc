"""Multi-replica router tests (docs/serving.md): per-replica results
bit-identical to single-replica, least-loaded routing around a stalled
replica, overload cascade then fleet-wide 503, cross-replica metrics
aggregation, the warm fleet-restart zero-compile receipt, and snapshot
hot-reload under closed-loop load (same digest = 0 new backend
compiles, zero dropped requests; new digest = background warm-up +
atomic cutover)."""

import threading
import time

import numpy
import pytest

from veles_tpu import chaos
from veles_tpu.backends import Device
from veles_tpu.observe.metrics import registry
from veles_tpu.serve import (
    AOTEngine, ReplicaPool, ServeOverload, ServeService)
from veles_tpu.serve.batcher import serve_snapshot
from tests.test_serve import _mlp_spec

pytestmark = pytest.mark.serve


def _pool(replicas=3, ladder=(8, 32), seed=11, **kwargs):
    plans, params = _mlp_spec(seed=seed)
    pool = ReplicaPool(plans, params, (16,), replicas=replicas,
                       ladder=ladder, **kwargs)
    pool.compile()
    return pool


def test_replicas_bit_identical_to_single_replica():
    """Every replica — and the router over them — returns results bit
    for bit equal to the single-replica sequential reference; the
    replicas really live on distinct devices (the 8-device test
    mesh)."""
    pool = _pool(replicas=3)
    assert len({str(rep.engine.device.jax_device)
                for rep in pool.replicas}) == 3
    assert pool.compile_receipt["replicas"] == 3
    pool.start()
    try:
        rng = numpy.random.RandomState(1)
        x = rng.rand(9, 16).astype(numpy.float32)
        ref = pool.engine.infer(x)
        for rep in pool.replicas:
            out = numpy.stack([rep.batcher.infer(x[i])
                               for i in range(len(x))])
            assert (out == ref).all(), \
                "replica %d diverged" % rep.index
        routed = numpy.stack([pool.infer(x[i]) for i in range(len(x))])
        assert (routed == ref).all()
        block = pool.infer_block(numpy.ascontiguousarray(x[:8]))
        assert (block == ref[:8]).all()
    finally:
        pool.stop()


@pytest.mark.chaos
def test_least_loaded_pick_avoids_stalled_replica():
    """With replica 0's worker stalled (chaos serve.stall) and its
    queue backed up, the router sends new work to an idle sibling."""
    pool = _pool(replicas=2, max_delay_s=0.0)
    chaos.install(chaos.FaultPlan(seed=1).add("serve.stall", "stall",
                                              param=0.4))
    pool.start()
    rep0 = pool.replicas[0]
    try:
        zeros = numpy.zeros(16, numpy.float32)
        stalled = [rep0.batcher.submit(zeros)]
        time.sleep(0.08)  # rep0's worker pops it and stalls 0.4s
        stalled += [rep0.batcher.submit(zeros) for _ in range(2)]
        assert rep0.batcher._q.qsize() >= 2
        routed = pool.submit(numpy.ones(16, numpy.float32))
        # the router picked the idle sibling, not the backed-up replica
        assert routed not in list(rep0.batcher._q.queue)
        assert routed.done.wait(10)
        assert routed.error is None
        for req in stalled:
            assert req.done.wait(10)
    finally:
        pool.stop()
        chaos.uninstall()


@pytest.mark.chaos
def test_overload_cascades_then_503():
    """An overloaded replica cascades the request to its siblings;
    only when EVERY replica sheds does the pool 503 — with the
    smallest retry_after any replica offered."""
    pool = _pool(replicas=2)
    pool.start()
    zeros = numpy.zeros(16, numpy.float32)
    try:
        before = registry.counter("serve.router.cascades").value
        chaos.install(chaos.FaultPlan(seed=1).add("serve.drop", "drop",
                                                  nth=1))
        out = pool.infer(zeros)  # first pick sheds, sibling serves
        assert out.shape == (4,)
        assert registry.counter("serve.router.cascades").value \
            == before + 1
        chaos.uninstall()
        chaos.install(chaos.FaultPlan(seed=1).add("serve.drop",
                                                  "drop"))
        with pytest.raises(ServeOverload) as info:
            pool.submit(zeros)
        assert info.value.retry_after > 0
    finally:
        pool.stop()
        chaos.uninstall()


def test_metrics_aggregate_across_replicas():
    """Counters are process-shared (totals sum across replicas by
    construction); gauges are per-replica and the serve snapshot
    carries the replica block with the aggregate queue depth."""
    requests_before = registry.counter("serve.requests").value
    pool = _pool(replicas=2)
    pool.start()
    try:
        rng = numpy.random.RandomState(3)
        for i in range(12):
            pool.infer(rng.rand(16).astype(numpy.float32))
    finally:
        pool.stop()
    assert registry.counter("serve.requests").value \
        >= requests_before + 12
    assert registry.peek("serve.replica.0.queue_depth") is not None
    assert registry.peek("serve.replica.1.queue_depth") is not None
    snap = serve_snapshot()
    assert snap["replicas"] == 2
    assert len(snap["replica_queue_depths"]) == 2
    assert snap["queue_depth"] == sum(snap["replica_queue_depths"])


def test_warm_fleet_restart_zero_compiles():
    """A restarted 2-replica fleet against the warm persistent cache
    performs 0 new backend compiles ACROSS ALL replicas (jax's cache
    key includes the device assignment, so the cold start wrote one
    entry set per device and the restart deserializes them all).  The
    hidden width is this test's own: its first start is cold."""
    plans, params = _mlp_spec(seed=13, hidden=27)
    cold = ReplicaPool(plans, params, (16,), replicas=2, ladder=(8,))
    cold_receipt = cold.compile()
    assert cold_receipt["new_compiles"] >= 2  # >= one per device
    warm = ReplicaPool(plans, params, (16,), replicas=2, ladder=(8,))
    warm_receipt = warm.compile()
    assert warm_receipt["new_compiles"] == 0, warm_receipt
    assert warm_receipt["cache_hits"] >= 2
    rng = numpy.random.RandomState(4)
    x = rng.rand(3, 16).astype(numpy.float32)
    assert (warm.engine.infer(x) == cold.engine.infer(x)).all()


def _closed_loop(pool, errors, stop, clients=4):
    def worker(k):
        rng = numpy.random.RandomState(k)
        x = rng.rand(16).astype(numpy.float32)
        while not stop.is_set():
            try:
                pool.infer(x, timeout=10.0)
            except Exception as exc:  # EVERY failure counts
                errors.append(exc)
    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(clients)]
    for t in threads:
        t.start()
    return threads


def test_hot_reload_under_load_zero_drops():
    """The acceptance receipt: closed-loop clients hammer the pool
    while (a) a same-digest snapshot reload swaps weights with 0 new
    backend compiles, then (b) a new-digest reload warm-compiles in
    the background and cuts over atomically — zero dropped or failed
    requests through both, and post-reload results match a fresh
    reference engine for the new weights."""
    plans, params = _mlp_spec(seed=17)
    pool = ReplicaPool(plans, params, (16,), replicas=2,
                       ladder=(8, 32), max_delay_s=0.001,
                       max_queue=4096)
    pool.compile()
    pool.start()
    errors, stop = [], threading.Event()
    threads = _closed_loop(pool, errors, stop)
    try:
        time.sleep(0.2)
        # (a) same digest: retrained weights, identical architecture
        _, params2 = _mlp_spec(seed=99)
        receipt = pool.reload(params2)
        assert receipt["mode"] == "params"
        assert receipt["new_compiles"] == 0, receipt
        assert receipt["digest"] == receipt["previous_digest"]
        time.sleep(0.2)
        probe = numpy.random.RandomState(5).rand(16).astype(
            numpy.float32)
        ref2 = pool.engine.infer(probe)[0]
        for rep in pool.replicas:
            assert (rep.batcher.infer(probe) == ref2).all()
        # (b) new digest: wider hidden layer -> full engine cutover
        plans3, params3 = _mlp_spec(seed=5, hidden=24)
        receipt3 = pool.reload(params3, plans=plans3)
        assert receipt3["mode"] == "engine"
        assert receipt3["new_compiles"] >= 1
        assert receipt3["digest"] != receipt3["previous_digest"]
        deadline = time.time() + 5.0
        while time.time() < deadline and any(
                rep.batcher.engine.digest != receipt3["digest"]
                for rep in pool.replicas):
            time.sleep(0.05)  # cutover lands between batches
        for rep in pool.replicas:
            assert rep.batcher.engine.digest == receipt3["digest"]
        time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        pool.stop()
    assert not errors, errors[:3]
    ref_engine = AOTEngine(plans3, params3, (16,), ladder=(8, 32),
                           device=Device(backend="cpu"))
    ref_engine.compile()
    probe = numpy.random.RandomState(6).rand(3, 16).astype(
        numpy.float32)
    assert (pool.engine.infer(probe)
            == ref_engine.infer(probe)).all()
    assert registry.counter("serve.reloads").value >= 2


def test_service_reload_single_engine():
    """The single-engine service mirrors the pool's reload semantics:
    params swap with 0 compiles on the same digest, engine cutover on
    a new one — through the public ServeService surface."""
    plans, params = _mlp_spec(seed=23)
    engine = AOTEngine(plans, params, (16,), ladder=(8,),
                       device=Device(backend="cpu"))
    engine.compile()
    svc = ServeService(engine, max_delay_s=0.001)
    svc.start_background()
    try:
        _, params2 = _mlp_spec(seed=24)
        receipt = svc.reload(params2)
        assert receipt["mode"] == "params"
        assert receipt["new_compiles"] == 0, receipt
        probe = numpy.random.RandomState(7).rand(16).astype(
            numpy.float32)
        answer = svc.infer_payload(probe)
        expect = svc.engine.infer(probe)[0]
        assert (numpy.asarray(answer["probabilities"][0],
                              numpy.float32) == expect).all()
        plans3, params3 = _mlp_spec(seed=25, hidden=24)
        receipt3 = svc.reload(params3, plans=plans3)
        assert receipt3["mode"] == "engine"
        assert svc.engine.digest == receipt3["digest"]
        deadline = time.time() + 5.0
        while time.time() < deadline and \
                svc.batcher.engine.digest != receipt3["digest"]:
            time.sleep(0.05)
        assert svc.batcher.engine.digest == receipt3["digest"]
        assert svc.last_reload is receipt3
    finally:
        svc.stop()


def test_service_over_pool_healthz_and_infer():
    """ServeService drives a whole pool: requests ride the router and
    /healthz carries the per-replica block."""
    import json
    import urllib.request

    pool = _pool(replicas=2, seed=29)
    svc = ServeService(pool, labels_mapping={0: "a", 1: "b", 2: "c",
                                             3: "d"})
    svc.start_background()
    try:
        base = "http://127.0.0.1:%d" % svc.port
        rng = numpy.random.RandomState(8)
        batch = rng.rand(3, 16).astype(numpy.float32)
        req = urllib.request.Request(
            base + "/infer",
            data=json.dumps({"input": batch.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            answer = json.loads(resp.read())
        ref = pool.engine.infer(batch)
        assert (numpy.asarray(answer["probabilities"],
                              numpy.float32) == ref).all()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health["replicas"]["replicas"] == 2
        assert health["model_digest"] == pool.digest
        assert health["compile"]["replicas"] == 2
    finally:
        svc.stop()
