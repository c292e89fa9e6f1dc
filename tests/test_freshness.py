"""Train-to-serve freshness-loop tests (docs/serving.md "Freshness
loop"): the publish contract (atomic LATEST pointer, export-ordinal
order, bounded view retention), watcher verify-before-unpickle with
skip-and-retry backoff and TTL poisoning, the canary state machine
(live-rotation exclusion, mirror-path bit-equality, shadow-excluded
served counters, promote/auto-rollback with the zero-recompile
rollback receipt), the EMA-spike comparator, and the chaos-soak
smoke behind FRESH.json."""

import json
import logging
import os
import threading
import time

import numpy
import pytest

from veles_tpu import chaos
from veles_tpu.backends import Device
from veles_tpu.health import EmaSpikeWatch
from veles_tpu.observe.metrics import registry
from veles_tpu.serve import (
    AOTEngine, CanaryComparator, FreshnessController, ReplicaPool,
    ServeOverload, SnapshotWatcher, export_model_spec, value_digest)
from veles_tpu.snapshotter import (
    LATEST_NAME, MANIFEST_SUFFIX, SnapshotError, publish_snapshot,
    read_latest)
from tests.test_serve import _mlp_spec

pytestmark = pytest.mark.freshness


def _spec_path(tmp_path, name, params, plans=None, shape=(16,)):
    if plans is None:
        plans, _ = _mlp_spec()
    path = str(tmp_path / name)
    export_model_spec(path, plans, params, shape)
    return path


def _pool(tmp_path, replicas=3, ladder=(8,), seed=11, **kwargs):
    plans, params = _mlp_spec(seed=seed)
    pool = ReplicaPool(plans, params, (16,), replicas=replicas,
                       ladder=ladder, max_delay_s=0.001,
                       max_queue=4096, **kwargs)
    pool.compile()
    return pool


def _controller(pool, tmp_path, **kwargs):
    kwargs.setdefault("poll_s", 0.02)
    kwargs.setdefault("min_mirrors", 4)
    kwargs.setdefault("mirror_fraction", 1.0)
    kwargs.setdefault("breach_budget", 2)
    kwargs.setdefault("verdict_timeout_s", 15.0)
    return FreshnessController(pool, str(tmp_path / "publish"),
                               **kwargs)


def _perturb(params, scale=0.05, seed=3):
    rng = numpy.random.RandomState(seed)
    return [{k: v + scale * rng.randn(*v.shape).astype(v.dtype)
             for k, v in entry.items()} for entry in params]


def _drive(pool, n=40, seed=5, sleep=0.0):
    """Closed-loop traffic; returns (samples, results) in order."""
    rng = numpy.random.RandomState(seed)
    samples = [rng.rand(16).astype(numpy.float32) for _ in range(n)]
    results = []
    for x in samples:
        results.append(numpy.array(pool.infer(x, timeout=15.0)))
        if sleep:
            time.sleep(sleep)
    return samples, results


# -- publish contract --------------------------------------------------------


def test_publish_contract_ordinals_latest_retention(tmp_path):
    plans, params = _mlp_spec(seed=1)
    pub = str(tmp_path / "pub")
    receipts = []
    for i in range(5):
        path = _spec_path(tmp_path, "s%d.pickle" % i,
                          _perturb(params, seed=i), plans)
        receipts.append(publish_snapshot(path, pub, keep=3))
    assert [r["ordinal"] for r in receipts] == [1, 2, 3, 4, 5]
    latest = read_latest(pub)
    assert latest["ordinal"] == 5
    assert latest["snapshot"].startswith("000005_")
    assert latest["sha256"] == receipts[-1]["sha256"]
    # bounded view: keep=3 newest ordinals survive, each with its
    # manifest; the LATEST target is among them by construction
    published = sorted(f for f in os.listdir(pub)
                       if f[0].isdigit() and
                       not f.endswith(MANIFEST_SUFFIX))
    assert [f.split("_")[0] for f in published] == \
        ["000003", "000004", "000005"]
    for f in published:
        assert os.path.exists(os.path.join(pub, f + MANIFEST_SUFFIX))
    assert os.path.exists(os.path.join(pub, latest["snapshot"]))


def test_publish_refuses_unverifiable(tmp_path):
    plans, params = _mlp_spec(seed=2)
    path = _spec_path(tmp_path, "good.pickle", params, plans)
    # corrupt the data after the manifest was written
    with open(path, "r+b") as fout:
        fout.write(b"\x00\x00garbage")
    with pytest.raises(SnapshotError):
        publish_snapshot(path, str(tmp_path / "pub"))
    bare = str(tmp_path / "bare.pickle")
    import pickle
    with open(bare, "wb") as fout:
        pickle.dump({"plans": plans, "params": params,
                     "sample_shape": (16,)}, fout)
    with pytest.raises(SnapshotError):  # no manifest -> unverifiable
        publish_snapshot(bare, str(tmp_path / "pub"))


def test_snapshotter_unit_publishes_real_workflow(tmp_path,
                                                  cpu_device):
    """The trainer-side hook end-to-end: a real Snapshotter with
    publish_dir pushes its manifest-verified workflow snapshot, and
    the watcher extracts a servable plans/params spec from it.  The
    publish dir is a retention-EXEMPT view: the train dir's keep=N
    does not govern it."""
    from veles_tpu.snapshotter import Snapshotter
    from tests.test_snapshot import _build
    sw = _build(cpu_device, max_epochs=1)
    sw.run()
    pub = str(tmp_path / "pub")
    snap = Snapshotter(sw, directory=str(tmp_path / "train"),
                       prefix="fw", interval=1, time_interval=0,
                       compression="gz", keep=1, publish_dir=pub)
    snap.initialize()
    for i in range(3):
        snap.suffix = "e%d" % i
        snap.export()
        time.sleep(0.02)
    # train dir keep=1 pruned history; the publish view kept all 3
    published = [f for f in os.listdir(pub) if f[0].isdigit() and
                 not f.endswith(MANIFEST_SUFFIX)]
    assert len(published) == 3
    assert read_latest(pub)["ordinal"] == 3
    watcher = SnapshotWatcher(pub, default_sample_shape=(16,))
    cand = watcher.poll_once()
    assert cand is not None and cand.ordinal == 3
    assert cand.sample_shape == (16,)
    assert len(cand.plans) == 2 and "weights" in cand.params[0]
    # the spec actually serves
    engine = AOTEngine(cand.plans, cand.params, cand.sample_shape,
                       ladder=(8,), device=Device(backend="cpu"))
    engine.compile()
    out = engine.infer(numpy.zeros((2, 16), numpy.float32))
    assert out.shape == (2, 4) and numpy.isfinite(out).all()


# -- watcher discipline ------------------------------------------------------


def test_watcher_skips_and_retries_torn_publish(tmp_path, caplog):
    """A half-written publish (chaos freshness.publish=truncate) is
    skipped and retried with backoff — at DEBUG, never a warning per
    poll tick — and the next good publish supersedes it."""
    plans, params = _mlp_spec(seed=3)
    pub = str(tmp_path / "pub")
    chaos.install(chaos.FaultPlan(seed=1).add(
        "freshness.publish", "truncate", nth=1))
    try:
        publish_snapshot(_spec_path(tmp_path, "a.pickle", params,
                                    plans), pub)
    finally:
        chaos.uninstall()
    watcher = SnapshotWatcher(pub, poll_s=0.01, invalid_ttl_s=60.0)
    with caplog.at_level(logging.DEBUG, logger="SnapshotWatcher"):
        for _ in range(6):
            assert watcher.poll_once() is None
            time.sleep(0.012)
    warnings = [r for r in caplog.records
                if r.levelno >= logging.WARNING]
    assert not warnings, warnings
    pend = watcher._pending
    assert pend is not None and pend["ordinal"] == 1
    assert pend["backoff"] > watcher.poll_s  # backoff actually grew
    # the re-publish supersedes the torn ordinal immediately
    publish_snapshot(_spec_path(tmp_path, "b.pickle", params, plans),
                     pub)
    cand = watcher.poll_once()
    assert cand is not None and cand.ordinal == 2
    assert watcher._pending is None


def test_watcher_ttl_rejects_stuck_invalid(tmp_path):
    plans, params = _mlp_spec(seed=4)
    pub = str(tmp_path / "pub")
    chaos.install(chaos.FaultPlan(seed=1).add(
        "freshness.publish", "truncate", nth=1))
    try:
        publish_snapshot(_spec_path(tmp_path, "a.pickle", params,
                                    plans), pub)
    finally:
        chaos.uninstall()
    before = registry.counter(
        "serve.freshness.poisoned_rejected").value
    watcher = SnapshotWatcher(pub, poll_s=0.01, invalid_ttl_s=0.05,
                              max_backoff_s=0.02)
    deadline = time.monotonic() + 5.0
    while 1 not in watcher._rejected and time.monotonic() < deadline:
        watcher.poll_once()
        time.sleep(0.015)
    assert 1 in watcher._rejected
    assert registry.counter(
        "serve.freshness.poisoned_rejected").value == before + 1
    assert watcher.poll_once() is None  # rejected ordinal stays dead


def test_watcher_push_notify_wakes_poll(tmp_path):
    plans, params = _mlp_spec(seed=5)
    pub = str(tmp_path / "pub")
    seen = []
    watcher = SnapshotWatcher(pub, callback=seen.append, poll_s=30.0)
    watcher.start()
    try:
        time.sleep(0.05)  # the poll loop is now parked for 30s
        publish_snapshot(_spec_path(tmp_path, "a.pickle", params,
                                    plans), pub)
        watcher.notify()
        deadline = time.monotonic() + 5.0
        while not seen and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        watcher.stop()
    assert seen and seen[0].ordinal == 1


# -- canary mechanics --------------------------------------------------------


def _compiled_candidate(pool, params, plans=None):
    cand_plans = plans if plans is not None else pool.engine.plans
    rep = pool._live()[-1]
    engine = AOTEngine(cand_plans, params, pool.engine.sample_shape,
                       device=rep.device, ladder=pool.engine.ladder)
    engine.compile()
    return engine


def test_canary_replica_leaves_rotation_and_cascade(tmp_path):
    """Satellite fix: a canary replica is never a routing pick NOR a
    cascade target, and the fleet 503's retry_after comes from live
    replicas only."""
    pool = _pool(tmp_path, replicas=3)
    pool.start()
    try:
        candidate = _compiled_candidate(
            pool, _perturb(pool.engine.params))
        rep = pool.cutover.begin(candidate)
        assert rep is pool.replicas[-1]
        assert [r.index for r in pool._live()] == [0, 1]
        assert pool.digest == pool.replicas[0].engine.digest
        for _ in range(12):
            pool.infer(numpy.zeros(16, numpy.float32))
        assert rep.batcher._q.qsize() == 0  # no routed traffic landed
        # every live replica sheds -> the canary is NOT a cascade
        # target and the 503 is computed over the 2 live replicas
        chaos.install(chaos.FaultPlan(seed=1).add("serve.drop",
                                                  "drop"))
        try:
            with pytest.raises(ServeOverload) as info:
                pool.submit(numpy.zeros(16, numpy.float32))
        finally:
            chaos.uninstall()
        assert "2 live replicas" in str(info.value)
        pool.cutover.rollback(reason="test teardown")
        assert not rep.canary
    finally:
        pool.stop()


def test_mirror_bit_equality_and_shadow_excluded_counters(tmp_path):
    """Satellite regression: a mirrored request's primary response is
    bit-identical to the unmirrored run, and the served counters
    (serve.requests, serve.latency_s) exclude shadow traffic."""
    pool = _pool(tmp_path, replicas=3)
    pool.start()
    try:
        samples, baseline = _drive(pool, n=20, seed=6)
        candidate = _compiled_candidate(
            pool, _perturb(pool.engine.params))
        pool.cutover.begin(candidate)
        shadows = []
        pool.mirror_hook = lambda sample, req: shadows.append(
            pool.cutover.shadow(numpy.array(sample, copy=True)))
        req_before = registry.counter("serve.requests").value
        lat_before = registry.histogram("serve.latency_s").count
        mirrored = [numpy.array(pool.infer(x, timeout=15.0))
                    for x in samples]
        for primary, ref in zip(mirrored, baseline):
            assert (primary == ref).all()  # bit-identical under mirror
        shadows = [s for s in shadows if s is not None]
        assert len(shadows) == len(samples)  # fraction 1.0 here
        for s in shadows:
            assert s.done.wait(10.0)
            assert s.error is None and s.latency is not None
        # EXACTLY the primary requests count as served: the shadows
        # (same number again) appear in neither counter
        assert registry.counter("serve.requests").value \
            == req_before + len(samples)
        assert registry.histogram("serve.latency_s").count \
            == lat_before + len(samples)
        # shadow results really came from the CANDIDATE model
        ref_engine = pool.cutover.canary_replica.engine
        for x, s in zip(samples, shadows):
            assert (s.result == ref_engine.infer(x)[0]).all()
        pool.mirror_hook = None
        pool.cutover.rollback(reason="test teardown")
    finally:
        pool.stop()


def test_promote_rolls_fleet_and_reload_guard(tmp_path):
    pool = _pool(tmp_path, replicas=3)
    pool.start()
    try:
        new_params = _perturb(pool.engine.params, seed=8)
        candidate = _compiled_candidate(pool, new_params)
        pool.cutover.begin(candidate)
        with pytest.raises(RuntimeError):  # reload refused mid-canary
            pool.reload(new_params)
        receipt = pool.cutover.promote()
        assert receipt["verdict"] == "promoted"
        assert receipt["new_compiles"] == 0  # same digest: params swap
        want = value_digest(new_params)
        for rep in pool.replicas:
            assert value_digest(rep.engine.params) == want
            assert not rep.canary
        assert pool.cutover.state == "idle"
        # traffic still flows and reflects the new weights everywhere
        x = numpy.random.RandomState(9).rand(16).astype(numpy.float32)
        ref = pool.engine.infer(x)[0]
        for rep in pool.replicas:
            assert (rep.batcher.infer(x) == ref).all()
    finally:
        pool.stop()


def test_rollback_restores_last_good_with_zero_compiles(tmp_path):
    """The acceptance contract: rollback is swap-backs only — zero new
    backend compiles by construction — and restores the last-good
    weights bit-exactly, including a NEW-digest candidate (wider
    hidden layer) whose canary engine replaced the replica's."""
    pool = _pool(tmp_path, replicas=2)
    pool.start()
    try:
        before = value_digest(pool.engine.params)
        x = numpy.random.RandomState(10).rand(16).astype(numpy.float32)
        ref = pool.engine.infer(x)[0]
        plans3, params3 = _mlp_spec(seed=5, hidden=24)
        candidate = _compiled_candidate(pool, params3, plans=plans3)
        canary_rep = pool.cutover.begin(candidate)
        deadline = time.monotonic() + 5.0
        while canary_rep.batcher.engine is not candidate and \
                time.monotonic() < deadline:
            pool.infer(x)  # keep batches flowing so the swap applies
        assert canary_rep.batcher.engine is candidate
        receipt = pool.cutover.rollback(reason="bad canary")
        assert receipt["verdict"] == "rolled_back"
        assert receipt["new_compiles"] == 0, receipt
        assert receipt["restored_digest"] == pool.digest
        for rep in pool.replicas:
            assert value_digest(rep.engine.params) == before
        # the rolled-back replica actually SERVES the old model again
        deadline = time.monotonic() + 5.0
        while canary_rep.batcher.engine is candidate and \
                time.monotonic() < deadline:
            pool.infer(x)
        assert (canary_rep.batcher.infer(x) == ref).all()
    finally:
        pool.stop()


# -- comparator / spike watch ------------------------------------------------


def test_ema_spike_watch_matches_decision_discipline():
    watch = EmaSpikeWatch(spike_factor=3.0, spike_floor=0.1, beta=0.5)
    assert watch.update(1.0) is None          # first value: no EMA yet
    assert watch.ema == 1.0
    assert watch.update(1.2) is None
    assert watch.ema == pytest.approx(1.1)
    reason = watch.update(100.0)
    assert reason is not None and "spiked" in reason
    assert watch.ema == pytest.approx(1.1)    # spike NOT folded in
    watch.reset()
    assert watch.ema is None
    # the floor: a near-zero baseline doesn't turn noise into spikes
    floor = EmaSpikeWatch(spike_factor=3.0, spike_floor=1.0)
    floor.update(0.001)
    assert floor.update(0.5) is None          # < 3.0 * max(ema, 1.0)


def test_comparator_verdicts():
    good = numpy.full(4, 0.25)
    # clean pairs -> promote at min_mirrors
    comp = CanaryComparator(min_mirrors=3, breach_budget=2)
    assert comp.add(good, good + 1e-4, 0.01, 0.01) is None
    assert comp.add(good, good - 1e-4, 0.01, 0.01) is None
    assert comp.add(good, good, 0.01, 0.01) == "promote"
    # non-finite canary output -> instant rollback
    comp = CanaryComparator(min_mirrors=3)
    bad = numpy.array([0.5, numpy.nan, 0.2, 0.1])
    assert comp.add(good, bad, 0.01, 0.01) == "rolled_back"
    assert "non-finite" in comp.reason()
    # divergence bound -> breaches -> rollback
    comp = CanaryComparator(min_mirrors=8, divergence_limit=0.5,
                            breach_budget=2)
    onehot = numpy.array([1.0, 0.0, 0.0, 0.0])
    assert comp.add(good, onehot, 0.01, 0.01) is None
    assert comp.add(good, onehot, 0.01, 0.01) == "rolled_back"
    assert "divergence" in comp.reason()
    # latency: live latencies prime the EMA, a slow canary spikes it
    comp = CanaryComparator(min_mirrors=8, latency_spike_factor=3.0,
                            latency_floor_s=0.01, breach_budget=2)
    for _ in range(4):
        assert comp.add(good, good, 0.01, 0.012) is None
    assert comp.add(good, good, 0.01, 5.0) is None   # breach 1
    assert comp.add(good, good, 0.01, 5.0) == "rolled_back"
    assert "latency" in comp.reason()


# -- controller end-to-end ---------------------------------------------------


def test_controller_cycle_promote_then_poison_then_rollback(tmp_path):
    """The loop end-to-end, one thread of truth: a good publish is
    canaried under mirrored closed-loop traffic and PROMOTED; a
    NaN-params publish dies at the finite gate; a finite-but-garbage
    publish (invisible to the gate) is canaried and auto-ROLLED BACK
    with zero new compiles; the fleet serves the promoted weights
    bit-exactly throughout, with zero failed requests."""
    pool = _pool(tmp_path, replicas=3)
    pool.start()
    controller = _controller(pool, tmp_path, invalid_ttl_s=1.0)
    controller.start()
    errors = []
    stop = threading.Event()

    def client(k):
        rng = numpy.random.RandomState(40 + k)
        x = rng.rand(16).astype(numpy.float32)
        while not stop.is_set():
            try:
                pool.infer(x, timeout=15.0)
            except Exception as exc:
                errors.append(exc)
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(3)]
    for t in threads:
        t.start()
    plans = pool.engine.plans
    pub = tmp_path  # publish dir is tmp_path/"publish" via _controller
    try:
        def publish(name, params):
            return publish_snapshot(
                _spec_path(pub, name, params, plans),
                str(tmp_path / "publish"))

        def wait_cycle(ordinal, timeout=30.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                for entry in controller.history:
                    if entry["ordinal"] == ordinal:
                        return entry
                time.sleep(0.02)
            raise TimeoutError("no verdict for #%d" % ordinal)

        good = _perturb(pool.engine.params, seed=21)
        entry = wait_cycle(publish("good.pickle", good)["ordinal"])
        assert entry["verdict"] == "promoted", entry
        assert entry["mirrors"] >= 4
        want = value_digest(good)
        for rep in pool.replicas:
            assert value_digest(rep.engine.params) == want

        nan_params = [{k: numpy.full_like(v, numpy.nan)
                       for k, v in e.items()} for e in good]
        entry = wait_cycle(publish("nan.pickle", nan_params)["ordinal"])
        assert entry["verdict"] == "poisoned"
        for rep in pool.replicas:  # never warmed, never served
            assert value_digest(rep.engine.params) == want

        # finite-but-wrong: the output classes permuted — a model that
        # confidently answers the WRONG question, invisible to every
        # static gate, exactly what the mirrored canary exists for
        garbage = [dict(e) for e in good]
        garbage[-1] = {
            "weights": numpy.roll(good[-1]["weights"], 1, axis=1),
            "bias": numpy.roll(good[-1]["bias"], 1)}
        entry = wait_cycle(publish("bad.pickle", garbage)["ordinal"])
        assert entry["verdict"] == "rolled_back", entry
        assert entry["new_compiles"] == 0, entry
        for rep in pool.replicas:
            assert value_digest(rep.engine.params) == want
        assert pool.cutover.state == "idle"
        snap = controller.snapshot()
        assert snap["promotions"] >= 1 and snap["rollbacks"] >= 1
        assert snap["poisoned_rejected"] >= 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        controller.stop()
        pool.stop()
    assert not errors, errors[:3]


def test_single_replica_falls_back_to_direct_reload(tmp_path):
    pool = _pool(tmp_path, replicas=1)
    pool.start()
    controller = _controller(pool, tmp_path)
    try:
        good = _perturb(pool.engine.params, seed=31)
        publish_snapshot(
            _spec_path(tmp_path, "solo.pickle", good,
                       pool.engine.plans),
            str(tmp_path / "publish"))
        cand = controller.watcher.poll_once()  # runs the cycle inline
        assert cand is not None
        assert controller.history[-1]["verdict"] == "reloaded"
        assert value_digest(pool.engine.params) == value_digest(good)
    finally:
        controller.stop()
        pool.stop()


def test_service_publish_endpoint_and_healthz(tmp_path):
    import urllib.request

    from veles_tpu.serve import ServeService
    pool = _pool(tmp_path, replicas=2)
    controller = _controller(pool, tmp_path, poll_s=30.0)
    controller.start()
    svc = ServeService(pool, freshness=controller)
    svc.start_background()
    try:
        base = "http://127.0.0.1:%d" % svc.port
        good = _perturb(pool.engine.params, seed=41)
        receipt = publish_snapshot(
            _spec_path(tmp_path, "push.pickle", good,
                       pool.engine.plans),
            str(tmp_path / "publish"))
        req = urllib.request.Request(
            base + "/publish",
            data=json.dumps({"snapshot": receipt["snapshot"]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            answer = json.loads(resp.read())
        assert answer["status"] == "notified"
        deadline = time.monotonic() + 20.0
        while not controller.history and time.monotonic() < deadline:
            time.sleep(0.05)  # the push, not the 30s poll, woke it
        assert controller.history, "push never woke the watcher"
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health["freshness"]["last_ordinal"] == 1
        assert health["freshness"]["cycles"] >= 1
    finally:
        svc.stop()
        controller.stop()
        pool.stop()


def test_watcher_retries_when_cycle_fails_transiently(tmp_path):
    """A transient controller failure (e.g. the candidate warm-up ran
    out of memory) must not consume the ordinal — the publish is
    retried with backoff — and, because the publish itself VERIFIED,
    it is never TTL-branded poisoned no matter how long the failures
    last: a healthy model must not be rejected because the serve side
    had a bad minute."""
    plans, params = _mlp_spec(seed=6)
    pub = str(tmp_path / "pub")
    publish_snapshot(_spec_path(tmp_path, "a.pickle", params, plans),
                     pub)
    poisoned = registry.counter("serve.freshness.poisoned_rejected")
    before = poisoned.value
    calls = []

    def flaky(cand):
        calls.append(cand.ordinal)
        if len(calls) <= 2:
            raise RuntimeError("transient warm-up failure")

    watcher = SnapshotWatcher(pub, callback=flaky, poll_s=0.01,
                              invalid_ttl_s=0.02, max_backoff_s=0.02)
    assert watcher.poll_once() is None  # failed cycle: NOT consumed
    assert watcher.last_ordinal == 0
    time.sleep(0.05)  # past the TTL: must NOT escalate to poisoned
    assert watcher.poll_once() is None
    assert 1 not in watcher._rejected
    assert poisoned.value == before
    time.sleep(0.05)
    cand = watcher.poll_once()  # failure cleared: third try lands
    assert cand is not None and cand.ordinal == 1
    assert calls == [1, 1, 1]


def test_idle_fleet_self_probes_to_a_verdict(tmp_path):
    """Zero client traffic: the controller self-probes (shadow pairs
    on BOTH sides — never counted as served) and still reaches a real
    verdict — a good candidate promotes, a class-permuted one rolls
    back — instead of timing out into a verdict nobody earned."""
    pool = _pool(tmp_path, replicas=2)
    pool.start()
    controller = _controller(pool, tmp_path, probe_idle_s=0.02)
    plans = pool.engine.plans
    try:
        req_before = registry.counter("serve.requests").value
        good = _perturb(pool.engine.params, seed=51)
        publish_snapshot(_spec_path(tmp_path, "g.pickle", good, plans),
                         str(tmp_path / "publish"))
        assert controller.watcher.poll_once() is not None
        entry = controller.history[-1]
        assert entry["verdict"] == "promoted", entry
        assert entry["mirrors"] >= 4  # real probe evidence, not a bye
        bad = [dict(e) for e in good]
        bad[-1] = {
            "weights": numpy.roll(good[-1]["weights"], 1, axis=1),
            "bias": numpy.roll(good[-1]["bias"], 1)}
        publish_snapshot(_spec_path(tmp_path, "b.pickle", bad, plans),
                         str(tmp_path / "publish"))
        assert controller.watcher.poll_once() is not None
        entry = controller.history[-1]
        assert entry["verdict"] == "rolled_back", entry
        assert entry["new_compiles"] == 0
        assert value_digest(pool.engine.params) == value_digest(good)
        # probes are shadows end to end: nothing was "served"
        assert registry.counter("serve.requests").value == req_before
    finally:
        controller.stop()
        pool.stop()


# -- the soak receipt --------------------------------------------------------


@pytest.mark.chaos
def test_freshness_soak_smoke(tmp_path):
    """Tier-1 smoke of the FRESH.json receipt: the fast profile —
    publish->canary->promote cycles under trainer crash + torn publish
    + replica stalls, a NaN and a garbage snapshot both contained,
    zero dropped requests, rollback with zero new compiles."""
    import scripts.freshness_soak as soak
    out = str(tmp_path / "FRESH.json")
    receipt = soak.run_soak(good_cycles=2, replicas=3, clients=3,
                            fast=True, out=out)
    assert receipt["passed"], receipt["checks"]
    assert receipt["checks"]["promote_cycles"] >= 2
    assert receipt["checks"]["zero_dropped_requests"]
    assert receipt["checks"]["poison_never_promoted"]
    assert receipt["checks"]["rollback_zero_new_compiles"]
    assert receipt["chaos"]["trainer_crashes"] >= 1
    assert receipt["chaos"]["torn_publishes_rejected"] >= 1
    with open(out) as fin:
        assert json.load(fin)["passed"]


@pytest.mark.chaos
@pytest.mark.slow
def test_freshness_soak_full(tmp_path):
    """The committed-receipt profile: >= 5 promote cycles plus both
    poison shapes under the full chaos plan."""
    import scripts.freshness_soak as soak
    receipt = soak.run_soak(good_cycles=6, replicas=3, clients=4,
                            fast=False,
                            out=str(tmp_path / "FRESH.json"))
    assert receipt["passed"], receipt["checks"]
    assert receipt["checks"]["promote_cycles"] >= 5
