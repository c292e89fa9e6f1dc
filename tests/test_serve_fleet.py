"""Multi-host serve tier (veles_tpu/serve/fleet.py, docs/serving.md
"Multi-host tier"): membership epochs over the pipelined binary link,
throughput-weighted least-loaded routing, request hedging with
first-result-wins bit-identity, the exactly-once duplicate-rejection
fence (chaos ``serve.hedge.lose_race``), host-kill requeue with zero
dropped requests, host-granular cascade-then-503 with the
fleet-minimum ``retry_after``, and the rejoin-re-warm 0-new-compiles
receipt.  Hosts are in-process socketpair adoptions (the ``transport``
marker pattern — tier-1 never binds a real port); the multi-process
SIGKILL soak lives in scripts/fleet_soak.py → HEDGE.json (slow)."""

import socket
import threading
import time

import numpy
import pytest

from veles_tpu import chaos
from veles_tpu.backends import Device
from veles_tpu.observe.metrics import registry
from veles_tpu.serve import (
    AOTEngine, BinaryTransportServer, ContinuousBatcher, FleetRouter,
    ServeOverload, serve_snapshot)
from veles_tpu.serve.batcher import ServeOverload as _Overload
from tests.test_serve import _mlp_spec

pytestmark = [pytest.mark.serve, pytest.mark.fleet]


class _Hosts(object):
    """N in-process serve hosts (engine + batcher + transport server)
    sharing ONE spec, plus socketpair plumbing into a router."""

    def __init__(self, n, plans, params):
        self.entries = []
        for i in range(n):
            engine = AOTEngine(plans, params, (16,), ladder=(8, 32),
                               device=Device(backend="cpu"))
            engine.compile()
            batcher = ContinuousBatcher(engine,
                                        max_delay_s=0.002).start()
            server = BinaryTransportServer(
                batcher, port=None, host_meta={"host_id": "h%d" % i})
            server.start_background()
            self.entries.append([engine, batcher, server])

    def connect(self, router, i):
        ours, theirs = socket.socketpair()
        self.entries[i][2].serve_socket(ours)
        return router.add_host(sock=theirs)

    def stop(self, i=None):
        which = self.entries if i is None else [self.entries[i]]
        for engine, batcher, server in which:
            server.stop()
            batcher.stop()


@pytest.fixture
def fleet():
    """Two-host fleet behind a hedging router, plus the sequential
    reference engine for bit-identity checks."""
    plans, params = _mlp_spec(seed=3)
    hosts = _Hosts(2, plans, params)
    router = FleetRouter(hedge_factor=1.5, hedge_floor_s=0.05,
                         hedge_tick_s=0.01).start()
    for i in range(2):
        hosts.connect(router, i)
    yield hosts, router, hosts.entries[0][0]
    router.stop()
    hosts.stop()


def _counter(name):
    metric = registry.counter(name)
    return metric.value


def _wait_for(predicate, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError("timed out waiting for " + what)


def test_fleet_routes_bit_identical_with_membership_epochs(fleet):
    """Routed singles and blocks come back bit-identical to the
    sequential engine wherever they land; joins bumped the membership
    epoch once each; the serve_snapshot/web-status block carries the
    fleet keys."""
    hosts, router, engine = fleet
    rng = numpy.random.RandomState(1)
    x = rng.rand(6, 16).astype(numpy.float32)
    ref = engine.infer(x)
    for row, want in zip(x, ref):
        out = router.infer(row, timeout=15.0)
        assert (out == want).all()
    out = router.infer_block(numpy.ascontiguousarray(x), timeout=15.0)
    assert (out == ref).all()
    assert router.fleet.membership_epoch == 2
    snap = router.snapshot()
    assert snap["hosts_live"] == 2
    assert snap["digest"] == engine.digest
    block = serve_snapshot()
    assert block["hosts_live"] == 2
    assert block["fleet_membership_epoch"] == 2
    # routing observed real throughput for at least one host
    assert any(h["throughput_ema"] != 1.0
               for h in snap["hosts"].values())


@pytest.mark.chaos
def test_hedged_first_result_wins_bit_identity(fleet):
    """An induced ``serve.host.stall`` straggler: the hedge fires past
    the threshold, the sibling's result answers the client well under
    the stall, bit-identical to the sequential reference — and the
    loser's cancel means no duplicate ever surfaces."""
    hosts, router, engine = fleet
    rng = numpy.random.RandomState(2)
    x = rng.rand(3, 16).astype(numpy.float32)
    ref = engine.infer(x)
    # seed the hedge_warmup window: a cold router deliberately never
    # hedges (no latency evidence = no threshold worth trusting)
    for i in range(router.hedge_warmup):
        router.infer(x[i % 2], timeout=15.0)
    fired = _counter("serve.hedge.fired")
    wins = _counter("serve.hedge.wins")
    chaos.install(chaos.FaultPlan(seed=1).add(
        "serve.host.stall", "stall", nth=1, param=2.0))
    try:
        t0 = time.perf_counter()
        out = router.infer(x[2], timeout=15.0)
        elapsed = time.perf_counter() - t0
    finally:
        chaos.uninstall()
    assert (out == ref[2]).all()
    assert elapsed < 1.5, \
        "hedge must beat the 2 s straggler (took %.2fs)" % elapsed
    assert _counter("serve.hedge.fired") == fired + 1
    assert _counter("serve.hedge.wins") == wins + 1


@pytest.mark.chaos
def test_lose_race_duplicate_result_rejected(fleet):
    """Chaos ``serve.hedge.lose_race`` skips the loser's wire cancel:
    the losing copy completes, its late result hits the exactly-once
    fence — rejected as a duplicate, the client's answer unchanged."""
    hosts, router, engine = fleet
    rng = numpy.random.RandomState(4)
    x = rng.rand(16).astype(numpy.float32)
    ref = engine.infer(x)
    for _ in range(router.hedge_warmup):  # arm the hedge watchdog
        router.infer(x, timeout=15.0)
    dups = _counter("serve.hedge.duplicates_dropped")
    chaos.install(chaos.FaultPlan(seed=1)
                  .add("serve.host.stall", "stall", nth=1, param=0.4)
                  .add("serve.hedge.lose_race", "skip"))
    try:
        out = router.infer(x, timeout=15.0)
        assert (out == ref[0]).all()
        # the stalled loser finishes ~0.4s later; its result must be
        # dropped at the fence, never re-answer the request
        _wait_for(lambda: _counter("serve.hedge.duplicates_dropped")
                  > dups, what="duplicate rejection")
    finally:
        chaos.uninstall()


@pytest.mark.chaos
def test_host_kill_requeues_in_flight_zero_drops():
    """A host severed mid-stream with requests wedged on it: membership
    epoch bumps, every in-flight request on the dead link is requeued
    to the survivor, and EVERY request completes bit-identical — zero
    failed requests, the tentpole's headline contract."""
    plans, params = _mlp_spec(seed=3)
    hosts = _Hosts(2, plans, params)
    router = FleetRouter(hedge=False).start()  # isolate the requeue
    try:
        for i in range(2):
            hosts.connect(router, i)
        rng = numpy.random.RandomState(5)
        x = rng.rand(6, 16).astype(numpy.float32)
        ref = hosts.entries[0][0].infer(x)
        requeues = _counter("serve.fleet.requeues")
        epoch_before = router.fleet.membership_epoch
        # wedge EVERY initial dispatch host-side so the kill lands
        # while the requests are provably in flight
        chaos.install(chaos.FaultPlan(seed=2).add(
            "serve.host.stall", "stall", times=6, param=0.5))
        try:
            reqs = [router.submit(row) for row in x]
            # both hosts hold wedged work; sever host 0 abruptly
            hosts.stop(0)
            for req in reqs:
                assert req.done.wait(20), "request dropped on the floor"
                assert req.error is None, req.error
        finally:
            chaos.uninstall()
        for req, want in zip(reqs, ref):
            assert (req.result == want).all()
        assert router.fleet.membership_epoch == epoch_before + 1
        assert _counter("serve.fleet.requeues") > requeues
        assert router.snapshot()["hosts_live"] == 1
    finally:
        router.stop()
        hosts.stop(1)


def test_cascade_then_503_with_fleet_minimum_retry_after(fleet):
    """Every live host shedding: the fleet sheds ONCE with the
    smallest retry_after any host offered (its best promise), after
    cascading through both."""
    hosts, router, engine = fleet

    def shedding(retry_after):
        def _admit(slo_class=None):
            raise _Overload("test shed", retry_after=retry_after)
        return _admit

    saved = [entry[1]._admit for entry in hosts.entries]
    hosts.entries[0][1]._admit = shedding(0.7)
    hosts.entries[1][1]._admit = shedding(0.3)
    try:
        req = router.submit(numpy.zeros(16, numpy.float32))
        assert req.done.wait(10)
        assert isinstance(req.error, ServeOverload)
        assert req.error.retry_after == pytest.approx(0.3)
    finally:
        for entry, admit in zip(hosts.entries, saved):
            entry[1]._admit = admit
    # the fleet recovered: the same request now serves
    out = router.infer(numpy.zeros(16, numpy.float32), timeout=15.0)
    assert out.shape == (4,)


def test_rejoin_rewarm_zero_new_compiles_receipt():
    """A host restarting against the shared persistent cache re-warms
    with new_compiles == 0, and its rejoin hello carries that receipt
    to the router before it re-enters rotation."""
    plans, params = _mlp_spec(seed=6)
    hosts = _Hosts(2, plans, params)
    router = FleetRouter(hedge=False).start()
    try:
        h0 = hosts.connect(router, 0)
        hosts.connect(router, 1)
        out = router.infer(numpy.zeros(16, numpy.float32),
                           timeout=15.0)
        assert out.shape == (4,)
        # "restart" host 0: same spec, same cache directory
        hosts.stop(0)
        _wait_for(lambda: router.snapshot()["hosts_live"] == 1,
                  what="host loss")
        engine = AOTEngine(plans, params, (16,), ladder=(8, 32),
                           device=Device(backend="cpu"))
        receipt = engine.compile()
        assert receipt["new_compiles"] == 0, \
            "the restart must deserialize its ladder from the cache"
        batcher = ContinuousBatcher(engine, max_delay_s=0.002).start()
        server = BinaryTransportServer(
            batcher, port=None, host_meta={"host_id": "h0"})
        server.start_background()
        hosts.entries[0] = [engine, batcher, server]
        epoch = router.fleet.membership_epoch
        rejoined = hosts.connect(router, 0)
        assert rejoined == h0
        snap = router.snapshot()
        assert snap["hosts"][rejoined]["new_compiles"] == 0, \
            "the rejoin hello must carry the re-warm receipt"
        assert router.fleet.membership_epoch == epoch + 1
        assert snap["hosts_live"] == 2
        out = router.infer(numpy.zeros(16, numpy.float32),
                           timeout=15.0)
        assert out.shape == (4,)
    finally:
        router.stop()
        hosts.stop()


def test_idle_link_keepalive_does_not_retire_healthy_hosts():
    """An idle fleet must not lose its hosts: the reader's socket
    timeout at a frame BOUNDARY is a keepalive ping, not a death —
    several silent keepalive intervals later the membership is
    untouched and the fleet still serves (regression: the first cut
    retired every host after one idle link_timeout)."""
    plans, params = _mlp_spec(seed=3)
    hosts = _Hosts(2, plans, params)
    router = FleetRouter(hedge=False, keepalive_s=0.2).start()
    try:
        for i in range(2):
            hosts.connect(router, i)
        x = numpy.zeros(16, numpy.float32)
        router.infer(x, timeout=15.0)
        epoch = router.fleet.membership_epoch
        time.sleep(1.0)  # ~5 keepalive intervals of silence
        assert router.snapshot()["hosts_live"] == 2
        assert router.fleet.membership_epoch == epoch
        assert router.infer(x, timeout=15.0).shape == (4,)
    finally:
        router.stop()
        hosts.stop()


def test_digest_mismatch_refused(fleet):
    """One fleet serves ONE digest: routed and hedged copies must be
    bit-identical wherever they land, so a host with a different
    architecture is refused at the handshake."""
    hosts, router, engine = fleet
    plans, params = _mlp_spec(seed=9, hidden=8)  # different shapes
    other = _Hosts(1, plans, params)
    try:
        with pytest.raises(ValueError, match="mixed fleet"):
            other.connect(router, 0)
        assert router.snapshot()["hosts_live"] == 2
    finally:
        other.stop()


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_soak_sigkill_receipt(tmp_path):
    """Acceptance (ISSUE 15): scripts/fleet_soak.py SIGKILLs a real
    serve-host subprocess mid-stream — zero failed requests, bounded
    p99, membership epochs bumped, every re-answered request
    bit-identical — and the hedging A/B under an induced straggler
    cuts p99.  The committed HEDGE.json is this driver at full size."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "HEDGE.json"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "scripts", "fleet_soak.py"),
         "--out", str(out), "--fast"],
        cwd=repo, timeout=900, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    receipt = json.loads(out.read_text())
    assert receipt["passed"] is True
    assert receipt["kill"]["failed_requests"] == 0
    assert receipt["kill"]["bit_identical"] is True
    assert receipt["hedge_ab"]["p99_cut_pct"] > 0
