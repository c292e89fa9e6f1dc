"""Multi-host serve-tier chaos soak -> HEDGE.json receipt.

The acceptance proof of the fleet tier (docs/serving.md "Multi-host
tier", ISSUE 15): a front-tier :class:`FleetRouter` dispatching over
REAL serve-host subprocesses, with the two headline failure semantics
measured rather than assumed:

- **kill**: a seeded driver-side ``serve.host.preempt`` schedule
  SIGKILLs a serve host mid-stream while closed-loop clients hammer
  the fleet.  Every in-flight request on the dead link must be
  re-answered by survivors — **zero failed requests**, every answer
  bit-identical to the sequential single-engine reference — at
  bounded p99; the host then respawns against the persistent
  compile cache and rejoins with a **0-new-compiles**
  re-warm receipt before re-entering rotation (membership epochs
  bumped for the leave AND the rejoin).
- **hedge_ab**: EVERY host armed with seeded random stalls
  (``serve.host.stall`` on a fraction of each host's frames — the
  tail-at-scale shape: any request may straggle, so the
  throughput-EMA routing cannot simply learn to avoid one sick host;
  a PERSISTENT straggler is the routing weights' job, and the EMA
  penalty on cancelled hedge losers makes sure hedging never masks
  one).  Closed-loop p50/p95/p99 measured with hedging OFF then ON:
  hedging must measurably cut p99 — a stalled request is
  re-dispatched to a sibling past the throughput-corrected
  threshold, first result wins, losers rejected at the exactly-once
  fence.

Usage::

    python scripts/fleet_soak.py --out HEDGE.json          # full
    python scripts/fleet_soak.py --fast --out /tmp/H.json  # smoke
    python scripts/fleet_soak.py --tenants --out QOS.json  # QoS soak
    python scripts/fleet_soak.py --alerts --out ALERTS.json  # alerts

``--tenants`` reuses the same subprocess-host harness for the
multi-tenant QoS receipt (:func:`run_tenant_soak` -> QOS.json; see
scripts/qos_soak.py for the dedicated entry): a best-effort flood
plus seeded stalls against interactive SLO clients, then the fleet
canary promote/poison-rollback cycle.  The fast profile is the
slow-marked test in tests/test_serve_fleet.py (tests/test_qos.py for
``--tenants``); the full profile is the committed HEDGE.json /
QOS.json receipt.  (``--host`` is the internal serve-host subprocess
entry the driver spawns.)
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy  # noqa: E402

SAMPLE_SHAPE = (16,)
LADDER = (8, 32)  # starts at 8: rung-1 is the ~1-ulp odd one out


def _mlp_spec(seed):
    from veles_tpu.compiler import LayerPlan
    from veles_tpu.models.all2all import All2AllSoftmax, All2AllTanh
    rng = numpy.random.RandomState(seed)
    plans = [LayerPlan(All2AllTanh), LayerPlan(All2AllSoftmax)]
    params = [
        {"weights": rng.rand(16, 24).astype(numpy.float32),
         "bias": rng.rand(24).astype(numpy.float32)},
        {"weights": rng.rand(24, 4).astype(numpy.float32),
         "bias": rng.rand(4).astype(numpy.float32)},
    ]
    return plans, params


def _build_engine(seed):
    """The soak is a CPU program: a chip belongs to ONE process, and
    this script runs several serve hosts per machine — so every
    engine sits on the cpu backend and every host subprocess is
    started with JAX_PLATFORMS=cpu (``_HostProc``).  All of them share
    the ONE compile cache (``backends.enable_compile_cache``: the
    environment's directory, else the checkout's), so a respawned
    host re-warms with zero new compiles."""
    from veles_tpu.backends import Device
    from veles_tpu.serve import AOTEngine
    plans, params = _mlp_spec(seed)
    engine = AOTEngine(plans, params, SAMPLE_SHAPE, ladder=LADDER,
                       device=Device(backend="cpu"))
    return engine, engine.compile()


def host_main(args):
    """The serve-host subprocess: one engine + batcher behind the
    binary transport, identity + re-warm receipt on the READY line.
    VELES_CHAOS in the environment arms per-host faults (the
    straggler's ``serve.host.stall``); the driver's SIGKILL is the
    preemption."""
    from veles_tpu.serve import BinaryTransportServer, ContinuousBatcher
    engine, receipt = _build_engine(args.seed)
    batcher = ContinuousBatcher(engine, max_delay_s=0.001,
                                max_queue=4096).start()
    server = BinaryTransportServer(
        batcher, port=0, host_meta={"host_id": args.host_id})
    server.start_background()
    print("FLEET_HOST_READY port=%d host_id=%s new_compiles=%d"
          % (server.port, args.host_id, receipt["new_compiles"]),
          flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        batcher.stop()
    return 0


class _HostProc(object):
    """Driver-side handle on one serve-host subprocess."""

    def __init__(self, host_id, seed, chaos_spec=None):
        # pinned to the CPU: several hosts share this machine, and a
        # chip can only ever belong to one process
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("VELES_CHAOS", None)
        if chaos_spec:
            env["VELES_CHAOS"] = chaos_spec
        self.host_id = host_id
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--host",
             "--host-id", host_id, "--seed", str(seed)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        deadline = time.monotonic() + 120.0
        self.port = None
        self.new_compiles = None
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                break
            if line.startswith("FLEET_HOST_READY"):
                fields = dict(kv.split("=") for kv in line.split()[1:])
                self.port = int(fields["port"])
                self.new_compiles = int(fields["new_compiles"])
                break
        if self.port is None:
            raise RuntimeError("host %s never came up" % host_id)

    def sigkill(self):
        os.kill(self.proc.pid, signal.SIGKILL)
        self.proc.wait(timeout=30)

    def stop(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


def _closed_loop(router, reference, clients, duration_s, on_ok=None):
    """Closed-loop client pool: every answer verified bit-identical to
    the sequential reference row.  Returns (latencies, failures,
    mismatches, ok_count)."""
    samples = reference["samples"]
    ref = reference["ref"]
    stop_at = time.perf_counter() + duration_s
    latencies, failures, mismatches = [], [], []
    lock = threading.Lock()

    def client(k):
        mine, bad, fail = [], 0, []
        n = 0
        while time.perf_counter() < stop_at:
            idx = (k * 131 + n) % len(samples)
            n += 1
            t0 = time.perf_counter()
            try:
                out = router.infer(samples[idx], timeout=30.0)
            except Exception as exc:  # EVERY failure is a drop
                fail.append("%s: %s" % (type(exc).__name__, exc))
                continue
            dt = time.perf_counter() - t0
            mine.append(dt)
            if not (out == ref[idx]).all():
                bad += 1
            if on_ok is not None:
                on_ok()
        with lock:
            latencies.extend(mine)
            failures.extend(fail)
            if bad:
                mismatches.append(bad)

    threads = [threading.Thread(target=client, args=(k,),
                                name="soak-client-%d" % k)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return latencies, failures, mismatches


def _pcts(latencies):
    from veles_tpu.observe.metrics import percentiles
    return {p: round(v * 1e3, 3)
            for p, v in percentiles(latencies).items()}


def _counters(names):
    from veles_tpu.observe.metrics import registry
    return {name: registry.counter(name).value for name in names}


_COUNTERS = ("serve.fleet.requests", "serve.fleet.failed",
             "serve.fleet.requeues", "serve.fleet.cascades",
             "serve.hedge.fired", "serve.hedge.wins",
             "serve.hedge.duplicates_dropped")


def run_soak(seed=11, fast=False, out=None, p99_bound_s=2.0):
    from veles_tpu import chaos
    from veles_tpu.serve import FleetRouter

    engine, _ = _build_engine(seed)
    rng = numpy.random.RandomState(seed + 1)
    samples = rng.rand(64, *SAMPLE_SHAPE).astype(numpy.float32)
    reference = {"samples": samples,
                 "ref": engine.infer(samples)}

    # ---- phase A: SIGKILL a host mid-stream -----------------------------
    duration = 6.0 if fast else 20.0
    clients = 4 if fast else 6
    hosts = [_HostProc("h%d" % i, seed) for i in range(3)]
    router = FleetRouter(hedge_factor=2.0, hedge_floor_s=0.05,
                         hedge_tick_s=0.01).start()
    for h in hosts:
        router.add_host(address=("127.0.0.1", h.port),
                        host_id=h.host_id)
    before = _counters(_COUNTERS)
    epoch_before = router.fleet.membership_epoch

    # the kill/rejoin schedule is a SEEDED FaultPlan the driver fires
    # once per completed request — deterministic in request count, like
    # elastic_soak's driver-side slave.rejoin_after
    kill_after = 40 if fast else 150
    plan = (chaos.FaultPlan(seed=seed)
            .add("serve.host.preempt", "kill", nth=kill_after)
            .add("slave.rejoin_after", "", nth=1, param=1.0))
    kill_state = {"killed_at": None, "rejoined": None,
                  "rejoin_compiles": None, "thread": None}
    lock = threading.Lock()

    def on_ok():
        with lock:
            fault = plan.fire("serve.host.preempt")
        if fault is not None:
            # MID-STREAM means mid-stream: pull the trigger only once
            # the victim observably holds in-flight work (closed-loop
            # clients re-arm it within a millisecond), so the kill
            # provably orphans requests for the requeue path to save
            for _ in range(2000):
                if router.snapshot()["hosts"].get(
                        "h0", {}).get("inflight"):
                    break
                time.sleep(0.001)
            kill_state["killed_at"] = time.perf_counter()
            hosts[0].sigkill()

            def rejoin():
                delay = plan.fire("slave.rejoin_after")
                time.sleep(delay.param if delay is not None else 1.0)
                hosts[0] = respawned = _HostProc("h0", seed)
                router.add_host(address=("127.0.0.1", respawned.port),
                                host_id="h0-rejoin")
                kill_state["rejoined"] = time.perf_counter()
                kill_state["rejoin_compiles"] = respawned.new_compiles
            kill_state["thread"] = threading.Thread(target=rejoin,
                                                   name="rejoin")
            kill_state["thread"].start()

    latencies, failures, mismatches = _closed_loop(
        router, reference, clients, duration, on_ok=on_ok)
    if kill_state["thread"] is not None:
        # the respawn (subprocess + warm compile) may outlast a short
        # closed loop: the rejoin must land BEFORE the membership /
        # re-warm receipts are read (and before the router stops)
        kill_state["thread"].join(timeout=180)
    kill_counters = {
        name: value - before[name]
        for name, value in _counters(_COUNTERS).items()}
    kill_snap = router.snapshot()
    epochs_bumped = router.fleet.membership_epoch - epoch_before
    router.stop()
    for h in hosts:
        h.stop()
    p99_s = (sorted(latencies)[
        max(0, int(len(latencies) * 0.99) - 1)] if latencies else None)
    kill = {
        "clients": clients,
        "duration_s": duration,
        "requests_ok": len(latencies),
        "failed_requests": len(failures),
        "failed_detail": failures[:5],
        "bit_identical": not mismatches,
        "host_killed": kill_state["killed_at"] is not None,
        "rejoined": kill_state["rejoined"] is not None,
        "rejoin_new_compiles": kill_state["rejoin_compiles"],
        "membership_epochs_bumped": epochs_bumped,
        "latency_ms": _pcts(latencies),
        "p99_bound_s": p99_bound_s,
        "p99_within_bound": (p99_s is not None and
                             p99_s <= p99_bound_s),
        "counters": kill_counters,
        "fleet": kill_snap,
    }

    # ---- phase B: hedging A/B under induced stragglers ------------------
    # random stalls on EVERY host (independent seeded streams): the
    # tail-at-scale shape routing cannot dodge — hedging is the only
    # tail cure, which is exactly what the A/B must isolate
    leg_s = 4.0 if fast else 10.0
    # stall 5% of each host's frames 150 ms: single-stall probability
    # (~5%) dominates p99 in the OFF leg, while double-stall — the
    # case hedging cannot rescue, original AND hedge both stalled —
    # stays well under the 1% percentile boundary (~0.25%), so the ON
    # leg's p99 is the hedge path, not the stall
    stall = "seed=%d;serve.host.stall=stall:p0.05:0.15"
    legs = {}
    hedge_counts = {}
    for name, hedge_on in (("off", False), ("on", True)):
        # fresh hosts per leg: each chaos stream restarts at its seed,
        # so both legs face the same per-host stall patterns
        stallers = [
            _HostProc("s%d" % i, seed,
                      chaos_spec=stall % (seed + 100 * (i + 1)))
            for i in range(2)]
        router = FleetRouter(hedge=hedge_on, hedge_factor=2.0,
                             hedge_floor_s=0.03,
                             hedge_tick_s=0.005).start()
        for i, h in enumerate(stallers):
            router.add_host(address=("127.0.0.1", h.port),
                            host_id="s%d" % i)
        before = _counters(_COUNTERS)
        latencies, failures, mismatches = _closed_loop(
            router, reference, 4, leg_s)
        hedge_counts[name] = {
            k: v - before[k] for k, v in _counters(_COUNTERS).items()}
        router.stop()
        for h in stallers:
            h.stop()
        legs[name] = {
            "requests_ok": len(latencies),
            "failed_requests": len(failures),
            "bit_identical": not mismatches,
            "latency_ms": _pcts(latencies),
        }
    p99_off = legs["off"]["latency_ms"].get("p99")
    p99_on = legs["on"]["latency_ms"].get("p99")
    cut = (round(100.0 * (p99_off - p99_on) / p99_off, 2)
           if p99_off else None)
    hedge_ab = {
        "straggler_chaos": stall % seed +
            " (per host, independent seed offsets)",
        "off": legs["off"],
        "on": legs["on"],
        "hedges_fired": hedge_counts["on"]["serve.hedge.fired"],
        "hedge_wins": hedge_counts["on"]["serve.hedge.wins"],
        "duplicates_dropped":
            hedge_counts["on"]["serve.hedge.duplicates_dropped"],
        "p99_cut_pct": cut,
    }

    checks = {
        "zero_failed_requests": kill["failed_requests"] == 0 and
        legs["off"]["failed_requests"] == 0 and
        legs["on"]["failed_requests"] == 0,
        "bit_identical": kill["bit_identical"] and
        legs["off"]["bit_identical"] and legs["on"]["bit_identical"],
        "host_killed_mid_stream": kill["host_killed"],
        "requeued_in_flight": kill_counters["serve.fleet.requeues"] > 0,
        "membership_epochs_bumped": epochs_bumped >= 2,
        "rejoin_rewarm_zero_compiles":
            kill_state["rejoin_compiles"] == 0,
        "p99_within_bound": kill["p99_within_bound"],
        "hedging_cuts_p99": cut is not None and cut > 0,
    }
    receipt = {
        "schema": 1,
        "mode": "fast" if fast else "full",
        "seed": seed,
        "hosts": 3,
        "ladder": list(LADDER),
        "kill": kill,
        "hedge_ab": hedge_ab,
        "checks": checks,
        "passed": all(checks.values()),
    }
    if out:
        with open(out, "w") as fout:
            json.dump(receipt, fout, indent=1, sort_keys=True)
            fout.write("\n")
    print("fleet soak %s: %d ok / %d failed (kill phase, p99 %.1fms), "
          "requeues %d, rejoin compiles %s, hedge p99 cut %s%%"
          % ("PASSED" if receipt["passed"] else "FAILED",
             kill["requests_ok"], kill["failed_requests"],
             kill["latency_ms"].get("p99", float("nan")),
             kill_counters["serve.fleet.requeues"],
             kill_state["rejoin_compiles"], cut))
    return receipt


_QOS_COUNTERS = ("serve.fleet.shed",
                 "serve.tenant.interactive.shed",
                 "serve.tenant.batch.shed",
                 "serve.tenant.best_effort.shed",
                 "serve.hedge.fired",
                 "serve.hedge.budget_exhausted",
                 "serve.fleet.canary.mirrors",
                 "serve.fleet.canary.promotions",
                 "serve.fleet.canary.rollbacks")


def run_tenant_soak(seed=11, fast=False, out=None, slo_p99_s=2.0):
    """`--tenants` mode -> QOS.json (docs/serving.md "Multi-tenant
    QoS"): the same subprocess-host harness as the kill/hedge soak,
    pointed at the QoS contracts.

    - **flood**: a 3x best-effort tenant flood plus seeded per-host
      ``serve.host.stall`` stragglers against steady interactive
      clients through a ``--max-inflight``-bounded fleet front:
      interactive p99 must stay within the SLO budget, with **0
      interactive sheds** — every shed the flood causes attributed to
      best_effort/batch (the class-ordered eviction contract).
    - **canary**: :class:`FleetCanaryController` promotes a good
      snapshot host-by-host and auto-rolls back a class-permuted
      poison on real mirrored evidence — 0 failed interactive
      requests, 0 new compiles either way.  This phase runs the hosts
      in-process (socketpair adoption): ``LocalHostControl`` stages
      params straight into a host's engines, which is the driver-side
      stand-in for what a production host's freshness watcher does on
      its own machine.
    """
    from veles_tpu import chaos  # noqa: F401  (parity with run_soak)
    from veles_tpu.serve import FleetRouter, HedgeBudget, ServeOverload

    engine, _ = _build_engine(seed)
    rng = numpy.random.RandomState(seed + 1)
    samples = rng.rand(64, *SAMPLE_SHAPE).astype(numpy.float32)
    reference = {"samples": samples, "ref": engine.infer(samples)}

    # ---- phase A: best-effort flood + stalls vs interactive SLO ---------
    duration = 6.0 if fast else 20.0
    clients = 3 if fast else 4
    flooders = 3  # the "3x" flood: 3 flooder threads per client pool
    stall = "seed=%d;serve.host.stall=stall:p0.05:0.15"
    hosts = [_HostProc("q%d" % i, seed,
                       chaos_spec=stall % (seed + 100 * (i + 1)))
             for i in range(2)]
    # the front bound is what the flood saturates: small enough that
    # eviction provably happens, large enough that the interactive
    # pool (clients << bound) never saturates it with its own class
    max_inflight = 32
    router = FleetRouter(hedge_factor=2.0, hedge_floor_s=0.03,
                         hedge_tick_s=0.01,
                         hedge_budget=HedgeBudget(),
                         max_inflight=max_inflight).start()
    for h in hosts:
        router.add_host(address=("127.0.0.1", h.port),
                        host_id=h.host_id)
    before = _counters(_QOS_COUNTERS)
    stop_at = time.perf_counter() + duration
    lock = threading.Lock()
    stats = {"latencies": [], "failures": [], "mismatches": 0,
             "interactive_sheds": 0, "flood_submitted": 0,
             "flood_shed": 0}

    def interactive_client(k):
        mine, fail, bad, sheds = [], [], 0, 0
        n = 0
        while time.perf_counter() < stop_at:
            idx = (k * 131 + n) % len(samples)
            n += 1
            t0 = time.perf_counter()
            try:
                out = router.infer(samples[idx], timeout=30.0,
                                   slo_class="interactive")
            except ServeOverload as exc:
                sheds += 1
                fail.append("ServeOverload: %s" % exc)
                continue
            except Exception as exc:
                fail.append("%s: %s" % (type(exc).__name__, exc))
                continue
            mine.append(time.perf_counter() - t0)
            if not (out == reference["ref"][idx]).all():
                bad += 1
        with lock:
            stats["latencies"].extend(mine)
            stats["failures"].extend(fail)
            stats["mismatches"] += bad
            stats["interactive_sheds"] += sheds

    def flooder(k):
        n, shed = 0, 0
        while time.perf_counter() < stop_at:
            try:
                # fire-and-forget: the storm wants the queue, not the
                # answers — exactly the noisy-neighbor shape
                router.submit(samples[(k * 17 + n) % 64],
                              slo_class="best_effort")
            except ServeOverload:
                shed += 1
            n += 1
            if n % 16 == 0:
                time.sleep(0.002)
        with lock:
            stats["flood_submitted"] += n
            stats["flood_shed"] += shed

    threads = [threading.Thread(target=interactive_client, args=(k,),
                                name="qos-int-%d" % k)
               for k in range(clients)]
    threads += [threading.Thread(target=flooder, args=(k,),
                                 name="qos-flood-%d" % k)
                for k in range(flooders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # drain the storm's stragglers before reading counters/stopping
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and \
            sum(router.snapshot()["unresolved"].values()):
        time.sleep(0.05)
    flood_counters = {name: value - before[name]
                      for name, value in _counters(_QOS_COUNTERS).items()}
    router.stop()
    for h in hosts:
        h.stop()
    flood = {
        "clients": clients,
        "flooders": flooders,
        "duration_s": duration,
        "max_inflight": max_inflight,
        "straggler_chaos": stall % seed +
            " (per host, independent seed offsets)",
        "interactive_ok": len(stats["latencies"]),
        "interactive_failed": len(stats["failures"]),
        "failed_detail": stats["failures"][:5],
        "interactive_sheds": stats["interactive_sheds"],
        "bit_identical": stats["mismatches"] == 0,
        "flood_submitted": stats["flood_submitted"],
        "flood_shed_client_side": stats["flood_shed"],
        "interactive_latency_ms": _pcts(stats["latencies"]),
        "slo_p99_bound_s": slo_p99_s,
        "counters": flood_counters,
    }
    p99 = (flood["interactive_latency_ms"] or {}).get("p99")

    # ---- phase B: fleet canary promote + poison rollback ----------------
    # in-process hosts: LocalHostControl needs engine access (see
    # docstring) — the router/mirror/judge path is the same code the
    # socketpair fleet tests and a remote fleet run
    import socket as _socket
    from veles_tpu.backends import Device
    from veles_tpu.serve import (
        AOTEngine, BinaryTransportServer, ContinuousBatcher)
    from veles_tpu.serve.freshness import (
        FleetCanaryController, LocalHostControl)

    plans, good = _mlp_spec(seed)
    poison = [dict(p) for p in good]
    poison[1] = dict(poison[1],
                     weights=numpy.ascontiguousarray(
                         good[1]["weights"][:, ::-1]),
                     bias=numpy.ascontiguousarray(good[1]["bias"][::-1]))
    entries = []
    for i in range(2):
        eng = AOTEngine(plans, good, SAMPLE_SHAPE, ladder=LADDER,
                        device=Device(backend="cpu"))
        eng.compile()
        batcher = ContinuousBatcher(eng, max_delay_s=0.002).start()
        server = BinaryTransportServer(
            batcher, port=None, host_meta={"host_id": "c%d" % i})
        server.start_background()
        entries.append((eng, batcher, server))
    router = FleetRouter(hedge=False).start()
    for _, _, server in entries:
        ours, theirs = _socket.socketpair()
        server.serve_socket(ours)
        router.add_host(sock=theirs)
    host_ids = sorted(router.snapshot()["hosts"])
    controls = {hid: LocalHostControl(entries[i][1])
                for i, hid in enumerate(host_ids)}
    controller = FleetCanaryController(
        router, controls, mirror_fraction=1.0, min_mirrors=8,
        divergence_limit=1e-4, breach_budget=2,
        verdict_timeout_s=60.0, seed=seed)
    canary_stats = {"failures": 0, "mismatches": 0, "served": 0}
    canary_stop = threading.Event()

    def canary_traffic():
        n = 0
        while not canary_stop.is_set():
            idx = n % len(samples)
            n += 1
            try:
                out = router.infer(samples[idx], timeout=30.0,
                                   slo_class="interactive")
            except Exception:
                canary_stats["failures"] += 1
                continue
            canary_stats["served"] += 1
            if not (out == reference["ref"][idx]).all():
                canary_stats["mismatches"] += 1

    traffic = threading.Thread(target=canary_traffic,
                               name="qos-canary-traffic")
    traffic.start()
    try:
        promote_receipt = controller.run(good, host_ids[0])
        rollback_receipt = controller.run(poison, host_ids[0])
    finally:
        canary_stop.set()
        traffic.join(timeout=30)
    # post-rollback: the fleet still answers with the good weights
    post_ok = all(
        (router.infer(samples[i], timeout=30.0)
         == reference["ref"][i]).all() for i in range(8))
    router.stop()
    for _, batcher, server in entries:
        server.stop()
        batcher.stop()
    canary = {
        "hosts": "2 in-process (socketpair adoption; see docstring)",
        "promote": promote_receipt,
        "rollback": rollback_receipt,
        "interactive_served": canary_stats["served"],
        "interactive_failed": canary_stats["failures"],
        "bit_identical": canary_stats["mismatches"] == 0,
        "post_rollback_bit_identical": post_ok,
    }

    checks = {
        "interactive_p99_within_slo": (p99 is not None and
                                       p99 / 1e3 <= slo_p99_s),
        "zero_interactive_sheds":
            stats["interactive_sheds"] == 0 and
            flood_counters["serve.tenant.interactive.shed"] == 0,
        "zero_interactive_failures": flood["interactive_failed"] == 0,
        "sheds_attributed_to_lower_classes":
            flood_counters["serve.tenant.best_effort.shed"] > 0,
        "flood_bit_identical": flood["bit_identical"],
        "canary_promoted":
            promote_receipt.get("verdict") == "promote",
        "canary_rolled_back":
            rollback_receipt.get("verdict") == "rolled_back",
        "canary_zero_new_compiles":
            promote_receipt.get("new_compiles") == 0 and
            rollback_receipt.get("new_compiles") == 0,
        "canary_zero_failed_interactive":
            canary["interactive_failed"] == 0,
        "canary_bit_identical": canary["bit_identical"] and
            canary["post_rollback_bit_identical"],
    }
    receipt = {
        "schema": 1,
        "mode": "fast" if fast else "full",
        "seed": seed,
        "ladder": list(LADDER),
        "flood": flood,
        "canary": canary,
        "checks": checks,
        "passed": all(checks.values()),
    }
    if out:
        with open(out, "w") as fout:
            json.dump(receipt, fout, indent=1, sort_keys=True)
            fout.write("\n")
    print("qos soak %s: interactive %d ok / %d failed / %d shed "
          "(p99 %.1fms), best_effort sheds %d, canary %s/%s "
          "(compiles %s/%s)"
          % ("PASSED" if receipt["passed"] else "FAILED",
             flood["interactive_ok"], flood["interactive_failed"],
             flood["interactive_sheds"],
             (p99 if p99 is not None else float("nan")),
             flood_counters["serve.tenant.best_effort.shed"],
             promote_receipt.get("verdict"),
             rollback_receipt.get("verdict"),
             promote_receipt.get("new_compiles"),
             rollback_receipt.get("new_compiles")))
    return receipt


def run_alert_soak(seed=11, fast=False, out=None):
    """``--alerts`` mode -> ALERTS.json (docs/observability.md "Fleet
    telemetry"): the burn-rate alerting plane proven on the same
    two-subprocess-host harness, positive AND negative:

    - **steady**: a quiet closed loop of interactive clients — the
      telemetry plane polls, rolls up, and sweeps the rules the whole
      time, and must fire ZERO alerts (a plane that pages on a
      healthy fleet is worse than no plane).
    - **stall**: the same loop with seeded ``serve.host.stall`` chaos
      parking 30% of frames 300 ms — far past the interactive budget,
      so the fleet-scope burn-rate pair (fast AND slow windows) must
      fire, and the firing must leave its evidence trail: a flight-
      recorder dump carrying the alert record and the tail-exemplar
      ring.
    - **rollup vs per-host evidence**: the merged latency digest's
      percentiles must be consistent with the per-host series the
      subprocesses actually shipped (count conservation; a mixture
      quantile lies within the component quantiles' envelope).
    """
    from veles_tpu.observe.flight import flight
    from veles_tpu.observe.timeseries import (
        FleetTelemetry, digest_percentiles, merge_digests, series)
    from veles_tpu.serve import FleetRouter

    workdir = tempfile.mkdtemp(prefix="alert_soak_")
    # soak-scale cadence: the subprocess hosts inherit the 0.25 s ring
    # interval through the environment; the front's already-built
    # global ring is retuned in place
    os.environ["VELES_SERIES_INTERVAL_S"] = "0.25"
    series.interval_s = 0.25
    # arm the flight recorder: a firing's dump IS part of the receipt
    flight.enabled = True
    flight.base_path = os.path.join(workdir, "flight")

    engine, _ = _build_engine(seed)
    rng = numpy.random.RandomState(seed + 1)
    samples = rng.rand(64, *SAMPLE_SHAPE).astype(numpy.float32)
    reference = {"samples": samples, "ref": engine.infer(samples)}

    duration = 8.0 if fast else 20.0
    clients = 3 if fast else 4
    # 30% of frames park 300 ms: the over-budget fraction (~0.3)
    # burns the 1% error budget ~30x in BOTH windows — far past the
    # 2x factor, while the steady leg's localhost-CPU tail sits well
    # under the 150 ms soak budget
    stall = "seed=%d;serve.host.stall=stall:p0.3:0.3"
    budgets = {"interactive": 0.15}
    legs = {}
    evidence = {}
    for leg_name, chaos_on in (("steady", False), ("stall", True)):
        hosts = [
            _HostProc("%s%d" % (leg_name, i), seed,
                      chaos_spec=(stall % (seed + 100 * (i + 1))
                                  if chaos_on else None))
            for i in range(2)]
        # hedging OFF on purpose: the stall leg needs the straggler
        # tail to REACH the front-door latency digest — this soak
        # proves the pager, the hedge soak proves the cure
        from veles_tpu.serve import qos as _qos
        # alert_rules=[]: nothing may fire during warmup
        router = FleetRouter(hedge=False, telemetry_interval_s=0.25,
                             alert_rules=[]).start()
        for h in hosts:
            router.add_host(address=("127.0.0.1", h.port),
                            host_id=h.host_id)
        # warmup OUTSIDE the books: the fleet's first requests pay
        # connect + dispatch-path costs that would read as a real (but
        # uninteresting) budget breach in the steady leg
        _closed_loop_classed(router, reference, clients, 2.0,
                             slo_class="interactive")
        # then reset the plane (drop warmup buckets) and arm the
        # rules fresh: soak-scale budget, fleet scope — the
        # front-door digest is the one the stall reaches.  Wider-
        # than-default windows: soak cells are 0.25 s so the default
        # fast window (newest 3 cells) holds too few requests to
        # clear min_count and would abstain forever.
        router.telemetry = FleetTelemetry(interval_s=0.25)
        router.alerts.configure(
            _qos.burn_rule_specs(budgets=budgets, scope="fleet",
                                 fast_buckets=6, slow_buckets=24,
                                 min_count=10))
        fired_before = flight.dumps
        latencies, failures, mismatches = _closed_loop_classed(
            router, reference, clients, duration,
            slo_class="interactive")
        # one final poll round so buckets that closed at the tail of
        # the loop still ship and sweep before the books are read
        router._last_poll = 0.0
        router._poll_telemetry(time.perf_counter())
        time.sleep(1.0)
        alert_snap = router.alerts.snapshot()
        telemetry_snap = router.telemetry.snapshot()
        rollup = router.telemetry.rollup()
        per_host = {
            host: router.telemetry.host_buckets(host)
            for host in router.telemetry.hosts()}
        router.stop()
        for h in hosts:
            h.stop()
        legs[leg_name] = {
            "requests_ok": len(latencies),
            "failed_requests": len(failures),
            "bit_identical": not mismatches,
            "latency_ms": _pcts(latencies),
            "alerts_fired": alert_snap["fired_total"],
            "alerts": alert_snap,
            "flight_dumps_written": flight.dumps - fired_before,
            "offsets": {
                h: round(info.get("offset_s") or 0.0, 6)
                for h, info in
                (telemetry_snap.get("hosts") or {}).items()},
        }
        evidence[leg_name] = {"rollup": rollup, "per_host": per_host}

    # ---- rollup percentiles vs per-host evidence ------------------------
    # the host batcher's serve.latency_s digest ships from BOTH
    # subprocesses: merged count must equal the sum of per-host
    # counts, and the merged p50/p99 must lie within the per-host
    # envelope (a mixture quantile cannot leave it)
    hist_name = "serve.latency_s"
    host_digests = {}
    for host, buckets in evidence["stall"]["per_host"].items():
        if host == "front":
            continue  # the front has no batcher; host evidence only
        digests = [
            (b.get("hists") or {}).get(hist_name)
            for b in (buckets or ())]
        digests = [d for d in digests if d]
        if digests:
            host_digests[host] = merge_digests(digests)
    merged = merge_digests(host_digests.values())
    merged_pcts = digest_percentiles(merged)
    host_pcts = {host: digest_percentiles(d)
                 for host, d in host_digests.items()}
    count_ok = merged["count"] == sum(
        d["count"] for d in host_digests.values())
    envelope_ok = bool(host_pcts) and all(
        min(h[p] for h in host_pcts.values()) <= merged_pcts[p]
        <= max(h[p] for h in host_pcts.values())
        for p in ("p50", "p99") if merged_pcts.get(p) is not None)
    rollup_check = {
        "hist": hist_name,
        "hosts": sorted(host_digests),
        "merged_count": merged.get("count"),
        "per_host_counts": {h: d["count"]
                            for h, d in host_digests.items()},
        "count_conserved": count_ok,
        "merged_percentiles": merged_pcts,
        "per_host_percentiles": host_pcts,
        "within_host_envelope": envelope_ok,
    }

    stall_fired = [r["alert"] for r in
                   legs["stall"]["alerts"]["history"]
                   if r.get("state") == "firing"]
    firing = {r["alert"]: r for r in
              legs["stall"]["alerts"]["firing"]}
    burn_name = "slo_burn.fleet.interactive"
    burn_rec = firing.get(burn_name) or next(
        (r for r in legs["stall"]["alerts"]["history"]
         if r.get("alert") == burn_name and
         r.get("state") == "firing"), None)
    dump_path = (burn_rec or {}).get("flight_dump") or \
        flight.last_dump_path
    dump_has_exemplars = False
    if dump_path and os.path.exists(dump_path):
        try:
            with open(dump_path) as fh:
                doc = json.load(fh)
            # flight.dump merges ``extra`` keys at the document's top
            # level, next to the event ring
            dump_has_exemplars = bool(
                (doc.get("alert") or {}).get("alert") == burn_name
                and doc.get("exemplars"))
        except (OSError, ValueError):
            pass

    checks = {
        "steady_zero_alerts": legs["steady"]["alerts_fired"] == 0,
        "stall_burn_rate_fired": burn_name in stall_fired,
        "flight_dump_with_exemplars": dump_has_exemplars,
        "zero_failed_requests":
            legs["steady"]["failed_requests"] == 0 and
            legs["stall"]["failed_requests"] == 0,
        "bit_identical": legs["steady"]["bit_identical"] and
            legs["stall"]["bit_identical"],
        "rollup_count_conserved": rollup_check["count_conserved"],
        "rollup_within_host_envelope":
            rollup_check["within_host_envelope"],
    }
    receipt = {
        "schema": 1,
        "mode": "fast" if fast else "full",
        "seed": seed,
        "hosts": 2,
        "ladder": list(LADDER),
        "telemetry_interval_s": 0.25,
        "budgets_s": budgets,
        "straggler_chaos": stall % seed +
            " (stall leg only; per host, independent seed offsets)",
        "burn_rule": burn_name,
        "burn_firing": burn_rec,
        "flight_dump": dump_path,
        "steady": legs["steady"],
        "stall": legs["stall"],
        "rollup_check": rollup_check,
        "checks": checks,
        "passed": all(checks.values()),
    }
    if out:
        with open(out, "w") as fout:
            json.dump(receipt, fout, indent=1, sort_keys=True,
                      default=repr)
            fout.write("\n")
    print("alert soak %s: steady fired %d (want 0), stall fired %s, "
          "dump %s, rollup count %s envelope %s"
          % ("PASSED" if receipt["passed"] else "FAILED",
             legs["steady"]["alerts_fired"], stall_fired,
             "ok" if dump_has_exemplars else "MISSING",
             "ok" if count_ok else "BAD",
             "ok" if envelope_ok else "BAD"))
    return receipt


def _closed_loop_classed(router, reference, clients, duration_s,
                         slo_class=None):
    """_closed_loop with an SLO class on every request (the alert
    soak's interactive clients)."""
    samples = reference["samples"]
    ref = reference["ref"]
    stop_at = time.perf_counter() + duration_s
    latencies, failures, mismatches = [], [], []
    lock = threading.Lock()

    def client(k):
        mine, bad, fail = [], 0, []
        n = 0
        while time.perf_counter() < stop_at:
            idx = (k * 131 + n) % len(samples)
            n += 1
            t0 = time.perf_counter()
            try:
                out = router.infer(samples[idx], timeout=30.0,
                                   slo_class=slo_class)
            except Exception as exc:
                fail.append("%s: %s" % (type(exc).__name__, exc))
                continue
            mine.append(time.perf_counter() - t0)
            if not (out == ref[idx]).all():
                bad += 1
        with lock:
            latencies.extend(mine)
            failures.extend(fail)
            if bad:
                mismatches.append(bad)

    threads = [threading.Thread(target=client, args=(k,),
                                name="alert-client-%d" % k)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return latencies, failures, mismatches


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--host", action="store_true",
                        help="internal: run as a serve-host subprocess")
    parser.add_argument("--host-id", default="host")
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--fast", action="store_true",
                        help="smoke profile (the slow-marked test)")
    parser.add_argument("--tenants", action="store_true",
                        help="multi-tenant QoS soak -> QOS.json "
                        "(flood + fleet canary) instead of the "
                        "kill/hedge phases")
    parser.add_argument("--alerts", action="store_true",
                        help="telemetry/alerting soak -> ALERTS.json "
                        "(steady leg fires zero, stall leg fires the "
                        "burn-rate pair with its flight dump) instead "
                        "of the kill/hedge phases")
    parser.add_argument("--p99-bound-s", type=float, default=2.0,
                        help="absolute p99 bound for the kill phase "
                        "(CPU-scale; the bound is about NOT hanging, "
                        "the receipt records the measured value)")
    parser.add_argument("--slo-p99-s", type=float, default=2.0,
                        help="interactive p99 SLO budget for the "
                        "--tenants flood phase (CPU-scale)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    if args.host:
        return host_main(args)
    if args.alerts:
        receipt = run_alert_soak(seed=args.seed, fast=args.fast,
                                 out=args.out or "ALERTS.json")
        return 0 if receipt["passed"] else 1
    if args.tenants:
        receipt = run_tenant_soak(seed=args.seed, fast=args.fast,
                                  out=args.out or "QOS.json",
                                  slo_p99_s=args.slo_p99_s)
    else:
        receipt = run_soak(seed=args.seed, fast=args.fast,
                           out=args.out or "HEDGE.json",
                           p99_bound_s=args.p99_bound_s)
    return 0 if receipt["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
