"""Freshness-loop chaos soak -> FRESH.json receipt.

The acceptance proof of the train-to-serve loop (docs/serving.md
"Freshness loop", ROADMAP "close the loop"): a trainer continuously
publishing manifest-verified snapshots and a multi-replica serve fleet
picking them up through the canary state machine, with chaos faults on
BOTH sides:

- trainer: ``snapshot.write=crash`` (die mid-export, torn ``.tmp``,
  no final file — the trainer "restarts" and re-exports) and
  ``freshness.publish=truncate`` (a torn NON-atomic copy lands at the
  final published path — the watcher must skip-and-retry, then
  TTL-reject, and the re-publish supersedes it);
- servers: ``serve.stall`` (a replica's worker stalls mid-soak);
- poison: one snapshot with NaN params (must die at the finite gate /
  watcher — ``poisoned``) and one with finite-but-garbage weights
  (the failure a static check CANNOT see: must be caught by the
  mirrored canary comparator and auto-ROLLED BACK with **zero new
  compiles**, never promoted).

Closed-loop clients hammer the pool the whole time; the receipt
asserts **zero dropped requests** across every cutover, that no
poisoned/garbage snapshot ever reached full-fleet cutover, and that
rollback restored the last-good weights (value-digest checked) without
compiling anything.

Usage::

    python scripts/freshness_soak.py --out FRESH.json          # full
    python scripts/freshness_soak.py --fast --out /tmp/F.json  # smoke

The fast profile is the tier-1 smoke (tests/test_freshness.py); the
full profile is the committed FRESH.json receipt.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy  # noqa: E402


def _mlp_spec(seed=0, fan_in=16, hidden=16, classes=4):
    from veles_tpu.compiler import LayerPlan
    from veles_tpu.models.all2all import All2AllSoftmax, All2AllTanh
    rng = numpy.random.RandomState(seed)
    plans = [LayerPlan(All2AllTanh), LayerPlan(All2AllSoftmax)]
    params = [
        {"weights": rng.rand(fan_in, hidden).astype(numpy.float32),
         "bias": rng.rand(hidden).astype(numpy.float32)},
        {"weights": rng.rand(hidden, classes).astype(numpy.float32),
         "bias": rng.rand(classes).astype(numpy.float32)},
    ]
    return plans, params


def _perturb(params, scale, seed):
    rng = numpy.random.RandomState(seed)
    out = []
    for entry in params:
        out.append({
            key: None if leaf is None else
            (leaf + scale * rng.randn(*leaf.shape).astype(leaf.dtype))
            for key, leaf in entry.items()})
    return out


def _poison(params, value=float("nan")):
    return [{key: None if leaf is None else
             numpy.full_like(leaf, value) for key, leaf in entry.items()}
            for entry in params]


def _garbage(params):
    """Finite but WRONG: the classifier head's output classes permuted
    — a model that confidently answers the wrong question.  Invisible
    to the finite gate (every value is healthy), undetectable by any
    static check; catching this on mirrored traffic is exactly the
    canary comparator's job."""
    out = [dict(entry) for entry in params]
    head = params[-1]
    out[-1] = {key: None if leaf is None else
               numpy.roll(leaf, 1, axis=leaf.ndim - 1)
               for key, leaf in head.items()}
    return out


def _schedule(good_cycles, fast):
    """Cycle plan: 'good' promotes interleaved with the two poison
    shapes.  The nan case lands early (prove the gate before investing
    in promotes), the garbage case after at least one promote (so the
    rollback has a non-initial last-good to restore)."""
    sched = ["good"] * good_cycles
    sched.insert(1, "nan")
    if not fast:
        sched.insert(3, "garbage")
    else:
        sched.append("garbage")
    return sched


def _wait_cycle(controller, ordinal, timeout):
    """Block until the controller verdicts `ordinal` (history entry) or
    the watcher TTL-rejects it; returns the history entry or None."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for entry in controller.history:
            if entry["ordinal"] == ordinal:
                return entry
        if ordinal in controller.watcher._rejected:
            return None
        time.sleep(0.02)
    raise TimeoutError("no verdict for publish #%d within %.1fs" %
                       (ordinal, timeout))


def run_soak(good_cycles=6, replicas=3, clients=4, fast=False,
             seed=7, publish_keep=8, out=None):
    from veles_tpu import chaos
    from veles_tpu.observe.metrics import registry
    from veles_tpu.serve import (
        FreshnessController, ReplicaPool, export_model_spec,
        value_digest)
    from veles_tpu.snapshotter import publish_snapshot

    workdir = tempfile.mkdtemp(prefix="freshness_soak_")
    publish_dir = os.path.join(workdir, "publish")
    train_dir = os.path.join(workdir, "train")
    os.makedirs(train_dir)
    # the poison cycles dump the flight ring on purpose: keep the
    # dumps with the soak artifacts, not in the caller's cwd
    from veles_tpu.observe.flight import flight
    flight.base_path = os.path.join(workdir, "veles_flight")
    ladder = (8,) if fast else (8, 32)

    plans, params = _mlp_spec(seed=seed)
    pool = ReplicaPool(plans, params, (16,), replicas=replicas,
                       ladder=ladder, max_delay_s=0.001,
                       max_queue=4096)
    pool.compile()
    pool.start()
    controller = FreshnessController(
        pool, publish_dir, poll_s=0.02, invalid_ttl_s=0.6,
        mirror_fraction=0.5, min_mirrors=4 if fast else 8,
        divergence_limit=0.5, breach_budget=2,
        verdict_timeout_s=20.0, seed=seed).start()

    # chaos on both sides: the 2nd spec export crashes mid-write, the
    # 3rd publish lands torn at the final path, replicas stall at
    # random throughout (param well under the comparator's latency
    # floor so a stall never fakes a quality regression)
    plan = (chaos.FaultPlan(seed=seed)
            .add("snapshot.write", "crash", nth=2)
            .add("freshness.publish", "truncate", nth=3)
            .add("serve.stall", "stall", probability=0.02,
                 param=0.03))
    chaos.install(plan)

    stop = threading.Event()
    ok_count = [0] * clients
    dropped = []

    def client(k):
        rng = numpy.random.RandomState(100 + k)
        x = rng.rand(16).astype(numpy.float32)
        while not stop.is_set():
            try:
                pool.infer(x, timeout=15.0)
                ok_count[k] += 1
            except Exception as exc:  # EVERY failure is a drop
                dropped.append("%s: %s" % (type(exc).__name__, exc))
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,),
                                name="soak-client-%d" % k)
               for k in range(clients)]
    for t in threads:
        t.start()

    cycles = []
    trainer_crashes = 0
    republishes = 0
    seq = 0
    last_promoted = value_digest(params)
    try:
        for kind in _schedule(good_cycles, fast):
            seq += 1
            if kind == "good":
                cand = _perturb(params, 0.02 * seq, seed + seq)
            elif kind == "nan":
                cand = _poison(params)
            else:
                cand = _garbage(params)
            entry = None
            attempts = 0
            while entry is None:
                attempts += 1
                if attempts > 6:
                    raise RuntimeError(
                        "cycle %d (%s) burned %d attempts" %
                        (seq, kind, attempts))
                path = os.path.join(train_dir,
                                    "spec_%03d_%d.pickle" %
                                    (seq, attempts))
                try:
                    export_model_spec(path, plans, cand, (16,))
                except chaos.ChaosCrash:
                    trainer_crashes += 1  # "trainer restarts", re-export
                    continue
                try:
                    receipt = publish_snapshot(path, publish_dir,
                                               keep=publish_keep)
                except chaos.ChaosCrash:
                    trainer_crashes += 1  # LATEST never flipped
                    continue
                entry = _wait_cycle(controller, receipt["ordinal"],
                                    timeout=60.0)
                if entry is None:
                    republishes += 1  # torn publish TTL-rejected
            expected = value_digest(cand) if kind == "good" else None
            cycles.append({
                "kind": kind, "attempts": attempts,
                "ordinal": entry["ordinal"],
                "verdict": entry["verdict"],
                "mirrors": entry.get("mirrors"),
                "new_compiles": entry.get("new_compiles"),
                "reason": entry.get("reason"),
            })
            if kind == "good" and entry["verdict"] == "promoted":
                last_promoted = expected
        time.sleep(0.3)  # a little steady-state traffic post-cutovers
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        controller.stop()
        chaos.uninstall()
        pool.stop()

    promoted = [c for c in cycles
                if c["kind"] == "good" and c["verdict"] == "promoted"]
    poison_cases = [c for c in cycles if c["kind"] in ("nan", "garbage")]
    poison_contained = [c for c in poison_cases
                        if c["verdict"] in ("poisoned", "rolled_back")]
    rollbacks = [c for c in cycles if c["verdict"] == "rolled_back"]
    served_digest = value_digest(pool.engine.params)
    receipt = {
        "schema": 1,
        "mode": "fast" if fast else "full",
        "replicas": replicas,
        "ladder": list(ladder),
        "clients": clients,
        "cycles": cycles,
        "chaos": {
            "plan": "snapshot.write=crash:n2; "
                    "freshness.publish=truncate:n3; "
                    "serve.stall=stall:p0.02:0.03",
            "trainer_crashes": trainer_crashes,
            "torn_publishes_rejected": republishes,
            "replica_stalls": plan.fired("serve.stall"),
        },
        "requests_served": sum(ok_count),
        "requests_dropped": len(dropped),
        "dropped_detail": dropped[:5],
        "counters": {
            name.rsplit(".", 1)[1]: registry.counter(name).value
            for name in (
                "serve.freshness.published",
                "serve.freshness.candidates",
                "serve.freshness.promotions",
                "serve.freshness.rollbacks",
                "serve.freshness.poisoned_rejected")},
        "checks": {
            "promote_cycles": len(promoted),
            "zero_dropped_requests": not dropped,
            "poison_cases": len(poison_cases),
            "poison_contained": len(poison_contained),
            "poison_never_promoted": (
                len(poison_contained) == len(poison_cases)),
            "rollback_zero_new_compiles": all(
                c["new_compiles"] == 0 for c in rollbacks),
            "fleet_serves_last_promoted": (
                served_digest == last_promoted),
        },
    }
    passed = (receipt["checks"]["zero_dropped_requests"] and
              receipt["checks"]["poison_never_promoted"] and
              receipt["checks"]["rollback_zero_new_compiles"] and
              receipt["checks"]["fleet_serves_last_promoted"] and
              len(promoted) >= (2 if fast else 5))
    receipt["passed"] = passed
    if out:
        with open(out, "w") as fout:
            json.dump(receipt, fout, indent=1, sort_keys=True)
            fout.write("\n")
    print("freshness soak %s: %d promotes, %d rollbacks, %d poisoned "
          "rejected, %d served, %d dropped, trainer crashes %d, torn "
          "publishes %d" %
          ("PASSED" if passed else "FAILED", len(promoted),
           len(rollbacks),
           receipt["counters"]["poisoned_rejected"],
           receipt["requests_served"], len(dropped), trainer_crashes,
           republishes))
    return receipt


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cycles", type=int, default=6,
                        help="good (promote) cycles; nan/garbage "
                        "poison cycles are added on top")
    parser.add_argument("--replicas", type=int, default=3)
    parser.add_argument("--clients", type=int, default=4)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--fast", action="store_true",
                        help="smoke profile: 2 promote cycles, "
                        "single-rung ladder (the tier-1 test)")
    parser.add_argument("--out", default="FRESH.json")
    args = parser.parse_args(argv)
    receipt = run_soak(
        good_cycles=2 if args.fast else args.cycles,
        replicas=args.replicas, clients=args.clients, fast=args.fast,
        seed=args.seed, out=args.out)
    return 0 if receipt["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
