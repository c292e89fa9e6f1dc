"""Fleet telemetry plane (veles_tpu/observe/timeseries.py, alerts.py;
docs/observability.md "Fleet telemetry"): series-ring
bucket semantics (counter deltas/rates over ACTUAL elapsed time,
gauge last-write, mergeable log-binned latency digests), the
take_chunk ship cursor and FleetTelemetry's seq-dedup'd
offset-corrected rollups with kind-true merge semantics (counters
sum, gauges max, digests merge bin-wise), NTP probe offset estimation
(min-delay wins), the multi-window burn-rate truth table (fast AND
slow must both burn; thin windows abstain), EMA spike rules,
edge-triggered alert lifecycle with the flight-recorder + tail-
exemplar evidence dump, heartbeat schema v2/v3 validation and the
JSONL digest, and the ``observe fleet`` CLI round-trip."""

import json
import math

import pytest

from veles_tpu.observe.alerts import (AlertManager, BurnRateRule,
                                      EmaSpikeRule, default_rules,
                                      rule_from_spec)
from veles_tpu.observe.metrics import MetricsRegistry
from veles_tpu.observe.timeseries import (DIGEST_BASE, FleetTelemetry,
                                          SERIES_SCHEMA_VERSION,
                                          SeriesRing, digest_percentiles,
                                          digest_values, fleet_summary,
                                          merge_digests)

pytestmark = [pytest.mark.observe, pytest.mark.telemetry]


# -- digests ----------------------------------------------------------------


def test_digest_values_shape_and_nan_safety():
    """A digest carries exact count/sum/min/max plus log-binned
    counts; non-finite observations are skipped, non-positive ones
    land in the zero bin."""
    d = digest_values([0.010, 0.020, 0.040, float("nan"),
                       float("inf"), 0.0, -1.0])
    assert d["count"] == 5          # nan/inf skipped, 0.0 and -1 kept
    assert d["min"] == -1.0 and d["max"] == 0.040
    assert d["bins"].get("z") == 2  # the two non-positive values
    assert sum(d["bins"].values()) == d["count"]
    assert d["sum"] == pytest.approx(0.010 + 0.020 + 0.040 - 1.0)


def test_digest_percentiles_bounded_by_bin_width():
    """A recovered percentile answers with its bin's UPPER edge:
    pessimistic, but by at most one bin width (~19% relative), and
    always clamped into the digest's exact [min, max]."""
    values = [0.001 * (i + 1) for i in range(1000)]
    pcts = digest_percentiles(digest_values(values))
    for p, exact in (("p50", 0.500), ("p95", 0.950), ("p99", 0.990)):
        assert exact <= pcts[p] <= exact * DIGEST_BASE * 1.0001
    one = digest_values([0.123])
    assert digest_percentiles(one)["p99"] == 0.123  # clamped to max
    assert digest_percentiles({"bins": {}}) == {}


def test_merge_digests_is_a_mixture():
    """Bin-wise merge: counts add, the merged percentile lies within
    the component envelope (the property averaged per-host
    percentiles can never have), malformed entries are skipped."""
    fast = digest_values([0.010] * 90 + [0.020] * 10)
    slow = digest_values([0.200] * 90 + [0.400] * 10)
    merged = merge_digests([fast, None, "junk", slow])
    assert merged["count"] == fast["count"] + slow["count"]
    assert merged["min"] == fast["min"]
    assert merged["max"] == slow["max"]
    m, f, s = (digest_percentiles(d) for d in (merged, fast, slow))
    for p in ("p50", "p99"):
        assert min(f[p], s[p]) <= m[p] <= max(f[p], s[p])


# -- series ring ------------------------------------------------------------


def test_series_ring_bucket_semantics():
    """First tick primes (no since-boot rate); then counters report
    {delta, rate-over-ACTUAL-elapsed}, gauges their last finite
    value, histograms a digest of exactly the new observations."""
    reg = MetricsRegistry()
    ring = SeriesRing(interval_s=1.0, registry=reg)
    reg.counter("req").inc(100)
    reg.gauge("depth").set(3)
    reg.histogram("lat").observe(0.010)
    assert ring.tick(now=10.0, wall=1000.0) is None  # priming
    reg.counter("req").inc(8)
    reg.gauge("depth").set(7)
    reg.histogram("lat").observe(0.020)
    reg.histogram("lat").observe(0.040)
    bucket = ring.tick(now=14.0, wall=1004.0)
    assert bucket["seq"] == 0 and bucket["ts"] == 1004.0
    assert bucket["counters"]["req"] == {"delta": 8, "rate": 2.0}
    assert bucket["gauges"]["depth"] == 7
    hist = bucket["hists"]["lat"]
    assert hist["count"] == 2            # pre-prime 0.010 NOT counted
    assert hist["min"] == 0.020 and hist["max"] == 0.040
    # an idle interval publishes a zero-delta counter and no digest
    bucket = ring.tick(now=15.0, wall=1005.0)
    assert bucket["counters"]["req"] == {"delta": 0, "rate": 0.0}
    assert "lat" not in bucket["hists"]


def test_series_ring_counter_reset_and_maybe_tick_cadence():
    """A registry reset between ticks (bench A/B legs) must not
    publish a negative delta; maybe_tick honors the interval."""
    reg = MetricsRegistry()
    ring = SeriesRing(interval_s=1.0, registry=reg)
    reg.counter("req").inc(50)
    ring.tick(now=0.0, wall=100.0)
    reg.reset()
    reg.counter("req").inc(3)            # reborn smaller than before
    bucket = ring.tick(now=2.0, wall=102.0)
    assert bucket["counters"]["req"]["delta"] == 3
    assert ring.maybe_tick(now=2.5) is None       # interval not up
    assert ring.maybe_tick(now=3.1) is not None


def test_take_chunk_cursor_and_fleet_dedup():
    """take_chunk pops only never-shipped buckets; a re-shipped
    overlap (snapshot-mode producers) dedups by seq on the receiving
    FleetTelemetry, and malformed chunks are counted, not raised."""
    reg = MetricsRegistry()
    ring = SeriesRing(interval_s=1.0, registry=reg)
    reg.counter("req").inc(1)
    ring.tick(now=0.0, wall=100.0)
    for i in range(3):
        reg.counter("req").inc(1)
        ring.tick(now=1.0 + i, wall=101.0 + i)
    chunk = ring.take_chunk(label="h0")
    assert chunk["schema"] == SERIES_SCHEMA_VERSION
    assert [b["seq"] for b in chunk["buckets"]] == [0, 1, 2]
    assert ring.take_chunk() is None     # drained
    fleet = FleetTelemetry(interval_s=1.0)
    assert fleet.add_chunk("h0", chunk)
    assert not fleet.add_chunk("h0", ring.snapshot(label="h0"))  # overlap
    assert len(fleet.host_buckets("h0")) == 3
    assert fleet.dropped == 0
    assert not fleet.add_chunk("h0", {"schema": 99, "buckets": []})
    assert not fleet.add_chunk("h0", "garbage")
    assert fleet.dropped == 2


def _host_chunk(host, wall0, latencies, reqs=10):
    """One host's two-bucket chunk with a known clock origin."""
    reg = MetricsRegistry()
    ring = SeriesRing(interval_s=1.0, registry=reg)
    ring.tick(now=0.0, wall=wall0)
    reg.counter("req").inc(reqs)
    reg.gauge("depth").set(reqs)
    for value in latencies:
        reg.histogram("lat").observe(value)
    ring.tick(now=1.0, wall=wall0 + 1.0)
    return ring.take_chunk(label=host)


def test_fleet_rollup_offset_corrected_merge():
    """Rollup cells land per LOCAL clock (ts + offset): counters sum
    across hosts, gauges take the max, digests merge — and the
    fleet_summary table recovers count-conserving percentiles."""
    fleet = FleetTelemetry(interval_s=1.0)
    # h1's wall clock runs 500 s ahead; its offset maps it back
    fleet.add_chunk("h0", _host_chunk("h0", 1000.0, [0.010] * 20,
                                     reqs=10))
    fleet.add_chunk("h1", _host_chunk("h1", 1500.0, [0.200] * 20,
                                      reqs=30))
    fleet.set_offset("h1", -500.0)
    cells = fleet.rollup()
    assert len(cells) == 1               # same corrected cell
    cell = cells[0]
    assert cell["hosts"] == ["h0", "h1"]
    assert cell["counters"]["req"]["delta"] == 40
    assert cell["gauges"]["depth"] == 30
    assert cell["hists"]["lat"]["count"] == 40
    table = fleet_summary(cells)
    assert table["hists"]["lat"]["count"] == 40
    assert 0.010 <= table["hists"]["lat"]["p50"] <= 0.200 * DIGEST_BASE
    # without the offset the buckets land 500 cells apart
    fleet.set_offset("h1", 0.0)
    assert len(fleet.rollup()) == 2


def test_add_probe_min_delay_offset_estimate():
    """The NTP discipline: among piggybacked (t0, t1, t2, t3) probes
    the MINIMUM-delay exchange wins — queueing noise only ever
    inflates delay, never deflates it."""
    fleet = FleetTelemetry()
    # true offset +5 s; a noisy probe (0.5 s RTT, asymmetric) first
    fleet.add_probe("h0", (100.0, 105.2, 105.3, 100.6))
    noisy = fleet.offset("h0")
    fleet.add_probe("h0", (200.0, 205.05, 205.06, 200.11))
    assert fleet.offset("h0") == pytest.approx(5.0, abs=1e-9)
    assert abs(fleet.offset("h0") - 5.0) <= abs(noisy - 5.0)
    fleet.add_probe("h0", ("junk",))           # ignored, not raised
    fleet.add_probe("h0", (1.0, float("nan"), 2.0, 3.0))
    assert fleet.offset("h0") == pytest.approx(5.0, abs=1e-9)


# -- alert rules ------------------------------------------------------------


def _lat_bucket(ts, values):
    return {"ts": ts, "dur_s": 1.0, "counters": {}, "gauges": {},
            "hists": {"lat": digest_values(values)}}


def test_burn_rate_truth_table():
    """The multi-window pair: fires only when the fast AND slow
    windows BOTH burn the error budget at >= factor; a window under
    min_count abstains (an idle series neither fires nor resolves)."""
    rule = BurnRateRule("burn", "lat", 0.100, objective=0.9,
                        fast_buckets=1, slow_buckets=4, factor=3.0,
                        min_count=5)
    over = [0.500] * 10
    under = [0.010] * 10
    # all windows burning: over-fraction 1.0 / allowed 0.1 = 10x
    assert rule.evaluate([_lat_bucket(t, over) for t in range(4)])
    # steady: nothing over budget
    assert rule.evaluate(
        [_lat_bucket(t, under) for t in range(4)]) is None
    # fast recovered, slow still polluted -> no fire (fast gate)
    hist = [_lat_bucket(t, over) for t in range(3)] + \
        [_lat_bucket(3, under)]
    assert rule.evaluate(hist) is None
    # fast burning but slow diluted to 2.5x < factor 3 -> no fire
    fresh = [_lat_bucket(t, under) for t in range(3)] + \
        [_lat_bucket(3, over)]
    assert rule.evaluate(fresh) is None
    # thin window abstains entirely
    assert rule.evaluate([_lat_bucket(0, [0.500])]) is None
    assert rule.window_burn([_lat_bucket(0, [0.500] * 4)]) is None


def test_burn_rate_spec_round_trip():
    rule = BurnRateRule("burn", "lat", 0.100, objective=0.95,
                        fast_buckets=2, slow_buckets=8, factor=4.0,
                        min_count=7)
    clone = rule_from_spec(rule.spec())
    assert clone.spec() == rule.spec()
    with pytest.raises(ValueError):
        rule_from_spec({"kind": "astrology"})


def test_ema_spike_rule_consumes_buckets_once():
    """A spike against the EMA baseline breaches on the newest bucket
    and is NOT folded into the baseline; already-seen buckets (by ts)
    are not re-consumed."""
    rule = EmaSpikeRule("errs", "err", spike_factor=10.0,
                        spike_floor=1.0, beta=0.5)

    def bucket(ts, rate):
        return {"ts": ts, "counters": {"err": {"delta": rate,
                                               "rate": rate}},
                "gauges": {}, "hists": {}}

    steady = [bucket(float(t), 2.0) for t in range(6)]
    assert rule.evaluate(steady) is None
    assert rule.evaluate(steady + [bucket(6.0, 200.0)])
    # breach persists until a NEW calm bucket arrives
    assert rule.evaluate(steady + [bucket(6.0, 200.0)])
    assert rule.evaluate(steady + [bucket(6.0, 200.0),
                                   bucket(7.0, 2.0)]) is None


def test_default_rules_tenant_vs_fleet_scope():
    """The stock set: one burn pair per budgeted QoS class plus the
    EMA anomaly rules; fleet scope points the burn rules at the
    front-door end-to-end histograms (the ones that see transport
    stalls) under distinct names."""
    tenant = {r.name: r for r in default_rules()}
    assert "slo_burn.interactive" in tenant
    assert tenant["slo_burn.interactive"].hist == \
        "serve.tenant.interactive.latency_s"
    assert "queue_depth_spike" in tenant
    assert "fleet_failures_spike" in tenant
    fleet = {r.name: r for r in default_rules(scope="fleet")}
    assert fleet["slo_burn.fleet.interactive"].hist == \
        "serve.fleet.interactive.latency_s"


# -- alert manager ----------------------------------------------------------


def test_alert_manager_edge_triggered_lifecycle(tmp_path):
    """One breach = one firing (however long it persists), with the
    evidence trail: the firing's flight dump carries the alert record
    and the tail-exemplar ring; recovery lands a resolved record."""
    from veles_tpu.observe.flight import flight
    prev_enabled = flight.enabled
    flight.enabled = True
    flight.base_path = str(tmp_path / "flight")
    try:
        manager = AlertManager([BurnRateRule(
            "burn", "lat", 0.100, objective=0.9, fast_buckets=1,
            slow_buckets=2, factor=2.0, min_count=5)])
        burning = [_lat_bucket(t, [0.500] * 10) for t in range(2)]
        fired = manager.evaluate(burning, wall=100.0,
                                 context={"scope": "test"})
        assert [r["alert"] for r in fired] == ["burn"]
        assert fired[0]["context"] == {"scope": "test"}
        assert manager.evaluate(burning, wall=101.0) == []  # persists
        assert manager.snapshot()["active"] == ["burn"]
        dump = fired[0].get("flight_dump")
        assert dump
        with open(dump) as fh:
            doc = json.load(fh)
        assert doc["alert"]["alert"] == "burn"
        assert "exemplars" in doc
        calm = [_lat_bucket(t, [0.010] * 10) for t in range(2)]
        assert manager.evaluate(calm, wall=102.0) == []
        states = [(r["alert"], r["state"]) for r in manager.history()]
        assert states == [("burn", "firing"), ("burn", "resolved")]
        snap = manager.snapshot()
        assert snap["fired_total"] == 1 and snap["active"] == []
        # re-breach after resolve is a NEW edge
        assert len(manager.evaluate(burning, wall=103.0)) == 1
    finally:
        flight.enabled = prev_enabled


def test_alert_manager_broken_rule_abstains():
    """A rule that raises must never take down the sweep — it simply
    abstains while the healthy rules keep evaluating."""

    class Broken(BurnRateRule):
        def evaluate(self, buckets):
            raise RuntimeError("boom")

    manager = AlertManager([
        Broken("broken", "lat", 0.1),
        BurnRateRule("burn", "lat", 0.100, objective=0.9,
                     fast_buckets=1, slow_buckets=2, factor=2.0,
                     min_count=5)])
    burning = [_lat_bucket(t, [0.500] * 10) for t in range(2)]
    fired = manager.evaluate(burning, dump=False)
    assert [r["alert"] for r in fired] == ["burn"]


# -- heartbeat schema v3 ----------------------------------------------------


def test_heartbeat_v3_line_carries_telemetry_blocks(tmp_path):
    """A live line is schema 3 with the ``series`` + ``alerts``
    blocks and passes its own validator."""
    from veles_tpu.observe.profile import (HEARTBEAT_SCHEMA_VERSION,
                                           Heartbeat,
                                           validate_heartbeat)
    hb = Heartbeat(str(tmp_path / "hb.jsonl"),
                   registry=MetricsRegistry())
    record = validate_heartbeat(hb.line())
    assert record["schema"] == HEARTBEAT_SCHEMA_VERSION == 3
    assert "schema" in record["series"]
    assert set(record["alerts"]) >= {"active", "firing",
                                     "fired_total", "history"}
    json.dumps(record)  # json-serializable end to end


def test_heartbeat_v2_stays_readable_and_v3_is_enforced(tmp_path):
    """Pre-telemetry v2 lines (no series/alerts blocks) still
    validate; a line CLAIMING v3 without the blocks is rejected."""
    from veles_tpu.observe.profile import Heartbeat, validate_heartbeat
    hb = Heartbeat(str(tmp_path / "hb.jsonl"),
                   registry=MetricsRegistry())
    v2 = hb.line()
    v2["schema"] = 2
    v2.pop("series")
    v2.pop("alerts")
    assert validate_heartbeat(v2)["schema"] == 2
    v3 = hb.line()
    v3.pop("series")
    with pytest.raises(ValueError, match="series"):
        validate_heartbeat(v3)
    with pytest.raises(ValueError, match="schema"):
        bad = hb.line()
        bad["schema"] = 99
        validate_heartbeat(bad)


def test_summarize_heartbeats_mixed_schemas(tmp_path):
    """The JSONL digest reads v2 and v3 lines side by side: schema
    census, steady-state rates from consecutive cumulative counters,
    and the set of alerts the file recorded as firing."""
    from veles_tpu.observe.profile import Heartbeat
    from veles_tpu.observe.summary import summarize
    reg = MetricsRegistry()
    hb = Heartbeat(str(tmp_path / "hb.jsonl"), registry=reg)
    records = []
    for i in range(5):
        reg.counter("train.steps").inc(10)
        line = hb.line()
        line["ts"] = 1000.0 + i          # deterministic 1 s cadence
        if i == 0:
            line["schema"] = 2
            line.pop("series")
            line.pop("alerts")
        elif i == 4:
            line["alerts"]["history"] = [
                {"alert": "slo_burn.interactive", "state": "firing",
                 "ts": line["ts"]}]
        records.append(line)
    records.append({"kind": "junk"})     # invalid line is counted
    digest = summarize({"kind": "heartbeats", "records": records})
    assert digest["events"] == 5 and digest["invalid"] == 1
    assert digest["schemas"] == {2: 1, 3: 4}
    assert digest["rates"]["train.steps"] == pytest.approx(10.0)
    assert digest["alerts_fired"] == ["slo_burn.interactive"]


# -- CLI --------------------------------------------------------------------


def test_observe_fleet_cli_round_trip(tmp_path, capsys):
    """``observe fleet`` merges saved per-host snapshots into the
    offset-corrected rollup table (and evaluates the stock rules with
    ``--rules``) — the offline twin of the router's live plane."""
    from veles_tpu.observe.__main__ import main
    a, b = tmp_path / "h0.json", tmp_path / "h1.json"
    a.write_text(json.dumps(_host_chunk("h0", 1000.0,
                                        [0.010] * 20, reqs=10)))
    b.write_text(json.dumps(_host_chunk("h1", 1500.0,
                                        [0.200] * 20, reqs=30)))
    rc = main(["fleet", str(a), str(b), "--offset", "h1=-500",
               "--rules", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["summary"]["hosts"] == ["h0", "h1"]
    assert out["summary"]["counters"]["req"]["delta"] == 40
    assert out["summary"]["hists"]["lat"]["count"] == 40
    assert sorted(out["fleet"]["hosts"]) == ["h0", "h1"]
    assert out["alerts"] == []           # no serve histograms here
    # human rendering exercises the same rollup
    assert main(["fleet", str(a), str(b)]) == 0
    assert "fleet rollup" in capsys.readouterr().out
