"""Cluster-scope observability (PR 5): clock-offset estimation, trace
chunk shipping + merge, the black-box flight recorder, XLA
introspection (recompiles, step FLOPs, live MFU), and the crash-path
trace/flight preservation in the launcher."""

import json
import threading
import time

import pytest

from veles_tpu.observe.cluster import TraceCollector, estimate_offset
from veles_tpu.observe.flight import (FlightRecorder, flight,
                                      validate_flight)
from veles_tpu.observe.merge import merge_parts, merge_run, part_from_doc
from veles_tpu.observe.metrics import MetricsRegistry, registry
from veles_tpu.observe.trace import SpanTracer, validate_trace

pytestmark = pytest.mark.observe


# -- clock-offset estimator (NTP-style join handshake) ---------------------


def test_estimate_offset_symmetric_rtt_recovers_exactly():
    """Symmetric path: the classic four-timestamp formula recovers the
    true offset regardless of the RTT magnitude."""
    true_offset = 2.5       # server clock ahead by 2.5 s
    one_way = 0.02          # symmetric 20 ms each way
    samples = []
    for i in range(5):
        t0 = 100.0 + i
        t1 = t0 + one_way + true_offset
        t2 = t1
        t3 = t0 + 2 * one_way
        samples.append((t0, t1, t2, t3))
    offset, delay = estimate_offset(samples)
    assert abs(offset - true_offset) < 1e-9
    assert abs(delay - 2 * one_way) < 1e-9


def test_estimate_offset_asymmetric_prefers_min_delay_sample():
    """Asymmetric probes mis-estimate by at most delay/2; the
    estimator must pick the MINIMUM-delay sample, where that bound is
    tightest — not average the noisy ones in."""
    true_offset = 1.0
    # 0.5 s out / 0.1 s back: grossly asymmetric, delay 0.6
    noisy = (0.0, 0.5 + true_offset, 0.5 + true_offset, 0.6)
    # 10/11 ms: near-symmetric, delay 21 ms
    clean = (10.0, 10.010 + true_offset, 10.010 + true_offset, 10.021)
    offset, delay = estimate_offset([noisy, clean])
    assert abs(delay - 0.021) < 1e-9, "min-delay sample must win"
    assert abs(offset - true_offset) <= 0.021 / 2 + 1e-9
    with pytest.raises(ValueError):
        estimate_offset([])


# -- trace chunks + merge --------------------------------------------------


def _recording_tracer(label):
    """A tracer with a private (disabled) flight sink so these tests
    never touch the process-global ring."""
    tracer = SpanTracer(flight=FlightRecorder(enabled=False))
    tracer.start()
    tracer.label = label
    return tracer


def test_take_chunk_pops_bounded_and_preserves_thread_names():
    tracer = _recording_tracer("worker")
    for i in range(10):
        tracer.instant("e%d" % i)
    chunk = tracer.take_chunk(max_events=4)
    assert chunk["schema"] == 1
    assert [e["name"] for e in chunk["events"]] == \
        ["e0", "e1", "e2", "e3"]
    assert chunk["label"] == "worker"
    assert chunk["wall_epoch"] > 0
    # the names map replaces the popped thread_name metadata event
    tid = chunk["events"][0]["tid"]
    assert chunk["threads"][str(tid)] != ""
    # the rest stays recorded; a later chunk picks it up
    rest = tracer.take_chunk()
    assert [e["name"] for e in rest["events"]] == \
        ["e%d" % i for i in range(4, 10)]
    assert tracer.take_chunk() is None


def test_take_chunk_thread_scoping_separates_shared_tracer():
    """trace_scope="threads" (in-process two-node tests): only events
    recorded by the named threads ship; the rest stay."""
    tracer = _recording_tracer("shared")
    tracer.instant("main-event")
    seen = {}

    def worker():
        seen["ident"] = threading.get_ident()
        tracer.instant("worker-event")

    thread = threading.Thread(target=worker, name="chunk-worker")
    thread.start()
    thread.join()
    chunk = tracer.take_chunk(idents={seen["ident"]})
    assert [e["name"] for e in chunk["events"]] == ["worker-event"]
    remaining = {e["name"] for e in tracer.events if e["ph"] != "M"}
    assert remaining == {"main-event"}


def test_merge_two_process_traces_tracks_and_corrected_timestamps(
        tmp_path):
    """Round-trip: two synthetic per-process traces -> one merged doc
    with separate process tracks, offset-corrected, monotonic
    timestamps."""
    master = _recording_tracer("master")
    with master.span("m.outer", cat="test"):
        with master.span("m.inner", cat="test"):
            time.sleep(0.002)
        master.instant("proto.job_out", cat="proto", job="j1")
    master.stop()

    slave = _recording_tracer("slave:host:1")
    with slave.span("slave.job", cat="proto", job="j1"):
        time.sleep(0.002)
    slave.stop()
    # pretend the slave's wall clock runs 5 s behind the master's; the
    # join-time estimate (+5 s) must pull its events back into line
    slave._epoch_wall -= 5.0

    mp, sp = str(tmp_path / "m.json"), str(tmp_path / "s.json")
    master.save(mp)
    slave.save(sp)
    with open(mp) as fin:
        mdoc = json.load(fin)
    with open(sp) as fin:
        sdoc = json.load(fin)
    merged = merge_parts(
        [part_from_doc(mdoc), part_from_doc(sdoc, offset_s=5.0)],
        trace_id="tid-1")
    validate_trace(merged)
    events = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    # monotonic corrected timeline
    stamps = [e["ts"] for e in events]
    assert stamps == sorted(stamps)
    assert all(ts >= 0 for ts in stamps)
    # track separation: per-part synthetic pids + process_name metadata
    by_name = {e["name"]: e for e in events}
    assert by_name["m.outer"]["pid"] != by_name["slave.job"]["pid"]
    procs = {(e.get("args") or {}).get("name")
             for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"master", "slave:host:1"}
    # offset correction: with +5 s applied the slave span lands within
    # the (sub-second) master recording window, not 5 s away
    span_gap = abs(by_name["slave.job"]["ts"] - by_name["m.outer"]["ts"])
    assert span_gap < 2e6, "offset correction must realign the clocks"
    assert merged["otherData"]["trace_id"] == "tid-1"


def test_trace_collector_bounds_and_labels():
    collector = TraceCollector(max_events=5)
    chunk = {"schema": 1, "pid": 1, "label": "slave:a",
             "wall_epoch": 1.0, "threads": {},
             "events": [{"ph": "i", "ts": 0.0, "name": "e",
                         "pid": 1, "tid": 1}] * 4}
    assert collector.add_chunk("a", chunk) == 4
    assert collector.add_chunk("a", chunk) == 1  # bounded
    assert collector.dropped_events == 3
    collector.add_chunk("a", {"schema": 99, "events": []})  # unknown
    collector.set_offset("a", 0.25, 0.01)
    parts = collector.parts()
    assert len(parts) == 1
    assert parts[0]["label"] == "slave:a"
    assert parts[0]["offset_s"] == 0.25
    assert sum(len(c["events"]) for c in parts[0]["chunks"]) == 5


# -- flight recorder -------------------------------------------------------


def test_flight_ring_semantics_and_dump_schema(tmp_path):
    recorder = FlightRecorder(capacity=32, enabled=True,
                              base_path=str(tmp_path / "fl"))
    for i in range(100):
        recorder.record("instant", "e%d" % i)
    assert len(recorder) == 32  # ring keeps only the most recent
    events = recorder.snapshot()
    assert events[0]["name"] == "e68"
    assert events[-1]["name"] == "e99"
    path = recorder.dump(reason="unit test")
    with open(path) as fin:
        doc = json.load(fin)
    validate_flight(doc)
    assert doc["reason"] == "unit test"
    assert len(doc["events"]) == 32
    # sequenced: a second dump never overwrites the first
    assert recorder.dump(reason="unit test") != path


def test_disabled_tracer_still_feeds_flight_ring():
    """The black box works without --trace: complete/instant/counter
    route into the flight ring even while full tracing is off."""
    ring = FlightRecorder(capacity=64, enabled=True)
    tracer = SpanTracer(flight=ring)
    assert not tracer.enabled and tracer.active
    with tracer.span("step", cat="test"):
        pass
    tracer.instant("proto.evt")
    tracer.counter("depth", 2)
    assert tracer.events == []  # the trace buffer stays empty
    kinds = [(e["kind"], e["name"]) for e in ring.snapshot()]
    assert kinds == [("span", "step"), ("instant", "proto.evt"),
                     ("counter", "depth")]
    span = ring.snapshot()[0]
    assert span["dur_s"] >= 0 and span["ts"] > 0
    # and with the ring ALSO off, nothing records anywhere
    ring.enabled = False
    assert not tracer.active
    with tracer.span("ignored"):
        pass
    assert len(ring) == 3


def test_validate_flight_rejects_malformed():
    with pytest.raises(ValueError):
        validate_flight([])
    with pytest.raises(ValueError, match="missing"):
        validate_flight({"kind": "flight"})
    good = FlightRecorder(capacity=16).document("x")
    validate_flight(good)
    bad = dict(good, schema=99)
    with pytest.raises(ValueError, match="schema"):
        validate_flight(bad)


# -- XLA introspection -----------------------------------------------------


def test_recompile_watcher_detects_forced_donated_shape_recompile():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from veles_tpu.observe.xla_introspect import CompileWatcher
    reg = MetricsRegistry()
    watcher = CompileWatcher(registry=reg, warn_after=1)
    assert watcher.install()

    step = jax.jit(lambda x: x * 3.0, donate_argnums=(0,))
    assert watcher.watch(step, "step")
    step(jnp.ones((8, 8)))
    assert watcher.poll() == {"step": 1}
    # a changed donated shape silently recompiles — the exact storm
    # signature the watcher exists to catch
    step(jnp.ones((4, 8)))
    warned = []
    sizes = watcher.poll(warn=lambda name, size:
                         warned.append((name, size)))
    assert sizes["step"] == 2
    assert warned == [("step", 2)]
    assert reg.counter("compile.recompiles").value >= 1
    # the monitoring listener counted the backend compiles globally
    assert reg.counter("compile.count").value >= 2
    assert reg.counter("compile.seconds").value > 0


def test_device_memory_gauges_census_fallback():
    pytest.importorskip("jax")
    from veles_tpu.observe.xla_introspect import device_memory_gauges
    reg = MetricsRegistry()
    out = device_memory_gauges(reg)
    # CPU backends lack memory_stats -> live-array census; either way
    # at least one gauge must land
    assert out
    assert all(isinstance(v, int) and v >= 0 for v in out.values())


def test_mfu_snapshot_pipeline(monkeypatch):
    from veles_tpu.observe import xla_introspect
    reg = MetricsRegistry()
    assert xla_introspect.mfu_snapshot(reg) is None  # nothing published
    xla_introspect.set_step_flops(2e9, reg)
    hist = reg.histogram("step.train_s")
    for _ in range(8):
        hist.observe(0.001)  # 2e9 flops / 1ms = 2 TFLOP/s achieved
    # no chip, no MFU: the CPU platform has no row in the peaks table
    # and nothing is published under the device metric's name
    assert xla_introspect.peak_flops() is None
    assert xla_introspect.mfu_snapshot(reg) is None
    assert reg.peek("xla.mfu_pct") is None
    # a chip the table does not know is an error, never a default
    class _Chip(object):
        platform = "tpu"
        device_kind = "TPU v9 imaginary"
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        xla_introspect.device_peaks(_Chip())
    _Chip.device_kind = "TPU v5 lite"
    assert xla_introspect.device_peaks(_Chip())["bf16"] == 197e12
    # the pipeline itself, rated against a stand-in 4 TFLOP/s chip
    monkeypatch.setattr(xla_introspect, "peak_flops",
                        lambda dtype=None: 4e12)
    mfu = xla_introspect.mfu_snapshot(reg)
    assert mfu is not None and abs(mfu - 50.0) < 1.0
    assert reg.peek("xla.mfu_pct").value == mfu
    # the health surface picks it up without extra publication
    from veles_tpu.observe.metrics import health_snapshot
    assert health_snapshot(reg)["mfu_pct"] == mfu


# -- heartbeat: compile/mfu fields on the fused path -----------------------


def test_heartbeat_carries_compile_count_and_mfu_on_fused_run(
        cpu_device, tmp_path, monkeypatch):
    """Acceptance: heartbeat JSONL lines from a fused run carry
    non-null compile.count and mfu_pct (rated against a stand-in chip
    row: the CPU platform itself has no peak, so no MFU)."""
    from veles_tpu.observe import xla_introspect
    from veles_tpu.observe.profile import validate_heartbeat
    from tests.test_observe import _trace_smoke_run
    # small enough that a toy MLP on the CPU rates above the gauge's
    # three-decimal rounding
    monkeypatch.setattr(xla_introspect, "peak_flops",
                        lambda dtype=None: 1e9)
    registry.reset()
    doc, lines = _trace_smoke_run(cpu_device, tmp_path, pipeline=False)
    assert lines
    final = lines[-1]
    validate_heartbeat(final)
    assert final["mono"] > 0  # schema v2: both clocks on every line
    assert final["compile"]["count"] > 0
    assert final["compile"]["seconds"] > 0
    assert final["mfu_pct"] is not None and final["mfu_pct"] > 0
    # the trace side still validates with the new anchor metadata
    validate_trace(doc)
    assert doc["otherData"]["wall_epoch"] > 0


# -- launcher crash paths --------------------------------------------------


def test_launcher_saves_trace_and_flight_on_unhandled_exception(
        cpu_device, tmp_path):
    """Satellite: --trace output (and a flight dump) must survive an
    unhandled exception, verified through a chaos kill point in the
    input pipeline worker."""
    from veles_tpu import chaos, prng
    from veles_tpu.chaos import FaultPlan
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.prng import RandomGenerator
    from tests.test_models import BlobsLoader

    registry.reset()
    trace_path = str(tmp_path / "crash_trace.json")
    prng.get().seed(991)
    launcher = Launcher(trace=trace_path)
    StandardWorkflow(
        launcher,
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 4,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: BlobsLoader(
            w, minibatch_size=32, on_device=False,
            prng=RandomGenerator("obsc_crash", seed=3)),
        decision_config=dict(max_epochs=4),
    ).fuse(pipeline=True)
    launcher.initialize(device=cpu_device)
    chaos.install(FaultPlan().add("pipeline.serve", "exc", nth=3))
    try:
        with pytest.raises(RuntimeError, match="injected serve"):
            launcher.run()
    finally:
        chaos.uninstall()
        launcher.stop()
    # the trace survived the crash (saved on the exception exit path)
    with open(trace_path) as fin:
        doc = json.load(fin)
    validate_trace(doc)
    # the crash lands during the first (eval) minibatches — the saved
    # buffer must still hold the spans recorded up to that point
    names = {e.get("name") for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert "FusedTrainer" in names and "pipeline.fill" in names
    # ...and the flight recorder dumped next to it
    dumps = list(tmp_path.glob("crash_trace.json.flight.exception.*"))
    assert dumps, "flight dump must be emitted on the exception path"
    with open(str(dumps[0])) as fin:
        fdoc = json.load(fin)
    validate_flight(fdoc)
    assert fdoc["reason"] == "exception"
    assert any(e["kind"] == "span" for e in fdoc["events"])


# -- end-to-end: two-node chaos run -> merged trace + flight dump ----------


@pytest.mark.chaos
def test_two_node_chaos_run_merged_trace_and_quarantine_dump(
        cpu_device, tmp_path):
    """Acceptance: an in-proc master+slave run with an injected
    poisoned update produces (a) a flight dump at the quarantine, and
    (b) a merged Perfetto trace where one job id links the master's
    proto.job_out and the slave's job span on separate process tracks
    under the run's trace id."""
    from veles_tpu import chaos
    from veles_tpu.chaos import FaultPlan
    from veles_tpu.client import Client
    from veles_tpu.observe.trace import tracer
    from tests.test_network import _build, _start_server

    registry.reset()
    old_base, flight.base_path = flight.base_path, \
        str(tmp_path / "flight")
    tracer.start()
    tracer.label = "master"
    try:
        master = _build("master", "obsc_m", cpu_device)
        slave = _build("slave", "obsc_s", cpu_device)
        server, _ = _start_server(master, blacklist_ttl=0.6)
        client = Client("127.0.0.1:%d" % server.port, slave,
                        trace_scope="threads")
        plan = chaos.install(
            FaultPlan().add("net.update", "nan", nth=2))
        try:
            client.run()
        finally:
            chaos.uninstall()
        assert server._done.wait(15)
        assert plan.fired("net.update") == 1
    finally:
        tracer.stop()
        flight.base_path = old_base
    assert server.quarantined == 1
    assert bool(master.decision.complete)

    # trace context propagated through the protocol at join time
    assert client.trace_id == server.trace_id
    assert client.clock_offset is not None
    assert abs(client.clock_offset) < 1.0  # same host, same clock
    assert client.trace_chunks_sent > 0

    # (a) schema-valid flight dump emitted AT the injected failure
    dumps = sorted(tmp_path.glob("flight.quarantine.*.json"))
    assert dumps
    with open(str(dumps[0])) as fin:
        fdoc = json.load(fin)
    validate_flight(fdoc)
    assert fdoc["reason"] == "quarantine"
    assert any(e["kind"] == "instant" and
               e["name"] == "proto.quarantine"
               for e in fdoc["events"])

    # (b) merged cluster trace: master doc + shipped slave chunks
    trace_path = str(tmp_path / "master.json")
    tracer.save(trace_path)
    with open(trace_path) as fin:
        master_doc = json.load(fin)
    assert server.trace_collector.keys()
    merged = merge_run(master_doc, server.trace_collector,
                       trace_id=server.trace_id)
    validate_trace(merged)
    assert merged["otherData"]["trace_id"] == server.trace_id
    # the shared in-proc tracer must not leak the master's label onto
    # the slave's shipped chunks: two DISTINCT process names
    procs = {(e.get("args") or {}).get("name")
             for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "master" in procs
    assert any(name.startswith("slave:") for name in procs)
    events = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    stamps = [e["ts"] for e in events]
    assert stamps == sorted(stamps), "merged timeline must be monotonic"

    def jobs_of(name, ph):
        return {(e.get("args") or {}).get("job"): e["pid"]
                for e in events
                if e["name"] == name and e.get("ph") == ph}

    job_out = jobs_of("proto.job_out", "i")
    slave_spans = jobs_of("slave.job", "X")
    update_in = jobs_of("proto.update_in", "i")
    stitched = set(job_out) & set(slave_spans) & set(update_in)
    assert stitched, "one job id must link master and slave events"
    for job in stitched:
        assert job_out[job] != slave_spans[job], \
            "master and slave events must sit on separate process tracks"
        assert job_out[job] == update_in[job]
    # the slave's protocol instants carry the shared trace id
    slave_traced = [e for e in events if e["name"] == "proto.job_in"]
    assert slave_traced
    assert all((e["args"] or {}).get("trace") ==
               server.trace_id[:8] for e in slave_traced)
