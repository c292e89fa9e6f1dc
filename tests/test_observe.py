"""Unified telemetry layer (veles_tpu/observe/): span tracer validity
and zero-overhead-when-disabled, metrics registry semantics, heartbeat
schema, print_stats baseline-vs-cumulative semantics, and the --trace
smoke run over a small fused workflow."""

import io
import json
import re
import threading
import time

import pytest

from veles_tpu.observe.metrics import (MetricsRegistry, health_snapshot,
                                       percentiles, registry)
from veles_tpu.observe.profile import (Heartbeat, ProfilerHook,
                                       validate_heartbeat)
from veles_tpu.observe.trace import SpanTracer, validate_trace

pytestmark = pytest.mark.observe


# -- span tracer -----------------------------------------------------------


def test_disabled_tracer_emits_nothing_and_stays_cheap():
    tracer = SpanTracer()
    start = time.perf_counter()
    for _ in range(20000):
        with tracer.span("x"):
            pass
        tracer.instant("y")
        tracer.complete("z", 0.0, 1.0)
        tracer.counter("c", 1)
    elapsed = time.perf_counter() - start
    assert tracer.events == []
    assert tracer.dropped == 0
    # 80k disabled calls: generous bound, but a host sync or lock on
    # the disabled path would blow straight through it
    assert elapsed < 2.0


def test_spans_nest_and_trace_parses(tmp_path):
    tracer = SpanTracer().start()
    with tracer.span("outer", cat="test", level=1):
        with tracer.span("inner", cat="test"):
            time.sleep(0.001)
        tracer.instant("marker", note="hello")
        tracer.counter("depth", 3)
    tracer.stop()
    path = tracer.save(str(tmp_path / "trace.json"))
    with open(path) as fin:
        doc = json.load(fin)
    validate_trace(doc)  # parses, known phases, spans nest
    events = doc["traceEvents"]
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner"}
    outer, inner = spans["outer"], spans["inner"]
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= \
        outer["ts"] + outer["dur"] + 1.0
    assert outer["args"] == {"level": 1}
    # per-thread track metadata is present
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in events)
    assert any(e["ph"] == "i" and e["name"] == "marker"
               for e in events)
    assert any(e["ph"] == "C" and e["args"] == {"value": 3}
               for e in events)


def test_validate_trace_rejects_overlapping_spans():
    doc = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 50.0, "dur": 100.0,
         "pid": 1, "tid": 1},
    ]}
    with pytest.raises(ValueError, match="does not nest"):
        validate_trace(doc)


def test_scope_feeds_every_sink_and_threads_get_own_tracks():
    """The one primitive: one measurement lands in the histogram, the
    timer, the trace (with the span's parent) and the flight ring; a
    scope opened on another thread gets its own track and no parent
    from this one."""
    from veles_tpu.observe.flight import FlightRecorder
    ring = FlightRecorder(capacity=16, enabled=True)
    tracer = SpanTracer(flight=ring).start()
    hist = MetricsRegistry().histogram("work_s")
    timers = {}

    def work():
        with tracer.scope("work", cat="test", hist=hist,
                          timers=(timers, "work")) as span:
            time.sleep(0.001)
        return span

    with tracer.scope("outer", cat="test", args={"level": 1}) as outer:
        inner = work()
        tracer.complete("stamped", inner.start, inner.elapsed / 2)
    thread = threading.Thread(target=work, name="observe-worker")
    thread.start()
    thread.join()
    tracer.stop()
    # one measurement, every sink
    assert hist.count == 2 and inner.elapsed >= 0.001
    assert timers["work"] == pytest.approx(hist.total)
    spans = [e for e in tracer.events if e["ph"] == "X"]
    by_name = {}
    for event in spans:
        by_name.setdefault(event["name"], []).append(event)
    assert sorted(by_name) == ["outer", "stamped", "work"]
    mine, theirs = by_name["work"]
    assert mine["dur"] == pytest.approx(inner.elapsed * 1e6)
    # the parent is the enclosing open scope ON THAT THREAD
    root, = by_name["outer"]
    assert root["parent"] is None and root["args"] == {"level": 1}
    assert mine["parent"] == root["sid"] == outer.sid
    assert by_name["stamped"][0]["parent"] == root["sid"]
    assert theirs["parent"] is None and theirs["tid"] != mine["tid"]
    assert len({e["sid"] for e in spans}) == len(spans) == 4
    names = [e["args"]["name"] for e in tracer.events
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "observe-worker" in names
    validate_trace({"traceEvents": tracer.events})
    # the flight ring carries the parent by name
    ring_events = {(e["name"], e.get("parent"), e["thread"] ==
                    "observe-worker") for e in ring.snapshot()}
    assert ("work", "outer", False) in ring_events
    assert ("work", None, True) in ring_events
    assert ("outer", None, False) in ring_events


def test_scope_measures_with_every_listener_off():
    """Tracing off, flight ring off, no profiler session: the timer and
    the histogram still get the measurement, nothing else is built."""
    from veles_tpu.observe.flight import FlightRecorder
    from veles_tpu.observe.trace import profiler_live, step_annotation
    tracer = SpanTracer(flight=FlightRecorder(enabled=False))
    timers = {"run": 1.0}
    with tracer.scope("quiet", timers=(timers, "run")) as span:
        assert tracer._open_scopes() == [span]
        assert span._note is None  # no annotation without a session
    assert timers["run"] == 1.0 + span.elapsed
    assert tracer.events == [] and tracer._open_scopes() == []
    assert not profiler_live()
    with step_annotation("train_step", 3) as nothing:
        assert nothing is None


def test_validate_trace_rejects_a_child_outside_its_parent():
    doc = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 1, "tid": 1, "sid": 1, "parent": None},
        {"name": "b", "ph": "X", "ts": 200.0, "dur": 10.0,
         "pid": 1, "tid": 1, "sid": 2, "parent": 1},
    ]}
    with pytest.raises(ValueError, match="not inside its parent"):
        validate_trace(doc)
    doc["traceEvents"][1]["ts"] = 20.0
    validate_trace(doc)
    # a chunk that left the parent behind still validates
    validate_trace({"traceEvents": doc["traceEvents"][1:]})


def test_tracer_bounded_memory():
    tracer = SpanTracer(max_events=3)
    tracer.start()
    for i in range(10):
        tracer.instant("e%d" % i)
    # slot 1 holds the thread_name metadata; e0/e1 fill the rest,
    # e2..e9 count as dropped instead of growing the buffer
    events = tracer.events
    assert len(events) == 3
    assert events[0]["name"] == "thread_name"
    assert tracer.dropped == 8


# -- metrics registry ------------------------------------------------------


def test_percentiles_nearest_rank():
    assert percentiles([]) == {}
    out = percentiles(list(range(1, 101)))
    # true nearest-rank: index ceil(p/100 * n) - 1
    assert out["p50"] == 50
    assert out["p95"] == 95
    assert out["p99"] == 99
    small = percentiles([3.0, 1.0, 2.0])
    assert small["p50"] == 2.0
    assert small["p99"] == 3.0
    assert percentiles([1.0, 2.0])["p50"] == 1.0


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("jobs").inc()
    reg.counter("jobs").inc(4)
    reg.gauge("depth").set(7)
    hist = reg.histogram("lat_s")
    for value in range(1, 101):
        hist.observe(value / 100.0)
    snap = reg.snapshot()
    assert snap["counters"]["jobs"] == 5
    assert snap["gauges"]["depth"] == 7
    lat = snap["histograms"]["lat_s"]
    assert lat["count"] == 100
    assert lat["min"] == 0.01 and lat["max"] == 1.0
    assert abs(lat["mean"] - 0.505) < 1e-9
    assert lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    # same name must keep its kind
    with pytest.raises(TypeError):
        reg.counter("depth")
    # peek never creates
    assert reg.peek("nope") is None
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_histogram_window_and_reset():
    reg = MetricsRegistry()
    hist = reg.histogram("w", window=4)
    for value in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        hist.observe(value)
    assert hist.count == 6  # lifetime count survives the window
    assert sorted(hist.window_values()) == [3.0, 4.0, 5.0, 6.0]
    hist.reset()
    assert hist.count == 0 and hist.window_values() == []


def test_health_snapshot_reads_only_published_keys():
    reg = MetricsRegistry()
    assert health_snapshot(reg) == {}
    reg.gauge("health.skip_count").set(3)
    reg.gauge("health.consecutive_skips").set(2)
    reg.gauge("health.rollbacks_remaining").set(1)
    reg.gauge("server.blacklist_size").set(4)
    reg.counter("server.quarantined").inc()
    assert health_snapshot(reg) == {
        "skip_count": 3, "consecutive_skips": 2,
        "rollbacks_remaining": 1, "blacklist_size": 4,
        "quarantined": 1}


# -- profiler hook ---------------------------------------------------------


def test_profiler_hook_window_accounting(monkeypatch, tmp_path):
    calls = []

    class FakeProfiler(object):
        @staticmethod
        def start_trace(logdir):
            calls.append(("start", logdir))

        @staticmethod
        def stop_trace():
            calls.append(("stop", None))

    import jax
    monkeypatch.setattr(jax, "profiler", FakeProfiler)
    logdir = str(tmp_path / "prof")
    hook = ProfilerHook(logdir, start_step=2, stop_step=4)
    for _ in range(10):
        hook.step()
    assert hook.state == "done"
    assert calls == [("start", logdir), ("stop", None)]
    hook.stop()  # idempotent
    assert calls[-1] == ("stop", None) and len(calls) == 2


def test_profiler_hook_env_window(monkeypatch, tmp_path):
    monkeypatch.setenv("VELES_PROFILE", str(tmp_path))
    monkeypatch.setenv("VELES_PROFILE_WINDOW", "7:9")
    hook = ProfilerHook.from_env()
    assert hook.logdir == str(tmp_path)
    assert (hook.start_step, hook.stop_step) == (7, 9)
    monkeypatch.delenv("VELES_PROFILE")
    assert ProfilerHook.from_env() is None


# -- heartbeat -------------------------------------------------------------


def test_heartbeat_lines_validate(tmp_path):
    reg = MetricsRegistry()
    reg.counter("train.samples").inc(640)
    reg.histogram("step.train_s").observe(0.01)
    reg.gauge("health.skip_count").set(0)
    path = str(tmp_path / "hb.jsonl")
    heartbeat = Heartbeat(path, interval=0.05, registry=reg)
    heartbeat.start()
    time.sleep(0.2)
    reg.counter("train.samples").inc(640)
    heartbeat.stop()
    with open(path) as fin:
        lines = [json.loads(line) for line in fin if line.strip()]
    assert len(lines) >= 2  # periodic lines + the final one
    for record in lines:
        validate_heartbeat(record)
    assert lines[-1]["counters"]["train.samples"] == 1280
    assert lines[-1]["health"] == {"skip_count": 0}
    assert "step.train_s" in lines[-1]["histograms"]
    assert any("throughput_sps" in record for record in lines)


def test_heartbeat_stays_strict_json_under_nan(tmp_path):
    """A diverging run (NaN metric) must not poison the JSONL: bare
    NaN tokens are not RFC-8259 JSON and break non-Python consumers."""
    reg = MetricsRegistry()
    reg.gauge("metric.train").set(float("nan"))
    reg.histogram("step.train_s").observe(0.01)

    class FakeDecision(object):
        epoch_number = 1
        epoch_metrics = [None, float("nan"), 2.0]

    class FakeWorkflow(object):
        decision = FakeDecision()

    path = str(tmp_path / "nan_hb.jsonl")
    heartbeat = Heartbeat(path, interval=60, registry=reg,
                          workflow=FakeWorkflow())
    heartbeat.write_line()
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    record = json.loads(raw)
    validate_heartbeat(record)
    assert record["gauges"]["metric.train"] is None
    assert record["metrics"] == [None, None, 2.0]


def test_decision_never_publishes_nonfinite_metric_gauge():
    from veles_tpu.observe.metrics import registry as global_registry
    from veles_tpu.models.decision import DecisionGD
    from veles_tpu.dummy import DummyWorkflow

    global_registry.reset()
    decision = DecisionGD(DummyWorkflow(), watchdog=False)
    decision.class_lengths = [0, 0, 10]
    decision.epoch_n_err = [0, 0, float("nan")]
    decision._record_class_metric(2)  # TRAIN
    assert decision.epoch_metrics[2] != decision.epoch_metrics[2]  # NaN
    assert global_registry.peek("metric.train") is None
    decision.epoch_n_err = [0, 0, 2]
    decision._record_class_metric(2)
    assert global_registry.peek("metric.train").value == 20.0


def test_validate_heartbeat_rejects_malformed():
    with pytest.raises(ValueError):
        validate_heartbeat([])
    with pytest.raises(ValueError, match="missing"):
        validate_heartbeat({"kind": "heartbeat"})


# -- print_stats baseline-vs-cumulative semantics --------------------------


def _two_run_workflow():
    from veles_tpu.dummy import DummyUnit, DummyWorkflow
    wf = DummyWorkflow()
    unit = DummyUnit(wf)
    unit.name = "Worker"
    unit.link_from(wf.start_point)
    wf.end_point.link_from(unit)
    wf.initialize()
    return wf, unit


def test_print_stats_baseline_vs_cumulative_run_counts():
    wf, unit = _two_run_workflow()
    wf.run()
    wf.run()
    # distributed-method timers participate in the same delta logic
    wf.generate_data_for_master()

    def stats(**kwargs):
        buf = io.StringIO()
        wf.print_stats(out=buf, **kwargs)
        return buf.getvalue()

    per_run = stats()
    assert "(this run)" in per_run
    match = re.search(r"Worker \((\d+) runs\)", per_run)
    assert match and int(match.group(1)) == 1  # only the LAST run
    cumulative = stats(cumulative=True)
    assert "(this run)" not in cumulative
    match = re.search(r"Worker \((\d+) runs\)", cumulative)
    assert match and int(match.group(1)) == 2  # lifetime total
    assert "generate_data_for_master" in cumulative


def test_print_stats_method_timer_deltas_reset_per_run():
    wf, unit = _two_run_workflow()
    wf.generate_data_for_master()  # before any run: baseline-less
    wf.run()
    # nothing distributed happened DURING this run, so the per-run view
    # must not re-attribute the pre-run call
    buf = io.StringIO()
    wf.print_stats(out=buf)
    assert "generate_data_for_master" not in buf.getvalue()


# -- smoke: trace + heartbeat over a real fused workflow -------------------


def _trace_smoke_run(cpu_device, tmp_path, pipeline):
    """2-epoch fused run through the LAUNCHER with --trace semantics:
    returns (trace doc, heartbeat lines)."""
    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.prng import RandomGenerator
    from tests.test_models import BlobsLoader

    trace_path = str(tmp_path / "run_trace.json")
    hb_path = str(tmp_path / "run_hb.jsonl")
    prng.get().seed(321)
    launcher = Launcher(trace=trace_path, metrics_interval=0.05,
                        metrics_path=hb_path)
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
            prng=RandomGenerator("observe", seed=11)),
        decision_config=dict(max_epochs=2),
    ).fuse(pipeline=pipeline)
    launcher.initialize(device=cpu_device)
    launcher.run()
    with open(trace_path) as fin:
        doc = json.load(fin)
    with open(hb_path) as fin:
        lines = [json.loads(line) for line in fin if line.strip()]
    return doc, lines


def test_smoke_trace_and_heartbeat_schema(cpu_device, tmp_path):
    registry.reset()
    doc, lines = _trace_smoke_run(cpu_device, tmp_path, pipeline=True)
    validate_trace(doc)  # Perfetto-loadable, spans nest per track
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    # unit-run spans, fused-step spans, prefetcher-stage spans
    assert "FusedTrainer" in names
    assert "fused.train_step" in names
    assert {"pipeline.fill", "pipeline.h2d", "pipeline.wait"} <= names
    assert any(name.endswith(".run") for name in names)  # workflow span
    # worker-thread stages live on their own track
    graph_tids = {e["tid"] for e in doc["traceEvents"]
                  if e["ph"] == "X" and e["name"] == "fused.train_step"}
    fill_tids = {e["tid"] for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "pipeline.fill"}
    assert graph_tids and fill_tids and not (graph_tids & fill_tids)
    # heartbeat: at least the final line, every line schema-valid
    assert lines
    for record in lines:
        validate_heartbeat(record)
    final = lines[-1]
    assert final["counters"]["train.steps"] > 0
    assert final["counters"]["train.samples"] > 0
    assert final["histograms"]["step.train_s"]["count"] > 0
    assert final["epoch"] >= 2
    assert final["workflow"] == "StandardWorkflow"
    # health counters rode the decision's class-end sync into the line
    assert final["health"].get("skip_count") == 0


def _host_lines(xplane_path):
    """[[(name, start_ns, end_ns, stats)]] per line of ``/host:CPU``."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events])
    return lines


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["prefetcher", "synchronous"])
def test_spans_land_on_the_profilers_clock(cpu_device, tmp_path,
                                           pipeline):
    """A profiler session started by ANYONE (here the test, not
    ProfilerHook) gets the program's spans as ``veles/<span>`` on
    ``/host:CPU`` of the same ``*.xplane.pb`` as the device ops, nested
    as the program nests them, a ``train_step`` step annotation per
    train step, and the worker's stages on their own line (with a
    Prefetcher; without one the loader's gather sits in its unit's
    span); the Chrome-JSON trace of the same run carries every span's
    parent."""
    import glob

    import jax

    from veles_tpu.observe.trace import tracer
    from tests.test_pipeline_input import _build_fused

    registry.reset()
    sw = _build_fused(cpu_device, pipeline, max_epochs=2)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the annotations, not every frame
    tracer.start()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        sw.run()
    finally:
        jax.profiler.stop_trace()
        tracer.stop()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    lines = _host_lines(path)

    def named(line, name):
        return [e for e in line if e[0] == name]

    def inside(inner, outers):
        return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)

    graph, = [line for line in lines if named(line, "veles/fused.dispatch")]
    steps = named(graph, "veles/fused.train_step")
    trainer = sw.fused_trainer
    assert len(steps) == trainer._iteration > 0
    units = named(graph, "veles/" + trainer.name)
    evals = named(graph, "veles/fused.eval_step")
    assert len(units) == len(steps) + len(evals)
    assert all(inside(step, units) for step in steps + evals)
    dispatches = named(graph, "veles/fused.dispatch")
    assert len(dispatches) == len(units)
    assert all(inside(d, steps + evals) for d in dispatches)
    stages = named(graph, "veles/fused.stage")
    assert len(stages) == len(units)
    assert all(inside(s, steps + evals) for s in stages)
    run, = named(graph, "veles/%s.run" % sw.name)
    assert all(inside(unit, [run]) for unit in units)
    hops = named(graph, "veles/workflow.hop")
    assert hops and all(inside(hop, [run]) for hop in hops)
    assert not any(inside(hop, units) for hop in hops)
    assert named(graph, "veles/decision.sync")
    # one step annotation per train step, around its dispatch alone
    marks = sorted(named(graph, "train_step"), key=lambda e: e[1])
    assert [m[3]["step_num"] for m in marks] == list(
        range(1, trainer._iteration + 1))
    assert all(inside(m, steps) for m in marks)
    train_dispatches = [d for d in dispatches if inside(d, steps)]
    assert all(inside(d, marks) for d in train_dispatches)
    loads = named(graph, "veles/" + sw.loader.name)
    if pipeline:
        # the worker's stages, the gather among them: another thread's
        # line
        assert named(graph, "veles/pipeline.wait")
        worker, = [line for line in lines
                   if named(line, "veles/pipeline.fill")]
        assert worker is not graph
        assert named(worker, "veles/pipeline.h2d")
        assert all(inside(g, named(worker, "veles/pipeline.fill"))
                   for g in named(worker, "veles/loader.gather"))
        assert not named(graph, "veles/pipeline.h2d")
        assert not named(graph, "veles/loader.gather")
    else:
        assert not any(named(line, "veles/pipeline.fill")
                       for line in lines)
        gathers = named(graph, "veles/loader.gather")
        assert len(gathers) == len(units)
        assert all(inside(g, loads) for g in gathers)

    # the tracer's view of the same run: a parent on every span
    events = tracer.events
    validate_trace({"traceEvents": events})
    spans = [e for e in events if e["ph"] == "X"]
    assert all("parent" in e and e["sid"] for e in spans)
    by_sid = {e["sid"]: e for e in spans}

    def parent_name(event):
        parent = by_sid.get(event["parent"])
        return parent and parent["name"]

    for event in spans:
        want = {"fused.dispatch": ("fused.train_step", "fused.eval_step"),
                "fused.stage": ("fused.train_step", "fused.eval_step"),
                "fused.train_step": (trainer.name,),
                "workflow.hop": (sw.name + ".run",),
                trainer.name: (sw.name + ".run",),
                "decision.sync": (sw.decision.name,),
                "pipeline.wait": (sw.loader.name,),
                "pipeline.fill": (None,), "pipeline.h2d": (None,),
                "loader.gather": ("pipeline.fill" if pipeline
                                  else sw.loader.name,),
                sw.name + ".run": (None,)}.get(event["name"])
        if want is not None:
            assert parent_name(event) in want, event
    # the same measurement in the registry: the histograms registered
    # at initialise hold one observation per span
    snap = registry.snapshot()["histograms"]
    assert snap["step.dispatch_s"]["count"] == len(steps)
    assert snap["step.eval_dispatch_s"]["count"] == len(evals)
    assert snap["step.stage_s"]["count"] == len(units)
    assert snap["workflow.hop_s"]["count"] == len(hops)
    assert snap["decision.sync_s"]["count"] == len(
        named(graph, "veles/decision.sync"))
    assert snap["loader.gather_s"]["count"] >= len(units)


def test_tracing_disabled_leaves_no_events_in_step_path(cpu_device):
    """The acceptance check's cheap proxy for 'no added host syncs':
    with tracing off, a fused run records nothing into the global
    tracer and the instrumented sites never build event payloads."""
    from veles_tpu.observe.trace import tracer
    from tests.test_pipeline_input import _build_fused

    registry.reset()
    assert not tracer.enabled
    before = len(tracer.events)
    sw = _build_fused(cpu_device, pipeline=False, max_epochs=2)
    sw.run()
    assert len(tracer.events) == before
    # the metrics side still collected (always-on, plain-host floats)
    assert registry.counter("train.steps").value > 0
    snap = registry.histogram("step.train_s").snapshot()
    assert snap["count"] > 0 and snap["p50"] > 0.0
