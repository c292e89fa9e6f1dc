"""Serving-subsystem tests (docs/serving.md): AOT ladder dispatch,
continuous-batching bit-equality with sequential inference (including
padded-tail masking), the warm persistent-cache zero-compile receipt,
SLO tripwires under an injected stall, overload shedding with the
503/retry_after protocol, OOM ladder degradation, and the RESTfulAPI
compatibility front."""

import json
import urllib.error
import urllib.request

import numpy
import pytest

from veles_tpu import chaos
from veles_tpu.backends import Device
from veles_tpu.compiler import LayerPlan
from veles_tpu.observe.metrics import registry
from veles_tpu.serve import (
    AOTEngine, ContinuousBatcher, ServeOverload, ServeService,
    model_digest, serve_snapshot)

pytestmark = pytest.mark.serve


def _mlp_spec(seed=0, fan_in=16, hidden=16, classes=4):
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


@pytest.fixture(scope="module")
def engine():
    """Shared AOT ladder over a random-parameter MLP.  The ladder
    starts at 8 ON PURPOSE: XLA:CPU lowers the rung-1 program to a
    different vector-matrix kernel whose rows differ from the batched
    rungs by ~1 ulp, while every rung >= the vector width produces
    bit-identical rows (measured; see serve/engine.py docstring) — the
    bit-equality contract below holds within such a ladder."""
    plans, params = _mlp_spec()
    eng = AOTEngine(plans, params, (16,), ladder=(8, 32),
                    device=Device(backend="cpu"))
    eng.compile()
    return eng


def _co_batch(batcher, samples, timeout=30.0):
    """Submit every sample inside ONE collect window (the batcher's
    queue-delay makes the worker wait for them), then gather results in
    submission order — a deterministic stand-in for concurrent
    clients."""
    requests = [batcher.submit(s) for s in samples]
    results, errors = [], []
    for i, req in enumerate(requests):
        if not req.done.wait(timeout):
            errors.append((i, TimeoutError("request %d timed out" % i)))
            results.append(None)
        elif req.error is not None:
            errors.append((i, req.error))
            results.append(None)
        else:
            results.append(req.result)
    return results, errors


# -- (a) batching correctness ------------------------------------------------


def test_batched_bit_identical_to_sequential(engine):
    """Continuously-batched results == sequential single-sample
    inference, bit for bit, including a padded tail (13 requests on an
    8/32 ladder co-batch into a 32-rung with 19 padding rows)."""
    rng = numpy.random.RandomState(1)
    samples = rng.rand(13, 16).astype(numpy.float32)
    sequential = numpy.stack(
        [engine.infer(samples[i])[0] for i in range(len(samples))])

    hist = registry.histogram("serve.batch_size")
    hist.reset()
    batcher = ContinuousBatcher(engine, max_delay_s=0.5).start()
    try:
        results, errors = _co_batch(batcher, list(samples))
    finally:
        batcher.stop()
    assert not errors, errors
    batched = numpy.stack(results)
    assert batched.shape == sequential.shape
    assert (batched == sequential).all(), \
        numpy.abs(batched - sequential).max()
    # the equality must have been proven ON a co-batched path, not 13
    # singleton batches racing through
    assert hist.count >= 1
    assert max(hist.window_values()) > 1


def test_padded_tail_never_leaks(engine):
    """Padding rows cannot influence real rows: the same 5 samples
    dispatched on the 8-rung with zero padding and with garbage
    padding produce identical real rows (no cross-row reduction in the
    forward; the per-row softmax stays per-row)."""
    rng = numpy.random.RandomState(2)
    x = rng.rand(5, 16).astype(numpy.float32)
    zeros = numpy.zeros((8, 16), numpy.float32)
    zeros[:5] = x
    garbage = (rng.rand(8, 16).astype(numpy.float32) * 1e3)
    garbage[:5] = x
    out_zeros = numpy.asarray(
        engine.run(engine.device.put(zeros), 8))[:5]
    out_garbage = numpy.asarray(
        engine.run(engine.device.put(garbage), 8))[:5]
    assert (out_zeros == out_garbage).all()


def test_engine_sequential_shapes(engine):
    """infer() accepts a bare sample and a batch; an overflowing batch
    chunks through the top rung."""
    rng = numpy.random.RandomState(3)
    one = engine.infer(rng.rand(16).astype(numpy.float32))
    assert one.shape == (1, 4)
    big = rng.rand(70, 16).astype(numpy.float32)  # > max rung 32
    out = engine.infer(big)
    assert out.shape == (70, 4)
    ref = numpy.stack([engine.infer(big[i])[0] for i in range(70)])
    # chunking pads the 6-row tail to the 8-rung; still bit-equal
    assert (out == ref).all()


# -- (b) warm persistent cache ----------------------------------------------


def test_warm_cache_reports_zero_new_compiles():
    """A second engine start against the warm persistent cache
    performs 0 new backend compiles: every compile request is answered
    by a cache hit (asserted via the xla_introspect compile.count /
    compile.cache_hits counters that feed the receipt).  The cache is
    the ONE directory the environment names (tests/conftest.py places
    it per session); the architecture is this test's own, so its first
    start is cold whatever ran before."""
    import os

    from veles_tpu.observe import xla_introspect

    plans, params = _mlp_spec(seed=7, hidden=23)
    cold = AOTEngine(plans, params, (16,), ladder=(8, 32),
                     device=Device(backend="cpu"))
    cold_receipt = cold.compile()
    assert cold_receipt["new_compiles"] >= 2  # one per rung, cold
    assert cold_receipt["cache_dir"] == \
        os.environ["JAX_COMPILATION_CACHE_DIR"]

    before = xla_introspect.compile_snapshot()
    warm = AOTEngine(plans, params, (16,), ladder=(8, 32),
                     device=Device(backend="cpu"))
    warm_receipt = warm.compile()
    after = xla_introspect.compile_snapshot()
    assert warm_receipt["new_compiles"] == 0, warm_receipt
    assert warm_receipt["cache_hits"] >= 2
    # the raw counters agree: every backend-compile request during the
    # warm start was served from the cache
    assert (after["count"] - before["count"]
            == after["cache_hits"] - before["cache_hits"])
    # same architecture, new weights -> same digest (a retrained model
    # swaps in with zero compiles); new topology -> different digest
    from veles_tpu.serve.engine import engine_digest_extra
    extra = engine_digest_extra(numpy.float32)
    plans2, params2 = _mlp_spec(seed=8, hidden=23)
    assert model_digest(plans2, params2, (16,),
                        extra=extra) == warm.digest
    plans3, params3 = _mlp_spec(seed=7, hidden=32)
    assert model_digest(plans3, params3, (16,),
                        extra=extra) != warm.digest

    rng = numpy.random.RandomState(4)
    x = rng.rand(3, 16).astype(numpy.float32)
    assert (warm.infer(x) == cold.infer(x)).all()


# -- (c) SLO tripwires under an injected stall -------------------------------


@pytest.mark.chaos
def test_slo_violations_fire_under_stall(engine):
    """serve.stall chaos makes every batch ~60 ms; with a 10 ms p99
    budget the SLO watch must trip the counter and record the
    trace/flight instant."""
    from veles_tpu.observe.trace import tracer

    before = registry.counter("serve.slo_violations").value
    chaos.install(chaos.FaultPlan(seed=1).add(
        "serve.stall", "stall", param=0.06))
    tracer.start()
    batcher = ContinuousBatcher(
        engine, max_delay_s=0.001, slo_p99_ms=10.0, slo_check_every=1)
    batcher.start()
    try:
        for _ in range(3):
            batcher.infer(numpy.zeros(16, numpy.float32))
    finally:
        batcher.stop()
        chaos.uninstall()
        tracer.stop()
    assert registry.counter("serve.slo_violations").value > before
    names = [e["name"] for e in tracer.events]
    assert "serve.slo_violation" in names
    snap = serve_snapshot()
    assert snap["slo_violations"] > 0
    assert snap["p99_ms"] > 10.0


# -- overload + degradation --------------------------------------------------


@pytest.mark.chaos
def test_overload_sheds_with_retry_after(engine):
    """Past max_queue pending requests submit() sheds with a transient
    ServeOverload instead of growing the queue; chaos serve.drop sheds
    deterministically."""
    chaos.install(chaos.FaultPlan(seed=1).add(
        "serve.stall", "stall", param=0.2))
    batcher = ContinuousBatcher(engine, max_delay_s=0.0, max_queue=2)
    batcher.start()
    shed = []
    try:
        for i in range(30):
            try:
                batcher.submit(numpy.zeros(16, numpy.float32))
            except ServeOverload as exc:
                shed.append(exc)
    finally:
        batcher.stop()
        chaos.uninstall()
    assert shed, "queue grew without bound"
    assert all(exc.retry_after > 0 for exc in shed)

    chaos.install(chaos.FaultPlan(seed=1).add("serve.drop", "drop",
                                              nth=1))
    batcher = ContinuousBatcher(engine).start()
    try:
        with pytest.raises(ServeOverload):
            batcher.submit(numpy.zeros(16, numpy.float32))
        # only the first submit was armed; the second serves fine
        assert batcher.infer(
            numpy.zeros(16, numpy.float32)).shape == (4,)
    finally:
        batcher.stop()
        chaos.uninstall()


@pytest.mark.chaos
def test_oom_degrades_ladder_and_replays(engine):
    """A RESOURCE_EXHAUSTED dispatch caps the ladder below the failing
    rung and replays the batch in chunks: every request still gets its
    bit-exact answer, only slower."""
    rng = numpy.random.RandomState(5)
    samples = rng.rand(13, 16).astype(numpy.float32)
    sequential = numpy.stack(
        [engine.infer(samples[i])[0] for i in range(len(samples))])
    chaos.install(chaos.FaultPlan(seed=1).add("serve.oom", "oom",
                                              nth=1))
    batcher = ContinuousBatcher(engine, max_delay_s=0.5).start()
    try:
        # 13 requests inside one collect window -> the 32-rung, whose
        # dispatch the armed fault kills
        results, errors = _co_batch(batcher, list(samples))
        assert not errors, errors
        assert (numpy.stack(results) == sequential).all()
        assert batcher._rung_cap == 8  # capped below the 32-rung
        assert registry.gauge("serve.rung_cap").value == 8
    finally:
        batcher.stop()
        chaos.uninstall()


# -- HTTP front + compatibility ---------------------------------------------


def test_service_http_roundtrip_and_healthz(engine):
    svc = ServeService(engine, labels_mapping={0: "a", 1: "b", 2: "c",
                                               3: "d"},
                       max_delay_s=0.002)
    svc.start_background()
    try:
        base = "http://127.0.0.1:%d" % svc.port
        rng = numpy.random.RandomState(6)
        batch = rng.rand(3, 16).astype(numpy.float32)
        req = urllib.request.Request(
            base + "/infer",
            data=json.dumps({"input": batch.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            answer = json.loads(resp.read())
        assert len(answer["result"]) == 3
        assert set(answer["result"]) <= {"a", "b", "c", "d"}
        assert len(answer["probabilities"]) == 3
        ref = engine.infer(batch)
        # float32 -> json -> float32 is lossless: the HTTP answer is
        # bit-identical to the in-process engine
        assert (numpy.asarray(answer["probabilities"],
                              numpy.float32) == ref).all()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health["compile"]["rungs"] == [8, 32]
        assert "queue_depth" in health["serve"]
        assert health["model_digest"] == engine.digest
        with urllib.request.urlopen(base + "/metrics.json",
                                    timeout=10) as r:
            metrics = json.loads(r.read())
        assert "serve.latency_s" in metrics["histograms"]
        assert "http.request_s" in metrics["histograms"]
    finally:
        svc.stop()


@pytest.mark.chaos
def test_service_answers_503_on_shed(engine):
    chaos.install(chaos.FaultPlan(seed=1).add("serve.drop", "drop"))
    svc = ServeService(engine)
    svc.start_background()
    try:
        req = urllib.request.Request(
            "http://127.0.0.1:%d/infer" % svc.port,
            data=json.dumps(
                {"input": [0.0] * 16}).encode())
        with pytest.raises(urllib.error.HTTPError) as info:
            urllib.request.urlopen(req, timeout=10)
        assert info.value.code == 503
        body = json.loads(info.value.read())
        assert body["retry_after"] > 0
        assert info.value.headers.get("Retry-After") is not None
    finally:
        svc.stop()
        chaos.uninstall()


def test_format_result_vectorized_contract():
    """format_result keeps the REST contract after the per-batch
    vectorization: one tolist over the whole (viewed, never re-copied)
    block, scalar result for single-row payloads, mapped labels when a
    mapping exists and plain ints (vectorized box) when not."""
    from veles_tpu.serve import format_result

    probs = numpy.array([[0.1, 0.9], [0.8, 0.2]], numpy.float32)
    out = format_result(probs, {0: "a", 1: "b"})
    assert out["result"] == ["b", "a"]
    assert out["probabilities"] == probs.tolist()
    unmapped = format_result(probs)
    assert unmapped["result"] == [1, 0]
    assert all(isinstance(label, int) for label in unmapped["result"])
    single = format_result(probs[0])
    assert single["result"] == 1
    assert single["probabilities"] == [probs[0].tolist()]
    one_row = format_result(probs[:1], {0: "a", 1: "b"})
    assert one_row["result"] == "b"
    # list payloads (the RESTful compat front) still work
    assert format_result(probs.tolist())["result"] == [1, 0]


def test_restful_api_delegates_to_engine():
    """The compatibility unit serves the old contract through the AOT
    engine: programmatic infer() without a started server uses the
    sequential engine path, and the engine mirrors the trained
    workflow's forward exactly."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.restful_api import RESTfulAPI
    from tests.test_models import BlobsLoader

    sw = StandardWorkflow(
        DummyWorkflow().workflow,
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "learning_rate": 0.05, "gradient_moment": 0.9},
            {"type": "softmax", "output_sample_shape": 4,
             "learning_rate": 0.05, "gradient_moment": 0.9},
        ],
        loader_factory=lambda w: BlobsLoader(
            w, minibatch_size=64,
            prng=RandomGenerator("serve-rest", seed=21)),
        decision_config=dict(max_epochs=2),
    )
    sw.initialize(device=Device(backend="cpu"))
    sw.run()
    api = RESTfulAPI(sw, ladder=(1, 8))
    api.initialize()
    try:
        x = sw.loader.original_data.mem[0]
        answer = api.infer(x.tolist())
        assert answer["result"] == sw.loader.original_labels[0]
        assert abs(sum(answer["probabilities"][0]) - 1.0) < 1e-3
        assert api.requests_served == 1
        assert api.engine.compile_receipt is not None
    finally:
        api.stop()
