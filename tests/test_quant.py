"""Int8 quantized-inference tests (docs/serving.md "Quantized
ladder"): the int8 Pallas matmul/conv bit-exactness contract vs the
jitted interpret-mode reference, the post-training quantization pass
(per-channel symmetric scales, percentile calibration, zero-channel /
saturating-outlier edge cases, spec round-trip bit-stability), the
f32-vs-int8 model-digest separation, the quantized AOTEngine
(accuracy parity, warm-restart 0-compile receipt, serve_snapshot
flag), the ``matmul_int8`` schedule-cache family, and the
quantized-candidate canary e2e through ``CanaryCutover``."""

import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.compiler import LayerPlan
from veles_tpu.models.all2all import All2AllSoftmax, All2AllTanh
from veles_tpu.quant import (
    build_quantized_forward, calibrate_activations, is_quantized_params,
    quantize_model_spec, quantize_weights)
from veles_tpu.serve.engine import (
    AOTEngine, engine_digest_extra, model_digest)
from tests.test_serve import _mlp_spec

pytestmark = pytest.mark.quant


def _quantized_mlp(seed=5, fan_in=16, hidden=32, classes=4,
                   n_calib=256):
    rng = numpy.random.RandomState(seed)
    plans = [LayerPlan(All2AllTanh), LayerPlan(All2AllSoftmax)]
    params = [
        {"weights": (rng.randn(fan_in, hidden) * 0.3).astype(
            numpy.float32),
         "bias": (rng.randn(hidden) * 0.1).astype(numpy.float32)},
        {"weights": (rng.randn(hidden, classes) * 0.3).astype(
            numpy.float32),
         "bias": (rng.randn(classes) * 0.1).astype(numpy.float32)},
    ]
    samples = rng.rand(n_calib, fan_in).astype(numpy.float32)
    qparams, calib = quantize_model_spec(plans, params, samples)
    return plans, params, qparams, calib


# -- (a) int8 Pallas kernel bit-exactness ------------------------------------


def test_int8_matmul_bitexact_vs_reference():
    """The acceptance anchor: the tiled int8 Pallas matmul (interpret
    mode on CPU) matches the JITTED untiled reference bit-exactly —
    integer accumulation is exact under any tile grouping and the
    dequant epilogue is the same FMA-contracted f32 expression.
    Shapes exercise padding on every axis and multi-block K walks."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.matmul_int8 import (matmul_int8,
                                           matmul_int8_reference)

    rng = numpy.random.RandomState(3)
    ref = jax.jit(matmul_int8_reference)
    for m, k, n, blocks in [(37, 91, 53, (64, 128, 128)),
                            (300, 500, 260, (64, 128, 128)),
                            (8, 1024, 128, (32, 128, 128)),
                            (129, 257, 385, None)]:
        a = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.randint(-127, 128, (k, n)), jnp.int8)
        scale = jnp.asarray(rng.rand(n).astype(numpy.float32) * 0.01)
        bias = jnp.asarray(rng.randn(n).astype(numpy.float32))
        out = matmul_int8(a, b, scale, bias, blocks=blocks)
        want = ref(a, b, scale, bias)
        assert out.dtype == jnp.float32
        assert (numpy.asarray(out) == numpy.asarray(want)).all(), \
            (m, k, n, blocks)
    # scalar scale, no bias — the other epilogue arity
    a = jnp.asarray(rng.randint(-127, 128, (40, 200)), jnp.int8)
    b = jnp.asarray(rng.randint(-127, 128, (200, 70)), jnp.int8)
    out = matmul_int8(a, b, jnp.float32(0.005), blocks=(32, 128, 128))
    want = jax.jit(lambda a, b, s: matmul_int8_reference(a, b, s))(
        a, b, jnp.float32(0.005))
    assert (numpy.asarray(out) == numpy.asarray(want)).all()


def test_int8_matmul_rejects_non_int8():
    import jax.numpy as jnp

    from veles_tpu.ops.matmul_int8 import matmul_int8
    with pytest.raises(TypeError):
        matmul_int8(jnp.zeros((4, 4), jnp.float32),
                    jnp.zeros((4, 4), jnp.int8), 1.0)


def test_int8_conv_matches_dequantized_f32_conv():
    """conv2d_int8 == the f32 conv of the dequantized integers (the
    patches are pure data movement, the contraction is exact int32):
    agreement to f32 rounding noise across stride/padding configs."""
    import jax.numpy as jnp
    from jax import lax

    from veles_tpu.ops.matmul_int8 import conv2d_int8

    rng = numpy.random.RandomState(7)
    for padding, sliding in [((0, 0, 0, 0), (1, 1)),
                             ((1, 1, 1, 1), (2, 2)),
                             ((2, 1, 0, 1), (1, 2))]:
        x = jnp.asarray(rng.randint(-127, 128, (2, 9, 11, 3)),
                        jnp.int8)
        w = jnp.asarray(rng.randint(-127, 128, (3, 3, 3, 5)),
                        jnp.int8)
        scale = jnp.asarray(rng.rand(5).astype(numpy.float32) * 0.01)
        bias = jnp.asarray(rng.randn(5).astype(numpy.float32))
        got = conv2d_int8(x, w, scale, bias, padding=padding,
                          sliding=sliding)
        left, top, right, bottom = padding
        sx, sy = sliding
        zf = lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32), (sy, sx),
            ((top, bottom), (left, right)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        want = zf * scale[None, None, None, :] + bias[None, None,
                                                      None, :]
        assert got.shape == want.shape
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(want),
                              rtol=1e-5, atol=1e-4), (padding, sliding)


# -- (b) the quantization pass -----------------------------------------------


def test_quantize_weights_per_channel_edges():
    """Zero-point-free symmetric edge cases: an all-zero channel gets
    scale 1.0 and zero codes (no div-by-zero, exact dequant); the
    largest magnitude in every channel lands exactly on +/-127; values
    beyond a channel's own max cannot exist by construction."""
    w = numpy.zeros((4, 3), numpy.float32)
    w[:, 0] = [1.0, -2.0, 0.5, 2.0]       # symmetric-ish channel
    w[:, 1] = 0.0                          # all-zero channel
    w[:, 2] = [1e-3, -1e-3, 5e-4, 1e-3]    # tiny channel
    q, scales = quantize_weights(w)
    assert q.dtype == numpy.int8 and scales.shape == (3,)
    assert scales[1] == 1.0 and (q[:, 1] == 0).all()
    assert abs(q[:, 0]).max() == 127
    assert abs(q[:, 2]).max() == 127  # per-channel: tiny channel keeps
    #                                   its full 8-bit resolution
    # round-trip error bounded by half a step per channel
    deq = q.astype(numpy.float32) * scales[None, :]
    assert numpy.abs(deq - w).max() <= (scales.max() / 2 + 1e-9)


def test_calibration_percentile_clips_saturating_outliers():
    """Percentile calibration deliberately clips the outlier tail: the
    scale stays near the bulk of the distribution, the clip fraction
    is recorded (and rides the serve.quant.clip_fraction gauge), and
    the quantized forward stays finite through saturation."""
    import jax.numpy as jnp

    from veles_tpu.observe.metrics import registry

    rng = numpy.random.RandomState(9)
    plans = [LayerPlan(All2AllTanh)]
    params = [{"weights": (rng.randn(8, 4) * 0.3).astype(numpy.float32),
               "bias": numpy.zeros(4, numpy.float32)}]
    samples = rng.rand(512, 8).astype(numpy.float32)
    samples[::97] *= 1e3  # saturating outlier rows
    minmax = calibrate_activations(plans, params, samples,
                                   mode="minmax")
    pct = calibrate_activations(plans, params, samples,
                                mode="percentile", percentile=99.0)
    assert pct.layers[0]["act_scale"] < minmax.layers[0]["act_scale"]
    assert minmax.layers[0]["clip_fraction"] == 0.0
    assert pct.layers[0]["clip_fraction"] > 0.0
    gauge = registry.peek("serve.quant.clip_fraction")
    assert gauge is not None and gauge.value == round(
        pct.clip_fraction, 6)
    # saturation stays finite end to end
    qparams, _ = quantize_model_spec(plans, params, calibration=pct)
    fwd = build_quantized_forward(plans)
    out = fwd([{k: jnp.asarray(v) for k, v in qparams[0].items()}],
              jnp.asarray(samples[:8]))
    assert bool(jnp.isfinite(out).all())


def test_per_channel_beats_per_tensor_on_skewed_mlp():
    """A weight matrix with a 100x inter-channel magnitude skew: one
    per-tensor scale crushes the small channels' resolution; the
    per-channel pass keeps every channel's full 8-bit grid, so its
    output error must be strictly smaller."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.compiler import build_forward

    rng = numpy.random.RandomState(11)
    plans = [LayerPlan(All2AllTanh), LayerPlan(All2AllSoftmax)]
    w0 = (rng.randn(16, 32) * 0.02).astype(numpy.float32)
    w0[:, ::4] *= 100.0  # channel skew
    params = [
        {"weights": w0,
         "bias": numpy.zeros(32, numpy.float32)},
        {"weights": (rng.randn(32, 4) * 0.3).astype(numpy.float32),
         "bias": numpy.zeros(4, numpy.float32)},
    ]
    samples = rng.rand(256, 16).astype(numpy.float32)
    x = jnp.asarray(rng.rand(64, 16).astype(numpy.float32))
    ref = jax.jit(build_forward(plans))(
        [{k: jnp.asarray(v) for k, v in e.items()} for e in params], x)
    errs = {}
    for gran in ("channel", "tensor"):
        qp, _ = quantize_model_spec(plans, params, samples,
                                    weight_granularity=gran)
        out = jax.jit(build_quantized_forward(plans))(
            [{k: jnp.asarray(v) for k, v in e.items()} for e in qp], x)
        errs[gran] = float(jnp.max(jnp.abs(out - ref)))
    assert errs["channel"] < errs["tensor"], errs


def test_quantized_spec_roundtrip_bit_stable(tmp_path):
    """The quantized spec round-trips through export_model_spec /
    import_file with bit-identical serving: scales and int8 codes
    survive the pickle byte-for-byte, the restored engine shares the
    original's digest, and re-quantizing the same params with the same
    calibration reproduces the identical artifacts."""
    from veles_tpu.serve.freshness import export_model_spec
    from veles_tpu.snapshotter import SnapshotterBase

    plans, params, qparams, calib = _quantized_mlp()
    path = str(tmp_path / "qspec.pickle")
    export_model_spec(path, plans, qparams, (16,))
    restored = SnapshotterBase.import_file(path, fallback=False)
    rparams = [dict(e) for e in restored["params"]]
    for orig, back in zip(qparams, rparams):
        assert sorted(orig) == sorted(back)
        for key in orig:
            assert (numpy.asarray(orig[key])
                    == numpy.asarray(back[key])).all()
            assert numpy.asarray(orig[key]).dtype \
                == numpy.asarray(back[key]).dtype
    # determinism: same params + same calibration -> identical pass
    qparams2, _ = quantize_model_spec(plans, params, calibration=calib)
    for a, b in zip(qparams, qparams2):
        for key in a:
            assert (numpy.asarray(a[key]) == numpy.asarray(b[key])).all()
    # and the restored spec serves bit-identically
    eng = AOTEngine(plans, qparams, (16,), ladder=(8,),
                    device=Device(backend="cpu"))
    eng.compile()
    eng2 = AOTEngine(list(restored["plans"]), rparams,
                     tuple(restored["sample_shape"]), ladder=(8,),
                     device=Device(backend="cpu"))
    eng2.compile()
    assert eng2.digest == eng.digest
    x = numpy.random.RandomState(4).rand(8, 16).astype(numpy.float32)
    assert (eng.infer(x) == eng2.infer(x)).all()


# -- (c) digest separation ---------------------------------------------------


def test_model_digest_f32_int8_collision_impossible():
    """The satellite regression: a quantized spec and its f32 source
    have identical topology and weight SHAPES — param dtypes and the
    quantization artifacts must still separate the digests, or the two
    engines would share one persistent compile cache entry and one
    freshness last-good identity.  The engine input dtype rides the
    digest too (f32-in vs bf16-in is a different compiled program)."""
    plans, params, qparams, _ = _quantized_mlp()
    extra = engine_digest_extra(numpy.float32)
    d_f32 = model_digest(plans, params, (16,), extra=extra)
    d_int8 = model_digest(plans, qparams, (16,), extra=extra)
    assert d_f32 != d_int8
    # engines agree with the module-level recipe
    e_f32 = AOTEngine(plans, params, (16,), device=Device(backend="cpu"))
    e_int8 = AOTEngine(plans, qparams, (16,),
                       device=Device(backend="cpu"))
    assert e_f32.digest == d_f32 and e_int8.digest == d_int8
    assert e_int8.quantized and not e_f32.quantized
    # input-dtype separation (same params, different ladder input)
    assert model_digest(plans, params, (16,),
                        extra=engine_digest_extra("float32")) != \
        model_digest(plans, params, (16,),
                     extra=engine_digest_extra("bfloat16"))


# -- (d) the quantized engine ------------------------------------------------


def test_quantized_engine_parity_and_snapshot_flag():
    """A quantized engine beside its f32 source: sub-percent top-1
    disagreement and small probability divergence on a seeded stream
    (random-weight MLPs have near-tie rows, so the bound is loose
    compared to the trained-zoo QUANT.json receipt), and the
    serve_snapshot/healthz quantized flag flips with the engine."""
    from veles_tpu.observe.metrics import registry
    from veles_tpu.serve.batcher import serve_snapshot

    plans, params, qparams, _ = _quantized_mlp(fan_in=16, hidden=32,
                                               classes=10)
    f32 = AOTEngine(plans, params, (16,), ladder=(8, 32),
                    device=Device(backend="cpu"))
    f32.compile()
    assert registry.peek("serve.quantized").value == 0
    q = AOTEngine(plans, qparams, (16,), ladder=(8, 32),
                  device=Device(backend="cpu"))
    receipt = q.compile()
    assert receipt["quantized"] is True
    assert registry.peek("serve.quantized").value == 1
    assert serve_snapshot().get("quantized") == 1
    x = numpy.random.RandomState(2).rand(128, 16).astype(numpy.float32)
    y32, y8 = f32.infer(x), q.infer(x)
    assert float((y32.argmax(1) != y8.argmax(1)).mean()) <= 0.05
    assert float(numpy.abs(y32 - y8).max()) < 0.05


def test_quantized_warm_restart_zero_compiles():
    """Acceptance: warm restart of a quantized engine = 0 new backend
    compiles — the int8 Pallas forward persists in the compile cache
    like any other program.  The hidden width is this test's own, so
    the first start is cold whatever ran before."""
    plans, _params, qparams, _ = _quantized_mlp(hidden=29)
    cold = AOTEngine(plans, qparams, (16,), ladder=(8, 32),
                     device=Device(backend="cpu"))
    cold_receipt = cold.compile()
    assert cold_receipt["new_compiles"] >= 2
    warm = AOTEngine(plans, qparams, (16,), ladder=(8, 32),
                     device=Device(backend="cpu"))
    warm_receipt = warm.compile()
    assert warm_receipt["new_compiles"] == 0, warm_receipt
    assert warm_receipt["cache_hits"] >= 2
    x = numpy.random.RandomState(4).rand(8, 16).astype(
        numpy.float32)
    assert (warm.infer(x) == cold.infer(x)).all()


# -- (e) schedule-cache family -----------------------------------------------


def test_schedule_cache_serves_int8_family():
    """A planted matmul_int8 entry is consulted by blocks=None calls
    (counted as a tune.cache_hit) and — schedules change scheduling,
    never math — serves BIT-identical results to the static default;
    the int8 family's digest can never collide with the f32 matmul's
    for the same raw shape."""
    import jax.numpy as jnp

    from veles_tpu.observe.metrics import registry
    from veles_tpu.ops.matmul_int8 import matmul_int8
    from veles_tpu.tune.cache import cache_for, schedule_key
    from veles_tpu.tune.spec import matmul_int8_spec, matmul_spec

    m, k, n = 48, 300, 200
    spec = matmul_int8_spec(m, k, n)
    digest, payload = schedule_key(
        spec["op"], spec["shape"], spec["dtype"],
        spec["precision_level"], "cpu", spec["extra"])
    f32_spec = matmul_spec(m, k, n, "float32", 0)
    f32_digest, _ = schedule_key(
        f32_spec["op"], f32_spec["shape"], f32_spec["dtype"],
        f32_spec["precision_level"], "cpu", f32_spec["extra"])
    assert digest != f32_digest
    cache = cache_for()
    cache.put(digest, payload, {"blocks": [32, 128, 128]},
              source="test")
    rng = numpy.random.RandomState(6)
    a = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.randint(-127, 128, (k, n)), jnp.int8)
    scale = jnp.asarray(rng.rand(n).astype(numpy.float32) * 0.01)
    hits_before = registry.counter("tune.cache_hits").value
    tuned = matmul_int8(a, b, scale)          # consults the cache
    static = matmul_int8(a, b, scale, blocks=(256, 512, 512))
    assert registry.counter("tune.cache_hits").value > hits_before
    assert (numpy.asarray(tuned) == numpy.asarray(static)).all()


def test_int8_family_quantization_and_feasibility():
    """MXU legality for int8: genes snap to sublane-32/lane-128
    multiples, and the feasibility gate rejects VMEM-overflow tiles
    before any compile."""
    from veles_tpu.tune.spec import (TUNE_VMEM_BUDGET_BYTES,
                                     family_for, matmul_int8_spec)

    family = family_for("matmul_int8")
    spec = matmul_int8_spec(1000, 1000, 1000)
    sched = family.quantize(spec, {"bm": 100, "bn": 200, "bk": 300})
    bm, bn, bk = sched["blocks"]
    assert bm % 32 == 0 and bn % 128 == 0 and bk % 128 == 0
    assert family.validate(sched) is not None
    assert family.validate({"blocks": [8, 128, 128]}) is None  # f32 tile
    assert family.feasible(spec, {"blocks": [32, 128, 128]})
    huge = {"blocks": [1024, 2048, 2048]}
    footprint = (1024 * 2048 + 2048 * 2048 + 2 * 1024 * 2048 * 4
                 + 2 * 2048 * 4)
    assert footprint > TUNE_VMEM_BUDGET_BYTES
    assert not family.feasible(spec, huge)


# -- (f) freshness / canary --------------------------------------------------


def test_watcher_accepts_quantized_spec(tmp_path):
    """A published quantized model spec is 'just another digest' to the
    freshness watcher: manifest-verified, finite-gated (int8 arrays are
    vacuously finite) and handed over as a candidate — never escalated
    as poisoned."""
    from veles_tpu.health import all_finite
    from veles_tpu.observe.metrics import registry
    from veles_tpu.serve import SnapshotWatcher, export_model_spec
    from veles_tpu.snapshotter import publish_snapshot

    plans, _params, qparams, _ = _quantized_mlp()
    assert all_finite(qparams)  # the controller's finite gate passes
    path = str(tmp_path / "qspec.pickle")
    export_model_spec(path, plans, qparams, (16,))
    pub = str(tmp_path / "pub")
    publish_snapshot(path, pub)
    poisoned_before = registry.counter(
        "serve.freshness.poisoned_rejected").value
    got = []
    watcher = SnapshotWatcher(pub, callback=got.append)
    cand = watcher.poll_once()
    assert cand is not None and got and got[0] is cand
    assert is_quantized_params(cand.params)
    assert tuple(cand.sample_shape) == (16,)
    assert registry.counter(
        "serve.freshness.poisoned_rejected").value == poisoned_before


def test_quantized_candidate_canary_promote_then_divergence_rollback(
        tmp_path):
    """The satellite e2e: an int8-quantized candidate is canaried
    against the f32 fleet under mirrored traffic and PROMOTED (its
    divergence sits far inside the bound); a scale-corrupted quantized
    candidate — finite, loads fine, answers garbage — breaches the
    divergence bound and is auto-ROLLED BACK with zero new compiles."""
    import threading
    import time

    from veles_tpu.serve import value_digest
    from veles_tpu.snapshotter import publish_snapshot
    from tests.test_freshness import (_controller, _pool, _spec_path)

    pool = _pool(tmp_path, replicas=3, seed=11)
    # quantize the fleet's OWN model — the production scenario: the
    # candidate is the serving weights at the int8 level, calibrated
    # on the same distribution the clients drive
    calib = numpy.random.RandomState(1).rand(256, 16).astype(
        numpy.float32)
    qparams, _ = quantize_model_spec(pool.engine.plans,
                                     pool.engine.params, calib)
    pool.start()
    controller = _controller(pool, tmp_path, divergence_limit=0.2,
                             invalid_ttl_s=1.0)
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
    try:
        def publish(name, p):
            return publish_snapshot(
                _spec_path(tmp_path, name, p, pool.engine.plans),
                str(tmp_path / "publish"))

        def wait_cycle(ordinal, timeout=30.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                for entry in controller.history:
                    if entry["ordinal"] == ordinal:
                        return entry
                time.sleep(0.02)
            raise TimeoutError("no verdict for #%d" % ordinal)

        # the quantized candidate promotes: the fleet cuts over to the
        # int8 digest (per-replica AOT warm — new digest, new engines)
        entry = wait_cycle(publish("quant.pickle", qparams)["ordinal"])
        assert entry["verdict"] == "promoted", entry
        want = value_digest(qparams)
        for rep in pool.replicas:
            assert rep.engine.quantized
            assert value_digest(rep.engine.params) == want

        # a finite-but-garbage quantized candidate: the output classes
        # permuted (weights/bias/scales rolled together) — loads,
        # warms, quantization artifacts all self-consistent, answers
        # the WRONG question confidently; the mirrored divergence
        # bound is exactly what catches it
        garbage = [dict(e) for e in qparams]
        garbage[-1] = dict(
            garbage[-1],
            weights=numpy.roll(garbage[-1]["weights"], 1, axis=1),
            weights_scale=numpy.roll(garbage[-1]["weights_scale"], 1),
            bias=numpy.roll(garbage[-1]["bias"], 1))
        entry = wait_cycle(publish("qbad.pickle", garbage)["ordinal"])
        assert entry["verdict"] == "rolled_back", entry
        assert entry["new_compiles"] == 0, entry
        for rep in pool.replicas:
            assert value_digest(rep.engine.params) == want
        assert pool.cutover.state == "idle"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        controller.stop()
        pool.stop()
    assert not errors, errors[:3]


def test_rejected_quantized_canary_restores_process_flags(tmp_path):
    """The quantized flag and MFU-ceiling dtype are process-global and
    a canary's warm-up compile flips them; rollback is swap-backs with
    ZERO compiles by construction, so it must republish from the live
    fleet anchor — a rejected int8 candidate cannot leave an f32 fleet
    branded quantized (and rating MFU against the int8 peak) forever."""
    from veles_tpu.observe import xla_introspect
    from veles_tpu.observe.metrics import registry
    from tests.test_freshness import _pool

    pool = _pool(tmp_path, replicas=2, seed=7)
    assert registry.peek("serve.quantized").value == 0
    assert xla_introspect.step_dtype() == "bf16"
    calib = numpy.random.RandomState(1).rand(128, 16).astype(
        numpy.float32)
    qparams, _ = quantize_model_spec(pool.engine.plans,
                                     pool.engine.params, calib)
    pool.start()
    try:
        candidate = AOTEngine(pool.engine.plans, qparams, (16,),
                              ladder=pool.engine.ladder,
                              device=pool.replicas[-1].device)
        candidate.compile()  # the warm-up flips the process globals
        assert registry.peek("serve.quantized").value == 1
        assert xla_introspect.step_dtype() == "int8"
        pool.cutover.begin(candidate)
        receipt = pool.cutover.rollback(reason="test rejection")
        assert receipt["new_compiles"] == 0
        # the restored f32 fleet owns the flags again
        assert registry.peek("serve.quantized").value == 0
        assert xla_introspect.step_dtype() == "bf16"
    finally:
        pool.stop()


# -- (g) MFU ceiling + bench machinery ---------------------------------------


def test_peak_tables_and_step_dtype(monkeypatch):
    """The peaks table carries an int8 rate beside bf16 (twice it on
    v5e, never below it); set_step_dtype drives the ceiling
    mfu_snapshot divides by (via peak_flops' dtype default) and the
    step-dtype gauge."""
    from veles_tpu.observe import xla_introspect as xi
    from veles_tpu.observe.metrics import registry

    for kind, row in xi.PEAKS.items():
        assert row["int8"] >= row["bf16"] > 0 and row["hbm"] > 0, kind
        assert row["source"], kind
    v5e = xi.PEAKS["TPU v5 lite"]
    assert (v5e["bf16"], v5e["int8"]) == (197e12, 393e12)
    monkeypatch.setattr(xi, "device_peaks", lambda device=None: v5e)
    prev = xi.step_dtype()
    try:
        assert xi.peak_flops() == v5e[prev]
        xi.set_step_dtype("int8")
        assert xi.step_dtype() == "int8"
        assert registry.peek("xla.step_dtype_int8").value == 1
        assert xi.peak_flops() == 393e12
        assert xi.peak_flops("bf16") == 197e12
        with pytest.raises(ValueError):
            xi.set_step_dtype("fp4")
    finally:
        xi.set_step_dtype(prev)
