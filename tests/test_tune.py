"""Schedule autotuner (veles_tpu/tune/, docs/kernels.md "Autotuning"):
cache key semantics, corrupt/stale fallback, planted-entry consults in
all four kernel families, tuned-vs-static bit-equality through the
Pallas interpreter, the GA fitness memo, quantization/feasibility
gates, the learned-cost-model fitness mode end to end, the fleet
schedule bank (export/merge/publish/watcher pickup), the fused-step
walk and the CLI round trip.

Every test sees a PRIVATE empty schedule cache (the conftest autouse
fixture redirects ``VELES_SCHEDULE_CACHE`` to tmp) — tests that want
entries plant them."""

import importlib
import json
import logging
import os

import numpy
import pytest

pytestmark = pytest.mark.tune

#: the module, not the function ``veles_tpu.ops``'s __init__ re-exports
#: under the same name
matmul_mod = importlib.import_module("veles_tpu.ops.matmul")


def _ints(rng, shape, lo=-3, hi=4):
    """Exactly-representable f32 operands: every precision level and
    tile order accumulates them without rounding, so tuned-vs-static
    comparisons can demand BIT equality."""
    import jax.numpy as jnp
    return jnp.asarray(rng.randint(lo, hi, shape).astype(numpy.float32))


def _plant(spec, schedule, source="test"):
    """Write one schedule-cache entry for ``spec`` keyed exactly the
    way the kernels' consults will look it up."""
    from veles_tpu.tune.cache import cache_for, device_kind, schedule_key
    digest, payload = schedule_key(
        spec["op"], spec["shape"], spec["dtype"],
        spec["precision_level"], device_kind(), spec["extra"])
    cache_for().put(digest, payload, schedule, source=source)
    return digest


# -- cache keys ---------------------------------------------------------------


def test_schedule_key_invariance_and_sensitivity():
    """Same spec -> same digest; every coordinate (shape, dtype,
    precision level, device kind, kernel version) changes it."""
    from veles_tpu.tune.cache import schedule_key
    base = ("matmul", (64, 128, 128), "float32", 0, "cpu",
            {"kernel_version": 2})
    d0, payload = schedule_key(*base)
    d1, _ = schedule_key(*base)
    assert d0 == d1
    assert payload["shape"] == [64, 128, 128]
    variants = [
        ("matmul", (64, 128, 256), "float32", 0, "cpu",
         {"kernel_version": 2}),
        ("matmul", (64, 128, 128), "bfloat16", 0, "cpu",
         {"kernel_version": 2}),
        ("matmul", (64, 128, 128), "float32", 1, "cpu",
         {"kernel_version": 2}),
        ("matmul", (64, 128, 128), "float32", 0, "TPU v5e",
         {"kernel_version": 2}),
        ("matmul", (64, 128, 128), "float32", 0, "cpu",
         {"kernel_version": 3}),
        ("conv_vjp", (64, 128, 128), "float32", 0, "cpu",
         {"kernel_version": 2}),
    ]
    digests = {schedule_key(*v)[0] for v in variants}
    assert d0 not in digests and len(digests) == len(variants)


def test_cache_roundtrip_and_len(tmp_path):
    from veles_tpu.tune.cache import ScheduleCache
    cache = ScheduleCache(str(tmp_path / "s.json"))
    assert len(cache) == 0 and cache.get("nope") is None
    cache.put("d1", {"op": "matmul"}, {"blocks": [8, 128, 128]},
              fitness=-0.5, evals=3)
    # a fresh instance reads the persisted file
    reloaded = ScheduleCache(str(tmp_path / "s.json"))
    entry = reloaded.get("d1")
    assert entry["schedule"] == {"blocks": [8, 128, 128]}
    assert entry["fitness"] == -0.5 and entry["evals"] == 3
    assert len(reloaded) == 1


def test_put_merges_concurrent_writers(tmp_path):
    """put() re-reads the file before its read-modify-write: a second
    writer's entries persisted after our lazy load survive our save
    (the fleet pre-tune must not be wiped by a later local sweep)."""
    from veles_tpu.tune.cache import ScheduleCache
    path = str(tmp_path / "s.json")
    ours = ScheduleCache(path)
    assert len(ours) == 0  # lazy load happens now, file absent
    theirs = ScheduleCache(path)
    for i in range(3):
        theirs.put("fleet-%d" % i, {"op": "matmul"},
                   {"blocks": [8, 128, 128]})
    ours.put("local", {"op": "matmul"}, {"blocks": [16, 128, 128]})
    merged = ScheduleCache(path)
    assert len(merged) == 4
    assert merged.get("fleet-2") is not None
    assert merged.get("local")["schedule"]["blocks"] == [16, 128, 128]


def test_provenance_rejects_invalid_entry_like_the_consult(caplog):
    """An entry the kernel consult would reject (MXU-illegal blocks)
    must not be attributed as "tuned" in MFU rows — provenance runs
    the same structural validation."""
    from veles_tpu.tune.cache import provenance
    from veles_tpu.tune.spec import matmul_spec
    spec = matmul_spec(40, 40, 40, "float32", 0)
    args = (spec["op"], spec["shape"], spec["dtype"],
            spec["precision_level"], spec["extra"])
    _plant(spec, {"blocks": [5, 99, 1]})  # MXU-illegal
    with caplog.at_level(logging.WARNING, logger="veles_tpu.tune"):
        assert provenance(*args) == "static"


def test_corrupt_cache_file_warns_and_serves_static(caplog):
    """A garbage cache file is a WARNING and a miss — the matmul call
    still runs on the static tables, bit-identical to a no-cache run."""
    from veles_tpu.ops.matmul import matmul
    cache_dir = os.environ["VELES_SCHEDULE_CACHE"]
    os.makedirs(cache_dir, exist_ok=True)
    with open(os.path.join(cache_dir, "schedules.json"), "w") as fout:
        fout.write("{this is not json")
    rng = numpy.random.RandomState(0)
    a, b = _ints(rng, (16, 24)), _ints(rng, (24, 32))
    with caplog.at_level(logging.WARNING, logger="veles_tpu.tune"):
        out = matmul(a, b)
    assert any("unreadable" in r.getMessage() for r in caplog.records)
    ref = matmul(a, b, blocks=(16, 128, 128))
    numpy.testing.assert_array_equal(numpy.asarray(out),
                                     numpy.asarray(ref))


def test_malformed_entry_warns_and_serves_static(caplog):
    """A structurally broken schedule (wrong multiples / not a dict)
    falls back to the static tables with a warning, never a crash."""
    from veles_tpu.ops.matmul import matmul
    from veles_tpu.tune.spec import matmul_spec
    rng = numpy.random.RandomState(1)
    a, b = _ints(rng, (16, 24)), _ints(rng, (24, 32))
    ref = numpy.asarray(matmul(a, b))

    spec = matmul_spec(16, 24, 32, "float32", 0)
    _plant(spec, {"blocks": [7, 100, 3]})  # MXU-illegal multiples
    with caplog.at_level(logging.WARNING, logger="veles_tpu.tune"):
        out = matmul(a, b)
    assert any("malformed" in r.getMessage() for r in caplog.records)
    numpy.testing.assert_array_equal(numpy.asarray(out), ref)


def test_stale_kernel_version_is_a_miss(monkeypatch):
    """An entry keyed to an older kernel version never serves the new
    algorithm: bumping the version turns the planted hit into a miss."""
    from veles_tpu.tune.spec import matmul_spec
    spec = matmul_spec(16, 24, 32, "float32", 0)
    _plant(spec, {"blocks": [8, 128, 128]})
    seen = []
    real = matmul_mod._matmul_jit

    def spy(a, b, pl, blocks, od, interp):
        seen.append(blocks)
        return real(a, b, pl, blocks, od, interp)

    monkeypatch.setattr(matmul_mod, "_matmul_jit", spy)
    rng = numpy.random.RandomState(2)
    a, b = _ints(rng, (16, 24)), _ints(rng, (24, 32))
    matmul_mod.matmul(a, b)
    assert seen[-1] == (8, 128, 128)  # hit on the current version
    monkeypatch.setattr(matmul_mod, "MATMUL_KERNEL_VERSION",
                        matmul_mod.MATMUL_KERNEL_VERSION + 1)
    matmul_mod.matmul(a, b)
    assert seen[-1] is None  # stale version: static tables


# -- planted-entry consults + bit-equality ------------------------------------


def test_planted_entry_serves_matmul_bit_equal(monkeypatch):
    """matmul() demonstrably loads tuned blocks from a planted cache
    entry, and the tuned result is BIT-identical to the static-table
    result on representable operands (tiles change schedules, never
    math)."""
    rng = numpy.random.RandomState(3)
    a, b = _ints(rng, (24, 40)), _ints(rng, (40, 48))
    base = numpy.asarray(matmul_mod.matmul(a, b))

    from veles_tpu.tune.spec import matmul_spec
    spec = matmul_spec(24, 40, 48, "float32", 0)
    _plant(spec, {"blocks": [8, 128, 128]})

    seen = []
    real = matmul_mod._matmul_jit

    def spy(a_, b_, pl, blocks, od, interp):
        seen.append(blocks)
        return real(a_, b_, pl, blocks, od, interp)

    monkeypatch.setattr(matmul_mod, "_matmul_jit", spy)
    tuned = numpy.asarray(matmul_mod.matmul(a, b))
    assert seen == [(8, 128, 128)]
    numpy.testing.assert_array_equal(tuned, base)


def test_planted_entry_serves_conv_vjp_bit_equal(monkeypatch):
    """fused_conv_vjp consults the cache for its wgrad tiles; the
    tuned schedule's gradients are bit-identical on representable
    operands."""
    from veles_tpu.ops import conv_vjp as conv_mod
    rng = numpy.random.RandomState(4)
    import jax.numpy as jnp
    x = _ints(rng, (2, 6, 6, 3))
    w = _ints(rng, (3, 3, 3, 4), -2, 3)
    dy = _ints(rng, (2, 6, 6, 4))
    y = jnp.zeros((2, 6, 6, 4), jnp.float32)  # linear epilogue: unused

    def run():
        _, gw, gb = conv_mod.fused_conv_vjp(
            x, w, y, dy, activation="linear", padding=(1, 1, 1, 1),
            sliding=(1, 1), need_err_input=False)
        return numpy.asarray(gw), numpy.asarray(gb)

    gw0, gb0 = run()

    from veles_tpu.tune.spec import conv_vjp_spec
    spec = conv_vjp_spec(x.shape, 3, 3, 4, (6, 6), "float32", 0)
    _plant(spec, {"blocks": [128, 128, 8]})

    seen = []
    real = conv_mod._fused_wgrad_jit

    def spy(x_, y_, dy_, act, ky, kx, out_hw, padding, sliding, pl,
            blocks, interp):
        seen.append(blocks)
        return real(x_, y_, dy_, act, ky, kx, out_hw, padding,
                    sliding, pl, blocks, interp)

    monkeypatch.setattr(conv_mod, "_fused_wgrad_jit", spy)
    gw1, gb1 = run()
    assert seen == [(128, 128, 8)]
    numpy.testing.assert_array_equal(gw1, gw0)
    numpy.testing.assert_array_equal(gb1, gb0)


def test_planted_entry_serves_pool_bwd_bit_equal(monkeypatch):
    """max_pool_bwd consults the cache for its W tiling; a tuned
    owb routes bit-identically (select-and-scatter is value-exact)."""
    import jax.numpy as jnp

    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops import pool_bwd as pool_mod
    rng = numpy.random.RandomState(5)
    x = _ints(rng, (2, 8, 8, 3), -5, 6)
    y = MaxPooling.apply({}, x, window=(2, 2), sliding=(2, 2),
                         pallas_bwd=False)
    dy = _ints(rng, (2,) + tuple(y.shape[1:]))
    base = numpy.asarray(pool_mod.max_pool_bwd(
        x, y, dy, window=(2, 2), sliding=(2, 2)))

    from veles_tpu.tune.spec import pool_bwd_spec
    spec = pool_bwd_spec(x.shape, (4, 4), (2, 2), (2, 2), "float32")
    _plant(spec, {"owb": 2})

    seen = []
    real = pool_mod._max_pool_bwd_jit

    def spy(x_, y_, dy_, window, sliding, interp, owb=None):
        seen.append(owb)
        return real(x_, y_, dy_, window, sliding, interp, owb)

    monkeypatch.setattr(pool_mod, "_max_pool_bwd_jit", spy)
    tuned = numpy.asarray(pool_mod.max_pool_bwd(
        x, y, dy, window=(2, 2), sliding=(2, 2)))
    assert seen == [2]
    numpy.testing.assert_array_equal(tuned, base)
    assert jnp.asarray(dy).dtype == jnp.float32


# -- measurement discipline ---------------------------------------------------


@pytest.mark.parametrize("samples,kept", [
    pytest.param([-1.0, 2.0, 3.0], [2.0, 3.0], id="tuner"),
    # five chain slopes of a backward A/B, one swamped by host jitter
    pytest.param([0.016, 0.017, -0.038, 0.016, 0.018],
                 [0.016, 0.017, 0.016, 0.018], id="bwd_slopes"),
])
def test_filter_passes_discards_never_clamps(samples, kept):
    """tune.measure.filter_passes is the one jitter policy: a
    non-positive pass is dropped, not clamped to a floor, so the
    median runs over the passes that measured the program."""
    from veles_tpu.tune.measure import filter_passes
    assert filter_passes(samples) == kept
    assert numpy.median(filter_passes(samples)) == \
        pytest.approx(numpy.median(kept))
    # all-jitter: raw list unchanged, the caller's floor rejects
    noise = [-abs(s) for s in samples]
    assert filter_passes(noise) == noise


def test_rank_positive_majority_discipline():
    """A candidate with a positive MINORITY of passes is rejected even
    if its surviving samples are tiny — the jitter-swamped-tile
    crowning the matmul autotuner documents."""
    from veles_tpu.tune.measure import rank
    meds = rank({"honest": [1.0, 1.1, 0.9],
                 "jitter_swamped": [-1.0, -1.0, 0.001],
                 "all_jitter": [-1.0, -2.0, -3.0]})
    assert meds["honest"] == 1.0
    assert meds["jitter_swamped"] is None
    assert meds["all_jitter"] is None


# -- GA memoization + quantization/feasibility --------------------------------


def test_duplicate_genomes_memoized_invocation_count():
    """Crossover/elitism duplicates are FREE: fitness_fn runs at most
    once per distinct genome across all generations."""
    from veles_tpu.genetics import GeneticsOptimizer, Tune
    from veles_tpu.prng import RandomGenerator

    calls = []

    def fitness(spec):
        calls.append(spec["x"])
        return -(spec["x"] - 0.7) ** 2

    opt = GeneticsOptimizer(
        {"x": Tune(0.0, 0.0, 1.0)}, fitness, generations=5,
        population=6, rng=RandomGenerator("memo", seed=5),
        binary_bits=1, mutation="binary", mutation_rate=0.5)
    opt.run()
    # binary_bits=1 collapses mutated genes onto {0.0, 1.0}: plenty of
    # duplicate genomes across 5 generations — every one memoized
    assert len(calls) == len(set(calls))
    assert all(c.fitness is not None
               for c in opt.population.chromosomes)


def test_batch_fitness_path_evaluates_generations_together():
    """batch_fitness_fn sees each generation's (deduplicated) pending
    specs as ONE list — the interleaved-measurement hook."""
    from veles_tpu.genetics import GeneticsOptimizer, Tune
    from veles_tpu.prng import RandomGenerator

    batches = []

    def boom(spec):  # the serial path must NOT be used
        raise AssertionError("serial fitness path used")

    def batch(specs):
        batches.append(len(specs))
        return [-(s["x"] - 0.5) ** 2 for s in specs]

    opt = GeneticsOptimizer(
        {"x": Tune(0.0, 0.0, 1.0)}, boom, generations=3, population=5,
        rng=RandomGenerator("batch", seed=9), batch_fitness_fn=batch)
    opt.run()
    # generation 0 evaluates the full population in ONE batch; later
    # generations only ship genomes the values-keyed memo hasn't seen
    # (a fully-duplicated generation ships nothing at all)
    assert batches and batches[0] == 5
    assert len(batches) <= 3 and sum(batches) <= 15
    assert all(c.fitness is not None
               for c in opt.population.chromosomes)


def test_quantization_lands_on_mxu_multiples():
    from veles_tpu.tune.spec import FAMILIES, matmul_spec
    family = FAMILIES["matmul"]
    spec = matmul_spec(300, 300, 300, "float32", 0)
    sched = family.quantize(spec, {"bm": 13.7, "bn": 200.2,
                                   "bk": 510.9})
    bm, bn, bk = sched["blocks"]
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
    # clamped into the padded-shape box
    assert bm <= 304 and bn <= 384 and bk <= 384
    assert family.validate(sched) is not None


def test_infeasible_candidate_rejected_before_compile(monkeypatch):
    """A VMEM-overflowing candidate is PENALTY'd without ever building
    a runner (= without paying a compile)."""
    from veles_tpu.tune import spec as spec_mod
    from veles_tpu.tune.autotune import PENALTY, evaluate_candidate
    from veles_tpu.tune.spec import matmul_spec

    spec = matmul_spec(4096, 4096, 4096, "float32", 0)
    big = {"blocks": [1024, 2048, 2048]}
    assert not spec_mod.FAMILIES["matmul"].feasible(spec, big)

    def boom(self, *a):
        raise AssertionError("compile paid for an infeasible tile")

    monkeypatch.setattr(spec_mod.MatmulFamily, "build_runner", boom)
    fitness = evaluate_candidate({
        "family": "matmul", "spec": spec,
        "genes": {"bm": 1024, "bn": 2048, "bk": 2048},
        "fitness_mode": "compile"})
    assert fitness == PENALTY


# -- the tuner end to end -----------------------------------------------------


def test_tuner_ga_then_cache_hit():
    """First tune: GA runs (compile fitness), persists.  Second tune of
    the same spec: pure cache hit, ZERO evaluations."""
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import matmul_spec

    spec = matmul_spec(16, 32, 48, "float32", 0)

    def tuner():
        return ScheduleTuner(spec, generations=2, population=4,
                             fitness="compile",
                             rng=RandomGenerator("t", seed=3))

    first = tuner().tune()
    assert first["source"] == "ga" and first["evals"] >= 1
    # "evals" counts compiles PAID; "genomes" distinct genomes
    # dispatched — memo/feasibility savings show as genomes >= evals
    assert first["genomes"] >= first["evals"]
    blocks = first["schedule"]["blocks"]
    assert (blocks[0] % 8 == 0 and blocks[1] % 128 == 0
            and blocks[2] % 128 == 0)
    second = tuner().tune()
    assert second["source"] == "cache" and second["evals"] == 0
    assert second["schedule"] == first["schedule"]


def test_autotune_matmul_migrates_shipped_device_info_entry():
    """A shipped devices/device_infos.json winner (the OLD persistence
    path) serves instantly on a fresh schedule cache AND is migrated
    into it — a fresh host never re-pays the headline sweep."""
    from veles_tpu.backends import DeviceInfo
    from veles_tpu.ops.matmul import (MATMUL_KERNEL_VERSION,
                                      autotune_matmul)
    from veles_tpu.tune.cache import cache_for

    info = DeviceInfo("legacy-chip")
    info.table["matmul:v%d:float32:pl0:s256" %
               MATMUL_KERNEL_VERSION] = [768, 512, 512]
    assert autotune_matmul(info, size=256) == (768, 512, 512)
    # migrated: a second call hits the schedule cache directly
    entries = cache_for().entries()
    assert any(e.get("source") == "device_info"
               for e in entries.values())


def test_tuner_invalid_cache_hit_retunes():
    """An entry the kernels' consult would reject must be a MISS for
    the tuner too — it retunes and overwrites instead of reporting
    source='cache' forever while static tiles actually serve."""
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import matmul_spec

    spec = matmul_spec(16, 32, 48, "float32", 0)
    _plant(spec, {"blocks": [5, 99, 1]})  # MXU-illegal
    row = ScheduleTuner(spec, generations=1, population=4,
                        fitness="compile",
                        rng=RandomGenerator("rt", seed=2)).tune()
    assert row["source"] == "ga"
    blocks = row["schedule"]["blocks"]
    assert blocks[0] % 8 == 0 and blocks[1] % 128 == 0


def test_put_does_not_revert_concurrent_retune(tmp_path):
    """Fresher disk state wins per digest: another process's re-tune
    of digest X survives our later put of digest Y."""
    from veles_tpu.tune.cache import ScheduleCache
    path = str(tmp_path / "s.json")
    ours = ScheduleCache(path)
    ours.put("X", {"op": "matmul"}, {"blocks": [8, 128, 128]})
    theirs = ScheduleCache(path)
    theirs.put("X", {"op": "matmul"}, {"blocks": [16, 256, 256]})
    ours.put("Y", {"op": "matmul"}, {"blocks": [8, 128, 128]})
    final = ScheduleCache(path)
    assert final.get("X")["schedule"]["blocks"] == [16, 256, 256]
    assert final.get("Y") is not None


def test_f32_winner_seeds_survive_small_populations():
    """The dtype-specific measured winners seed FIRST so a default
    population of 8 cannot truncate them away."""
    from veles_tpu.tune.spec import FAMILIES, matmul_spec
    seeds = FAMILIES["matmul"].seeds(
        matmul_spec(3001, 3001, 3001, "float32", 0))
    assert seeds[0]["blocks"] == [768, 512, 512]
    # bf16 has no dtype-specific tiles: generic list unchanged
    bf16 = FAMILIES["matmul"].seeds(
        matmul_spec(3001, 3001, 3001, "bfloat16", 0))
    assert bf16[0]["blocks"] == [256, 256, 256]


def test_snap_collapses_clamp_identical_genomes():
    """Genomes that quantize to the same schedule snap to bit-equal
    values — so the GA's values-keyed memo dedupes them on EVERY
    evaluator path (workers/farm children share no schedule memo)."""
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import matmul_spec
    spec = matmul_spec(512, 512, 512, "float32", 0)
    tuner = ScheduleTuner(spec, fitness="compile")
    snap = tuner._snap_genome(tuner.family.space(spec))
    # gene order is the GA's sorted-path order: (bk, bm, bn)
    a = snap([130.2, 254.0, 260.0])
    b = snap([127.9, 253.1, 270.1])
    numpy.testing.assert_array_equal(a, b)
    numpy.testing.assert_array_equal(a, [128.0, 256.0, 256.0])


def test_pool_footprint_formula_is_shared():
    """tune.spec's pool feasibility calls the kernel planner's OWN
    footprint helper — one formula, no drift."""
    from veles_tpu.ops.pool_bwd import (POOL_VMEM_BUDGET_BYTES,
                                        pool_block_footprint)
    from veles_tpu.tune.spec import FAMILIES, pool_bwd_spec
    spec = pool_bwd_spec((2, 8, 8, 3), (4, 4), (2, 2), (2, 2),
                         "float32")
    family = FAMILIES["pool_bwd"]
    assert family.feasible(spec, {"owb": 2})
    assert (pool_block_footprint(4, 2, (2, 2), (2, 2), 4)
            <= POOL_VMEM_BUDGET_BYTES)


def test_tuner_untunable_pool_shape():
    """Overlapping pool windows admit no halo-free W tiling: the tuner
    reports 'untunable' and persists nothing."""
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.cache import cache_for
    from veles_tpu.tune.spec import pool_bwd_spec

    spec = pool_bwd_spec((2, 9, 9, 3), (4, 4), (3, 3), (2, 2),
                         "float32")
    row = ScheduleTuner(spec, fitness="compile").tune()
    assert row["source"] == "untunable" and row["schedule"] is None
    assert len(cache_for()) == 0


def test_provenance_and_counters():
    from veles_tpu.tune.cache import provenance, tune_counters
    from veles_tpu.tune.spec import matmul_spec
    spec = matmul_spec(16, 24, 32, "float32", 0)
    args = (spec["op"], spec["shape"], spec["dtype"],
            spec["precision_level"], spec["extra"])
    assert provenance(*args) == "static"
    _plant(spec, {"blocks": [8, 128, 128]})
    assert provenance(*args) == "tuned"
    counters = tune_counters()
    assert counters["entries"] == 1


# -- the walk + CLI -----------------------------------------------------------


def test_walk_collects_conv_pool_and_matmul_specs():
    """One lowering of a conv+pool+softmax fused step yields specs for
    all three kernel families (conv/pool from the recorded consults,
    matmul from the dot_general harvest)."""
    from veles_tpu.models.zoo import build_plans_and_state
    from veles_tpu.tune.walk import collect_specs

    layer_specs = [
        {"type": "conv_str", "n_kernels": 4, "kx": 3, "ky": 3,
         "padding": 1, "learning_rate": 0.05, "gradient_moment": 0.9},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "softmax", "output_sample_shape": 5,
         "learning_rate": 0.05, "gradient_moment": 0.9},
    ]
    plans, state, _ = build_plans_and_state(layer_specs, (8, 8, 3),
                                            seed=2)
    specs = collect_specs(plans, state, 4, (8, 8, 3))
    ops = {spec["op"] for spec in specs}
    assert {"conv_vjp", "pool_bwd", "matmul"} <= ops
    digests = [spec["digest"] for spec in specs]
    assert len(digests) == len(set(digests))  # deduplicated
    conv = next(s for s in specs if s["op"] == "conv_vjp")
    assert conv["shape"][0] == 9  # 3x3 taps
    assert conv["raw"]["x_shape"] == [4, 8, 8, 3]


def test_cli_tune_receipt_and_second_run_hits(tmp_path, capsys):
    """python -m veles_tpu.tune round trip: first run tunes and writes
    TUNE.json + the persisted cache; the second run is ALL cache hits
    with zero evaluations."""
    from veles_tpu.tune.__main__ import main

    out1 = str(tmp_path / "TUNE1.json")
    out2 = str(tmp_path / "TUNE2.json")
    argv = ["--model", "mlp", "--hidden", "16", "--batch", "8",
            "--fitness", "compile", "--generations", "1",
            "--population", "4", "--ops", "matmul",
            "--max-specs", "2", "--out", out1]
    assert main(argv) == 0
    receipt = json.load(open(out1))
    assert receipt["counts"].get("ga", 0) >= 1
    assert receipt["evals"] >= 1
    assert os.path.exists(receipt["cache_path"])
    for row in receipt["specs"]:
        assert row["op"] == "matmul"

    assert main(argv[:-1] + [out2]) == 0
    second = json.load(open(out2))
    assert second["counts"] == {"cache": len(second["specs"])}
    assert second["evals"] == 0
    capsys.readouterr()  # swallow the CLI's progress prints


# -- the attention family -----------------------------------------------------


def test_attention_family_space_quantize_feasibility():
    """The attention gene box tracks the padded grid (bq rides the
    sublane quantum, bk the lane quantum), quantization lands on legal
    multiples inside the caps, and the feasibility gate uses the
    kernel's own VMEM footprint."""
    from veles_tpu.tune.spec import (FAMILIES, TUNE_VMEM_BUDGET_BYTES,
                                     attention_spec)
    fam = FAMILIES["attention"]
    spec = attention_spec(2, 192, 32, "float32", 0)
    # shape = (B, ceil8(T), ceil128(T), ceil128(dh)) — grid coords
    assert spec["shape"] == [2, 192, 256, 128]
    space = fam.space(spec)
    assert (space["bq"].min, space["bq"].max) == (8, 192)
    assert (space["bk"].min, space["bk"].max) == (128, 256)
    sched = fam.quantize(spec, {"bq": 61.7, "bk": 200.0})
    assert sched["blocks"][0] % 8 == 0 and sched["blocks"][1] % 128 == 0
    assert sched["blocks"][0] <= 192 and sched["blocks"][1] <= 256
    assert fam.feasible(spec, sched)
    assert fam.footprint(spec, {"blocks": [8, 128]}) <= \
        TUNE_VMEM_BUDGET_BYTES
    # validate mirrors the consult: MXU-illegal or malformed -> None
    assert fam.validate({"blocks": [64, 256]}) == {"blocks": [64, 256]}
    assert fam.validate({"blocks": [60, 256]}) is None
    assert fam.validate({"blocks": [64, 200]}) is None
    assert fam.validate({"blocks": [64]}) is None
    assert fam.genes_of({"blocks": [64, 256]}) == {"bq": 64, "bk": 256}


def test_planted_entry_serves_attention_bit_equal(monkeypatch):
    """flash_attention() demonstrably loads tuned (bq, bk) from a
    planted cache entry: the consult run is BIT-identical to passing
    the planted blocks explicitly (same program, so the cache changed
    nothing but the schedule), and stays within the single-k-tile ULP
    contract of the default-blocks run (a bq-only change repartitions
    q rows; XLA's vectorized transcendentals may round the same row
    differently across tile layouts — test_transformer's bound)."""
    from veles_tpu.ops import attention as att_mod

    rng = numpy.random.RandomState(7)
    q = _ints(rng, (2, 192, 32))
    k = _ints(rng, (2, 192, 32))
    v = _ints(rng, (2, 192, 32))

    seen = []
    real = att_mod._flash_fn

    def spy(scale, level, blocks):
        seen.append(blocks)
        return real(scale, level, blocks)

    monkeypatch.setattr(att_mod, "_flash_fn", spy)
    base = numpy.asarray(att_mod.flash_attention(q, k, v))
    assert seen == [att_mod._DEFAULT_BLOCKS]  # empty cache -> static
    explicit = numpy.asarray(
        att_mod.flash_attention(q, k, v, blocks=(64, 256)))

    from veles_tpu.tune.spec import attention_spec
    _plant(attention_spec(2, 192, 32, "float32", 0),
           {"blocks": [64, 256]})
    seen.clear()
    tuned = numpy.asarray(att_mod.flash_attention(q, k, v))
    assert seen == [(64, 256)]
    numpy.testing.assert_array_equal(tuned, explicit)
    assert float(numpy.abs(tuned - base).max()) < 1e-5


def test_attention_tuner_ga_then_cache_hit():
    """Attention joins the tune-once contract: the first tune runs the
    GA (compile fitness over the full fwd+bwd custom_vjp step) and
    persists; the SECOND run of the same spec is all cache hits with
    ZERO evaluations."""
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import attention_spec

    spec = attention_spec(2, 64, 16, "float32", 0)

    def tuner():
        return ScheduleTuner(spec, generations=1, population=3,
                             fitness="compile",
                             rng=RandomGenerator("att", seed=5))

    first = tuner().tune()
    assert first["source"] == "ga" and first["evals"] >= 1
    blocks = first["schedule"]["blocks"]
    assert blocks[0] % 8 == 0 and blocks[1] % 128 == 0
    second = tuner().tune()
    assert second["source"] == "cache" and second["evals"] == 0
    assert second["schedule"] == first["schedule"]


# -- fitness="model" ----------------------------------------------------------


def test_model_fitness_thin_data_falls_back_to_base():
    """fitness='model' with an empty measurement sidecar degrades to
    the base mode and SAYS SO: the receipt row carries the fallback
    reason, and the tune still lands a valid persisted winner."""
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import matmul_spec

    spec = matmul_spec(16, 32, 48, "float32", 0)
    row = ScheduleTuner(spec, generations=1, population=4,
                        fitness="model", model_base="compile",
                        rng=RandomGenerator("mf", seed=9)).tune()
    assert row["source"] == "ga" and row["evals"] >= 1
    assert row["model"]["fallback"] == "thin-data"
    assert row["model"]["predicted"] == 0
    assert row["schedule"]["blocks"][0] % 8 == 0


def test_model_fitness_pool_run_degrades_to_base(caplog):
    """Model ranking is in-process only: asking for workers (or farm
    slaves) degrades fitness='model' to the base mode up front instead
    of mis-ranking across children that share no model."""
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import matmul_spec

    with caplog.at_level(logging.WARNING):
        tuner = ScheduleTuner(matmul_spec(16, 32, 48, "float32", 0),
                              fitness="model", model_base="compile",
                              workers=2)
    assert tuner.fitness_mode == "compile"
    assert any("in-process only" in r.message for r in caplog.records)


def test_model_fitness_e2e_tunes_with_fewer_compiles_and_serves():
    """The headline loop end to end on real compiles: a measured base
    leg builds the sidecar, then a fitness='model' re-tune trains the
    stump model, compiles only the top-ranked slice (predicted >= 1,
    evals below the base leg's), and its MEASURED winner both persists
    and serves the actual matmul consult bit-identically."""
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.spec import matmul_spec

    rng = numpy.random.RandomState(11)
    a, b = _ints(rng, (64, 512)), _ints(rng, (512, 512))
    base_out = numpy.asarray(matmul_mod.matmul(a, b))  # static tiles

    # base leg: compile-fitness GA over two specs -> measurement
    # triples in >= 2 spec groups (leave-one-spec-out needs a held-out
    # group to validate against).  population 14 so the seeded initial
    # generation carries enough DISTINCT schedules for the model leg's
    # top-decile cut to actually skip some (floor is 2 per generation)
    spec = matmul_spec(64, 512, 512, "float32", 0)
    side = matmul_spec(128, 512, 512, "float32", 0)
    base_row = ScheduleTuner(spec, generations=2, population=14,
                             fitness="compile",
                             rng=RandomGenerator("mb", seed=13)).tune()
    ScheduleTuner(side, generations=1, population=10,
                  fitness="compile",
                  rng=RandomGenerator("ms", seed=14)).tune()
    assert base_row["source"] == "ga" and base_row["evals"] >= 3

    model_row = ScheduleTuner(
        spec, generations=2, population=14, fitness="model",
        model_base="compile", model_min_triples=6, model_trust=10.0,
        rng=RandomGenerator("mb", seed=13)).tune(force=True)
    info = model_row["model"]
    assert info["fallback"] is None and info["trusted"]
    assert info["triples"] >= 6 and info["groups"] >= 2
    # the receipt: predictions replaced compiles
    assert info["predicted"] >= 1
    assert model_row["evals"] < base_row["evals"]
    # the winner is a real MEASUREMENT, never a prediction
    assert model_row["source"] == "ga"
    assert model_row["fitness"] is not None
    winner = model_row["schedule"]["blocks"]

    seen = []
    real = matmul_mod._matmul_jit

    def spy(a_, b_, pl, blocks, od, interp):
        seen.append(blocks)
        return real(a_, b_, pl, blocks, od, interp)

    import pytest as _pytest
    mp = _pytest.MonkeyPatch()
    try:
        mp.setattr(matmul_mod, "_matmul_jit", spy)
        tuned_out = numpy.asarray(matmul_mod.matmul(a, b))
    finally:
        mp.undo()
    assert seen == [tuple(winner)]
    numpy.testing.assert_array_equal(tuned_out, base_out)


# -- the fleet schedule bank --------------------------------------------------


def test_bank_merge_into_fresh_cache_serves_with_zero_local_evals(
        monkeypatch):
    """The fleet contract: host A tunes and exports; host B (a FRESH
    empty cache) merges the bank and immediately serves the identical
    schedule — consult bit-equal, re-tune all cache hits, ZERO local
    evaluations paid."""
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.tune.autotune import ScheduleTuner
    from veles_tpu.tune.cache import cache_for
    from veles_tpu.tune.spec import matmul_spec

    rng = numpy.random.RandomState(17)
    a, b = _ints(rng, (16, 32)), _ints(rng, (32, 48))
    base_out = numpy.asarray(matmul_mod.matmul(a, b))

    spec = matmul_spec(16, 32, 48, "float32", 0)
    first = ScheduleTuner(spec, generations=2, population=4,
                          fitness="compile",
                          rng=RandomGenerator("bk", seed=3)).tune()
    assert first["source"] == "ga"

    import tempfile
    bank_path = os.path.join(tempfile.mkdtemp(prefix="veles_bank"),
                             "bank.json")
    assert cache_for().export_bank(bank_path) == 1

    # host B: point the env at a fresh directory — cache_for() is
    # path-keyed, so this is a brand-new empty cache
    fresh_dir = tempfile.mkdtemp(prefix="veles_fresh")
    monkeypatch.setenv("VELES_SCHEDULE_CACHE",
                       os.path.join(fresh_dir, "schedule_cache"))
    assert len(cache_for()) == 0
    counts = cache_for().merge_bank(bank_path)
    assert counts["adopted"] == 1 and counts["total"] == 1

    seen = []
    real = matmul_mod._matmul_jit

    def spy(a_, b_, pl, blocks, od, interp):
        seen.append(blocks)
        return real(a_, b_, pl, blocks, od, interp)

    monkeypatch.setattr(matmul_mod, "_matmul_jit", spy)
    merged_out = numpy.asarray(matmul_mod.matmul(a, b))
    assert seen == [tuple(first["schedule"]["blocks"])]
    numpy.testing.assert_array_equal(merged_out, base_out)

    retune = ScheduleTuner(spec, generations=2, population=4,
                           fitness="compile",
                           rng=RandomGenerator("bk", seed=3)).tune()
    assert retune["source"] == "cache" and retune["evals"] == 0
    assert retune["schedule"] == first["schedule"]


def test_publish_schedule_bank_channel_and_watcher_pickup(tmp_path):
    """The publish channel end to end: publish_schedule_bank writes a
    manifest-verified schedule_bank.json beside the snapshots; the
    serve watcher's _maybe_merge_bank adopts it into the LOCAL cache,
    consumes the (mtime, size) stamp, and a mid-replace corruption is
    retried (stamp NOT consumed) instead of half-merged."""
    from veles_tpu.serve.freshness import SnapshotWatcher
    from veles_tpu.snapshotter import publish_schedule_bank
    from veles_tpu.tune.cache import (BANK_FILE_NAME, ScheduleCache,
                                      cache_for, device_kind,
                                      schedule_key)

    pub = str(tmp_path / "pub")
    # nothing to share is not an error
    empty = ScheduleCache(str(tmp_path / "empty" / "schedules.json"))
    assert publish_schedule_bank(pub, cache=empty) is None

    # the trainer-side cache with one real keyed winner
    from veles_tpu.ops.matmul import MATMUL_KERNEL_VERSION
    producer = ScheduleCache(str(tmp_path / "prod" / "schedules.json"))
    digest, payload = schedule_key(
        "matmul", [16, 128, 128], "float32", 0, device_kind(),
        {"kernel_version": MATMUL_KERNEL_VERSION})
    producer.put(digest, payload, {"blocks": [8, 128, 128]},
                 fitness=-1e-3, evals=4)
    res = publish_schedule_bank(pub, cache=producer)
    assert res["entries"] == 1
    assert os.path.basename(res["bank"]) == BANK_FILE_NAME

    watcher = SnapshotWatcher(pub, poll_s=30.0)
    counts = watcher._maybe_merge_bank()
    assert counts["adopted"] == 1 and counts["total"] == 1
    entry = cache_for().get(digest)  # the conftest-private local cache
    assert entry["schedule"]["blocks"] == [8, 128, 128]
    assert entry["host"]  # provenance survives the trip
    # stamp consumed: the unchanged bank is not re-merged every poll
    assert watcher._maybe_merge_bank() is None

    # publisher mid-replace: bank bytes no longer match the manifest —
    # skip WITHOUT consuming the stamp so the next poll retries
    bank_file = os.path.join(pub, BANK_FILE_NAME)
    stamp_before = watcher._bank_stamp
    with open(bank_file, "a") as fout:
        fout.write("\n")
    assert watcher._maybe_merge_bank() is None
    assert watcher._bank_stamp == stamp_before

    # the publisher finishes its replace: the retry adopts the update
    producer.put(digest, payload, {"blocks": [16, 128, 128]},
                 fitness=-5e-4, evals=4)
    publish_schedule_bank(pub, cache=producer)
    counts = watcher._maybe_merge_bank()
    assert counts["adopted"] == 1
    assert cache_for().get(digest)["schedule"]["blocks"] == \
        [16, 128, 128]
