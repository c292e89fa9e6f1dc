"""Pallas kernel tests (reference analogs: OCLBLAS, matrix kernels,
random bitstream, fullbatch gather).  Run in interpreter mode on CPU;
the same code compiles via Mosaic on TPU."""

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import (gather_minibatch, gemm, join,
                           matmul, mean_disp_normalize,
                           reduce_cols, reduce_rows)
from veles_tpu.ops import random as vrandom


RS = numpy.random.RandomState(42)


class TestMatmul:
    @pytest.mark.parametrize("shape", [
        (64, 32, 48), (128, 128, 128), (100, 77, 33), (8, 300, 120)])
    def test_matches_numpy(self, shape):
        m, k, n = shape
        a = RS.rand(m, k).astype(numpy.float32)
        b = RS.rand(k, n).astype(numpy.float32)
        out = numpy.asarray(matmul(jnp.asarray(a), jnp.asarray(b),
                                   blocks=(32, 128, 128)))
        numpy.testing.assert_allclose(out, a @ b, rtol=1e-5)

    @pytest.mark.parametrize("level", [0, 1, 2])
    def test_precision_levels(self, level):
        a = RS.rand(32, 256).astype(numpy.float32)
        b = RS.rand(256, 32).astype(numpy.float32)
        out = numpy.asarray(matmul(
            jnp.asarray(a), jnp.asarray(b), precision_level=level,
            blocks=(32, 128, 128)))
        oracle = (a.astype(numpy.float64) @ b.astype(numpy.float64))
        numpy.testing.assert_allclose(out, oracle, rtol=1e-5)

    def test_precision_level_accuracy_ladder(self):
        """Adversarial accumulation (large alternating terms): higher
        precision levels must not be worse than level 0 against the f64
        oracle — the property the reference's precise kernels buy
        (ocl/matrix_multiplication_precise.cl:36-41)."""
        k = 4096
        a = numpy.where(numpy.arange(k) % 2 == 0, 1e6, 1.0).astype(
            numpy.float32).reshape(1, k)
        a = numpy.repeat(a, 8, axis=0)
        b = numpy.where(numpy.arange(k) % 2 == 0, 1.0, -1e-3).astype(
            numpy.float32).reshape(k, 1)
        b = numpy.repeat(b, 8, axis=1)
        oracle = a.astype(numpy.float64) @ b.astype(numpy.float64)
        errs = []
        for level in (0, 1, 2):
            out = numpy.asarray(matmul(
                jnp.asarray(a), jnp.asarray(b), precision_level=level,
                blocks=(8, 128, 256)))
            errs.append(numpy.abs(out - oracle).max())
        assert errs[1] <= errs[0] * 1.001
        assert errs[2] <= errs[1] * 1.001

    def test_bfloat16_inputs(self):
        a = RS.rand(32, 64).astype(numpy.float32)
        b = RS.rand(64, 32).astype(numpy.float32)
        out = numpy.asarray(matmul(
            jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16),
            blocks=(32, 128, 128), out_dtype=jnp.float32))
        numpy.testing.assert_allclose(out, a @ b, rtol=2e-2)

    def test_shape_mismatch(self):
        with pytest.raises(ValueError):
            matmul(jnp.zeros((4, 5)), jnp.zeros((6, 4)))


class TestGemm:
    def test_alpha_beta(self):
        a = RS.rand(16, 24).astype(numpy.float32)
        b = RS.rand(24, 8).astype(numpy.float32)
        c = RS.rand(16, 8).astype(numpy.float32)
        out = numpy.asarray(gemm(jnp.asarray(a), jnp.asarray(b),
                                 jnp.asarray(c), alpha=2.0, beta=0.5))
        numpy.testing.assert_allclose(out, 2.0 * (a @ b) + 0.5 * c,
                                      rtol=1e-5)

    def test_transposes(self):
        a = RS.rand(24, 16).astype(numpy.float32)
        b = RS.rand(8, 24).astype(numpy.float32)
        out = numpy.asarray(gemm(jnp.asarray(a), jnp.asarray(b),
                                 trans_a=True, trans_b=True))
        numpy.testing.assert_allclose(out, a.T @ b.T, rtol=1e-5)


class TestReduce:
    def test_cols(self):
        x = RS.rand(300, 70).astype(numpy.float32)
        out = numpy.asarray(reduce_cols(jnp.asarray(x), block=64))
        numpy.testing.assert_allclose(out, x.sum(0, keepdims=True),
                                      rtol=1e-4)

    def test_rows(self):
        x = RS.rand(100, 500).astype(numpy.float32)
        out = numpy.asarray(reduce_rows(jnp.asarray(x), block=128))
        numpy.testing.assert_allclose(out, x.sum(1, keepdims=True),
                                      rtol=1e-4)


class TestGather:
    def test_gather_with_cast(self):
        data = (RS.rand(50, 12) * 255).astype(numpy.uint8)
        idx = RS.permutation(50)[:16].astype(numpy.int32)
        out = numpy.asarray(gather_minibatch(
            jnp.asarray(data), jnp.asarray(idx), out_dtype=jnp.float32))
        numpy.testing.assert_array_equal(out, data[idx].astype(
            numpy.float32))

    def test_gather_multidim(self):
        data = RS.rand(20, 4, 6).astype(numpy.float32)
        idx = numpy.array([3, 1, 19], numpy.int32)
        out = numpy.asarray(gather_minibatch(jnp.asarray(data),
                                             jnp.asarray(idx)))
        numpy.testing.assert_array_equal(out, data[idx])


    @pytest.mark.parametrize("shape,dtype,out_dtype,store", [
        ((40, 784), "float32", None, (40, 1, 896)),
        ((5, 154587), "bfloat16", None, (5, 1216, 128)),
        ((30, 10), "float32", None, (30, 1, 128)),
        ((20, 300), "bfloat16", None, (20, 16, 128)),
        ((33, 5, 7, 3), "uint8", "float32", (33, 32, 128)),
        ((9, 4097), "uint8", None, (9, 64, 128)),
        ((17,), "int32", None, (17, 1, 128)),
    ], ids=["f32_784", "bf16_154587", "f32_10", "bf16_300", "u8_to_f32",
            "u8_4097", "i32_scalar_rows"])
    def test_row_store_parity(self, shape, dtype, out_dtype, store):
        """Widths off 128 through the ONE path: the store built on the
        host (what FullBatchLoader uploads) and the raw-array
        composition give ``data[idx]`` bit for bit."""
        from veles_tpu.ops import gather
        dtype = jnp.dtype(dtype)
        data = (RS.rand(*shape) * 200).astype(dtype)
        idx = RS.randint(0, shape[0], 7).astype(numpy.int32)
        want = data[idx].astype(out_dtype or dtype)
        assert gather.store_shape(
            shape[0], int(numpy.prod(shape[1:])), dtype) == store
        buf = gather.build_store(data)
        assert buf.shape == store and buf.dtype == dtype
        for got in (
                gather_minibatch(jnp.asarray(buf), jnp.asarray(idx),
                                 out_dtype=out_dtype,
                                 sample_shape=shape[1:]),
                gather_minibatch(jnp.asarray(data), jnp.asarray(idx),
                                 out_dtype=out_dtype)):
            got = numpy.asarray(got)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == want.tobytes()

    def test_host_store_window_is_the_store(self):
        """What is written through the ``(N,) + sample_shape`` window —
        also through its ``reshape(N, -1)``, as the normalizers and the
        benchmark's dataset write — lands in the store, pad zero."""
        from veles_tpu.ops import gather
        buf, view = gather.host_store(6, (5, 7, 3), numpy.float32)
        assert view.shape == (6, 5, 7, 3) and buf.shape == (6, 1, 128)
        assert gather.host_store_of(view) is buf
        flat = view.reshape(6, -1)
        assert numpy.shares_memory(flat, buf)
        flat[:] = numpy.arange(6 * 105).reshape(6, 105)
        numpy.testing.assert_array_equal(
            buf.reshape(6, 128)[:, :105],
            numpy.arange(6 * 105).reshape(6, 105))
        assert not buf.reshape(6, 128)[:, 105:].any()
        # a plain array, a copy and a row slice are not a store's window
        assert gather.host_store_of(numpy.zeros((6, 5, 7, 3))) is None
        assert gather.host_store_of(view.copy()) is None
        assert gather.host_store_of(view[1:]) is None

    @pytest.mark.parametrize("count", [1000, 128, 5])
    def test_gather_labels_from_store(self, count):
        from veles_tpu.ops import gather, gather_labels
        labels = RS.randint(0, 10, count).astype(numpy.int32)
        idx = numpy.array([0, count - 1, count // 2, 0], numpy.int32)
        store = gather.build_label_store(labels)
        assert store.shape == (-(-count // 128), 1, 128)
        for table in (store, labels):
            got = numpy.asarray(gather_labels(jnp.asarray(table),
                                              jnp.asarray(idx)))
            numpy.testing.assert_array_equal(got, labels[idx])

    def test_gather_clamps_indices_into_the_table(self):
        """A DMA from a row that is not there would fault the chip."""
        data = RS.rand(10, 12).astype(numpy.float32)
        idx = numpy.array([-3, 0, 9, 10, 1 << 30], numpy.int32)
        out = numpy.asarray(gather_minibatch(jnp.asarray(data),
                                             jnp.asarray(idx)))
        numpy.testing.assert_array_equal(out, data[[0, 0, 9, 9, 9]])


class TestNormalize:
    def test_mean_disp(self):
        x = (RS.rand(30, 50) * 255).astype(numpy.uint8)
        mean = x.mean(0).astype(numpy.float32)
        disp = numpy.ptp(x.astype(numpy.float32), axis=0) + 1.0
        rdisp = (1.0 / disp).astype(numpy.float32)
        out = numpy.asarray(mean_disp_normalize(
            jnp.asarray(x), jnp.asarray(mean), jnp.asarray(rdisp),
            block=32))
        oracle = (x.astype(numpy.float32) - mean) * rdisp
        numpy.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-6)


class TestJoin:
    def test_two(self):
        a = RS.rand(10, 3).astype(numpy.float32)
        b = RS.rand(10, 5).astype(numpy.float32)
        out = numpy.asarray(join(jnp.asarray(a), jnp.asarray(b)))
        numpy.testing.assert_array_equal(
            out, numpy.concatenate([a, b], axis=1))

    def test_three_multidim(self):
        a = RS.rand(4, 2, 3).astype(numpy.float32)
        b = RS.rand(4, 7).astype(numpy.float32)
        c = RS.rand(4, 1).astype(numpy.float32)
        out = numpy.asarray(join(jnp.asarray(a), jnp.asarray(b),
                                 jnp.asarray(c)))
        oracle = numpy.concatenate(
            [a.reshape(4, -1), b, c], axis=1)
        numpy.testing.assert_array_equal(out, oracle)


class TestXorshift:
    def test_128plus_bit_exact(self):
        """JAX u32-pair emulation matches the u64 numpy oracle."""
        streams = 4
        hi = RS.randint(0, 2 ** 31, (2, streams)).astype(numpy.uint32)
        lo = RS.randint(0, 2 ** 31, (2, streams)).astype(numpy.uint32)
        state = numpy.stack([hi, lo], axis=1)  # (2, 2, S)
        jstate, jbits = vrandom.xorshift128plus(jnp.asarray(state), 16)
        _, oracle = vrandom.numpy_xorshift128plus(state, 16)
        jax_u64 = (numpy.asarray(jbits[:, 0]).astype(numpy.uint64) <<
                   numpy.uint64(32)) | numpy.asarray(
                       jbits[:, 1]).astype(numpy.uint64)
        numpy.testing.assert_array_equal(jax_u64, oracle)

    def test_1024star_bit_exact(self):
        streams = 3
        state64 = RS.randint(1, 2 ** 62, (16, streams)).astype(
            numpy.uint64)
        hi = (state64 >> numpy.uint64(32)).astype(numpy.uint32)
        lo = (state64 & numpy.uint64(0xffffffff)).astype(numpy.uint32)
        _, _, _, jbits = vrandom.xorshift1024star(
            jnp.asarray(hi), jnp.asarray(lo), jnp.int32(0), 12)
        _, _, oracle = vrandom.numpy_xorshift1024star(state64, 0, 12)
        jax_u64 = (numpy.asarray(jbits[:, 0]).astype(numpy.uint64) <<
                   numpy.uint64(32)) | numpy.asarray(
                       jbits[:, 1]).astype(numpy.uint64)
        numpy.testing.assert_array_equal(jax_u64, oracle)

    def test_uniform_from_bits_range(self):
        bits = jnp.asarray(RS.randint(0, 2 ** 31, (1000,)),
                           jnp.uint32)
        u = numpy.asarray(vrandom.uniform_from_bits(bits, -2.0, 3.0))
        assert (u >= -2.0).all() and (u < 3.0).all()

    def test_hardware_uniform_cpu_fallback(self):
        u = numpy.asarray(vrandom.hardware_uniform(7, (64, 128)))
        assert u.shape == (64, 128)
        assert (u >= 0).all() and (u < 1).all()
        u2 = numpy.asarray(vrandom.hardware_uniform(7, (64, 128)))
        numpy.testing.assert_array_equal(u, u2)  # deterministic per seed


def _matmul_256_digest():
    """The schedule-cache key autotune_matmul uses for size=256 on the
    test chip kind — built through the SAME spec builder the consult
    path uses, so the test can't drift from the implementation."""
    from veles_tpu.tune.cache import schedule_key
    from veles_tpu.tune.spec import matmul_spec
    spec = matmul_spec(256, 256, 256, "float32", 0)
    return schedule_key(spec["op"], spec["shape"], spec["dtype"],
                        spec["precision_level"], "test-chip-kind",
                        spec["extra"])


def test_autotune_matmul_round_robin_picks_and_persists():
    """The autotuner measures candidates round-robin (load drift
    hits every tile equally), picks a majority-positive-median winner,
    and persists it in the digest-keyed ScheduleCache — or falls back
    to the defaults WITHOUT persisting when timing jitter swamps every
    tile.  (The conftest autouse fixture gives this test a private
    empty cache.)"""
    from veles_tpu.backends import DeviceInfo
    from veles_tpu.ops.matmul import _DEFAULT_BLOCKS, autotune_matmul
    from veles_tpu.tune.cache import cache_for

    info = DeviceInfo("test-chip-kind")
    blocks = autotune_matmul(info, size=256)
    assert len(blocks) == 3 and all(b > 0 for b in blocks)
    digest, _ = _matmul_256_digest()
    entry = cache_for().get(digest)
    if entry is not None:  # a tile was ranked
        assert tuple(entry["schedule"]["blocks"]) == tuple(blocks)
        assert entry["source"] == "sweep"
    else:  # all-jitter fallback: defaults, deliberately unpersisted
        assert blocks == _DEFAULT_BLOCKS


def test_autotune_matmul_cache_hit_skips_measurement():
    """A persisted entry is served verbatim — no timing runs."""
    from veles_tpu.backends import DeviceInfo
    from veles_tpu.ops.matmul import autotune_matmul
    from veles_tpu.tune.cache import cache_for

    info = DeviceInfo("test-chip-kind")
    digest, payload = _matmul_256_digest()
    sentinel = [128, 128, 128]  # not a real candidate: proves the
    cache_for().put(digest, payload,  # value came from the cache
                    {"blocks": sentinel}, source="test")
    assert autotune_matmul(info, size=256) == tuple(sentinel)


def test_estimate_computing_power_positive():
    from veles_tpu.ops.benchmark import estimate_computing_power
    power = estimate_computing_power(size=128, repeats=2)
    assert power > 0
