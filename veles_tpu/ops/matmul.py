"""Tiled Pallas matmul with precision levels.

TPU-native counterpart of the reference's flagship kernel family
(reference: ocl/matrix_multiplication.cl:1, matrix_multiplication_precise
.cl:47-185, cuda equivalents).  The reference tiles into shared memory
with BLOCK_SIZE x BLOCK_SIZE tiles and offers PRECISION_LEVEL
0 (plain) / 1 (Kahan) / 2 (multi-partial) accumulation.

Design mapping (SURVEY.md section 7, hard part 7):

- Tiling targets the MXU through ``jnp.dot(..., preferred_element_type=
  float32)`` over VMEM-resident blocks; the grid walks (M/bm, N/bn) with
  the K loop inside the kernel accumulating in an f32 VMEM scratch.
- PRECISION_LEVEL 0 ("plain", fastest): f32 inputs run a bf16x3
  decomposition (a_hi@b_hi + a_hi@b_lo + a_lo@b_hi) — f32-class
  products (5.1e-7 max rel err vs an f64 oracle at 3001^2 on a v5e,
  chip_smoke.py, PR 21) in three MXU passes where true f32 takes six
  (speeds: not measured on today's code); accumulation is always
  f32.
- Level 1 pays for true-f32 products (HIGHEST) plus Kahan
  compensation across K-tile partial sums.
- Level 2 adds Neumaier (improved Kahan) compensation, the analog of
  the reference's multi-partial summation.  The speed/digits ladder
  mirrors the reference's (config.py:245-248: each level costs more).

Tile sizes come from the per-chip autotune table
(veles_tpu.backends.DeviceInfo), the analog of devices/device_infos.json.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops import common as _common
from veles_tpu.ops.common import (ceil_mult, interpret_for,
                                   mxu_partial_dot, pad_to, unpad)

__all__ = ["matmul", "matmul_benchmark", "autotune_matmul",
           "MATMUL_KERNEL_VERSION"]

#: the kernel's name in compiled HLO and device traces (``%veles_matmul``)
KERNEL_NAME = "veles_matmul"

_DEFAULT_BLOCKS = (512, 512, 512)

#: bump when the kernel's algorithm changes: persisted autotune tables
#: and measured-ceiling entries are only valid for the algorithm they
#: were measured on (v2 = bf16x3 level-0 f32 path; v1 entries in old
#: caches are ignored, not silently served)
MATMUL_KERNEL_VERSION = 2


def _matmul_kernel(a_ref, b_ref, out_ref, acc_ref, comp_ref,
                   *, n_k, precision_level):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j].

    ``acc_ref`` is the f32 accumulator scratch; ``comp_ref`` carries the
    Kahan/Neumaier compensation for precision levels 1/2.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if precision_level > 0:
            comp_ref[:] = jnp.zeros_like(comp_ref)

    # f32 multiply precision maps the reference's speed/accuracy ladder
    # onto the MXU's pass structure (the PRODUCT step is the shared
    # common.mxu_partial_dot, so the conv-VJP wgrad kernel and this one
    # cannot drift): level 0 ("plain", fastest) = bf16x3 decomposition
    # for f32 inputs, levels 1/2 pay for HIGHEST = 6 passes (true-f32
    # products) plus Kahan/Neumaier accumulation — like the reference,
    # each level trades speed for digits (config.py:245-248).
    partial = mxu_partial_dot(a_ref[:], b_ref[:], precision_level)
    if precision_level == 0:
        acc_ref[:] += partial
    elif precision_level == 1:
        # Kahan: y = partial - c; t = acc + y; c = (t - acc) - y
        y = partial - comp_ref[:]
        t = acc_ref[:] + y
        comp_ref[:] = (t - acc_ref[:]) - y
        acc_ref[:] = t
    else:
        # Neumaier: compensation works for |partial| > |acc| too
        acc = acc_ref[:]
        t = acc + partial
        big = jnp.abs(acc) >= jnp.abs(partial)
        comp_ref[:] += jnp.where(big, (acc - t) + partial,
                                 (partial - t) + acc)
        acc_ref[:] = t

    @pl.when(k == n_k - 1)
    def _store():
        total = acc_ref[:]
        if precision_level == 2:
            total = total + comp_ref[:]
        out_ref[:] = total.astype(out_ref.dtype)


def matmul(a, b, precision_level=0, blocks=None, out_dtype=None):
    """``a @ b`` through the Pallas tiled kernel.

    a: (M, K), b: (K, N).  Inputs may be float32 or bfloat16; the MXU
    accumulates in float32 regardless.

    ``precision_level`` trades digits for speed (the reference's
    PRECISION_LEVEL ladder).  Level 0 (default, fastest) computes
    float32 products via a bf16x3 decomposition on the MXU: ~5e-7 max
    relative error vs an f64 oracle (f32-class results) at ~2x the
    true-f32 throughput, BUT operands with |x| >= bf16 max (~3.39e38)
    or inf land outside the decomposition's domain and produce NaN.
    For inputs that large — or when bit-exact f32 products matter —
    use level 1 (true-f32 HIGHEST products + Kahan accumulation) or
    level 2 (adds Neumaier compensation).  bfloat16 inputs are
    unaffected: they always take single-pass MXU products.

    A thin eager wrapper around the jitted kernel: the interpret-mode
    decision needs the CONCRETE operand placement (CPU-committed arrays
    on a TPU-default host must interpret), which is invisible once
    everything is a tracer inside one jit.

    ``blocks=None`` consults the tuned schedule cache (docs/kernels.md
    "Autotuning": digest-keyed per padded shape/dtype/precision/device)
    before falling back to the static ``_DEFAULT_BLOCKS`` — tiles
    change the SCHEDULE, never the math, and a corrupt cache entry
    degrades to the static table with a warning.

    Debug guard (docs/health.md): set ``VELES_DEBUG_NONFINITE=1`` and
    every eager call validates its output, raising FloatingPointError
    with per-operand stats when inf/NaN appears — the level-0 bf16x3
    decomposition silently maps ``|x| >= bf16-max`` (and inf) to NaN,
    which otherwise surfaces only steps later as a skipped update.
    The check forces a device sync per call, so it is opt-in and for
    debugging only.
    """
    if blocks is None:
        blocks = _tuned_blocks(a, b, precision_level)
    out = _matmul_jit(a, b, precision_level, blocks, out_dtype,
                      interpret_for(a, b))
    # read live from ops.common — ONE patch point for every kernel's
    # guard (conv_vjp reads the same flag), per common.py's contract
    if _common.DEBUG_NONFINITE:
        _debug_check_finite(a, b, out, precision_level)
    return out


def _tuned_blocks(a, b, precision_level):
    """Schedule-cache consult for a ``blocks=None`` call: the tuned
    (bm, bn, bk) for this (padded shape, dtype, precision, device) or
    None (-> ``_DEFAULT_BLOCKS``).  Works on tracers too — only shapes
    and dtypes are read — so the consult happens at TRACE time inside
    an outer jit (e.g. the fused train step's lowering, which is how
    ``tune/walk.py`` records the shapes a step actually uses)."""
    if (getattr(a, "ndim", None) != 2 or getattr(b, "ndim", None) != 2
            or a.shape[1] != b.shape[0]):
        return None
    m, k = a.shape
    n = b.shape[1]
    if not (m and k and n):
        return None
    from veles_tpu.tune.cache import schedule_for
    from veles_tpu.tune.spec import matmul_spec, valid_schedule
    spec = matmul_spec(m, k, n, jnp.dtype(a.dtype).name,
                       precision_level)
    schedule = schedule_for(spec["op"], spec["shape"], spec["dtype"],
                            spec["precision_level"], spec["extra"],
                            raw=spec["raw"])
    if schedule is None:
        return None
    normalized = valid_schedule("matmul", schedule)
    return tuple(normalized["blocks"]) if normalized else None


def _operand_stats(name, x):
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return "%s: %s %s" % (name, x.shape, x.dtype)
    finite = jnp.isfinite(x)
    n_bad = int(jnp.sum(~finite))
    finite_abs = jnp.where(finite, jnp.abs(x), 0.0)
    return ("%s: %s %s, %d non-finite, max|finite| %.6g" %
            (name, x.shape, x.dtype, n_bad, float(jnp.max(finite_abs))
             if x.size else 0.0))


def _debug_check_finite(a, b, out, precision_level):
    if not bool(jnp.isfinite(out).all()):
        bf16_max = float(jnp.finfo(jnp.bfloat16).max)
        hint = ""
        if (precision_level == 0 and jnp.asarray(a).dtype ==
                jnp.float32 and bool(jnp.isfinite(a).all()) and
                bool(jnp.isfinite(b).all())):
            hint = (" — operands are finite, so this is the level-0 "
                    "bf16x3 domain limit (|x| >= %.4g maps to NaN); "
                    "use precision_level >= 1 for operands this large"
                    % bf16_max)
        raise FloatingPointError(
            "matmul produced non-finite output (%s)%s" % (
                "; ".join((_operand_stats("lhs", a),
                           _operand_stats("rhs", b),
                           _operand_stats("out", out))), hint))


@functools.partial(
    jax.jit, static_argnames=("precision_level", "blocks", "out_dtype",
                              "interpret"))
def _matmul_jit(a, b, precision_level, blocks, out_dtype, interpret):
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("matmul expects 2-D operands")
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError("shape mismatch: %s @ %s" % (a.shape, b.shape))
    out_dtype = out_dtype or a.dtype
    if m == 0 or n == 0 or k == 0:
        return jnp.zeros((m, n), out_dtype)
    bm, bn, bk = blocks or _DEFAULT_BLOCKS
    bm, bn, bk = (min(bm, ceil_mult(m, 8)), min(bn, ceil_mult(n, 128)),
                  min(bk, ceil_mult(k, 128)))
    a = pad_to(a, (bm, bk))
    b = pad_to(b, (bk, bn))
    mp, kp = a.shape
    _, np_ = b.shape
    n_k = kp // bk
    grid = (mp // bm, np_ // bn, n_k)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k,
                          precision_level=precision_level),
        name=KERNEL_NAME,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return unpad(out, (m, n))


def _chain_slope(mm, a, repeats):
    """One (chain(repeats+1) - chain(1)) / repeats slope sample over
    dependent ``acc = mm(acc)`` chains ended by a scalar fetch — the
    benchmark facade's sampling.  The autotuner runs the SAME chains
    through ``tune/measure.py`` (``slope_sample`` over the matmul
    family's dependent-chain runner), so the two cannot drift on
    methodology; this local helper only serves ``matmul_benchmark``'s
    one-shot power-rating path."""
    import time

    def chain(n):
        start = time.perf_counter()
        acc = a
        for _ in range(n):
            acc = mm(acc)
        float(acc[0, 0].astype(jnp.float32))
        return time.perf_counter() - start

    return (chain(repeats + 1) - chain(1)) / repeats


def matmul_benchmark(size=3001, dtype=jnp.float32, precision_level=0,
                     repeats=10, blocks=None, samples=1):
    """Time the kernel on an NxN self-multiply — the same measurement the
    reference's autotuner and DeviceBenchmark unit make
    (reference: ocl/benchmark.cl:1-11, accelerated_units.py:706).

    Measured as the slope between a 1-long and an (repeats+1)-long
    DEPENDENT chain, each ended by a scalar fetch: the fixed dispatch
    and fetch cost cancels, device time per matmul remains.  With
    ``samples`` > 1 the median of that many slopes is returned — single
    slopes can be noisy enough to go non-positive, so rank-sensitive
    callers (the autotuner) raise it; the one-shot default keeps the
    client power-rating handshake cheap.

    Returns the RAW slope, which may be zero or negative when jitter
    swamps the chain delta.  Callers must validate and discard
    non-positive samples (never clamp: a floored nonsense slope once
    crowned the wrong autotune tile and published an impossible rate).
    """
    import numpy
    a = jnp.asarray(
        (numpy.random.RandomState(13).rand(size, size) - 0.5) * 0.01,
        dtype=dtype)

    def mm(x):
        return matmul(x, a, precision_level=precision_level,
                      blocks=blocks)

    float(mm(a)[0, 0])  # compile + warmup

    slopes = sorted(_chain_slope(mm, a, repeats)
                    for _ in range(samples))
    mid = samples // 2
    return (slopes[mid] if samples % 2
            else (slopes[mid - 1] + slopes[mid]) / 2.0)


def autotune_matmul(device_info, size=2048, dtype=jnp.float32,
                    precision_level=0):
    """Pick the best block config for this chip and persist it
    (analog of reference backends.py:672-731 _find_optimal_bs_vo).

    Rewired onto the shared tune machinery (ONE measurement
    discipline, ONE persistence path, docs/kernels.md "Autotuning"):
    the curated candidate list lives in
    ``tune.spec.matmul_seed_candidates`` — where it also seeds the
    GA's population — and the sweep runs through
    ``tune.autotune.sweep_candidates``: round-robin interleaved
    chain-slope samples (timing each tile's samples back to back
    lets a drift in machine load crown the wrong tile), ranked under
    the positive-majority-median rule (a floor-clamped nonsense slope
    once crowned the wrong tile and published an impossible rate).
    VMEM-overflow tiles fail at the warm-up compile and are skipped.
    The winner persists in the digest-keyed ScheduleCache — the SAME
    entry ``matmul()`` consults for ``blocks=None`` calls of this
    padded shape — keyed by padded shape (tile optima don't transfer
    between shapes) and kernel version (optima measured on an old
    algorithm must never serve a new one).  When every tile's timing
    is jitter-swamped: fall back to ``_DEFAULT_BLOCKS`` and do NOT
    persist."""
    from veles_tpu.tune.autotune import sweep_candidates
    from veles_tpu.tune.cache import cache_for, schedule_key
    from veles_tpu.tune.spec import (matmul_seed_candidates,
                                     matmul_spec, valid_schedule)

    dtype_name = jnp.dtype(dtype).name
    spec = matmul_spec(size, size, size, dtype_name, precision_level)
    kind = device_info.device_kind
    digest, payload = schedule_key(
        spec["op"], spec["shape"], spec["dtype"],
        spec["precision_level"], kind, spec["extra"])
    cache = cache_for()
    entry = cache.get(digest)
    if entry is not None:
        normalized = valid_schedule("matmul", entry["schedule"])
        if normalized is not None:
            return tuple(normalized["blocks"])
    # the shipped per-chip table (devices/device_infos.json, the old
    # persistence path) still holds measured winners for the headline
    # sizes — migrate a hit into the schedule cache instead of paying
    # a fresh sweep on every fresh host
    legacy = device_info.get("matmul:v%d:%s:pl%d:s%d" % (
        MATMUL_KERNEL_VERSION, dtype_name, precision_level, size))
    if legacy is not None:
        normalized = valid_schedule(
            "matmul", {"blocks": [int(b) for b in legacy]})
        if normalized is not None:
            cache.put(digest, payload, normalized,
                      source="device_info")
            return tuple(normalized["blocks"])
    candidates = [{"blocks": list(c)} for c in
                  matmul_seed_candidates(dtype_name, precision_level)]
    # repeats=24: short chains (~8) can INVERT tile rankings — the
    # chain delta must stand clear of the per-chain jitter
    best, _ranking = sweep_candidates(
        spec, candidates, repeats=24, rounds=5, device_kind=kind,
        cache=cache)
    if best is None:
        import logging
        logging.getLogger("veles_tpu.autotune").warning(
            "autotune_matmul: no tile produced a positive timing "
            "slope (size=%d dtype=%s); falling back to %s and NOT "
            "persisting", size, dtype_name, _DEFAULT_BLOCKS)
        return _DEFAULT_BLOCKS
    return tuple(best["blocks"])
