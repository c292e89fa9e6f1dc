"""Device benchmark: matmul self-multiply timing.

TPU-native counterpart of reference ocl/benchmark.cl:1-11 and the
DeviceBenchmark unit (reference: accelerated_units.py:706,768-778) used
for (a) kernel autotuning and (b) the "computing power" rating that load-
balances job farming across heterogeneous workers.
"""

from veles_tpu.ops.matmul import autotune_matmul, matmul_benchmark

__all__ = ["estimate_computing_power", "matmul_benchmark",
           "autotune_matmul"]


def estimate_computing_power(size=1024, repeats=3):
    """1000 / avg-matmul-seconds, the reference's arbitrary power unit.

    An implausible slope (jitter swamping the chain delta) is
    remeasured with a longer chain; if it never becomes credible the
    rating fails loudly — a clamped nonsense rating would skew the
    master's load balancing invisibly.  Credible means implying a
    rate below 1 PFLOP/s for the measured shape: a bare ``> 0`` check
    passes microsecond jitter slopes and publishes the same invisible
    skew the loud-failure path exists to prevent."""
    min_credible_s = 2.0 * size ** 3 / 1e15
    for scale in (1, 4, 16):
        elapsed = matmul_benchmark(size=size, repeats=repeats * scale)
        if elapsed >= min_credible_s:
            return 1000.0 / elapsed
    raise RuntimeError(
        "estimate_computing_power: matmul timing slope stayed below "
        "the minimum credible time (%.3g s for a %d^3 matmul) after "
        "remeasurement; refusing to publish a power rating from "
        "noise" % (min_credible_s, size))
