"""The ONE timing-measurement discipline for schedule ranking.

Every published kernel-schedule ranking — the GA autotuner's fitness,
``ops/matmul.py``'s curated candidate sweep — runs through these
helpers, so the jitter policy can never drift between the tuners:

- **Pass filtering** (``filter_passes``): a non-positive chain slope
  means host jitter exceeded the whole chain delta for that pass — it
  measured the noise, not the program.  Such passes are
  DISCARDED, never clamped (a floor-clamped negative slope once
  published an impossible rate and crowned the wrong autotune tile).
- **Positive majority** (``rank``): a candidate's median runs over ALL
  its samples and must be positive with a positive MAJORITY.
  Filtering negatives first would let a jitter-swamped candidate win
  on its two tiny surviving samples.
- **Interleaving** (``interleaved_slopes``): machine load drifts
  during a run, so timing each candidate's samples back to back lets
  a drift crown the wrong schedule.  One sample of EVERY candidate per round spreads the
  drift across all candidates equally; the median over rounds then
  ranks honestly — the same hazard ``ops/matmul.py`` documents.
"""

import time

__all__ = ["filter_passes", "chain_seconds", "slope_sample",
           "interleaved_slopes", "rank", "positive_majority_median"]


def filter_passes(samples):
    """Drop jitter-dominated timing passes: a non-positive slope means
    host jitter exceeded the whole chain delta for that pass — it
    measures the noise, not the program (same discard-never-clamp
    policy as the matmul autotuner).  Returns the retained passes; when EVERY pass is
    jitter-dominated the raw list comes back unchanged so the caller's
    plausibility floor (not this filter) rejects the measurement."""
    used = [s for s in samples if s > 0]
    return used if used else list(samples)


def positive_majority_median(samples):
    """Median over ALL samples, published only when a positive
    MAJORITY of passes survived and the median itself is positive;
    ``None`` otherwise (the candidate measured only noise)."""
    import numpy
    positive = sum(1 for s in samples if s > 0)
    if not samples or positive < len(samples) // 2 + 1:
        return None
    med = float(numpy.median(samples))
    return med if med > 0 else None


def chain_seconds(run, n):
    """Wall seconds for ``run(n)`` — run ``n`` dependent/queued kernel
    executions ended by a completion fetch.  ``run`` owns the blocking
    discipline (a scalar fetch or block_until_ready)."""
    start = time.perf_counter()
    run(n)
    return time.perf_counter() - start


def slope_sample(run, n1, n2):
    """One (t(n2) - t(n1)) / (n2 - n1) slope sample: the fixed
    dispatch and fetch cost cancels, per-execution time remains.  May be
    zero or negative when jitter swamps the chain delta — callers
    filter (``filter_passes``), never clamp."""
    t1 = chain_seconds(run, n1)
    t2 = chain_seconds(run, n2)
    return (t2 - t1) / (n2 - n1)


def interleaved_slopes(runners, n1, n2, rounds=5):
    """Round-robin slope samples: one sample of EVERY candidate per
    round, ``rounds`` rounds.  ``runners`` maps candidate key ->
    ``run(n)`` callable (already compiled/warmed — a cold compile
    inside a timed chain would be charged as device time).  A runner
    that raises mid-round just misses that round's sample."""
    samples = {key: [] for key in runners}
    for _ in range(rounds):
        for key, run in runners.items():
            try:
                samples[key].append(slope_sample(run, n1, n2))
            except Exception:
                continue
    return samples


def rank(samples_by_key):
    """{key: median seconds or None} under the positive-majority
    discipline; keys whose every sample was jitter come back None and
    must never be crowned."""
    return {key: positive_majority_median(samples)
            for key, samples in samples_by_key.items()}
