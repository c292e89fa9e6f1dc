"""veles_tpu.tune — genetics-driven Pallas schedule autotuner.

The pieces (docs/kernels.md, "Autotuning"):

- ``tune.cache`` — the digest-keyed on-disk :class:`ScheduleCache` the
  kernels consult (beside the XLA compile cache), plus the
  ``record_specs`` walk hook and the ``tune.*`` counters;
- ``tune.spec`` — per-kernel-family search spaces (Tune markers),
  MXU-legal quantization, VMEM feasibility, the shared cache-key spec
  builders;
- ``tune.measure`` — the ONE timing discipline (pass filtering,
  positive-majority ranking, interleaved round-robin sampling) shared
  with ``autotune_matmul``;
- ``tune.costmodel`` — the deterministic learned cost model (boosted
  stumps over hand-built features, pure numpy) trained on the
  ``measurements.jsonl`` sidecar, with its leave-one-spec-out trust
  gate;
- ``tune.autotune`` — the GA driver (:class:`ScheduleTuner`, incl.
  the model-ranked ``fitness="model"`` mode) and the plain curated
  sweep (:func:`sweep_candidates`);
- ``tune.walk`` — spec harvesting from a fused step's lowering;
- ``python -m veles_tpu.tune`` — tune the shapes a zoo model actually
  uses and commit a ``TUNE.json`` receipt; ``--merge-bank`` folds a
  fleet schedule bank into the local cache, ``--report`` audits the
  training data/bank provenance.
"""

from veles_tpu.tune.cache import (  # noqa: F401
    MeasurementLog, ScheduleCache, cache_for, default_cache_dir,
    load_bank, measurement_log, provenance, record_specs,
    schedule_for, schedule_key, tune_counters)
from veles_tpu.tune.measure import filter_passes  # noqa: F401
from veles_tpu.tune.spec import (  # noqa: F401
    FAMILIES, conv_vjp_spec, family_for, matmul_int8_spec,
    matmul_spec, pool_bwd_spec, valid_schedule)

__all__ = ["ScheduleCache", "MeasurementLog", "cache_for",
           "measurement_log", "load_bank", "default_cache_dir",
           "provenance", "record_specs", "schedule_for",
           "schedule_key", "tune_counters", "filter_passes",
           "FAMILIES", "family_for", "matmul_spec",
           "matmul_int8_spec", "conv_vjp_spec", "pool_bwd_spec",
           "valid_schedule"]
