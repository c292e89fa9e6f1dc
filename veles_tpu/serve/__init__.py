"""Production inference serving (docs/serving.md).

The libVeles role of the reference — a standalone, load-and-run
inference runtime — rebuilt TPU-idiomatically in three layers:

- :mod:`veles_tpu.serve.engine` — :class:`AOTEngine`: ahead-of-time
  compiled executables over a ladder of padded batch shapes, backed by
  the persistent XLA compilation cache so a restarted server performs
  0 new backend compiles (receipt:
  ``engine.compile_receipt`` via the ``compile.count`` /
  ``compile.cache_hits`` counters);
- :mod:`veles_tpu.serve.batcher` — :class:`ContinuousBatcher`: a worker
  thread draining the request queue into the largest fitting rung with
  a bounded queue-delay, ping-pong host staging (the PR 1 machinery),
  load shedding (``ServeOverload`` -> HTTP 503 + retry_after) and
  p50/p99 latency SLO tripwires;
- :mod:`veles_tpu.serve.router` — :class:`ReplicaPool`: one
  engine+batcher replica per visible device behind a least-loaded
  router with overload cascade, shared persistent compile cache (warm
  fleet start = one compile set), and snapshot hot-reload (same digest
  = zero-recompile buffer swap; new digest = background AOT warm-up +
  atomic cutover, queue never dropped);
- :mod:`veles_tpu.serve.transport` — the binary frame listener beside
  the JSON front: ``network_common``'s ``!IIB`` framing + HMAC with a
  fixed dtype/shape/raw-bytes tensor codec (the serve port never
  unpickles) and a same-host :class:`ShmChannel` payload bypass;
- :mod:`veles_tpu.serve.service` — :class:`ServeService`: the tornado
  front (``/infer``, ``/healthz``, ``/metrics.json``, ``/reload``,
  ``/publish``), async handlers so concurrent clients actually
  co-batch;
- :mod:`veles_tpu.serve.freshness` — the train-to-serve freshness
  loop: :class:`SnapshotWatcher` (manifest-verified pickup of the
  trainer's published snapshots), :class:`FreshnessController`
  (finite gate, background warm-up, mirrored canary judgment via
  :class:`CanaryComparator`) over the router's canary state machine —
  promote fleet-wide or auto-roll back to the last-good digest with
  zero new compiles;
- :mod:`veles_tpu.serve.qos` — multi-tenant QoS: SLO classes
  (``interactive`` / ``batch`` / ``best_effort``), per-tenant
  token-bucket admission quotas, class-ordered shedding
  (:data:`~veles_tpu.serve.qos.SHED_ORDER`), per-class hedge budgets
  and the seeded per-class ``retry_after`` jitter — the serve tier
  degrades selectively under overload instead of uniformly;
- :mod:`veles_tpu.serve.fleet` — the multi-host tier:
  :class:`FleetRouter` dispatches over many serve HOSTS (pipelined
  binary links, membership epochs via ``elastic.FleetView``,
  throughput-EMA weighted least-loaded routing with host-granular
  overload cascade) and hedges stragglers — re-dispatch past the
  power-corrected threshold, first result wins, loser cancelled over
  the wire — with exactly-once completion under host loss (a SIGKILL
  mid-stream costs bounded p99, never a failed request).

``python -m veles_tpu.serve --snapshot model.pickle`` serves a trained
snapshot; ``scripts/serve_load.py`` is the closed-loop load generator
behind ``BENCH_serve.json``.
"""

from veles_tpu.serve.qos import (  # noqa: F401
    DEFAULT_CLASS, HedgeBudget, RetryJitter, SHED_ORDER, SLO_CLASSES,
    TenantQuota, TokenBucket, normalize_class, parse_quota_spec)
from veles_tpu.serve.batcher import (  # noqa: F401
    ContinuousBatcher, ServeOverload, serve_snapshot)
from veles_tpu.serve.engine import (  # noqa: F401
    AOTEngine, DEFAULT_LADDER, model_digest, value_digest)
from veles_tpu.serve.fleet import (  # noqa: F401
    FleetRequest, FleetRouter, HostLink)
from veles_tpu.serve.freshness import (  # noqa: F401
    CanaryComparator, FleetCanaryController, FreshnessController,
    LocalHostControl, SnapshotWatcher, export_model_spec)
from veles_tpu.serve.router import (  # noqa: F401
    CanaryCutover, Replica, ReplicaPool, local_devices)
from veles_tpu.serve.service import (  # noqa: F401
    ServeService, format_result)
from veles_tpu.serve.transport import (  # noqa: F401
    BinaryTransportClient, BinaryTransportServer, decode_tensor,
    encode_tensor)

__all__ = ["AOTEngine", "BinaryTransportClient",
           "BinaryTransportServer", "CanaryComparator",
           "CanaryCutover", "ContinuousBatcher", "FleetCanaryController",
           "FleetRequest", "FleetRouter", "FreshnessController",
           "HedgeBudget", "HostLink", "LocalHostControl", "Replica",
           "ReplicaPool", "RetryJitter", "ServeOverload",
           "ServeService", "SnapshotWatcher", "TenantQuota",
           "TokenBucket", "DEFAULT_CLASS", "DEFAULT_LADDER",
           "SHED_ORDER", "SLO_CLASSES", "decode_tensor",
           "encode_tensor", "export_model_spec", "format_result", "local_devices",
           "model_digest", "normalize_class", "parse_quota_spec",
           "serve_snapshot", "value_digest"]
