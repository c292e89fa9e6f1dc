"""HTTP front for the serving subsystem.

``POST <path> {"input": sample-or-batch}`` answers like the original
``RESTfulAPI`` contract (``{"result": label(s), "probabilities":
[...]}``) but the handler is *async*: the tornado IO loop hands the
blocking batcher wait to a thread pool and keeps accepting requests,
so concurrent clients actually co-batch — a synchronous handler would
serialize the queue and continuous batching could never see more than
one request at a time.

Besides inference the service exposes the operational surface:

- ``GET /healthz`` — the serve health block (queue depth, SLO
  violations, latency percentiles), the engine's compile receipt and
  the model digest; what a load balancer or the web-status dashboard
  polls;
- ``GET /metrics.json`` — the full metrics-registry snapshot.

Overload answers ``503`` with a ``retry_after`` hint (the blacklist
protocol's shape); per-request wall time lands in the ``http.request_s``
histogram and a per-request ``serve.request`` span via the
``http_util.RequestTimer`` mixin (perf_counter, not tornado's
``time.time``-based ``request_time``).
"""

import json
import threading
import time

import numpy

from veles_tpu.http_util import BackgroundHTTPServer, RequestTimer
from veles_tpu.logger import Logger
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.serve import qos
from veles_tpu.serve.batcher import ContinuousBatcher, ServeOverload
from veles_tpu.serve.batcher import serve_snapshot

__all__ = ["ServeService", "format_result"]


def format_result(probs, labels_mapping=None):
    """Shape a probability block into the REST response contract:
    argmax label(s) mapped through the loader's reverse mapping, plus
    the raw probabilities.

    Vectorized once per payload: ``probs`` arrives as (a view of) the
    batcher's per-batch host buffer — never re-copied here — and the
    float boxing the JSON front must pay happens in exactly ONE
    C-level ``tolist`` over the whole block, not per element through
    ``numpy.asarray`` round-trips per request (the pre-PR-10 shape of
    this function)."""
    if not isinstance(probs, numpy.ndarray):
        probs = numpy.asarray(probs)
    single = probs.ndim == 1
    block = probs[None] if single else probs  # [None] is a view
    labels = block.argmax(axis=1)
    if labels_mapping:
        named = [labels_mapping.get(int(label), int(label))
                 for label in labels]
    else:
        named = labels.tolist()  # one vectorized box, no dict probes
    return {"result": named[0] if single or len(named) == 1 else named,
            "probabilities": block.tolist()}


class ServeService(Logger):
    """Tornado service over an :class:`AOTEngine` + batcher, or a
    whole :class:`ReplicaPool`.

    ``engine`` may be a single AOT engine (``batcher`` optionally
    shared — the RESTful unit passes its own; when None one is built
    from ``batcher_kwargs`` and owned) or a :class:`ReplicaPool`, in
    which case every request rides the pool's least-loaded router and
    ``/healthz`` carries the per-replica state.  ``transport_port``
    additionally opens the binary frame listener
    (:mod:`veles_tpu.serve.transport`) beside the JSON front — same
    batcher/pool, so JSON and binary clients co-batch."""

    def __init__(self, engine, batcher=None, port=0, path="/infer",
                 labels_mapping=None, executor_workers=64,
                 transport_port=None, transport_secret=None,
                 freshness=None, quota=None, retry_jitter=None,
                 **batcher_kwargs):
        super(ServeService, self).__init__()
        #: per-tenant admission quota (qos.TenantQuota), shared with
        #: the binary transport when one is opened; None disables
        #: quota — legacy behavior, nothing is rejected here
        self.quota = quota
        self.retry_jitter = retry_jitter if retry_jitter is not None \
            else qos.RetryJitter()
        from veles_tpu.serve.fleet import FleetRouter
        from veles_tpu.serve.router import ReplicaPool
        self._is_fleet = isinstance(engine, FleetRouter)
        if isinstance(engine, (ReplicaPool, FleetRouter)):
            # a pool of local replicas or a FRONT over remote serve
            # hosts (docs/serving.md "Multi-host tier") — both speak
            # the batcher submit contract, so /infer and the binary
            # transport drive them identically
            self.router = engine
            self._engine = None
            self._owns_batcher = True
            self.batcher = engine  # same submit contract
        else:
            self.router = None
            self._engine = engine
            self._owns_batcher = batcher is None
            self.batcher = batcher if batcher is not None else \
                ContinuousBatcher(engine, **batcher_kwargs)
        self.path = path
        self.labels_mapping = labels_mapping or {}
        self.samples_served = 0
        self.last_reload = None
        self._served_lock = threading.Lock()
        self._reload_lock = threading.Lock()
        self._executor = None
        self._executor_workers = int(executor_workers)
        self._server = None
        self._port = port
        self._transport = None
        self._transport_port = transport_port
        self._transport_secret = transport_secret
        #: optional FreshnessController (docs/serving.md "Freshness
        #: loop"): referenced, not owned — the caller manages its
        #: lifecycle; the service adds the ``POST /publish`` push
        #: front and the /healthz freshness block
        self.freshness = freshness

    @property
    def engine(self):
        """The (replica 0) engine — LIVE across hot reloads."""
        return self.router.engine if self.router is not None \
            else self._engine

    @property
    def compile_receipt(self):
        source = self.router if self.router is not None else self.engine
        return source.compile_receipt

    @property
    def port(self):
        return self._server.port if self._server is not None \
            else self._port

    @property
    def transport_port(self):
        return self._transport.port if self._transport is not None \
            else self._transport_port

    # -- request handling (executor thread) ---------------------------------

    def infer_payload(self, sample, tenant=None, slo_class=None,
                      trace=None):
        """Blocking inference for one payload: a single sample or a
        batch.  Batch payloads are submitted row-by-row, so their rows
        co-batch with every other in-flight request — a large payload
        does not monopolize a rung.  A payload that sheds partway
        through submission cancels its already-queued rows (the worker
        drops them at dispatch) so a 503'd request never leaves orphan
        work computing for nobody.

        ``tenant``/``slo_class`` are the QoS identity (docs/serving.md
        "Multi-tenant QoS"): the tenant's token-bucket quota is charged
        per SAMPLE here — one admission decision covers the payload —
        and the class labels every row for class-ordered shedding;
        un-labelled legacy payloads serve as class ``batch``.

        ``trace`` is the request trace id (docs/observability.md
        "Request tracing"): a client-supplied id is validated through
        ``normalize_trace_id`` (bounded plain string — the trust
        boundary is unchanged), an absent one is minted here so every
        admitted payload is attributable; all rows of one payload
        share the id.  The answer echoes it as ``"trace"``."""
        from veles_tpu.observe import requests as reqtrace
        slo_class = qos.normalize_class(slo_class)
        if reqtrace.enabled:
            trace = reqtrace.normalize_trace_id(trace) or \
                reqtrace.mint_trace_id()
            t_admit = time.perf_counter()
        else:
            trace = None
            t_admit = None
        x = numpy.asarray(sample, self.engine.dtype)
        if x.shape == self.engine.sample_shape:
            x = x[None]
        if self.quota is not None:
            wait = self.quota.admit(tenant, cost=float(x.shape[0]))
            if wait is not None:
                qos.note_shed(slo_class)
                raise ServeOverload(
                    "tenant %r over quota" % (tenant,),
                    retry_after=self.retry_jitter.apply(
                        max(wait, 0.05), slo_class))
        requests = []
        try:
            for row in x:
                req = self.batcher.submit(row, slo_class=slo_class,
                                          trace=trace)
                if t_admit is not None and \
                        getattr(req, "marks", 0) is None:
                    # front-door admission segment (decode + quota
                    # charge); the worker appends the queue/batch
                    # marks behind it at completion
                    req.marks = [("admit", t_admit,
                                  req.enqueued - t_admit)]
                requests.append(req)
        except Exception:
            for req in requests:
                req.cancelled = True
            raise
        probs = []
        for req in requests:
            if not req.done.wait(30.0):
                raise TimeoutError("inference timed out")
            if req.error is not None:
                raise req.error
            probs.append(req.result)
        with self._served_lock:
            self.samples_served += len(probs)
        # the results are views of per-batch host buffers (no
        # per-request copies anywhere behind us); a single-row payload
        # needs no stack at all — [None] is a view
        block = probs[0][None] if len(probs) == 1 \
            else numpy.stack(probs)
        answer = format_result(block, self.labels_mapping)
        if trace is not None:
            answer["trace"] = trace
        return answer

    # -- snapshot hot-reload ------------------------------------------------

    def reload_snapshot(self, path):
        """Swap the served model for a trained-workflow snapshot (the
        crash-consistent pickles ``snapshotter.py`` writes) WITHOUT
        dropping the queue; returns the reload receipt.  Triggered by
        ``POST /reload {"snapshot": path}`` or SIGHUP (serve CLI)."""
        from veles_tpu.workflow import restore_workflow
        return self.reload_workflow(restore_workflow(path))

    def reload_workflow(self, sw):
        if self.router is not None:
            receipt = self.router.reload_workflow(sw)
        else:
            from veles_tpu.serve.router import ReplicaPool
            try:
                plans, params, shape = ReplicaPool._workflow_spec(sw)
            except ValueError:
                plans, params, shape = ReplicaPool._workflow_spec(
                    sw, self.engine.sample_shape)
            receipt = self.reload(params, plans=plans,
                                  sample_shape=shape)
        self.last_reload = receipt
        return receipt

    def reload(self, params, plans=None, sample_shape=None):
        """Snapshot hot-reload through the ONE shared state machine
        (:func:`veles_tpu.serve.router.reload_replicas`): a same-digest
        snapshot swaps weight buffers in place (zero recompiles), a
        changed digest AOT-warms a new engine off the dispatch path
        and cuts the batcher over between batches.  The single-engine
        service is simply a fleet of one entry — same receipt, same
        lock discipline, and the replacement engine inherits the
        current one's ladder and dtype."""
        if self.router is not None:
            receipt = self.router.reload(
                params, plans=plans, sample_shape=sample_shape)
            self.last_reload = receipt
            return receipt
        from veles_tpu.serve.router import Replica, reload_replicas
        with self._reload_lock:
            current = self.engine
            entry = Replica(0, current.device, current, self.batcher)
            receipt = reload_replicas(
                [entry], params, plans=plans,
                sample_shape=sample_shape,
                engine_kwargs=dict(
                    ladder=current.ladder, dtype=current.dtype))
            self._engine = entry.engine
        self.last_reload = receipt
        return receipt

    # -- HTTP ---------------------------------------------------------------

    def _make_app(self):
        import tornado.web

        svc = self

        class InferHandler(RequestTimer, tornado.web.RequestHandler):
            async def post(self):
                import asyncio
                try:
                    body = json.loads(self.request.body)
                    payload = body["input"]
                except Exception as exc:
                    self.set_status(400)
                    self.write({"error": "bad request: %s" % exc})
                    return
                # QoS identity: body fields win over headers; both
                # optional — un-labelled legacy clients serve as
                # tenant None / class "batch"
                tenant = body.get("tenant") or \
                    self.request.headers.get("X-Tenant")
                slo_class = body.get("slo_class") or \
                    self.request.headers.get("X-SLO-Class")
                # request trace id (docs/observability.md "Request
                # tracing"): body field wins over header; invalid or
                # absent ids are re-minted inside infer_payload
                trace = body.get("trace") or \
                    self.request.headers.get("X-Trace-Id")
                loop = asyncio.get_event_loop()
                try:
                    answer = await loop.run_in_executor(
                        svc._executor,
                        lambda: svc.infer_payload(
                            payload, tenant=tenant,
                            slo_class=slo_class, trace=trace))
                except ServeOverload as exc:
                    # the blacklist protocol's transient-reject shape
                    self.set_status(503)
                    self.set_header("Retry-After",
                                    "%.3f" % exc.retry_after)
                    self.write({"error": str(exc),
                                "retry_after": exc.retry_after})
                except (ValueError, TypeError) as exc:
                    self.set_status(400)
                    self.write({"error": str(exc)})
                except Exception as exc:
                    self.set_status(500)
                    self.write({"error": str(exc)})
                else:
                    self.write(answer)

        class HealthHandler(RequestTimer, tornado.web.RequestHandler):
            def get(self):
                health = {
                    "status": "ok",
                    "model_digest": svc.engine.digest,
                    "ladder": list(svc.engine.ladder),
                    "compile": svc.compile_receipt,
                    "serve": serve_snapshot(),
                }
                if svc.router is not None:
                    health["fleet" if svc._is_fleet else
                           "replicas"] = svc.router.snapshot()
                if svc.transport_port is not None:
                    health["transport_port"] = svc.transport_port
                if svc.last_reload is not None:
                    health["last_reload"] = svc.last_reload
                if svc.freshness is not None:
                    health["freshness"] = svc.freshness.snapshot()
                # the alert-history ring (observe/alerts.py): a
                # fleet front reports its router's OWN manager (the
                # one sweeping fleet rollups); everything else the
                # process-global one
                manager = getattr(svc.router, "alerts", None) \
                    if svc.router is not None else None
                if manager is None:
                    from veles_tpu.observe.alerts import alerts \
                        as manager
                health["alerts"] = manager.snapshot()
                self.write(health)

        class MetricsHandler(RequestTimer, tornado.web.RequestHandler):
            def get(self):
                self.set_header("Content-Type", "application/json")
                self.write(json.dumps(_registry.snapshot(),
                                      default=repr))

        class ReloadHandler(RequestTimer, tornado.web.RequestHandler):
            async def post(self):
                import asyncio
                try:
                    body = json.loads(self.request.body or b"{}")
                    snapshot = body["snapshot"]
                except Exception as exc:
                    self.set_status(400)
                    self.write({"error": "bad request (need "
                                "{\"snapshot\": path}): %s" % exc})
                    return
                loop = asyncio.get_event_loop()
                try:
                    # blocking restore+reload off the IO loop: requests
                    # keep serving while the new weights warm up
                    receipt = await loop.run_in_executor(
                        svc._executor, svc.reload_snapshot, snapshot)
                except FileNotFoundError as exc:
                    self.set_status(404)
                    self.write({"error": str(exc)})
                except Exception as exc:
                    self.set_status(500)
                    self.write({"error": str(exc)})
                else:
                    self.write(receipt)

        class PublishHandler(RequestTimer, tornado.web.RequestHandler):
            def post(self):
                """Freshness push: a trainer (or CI) announces a new
                publish instead of waiting out the poll interval.  The
                body's ``snapshot`` path is ADVISORY — the watcher
                still reads LATEST and verifies the manifest before
                unpickling; a push can never bypass the gate."""
                if svc.freshness is None:
                    self.set_status(409)
                    self.write({"error": "no freshness loop attached "
                                "(start the service with a "
                                "FreshnessController / --watch-dir)"})
                    return
                try:
                    body = json.loads(self.request.body or b"{}")
                except Exception as exc:
                    self.set_status(400)
                    self.write({"error": "bad request: %s" % exc})
                    return
                svc.freshness.notify(body.get("snapshot"))
                self.write({"status": "notified",
                            "freshness": svc.freshness.snapshot()})

        return tornado.web.Application([
            (self.path, InferHandler),
            (r"/healthz", HealthHandler),
            (r"/metrics.json", MetricsHandler),
            (r"/reload", ReloadHandler),
            (r"/publish", PublishHandler),
        ])

    def start_background(self):
        from concurrent.futures import ThreadPoolExecutor
        # waiting requests only block on an Event, so workers are
        # cheap; the pool bounds in-flight HTTP requests, the batcher's
        # max_queue bounds admitted ones
        self._executor = ThreadPoolExecutor(
            max_workers=self._executor_workers,
            thread_name_prefix="serve-http")
        if self._owns_batcher:
            self.batcher.start()
        if self._transport_port is not None:
            from veles_tpu.serve.transport import BinaryTransportServer
            self._transport = BinaryTransportServer(
                self.batcher, port=self._transport_port,
                secret=self._transport_secret, quota=self.quota,
                retry_jitter=self.retry_jitter)
            self._transport.start_background()
        self._server = BackgroundHTTPServer(self._make_app(),
                                            port=self._port)
        thread = self._server.start()
        self.info("serve endpoint on http://127.0.0.1:%d%s "
                  "(healthz, metrics.json%s)", self.port, self.path,
                  "; binary transport :%d" % self.transport_port
                  if self._transport is not None else "")
        return thread

    def stop(self):
        # order matters: close the listeners (no new work), fail the
        # batcher's pending requests (unblocks executor tasks), THEN
        # join the executor so no worker thread outlives the service
        if self._transport is not None:
            self._transport.stop()
            self._transport = None
        if self._server is not None:
            self._server.stop()
            self._server = None
        if self._owns_batcher:
            self.batcher.stop()
        if self._executor is not None:
            self._executor.shutdown(wait=self._owns_batcher)
            self._executor = None
