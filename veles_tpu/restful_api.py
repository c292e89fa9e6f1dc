"""RESTful serving of a trained workflow.

Reference veles/restful_api.py:78: HTTP POST /api with {"input": ...}
feeds the loader and returns the transformed evaluation result.  Since
PR 7 this unit is a compatibility front over the real serving
subsystem (:mod:`veles_tpu.serve`, docs/serving.md): initialization
builds an :class:`~veles_tpu.serve.AOTEngine` (pre-compiled batch-shape
ladder, optional persistent compile cache) and a continuous batcher,
and the tornado endpoint is served by :class:`~veles_tpu.serve.
ServeService`'s async handler — concurrent requests co-batch into one
device dispatch with a single host sync per BATCH, where the old unit
jit-compiled ad hoc and synced per request.  The endpoint contract
(``{"input": ...}`` -> ``{"result", "probabilities"}``), the
``infer()`` method and ``requests_served`` are unchanged; overload now
answers ``503`` + ``retry_after`` instead of queueing without bound.
"""

import numpy

from veles_tpu.units import Unit

__all__ = ["RESTfulAPI"]


class RESTfulAPI(Unit):
    def __init__(self, workflow, **kwargs):
        super(RESTfulAPI, self).__init__(workflow, **kwargs)
        self.port = kwargs.get("port", 0)
        self.path = kwargs.get("path", "/api")
        #: serving knobs (docs/serving.md); defaults keep the unit a
        #: drop-in for the old single-sample server
        self.ladder = tuple(kwargs.get("ladder", (1, 8, 32, 128)))
        self.max_delay_s = kwargs.get("max_delay_s", 0.002)
        self.max_queue = kwargs.get("max_queue", 256)
        self.slo_p50_ms = kwargs.get("slo_p50_ms")
        self.slo_p99_ms = kwargs.get("slo_p99_ms")
        self.engine = None
        self._service_ = None
        self.restartable = False  # stop() shuts the HTTP server down

    @property
    def requests_served(self):
        return (self._service_.samples_served
                if self._service_ is not None else 0)

    def initialize(self, **kwargs):
        super(RESTfulAPI, self).initialize(**kwargs)
        from veles_tpu.serve import AOTEngine, ServeService
        loader = getattr(self.workflow, "loader", None)
        self.engine = AOTEngine.from_workflow(
            self.workflow, ladder=self.ladder)
        self.engine.compile()
        self._service_ = ServeService(
            self.engine, port=self.port, path=self.path,
            labels_mapping=getattr(loader, "reversed_labels_mapping",
                                   None),
            max_delay_s=self.max_delay_s, max_queue=self.max_queue,
            slo_p50_ms=self.slo_p50_ms, slo_p99_ms=self.slo_p99_ms)
        return True

    def infer(self, sample):
        """sample: nested list/array (with or without batch dim);
        compatibility wrapper over the batcher (rows co-batch with any
        concurrent HTTP traffic)."""
        if self._service_ is None:
            raise RuntimeError("initialize() the unit before infer()")
        if not self._service_.batcher.running:
            # programmatic use without start_background(): serve
            # in-process through the engine's sequential path (the
            # engine normalizes bare samples to a batch itself)
            probs = self.engine.infer(
                numpy.asarray(sample, self.engine.dtype))
            with self._service_._served_lock:
                self._service_.samples_served += len(probs)
            from veles_tpu.serve import format_result
            return format_result(probs, self._service_.labels_mapping)
        return self._service_.infer_payload(sample)

    # -- HTTP ---------------------------------------------------------------

    def start_background(self):
        thread = self._service_.start_background()
        self.port = self._service_.port
        self.info("REST API on http://127.0.0.1:%d%s (serve engine: "
                  "ladder %s)", self.port, self.path,
                  list(self.engine.ladder))
        return thread

    def stop(self):
        super(RESTfulAPI, self).stop()
        if self._service_ is not None:
            self._service_.stop()
