"""The genetics-driven schedule tuner (docs/kernels.md, "Autotuning").

TVM's lesson (PAPERS.md) applied with the repo's own GA: a
:class:`ScheduleTuner` searches one kernel family's tile/grid space
per (op, padded shape, dtype, precision level, device kind) spec and
persists the winner in the digest-keyed :class:`~veles_tpu.tune.cache.
ScheduleCache` the kernels consult.

Fitness = **negative measured seconds per kernel execution**, under
the shared measurement discipline (``tune/measure.py``): the
in-process path evaluates a whole GA generation's candidates with
interleaved round-robin slope sampling — one sample of EVERY candidate
per pass, ``filter_passes``/positive-majority ranking — so a drift
in machine load cannot crown the wrong tile (the hazard
``ops/matmul.py`` documents).  Candidate schedules are quantized to
MXU-legal multiples and VMEM-checked BEFORE any compile; duplicate or
clamped-identical genomes hit the schedule-keyed fitness memo (plus
GeneticsOptimizer's own values-keyed memo) and never pay a second
compile.

Evaluator plumbing mirrors the GA's: ``workers=N`` uses the process
pool — CPU-pinned children, so compile-fitness only: measured fitness
on a chip refuses it, the chip belongs to this process —
``farm_slaves``/``farm_address`` the control-plane job farm (remote
hosts with their own chips join via :func:`GeneticsOptimizer.worker`
quoting :func:`evaluate_candidate`) — a fleet can tune in parallel.  Those
paths score candidates independently (each with its own multi-pass
filtered timing); only the in-process default gets cross-candidate
interleaving.

``fitness="compile"`` replaces timing with one compile+execute pass
(fitness = negative wall seconds of the warm-up) — the CI mode: it
exercises every moving part on CPU interpret kernels in seconds and
still rejects uncompilable candidates.

``fitness="model"`` is the learned-cost-model mode (``tune/
costmodel.py``): every generation's distinct feasible schedules are
ranked by the model and only the top decile (floor: 2) compiles and
measures under the base discipline (``model_base``: "measure", or
"compile" for CI); the rest inherit their PREDICTED fitness for
selection purposes only.  Every measured slope — in every mode — is
appended to the ``measurements.jsonl`` sidecar, which is where the
model's training data comes from in the first place.  The persisted
winner is always the best MEASURED schedule; a predicted fitness can
steer the GA but can never reach the cache.  When the family's
training data is thin or the model fails its leave-one-spec-out trust
gate the tuner silently degrades to the base mode (the receipt row
says why).
"""

import json

from veles_tpu.genetics.config import Tune
from veles_tpu.genetics.optimizer import GeneticsOptimizer
from veles_tpu.logger import Logger
from veles_tpu.observe.metrics import registry as _registry
from veles_tpu.observe.trace import tracer as _tracer
from veles_tpu.tune import cache as _cache
from veles_tpu.tune import measure as _measure
from veles_tpu.tune.spec import family_for

__all__ = ["ScheduleTuner", "evaluate_candidate", "sweep_candidates",
           "PENALTY"]

#: fitness for infeasible / uncompilable / unmeasurable candidates —
#: large-negative-but-FINITE so roulette selection stays well-defined
PENALTY = -1.0e9


def _schedule_memo_key(schedule):
    return json.dumps(schedule, sort_keys=True)


def _compile_runner(family, spec, schedule):
    """(run, compile_seconds) or (None, None) when the candidate fails
    to build — a VMEM overflow Mosaic rejects at compile is a PENALTY,
    never a crash."""
    import time
    try:
        warm, run = family.build_runner(spec, schedule)
        start = time.perf_counter()
        warm()
        return run, time.perf_counter() - start
    except Exception:
        return None, None


def _timed_fitness(run, repeats, rounds):
    """Multi-pass filtered slope timing of one already-warm runner:
    ``rounds`` passes, positive-majority median, PENALTY when every
    pass measured jitter."""
    samples = [_measure.slope_sample(run, 1, repeats + 1)
               for _ in range(rounds)]
    med = _measure.positive_majority_median(samples)
    return PENALTY if med is None else -med


def _record_triple(spec, schedule, slope, mode):
    """Append one measured (spec, schedule, slope) triple to the
    ``measurements.jsonl`` sidecar — the cost model's training data;
    never raises."""
    try:
        digest, payload = _cache.schedule_key(
            spec["op"], spec["shape"], spec["dtype"],
            spec["precision_level"], _cache.device_kind(),
            spec.get("extra"))
        _cache.record_measurement(digest, payload, schedule, slope,
                                  mode=mode)
    except Exception:
        pass


def evaluate_candidate(candidate):
    """Per-candidate fitness — module-level and self-contained so the
    process-pool and control-plane farm evaluators can pickle/quote it.
    ``candidate`` is the GA's applied spec: ``{"family", "spec",
    "genes", "fitness_mode", "repeats", "rounds"}``."""
    family = family_for(candidate["family"])
    spec = candidate["spec"]
    schedule = family.quantize(spec, candidate["genes"])
    if not family.feasible(spec, schedule):
        return PENALTY
    run, compile_s = _compile_runner(family, spec, schedule)
    if run is None:
        return PENALTY
    _registry.counter("tune.evals").inc()
    if candidate.get("fitness_mode") == "compile":
        _record_triple(spec, schedule, compile_s, "compile")
        return -compile_s
    fitness = _timed_fitness(run, candidate.get("repeats", 8),
                             candidate.get("rounds", 3))
    if fitness > PENALTY:
        _record_triple(spec, schedule, -fitness, "measure")
    return fitness


class _TunerGA(GeneticsOptimizer):
    """GeneticsOptimizer + the observe plane: every generation's
    evaluation runs under a ``tune.generation`` span, and the number
    of genuinely dispatched (non-memoized) evaluations is tracked for
    the receipt.  ``snap_fn`` projects raw genomes onto the quantized
    schedule lattice BEFORE the memo lookup, so genomes that clamp to
    the same schedule are bit-identical values — the values-keyed memo
    then dedupes them on EVERY evaluator path, including the
    process-pool/farm children that cannot share the in-process
    schedule memo."""

    def __init__(self, *args, snap_fn=None, **kwargs):
        super(_TunerGA, self).__init__(*args, **kwargs)
        self.snap_fn = snap_fn
        self.dispatched = 0

    def _evaluate_all(self):
        if self.snap_fn is not None:
            for chromo in self.population.unevaluated():
                chromo.values = self.snap_fn(chromo.values)
        memo_before = len(self._fitness_memo)
        pending = len(self.population.unevaluated())
        with _tracer.span("tune.generation", cat="tune",
                          generation=self.population.generation,
                          pending=pending):
            super(_TunerGA, self)._evaluate_all()
        self.dispatched += (len(self._fitness_memo) - memo_before
                            if self.memoize_fitness else pending)


class ScheduleTuner(Logger):
    """Tune ONE (op, shape, dtype, precision, device) spec.

    ``spec`` comes from the ``tune/spec.py`` builders or a
    ``record_specs`` walk.  :meth:`tune` consults the schedule cache
    first (a hit skips the GA entirely); on a miss it runs the GA —
    population seeded with the family's curated candidates — and
    persists the winner.
    """

    def __init__(self, spec, cache=None, generations=4, population=8,
                 workers=0, farm_slaves=0, farm_address="127.0.0.1:0",
                 fitness="measure", repeats=8, rounds=3, rng=None,
                 device_kind=None, model_base="measure",
                 model_min_triples=None, model_trust=None, **kwargs):
        super(ScheduleTuner, self).__init__(**kwargs)
        self.spec = dict(spec)
        self.family = family_for(self.spec["op"])
        self.cache = cache or _cache.cache_for()
        self.generations = generations
        self.population = population
        self.workers = workers
        self.farm_slaves = farm_slaves
        self.farm_address = farm_address
        self.fitness_mode = fitness
        self.repeats = repeats
        self.rounds = rounds
        self.rng = rng
        self.device_kind = device_kind or _cache.device_kind()
        self.model_base = model_base
        self.model_min_triples = model_min_triples
        self.model_trust = model_trust
        if fitness == "model" and (workers or farm_slaves):
            # model ranking needs the in-process batch evaluator (the
            # pool/farm children score candidates independently);
            # degrade to the base mode rather than mis-rank
            self.warning("tune: fitness='model' is in-process only; "
                         "using fitness=%r for the pool/farm run",
                         model_base)
            self.fitness_mode = model_base
        if workers and self.fitness_mode == "measure":
            import jax
            if jax.default_backend() != "cpu":
                # the pool's workers are pinned to the CPU (a chip
                # belongs to one process — genetics/optimizer.py): they
                # would time the Pallas interpreter and persist its
                # ranking under this chip's device kind
                raise ValueError(
                    "workers=%d cannot time kernels for a %s chip: the "
                    "chip belongs to this process and pool workers run "
                    "on the CPU.  Time in-process (workers=0: one "
                    "interleaved sample of every candidate per pass), "
                    "or farm over hosts that have their own chips "
                    "(farm_slaves / tune --worker)"
                    % (workers, self.device_kind))
        self._model = None
        self._model_info = None
        self._best_measured = (PENALTY, None)
        self._sched_memo = {}

    @property
    def _measure_mode(self):
        """The mode actual measurements run under: the base mode in
        (and under fallback from) fitness='model'."""
        if self.fitness_mode == "model":
            return self.model_base
        return self.fitness_mode

    # -- cache key -----------------------------------------------------------

    def key(self):
        return _cache.schedule_key(
            self.spec["op"], self.spec["shape"], self.spec["dtype"],
            self.spec["precision_level"], self.device_kind,
            self.spec.get("extra"))

    # -- the in-process batch evaluator (interleaved discipline) -------------

    def _batch_fitness(self, candidates):
        fits = [None] * len(candidates)
        to_measure = {}   # schedule memo key -> (schedule, [indices])
        for i, cand in enumerate(candidates):
            schedule = self.family.quantize(self.spec, cand["genes"])
            key = _schedule_memo_key(schedule)
            if key in self._sched_memo:
                fits[i] = self._sched_memo[key]
            elif not self.family.feasible(self.spec, schedule):
                fits[i] = self._sched_memo[key] = PENALTY
            else:
                entry = to_measure.setdefault(key, (schedule, []))
                entry[1].append(i)

        measure_keys = list(to_measure)
        if self._model is not None and len(measure_keys) > 2:
            # model mode: rank the generation's distinct feasible
            # schedules, compile+measure only the top decile (floor 2);
            # the rest carry their PREDICTED fitness — selection
            # pressure only, never persisted, never a tune.eval
            schedules = [to_measure[key][0] for key in measure_keys]
            predicted = self._model.predict_seconds(self.spec,
                                                    schedules)
            order = sorted(range(len(measure_keys)),
                           key=lambda i: (float(predicted[i]), i))
            top = max(2, -(-len(measure_keys) // 10))
            for rank_i in order[top:]:
                key = measure_keys[rank_i]
                fitness = -float(predicted[rank_i])
                self._sched_memo[key] = fitness
                self._model_info["predicted"] += 1
                for i in to_measure[key][1]:
                    fits[i] = fitness
            measure_keys = [measure_keys[rank_i]
                            for rank_i in order[:top]]

        mode = self._measure_mode
        runners, compile_s = {}, {}
        for key in measure_keys:
            schedule, indices = to_measure[key]
            run, seconds = _compile_runner(self.family, self.spec,
                                           schedule)
            if run is None:
                self._sched_memo[key] = PENALTY
                for i in indices:
                    fits[i] = PENALTY
                continue
            _registry.counter("tune.evals").inc()
            runners[key] = run
            compile_s[key] = seconds

        if mode == "compile":
            ranked = {key: compile_s[key] for key in runners}
        else:
            # ONE sample of every candidate per pass: load drift
            # spreads across all candidates equally
            samples = _measure.interleaved_slopes(
                runners, 1, self.repeats + 1, rounds=self.rounds)
            ranked = _measure.rank(samples)

        for key in runners:
            med = ranked.get(key)
            fitness = PENALTY if med is None else -med
            self._sched_memo[key] = fitness
            if med is not None:
                schedule = to_measure[key][0]
                _record_triple(self.spec, schedule, med, mode)
                if fitness > self._best_measured[0]:
                    self._best_measured = (fitness, schedule)
            for i in to_measure[key][1]:
                fits[i] = fitness
        return fits

    # -- the cost model ------------------------------------------------------

    def _setup_model(self):
        """Train-and-trust-gate the family's cost model from the
        measurement sidecar; on thin data or a failed validation gate
        ``self._model`` stays None and the run degrades to the base
        mode (the receipt row's ``model.fallback`` says why)."""
        from veles_tpu.tune import costmodel
        kwargs = {}
        if self.model_min_triples is not None:
            kwargs["min_triples"] = self.model_min_triples
        if self.model_trust is not None:
            kwargs["trust_error"] = self.model_trust
        try:
            model, info = costmodel.train_for(
                self.family.name, mode=self.model_base, **kwargs)
        except Exception as exc:
            model, info = None, {"family": self.family.name,
                                 "fallback": "train-error: %s" % exc}
        self._model = model
        info["predicted"] = 0
        self._model_info = info
        if model is None:
            self.warning(
                "tune: cost model unavailable for %s (%s); measuring "
                "every candidate (fitness=%r)", self.family.name,
                info.get("fallback"), self.model_base)

    # -- the GA run ----------------------------------------------------------

    def _ga_spec(self, space):
        return {
            "family": self.family.name,
            "spec": {k: v for k, v in self.spec.items()},
            "genes": space,
            # the pool/farm children measure every candidate they get
            # (model ranking is in-process only), so they are told the
            # base mode, never "model"
            "fitness_mode": self._measure_mode,
            "repeats": self.repeats,
            "rounds": self.rounds,
        }

    def _snap_genome(self, space):
        """A genome -> genome projection onto the quantized schedule
        lattice: raw genes become the exact quantize()d tile values
        (which live inside the Tune boxes by construction), so two
        genomes that clamp to the same schedule ARE the same genome."""
        import numpy

        from veles_tpu.genetics.config import extract_tunes
        order = [path[-1] for path, _ in extract_tunes(space)]

        def snap(values):
            genes = dict(zip(order, (float(v) for v in values)))
            schedule = self.family.quantize(self.spec, genes)
            snapped = self.family.genes_of(schedule)
            return numpy.asarray([float(snapped[name])
                                  for name in order], numpy.float64)

        return snap

    def _seed_population(self, opt):
        """Overwrite the random initial genomes with the family's
        curated candidates (clamped into the Tune boxes) — the GA
        starts from measured winners, mutation explores around them."""
        import numpy
        tunes = opt.tunes  # [(path, Tune)] in the GA's gene order
        seeds = self.family.seeds(self.spec)
        for chromo, schedule in zip(opt.population.chromosomes, seeds):
            genes = self.family.genes_of(
                self.family.quantize(self.spec,
                                     self.family.genes_of(schedule)))
            chromo.values = numpy.asarray(
                [min(max(float(genes[path[-1]]), tune.min), tune.max)
                 for path, tune in tunes], numpy.float64)
            chromo.fitness = None

    def tune(self, force=False):
        """Returns the receipt row: ``{"digest", "op", "shape",
        "dtype", "schedule", "fitness", "source", "evals",
        "generations"}`` with ``source`` one of ``cache`` / ``ga`` /
        ``untunable`` / ``unranked``."""
        digest, payload = self.key()
        row = {"digest": digest, "op": self.spec["op"],
               "shape": list(self.spec["shape"]),
               "dtype": self.spec["dtype"],
               "precision_level": self.spec["precision_level"],
               "evals": 0, "genomes": 0}
        if not force:
            entry = self.cache.get(digest)
            if entry is not None:
                # same structural validation as the kernels' consult:
                # a malformed/stale entry the kernels would reject
                # must be a MISS here too (and get retuned/overwritten)
                # — otherwise it reports source="cache" forever while
                # static tiles actually serve
                from veles_tpu.tune.spec import valid_schedule
                normalized = valid_schedule(self.spec["op"],
                                            entry["schedule"])
                if normalized is not None:
                    row.update(schedule=normalized,
                               fitness=entry.get("fitness"),
                               source="cache")
                    _registry.counter("tune.cache_hits").inc()
                    return row
        _registry.counter("tune.cache_misses").inc()

        space = self.family.space(self.spec)
        if space is None:
            row.update(schedule=None, source="untunable")
            return row

        if self.fitness_mode == "model":
            self._setup_model()
            row["model"] = self._model_info

        batch = None if (self.workers or self.farm_slaves) \
            else self._batch_fitness
        opt = _TunerGA(
            self._ga_spec(space), evaluate_candidate,
            generations=self.generations, population=self.population,
            workers=self.workers, farm_slaves=self.farm_slaves,
            farm_address=self.farm_address, rng=self.rng,
            batch_fitness_fn=batch,
            snap_fn=self._snap_genome(space))
        self._seed_population(opt)
        evals_before = _registry.counter("tune.evals").value
        with _tracer.span("tune.spec", cat="tune", op=self.spec["op"],
                          digest=digest[:12]):
            best_candidate, best_fitness = opt.run()
        # "evals" = compiles actually PAID (the tune.evals counter
        # delta; infeasible and memo-hit genomes are free and must not
        # inflate the receipt).  "genomes" = distinct genomes the GA
        # dispatched — the memo's denominator.  On subprocess paths
        # (workers/farm) the counter ticks in the children, so fall
        # back to the dispatch count there rather than claim zero.
        evals = _registry.counter("tune.evals").value - evals_before
        if (self.workers or self.farm_slaves) and evals == 0:
            evals = opt.dispatched
        row["evals"] = evals
        row["genomes"] = opt.dispatched

        if self._model is not None:
            # the GA's champion may carry a PREDICTED fitness; only a
            # measured winner may be persisted or reported — swap in
            # the best measured schedule (every generation measured
            # its top slice, so one exists whenever anything ranked)
            best_fitness, best_schedule = self._best_measured
            if best_fitness > PENALTY:
                self.cache.put(digest, payload, best_schedule,
                               fitness=best_fitness, source="ga",
                               evals=evals)
                row.update(schedule=best_schedule,
                           fitness=best_fitness, source="ga")
                self.info(
                    "tune: %s %s -> %s (model-ranked; fitness %.3g, "
                    "%d evals / %d genomes, %d predicted-only)",
                    self.spec["op"], tuple(self.spec["shape"]),
                    best_schedule, best_fitness, evals,
                    opt.dispatched, self._model_info["predicted"])
                return row

        if best_fitness <= PENALTY:
            # every candidate was infeasible or measured only jitter:
            # nothing rankable — do NOT persist (the static tables
            # keep serving; a later, quieter run may succeed)
            self.warning(
                "tune: no candidate for %s %s produced a rankable "
                "measurement; keeping static tables",
                self.spec["op"], tuple(self.spec["shape"]))
            row.update(schedule=None, source="unranked")
            return row

        schedule = self.family.quantize(self.spec,
                                        best_candidate["genes"])
        self.cache.put(digest, payload, schedule,
                       fitness=best_fitness, source="ga", evals=evals)
        row.update(schedule=schedule, fitness=best_fitness,
                   source="ga")
        self.info("tune: %s %s -> %s (fitness %.3g, %d evals / %d "
                  "genomes)", self.spec["op"],
                  tuple(self.spec["shape"]), schedule, best_fitness,
                  evals, opt.dispatched)
        return row


def sweep_candidates(spec, candidates, repeats=24, rounds=5,
                     device_kind=None, cache=None, persist=True,
                     fitness="measure"):
    """The plain curated-candidate sweep (no GA) under the SAME
    measurement discipline and persistence path — what
    ``ops.matmul.autotune_matmul`` runs.  ``candidates`` are schedule
    dicts; clamp-identical ones are measured once.  Returns
    ``(best_schedule_or_None, ranking)`` where ranking maps the memo
    key of each distinct schedule to its median seconds (None =
    jitter-rejected)."""
    family = family_for(spec["op"])
    distinct = {}
    for candidate in candidates:
        schedule = family.quantize(spec, family.genes_of(candidate))
        key = _schedule_memo_key(schedule)
        if key not in distinct and family.feasible(spec, schedule):
            distinct[key] = schedule

    runners, compile_s = {}, {}
    for key, schedule in distinct.items():
        run, seconds = _compile_runner(family, spec, schedule)
        if run is None:
            continue  # VMEM-overflow tiles fail to compile: skipped
        _registry.counter("tune.evals").inc()
        runners[key] = run
        compile_s[key] = seconds

    if fitness == "compile":
        ranking = {key: compile_s[key] for key in runners}
    else:
        samples = _measure.interleaved_slopes(
            runners, 1, repeats + 1, rounds=rounds)
        ranking = _measure.rank(samples)
    for key, med in ranking.items():
        if med is not None:
            _record_triple(spec, distinct[key], med, fitness)
    best_key, best_time = None, float("inf")
    for key, med in ranking.items():
        if med is not None and med < best_time:
            best_key, best_time = key, med
    if best_key is None:
        return None, ranking
    best = distinct[best_key]
    if persist:
        kind = device_kind or _cache.device_kind()
        digest, payload = _cache.schedule_key(
            spec["op"], spec["shape"], spec["dtype"],
            spec["precision_level"], kind, spec.get("extra"))
        (cache or _cache.cache_for()).put(
            digest, payload, best, fitness=-best_time, source="sweep",
            evals=len(runners))
    return best, ranking
