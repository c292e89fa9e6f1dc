"""GeneticsOptimizer: evolve a population by evaluating candidate
configurations as jobs.

Reference: genetics/optimization_workflow.py:70-260 farmed chromosome
evaluations to slaves as master-slave jobs (each spawning a child veles
process).  Here evaluations run through a pluggable evaluator:

- in-process (default): ``fitness_fn(candidate_spec) -> float``;
- process pool: ``workers=N`` evaluates candidates concurrently in
  SPAWNED subprocesses pinned to the CPU — a chip belongs to one
  process, and this one usually holds it already (``_cpu_worker_pool``);
- control plane: ``farm_slaves=N`` farms each generation's candidate
  specs as jobs through the Server/Client stack
  (veles_tpu.jobfarm.JobFarm) — the reference's strategy — with
  remote hosts joining via :meth:`GeneticsOptimizer.worker`; see
  tests/test_genetics.py::test_optimizer_farms_over_control_plane.

Fitness is MAXIMIZED (use -validation_error).
"""

import concurrent.futures
import contextlib
import multiprocessing
import os

from veles_tpu.genetics.config import apply_values, extract_tunes
from veles_tpu.genetics.core import Population
from veles_tpu.logger import Logger

__all__ = ["GeneticsOptimizer"]


@contextlib.contextmanager
def _cpu_worker_pool(workers):
    """A process pool whose workers can never reach for the chip.

    A chip belongs to ONE process: once this one has touched JAX it
    holds the chip, and a child that needed it would fail or hang.  So
    the workers are spawned (never forked: this process has threads
    and, usually, a live JAX runtime) with ``JAX_PLATFORMS=cpu`` in the
    environment they start from — jax reads it at import, before any
    worker code runs.  Work that must run ON the chip evaluates
    in-process (``workers=0``) or on other hosts (``farm_slaves`` /
    ``GeneticsOptimizer.worker``)."""
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        with concurrent.futures.ProcessPoolExecutor(
                max_workers=workers,
                mp_context=multiprocessing.get_context("spawn")) as pool:
            yield pool
    finally:
        if saved is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = saved


class GeneticsOptimizer(Logger):

    FARM_TAG = "genetics"

    def __init__(self, spec, fitness_fn, generations=5, population=12,
                 workers=0, farm_slaves=0, farm_address="127.0.0.1:0",
                 rng=None, batch_fitness_fn=None, memoize_fitness=True,
                 **population_kwargs):
        super(GeneticsOptimizer, self).__init__()
        self.spec = spec
        self.fitness_fn = fitness_fn
        self.generations = generations
        self.workers = workers
        self.farm_slaves = farm_slaves
        self.farm_address = farm_address
        #: optional whole-generation evaluator ``fn(specs) -> [fitness]``
        #: for fitness functions that must see a generation's candidates
        #: TOGETHER (the schedule autotuner's interleaved round-robin
        #: timing: one sample of every candidate per pass, so a drift
        #: in machine load cannot crown the wrong candidate).  Ignored
        #: on the farm/process-pool paths, which are per-candidate by
        #: construction.
        self.batch_fitness_fn = batch_fitness_fn
        #: evolve() produces duplicates of already-scored genomes
        #: (elitism copies keep their fitness, but crossover routinely
        #: recreates a parent when both picks agree on a segment) — the
        #: values-keyed memo serves those for free, so a duplicate
        #: genome never pays a second evaluation (for the autotuner:
        #: never a second kernel compile)
        self.memoize_fitness = memoize_fitness
        self._fitness_memo = {}
        self.tunes = extract_tunes(spec)
        if not self.tunes:
            raise ValueError("spec contains no Tune markers")
        mins = [t.min for _, t in self.tunes]
        maxs = [t.max for _, t in self.tunes]
        self.population = Population(
            mins, maxs, size=population, rng=rng, **population_kwargs)
        self.history = []  # (generation, best_fitness, best_spec)
        self._farm = None

    def candidate_spec(self, chromosome):
        return apply_values(self.spec, self.tunes, chromosome.values)

    def worker(self, address):
        """Blocking remote-worker loop: evaluate candidate specs the
        optimizing master at ``address`` hands out (the worker quotes
        the same fitness_fn)."""
        from veles_tpu.jobfarm import JobFarm
        return JobFarm(self.FARM_TAG).worker(address, self.fitness_fn)

    @property
    def farm_enabled(self):
        from veles_tpu.jobfarm import farm_enabled
        return farm_enabled(self.farm_slaves, self.farm_address)

    @staticmethod
    def _genome_key(chromosome):
        return tuple(float(v) for v in chromosome.values)

    def _evaluate_all(self):
        pending = self.population.unevaluated()
        if self.memoize_fitness:
            # serve memo hits, then collapse the remainder onto one
            # representative per DISTINCT genome (within-batch
            # duplicates are also free)
            groups = {}
            for chromo in pending:
                key = self._genome_key(chromo)
                memoized = self._fitness_memo.get(key)
                if memoized is not None:
                    chromo.fitness = memoized
                else:
                    groups.setdefault(key, []).append(chromo)
            reps = [chromos[0] for chromos in groups.values()]
        else:
            groups = None
            reps = pending
        specs = [self.candidate_spec(c) for c in reps]
        if self.farm_enabled and specs:
            # ONE farm for the whole optimization: remote workers stay
            # connected between generations (a fresh server per batch
            # would disconnect them after generation 0)
            if self._farm is None:
                from veles_tpu.jobfarm import JobFarm
                self._farm = JobFarm(self.FARM_TAG).start(
                    runner=self.fitness_fn,
                    address=self.farm_address,
                    local_slaves=self.farm_slaves)
            fits = self._farm.submit(specs)
        elif self.workers and len(reps) > 1:
            self.info("evaluating %d candidates on %d CPU-pinned "
                      "worker processes (the chip, if any, stays with "
                      "this process)", len(specs), self.workers)
            with _cpu_worker_pool(self.workers) as pool:
                fits = list(pool.map(self.fitness_fn, specs))
        elif self.batch_fitness_fn is not None:
            fits = list(self.batch_fitness_fn(specs)) if specs else []
        else:
            fits = [self.fitness_fn(spec) for spec in specs]
        for chromo, fitness in zip(reps, fits):
            fitness = float(fitness)
            if groups is None:
                chromo.fitness = fitness
                continue
            key = self._genome_key(chromo)
            self._fitness_memo[key] = fitness
            for duplicate in groups[key]:
                duplicate.fitness = fitness

    def run(self):
        """Returns (best_spec, best_fitness)."""
        try:
            for gen in range(self.generations):
                self._evaluate_all()
                best = self.population.best
                self.history.append(
                    (gen, best.fitness, self.candidate_spec(best)))
                self.info("generation %d best fitness %.4f", gen,
                          best.fitness)
                if gen < self.generations - 1:
                    self.population.evolve()
        finally:
            if self._farm is not None:
                self._farm.shutdown()
                self._farm = None
        best = self.population.best
        return self.candidate_spec(best), best.fitness
