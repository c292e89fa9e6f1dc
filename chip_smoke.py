#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that veles-tpu still starts on the
chip.

One process drives the product's main path once on whatever TPU chips
jax reports, through the entry points a user calls, and checks what
comes out by the repo's own means:

- **train**: AlexNet at full width (``zoo.alexnet_layers()``,
  227x227x3, 1000 outputs, batch 256 per chip, bfloat16) as a
  ``StandardWorkflow`` under a ``Launcher`` on ``Device(backend="tpu")``
  with an HBM-resident ``FullBatchLoader`` and the snapshotter on;
- **serve**: the trained workflow behind ``ReplicaPool`` +
  ``ServeService`` over HTTP and the binary transport;
- **kernels**: every Pallas family in ``veles_tpu/ops`` compiled by
  Mosaic at a deployment-sized shape, against the reference and the
  tolerance its tier-1 test uses.

With more than one local chip the train phase runs data-parallel over
all of them (``sw.fuse(mesh=auto_mesh("data"))``) and serving runs one
replica per chip.  Depth is what it is for AlexNet; the weights and the
data are random, made from seeds — nothing is read from the network.

It refuses to run (exit 2, no result line) unless jax's default backend
is ``tpu``; any failed phase makes the exit code 1.  The last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``.
The phase functions take their sizes as arguments so tier-1
(tests/test_chip_smoke.py) drives the same code at toy width on the CPU
with interpreter kernels.
"""

import errno
import gc
import json
import os
import pickle
import resource
import shutil
import sys
import tempfile
import time
import traceback
import urllib.request

import numpy

from veles_tpu.loader.base import TRAIN
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.memory import Array
from veles_tpu.units import Unit

ALEXNET_SAMPLE = (227, 227, 3)
BATCH_PER_CHIP = 256
LADDER = (1, 8, 32, 128)


def say(fmt, *args):
    print(fmt % args if args else fmt, flush=True)


def check(cond, fmt, *args):
    """The phases' assertion: raises with the message, survives -O."""
    if not cond:
        raise AssertionError(fmt % args if args else fmt)


def max_rel(out, ref):
    """max|out - ref| / max|ref| — the bound tier-1's kernel tests use."""
    out = numpy.asarray(out, numpy.float64)
    ref = numpy.asarray(ref, numpy.float64)
    return float(numpy.abs(out - ref).max() / max(numpy.abs(ref).max(),
                                                  1e-9))


def compile_counts():
    from veles_tpu.observe import xla_introspect
    xla_introspect.ensure_installed()
    return xla_introspect.compile_snapshot()


def say_compiles(label, before):
    after = compile_counts()
    delta = {k: after[k] - before[k] for k in
             ("count", "cache_hits", "cache_misses", "seconds")}
    say("  [%s] compile requests %d, persistent-cache hits %d, misses "
        "%d, %.1f s in the backend compiler", label, delta["count"],
        delta["cache_hits"], delta["cache_misses"], delta["seconds"])


# -- train ------------------------------------------------------------------


class SeededImages(FullBatchLoader):
    """A seeded HBM-resident image dataset: ``label_kinds`` classes,
    each a fixed random pattern under noise, so a few dozen steps lower
    the loss whatever the model.  Module-level: snapshots pickle the
    loader by its import path."""

    def __init__(self, workflow, **kwargs):
        super(SeededImages, self).__init__(workflow, **kwargs)
        self.sample_shape = tuple(kwargs["sample_shape"])
        self.label_kinds = kwargs["label_kinds"]
        self.lengths = kwargs["lengths"]
        self.data_seed = kwargs["data_seed"]

    def load_data(self):
        self.class_lengths[:] = self.lengths
        self._calc_class_end_offsets()
        self.create_originals(self.sample_shape)
        rng = numpy.random.RandomState(self.data_seed)
        kinds = rng.rand(self.label_kinds, *self.sample_shape).astype(
            numpy.float32)
        labels = rng.randint(0, self.label_kinds, self.total_samples)
        data = self.original_data.mem
        for start in range(0, self.total_samples, 256):
            idx = labels[start:start + 256]
            noise = rng.rand(len(idx), *self.sample_shape).astype(
                numpy.float32)
            data[start:start + len(idx)] = (
                0.75 * kinds[idx] + 0.25 * noise - 0.5)
        self.original_labels[:] = labels.tolist()

    def _getstate_quiesced(self):
        # the dataset is a function of data_seed, and load_data() makes
        # it again at every initialize: a snapshot carries the seed
        state = super(SeededImages, self)._getstate_quiesced()
        state["_original_data"] = Array()
        return state


def file_cap_allows(directory, nbytes):
    """Whether this machine lets one file in ``directory`` grow to
    ``nbytes``: a sparse probe file is extended to that size and
    removed.  False on EFBIG — a ``ulimit -f`` or the file system's own
    ceiling; nothing is written either way."""
    os.makedirs(directory, exist_ok=True)
    with tempfile.TemporaryFile(dir=directory) as probe:
        try:
            os.truncate(probe.fileno(), nbytes)
        except OSError as exc:
            if exc.errno != errno.EFBIG:
                raise
            return False
    return True


class StepRecorder(Unit):
    """Runs after the fused trainer and keeps every train step's lazy
    device scalars (no host sync on the step path)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(StepRecorder, self).__init__(workflow, **kwargs)
        self.losses, self.finite = [], []

    def run(self):
        sw = self.workflow
        if sw.loader.minibatch_class == TRAIN:
            self.losses.append(sw.fused_trainer.last_loss)
            self.finite.append(sw.fused_trainer.last_step_finite)


def _step_avals(sw):
    """Abstract arguments of the compiled train step, placed like the
    real ones, for lowering it again."""
    import jax

    trainer = sw.fused_trainer

    def aval(leaf):
        return None if leaf is None else jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype,
            sharding=getattr(leaf, "sharding", None))

    loader = sw.loader
    if trainer.mesh is not None:
        x, labels = trainer._stage(loader)
    else:
        x, labels = loader.minibatch_data.mem, loader.minibatch_labels.mem
    args = [jax.tree.map(aval, trainer._state,
                         is_leaf=lambda v: v is None),
            aval(x), aval(labels),
            jax.ShapeDtypeStruct((), numpy.float32)]
    if trainer._has_dropout:
        args.append(jax.ShapeDtypeStruct((2,), numpy.uint32))
    return args


def backward_routes(sw):
    """[(layer name, "pallas" | "autodiff")] for the conv and max-pool
    layers, from the shapes the kernels route on."""
    from veles_tpu.models.conv import Conv
    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops.common import pallas_bwd_enabled
    from veles_tpu.ops.conv_vjp import conv_vjp_route
    from veles_tpu.ops.pool_bwd import pool_bwd_route
    routes = []
    for index, fwd in enumerate(sw.forwards):
        if isinstance(fwd, Conv):
            road = conv_vjp_route(fwd.ky, fwd.kx)
        elif isinstance(fwd, MaxPooling):
            shape = tuple(fwd.input.shape)
            if len(shape) == 3:
                shape += (1,)
            road = pool_bwd_route(shape, (fwd.ky, fwd.kx), fwd.sliding,
                                  sw.loader.minibatch_data.dtype)
        else:
            continue
        if not pallas_bwd_enabled():
            road = "autodiff"
        routes.append(("%d:%s" % (index, type(fwd).__name__), road))
    return routes


def train_phase(device, layers, sample_shape, batch, snapshot_dir,
                chips=1, train_batches=8, valid_batches=2, epochs=4,
                label_kinds=16, seed=20260926, expect_mosaic=True):
    """StandardWorkflow under a Launcher; returns the trained workflow.

    ``chips`` > 1 fuses over ``auto_mesh("data")`` (``batch`` is then
    the GLOBAL minibatch); one chip takes the default entry, where
    auto-fuse and the input pipeline must come by themselves."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.nn_workflow import StandardWorkflow
    from veles_tpu.observe import xla_introspect
    from veles_tpu.observe.metrics import registry
    from veles_tpu.snapshotter import SnapshotterBase

    before = compile_counts()
    started = time.perf_counter()
    prng.get().seed(seed)
    # the snapshotter is part of the path: every improved epoch exports
    root.common.snapshot.update({"dir": snapshot_dir, "compression": "",
                                 "time_interval": 0, "keep": 1})
    launcher = Launcher()
    sw = StandardWorkflow(
        launcher, layers=layers,
        loader_factory=lambda workflow: SeededImages(
            workflow, minibatch_size=batch,
            prng=prng.RandomGenerator("chip_smoke", seed=seed),
            sample_shape=sample_shape, label_kinds=label_kinds,
            lengths=(0, valid_batches * batch, train_batches * batch),
            data_seed=seed),
        decision_config=dict(max_epochs=epochs))
    check(sw.snapshotter is not None, "the snapshotter is not wired")
    if chips > 1:
        from veles_tpu.parallel import auto_mesh
        mesh = auto_mesh("data")
        check(mesh.shape["data"] == chips, "mesh %s over %d chips",
              dict(mesh.shape), chips)
        sw.fuse(mesh=mesh)
    launcher.initialize(device=device)
    trainer = getattr(sw, "fused_trainer", None)
    check(trainer is not None, "auto-fuse did not happen: the run would "
          "take the per-unit path on the chip")
    if chips == 1:
        check(trainer._prefetcher is not None,
              "the Prefetcher is not attached on one chip")
    check(sw.loader._use_device_path(),
          "the dataset is not HBM-resident")
    recorder = StepRecorder(sw)
    recorder.link_from(trainer)
    recorder.initialize()
    # a snapshot is the weights and the solver state (no dataset, no
    # activations); where the machine caps a file below even that, say
    # so and keep the round trip at the end in memory
    snapshot_bytes = len(pickle.dumps(sw, protocol=pickle.HIGHEST_PROTOCOL))
    to_disk = file_cap_allows(snapshot_dir, snapshot_bytes + (1 << 20))
    if not to_disk:
        sw.snapshotter.skip <<= True
        say("  this machine caps one file below the %.1f MB a snapshot "
            "of this workflow weighs (RLIMIT_FSIZE %s): the snapshotter "
            "is off for this run and the export -> import round trip "
            "goes through memory", snapshot_bytes / 1e6,
            resource.getrlimit(resource.RLIMIT_FSIZE))
    say("  workflow built and initialized in %.1f s (dataset %s %s, "
        "%.2f GB in HBM)", time.perf_counter() - started,
        sw.loader.original_data.shape, sw.loader.original_data.dtype,
        sw.loader.original_data.nbytes / 1e9)

    started = time.perf_counter()
    launcher.run()
    ran = time.perf_counter() - started
    check(bool(sw.decision.complete), "the decision never completed")

    losses = numpy.array([float(v) for v in recorder.losses])
    finite = numpy.array([bool(v) for v in recorder.finite])
    steps = epochs * train_batches
    check(len(losses) == steps, "%d train steps ran, expected %d",
          len(losses), steps)
    check(finite.all() and numpy.isfinite(losses).all(),
          "non-finite steps: %s", losses)
    check(int(trainer.skip_count) == 0, "%d skipped steps",
          int(trainer.skip_count))
    # first epoch against last: a bfloat16 loss moves in steps of
    # 2^-5 around ln(1000), single steps are too coarse to compare
    head = losses[:train_batches].mean()
    tail = losses[-train_batches:].mean()
    check(tail < head, "loss did not fall: %.4f -> %.4f (%s)", head,
          tail, losses)
    say("  %d train + %d eval steps in %.1f s wall (compilation "
        "included); loss %.4f -> %.4f, validation errors %s %%",
        steps, trainer.run_calls - steps, ran, head, tail,
        sw.decision.epoch_metrics[1])

    sizes = xla_introspect.poll_recompiles()
    recompiles = registry.peek("compile.recompiles")
    check(recompiles is None or recompiles.value == 0,
          "%s recompile(s) after the first train/eval step",
          recompiles and recompiles.value)
    check(sizes.get("fused.step") == 1 and sizes.get("fused.eval") == 1,
          "compiled signatures %s, expected one each", sizes)

    # what the compiled step is made of
    lowered = trainer._step_fn.lower(*_step_avals(sw))
    mosaic = lowered.as_text().count("tpu_custom_call")
    routes = backward_routes(sw)
    say("  lowered train step holds %d Mosaic custom call(s); backward "
        "routes: %s", mosaic,
        ", ".join("%s=%s" % r for r in routes) or "none")
    pallas_layers = sum(road == "pallas" for _, road in routes)
    check(mosaic >= pallas_layers or not expect_mosaic,
          "%d layer(s) route to the Pallas backward but the step holds "
          "%d Mosaic call(s)", pallas_layers, mosaic)

    if chips > 1:
        _check_spread(sw, lowered, chips)

    # snapshot: exported during the run, and the final state survives
    # an export -> import round trip bit for bit
    if to_disk:
        exports = registry.peek("snapshot.exports")
        check(exports is not None and exports.value >= 1,
              "the run exported no snapshot")
        written = sw.snapshotter.destination
        sw.snapshotter.export()
        where = sw.snapshotter.destination
        check(where != written, "the final export wrote no file")
        restored = SnapshotterBase.import_file(where, fallback=False)
    else:
        where = "in memory"
        restored = pickle.loads(pickle.dumps(
            sw, protocol=pickle.HIGHEST_PROTOCOL))
    for live, back in zip(sw.forwards, restored.forwards):
        for name in ("weights", "bias"):
            a, b = getattr(live, name), getattr(back, name)
            if not a:
                continue
            a.map_read()
            check(a.mem.dtype == b.mem.dtype and
                  a.mem.tobytes() == b.mem.tobytes(),
                  "snapshot %s of %s is not bit-equal", name, live.name)
    say("  snapshot %s (%.1f MB) re-imported bit-equal (%d layers, %s)",
        where, snapshot_bytes / 1e6, len(sw.forwards),
        sw.forwards[0].weights.dtype)
    say_compiles("train", before)
    return sw


def _check_spread(sw, lowered, chips):
    """Data-parallel run: state and batch really sit on ``chips``
    distinct devices, and the step's gradient merge is one all-reduce
    per bucket."""
    import jax

    from veles_tpu.observe.metrics import registry
    from veles_tpu.parallel.bucketed import plan_buckets
    from veles_tpu.parallel.analysis import parse_collective_ops

    trainer = sw.fused_trainer
    leaf = next(s["weights"] for s in trainer._state
                if s["weights"] is not None)
    check(len(leaf.sharding.device_set) == chips,
          "state sits on %d device(s)", len(leaf.sharding.device_set))

    def counted(name):
        metric = registry.peek(name)
        return 0 if metric is None else metric.value

    x = trainer._stage(sw.loader)[0]
    homes = {shard.device for shard in x.addressable_shards}
    check(len(homes) == chips and
          x.addressable_shards[0].data.shape[0] * chips == x.shape[0],
          "batch shards sit on %d device(s)", len(homes))
    # the resident dataset lives on the mesh, a chip its own rows, and
    # the minibatch is gathered there: nothing of it through the host
    for name, store in sw.loader._stores_.items():
        held = {shard.data.shape[0] for shard in store.addressable_shards}
        check(len(store.sharding.device_set) == chips and
              held == {store.shape[0] // chips},
              "store %r: %s rows a chip on %d device(s)", name, held,
              len(store.sharding.device_set))
    check(counted("step.host_staged_bytes") == 0 and
          counted("loader.mesh_gathers") > 0,
          "%d bytes of minibatches went through the host, %d mesh "
          "gathers", counted("step.host_staged_bytes"),
          counted("loader.mesh_gathers"))
    # XLA:CPU keeps no per-device memory statistics; every TPU does
    stats = [d.memory_stats() for d in jax.local_devices()]
    in_use = [s["bytes_in_use"] for s in stats if s]
    check(len(in_use) == chips or jax.default_backend() == "cpu",
          "no memory statistics on %s", jax.local_devices())
    # every chip holds at least its replica of the state
    state_bytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        trainer._state))
    check(not in_use or min(in_use) >= state_bytes,
          "a chip holds less than the %d-byte state: %s", state_bytes,
          in_use)
    grads_like = [{"weights": s["weights"], "bias": s["bias"]}
                  for s in trainer._state]
    plan = plan_buckets(jax.tree_util.tree_leaves(grads_like),
                        trainer._step_fn.bucket_bytes)
    # by construction: one all_reduce per bucket in the lowered step
    # (plus the loss and error-count psums), and every bucket its own
    # payload in the optimized one — how many ops XLA then issues them
    # as is its combiner's business, and is printed, not asserted
    lowered_ops = lowered.as_text().count("stablehlo.all_reduce")
    check(lowered_ops == len(plan.buckets) + 2,
          "%d all_reduce(s) lowered for %d bucket(s) + 2 metric psums",
          lowered_ops, len(plan.buckets))
    ops = [op for op in parse_collective_ops(lowered.compile().as_text())
           if op["kind"] == "all-reduce"]
    payloads = sorted(n for op in ops for n in op["elems"] if n > 1)
    check(payloads == sorted(b.elems for b in plan.buckets),
          "gradient payloads of %s elements for buckets of %s",
          payloads, [b.elems for b in plan.buckets])
    say("  %d chips: state replicated on %d devices, batch sharded %s "
        "per chip, bytes in use per chip %s; %d gradient buckets "
        "(%.1f MB) lowered as %d all_reduces, issued by XLA as %d "
        "all-reduce op(s)", chips, len(leaf.sharding.device_set),
        x.addressable_shards[0].data.shape, in_use, len(plan.buckets),
        sum(b.nbytes for b in plan.buckets) / 2.0 ** 20,
        lowered_ops - 2,
        sum(1 for op in ops if any(n > 1 for n in op["elems"])))


# -- serve ------------------------------------------------------------------


def serve_phase(sw, ladder=LADDER, blocks=(1, 37, 128), seed=7):
    """ReplicaPool (one replica per local device) behind ServeService:
    HTTP and binary answers equal ``pool.engine.infer``."""
    from veles_tpu.observe.metrics import registry
    from veles_tpu.serve import (BinaryTransportClient, ReplicaPool,
                                 ServeService)

    before = compile_counts()
    # float16 on the wire: the binary transport frames numeric numpy
    # dtypes only, and a top-rung block of 227x227x3 float32 rows is
    # past its frame cap; the engine casts to the model's precision
    pool = ReplicaPool.from_workflow(sw, ladder=ladder,
                                     dtype=numpy.float16)
    receipt = pool.compile()
    say("  %d replica(s), ladder %s compiled in %.1f s: %d requests, %d "
        "cache hits, %d new compiles", receipt["replicas"],
        receipt["rungs"], receipt["seconds"],
        receipt["backend_compiles"], receipt["cache_hits"],
        receipt["new_compiles"])
    svc = ServeService(pool, port=0, transport_port=0)
    svc.start_background()
    try:
        rng = numpy.random.RandomState(seed)
        shape = pool.engine.sample_shape
        x = (rng.rand(max(blocks), *shape) - 0.5).astype(numpy.float16)
        answers = {}
        body = json.dumps({"input": x[0].tolist()}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                "http://127.0.0.1:%d/infer" % svc.port, data=body,
                headers={"Content-Type": "application/json"}),
                timeout=120) as reply:
            answers["http/1"] = numpy.asarray(
                json.loads(reply.read())["probabilities"],
                numpy.float32)
        with BinaryTransportClient(
                port=svc.transport_port, timeout=120,
                shm_slot_mb=x.nbytes / 2.0 ** 20 + 1) as client:
            check(client.server_digest == pool.digest, "digest mismatch")
            for rows in blocks:
                answers["binary/%d" % rows] = client.infer(
                    x[0] if rows == 1 else x[:rows])
        for name, out in answers.items():
            rows = int(name.split("/")[1])
            ref = pool.engine.infer(x[:rows])
            out = numpy.asarray(out, numpy.float32).reshape(ref.shape)
            check(numpy.isfinite(out).all(), "%s: non-finite", name)
            check(numpy.array_equal(out, ref),
                  "%s differs from pool.engine.infer (max |d| %g)",
                  name, numpy.abs(out - ref).max())
            check(numpy.allclose(out.sum(axis=1), 1.0, atol=2e-2),
                  "%s: rows are not distributions", name)
        say("  answers equal pool.engine.infer and finite: %s",
            ", ".join(sorted(answers)))

        with urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % svc.port,
                timeout=30) as reply:
            health = json.loads(reply.read())
        check(health["compile"]["rungs"] == list(ladder) and
              "new_compiles" in health["compile"],
              "/healthz carries no compile receipt: %s", health)
        check(health["replicas"]["replicas"] == len(pool.replicas),
              "replica count %s", health["replicas"])
        for rep in pool.replicas:
            cap = registry.peek(
                "serve.replica.%d.rung_cap" % rep.index)
            check(cap is not None and cap.value == ladder[-1],
                  "replica %d degraded its rung cap to %s", rep.index,
                  cap and cap.value)
        # every replica answers: the router picks by queue depth, so
        # ask each one directly too
        for rep in pool.replicas:
            request = rep.batcher.submit(x[0])
            check(request.done.wait(120) and request.error is None,
                  "replica %d did not answer: %s", rep.index,
                  request.error)
            out = request.result
            check(numpy.array_equal(
                numpy.asarray(out, numpy.float32).reshape(1, -1),
                pool.engine.infer(x[:1])),
                "replica %d answers differently", rep.index)
        reload_receipt = svc.reload(pool.engine.params)
        check(reload_receipt["mode"] == "params" and
              reload_receipt["new_compiles"] == 0,
              "same-digest reload compiled: %s", reload_receipt)
        say("  /healthz carries the compile receipt; rung cap %d on %d "
            "replica(s); same-digest reload: 0 new compiles",
            ladder[-1], len(pool.replicas))
    finally:
        svc.stop()
    say_compiles("serve", before)
    return receipt


# -- kernels ----------------------------------------------------------------

#: deployment-sized shapes; tests/test_chip_smoke.py passes toy ones
KERNEL_SIZES = dict(
    matmul=3001,
    int8_matmul=(128, 9216, 4096),          # AlexNet fc6, rung 128
    int8_conv=(32, 13, 13, 256, 384, 3),    # AlexNet conv3, rung 32
    attention=(8, 512, 512, 8),             # batch, T, D, heads
    conv_vjp=(256, 27, 27, 96, 256, 5, 2),  # AlexNet conv2 at batch 256
    pools=(((256, 55, 55, 96), (3, 3), (2, 2)),     # AlexNet pool1
           ((64, 112, 112, 128), (2, 2), (2, 2))),  # VGG16 pool2
    reduce=(3001, 4096),
    normalize=(256, 224 * 224 * 3),
    join=((256, 4096), (256, 1000), (256, 7)),
    gather=(2048, 256, (227, 227, 3)),
    uniform=(512, 4096),
)


def kernels_phase(sizes=KERNEL_SIZES, expect_mosaic=True):
    """Every ``pl.pallas_call`` family against its tier-1 reference.
    A family that fails does not stop the others; the phase fails at
    the end naming every one that did."""
    from veles_tpu.ops import common

    before = compile_counts()
    check(common.interpret_mode() is not expect_mosaic,
          "ops.common.interpret_mode() is %s", common.interpret_mode())
    done, failed = [], []

    def passed(name, fmt="", *args):
        done.append(name)
        say("  ok %-22s %s", name, fmt % args if args else fmt)

    for family in (_check_matmul, _check_int8, _check_attention,
                   _check_conv_vjp, _check_pool_bwd, _check_small_ops,
                   _check_hardware_uniform):
        # one seed per family: a failure upstream moves no data below
        rng = numpy.random.RandomState(42)
        try:
            family(sizes, rng, passed, expect_mosaic)
        except Exception as exc:
            failed.append(family.__name__[len("_check_"):])
            traceback.print_exc(file=sys.stdout)
            say("  FAILED %s: %s", failed[-1], exc)
        gc.collect()
    say_compiles("kernels", before)
    check(not failed, "kernel families failed: %s", ", ".join(failed))
    return done


def _check_matmul(sizes, rng, passed, expect_mosaic):
    """f32 levels 0/1/2 vs the f64 product (rtol 1e-5), bf16 vs the
    same (rtol 2e-2) — tests/test_ops.py."""
    import jax.numpy as jnp

    from veles_tpu import ops
    n = sizes["matmul"]
    a = rng.rand(n, n).astype(numpy.float32)
    b = rng.rand(n, n).astype(numpy.float32)
    oracle = a.astype(numpy.float64) @ b.astype(numpy.float64)
    for level in (0, 1, 2):
        out = numpy.asarray(ops.matmul(jnp.asarray(a), jnp.asarray(b),
                                       precision_level=level))
        numpy.testing.assert_allclose(out, oracle, rtol=1e-5)
        passed("matmul f32 level %d" % level, "%d^2, max rel %.2g", n,
               max_rel(out, oracle))
    out = numpy.asarray(ops.matmul(
        jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16),
        out_dtype=jnp.float32))
    numpy.testing.assert_allclose(out, oracle, rtol=2e-2)
    passed("matmul bf16", "%d^2, max rel %.2g", n, max_rel(out, oracle))


def _check_int8(sizes, rng, passed, expect_mosaic):
    """int8 matmul bit-exact vs the jitted reference, int8 conv within
    f32 rounding (rtol 1e-5, atol 1e-4) of the exact integer conv —
    tests/test_quant.py."""
    import jax

    from veles_tpu.ops.matmul_int8 import (conv2d_int8, matmul_int8,
                                           matmul_int8_reference)
    m, k, n8 = sizes["int8_matmul"]
    qa = rng.randint(-127, 128, (m, k)).astype(numpy.int8)
    qb = rng.randint(-127, 128, (k, n8)).astype(numpy.int8)
    scale = (rng.rand(n8) * 1e-3 + 1e-4).astype(numpy.float32)
    bias = rng.randn(n8).astype(numpy.float32)
    out = numpy.asarray(matmul_int8(qa, qb, scale, bias))
    ref = numpy.asarray(jax.jit(matmul_int8_reference)(qa, qb, scale,
                                                       bias))
    numpy.testing.assert_array_equal(out, ref)
    passed("matmul_int8", "%dx%dx%d bit-exact", m, k, n8)
    bn, h, w, ci, co, kk = sizes["int8_conv"]
    qx = rng.randint(-127, 128, (bn, h, w, ci)).astype(numpy.int8)
    qw = rng.randint(-127, 128, (kk, kk, ci, co)).astype(numpy.int8)
    cscale = (rng.rand(co) * 1e-3 + 1e-4).astype(numpy.float32)
    cbias = rng.randn(co).astype(numpy.float32)
    half = kk // 2
    out = numpy.asarray(conv2d_int8(qx, qw, cscale, cbias,
                                    padding=(half,) * 4))
    # the exact integer conv, on the host: |acc| < 2^53, so float64
    # BLAS over the im2col patches loses nothing
    xp = numpy.pad(qx, ((0, 0), (half, half), (half, half), (0, 0)))
    patches = numpy.concatenate(
        [xp[:, dy:dy + h, dx:dx + w, :] for dy in range(kk)
         for dx in range(kk)], axis=-1).reshape(-1, kk * kk * ci)
    acc = patches.astype(numpy.float64) @ qw.reshape(-1, co).astype(
        numpy.float64)
    ref = (acc.astype(numpy.float32) * cscale + cbias).reshape(out.shape)
    numpy.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)
    passed("conv2d_int8", "%s * %s, max rel %.2g", qx.shape, qw.shape,
           max_rel(out, ref))


def _check_attention(sizes, rng, passed, expect_mosaic):
    """Flash attention at the kernel level — tests/test_transformer.py:
    forward level 0 < 1e-5, backward at level 1 < 5e-6, both against
    attention_reference at the same level — and through the model: one
    zoo.transformer_layers train step with the flash pair against the
    same step on the stock reference."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.attention import (attention_reference,
                                         flash_attention)
    bsz, t, d, heads = sizes["attention"]
    q, kq, v = (jnp.asarray(rng.randn(bsz * heads, t, d // heads),
                            jnp.float32) for _ in range(3))
    rel = max_rel(flash_attention(q, kq, v, precision_level=0),
                  attention_reference(q, kq, v, precision_level=0))

    def sq(fn):
        return lambda *qkv: jnp.sum(fn(*qkv, precision_level=1) ** 2)

    got = jax.grad(sq(flash_attention), argnums=(0, 1, 2))(q, kq, v)
    want = jax.grad(sq(attention_reference), argnums=(0, 1, 2))(q, kq, v)
    brels = [max_rel(g, w_) for g, w_ in zip(got, want)]
    say("  attention (%d, %d, %d): forward level 0 rel %.2g; backward "
        "level 1 dq/dk/dv rel %s", bsz * heads, t, d // heads, rel,
        " ".join("%.2g" % r for r in brels))
    check(rel < 1e-5, "flash forward level 0 off by %g", rel)
    check(max(brels) < 5e-6 and
          all(bool(jnp.isfinite(g).all()) for g in got),
          "flash backward level 1 (dq, dk, dv) off by %s", brels)
    passed("attention kernels", "fwd rel %.2g, bwd rel %.2g", rel,
           max(brels))
    step_rel = _transformer_step_parity(bsz, t, d, heads, expect_mosaic)
    passed("attention train step", "T=%d D=%d heads=%d: level 1 within "
           "%.2g of the reference", t, d, heads, step_rel)


def _check_conv_vjp(sizes, rng, passed, expect_mosaic):
    """wgrad/bias-grad/dgrad within 1e-5 of autodiff (f32, level 0) —
    tests/test_pallas_bwd.py."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.conv_vjp import (_autodiff_conv_vjp,
                                        fused_conv_vjp)
    bn, h, w, ci, co, kk, pd = sizes["conv_vjp"]
    x = jnp.asarray(rng.randn(bn, h, w, ci) * 0.5, jnp.float32)
    wt = jnp.asarray(rng.randn(kk, kk, ci, co) * 0.05, jnp.float32)
    y = jnp.maximum(jax.lax.conv_general_dilated(
        x, wt, (1, 1), ((pd, pd), (pd, pd)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST), 0)
    dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    cfg = dict(activation="strict_relu", padding=(pd,) * 4,
               sliding=(1, 1), include_bias=True, need_err_input=True)
    # true-f32 XLA convs on both sides (the chip's default rounds f32
    # operands to bf16): what is compared is the kernel, whose products
    # carry their own precision
    with jax.default_matmul_precision("highest"):
        got = fused_conv_vjp(x, wt, y, dy, **cfg)
        want = _autodiff_conv_vjp(x, wt, y, dy, **cfg)
    rels = [max_rel(g, w_) for g, w_ in zip(got, want)]
    check(max(rels) < 1e-5, "conv_vjp (dx, dw, db) off by %s", rels)
    passed("conv_vjp", "x %s w %s: dx/dw/db rel %s", x.shape, wt.shape,
           " ".join("%.2g" % r for r in rels))


def _check_pool_bwd(sizes, rng, passed, expect_mosaic):
    """Routing bit-exact vs jax.vjp(reduce_window) for disjoint
    windows, within 1e-6 for overlapping — tests/test_pallas_bwd.py."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.models.pooling import MaxPooling
    from veles_tpu.ops.pool_bwd import max_pool_bwd, pool_bwd_route
    for shape, window, sliding in sizes["pools"]:
        check(pool_bwd_route(shape, window, sliding, jnp.float32) ==
              "pallas", "pool %s routes to autodiff", shape)
        x = jnp.asarray(rng.randn(*shape), jnp.float32)

        def pool(x_, window=window, sliding=sliding):
            return MaxPooling.apply({}, x_, window=window,
                                    sliding=sliding, pallas_bwd=False)

        y, vjp = jax.vjp(pool, x)
        dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
        out = numpy.asarray(max_pool_bwd(x, y, dy, window=window,
                                         sliding=sliding))
        ref = numpy.asarray(vjp(dy)[0])
        if window == sliding:
            numpy.testing.assert_array_equal(out, ref)
        else:
            numpy.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)
        passed("pool_bwd %dx%d/%d" % (window + sliding[:1]),
               "%s %s", shape,
               "bit-exact" if window == sliding else "within 1e-6")


def _check_small_ops(sizes, rng, passed, expect_mosaic):
    """reduce, normalize, join, gather — tests/test_ops.py."""
    import jax.numpy as jnp

    from veles_tpu import ops
    rows, cols = sizes["reduce"]
    x = rng.rand(rows, cols).astype(numpy.float32)
    numpy.testing.assert_allclose(
        numpy.asarray(ops.reduce_cols(jnp.asarray(x))),
        x.sum(0, keepdims=True), rtol=1e-4)
    numpy.testing.assert_allclose(
        numpy.asarray(ops.reduce_rows(jnp.asarray(x))),
        x.sum(1, keepdims=True), rtol=1e-4)
    passed("reduce rows/cols", "%s", x.shape)

    rows, cols = sizes["normalize"]
    x = (rng.rand(rows, cols) * 255).astype(numpy.uint8)
    mean = x[:64].mean(0).astype(numpy.float32)
    rdisp = (1.0 / (numpy.ptp(x[:64].astype(numpy.float32), axis=0)
                    + 1.0)).astype(numpy.float32)
    numpy.testing.assert_allclose(
        numpy.asarray(ops.mean_disp_normalize(
            jnp.asarray(x), jnp.asarray(mean), jnp.asarray(rdisp))),
        (x.astype(numpy.float32) - mean) * rdisp, rtol=1e-5, atol=1e-6)
    passed("normalize", "uint8 %s", x.shape)

    parts = [rng.rand(*shape).astype(numpy.float32)
             for shape in sizes["join"]]
    numpy.testing.assert_array_equal(
        numpy.asarray(ops.join(*[jnp.asarray(p) for p in parts])),
        numpy.concatenate(parts, axis=1))
    passed("join", "%s", [p.shape for p in parts])

    count, batch, sample = sizes["gather"]
    data = (rng.rand(count, *sample) * 255).astype(numpy.uint8)
    idx = rng.permutation(count)[:batch].astype(numpy.int32)
    # every width takes the row-DMA kernel; AlexNet's own is off 128.
    # Once from the row store (what the loader holds), once from the
    # raw array (the store built inside the call)
    store = jnp.asarray(ops.gather.build_store(data))
    for table, shape in ((store, sample), (jnp.asarray(data), None)):
        numpy.testing.assert_array_equal(
            numpy.asarray(ops.gather_minibatch(
                table, jnp.asarray(idx), out_dtype=jnp.float32,
                sample_shape=shape)),
            data[idx].astype(numpy.float32))
    passed("gather", "%s rows of uint8 %s (store %s) -> float32", batch,
           sample, store.shape)


def _check_hardware_uniform(sizes, rng, passed, expect_mosaic):
    """The hardware PRNG: [0, 1), deterministic per seed, seeds differ,
    and uniform enough that the mean sits at 1/2."""
    from veles_tpu.ops import random as vrandom
    shape = tuple(sizes["uniform"])
    u = numpy.asarray(vrandom.hardware_uniform(7, shape))
    u2 = numpy.asarray(vrandom.hardware_uniform(7, shape))
    u3 = numpy.asarray(vrandom.hardware_uniform(8, shape))
    check(u.shape == shape and (u >= 0).all() and (u < 1).all(),
          "hardware_uniform out of [0, 1)")
    numpy.testing.assert_array_equal(u, u2)
    check(not numpy.array_equal(u, u3), "seeds 7 and 8 agree")
    check(abs(u.mean() - 0.5) < 5.0 / numpy.sqrt(12.0 * u.size) + 1e-3
          and len(numpy.unique(u)) > u.size // 2,
          "hardware_uniform is not uniform: mean %g, %d distinct of %d",
          u.mean(), len(numpy.unique(u)), u.size)
    passed("hardware_uniform", "%s mean %.4f", u.shape, u.mean())


def _transformer_step_parity(bsz, t, d, heads, expect_mosaic):
    """One ``zoo.transformer_layers`` train step on three roads: the
    stock ``attention_reference`` (true-f32 products), the flash
    kernels as the model calls them (precision level 0: bf16x3
    products) and the flash kernels at level 1 (true-f32 products).

    Level 1 against the reference proves the kernels inside a fused
    step, to tests/test_pallas_bwd.py's fused-step knob-parity bound
    (1e-4).  Level 0 is the default road: its loss must agree as
    tightly, while its gradients carry bf16x3's 16-bit products
    through the softmax backward's cancellation — 3.8e-3 on the
    block's weight gradient at T=512, D=512, the same in the
    interpreter and under Mosaic (PR 21) — so they are held to 1e-2
    and printed."""
    import functools
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from veles_tpu.compiler import build_train_step
    from veles_tpu.models.zoo import (build_plans_and_state,
                                      transformer_layers)
    from veles_tpu.ops import attention, common

    plans, state, _ = build_plans_and_state(
        transformer_layers(blocks=1, heads=heads, classes=10, lr=0.01),
        (t, d), seed=3)
    rng = numpy.random.RandomState(5)
    x = jnp.asarray(rng.randn(bsz, t, d) * 0.5, jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, bsz), jnp.int32)

    def to_device(tree):
        return jax.tree.map(
            lambda leaf: None if leaf is None else jnp.asarray(leaf),
            tree, is_leaf=lambda leaf: leaf is None)

    def one_step(knob, flash_level=None):
        flash = attention.flash_attention if flash_level is None else \
            functools.partial(attention.flash_attention,
                              precision_level=flash_level)
        with mock.patch.object(common, "PALLAS_BWD_ENV", knob), \
                mock.patch.object(attention, "flash_attention", flash):
            step = build_train_step(plans, donate=False)
            if knob == "1" and flash_level is None:
                calls = step.lower(to_device(state), x, labels,
                                   numpy.float32(bsz)).as_text().count(
                                       "tpu_custom_call")
                check(calls >= 3 or not expect_mosaic,
                      "the transformer step holds %d Mosaic calls, "
                      "expected the flash forward + backward pair",
                      calls)
            # true-f32 projections and MLP on every road, so the steps
            # differ by the attention implementation alone
            with jax.default_matmul_precision("highest"):
                new_state, metrics = step(to_device(state), x, labels,
                                          numpy.float32(bsz))
        check(bool(metrics["finite"]), "non-finite transformer step")
        return new_state, metrics

    def distance(road, ref):
        (sa, ma), (sb, mb) = road, ref
        loss = abs(float(ma["loss"]) - float(mb["loss"])) / abs(
            float(mb["loss"]))
        # the momentum accumulators after one step ARE lr * gradient:
        # the weights would hide any error behind their own magnitude
        grads = [max_rel(new_a[key], new_b[key])
                 for new_a, new_b in zip(sa, sb)
                 for key in ("accum_weights", "accum_bias")
                 if new_b[key] is not None]
        return loss, max(grads)

    reference = one_step("0")
    loss1, grad1 = distance(one_step("1", flash_level=1), reference)
    loss0, grad0 = distance(one_step("1"), reference)
    say("  transformer step vs the stock reference: flash level 1 loss "
        "rel %.2g gradient rel %.2g; flash level 0 (the model's "
        "default) loss rel %.2g gradient rel %.2g", loss1, grad1, loss0,
        grad0)
    check(max(loss1, grad1) < 1e-4,
          "flash level 1 train step off by loss %g gradient %g", loss1,
          grad1)
    check(loss0 < 1e-4 and grad0 < 1e-2,
          "flash level 0 train step off by loss %g gradient %g", loss0,
          grad0)
    return max(loss1, grad1)


# -- main -------------------------------------------------------------------


def main():
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.stderr.write(
            "chip_smoke: no TPU — jax's default backend is %r (devices "
            "%s); this script runs on the chip only\n"
            % (backend, jax.devices()))
        return 2
    import importlib.metadata as metadata

    import jaxlib
    devices = jax.devices()
    device_info = {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)}
    say("chip_smoke: platform %s, device_kind %r, %d device(s); jax %s, "
        "jaxlib %s, libtpu %s", device_info["platform"],
        device_info["kind"], device_info["count"], jax.__version__,
        jaxlib.__version__, metadata.version("libtpu"))

    from veles_tpu.backends import Device, enable_compile_cache
    from veles_tpu.config import root
    from veles_tpu.logger import setup_logging
    from veles_tpu.models.zoo import alexnet_layers
    setup_logging()
    say("compile cache: %s", enable_compile_cache())
    cap = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    say("file size cap (RLIMIT_FSIZE): %s",
        "none" if cap == resource.RLIM_INFINITY else "%d bytes" % cap)
    root.common.engine.precision_type = "bfloat16"
    chips = len(devices)
    device = Device(backend="tpu")
    snapshot_dir = tempfile.mkdtemp(prefix="chip_smoke_snapshots_")
    failed = []
    state = {}

    def run(name, fn):
        say("== %s ==", name)
        started = time.perf_counter()
        try:
            state[name] = fn()
        except Exception:
            failed.append(name)
            traceback.print_exc(file=sys.stdout)
            say("== %s FAILED after %.1f s ==", name,
                time.perf_counter() - started)
        else:
            say("== %s passed in %.1f s ==", name,
                time.perf_counter() - started)

    started = time.perf_counter()
    try:
        run("train", lambda: train_phase(
            device, alexnet_layers(), ALEXNET_SAMPLE,
            BATCH_PER_CHIP * chips, snapshot_dir, chips=chips))
        if "train" in state:
            run("serve", lambda: serve_phase(state["train"]))
        else:
            failed.append("serve")
            say("== serve skipped: no trained workflow ==")
        # the workflow holds the dataset and the state in HBM
        state.clear()
        gc.collect()
        run("kernels", kernels_phase)
    finally:
        shutil.rmtree(snapshot_dir, ignore_errors=True)
    totals = compile_counts()
    say("chip_smoke: %.1f s; compile requests %d, persistent-cache hits "
        "%d, misses %d", time.perf_counter() - started, totals["count"],
        totals["cache_hits"], totals["cache_misses"])
    result = {"ok": not failed, "device": device_info}
    if failed:
        result["failed"] = failed
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
