"""Per-layer MFU/roofline attribution for a conv-family training step
(AlexNet / VGG; round-3 verdict item 3: say WHERE the non-MXU time
goes).

Method: the full fused train step is measured once on the real chip
(same machinery as bench.py), and XLA's own cost analysis supplies the
program-level FLOP count and HBM bytes accessed.  Attribution across
layers is ANALYTIC — per-layer forward FLOPs from the conv/dense
shapes (backward ~= 2x forward), per-layer HBM traffic from activation
+ parameter + optimizer-state sizes — then each layer's roofline time
is max(flops / MXU peak, bytes / HBM bandwidth).  The analytic total
is compared against the measured step so the attribution's credibility
is visible in the record (see "model_vs_measured_ratio").

Writes chiprun_out/MFU[_MODEL].json (the directory a chip run brings
back): {measured: {...}, layers: [...], conclusion: "..."}

    python scripts/mfu_breakdown.py [--batch 256] [--dtype bfloat16]

An ANALYTIC table, not a measurement of layers: ROADMAP D7 replaces it
with the profiler-trace reduction.  The peaks come from the ONE table
(``observe.xla_introspect.PEAKS``) by the chip's exact ``device_kind``;
without a chip (``--skip-measure`` on the CPU) the table is computed
for the v5e row and says so.

Pass filtering (``tune/measure.py``): every timing median — the
measured step, the forward-only split — rides the jitter-FILTERED
passes (a non-positive chain slope measured noise, not the program,
and is discarded by ``bench._filter_passes``).  The spread block
records ``passes`` (raw), ``passes_used`` (retained) and the per-pass
``slopes`` so the filter's effect is auditable from the record alone.
"""

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the row the analytic table is computed for when no chip is attached
ANALYTIC_DEVICE_KIND = "TPU v5 lite"


def layer_shapes(plans, state, input_shape, batch):
    """Fold the forward per layer with jax.eval_shape, returning
    [(name, in_shape, out_shape, param_bytes)]."""
    import jax

    from veles_tpu.models.all2all import All2All, All2AllSoftmax
    from veles_tpu.models.dropout import DropoutForward

    rows = []
    h = jax.ShapeDtypeStruct((batch,) + tuple(input_shape), "bfloat16")
    for i, (plan, p) in enumerate(zip(plans, state)):
        name = "%d_%s" % (i, plan.forward_cls.__name__)
        param_bytes = sum(
            v.size * 2 for v in (p or {}).values()
            if v is not None and hasattr(v, "size"))

        def apply(h, plan=plan, p=p):
            params = {k: jax.numpy.asarray(v, "bfloat16")
                      for k, v in (p or {}).items() if v is not None}
            if plan.forward_cls is All2AllSoftmax:
                return All2All.apply(params, h)
            if issubclass(plan.forward_cls, DropoutForward):
                return h
            return plan.forward_cls.apply(params, h, **plan.static)

        out = jax.eval_shape(apply, h)
        rows.append((name, tuple(h.shape), tuple(out.shape),
                     param_bytes))
        h = out
    return rows


def schedule_provenance(plan, params, ish, osh, dtype):
    """Tuned-vs-static provenance of the layer's backward kernel
    schedule (docs/kernels.md "Autotuning"): "tuned" when the schedule
    cache holds an entry the kernel's consult would serve for this
    exact (padded shape, dtype, precision, device) — so a future
    MFU.json regression is attributable to the schedule that actually
    ran.  "autodiff" marks shapes the Pallas backward falls back on
    (many-tap convs, overlapping-pool VMEM overflows have their own
    plan); None = the layer has no Pallas-scheduled kernel (dense
    layers run XLA's own dot inside the fused step)."""
    from veles_tpu.tune.cache import provenance
    from veles_tpu.tune.spec import conv_vjp_spec, pool_bwd_spec

    name = plan.forward_cls.__name__
    if "Conv" in name:
        w = (params or {}).get("weights")
        if w is None or len(getattr(w, "shape", ())) != 4:
            return None
        ky, kx = int(w.shape[0]), int(w.shape[1])
        from veles_tpu.ops.conv_vjp import MAX_FUSED_TAPS
        if ky * kx > MAX_FUSED_TAPS:
            return "autodiff"
        # precision_level 0 = what the fused step's gd units pass
        spec = conv_vjp_spec(ish, ky, kx, osh[-1], osh[1:3], dtype, 0,
                             plan.static.get("padding", (0, 0, 0, 0)),
                             plan.static.get("sliding", (1, 1)))
    elif ("Max" in name and "Abs" not in name
          and "window" in plan.static):
        spec = pool_bwd_spec(ish, osh[1:3], plan.static["window"],
                             plan.static["sliding"], dtype)
    else:
        return None
    return provenance(spec["op"], spec["shape"], spec["dtype"],
                      spec["precision_level"], spec["extra"])


def analytic_layer(name, in_shape, out_shape, param_bytes):
    """Forward FLOPs + training-step HBM traffic for one layer.

    FLOPs: conv = 2*B*OH*OW*K (K = kernel volume * Cin, recovered from
    the weight size); dense = 2*B*fan_in*fan_out; pool/dropout ~ 0.
    Training multiplies forward FLOPs by 3 (dgrad + wgrad each cost
    about one forward).

    Traffic model (bf16 = 2 bytes): activations in+out each touched
    ~3x across fwd+bwd (fwd read/write, bwd read grad + read saved
    activation / write dinput), parameters + momentum touched ~4x
    (fwd read W; bwd write dW; solver read accum, write accum+W).
    XLA fusion saves some of this, so the roofline is an upper-ish
    bound per layer; the committed ratio vs the measured step shows
    how tight it is.
    """
    bpe = 2.0
    in_elems = float(math.prod(in_shape))
    out_elems = float(math.prod(out_shape))
    # param_bytes counts weights+bias+accum_weights+accum_bias, so the
    # weight tensor alone holds about half the state elements
    weights_only = param_bytes / bpe / 2.0
    if "Conv" in name and param_bytes:
        # weights are (KH*KW*Cin, Cout): kernel_volume*Cin =
        # w_elems / Cout, and fwd flops = 2 * out_elems * that
        cout = out_shape[-1]
        kvol_cin = weights_only / cout
        flops_fwd = 2.0 * out_elems * kvol_cin
    elif ("All2All" in name or "Softmax" in name) and param_bytes:
        fan_in = in_elems / in_shape[0]
        fan_out = out_elems / out_shape[0]
        flops_fwd = 2.0 * in_shape[0] * fan_in * fan_out
    else:
        flops_fwd = 0.0
    flops_train = 3.0 * flops_fwd
    traffic = (3.0 * (in_elems + out_elems) * bpe
               + 2.0 * param_bytes)  # param_bytes already has accums
    return flops_train, traffic


def _measure_forward_only(plans, state, batch, peak_flops,
                          input_shape):
    """Slope-time the inference-only program: isolates how much of the
    train step's MFU gap lives in forward vs backward+update."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu.compiler import build_forward

    rng = numpy.random.RandomState(0)
    params = [{k: jnp.asarray(v, jnp.bfloat16)
               for k, v in (s or {}).items() if v is not None}
              for s in state]
    x = jax.device_put(
        (rng.rand(batch, *input_shape) * 0.5).astype(numpy.float32)
    ).astype(jnp.bfloat16)
    fwd = build_forward(plans)

    @jax.jit
    def fstep(params, x):
        return fwd(params, x).sum().astype(jnp.float32)

    float(fstep(params, x))  # compile + first exec

    def aval(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype)
    cost = fstep.lower(jax.tree.map(aval, params),
                       aval(x)).compile().cost_analysis()
    flops = float(cost.get("flops", 0)) if cost else 0.0

    def chain(k):
        start = time.perf_counter()
        v = None
        for _ in range(k):
            v = fstep(params, x)
        float(v)
        return time.perf_counter() - start

    from bench import _filter_passes, _spread
    slopes = []
    for _ in range(5):
        t1, t2 = chain(4), chain(24)
        slopes.append((t2 - t1) / 20)
    # the published median rides the jitter-filtered passes; the spread
    # block records passes_used + every per-pass slope (see main())
    per = float(numpy.median(_filter_passes(slopes)))
    row = {"step_ms": round(per * 1e3, 3),
           "images_per_sec": round(batch / per, 1),
           "spread": _spread(slopes)}
    if flops:
        row["xla_flops_per_step_g"] = round(flops / 1e9, 2)
        row["tflops"] = round(flops / per / 1e12, 1)
        row["mfu_pct"] = round(100.0 * flops / per / peak_flops, 1)
    return row


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="alexnet",
                        choices=("alexnet", "vgg16", "vgg11"),
                        help="model family from the zoo")
    parser.add_argument("--batch", type=int, default=256)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--out", default=None,
                        help="report path; defaults to "
                             "chiprun_out/MFU.json for alexnet, "
                             "chiprun_out/MFU_<MODEL>.json otherwise")
    parser.add_argument("--skip-measure", action="store_true",
                        help="analytic table only (no chip)")
    parser.add_argument("--fwd-split", action="store_true",
                        help="also measure the forward-only program "
                             "(one extra compile) to attribute the "
                             "MFU gap between forward and "
                             "backward+update")
    args = parser.parse_args()
    if args.out is None:
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        name = ("MFU.json" if args.model == "alexnet"
                else "MFU_%s.json" % args.model.upper())
        args.out = os.path.join(repo, "chiprun_out", name)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)

    from veles_tpu.models.zoo import (alexnet_layers,
                                      build_plans_and_state,
                                      vgg_layers)

    if args.model == "alexnet":
        specs, input_shape = alexnet_layers(classes=1000), (227, 227, 3)
    else:
        config = "D" if args.model == "vgg16" else "A"
        specs, input_shape = (vgg_layers(classes=1000, config=config),
                              (224, 224, 3))
    plans, state, _ = build_plans_and_state(specs, input_shape, seed=1)
    rows = layer_shapes(plans, state, input_shape, args.batch)

    from veles_tpu.observe.xla_introspect import PEAKS, device_peaks
    peaks = device_peaks()
    if peaks is None:
        if not args.skip_measure:
            raise SystemExit("mfu_breakdown: no chip attached; only "
                             "--skip-measure (the analytic table) "
                             "runs on the CPU")
        peaks = PEAKS[ANALYTIC_DEVICE_KIND]
    peak_flops = peaks["bf16"]
    bw = peaks["hbm"]
    # a populated schedule cache means tuned tiles may be serving some
    # layers' backward kernels: annotate each row with the schedule's
    # provenance so a future MFU regression is attributable to the
    # schedule that actually ran (docs/kernels.md "Autotuning")
    from veles_tpu.tune.cache import cache_for
    schedule_cache = cache_for()
    annotate = len(schedule_cache) > 0
    layers = []
    for (name, ish, osh, pbytes), plan, params in zip(
            rows, plans, state):
        fl, tr = analytic_layer(name, ish, osh, pbytes)
        t_mxu = fl / peak_flops
        t_hbm = tr / bw
        row = {
            "layer": name, "in": list(ish), "out": list(osh),
            "train_gflops": round(fl / 1e9, 2),
            "hbm_mbytes": round(tr / 1e6, 1),
            "t_mxu_us": round(t_mxu * 1e6, 1),
            "t_hbm_us": round(t_hbm * 1e6, 1),
            "bound": ("mxu" if t_mxu > t_hbm else "hbm"),
            "roofline_us": round(max(t_mxu, t_hbm) * 1e6, 1),
        }
        if annotate:
            prov = schedule_provenance(plan, params, ish, osh,
                                       args.dtype)
            if prov is not None:
                row["schedule"] = prov
        layers.append(row)
    total_roofline = sum(l["roofline_us"] for l in layers) / 1e6

    report = {
        "config": {"model": args.model, "batch": args.batch,
                   "dtype": args.dtype,
                   "peak_bf16_tflops": peak_flops / 1e12,
                   "hbm_gbps": bw / 1e9,
                   "peaks_source": peaks["source"]},
        "layers": layers,
        "roofline_total_ms": round(total_roofline * 1e3, 2),
    }
    if annotate:
        report["config"]["schedule_cache"] = schedule_cache.path

    if not args.skip_measure:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from bench import _train_step_images_per_sec
        dataset_size = max(1024, args.batch * 2)
        per_step, ips, flops, spread = _train_step_images_per_sec(
            specs, input_shape, args.batch, dataset_size, args.dtype,
            (4, 24) if args.batch > 128 else (4, 44), classes=1000)
        measured = {
            "step_ms": round(per_step * 1e3, 3),
            "images_per_sec": round(ips, 1),
            "spread": spread,
        }
        if flops:
            measured["xla_flops_per_step_g"] = round(flops / 1e9, 2)
            measured["tflops"] = round(flops / per_step / 1e12, 2)
            measured["mfu_pct"] = round(
                100.0 * flops / per_step / peak_flops, 1)
        report["measured"] = measured
        report["model_vs_measured_ratio"] = round(
            total_roofline / per_step, 3)

        if args.fwd_split:
            report["forward_only"] = _measure_forward_only(
                plans, state, args.batch, peak_flops, input_shape)
            fwd = report["forward_only"]
            bwd_ms = measured["step_ms"] - fwd["step_ms"]
            fwd_g = fwd.get("xla_flops_per_step_g")
            bwd_flops = (flops - fwd_g * 1e9
                         if flops and fwd_g else None)
            split = {"bwd_plus_update_ms": round(bwd_ms, 3)}
            if bwd_flops:
                split["bwd_tflops"] = round(
                    bwd_flops / (bwd_ms / 1e3) / 1e12, 1)
                split["bwd_mfu_pct"] = round(
                    100.0 * bwd_flops / (bwd_ms / 1e3) / peak_flops, 1)
            report["backward_attribution"] = split

    # the story the table tells, computed so it can't go stale
    hbm_us = sum(l["roofline_us"] for l in layers
                 if l["bound"] == "hbm")
    mxu_us = sum(l["roofline_us"] for l in layers
                 if l["bound"] == "mxu")
    top = sorted(layers, key=lambda l: -l["roofline_us"])[:3]
    top_txt = ", ".join("%s (%.0fus %s)" % (
        l["layer"], l["roofline_us"], l["bound"]) for l in top)
    hbm_share = hbm_us / max(hbm_us + mxu_us, 1e-9)
    attainable = None
    if not args.skip_measure and report.get("measured", {}).get(
            "xla_flops_per_step_g"):
        # MFU the roofline permits: XLA's own FLOP count over the
        # roofline time at chip peak
        attainable = round(
            100.0 * report["measured"]["xla_flops_per_step_g"] * 1e9
            / (total_roofline * peak_flops), 1)
        report["roofline_attainable_mfu_pct"] = attainable
    if hbm_share > 0.5:
        report["conclusion"] = (
            "%.0f%% of roofline time sits in HBM-bound layers "
            "(%.0fus hbm vs %.0fus mxu); top costs: %s.  The non-MXU "
            "share of the step is memory traffic — raising MFU means "
            "cutting activation traffic (fusion/remat), not faster "
            "matmuls." % (100 * hbm_share, hbm_us, mxu_us, top_txt))
    else:
        split = ""
        fwd = report.get("forward_only")
        bwd = report.get("backward_attribution")
        if fwd and bwd and fwd.get("mfu_pct"):
            split = (
                "  Measured split: forward %.0f%% MFU, "
                "backward+update %.0f%%."
                % (fwd["mfu_pct"], bwd.get("bwd_mfu_pct", 0)))
        report["conclusion"] = (
            "The roofline is MXU-bound (%.0fus mxu vs %.0fus hbm; "
            "top costs: %s)%s.%s" % (
                mxu_us, hbm_us, top_txt,
                ("; the roofline would permit ~%.0f%% MFU"
                 % attainable) if attainable else "", split))

    with open(args.out, "w") as fout:
        json.dump(report, fout, indent=1, sort_keys=True)
        fout.write("\n")
    print(json.dumps(report.get("measured", {})))
    print("roofline total %.2f ms; wrote %s" % (
        total_roofline * 1e3, args.out))


if __name__ == "__main__":
    main()
