"""QUANT.json — the int8 quantized-inference accuracy-parity receipt
(docs/serving.md "Quantized ladder").

Two zoo models (the mnist MLP and a conv stack) are trained to
decisiveness on seeded synthetic class data through the fused train
step, post-training-quantized (per-channel symmetric weights,
percentile activation calibration on a training-distribution stream),
and served through BOTH AOTEngine ladders in one process.  The
receipt records, per model:

- **top-1 accuracy** of the f32 and int8 engines on a held-out stream
  and their delta (the acceptance bound: <= 1 %), plus the raw
  prediction agreement and max softmax-probability divergence;
- the **bit-exactness** flag of the int8 Pallas matmul vs the jitted
  interpret-mode reference on the exact quantized operands the model
  serves (not a synthetic shape);
- **CPU latency rows** for both engines, honestly labeled: on CPU the
  int8 kernels execute through the Pallas INTERPRETER, so the int8
  leg's wall time measures the interpreter and carries no speedup
  claim — the 8-bit rate on a TPU is not measured (no cell of the
  benchmark serves; ROADMAP R2);
- warm-restart **compile receipts** for the quantized digests.

A compact ``quant_ab`` block is also folded into BENCH_serve.json so
the serving receipt carries the quantized ladder next to its
latency/throughput rows.

Run:  JAX_PLATFORMS=cpu python scripts/quant_receipt.py
"""

import json
import os
import sys
import time

import numpy

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _blob_data(rng, n, sample_shape, classes, sep=3.2, noise=1.0):
    """Seeded Gaussian class blobs with genuine overlap: the center
    spread scales as 1/sqrt(dim) so the pairwise separation along the
    discriminant is ~sep noise-sigmas REGARDLESS of dimensionality,
    landing the trained models in the ~90-98% top-1 band — the int8
    delta is then measured where decision boundaries actually live
    instead of on a saturated 100%-accuracy task where any delta
    would read as 0."""
    dim = int(numpy.prod(sample_shape))
    centers = rng.randn(classes, *sample_shape).astype(
        numpy.float32) * (sep / numpy.sqrt(dim))
    labels = rng.randint(0, classes, n).astype(numpy.int32)
    data = centers[labels] + rng.randn(
        n, *sample_shape).astype(numpy.float32) * noise
    return data, labels


def _train(plans, state, data, labels, batch=128, steps=80):
    """A short fused-step run — enough to make the heads decisive."""
    from veles_tpu.compiler import build_train_step

    step = build_train_step(plans, loss="softmax", donate=False)
    n = data.shape[0]
    for i in range(steps):
        lo = (i * batch) % (n - batch)
        state, metrics = step(state, data[lo:lo + batch],
                              labels[lo:lo + batch], float(batch))
    return state, {k: float(v) for k, v in metrics.items()}


def _latency_row(engine, x, reps=20):
    """Median whole-batch infer wall time (ms) — a CPU machinery
    number, labeled as such in the receipt."""
    engine.infer(x[:8])  # warm every rung the chunker will touch
    times = []
    for _ in range(reps):
        start = time.perf_counter()
        engine.infer(x)
        times.append(time.perf_counter() - start)
    return round(float(numpy.median(times)) * 1e3, 3)


def _receipt_for_model(name, specs, sample_shape, seed, train_n=4096,
                       eval_n=2048, steps=80, sep=3.2):
    import jax
    import jax.numpy as jnp

    from veles_tpu.backends import Device
    from veles_tpu.models.zoo import build_plans_and_state
    from veles_tpu.ops.matmul_int8 import (matmul_int8,
                                           matmul_int8_reference)
    from veles_tpu.quant import quantize_model_spec
    from veles_tpu.quant.forward import quantize_activation
    from veles_tpu.serve.engine import AOTEngine

    rng = numpy.random.RandomState(seed)
    classes = specs[-1]["output_sample_shape"]
    plans, state, _out_shape = build_plans_and_state(
        specs, sample_shape, seed=seed)
    data, labels = _blob_data(rng, train_n + eval_n, sample_shape,
                              classes, sep=sep)
    state, last_metrics = _train(plans, state, data[:train_n],
                                 labels[:train_n], steps=steps)
    params = [{"weights": None if s["weights"] is None
               else numpy.asarray(s["weights"]),
               "bias": None if s["bias"] is None
               else numpy.asarray(s["bias"])} for s in state]

    calib = data[:512]
    qparams, calibration = quantize_model_spec(plans, params, calib)

    device = Device(backend="cpu")
    ladder = (32, 128)
    engines = {}
    for leg, p in (("f32", params), ("int8", qparams)):
        engines[leg] = AOTEngine(plans, p, sample_shape, ladder=ladder,
                                 device=device)
        engines[leg].compile()

    x_eval = data[train_n:train_n + eval_n]
    y_eval = labels[train_n:train_n + eval_n]
    probs = {leg: engines[leg].infer(x_eval) for leg in engines}
    preds = {leg: probs[leg].argmax(1) for leg in engines}
    acc = {leg: float((preds[leg] == y_eval).mean()) for leg in preds}

    # kernel-vs-reference bit-exactness on the model's OWN quantized
    # weights: the contraction shape the served ladder runs (for a
    # conv entry, the im2col-flattened (taps*Cin, Cout) matrix), fed
    # grid-true int8 activations quantized on the entry's calibrated
    # scale
    q_entry = next(e for e in qparams if e.get("weights_scale")
                   is not None)
    w_q = jnp.asarray(q_entry["weights"].reshape(
        -1, q_entry["weights"].shape[-1]))
    act_scale = jnp.asarray(q_entry["act_scale"])
    a_q = quantize_activation(
        jnp.asarray(rng.rand(32, w_q.shape[0]).astype(numpy.float32)
                    * float(act_scale) * 127.0), act_scale)
    scale = jnp.asarray(q_entry["act_scale"]
                        * q_entry["weights_scale"])
    bias = jnp.asarray(q_entry["bias"])
    bitexact = bool(
        (numpy.asarray(matmul_int8(a_q, w_q, scale, bias)) ==
         numpy.asarray(jax.jit(matmul_int8_reference)(
             a_q, w_q, scale, bias))).all())

    return {
        "model": name,
        "sample_shape": list(sample_shape),
        "classes": int(classes),
        "train_steps": steps,
        "final_train_loss": round(last_metrics["loss"], 5),
        "eval_samples": eval_n,
        "top1_f32_pct": round(100 * acc["f32"], 3),
        "top1_int8_pct": round(100 * acc["int8"], 3),
        "top1_delta_pct": round(100 * abs(acc["f32"] - acc["int8"]),
                                3),
        "prediction_agreement_pct": round(
            100 * float((preds["f32"] == preds["int8"]).mean()), 3),
        "max_abs_dprob": float(numpy.abs(probs["f32"]
                                         - probs["int8"]).max()),
        "clip_fraction": round(calibration.clip_fraction, 6),
        "pallas_bitexact_vs_reference": bitexact,
        "digests": {leg: engines[leg].digest for leg in engines},
        "compile_receipts": {
            leg: {k: engines[leg].compile_receipt[k]
                  for k in ("backend_compiles", "cache_hits",
                            "new_compiles", "rungs", "quantized")}
            for leg in engines},
        "cpu_latency_ms_batch128": {
            leg: _latency_row(engines[leg], x_eval[:128])
            for leg in engines},
    }


def main():
    t0 = time.time()
    from veles_tpu.models.zoo import mnist_mlp_layers

    conv_specs = [
        {"type": "conv_str", "n_kernels": 8, "kx": 5, "ky": 5,
         "sliding": (1, 1), "padding": 2, "learning_rate": 0.02,
         "gradient_moment": 0.9},
        {"type": "max_pooling", "kx": 2, "ky": 2, "sliding": (2, 2)},
        {"type": "all2all_tanh", "output_sample_shape": 64,
         "learning_rate": 0.02, "gradient_moment": 0.9},
        {"type": "softmax", "output_sample_shape": 10,
         "learning_rate": 0.02, "gradient_moment": 0.9},
    ]
    models = [
        ("mnist_mlp_784_100_10",
         mnist_mlp_layers(lr=0.05), (784,), 13, 3.2),
        ("convnet_16x16_c8_p2_fc64_10", conv_specs, (16, 16, 1), 17,
         7.0),
    ]
    rows = [
        _receipt_for_model(name, specs, shape, seed, sep=sep)
        for name, specs, shape, seed, sep in models]

    import jax
    receipt = {
        "kind": "quantized-inference parity receipt "
                "(docs/serving.md 'Quantized ladder')",
        "schema": 1,
        "platform": jax.devices()[0].device_kind,
        "scheme": "w8a8 symmetric: per-channel weight scales, "
                  "per-tensor percentile-99.9 activation scales, "
                  "int32 accumulation, fused dequant epilogue "
                  "(ops/matmul_int8.py)",
        "acceptance": {
            "top1_delta_bound_pct": 1.0,
            "all_within_bound": all(
                r["top1_delta_pct"] <= 1.0 for r in rows),
            "all_bitexact": all(
                r["pallas_bitexact_vs_reference"] for r in rows),
        },
        "models": rows,
        "latency_note": (
            "cpu_latency_ms rows are CPU-interpreter machinery "
            "evidence only: the int8 Pallas kernels run through the "
            "Pallas interpreter on CPU, so the int8 leg measures the "
            "interpreter, not the MXU's 8-bit rate.  The 8-bit rate "
            "on a TPU is not measured: no cell of the benchmark "
            "serves (ROADMAP R2)."),
        "wall_s": round(time.time() - t0, 1),
    }
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "QUANT.json")
    with open(out, "w") as fout:
        json.dump(receipt, fout, indent=1)
    print(json.dumps(receipt, indent=1))

    # fold the compact quantized block into BENCH_serve.json so the
    # serving receipt carries the quantized ladder beside its
    # latency/throughput rows
    bench_path = os.path.join(os.path.dirname(out), "BENCH_serve.json")
    try:
        with open(bench_path) as fin:
            bench = json.load(fin)
        bench["quant_ab"] = {
            "see": "QUANT.json",
            "platform": receipt["platform"],
            "models": {r["model"]: {
                "top1_delta_pct": r["top1_delta_pct"],
                "agreement_pct": r["prediction_agreement_pct"],
                "bitexact": r["pallas_bitexact_vs_reference"],
                "cpu_latency_ms_batch128":
                    r["cpu_latency_ms_batch128"],
            } for r in rows},
            "note": receipt["latency_note"],
        }
        with open(bench_path, "w") as fout:
            json.dump(bench, fout, indent=1)
        print("BENCH_serve.json: quant_ab block updated")
    except (OSError, ValueError) as exc:
        print("BENCH_serve.json not updated: %s" % exc)
    return 0


if __name__ == "__main__":
    sys.exit(main())
