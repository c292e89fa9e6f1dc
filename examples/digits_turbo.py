"""Digits via the epoch-scan turbo path — the whole epoch as ONE
XLA dispatch per class (compiler.build_train_epoch/build_eval_epoch).

The standard workflow (examples/digits.py) drives the unit graph:
loader -> fused trainer -> decision, one dispatch per minibatch.  This
example trades the per-minibatch decision gates for raw speed: train
and validation passes each compile to a single scanned program, so a
dispatch-bound model spends its wall time on compute alone (how much
faster that is on the chip: not measured on today's code).  Early
stopping happens between epochs.

Run it directly (no CLI wrapper: the turbo path IS the loop):

    python examples/digits_turbo.py [--epochs 40] [--backend tpu]
"""

import argparse
import os
import sys

import numpy

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=40)
    parser.add_argument("--backend", default=None,
                        help="tpu | cpu | auto (default: auto)")
    parser.add_argument("--batch", type=int, default=48)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from veles_tpu.backends import Device
    from veles_tpu.compiler import (build_eval_epoch,
                                    build_train_epoch)
    from veles_tpu.datasets import digits_arrays
    from veles_tpu.models.zoo import build_plans_and_state

    Device(backend=args.backend)  # resolve + init backend/caches

    # same deterministic split the standard digits anchor trains on;
    # the epoch scans run sub-batch tails as masked steps, so the
    # full validation set participates
    train_x, train_y, valid_x, valid_y = digits_arrays()
    data = numpy.concatenate([train_x, valid_x])
    labels = numpy.concatenate([train_y, valid_y])
    train_idx = numpy.arange(len(train_x))
    valid_idx = numpy.arange(len(train_x), len(data))
    rng = numpy.random.RandomState(2)

    specs = [
        {"type": "all2all_tanh", "output_sample_shape": 64,
         "learning_rate": 0.08, "gradient_moment": 0.9,
         "weights_decay": 1e-4},
        {"type": "softmax", "output_sample_shape": 10,
         "learning_rate": 0.08, "gradient_moment": 0.9,
         "weights_decay": 1e-4},
    ]
    plans, state, _ = build_plans_and_state(specs, (64,), seed=2)
    state = jax.tree.map(
        lambda l: None if l is None else jnp.asarray(l),
        state, is_leaf=lambda x: x is None)

    dataset = jax.device_put(data)
    labels_dev = jax.device_put(labels.astype(numpy.int32))
    valid_order = jax.device_put(valid_idx.astype(numpy.int32))

    from veles_tpu.compiler import step_compiler_options
    opts = step_compiler_options()  # per-chip tuned XLA options
    train = build_train_epoch(plans, args.batch, compiler_options=opts)
    evaluate = build_eval_epoch(plans, args.batch,
                                compiler_options=opts)

    best_err, best_epoch = float("inf"), -1
    for epoch in range(args.epochs):
        train_order = jax.device_put(
            rng.permutation(train_idx).astype(numpy.int32))
        state, totals = train(state, dataset, labels_dev, train_order)
        params = [{"weights": s["weights"], "bias": s["bias"]}
                  for s in state]
        m = evaluate(params, dataset, labels_dev, valid_order)
        err_pct = 100.0 * int(m["n_err"]) / int(m["samples"])
        if err_pct < best_err:
            best_err, best_epoch = err_pct, epoch
        print("epoch %2d: train loss %.4f  valid err %.2f%%" % (
            epoch, float(totals["loss_mean"]), err_pct))
    print("best validation error %.2f%% (epoch %d)" % (
        best_err, best_epoch))
    return best_err


if __name__ == "__main__":
    main()
