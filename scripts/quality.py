"""Model-quality harness: trains the example workflows on real data
through the full loader->workflow->decision->snapshotter graph and
records the reached validation errors in QUALITY.json (committed).

Always runs the offline anchors (real handwritten digits bundled with
scikit-learn, across MLP/conv/LSTM/autoencoder families).  Runs the
dataset-gated parity anchors — MNIST 1.48 %, CIFAR-10 17.21 %, STL-10
35.10 %, MNIST autoencoder RMSE 0.5478
(/root/reference/docs/source/manualrst_veles_algorithms.rst:31,50,51,69)
— when their datasets are present; ``--skip-datasets`` skips all of
them.

Rows are keyed by backend and path: ``--backend cpu`` writes under
``results`` (the historical CPU key), any other backend under
``results_<backend>`` — all kept in the same file, so a TPU run
records on-chip proof alongside the CPU anchors (round-3 verdict
item 2).  On TPU the DEFAULT path auto-fuses (StandardWorkflow fuses
the train loop into one dispatch per minibatch), so ``results_tpu``
is fused-path evidence; every row carries a ``fused`` flag.
``--fuse`` forces fusing on a backend whose default is per-unit
(rows land under ``results_<backend>_fused``, including cpu);
``--no-fuse`` keeps the per-unit debug path on TPU (rows land under
``results_tpu_unit``).  Anchors no longer in the known set are
dropped from every results_* map on rewrite.  ``--anchors`` selects
a subset (default: all offline anchors + mnist/cifar when data
exists).

    python scripts/quality.py [--out QUALITY.json] [--backend cpu]
                              [--anchors digits,sequence,...]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_example(module_name, backend, snapshot_check=False,
                fuse=False, no_fuse=False):
    """Build the example's workflow, run it, and report
    {best_error_pct, best_epoch, epochs, seconds}.  With
    ``snapshot_check`` a snapshotter rides the loop (snapshot on every
    improved epoch) and the best snapshot is re-imported afterwards —
    anchors without the flag run snapshot-free, so their ``seconds``
    exclude snapshot overhead."""
    import importlib

    from veles_tpu.launcher import Launcher
    from veles_tpu.snapshotter import Snapshotter, SnapshotterBase

    from veles_tpu.config import root
    if no_fuse:
        root.common.engine.auto_fuse = False
    module = importlib.import_module(module_name)
    launcher = Launcher()
    workflow = module.build(launcher)
    if fuse and getattr(workflow, "fused_trainer", None) is None:
        # force the fused path on a backend whose default is per-unit
        # (on TPU the StandardWorkflow auto-fuses at initialize)
        workflow.fuse()

    # the snapshotter rides the loop only for the anchor that proves
    # restore: each whole-workflow pickle map_reads every param from
    # the device, so attaching it everywhere multiplies on-chip anchor
    # wall time for no additional evidence
    snap = None
    if snapshot_check:
        tmpdir = tempfile.mkdtemp(prefix="quality_snap_")
        snap = Snapshotter(workflow, directory=tmpdir,
                           prefix=module_name, interval=1,
                           time_interval=0, compression="gz")
        snap.link_from(workflow.decision)
        snap.gate_skip = ~workflow.decision.improved

    started = time.time()
    launcher.initialize(device=backend)
    launcher.run()
    elapsed = time.time() - started

    result = {
        "best_error_pct": workflow.decision.best_metric,
        "best_epoch": workflow.decision.best_epoch,
        "epochs": int(workflow.loader.epoch_number),
        "seconds": round(elapsed, 2),
        "backend": backend,
        "fused": getattr(workflow, "fused_trainer", None) is not None,
    }
    if snapshot_check:
        # checkpoint/resume proof: the best snapshot reloads and its
        # weights are live (finite) after re-initialize
        restored = SnapshotterBase.import_file(snap.destination)
        relauncher = Launcher()
        restored.workflow = relauncher
        restored.restored_from_snapshot_ = True
        relauncher._workflow = restored
        relauncher.initialize(device=backend)
        import numpy
        restored.forwards[0].weights.map_read()
        assert numpy.isfinite(restored.forwards[0].weights.mem).all()
        result["snapshot_restored"] = True
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "QUALITY.json"))
    parser.add_argument("--backend", default=os.environ.get(
        "VELES_BACKEND", "cpu"))
    parser.add_argument("--anchors", default=None,
                        help="comma list; default all")
    parser.add_argument("--fuse", action="store_true",
                        help="force the fused single-dispatch trainer "
                             "on a backend whose default is per-unit "
                             "(rows land under "
                             "results_<backend>_fused, incl. cpu)")
    parser.add_argument("--no-fuse", action="store_true",
                        help="keep the per-unit debug path on TPU "
                             "(rows land under results_tpu_unit)")
    parser.add_argument("--skip-mnist", action="store_true")
    parser.add_argument("--skip-cifar", action="store_true")
    parser.add_argument("--skip-datasets", action="store_true",
                        help="skip every dataset-gated anchor "
                             "(mnist, cifar10, stl10, "
                             "mnist_autoencoder)")
    args = parser.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples"))

    from veles_tpu.datasets import DatasetNotFound

    targets = {
        "digits": {"note": "offline anchor, no reference number"},
        "digits_conv": {"note": "conv *classification* through the "
                                "conv/pool stack on digits (reference "
                                "conv numbers are classification, "
                                "manualrst_veles_algorithms.rst:50)"},
        "sequence": {"note": "LSTM over digit rows; the reference "
                             "shipped RNN/LSTM untested — no number "
                             "to match, anchor is ours"},
        "conv_autoencoder": {"note": "conv+deconv reconstruction on "
                                     "digits (reference family: conv "
                                     "autoencoders)"},
        "autoencoder": {"reference_rmse": 0.5478,
                        "source": "manualrst_veles_algorithms.rst:69",
                        "note": "reference number is MNIST; offline "
                                "anchor reconstructs 8x8 digits"},
        "mnist": {"reference_error_pct": 1.48,
                  "source": "manualrst_veles_algorithms.rst:31"},
        "cifar10": {"reference_error_pct": 17.21,
                    "source": "manualrst_veles_algorithms.rst:50"},
        "stl10": {"reference_error_pct": 35.10,
                  "source": "manualrst_veles_algorithms.rst:51"},
        "mnist_autoencoder": {
            "reference_rmse": 0.5478,
            "source": "manualrst_veles_algorithms.rst:69"},
    }

    # merge into the existing record so a TPU pass extends (not
    # clobbers) the committed CPU rows
    report = {"targets": targets, "results": {}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as fin:
                report.update(json.load(fin))
            report["targets"] = targets
        except ValueError:
            pass
    if args.fuse and args.no_fuse:
        parser.error("--fuse and --no-fuse are mutually exclusive")
    base_key = ("results" if args.backend == "cpu"
                else "results_%s" % args.backend)
    if args.fuse:
        # explicit fused suffix always names the backend (cpu included)
        results_key = "results_%s_fused" % args.backend
    elif args.no_fuse and args.backend == "tpu":
        # the TPU default IS fused; the opt-out is the marked path
        results_key = "results_tpu_unit"
    else:
        results_key = base_key
    results = report.setdefault(results_key, {})
    # drop rows for anchors that no longer exist (renamed/removed
    # anchors otherwise live in the record forever)
    for key, rows in list(report.items()):
        if key.startswith("results") and isinstance(rows, dict):
            for stale in set(rows) - set(targets):
                del rows[stale]

    anchors = (args.anchors.split(",") if args.anchors else
               ["digits", "digits_conv", "sequence", "autoencoder",
                "conv_autoencoder", "mnist", "cifar10", "stl10",
                "mnist_autoencoder"])

    rmse_anchors = {"autoencoder", "conv_autoencoder",
                    "mnist_autoencoder"}
    dataset_gated = {"mnist", "cifar10", "stl10", "mnist_autoencoder"}
    for name in anchors:
        if (name == "mnist" and args.skip_mnist
                or name == "cifar10" and args.skip_cifar
                or name in dataset_gated and args.skip_datasets):
            results[name] = {"status": "skipped"}
            continue
        try:
            row = run_example(name, args.backend,
                              snapshot_check=(name == "digits"),
                              fuse=args.fuse, no_fuse=args.no_fuse)
        except DatasetNotFound as exc:
            results[name] = {"status": "data_unavailable",
                             "detail": str(exc)}
            print("%s: data unavailable (%s)" % (name, exc))
            continue
        if name in rmse_anchors:
            row["best_rmse"] = row.pop("best_error_pct")
            print("%s: RMSE %.4f (epoch %d)" % (
                name, row["best_rmse"], row["best_epoch"]))
        else:
            print("%s: %.2f%% (epoch %d)" % (
                name, row["best_error_pct"], row["best_epoch"]))
        results[name] = row

    with open(args.out, "w") as fout:
        json.dump(report, fout, indent=1, sort_keys=True)
        fout.write("\n")
    print("wrote", args.out)


if __name__ == "__main__":
    main()
