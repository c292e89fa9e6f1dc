#!/usr/bin/env python3
"""benchmark/run.py -- one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

One new process per run: find the cell in ``BENCHMARK.json``, load its
configuration's file and its traffic mix's file, hand them to the runner
the traffic file names, and print one JSON line last: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones) and ``device``.

Everything that belongs to one configuration, one traffic mix, one
runner or one per-layer metric is a file found BY NAME (README.md in
this directory): a later PR adds files and manifest entries and edits
nothing here.  No TPU, or fewer chips than the cell asks for: exit 2 and
no result line.  There is no CPU fallback and no flag that allows one;
tier-1 drives the runner's functions at toy width.
"""

import time

STARTED = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def say(fmt, *args):
    print(fmt % args if args else fmt, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as fin:
        return json.load(fin)


def load_manifest(repo=REPO):
    return load_json(repo, "BENCHMARK.json")


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError("BENCHMARK.json has no %s named %r (it has %s)"
                   % (what, name, [e["name"] for e in entries]))


def load_cell(manifest, name, repo=REPO):
    """(cell entry, configuration file's contents, traffic file's)."""
    cell = find(manifest["workloads"], name, "workload")
    entry = find(manifest["configs"], cell["config"], "config")
    return (cell, load_json(repo, entry["file"]),
            load_json(repo, "benchmark", "traffic",
                      cell["traffic"] + ".json"))


def cell_metrics(manifest, group, cell_name):
    """The metrics of ``group`` that the cell reports."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


def load_reader(name, repo=REPO):
    """The per-layer metric's own file, ``layer_metrics/<name>.py``."""
    path = os.path.join(repo, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_layer_metrics(manifest, cell_name, context, repo=REPO):
    """{name: value} from each of the cell's per-layer readers; one that
    finds nothing to read returns None and is left out."""
    out = {}
    for metric in cell_metrics(manifest, "per_layer", cell_name):
        value = load_reader(metric["name"], repo).read(context)
        if value is not None:
            out[metric["name"]] = float(value)
    return out


def device_block(devices, trace=None):
    stats = [d.memory_stats() or {} for d in devices]
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices),
             "memory_peak_bytes": max(
                 s.get("peak_bytes_in_use", 0) for s in stats)}
    if trace is not None:
        block["busy_s"] = trace["busy_s"]
        block["window_s"] = trace["window_s"]
    return block


def result_line(manifest, ctx, result, devices):
    """The last line's object, from what the runner returned."""
    cell_name = ctx.cell["name"]
    units = {m["name"]: m["unit"]
             for group in ("end_to_end", "per_layer")
             for m in manifest[group]}
    trace = result["layers"]["trace"]
    if ctx.trace:
        if trace is None:
            raise RuntimeError("the traced run holds no whole train step")
        values = read_layer_metrics(manifest, cell_name, result["layers"])
    else:
        names = [m["name"] for m in
                 cell_metrics(manifest, "end_to_end", cell_name)]
        missing = [n for n in names if n not in result["metrics"]]
        if missing:
            raise RuntimeError(
                "this run of %s gave no %s (the runner said why): no "
                "result" % (cell_name, ", ".join(missing)))
        values = {name: result["metrics"][name] for name in names}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": {name: {"value": value, "unit": units[name]}
                        for name, value in values.items()},
            "device": device_block(devices, trace if ctx.trace else None)}
    if ctx.trace:
        from benchmark import reduce_trace
        line["breakdown"] = reduce_trace.breakdown(trace)
    # last in the line: every number compared for `correct`, [it, limit]
    line["compared"] = result["compared"]
    return line


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default="", metavar="DIR",
                        help="also copy the run's *.xplane.pb there")
    args = parser.parse_args(argv)
    manifest = load_manifest()
    cell, config, traffic = load_cell(manifest, args.workload)

    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or len(devices) < cell["chips"]:
        sys.stderr.write(
            "benchmark: cell %s needs %d TPU chip(s); jax's default "
            "backend is %r with %d device(s) %s -- no result\n"
            % (cell["name"], cell["chips"], backend, len(devices),
               devices))
        return 2
    devices = devices[:cell["chips"]]

    from veles_tpu.backends import Device, enable_compile_cache
    from veles_tpu.logger import setup_logging
    setup_logging()
    say("benchmark: cell %s on %s %r x %d; compile cache %s",
        cell["name"], devices[0].platform, devices[0].device_kind,
        len(devices), enable_compile_cache())
    runner = importlib.import_module(
        "benchmark.runners." + traffic["runner"])
    ctx = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        keep_trace=args.keep_trace, started=STARTED, say=say,
        chips=cell["chips"], devices=devices,
        device_kind=devices[0].device_kind,
        device=Device(backend="tpu"))
    result = runner.run(ctx)
    line = result_line(manifest, ctx, result, devices)
    for name, (number, limit) in line["compared"].items():
        sys.stderr.write("benchmark: compared %s %r limit %r\n"
                         % (name, number, limit))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
