#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, once.

  python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts a ray_tpu cluster on this host, runs the cell's job (found by the
``kind`` of its traffic file under ``benchmarks/jobs/``), shuts everything
down, and prints the contract's JSON object as the last line of stdout.
``--trace 0`` gives the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (each read by its own reader from the profiler's trace and
the job's host-clock stats).  This process never initialises a jax backend,
and fails unless the workers' jax is on a TPU.  ``--rehearse-cpu`` walks the
same code at tiny widths on CPU workers; its last line says
``rehearsal_ok`` and carries no metric.  See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_START_WALL = time.time()

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


@dataclasses.dataclass
class Job:
    """What a job kind's ``run(job)`` gets."""
    chips: int
    config: dict
    mix: dict
    seed: int
    seconds: float
    rehearse: bool
    out_dir: str
    trace_dir: str  # "" = tracing off
    t_start_wall: float


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric's reader gets."""
    trace: object  # lib.trace_reduce.Trace or None
    stats: dict    # the job's host-clock measurements and sizes
    config: dict
    mix: dict
    peaks: dict    # this device kind's row of lib/peaks.json
    chips: int


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "lib", "peaks.json"))
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"device kind {kind!r} is not in lib/peaks.json: add "
                       "its published peaks with their source")
    return table[kind]


def end_to_end_metrics(bench: dict, cell: str, values: dict) -> dict:
    """The cell's end-to-end metrics as the line carries them: those that
    list the cell, and those with no list (``setup_s``), which a cell in no
    metric's ``workloads`` prints alone."""
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, cell)}


def read_layer_metrics(bench: dict, cell: str, ctx: ReadContext) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell):
            continue
        spec = load_json(os.path.join(
            HERE, "layer_metrics", metric["name"] + ".json"))
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:  # nothing to read: left out of the line
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on CPU workers; never prints a metric")
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT, os.environ.get("PYTHONPATH", "")])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]

    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_dir = os.path.join(out_dir, "trace") if args.trace else ""

    import ray_tpu  # noqa: F401 - without the repo around it, this fails here

    from benchmarks.lib import cluster
    from benchmarks.lib.cluster import log

    job = Job(chips=cell["chips"], config=config, mix=mix,
              seed=args.seed, seconds=float(seconds),
              rehearse=args.rehearse_cpu, out_dir=out_dir,
              trace_dir=trace_dir, t_start_wall=T_START_WALL)
    kind = importlib.import_module("benchmarks.jobs." + mix["kind"])

    cluster.adopt_orphans()
    result = None
    try:
        cluster.start_cluster(cell["chips"], args.rehearse_cpu)
        result = kind.run(job)
    except Exception:  # the boundary: report, clean up, exit non-zero
        traceback.print_exc()
        cluster.print_worker_logs()
    finally:
        leaked = cluster.stop_everything()
    if leaked:
        log(f"processes outlived shutdown(): {leaked}")
    if result is None or leaked:
        log("FAILED")
        return 1
    bridge = sys.modules.get("jax._src.xla_bridge")
    cluster.check(bridge is None or not bridge.backends_are_initialized(),
                  "this process initialised a jax backend")

    device = result["device"]
    for problem in result["problems"]:
        log(f"NOT CORRECT: {problem}")
    log("notes: " + json.dumps(result.get("notes", {}), default=str))
    if args.rehearse_cpu:
        cluster.check(device["platform"] == "cpu", f"rehearsal on {device}")
        print(json.dumps({
            "rehearsal_ok": not result["problems"], "workload": cell["name"],
            "attempted": result["attempted"], "failed": result["failed"],
            "problems": result["problems"]}))
        return 0 if not result["problems"] else 1
    cluster.check(device["platform"] == "tpu" and device["count"] == cell["chips"],
                  f"cell wants {cell['chips']} TPU chip(s), workers ran on {device}")

    line = {"correct": not result["problems"],
            "attempted": result["attempted"], "failed": result["failed"]}
    if not args.trace:
        line["metrics"] = end_to_end_metrics(
            bench, cell["name"], result["end_to_end"])
    else:
        from benchmarks.lib.trace_reduce import Trace

        trace = Trace.from_dir(trace_dir)
        cluster.check(trace is not None and trace.busy_s > 0,
                      "the traced window holds no operation on the device")
        with open(os.path.join(out_dir, "trace_summary.txt"), "w") as f:
            f.write(trace.describe())
        ctx = ReadContext(trace=trace, stats=result["stats"], config=config,
                          mix=mix, peaks=peaks_for(device["kind"]),
                          chips=cell["chips"])
        line["metrics"] = read_layer_metrics(bench, cell["name"], ctx)
        device = dict(device, busy_s=trace.busy_s, window_s=trace.window_s)
        line["breakdown"] = trace.breakdown()
    line["device"] = device
    # Each number compared beside its limit, last in the line and on stderr.
    line["compared"] = result.get("compared", {})
    log("compared (value, limit): " + json.dumps(line["compared"]))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
