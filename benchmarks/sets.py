#!/usr/bin/env python3
"""Two interleaved sets of runs of one cell, and the no-op test on them: how
a ``benchmark`` PR derives a bound (PERF.md section 2).  By hand, on the machine that
holds the chips; this process never touches jax.

  python3 benchmarks/sets.py run --cell <cell> --seeds a,b,c,d,e,f \\
      [--seconds 45] [--cold-seed n] [--trace-seeds x,y,z] \\
      [--parent DIR --parent-seeds a,b] --out chiprun_out/sets/<cell>.jsonl
  python3 benchmarks/sets.py table <file.jsonl> ...

``run``: one cold run (compiles; kept apart), then set 1 and set 2 turn and
turn about, both over the same seeds (set 2 starts half-way round them, so no
seed runs twice in a row and an hour's drift of the host falls on both),
then the traced runs, then the runs of another tree (``--parent``: the parent
commit unpacked by ``git archive``) on seeds of the sets.  Every run's last
line and the job's ``notes`` go to ``--out``, one JSON object a line.
``table``: run by run, and for every number the sets' medians, each set's
quartile spread and range5, and the bounds the driver's check would take.
A file that holds one set only (a further set of a tree that has its two)
is shown against itself.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN_TIMEOUT_S = 1500


def one_run(tree: str, cell: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    try:
        out = subprocess.run(cmd, cwd=tree, capture_output=True, text=True,
                             timeout=RUN_TIMEOUT_S)
        rc, stdout, stderr = out.returncode, out.stdout, out.stderr
    except subprocess.TimeoutExpired as e:
        rc, stdout, stderr = 124, "", str(e.stderr or "")
    rec = {"cell": cell, "seed": seed, "seconds": seconds, "trace": trace,
           "rc": rc, "wall_s": time.time() - t0, "line": None, "notes": None}
    lines = stdout.strip().splitlines()
    if rc == 0 and lines:
        rec["line"] = json.loads(lines[-1])
    for text in stderr.splitlines():
        if "] notes: " in text:
            rec["notes"] = json.loads(text.split("] notes: ", 1)[1])
    if rec["line"] is None or not rec["line"].get("correct"):
        rec["stderr_tail"] = stderr[-6000:]
    return rec


def plan(args) -> list:
    """(label, tree, seed, seconds, trace) in the order they run."""
    ints = lambda text: [int(s) for s in text.split(",")] if text else []  # noqa: E731
    seeds = ints(args.seeds)
    runs = []
    if args.cold_seed is not None:
        runs.append(("cold", ROOT, args.cold_seed, args.seconds, 0))
    half = len(seeds) // 2
    for i, seed in enumerate(seeds):
        runs.append(("set1", ROOT, seed, args.seconds, 0))
        runs.append(("set2", ROOT, seeds[(i + half) % len(seeds)],
                     args.seconds, 0))
    runs += [("traced", ROOT, s, args.seconds, 1) for s in ints(args.trace_seeds)]
    if args.parent:
        tree = os.path.abspath(args.parent)
        runs += [("parent", tree, s, args.seconds, 0)
                 for s in ints(args.parent_seeds)]
    return runs


def run(args) -> int:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bad = 0
    for label, tree, seed, seconds, trace in plan(args):
        rec = dict(one_run(tree, args.cell, seed, seconds, trace), label=label)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        line = rec["line"] or {}
        ok = line.get("correct") is True and line.get("failed") == 0
        bad += not ok
        if label == "cold" and not ok:
            print((rec.get("stderr_tail") or "")[-3000:], flush=True)
            return 2  # nothing after it would run either
        print(f"{label} seed {seed} rc {rec['rc']} ok {ok} "
              f"{rec['wall_s']:.0f}s " + json.dumps(
                  {k: v["value"] for k, v in line.get("metrics", {}).items()}),
              flush=True)
    return 1 if bad else 0


def without_farthest(values: list) -> list:
    """The set without the run farthest from its median: one far-off run a
    set is carried, two are not."""
    med = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - med))[:-1] if len(
        values) > 2 else list(values)


def range5(values: list) -> float:
    """(max - min) / median of the set without its farthest run."""
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(values)


def iqr(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


CAP = 0.10  # the contract's: no bound is wider


def rule(set1: list, set2: list) -> dict:
    """The no-op test's readings, as the driver's check takes them.  A bound
    is too tight under ``tight`` (twice the mean of the sets' quartile
    spreads, each set without its farthest run) and too loose over ``loose``
    (eight times the spread of all the runs; 1 % never is).  ``bound`` is
    the contract's five times the widest set's spread, brought inside
    [max(tight, 1 %), min(loose, 10 %)]; None where that is empty: the
    number cannot be judged."""
    m1, m2 = statistics.median(set1), statistics.median(set2)
    spreads, pooled = [iqr(set1), iqr(set2)], iqr(set1 + set2)
    tight = 2 * statistics.mean(
        iqr(without_farthest(s)) for s in (set1, set2))
    low, high = max(tight, 0.01), min(max(8 * pooled, 0.01), CAP)
    return {"medians": [m1, m2], "medians_differ": abs(m2 - m1) / m1,
            "iqr_sets": spreads, "iqr_all": pooled,
            "range5": [range5(set1), range5(set2)],
            "tight": tight, "loose": 8 * pooled,
            "bound": min(max(5 * max(spreads), low), high)
            if low <= high else None}


def passes(readings: dict, bound: float) -> bool:
    """The no-op test at ``bound``: two sets of one tree would be let
    through by a check that holds this bound."""
    return (readings["medians_differ"] < bound <= CAP
            and readings["tight"] <= bound
            and (bound <= readings["loose"] or bound <= 0.01))


NOTED = ("gang_ready_s", "replica_ready_s", "stall_s", "ttft_p50_ms",
         "ttft_p90_ms", "ttft_p95_ms", "ttft_p97_ms", "itl_p50_ms",
         "itl_p95_ms", "requests_in_window")


def numbers(rec: dict) -> dict:
    """Every number of one run that a bound could be asked of: the line's
    metrics, and what the job's notes carry beside them."""
    out = {k: v["value"] for k, v in rec["line"]["metrics"].items()}
    notes = rec["notes"] or {}
    for key in NOTED:
        if notes.get(key) is not None:
            out.setdefault(key, notes[key])
    return out


def table(paths: list) -> int:
    for path in paths:
        with open(path) as f:
            recs = [json.loads(line) for line in f if line.strip()]
        print(f"== {path}")
        for rec in recs:
            if rec["line"] is None:
                print(f"{rec['label']} seed {rec['seed']} rc {rec['rc']} NO LINE")
                continue
            print(f"{rec['label']} seed {rec['seed']} trace {rec['trace']} "
                  f"correct {rec['line']['correct']} failed "
                  f"{rec['line']['failed']} " + json.dumps(
                      {k: round(v, 4) for k, v in numbers(rec).items()}
                      if not rec["trace"] else
                      {k: round(v["value"], 4)
                       for k, v in rec["line"]["metrics"].items()}))
        sets = {label: [numbers(r) for r in recs
                        if r["label"] == label and r["line"]]
                for label in ("set1", "set2")}
        if not sets["set1"]:
            continue
        if not sets["set2"]:
            sets["set2"] = sets["set1"]  # one set: its own spread, twice
        for key in sets["set1"][0]:
            one, two = ([n[key] for n in sets[s] if n.get(key) is not None]
                        for s in ("set1", "set2"))
            if len(one) < 3 or len(two) < 3 or not statistics.median(one):
                continue
            print(f"{key}: " + json.dumps(
                {k: [round(x, 5) for x in v] if isinstance(v, list)
                 else v if v is None else round(v, 5)
                 for k, v in rule(one, two).items()}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("run")
    r.add_argument("--cell", required=True)
    r.add_argument("--seeds", default="")
    r.add_argument("--seconds", type=float, default=45)
    r.add_argument("--cold-seed", type=int, default=None)
    r.add_argument("--trace-seeds", default="")
    r.add_argument("--parent", default="")
    r.add_argument("--parent-seeds", default="")
    r.add_argument("--out", required=True)
    t = sub.add_parser("table")
    t.add_argument("paths", nargs="+")
    args = ap.parse_args()
    return run(args) if args.what == "run" else table(args.paths)


if __name__ == "__main__":
    sys.exit(main())
