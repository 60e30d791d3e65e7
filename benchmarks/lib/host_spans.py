"""The program's host spans, on the device trace's clock.

``ray_tpu.util.tracing.host_span`` is ``jax.profiler.TraceAnnotation``: while
the benchmark's profiler session runs, each span lands in plane ``/host:CPU``
of the same ``.xplane.pb`` that holds the device's operations, one line per
thread, with its keyword attributes as the event's ``stats``.  That file is
the shared clock: a host span's ``start_ns`` and a device operation's are on
one time base, so "which host work fills an idle gap" is an intersection of
intervals, not an inference from the names of the programs beside the gap.

``ReadContext`` carries no trace directory, so ``for_ctx`` resolves it: the
cell is the one entry of ``BENCHMARK.json`` ``workloads`` whose ``config`` and
``traffic`` are this run's, and its trace is ``.bench_out/<cell>/trace/``,
the files ``Trace.from_dir`` read.  A context that already carries
``host_spans`` (a test) is taken at its word.  A program without the spans
(the parent commit) gives empty lists, and every reader returns ``None``.

From the root of the checkout:

  python3 -m benchmarks.lib.host_spans report <trace dir>   # spans, idle by span, clock check
  python3 -m benchmarks.lib.host_spans cut <in.xplane.pb> <out.json> <start ms> <length ms>
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks.lib import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HOST_PLANE = "/host:CPU"
# The program's spans are named <layer>.<what>; the plane's other events
# (XLA's own TraceMes) are not read.
PROGRAM_SPANS = ("engine.", "train.")
Interval = Tuple[int, int]


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns, the trace file's time base
    end: int
    stats: dict
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def duration_ns(self) -> int:
        return self.end - self.start

    def descendants(self) -> Iterable["Span"]:
        for child in self.children:
            yield child
            yield from child.descendants()


def nest(spans: List[Span]) -> List[Span]:
    """Spans of ONE thread -> its top-level spans, each with the spans that
    lie inside it as ``children`` (annotations are a per-thread stack, so
    containment is parenthood).  Equal starts: the longer is the parent."""
    roots, stack = [], []
    for span in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end < span.end:
            stack.pop()
        (stack[-1].children if stack else roots).append(span)
        stack.append(span)
    return roots


@dataclasses.dataclass
class FileTrace:
    """One trace file = one traced process: its host threads, its chips."""
    threads: List[List[Span]]  # top-level spans per thread, nested
    chips: List[tr.ChipTrace]

    def spans(self, name: Optional[str] = None) -> List[Span]:
        out = []
        for roots in self.threads:
            for root in roots:
                out.extend(s for s in (root, *root.descendants())
                           if name is None or s.name == name)
        return out

    def covered(self, name: str) -> List[Interval]:
        """Merged intervals in which some thread is inside a span ``name``."""
        return tr.union((s.start, s.end) for s in self.spans(name))


def idle_gaps(chip: tr.ChipTrace) -> List[Interval]:
    """The chip's traced window less the time an operation ran on it."""
    return tr.subtract([(chip.start, chip.end)], chip.busy)


def intersect_ns(a: List[Interval], b: List[Interval]) -> int:
    """Length of the part of merged ``a`` that merged ``b`` covers."""
    return tr.total(a) - tr.total(tr.subtract(a, b))


def from_planes(host: List[List[list]], device: dict) -> FileTrace:
    """``host``: per thread, events ``[name, start_ns, duration_ns, stats]``;
    ``device``: what ``trace_reduce.load`` gives."""
    threads = [nest([Span(n, int(s), int(s) + int(d), dict(st))
                     for n, s, d, st in events])
               for events in host]
    chips = [tr.ChipTrace(plane, {k: [tuple(ev) for ev in v]
                                  for k, v in lines.items()})
             for plane, lines in sorted(device.items())
             if lines.get(tr.OPS_LINE)]
    return FileTrace(threads, chips)


def load_host(path: str) -> List[List[list]]:
    """The program's spans in one file's host plane, per thread."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                       dict(ev.stats)]
                      for ev in line.events
                      if ev.name.startswith(PROGRAM_SPANS)]
            if events:
                threads.append(events)
    return threads


@functools.lru_cache(maxsize=8)
def load_file(path: str) -> FileTrace:
    return from_planes(load_host(path), tr.load(path))


def cell_name(config: dict, mix: dict) -> str:
    """The cell that runs this configuration under this traffic."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    found = [w["name"] for w in cells
             if w["config"] == config["name"] and w["traffic"] == mix["name"]]
    if len(found) != 1:
        raise LookupError(f"{len(found)} cells run {config['name']!r} under "
                          f"{mix['name']!r}: {found}")
    return found[0]


def trace_dir(ctx) -> str:
    return os.path.join(ROOT, ".bench_out", cell_name(ctx.config, ctx.mix),
                        "trace")


def for_ctx(ctx) -> List[FileTrace]:
    """The traced processes of this run that hold a chip, with their spans."""
    given = getattr(ctx, "host_spans", None)
    if given is not None:
        return given
    if ctx.trace is None:
        return []
    files = [load_file(p) for p in tr.find_traces(trace_dir(ctx))]
    return [f for f in files if f.chips]


def spans_named(ctx, name: str) -> List[Span]:
    return [s for f in for_ctx(ctx) for s in f.spans(name)]


def idle_pct(files: List[FileTrace], name: str, inside: bool) -> Optional[float]:
    """Device-idle time inside spans ``name`` (``inside``) or while no thread
    is in one, as a share of the traced window, mean over chips, in %: the
    same window and the same idle time as ``device_idle_pct``.  ``None`` when
    no file holds such a span."""
    shares = []
    for f in files:
        covered = f.covered(name)
        if not covered:
            continue
        for chip in f.chips:
            gaps = idle_gaps(chip)
            ns = intersect_ns(gaps, covered)
            if not inside:
                ns = tr.total(gaps) - ns
            shares.append(100.0 * ns / chip.window_ns)
    return statistics.fmean(shares) if shares else None


# ------------------------------------------------------------ by hand
def idle_by_span(f: FileTrace, chip: tr.ChipTrace, step: str) -> dict:
    """Where the chip's idle time lies (s): by the name of each direct child
    of a ``step`` span, inside a step but in no child, and outside every
    step.  Children of one step never overlap (one thread), and steps of
    different threads do not either where a lock serialises them; where they
    do, a gap is counted under each."""
    gaps = idle_gaps(chip)
    out: Dict[str, float] = {}
    steps = f.spans(step)
    in_child = 0
    for s in steps:
        for child in s.children:
            ns = intersect_ns(gaps, [(child.start, child.end)])
            out[child.name] = out.get(child.name, 0.0) + ns / 1e9
            in_child += ns
    in_step = intersect_ns(gaps, f.covered(step))
    out["(in a step, in no child)"] = (in_step - in_child) / 1e9
    out["(outside every step)"] = (tr.total(gaps) - in_step) / 1e9
    out["(all idle)"] = tr.total(gaps) / 1e9
    out["(window)"] = chip.window_ns / 1e9
    return out


def dispatch_lags_ms(f: FileTrace, chip: tr.ChipTrace, span: str,
                     module: str) -> List[float]:
    """For each span ``span``, the signed lag (ms) from its start to the
    nearest start of a run of the program matching ``module``.  The span
    opens before the program is dispatched, so on one clock no lag is
    negative."""
    starts = [s for _n, s, _d in chip.matching(chip.modules, module)]
    if not starts:
        return []
    return [min((s - sp.start for s in starts), key=abs) / 1e6
            for sp in f.spans(span)]


def tail_margins_ms(f: FileTrace, chip: tr.ChipTrace, span: str,
                    module: str) -> List[float]:
    """For each span ``span`` in which a run of ``module`` starts, the time
    (ms) from the end of the last such run to the span's end.  The span's
    host code waits for the result, so on one clock none is negative."""
    runs = [(s, s + d) for _n, s, d in chip.matching(chip.modules, module)]
    out = []
    for sp in f.spans(span):
        ends = [e for s, e in runs if sp.start <= s < sp.end]
        if ends:
            out.append((sp.end - max(ends)) / 1e6)
    return out


def summary(values: List[float]) -> str:
    if not values:
        return "none"
    return (f"n={len(values)} min={min(values):.3f} "
            f"median={statistics.median(values):.3f} max={max(values):.3f}")


def report(root: str) -> str:
    rows = []
    for path in tr.find_traces(root):
        f = load_file(path)
        names: Dict[str, List[int]] = {}
        for s in f.spans():
            names.setdefault(s.name, []).append(s.duration_ns)
        rows.append(f"{path}: {len(f.threads)} thread(s) with spans, "
                    f"{len(f.chips)} chip(s)")
        for name, ds in sorted(names.items()):
            rows.append(f"  span {name}: n={len(ds)} total={sum(ds) / 1e9:.4f}s"
                        f" median={statistics.median(ds) / 1e6:.3f}ms")
        for chip in f.chips:
            for step in ("engine.step", "train.report"):
                if f.spans(step):
                    rows.append(f"  {chip.plane} idle by child of {step} (s): "
                                + json.dumps({k: round(v, 4) for k, v in
                                              idle_by_span(f, chip, step).items()}))
            rows.append("  lag engine.decode.dispatch start -> decode program "
                        "start (ms): " + summary(dispatch_lags_ms(
                            f, chip, "engine.decode.dispatch", "^jit__lambda")))
            rows.append("  engine.sample end - end of last jit_sample_logits "
                        "inside it (ms): " + summary(tail_margins_ms(
                            f, chip, "engine.sample", "^jit_sample_logits")))
    return "\n".join(rows)


def cut_sample(path: str, start_ms: float, length_ms: float) -> dict:
    """A slice of a recorded trace for the tests: the program's spans that
    lie wholly inside it, the device's program runs, and the device's busy
    intervals as merged ``busy`` operations; times from the slice's start."""
    device = tr.load(path)
    t0 = min(s for lines in device.values() for evs in lines.values()
             for _n, s, _d in evs)
    lo = t0 + int(start_ms * 1e6)
    hi = lo + int(length_ms * 1e6)
    out_dev = {}
    for plane, lines in device.items():
        busy = tr.union(tr.spans(lines.get(tr.OPS_LINE, [])))
        out_dev[plane] = {
            tr.OPS_LINE: [["busy", max(s, lo) - lo, min(e, hi) - max(s, lo)]
                          for s, e in busy if s < hi and e > lo],
            tr.MODULES_LINE: [[n, s - lo, d] for n, s, d in
                              lines.get(tr.MODULES_LINE, [])
                              if s >= lo and s + d <= hi]}
    host = [[[n, s - lo, d, st] for n, s, d, st in events
             if s >= lo and s + d <= hi] for events in load_host(path)]
    return {"host": [t for t in host if t], "device": out_dev}


if __name__ == "__main__":
    import sys

    if sys.argv[1] == "report":
        print(report(sys.argv[2]))
    else:
        src, dst, start, length = sys.argv[2:6]
        with open(dst, "w") as fh:
            json.dump(cut_sample(src, float(start), float(length)), fh)
