"""The program's wall-clock spans, read after the cluster is gone.

``ray_tpu.util.tracing.start_span`` / ``record_span`` write to the control
plane's store from every process of the cluster; ``ray_tpu.shutdown()`` of
the driver that started the head writes the store's span rows to
``<session's log directory>/spans.jsonl`` (first row: ``session``,
``dropped_spans``, ``spans``).  They cover what no profiler session can: a
request's way in and out of the engine (``serve.http.stream`` in the proxy's
process, ``serve.request.stream`` and ``engine.stream`` in the replica's) and
the start of a replica and of a gang (``serve.run`` / ``train.fit`` and
below), with every compilation as ``xla.compile`` in the process that made it.

Two clocks, one relation.  These spans are on the host's wall clock; the
traced window's host spans (``lib/host_spans``) are on the device trace's.
``engine.admit`` carries ``unix_ns`` (the wall clock at its entry) and the
request's ``trace_id``, so every admission of the traced window is one reading
of (wall clock - trace clock): their median is the offset, their spread (third
less first quartile) the clock check, and beyond ``CLOCK_LIMIT_MS`` every
request-path reader gives ``None``.  The same join picks the sample: a
request-path metric is taken over the requests ADMITTED IN THE TRACED WINDOW,
the same seconds every other per-layer number describes.

The run's file is found by name: the cell is ``host_spans.cell_name``'s, its
outputs are ``.bench_out/<cell>/`` (made anew when ``run.py`` starts), and the
session is THE one under ``tempfile.gettempdir()/ray_tpu/`` whose
``spans.jsonl`` was written after that directory was made and holds a start
(``serve.run`` / ``train.fit``): a run makes one session and writes the file
when it shuts down.  Two such files (another driver shut down in the same
temporary directory meanwhile) are nobody's, and every reader gives ``None``.
A context that carries ``cluster_spans`` (a test) is taken at its word.  A
missing file (the parent commit's program writes none) or ``dropped_spans >
0`` (the file has holes) gives ``None`` too.

From the root of the checkout:

  python3 -m benchmarks.lib.cluster_spans report <cell | spans.jsonl>
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import statistics
import tempfile
import types
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks.lib import host_spans
from benchmarks.lib import trace_reduce as tr

ROOT = host_spans.ROOT
FILE_NAME = "spans.jsonl"
CLOCK_LIMIT_MS = 2.0
ADMIT, HTTP, REPLICA, STREAM = (
    "engine.admit", "serve.http.stream", "serve.request.stream",
    "engine.stream")
START_ROOTS = ("serve.run", "train.fit")
Interval = Tuple[float, float]


@dataclasses.dataclass
class Row:
    name: str
    start: float  # s, the host's wall clock
    end: float
    trace_id: Optional[str]
    span_id: Optional[str]
    parent_id: Optional[str]
    worker_id: Optional[str]
    attrs: dict

    @property
    def duration_s(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ClusterTrace:
    session: str
    dropped_spans: int
    rows: List[Row]

    def named(self, name: str, where: Optional[dict] = None) -> List[Row]:
        """Rows called ``name`` whose attributes hold ``where``, by start."""
        return sorted(
            (r for r in self.rows if r.name == name and all(
                r.attrs.get(k) == v for k, v in (where or {}).items())),
            key=lambda r: r.start)

    def children(self, row: Row) -> List[Row]:
        return sorted((r for r in self.rows if r.parent_id == row.span_id
                       and r.trace_id == row.trace_id and r is not row),
                      key=lambda r: r.start)


def from_rows(rows: Iterable[dict]) -> ClusterTrace:
    """``rows``: the file's JSON objects, the first its head."""
    head, *spans = rows
    return ClusterTrace(
        session=str(head.get("session", "")),
        dropped_spans=int(head.get("dropped_spans", 0)),
        rows=[Row(name=r["name"], start=float(r["start"]),
                  end=float(r["end"]), trace_id=r.get("trace_id"),
                  span_id=r.get("span_id"), parent_id=r.get("parent_id"),
                  worker_id=r.get("worker_id"),
                  attrs=dict(r.get("attributes") or {})) for r in spans])


def parse(path: str) -> ClusterTrace:
    with open(path) as f:
        return from_rows(json.loads(line) for line in f if line.strip())


# ------------------------------------------------------ finding the file
def made_at(out_dir: str) -> float:
    """When ``out_dir`` was made, from above: the earliest modification time
    of it and of everything in it (nothing in it is older than it)."""
    times = [os.path.getmtime(out_dir)]
    for folder, _dirs, files in os.walk(out_dir):
        times.append(os.path.getmtime(folder))
        times.extend(os.path.getmtime(os.path.join(folder, f)) for f in files)
    return min(times)


def find(cell: str) -> Optional[str]:
    """The ``spans.jsonl`` of the cell's last run: the ONE written after
    ``.bench_out/<cell>/`` was made that holds a start (a run makes that
    directory, then one session, and writes the file when it shuts down,
    before the next run starts).  With two there is no telling whose is
    whose: ``None``."""
    out_dir = os.path.join(ROOT, ".bench_out", cell)
    if not os.path.isdir(out_dir):
        return None
    since = made_at(out_dir)
    after = [p for p in glob.glob(os.path.join(
        tempfile.gettempdir(), "ray_tpu", "session_*", FILE_NAME))
        if os.path.getmtime(p) >= since]
    if len(after) != 1:
        return None
    started = any(row.name in START_ROOTS for row in parse(after[0]).rows)
    return after[0] if started else None


def whole(trace: Optional[ClusterTrace]) -> Optional[ClusterTrace]:
    """A trace with holes is no trace."""
    return trace if trace is not None and not trace.dropped_spans else None


def for_ctx(ctx) -> Optional[ClusterTrace]:
    given = getattr(ctx, "cluster_spans", None)
    if given is not None:
        return whole(given)
    path = find(host_spans.cell_name(ctx.config, ctx.mix))
    return whole(parse(path)) if path else None


# ------------------------------------------------------- the two clocks
@dataclasses.dataclass
class Clock:
    offset_ns: int  # wall clock - the device trace's clock, median
    spread_ms: float  # third less first quartile of the readings
    range_ms: float  # largest less smallest
    readings: int

    @property
    def good(self) -> bool:
        return self.spread_ms <= CLOCK_LIMIT_MS

    def on_trace_ns(self, unix_s: float) -> float:
        return unix_s * 1e9 - self.offset_ns


def admissions(ctx) -> List[host_spans.Span]:
    """The traced window's ``engine.admit`` spans that carry the anchor."""
    return [s for s in host_spans.spans_named(ctx, ADMIT)
            if "unix_ns" in s.stats]


def clock(admits: List[host_spans.Span]) -> Optional[Clock]:
    offsets = sorted(int(s.stats["unix_ns"]) - s.start for s in admits)
    if not offsets:
        return None
    # From the smallest reading on: 1.7e18 ns is past what a float holds
    # to the nanosecond, the differences are not.
    base = offsets[0]
    near = [o - base for o in offsets]
    q1, _q2, q3 = (statistics.quantiles(near, n=4) if len(near) > 1
                   else (0, 0, 0))
    return Clock(offset_ns=base + int(statistics.median(near)),
                 spread_ms=(q3 - q1) / 1e6, range_ms=near[-1] / 1e6,
                 readings=len(offsets))


def window_trace_ids(ctx) -> Optional[List[str]]:
    """The cluster traces of the requests admitted in the traced window, in
    admission order; ``None`` when there is none or the clocks disagree."""
    admits = admissions(ctx)
    relation = clock(admits)
    if relation is None or not relation.good:
        return None
    return trace_ids_of(admits) or None


def trace_ids_of(admits: List[host_spans.Span]) -> List[str]:
    return [s.stats["trace_id"] for s in sorted(admits, key=lambda s: s.start)
            if s.stats.get("trace_id")]


def first_admission(ctx) -> Optional[float]:
    """The wall clock (s) at the traced window's first admission: what the
    replica's process did before it is set-up (a compilation between the
    warm-up and here makes the run not correct), what it did after the
    window is the benchmark's own checks."""
    admits = admissions(ctx)
    if not admits:
        return None
    return min(int(s.stats["unix_ns"]) for s in admits) / 1e9


# ------------------------------------------------- a request's way through
@dataclasses.dataclass
class Request:
    """One streamed request: the six points of its way to its first token
    (s, wall clock) and what its two ends counted."""
    trace_id: str
    proxy_in: float  # serve.http.stream opens: the proxy has the request
    replica_in: Optional[float]  # serve.request.stream: the handler's entry
    engine_in: float  # engine.stream: add_request, after the tokenizer
    admitted: float
    first_token: float  # the loop put it into the request's mailbox
    first_write: float  # the proxy's first resp.write returned
    http: Row
    stream: Row

    @property
    def last_write(self) -> float:
        """The proxy's last ``resp.write`` of a chunk returned."""
        return self.http.attrs.get("last_write_unix_ns", 0) / 1e9

    def legs_ms(self) -> Dict[str, float]:
        out = {
            "ingress": self.engine_in - self.proxy_in,
            "queue": self.admitted - self.engine_in,
            "prefill": self.first_token - self.admitted,
            "egress_first": self.first_write - self.first_token,
            "proxy_ttft": self.first_write - self.proxy_in,
        }
        if self.replica_in is not None:
            out["proxy_to_replica"] = self.replica_in - self.proxy_in
            out["replica_to_engine"] = self.engine_in - self.replica_in
        if self.last_write:
            # The stream as the client saw it, and how far behind the engine
            # the proxy's last chunk left (``engine.stream`` ends when the
            # replica's thread has taken the result): against
            # ``egress_first`` it says whether the proxy fell behind as the
            # stream went.
            out["first_to_last_write"] = self.last_write - self.first_write
            out["egress_last"] = self.last_write - self.stream.end
        return {k: v * 1e3 for k, v in out.items()}


def requests_of(trace: ClusterTrace, trace_ids: List[str]) -> List[Request]:
    """The requests among ``trace_ids`` whose two ends are both in the file
    and that got as far as a first write."""
    by_trace: Dict[str, Dict[str, Row]] = {}
    wanted = set(trace_ids)
    for row in trace.rows:
        if row.trace_id in wanted and row.name in (HTTP, REPLICA, STREAM):
            by_trace.setdefault(row.trace_id, {})[row.name] = row
    out = []
    for trace_id in trace_ids:
        rows = by_trace.get(trace_id, {})
        http, stream = rows.get(HTTP), rows.get(STREAM)
        if http is None or stream is None:
            continue
        points = (stream.attrs.get("admitted_unix_ns"),
                  stream.attrs.get("first_token_unix_ns"),
                  http.attrs.get("first_write_unix_ns"))
        if not all(points):
            continue
        replica = rows.get(REPLICA)
        out.append(Request(
            trace_id=trace_id, proxy_in=http.start,
            replica_in=replica.start if replica else None,
            engine_in=stream.start, admitted=points[0] / 1e9,
            first_token=points[1] / 1e9, first_write=points[2] / 1e9,
            http=http, stream=stream))
    return out


def window_requests(ctx) -> Optional[List[Request]]:
    trace = for_ctx(ctx)
    ids = window_trace_ids(ctx) if trace is not None else None
    if ids is None:
        return None
    return requests_of(trace, ids) or None


# ------------------------------------------------------------ intervals
def union_s(intervals: Iterable[Interval]) -> float:
    """Length of the union of ``(start, end)`` intervals, s."""
    return float(tr.total(tr.union(intervals)))


def self_s(trace: ClusterTrace, row: Row) -> float:
    """A span less what its children cover of it (a child may outlast its
    parent: ``serve.run`` returns before its replica is up)."""
    covered = union_s(
        (max(c.start, row.start), min(c.end, row.end))
        for c in trace.children(row)
        if c.end > row.start and c.start < row.end)
    return max(row.duration_s - covered, 0.0)


def process_of(trace: ClusterTrace, name: str,
               where: Optional[dict] = None) -> Optional[str]:
    """The ``worker_id`` of the process that recorded the first span
    ``name`` (with ``where``): processes are picked by what they did, never
    by trace."""
    rows = trace.named(name, where)
    return rows[0].worker_id if rows else None


# ------------------------------------------------------------- by hand
def tree_lines(trace: ClusterTrace, row: Row, t0: float, depth: int = 0,
               note: str = "") -> List[str]:
    attrs = {k: v for k, v in row.attrs.items()
             if k in ("program", "rung", "rank", "attempt", "deployment",
                      "workers", "bundles")}
    head = (f"{'  ' * depth}{row.name} +{row.start - t0:.3f}s "
            f"{row.duration_s:.3f}s self {self_s(trace, row):.3f}s"
            + (f" {json.dumps(attrs)}" if attrs else "") + note)
    return [head] + rows_lines(trace, trace.children(row), t0, depth + 1)


def rows_lines(trace: ClusterTrace, rows: List[Row], t0: float, depth: int,
               note: str = "", fold_over: int = 3) -> List[str]:
    """Each of ``rows`` as a tree, but more than ``fold_over`` childless
    rows of one name as one line (a build's ``xla.compile``s)."""
    by_name: Dict[str, List[Row]] = {}
    for row in rows:
        by_name.setdefault(row.name, []).append(row)
    folded = {n for n, same in by_name.items() if len(same) > fold_over
              and not any(trace.children(r) for r in same)}
    lines = []
    for name in sorted(folded):
        same = by_name[name]
        lines.append(
            f"{'  ' * depth}{name} x{len(same)} union "
            f"{union_s((r.start, r.end) for r in same):.3f}s sum "
            f"{sum(r.duration_s for r in same):.3f}s" + note)
    for row in rows:
        if row.name not in folded:
            lines.extend(tree_lines(trace, row, t0, depth, note))
    return lines


LONGEST = 3


def compile_lines(trace: ClusterTrace,
                  window_from: Optional[float] = None) -> List[str]:
    """Every process's ``xla.compile`` rows: their union, by ``event``, the
    ``LONGEST`` with the function each compiled, and (``window_from``: the
    traced window's first admission) how much of the union came after the
    window opened, which is no set-up."""
    lines = []
    labels = {}
    for row in trace.rows:
        if row.name == "llm.engine.build":
            labels[row.worker_id] = "replica"
        elif row.name in ("train.worker.loop", "train.worker.jax_init"):
            labels[row.worker_id] = f"gang rank {row.attrs.get('rank')}"
    by_proc: Dict[str, List[Row]] = {}
    for row in trace.named("xla.compile"):
        by_proc.setdefault(row.worker_id, []).append(row)
    for worker_id, rows in sorted(by_proc.items(),
                                  key=lambda kv: kv[1][0].start):
        events: Dict[str, List[float]] = {}
        for r in rows:
            events.setdefault(short_event(r), []).append(r.duration_s)
        union = union_s((r.start, r.end) for r in rows)
        late = (union - union_s((r.start, r.end) for r in rows
                                if r.end <= window_from)
                if window_from is not None else None)
        lines.append(
            f"  {labels.get(worker_id, 'process')} {str(worker_id)[:8]}: "
            f"union {union:.3f}s"
            + ("" if late is None else
               f" ({late:.3f}s of it after the traced window opened)")
            + "; " + "; ".join(
                f"{name} x{len(ds)} sum {sum(ds):.3f}s max {max(ds):.3f}s"
                for name, ds in sorted(events.items())))
        t0 = rows[0].start
        for r in sorted(rows, key=lambda r: -r.duration_s)[:LONGEST]:
            lines.append(
                f"    {r.duration_s:.3f}s +{r.start - t0:.3f}s "
                f"{short_event(r)} {r.attrs.get('fun_name', '')}")
    return lines


def short_event(row: Row) -> str:
    return str(row.attrs.get("event", "")).rsplit("/", 1)[-1]


def rank_lines(trace: ClusterTrace) -> List[str]:
    inits = sorted(trace.named("train.worker.jax_init"),
                   key=lambda r: r.attrs.get("rank", 0))
    if not inits:
        return []
    t0 = min(r.start for r in inits)
    lines = [f"  rank {r.attrs.get('rank')}: +{r.start - t0:.3f}s .. "
             f"+{r.end - t0:.3f}s ({r.duration_s:.3f}s)" + "".join(
                 f" {part} {float(r.attrs[part]):.3f}s" for part in
                 ("import_s", "initialize_s", "runtime_s") if part in r.attrs)
             for r in inits]
    together = union_s((r.start, r.end) for r in inits)
    lines.append(
        f"  extent {max(r.end for r in inits) - t0:.3f}s, union "
        f"{together:.3f}s, sum {sum(r.duration_s for r in inits):.3f}s: "
        + ("they overlap" if together < sum(r.duration_s for r in inits)
           - 1e-6 else "one after the other"))
    return lines


PREFILL_PROGRAM = "^jit_prefill_one"


def device_split_ms(requests: List[Request], relation: Clock,
                    chips: List[tr.ChipTrace]) -> Dict[str, List[float]]:
    """The ``prefill`` leg (admitted -> first token handed over) against the
    device's own record: how long the request's prefill program waited behind
    what the device had queued (the decode step in flight), how long it ran,
    and its end -> the first token in the mailbox (the sampler, the read, the
    hand-over).  A request takes the first run that starts after its admission
    and that no earlier admission took."""
    runs = sorted((start, start + dur) for chip in chips for _n, start, dur
                  in chip.matching(chip.modules, PREFILL_PROGRAM))
    out: Dict[str, List[float]] = {
        "behind the device's queue": [], "the program's run": [],
        "its end -> handed over": []}
    taken = 0
    for r in sorted(requests, key=lambda r: r.admitted):
        admitted = relation.on_trace_ns(r.admitted)
        while taken < len(runs) and runs[taken][0] < admitted:
            taken += 1
        if taken == len(runs):
            break
        start, end = runs[taken]
        taken += 1
        out["behind the device's queue"].append((start - admitted) / 1e6)
        out["the program's run"].append((end - start) / 1e6)
        out["its end -> handed over"].append(
            (relation.on_trace_ns(r.first_token) - end) / 1e6)
    return {k: v for k, v in out.items() if v}


def request_lines(trace: ClusterTrace, files) -> List[str]:
    ctx = types.SimpleNamespace(host_spans=files, cluster_spans=trace,
                                trace=object())
    admits = admissions(ctx)
    relation = clock(admits)
    if relation is None:
        return ["no traced window with anchored admissions: no legs"]
    lines = [f"clock: wall - trace = {relation.offset_ns} ns over "
             f"{relation.readings} admissions, spread "
             f"{relation.spread_ms:.3f} ms (range {relation.range_ms:.3f} ms,"
             f" limit {CLOCK_LIMIT_MS} ms): "
             + ("good" if relation.good else "NOT good: no metric")]
    ids = trace_ids_of(admits)
    requests = requests_of(trace, ids)
    chips = [c for f in files for c in f.chips]
    t0 = min((c.start for c in chips), default=relation.on_trace_ns(
        min((r.proxy_in for r in requests), default=0.0)))
    lines.append(f"{len(requests)} of {len(ids)} admitted requests have both "
                 "ends in the file; points in ms from the trace's start:")
    lines.append("  request_id: proxy_in replica_in engine_in admitted "
                 "first_token first_write last_write | chunks/writes "
                 "tokens/deltas")
    for r in requests:
        points = (r.proxy_in, r.replica_in, r.engine_in, r.admitted,
                  r.first_token, r.first_write, r.last_write or None)
        lines.append(f"  {r.stream.attrs.get('request_id')}: " + " ".join(
            "-" if p is None else
            f"{(relation.on_trace_ns(p) - t0) / 1e6:.2f}" for p in points)
            + f" | {r.http.attrs.get('chunks')}/{r.http.attrs.get('writes')}"
            f" {r.stream.attrs.get('tokens')}/{r.stream.attrs.get('deltas')}")
    legs: Dict[str, List[float]] = {}
    for r in requests:
        for name, ms in r.legs_ms().items():
            legs.setdefault(name, []).append(ms)
    for name, values in legs.items():
        lines.append(f"  leg {name} (ms): " + host_spans.summary(values))
    for name, values in device_split_ms(requests, relation, chips).items():
        lines.append(f"  of prefill, {name} (ms): "
                     + host_spans.summary(values))
    # Inside ``ingress``, as the two processes timed their own part.
    replicas = {r.trace_id: r for r in trace.named(REPLICA)}
    for name, values in (
            ("route_ms (proxy: entry -> handle.remote returned)",
             [r.http.attrs.get("route_ms") for r in requests]),
            ("sem_wait_ms (replica: the user semaphore)",
             [replicas[r.trace_id].attrs.get("sem_wait_ms")
              for r in requests if r.trace_id in replicas])):
        lines.append(f"  {name}: " + host_spans.summary(
            [float(v) for v in values if v is not None]))
    return lines


def report(target: str) -> str:
    if os.path.isfile(target):
        path, trace_dir = target, None
    else:
        path = find(target)
        trace_dir = os.path.join(ROOT, ".bench_out", target, "trace")
    if path is None:
        return f"no {FILE_NAME} was written after .bench_out/{target}/"
    trace = parse(path)
    lines = [f"{path}: session {trace.session}, {len(trace.rows)} spans, "
             f"dropped {trace.dropped_spans}"
             + (" (HOLES: no metric is read from it)"
                if trace.dropped_spans else "")]
    known = {r.span_id for r in trace.rows}
    for root in START_ROOTS:
        for row in trace.named(root):
            lines.extend(tree_lines(trace, row, row.start))
            # A span whose parent died with its process before a pull (a
            # gang's ``task:run`` ends as the gang is killed).
            lines.extend(rows_lines(
                trace, [r for r in trace.rows if r.trace_id == row.trace_id
                        and r is not row and r.parent_id not in known],
                row.start, 1, note=" (its parent is not in the file)"))
    files = []
    if trace_dir and os.path.isdir(trace_dir):
        files = [f for f in map(host_spans.load_file,
                                tr.find_traces(trace_dir)) if f.chips]
    compiled = compile_lines(trace, first_admission(
        types.SimpleNamespace(host_spans=files, trace=object())))
    if compiled:
        lines.append("xla.compile by process:")
        lines.extend(compiled)
    ranks = rank_lines(trace)
    if ranks:
        lines.append("train.worker.jax_init by rank:")
        lines.extend(ranks)
    if files:
        lines.extend(request_lines(trace, files))
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    if len(sys.argv) != 3 or sys.argv[1] != "report":
        sys.exit(__doc__)
    print(report(sys.argv[2]))
