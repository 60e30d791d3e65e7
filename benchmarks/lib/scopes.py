"""A program's device time by the part of the model it belongs to.

The trace's ``XLA Ops`` line names every event by its HLO instruction
(``fusion.556``); which ``jax.named_scope`` the instruction came from is its
``op_name``, which the v5e's ``.xplane.pb`` leaves out.  The program writes
it beside the trace: ``ray_tpu.util.tracing.stop_profile`` leaves
``programs.jsonl`` under the directory ``start_profile`` was given, a row for
every compiled program alive in the traced process: ``{"module", "fingerprint",
"ops": {instruction: op_name}}`` (``""`` for an instruction the compiler made
and gave no name).  This module joins the two:

* each ``XLA Ops`` event belongs to the ``XLA Modules`` run that contains it
  on its chip, and counts with its SELF time (``trace_reduce.self_times``;
  the wrappers ``while`` / ``conditional`` / ``call`` hold none);
* the run's program is looked up by its module name (``jit__lambda(123)`` ->
  ``jit__lambda``).  The number in the parenthesis is a fingerprint the
  executable does not offer (checked on the v5e, PERF.md section 3), so where
  several rows carry the name (the rungs of ``jit_prefill_one``) an
  instruction's ``op_name`` is what the rows that have it AGREE on; where they
  disagree, what the rows agree on whose instruction names cover all of the
  run's events; where that does not decide, the event's time is *unresolved*.
  An instruction in no row is unresolved too; a trace with no
  ``programs.jsonl`` (a program from before PR 58) is unresolved whole;
* an ``op_name``'s scope is the innermost ``<family>.<part>`` in it, ``part``
  one of ``PARTS``; a fusion carries its ROOT's ``op_name``, so a fusion
  across two parts is booked to the part of its root.

Per program: runs, the runs' durations, and self time by ``op_name`` and by
instruction.  A program's rows (every scope, unscoped, unresolved) add up to
the sum of its events' self times exactly; that sum is less than the runs'
durations by the time between operations inside a run.

``for_ctx(ctx)`` finds the cell's trace directory as ``host_spans.trace_dir``
does (and reads the files through ``host_spans.load_file``, which the span
readers have loaded already); a context that carries ``scope_join`` (a test)
is taken at its word.  From the root of the checkout:

  python3 -m benchmarks.lib.scopes report <trace dir>

prints, for each program: runs, ms a run (mean and median of the runs, and
the sum of self times), a row a scope (ms a run, %, its three largest
instructions with their full ``op_name``), the unscoped and the unresolved
rows; then every idle gap of the device over 10 ms with the programs on both
sides and the innermost host span open when it began.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks.lib import host_spans
from benchmarks.lib import trace_reduce as tr

TABLES = "programs.jsonl"
PARTS = ("attn_full", "attn_window", "attn", "mla", "moe", "shared", "mlp",
         "ffn", "mamba", "delta", "head", "embed")
# <family>.<part> as one component of an op_name: between "/" or the
# parentheses of jvp(...) / transpose(jvp(...)).
PART = re.compile(r"(?<![\w.])(\w+\.(?:" + "|".join(PARTS) + r"))(?![\w.])")
UNSCOPED, UNRESOLVED = "(unscoped)", "(unresolved)"
MAX_UNRESOLVED = 0.01  # of a program's self time; over it a metric is None
GAP_NS = 10_000_000
Tables = Dict[str, List[Dict[str, str]]]  # module name -> each row's ``ops``


def instruction(event_name: str) -> str:
    """``trace_reduce.short_name`` marks a Pallas kernel's event
    (``fusion.3 tpu_custom_call``); the tables have the instruction alone."""
    return event_name.split(" ", 1)[0]


def scope_of(op_name: Optional[str]) -> str:
    if op_name is None:
        return UNRESOLVED
    found = PART.findall(op_name)
    return found[-1] if found else UNSCOPED


@dataclasses.dataclass
class Program:
    """One module name's runs, all chips and files of a trace directory."""
    name: str
    run_ns: List[int] = dataclasses.field(default_factory=list)
    # (instruction, op_name or None = unresolved) -> self time, ns
    by_instruction: Dict[Tuple[str, Optional[str]], int] = dataclasses.field(
        default_factory=dict)

    @property
    def self_ns(self) -> int:
        return sum(self.by_instruction.values())

    @property
    def unresolved_ns(self) -> int:
        return sum(ns for (_i, op_name), ns in self.by_instruction.items()
                   if op_name is None)

    def matching_ns(self, pattern: str) -> int:
        """Self time of the instructions whose ``op_name`` ``pattern``
        finds."""
        rx = re.compile(pattern)
        return sum(ns for (_i, op_name), ns in self.by_instruction.items()
                   if op_name is not None and rx.search(op_name))

    def by_scope(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for (_i, op_name), ns in self.by_instruction.items():
            scope = scope_of(op_name)
            out[scope] = out.get(scope, 0) + ns
        return out


def read_tables(path: str) -> Tables:
    out: Tables = {}
    with open(path) as f:
        for line in f:
            if line.strip():
                row = json.loads(line)
                out.setdefault(row["module"], []).append(row["ops"])
    return out


def tables_beside(trace_file: str, root: str) -> Tables:
    """The tables of the process that wrote ``trace_file``: the first
    ``programs.jsonl`` from the file's directory up to ``root`` (the profiler
    writes ``<path>/plugins/profile/<time>/*.xplane.pb``, ``stop_profile``
    ``<path>/programs.jsonl``)."""
    here = os.path.dirname(os.path.abspath(trace_file))
    root = os.path.abspath(root)
    while True:
        if os.path.exists(os.path.join(here, TABLES)):
            return read_tables(os.path.join(here, TABLES))
        if here == root or os.path.dirname(here) == here:
            return {}
        here = os.path.dirname(here)


def resolve(names: frozenset,
            rows: List[Dict[str, str]]) -> Dict[str, Optional[str]]:
    """{instruction: ``op_name``, or ``None`` where the rows do not say} for
    one run's event names and the rows that carry its module name."""
    covering = [ops for ops in rows if names <= ops.keys()]
    out = {}
    for name in names:
        said = {ops[name] for ops in rows if name in ops}
        if len(said) > 1:  # the rungs disagree: those that can be this run
            said = {ops[name] for ops in covering}
        out[name] = said.pop() if len(said) == 1 else None
    return out


def add_chip(programs: Dict[str, Program], chip: tr.ChipTrace,
             tables: Tables) -> None:
    """One chip's events into ``programs``."""
    runs = sorted(chip.modules, key=lambda ev: ev[1])
    starts = [s for _n, s, _d in runs]
    inside: List[List[tr.Event]] = [[] for _ in runs]
    stray: List[tr.Event] = []
    for ev in chip.ops:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < runs[i][1] + runs[i][2]:
            inside[i].append(ev)
        else:
            stray.append(ev)
    resolved: Dict[Tuple[str, frozenset], Dict[str, Optional[str]]] = {}
    for (name, _s, dur), events in zip(runs, inside):
        module = tr.base_name(name)
        program = programs.setdefault(module, Program(module))
        program.run_ns.append(dur)
        key = (module, frozenset(instruction(n) for n, _s, _d in events))
        if key not in resolved:
            resolved[key] = resolve(key[1], tables.get(module, []))
        book(program, events, resolved[key])
    if stray:  # events in no program's run: the report shows them
        book(programs.setdefault("(no run)", Program("(no run)")), stray, {})


def book(program: Program, events: List[tr.Event],
         op_names: Dict[str, Optional[str]]) -> None:
    for name, own in tr.self_times(events):
        if own and not tr.WRAPPERS.match(name):
            key = (name, op_names.get(instruction(name)))
            program.by_instruction[key] = (
                program.by_instruction.get(key, 0) + own)


def join(files: List[Tuple[host_spans.FileTrace, Tables]]
         ) -> Dict[str, Program]:
    """(a traced process, its tables) for each -> {module name: Program}."""
    programs: Dict[str, Program] = {}
    for f, tables in files:
        for chip in f.chips:
            add_chip(programs, chip, tables)
    return programs


@functools.lru_cache(maxsize=2)  # a cell's readers share one join
def load(root: str) -> Dict[str, Program]:
    return join([(host_spans.load_file(path), tables_beside(path, root))
                 for path in tr.find_traces(root)])


def for_ctx(ctx) -> Optional[Dict[str, Program]]:
    given = getattr(ctx, "scope_join", None)
    if given is not None:
        return given
    if ctx.trace is None:
        return None
    return load(host_spans.trace_dir(ctx))


def matching(programs: Dict[str, Program], per_module: str) -> List[Program]:
    rx = re.compile(per_module)
    return [p for name, p in sorted(programs.items()) if rx.search(name)]


def stat(programs: Dict[str, Program], per_module: str, scope: str,
         what: str) -> Optional[float]:
    """Over the programs ``per_module`` finds: ``ms`` = self time of the
    instructions whose ``op_name`` ``scope`` finds, per run; ``pct_outside``
    = the share (%) of the programs' self time it does NOT find.  ``None``
    with no run, no such instruction (``ms``), or more than
    ``MAX_UNRESOLVED`` of the self time unresolved."""
    if what not in ("ms", "pct_outside"):
        raise ValueError(f"stat {what!r}: ms or pct_outside")
    found = matching(programs, per_module)
    runs = sum(len(p.run_ns) for p in found)
    whole = sum(p.self_ns for p in found)
    if not runs or not whole:
        return None
    if sum(p.unresolved_ns for p in found) > MAX_UNRESOLVED * whole:
        return None
    inside = sum(p.matching_ns(scope) for p in found)
    if what == "pct_outside":
        return 100.0 * (whole - inside) / whole
    return inside / runs / 1e6 if inside else None


# ------------------------------------------------------------ by hand
def report_program(p: Program, top: int = 3) -> List[str]:
    runs, whole = len(p.run_ns), p.self_ns
    a_run = max(runs, 1) * 1e6  # ns of all runs -> ms a run
    head = f"program {p.name}: runs={runs}"
    if runs:
        head += (f" run mean={statistics.fmean(p.run_ns) / 1e6:.4f}ms"
                 f" median={statistics.median(p.run_ns) / 1e6:.4f}ms")
    head += f" self={whole / a_run:.4f}ms a run"
    if sum(p.run_ns):
        head += f" ({100.0 * whole / sum(p.run_ns):.2f}% of the runs)"
    rows, by_scope = [head], p.by_scope()
    last = (UNSCOPED, UNRESOLVED)  # after the scopes, largest first
    for scope in sorted(by_scope, key=lambda s: (
            last.index(s) + 1 if s in last else 0, -by_scope[s])):
        ns = by_scope[scope]
        rows.append(f"  {scope}: {ns / a_run:.4f}ms a run "
                    f"{100.0 * ns / whole:.2f}%")
        largest = sorted(((n, key) for key, n in p.by_instruction.items()
                          if scope_of(key[1]) == scope), reverse=True,
                         key=lambda kv: kv[0])[:top]
        for n, (instruction_name, op_name) in largest:
            rows.append(f"    {n / a_run:.4f}ms {instruction_name}: "
                        f"{op_name if op_name is not None else '?'}")
    if whole:
        rows.append("  (sum of the rows): "
                    f"{sum(by_scope.values()) / a_run:.4f}ms a run")
    return rows


def innermost(f: host_spans.FileTrace, at: int) -> str:
    """The spans open at ``at`` on each thread that has one, outermost
    first: ``engine.step > engine.sample``."""
    paths = []
    for roots in f.threads:
        path, level = [], roots
        while True:
            inside = [s for s in level if s.start <= at < s.end]
            if not inside:
                break
            path.append(inside[0].name)
            level = inside[0].children
        if path:
            paths.append(" > ".join(path))
    return "; ".join(paths) if paths else "(no span)"


def report_gaps(f: host_spans.FileTrace, chip: tr.ChipTrace) -> List[str]:
    runs = sorted(chip.modules, key=lambda ev: ev[1])
    starts = [s for _n, s, _d in runs]

    def module_at(t: int) -> str:  # the last program started by then
        i = bisect.bisect_right(starts, t) - 1
        return tr.base_name(runs[i][0]) if i >= 0 else "?"

    rows = []
    for (_s0, e0), (s1, _e1) in zip(chip.busy, chip.busy[1:]):
        if s1 - e0 >= GAP_NS:
            rows.append(f"  {chip.plane} idle {(s1 - e0) / 1e6:.3f}ms at "
                        f"{(e0 - chip.start) / 1e6:.3f}ms: "
                        f"{module_at(e0 - 1)} -> {module_at(s1)}; host: "
                        f"{innermost(f, e0)}")
    return rows


def report(root: str) -> str:
    rows = []
    programs = load(root)
    for name in sorted(programs, key=lambda n: -programs[n].self_ns):
        rows.extend(report_program(programs[name]))
    for path in tr.find_traces(root):
        f = host_spans.load_file(path)
        rows.append(f"{path}: idle gaps of {GAP_NS / 1e6:.0f} ms and more")
        for chip in f.chips:
            rows.extend(report_gaps(f, chip) or [f"  {chip.plane}: none"])
    return "\n".join(rows)


if __name__ == "__main__":
    import sys

    if len(sys.argv) != 3 or sys.argv[1] != "report":
        sys.exit(__doc__)
    print(report(sys.argv[2]))
