"""From a profiler trace (``.xplane.pb``) to numbers.

Kept with the benchmark so that every PR computes the same number the same
way.  ``jax.profiler.ProfileData`` parses the file with nothing but jax and
touches no device.  A chip is a plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation run (nested: a ``while`` spans its
body), ``XLA Modules`` one per program run.  Times are kept in nanoseconds
as integers until the last division.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# Operations that only wrap others; their own time is their children's.
WRAPPERS = re.compile(r"^(while|conditional|call)([.\d]*)$")
_SUFFIX = re.compile(r"(\(\d+\)|(?<=\D)[.\d]+)(?= |$)")


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%fusion.3 = bf16[..] fusion(...), custom_call_target="tpu_custom_call"``.
    Keep the operation's own name, and say when it is a Pallas kernel."""
    if not name.startswith("%"):
        return name
    short = name[1:].split(" = ", 1)[0]
    return short + " tpu_custom_call" if "tpu_custom_call" in name else short


def find_traces(root: str) -> List[str]:
    return sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True))


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: events}} for the device planes of one file."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.setdefault(line.name, []).extend(
                (short_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]):
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def spans(events: Iterable[Event]) -> List[Tuple[int, int]]:
    return [(s, s + d) for _n, s, d in events]


def self_times(events: List[Event]) -> List[Tuple[str, int]]:
    """(name, duration minus the part its nested events cover)."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []  # stack of [name, end, self]
    for name, start, dur in order:
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out.extend((name, own) for name, _end, own in stack)
    return out


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``jit_step(7)`` -> ``jit_step``;
    ``checkpoint.21 tpu_custom_call`` -> ``checkpoint tpu_custom_call``."""
    return _SUFFIX.sub("", name)


class ChipTrace:
    def __init__(self, plane: str, lines: Dict[str, List[Event]]):
        self.plane = plane
        self.ops: List[Event] = lines.get(OPS_LINE, [])
        self.modules: List[Event] = lines.get(MODULES_LINE, [])
        every = self.ops + self.modules
        self.start = min((s for _n, s, _d in every), default=0)
        self.end = max((s + d for _n, s, d in every), default=0)
        self.busy = union(spans(self.ops))

    @property
    def window_ns(self) -> int:
        return self.end - self.start

    @property
    def busy_ns(self) -> int:
        return total(self.busy)

    def matching(self, events: List[Event], pattern: str) -> List[Event]:
        rx = re.compile(pattern)
        return [ev for ev in events if rx.search(ev[0])]

    def exposed_ns(self, pattern: str) -> int:
        """Time in operations matching ``pattern`` while no other operation
        (wrappers aside) runs on this chip."""
        rx = re.compile(pattern)
        mine = union(spans(ev for ev in self.ops if rx.search(ev[0])))
        others = union(spans(
            ev for ev in self.ops
            if not rx.search(ev[0]) and not WRAPPERS.match(ev[0])))
        return total(subtract(mine, others))

    def idle_gaps(self) -> List[Tuple[str, int]]:
        """(what ran before -> what ran after, ns) for every gap between
        busy intervals, named by the programs on either side."""
        mods = sorted(self.modules, key=lambda ev: ev[1])
        starts = [s for _n, s, _d in mods]

        def module_at(t: int) -> str:  # the last program started by then
            i = bisect.bisect_right(starts, t) - 1
            return base_name(mods[i][0]) if i >= 0 else "?"

        return [(f"{module_at(e0 - 1)} -> {module_at(s1)}", s1 - e0)
                for (_s0, e0), (s1, _e1) in zip(self.busy, self.busy[1:])]


class Trace:
    """Every chip of one run's trace directory."""

    def __init__(self, chips: List[ChipTrace]):
        self.chips = chips

    @classmethod
    def from_planes(cls, planes: Dict[str, Dict[str, List[Event]]]) -> "Trace":
        return cls([ChipTrace(name, {k: [tuple(ev) for ev in v]
                                     for k, v in lines.items()})
                    for name, lines in sorted(planes.items())])

    @classmethod
    def from_dir(cls, root: str) -> Optional["Trace"]:
        chips = []
        for path in find_traces(root):
            for plane, lines in sorted(load(path).items()):
                if lines.get(OPS_LINE):
                    chips.append(ChipTrace(plane, lines))
        return cls(chips) if chips else None

    # Averages over the chips used, in seconds.
    @property
    def busy_s(self) -> float:
        return statistics.fmean(c.busy_ns for c in self.chips) / 1e9

    @property
    def window_s(self) -> float:
        return statistics.fmean(c.window_ns for c in self.chips) / 1e9

    def module_runs(self, pattern: str) -> List[int]:
        """Durations (ns) of every run of the programs matching ``pattern``,
        all chips."""
        return [d for c in self.chips
                for _n, _s, d in c.matching(c.modules, pattern)]

    def module_names(self, pattern: str) -> set:
        """Distinct programs matching ``pattern`` (run numbers dropped)."""
        return {base_name(n) for c in self.chips
                for n, _s, _d in c.matching(c.modules, pattern)}

    def op_ns_per_chip(self, pattern: str) -> float:
        """Device time in operations matching ``pattern``, mean over chips."""
        return statistics.fmean(
            sum(d for _n, _s, d in c.matching(c.ops, pattern))
            for c in self.chips)

    def breakdown(self, top: int = 10) -> dict:
        ops: Dict[str, int] = {}
        gaps: Dict[str, int] = {}
        for c in self.chips:
            for name, own in self_times(c.ops):
                if not WRAPPERS.match(name):
                    ops[base_name(name)] = ops.get(base_name(name), 0) + own
            for name, ns in c.idle_gaps():
                gaps[name] = gaps.get(name, 0) + ns
        n = len(self.chips)

        def first(table):
            rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
            return [[name, ns / n / 1e9] for name, ns in rows]

        return {"device_ops": first(ops), "idle_gaps": first(gaps)}

    def describe(self, limit: int = 40) -> str:
        """What a person looks at first: planes, counts, the biggest names."""
        rows = []
        for c in self.chips:
            rows.append(f"{c.plane}: {len(c.ops)} ops, {len(c.modules)} "
                        f"module runs, window {c.window_ns / 1e9:.4f}s, "
                        f"busy {c.busy_ns / 1e9:.4f}s")
            mods: Dict[str, List[int]] = {}
            for name, _s, d in c.modules:
                mods.setdefault(base_name(name), []).append(d)
            for name, ds in sorted(mods.items(), key=lambda kv: -sum(kv[1])):
                rows.append(f"  module {name}: n={len(ds)} total="
                            f"{sum(ds) / 1e9:.4f}s median="
                            f"{statistics.median(ds) / 1e6:.3f}ms")
            agg: Dict[str, List[int]] = {}
            for name, own in self_times(c.ops):
                agg.setdefault(name, []).append(own)
            for name, ds in sorted(agg.items(),
                                   key=lambda kv: -sum(kv[1]))[:limit]:
                rows.append(f"  op {name}: n={len(ds)} self="
                            f"{sum(ds) / 1e9:.4f}s")
        return "\n".join(rows)


def cut_sample(path: str, start_ms: float, length_ms: float) -> dict:
    """A slice of a recorded trace, events clipped to it, times from 0: the
    small trace the self-test pins numbers on."""
    out = {}
    for plane, lines in load(path).items():
        t0 = min(s for evs in lines.values() for _n, s, _d in evs)
        lo = t0 + int(start_ms * 1e6)
        hi = lo + int(length_ms * 1e6)
        out[plane] = {
            line: [[n, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                   for n, s, d in evs if s < hi and s + d > lo]
            for line, evs in lines.items()}
    return out


if __name__ == "__main__":  # python trace_reduce.py in.xplane.pb out.json 100 40
    import json
    import sys

    src, dst, start, length = sys.argv[1:5]
    with open(dst, "w") as f:
        json.dump(cut_sample(src, float(start), float(length)), f)
