"""A number from the run's cluster trace (``lib/cluster_spans``: the
wall-clock spans ``ray_tpu.shutdown()`` wrote to ``spans.jsonl``), over the
spans named ``name``.

``stat``: ``max`` of the spans' durations, s; ``ratio`` = sum of attribute
``attr`` / sum of attribute ``over``; ``union`` = the length of the union of
the spans' intervals, s; ``extent`` = the first start -> the last end, s;
``until`` = the first such span's start -> the ``edge`` (``start`` / ``end``)
of the first span ``until`` (whose attributes hold ``until_where``), s.

``window``: only the spans of the requests admitted in the traced window (by
``trace_id``: the same seconds as every other per-layer number; ``None`` when
the two clocks disagree).  ``before_window``: only the spans that ended before
the traced window's first admission (what a serving process did until then is
set-up; after the window come the benchmark's own checks), ``None`` with no
anchored admission.  ``process_of``: only the spans recorded by the process
that recorded the first span of that ``name`` / ``where`` (a process is
picked by what it did, never by trace).  ``None`` when there is no file, the
file has holes, or nothing matches."""

from benchmarks.lib import cluster_spans as cs


def read(ctx, name, stat, attr=None, over=None, window=False,
         before_window=False, process_of=None, until=None, until_where=None,
         edge="start"):
    trace = cs.for_ctx(ctx)
    if trace is None:
        return None
    rows = trace.named(name)
    if window:
        ids = cs.window_trace_ids(ctx)
        if ids is None:
            return None
        wanted = set(ids)
        rows = [r for r in rows if r.trace_id in wanted]
    if before_window:
        opened = cs.first_admission(ctx)
        if opened is None:
            return None
        rows = [r for r in rows if r.end <= opened]
    if process_of is not None:
        worker_id = cs.process_of(trace, **process_of)
        rows = [r for r in rows if r.worker_id == worker_id]
    if attr is not None:
        rows = [r for r in rows if attr in r.attrs]
    if not rows:
        return None
    if stat == "union":
        return cs.union_s((r.start, r.end) for r in rows)
    if stat == "extent":
        return max(r.end for r in rows) - min(r.start for r in rows)
    if stat == "until":
        then = trace.named(until, until_where)
        return getattr(then[0], edge) - rows[0].start if then else None
    if stat == "ratio":
        below = sum(float(r.attrs.get(over, 0)) for r in rows)
        above = sum(float(r.attrs[attr]) for r in rows)
        return above / below if below else None
    if stat == "max":
        return max(r.duration_s for r in rows)
    raise ValueError(f"stat {stat!r}: max, ratio, union, extent or until")
