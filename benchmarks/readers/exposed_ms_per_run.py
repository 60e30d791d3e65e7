"""Device time in operations matching ``pattern`` during which no other
operation runs on that chip, for each run of the program matching
``per_module``, mean over chips, ms.  For collectives: the part not hidden
behind compute."""

import statistics

from benchmarks.readers.op_ms_per_run import runs_per_chip


def read(ctx, pattern, per_module):
    if ctx.trace is None:
        return None
    n = runs_per_chip(ctx, per_module)
    if not n or not any(c.matching(c.ops, pattern) for c in ctx.trace.chips):
        return None
    ns = statistics.fmean(c.exposed_ns(pattern) for c in ctx.trace.chips)
    return ns / n / 1e6
