"""Device time of one run of the compiled program whose name matches
``pattern`` (the trace's ``XLA Modules`` line), median over the runs, ms.
One program: a pattern that matches two distinct programs would merge them
into one median, so that is an error, not a number."""

import statistics


def read(ctx, pattern):
    if ctx.trace is None:
        return None
    names = ctx.trace.module_names(pattern)
    if len(names) > 1:
        raise ValueError(f"pattern {pattern!r} matches {len(names)} programs "
                         f"({sorted(names)}): name the one that is meant")
    runs = ctx.trace.module_runs(pattern)
    return statistics.median(runs) / 1e6 if runs else None
