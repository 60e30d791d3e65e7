"""A count the program wrote on its host spans (``lib/host_spans``): over the
spans named ``name`` in the traced window, the ``median`` or ``mean`` of
attribute ``attr``, or ``ratio_pct`` = 100 x sum of ``attr`` / sum of
``over``.  ``None`` when the trace holds no such span (or ``over`` sums to
nothing)."""

import statistics

from benchmarks.lib import host_spans


def read(ctx, name, stat, attr, over=None):
    spans = [s for s in host_spans.spans_named(ctx, name) if attr in s.stats]
    if not spans:
        return None
    values = [float(s.stats[attr]) for s in spans]
    if stat == "median":
        return statistics.median(values)
    if stat == "mean":
        return statistics.fmean(values)
    if stat == "ratio_pct":
        below = sum(float(s.stats[over]) for s in spans)
        return 100.0 * sum(values) / below if below else None
    raise ValueError(f"stat {stat!r}: median, mean or ratio_pct")
