"""Duration of the program's host spans named ``name`` (``lib/host_spans``:
``TraceAnnotation``s in the profiler's trace, on the device's clock), median
over the traced window, ms.  ``without``: leave out spans that hold a span of
that name (a step that admitted a request is a prefill, not a decode step).
``None`` when the trace holds no such span."""

import statistics

from benchmarks.lib import host_spans


def read(ctx, name, without=None):
    spans = host_spans.spans_named(ctx, name)
    if without is not None:
        spans = [s for s in spans
                 if not any(d.name == without for d in s.descendants())]
    if not spans:
        return None
    return statistics.median(s.duration_ns for s in spans) / 1e6
