"""Share of the traced window in which no operation ran on the device AND
some thread was inside a host span named ``name`` (``where`` = ``inside``),
or no thread was (``outside``); mean over the chips used, in %.  The window
and the idle time are ``device_idle_pct``'s, so ``inside`` + ``outside`` of
one name add up to it.  ``None`` when the trace holds no such span."""

from benchmarks.lib import host_spans


def read(ctx, name, where):
    if where not in ("inside", "outside"):
        raise ValueError(f"where {where!r}: inside or outside")
    return host_spans.idle_pct(
        host_spans.for_ctx(ctx), name, inside=where == "inside")
