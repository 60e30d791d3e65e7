"""Tokens one held expert sees a step: the mean over the spans ``name`` of
attribute ``attr`` (choices that fell on held experts, all layers, as the
engine wrote them) over the held experts of all layers
(``model["experts_held"] * model["n_layer"]``).  ``None`` when no span
carries the attribute."""

from benchmarks.readers.span_stat import read as span_stat


def read(ctx, name, attr):
    held = span_stat(ctx, name, "mean", attr)
    if held is None:
        return None
    model = ctx.stats["model"]
    return held / (model["experts_held"] * model["n_layer"])
