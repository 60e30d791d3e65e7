"""A routing count a held expert: the mean over the spans ``name`` of
attribute ``attr`` (summed over the expert layers, as the engine wrote it)
over the held experts of all EXPERT layers, which the family counts
(``families/<family>.held_expert_slots(model)``: a hybrid model's ``n_layer``
counts layers that hold none), times ``scale`` (100 for a share in %).
``None`` when no span carries the attribute or the family has no such
function."""

import importlib

from benchmarks.readers.span_stat import read as span_stat


def read(ctx, name, attr, scale=1.0):
    total = span_stat(ctx, name, "mean", attr)
    fam = importlib.import_module("benchmarks.families." + ctx.config["family"])
    if total is None or not hasattr(fam, "held_expert_slots"):
        return None
    return scale * total / fam.held_expert_slots(ctx.stats["model"])
