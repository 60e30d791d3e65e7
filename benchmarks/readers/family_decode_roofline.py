"""A decode step's share of its memory roofline, for any family that says
what its step must move: the bytes of
``families/<family>.decode_step_bytes(model, counts, occupied, context)`` at
the chip's bandwidth, over the device time of the decode program
(``module``), in %.  ``counts`` (the reader's argument) names the spans that
carry the engine's counts: every numeric attribute's mean over them goes to
the family as ``counts`` (what its programs counted, ``experts_touched``
among them, and the engine's own ``occupied``); the mean context of an
occupied slot comes from the traffic's sizes.  ``None`` when the program or
the spans are not in the trace, or the family has no such function (a
parent commit's)."""

import importlib
import statistics

from benchmarks.lib import flops, host_spans, traffic
from benchmarks.readers.module_ms import read as module_ms


def read(ctx, module, counts):
    ms = module_ms(ctx, module)
    spans = host_spans.spans_named(ctx, counts)
    fam = importlib.import_module("benchmarks.families." + ctx.config["family"])
    if ms is None or not spans or not hasattr(fam, "decode_step_bytes"):
        return None
    names = set.intersection(*(set(s.stats) for s in spans))
    means = {}
    for name in names:
        try:
            means[name] = statistics.fmean(float(s.stats[name]) for s in spans)
        except (TypeError, ValueError):
            continue  # an attribute that is no number (a trace id)
    nbytes = fam.decode_step_bytes(
        ctx.stats["model"], means, means["occupied"],
        flops.mean_decode_context(traffic.sizes(ctx.mix)))
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
