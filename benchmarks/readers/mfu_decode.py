"""The whole decode step's share of the chip's peak (model FLOP/s
utilisation): the operations one decoded token needs (the family's
``decode_flops_per_token`` at the traffic's mean context) times the slots
occupied a step (the engine's own count on the spans ``counts``), over the
device time of the decode program (``module``), against the chip's bf16
peak, in %.  Decode is bound by the memory's bandwidth, so this is small;
it bounds what any kernel inside the step can claim.  ``None`` when the
program or the counts are not in the trace."""

import importlib

from benchmarks.lib import flops, traffic
from benchmarks.readers.module_ms import read as module_ms
from benchmarks.readers.span_stat import read as span_stat


def read(ctx, module, counts):
    ms = module_ms(ctx, module)
    occupied = span_stat(ctx, counts, "mean", "occupied")
    if ms is None or occupied is None:
        return None
    fam = importlib.import_module("benchmarks.families." + ctx.config["family"])
    per_token = fam.decode_flops_per_token(
        ctx.stats["model"], flops.mean_decode_context(traffic.sizes(ctx.mix)))
    return (100.0 * occupied * per_token / (ms / 1e3)
            / ctx.peaks["bf16_flops_per_s"])
