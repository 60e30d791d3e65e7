"""A LongCat decode step's share of its memory roofline: the bytes the step
must read (``lib/flops_longcat.decode_step_bytes``) at the chip's bandwidth,
over the device time of the decode program (``module``), in %.  Experts
touched and slots occupied a step are the engine's own counts on the spans
``counts``; the mean context of an occupied slot comes from the traffic's
sizes.  ``None`` when the program or the counts are not in the trace."""

from benchmarks.lib import flops, flops_longcat, traffic
from benchmarks.readers.module_ms import read as module_ms
from benchmarks.readers.span_stat import read as span_stat


def read(ctx, module, counts):
    ms = module_ms(ctx, module)
    touched = span_stat(ctx, counts, "mean", "experts_touched")
    occupied = span_stat(ctx, counts, "mean", "occupied")
    if ms is None or touched is None or occupied is None:
        return None
    nbytes = flops_longcat.decode_step_bytes(
        ctx.stats["model"], touched, occupied,
        flops.mean_decode_context(traffic.sizes(ctx.mix)))
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
