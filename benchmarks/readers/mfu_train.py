"""The whole training step's share of the chip's peak (model FLOP/s
utilisation): the operations the step's tokens need on one chip
(``stats["flops_per_token"]``: forward + backward, recomputation not
counted) over the device time of one run of the step program (``module``,
median over the runs and the chips), against the chip's bf16 peak, in %.
It bounds every kernel's roofline share in the step.  ``None`` when the
program is not in the trace."""

from benchmarks.readers.module_ms import read as module_ms


def read(ctx, module):
    ms = module_ms(ctx, module)
    if ms is None:
        return None
    s = ctx.stats
    per_chip = s["flops_per_token"] * s["rows_per_chip"] * s["seq"]
    return 100.0 * per_chip / (ms / 1e3) / ctx.peaks["bf16_flops_per_s"]
