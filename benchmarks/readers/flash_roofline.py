"""Flash attention kernels' share of their roofline over one optimizer step:
the least time the chip could take for the attention the step needs
(``lib/flops.flash_step_need``: recomputation not counted, causal half) over
the device time of the kernels matching ``pattern``, in %."""

from benchmarks.lib import flops
from benchmarks.readers.op_ms_per_run import read as kernel_ms


def read(ctx, pattern, per_module):
    ms = kernel_ms(ctx, pattern, per_module)
    if ms is None:
        return None
    s = ctx.stats
    need = flops.flash_step_need(
        s["model"], s["rows_per_chip"], s["seq"], s["model"].get("remat"))
    return flops.roofline_share(
        need["flops"], need["bytes"], ms / 1e3, ctx.peaks)["pct"]
