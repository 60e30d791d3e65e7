"""A memory-bound kernel's share of its own roofline inside a decode step:
the bytes it must move, by the family's
``families/<family>.<bytes_fn>(model, occupied)`` (``occupied``: the mean of
the engine's own count over the spans ``counts``), at the chip's bandwidth,
over the device time of the operations matching ``pattern`` a run of the
program ``per_module`` (``op_ms_per_run``), in %.  ``None`` when the trace
holds no such operation or span, or the family has no such function (a
parent commit's)."""

import importlib

from benchmarks.readers.op_ms_per_run import read as kernel_ms
from benchmarks.readers.span_stat import read as span_stat


def read(ctx, pattern, per_module, counts, bytes_fn):
    fam = importlib.import_module(
        "benchmarks.families." + ctx.config["family"])
    if not hasattr(fam, bytes_fn):
        return None
    ms = kernel_ms(ctx, pattern, per_module)
    occupied = span_stat(ctx, counts, "mean", "occupied")
    if ms is None or occupied is None:
        return None
    nbytes = getattr(fam, bytes_fn)(ctx.stats["model"], occupied)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
