"""The prefill program's share of the chip's peak (model FLOP/s utilisation):
the operations of the traced window's prefills at their TRUE prompt lengths
(the family's ``prefill_flops(model, prompt_len)``: attention counted below
the diagonal only) over the device time those prefills took, against the
chip's bf16 peak, in %.  Prefill is bound by compute, so this is its share
of its roofline; a rung's padding and work above the diagonal lower it, as
they should.  Which run of the program ``module`` is which admission's: the
engine dispatches a prefill inside a span ``spans`` (``engine.admit``, which
carries ``prompt_len``) and the device runs it later, in order, before the
next admission begins (the loop is at most one step ahead of the device), so
a run belongs to the LAST admission that began before the run did, each
admission to one run.  A run whose admission began before the trace did, or
an admission whose program ran after the trace ended, is left out, so the
operations and the time are of the same prefills.  ``None`` when no such pair
is in the trace, or the family has no ``prefill_flops`` (a parent commit's)."""

import bisect
import importlib

from benchmarks.lib import host_spans


def read(ctx, module, spans):
    fam = importlib.import_module("benchmarks.families." + ctx.config["family"])
    if not hasattr(fam, "prefill_flops"):
        return None
    flops = ns = 0.0
    for f in host_spans.for_ctx(ctx):
        admits = sorted((s for s in f.spans(spans) if "prompt_len" in s.stats),
                        key=lambda s: s.start)
        starts = [s.start for s in admits]
        for chip in f.chips:
            taken = set()
            for _name, start, duration in sorted(
                    chip.matching(chip.modules, module), key=lambda ev: ev[1]):
                i = bisect.bisect_right(starts, start) - 1
                if i < 0 or i in taken:
                    continue
                taken.add(i)
                flops += fam.prefill_flops(
                    ctx.stats["model"], int(admits[i].stats["prompt_len"]))
                ns += duration
    if not ns:
        return None
    return 100.0 * flops / (ns / 1e9) / ctx.peaks["bf16_flops_per_s"]
