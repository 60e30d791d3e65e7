"""Device time in operations matching ``pattern`` for each run of the
program matching ``per_module`` (one optimizer step, one decode step), mean
over chips, ms."""


def runs_per_chip(ctx, per_module):
    return len(ctx.trace.module_runs(per_module)) / len(ctx.trace.chips)


def read(ctx, pattern, per_module):
    if ctx.trace is None:
        return None
    n = runs_per_chip(ctx, per_module)
    ns = ctx.trace.op_ns_per_chip(pattern)
    return ns / n / 1e6 if n and ns else None
