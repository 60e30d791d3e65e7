"""A number the job measured itself on the host's clock (``stats[key]``): a
scalar as it is, a list by its median."""

import statistics


def read(ctx, key):
    value = ctx.stats.get(key)
    if value is None or value == []:
        return None
    if isinstance(value, list):
        return float(statistics.median(value))
    return float(value)
