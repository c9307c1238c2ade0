"""A count or reading the run already holds: the sum of the named
counters, times `scale`. Nothing when any of them is missing."""


def read(ctx, *, keys, scale: float = 1.0):
    vals = [ctx.counters.get(k) for k in keys]
    if any(v is None for v in vals):
        return None
    return float(sum(vals)) * scale
