"""Share of the traced window in which no operation ran on a device, in
percent: the mean over the chips used, or with `which="max"` the idlest."""

from benchmark.lib import reduce_trace


def read(ctx, *, which: str = "mean"):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    t0, t1 = ctx.window
    idle = [100.0 * (1.0 - reduce_trace.busy_seconds(d["ops"], t0, t1)
                     / ((t1 - t0) / 1e9))
            for d in ctx.trace["devices"].values()]
    return max(idle) if which == "max" else sum(idle) / len(idle)
