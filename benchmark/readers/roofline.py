"""Share of a roof reached, in percent: the least time the chip could
take for the work (`work`, a function of `benchmark/lib/flops.py` or
`bytes.py`, times `count`) over the time it took (`seconds`).

`work_args`, `count` and `seconds` are resolved by the context, so they
can name counters, the cell's data files or another per-layer metric.
`roof` names the row of the peaks table that binds."""

import importlib


def read(ctx, *, work: str, work_args: dict, roof: str, seconds, count=1):
    module, _, fn = work.partition(".")
    args = {k: ctx.resolve(v) for k, v in work_args.items()}
    secs, n = ctx.resolve(seconds), ctx.resolve(count)
    if secs is None or n is None or any(v is None for v in args.values()):
        return None
    if secs <= 0 or roof not in ctx.peaks:      # no roof: a CPU rehearsal
        return None
    amount = getattr(importlib.import_module(f"benchmark.lib.{module}"),
                     fn)(**args) * n
    return 100.0 * (amount / ctx.peaks[roof]) / secs
