"""A quantile of the durations of one kind of the program's spans, in
milliseconds, over the measured window."""

from benchmark.lib import stats


def read(ctx, *, span: str, q: float):
    durs = [r["dur_ms"] for r in ctx.span_records if r["name"] == span]
    return stats.percentile(durs, q) if durs else None
