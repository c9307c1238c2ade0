"""A quantile of the time, in milliseconds, from the end of one span to
the end of the next, over the spans of one kind in the measured window
that meet `where` (`lib/spans.py`); with `weight`, a gap counts as often
as that attribute of the span that STARTS it says (the streams left live
at one delivery are the ones that wait for the next; a stretch nobody
waited through weighs nothing). Nothing under two such spans."""

from benchmark.lib import spans, stats


def read(ctx, *, span: str, q: float, weight=None, where=None):
    rows = spans.select(ctx.span_records, span, where)
    gaps = [spans.end_ms(b) - spans.end_ms(a) for a, b in zip(rows, rows[1:])]
    if not gaps:
        return None
    if weight is None:
        return stats.percentile(gaps, q)
    weights = [spans.attr(r, weight) or 0 for r in rows[:-1]]
    return spans.weighted_percentile(gaps, weights, q)
