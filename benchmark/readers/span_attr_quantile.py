"""A quantile, over the spans of one kind in the measured window that
meet `where` (`lib/spans.py`), of an attribute they carry, or of their
duration in milliseconds where none is named; with `weight`, each span
counts as often as that attribute says. Nothing where no such span
carries it (an older program's spans carry no attributes)."""

from benchmark.lib import spans, stats


def read(ctx, *, span: str, q: float, attr=None, weight=None, where=None):
    rows = [(r["dur_ms"] if attr is None else spans.attr(r, attr),
             1 if weight is None else spans.attr(r, weight))
            for r in spans.select(ctx.span_records, span, where)]
    rows = [(v, w) for v, w in rows if v is not None and w is not None]
    if not rows:
        return None
    if weight is None:
        return stats.percentile([v for v, _ in rows], q)
    return spans.weighted_percentile(*zip(*rows), q)
