"""What the spans of one kind count a second: the sum of an attribute
over the second to the last of those in the measured window that carry
it (and meet `where`, `lib/spans.py`), over the time from the first
one's end to the last one's. The first is left out: what it counts came
before its end, and its end is where the clock starts. Nothing under two
such spans."""

from benchmark.lib import spans


def read(ctx, *, span: str, attr: str, where=None):
    rows = [r for r in spans.select(ctx.span_records, span, where)
            if spans.attr(r, attr) is not None]
    if len(rows) < 2:
        return None
    seconds = (spans.end_ms(rows[-1]) - spans.end_ms(rows[0])) / 1e3
    if seconds <= 0.0:
        return None
    return sum(spans.attr(r, attr) for r in rows[1:]) / seconds
