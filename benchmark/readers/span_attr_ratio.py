"""The sum of one attribute over the sum of another, or over their
number where `den` is not given, times `scale`, over the spans of one
kind in the measured window that meet `where` (`lib/spans.py`). Nothing
where no span of the kind carries both or the denominator is 0; where
spans carry them and none meets `where`, `otherwise` (a share of what
never happened is 0, if the data file says so)."""

from benchmark.lib import spans


def read(ctx, *, span: str, num: str, den=None, where=None,
         scale: float = 1.0, otherwise=None):
    carry = [r for r in spans.select(ctx.span_records, span)
             if spans.attr(r, num) is not None
             and (den is None or spans.attr(r, den) is not None)]
    met = [r for r in carry if spans.holds(r, where)]
    below = sum(1 if den is None else spans.attr(r, den) for r in met)
    if below <= 0:
        return otherwise if carry and not met else None
    return scale * sum(spans.attr(r, num) for r in met) / below
