"""Time inside one kind of the program's spans over time inside another,
in percent, over the measured window."""


def read(ctx, *, num: str, den: str):
    total = {num: 0.0, den: 0.0}
    for r in ctx.span_records:
        if r["name"] in total:
            total[r["name"]] += r["dur_ms"]
    if total[den] <= 0.0:
        return None
    return 100.0 * total[num] / total[den]
