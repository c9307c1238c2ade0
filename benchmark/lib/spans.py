"""Choosing among the program's spans by what they carry, for the readers
that read a span's attributes (`Tracer.records()` keeps them under
`attrs`), so that a population is named in a metric's data file and not
in code."""

from __future__ import annotations

import operator

_OPS = {">": operator.gt, ">=": operator.ge, "==": operator.eq}


def holds(record: dict, where) -> bool:
    """`where` is `{attr: [op, value]}` with op `>`, `>=` or `==`: every
    condition has to hold; a list of such dicts: any one of them; None:
    nothing asked. A span without the attribute (an older program's)
    meets no condition on it."""
    if where is None:
        return True
    if isinstance(where, list):
        return any(holds(record, w) for w in where)
    return all(attr(record, a) is not None and _OPS[op](attr(record, a), v)
               for a, (op, v) in where.items())


def select(records, span: str, where=None) -> list[dict]:
    """The records named `span` that meet `where`, by their end."""
    return sorted((r for r in records
                   if r["name"] == span and holds(r, where)), key=end_ms)


def end_ms(record: dict) -> float:
    return record["t_ms"] + record["dur_ms"]


def attr(record: dict, name: str):
    """The span's attribute, None where it carries none of that name."""
    return record.get("attrs", {}).get(name)


def weighted_percentile(values, weights, q: float):
    """The smallest value at or below which `q` percent of the weight
    lies (no interpolation: with weights there is no rank to interpolate
    between); None where there is no weight."""
    total = float(sum(weights))
    if total <= 0.0:
        return None
    seen = 0.0
    for v, w in sorted(zip(values, weights)):
        seen += w
        if seen >= total * q / 100.0:
            return float(v)
    return float(max(values))
