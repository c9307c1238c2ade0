"""Device time of what matches `pattern`, in milliseconds per unit.

`line` is `"programs"` (executables, by the name the trace prints) or
`"ops"` (operations, self time; with `pattern` null, the device's whole
busy time). `per` is `"count"` (per matching program run, whole runs
that began inside the slice), a reference to a count the run holds, or
`{"programs": regex}`: per run of the executables matching `regex`,
counted as their time inside the slice over their mean length, so that
the runs the slice cuts at its edges count by the part it holds.
Device 0 is read."""

import re

from benchmark.lib import reduce_trace


def read(ctx, *, pattern, line: str = "programs", per="count"):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    dev = ctx.trace["devices"][sorted(ctx.trace["devices"])[0]]
    t0, t1 = ctx.window
    if pattern is None:
        seconds, n = reduce_trace.busy_seconds(dev["ops"], t0, t1), 0
    elif line == "programs":
        seconds, n = reduce_trace.program_seconds(dev["programs"], pattern,
                                                  t0, t1)
    else:
        rx = re.compile(pattern)
        hit = {k: v for k, v in
               reduce_trace.op_self_seconds(dev["ops"], t0, t1).items()
               if rx.search(k)}
        seconds, n = sum(hit.values()), len(hit)
    if isinstance(per, dict):
        whole, runs = reduce_trace.program_seconds(dev["programs"],
                                                   per["programs"], t0, t1)
        rx = re.compile(per["programs"])
        inside = reduce_trace.busy_seconds(
            [e for e in dev["programs"] if rx.search(e[0])], t0, t1)
        div = inside / (whole / runs) if runs else 0
    else:
        div = n if per == "count" else ctx.resolve(per)
    if not div:
        return None
    return 1e3 * seconds / div
