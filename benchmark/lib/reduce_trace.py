"""From a profiler trace to numbers: device busy union, per-op totals,
per-program totals, idle gaps and what the host was doing in each.

Two steps, kept apart so the arithmetic can be checked on a small recorded
trace (`benchmark/tests/data/`): `load_xplane` turns jax's `.xplane.pb`
into plain lists, and everything else works on those lists.

A trace, as this module sees it:

  {"devices": {"<plane>": {"ops": [[name, start_ns, dur_ns], ...],
                           "programs": [[name, start_ns, dur_ns], ...]}},
   "host": [[name, start_ns, dur_ns], ...]}

`ops` are the device's operations (they may nest: a `while` contains its
body), `programs` the executables as the device ran them, `host` the
`jax.profiler.TraceAnnotation`s whose name starts with `bench.`. All on
the profiler's clock, in nanoseconds.
"""

from __future__ import annotations

import functools
import itertools
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PREFIX = "bench."
HOST_PROBE = 500
WINDOW = "bench.profile_window"
SYNC = "bench.sync"
MARKER = "bench_marker"


_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")


@functools.lru_cache(maxsize=None)
def short_op_name(text: str) -> str:
    """The trace prints a device operation as its whole HLO line. What
    says which work it was: the kind of operation (its name without the
    instance number), the shape it makes and the shapes of its first two
    operands. The 36 layers' instances of one fusion so share a name.
    (Cached: a 3 s serve trace has 1.5 M events of a few thousand names.)"""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    kind = re.sub(r"[.\d]+$", "", head.lstrip("%").strip())
    shapes = _SHAPE.findall(rest)
    made = shapes[0] if shapes else ""
    return f"{kind} {made} <- {', '.join(shapes[1:3])}"[:120]


def find_xplane(trace_dir) -> Path | None:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def load_xplane(path, *, describe: bool = False) -> dict:
    """Read an `.xplane.pb` with jax's own reader. With `describe`, the
    result also lists every plane and line with its event count, to look
    at a new kind of trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    seen = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name
        dev = {"ops": [], "programs": []}
        for line in plane.lines:
            n = 0
            # the runner's annotations sit on its own threads, from their
            # first events on; the runtime's transfer threads hold
            # millions of events and none of them, and are not read
            if not (is_dev or describe or any(
                    e.name.startswith(HOST_PREFIX)
                    for e in itertools.islice(line.events, HOST_PROBE))):
                continue
            for ev in line.events:
                n += 1
                row = [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                if is_dev and line.name == OPS_LINE:
                    row[0] = short_op_name(ev.name)
                    dev["ops"].append(row)
                elif is_dev and line.name == PROGRAMS_LINE:
                    dev["programs"].append(row)
                elif not is_dev and ev.name.startswith(HOST_PREFIX):
                    out["host"].append(row)
            seen.append([plane.name, line.name, n])
        if is_dev and (dev["ops"] or dev["programs"]):
            out["devices"][plane.name] = dev
    if describe:
        out["lines"] = seen
    return out


def window_of(trace: dict) -> tuple[float, float]:
    """The traced window on the profiler's clock: the runner's
    `bench.profile_window` annotation; without host tracing, from the
    first to the last `bench_marker` program on the device; or else from
    the first to the last device event."""
    for name, start, dur in trace["host"]:
        if name == WINDOW:
            return start, start + dur
    marks = _marker_starts(trace)
    if len(marks) >= 2:
        return marks[0], marks[-1]
    evs = [e for d in trace["devices"].values() for e in d["ops"]]
    if not evs:
        raise ValueError("trace has neither a window annotation nor device ops")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def _marker_starts(trace: dict) -> list[float]:
    return sorted(start for d in trace["devices"].values()
                  for name, start, _ in d["programs"] if MARKER in name)


def _clip(events, t0, t1):
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            yield name, a, b


def busy_intervals(ops, t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of the intervals in which an operation ran, inside [t0, t1]."""
    out: list[list[float]] = []
    for _, a, b in sorted(_clip(ops, t0, t1), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(ops, t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(ops, t0, t1)) / 1e9


def idle_gaps(ops, t0: float, t1: float) -> list[tuple[float, float]]:
    """The complement of the busy union inside [t0, t1]."""
    gaps, at = [], t0
    for a, b in busy_intervals(ops, t0, t1):
        if a > at:
            gaps.append((at, a))
        at = b
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def op_self_seconds(ops, t0: float, t1: float) -> dict[str, float]:
    """Seconds each operation name took itself: its events' durations
    minus the operations nested inside them (a `while` is charged only
    what its body does not cover), so the totals add up to the busy time."""
    totals: dict[str, float] = {}
    stack: list[list] = []                     # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            totals[name] = totals.get(name, 0.0) + self_ns / 1e9

    for name, a, b in sorted(_clip(ops, t0, t1), key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return totals


def program_seconds(programs, pattern: str, t0: float,
                    t1: float) -> tuple[float, int]:
    """Total seconds and count of the executables whose name matches
    `pattern` and that started inside [t0, t1]."""
    rx = re.compile(pattern)
    hit = [dur for name, start, dur in programs
           if t0 <= start < t1 and rx.search(name)]
    return sum(hit) / 1e9, len(hit)


def clock_offset_ns(trace: dict, sync_clock_s: float) -> float | None:
    """Profiler time minus the program tracer's time, from the one
    `bench.sync` annotation the runner wrote at `sync_clock_s` of the
    tracer's clock; without host tracing, from the first device marker,
    which was dispatched right after that time. None when the trace has
    neither."""
    for name, start, _ in trace["host"]:
        if name == SYNC:
            return start - sync_clock_s * 1e9
    marks = _marker_starts(trace)
    return marks[0] - sync_clock_s * 1e9 if marks else None


def name_gaps(gaps, spans, *, consider: int = 200,
              top: int = 10) -> list[list]:
    """Idle seconds by what the host was doing. Each of the `consider`
    longest gaps is named by the host span that covers most of it:
    `spans` are (name, start_ns, end_ns) on the profiler's clock. Of the
    spans covering at least half a gap the shortest wins, since it says
    most exactly what the host was doing; failing that, the one with the
    largest overlap. Gaps of one name are added up, and the `top` names
    with most idle time come back."""
    by_name: dict[str, float] = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:consider]:
        best, best_key = "(no host span)", None
        for name, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov <= 0:
                continue
            covers = ov >= 0.5 * (b - a)
            key = (covers, -(e - s) if covers else ov)
            if best_key is None or key > best_key:
                best, best_key = name, key
        by_name[best] = by_name.get(best, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def top_ops(ops, t0: float, t1: float, *, top: int = 10) -> list[list]:
    totals = op_self_seconds(ops, t0, t1)
    return [[n, s] for n, s in
            sorted(totals.items(), key=lambda kv: -kv[1])[:top]]
