"""Which of the program's named scopes a device operation ran under.

`reduce_trace` reads events through `jax.profiler.ProfileData`, which
hands out an event's name and its own statistics but not the statistics
the trace keeps once per KIND of event (`XEventMetadata.stats`), and the
framework's name of an operation ("jit(window_body)/.../moe_experts/...",
where `jax.named_scope` shows) is one of those. This module reads them
from the `.xplane.pb` itself: a protobuf of planes, each with its lines
of events (skipped here, unread) and two small maps, event metadata and
statistic metadata. Only those two maps are decoded, by hand: the wire
format is tags, varints and length-prefixed fields.

  XSpace.planes = 1;  XPlane: name = 2, lines = 3, event_metadata = 4
  (map: key = 1, value = 2), stat_metadata = 5 (same)
  XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5
  XStatMetadata: id = 1, name = 2
  XStat: metadata_id = 1, str_value = 5, ref_value = 7 (a stat-metadata
  id whose NAME is the value)
"""

from __future__ import annotations

import functools
import re

from benchmark.lib import reduce_trace

# statistic names under which profilers have kept the framework's name
# of an operation, most specific first
OP_NAME_STATS = ("tf_op", "op_name", "long_name", "hlo_op_name")


def _varint(buf, at):
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, at
        shift += 7


def _fields(buf, start=0, end=None):
    """(field number, wire type, value) of one message: a varint's
    value, or the (start, end) span of a length-prefixed field."""
    at, end = start, len(buf) if end is None else end
    while at < end:
        tag, at = _varint(buf, at)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            val, at = _varint(buf, at)
        elif wire == 2:
            n, at = _varint(buf, at)
            val, at = (at, at + n), at + n
        elif wire == 1:
            val, at = None, at + 8
        elif wire == 5:
            val, at = None, at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def _map_values(buf, span):
    for num, _, val in _fields(buf, *span):
        if num == 2:
            return val
    return None


def plane_metadata(path, plane_prefix: str = "/device:TPU:0") -> dict:
    """{event name: {statistic name: text}} of the first plane whose name
    starts with `plane_prefix`: the per-kind statistics that hold text."""
    buf = memoryview(open(path, "rb").read())
    for num, _, span in _fields(buf):
        if num != 1:
            continue
        name, events, stats = "", [], {}
        for f, _, val in _fields(buf, *span):
            if f == 2:
                name = bytes(buf[val[0]:val[1]]).decode("utf-8", "replace")
            elif f == 4:
                events.append(_map_values(buf, val))
            elif f == 5:
                v = _map_values(buf, val)
                if v is not None:
                    sid = sname = None
                    for g, _, x in _fields(buf, *v):
                        if g == 1:
                            sid = x
                        elif g == 2:
                            sname = bytes(buf[x[0]:x[1]]).decode("utf-8", "replace")
                    stats[sid] = sname
        if not name.startswith(plane_prefix):
            continue
        out = {}
        for ev in events:
            if ev is None:
                continue
            ev_name, ev_stats = "", {}
            for f, _, val in _fields(buf, *ev):
                if f == 2:
                    ev_name = bytes(buf[val[0]:val[1]]).decode("utf-8", "replace")
                elif f == 5:
                    key = text = None
                    for g, _, x in _fields(buf, *val):
                        if g == 1:
                            key = stats.get(x)
                        elif g == 5:
                            text = bytes(buf[x[0]:x[1]]).decode("utf-8", "replace")
                        elif g == 7:
                            text = stats.get(x)
                    if key is not None and text is not None:
                        ev_stats[key] = text
            out[ev_name] = ev_stats
        return out
    return {}


def cell_xplane(cell_name: str):
    """The `.xplane.pb` a traced run of `cell_name` wrote (where every
    runner puts its profile), or None."""
    from pathlib import Path

    return reduce_trace.find_xplane(
        Path(__file__).resolve().parents[2] / ".bench_scratch" / cell_name
        / "profile")


def op_names(path, plane_prefix: str = "/device:TPU:0") -> dict:
    """{event name: the framework's name of that operation} for the
    events of the plane that have one."""
    out = {}
    for name, stats in plane_metadata(path, plane_prefix).items():
        for key in OP_NAME_STATS:
            if stats.get(key):
                out[name] = stats[key]
                break
    return out


@functools.lru_cache(maxsize=2)
def _named_ops(path, plane_prefix: str):
    """[[framework name, start_ns, dur_ns], ...] of the plane's
    operations, or None when the trace names no operation at all."""
    from jax.profiler import ProfileData

    names = op_names(path, plane_prefix)
    if not names:
        return None
    ops = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line.name == reduce_trace.OPS_LINE:
                ops += [[names.get(ev.name, ""), float(ev.start_ns),
                         float(ev.duration_ns)] for ev in line.events]
    return ops


def scope_self_seconds(path, scope: str, t0: float, t1: float,
                       plane_prefix: str = "/device:TPU:0"):
    """Self seconds, inside [t0, t1], of the device operations whose
    framework name has `scope` as one of its path components; None when
    the trace names no operation's scope at all."""
    ops = _named_ops(path, plane_prefix)
    if ops is None:
        return None
    rx = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    marked = [["in" if rx.search(name) else "out", start, dur]
              for name, start, dur in ops]
    return reduce_trace.op_self_seconds(marked, t0, t1).get("in", 0.0)
