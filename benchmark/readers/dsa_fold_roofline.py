"""The sparse-attention sublayers' share of their roofline in the decode
windows of the traced slice, in percent: the least time the chip could
take to move what they must (`lib/dsa_work.sparse_attention_step`: the
attention and indexer weights, the index keys to each slot's length, the
selected rows of K and V, the new rows; memory binds a decode step) over
the device time they took: the self time of the operations under any of
`scopes` INSIDE `program` (the same scopes also run in the prefill
chunk, whose time is not this metric's). Nothing where the trace names
no operation's scope."""

import re

from benchmark.lib import dsa_work, reduce_trace, scope_trace


def read(ctx, *, scopes, program: str):
    if not ctx.trace or not ctx.trace["devices"] or not ctx.peaks:
        return None
    path = scope_trace.cell_xplane(ctx.cell["name"])
    live_tokens = ctx.counters.get("runner.live_tokens_mean")
    live_slots = ctx.counters.get("runner.live_slots_mean")
    if path is None or not live_slots:
        return None
    plane = sorted(ctx.trace["devices"])[0]
    ops = scope_trace._named_ops(path, plane)
    if ops is None:
        return None
    t0, t1 = ctx.window
    rx = re.compile(rf"(^|/)({'|'.join(map(re.escape, scopes))})(/|$)")
    marked = [["in" if program in name and rx.search(name) else "out",
               start, dur] for name, start, dur in ops]
    seconds = reduce_trace.op_self_seconds(marked, t0, t1).get("in", 0.0)
    _, windows = reduce_trace.program_seconds(
        ctx.trace["devices"][plane]["programs"], program, t0, t1)
    if not seconds or not windows:
        return None
    engine = ctx.config["engine"]
    least = (windows * engine["window"] * dsa_work.sparse_attention_step(
        ctx.config, engine, live_tokens, live_slots)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
