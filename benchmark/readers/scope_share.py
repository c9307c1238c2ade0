"""Share of the device's busy time, in the traced slice, that went to the
operations under one of the program's named scopes (`jax.named_scope`:
`attn_full`, `attn_window`, `moe_experts`, ...), in percent: self time
under the scope over the busy union. Device 0 is read. Nothing where the
trace names no operation's scope (`lib/scope_trace.py`)."""

from benchmark.lib import reduce_trace, scope_trace


def read(ctx, *, scope: str):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    path = scope_trace.cell_xplane(ctx.cell["name"])
    if path is None:
        return None
    plane = sorted(ctx.trace["devices"])[0]
    t0, t1 = ctx.window
    inside = scope_trace.scope_self_seconds(path, scope, t0, t1, plane)
    busy = reduce_trace.busy_seconds(ctx.trace["devices"][plane]["ops"], t0, t1)
    if inside is None or busy <= 0:
        return None
    return 100.0 * inside / busy
