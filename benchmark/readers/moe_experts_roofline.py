"""The grouped expert product's share of its roofline in the traced
slice, in percent: the least time the chip could take for the products
the slice ran over the device time they took (self time under the
`scope`, `lib/scope_trace.py`).

The products the slice ran: one per sparse layer for every token step of
a decode window (`window_program` runs inside the slice x the engine's
`window`), each over the live rows' share of the held experts (the
run's mean of experts touched and of assignments to held experts a
step); and one per sparse layer for every prefill chunk
(`chunk_program` runs), each over a whole chunk of tokens, which touches
every held expert. `lib/moe_work.py` has the bytes and operations of
one product; whichever of the two roofs binds it, binds."""

from benchmark.lib import moe_work, reduce_trace, scope_trace


def read(ctx, *, scope: str, window_program: str, chunk_program: str):
    if not ctx.trace or not ctx.trace["devices"] or not ctx.peaks:
        return None
    path = scope_trace.cell_xplane(ctx.cell["name"])
    touched = ctx.counters.get("summary.serve_moe_experts_touched_mean")
    held_total = ctx.counters.get("summary.serve_moe_assignments_held")
    all_total = ctx.counters.get("summary.serve_moe_assignments")
    if path is None or touched is None or not all_total:
        return None
    config, engine = ctx.config, ctx.config["engine"]
    plane = sorted(ctx.trace["devices"])[0]
    t0, t1 = ctx.window
    seconds = scope_trace.scope_self_seconds(path, scope, t0, t1, plane)
    if not seconds:
        return None
    programs = ctx.trace["devices"][plane]["programs"]
    _, windows = reduce_trace.program_seconds(programs, window_program, t0, t1)
    _, chunks = reduce_trace.program_seconds(programs, chunk_program, t0, t1)
    sparse = sum(1 for kind in config["mlp_layer_types"][
        :config["num_hidden_layers"]] if kind == "sparse")
    k, held = config["num_experts_per_tok"], config["num_experts"]
    # of a live token's k assignments, the share that went to held experts
    held_share = held_total / all_total
    step_rows = ctx.counters.get("runner.live_slots_mean", 0.0) * k * held_share
    chunk_rows = engine["prefill_chunk"] * k * held_share
    least = sparse * (
        windows * engine["window"] * moe_work.expert_product_seconds(
            config, ctx.peaks, touched, step_rows)
        + chunks * moe_work.expert_product_seconds(
            config, ctx.peaks, held, chunk_rows))
    return 100.0 * least / seconds
