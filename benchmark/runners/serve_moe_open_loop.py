"""Runner for `open_loop` traffic over a model given as a layer spec: the
Laguna configuration's `LMServer`, under the replay loop, the per-request
bookkeeping and the window reduction of `serve_open_loop` (imported
unchanged). Its own: the parameter tree from the spec, the server from
the spec, the stretches the arrivals are drawn over (`arrival_segments`)
and the comparison that decides `correct`.

That comparison runs after the window, through the ENGINE the window
ran on: its chunk program and its decode window program at the cell's
slots and `t_max`, with the logits of a slot handed out by
`SlotEngine.slot_logits` (a path the timed loop does not take) after
every one-token window. Logits are compared, not tokens, with the plain
reference `benchmark/reference/laguna_ref.py` on the same weights upcast
to float32 and the same held experts.

The router hazard: a bfloat16 activation can swap the 10th and the 11th
expert of a token where the reference's own margin between them is
tiny, and one swapped expert moves the logits by more than every
rounding together. So the engine also hands out the router's picks
(`SlotEngine.router_picks`), at every position of the sequence: a swap
at an earlier position moves the keys and values every later one reads.
The reference runs with the system's choice forced everywhere, and the
logits are compared with that; and every expert the system picked has
to score within `router_margin` (in router logits) of the reference's
k-th best at its position, on that same path, or the run is not
correct: a wrong router still fails.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark.lib import harness, traffic_gen
from benchmark.reference import laguna_ref
from benchmark.runners.serve_open_loop import Replay, reduce_rows


# The window's arrivals are drawn in stretches of about this length.
STRETCH_S = 5.0


def arrival_segments(t_open: float, t_close: float, t_stop: float) -> list:
    """The consecutive stretches `traffic_gen.open_loop_trace` draws the
    mix over: the start burst alone, the warm-up, the window in equal
    stretches of about `STRETCH_S`, the drain. The generator gives every
    stretch its own fixed count of arrivals and its own stratified
    lengths. Above the knee the queue is full and the generator blocked,
    so only a prefix of the window's arrivals is ever offered. Were the
    window ONE stretch (lengths stratified over all of it, in an order
    the seed shuffles), that prefix would be a random sample of the
    lengths, and the seeds would differ in the work that gets served
    (PERF.md §6, PR 27). Drawn stretch by stretch, any prefix is whole
    stretches and part of one: every seed SERVES the same work too, not
    only offers it. The mix (rate, Poisson placing inside a stretch,
    both length distributions) is the traffic file's."""
    n = max(1, round((t_close - t_open) / STRETCH_S))
    edges = np.linspace(t_open, t_close, n + 1).tolist()
    return ([(0.0, 0.0), (0.0, t_open)] + list(zip(edges[:-1], edges[1:]))
            + [(t_close, t_stop)])


def held_range(config: dict) -> tuple[int, int]:
    return config["held_first"], config["num_experts"]


def model_spec(config: dict):
    from idc_models_tpu.models import lm

    # the file's `num_experts` counts the experts HELD (it is in
    # `reduced`); the router keeps the published width
    return lm.laguna_spec(
        dict(config, num_experts=config["num_experts_published"]),
        held=held_range(config), param_dtype=config["param_dtype"])


def make_params(config: dict, seed: int):
    """The parameter tree on the device, in one jitted call from the
    seed: `lm.init_params` for the spec, then the file's `weight_gains`
    (see `changed.weights` there). The dtype is checked against the file."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models import lm

    spec = model_spec(config)
    gains = config["weight_gains"]

    def init(key):
        params = lm.init_params(
            spec, config["vocab_size"], key,
            mlp_dim=config["intermediate_size"],
            expert_dim=config["moe_intermediate_size"])
        for i in range(len(spec.layers)):
            block = params[f"block{i}"]
            for name in ("wq", "wo"):
                block["mha"][name] = block["mha"][name] * gains[name]
            if "moe" in block:
                block["moe"]["router"] = (block["moe"]["router"]
                                          * gains["router"])
        return params

    params = jax.jit(init)(jax.random.key(seed))
    want = jnp.dtype(config["param_dtype"])
    got = {a.dtype for a in jax.tree.leaves(params)}
    if got != {want}:
        raise SystemExit(f"parameters are {got}, the configuration "
                         f"states {want}")
    return params


def build_server(params, config: dict, engine: dict):
    import jax.numpy as jnp

    from idc_models_tpu.serve import LMServer

    return LMServer(
        params, spec=model_spec(config), t_max=engine["t_max"],
        n_slots=engine["n_slots"], window=engine["window"],
        cache_dtype=jnp.dtype(engine["cache_dtype"]),
        temperature=engine["temperature"],
        max_queue_depth=engine["max_queue_depth"],
        max_prefills_per_cycle=engine["max_prefills_per_cycle"],
        prefill_chunk=engine["prefill_chunk"], warmup=True)


def _quiesce(engine) -> None:
    """Leave the replay's requests where they are and free every slot:
    the check admits over their rows (an insert overwrites a whole row,
    and a released row never touches a live one)."""
    engine.collect()
    for slot in range(engine.n_slots):
        engine.cancel_prefill(slot)
        engine.release(slot)


def serve_for_check(engine, prompts, n_dec: int):
    """Each prompt through the engine's chunk program into a slot of its
    own, then `n_dec` one-token windows over all of them. Per prompt:
    the emitted tokens, the logits at the last prompt position and after
    every token [n_dec + 1, V], and the router's picks at EVERY position
    (a window step feeds the token it emits) [sparse layers, P + n_dec, k]."""
    logits, picks = [], []
    for slot, prompt in enumerate(prompts):
        engine.start_prefill(slot, prompt, n_dec)
        chunks, done = [], False
        while not done:
            done = engine.prefill_step(slot)
            chunks.append(engine.router_picks("prefill")[:, 0])
        logits.append([engine.slot_logits(slot)])
        picks.append([np.concatenate(chunks, axis=1)[:, :len(prompt)]])
    tokens = [[] for _ in prompts]
    for step in range(n_dec):
        out = engine.step_window(1)
        step_picks = engine.router_picks("window")[0]     # [layers, S, k]
        for slot in range(len(prompts)):
            tokens[slot] += out[slot]
            logits[slot].append(engine.slot_logits(slot))
            picks[slot].append(step_picks[:, slot, None])
    # a step's picks are those of the token it FED: the prompt's tokens,
    # then each emitted token; row j of the logits follows the j-th
    return (tokens, [np.stack(x) for x in logits],
            [np.concatenate(x, axis=1) for x in picks])


def compare_with_reference(params, config: dict, seq, n_dec: int,
                           got_logits, got_picks, dot=None) -> dict:
    """One prompt's comparison (see the module text): the reference runs
    once, with the system's choice of experts forced at every position.
    A layer's router logits then come from hidden states that hold the
    forced choices of the layers before it and of the positions before
    it, so every choice is judged on the system's own path. Returns the
    logit error over the last `n_dec + 1` positions, the share of
    (layer, position) choices that are not the reference's own top k,
    and the furthest any chosen expert's router logit lay below the
    reference's k-th best. `dot` goes to the reference (its hook for a
    lower-precision product: `tools/laguna_check_faults.py`)."""
    import jax

    k = config["num_experts_per_tok"]
    t = len(seq)
    fwd = jax.jit(lambda params, seq, picks: laguna_ref.forward(
        params, seq, config, held_range(config), rows=(t - n_dec - 1, t),
        picks=picks, dot=dot))
    ref, routers = (np.asarray(x) for x in fwd(params, seq, got_picks))
    own = np.argsort(-routers, axis=-1)[..., :k]              # [L, T, k]
    swapped = (np.sort(own, -1) != np.sort(got_picks, -1)).any(-1)
    kth = np.sort(routers, axis=-1)[..., -k]
    lowest = np.take_along_axis(routers, got_picks, axis=-1).min(-1)
    err = float(np.max(np.abs(got_logits - ref)) / np.max(np.abs(ref)))
    return {"logit_err": err, "swapped_share": float(swapped.mean()),
            "router_deficit": float((kth - lowest).max()),
            "choices": int(swapped.size)}


def check_prompts(config: dict, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, config["vocab_size"], n).astype(np.int32)
            for n in config["check"]["prompt_lens"]]


def check_against_reference(params, config: dict, prompts, served) -> dict:
    """What `serve_for_check` got for `prompts`, against the reference."""
    spec = config["check"]
    res = []
    for prompt, toks, lg, pk in zip(prompts, *served):
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        res.append(compare_with_reference(
            params, config, seq, spec["decode_positions"], lg, pk))
    worst = max(r["logit_err"] for r in res)
    deficit = max(r["router_deficit"] for r in res)
    return {"ok": bool(np.isfinite(worst) and worst <= spec["logit_tol"]
                       and deficit <= spec["router_margin"]),
            "logit_err": worst, "logit_errs": [r["logit_err"] for r in res],
            "router_deficit": deficit,
            "router_swapped_share": [r["swapped_share"] for r in res],
            "router_choices": sum(r["choices"] for r in res)}


def run(job) -> dict:
    from idc_models_tpu.observe import trace as ptrace

    config, engine = job.config, job.config["engine"]
    mix, judge = job.traffic["open_loop"], job.traffic["judge"]
    harness.note("making the weights on the device")
    params = make_params(config, job.seed)
    harness.note("building and warming the server")
    server = build_server(params, config, engine)
    harness.note("server warm")

    t_open = mix["warmup_s"]
    t_close = t_open + job.seconds
    t_stop = t_close + mix["drain_s"]
    arrivals = traffic_gen.open_loop_trace(
        mix, seed=job.seed, segments=arrival_segments(t_open, t_close, t_stop),
        vocab=config["vocab_size"], t_max=engine["t_max"])
    profiler = (harness.ProfilerSlice(job.scratch / "profile",
                                      mix["profile_s"])
                if job.trace else None)
    tracer = ptrace.Tracer() if job.trace else None
    prev = ptrace.set_tracer(tracer) if tracer is not None else None
    replay = Replay(server, arrivals)
    p_start = t_open + 0.5 * (job.seconds - mix["profile_s"])

    def on_cycle(now):
        if profiler is not None and not profiler.started and now >= p_start:
            profiler.start()

    try:
        replay.run_until(t_open)
        t_open_abs = time.perf_counter()
        harness.note("warm-up arrivals done, window open")
        replay.run_until(t_close, on_cycle)
        t_close_abs = time.perf_counter()
        due_ids = [a.rid for a in arrivals if t_open <= a.due_s < t_close]
        while replay.now() < t_stop and judge == "latency":
            replay.cycle()
            if all(i in replay.results for i in due_ids):
                break
    finally:
        if tracer is not None:
            ptrace.set_tracer(prev)
    summary = server.summary()
    memory_peak = harness.memory_peak_bytes(1)
    harness.note(f"replay done, {replay.next} requests submitted")

    rows = replay.rows()
    (job.scratch / "rows.json").write_text(json.dumps(rows))
    red = reduce_rows(rows, arrivals, t_open=t_open, t_close=t_close,
                      judge=judge,
                      slice_s=(p_start, p_start + mix["profile_s"]))
    counters = {f"runner.{k}": v for k, v in red.items()}
    counters |= {f"summary.{k}": v for k, v in summary.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
    counters["runner.submitted"] = replay.next
    if summary.get("serve_moe_experts_touched_mean") is not None:
        counters["runner.moe_touched_share"] = (
            summary["serve_moe_experts_touched_mean"]
            / summary["serve_moe_experts_held"])

    # Correctness, outside the window, on the engine the window ran on
    # (a second one's caches would not fit beside it); the engine goes
    # before the reference runs, which needs the room its caches take.
    _quiesce(server.engine)
    prompts = check_prompts(config, job.seed + 1)
    served = serve_for_check(server.engine, prompts,
                             config["check"]["decode_positions"])
    del replay, server
    checks = check_against_reference(params, config, prompts, served)
    harness.note("checked against the reference")
    budgets_ok = red["bad_requests"] == 0 and red["done_requests"] > 0
    checks["budgets_ok"] = budgets_ok
    e2e = {"serve_out_tokens_per_s": red["out_tokens_per_s"],
           "ttft_mean_ms": red["ttft_mean_ms"],
           "tpot_p50_ms": red["tpot_p50_ms"]}
    return {
        "correct": bool(checks["ok"] and budgets_ok and red["failed"] == 0),
        "attempted": red["attempted"], "failed": red["failed"],
        "end_to_end": e2e,
        "t_open": t_open_abs, "t_close": t_close_abs,
        "memory_peak_bytes": memory_peak,
        "counters": counters, "checks": checks,
        "tracer": tracer, "profiler": profiler,
        "life_spans": ("serve.request", "serve.queued", "serve.first_token"),
    }
