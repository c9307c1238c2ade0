"""Runner for `open_loop` traffic over a model whose layers pick the
positions they attend (a learned indexer, `models/lm.Indexer`): the Keye
configuration's `LMServer`, under the replay loop, the per-request
bookkeeping, the window reduction and the arrival stretches of
`serve_open_loop` / `serve_moe_open_loop` (imported unchanged). Its own:
the spec, the parameter tree, and the comparison that decides `correct`.

That comparison runs after the window, through the ENGINE the window
ran on and through its PROGRAMS (`serve_moe_open_loop.serve_for_check`'s
pattern, but for the decode: the chunk program into a slot, every slot
filled, then whole windows of the cell's `window` steps, logits from
`SlotEngine.slot_logits` after each), against
`benchmark/reference/keye_ref.py` on the same weights upcast to float32
and the same held experts. Logits are compared, and every emitted token
is held to the reference's logits at its position; TWO choices that hang
on a rounding are handled alike:

- the router's 8th against its 9th expert, as in the Laguna cell: the
  engine hands out its picks at every position (`router_picks`), the
  reference runs with them forced, and every expert picked has to score
  within `router_margin` of the reference's own 8th best;
- the indexer's 2,048th against its 2,049th position: the engine hands
  out the positions every query attended (`selected_positions`: bits
  from the prefill chunks, indices from the decode windows), the
  reference attends exactly those, and its own index scores have to
  place every one of them within `select_margin` of its own 2,048th
  best; and every query has to have attended exactly
  min(position + 1, topk) positions, none of them ahead of itself.

A wrong indexer, a wrong `topk` or a shifted selection so fails even
though the logits follow the forced choice.

The arrivals are the generator's (`traffic_gen.open_loop_trace` over
`arrival_segments`), steadied: see `steady_trace`.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark.lib import harness, traffic_gen
from benchmark.reference import keye_ref
from benchmark.runners.serve_moe_open_loop import (_quiesce, arrival_segments,
                                                   check_prompts, held_range)
from benchmark.runners.serve_open_loop import Replay, reduce_rows


def model_spec(config: dict):
    from idc_models_tpu.models import lm

    if not hasattr(lm, "keye_spec"):
        raise SystemExit("this program serves no layer with an indexer "
                         "(models/lm.keye_spec is missing)")
    # the file's `num_experts` counts the experts HELD (it is in
    # `reduced`); the router keeps the published width
    return lm.keye_spec(
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
        params = lm.init_params(spec, config["vocab_size"], key,
                                expert_dim=config["moe_intermediate_size"])
        for i in range(len(spec.layers)):
            block = params[f"block{i}"]
            for name in ("wq", "wo"):
                block["mha"][name] = block["mha"][name] * gains[name]
            block["idx"]["wq"] = block["idx"]["wq"] * gains["wiq"]
            block["moe"]["router"] = block["moe"]["router"] * gains["router"]
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


def steady_trace(mix: dict, *, seed: int, segments, vocab: int,
                 t_max: int) -> list:
    """The generator's arrivals for `seed`, each with the prompt and
    output LENGTH that the generator's draw for `mix["lengths_seed"]`
    gives the arrival of the same rank: due times and token ids are the
    seed's, the lengths, in their order, one draw of the mix's
    distributions that every seed shares.

    Why: here a prompt's cost grows with the square of its length (a
    chunk's index scores and masked attention reach back to the prompt's
    start), a stretch holds six arrivals, and a window completes about
    55 requests. Stratified over six, the generator's lengths still
    differ from seed to seed inside each sixth of the distribution, the
    longest sixth 16k-28.7k, and in their order, and above the knee the
    window serves what it can of them: `serve_out_tokens_per_s` spread
    by 9-11% over seeds (bound 6%; PERF.md section 6, PR 34). The work
    is now the same under every seed and in the same order; what a seed
    changes is the weights, the token ids (so the router's and the
    indexer's choices) and when a request is due."""
    own = traffic_gen.open_loop_trace(mix, seed=seed, segments=segments,
                                      vocab=vocab, t_max=t_max)
    lengths = traffic_gen.open_loop_trace(
        mix, seed=mix["lengths_seed"], segments=segments, vocab=vocab,
        t_max=t_max)
    rng = np.random.default_rng((seed, 1))    # not the generator's stream
    return [traffic_gen.Arrival(
                a.due_s, a.rid,
                tuple(rng.integers(0, vocab, len(b.prompt)).tolist()),
                b.max_new_tokens)
            for a, b in zip(own, lengths, strict=True)]


def _bits_of(indices: np.ndarray, words: int) -> np.ndarray:
    """Selected positions [.., k] int (-1: none) as bits [.., words]."""
    out = np.zeros(indices.shape[:-1] + (words,), np.uint32)
    flat, idx = out.reshape(-1, words), indices.reshape(-1, indices.shape[-1])
    for r in range(flat.shape[0]):
        at = idx[r][idx[r] >= 0]
        np.bitwise_or.at(flat[r], at // 32,
                         np.uint32(1) << (at % 32).astype(np.uint32))
    return out


def serve_for_check(engine, prompts, n_dec: int, window: int):
    """Each prompt through the engine's chunk program into a slot of its
    own, every slot left over filled with a request of the same kind
    (the check's prompts but the longest, in turn), then `n_dec` tokens
    as `n_dec / window` windows of `window` steps: the window program
    the cell timed, with every slot live. Per prompt: the emitted
    tokens, the logits at the last prompt position and after every
    window [n_dec / window + 1, V] (the window program keeps its last
    step's alone), the router's picks at EVERY position
    [layers, P + n_dec, k] and the positions every query attended, as
    bits [layers, P + n_dec, ceil((P + n_dec) / 32)] (a window step
    feeds the token it emits)."""
    if n_dec % window:
        raise SystemExit(f"check.decode_positions {n_dec} is no whole "
                         f"number of windows of {window}")
    fillers = [prompts[i % max(len(prompts) - 1, 1)]
               for i in range(engine.n_slots - len(prompts))]
    logits, picks, chosen = [], [], []
    for slot, prompt in enumerate(list(prompts) + fillers):
        words = -(-(len(prompt) + n_dec) // 32)
        engine.start_prefill(slot, prompt, n_dec)
        pk, sel, done = [], [], False
        while not done:
            done = engine.prefill_step(slot)
            if slot >= len(prompts):
                continue
            pk.append(engine.router_picks("prefill")[:, 0])
            bits = engine.selected_positions("prefill")[:, :, :words]
            sel.append(np.pad(bits, ((0, 0), (0, 0),
                                     (0, words - bits.shape[2]))))
        if slot < len(prompts):
            logits.append([engine.slot_logits(slot)])
            picks.append([np.concatenate(pk, axis=1)[:, :len(prompt)]])
            chosen.append([np.concatenate(sel, axis=1)[:, :len(prompt)]])
    tokens = [[] for _ in prompts]
    for _ in range(n_dec // window):
        out = engine.step_window(window)
        win_picks = engine.router_picks("window")       # [steps, layers, S, k]
        win_sel = engine.selected_positions("window")   # [steps, layers, S, K]
        for slot in range(len(prompts)):
            tokens[slot] += out[slot]
            logits[slot].append(engine.slot_logits(slot))
            picks[slot].append(win_picks[:, :, slot].swapaxes(0, 1))
            chosen[slot].append(_bits_of(win_sel[:, :, slot].swapaxes(0, 1),
                                         chosen[slot][0].shape[2]))
    return (tokens, [np.stack(x) for x in logits],
            [np.concatenate(x, axis=1) for x in picks],
            [np.concatenate(x, axis=1) for x in chosen])


def compare_with_reference(params, config: dict, seq, n_dec: int,
                           got_logits, got_picks, got_select, dot=None,
                           idx_dot=None, index_rotary: bool = True) -> dict:
    """One prompt's comparison (see the module text): the reference runs
    once, with the system's experts and the system's positions forced
    everywhere. Returns the logit error over the rows the system kept
    (`got_logits`: the last prompt position's and every window's last,
    evenly spaced over the last `n_dec + 1` positions), how far below
    the reference's best logit the token emitted at each of the `n_dec`
    positions lies (`token_gap`, in the logit error's unit: a greedy
    system within `e` of the reference emits no token more than `2 e`
    below its best), the router's and the indexer's swapped shares and
    deficits, and whether every query attended as many positions as it
    has to. `dot`, `idx_dot` and `index_rotary` go to the reference (its
    hooks for a lower-precision product and a planted fault:
    `tools/keye_check_faults.py`)."""
    import jax

    k, topk = config["num_experts_per_tok"], config["sa_config"]["topk"]
    t = len(seq)
    fwd = jax.jit(lambda params, seq, picks, select: keye_ref.forward(
        params, seq, config, held_range(config), rows=(t - n_dec - 1, t),
        picks=picks, select=select, dot=dot, idx_dot=idx_dot,
        index_rotary=index_rotary))
    ref, routers, account = jax.device_get(
        fwd(params, seq, got_picks, got_select))
    deficit, count, swapped = account                         # [L, T] each
    own = np.argsort(-routers, axis=-1)[..., :k]              # [L, T, k]
    r_swapped = (np.sort(own, -1) != np.sort(got_picks, -1)).any(-1)
    kth = np.sort(routers, axis=-1)[..., -k]
    lowest = np.take_along_axis(routers, got_picks, axis=-1).min(-1)
    scale = np.max(np.abs(ref))
    stride = n_dec // (len(got_logits) - 1)
    err = float(np.max(np.abs(got_logits - ref[::stride])) / scale)
    emitted = np.take_along_axis(ref[:n_dec], seq[t - n_dec:, None], axis=1)
    gap = float(np.max(ref[:n_dec].max(axis=1) - emitted[:, 0]) / scale)
    want = np.minimum(np.arange(t) + 1, topk)[None, :]
    return {"logit_err": err, "token_gap": gap,
            "swapped_share": float(r_swapped.mean()),
            "router_deficit": float((kth - lowest).max()),
            "choices": int(r_swapped.size),
            "select_deficit": float(deficit.max()),
            "select_swapped_share": float(swapped.sum() / count.sum()),
            "select_count_ok": bool((count == want).all())}


def check_against_reference(params, config: dict, prompts, served) -> dict:
    """What `serve_for_check` got for `prompts`, against the reference."""
    spec = config["check"]
    res = []
    for prompt, toks, lg, pk, sel in zip(prompts, *served):
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        res.append(compare_with_reference(
            params, config, seq, spec["decode_positions"], lg, pk, sel))
    worst = max(r["logit_err"] for r in res)
    gap = max(r["token_gap"] for r in res)
    deficit = max(r["router_deficit"] for r in res)
    select = max(r["select_deficit"] for r in res)
    counted = all(r["select_count_ok"] for r in res)
    return {"ok": bool(np.isfinite(worst) and worst <= spec["logit_tol"]
                       and gap <= 2 * spec["logit_tol"]
                       and deficit <= spec["router_margin"]
                       and select <= spec["select_margin"] and counted),
            "logit_err": worst, "logit_errs": [r["logit_err"] for r in res],
            "token_gap": gap, "router_deficit": deficit,
            "router_swapped_share": [r["swapped_share"] for r in res],
            "router_choices": sum(r["choices"] for r in res),
            "select_deficit": select,
            "select_deficits": [r["select_deficit"] for r in res],
            "select_swapped_share": [r["select_swapped_share"] for r in res],
            "select_count_ok": counted}


def run(job) -> dict:
    from idc_models_tpu.observe import trace as ptrace

    config, engine = job.config, job.config["engine"]
    mix, judge = job.traffic["open_loop"], job.traffic["judge"]
    model_spec(config)          # a program without the layer kind ends here
    harness.note("making the weights on the device")
    params = make_params(config, job.seed)
    harness.note("building and warming the server")
    server = build_server(params, config, engine)
    harness.note("server warm")

    t_open = mix["warmup_s"]
    t_close = t_open + job.seconds
    t_stop = t_close + mix["drain_s"]
    arrivals = steady_trace(
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
                             config["check"]["decode_positions"],
                             engine["window"])
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
