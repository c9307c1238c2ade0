"""Runner for `open_loop` traffic over a model of latent attention whose
layers attend a selection of positions, made by their own indexer or
taken from an earlier layer's (`models/lm.Latent`, `LayerSpec.shares`):
the GLM-5.2 configuration's `LMServer`, under the replay loop, the
per-request bookkeeping, the window reduction, the arrival stretches and
the steadied lengths of `serve_open_loop` / `serve_moe_open_loop` /
`serve_dsa_open_loop` (imported unchanged). Its own: the spec, the
parameter tree, and the comparison that decides `correct`.

That comparison runs after the window, through the ENGINE the window
ran on and through its PROGRAMS (`serve_dsa_open_loop.serve_for_check`:
the chunk program into a slot, every slot filled, then whole windows of
the cell's `window` steps), against `benchmark/reference/glm_ref.py` on
the same weights upcast to float32 and the same held experts. The
reference takes the EXPANDED form of latent attention (keys and values
decompressed from the latent), the system the absorbed one. Compared:

- logits, and every emitted token against the reference's logits at its
  position (`token_gap`), as in the Keye cell;
- the router's picks: the engine hands them out at every position, the
  reference runs with them forced, and every expert picked has to score
  within `router_margin` of the reference's own 8th best in the score
  that picks them, sigmoid plus the correction bias;
- the selection of each layer that makes one: forced on the reference,
  whose own index scores have to place every attended position within
  `select_margin` of its own 2,048th best, and every query has to have
  attended exactly min(position + 1, topk) positions;
- each layer that shares a selection has to have attended EXACTLY the
  positions of the layer it shares (a structural check, no tolerance).
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark.lib import harness
from benchmark.reference import glm_ref
from benchmark.runners.serve_dsa_open_loop import serve_for_check, steady_trace
from benchmark.runners.serve_moe_open_loop import (_quiesce, arrival_segments,
                                                   check_prompts, held_range)
from benchmark.runners.serve_open_loop import Replay, reduce_rows

# query rows the reference takes at a time: its scores of a block are
# [heads, block, T] float32, half a GB at 16k positions
REF_BLOCK = 128


def model_spec(config: dict):
    from idc_models_tpu.models import lm

    if not hasattr(lm, "glm_spec"):
        raise SystemExit("this program serves no latent-attention layer "
                         "(models/lm.glm_spec is missing)")
    # the file's `n_routed_experts` counts the experts HELD (it is in
    # `reduced`); the router keeps the published width
    return lm.glm_spec(
        dict(config, n_routed_experts=config["n_routed_experts_published"]),
        held=held_range(config), param_dtype=config["param_dtype"])


def make_params(config: dict, seed: int):
    """The parameter tree on the device, in one jitted call from the
    seed: `lm.init_params` for the spec, then the file's `weight_gains`
    (see `changed` there). The dtype is checked against the file: every
    weight in it, the routers' correction biases in float32."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models import lm

    spec = model_spec(config)
    gains = config["weight_gains"]

    def init(key):
        params = lm.init_params(spec, config["vocab_size"], key,
                                mlp_dim=config["intermediate_size"],
                                expert_dim=config["moe_intermediate_size"])
        for i in range(len(spec.layers)):
            block = params[f"block{i}"]
            for name in ("wq_b", "wo"):
                block["mla"][name] = block["mla"][name] * gains[name]
            if "idx" in block:
                block["idx"]["wq"] = block["idx"]["wq"] * gains["wiq"]
            if "moe" in block:
                block["moe"]["router"] = block["moe"]["router"] * gains["router"]
                block["moe"]["bias"] = block["moe"]["bias"] * gains["bias"]
        return params

    params = jax.jit(init)(jax.random.key(seed))
    want = jnp.dtype(config["param_dtype"])
    leaves = jax.tree_util.tree_leaves_with_path(params)
    router_bias = lambda path: [p.key for p in path[-2:]] == ["moe", "bias"]
    got = {a.dtype for path, a in leaves if not router_bias(path)}
    biases = {a.dtype for path, a in leaves if router_bias(path)}
    if got != {want} or biases != {jnp.dtype(jnp.float32)}:
        raise SystemExit(f"parameters are {got} (biases {biases}), the "
                         f"configuration states {want}")
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


def selecting_layers(config: dict) -> tuple[list, dict]:
    """The layers that select for themselves, and for each that shares a
    selection, the layer it takes it from (the nearest selecting one
    before it)."""
    own, shares = [], {}
    for i, kind in enumerate(config["indexer_types"]):
        if kind == "full":
            own.append(i)
        else:
            shares[i] = own[-1]
    return own, shares


def compare_with_reference(params, config: dict, seq, n_dec: int,
                           got_logits, got_picks, got_select, prod=None,
                           fault=None) -> dict:
    """One prompt's comparison (see the module text): the reference runs
    once, with the system's experts and the selecting layers' positions
    forced everywhere. `got_select` holds every layer's attended
    positions as bits [layers, T, words]. Returns the logit error over
    the rows the system kept, `token_gap`, the router's and the
    indexer's swapped shares and deficits, whether every query attended
    as many positions as it has to, and whether each sharing layer
    attended exactly its source's positions. `prod` and `fault` go to
    the reference (its hooks for a lower-precision product and a planted
    fault: `tools/glm_check_faults.py`)."""
    import jax

    k, topk = config["num_experts_per_tok"], config["index_topk"]
    own, shares = selecting_layers(config)
    t = len(seq)
    fwd = jax.jit(lambda params, seq, picks, select: glm_ref.forward(
        params, seq, config, held_range(config), rows=(t - n_dec - 1, t),
        block=REF_BLOCK, picks=picks, select=select, prod=prod,
        fault=fault))
    ref, routers, account = jax.device_get(
        fwd(params, seq, got_picks, got_select[own]))
    deficit, count, swapped = account                         # [L, T] each
    r_own = np.argsort(-routers, axis=-1)[..., :k]            # [L, T, k]
    r_swapped = (np.sort(r_own, -1) != np.sort(got_picks, -1)).any(-1)
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
            "select_count_ok": bool((count == want).all()),
            "shared_ok": all(bool((got_select[i] == got_select[s]).all())
                             for i, s in shares.items())}


def check_against_reference(params, config: dict, prompts, served, **kw) -> dict:
    """What `serve_for_check` got for `prompts`, against the reference."""
    spec = config["check"]
    res = []
    for prompt, toks, lg, pk, sel in zip(prompts, *served):
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        res.append(compare_with_reference(
            params, config, seq, spec["decode_positions"], lg, pk, sel, **kw))
    worst = max(r["logit_err"] for r in res)
    gap = max(r["token_gap"] for r in res)
    deficit = max(r["router_deficit"] for r in res)
    select = max(r["select_deficit"] for r in res)
    counted = all(r["select_count_ok"] for r in res)
    shared = all(r["shared_ok"] for r in res)
    return {"ok": bool(np.isfinite(worst) and worst <= spec["logit_tol"]
                       and gap <= 2 * spec["logit_tol"]
                       and deficit <= spec["router_margin"]
                       and select <= spec["select_margin"] and counted
                       and shared),
            "logit_err": worst, "logit_errs": [r["logit_err"] for r in res],
            "token_gap": gap, "router_deficit": deficit,
            "router_deficits": [r["router_deficit"] for r in res],
            "router_swapped_share": [r["swapped_share"] for r in res],
            "router_choices": sum(r["choices"] for r in res),
            "select_deficit": select,
            "select_deficits": [r["select_deficit"] for r in res],
            "select_swapped_share": [r["select_swapped_share"] for r in res],
            "select_count_ok": counted, "shared_ok": shared}


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
