"""Runner for `open_loop` traffic: an `LMServer` under arrivals at a fixed
rate, replayed on the wall clock.

The replay loop is a copy of `LMServer.run(realtime=True)` (submit what is
due, then `step()`; a full queue blocks the generator), kept here so that
it can stamp each request's due time and submit time: an open loop times
a request from when it was due, not from when the loop got round to
offering it. First-token time is submit time + `Result.ttft_ms`, done
time is submit time + `Result.latency_ms`.

Timeline of a run: warm-up arrivals (`warmup_s`), the measured window
(`--seconds`), then `drain_s` more with arrivals still coming, so that
requests due late in the window get their first token under the same load.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark.lib import harness, stats, traffic_gen
from benchmark.reference import gpt2_ref


def make_params(model_cfg: dict, seed: int):
    """The parameter tree on the device, in one jitted call from the
    seed: `attention_lm.init`, with biases and LayerNorm parameters (which
    it makes 0 and 1) perturbed so that the reference check sees them."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models.lm import attention_lm

    model = attention_lm(
        model_cfg["vocab_size"], model_cfg["n_positions"],
        embed_dim=model_cfg["embed_dim"], num_heads=model_cfg["num_heads"],
        mlp_dim=model_cfg["mlp_dim"], num_blocks=model_cfg["num_blocks"])

    def init(key):
        k_init, k_noise = jax.random.split(key)
        params = model.init(k_init).params
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(k_noise, len(leaves))
        leaves = [a + 0.02 * jax.random.normal(k, a.shape, a.dtype)
                  if a.ndim == 1 else a for a, k in zip(leaves, keys)]
        return jax.tree.unflatten(tree, leaves)

    params = jax.jit(init)(jax.random.key(seed))
    want = jnp.dtype(model_cfg["param_dtype"])
    got = {a.dtype for a in jax.tree.leaves(params)}
    if got != {want}:
        raise SystemExit(f"parameters are {got}, the configuration "
                         f"states {want}")
    return params


def build_server(params, model_cfg: dict, engine: dict):
    import jax.numpy as jnp

    from idc_models_tpu.serve import LMServer

    return LMServer(
        params, embed_dim=model_cfg["embed_dim"],
        num_heads=model_cfg["num_heads"], num_blocks=model_cfg["num_blocks"],
        t_max=engine["t_max"], n_slots=engine["n_slots"],
        window=engine["window"], cache_dtype=jnp.dtype(engine["cache_dtype"]),
        temperature=engine["temperature"],
        max_queue_depth=engine["max_queue_depth"],
        max_prefills_per_cycle=engine["max_prefills_per_cycle"],
        prefill_chunk=engine["prefill_chunk"], warmup=True)


class Replay:
    """The open-loop replay and its bookkeeping: per request the due,
    submit, first-token and done times on one clock (seconds since the
    replay began)."""

    def __init__(self, server, arrivals):
        from idc_models_tpu.serve import Request

        self.server = server
        self.arrivals = arrivals
        self.requests = [Request(id=a.rid, prompt=a.prompt,
                                 max_new_tokens=a.max_new_tokens)
                         for a in arrivals]
        self.submit_s: dict[str, float] = {}
        self.results: dict[str, object] = {}
        self.next = 0
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit_due(self) -> None:
        """Offer every request that is due. As in `LMServer.run` with
        `on_full="block"`, a full queue is not offered to: the head
        request waits for the next cycle and everything behind it waits
        too, and that lateness is the caller's to feel."""
        queue = self.server.scheduler.queue
        while (self.next < len(self.arrivals)
               and self.arrivals[self.next].due_s <= self.now()):
            if (len(queue) >= queue.max_depth
                    or not self.server.submit(self.requests[self.next])):
                return
            self.submit_s[self.arrivals[self.next].rid] = self.now()
            self.next += 1

    def cycle(self) -> None:
        with harness.annotate("bench.submit"):
            self.submit_due()
        if self.server.scheduler.idle() and self.next < len(self.arrivals):
            # nothing running and the next arrival is in the future
            gap = self.arrivals[self.next].due_s - self.now()
            if gap > 0:
                with harness.annotate("bench.wait_for_arrival"):
                    time.sleep(min(gap, 0.005))
                return
        with harness.annotate("bench.step"):
            for r in self.server.step():
                self.results[r.id] = r

    def run_until(self, t_end: float, on_cycle=None) -> None:
        while self.now() < t_end:
            self.cycle()
            if on_cycle is not None:
                on_cycle(self.now())

    def rows(self) -> list[dict]:
        """One row per submitted request, times in seconds on the replay
        clock; `first_s` / `done_s` are None until they happened."""
        out = []
        for a in self.arrivals[:self.next]:
            sub, r = self.submit_s[a.rid], self.results.get(a.rid)
            first = done = None
            if r is not None and r.ttft_ms is not None:
                first = sub + r.ttft_ms / 1e3
            if r is not None and r.latency_ms is not None:
                done = sub + r.latency_ms / 1e3
            out.append({
                "rid": a.rid, "due_s": a.due_s, "submit_s": sub,
                "first_s": first, "done_s": done,
                "prompt_len": len(a.prompt), "budget": a.max_new_tokens,
                "n_out": None if r is None else len(r.tokens),
                "status": None if r is None else r.status})
        return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce_rows(rows, arrivals, *, t_open: float, t_close: float,
                judge: str, slice_s: tuple[float, float]) -> dict:
    """The window's numbers from the per-request rows. `slice_s` is the
    stretch (the profiled slice) over which live cached positions are
    averaged."""
    window = t_close - t_open
    done = [r for r in rows if r["done_s"] is not None]
    bad = [r for r in done
           if r["status"] != "ok" or r["n_out"] != r["budget"]]
    # Output tokens of the requests delivered inside the window. In a
    # steady state what is in flight at either edge cancels.
    in_window_done = [r for r in done if t_open <= r["done_s"] < t_close]
    tokens = float(sum(r["n_out"] for r in in_window_done))
    tpot = [(r["done_s"] - r["first_s"]) / (r["n_out"] - 1) * 1e3
            for r in in_window_done
            if r["n_out"] and r["n_out"] >= 2 and r["first_s"] is not None]
    due = [r for r in rows if t_open <= r["due_s"] < t_close]
    # Requests due in the window that were never even submitted (the
    # generator was blocked to the end) have no row: they count as due.
    n_due = sum(1 for a in arrivals if t_open <= a.due_s < t_close)
    ttft = [(r["first_s"] - r["due_s"]) * 1e3 for r in due
            if r["first_s"] is not None]
    missing = n_due - len(ttft)
    late = [(r["submit_s"] - r["due_s"]) * 1e3 for r in due]
    # Live cached positions, averaged over the slice: what a decode step
    # has to read. A request holds its prompt from its first token on,
    # and about half its output on average until it is done.
    live_tok = live_slot = 0.0
    for r in done:
        if r["first_s"] is not None:
            ov = _overlap(r["first_s"], r["done_s"], *slice_s)
            live_tok += ov * (r["prompt_len"] + r["n_out"] / 2.0)
            live_slot += ov
    slice_len = slice_s[1] - slice_s[0]
    half = 0.5 * (t_open + t_close)
    ttft_half = [[(r["first_s"] - r["due_s"]) * 1e3 for r in due
                  if r["first_s"] is not None and (r["due_s"] < half) == first]
                 for first in (True, False)]
    out = {
        "window_s": window,
        "tokens_in_window": tokens,
        "out_tokens_per_s": tokens / window,
        "tpot_p50_ms": stats.median(tpot) if tpot else None,
        "tpot_samples": len(tpot),
        "ttft_samples": n_due,
        "ttft_missing": missing,
        "gen_late_p95_ms": stats.percentile(late, 95) if late else None,
        "gen_late_p50_ms": stats.median(late) if late else None,
        "live_tokens_mean": live_tok / slice_len,
        "live_slots_mean": live_slot / slice_len,
        "ttft_p50_first_half_ms": (stats.median(ttft_half[0])
                                   if ttft_half[0] else None),
        "ttft_p50_second_half_ms": (stats.median(ttft_half[1])
                                    if ttft_half[1] else None),
        "prompt_len_mean": (float(np.mean([r["prompt_len"] for r in due]))
                            if due else None),
        "bad_requests": len(bad),
        "done_requests": len(done),
    }
    if n_due:
        # a request with no first token counts as the largest value
        worst = max(ttft, default=0.0)
        worst = max(worst, (t_close - t_open) * 1e3)
        ttft_all = ttft + [worst] * missing
        out["ttft_mean_ms"] = sum(ttft_all) / len(ttft_all)
        out["ttft_p90_ms"] = stats.percentile(ttft_all, 90)
        out["ttft_p50_ms"] = stats.median(ttft_all)
    else:
        out["ttft_mean_ms"] = out["ttft_p90_ms"] = out["ttft_p50_ms"] = None
    if judge == "capacity":
        out["attempted"] = len(in_window_done)
        out["failed"] = sum(1 for r in in_window_done
                            if r["status"] != "ok" or r["n_out"] != r["budget"])
    else:
        out["attempted"] = n_due
        out["failed"] = missing + sum(
            1 for r in due if r["done_s"] is not None
            and (r["status"] != "ok" or r["n_out"] != r["budget"]))
    return out


def check_against_reference(params, model_cfg: dict, engine: dict,
                            spec: dict, seed: int) -> dict:
    """`Generator` (chunked prefill, then decoding one position at a time
    through the bf16 cache) against `gpt2_ref`'s full forward, on seeded
    prompts: logits compared at the last prompt position and at every
    decoded one. Tokens are not compared: with random weights the largest
    logit changes on rounding."""
    import jax.numpy as jnp

    from idc_models_tpu.models.lm import Generator

    gen = Generator(params, embed_dim=model_cfg["embed_dim"],
                    num_heads=model_cfg["num_heads"],
                    num_blocks=model_cfg["num_blocks"], t_max=engine["t_max"],
                    cache_dtype=jnp.dtype(engine["cache_dtype"]),
                    temperature=engine["temperature"],
                    prefill_chunk=engine["prefill_chunk"])
    rng = np.random.default_rng(seed)
    n_dec = spec["decode_positions"]
    pad_to = max(spec["prompt_lens"]) + n_dec
    worst, errs = 0.0, []
    for p_len in spec["prompt_lens"]:
        prompt = rng.integers(0, model_cfg["vocab_size"], p_len)
        logits, caches = gen.prefill(prompt[None].astype(np.int32))
        got, toks = [np.asarray(logits[0], np.float32)], []
        for i in range(n_dec):
            tok, logits, caches = gen.decode(caches, logits, p_len + i, 1)
            toks.append(int(tok[0, 0]))
            got.append(np.asarray(logits[0], np.float32))
        seq = np.zeros(pad_to, np.int32)
        seq[:p_len], seq[p_len:p_len + n_dec] = prompt, toks
        ref = np.asarray(gpt2_ref.forward(
            params, seq, num_heads=model_cfg["num_heads"],
            num_blocks=model_cfg["num_blocks"],
            rows=(p_len - 1, p_len + n_dec)))
        err = float(np.max(np.abs(np.stack(got) - ref)) / np.max(np.abs(ref)))
        errs.append(err)
        worst = max(worst, err)
    return {"ok": bool(np.isfinite(worst) and worst <= spec["logit_tol"]),
            "logit_err": worst, "logit_errs": errs}


def run(job) -> dict:
    from idc_models_tpu.observe import trace as ptrace

    model_cfg, engine = job.config["model"], job.config["engine"]
    mix, judge = job.traffic["open_loop"], job.traffic["judge"]
    harness.note("making the weights on the device")
    params = make_params(model_cfg, job.seed)
    harness.note("building and warming the server")
    server = build_server(params, model_cfg, engine)
    harness.note("server warm")

    t_open = mix["warmup_s"]
    t_close = t_open + job.seconds
    t_stop = t_close + mix["drain_s"]
    arrivals = traffic_gen.open_loop_trace(
        mix, seed=job.seed,
        segments=[(0.0, t_open), (t_open, t_close), (t_close, t_stop)],
        vocab=model_cfg["vocab_size"], t_max=engine["t_max"])
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
        # drain: arrivals keep coming, until every request due in the
        # window has its first token or the drain time is spent
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
    # one row per request, for whoever reads a run by hand
    (job.scratch / "rows.json").write_text(json.dumps(rows))
    red = reduce_rows(rows, arrivals, t_open=t_open, t_close=t_close,
                      judge=judge,
                      slice_s=(p_start, p_start + mix["profile_s"]))
    counters = {f"runner.{k}": v for k, v in red.items()}
    counters |= {f"summary.{k}": v for k, v in summary.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
    counters["runner.submitted"] = replay.next
    if red["prompt_len_mean"] is not None:
        # the last token of a prompt's chunks sees, on average, about
        # half the prompt plus half a chunk
        counters["runner.prefill_context_mean"] = 0.5 * (
            red["prompt_len_mean"] + engine["prefill_chunk"])

    # Correctness, outside the window. The server goes first: its caches
    # are most of the chip.
    del replay, server
    checks = check_against_reference(params, model_cfg, engine,
                                     job.config["check"], job.seed + 1)
    harness.note("checked against the reference")
    budgets_ok = red["bad_requests"] == 0 and red["done_requests"] > 0
    checks["budgets_ok"] = budgets_ok
    e2e = {"serve_out_tokens_per_s": red["out_tokens_per_s"],
           "ttft_mean_ms": red["ttft_mean_ms"],
           "ttft_p50_ms": red["ttft_p50_ms"],
           "ttft_p90_ms": red["ttft_p90_ms"],
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
