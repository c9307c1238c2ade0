"""The one traffic generator. A mix is a data file of parameters
(`benchmark/traffic/<mix>.json`); this module turns it and a seed into
arrivals. The mechanism is `serve/api.py::poisson_trace`'s (seeded,
open loop); the length distributions come from the mix file instead of
being uniform.

A mix file's `open_loop` fields:

  rate_per_s        arrivals per second
  arrivals          "poisson" (default): a Poisson stream conditioned on
                    its count; "stratified": one arrival in every 1/rate
                    seconds, placed uniformly inside its own stretch (see
                    `arrival_times`)
  burst_at_start    arrivals at t = 0, before the Poisson stream (fills
                    the slots so warm-up is short); default 0
  bursts            optional {"every_s", "size"}: `size` extra arrivals at
                    each multiple of `every_s`
  prompt_len,       {"dist": "lognormal", "median", "sigma", "min", "max"}
  output_len        | {"dist": "uniform", "min", "max"}
                    | {"dist": "fixed", "value"}
  prefix_sharing    optional {"share", "prefix_len", "n_prefixes"}: that
                    share of prompts starts with one of `n_prefixes`
                    fixed prefixes of `prefix_len` tokens
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Arrival(NamedTuple):
    due_s: float
    rid: str
    prompt: tuple
    max_new_tokens: int


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """`n` whole lengths from one distribution spec, clipped to its range.

    Stratified: the lengths are the distribution's values at `n` evenly
    spaced quantiles (each jittered inside its stratum), in an order the
    seed shuffles. Every seed so draws the same amount of work from the
    same distribution, and differs in which request gets which length."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    u = rng.permutation((np.arange(n) + rng.random(n)) / max(n, 1))
    if dist == "uniform":
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(np.int64)
    if dist == "lognormal":
        z = np.sqrt(2.0) * _erfinv(2.0 * np.clip(u, 1e-9, 1 - 1e-9) - 1.0)
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def _erfinv(y: np.ndarray) -> np.ndarray:
    """Inverse error function (Giles' single-precision polynomial, good
    to 1e-6: lengths are rounded to whole tokens)."""
    w = -np.log((1.0 - y) * (1.0 + y))
    small = w < 5.0
    ws, wl = w - 2.5, np.sqrt(np.maximum(w, 5.0)) - 3.0
    ps = 2.81022636e-08
    for c in (3.43273939e-07, -3.5233877e-06, -4.39150654e-06, 0.00021858087,
              -0.00125372503, -0.00417768164, 0.246640727, 1.50140941):
        ps = c + ps * ws
    pl = -0.000200214257
    for c in (0.000100950558, 0.00134934322, -0.00367342844, 0.00573950773,
              -0.0076224613, 0.00943887047, 1.00167406, 2.83297682):
        pl = c + pl * wl
    return np.where(small, ps, pl) * y


def arrival_times(rng: np.random.Generator, mix: dict, t0: float,
                  t1: float) -> np.ndarray:
    """Due times in [t0, t1): round(rate x length) arrivals, so that the
    offered load is the same under every seed.

    "poisson": placed independently and uniformly, which is what a
    Poisson stream looks like given how many came; gaps and bunches are
    a Poisson stream's. "stratified": the stretch is cut into as many
    equal parts as there are arrivals and each part gets one, placed
    uniformly inside it; neighbours can still fall together or two gaps
    apart, but no long bunch or lull can form. That is what a tail
    measured over a hundred-odd requests needs in order to repeat: under
    Poisson arrivals at 0.8 of the knee the 90th percentile of the time
    to first token moved by 18% and 65% between runs (my chip runs,
    PR 22). Periodic bursts, if the mix has them, come on top."""
    n = int(round(mix["rate_per_s"] * (t1 - t0)))
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        parts = [t0 + (t1 - t0) * rng.random(n)]
    elif kind == "stratified":
        parts = [t0 + (t1 - t0) * (np.arange(n) + rng.random(n)) / max(n, 1)]
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    bursts = mix.get("bursts")
    if bursts:
        every = bursts["every_s"]
        at = np.arange(np.ceil(t0 / every) * every, t1, every)
        parts.append(np.repeat(at[at > 0], int(bursts["size"])))
    return np.sort(np.concatenate(parts), kind="stable")


def open_loop_trace(mix: dict, *, seed: int, segments, vocab: int,
                    t_max: int) -> list[Arrival]:
    """Arrivals over consecutive `segments` [(t0, t1), ...] (warm-up,
    window, drain): the same seed gives the same trace. Each segment has
    its own fixed count and its own stratified lengths, so the window's
    work does not depend on what the seed put into the warm-up. The
    start burst is due at the first segment's start. Lengths are clamped
    so that prompt + output fits `t_max`."""
    rng = np.random.default_rng(seed)
    due, p_lens, budgets = [], [], []
    for i, (t0, t1) in enumerate(segments):
        t = arrival_times(rng, mix, t0, t1)
        if i == 0:
            t = np.concatenate([np.full(int(mix.get("burst_at_start", 0)), t0), t])
        due.append(t)
        p_lens.append(draw_lengths(rng, mix["prompt_len"], len(t)))
        budgets.append(draw_lengths(rng, mix["output_len"], len(t)))
    due, p_lens, budgets = (np.concatenate(x) for x in (due, p_lens, budgets))
    n = len(due)
    p_lens = np.minimum(p_lens, t_max - 1)
    budgets = np.minimum(budgets, t_max - p_lens)
    share = mix.get("prefix_sharing") or {}
    prefixes = rng.integers(0, vocab, (int(share.get("n_prefixes", 0)),
                                       int(share.get("prefix_len", 0))))
    shared = rng.random(n) < float(share.get("share", 0.0))
    which = rng.integers(0, max(len(prefixes), 1), n)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(p_lens[i]))
        if shared[i] and len(prefixes):
            k = min(prefixes.shape[1], len(toks) - 1)
            toks[:k] = prefixes[which[i], :k]
        out.append(Arrival(float(due[i]), f"r{i}",
                           tuple(int(x) for x in toks), int(budgets[i])))
    return out
