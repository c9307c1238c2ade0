"""Times, on the chip, the pieces a learned-sparse-attention fold can be
built from, at the served shapes (16 slots, 32,768 positions, 2,048
selected, 4 KV heads of 128, one 64-wide index key): which top-k, which
gather. Writes chiprun_out/dsa_probe.jsonl; PERF.md section 6 (PR 34)
has the readings.

    chiprun --chips 1 -- python experiments/dsa_probe.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

S, T, K, G, D, DI, J, C = 16, 32768, 2048, 4, 128, 64, 16, 512


def timed(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def sortable(x):
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 0, u | jnp.uint32(0x80000000), ~u)


def kth_key(keys, k):
    """Largest K with count(keys >= K) >= k, per row, bit by bit."""
    def body(i, cur):
        cand = cur | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, cur)

    return lax.fori_loop(0, 32, body,
                         jnp.zeros(keys.shape[0], jnp.uint32))


def select_bisect(x, k):
    keys = sortable(x)
    kth = kth_key(keys, k)
    sel = keys >= kth[:, None]
    rank = jnp.cumsum(sel.astype(jnp.int32), axis=1)
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    idx = jax.vmap(lambda r: jnp.searchsorted(r, want, side="left"))(rank)
    return idx.astype(jnp.int32)


def main():
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    rows = []
    key = jax.random.key(0)
    x16 = jax.random.normal(key, (S, T), jnp.float32)
    x512 = jax.random.normal(key, (C, T), jnp.float32)
    kc = jax.random.normal(key, (S, T, G, D), jnp.bfloat16)
    kc3 = kc.reshape(S, T, G * D)
    ic = jax.random.normal(key, (S, T, DI), jnp.bfloat16)
    qi = jax.random.normal(key, (S, J, DI), jnp.bfloat16)

    def note(name, ms, **kw):
        rows.append({"name": name, "ms": ms, **kw})
        print(rows[-1], flush=True)

    topk = jax.jit(lambda x: lax.top_k(x, K))
    note("top_k[16,32768]", timed(topk, x16))
    note("top_k[512,32768]", timed(topk, x512, n=3))
    approx = jax.jit(lambda x: lax.approx_max_k(x, K, recall_target=0.99))
    note("approx_max_k[16,32768]", timed(approx, x16))
    bis = jax.jit(lambda x: select_bisect(x, K))
    note("bisect+cumsum+search[16,32768]", timed(bis, x16))
    a = np.sort(np.asarray(topk(x16)[1]), axis=1)
    b = np.asarray(bis(x16))
    note("bisect equals top_k", float((a == b).mean()))
    kth = jax.jit(lambda x: kth_key(sortable(x), K))
    note("kth_bisect[512,32768]", timed(kth, x512, n=3))
    note("kth_bisect[512,8192]", timed(kth, x512[:, :8192], n=3))
    srt = jax.jit(lambda x: jnp.sort(x, axis=1))
    note("sort[512,32768]", timed(srt, x512, n=3))
    note("sort[16,32768]", timed(srt, x16))

    idx = topk(x16)[1]
    idx_sorted = jnp.sort(idx, axis=1)
    rows_ix = np.arange(S)[:, None]
    g4 = jax.jit(lambda c, i: c[rows_ix, i])
    note("gather 4-D rows, unsorted", timed(g4, kc, idx))
    note("gather 4-D rows, sorted", timed(g4, kc, idx_sorted))
    note("gather merged rows, sorted", timed(g4, kc3, idx_sorted))
    tk = jax.jit(lambda c, i: jnp.take_along_axis(
        c, i[:, :, None], axis=1))
    note("take_along_axis merged", timed(tk, kc3, idx_sorted))
    vm = jax.jit(jax.vmap(lambda c, i: jnp.take(c, i, axis=0)))
    note("vmap take 4-D", timed(vm, kc, idx_sorted))
    dense = jax.jit(lambda q, c: jnp.einsum(
        "bhd,bkgd->bhk", q, c, preferred_element_type=jnp.float32))
    q = jax.random.normal(key, (S, G, D), jnp.bfloat16)
    note("dense scores over all K rows (G heads)", timed(dense, q, kc))
    isc = jax.jit(lambda q, c: jnp.einsum(
        "bjd,bkd->bjk", q, c, preferred_element_type=jnp.float32))
    note("index scores [16,16,64]x[16,T,64]", timed(isc, qi, ic))
    (out / "dsa_probe.jsonl").write_text(
        "\n".join(json.dumps(r) for r in rows) + "\n")


if __name__ == "__main__":
    main()
