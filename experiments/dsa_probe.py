"""Times, on the chip, the pieces a learned-sparse-attention fold can be
built from, at the served shapes (16 slots, 32,768 positions, 2,048
selected, 4 KV heads of 128, one 64-wide index key), and the decode
window built from them. Appends to chiprun_out/dsa_probe.jsonl; PERF.md
section 6 (PRs 34 and 35) has the readings. One section a process (a
chip belongs to one process at a time):

    chiprun --chips 1 -- python experiments/dsa_probe.py pieces
    chiprun --chips 1 -- python experiments/dsa_probe.py rows
    chiprun --chips 1 -- python experiments/dsa_probe.py window [ROOT [GROUP]]

`pieces` (PR 34): which top-k, which gather. `rows` (PR 35): how
`lax.top_k` and the row gather scale at 4, 8 and 16 rows (this chose
`ring_decode._FOLD_GROUP`), and one gather of 2 KiB rows, K and V as
one row. `window`: the served model's decode window of 8 steps with 1
to 16 of 16 slots live, through `SlotEngine` of the checkout at ROOT
(default: this one; a copy of the parent commit reads the other side),
with `ring_decode._FOLD_GROUP` set to GROUP where that is given.
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


def pieces(note):
    key = jax.random.key(0)
    x16 = jax.random.normal(key, (S, T), jnp.float32)
    x512 = jax.random.normal(key, (C, T), jnp.float32)
    kc = jax.random.normal(key, (S, T, G, D), jnp.bfloat16)
    kc3 = kc.reshape(S, T, G * D)
    ic = jax.random.normal(key, (S, T, DI), jnp.bfloat16)
    qi = jax.random.normal(key, (S, J, DI), jnp.bfloat16)

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


def by_rows(note):
    """`lax.top_k` and the gather of the selected rows for the first 4,
    8 and 16 rows of the batch, the caches whole; and 16 x 2,048 rows of
    2 KiB from a cache that keeps K and V as one row."""
    key = jax.random.key(0)
    x16 = jax.random.normal(key, (S, T), jnp.float32)
    kc = jax.random.normal(key, (S, T, G, D), jnp.bfloat16)
    topk = jax.jit(lambda x: lax.top_k(x, K))
    idx = topk(x16)[1]
    gather = jax.jit(lambda c, ids, i: c[ids[:, None], i])
    for n in (4, 8, 16):
        ids = jnp.arange(n)
        note(f"top_k[{n},{T}]", timed(topk, x16[:n], n=20))
        note(f"gather {n} x {K} rows of 1 KiB", timed(
            gather, kc, ids, idx[:n], n=20))
    kv = jax.random.normal(key, (S, T, 2 * G, D), jnp.bfloat16)
    note(f"gather {S} x {K} rows of 2 KiB (K and V as one row)", timed(
        gather, kv, jnp.arange(S), idx, n=20))


# the published keys `lm.keye_spec` reads, at the cell's cut: 8 of 48
# layers, 16 of 128 experts held, an eighth of the vocabulary
KEYE = {"num_hidden_layers": 8, "hidden_size": 2048, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "rope_theta": 1e7, "num_experts": 128,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "topk": K}}
VOCAB, WINDOW, PROMPT, BUDGET = 18992, 8, 8192, 1024


def window(note, root, group=None):
    """ms of one decode window of 8 steps with the first n of 16 slots
    live (prompts of 8,192 tokens), n rising as slots are admitted."""
    from idc_models_tpu import ring_decode
    from idc_models_tpu.models import lm
    from idc_models_tpu.serve.engine import SlotEngine

    if group is not None:
        ring_decode._FOLD_GROUP = group

    spec = lm.keye_spec(KEYE, held=(0, 16))
    params = jax.jit(lambda k: lm.init_params(
        spec, VOCAB, k, expert_dim=768))(jax.random.key(0))
    eng = SlotEngine(params, spec=spec, t_max=T, n_slots=S,
                     prefill_chunk=C, cache_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    live = 0
    for n in (1, 4, 5, 7, 8, 9, 12, 16):
        while live < n:
            eng.admit(live, rng.integers(0, VOCAB, PROMPT).astype(np.int32),
                      BUDGET)
            live += 1
        eng.step_window(WINDOW)
        t0 = time.perf_counter()
        for _ in range(8):
            eng.step_window(WINDOW)
        note(f"window of {WINDOW} steps, {n} of {S} live",
             (time.perf_counter() - t0) / 8 * 1e3, root=root, group=group)


def main():
    section = sys.argv[1] if len(sys.argv) > 1 else "pieces"
    root = sys.argv[2] if len(sys.argv) > 2 else "."
    sys.path.insert(0, str(Path(root).resolve()))
    out = Path(__file__).resolve().parent.parent / "chiprun_out"
    out.mkdir(exist_ok=True)

    def note(name, ms, **kw):
        row = {"section": section, "name": name, "ms": ms, **kw}
        print(row, flush=True)
        with open(out / "dsa_probe.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")

    if section == "window":
        window(note, root, int(sys.argv[3]) if len(sys.argv) > 3 else None)
    else:
        {"pieces": pieces, "rows": by_rows}[section](note)


if __name__ == "__main__":
    main()
