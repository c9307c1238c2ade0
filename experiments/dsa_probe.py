"""Times, on the chip, the pieces a learned-sparse-attention fold can be
built from, at the served shapes (16 slots, 32,768 positions, 2,048
selected, 4 KV heads of 128, one 64-wide index key), and the decode
window built from them. Appends to chiprun_out/dsa_probe.jsonl; PERF.md
section 6 (PRs 34 and 35) has the readings. One section a process (a
chip belongs to one process at a time):

    chiprun --chips 1 -- python experiments/dsa_probe.py pieces
    chiprun --chips 1 -- python experiments/dsa_probe.py rows
    chiprun --chips 1 -- python experiments/dsa_probe.py window [ROOT [GROUP]]
    python experiments/dsa_probe.py decode_select

`pieces` (PR 34): which top-k, which gather. `rows` (PR 35): how
`lax.top_k` and the row gather scale at 4, 8 and 16 rows (this chose
`ring_decode._FOLD_GROUP`), and one gather of 2 KiB rows, K and V as
one row. `window`: the served model's decode window of 8 steps with 1
to 16 of 16 slots live, through `SlotEngine` of the checkout at ROOT
(default: this one; a copy of the parent commit reads the other side),
with `ring_decode._FOLD_GROUP` set to GROUP where that is given.
`select`, run the same way: the chunk fold's top-k selection alone,
the counting passes in XLA that the fold ran before against the Pallas
kernel that replaced them, at three frontiers and 8 to 128 query rows a
grid cell (this chose `ops.topk_select.ROWS`). `decode_select`: the
decode fold's selection for a trip of 8 rows, `lax.top_k` against the
kernel with its choice compacted to positions (this chose how the
decode fold gets its positions).
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


def parent_sortable(x):
    """The sort-free selection of the chunk fold before its kernel
    (`ring_decode._sortable`, `_bisect`, `_select_topk` and `pack_bits`
    as they were), the other side of `select`."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 0, u | jnp.uint32(0x80000000), ~u)


def parent_bisect(count, want, bits):
    def bit(i, cur):
        cand = cur | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(count(cand) >= want, cand, cur)

    return lax.fori_loop(0, bits, bit, jnp.zeros(want.shape, jnp.uint32))


def parent_select_topk(score, k, frontier, blk):
    n, t = score.shape
    keys = parent_sortable(score)
    pos = jnp.arange(t, dtype=jnp.uint32)

    def count(test):
        def body(j, acc):
            kb = lax.dynamic_slice_in_dim(keys, j * blk, blk, axis=1)
            pb = lax.dynamic_slice_in_dim(pos, j * blk, blk)
            return acc + jnp.sum(test(kb, pb), axis=1, dtype=jnp.int32)

        trips = (jnp.clip(frontier, 0, t) + blk - 1) // blk
        return lax.fori_loop(0, trips, body, jnp.zeros(n, jnp.int32))

    want = jnp.full(n, k, jnp.int32)
    kth = parent_bisect(lambda v: count(lambda kb, _: kb >= v[:, None]),
                        want, 32)
    room = k - count(lambda kb, _: kb > kth[:, None])
    last = parent_bisect(
        lambda v: -count(lambda kb, pb: (kb == kth[:, None])
                         & (pb[None, :] < v[:, None])),
        1 - room, max(1, (t - 1).bit_length()))
    return (((keys > kth[:, None])
             | ((keys == kth[:, None]) & (pos[None, :] <= last[:, None])))
            & (score > -jnp.inf))


def parent_pack_bits(mask):
    t = mask.shape[-1]
    mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, -t % 32)])
    bits = mask.reshape(*mask.shape[:-1], -1, 32).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def select(note):
    """The chunk fold's selection alone: a chunk of C = 512 queries whose
    last ends at `frontier` (query c sees positions up to frontier - C +
    c), scores [512, 32,768] float32, the parent's counting passes in
    XLA (blocks of 2,048, then `pack_bits`) against the kernel
    (`ops/topk_select.py`) at 8 to 128 rows a grid cell and 16k or 32k
    keys a trip of a counting pass; `ties`:
    scores of seven values, so every row runs its tie passes. The two
    must give the same bits."""
    from idc_models_tpu.ops import topk_select

    parent = jax.jit(lambda s, f: parent_pack_bits(
        parent_select_topk(s, K, f, 2048)))
    for frontier, kind in ((2048, "normal"), (8192, "normal"),
                           (28672, "normal"), (8192, "ties")):
        key = jax.random.key(frontier)
        if kind == "ties":
            x = jax.random.randint(key, (C, T), -3, 4).astype(jnp.float32)
        else:
            x = jax.random.normal(key, (C, T), jnp.float32)
        see = (jnp.arange(T)[None, :]
               <= frontier - C + jnp.arange(C)[:, None])
        x = jnp.where(see, x, -jnp.inf)
        f = jnp.int32(frontier)
        want = parent(x, f)
        note(f"parent passes, frontier {frontier} {kind}",
             timed(parent, x, f, n=5), frontier=frontier)
        for rows, span in ((8, 32768), (16, 32768), (32, 32768),
                           (64, 32768), (128, 32768), (64, 16384)):
            topk_select.ROWS, topk_select._SPAN = rows, span
            kern = jax.jit(lambda s, f: topk_select.select_topk_bits(
                s, K, f))
            same = bool((kern(x, f) == want).all())
            note(f"kernel rows {rows} span {span}, frontier {frontier} "
                 f"{kind}", timed(kern, x, f, n=20), frontier=frontier,
                 rows=rows, span=span, equal=same)
        topk_select.ROWS, topk_select._SPAN = 64, 32768


def positions_by_gather(words, k):
    """`topk_select._positions` with XLA's gathers in place of its
    one-hot products (the other side of `decode_select`)."""
    pop = lax.population_count(words).astype(jnp.int32)
    ends = jnp.cumsum(pop, axis=1)
    j = jnp.arange(k, dtype=jnp.int32)
    word = jax.vmap(lambda e: jnp.searchsorted(e, j, side="right"))(ends)
    at = jnp.minimum(word, words.shape[1] - 1)
    x = jnp.take_along_axis(words, at, axis=1)
    rank = j - jnp.take_along_axis(ends - pop, at, axis=1)
    bit = jnp.zeros_like(rank)
    for half in (16, 8, 4, 2, 1):
        below = lax.population_count(x & ((1 << half) - 1)).astype(jnp.int32)
        up = rank >= below
        rank = jnp.where(up, rank - below, rank)
        x = jnp.where(up, x >> half, x)
        bit = jnp.where(up, bit + half, bit)
    return jnp.where(j < ends[:, -1:], 32 * word + bit, -1)


def decode_select(note):
    """The decode fold's selection for ONE trip of 8 live rows at the
    served length, ms a trip: 64 trips in one `fori_loop`, each taking
    8 of 16 rows of [16, 32,768] float32 scores (`score[at]`, as the
    fold does; the rows end at random positions below the frontier, one
    of them at it) and selecting 2,048 of each: `lax.top_k` (what the
    fold ran) against the kernel with its choice compacted to positions
    (`ops.topk_select.select_topk_positions`: its words compacted in
    XLA by one-hot products), beside
    the row gather alone, the kernel's bits alone, the compaction by
    XLA's gathers instead of one-hot products, and the compaction alone
    (from words computed once); `zeros`: scores of a relu times a head
    weight of either sign, so that the tie passes run. The positions
    must equal `lax.top_k`'s, sorted."""
    from idc_models_tpu.ops import topk_select as ts

    trips = 64

    def looped(select):
        def run(x, f):
            def body(i, acc):
                at = (i * 8 + jnp.arange(8)) % S
                return acc + jnp.sum(select(x[at], f, at))
            return lax.fori_loop(0, trips, body, jnp.int32(0))
        return jax.jit(run)

    variants = {name: looped(fn) for name, fn in {
        "row gather alone": lambda s, f, at: (s > 0).astype(jnp.int32),
        "lax.top_k": lambda s, f, at: lax.top_k(s, K)[1],
        "kernel bits": lambda s, f, at: ts.select_topk_bits(s, K, f)
        .astype(jnp.int32),
        "kernel + positions": lambda s, f, at: ts.select_topk_positions(
            s, K, f),
        "kernel + positions by XLA gathers": lambda s, f, at:
            positions_by_gather(ts._select_words(s, K, f, None, "probe")
                                [:, :T // 32], K),
    }.items()}
    compact = looped(lambda words, f, at: ts._positions(words, K))
    words_of = jax.jit(lambda s, f: ts._select_words(s, K, f, None, "probe"))
    # frontiers 2,048 / 8,192 / 16,384 / 28,672 at the served length
    for frontier, kind in ((T // 16, "normal"), (T // 4, "normal"),
                           (T // 2, "normal"), (T * 7 // 8, "normal"),
                           (T // 2, "zeros")):
        key = jax.random.key(frontier)
        x = jax.random.normal(key, (S, T), jnp.float32)
        if kind == "zeros":
            x = jnp.maximum(x, 0) * jnp.where(
                jax.random.bernoulli(jax.random.key(1), 0.5, (S, T)), 1.0, -1.0)
        ends = jax.random.randint(jax.random.key(2), (S,), frontier // 2,
                                  frontier).at[3].set(frontier - 1)
        x = jnp.where(jnp.arange(T)[None, :] <= ends[:, None], x, -jnp.inf)
        f = jnp.int32(frontier)
        at = jnp.arange(8)
        got = np.asarray(jax.jit(lambda s, f: ts.select_topk_positions(
            s, K, f))(x[at], f))
        top, idx = lax.top_k(x[at], K)
        want = np.where(np.asarray(top) > -np.inf, np.asarray(idx), T + 1)
        want = np.sort(want, axis=1)
        want = np.where(want > T, -1, want)
        same = bool((got == want).all())
        for name, fn in variants.items():
            note(f"{name}, frontier {frontier} {kind}",
                 timed(fn, x, f, n=5) / trips, frontier=frontier,
                 kind=kind, equal=same)
        note(f"positions alone, frontier {frontier} {kind}",
             timed(compact, words_of(x, f), f, n=5) / trips,
             frontier=frontier, kind=kind)


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
        {"pieces": pieces, "rows": by_rows, "select": select,
         "decode_select": decode_select}[section](note)


if __name__ == "__main__":
    main()
