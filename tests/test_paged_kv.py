"""Paged KV memory (ISSUE 11) against its hard contracts:

1. INDIRECTION IS INVISIBLE — the page-table-indirect folds and the
   paged engine emit BIT-IDENTICAL tokens to the contiguous path
   (greedy and seeded sampling, chunk boundaries, slot recycling,
   speculative verify, prefix-cache hits) on a 1-device mesh, because
   the gathered logical view presents the same values in the same
   reduction order and the sampling/retirement math is the shared
   `_window_core`/`_verify_core`.
2. PAGES ARE SAFE — dead rows and foreign pages are bit-untouched,
   allocator refcounts balance across 100 recycles (no leak), shared
   prefix pages are never written, and page exhaustion mid-decode
   finishes or retries the starved request honestly without touching a
   neighbor's pages.
3. ZERO RECOMPILATION — mixed page-count traffic after warmup grows no
   jit cache (page tables are VALUES, not shapes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.ring_decode import (
    cache_shape, init_cache, make_batched_ring_decode, make_chunk_ring_decode,
    make_paged_batched_ring_decode, make_paged_chunk_ring_decode,
)
from idc_models_tpu.serve import (
    LMServer, PageAllocator, PagedPrefixCache, PrefixCache, Request,
    RetryPolicy, SlotEngine,
)

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2
PS, PAGES, CHUNK = 4, 24, 8          # the shared paged config


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _kw(mesh=None):
    return dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)


def _pkw():
    return dict(prefill_chunk=CHUNK, kv_page_size=PS, kv_pages=PAGES)


def _serial_tokens(gen, prompt, steps, *, rng=None):
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps, rng=rng)
    return toks.tolist()[0]


# -- fold level ----------------------------------------------------------


def _pool_from_rows(rows, pt, n_pages, ps):
    """Scatter contiguous [S, T, H, D] rows into a pool per the page
    table — the ground-truth inverse of the fold's gathered view."""
    s, t, h, d = rows.shape
    pool = np.zeros((n_pages, ps, h, d), rows.dtype)
    for b in range(s):
        for j in range(t // ps):
            if pt[b, j] >= 0:
                pool[pt[b, j]] = rows[b, j * ps:(j + 1) * ps]
    return pool


def _stored(rows):
    """Contiguous rows [S, T, H, D] in the form the contiguous folds
    store them (`cache_shape`: narrow heads merged into rows)."""
    return jnp.asarray(rows).reshape(cache_shape(*rows.shape))


def _by_head(cache, like):
    return np.asarray(cache).reshape(like.shape)


def _rows_from_pool(pool, pt, t):
    s, l = pt.shape
    ps = pool.shape[1]
    out = np.zeros((s, t) + pool.shape[2:], pool.dtype)
    for b in range(s):
        for j in range(l):
            if pt[b, j] >= 0:
                out[b, j * ps:(j + 1) * ps] = pool[pt[b, j]]
    return out


def test_paged_batched_fold_bitwise_matches_contiguous(devices):
    """One-token batched fold: with pages SCATTERED arbitrarily in the
    pool, live rows' appended K/V are bit-equal to the contiguous
    fold's and their outputs equal to float32 rounding (since PR 30 the
    contiguous fold contracts narrow heads over merged rows, the paged
    one head by head: the same terms summed in another order) — and
    dead rows' pages are bit-untouched."""
    mesh = meshlib.seq_mesh(1)
    S, H, D = 3, 2, 8
    rng = np.random.default_rng(0)
    kc = rng.normal(size=(S, SEQ, H, D)).astype(np.float32)
    vc = rng.normal(size=(S, SEQ, H, D)).astype(np.float32)
    pos = np.array([5, 0, 9], np.int32)
    live = np.array([True, True, False])
    # zero cache content beyond each row's position (the engine
    # invariant the visibility mask rides on)
    for b in range(S):
        kc[b, pos[b]:] = 0.0
        vc[b, pos[b]:] = 0.0
    q = rng.normal(size=(S, 1, H, D)).astype(np.float32)
    kt = rng.normal(size=(S, 1, H, D)).astype(np.float32)
    vt = rng.normal(size=(S, 1, H, D)).astype(np.float32)

    cfold = make_batched_ring_decode(mesh, jit=False)
    out_c, kc2, vc2 = cfold(_stored(kc), _stored(vc),
                            jnp.asarray(q), jnp.asarray(kt),
                            jnp.asarray(vt), pos, live)
    kc2, vc2 = _by_head(kc2, kc), _by_head(vc2, vc)

    # a scattered-but-valid page table: every row's logical pages land
    # on arbitrary distinct physical pages (pool oversized so an
    # unowned page exists)
    l_pages = SEQ // PS
    n_pg = S * l_pages + 4
    perm = rng.permutation(n_pg)[:S * l_pages]
    pt = perm.reshape(S, l_pages).astype(np.int32)
    kp = _pool_from_rows(kc, pt, n_pg, PS)
    vp = _pool_from_rows(vc, pt, n_pg, PS)
    # a poison page no slot owns: must come back bit-identical
    spare = [p for p in range(n_pg) if p not in set(perm.tolist())][0]
    kp[spare] = 7.25
    pfold = make_paged_batched_ring_decode(mesh, page_size=PS,
                                           jit=False)
    out_p, kp2, vp2 = pfold(jnp.asarray(kp), jnp.asarray(vp),
                            jnp.asarray(pt), jnp.asarray(q),
                            jnp.asarray(kt), jnp.asarray(vt), pos,
                            live)
    out_c, out_p = np.asarray(out_c), np.asarray(out_p)
    kp2, vp2 = np.asarray(kp2), np.asarray(vp2)
    # live rows equal (dead row's output is garbage in both paths)
    np.testing.assert_allclose(out_p[live], out_c[live], rtol=1e-6,
                               atol=1e-6)
    # appended pool content == appended contiguous content, logically
    assert np.array_equal(_rows_from_pool(kp2, pt, SEQ)[live],
                          np.asarray(kc2)[live])
    assert np.array_equal(_rows_from_pool(vp2, pt, SEQ)[live],
                          np.asarray(vc2)[live])
    # the dead row's pages and the unowned page are bit-untouched
    dead = 2
    for j in range(l_pages):
        assert np.array_equal(kp2[pt[dead, j]], kp[pt[dead, j]])
    assert np.array_equal(kp2[spare], kp[spare])


def test_paged_chunk_fold_bitwise_matches_contiguous(devices):
    """Chunk-prefill fold: splicing a chunk through the page table
    yields the same outputs and the same logical cache content as the
    contiguous chunk fold, including the ragged final chunk."""
    mesh = meshlib.seq_mesh(1)
    H, D, C = 2, 8, 8
    rng = np.random.default_rng(1)
    start, p_end = 8, 13                    # ragged: 5 real of 8
    kc = np.zeros((1, SEQ, H, D), np.float32)
    vc = np.zeros((1, SEQ, H, D), np.float32)
    kc[:, :start] = rng.normal(size=(1, start, H, D))
    vc[:, :start] = rng.normal(size=(1, start, H, D))
    q = rng.normal(size=(1, C, H, D)).astype(np.float32)
    kt = rng.normal(size=(1, C, H, D)).astype(np.float32)
    vt = rng.normal(size=(1, C, H, D)).astype(np.float32)

    cfold = make_chunk_ring_decode(mesh, jit=False)
    out_c, kc2, vc2 = cfold(_stored(kc), _stored(vc),
                            jnp.asarray(q), jnp.asarray(kt),
                            jnp.asarray(vt), np.int32(start),
                            np.int32(p_end))
    kc2 = _by_head(kc2, kc)

    l_pages = SEQ // PS
    pt = rng.permutation(PAGES)[:l_pages].reshape(1, l_pages)
    pt = pt.astype(np.int32)
    kp = _pool_from_rows(kc, pt, PAGES, PS)
    vp = _pool_from_rows(vc, pt, PAGES, PS)
    pfold = make_paged_chunk_ring_decode(mesh, page_size=PS, jit=False)
    out_p, kp2, vp2 = pfold(jnp.asarray(kp), jnp.asarray(vp),
                            jnp.asarray(pt), jnp.asarray(q),
                            jnp.asarray(kt), jnp.asarray(vt),
                            np.int32(start), np.int32(p_end))
    # real queries bit-equal (pad-tail outputs are garbage both sides)
    n_real = p_end - start
    assert np.array_equal(np.asarray(out_p)[:, :n_real],
                          np.asarray(out_c)[:, :n_real])
    got = _rows_from_pool(np.asarray(kp2), pt, SEQ)
    assert np.array_equal(got[:, :p_end], np.asarray(kc2)[:, :p_end])
    # positions past p_end never written (zeros in both)
    assert np.array_equal(got[:, p_end:], np.asarray(kc2)[:, p_end:])


def test_paged_fold_validation(devices):
    mesh = meshlib.seq_mesh(1)
    pfold = make_paged_batched_ring_decode(mesh, page_size=PS,
                                           jit=False)
    kp = jnp.zeros((PAGES, PS, 2, 8))
    pt = jnp.zeros((2, SEQ // PS), jnp.int32)
    q = jnp.zeros((2, 1, 2, 8))
    with pytest.raises(ValueError, match="page dim"):
        pfold(jnp.zeros((PAGES, PS + 1, 2, 8)), kp, pt, q, q, q,
              np.zeros(2, np.int32), np.ones(2, bool))
    with pytest.raises(ValueError, match="ONE token"):
        pfold(kp, kp, pt, jnp.zeros((2, 2, 2, 8)), q, q,
              np.zeros(2, np.int32), np.ones(2, bool))
    with pytest.raises(ValueError, match="one position per"):
        pfold(kp, kp, pt, q, q, q, np.zeros(3, np.int32),
              np.ones(2, bool))
    with pytest.raises(ValueError, match="scales"):
        pfold(kp, kp, pt, q, q, q, np.zeros(2, np.int32),
              np.ones(2, bool), jnp.zeros((PAGES, 2)))
    cfold = make_paged_chunk_ring_decode(mesh, page_size=PS, jit=False)
    with pytest.raises(ValueError, match="multiple of the page"):
        cfold(kp, kp, pt[:1], jnp.zeros((1, PS + 1, 2, 8)),
              jnp.zeros((1, PS + 1, 2, 8)), jnp.zeros((1, PS + 1, 2, 8)),
              np.int32(0), np.int32(0))


# -- allocator -----------------------------------------------------------


def test_page_allocator_refcounts_and_determinism():
    a = PageAllocator(8, 4)
    g1 = a.alloc(3)
    assert g1 == [0, 1, 2] and a.free_count() == 5
    assert a.alloc(6) is None and a.free_count() == 5   # no partial
    a.retain(g1[:1])
    assert a.release(g1) == 2                  # page 0 still shared
    assert a.refcount(0) == 1 and a.free_count() == 7
    assert a.release([0]) == 1 and a.free_count() == 8
    # lowest-free-first: a replayed sequence gets identical placement
    assert a.alloc(2) == [0, 1]
    with pytest.raises(ValueError):
        a.release([5])                         # free page
    with pytest.raises(ValueError):
        a.retain([5])
    with pytest.raises(ValueError):
        PageAllocator(0, 4)


# -- engine / server parity ----------------------------------------------


def test_paged_token_parity_and_no_recompile_greedy(devices, params):
    """The acceptance pair: mixed prompt lengths/budgets through a
    paged server — bit-identical to serial Generator calls, zero jit
    growth after the first wave (page COUNTS vary per request; they
    are values, not shapes), and every page returned at drain."""
    server = LMServer(params, n_slots=3, window=4, **_pkw(), **_kw())
    rng = np.random.default_rng(5)
    reqs = [Request(id=f"r{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + 2 * i)),
                    max_new_tokens=4 + (i % 5) * 2)
            for i in range(8)]
    server.run([(0.0, r) for r in reqs[:2]])
    sizes = server.engine.cache_sizes()
    server.run([(0.0, r) for r in reqs[2:]])
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)
    gen = Generator(params, **_kw())
    for r in reqs:
        got = server.poll(r.id)
        assert got is not None and got.status == "ok"
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert got.tokens == want, (r.id, got.tokens, want)
    assert server.engine._alloc.used_count() == 0
    s = server.summary()
    assert s["serve_kv_pages_total"] == PAGES
    assert 0 < s["serve_kv_pages_used_peak"] <= PAGES
    assert s["serve_kv_tokens_per_hbm_byte"] > 0


def test_paged_seeded_sampling_parity(devices, params):
    server = LMServer(params, n_slots=2, window=4, temperature=1.3,
                      top_k=4, **_pkw(), **_kw())
    rng = np.random.default_rng(9)
    reqs = [Request(id=f"s{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 4 + 3 * i)),
                    max_new_tokens=6, seed=100 + i)
            for i in range(4)]
    server.run([(0.0, r) for r in reqs])
    gen = Generator(params, temperature=1.3, top_k=4, **_kw())
    for r in reqs:
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens,
                              rng=jax.random.key(r.seed))
        assert server.poll(r.id).tokens == want, r.id


def test_paged_chunk_boundary_prompt_lengths(devices, params):
    """Prompt lengths straddling every boundary class: 1, C-1, C, C+1,
    a page-exact length, and the longest admissible prompt."""
    server = LMServer(params, n_slots=2, window=4, **_pkw(), **_kw())
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(3)
    for i, p_len in enumerate([1, CHUNK - 1, CHUNK, CHUNK + 1,
                               2 * PS, SEQ - 2]):
        prompt = tuple(int(x) for x in rng.integers(0, VOCAB, p_len))
        budget = min(3, SEQ - p_len)
        server.run([(0.0, Request(id=f"b{i}", prompt=prompt,
                                  max_new_tokens=budget))])
        want = _serial_tokens(gen, prompt, budget)
        assert server.poll(f"b{i}").tokens == want, p_len


def test_paged_slot_recycle_returns_every_page(devices, params):
    """100 admit/decode/release cycles through 2 slots: the free list
    returns to full every time (no leak), and the last request is
    still bit-identical to serial — recycled pages carry stale content
    that masking must keep invisible."""
    eng = SlotEngine(params, n_slots=2, **_pkw(), **_kw())
    eng.warmup(4)
    rng = np.random.default_rng(7)
    gen = Generator(params, **_kw())
    for i in range(100):
        slot = i % 2
        p_len = 3 + int(rng.integers(0, 8))
        prompt = rng.integers(0, VOCAB, p_len)
        eng.admit(slot, prompt, 2)
        got = []
        while not eng.finished(slot):
            got.extend(eng.step_window(2).get(slot, []))
        eng.release(slot)
        assert eng._alloc.used_count() == 0, i
        if i >= 98:
            assert got == _serial_tokens(gen, tuple(prompt), 2), i


def test_paged_spec_decode_parity(devices, params):
    """Speculative verify through the paged folds: repetitive traffic
    drafts and verifies, outputs stay bit-identical to serial."""
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=4, draft_order=2, **_pkw(), **_kw())
    gen = Generator(params, **_kw())
    reqs = [Request(id=f"p{i}", prompt=tuple([1, 2, 3, 1, 2, 3, 1, 2]),
                    max_new_tokens=10) for i in range(3)]
    server.run([(0.0, r) for r in reqs])
    for r in reqs:
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert server.poll(r.id).tokens == want, r.id
    # speculation genuinely ran (not a silent window fallback)
    assert server.summary()["serve_spec_verify_dispatches"] > 0


def test_paged_int8_deterministic_and_page_capacity(devices, params):
    """int8 pages: per-(page, head) scales are finer than the
    contiguous per-slot ones, so the gates are determinism (identical
    runs bit-identical), bounded drift vs the float paged engine, and
    the page-byte capacity ratio."""
    rng = np.random.default_rng(3)
    reqs = [Request(id=f"q{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 5 + i)),
                    max_new_tokens=6) for i in range(3)]
    outs = []
    for _ in range(2):
        srv = LMServer(params, n_slots=2, window=4, kv_dtype="int8",
                       **_pkw(), **_kw())
        srv.run([(0.0, r) for r in reqs])
        outs.append({r.id: tuple(srv.poll(r.id).tokens) for r in reqs})
    assert outs[0] == outs[1]
    f32 = SlotEngine(params, n_slots=2, **_pkw(), **_kw())
    i8 = SlotEngine(params, n_slots=2, kv_dtype="int8", **_pkw(),
                    **_kw())
    # int8 pages cost ~1/4 the f32 page (scales are the small +)
    assert f32.kv_page_bytes() / i8.kv_page_bytes() >= 3.0
    # drift check at the logits level: same request, final logits of
    # int8-paged close to f32-paged (the PR-4 int8 contract, per page)
    f32.admit(0, np.asarray(reqs[0].prompt), 4)
    i8.admit(0, np.asarray(reqs[0].prompt), 4)
    f32.step_window(4), i8.step_window(4)
    lf = np.asarray(f32._logits[0], np.float32)
    li = np.asarray(i8._logits[0], np.float32)
    assert np.max(np.abs(lf - li)) < 0.35 * max(np.max(np.abs(lf)), 1)


def test_paged_prefix_sharing_zero_copy_and_parity(devices, params):
    """Two requests sharing a 16-token prefix: the snapshot shares the
    FIRST request's pages (refcounted — no copies), the second request
    allocates fewer fresh pages, both outputs bit-identical to
    serial, and release + eviction return every page."""
    server = LMServer(params, n_slots=2, window=4, prefix_cache_mb=64.0,
                      **_pkw(), **_kw())
    eng = server.engine
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(11)
    pre = tuple(int(x) for x in rng.integers(0, VOCAB, 2 * CHUNK))
    r1 = Request(id="a", prompt=pre + (1, 2), max_new_tokens=4)
    server.run([(0.0, r1)])
    pc = eng.prefix_cache
    assert pc.n_snapshots >= 1 and pc.cached_pages() > 0
    # snapshot pages are SHARED refs on pool pages, not copies: the
    # deepest snapshot's pages are refcounted in the allocator
    shared_before = pc.cached_pages()
    used_between = eng._alloc.used_count()
    assert used_between == shared_before        # only the cache holds
    r2 = Request(id="b", prompt=pre + (3, 4, 5), max_new_tokens=4)
    server.run([(0.0, r2)])
    assert pc.hits >= 1
    for r in (r1, r2):
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert server.poll(r.id).tokens == want, r.id
    # a shared page held by cache + (released) slots: refcount balance
    # leaves exactly the cache's references at drain
    assert eng._alloc.used_count() == pc.cached_pages()
    # evict everything: the pool drains to empty
    freed = pc.reclaim(PAGES)
    assert freed == pc.cached_pages() or pc.n_snapshots == 0
    assert eng._alloc.used_count() == 0


def test_paged_prefix_reclaim_spares_slot_pinned_snapshots():
    """Pool-pressure reclaim ranks FREEABILITY above LRU: a snapshot
    whose pages live slots still share frees nothing and is never
    evicted by reclaim (destroying a hit-proven shared prefix for
    zero pages), while a freeable one goes regardless of its rank."""
    a = PageAllocator(8, 4)
    pc = PagedPrefixCache(CHUNK, max_pages=8)
    pc.bind(a, 64)
    slot1 = a.alloc(2)                  # a "live slot" holds these
    pc.insert([1] * CHUNK, slot1, np.zeros((1, 4), np.float32))
    pc.lookup([1] * CHUNK)              # hit-proven AND older
    slot2 = a.alloc(2)
    pc.insert([2] * CHUNK, slot2, np.zeros((1, 4), np.float32))
    a.release(slot2)                    # its slot finished: exclusive
    assert pc.reclaimable_pages() == 2
    assert pc.reclaim(1) == 2           # evicts the FREEABLE snapshot
    assert pc.n_snapshots == 1
    assert pc.lookup([1] * CHUNK)[0] == CHUNK    # pinned one survives
    # nothing else is freeable: reclaim refuses to destroy it
    assert pc.reclaim(4) == 0
    assert pc.n_snapshots == 1


def test_paged_prefix_cache_rebind_drops_stale_pages(devices, params):
    """Warm-restart: rebinding a populated paged cache to a NEW
    engine's allocator must drop every snapshot — the stored page ids
    name the dead pool's pages, and carrying them over would
    retain/corrupt pages the new allocator grants to live requests.
    The rebuilt server starts cold, re-warms, and stays
    bit-identical."""
    pc = PagedPrefixCache(CHUNK, max_pages=16)
    kw = _kw()
    srv_a = LMServer(params, n_slots=2, window=4, prefix_cache=pc,
                     **_pkw(), **kw)
    gen = Generator(params, **kw)
    rng = np.random.default_rng(29)
    pre = tuple(int(x) for x in rng.integers(0, VOCAB, 2 * CHUNK))
    srv_a.run([(0.0, Request(id="a", prompt=pre + (1,),
                             max_new_tokens=4))])
    assert pc.n_snapshots > 0
    srv_a.close()
    # the "crashed" engine is gone; a rebuilt server reuses the cache
    srv_b = LMServer(params, n_slots=2, window=4, prefix_cache=pc,
                     **_pkw(), **kw)
    assert pc.n_snapshots == 0 and pc.cached_pages() == 0   # cold
    r1 = Request(id="b1", prompt=pre + (2,), max_new_tokens=4)
    r2 = Request(id="b2", prompt=pre + (3,), max_new_tokens=4)
    srv_b.run([(0.0, r1)])
    srv_b.run([(0.0, r2)])
    assert pc.hits >= 1                 # re-warmed on the new pool
    for r in (r1, r2):
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert srv_b.poll(r.id).tokens == want, r.id


def test_paged_prefix_eviction_under_page_budget(devices, params):
    """A 4-page snapshot budget under many distinct prefixes: the LRU
    evicts, the budget holds, and a hit after evict re-prefills with
    bit-identical output (never stale)."""
    pc = PagedPrefixCache(CHUNK, max_pages=4)
    server = LMServer(params, n_slots=2, window=4, prefix_cache=pc,
                      **_pkw(), **_kw())
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(13)
    prompts = [tuple(int(x) for x in rng.integers(0, VOCAB, CHUNK))
               + (i,) for i in range(4)]
    for i, p in enumerate(prompts):
        server.run([(0.0, Request(id=f"e{i}", prompt=p,
                                  max_new_tokens=3))])
    assert pc.evictions > 0
    assert pc.cached_pages() <= 4
    # the first prefix was evicted — a re-run misses, re-prefills, and
    # still matches serial bit-for-bit
    server.run([(0.0, Request(id="again", prompt=prompts[0],
                              max_new_tokens=3))])
    assert (server.poll("again").tokens
            == _serial_tokens(gen, prompts[0], 3))


def test_page_exhaustion_mid_decode_is_honest(devices, params):
    """A small pool + a small decode reserve forces mid-decode growth
    to fail: the starved request retries (restarting bit-identically)
    or finishes with an honest error — and the surviving neighbor's
    output is untouched. Every page returns at drain."""
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(17)
    pa = tuple(int(v) for v in rng.integers(0, VOCAB, 8))
    pb = tuple(int(v) for v in rng.integers(0, VOCAB, 8))
    ra = Request(id="x", prompt=pa, max_new_tokens=20)
    rb = Request(id="y", prompt=pb, max_new_tokens=20)
    # with retries: one request wins the pool race, the other retries
    # once pages free — BOTH eventually ok and bit-identical
    srv = LMServer(params, n_slots=2, window=4, prefill_chunk=CHUNK,
                   kv_page_size=PS, kv_pages=8, kv_decode_reserve=4,
                   retry=RetryPolicy(max_retries=4, backoff_s=0.0),
                   **_kw())
    srv.run([(0.0, ra), (0.0, rb)])
    assert srv.summary()["serve_slot_faults"] > 0    # exhaustion fired
    n_ok = 0
    for r in (ra, rb):
        got = srv.poll(r.id)
        if got.status == "ok":
            n_ok += 1
            assert got.tokens == _serial_tokens(gen, r.prompt, 20), r.id
    assert n_ok >= 1
    assert srv.engine._alloc.used_count() == 0
    # without retries: the starved request finishes error/slot_fault
    # honestly; the survivor is still bit-identical
    srv2 = LMServer(params, n_slots=2, window=4, prefill_chunk=CHUNK,
                    kv_page_size=PS, kv_pages=8, kv_decode_reserve=4,
                    **_kw())
    srv2.run([(0.0, ra), (0.0, rb)])
    statuses = {r.id: srv2.poll(r.id).status for r in (ra, rb)}
    assert "error" in statuses.values()
    for r in (ra, rb):
        got = srv2.poll(r.id)
        if got.status == "ok":
            assert got.tokens == _serial_tokens(gen, r.prompt, 20)
        else:
            assert got.finish_reason == "slot_fault"
    assert srv2.summary()["serve_page_exhaustions"] > 0
    assert srv2.engine._alloc.used_count() == 0


def test_paged_admission_backpressure_feeds_brownout(devices, params):
    """A pool that fits one request at a time: the queue head WAITS on
    pages (page-aware admission — no refusal, no corruption), the
    exhaustion is counted, the brownout controller escalates with the
    'pages' reason, and everything still finishes bit-identically."""
    from idc_models_tpu.serve import BrownoutController

    bo = BrownoutController(queue_high=10_000, clamp_tokens=4,
                            escalate_dwell_s=0.0, clear_after_s=60.0)
    srv = LMServer(params, n_slots=2, window=4, prefill_chunk=CHUNK,
                   kv_page_size=PS, kv_pages=8, brownout=bo, **_kw())
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(19)
    reqs = [Request(id=f"w{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 10)),
                    max_new_tokens=16) for i in range(3)]
    srv.run([(0.0, r) for r in reqs])
    s = srv.summary()
    assert s["serve_page_exhaustions"] > 0
    assert any("pages" in t["reason"] for t in bo.transitions)
    for r in reqs:
        got = srv.poll(r.id)
        assert got.status == "ok"
        # brownout stage 2 may clamp budgets — parity at the SERVED
        # length (the clamp is an admission policy, not corruption)
        n = len(got.tokens)
        assert got.tokens == _serial_tokens(gen, r.prompt, 16)[:n]


def test_paged_release_kills_zombie_row(devices, params):
    """Releasing a slot MID-RUN (deadline cancel) must kill its device
    row in the same dispatch its pages free: the freed pages are
    re-granted immediately, and a still-live row writing through its
    stale page table would corrupt the new owner (the contiguous
    ride-along contract does not transfer to a shared pool)."""
    eng = SlotEngine(params, n_slots=2, **_pkw(), **_kw())
    eng.warmup(4)
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(23)
    pa = rng.integers(0, VOCAB, 4)
    eng.admit(0, pa, 24)                      # long budget
    eng.step_window(2)                        # decode a little
    eng.release(0)                            # cancel with ~22 left
    assert eng._alloc.used_count() == 0
    assert int(np.asarray(eng._rem)[0]) == 0  # device row KILLED
    # a LONG-prompt request takes the freed pages in the OTHER slot:
    # without the kill, the cancelled row (position BEHIND the new
    # owner's) would keep appending through its stale table straight
    # into the new owner's already-attended prompt pages (A/B-verified
    # against the old release semantics — prompt region diverges)
    pb = rng.integers(0, VOCAB, 16)
    eng.admit(1, pb, 8)

    def prompt_region():
        kp = np.asarray(eng._caches[0][0])
        pt = np.asarray(eng._pt)[1]
        return np.stack([kp[pt[j]] for j in range(16 // PS)])

    before = prompt_region()
    got = []
    while not eng.finished(1):
        got.extend(eng.step_window(2).get(1, []))
    assert np.array_equal(before, prompt_region())  # pages untouched
    eng.release(1)
    assert got == _serial_tokens(gen, tuple(pb), 8)


def test_paged_deadline_cancel_frees_prefill_grant(devices, params):
    """A request cancelled while still chunking returns its whole
    grant — nothing ever reached the batch row."""
    eng = SlotEngine(params, n_slots=1, **_pkw(), **_kw())
    eng.warmup(2)
    eng.start_prefill(0, np.arange(20) % VOCAB, 4)
    assert eng._alloc.used_count() > 0
    eng.prefill_step(0)                       # one chunk in
    eng.cancel_prefill(0)
    assert eng._alloc.used_count() == 0
    assert eng.free_slots() == [0]


def test_paged_validation_errors(devices, params):
    with pytest.raises(ValueError, match="BOTH kv_page_size"):
        SlotEngine(params, kv_page_size=PS, **_kw())
    with pytest.raises(ValueError, match="chunked prefill"):
        SlotEngine(params, kv_page_size=PS, kv_pages=PAGES, **_kw())
    with pytest.raises(ValueError, match="divide t_max"):
        SlotEngine(params, prefill_chunk=CHUNK, kv_page_size=5,
                   kv_pages=PAGES, **_kw())
    with pytest.raises(ValueError, match="multiple of kv_page_size"):
        SlotEngine(params, prefill_chunk=2, kv_page_size=4,
                   kv_pages=PAGES, **_kw())
    with pytest.raises(ValueError, match="could never be admitted"):
        SlotEngine(params, prefill_chunk=CHUNK, kv_page_size=PS,
                   kv_pages=SEQ // PS - 1, **_kw())
    with pytest.raises(ValueError, match="kv_decode_reserve"):
        SlotEngine(params, kv_decode_reserve=4, **_kw())
    with pytest.raises(ValueError, match="flavor must match"):
        SlotEngine(params, prefix_cache=PrefixCache(CHUNK, 1 << 20),
                   **_pkw(), **_kw())
    with pytest.raises(ValueError, match="flavor must match"):
        SlotEngine(params, prefill_chunk=CHUNK,
                   prefix_cache=PagedPrefixCache(CHUNK, max_pages=4),
                   **_kw())
    with pytest.raises(ValueError, match="exactly one"):
        PagedPrefixCache(CHUNK)
    with pytest.raises(ValueError, match="exactly one"):
        PagedPrefixCache(CHUNK, max_pages=4, budget_mb=1.0)


def test_paged_pool_at_equal_hbm_holds_more_residents(devices):
    """The capacity claim by its arithmetic and its counts: a page pool
    of exactly the bytes the contiguous engine reserves for 4 slots
    (pages x page bytes == 4 x bytes per slot), shared by 16 slots,
    holds at least 1.5x as many requests of mixed lengths at once as
    the contiguous engine can (4, whatever their lengths), and every
    stream is the same in both."""
    vocab, e, heads, blocks, mlp = 32, 32, 2, 2, 64
    t_max, s_contig, window, chunk, ps = 128, 4, 4, 16, 16
    n_req = 24
    model = attention_lm(vocab, t_max, embed_dim=e, num_heads=heads,
                         mlp_dim=mlp, num_blocks=blocks)
    params = model.init(jax.random.key(0)).params
    kw = dict(embed_dim=e, num_heads=heads, num_blocks=blocks,
              t_max=t_max, cache_dtype=jnp.bfloat16, prefill_chunk=chunk,
              max_queue_depth=2 * n_req, max_prefills_per_cycle=4,
              window=window)
    n_pages = s_contig * (t_max // ps)
    rng = np.random.default_rng(11)
    trace = [(0.0, Request(
        id=f"r{i}",
        prompt=tuple(int(x) for x in
                     rng.integers(0, vocab, int(rng.integers(3, 16)))),
        max_new_tokens=int(rng.integers(4, 24)))) for i in range(n_req)]

    def run(paged):
        server = LMServer(
            params, n_slots=4 * s_contig if paged else s_contig,
            kv_page_size=ps if paged else None,
            kv_pages=n_pages if paged else None, **kw)
        results = server.run(trace)
        assert all(r.status == "ok" for r in results)
        peak = round(max(server.metrics.occupancies)
                     * server.engine.n_slots)
        return server, {r.id: tuple(r.tokens) for r in results}, peak

    contig, tok_c, peak_c = run(False)
    paged, tok_p, peak_p = run(True)
    assert (paged.engine.kv_pages * paged.engine.kv_page_bytes()
            == s_contig * contig.engine.kv_bytes_per_slot())
    assert peak_c <= s_contig
    assert tok_p == tok_c
    assert peak_p >= 1.5 * peak_c, (peak_p, peak_c)
    assert paged.engine._alloc.used_count() == 0


def test_paged_kv_resident_accounting(devices, params):
    """kv_bytes_resident tracks pages, not slots: a short resident
    request costs its pages only, and the tokens-per-HBM-byte figure
    beats the contiguous engine's reservation arithmetic."""
    eng = SlotEngine(params, n_slots=4, **_pkw(), **_kw())
    eng.warmup(2)
    contig = SlotEngine(params, n_slots=4, **_kw())
    assert eng.kv_bytes_resident() == 0
    eng.admit(0, np.arange(5) % VOCAB, 3)     # 8 tokens -> 2 pages
    assert eng._alloc.used_count() == 2
    assert eng.kv_bytes_resident() == 2 * eng.kv_page_bytes()
    stats = eng.page_stats()
    assert stats["pages_total"] == PAGES
    assert stats["pages_used"] == 2
    assert stats["resident_tokens"] == 5
    # the contiguous engine reserves 4 full rows no matter what
    assert contig.page_stats() is None
    assert (contig.kv_bytes_resident()
            == 4 * contig.kv_bytes_per_slot())
    assert eng.kv_bytes_resident() < contig.kv_bytes_resident()
    eng.release(0)
    assert eng.kv_bytes_resident() == 0
