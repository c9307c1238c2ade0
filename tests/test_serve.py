"""The continuous-batching serving engine (serve/) against its two hard
contracts:

1. TOKEN PARITY — with identical prompts/seeds, the engine's per-request
   outputs are bit-identical to serial `Generator` calls (greedy and
   seeded top-k), including across a slot-recycle boundary (a request
   admitted into the slot another vacated mid-run). The engine shares
   the serial path's prefill program, per-token forward, fold algebra,
   and sampling rule — this gates that the sharing actually holds.
2. ZERO RECOMPILATION — after warmup, admitting requests of varying
   prompt lengths and budgets into a running engine triggers no new XLA
   compilations (jit cache-size counters).

Plus the scheduling semantics: FIFO admission with backpressure,
deadlines (queued drop + running cancel), EOS/budget recycling, masked
no-op appends for dead slots, and the serving metrics rollup.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.serve import (
    LMServer, Request, SlotEngine, load_trace, poisson_trace, save_trace,
)

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _kw(mesh=None):
    return dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)


def _serial_tokens(gen, prompt, steps, *, rng=None):
    """The serial reference: prefill + one fused decode, generated
    tokens only."""
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps, rng=rng)
    return toks.tolist()[0]


def test_token_parity_and_no_recompile_greedy(devices, params):
    """The acceptance pair in one run: 8 greedy requests of VARYING
    prompt lengths and budgets through 3 slots — so slots recycle
    mid-run — must (a) emit bit-identical tokens to serial Generator
    calls and (b) grow no jit cache after the warmup + first admission
    wave."""
    server = LMServer(params, n_slots=3, window=4, **_kw())
    rng = np.random.default_rng(5)
    reqs = [Request(id=f"r{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + 2 * i)),
                    max_new_tokens=4 + (i % 5) * 2)
            for i in range(8)]
    # first wave: two requests, then freeze the compile counters
    server.run([(0.0, r) for r in reqs[:2]])
    sizes = server.engine.cache_sizes()
    # second wave: six NEW lengths/budgets into the running engine
    server.run([(0.0, r) for r in reqs[2:]])
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)

    gen = Generator(params, **_kw())
    for r in reqs:
        got = server.poll(r.id)
        assert got is not None and got.status == "ok"
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert got.tokens == want, (r.id, got.tokens, want)


def test_token_parity_across_slot_recycle(devices, params):
    """Request C fills the slot request A vacated mid-run (B still
    decoding) — C's output must equal its serial generation exactly."""
    eng = SlotEngine(params, n_slots=2, **_kw())
    eng.warmup(4)
    rng = np.random.default_rng(7)
    pa = rng.integers(0, VOCAB, 9)
    pb = rng.integers(0, VOCAB, 5)
    pc = rng.integers(0, VOCAB, 13)
    eng.admit(0, pa, 5)
    eng.admit(1, pb, 17)
    got = {0: [], 1: []}
    got_c, c_admitted = [], False
    for _ in range(16):
        for s, t in eng.step_window(4).items():
            (got_c if (s == 0 and c_admitted) else got[s]).extend(t)
        if eng.finished(0):
            eng.release(0)
            if not c_admitted:
                eng.admit(0, pc, 7)
                c_admitted = True
        if eng.finished(1):
            eng.release(1)
        if c_admitted and not eng._occupied.any():
            break
    gen = Generator(params, **_kw())
    assert got[0] == _serial_tokens(gen, tuple(pa), 5)
    assert got[1] == _serial_tokens(gen, tuple(pb), 17)
    assert got_c == _serial_tokens(gen, tuple(pc), 7)


def test_token_parity_sampled_on_ring(devices, params):
    """Seeded top-k sampling through the RING-SHARDED engine (caches
    sharded over a seq=4 mesh): per-request streams must match serial
    decode with the same per-request key, bit for bit."""
    mesh = meshlib.seq_mesh(4)
    server = LMServer(params, n_slots=2, window=4, temperature=1.3,
                      top_k=4, **_kw(mesh))
    rng = np.random.default_rng(9)
    reqs = [Request(id=f"s{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 4 + 3 * i)),
                    max_new_tokens=6, seed=100 + i)
            for i in range(4)]
    server.run([(0.0, r) for r in reqs])
    gen = Generator(params, temperature=1.3, top_k=4, **_kw(mesh))
    for r in reqs:
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens,
                              rng=jax.random.key(r.seed))
        assert server.poll(r.id).tokens == want, r.id


def test_eos_stops_and_recycles(devices, params):
    """A request whose stream hits its stop token finishes early
    (finish_reason 'eos', EOS included), frees the slot for the queue,
    and matches the serial stream truncated at the first EOS."""
    gen = Generator(params, **_kw())
    prompt = (1, 2, 3)
    stream = _serial_tokens(gen, prompt, 12)
    eos = stream[3]                      # guaranteed to appear
    cut = stream[:stream.index(eos) + 1]
    server = LMServer(params, n_slots=1, window=4, eos_id=eos, **_kw())
    out = server.run([(0.0, Request(id="a", prompt=prompt,
                                    max_new_tokens=12)),
                      (0.0, Request(id="b", prompt=(4, 5),
                                    max_new_tokens=3, eos_id=-1))])
    a = server.poll("a")
    assert a.finish_reason == "eos" and a.tokens == cut
    b = server.poll("b")                 # eos_id=-1 opts out
    assert b.finish_reason == "budget" and len(b.tokens) == 3
    assert len(out) == 2


def test_backpressure_and_rejection(devices, params):
    """Bounded admission queue: submits beyond max_queue_depth return
    False; run(on_full='reject') records rejected Results; 'block'
    (default) serves everything in FIFO order."""
    server = LMServer(params, n_slots=1, window=4, max_queue_depth=2,
                      **_kw())
    reqs = [Request(id=f"q{i}", prompt=(i + 1,), max_new_tokens=2)
            for i in range(4)]
    assert server.submit(reqs[0])
    assert server.submit(reqs[1])
    assert not server.submit(reqs[2])    # depth 2 -> backpressure
    server.drain()
    assert server.poll("q0").status == "ok"
    rs = server.run([(0.0, Request(id="q9", prompt=(1,), max_new_tokens=2)),
                     (0.0, Request(id="q10", prompt=(2,), max_new_tokens=2)),
                     (0.0, Request(id="q11", prompt=(3,), max_new_tokens=2)),
                     (0.0, Request(id="q12", prompt=(4,), max_new_tokens=2))],
                    on_full="reject")
    statuses = {r.id: r.status for r in rs}
    assert statuses["q11"] == "rejected" or statuses["q12"] == "rejected"
    # blocking mode serves every request eventually — and a request that
    # merely WAITED for queue room must not count as rejected
    server2 = LMServer(params, n_slots=1, window=4, max_queue_depth=2,
                       **_kw())
    rs2 = server2.run([(0.0, Request(id=f"b{i}", prompt=(i + 1,),
                                     max_new_tokens=2))
                       for i in range(5)])
    assert sum(r.status == "ok" for r in rs2) == 5
    assert server2.summary()["serve_rejected"] == 0
    # duplicate ids are refused while the original is still in flight
    server2.submit(Request(id="dup", prompt=(1,), max_new_tokens=2))
    with pytest.raises(ValueError, match="already used"):
        server2.submit(Request(id="dup", prompt=(2,), max_new_tokens=2))
    server2.drain()
    with pytest.raises(ValueError, match="already used"):
        server2.submit(Request(id="dup", prompt=(2,), max_new_tokens=2))


def test_deadlines_queued_and_running(devices, params):
    """Deadlines on a FAKE clock: a queued request past its deadline
    times out without occupying a slot; a running request is cancelled
    mid-generation with its partial tokens returned."""
    now = [0.0]

    def clock():
        return now[0]

    server = LMServer(params, n_slots=1, window=4, clock=clock, **_kw())
    # "slow" occupies the slot; "late" waits in the queue past its
    # deadline; "slow" itself dies mid-run at t=1
    server.submit(Request(id="slow", prompt=(1, 2), max_new_tokens=24,
                          deadline_s=1.0))
    server.submit(Request(id="late", prompt=(3,), max_new_tokens=4,
                          deadline_s=0.5))
    server.step()                        # admits "slow", first window
    now[0] = 0.6
    server.step()                        # expires "late" in the queue
    late = server.poll("late")
    assert late.status == "timeout" and late.finish_reason == "deadline"
    assert late.tokens == []
    now[0] = 1.1
    server.step()
    server.drain()
    slow = server.poll("slow")
    assert slow.status == "timeout" and slow.finish_reason == "deadline"
    assert 0 < len(slow.tokens) < 24     # partial output survives
    # the vacated slot serves the next request normally
    server.submit(Request(id="next", prompt=(4,), max_new_tokens=3))
    server.drain()
    assert server.poll("next").status == "ok"
    # both deadline paths count in the summary's timeout field
    assert server.summary()["serve_timed_out"] == 2


def test_dead_slot_cache_untouched(devices, params):
    """The masked append: windows decoded while a slot is dead leave its
    cache rows bit-untouched (the recycled request's correctness rests
    on this, and on insert overwriting the full row)."""
    eng = SlotEngine(params, n_slots=2, **_kw())
    eng.warmup(4)
    eng.admit(0, (1, 2, 3), 4)
    eng.admit(1, (4, 5), 20)
    while not eng.finished(0):
        eng.step_window(4)
    eng.release(0)
    before = [np.asarray(kc)[0].copy() for kc, _ in eng._caches]
    eng.step_window(4)                   # slot 0 dead, slot 1 decoding
    after = [np.asarray(kc)[0] for kc, _ in eng._caches]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)


def test_admit_rejections(devices, params):
    eng = SlotEngine(params, n_slots=1, **_kw())
    with pytest.raises(ValueError, match="exceeds t_max"):
        eng.admit(0, list(range(SEQ - 2)), 3)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.admit(0, (1, 2), 0)
    with pytest.raises(ValueError, match="non-empty"):
        eng.admit(0, np.zeros((1, 0), np.int32), 2)
    eng.admit(0, (1, 2), 2)
    with pytest.raises(ValueError, match="occupied"):
        eng.admit(0, (1, 2), 2)
    with pytest.raises(ValueError, match="seq-only"):
        SlotEngine(params, n_slots=1, **_kw(meshlib.data_seq_mesh(2, 2)))
    server = LMServer(params, n_slots=1, temperature=1.0, **_kw())
    with pytest.raises(ValueError, match="rng"):
        server.submit(Request(id="x", prompt=(1,), max_new_tokens=2))


def test_metrics_summary_and_jsonl(devices, params, tmp_path):
    """The serving metrics roll up into the `serve_*` summary fields and
    stream through JsonlLogger in the standard record shape."""
    import json

    from idc_models_tpu.observe import JsonlLogger

    log = tmp_path / "serve.jsonl"
    with JsonlLogger(log) as logger:
        server = LMServer(params, n_slots=2, window=4, logger=logger,
                          **_kw())
        server.run([(0.0, Request(id=f"m{i}", prompt=(1, 2, 3),
                                  max_new_tokens=5))
                    for i in range(3)])
        s = server.summary()
    assert s["serve_requests"] == 3 and s["serve_tokens"] == 15
    assert s["serve_tokens_per_sec"] > 0
    assert s["serve_ttft_ms_p50"] > 0
    assert s["serve_ttft_ms_p95"] >= s["serve_ttft_ms_p50"]
    assert 0 < s["serve_slot_occupancy"] <= 1
    recs = [json.loads(line) for line in
            log.read_text().splitlines()]
    events = {r["event"] for r in recs}
    assert {"serve_submit", "serve_first_token",
            "serve_finish"} <= events
    assert all("ts" in r for r in recs)


def test_trace_roundtrip_and_poisson(devices, tmp_path):
    trace = poisson_trace(6, rate_per_s=100.0, vocab=VOCAB, t_max=SEQ,
                          seed=3, eos_id=2, deadline_s=5.0, sampled=True)
    assert len(trace) == 6
    ts = [t for t, _ in trace]
    assert ts == sorted(ts) and all(t > 0 for t in ts)
    for _, r in trace:
        assert len(r.prompt) + r.max_new_tokens <= SEQ
        assert r.seed is not None
    p = save_trace(tmp_path / "t.jsonl", trace)
    assert load_trace(p) == trace


def test_trace_generation_is_byte_deterministic(devices, tmp_path):
    """ISSUE 12 satellite: same seed => byte-identical trace FILE. The
    cluster drills replay one trace against fleets of different sizes;
    the comparison is meaningless if trace generation drifts between
    the passes, so determinism is gated at the byte level — generation,
    serialization, and the save->load->save fixpoint."""
    kw = dict(rate_per_s=75.0, vocab=VOCAB, t_max=SEQ, eos_id=2,
              deadline_s=5.0, sampled=True)
    a = poisson_trace(12, seed=42, **kw)
    b = poisson_trace(12, seed=42, **kw)
    assert a == b                       # full structural equality,
    #                                     Request fields included
    pa = save_trace(tmp_path / "a.jsonl", a)
    pb = save_trace(tmp_path / "b.jsonl", b)
    bytes_a = (tmp_path / "a.jsonl").read_bytes()
    assert bytes_a == (tmp_path / "b.jsonl").read_bytes()
    del pa, pb
    # a DIFFERENT seed must actually move the stream (the determinism
    # above is not the degenerate constant-output kind)
    c = poisson_trace(12, seed=43, **kw)
    assert c != a
    # save -> load -> save is a fixpoint: replaying from the file is
    # the same trace, byte for byte
    reloaded = load_trace(tmp_path / "a.jsonl")
    assert reloaded == a
    save_trace(tmp_path / "a2.jsonl", reloaded)
    assert (tmp_path / "a2.jsonl").read_bytes() == bytes_a


def test_chunked_prefill_token_parity_and_no_recompile(devices, params):
    """Chunked admission (prefill_chunk=8) at every boundary length —
    1, chunk-1, chunk, chunk+1 — emits tokens bit-identical to the
    serial MONOLITHIC Generator, and after the first wave admits of
    every further length compile nothing (the chunk program is one
    executable for all prompt lengths, including the ragged tail)."""
    server = LMServer(params, n_slots=2, window=4, prefill_chunk=8,
                      **_kw())
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(11)
    lens = [1, 7, 8, 9, 17]
    reqs = [Request(id=f"c{p}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, p)),
                    max_new_tokens=5)
            for p in lens]
    server.run([(0.0, reqs[0])])
    sizes = server.engine.cache_sizes()
    assert "prefill_chunk" in sizes
    server.run([(0.0, r) for r in reqs[1:]])
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)
    for r in reqs:
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert server.poll(r.id).tokens == want, r.id
    # NOTE: sizes["prefill"] is not asserted 0 — the monolithic program
    # cache is process-wide per config and other tests share it; the
    # stability assertion above is the admission-path contract


def test_chunked_prefill_sampled_parity_with_prefix_hits(devices, params):
    """Seeded top-k sampling through CHUNKED admission WITH prefix-cache
    hits: per-request streams must still match the serial Generator with
    the same key, bit for bit — the request's rng stream is independent
    of how its prompt was prefilled (and on a 1-device serving mesh the
    chunk path's prefill state is bit-identical to the monolithic
    one)."""
    sys_p = tuple(int(x) for x in
                  np.random.default_rng(21).integers(0, VOCAB, 8))
    server = LMServer(params, n_slots=2, window=4, temperature=1.3,
                      top_k=4, prefill_chunk=8, prefix_cache_mb=64.0,
                      **_kw())
    reqs = [Request(id=f"t{i}", prompt=sys_p + (i,), max_new_tokens=6,
                    seed=300 + i)
            for i in range(4)]
    server.run([(0.0, r) for r in reqs])
    assert server.summary()["serve_prefix_hits"] >= 3
    gen = Generator(params, temperature=1.3, top_k=4, **_kw())
    for r in reqs:
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens,
                              rng=jax.random.key(r.seed))
        assert server.poll(r.id).tokens == want, r.id


def test_chunked_prefill_full_cache_prompt(devices, params):
    """Prompt length == t_max: the chunk path fills the entire cache
    and its final logits/caches match the monolithic prefill (argmax-
    equal logits, fp-close caches) — the upper boundary the chunk grid
    must tile exactly."""
    gen = Generator(params, **_kw())
    genc = Generator(params, prefill_chunk=8, **_kw())
    prompt = jnp.asarray(
        [np.random.default_rng(3).integers(0, VOCAB, SEQ)], jnp.int32)
    l0, c0 = gen.prefill(prompt)
    l1, c1 = genc.prefill(prompt)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=2e-5, atol=2e-5)
    assert int(jnp.argmax(l0)) == int(jnp.argmax(l1))
    for (k0, v0), (k1, v1) in zip(c0, c1):
        np.testing.assert_allclose(np.asarray(k0), np.asarray(k1),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                                   rtol=2e-5, atol=2e-5)


def test_chunked_prefill_interleaves_with_decode(devices, params):
    """The point of chunking: while a long prompt is being prefilled
    chunk by chunk, an already-running request KEEPS emitting tokens
    every window — and the chunked request's own output still matches
    its serial generation bit-for-bit."""
    eng = SlotEngine(params, n_slots=2, prefill_chunk=4, **_kw())
    eng.warmup(4)
    rng = np.random.default_rng(13)
    pa = rng.integers(0, VOCAB, 3)
    pb = rng.integers(0, VOCAB, 17)          # 5 chunks of 4
    eng.admit(0, pa, 16)                     # decoding from the start
    eng.start_prefill(1, pb, 6)
    assert 1 not in eng.free_slots()         # reserved while chunking
    got_a, got_b, windows_during_prefill = [], [], 0
    done = False
    while not done:
        done = eng.prefill_step(1)
        out = eng.step_window(2)
        if not done:
            windows_during_prefill += 1
            assert out.get(0), "running slot stalled behind a prefill"
        got_a.extend(out.get(0, []))
        got_b.extend(out.get(1, []))
    while eng._occupied.any():
        for s, t in eng.step_window(2).items():
            (got_a if s == 0 else got_b).extend(t)
        for s in (0, 1):
            if eng.finished(s):
                eng.release(s)
    assert windows_during_prefill >= 4       # decode ran between chunks
    gen = Generator(params, **_kw())
    assert got_a == _serial_tokens(gen, tuple(pa), 16)
    assert got_b == _serial_tokens(gen, tuple(pb), 6)


def test_chunked_deadline_cancels_prefilling_request(devices, params):
    """A deadline that lands while a request is still CHUNKING its
    prompt cancels the prefill: the reserved slot frees immediately, no
    tokens are attributed, and the queue keeps moving."""
    now = [0.0]
    server = LMServer(params, n_slots=1, window=4, prefill_chunk=4,
                      clock=lambda: now[0], **_kw())
    # prompt of 5 chunks, one chunk per tick: deadline hits mid-chunking
    server.submit(Request(id="long", prompt=tuple(range(1, 18)),
                          max_new_tokens=4, deadline_s=1.0))
    server.step()                            # start + first chunk
    now[0] = 1.5
    server.step()                            # deadline: cancel_prefill
    r = server.poll("long")
    assert r is not None and r.status == "timeout"
    assert r.tokens == []
    server.submit(Request(id="next", prompt=(1, 2), max_new_tokens=3))
    server.drain()
    assert server.poll("next").status == "ok"


def test_int8_kv_capacity_and_bounded_drift(devices, params):
    """int8 KV: ring-cache bytes per slot drop >= 1.5x vs the same
    engine at bf16 (the capacity headroom the quantization buys), and
    the quantized engine's greedy decode still tracks the serial bf16
    path exactly on this model (drift is bounded well inside the
    greedy argmax margin at these scales; docs/LONG_CONTEXT.md owns the
    caveat for when it is not)."""
    kw = dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
              t_max=SEQ, mesh=None, cache_dtype=jnp.bfloat16)
    eng16 = SlotEngine(params, n_slots=2, **kw)
    eng8 = SlotEngine(params, n_slots=2, kv_dtype="int8", **kw)
    ratio = eng16.kv_bytes_per_slot() / eng8.kv_bytes_per_slot()
    assert ratio >= 1.5, ratio
    server = LMServer(params, n_slots=2, window=4, kv_dtype="int8",
                      **_kw())
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(17)
    reqs = [Request(id=f"i{k}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 4 + 3 * k)),
                    max_new_tokens=6)
            for k in range(3)]
    server.run([(0.0, r) for r in reqs])
    for r in reqs:
        got = server.poll(r.id)
        assert got.status == "ok"
        assert got.tokens == _serial_tokens(gen, r.prompt,
                                            r.max_new_tokens), r.id


def test_spec_decode_token_parity_and_no_recompile(devices, params):
    """The ISSUE-10 extension of the acceptance pair: with speculative
    decoding armed (n-gram prompt-lookup drafter, fixed-k verify
    program), greedy requests of VARYING prompt lengths — repetitive
    prompts that draft-hit and random ones that mostly miss or fall
    back to plain windows — must (a) emit tokens bit-identical to
    serial Generator calls and (b) grow no jit cache entry (the verify
    included) after the warmup + first admission wave."""
    server = LMServer(params, n_slots=3, window=4, spec_decode=True,
                      draft_k=4, **_kw())
    rng = np.random.default_rng(29)
    reqs = []
    for i in range(8):
        if i % 2:                       # repetitive: the drafter's food
            pat = [int(x) for x in rng.integers(0, VOCAB, 2 + i % 3)]
            prompt = tuple((pat * 6)[:5 + 2 * i])
        else:                           # random: misses and fallbacks
            prompt = tuple(int(x) for x in
                           rng.integers(0, VOCAB, 3 + 2 * i))
        reqs.append(Request(id=f"sp{i}", prompt=prompt,
                            max_new_tokens=4 + (i % 5) * 2))
    server.run([(0.0, r) for r in reqs[:2]])
    sizes = server.engine.cache_sizes()
    assert "verify" in sizes
    server.run([(0.0, r) for r in reqs[2:]])
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)
    gen = Generator(params, **_kw())
    for r in reqs:
        got = server.poll(r.id)
        assert got is not None and got.status == "ok"
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert got.tokens == want, (r.id, got.tokens, want)
    # speculation actually ran (the drafts proposed and verified);
    # correctness above never depended on it
    assert server.summary()["serve_spec_verify_dispatches"] > 0


def test_engine_failure_releases_slots_and_surfaces_error(devices, params):
    """Satellite contract: if the engine fails mid-tick, the in-flight
    requests become status="error" Results (with the failure detail),
    their slots are released, the error re-raises — and the server
    keeps serving new requests afterwards instead of wedging."""
    server = LMServer(params, **_kw(), n_slots=2, window=4, eos_id=None)
    assert server.submit(Request(id="a", prompt=(1, 2, 3),
                                 max_new_tokens=8))
    assert server.submit(Request(id="b", prompt=(4, 5),
                                 max_new_tokens=8))
    server.step()                     # admit a; window in flight
    server.step()                     # admit b; next window in flight
    assert server.scheduler._running

    real_collect = server.engine.collect

    def boom():
        raise RuntimeError("device fell off the bus")

    server.engine.collect = boom
    with pytest.raises(RuntimeError, match="fell off the bus"):
        server.step()
    server.engine.collect = real_collect

    # every in-flight request got an error Result with the detail
    for rid in ("a", "b"):
        r = server.poll(rid)
        assert r is not None and r.status == "error"
        assert "fell off the bus" in r.error
    # slots were released, nothing is running, the queue is sane
    assert server.scheduler._running == {}
    assert sorted(server.engine.free_slots()) == [0, 1]
    assert server.scheduler.idle()

    # the server is still serviceable: a fresh request completes ok and
    # matches the serial path (the engine state machine was not wedged)
    gen = Generator(params, **_kw())
    assert server.submit(Request(id="c", prompt=(1, 2, 3),
                                 max_new_tokens=6))
    out = server.drain()
    assert [r.id for r in out] == ["c"] and out[0].status == "ok"
    assert out[0].error is None
    assert out[0].tokens == _serial_tokens(gen, [1, 2, 3], 6)


def test_chunked_prefill_failure_releases_and_recovers(devices, params):
    """An engine failure raised from a CHUNK dispatch mid-admission
    gets the same cleanup contract as collect/begin_window failures:
    the prefilling entry becomes an error Result, its reserved slot
    frees, and the server keeps serving."""
    server = LMServer(params, n_slots=2, window=4, prefill_chunk=4,
                      **_kw())
    assert server.submit(Request(id="long", prompt=tuple(range(1, 14)),
                                 max_new_tokens=4))
    real_step = server.engine.prefill_step

    def boom(slot):
        raise RuntimeError("chunk dispatch died")

    server.engine.prefill_step = boom
    with pytest.raises(RuntimeError, match="chunk dispatch died"):
        server.step()
    server.engine.prefill_step = real_step

    r = server.poll("long")
    assert r is not None and r.status == "error"
    assert "chunk dispatch died" in r.error
    assert server.scheduler.idle()
    assert sorted(server.engine.free_slots()) == [0, 1]
    # still serviceable, and output still matches serial
    gen = Generator(params, **_kw())
    assert server.submit(Request(id="next", prompt=(1, 2, 3),
                                 max_new_tokens=5))
    server.drain()
    assert server.poll("next").tokens == _serial_tokens(gen, [1, 2, 3],
                                                        5)


def test_engine_failure_preserves_completed_entries(devices, params):
    """A request that COMPLETED on the failed tick (budget reached at
    collect) keeps its real 'ok' Result — only the genuinely in-flight
    request becomes an error — even though tick() re-raised before its
    normal bookkeeping ran."""
    server = LMServer(params, **_kw(), n_slots=2, window=4, eos_id=None)
    assert server.submit(Request(id="done", prompt=(1, 2, 3),
                                 max_new_tokens=4))   # == one window
    assert server.submit(Request(id="run", prompt=(4, 5),
                                 max_new_tokens=12))
    calls = {"n": 0}
    real_begin = server.engine.begin_window

    def failing_begin(n):
        calls["n"] += 1
        if calls["n"] >= 2:          # the window AFTER "done" finishes
            raise RuntimeError("begin blew up")
        return real_begin(n)

    server.engine.begin_window = failing_begin
    server.step()                    # admit both, window 1 in flight
    with pytest.raises(RuntimeError, match="begin blew up"):
        server.step()                # collect: "done" finishes; begin dies
    server.engine.begin_window = real_begin

    done = server.poll("done")
    assert done.status == "ok" and done.finish_reason == "budget"
    assert len(done.tokens) == 4 and done.error is None
    # the serial path agrees with the salvaged tokens
    gen = Generator(params, **_kw())
    assert done.tokens == _serial_tokens(gen, [1, 2, 3], 4)
    failed = server.poll("run")
    assert failed.status == "error" and "begin blew up" in failed.error
    assert len(failed.tokens) == 4   # the collected window's tokens kept
    assert server.scheduler.idle()
