"""Speculative decoding (ISSUE 10) against its contracts:

1. TOKEN PARITY — greedy speculative output is bit-identical to the
   serial `Generator`, at EVERY accepted-prefix length (0, 1, k-1, k,
   driven by a scripted drafter), across slot recycling, with
   chunked-prefill admission interleaved in the same cycle, and under
   seeded top-k sampling (the verify consumes the request's key chain
   exactly as the fused window would). Over int8 KV caches the oracle
   is the int8 server WITHOUT speculation: quantisation may flip a
   near-tie argmax against the float path, speculation may not change
   what plain decode over the same cache emits.
2. DRAFTS ARE UNTRUSTED — any `propose` output is sound: the verify
   accepts only what the model itself would have emitted, so garbage
   drafts cost acceptance rate, never correctness.
3. ZERO RECOMPILATION — the verify program is ONE fixed-k executable;
   varying draft-hit patterns and prompt lengths compile nothing after
   warmup (gated here and in tests/test_serve.py).

Plus the n-gram prompt-lookup drafter's host-side semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models.draft import NGramDrafter
from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.serve import LMServer, Request, SlotEngine

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
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps, rng=rng)
    return toks.tolist()[0]


class ScriptedDrafter:
    """Test drafter forcing an EXACT accepted-prefix length per
    request: the serial oracle's true continuation for the first
    `accept` positions, then guaranteed-wrong tokens (true + 1 mod
    vocab — never equal to the model's own pick). Requests are keyed
    by prompt prefix, so plans need prefix-distinct prompts."""

    def __init__(self, k, plans):
        self.k = k
        self.plans = plans          # [(prompt tuple, stream, accept)]

    def propose(self, history):
        h = [int(x) for x in history]
        for prompt, stream, accept in self.plans:
            p = list(prompt)
            if len(h) < len(p) or h[:len(p)] != p:
                continue
            done = len(h) - len(p)
            cont = list(stream[done:done + self.k])
            cont += [0] * (self.k - len(cont))
            for j in range(accept, self.k):
                cont[j] = (cont[j] + 1) % VOCAB
            return np.asarray(cont, np.int32)
        return None


# -- the drafter ----------------------------------------------------------


def test_ngram_drafter_lookup_and_fallback():
    d = NGramDrafter(3, order=2)
    # trailing (2, 3) recurred: propose what followed it (4, 5, 6)
    got = d.propose([1, 2, 3, 4, 5, 6, 2, 3])
    assert got.tolist() == [4, 5, 6]
    # the MOST RECENT occurrence wins when the n-gram recurs twice
    got = d.propose([2, 3, 7, 2, 3, 9, 1, 2, 3])
    assert got.tolist()[0] == 9
    # order falls back: (5, 1) never recurs but 1 does (order 2 -> 1)
    got = d.propose([1, 8, 4, 5, 1])
    assert got.tolist() == [8, 4, 5]
    # continuation shorter than k pads with the final history token
    got = NGramDrafter(4, order=1).propose([7, 3, 7])
    assert got.tolist() == [3, 7, 7, 7]
    # nothing recurs -> None (fall back to the plain window)
    assert d.propose([1, 2, 3, 4, 5]) is None
    assert d.propose([4]) is None and d.propose([]) is None
    # min_order bounds the fallback
    assert NGramDrafter(2, order=3, min_order=2).propose(
        [1, 8, 4, 5, 1]) is None


def test_ngram_drafter_lookback_bounds_the_scan():
    """The critical-path bound: only the last `lookback` tokens are
    scanned, so a match reachable only in deep history is (cheaply)
    missed, while recent matches still hit — and the default stays
    O(lookback) however long the stream grows."""
    d = NGramDrafter(2, order=2, lookback=6)
    long_hist = [7, 8, 9, 9, 9] * 40 + [1, 2, 3, 4, 1, 2]
    assert d.propose(long_hist).tolist() == [3, 4]   # inside lookback
    # the (7, 8) match exists only beyond the lookback window -> None
    assert d.propose([7, 8, 5] + [0, 6] * 10 + [7, 8]) is None
    assert NGramDrafter(2, order=2, lookback=None).propose(
        [7, 8, 5] + [0, 6] * 10 + [7, 8]).tolist() == [5, 0]


def test_ngram_drafter_validation():
    with pytest.raises(ValueError, match="k >= 1"):
        NGramDrafter(0)
    with pytest.raises(ValueError, match="min_order"):
        NGramDrafter(2, order=2, min_order=3)
    with pytest.raises(ValueError, match="min_order"):
        NGramDrafter(2, order=2, min_order=0)
    with pytest.raises(ValueError, match="lookback"):
        NGramDrafter(2, order=3, lookback=2)


# -- accept-length boundary parity ---------------------------------------


def test_parity_at_every_accept_length(devices, params):
    """Accepted-prefix lengths 0, 1, k-1, and k (scripted drafter) all
    emit streams bit-identical to the serial Generator — the verify's
    budget/bonus/logits bookkeeping is exact at every boundary."""
    k = 4
    gen = Generator(params, **_kw())
    prompts = [(i, 2 + i % 3, 5) for i in range(4)]   # prefix-distinct
    budgets = [11, 12, 13, 9]
    accepts = [0, 1, k - 1, k]
    streams = [_serial_tokens(gen, p, b)
               for p, b in zip(prompts, budgets)]
    drafter = ScriptedDrafter(
        k, [(p, s, a) for p, s, a in zip(prompts, streams, accepts)])
    server = LMServer(params, n_slots=4, window=4, spec_decode=True,
                      draft_k=k, drafter=drafter, **_kw())
    reqs = [Request(id=f"a{i}", prompt=p, max_new_tokens=b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]
    server.run([(0.0, r) for r in reqs])
    for r, s in zip(reqs, streams):
        got = server.poll(r.id)
        assert got is not None and got.status == "ok"
        assert got.tokens == s, (r.id, got.tokens, s)
    summary = server.summary()
    assert summary["serve_spec_verify_dispatches"] > 0
    assert summary["serve_spec_accepted"] > 0
    # the full-accept request advanced k+1 tokens on some verify; the
    # zero-accept one advanced exactly 1 per verify — both are inside
    # the per-slot tokens-per-dispatch mean
    assert summary["serve_spec_tokens_per_dispatch"] >= 1.0


def test_parity_with_eos_inside_accepted_prefix(devices, params):
    """An EOS hit INSIDE the accepted draft prefix truncates exactly
    like the fused window's device rule: emitted through the EOS
    (inclusive), budget zeroed, the stream equal to the serial one cut
    at its first EOS."""
    k = 4
    gen = Generator(params, **_kw())
    prompt = (1, 2, 3)
    stream = _serial_tokens(gen, prompt, 12)
    eos = stream[5]                       # lands mid-draft at k=4
    cut = stream[:stream.index(eos) + 1]
    drafter = ScriptedDrafter(k, [(prompt, stream, k)])  # full accept
    server = LMServer(params, n_slots=1, window=4, eos_id=eos,
                      spec_decode=True, draft_k=k, drafter=drafter,
                      **_kw())
    server.run([(0.0, Request(id="e", prompt=prompt,
                              max_new_tokens=12))])
    got = server.poll("e")
    assert got.finish_reason == "eos" and got.tokens == cut


def test_parity_across_slot_recycle_and_budget_edges(devices, params):
    """Speculative traffic with slot recycling AND a request whose
    prompt + budget fills the cache to t_max exactly: near the edge
    `spec_room` fails and the policy falls back to plain windows, so
    the request still finishes — all streams bit-identical to
    serial."""
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(23)
    reqs, plans = [], []
    for i in range(6):
        p = tuple(int(x) for x in rng.integers(0, VOCAB, 3 + 2 * i))
        b = SEQ - len(p) if i == 2 else 4 + (i % 4) * 3
        reqs.append(Request(id=f"r{i}", prompt=p, max_new_tokens=b))
        plans.append((p, _serial_tokens(gen, p, b), 4))
    drafter = ScriptedDrafter(4, plans)
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=4, drafter=drafter, **_kw())
    server.run([(0.0, r) for r in reqs])
    for r, (_, s, _) in zip(reqs, plans):
        got = server.poll(r.id)
        assert got.status == "ok" and got.tokens == s, r.id


def test_sampled_parity_with_speculation(devices, params):
    """Seeded top-k sampling THROUGH the verify program: the accept
    rule samples along the request's exact serial key chain (one split
    per emitted token), so speculative streams match serial seeded
    decode bit-for-bit — accepted drafts, bonus picks, and the key
    handed to the next window alike."""
    k = 3
    gen = Generator(params, temperature=1.3, top_k=4, **_kw())
    prompts = [(i, 9 - i, 1, 4) for i in range(3)]
    seeds = [100 + i for i in range(3)]
    budgets = [8, 10, 7]
    streams = [_serial_tokens(gen, p, b, rng=jax.random.key(s))
               for p, b, s in zip(prompts, budgets, seeds)]
    # mixed accept lengths, incl. full accept of SAMPLED continuations
    drafter = ScriptedDrafter(
        k, [(p, s, a) for p, s, a
            in zip(prompts, streams, (k, 1, 0))])
    server = LMServer(params, n_slots=3, window=4, temperature=1.3,
                      top_k=4, spec_decode=True, draft_k=k,
                      drafter=drafter, **_kw())
    reqs = [Request(id=f"s{i}", prompt=p, max_new_tokens=b, seed=s)
            for i, (p, b, s) in enumerate(zip(prompts, budgets, seeds))]
    server.run([(0.0, r) for r in reqs])
    for r, s in zip(reqs, streams):
        got = server.poll(r.id)
        assert got.status == "ok" and got.tokens == s, r.id
    assert server.summary()["serve_spec_accepted"] > 0


def test_spec_parity_on_ring_sharded_cache(devices, params):
    """Speculative decode with the KV caches SHARDED over a seq=4
    ring: the batched chunk fold's per-row splice + two-collective
    merge must reproduce the serial ring decode's streams exactly
    (greedy), drafts hitting and missing alike."""
    from idc_models_tpu import mesh as meshlib

    mesh = meshlib.seq_mesh(4)
    gen = Generator(params, **_kw(mesh))
    rng = np.random.default_rng(47)
    prompts = [tuple(int(x) for x in rng.integers(0, VOCAB, 4 + 3 * i))
               for i in range(3)]
    budgets = [7, 9, 6]
    plans = [(p, _serial_tokens(gen, p, b), a)
             for p, b, a in zip(prompts, budgets, (4, 2, 0))]
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=4, drafter=ScriptedDrafter(4, plans),
                      **_kw(mesh))
    reqs = [Request(id=f"g{i}", prompt=p, max_new_tokens=b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]
    server.run([(0.0, r) for r in reqs])
    for r, (_, s, _) in zip(reqs, plans):
        got = server.poll(r.id)
        assert got.status == "ok" and got.tokens == s, r.id
    assert server.summary()["serve_spec_accepted"] > 0


def test_int8_kv_speculative_parity(devices, params):
    """Spec decode over int8 KV caches: the verify's chunk fold
    dequantizes by the same factored per-(slot, head) scales as the
    decode fold, so the speculative server emits exactly what an int8
    server of the same settings emits without speculation — the
    invariant speculation owes. The float serial `Generator` is NOT
    the oracle here: int8 rounding flips one near-tie argmax of these
    random weights (request i2, token 5) with or without speculation;
    the drift bound against float is test_serve.py's
    test_int8_kv_capacity_and_bounded_drift."""
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(31)
    prompts = [tuple(int(x) for x in rng.integers(0, VOCAB, 4 + 3 * i))
               for i in range(3)]
    budgets = [6, 8, 7]
    # drafts scripted from the float stream: accepted where int8 agrees
    # with it, rejected where it does not — sound either way
    plans = [(p, _serial_tokens(gen, p, b), 4)
             for p, b in zip(prompts, budgets)]
    reqs = [Request(id=f"i{i}", prompt=p, max_new_tokens=b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]

    def serve(**spec):
        server = LMServer(params, n_slots=2, window=4, kv_dtype="int8",
                          **spec, **_kw())
        server.run([(0.0, r) for r in reqs])
        return server

    plain = serve(spec_decode=False)
    server = serve(spec_decode=True, draft_k=4,
                   drafter=ScriptedDrafter(4, plans))
    for r in reqs:
        got, want = server.poll(r.id), plain.poll(r.id)
        assert got.status == want.status == "ok"
        assert got.tokens == want.tokens, r.id
    s = server.summary()
    assert s["serve_spec_verify_dispatches"] > 0
    assert s["serve_spec_accepted"] > 0


def test_spec_with_chunked_prefill_same_cycle(devices, params):
    """A long prompt chunking its way in WHILE other slots run verify
    dispatches — speculative decode and chunked-prefill admission in
    one scheduler cycle — with every stream bit-identical to serial,
    including the chunked request once it starts decoding."""
    gen = Generator(params, **_kw())
    p_run = (1, 2, 3)
    p_long = tuple(int(x) for x in
                   np.random.default_rng(41).integers(0, VOCAB, 17))
    s_run = _serial_tokens(gen, p_run, 16)
    s_long = _serial_tokens(gen, p_long, 6)
    drafter = ScriptedDrafter(4, [(p_run, s_run, 4),
                                  (p_long, s_long, 4)])
    server = LMServer(params, n_slots=2, window=2, prefill_chunk=4,
                      spec_decode=True, draft_k=4, drafter=drafter,
                      **_kw())
    server.submit(Request(id="run", prompt=p_run, max_new_tokens=16))
    server.step()                # admit "run"; it decodes from here
    server.submit(Request(id="long", prompt=p_long, max_new_tokens=6))
    # while "long" chunks (5 chunks of 4), "run" must keep emitting —
    # and with its drafter scripted to full accept, via VERIFY
    # dispatches in the same cycles the chunks step
    before = server.summary()["serve_spec_verify_dispatches"]
    while server.poll("long") is None or server.poll("run") is None:
        server.step()
    assert server.summary()["serve_spec_verify_dispatches"] > before
    assert server.poll("run").tokens == s_run
    assert server.poll("long").tokens == s_long


def test_lookup_drafts_are_accepted_on_a_model_that_counts(devices):
    """What speculation is for, by its counts: a model trained to count
    (next = tok + 1 mod vocab) serves counting prompts longer than the
    vocabulary, so every trailing n-gram has occurred before and the
    lookup drafter always proposes what the model will say. At least
    half of the drafts are accepted, a verify advances a slot by more
    than 1.5 tokens, and the streams are those of plain decode."""
    from idc_models_tpu.models.lm import next_token_loss
    from idc_models_tpu.train import TrainState, make_train_step, rmsprop

    vocab, t_max, k = 16, 256, 16
    model = attention_lm(vocab, t_max, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    p0 = model.init(jax.random.key(0)).params
    opt = rmsprop(3e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=p0,
                       model_state={}, opt_state=opt.init(p0))
    step = jax.jit(make_train_step(model, opt, next_token_loss))
    rng = np.random.default_rng(3)
    key = jax.random.key(4)
    for _ in range(300):
        seqs = jnp.asarray((rng.integers(0, vocab, (8, 1))
                            + np.arange(t_max)) % vocab, jnp.int32)
        key, sub = jax.random.split(key)
        state, _ = step(state, seqs, seqs, sub)
    trained = jax.device_get(state.params)

    reqs = []
    for i in range(8):
        p_len = int(rng.integers(vocab + 4, vocab * 2))
        start = int(rng.integers(0, vocab))
        reqs.append(Request(
            id=f"s{i}",
            prompt=tuple((start + j) % vocab for j in range(p_len)),
            max_new_tokens=int(rng.integers(150, 180))))

    def serve(spec):
        server = LMServer(trained, embed_dim=E, num_heads=HEADS,
                          num_blocks=BLOCKS, t_max=t_max,
                          cache_dtype=jnp.bfloat16, n_slots=4, window=8,
                          max_prefills_per_cycle=4, spec_decode=spec,
                          draft_k=k, draft_order=2)
        out = server.run([(0.0, r) for r in reqs])
        assert all(r.status == "ok" for r in out)
        return {r.id: tuple(r.tokens) for r in out}, server.summary()

    tok_spec, s = serve(True)
    tok_plain, _ = serve(False)
    assert tok_spec == tok_plain
    assert s["serve_spec_accept_rate"] >= 0.5, s
    assert s["serve_spec_tokens_per_dispatch"] > 1.5, s


def test_spec_ledger_counts_only_real_proposals(devices, params):
    """A slot riding along on the scheduler's placeholder drafts (its
    drafter returned None) must not dilute the accept ledger: with one
    full-accept proposer and one silent slot, the accept rate reads
    ~1.0 — not ~0.5 — and every drafted token belongs to the slot that
    actually proposed. Operators tune speculation off below ~1/k
    acceptance, so dilution here would disable it exactly where it
    wins."""
    gen = Generator(params, **_kw())
    p_hit, p_quiet = (1, 2, 3), (4, 5)
    s_hit = _serial_tokens(gen, p_hit, 12)
    s_quiet = _serial_tokens(gen, p_quiet, 12)
    drafter = ScriptedDrafter(4, [(p_hit, s_hit, 4)])  # quiet: None
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=4, drafter=drafter, **_kw())
    server.run([(0.0, Request(id="h", prompt=p_hit, max_new_tokens=12)),
                (0.0, Request(id="q", prompt=p_quiet,
                              max_new_tokens=12))])
    assert server.poll("h").tokens == s_hit
    assert server.poll("q").tokens == s_quiet      # rode along, exact
    s = server.summary()
    assert s["serve_spec_verify_dispatches"] > 0
    # drafted counts ONLY the proposing slot: k per verify dispatch
    assert s["serve_spec_drafted"] == 4 * s["serve_spec_verify_dispatches"]
    assert s["serve_spec_accept_rate"] >= 0.75, s


def test_spec_no_recompile_across_hit_patterns(devices, params):
    """The fixed-k verify program is ONE executable: after the first
    wave, speculative traffic of varying prompt lengths AND varying
    draft-hit patterns (full accept, partial, zero, drafter silence ->
    window fallback) grows no jit cache — the ISSUE-10 compile gate at
    the unit level (the server-level gate lives in test_serve.py)."""
    gen = Generator(params, **_kw())
    rng = np.random.default_rng(57)

    def mk(i, accept, n):
        p = tuple(int(x) for x in rng.integers(0, VOCAB, 3 + i))
        b = 4 + (i % 3) * 3
        return (Request(id=f"{n}{i}", prompt=p, max_new_tokens=b),
                (p, _serial_tokens(gen, p, b), accept))
    wave1 = [mk(i, a, "w") for i, a in enumerate((4, 0))]
    wave2 = [mk(i + 2, a, "x") for i, a in enumerate((1, 3, 4, 0))]
    drafter = ScriptedDrafter(4, [pl for _, pl in wave1 + wave2])
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=4, drafter=drafter, **_kw())
    server.run([(0.0, r) for r, _ in wave1])
    sizes = server.engine.cache_sizes()
    assert "verify" in sizes
    server.run([(0.0, r) for r, _ in wave2])
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)
    for r, (_, s, _) in wave1 + wave2:
        assert server.poll(r.id).tokens == s, r.id


def test_engine_verify_validation(devices, params):
    """The engine-level contracts: draft_k bounds, drafts/vlive shape
    checks, verify on an unarmed engine, and vlive rows that lack
    occupancy or room are refused before any dispatch."""
    with pytest.raises(ValueError, match="draft_k"):
        SlotEngine(params, n_slots=1, draft_k=SEQ, **_kw())
    eng = SlotEngine(params, n_slots=2, **_kw())
    with pytest.raises(RuntimeError, match="without draft_k"):
        eng.begin_verify(np.zeros((2, 4), np.int32),
                         np.zeros(2, bool))
    assert not eng.spec_room(0)          # unarmed: never eligible
    eng = SlotEngine(params, n_slots=2, draft_k=4, **_kw())
    eng.warmup(2)
    with pytest.raises(ValueError, match="drafts must be"):
        eng.begin_verify(np.zeros((2, 3), np.int32), np.zeros(2, bool))
    with pytest.raises(ValueError, match="vlive must be"):
        eng.begin_verify(np.zeros((2, 4), np.int32), np.zeros(3, bool))
    with pytest.raises(ValueError, match="not occupied"):
        eng.begin_verify(np.zeros((2, 4), np.int32),
                         np.ones(2, bool))
    # a slot too close to t_max for k drafts + the bonus is refused
    eng.admit(0, list(range(1, SEQ - 3)), 4)     # pos = SEQ - 4
    assert not eng.spec_room(0)
    vl = np.array([True, False])
    with pytest.raises(ValueError, match="lacks room"):
        eng.begin_verify(np.zeros((2, 4), np.int32), vl)
