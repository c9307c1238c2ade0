"""The learned draft model (models/draft_lm.py) across its whole arc:
distillation through `train/loop.fit`, the sharded-checkpoint
round-trip (including cross-mesh restore), the serve-side contracts —
bit-identical greedy output spec-on vs spec-off, zero jit-cache growth
across mixed draft-hit patterns, slot migration carrying drafter
state — the ChainedDrafter composition rules, and the teaching errors
at every misuse point (malformed `propose()` returns at the
scheduler's one validation choke point, engine construction misfits).

The drafter is deliberately left UNTRAINED in most serve tests: the
verify program makes any drafter sound, so parity/recompile gates must
hold regardless of draft quality. One test trains it
(test_distilled_drafter_is_accepted_where_lookup_is_inert): the
accept-rate-with-a-TRAINED-drafter story on text that never repeats.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models import draft_lm as dlm
from idc_models_tpu.models.draft import ChainedDrafter, NGramDrafter
from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.serve import LMServer, Request, SlotEngine

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2
K = 3


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


@pytest.fixture(scope="module")
def drafter():
    cfg = dlm.draft_config(VOCAB, SEQ)
    dparams = dlm.draft_lm(cfg).init(jax.random.key(1)).params
    return dlm.DraftLM(K, dparams, cfg)


def _kw(mesh=None):
    return dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)


def _serial_tokens(gen, prompt, steps):
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps)
    return toks.tolist()[0]


# -- distillation + checkpoint ------------------------------------------


def test_distill_through_fit_and_checkpoint_roundtrip(tmp_path):
    """The recipe end to end: the target's own greedy streams as the
    corpus, KL distillation through the STANDARD train/loop.fit, and
    the save/load round-trip (sharded tree + config sidecar) restoring
    the params bit-identically."""
    model = attention_lm(VOCAB, SEQ, embed_dim=16, num_heads=2,
                         mlp_dim=32, num_blocks=1)
    variables = model.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, VOCAB, (8, 3))
    streams = dlm.greedy_streams(model, variables, prompts, SEQ)
    assert streams.shape == (8, SEQ)
    assert (streams[:, :3] == prompts).all()

    cfg = dlm.draft_config(VOCAB, SEQ, embed_dim=16, mlp_dim=32,
                           num_blocks=1)
    _, state, history = dlm.distill_draft_lm(
        model, variables, streams, config=cfg,
        mesh=meshlib.data_seq_mesh(1, 2), epochs=3, batch_size=8,
        lr=1e-2, seed=4)
    # KL against the teacher demonstrably decreases over epochs
    assert history["loss"][-1] < history["loss"][0]

    host = jax.device_get(state.params)
    dlm.save_draft_lm(tmp_path / "d", host, config=cfg).wait()
    restored, rcfg = dlm.load_draft_lm(tmp_path / "d")
    assert rcfg == cfg
    flat_a = jax.tree.leaves(host)
    flat_b = jax.tree.leaves(restored)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the restored drafter proposes exactly what the saved one does
    h = streams[0, :10]
    np.testing.assert_array_equal(
        dlm.DraftLM(K, host, cfg).propose(h),
        dlm.DraftLM(K, restored, rcfg).propose(h))


def test_ckpt_cross_mesh_restore_bit_identical_proposals(
        devices, tmp_path, drafter):
    """A drafter saved from host params restores onto DIFFERENT mesh
    shapes (FSDP vs TP rule resolution, registry "draft_lm" rules) with
    bit-identical params — so its proposals are bit-identical too."""
    host = jax.device_get(drafter.params)
    dlm.save_draft_lm(tmp_path / "d", host, config=drafter.config).wait()
    hist = np.arange(1, 9) % VOCAB
    want = drafter.propose(hist)
    for mesh in (meshlib.fsdp_tp_mesh(fsdp=2),
                 meshlib.fsdp_tp_mesh(tp=2)):
        restored, rcfg = dlm.load_draft_lm(tmp_path / "d", mesh=mesh)
        got = jax.device_get(restored)
        for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            dlm.DraftLM(K, got, rcfg).propose(hist), want)
    # a bare sharded tree without the sidecar is refused with the
    # teaching error, not a KeyError
    from idc_models_tpu.checkpoint import save_sharded

    save_sharded(str(tmp_path / "bare"), host).wait()
    with pytest.raises(FileNotFoundError, match="draft_config.json"):
        dlm.load_draft_lm(tmp_path / "bare")


# -- ChainedDrafter -----------------------------------------------------


class _Fixed:
    """Host drafter stub: returns a fixed row, or None."""

    def __init__(self, k, row):
        self.k = k
        self.row = row
        self.calls = 0

    def propose(self, history):
        self.calls += 1
        return self.row


def test_chained_drafter_first_hit_wins_and_validation(drafter):
    a = _Fixed(K, None)
    b = _Fixed(K, np.arange(K, dtype=np.int32))
    c = _Fixed(K, np.full(K, 7, np.int32))
    chain = ChainedDrafter(a, b, c)
    got = chain.propose(np.arange(5))
    np.testing.assert_array_equal(got, b.row)       # first non-None
    assert (a.calls, b.calls) == (1, 1)
    assert c.calls == 0                             # never consulted
    assert ChainedDrafter(a, c).propose(np.arange(5))[0] == 7
    # composition rules are teaching errors at construction
    with pytest.raises(ValueError, match="at least 2"):
        ChainedDrafter(a)
    with pytest.raises(ValueError, match="disagree on k"):
        ChainedDrafter(_Fixed(2, None), _Fixed(3, None))
    with pytest.raises(ValueError, match="ONE set of drafter ring"):
        ChainedDrafter(drafter, drafter)
    # the learned handle surfaces the (single) engine-backed member
    assert ChainedDrafter(a, drafter).learned is drafter
    assert ChainedDrafter(a, b).learned is None


# -- serve integration: parity, zero-recompile, migration ---------------


def test_learned_spec_parity_and_zero_recompile(devices, params,
                                                drafter):
    """The tentpole gates on CPU: spec-on with the learned drafter
    emits bit-identical greedy tokens to spec-off, and mixed
    draft-hit patterns (plain windows, full verifies, partial accepts)
    grow no jit cache after the first admission wave."""
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=K, drafter=drafter, **_kw())
    rng = np.random.default_rng(5)
    reqs = [Request(id=f"r{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + 2 * i)),
                    max_new_tokens=4 + (i % 4) * 3)
            for i in range(6)]
    server.run([(0.0, r) for r in reqs[:2]])
    sizes = server.engine.cache_sizes()
    # the drafter's own programs are in the frozen counter set
    assert {"propose", "draft_ingest", "draft_insert"} <= set(sizes)
    server.run([(0.0, r) for r in reqs[2:]])
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)
    summary = server.summary()
    assert summary["serve_spec_drafted"] > 0
    assert summary["serve_spec_propose_s"] is not None

    gen = Generator(params, **_kw())
    for r in reqs:
        got = server.poll(r.id)
        assert got is not None and got.status == "ok"
        want = _serial_tokens(gen, r.prompt, r.max_new_tokens)
        assert got.tokens == want, (r.id, got.tokens, want)


def test_distilled_drafter_is_accepted_where_lookup_is_inert(tmp_path):
    """Text that never repeats: a full-period LCG (next = 5 tok + 3 mod
    vocab) visits every token once before it returns, so in a stream
    shorter than the vocabulary no trailing n-gram has occurred before
    and the lookup drafter has nothing to propose (it drafts at most 2%
    of the tokens). A student distilled from the target's own greedy
    streams, through the checkpoint round trip, does propose and has
    drafts accepted; all three servers (off, lookup, learned) emit the
    same streams."""
    import types

    from idc_models_tpu.models.lm import next_token_loss
    from idc_models_tpu.train import TrainState, make_train_step, rmsprop

    vocab, t_max, k = 64, 64, 4

    def orbit(starts, length):
        seq = np.empty((len(starts), length), np.int64)
        seq[:, 0] = starts
        for t in range(1, length):
            seq[:, t] = (5 * seq[:, t - 1] + 3) % vocab
        return seq

    model = attention_lm(vocab, t_max, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    p0 = model.init(jax.random.key(7)).params
    opt = rmsprop(3e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=p0,
                       model_state={}, opt_state=opt.init(p0))
    step = jax.jit(make_train_step(model, opt, next_token_loss))
    rng = np.random.default_rng(11)
    key = jax.random.key(12)
    for _ in range(300):
        seqs = jnp.asarray(orbit(rng.integers(0, vocab, 8), t_max),
                           jnp.int32)
        key, sub = jax.random.split(key)
        state, _ = step(state, seqs, seqs, sub)
    target = jax.device_get(state.params)
    variables = types.SimpleNamespace(params=target, state={})

    cfg = dlm.draft_config(vocab, t_max)
    streams = dlm.greedy_streams(
        model, variables, orbit(rng.integers(0, vocab, 32), 4), t_max)
    _, dstate, _ = dlm.distill_draft_lm(
        model, variables, streams, config=cfg,
        mesh=meshlib.data_seq_mesh(1, 1), epochs=20, batch_size=8,
        lr=1e-2, seed=13)
    dlm.save_draft_lm(tmp_path / "d", jax.device_get(dstate.params),
                      config=cfg).wait()
    learned = dlm.DraftLM(k, *dlm.load_draft_lm(tmp_path / "d"))

    reqs = []
    for i in range(6):
        p_len = int(rng.integers(6, 12))
        reqs.append(Request(
            id=f"n{i}",
            prompt=tuple(int(t) for t in
                         orbit([int(rng.integers(0, vocab))], p_len)[0]),
            max_new_tokens=min(int(rng.integers(30, 44)),
                               t_max - p_len - 1)))

    def serve(mode):
        server = LMServer(target, embed_dim=E, num_heads=HEADS,
                          num_blocks=BLOCKS, t_max=t_max,
                          cache_dtype=jnp.bfloat16, n_slots=4, window=8,
                          max_prefills_per_cycle=4,
                          spec_decode=(mode != "off"), draft_k=k,
                          drafter=learned if mode == "learned" else None)
        out = server.run([(0.0, r) for r in reqs])
        assert all(r.status == "ok" for r in out)
        return {r.id: tuple(r.tokens) for r in out}, server.summary()

    tok_l, s_l = serve("learned")
    tok_o, _ = serve("off")
    tok_n, s_n = serve("ngram")
    assert tok_l == tok_o == tok_n
    assert s_n["serve_spec_drafted"] <= 0.02 * s_l["serve_tokens"], s_n
    assert s_l["serve_spec_drafted"] > 0
    assert s_l["serve_spec_accept_rate"] > 0, s_l


def test_chained_drafter_serves_with_batched_learned_member(
        devices, params, drafter):
    """The production composition through the scheduler's batched
    path: lookup-first/learned-fallback emits the same tokens as
    plain decode (any drafter is sound), and the learned member's
    device backlog is drained even on lookup-hit cycles."""
    chain = ChainedDrafter(NGramDrafter(K, order=3), drafter)
    server = LMServer(params, n_slots=2, window=4, spec_decode=True,
                      draft_k=K, drafter=chain, **_kw())
    rng = np.random.default_rng(6)
    reqs = [Request(id=f"c{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 4 + 3 * i)),
                    max_new_tokens=6 + 2 * i)
            for i in range(4)]
    server.run([(0.0, r) for r in reqs])
    gen = Generator(params, **_kw())
    for r in reqs:
        got = server.poll(r.id)
        assert got is not None and got.status == "ok"
        assert got.tokens == _serial_tokens(gen, r.prompt,
                                            r.max_new_tokens)


def test_migration_carries_drafter_state(devices, params, drafter):
    """PR 18's live slot migration extended to drafter state: a
    mid-decode slot exported from one spec-armed engine and imported
    into another resumes with bit-identical output — including the
    drafter's ring rows and pending-token backlog."""
    src = SlotEngine(params, n_slots=2, draft_k=K, draft_model=drafter,
                     **_kw())
    src.warmup(4)
    dst = SlotEngine(params, n_slots=2, draft_k=K, draft_model=drafter,
                     **_kw())
    dst.warmup(4)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, VOCAB, 7)
    src.admit(1, prompt, 12)
    mid = src.step_window(4)[1]              # decode a bit, then move
    snap = src.export_slot(1)
    assert snap["draft"]["front"] > 0
    dst.import_slot(0, snap)
    rest = []
    for _ in range(20):
        if not dst._occupied[0]:
            break
        r = dst.propose_all()
        if r is None:
            rest.extend(dst.step_window(4).get(0, []))
        else:
            drafts, live = r
            dst.begin_verify(drafts, live)
            rest.extend(dst.collect()[0])
        if dst._occupied[0] and dst._rem_h[0] < 1:
            dst.release(0)
    gen = Generator(params, **_kw())
    want = _serial_tokens(gen, prompt, 12)
    assert mid + rest == want, (mid, rest, want)

    # presence mismatches are teaching errors BOTH ways
    plain = SlotEngine(params, n_slots=1, **_kw())
    plain.warmup(4)
    plain.admit(0, prompt, 9)
    plain.step_window(4)
    with pytest.raises(ValueError, match="no learned-drafter state"):
        dst.import_slot(1, plain.export_slot(0))
    plain.release(0)                      # export does not free the slot
    src.admit(0, prompt, 9)
    src.step_window(4)
    with pytest.raises(ValueError, match="no draft_model"):
        plain.import_slot(0, src.export_slot(0))


# -- teaching errors ----------------------------------------------------


class _Settable:
    """Drafter whose next proposal the test scripts."""

    def __init__(self, k):
        self.k = k
        self.row = None

    def propose(self, history):
        return self.row


def test_malformed_propose_teaching_errors(devices, params):
    """Every malformed `propose()` return dies at the scheduler's ONE
    validation choke point with a message naming the drafter class and
    the contract — never a raw jit shape error downstream."""
    bad = _Settable(K)
    server = LMServer(params, n_slots=1, window=4, spec_decode=True,
                      draft_k=K, drafter=bad, **_kw())
    cases = [
        (np.zeros(K, np.float32), "dtype float32"),
        (np.zeros((1, K), np.int32), "ONE flat row"),
        (np.zeros(K + 1, np.int32), f"compiled at exactly k={K}"),
        (np.full(K, VOCAB, np.int32), "out-of-vocab id"),
    ]
    # each raise ABORTS the running request (the scheduler cannot
    # trust device state after a mid-cycle failure), so every case
    # gets a fresh one
    for i, (row, msg) in enumerate(cases):
        bad.row = None
        server.submit(Request(id=f"m{i}", prompt=(1, 2, 3),
                              max_new_tokens=12))
        server.step()                              # admission cycle
        bad.row = row
        with pytest.raises(ValueError) as e:
            for _ in range(4):
                server.step()
        assert "_Settable.propose returned" in str(e.value)
        assert msg in str(e.value)
        assert "models/draft.py contract" in str(e.value)
    # a well-formed row (and None) flow on untouched
    bad.row = None
    server.submit(Request(id="ok", prompt=(1, 2, 3),
                          max_new_tokens=12))
    server.step()
    bad.row = np.zeros(K, np.int32)
    server.step()
    bad.row = None
    server.step()


def test_engine_drafter_construction_teaching_errors(params, drafter):
    """Misfits between drafter and engine die at construction with
    errors that say what to change."""
    with pytest.raises(ValueError, match="needs draft_k"):
        SlotEngine(params, n_slots=1, draft_model=drafter, **_kw())
    cfg13 = dlm.draft_config(13, SEQ)
    d13 = dlm.DraftLM(K, dlm.draft_lm(cfg13).init(
        jax.random.key(8)).params, cfg13)
    with pytest.raises(ValueError, match="share one tokenizer"):
        SlotEngine(params, n_slots=1, draft_k=K, draft_model=d13,
                   **_kw())
    short = dlm.draft_config(VOCAB, SEQ // 2)
    dshort = dlm.DraftLM(K, dlm.draft_lm(short).init(
        jax.random.key(9)).params, short)
    with pytest.raises(ValueError, match="seq_len >= t_max"):
        SlotEngine(params, n_slots=1, draft_k=K, draft_model=dshort,
                   **_kw())
    with pytest.raises(ValueError, match="without a learned drafter"):
        LMServer(params, n_slots=1, spec_decode=True, draft_k=K,
                 draft_partition_rules=(), **_kw())
