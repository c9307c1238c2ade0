"""A model whose layers pick the positions they attend (a learned
indexer over index keys cached beside K/V) through the ONE serving
forward, on the contiguous engine that prefills into the slot's own
rows. Tiny sizes, seeded float32 weights, against the plain reference
`tests/keye_ref.py` (which imports nothing of the package)."""

import filecmp
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import keye_ref
from idc_models_tpu import ring_decode as rd
from idc_models_tpu.models import lm, moe
from idc_models_tpu.serve import LMServer, Request
from idc_models_tpu.serve.engine import SlotEngine

ROOT = pathlib.Path(__file__).resolve().parent.parent
VOCAB, T_MAX, TOPK, CHUNK = 61, 128, 16, 16
N_EXPERTS, TOP_K, HELD = 8, 3, (2, 2)
# the reference's view: the published config.json keys
CFG = {
    "num_hidden_layers": 3, "hidden_size": 32, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 1e7, "num_experts": N_EXPERTS,
    "num_experts_per_tok": TOP_K, "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "topk": TOPK},
}


@pytest.fixture(scope="module")
def model():
    spec = lm.keye_spec(CFG, held=HELD, param_dtype="float32")
    params = jax.jit(lambda k: lm.init_params(
        spec, VOCAB, k, expert_dim=16))(jax.random.key(11))
    return spec, params


def engine(model, n_slots=3):
    spec, params = model
    return SlotEngine(params, spec=spec, t_max=T_MAX, n_slots=n_slots,
                      prefill_chunk=CHUNK, cache_dtype=jnp.float32)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def reference(params, seq, **kw):
    out = keye_ref.forward(params, seq, CFG, HELD, block=16, **kw)
    return jax.tree.map(np.asarray, out)


def bits_of(positions, words):
    """[.., k] positions (-1: none) -> [.., words] uint32 bits."""
    mask = np.zeros(positions.shape[:-1] + (32 * words,), bool)
    for at in np.ndindex(*positions.shape[:-1]):
        mask[at][positions[at][positions[at] >= 0]] = True
    return keye_ref.pack_bits(mask)


def test_the_spec_reads_the_published_keys():
    spec = lm.keye_spec(CFG, held=HELD, param_dtype="float32")
    (l,) = set(spec.layers)
    assert len(spec.layers) == 3 and spec.sparse and not spec.classic
    assert (l.heads, l.kv_heads, l.head_dim, l.qk_norm) == (4, 2, 8, True)
    assert l.indexer == lm.Indexer(4, 8, TOPK, rotary=lm.Rotary(1e7, 8))
    assert l.rotary == lm.Rotary(1e7, 8) and l.window is None and not l.gate
    assert l.experts == moe.Experts(N_EXPERTS, TOP_K, 2, 2, routed_scale=1.0,
                                    shared=False)
    with pytest.raises(ValueError, match="mlp_only_layers"):
        lm.keye_spec(dict(CFG, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="one shared index key"):
        lm.keye_spec(dict(CFG, sa_config=dict(CFG["sa_config"],
                                              indexer_num_kv_heads=2)))
    # what the layers add draws from a stream of its own: a spec without
    # them makes the tree it always made
    plain = lm.ModelSpec(32, (lm.LayerSpec(4, 2, 8, ffn="swiglu"),),
                         norm="rmsnorm", learned_pos=False)
    normed = plain._replace(layers=(plain.layers[0]._replace(qk_norm=True),))
    a = lm.init_params(plain, 7, jax.random.key(0), mlp_dim=8)
    b = lm.init_params(normed, 7, jax.random.key(0), mlp_dim=8)
    del b["block0"]["mha"]["q_norm"], b["block0"]["mha"]["k_norm"]
    assert jax.tree.all(jax.tree.map(lambda x, y: bool((x == y).all()), a, b))


@pytest.mark.parametrize("p_len", [5, 16, 23, 50, 97])
def test_engine_prefill_and_decode_equal_the_reference(model, p_len):
    """Chunked prefill into the slot's own rows, then one-token windows:
    prompts shorter than `topk` (the selection is every position), as
    long as a chunk, ending inside a chunk, and several times `topk`.
    The logits at the last prompt position and at every decoded one are
    the full forward's, which selects for itself."""
    eng = engine(model)
    seq, n_dec = prompt(p_len, p_len), 9
    eng.admit(1, seq, n_dec)
    got = [eng.slot_logits(1)]
    for _ in range(n_dec):
        seq = np.append(seq, eng.step_window(1)[1]).astype(np.int32)
        got.append(eng.slot_logits(1))
    want, _, (_, count, _) = reference(model[1], seq,
                                       rows=(p_len - 1, p_len + n_dec))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)
    assert (count == np.minimum(np.arange(len(seq)) + 1, TOPK)).all()


def test_two_slots_of_different_lengths_in_one_window(model):
    """A fused window over a slot below `topk` and one far above it, and
    a third admitted while they decode: every slot's logits after every
    window are the reference's at its own position."""
    eng = engine(model)
    seqs = {0: prompt(7, 1), 2: prompt(70, 2)}
    for slot, s in seqs.items():
        eng.admit(slot, s, 12)
    seen = []
    for step in range(4):
        if step == 1:
            seqs[1] = prompt(33, 3)
            eng.admit(1, seqs[1], 12)
        for slot, toks in eng.step_window(3).items():
            seqs[slot] = np.append(seqs[slot], toks).astype(np.int32)
            seen.append((slot, len(seqs[slot]) - 1, eng.slot_logits(slot)))
    want = {slot: reference(model[1], s)[0] for slot, s in seqs.items()}
    assert len(seen) == 11
    for slot, row, got in seen:
        np.testing.assert_allclose(got, want[slot][row], atol=2e-4, rtol=0)


def test_the_selection_handed_out_is_the_references(model):
    """The positions the folds selected, at every position of a
    sequence (bits from the prefill chunks, indices from the decode
    windows), forced on the reference: none of them is outside the
    reference's own choice, every query attended min(position + 1, topk)
    positions, and the logits follow. Equal scores (the relu makes exact
    zeros) go to the lower position on both sides."""
    eng = engine(model)
    p_len, n_dec = 45, 6
    seq, words = prompt(p_len, 9), -(-(p_len + n_dec) // 32)
    eng.start_prefill(1, seq, n_dec)
    chosen, done = [], False
    while not done:
        done = eng.prefill_step(1)
        chosen.append(eng.selected_positions("prefill")[:, :, :words])
    chosen = [np.concatenate(chosen, axis=1)[:, :p_len]]
    assert chosen[0].shape == (3, p_len, words)
    got = []
    for _ in range(n_dec):
        seq = np.append(seq, eng.step_window(1)[1]).astype(np.int32)
        sel = eng.selected_positions("window")        # [1, 3, S, topk]
        assert sel.shape == (1, 3, 3, TOPK)
        chosen.append(bits_of(sel[0][:, 1, None], words))
        got.append(eng.slot_logits(1))
    want, _, (deficit, count, swapped) = reference(
        model[1], seq, rows=(p_len, p_len + n_dec),
        select=np.concatenate(chosen, axis=1))
    assert swapped.sum() == 0 and deficit.max() == 0
    assert (count == np.minimum(np.arange(len(seq)) + 1, TOPK)).all()
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)
    # a selection moved by one position is caught by the account
    shifted = np.concatenate(chosen, axis=1)
    shifted = (shifted << np.uint32(1)) | np.pad(
        shifted[..., :-1] >> np.uint32(31), ((0, 0), (0, 0), (1, 0)))
    _, _, (deficit, _, swapped) = reference(model[1], seq, select=shifted)
    assert np.isinf(deficit).any() and swapped.sum() > 0


def test_select_topk_is_lax_top_k_with_ties_to_the_lower_position():
    """The sort-free selection of the chunk fold against `lax.top_k`, on
    scores full of exact ties, rows with fewer visible positions than k,
    and a frontier inside the buffer (blocks of 8)."""
    rng = np.random.default_rng(0)
    n, t, k = 12, 64, 10
    score = rng.integers(-3, 4, (n, t)).astype(np.float32)    # many ties
    score[:4] = rng.normal(size=(4, t))
    visible = np.arange(t)[None, :] <= (np.arange(n)[:, None] * 5 + 2)
    score = np.where(visible, score, -np.inf).astype(np.float32)
    frontier = int(visible.sum(1).max())
    got = np.asarray(jax.jit(lambda s: rd._select_topk(s, k, frontier, 8))(
        jnp.asarray(score)))
    top, idx = jax.lax.top_k(jnp.asarray(score), k)
    want = np.zeros((n, t), bool)
    for r in range(n):
        want[r, np.asarray(idx[r])[np.asarray(top[r]) > -np.inf]] = True
    assert (got == want).all()
    assert (got.sum(1) == np.minimum(visible.sum(1), k)).all()
    np.testing.assert_array_equal(
        np.asarray(rd.pack_bits(jnp.asarray(got))), keye_ref.pack_bits(got))


GRP = rd._FOLD_GROUP
LIVE_MASKS = {
    "none": [],
    "first slot": [0],
    "last slot": [2 * GRP],
    "scattered, fewer than a group": list(range(1, 2 * GRP, 3))[:GRP - 1],
    "a whole group": list(range(0, 2 * GRP, 2)),
    "a group and one": list(range(GRP)) + [2 * GRP],
    "all": list(range(2 * GRP + 1)),
}


@pytest.mark.parametrize("rows", LIVE_MASKS.values(), ids=LIVE_MASKS.keys())
def test_decode_fold_selects_gathers_and_attends_for_live_rows_alone(rows):
    """`make_sparse_decode` over a batch of two groups and one slot, the
    slots at different lengths (below `topk` and far above it), against
    the fold spelled over ALL rows (append, score, `lax.top_k`, gather,
    softmax): the live rows select and attend as there, the dead ones
    report -1, put out zeros and leave their rows of all three caches as
    they were, and the account says how many rows were folded."""
    from idc_models_tpu import mesh as meshlib

    b, t, h, g, d, j, di, k = 2 * GRP + 1, 96, 4, 2, 128, 3, 8, 12
    r = iter(jax.random.split(jax.random.key(5), 9))
    norm = lambda *shape: jax.random.normal(next(r), shape, jnp.float32)
    kc, vc, ic = norm(b, t, g, d), norm(b, t, g, d), norm(b, di, t)
    q, kt, vt = norm(b, 1, h, d), norm(b, 1, g, d), norm(b, 1, g, d)
    qi, kit, w = norm(b, 1, j, di), norm(b, 1, 1, di), norm(b, 1, j)
    pos = jnp.asarray((np.arange(b) * 37 + 5) % (t - 1), jnp.int32)
    assert (np.asarray(pos) < k).any() and (np.asarray(pos) > 4 * k).any()
    live = np.zeros(b, bool)
    live[rows] = True
    fold = jax.jit(rd.make_sparse_decode(meshlib.seq_mesh(1), topk=k))
    out, kc2, vc2, ic2, account = jax.tree.map(np.asarray, fold(
        kc, vc, ic, q, kt, vt, (qi, kit, w), pos, jnp.asarray(live)))

    # the fold over every row, as it stood before the live rows' groups
    at, every = np.asarray(pos), np.arange(b)
    want_kc = np.asarray(kc).copy()
    want_vc = np.asarray(vc).copy()
    want_ic = np.asarray(ic).copy()
    want_kc[every, at] = np.asarray(kt)[:, 0]
    want_vc[every, at] = np.asarray(vt)[:, 0]
    want_ic[every, :, at] = np.asarray(kit)[:, 0, 0]
    hi = dict(precision="highest")
    score = jnp.sum(jnp.maximum(jnp.einsum("bjd,bdt->bjt", qi[:, 0], want_ic,
                                           **hi), 0.0)
                    * w[:, 0, :, None], axis=1)
    score = jnp.where(np.arange(t)[None, :] <= at[:, None], score, -jnp.inf)
    top, idx = jax.lax.top_k(score, k)
    valid = np.asarray(top) > -np.inf
    kg, vg = want_kc[every[:, None], idx], want_vc[every[:, None], idx]
    qh = np.asarray(q)[:, 0].reshape(b, g, h // g, d)
    s = jnp.einsum("bgrd,bkgd->bgrk", qh, kg, **hi) * d ** -0.5
    p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, -jnp.inf), -1)
    want = np.asarray(jnp.einsum("bgrk,bkgd->bgrd", p, vg, **hi)
                      ).reshape(b, h, d)

    dead = ~live
    np.testing.assert_allclose(out[live, 0], want[live], atol=2e-5, rtol=0)
    assert (out[dead] == 0).all()
    got = account["selected"]
    assert got.shape == (b, k) and (got[dead] == -1).all()
    for row in np.flatnonzero(live):
        assert (got[row] >= 0).sum() == min(at[row] + 1, k)
        assert set(got[row][got[row] >= 0]) == set(
            np.asarray(idx)[row][valid[row]])
    np.testing.assert_allclose(
        account["sel_share"],
        np.where(live, np.minimum(at + 1, k) / (at + 1.0), 0.0), rtol=1e-6)
    assert (account["sel_rows"] == live).all()
    assert account["fold_rows"] == -(-live.sum() // GRP) * GRP
    for new, old, full in ((kc2, kc, want_kc), (vc2, vc, want_vc),
                           (ic2, ic, want_ic)):
        assert (new[dead] == np.asarray(old)[dead]).all()
        assert (new[live] == full[live]).all()


def test_in_place_prefill_touches_no_other_slot_and_owns_no_row(model):
    """A request's chunks write the reserved slot's own rows: the other
    slots' rows of every cache (K, V, index keys) stay bit-identical, a
    pending prefill holds no cache of its own, and the insert moves
    none."""
    eng = engine(model)
    eng.admit(0, prompt(40, 1), 5)
    eng.admit(2, prompt(21, 2), 5)
    eng.step_window(2)
    before = jax.tree.map(np.asarray, eng._caches)
    eng.start_prefill(1, prompt(55, 3), 4)
    assert eng._prefills[1].caches is None and 1 not in eng.free_slots()
    while not eng.prefill_step(1):
        assert eng._prefills[1].caches is None
    after = jax.tree.map(np.asarray, eng._caches)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert (b[[0, 2]] == a[[0, 2]]).all()
    assert any((b[1] != a[1]).any() for b, a in
               zip(jax.tree.leaves(before), jax.tree.leaves(after)))
    # three caches a layer: K, V [S, T, G*D] and index keys [S, DI, T]
    assert [tuple(c.shape for c in layer) for layer in eng._caches] == [
        ((3, T_MAX, 16), (3, T_MAX, 16), (3, 8, T_MAX))] * 3
    assert eng.kv_bytes_by_kind() == {
        "full": 3 * 2 * 3 * T_MAX * 16 * 4, "window": 0,
        "index": 3 * 3 * T_MAX * 8 * 4}
    assert eng.kv_bytes_per_slot() == sum(eng.kv_bytes_by_kind().values()) // 3


def test_a_recycled_slot_never_sees_the_last_tenants_index_keys(model):
    """Insert, release and reuse: a long request's rows (index keys
    among them) lie under a short one's frontier, which decodes as if
    the slot were fresh; a slot released with budget left stops riding
    along before the next tenant's chunks land."""
    eng = engine(model, n_slots=2)
    eng.admit(0, prompt(90, 1), 20)
    eng.step_window(4)
    eng.release(0)                          # budget left: it would ride
    assert eng._riding[0]
    seq = prompt(19, 2)
    eng.admit(0, seq, 8)
    assert not eng._riding[0]
    got = [eng.slot_logits(0)]
    for _ in range(4):
        seq = np.append(seq, eng.step_window(2)[0]).astype(np.int32)
        got.append(eng.slot_logits(0))
    want = reference(model[1], seq)[0][[18, 20, 22, 24, 26]]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)
    # the old tenant's index keys are still in the rows beyond
    assert np.asarray(eng._caches[0][2])[0, :, 40:90].any()


def test_server_serves_the_spec_and_counts_its_selection(model):
    """LMServer over the spec: every request's tokens are the
    reference's greedy ones (each the reference's best after the tokens
    before it), nothing compiles after warm-up, and the indexer's
    account reaches summary()."""
    spec, params = model
    server = LMServer(params, spec=spec, t_max=T_MAX, n_slots=2, window=3,
                      prefill_chunk=CHUNK, cache_dtype=jnp.float32)
    lens = (4, 30, 57, 9)
    reqs = [Request(id=f"r{i}", prompt=tuple(prompt(n, 10 + i)),
                    max_new_tokens=7) for i, n in enumerate(lens)]
    results = {r.id: r for r in server.run([(0.0, r) for r in reqs])}
    for r in reqs:
        assert results[r.id].status == "ok"
        seq = np.asarray(r.prompt + tuple(results[r.id].tokens), np.int32)
        want = reference(params, seq)[0][len(r.prompt) - 1:-1].argmax(-1)
        assert list(results[r.id].tokens) == list(want)
    s = server.summary()
    assert s["serve_compiles_observed"] == 0
    assert s["serve_moe_assignments"] == 28 * 3 * TOP_K
    # selected over visible, mean over the 28 live token steps: the step
    # that feeds token j of a prompt of n sees n + j + 1 positions
    want = np.mean([min(TOPK, n + j + 1) / (n + j + 1)
                    for n in lens for j in range(7)])
    assert s["serve_dsa_selected_share"] == pytest.approx(want, rel=1e-6)
    assert s["serve_index_cache_bytes"] == 3 * 2 * T_MAX * 8 * 4
    assert "serve_attn_read_share" not in s
    names = server.metrics._reg.prometheus_text()
    assert "serve_dsa_selected_share" in names
    assert "serve_index_cache_bytes" in names


def test_summary_says_how_many_rows_the_fold_ran_for_a_live_one(model):
    """`serve_dsa_folded_over_live`: rows the decode fold sorted,
    gathered and attended for over live (step, slot) pairs,
    ceil(n / group) * group / n with n slots live. One request alone on
    a server of a group and one slot reads the group; every slot live
    reads two groups over the slots."""
    spec, params = model
    server = LMServer(params, spec=spec, t_max=T_MAX, n_slots=GRP + 1,
                      window=3, prefill_chunk=CHUNK, cache_dtype=jnp.float32)
    server.run([(0.0, Request(id="r", prompt=tuple(prompt(30)),
                              max_new_tokens=7))])
    assert server.summary()["serve_dsa_folded_over_live"] == GRP
    assert "serve_dsa_folded_over_live" in (
        server.metrics._reg.prometheus_text())
    eng = engine(model, n_slots=GRP + 1)
    for slot in range(GRP + 1):
        eng.admit(slot, prompt(20 + slot, slot), 5)
    eng.step_window(2)
    assert eng.last_dsa["rows"] == 2 * (GRP + 1)
    assert eng.last_dsa["fold_rows"] == 2 * 2 * GRP


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test at the published split: the
    routed terms of the eight shares (experts 0-15, 16-31, ... 112-127,
    no shared expert) add up to the layer with every expert held, and
    that is the reference's uncut layer."""
    e, f, n, k = 16, 8, 9, 8
    whole = moe.Experts(128, k, 0, 128, routed_scale=1.0, shared=False)
    spec = lm.ModelSpec(e, (lm.LayerSpec(2, 2, 8, ffn="experts",
                                         experts=whole),),
                        norm="rmsnorm", learned_pos=False)
    p = lm.init_params(spec, 5, jax.random.key(1), expert_dim=f)["block0"]["moe"]
    x = jax.random.normal(jax.random.key(2), (n, e))
    full, _ = moe.expert_ffn(p, x, whole, interpret=True)
    total = jnp.zeros_like(full)
    for first in range(0, 128, 16):
        ps = dict(p, experts=jax.tree.map(lambda a: a[first:first + 16],
                                          p["experts"]))
        y, st = moe.expert_ffn(ps, x, whole._replace(first=first, count=16),
                               interpret=True)
        total = total + y
        assert st["held"].shape == (16,) and st["assigned"] == n * k
    np.testing.assert_allclose(total, full, atol=1e-5)
    dot = lambda a, w: jnp.matmul(a, jnp.asarray(w, jnp.float32),
                                  precision="highest")
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True}
    ref, _ = keye_ref._experts(p, x, cfg, (0, 128), None, dot, 3)
    np.testing.assert_allclose(full, ref, atol=1e-5)
    shares = sum(keye_ref._experts(
        dict(p, experts=jax.tree.map(lambda a: a[first:first + 16],
                                     p["experts"])),
        x, cfg, (first, 16), None, dot, 3)[0] for first in range(0, 128, 16))
    np.testing.assert_allclose(shares, ref, atol=1e-5)


def test_the_two_copies_of_the_reference_agree():
    assert filecmp.cmp(ROOT / "tests" / "keye_ref.py",
                       ROOT / "benchmark" / "reference" / "keye_ref.py",
                       shallow=False)


@pytest.mark.parametrize("mechanism,kwargs", [
    ("paged KV", dict(kv_page_size=4, kv_pages=64)),
    ("int8 KV", dict(kv_dtype="int8")),
    ("speculative decoding", dict(spec_decode=True, draft_k=2)),
    ("the prefix cache", dict(prefix_cache_mb=1.0)),
    ("monolithic ring prefill", dict(prefill_chunk=None)),
    ("a sequence ring of 2 devices", dict(mesh="seq2")),
])
def test_paths_that_cannot_carry_the_spec_refuse_by_name(model, mechanism,
                                                         kwargs):
    from idc_models_tpu import mesh as meshlib

    spec, params = model
    kw = dict(prefill_chunk=CHUNK) | kwargs
    if kw.get("mesh") == "seq2":
        kw["mesh"] = meshlib.seq_mesh(2)
    with pytest.raises(ValueError, match=mechanism):
        LMServer(params, spec=spec, t_max=T_MAX, n_slots=2, warmup=False,
                 **kw)


def test_migration_rollout_and_the_aot_cache_refuse_by_name(model):
    eng = engine(model, n_slots=2)
    eng.admit(0, prompt(5), 3)
    assert not eng.supports_slot_migration
    with pytest.raises(ValueError, match="slot migration"):
        eng.export_slot(0)
    with pytest.raises(ValueError, match="slot migration"):
        eng.import_slot(1, {})
    with pytest.raises(ValueError, match="prefills in place"):
        eng.spot_check_params(model[1])
    with pytest.raises(ValueError, match="AOT compile cache"):
        eng.warmup(2, compile_cache=object())
    with pytest.raises(ValueError, match="the serial Generator"):
        lm.Generator(model[1], spec=model[0], t_max=T_MAX,
                     prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="indexer"):
        model[0].require_classic("anything else")
