"""A model of latent attention (one cached row a position) whose layers
attend a selection of positions, their own indexer's or an earlier
layer's, with sigmoid routing and a correction bias, through the ONE
serving forward, on the contiguous engine that prefills into the slot's
own rows. Tiny sizes, seeded float32 weights, against the plain
reference `tests/glm_ref.py` (which imports nothing of the package and
takes the expanded form of the attention)."""

import filecmp
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_ref
from idc_models_tpu import mesh as meshlib
from idc_models_tpu import ring_decode as rd
from idc_models_tpu.models import lm, moe
from idc_models_tpu.serve import LMServer, Request
from idc_models_tpu.serve.engine import SlotEngine

ROOT = pathlib.Path(__file__).resolve().parent.parent
VOCAB, T_MAX, TOPK, CHUNK = 61, 128, 16, 16
N_EXPERTS, TOP_K, HELD = 8, 3, (2, 4)
# the reference's view: the published config.json keys, at a tiny size
CFG = {
    "num_hidden_layers": 5, "hidden_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "qk_head_dim": 12, "v_head_dim": 8,
    "index_n_heads": 2, "index_head_dim": 8, "index_topk": TOPK,
    "indexer_types": ["full", "shared", "shared", "shared", "full"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "n_routed_experts": N_EXPERTS, "num_experts_per_tok": TOP_K,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 8e6, "rope_type": "default"},
    "rope_interleave": True, "indexer_rope_interleave": True,
    "attention_bias": False, "moe_layer_freq": 1,
}
ROW = 16 + 4                                  # kv_lora_rank + qk_rope_head_dim
LANES = rd.latent_cache_shape(1, T_MAX, ROW)[-1]


@pytest.fixture(scope="module")
def model():
    spec = lm.glm_spec(CFG, held=HELD, param_dtype="float32")

    def init(key):
        params = lm.init_params(spec, VOCAB, key, mlp_dim=24, expert_dim=16)
        for i in range(len(spec.layers)):
            if "moe" in params[f"block{i}"]:
                # a bias that changes picks at this size
                params[f"block{i}"]["moe"]["bias"] *= 10.0
        return params

    return spec, jax.jit(init)(jax.random.key(11))


def engine(model, n_slots=3):
    spec, params = model
    return SlotEngine(params, spec=spec, t_max=T_MAX, n_slots=n_slots,
                      prefill_chunk=CHUNK, cache_dtype=jnp.float32)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def reference(params, seq, **kw):
    out = glm_ref.forward(params, seq, CFG, HELD, block=16, **kw)
    return jax.tree.map(np.asarray, out)


def test_the_spec_reads_the_published_keys():
    spec = lm.glm_spec(CFG, held=HELD, param_dtype="float32")
    assert len(spec.layers) == 5 and spec.sparse and not spec.classic
    lat = lm.Latent(16, 16, 8, 4, 8)
    for i, l in enumerate(spec.layers):
        assert (l.heads, l.kv_heads, l.head_dim, l.latent) == (4, 1, 12, lat)
        assert l.rotary == lm.Rotary(8e6, 4, interleave=True)
        assert l.selects and (l.shares is None) == (l.indexer is not None)
        assert l.shares == (None if i in (0, 4) else 0)
    assert spec.layers[0].indexer == lm.Indexer(
        2, 8, TOPK, rotary=lm.Rotary(8e6, 4, interleave=True),
        query="q_latent")
    assert spec.layers[0].ffn == "swiglu" and spec.layers[0].experts is None
    assert spec.layers[1].experts == moe.Experts(
        N_EXPERTS, TOP_K, 2, 4, routed_scale=2.5, shared=True,
        scoring="sigmoid")
    assert lat.row == 20 and lat.scale == 12 ** -0.5
    for key, value in (("n_group", 2), ("topk_group", 4),
                       ("scoring_func", "softmax"), ("topk_method", "greedy"),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            lm.glm_spec(dict(CFG, **{key: value}))
    with pytest.raises(ValueError, match="rope_type"):
        lm.glm_spec(dict(CFG, rope_parameters={"rope_theta": 1.0,
                                               "rope_type": "yarn"}))
    with pytest.raises(ValueError, match="indexer_types"):
        lm.glm_spec(dict(CFG, indexer_types=["full", "dense"] + ["full"] * 3))
    with pytest.raises(ValueError, match="no layer before it"):
        lm.glm_spec(dict(CFG, indexer_types=["shared"] + ["full"] * 4))


def test_indexer_types_are_the_rule_of_freq_and_offset():
    """The published list, all 78 layers, is what index_skip_topk_offset
    and index_topk_freq generate; the served layers are its slice."""
    c = json.loads((ROOT / "benchmark" / "configs" / "glm-5.2.json").read_text())
    published = c["indexer_types_published"]
    off, freq = c["index_skip_topk_offset"], c["index_topk_freq"]
    assert len(published) == 78 == len(c["mlp_layer_types_published"])
    assert published == ["full" if i < off or (i - off) % freq == freq - 1
                         else "shared" for i in range(78)]
    assert c["indexer_types"] == [published[i] for i in c["layers_taken"]]
    assert c["mlp_layer_types"] == [c["mlp_layer_types_published"][i]
                                    for i in c["layers_taken"]]


def test_the_new_kinds_draw_from_streams_of_their_own():
    """A spec without latent layers or a sigmoid router makes the tree it
    always made."""
    plain = lm.ModelSpec(32, (lm.LayerSpec(4, 2, 8, ffn="experts",
                                           experts=moe.Experts(8, 2, 0, 8)),),
                         norm="rmsnorm", learned_pos=False)
    a = lm.init_params(plain, 7, jax.random.key(0), expert_dim=8)
    biased = plain._replace(layers=(plain.layers[0]._replace(
        experts=moe.Experts(8, 2, 0, 8, scoring="sigmoid")),))
    b = lm.init_params(biased, 7, jax.random.key(0), expert_dim=8)
    assert b["block0"]["moe"].pop("bias").dtype == jnp.float32
    assert jax.tree.all(jax.tree.map(lambda x, y: bool((x == y).all()), a, b))


@pytest.mark.parametrize("p_len", [5, 16, 23, 50, 97])
def test_engine_prefill_and_decode_equal_the_reference(model, p_len):
    """Chunked prefill into the slot's own rows, then one-token windows:
    prompts shorter than `topk` (the selection is every position), as
    long as a chunk, ending inside a chunk, and several times `topk`.
    The logits at the last prompt position and at every decoded one are
    the full forward's, which selects for itself, in expanded form."""
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
    assert count.shape == (2, len(seq))                  # two layers select
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


def test_absorbed_equals_expanded_attention_on_the_same_rows():
    """The chunk fold's absorbed attention (each head's nope query taken
    into the latent, the cached rows read as keys and values at once)
    against the reference's expanded form (keys and values decompressed
    from the latent) on the same rows, queries and selection."""
    t, h, nope, rope, v, r = 48, 4, 8, 4, 8, 16
    ks = jax.random.split(jax.random.key(5), 6)
    q_nope = jax.random.normal(ks[0], (t, h, nope))
    q_pe = jax.random.normal(ks[1], (t, h, rope))
    c_kv = jax.random.normal(ks[2], (t, r))
    k_pe = jax.random.normal(ks[3], (t, rope))
    w_kv = jax.random.normal(ks[4], (r, h, nope + v)) / np.sqrt(r)
    mask = np.tril(np.asarray(jax.random.bernoulli(ks[5], 0.5, (t, t))))
    mask[np.arange(t), np.arange(t)] = True
    prod = lambda s, x, w: jnp.einsum(s, x, w, precision="highest")
    with jax.default_matmul_precision("highest"):
        want = glm_ref._attention(q_nope, q_pe, c_kv, k_pe, w_kv,
                                  jnp.asarray(mask), block=16,
                                  scale=(nope + rope) ** -0.5, prod=prod)
        q = jnp.concatenate([jnp.einsum("thn,rhn->thr", q_nope,
                                        w_kv[..., :nope]), q_pe], axis=-1)
        row = jnp.concatenate([c_kv, k_pe], axis=-1)
        lc = jnp.zeros(rd.latent_cache_shape(2, t, r + rope))
        fold = rd.make_sparse_chunk_decode(
            meshlib.seq_mesh(1), topk=TOPK, latent=True, shared=True,
            scale=(nope + rope) ** -0.5)
        o, lc2, account = fold(lc, q[None], row[None, :, None],
                               {"selected_bits": jnp.asarray(
                                   glm_ref.pack_bits(mask))}, 0, 1)
        got = jnp.einsum("thr,rhv->thv", o[0, ..., :r], w_kv[..., nope:])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # the rows written are the slot's alone, padded with zeros
    np.testing.assert_array_equal(lc2[1, :, :r + rope], row)
    assert not np.asarray(lc2[0]).any() and not np.asarray(lc2[1, :, r + rope:]).any()


def test_a_shared_layer_attends_its_sources_selection_alone(model):
    """Every layer that shares attends exactly the positions its source
    selected, in the prefill chunks (bits) and in the decode windows
    (indices), and holds one cache, its rows: no index keys."""
    eng = engine(model)
    eng.start_prefill(0, prompt(45, 4), 6)
    done = False
    while not done:
        done = eng.prefill_step(0)
        bits = eng.selected_positions("prefill")
        assert bits.shape == (5, CHUNK, T_MAX // 32)
        for i in (1, 2, 3):
            np.testing.assert_array_equal(bits[i], bits[0])
    # past `topk`, the last chunk's: layer 4 selects anew
    assert (bits[4] != bits[0]).any()
    eng.step_window(3)
    sel = eng.selected_positions("window")
    assert sel.shape == (3, 5, 3, TOPK)
    for i in (1, 2, 3):
        np.testing.assert_array_equal(sel[:, i], sel[:, 0])
    assert [len(c) for c in eng._caches] == [2, 1, 1, 1, 2]
    assert {c.shape for layer in eng._caches for c in layer} == {
        (3, T_MAX, LANES), (3, 8, T_MAX)}
    # the decode fold of a sharing layer, handed a selection, attends it
    fold = rd.make_sparse_decode(meshlib.seq_mesh(1), topk=TOPK,
                                 latent=True, shared=True, scale=0.5)
    lc = jax.random.normal(jax.random.key(1), (2, 32, LANES)).at[..., ROW:].set(0)
    q = jax.random.normal(jax.random.key(2), (2, 1, 4, ROW))
    row = jax.random.normal(jax.random.key(3), (2, 1, 1, ROW))
    taken = jnp.asarray([[3, 9, 20, -1], [0, 1, 2, 5]], jnp.int32)
    out, lc2, account = fold(lc, q, row, {"selected": taken},
                             jnp.asarray([20, 7]), jnp.asarray([True, True]))
    np.testing.assert_array_equal(account["selected"], taken)
    for b in range(2):
        at = np.asarray(taken[b][taken[b] >= 0])
        keys = np.asarray(lc2[b, at, :ROW])
        s = np.einsum("hd,kd->hk", np.asarray(q[b, 0]), keys) * 0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ keys
        np.testing.assert_allclose(out[b, 0, :, :ROW], want, atol=1e-5)


@pytest.mark.parametrize("pos", [(3, 40), (TOPK - 1, 100), (100, T_MAX - 1)])
def test_a_sharing_decode_fold_attends_the_set_its_source_selected(pos):
    """A selecting latent layer's decode fold hands its account to a
    sharing layer's: the positions it took (the selection kernel's,
    ascending, -1 after the last where a row sees fewer than `topk`)
    are the set `lax.top_k` takes of the same index scores, and the
    sharing layer attends exactly that set."""
    mesh = meshlib.seq_mesh(1)
    b, h, di, j = 2, 4, 8, 2
    ks = iter(jax.random.split(jax.random.key(9), 8))
    norm = lambda *shape: jax.random.normal(next(ks), shape, jnp.float32)
    lc = norm(b, T_MAX, LANES).at[..., ROW:].set(0)
    ic = norm(b, di, T_MAX)
    q, row = norm(b, 1, h, ROW), norm(b, 1, 1, ROW)
    qi, kit, w = norm(b, 1, j, di), norm(b, 1, 1, di), norm(b, 1, j)
    pos, live = jnp.asarray(pos, jnp.int32), jnp.ones(b, bool)
    source = rd.make_sparse_decode(mesh, topk=TOPK, latent=True, scale=0.5)
    sharer = rd.make_sparse_decode(mesh, topk=TOPK, latent=True,
                                   shared=True, scale=0.5)
    _, lc, ic, taken = source(lc, ic, q, row, (qi, kit, w), pos, live)
    out, lc, account = sharer(lc, q, row, taken, pos, live)
    sel = np.asarray(taken["selected"])
    np.testing.assert_array_equal(account["selected"], sel)
    score = jnp.sum(jnp.maximum(jnp.einsum(
        "bjd,bdt->bjt", qi[:, 0], ic, precision="highest"), 0.0)
        * w[:, 0, :, None], axis=1)
    score = jnp.where(jnp.arange(T_MAX) <= pos[:, None], score, -jnp.inf)
    top, idx = jax.lax.top_k(score, TOPK)
    for r in range(b):
        mine = sel[r][sel[r] >= 0]
        assert (sel[r][len(mine):] == -1).all()
        np.testing.assert_array_equal(mine, np.sort(
            np.asarray(idx[r])[np.asarray(top[r]) > -np.inf]))
        keys = np.asarray(lc[r, mine, :ROW])
        s = np.einsum("hd,kd->hk", np.asarray(q[r, 0]), keys) * 0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ keys
        np.testing.assert_allclose(out[r, 0, :, :ROW], want, atol=1e-5)


def test_the_bias_changes_the_picks_and_not_the_weights_formula():
    """Sigmoid routing picks by score plus bias and weighs by the picks'
    own scores, renormalised and scaled; a zero bias picks the unbiased
    top k; the softmax router is what it was."""
    n, k, e = 16, 4, 12
    x = jax.random.normal(jax.random.key(0), (64, e))
    w = jax.random.normal(jax.random.key(1), (e, n)) / np.sqrt(e)
    bias = 0.1 * jax.random.normal(jax.random.key(2), (n,))
    sig = moe.Experts(n, k, 0, n, routed_scale=2.5, scoring="sigmoid")
    weights, picks = moe.route(x, w, sig, bias)
    s = jax.nn.sigmoid(x @ w)
    want = np.asarray(jax.lax.top_k(s + bias, k)[1])
    np.testing.assert_array_equal(picks, want)
    chosen = np.take_along_axis(np.asarray(s), want, axis=1)
    np.testing.assert_allclose(weights, 2.5 * chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-6)
    unbiased = np.asarray(moe.route(x, w, sig, jnp.zeros(n))[1])
    np.testing.assert_array_equal(unbiased, jax.lax.top_k(s, k)[1])
    assert (np.sort(unbiased, 1) != np.sort(want, 1)).any(1).mean() > 0.1
    soft = moe.Experts(n, k, 0, n, routed_scale=1.5)
    weights, picks = moe.route(x, w, soft)
    top, idx = jax.lax.top_k(jax.nn.softmax(x @ w, axis=-1), k)
    np.testing.assert_array_equal(picks, idx)
    np.testing.assert_allclose(weights, 1.5 * top / top.sum(1, keepdims=True),
                               rtol=1e-6)


def test_interleaved_rotary_is_rotate_half_on_permuted_dims():
    """Rotating adjacent pairs equals rotate-half of the dims reordered
    evens first, reordered back; the dims past `dims` pass through."""
    x = jax.random.normal(jax.random.key(0), (3, 5, 2, 12))
    pos = jnp.arange(5)[None, :]
    inter = lm.Rotary(8e6, 8, interleave=True)
    half = inter._replace(interleave=False)
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2),
                           np.arange(8, 12)])
    want = lm._rope(x[..., perm], pos, half)[..., np.argsort(perm)]
    np.testing.assert_allclose(lm._rope(x, pos, inter), want, atol=1e-6)
    ref = glm_ref.rotate(x.reshape(15, 2, 12), jnp.tile(jnp.arange(5), 3),
                         8e6, 8)
    np.testing.assert_allclose(lm._rope(x, pos, inter).reshape(15, 2, 12),
                               ref, atol=1e-5)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The one-chip share test at the published split: the
    routed terms of the sixteen shares (experts 0-15, 16-31, ...
    240-255) with the shared expert counted once add up to the layer
    with every expert held, and that is the reference's uncut layer."""
    e, f, n, k = 16, 8, 9, 8
    whole = moe.Experts(256, k, 0, 256, routed_scale=2.5, shared=True,
                        scoring="sigmoid")
    spec = lm.ModelSpec(e, (lm.LayerSpec(2, 2, 8, ffn="experts",
                                         experts=whole),),
                        norm="rmsnorm", learned_pos=False)
    p = lm.init_params(spec, 5, jax.random.key(1), expert_dim=f)["block0"]["moe"]
    p["bias"] = p["bias"] * 10.0
    x = jax.random.normal(jax.random.key(2), (n, e))
    full, _ = moe.expert_ffn(p, x, whole, interpret=True)
    shared = moe.swiglu(p["shared"], x)
    total = shared
    for first in range(0, 256, 16):
        ps = dict(p, experts=jax.tree.map(lambda a: a[first:first + 16],
                                          p["experts"]))
        y, st = moe.expert_ffn(ps, x, whole._replace(first=first, count=16),
                               interpret=True)
        total = total + (y - shared)
        assert st["held"].shape == (16,) and st["assigned"] == n * k
    np.testing.assert_allclose(total, full, atol=1e-5)
    prod = lambda s, a, w: jnp.einsum(s, jnp.asarray(a, jnp.float32),
                                      jnp.asarray(w, jnp.float32),
                                      precision="highest")
    cfg = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5,
           "n_shared_experts": 1}
    ref, _ = glm_ref._experts(p, x, cfg, (0, 256), None, prod, 3, None)
    np.testing.assert_allclose(full, ref, atol=1e-5)


def test_in_place_prefill_touches_no_other_slot_and_owns_no_row(model):
    """A request's chunks write the reserved slot's own rows: the other
    slots' rows of every cache (latent rows, index keys) stay
    bit-identical, and a pending prefill holds no cache of its own."""
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
        assert (b[1] != a[1]).any()
    assert eng.kv_bytes_by_kind() == {
        "full": 0, "window": 0, "latent": 5 * 3 * T_MAX * LANES * 4,
        "index": 2 * 3 * 8 * T_MAX * 4}
    assert eng.kv_bytes_per_slot() == sum(eng.kv_bytes_by_kind().values()) // 3


def test_a_recycled_slot_never_sees_the_last_tenants_rows(model):
    """Insert, release and reuse: a long request's latent rows and index
    keys lie under a short one's frontier, which decodes as if the slot
    were fresh; a slot released with budget left stops riding along
    before the next tenant's chunks land."""
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
    # the old tenant's rows and index keys are still beyond the frontier
    assert np.asarray(eng._caches[1][0])[0, 40:90].any()
    assert np.asarray(eng._caches[0][1])[0, :, 40:90].any()


def test_server_serves_the_spec_and_counts_its_cache(model):
    """LMServer over the spec: every request's tokens are the
    reference's greedy ones, nothing compiles after warm-up, and the
    latent cache reaches summary() and the exposition."""
    spec, params = model
    server = LMServer(params, spec=spec, t_max=T_MAX, n_slots=2, window=3,
                      prefill_chunk=CHUNK, cache_dtype=jnp.float32)
    lens = (4, 30, 57)
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
    assert s["serve_moe_assignments"] == 21 * 4 * TOP_K
    assert s["serve_latent_cache_bytes"] == 5 * 2 * T_MAX * LANES * 4
    assert s["serve_index_cache_bytes"] == 2 * 2 * 8 * T_MAX * 4
    names = server.metrics._reg.prometheus_text()
    assert "serve_latent_cache_bytes" in names


def test_the_two_copies_of_the_reference_agree():
    assert filecmp.cmp(ROOT / "tests" / "glm_ref.py",
                       ROOT / "benchmark" / "reference" / "glm_ref.py",
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
    with pytest.raises(ValueError, match="latent attention"):
        model[0].require_classic("anything else")


def test_a_layer_shares_only_an_earlier_selecting_layers_choice(model):
    spec, params = model
    layers = list(spec.layers)
    for bad in (layers[1]._replace(latent=None), layers[1]._replace(shares=3),
                layers[1]._replace(indexer=layers[0].indexer)):
        with pytest.raises(ValueError, match="shares the selection"):
            SlotEngine(params, spec=spec._replace(
                layers=tuple(layers[:1] + [bad] + layers[2:])), t_max=T_MAX,
                n_slots=2, prefill_chunk=CHUNK, cache_dtype=jnp.float32)
