"""A model beyond attention_lm's block through the ONE serving forward:
RMSNorm, grouped-query heads of two counts, rotary positions of two
kinds, window layers beside full ones (two cache lengths), a per-head
output gate, a dense SwiGLU layer and expert layers that hold a share of
their experts. Tiny sizes, seeded float32 weights, against the plain
reference `tests/laguna_ref.py` (which imports nothing of the package)."""

import filecmp
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import laguna_ref
from idc_models_tpu.models import lm, moe
from idc_models_tpu.serve import LMServer, Request
from idc_models_tpu.serve.engine import SlotEngine

ROOT = pathlib.Path(__file__).resolve().parent.parent
VOCAB, EMBED, T_MAX, WINDOW = 61, 32, 48, 8
N_EXPERTS, TOP_K, HELD = 8, 3, (2, 4)
# the reference's view: the published config.json keys
CFG = {
    "num_hidden_layers": 4, "head_dim": 8, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "sliding_window": WINDOW,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 6, 4],
    "num_experts_per_tok": TOP_K, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000.0, "factor": 4.0,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                              "partial_rotary_factor": 1}},
}


def make_spec(held=HELD) -> lm.ModelSpec:
    full = lm.Rotary(500000.0, 4, factor=4.0, original_max=16,
                     attention_factor=1.1386)
    ex = moe.Experts(N_EXPERTS, TOP_K, held[0], held[1], routed_scale=2.5)
    f = lm.LayerSpec(4, 2, 8, gate=True, rotary=full)
    w = lm.LayerSpec(6, 2, 8, window=WINDOW, gate=True,
                     rotary=lm.Rotary(10000.0, 8))
    return lm.ModelSpec(
        EMBED,
        (f._replace(ffn="swiglu"),
         w._replace(ffn="experts", experts=ex),
         w._replace(ffn="experts", experts=ex),
         f._replace(ffn="experts", experts=ex)),
        norm="rmsnorm", learned_pos=False)


@pytest.fixture(scope="module")
def model():
    spec = make_spec()
    params = jax.jit(lambda k: lm.init_params(
        spec, VOCAB, k, mlp_dim=48, expert_dim=16))(jax.random.key(7))
    return spec, params


def ref_logits(params, seq, rows):
    return np.asarray(laguna_ref.forward(params, seq, CFG, HELD, rows=rows,
                                         block=16)[0])


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("p_len", [5, 8, 27])
def test_generator_prefill_and_decode_equal_the_reference(model, chunk, p_len):
    """Chunked prefill (chunk below and at the window) then decoding one
    position at a time through the two cache lengths, prompts shorter
    than, equal to and several times the window: the logits at the last
    prompt position and at every decoded one are the full forward's."""
    spec, params = model
    gen = lm.Generator(params, spec=spec, t_max=T_MAX, prefill_chunk=chunk,
                       cache_dtype=jnp.float32)
    n_dec = 10
    seq = prompt(p_len)
    logits, caches = gen.prefill(seq[None])
    got = [np.asarray(logits[0])]
    for i in range(n_dec):
        tok, logits, caches = gen.decode(caches, logits, p_len + i, 1)
        seq = np.append(seq, int(tok[0, 0]))
        got.append(np.asarray(logits[0]))
    want = ref_logits(params, seq, (p_len - 1, p_len + n_dec))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)
    # window layers keep a ring of W rows, full layers t_max
    assert [c[0].shape[1] for c in caches] == [T_MAX, WINDOW, WINDOW, T_MAX]
    # each row the layer's 2 cached heads of 8, merged (narrow heads)
    assert [c[0].shape[2:] for c in caches] == [(2 * 8,)] * 4


def test_every_prompt_position_matches_the_reference(model):
    """Prefixes of one prompt, each prefilled on its own: the last
    position's logits sweep every position of the full forward."""
    spec, params = model
    gen = lm.Generator(params, spec=spec, t_max=T_MAX, prefill_chunk=8,
                       cache_dtype=jnp.float32)
    seq = prompt(20, seed=3)
    want = ref_logits(params, seq, None)
    for n in range(1, 21):
        logits, _ = gen.prefill(seq[None, :n])
        np.testing.assert_allclose(np.asarray(logits[0]), want[n - 1],
                                   atol=2e-4, rtol=0)


def test_engine_windows_equal_the_reference(model):
    """Through SlotEngine: three requests of different lengths in three
    slots, one recycled, decoded by fused windows; after every window
    each slot's logits are the reference's at that slot's position."""
    spec, params = model
    eng = SlotEngine(params, spec=spec, t_max=T_MAX, n_slots=3,
                     prefill_chunk=4, cache_dtype=jnp.float32)
    seqs = {0: prompt(3, 1), 1: prompt(8, 2), 2: prompt(21, 3)}
    for slot, s in seqs.items():
        eng.admit(slot, s, 9)
    seen, ended, recycled = [], [], False     # (sequence id, row, logits)
    ids = {0: 0, 1: 1, 2: 2}
    for _ in range(5):
        out = eng.step_window(2)
        for slot, toks in out.items():
            seqs[slot] = np.append(seqs[slot], toks).astype(np.int32)
        for slot, s in seqs.items():
            if not eng.finished(slot):
                seen.append((ids[slot], len(s) - 1, eng.slot_logits(slot)))
        if eng.finished(0) and not recycled:
            eng.release(0)                 # a short request ends first:
            ended.append(seqs[0])          # its slot takes a new one,
            seqs[0], ids[0] = prompt(13, 4), 3   # over the old ring's rows
            eng.admit(0, seqs[0], 5)
            recycled = True
    final = {ids[slot]: s for slot, s in seqs.items()} | {0: ended[0]}
    want = {i: ref_logits(params, s, None) for i, s in final.items()}
    assert len(seen) >= 12
    for i, row, got in seen:
        np.testing.assert_allclose(got, want[i][row], atol=2e-4, rtol=0)
    assert recycled
    assert eng.kv_bytes_by_kind() == {
        "full": 2 * 2 * 3 * T_MAX * 2 * 8 * 4,
        "window": 2 * 2 * 3 * WINDOW * 2 * 8 * 4}
    assert eng.kv_bytes_per_slot() == sum(eng.kv_bytes_by_kind().values()) // 3


def test_server_serves_the_spec_and_counts_its_experts(model):
    """LMServer over the spec: every request's tokens are the serial
    Generator's, nothing compiles after warm-up, and the window's
    on-device counts reach summary()."""
    spec, params = model
    gen = lm.Generator(params, spec=spec, t_max=T_MAX, prefill_chunk=4,
                       cache_dtype=jnp.float32)
    server = LMServer(params, spec=spec, t_max=T_MAX, n_slots=2, window=3,
                      prefill_chunk=4, cache_dtype=jnp.float32)
    reqs = [Request(id=f"r{i}", prompt=tuple(prompt(n, 10 + i)),
                    max_new_tokens=7) for i, n in enumerate((4, 11, 19, 9))]
    results = {r.id: r for r in server.run([(0.0, r) for r in reqs])}
    for r in reqs:
        assert results[r.id].status == "ok"
        want = np.asarray(gen(np.asarray(r.prompt)[None], 7))[0, -7:]
        assert list(results[r.id].tokens) == list(want)
    s = server.summary()
    assert s["serve_compiles_observed"] == 0
    # 4 requests x 7 tokens; the first token of each comes from the
    # prefill's logits, the window routes the 7 it feeds back... all 28
    # live token steps x 3 expert layers x top-3
    assert s["serve_moe_assignments"] == 28 * 3 * TOP_K
    assert 0 < s["serve_moe_assignments_held"] < s["serve_moe_assignments"]
    assert s["serve_moe_experts_held"] == HELD[1]
    assert 0 < s["serve_moe_experts_touched_mean"] <= HELD[1]
    assert s["serve_moe_load_max_over_mean"] >= 1.0
    assert s["serve_kv_bytes_window"] * (T_MAX // WINDOW) == s["serve_kv_bytes_full"]
    names = server.metrics._reg.prometheus_text()
    for n in ("serve_moe_assignments_total", "serve_moe_assignments_held_total",
              "serve_moe_experts_touched", "serve_moe_load_max_over_mean",
              "serve_kv_bytes_full", "serve_kv_bytes_window"):
        assert n in names


def test_window_counts_are_the_routers(model):
    """The int32 counts a window hands back: per held expert, the
    assignments of the LIVE rows alone, as the reference's router makes
    them; the picks are its top-k."""
    spec, params = model
    eng = SlotEngine(params, spec=spec, t_max=T_MAX, n_slots=3,
                     prefill_chunk=4, cache_dtype=jnp.float32)
    s = prompt(10, 5)
    eng.admit(1, s, 4)                       # slots 0 and 2 stay dead
    pre = eng.router_picks("prefill")[:, :, 1]   # [3 layers, 1, chunk, k]:
    #                 the last chunk held positions 8 and 9
    _, routers = laguna_ref.forward(params, s, CFG, HELD, block=16)
    want = np.argsort(-np.asarray(routers), axis=-1)[:, 9, :TOP_K]
    assert (np.sort(pre[:, 0], -1) == np.sort(want, -1)).all()
    out = eng.step_window(4)
    seq = np.append(s, out[1]).astype(np.int32)
    st = eng.last_moe
    assert st["assigned"] == 4 * 3 * TOP_K and st["steps"] == 4
    _, routers = laguna_ref.forward(params, seq, CFG, HELD, block=16)
    top = np.argsort(-np.asarray(routers)[:, 10:14], axis=-1)[..., :TOP_K]
    held = np.stack([np.bincount(top[l].ravel(), minlength=N_EXPERTS)[2:6]
                     for l in range(3)])
    assert (st["held"] == held).all()
    picks = eng.router_picks("window")       # [4 steps, 3 layers, 3 slots, k]
    assert (np.sort(picks[:, :, 1], -1)
            == np.sort(np.moveaxis(top, 0, 1), -1)).all()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the held-range outputs of
    all four shares, with the shared expert (computed alike everywhere)
    counted once, add up to the layer with every expert held — and that
    is the reference's uncut layer."""
    e, f, n = 16, 8, 9
    whole = moe.Experts(N_EXPERTS, TOP_K, 0, N_EXPERTS, routed_scale=2.5)
    spec = lm.ModelSpec(e, (lm.LayerSpec(2, 2, 8, ffn="experts",
                                         experts=whole),),
                        norm="rmsnorm", learned_pos=False)
    p = lm.init_params(spec, 5, jax.random.key(1), expert_dim=f)["block0"]["moe"]
    x = jax.random.normal(jax.random.key(2), (n, e))
    full, _ = moe.expert_ffn(p, x, whole, interpret=True)
    shared = moe.swiglu(p["shared"], x)
    total = shared
    for first in range(0, N_EXPERTS, 2):
        share = whole._replace(first=first, count=2)
        ps = dict(p, experts=jax.tree.map(lambda a: a[first:first + 2],
                                          p["experts"]))
        y, st = moe.expert_ffn(ps, x, share, interpret=True)
        total = total + (y - shared)
        assert st["held"].shape == (2,) and st["assigned"] == n * TOP_K
    np.testing.assert_allclose(total, full, atol=1e-5)
    cfg = dict(CFG)
    ref, _ = laguna_ref._experts(
        jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p), x, cfg,
        (0, N_EXPERTS), None, lambda a, w: jnp.matmul(a, w, precision="highest"),
        3)
    np.testing.assert_allclose(full - shared, ref, atol=1e-5)


@pytest.mark.parametrize("routing", ["all_to_one", "one_expert_empty",
                                     "none_held"])
def test_grouped_product_equals_a_per_token_loop(routing):
    """Very uneven routing: every token to one expert, an expert with no
    token, no token for any held expert: the grouped product is the
    per-token loop's sum, in one shape-stable program."""
    e, f, n, count = 16, 8, 7, 3
    ex = moe.Experts(6, 2, 1, count, shared=False)
    keys = jax.random.split(jax.random.key(3), 5)
    p = {"experts": {"w_gate": jax.random.normal(keys[0], (count, e, f)),
                     "w_up": jax.random.normal(keys[1], (count, e, f)),
                     "w_down": jax.random.normal(keys[2], (count, f, e))}}
    x = jax.random.normal(keys[3], (n, e))
    # steer the router by its weights: the favoured experts get a large
    # score on every token
    fav = {"all_to_one": [2, 5], "one_expert_empty": [1, 3],
           "none_held": [0, 4]}[routing]
    router = np.full((e, 6), 0.0, np.float32)
    p["router"] = jnp.asarray(router)
    bias = np.zeros(6, np.float32)
    bias[fav] = [20.0, 10.0]
    x = x.at[:, 0].set(1.0)
    p["router"] = p["router"].at[0].set(bias)
    y, st = jax.jit(lambda p, x: moe.expert_ffn(p, x, ex, interpret=True))(p, x)
    weights, picks = moe.route(x, p["router"], ex)
    assert (np.sort(np.asarray(picks), -1) == sorted(fav)).all()
    want = np.zeros((n, e), np.float32)
    for t in range(n):
        for w, g in zip(np.asarray(weights[t]), np.asarray(picks[t])):
            if 1 <= g < 1 + count:
                one = {k: v[g - 1] for k, v in p["experts"].items()}
                want[t] += w * np.asarray(moe.swiglu(one, x[t]))
    np.testing.assert_allclose(y, want, atol=1e-4)
    sizes = np.bincount(np.asarray(picks).ravel(), minlength=6)[1:1 + count]
    assert (np.asarray(st["held"]) == sizes).all()


def test_yarn_frequencies_against_hand_computed_values():
    """Laguna's full-attention rotary set: theta 500000, 64 rotary dims,
    factor 128 over 8192 original positions, beta 32 / 1. The ramp runs
    from dimension 9 to 18 (floor 9.04, ceil 17.49)."""
    rot = lm.Rotary(500000.0, 64, factor=128.0, original_max=8192,
                    beta_fast=32.0, beta_slow=1.0,
                    attention_factor=1.4852030263919618)
    inv = lm.rotary_inv_freq(rot)
    assert inv.shape == (32,)
    extra = lambda i: 500000.0 ** (-2 * i / 64)
    np.testing.assert_allclose(inv[0], 1.0)
    np.testing.assert_allclose(inv[9], extra(9), rtol=1e-12)      # ramp 0
    np.testing.assert_allclose(inv[12], extra(12) * (2 / 3 + 1 / 3 / 128),
                               rtol=1e-12)                        # ramp 1/3
    np.testing.assert_allclose(inv[18], extra(18) / 128, rtol=1e-12)
    np.testing.assert_allclose(inv[31], 500000.0 ** (-62 / 64) / 128,
                               rtol=1e-12)
    # 0.1 ln(128) + 1: the attention factor the config states
    np.testing.assert_allclose(0.1 * np.log(128.0) + 1.0,
                               rot.attention_factor, rtol=1e-12)
    # the plain set of the window layers
    np.testing.assert_allclose(lm.rotary_inv_freq(lm.Rotary(10000.0, 128))[1],
                               10000.0 ** (-2 / 128), rtol=1e-12)
    # cos and sin both carry the factor: a rotated vector's norm grows by it
    x = jnp.ones((1, 1, 128))
    y = lm._rope(x, jnp.asarray([5]), rot)
    np.testing.assert_allclose(
        jnp.linalg.norm(y[..., :64]) / jnp.linalg.norm(x[..., :64]),
        rot.attention_factor, rtol=1e-5)
    np.testing.assert_allclose(y[..., 64:], x[..., 64:])          # passed through
    np.testing.assert_allclose(laguna_ref.rotary_inv_freq(
        {"rope_type": "yarn", "rope_theta": 500000, "factor": 128,
         "original_max_position_embeddings": 8192, "beta_slow": 1,
         "beta_fast": 32, "partial_rotary_factor": 0.5}, 128), inv, rtol=1e-12)


@pytest.mark.parametrize("mechanism,kwargs", [
    ("paged KV", dict(kv_page_size=4, kv_pages=24)),
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
    kw = dict(prefill_chunk=4) | kwargs
    if kw.get("mesh") == "seq2":
        kw["mesh"] = meshlib.seq_mesh(2)
    with pytest.raises(ValueError, match=mechanism):
        LMServer(params, spec=spec, t_max=T_MAX, n_slots=2, warmup=False,
                 **kw)


def test_slot_export_refuses_the_spec_by_name(model):
    spec, params = model
    eng = SlotEngine(params, spec=spec, t_max=T_MAX, n_slots=2,
                     prefill_chunk=4)
    eng.admit(0, prompt(5), 3)
    assert not eng.supports_slot_migration
    with pytest.raises(ValueError, match="slot migration"):
        eng.export_slot(0)
    with pytest.raises(ValueError, match="slot migration"):
        eng.import_slot(1, {})


def test_the_model_is_named_once(model):
    spec, params = model
    with pytest.raises(ValueError, match="name the model once"):
        lm.Generator(params, t_max=T_MAX, prefill_chunk=4)
    with pytest.raises(ValueError, match="name the model once"):
        lm.Generator(params, spec=spec, embed_dim=EMBED, t_max=T_MAX,
                     prefill_chunk=4)
    with pytest.raises(ValueError, match="spec states float32"):
        lm.Generator(jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
                     spec=spec, t_max=T_MAX, prefill_chunk=4)


def test_attention_lm_is_one_instance_of_the_spec():
    """attention_lm's block through spec= is the SAME configuration as
    through its three numbers: one compiled program set, equal tokens."""
    model = lm.attention_lm(17, 32, embed_dim=16, num_heads=2, mlp_dim=32,
                            num_blocks=2)
    params = model.init(jax.random.key(0)).params
    by_numbers = lm.Generator(params, embed_dim=16, num_heads=2, num_blocks=2,
                              t_max=32, cache_dtype=jnp.float32)
    spec = lm.attention_spec(16, 2, 2)
    assert spec.classic and not make_spec().classic
    by_spec = lm.Generator(params, spec=spec, t_max=32,
                           cache_dtype=jnp.float32)
    assert by_spec._cfg == by_numbers._cfg
    assert by_spec._fns is by_numbers._fns
    p = prompt(6) % 17
    assert (np.asarray(by_spec(p[None], 5))
            == np.asarray(by_numbers(p[None], 5))).all()
    # and its tree is what init_params makes for that spec
    made = lm.init_params(spec, 17, jax.random.key(1), seq_len=32, mlp_dim=32)
    assert (jax.tree.structure(made) == jax.tree.structure(params))
    assert (jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params))


def test_the_two_copies_of_the_reference_are_one():
    assert filecmp.cmp(ROOT / "tests" / "laguna_ref.py",
                       ROOT / "benchmark" / "reference" / "laguna_ref.py",
                       shallow=False)
