"""The causal LM closes the train→serve loop: the cached decoder must
reproduce the training path's logits EXACTLY (fp tolerance), position
by position, from the same parameter tree — on rings, the 2-D mesh,
and for weights trained under the zigzag layout (which is a schedule
permutation, not a different function). Plus: the LM learns a
next-token task through the standard train step, and greedy generation
extends the pattern it learned."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models.lm import (
    Generator, attention_lm, generate, make_lm_decoder, next_token_loss,
)
from idc_models_tpu.train import (
    TrainState, jit_data_parallel, make_train_step, replicate, rmsprop,
    shard_batch,
)

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2


def _model(mesh, seq=SEQ, **kw):
    return attention_lm(VOCAB, seq, embed_dim=E, num_heads=HEADS,
                        mlp_dim=MLP, num_blocks=BLOCKS, mesh=mesh, **kw)


def _toks(n, seed=0, seq=SEQ):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, VOCAB, (n, seq)), jnp.int32)


def _decode_logits(params, tokens, mesh, t_max=SEQ):
    init_caches, step, _ = make_lm_decoder(
        params, embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
        t_max=t_max, mesh=mesh, cache_dtype=jnp.float32)
    caches = init_caches(tokens.shape[0])
    rows = []
    for pos in range(tokens.shape[1]):
        logits, caches = step(caches, tokens[:, pos], pos)
        rows.append(logits[:, None])
    return jnp.concatenate(rows, axis=1)


@pytest.mark.parametrize("n_ring,seq", [(1, 32), (3, 24), (4, 32)])
def test_incremental_equals_full(devices, n_ring, seq):
    """Teacher-forced cached decode == the training forward, every
    position, on rings incl. non-power-of-2 (seq divisible by ring)."""
    mesh = meshlib.seq_mesh(n_ring) if n_ring > 1 else None
    model = _model(mesh, seq=seq)
    params = model.init(jax.random.key(0)).params
    toks = _toks(2, seed=n_ring, seq=seq)
    full, _ = model.apply(params, {}, toks)
    inc = _decode_logits(params, toks, mesh, t_max=seq)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_weights_decode_identically(devices):
    """Layout is a training knob, not a serving constraint: the zigzag
    model computes the same function, so its params decode through the
    natural-order cached path to the same logits."""
    mesh = meshlib.seq_mesh(4)
    zig = _model(mesh, layout="zigzag")
    params = zig.init(jax.random.key(1)).params
    toks = _toks(2, seed=9)
    full, _ = zig.apply(params, {}, toks)
    inc = _decode_logits(params, toks, mesh)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_lm_learns_and_generates(devices):
    """Golden loop: train next = (tok + 1) % VOCAB through the standard
    DP train step on the ("data", "seq") mesh, then greedy-generate the
    learned successor pattern through the cached decoder."""
    mesh = meshlib.data_seq_mesh(4, 2)
    model = _model(mesh)
    opt = rmsprop(3e-3)
    variables = model.init(jax.random.key(2))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    step = jit_data_parallel(
        make_train_step(model, opt, lambda lg, tk: next_token_loss(lg, tk)),
        mesh, axis="data")
    state = replicate(mesh, state)
    rng = np.random.default_rng(3)
    key = jax.random.key(4)
    loss = None
    for i in range(150):
        starts = rng.integers(0, VOCAB, (32, 1))
        seqs = (starts + np.arange(SEQ)) % VOCAB
        bx = shard_batch(mesh, jnp.asarray(seqs, jnp.int32), axis="data")
        key, sub = jax.random.split(key)
        state, m = step(state, bx, bx, sub)
        loss = float(m["loss"])
    assert loss < 0.1, loss
    params = jax.device_get(state.params)
    prompt = jnp.asarray([[3, 4, 5, 6]], jnp.int32)
    out = generate(params, prompt, 8, embed_dim=E, num_heads=HEADS,
                   num_blocks=BLOCKS, t_max=SEQ,
                   cache_dtype=jnp.float32)
    want = [(3 + i) % VOCAB for i in range(12)]
    assert out.tolist() == [want], (out.tolist(), want)


def test_decoder_rejections(devices):
    model = _model(None)
    params = model.init(jax.random.key(0)).params
    with pytest.raises(ValueError, match="position table"):
        make_lm_decoder(params, embed_dim=E, num_heads=HEADS,
                        num_blocks=BLOCKS, t_max=SEQ * 2)
    with pytest.raises(ValueError, match="not divisible"):
        make_lm_decoder(params, embed_dim=30, num_heads=4,
                        num_blocks=BLOCKS, t_max=SEQ)
    with pytest.raises(ValueError, match="exceeds"):
        generate(params, jnp.zeros((1, 30), jnp.int32), 8,
                 embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                 t_max=SEQ)


def test_lm_checkpoint_roundtrip(devices, tmp_path):
    """The LM rides the standard orbax checkpoint machinery (C8/§5):
    params saved after a few train steps restore to a tree that decodes
    IDENTICAL tokens — training, persistence, and serving all share one
    parameter pytree."""
    from idc_models_tpu.train import restore_checkpoint, save_checkpoint

    mesh = meshlib.data_seq_mesh(4, 2)
    model = _model(mesh)
    opt = rmsprop(3e-3)
    variables = model.init(jax.random.key(5))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    step = jit_data_parallel(
        make_train_step(model, opt, next_token_loss), mesh, axis="data")
    state = replicate(mesh, state)
    rng = np.random.default_rng(6)
    key = jax.random.key(7)
    for i in range(5):
        starts = rng.integers(0, VOCAB, (16, 1))
        seqs = (starts + np.arange(SEQ)) % VOCAB
        bx = shard_batch(mesh, jnp.asarray(seqs, jnp.int32), axis="data")
        key, sub = jax.random.split(key)
        state, _ = step(state, bx, bx, sub)
    save_checkpoint(tmp_path / "lm", state)
    template = jax.tree.map(np.zeros_like, jax.device_get(state))
    restored = restore_checkpoint(tmp_path / "lm", template)
    prompt = _toks(1, seed=8)[:, :5]
    a = generate(jax.device_get(state.params), prompt, 6, embed_dim=E,
                 num_heads=HEADS, num_blocks=BLOCKS, t_max=SEQ,
                 cache_dtype=jnp.float32)
    b = generate(restored.params, prompt, 6, embed_dim=E,
                 num_heads=HEADS, num_blocks=BLOCKS, t_max=SEQ,
                 cache_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_tokens_equals_tokenwise(devices):
    """One-pass prompt prefill == feeding the prompt through step()
    token by token: caches and last-position logits equal to fp
    tolerance (the batched projections reassociate the same matmuls) —
    on the ring, so the prefilled caches land sharded correctly."""
    mesh = meshlib.seq_mesh(4)
    model = _model(mesh)
    params = model.init(jax.random.key(9)).params
    toks = _toks(2, seed=13)
    p_len = 20
    init_caches, step, prefill_tokens = make_lm_decoder(
        params, embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
        t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)
    # path A: token by token
    caches_a = init_caches(2)
    logits_a = None
    for pos in range(p_len):
        logits_a, caches_a = step(caches_a, toks[:, pos], pos)
    # path B: one pass
    logits_b, caches_b = prefill_tokens(toks[:, :p_len])
    np.testing.assert_allclose(np.asarray(logits_b),
                               np.asarray(logits_a),
                               rtol=2e-4, atol=2e-4)
    for (ka, va), (kb, vb) in zip(caches_a, caches_b):
        np.testing.assert_allclose(np.asarray(ka), np.asarray(kb),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                   rtol=1e-5, atol=1e-5)
    # rejections
    with pytest.raises(ValueError, match="non-empty"):
        prefill_tokens(jnp.zeros((2, 0), jnp.int32))
    with pytest.raises(ValueError, match="exceeds"):
        prefill_tokens(jnp.zeros((2, SEQ + 1), jnp.int32))


def test_prefill_runs_through_ring(devices):
    """Ring prefill == the single-device full-attention forward (the
    old prefill path) at the last prompt position — for prompts both
    divisible and NOT divisible by the ring (internal end-padding),
    with the caches landing ring-sharded and the pad region zero."""
    from idc_models_tpu.ring_decode import cache_sharding

    mesh = meshlib.seq_mesh(4)
    params = _model(mesh).init(jax.random.key(21)).params
    ref_model = _model(None)          # full_attention blocks
    toks = _toks(2, seed=17)
    full, _ = ref_model.apply(params, {}, toks)
    _, _, prefill_tokens = make_lm_decoder(
        params, embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
        t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)
    want = cache_sharding(mesh)
    for p_len in (16, 18):            # 18 % 4 != 0 -> padded internally
        logits, caches = prefill_tokens(toks[:, :p_len])
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, p_len - 1]),
                                   rtol=2e-4, atol=2e-4)
        for kc, vc in caches:
            assert kc.sharding.is_equivalent_to(want, kc.ndim)
            assert vc.sharding.is_equivalent_to(want, vc.ndim)
            # slots past the prompt stay zero — the fresh-cache
            # contract decode's visibility masking relies on
            assert not np.asarray(kc)[:, p_len:].any()
            assert not np.asarray(vc)[:, p_len:].any()


def _step_loop_reference(params, prompt, steps, mesh, temperature,
                         top_k, rng):
    """The pre-fused serving loop — prefill, then one pick + one step()
    dispatch per token — with pick's exact math inlined. The fused scan
    must reproduce its token sequence bit-for-bit (same rng split
    order: one split per emitted token, before the pick)."""
    _, step, prefill_tokens = make_lm_decoder(
        params, embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
        t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)
    logits, caches = prefill_tokens(prompt)
    p_len = prompt.shape[1]
    toks = [prompt]
    for s in range(steps):
        rng, sub = jax.random.split(rng)
        lg = logits.astype(jnp.float32)
        if top_k is not None and top_k < lg.shape[-1]:
            kth = jax.lax.top_k(lg, top_k)[0][:, -1]
            lg = jnp.where(lg >= kth[:, None], lg, -jnp.inf)
        if temperature == 0.0:
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            tok = jax.random.categorical(sub, lg / temperature,
                                         axis=-1).astype(jnp.int32)
        toks.append(tok[:, None])
        if s + 1 < steps:
            logits, caches = step(caches, tok, p_len + s)
    return jnp.concatenate(toks, axis=1)


def test_fused_decode_matches_step_loop(devices):
    """The one-dispatch scan decode emits the SAME token sequence as
    driving step() from the host, greedy and seeded top-k sampling."""
    mesh = meshlib.seq_mesh(4)
    params = _model(mesh).init(jax.random.key(23)).params
    prompt = _toks(2, seed=19)[:, :10]
    kw = dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
              t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)
    fused = generate(params, prompt, 8, **kw)
    ref = _step_loop_reference(params, prompt, 8, mesh, 0.0, None,
                               jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    gen = Generator(params, temperature=1.3, top_k=4, **kw)
    fused = gen(prompt, 8, rng=jax.random.key(42))
    ref = _step_loop_reference(params, prompt, 8, mesh, 1.3, 4,
                               jax.random.key(42))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


def test_generator_reuses_compilation(devices):
    """Zero recompilation on reuse: a second same-shape call — and a
    second Generator over a fresh same-shape parameter tree — must not
    grow any program's jit cache (the ADVICE r5 per-request re-jit)."""
    mesh = meshlib.seq_mesh(2)
    params = _model(mesh).init(jax.random.key(31)).params
    kw = dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
              t_max=SEQ, mesh=mesh, cache_dtype=jnp.float32)
    gen = Generator(params, **kw)
    prompt = _toks(2, seed=33)[:, :8]
    out1 = gen(prompt, 5)
    sizes = gen.cache_sizes()
    out2 = gen(prompt, 5)
    assert gen.cache_sizes() == sizes, (gen.cache_sizes(), sizes)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    params2 = jax.tree.map(lambda a: np.array(a), params)
    gen2 = Generator(params2, **kw)
    out3 = gen2(prompt, 5)
    assert gen2.cache_sizes() == sizes, (gen2.cache_sizes(), sizes)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out3))


def test_generator_chained_decode_windows(devices):
    """decode() windows chain exactly: two back-to-back windows through
    the returned (logits, caches) equal one window of the combined
    length — the contract the serve path leans on."""
    params = _model(None).init(jax.random.key(35)).params
    kw = dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
              t_max=SEQ, cache_dtype=jnp.float32)
    gen = Generator(params, **kw)
    prompt = _toks(1, seed=37)[:, :6]
    one = gen(prompt, 10)
    logits, caches = gen.prefill(prompt)
    t1, logits, caches = gen.decode(caches, logits, 6, 4)
    t2, _, _ = gen.decode(caches, logits, 10, 6)
    two = jnp.concatenate([prompt, t1, t2], axis=1)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))
    with pytest.raises(ValueError, match="exceeds t_max"):
        gen.decode(gen.init_caches(1), jnp.zeros((1, VOCAB)), SEQ - 2, 4)
    with pytest.raises(ValueError, match=">= 0"):
        gen.decode(gen.init_caches(1), jnp.zeros((1, VOCAB)), -1, 2)


def test_int_tokens_skip_compute_dtype_cast(devices):
    """bf16 train/eval steps must not round-trip token ids through the
    compute dtype: ids > 256 would corrupt before attention_lm's int32
    cast-back (ADVICE r5). With the integer-dtype skip, a bf16 step is
    bit-identical to the f32 step on the same int tokens."""
    from idc_models_tpu.train.step import make_eval_step
    from idc_models_tpu.train import rmsprop

    vocab, seq = 600, 8
    model = attention_lm(vocab, seq, embed_dim=16, num_heads=2,
                         mlp_dim=32, num_blocks=1)
    variables = model.init(jax.random.key(41))
    opt = rmsprop(1e-3)

    def fresh_state():
        return TrainState(step=jnp.zeros((), jnp.int32),
                          params=variables.params,
                          model_state=variables.state,
                          opt_state=opt.init(variables.params))

    toks = jnp.asarray([[1, 511, 512, 513, 300, 2, 3, 4]], jnp.int32)
    ev_bf = make_eval_step(model, next_token_loss,
                           compute_dtype=jnp.bfloat16)(
        fresh_state(), toks, toks)
    ev_f32 = make_eval_step(model, next_token_loss,
                            compute_dtype=jnp.float32)(
        fresh_state(), toks, toks)
    np.testing.assert_array_equal(np.asarray(ev_bf["logits"]),
                                  np.asarray(ev_f32["logits"]))
    key = jax.random.key(43)
    _, m_bf = make_train_step(model, opt, next_token_loss,
                              compute_dtype=jnp.bfloat16)(
        fresh_state(), toks, toks, key)
    _, m_f32 = make_train_step(model, opt, next_token_loss,
                               compute_dtype=jnp.float32)(
        fresh_state(), toks, toks, key)
    assert float(m_bf["loss"]) == float(m_f32["loss"])


def test_generator_bounds_edges(devices):
    """The t_max boundary exactly: a prompt of exactly t_max tokens
    prefills fine, but ANY decode from there must be rejected BEFORE
    dispatch (inside the fused scan an out-of-range append would be
    silently dropped); steps=0/negative are rejected with clear
    messages."""
    params = _model(None).init(jax.random.key(51)).params
    gen = Generator(params, embed_dim=E, num_heads=HEADS,
                    num_blocks=BLOCKS, t_max=SEQ, cache_dtype=jnp.float32)
    full = _toks(1, seed=53)                      # exactly t_max tokens
    assert full.shape[1] == SEQ
    logits, caches = gen.prefill(full)            # fine: fills the cache
    assert logits.shape == (1, VOCAB)
    for kc, _vc in caches:
        assert np.asarray(kc)[:, -1].any()        # last slot occupied
    # any decode from the full cache must fail before dispatch
    with pytest.raises(ValueError, match="exceeds t_max"):
        gen.decode(caches, logits, SEQ, 1)
    # __call__ refuses a full-length prompt + any steps the same way
    with pytest.raises(ValueError, match="exceeds"):
        gen(full, 1)
    # steps=0 / negative: rejected with a clear message, no dispatch
    with pytest.raises(ValueError, match="steps >= 1"):
        gen.decode(caches, logits, 4, 0)
    with pytest.raises(ValueError, match="steps >= 1"):
        gen.decode(caches, logits, 4, -3)
    with pytest.raises(ValueError, match="steps >= 1"):
        gen(full[:, :4], 0)


def test_prefill_buckets(devices):
    """Prompt length maps onto the fixed bucket set (n_ring * powers of
    two, capped at t_max) — the compile-set contract the serving engine
    warms up against."""
    from idc_models_tpu.models.lm import prefill_bucket, prefill_buckets

    assert prefill_buckets(32, 1) == (1, 2, 4, 8, 16, 32)
    assert prefill_buckets(32, 4) == (4, 8, 16, 32)
    assert prefill_buckets(24, 4) == (4, 8, 16, 24)
    for n_ring, t_max in ((1, 32), (4, 32), (4, 24), (3, 24)):
        buckets = prefill_buckets(t_max, n_ring)
        assert all(b % n_ring == 0 for b in buckets)
        for p in range(1, t_max + 1):
            b = prefill_bucket(p, t_max, n_ring)
            assert b in buckets and b >= p
    with pytest.raises(ValueError, match="outside"):
        prefill_bucket(0, 32, 1)
    with pytest.raises(ValueError, match="outside"):
        prefill_bucket(33, 32, 1)


def test_generate_sampling_modes(devices):
    """temperature/top_k: greedy is deterministic and equals the
    default; sampling varies with the rng but respects top_k=1 ==
    greedy; invalid knobs are rejected."""
    model = _model(None)
    params = model.init(jax.random.key(11)).params
    kw = dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
              t_max=SEQ, cache_dtype=jnp.float32)
    prompt = _toks(2, seed=15)[:, :6]
    greedy = generate(params, prompt, 6, **kw)
    np.testing.assert_array_equal(
        np.asarray(generate(params, prompt, 6, temperature=0.0, **kw)),
        np.asarray(greedy))
    # top_k=1 sampling has a single-token support -> exactly greedy
    np.testing.assert_array_equal(
        np.asarray(generate(params, prompt, 6, temperature=5.0,
                            top_k=1, rng=jax.random.key(0), **kw)),
        np.asarray(greedy))
    # high temperature over an untrained (near-uniform) head varies
    a = generate(params, prompt, 6, temperature=5.0,
                 rng=jax.random.key(1), **kw)
    c = generate(params, prompt, 6, temperature=5.0,
                 rng=jax.random.key(2), **kw)
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError, match="needs an rng"):
        generate(params, prompt, 2, temperature=1.0, **kw)
    with pytest.raises(ValueError, match="temperature"):
        generate(params, prompt, 2, temperature=-1.0, **kw)
    with pytest.raises(ValueError, match="top_k"):
        generate(params, prompt, 2, top_k=0, **kw)
