"""The serve path's attention stops at the live frontier (PR 28).

`ring_decode._attend_to_frontier` reads the resident shard in blocks up
to the furthest live position; the contiguous decode fold and the
prefill-chunk fold both go through it. Every case compares against a
plain one-pass masked attend over the WHOLE cache kept by head
`[B, T, G, D]` (numpy, float64) and against the fold with one block per
shard, which is the fold as it was before blocks existed; caches must
come out bit-equal, and rows beyond the count `decode_rows_read` reports
are poisoned to show they are not read at all.

The folds read the cache in the form `ring_decode.cache_shape` declares
(PR 30): heads narrower than a tile's 128 lanes merged into rows
`[B, T, G*D]`, wider ones kept apart. Cases at both widths, and at
`gpt2-large`'s and Laguna's own (H, G, D), go through `_stored`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu import ring_decode as rd

T, BLK, D = 32, 8, 8
ONE_PASS = 1 << 30       # a block target no shard reaches: one pass


def _softmax_attend(q, kc, vc, see):
    """q [B, C, H, D] over kc / vc [B, T, G, D] (float), `see` bool
    [B, C, T]: plain masked softmax attention, float64 -> [B, C, H, D];
    a query that sees nothing gives zeros."""
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    b, c, h, d = q.shape
    g = kc.shape[2]
    out = np.zeros((b, c, h, d))
    for bi in range(b):
        for ci in range(c):
            rows = np.flatnonzero(see[bi, ci])
            if not rows.size:
                continue
            for hi in range(h):
                gi = hi // (h // g)
                s = kc[bi, rows, gi] @ q[bi, ci, hi] * d ** -0.5
                w = np.exp(s - s.max())
                out[bi, ci, hi] = (w / w.sum()) @ vc[bi, rows, gi]
    return out


def _rand(rng, *shape):
    return rng.normal(0, 1, shape).astype(np.float32)


def _stored(c):
    """A cache kept by head [B, T, G, D] in its declared stored form."""
    return c.reshape(rd.cache_shape(*c.shape))


def _by_head(c, like):
    """A stored cache back as `like`'s [B, T, G, D]."""
    return np.asarray(c).reshape(like.shape)


@pytest.mark.parametrize("g,d,want", [(20, 64, (3, 16, 1280)),
                                      (2, 8, (3, 16, 16)),
                                      (8, 128, (3, 16, 8, 128)),
                                      (2, 256, (3, 16, 2, 256))])
def test_stored_form_follows_the_width_of_a_head(g, d, want):
    assert rd.cache_shape(3, 16, g, d) == want
    kc, _ = rd.init_cache(meshlib.seq_mesh(1), 3, 16, g, d)
    assert kc.shape == want
    # bytes at rest are those of the heads, whichever the form
    assert kc.size == 3 * 16 * g * d


# -- the helper itself --------------------------------------------------


@pytest.mark.parametrize("h,g,d", [(6, 2, D), (6, 2, 128), (20, 20, 64)],
                         ids=["narrow_grouped", "laguna_heads",
                              "gpt2_large_heads"])
@pytest.mark.parametrize("frontier", [11, 16, T, 0],
                         ids=["inside_a_block", "on_a_block_edge",
                              "at_t_max", "nothing_live"])
def test_helper_against_one_pass(frontier, h, g, d):
    rng = np.random.default_rng(frontier)
    b = 3
    q = _rand(rng, b, h, d)
    kc, vc = _rand(rng, b, T, g, d), _rand(rng, b, T, g, d)
    # each row sees up to its own position, the furthest at frontier - 1
    pos = np.maximum(frontier - 1 - 4 * np.arange(b), -1)
    see = np.arange(T)[None, :] <= pos[:, None]             # [B, T]
    # values beyond the last block read are never touched: poison them
    n_read = -(-frontier // BLK) * BLK
    vc_poison = vc.copy()
    vc_poison[:, n_read:] = np.nan

    def run(vcache, blk):
        m, l, acc = jax.jit(lambda q, kc, vc, frontier: (
            rd._attend_to_frontier(
                q, kc, vc,
                lambda rows: (rows[None, :] <= pos[:, None])[:, None, :],
                frontier, blk, scale=d ** -0.5)))(
            q, _stored(kc), _stored(vcache), jnp.int32(frontier))
        return np.asarray(m), np.asarray(l), np.asarray(acc)

    m, l, acc = run(vc_poison, BLK)
    ref = _softmax_attend(q[:, None], kc, vc, see[:, None])[:, 0]
    np.testing.assert_allclose(acc / np.maximum(l, 1e-37)[..., None], ref,
                               atol=1e-5, rtol=1e-5)
    if frontier == 0:
        assert (m == rd._MASKED).all() and not l.any() and not acc.any()
    # against the one-pass fold (one block = the whole shard): the same
    # maximum and the same output up to the merge's rounding
    m1, l1, acc1 = run(vc, T)
    live = pos >= 0
    # (float32 sums of G * D terms in another order: G * D * 2**-24)
    np.testing.assert_allclose(m[live], m1[live], rtol=g * d * 2.0 ** -24,
                               atol=1e-6)
    np.testing.assert_allclose(acc[live] / l[live][..., None],
                               acc1[live] / l1[live][..., None],
                               atol=1e-5, rtol=1e-5)


def test_helper_refuses_a_block_that_does_not_divide():
    z = jnp.zeros((1, 24, 2 * D))
    with pytest.raises(ValueError, match="does not divide"):
        rd._attend_to_frontier(jnp.zeros((1, 2, D)), z, z,
                               lambda rows: rows[None, None, :] >= 0,
                               jnp.int32(3), 16, scale=1.0)


@pytest.mark.parametrize("t_shard,target,want", [
    (128, 256, 128), (256, 256, 256), (1024, 256, 256), (8192, 1024, 1024),
    (1024, 300, 256), (96, 64, 32), (100, 64, 100), (24, 16, 8)])
def test_fold_block_rule(t_shard, target, want):
    assert rd._fold_block(t_shard, target) == want


# -- the decode fold ----------------------------------------------------


def _decode_case(monkeypatch, *, pos, live, n_dev=1, h=2, g=2, d=D,
                 quantized=False, seed=0):
    """Run the batched fold in blocks and in one pass on the same
    inputs; check caches, live outputs, the numpy reference and the
    rows-read count (poisoned beyond it). Returns rows read a slot."""
    rng = np.random.default_rng(seed)
    mesh = meshlib.seq_mesh(n_dev)
    pos, live = np.asarray(pos, np.int32), np.asarray(live, bool)
    b = len(pos)
    q, kt, vt = (_rand(rng, b, 1, h, d) for _ in range(3))
    kt, vt = kt[:, :, :g], vt[:, :, :g]
    kc, vc = _rand(rng, b, T, g, d), _rand(rng, b, T, g, d)
    scales = ()
    if quantized:
        kc = np.clip(np.round(kc * 40), -127, 127).astype(np.int8)
        vc = np.clip(np.round(vc * 40), -127, 127).astype(np.int8)
        scales = (np.full((b, h), 1 / 40, np.float32),
                  np.full((b, h), 1 / 32, np.float32))

    def run(target, vcache):
        monkeypatch.setattr(rd, "_DECODE_BLOCK", target)
        fold = rd.make_batched_ring_decode(mesh, jit=True,
                                           quantized=quantized)
        sh = rd.cache_sharding(mesh)
        o, k2, v2 = fold(jax.device_put(_stored(kc), sh),
                         jax.device_put(_stored(vcache), sh),
                         q, kt, vt, pos, live, *scales)
        rows = int(jax.jit(functools.partial(rd.decode_rows_read, mesh, T))(
            pos, live))
        return np.asarray(o), _by_head(k2, kc), _by_head(v2, vc), rows

    o0, k0, v0, rows0 = run(ONE_PASS, vc)
    assert rows0 == b * T
    o1, k1, v1, rows1 = run(BLK, vc)
    np.testing.assert_array_equal(k1, k0)
    np.testing.assert_array_equal(v1, v0)
    np.testing.assert_allclose(o1[live], o0[live], atol=1e-5, rtol=1e-5)
    # a dead row's cache row is bit-untouched, a live row's holds its
    # new token at its own position and nothing else moved
    want_k, want_v = kc.copy(), vc.copy()
    for r in np.flatnonzero(live):
        if quantized:
            want_k[r, pos[r]] = np.clip(np.round(
                kt[r, 0] / scales[0][r, :g, None]), -127, 127)
            want_v[r, pos[r]] = np.clip(np.round(
                vt[r, 0] / scales[1][r, :g, None]), -127, 127)
        else:
            want_k[r, pos[r]], want_v[r, pos[r]] = kt[r, 0], vt[r, 0]
    np.testing.assert_array_equal(k1, want_k)
    np.testing.assert_array_equal(v1, want_v)
    # the plain attend over the whole (updated) cache
    kf, vf = k1.astype(np.float64), v1.astype(np.float64)
    if quantized:
        kf = kf * scales[0][:, None, :, None]
        vf = vf * scales[1][:, None, :, None]
    see = np.arange(T)[None, :] <= np.clip(pos, 0, T - 1)[:, None]
    ref = _softmax_attend(q, kf, vf, see[:, None])
    np.testing.assert_allclose(o1[live], ref[live], atol=1e-5, rtol=1e-5)
    # rows the count says were not read are not read: poison them on
    # every device's shard and nothing moves, bit for bit
    if not quantized:
        t_shard = T // n_dev
        per_dev, left = [], rows1 // b
        for _ in range(n_dev):
            per_dev.append(min(left, t_shard))
            left -= per_dev[-1]
        vp = vc.copy()
        for i, n_read in enumerate(per_dev):
            vp[:, i * t_shard + n_read:(i + 1) * t_shard] = np.nan
        # the live rows' appends land below the frontier, so the
        # poison stays where it was put
        o2, _, _, _ = run(BLK, vp)
        np.testing.assert_array_equal(o2[live], o1[live])
        if not live.any():
            assert not o2.any()
    return rows1 // b


@pytest.mark.parametrize("name,kw,want_rows", [
    ("frontier_inside_a_block",
     dict(pos=[10, 3, 7], live=[True, True, True]), 16),
    ("frontier_on_a_block_edge",
     dict(pos=[15, 3, 0], live=[True, True, True]), 16),
    ("frontier_at_t_max",
     dict(pos=[T - 1, 3, 12], live=[True, True, True]), T),
    ("no_live_row",
     dict(pos=[T, 5, T], live=[False, False, False]), 0),
    ("dead_row_at_t_max_beside_a_short_live_row",
     dict(pos=[T, 3, 20], live=[False, True, False]), 8),
    ("grouped_queries",
     dict(pos=[10, 17, 2], live=[True, True, False], h=6, g=2), 24),
    ("int8_cache",
     dict(pos=[10, 3, 20], live=[True, True, False], quantized=True), 16),
    ("gpt2_large_heads_frontier_inside_a_block",
     dict(pos=[10, T, 7], live=[True, False, True], h=20, g=20, d=64), 16),
    ("gpt2_large_heads_int8",
     dict(pos=[18, 3, 20], live=[True, True, False], h=20, g=20, d=64,
          quantized=True), 24),
    ("laguna_heads_grouped",
     dict(pos=[10, 17, 2], live=[True, True, False], h=6, g=2, d=128), 24),
    ("two_device_ring_one_shard_beyond_the_frontier",
     dict(pos=[10, 3, T], live=[True, True, False], n_dev=2), 16),
    ("two_device_ring_frontier_in_the_second_shard",
     dict(pos=[18, 3, 9], live=[True, True, True], n_dev=2), 24),
], ids=lambda v: v if isinstance(v, str) else "")
def test_decode_fold_stops_at_the_frontier(monkeypatch, devices, name, kw,
                                           want_rows):
    assert _decode_case(monkeypatch, **kw) == want_rows


@pytest.mark.parametrize("h,g,d", [(6, 2, D), (20, 20, 64), (6, 2, 128)],
                         ids=["narrow_grouped", "gpt2_large_heads",
                              "laguna_heads"])
def test_wrapped_ring_reads_every_row_it_has_written(h, g, d):
    """A window layer's ring (position p at row p mod W): a row that has
    wrapped sees all W rows, one that has not sees rows up to its own;
    a dead row's ring is bit-untouched."""
    rng = np.random.default_rng(d)
    w, pos, live = 16, np.asarray([5, 21, 40], np.int32), [True, True, False]
    q, kt, vt = (_rand(rng, 3, 1, h, d) for _ in range(3))
    kt, vt = kt[:, :, :g], vt[:, :, :g]
    kc, vc = _rand(rng, 3, w, g, d), _rand(rng, 3, w, g, d)
    fold = rd.make_batched_ring_decode(meshlib.seq_mesh(1), jit=True,
                                       wrap=True)
    o, k2, v2 = fold(_stored(kc), _stored(vc), q, kt, vt, pos,
                     np.asarray(live))
    want_k, want_v = kc.copy(), vc.copy()
    for r in (0, 1):
        want_k[r, pos[r] % w], want_v[r, pos[r] % w] = kt[r, 0], vt[r, 0]
    np.testing.assert_array_equal(_by_head(k2, kc), want_k)
    np.testing.assert_array_equal(_by_head(v2, vc), want_v)
    see = np.arange(w)[None, :] <= pos[:, None]
    ref = _softmax_attend(q, want_k, want_v, see[:, None])
    np.testing.assert_allclose(np.asarray(o)[:2], ref[:2], atol=1e-5,
                               rtol=1e-5)


# -- the chunk fold -----------------------------------------------------


@pytest.mark.parametrize("name,start,p_end,n_dev,h,g,d", [
    ("ragged_last_chunk", 16, 21, 1, 2, 2, D),
    ("first_chunk", 0, 8, 1, 2, 2, D),
    ("last_rows_of_the_cache", T - 8, T, 1, 6, 2, D),
    ("two_device_ring", 8, 16, 2, 2, 2, D),
    ("gpt2_large_heads_first_chunk", 0, 8, 1, 20, 20, 64),
    ("gpt2_large_heads_mid_cache", 16, 22, 1, 20, 20, 64),
    ("laguna_heads_grouped_mid_cache", 8, 16, 1, 6, 2, 128),
], ids=lambda v: v if isinstance(v, str) else "")
def test_chunk_fold_stops_at_the_chunk(monkeypatch, devices, name, start,
                                       p_end, n_dev, h, g, d):
    rng = np.random.default_rng(start)
    mesh = meshlib.seq_mesh(n_dev)
    c = 8
    q = _rand(rng, 1, c, h, d)
    kt, vt = _rand(rng, 1, c, g, d), _rand(rng, 1, c, g, d)
    kc, vc = _rand(rng, 1, T, g, d), _rand(rng, 1, T, g, d)
    sh = rd.cache_sharding(mesh)

    def run(target, vcache):
        monkeypatch.setattr(rd, "_CHUNK_BLOCK", target)
        fold = rd.make_chunk_ring_decode(mesh, jit=True)
        o, k2, v2 = fold(jax.device_put(_stored(kc), sh),
                         jax.device_put(_stored(vcache), sh),
                         q, kt, vt, np.int32(start), np.int32(p_end))
        return np.asarray(o), _by_head(k2, kc), _by_head(v2, vc)

    o0, k0, v0 = run(ONE_PASS, vc)
    o1, k1, v1 = run(BLK, vc)
    np.testing.assert_array_equal(k1, k0)
    np.testing.assert_array_equal(v1, v0)
    real = p_end - start                    # rows past it are padding
    np.testing.assert_allclose(o1[:, :real], o0[:, :real], atol=1e-5,
                               rtol=1e-5)
    qpos = start + np.arange(c)
    see = (np.arange(T)[None, :] <= qpos[:, None])[None]    # [1, C, T]
    ref = _softmax_attend(q, k1, v1, see)
    np.testing.assert_allclose(o1[:, :real], ref[:, :real], atol=1e-5,
                               rtol=1e-5)
    # nothing beyond the chunk's end is read
    vp = vc.copy()
    vp[:, start + c:] = np.nan
    o2, _, _ = run(BLK, vp)
    np.testing.assert_array_equal(o2[:, :real], o1[:, :real])


# -- the engine ---------------------------------------------------------


def test_engine_streams_and_read_share(monkeypatch):
    """A server whose t_max spans several blocks emits the greedy
    streams of the one-pass fold and reports how far its windows
    read."""
    from idc_models_tpu.models import lm
    from idc_models_tpu.models.lm import attention_lm
    from idc_models_tpu.serve import LMServer, Request, engine

    vocab, t_max = 23, 64
    model = attention_lm(vocab, t_max, embed_dim=32, num_heads=2,
                         mlp_dim=64, num_blocks=2)
    params = model.init(jax.random.key(3)).params
    rng = np.random.default_rng(5)
    reqs = [Request(id=f"r{i}", max_new_tokens=int(n),
                    prompt=tuple(int(t) for t in rng.integers(0, vocab, p)))
            for i, (p, n) in enumerate([(5, 9), (19, 12), (3, 20), (11, 6)])]

    def serve(decode_blk, chunk_blk):
        monkeypatch.setattr(rd, "_DECODE_BLOCK", decode_blk)
        monkeypatch.setattr(rd, "_CHUNK_BLOCK", chunk_blk)
        # the compiled programs are shared by configuration: drop them
        # so that each server traces its folds at its own block size
        engine._engine_fns.cache_clear()
        lm._serving_fns.cache_clear()
        server = LMServer(params, embed_dim=32, num_heads=2, num_blocks=2,
                          t_max=t_max, n_slots=3, window=4, prefill_chunk=8,
                          cache_dtype=jnp.float32)
        server.run([(0.0, r) for r in reqs])
        out = {r.id: tuple(server.poll(r.id).tokens) for r in reqs}
        return out, server.summary()

    try:
        blocks, s_blocks = serve(BLK, BLK)
        whole, s_whole = serve(ONE_PASS, ONE_PASS)
    finally:
        engine._engine_fns.cache_clear()
        lm._serving_fns.cache_clear()
    assert blocks == whole
    assert all(len(blocks[r.id]) == r.max_new_tokens for r in reqs)
    assert s_whole["serve_attn_read_share"] == 1.0
    assert 0.0 < s_blocks["serve_attn_read_share"] < 1.0
    assert s_blocks["serve_compiles_observed"] == 0
