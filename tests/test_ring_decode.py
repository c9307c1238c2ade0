"""Ring-sharded KV-cache decode == full causal attention, step by step.

Every decoded token's output must equal the LAST ROW of full causal
attention over the sequence so far (exact attention, distributed
softmax merge) — on 1-D rings of several sizes (incl. non-power-of-2),
on the 2-D ("data", "seq") mesh, and continuing from a `prefill`-placed
prompt bit-identically to having decoded the prompt token by token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.ring_attention import full_attention
from idc_models_tpu.ring_decode import (
    cache_sharding, init_cache, make_ring_decode, prefill,
)

B, H, D = 2, 2, 8


def _kvq(t, seed=0, b=B):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (b, t, H, D)), jnp.float32)
    return mk(), mk(), mk()


def _decode_all(mesh, q, k, v, t_max, *, dtype=jnp.float32):
    """Feed tokens one at a time; stack the per-step outputs."""
    b = q.shape[0]
    kc, vc = init_cache(mesh, b, t_max, H, D, dtype=dtype)
    step = make_ring_decode(mesh)
    outs = []
    for pos in range(q.shape[1]):
        tok = slice(pos, pos + 1)
        out, kc, vc = step(kc, vc, q[:, tok], k[:, tok], v[:, tok],
                           pos)
        outs.append(out)
    return jnp.concatenate(outs, axis=1), kc, vc


@pytest.mark.parametrize("n_dev", [1, 3, 4, 8])
def test_decode_matches_full_causal(devices, n_dev):
    t = 24
    q, k, v = _kvq(t, seed=n_dev)
    mesh = meshlib.seq_mesh(n_dev)
    out, _, _ = _decode_all(mesh, q, k, v, t)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_decode_on_2d_mesh(devices):
    """Batch shards over "data" while every data row reduces its own
    ("seq")-sharded cache — DP serving composes like DP training."""
    t = 16
    q, k, v = _kvq(t, seed=9, b=4)
    mesh = meshlib.data_seq_mesh(2, 4)
    out, _, _ = _decode_all(mesh, q, k, v, t)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_decode_partial_cache(devices):
    """t_max larger than the decoded length: empty slots (including
    entire shards nobody has reached yet) contribute exactly zero to
    the merge."""
    t, t_max = 6, 32
    q, k, v = _kvq(t, seed=3)
    mesh = meshlib.seq_mesh(8)   # shards of 4; slots 6..31 empty
    out, _, _ = _decode_all(mesh, q, k, v, t_max)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_prefill_equals_tokenwise(devices):
    """`prefill`-placed prompt K/V + decode of the suffix == decoding
    everything token by token (caches bit-identical, outputs equal)."""
    t, p_len = 16, 10
    q, k, v = _kvq(t, seed=5)
    mesh = meshlib.seq_mesh(4)
    # path A: decode everything
    out_a, kc_a, vc_a = _decode_all(mesh, q, k, v, t)
    # path B: prefill the first p_len, decode the rest
    kc, vc = prefill(mesh, k[:, :p_len], v[:, :p_len], t,
                     dtype=jnp.float32)
    step = make_ring_decode(mesh)
    outs = []
    for pos in range(p_len, t):
        tok = slice(pos, pos + 1)
        out, kc, vc = step(kc, vc, q[:, tok], k[:, tok], v[:, tok], pos)
        outs.append(out)
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(kc_a))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vc_a))
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(outs, axis=1)),
        np.asarray(out_a[:, p_len:]), rtol=1e-6, atol=1e-6)


def test_cache_stays_sharded(devices):
    """The cache keeps its ring sharding through decode steps — no
    device ever holds the full cache (the serving-side O(T/n) claim)."""
    t = 16
    q, k, v = _kvq(t, seed=7)
    mesh = meshlib.seq_mesh(8)
    _, kc, vc = _decode_all(mesh, q, k, v, t)
    want = cache_sharding(mesh)
    assert kc.sharding.is_equivalent_to(want, kc.ndim)
    assert vc.sharding.is_equivalent_to(want, vc.ndim)


def test_decode_bf16_cache(devices):
    """bf16 caches (the serving default) stay within bf16 tolerance of
    the f32 reference — accumulation is f32 inside the merge."""
    t = 12
    q, k, v = _kvq(t, seed=11)
    mesh = meshlib.seq_mesh(4)
    out, _, _ = _decode_all(mesh, q.astype(jnp.bfloat16),
                            k.astype(jnp.bfloat16),
                            v.astype(jnp.bfloat16), t,
                            dtype=jnp.bfloat16)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


def test_decode_rejections(devices):
    mesh = meshlib.seq_mesh(4)
    with pytest.raises(ValueError, match="not divisible"):
        init_cache(mesh, B, 30, H, D)
    with pytest.raises(ValueError, match="ONE token"):
        kc, vc = init_cache(mesh, B, 16, H, D, dtype=jnp.float32)
        q, k, v = _kvq(16)
        make_ring_decode(mesh)(kc, vc, q[:, :2], k[:, :2], v[:, :2], 0)
    with pytest.raises(ValueError, match="exceeds t_max"):
        q, k, v = _kvq(16)
        prefill(mesh, k, v, 8)
    with pytest.raises(ValueError, match="outside the cache"):
        kc, vc = init_cache(mesh, B, 16, H, D, dtype=jnp.float32)
        q, k, v = _kvq(16)
        make_ring_decode(mesh)(kc, vc, q[:, :1], k[:, :1], v[:, :1], 16)
    # a CONCRETE jax scalar must fail the same way, not silently drop
    # the append (no shard owns slot t_max); same for a numpy 0-d array
    for bad in (jnp.int32(16), np.asarray(16)):
        with pytest.raises(ValueError, match="outside the cache"):
            kc, vc = init_cache(mesh, B, 16, H, D, dtype=jnp.float32)
            q, k, v = _kvq(16)
            make_ring_decode(mesh)(kc, vc, q[:, :1], k[:, :1], v[:, :1],
                                   bad)


def test_batched_decode_rowwise_bit_parity(devices):
    """The serving engine's per-row fold: with uniform positions and all
    rows live it is BIT-identical to the scalar fold (same einsums, same
    masking, same merge), and with per-row live masks a dead row's cache
    shard is bit-untouched while live rows still match the scalar
    path."""
    from idc_models_tpu.ring_decode import make_batched_ring_decode

    mesh = meshlib.seq_mesh(4)
    t_max = 16
    kc_a, vc_a = init_cache(mesh, B, t_max, H, D, dtype=jnp.float32)
    kc_b, vc_b = init_cache(mesh, B, t_max, H, D, dtype=jnp.float32)
    dec = make_ring_decode(mesh, jit=False)
    bdec = make_batched_ring_decode(mesh)
    rng = np.random.default_rng(0)

    def tok():
        return (jnp.asarray(rng.normal(0, 1, (B, 1, H, D)), jnp.float32)
                for _ in range(3))

    for pos in range(5):
        q, k, v = tok()
        o_a, kc_a, vc_a = dec(kc_a, vc_a, q, k, v, pos)
        o_b, kc_b, vc_b = bdec(kc_b, vc_b, q, k, v,
                               np.full(B, pos, np.int32),
                               np.ones(B, bool))
        np.testing.assert_array_equal(np.asarray(o_a), np.asarray(o_b))
        np.testing.assert_array_equal(np.asarray(kc_a), np.asarray(kc_b))
    # dead row: row 1 masked out — its shard bit-untouched, row 0 equals
    # the scalar fold's row 0
    q, k, v = tok()
    o_a, kc_a2, _ = dec(kc_a, vc_a, q, k, v, 5)
    o_b, kc_b2, vc_b2 = bdec(kc_b, vc_b, q, k, v,
                             np.array([5, t_max], np.int32),
                             np.array([True, False]))
    np.testing.assert_array_equal(np.asarray(kc_b2)[1],
                                  np.asarray(kc_b)[1])
    np.testing.assert_array_equal(np.asarray(kc_a2)[0],
                                  np.asarray(kc_b2)[0])
    np.testing.assert_array_equal(np.asarray(o_a)[0], np.asarray(o_b)[0])
    # dead rows may sit at pos == t_max (the finished frontier): no
    # crash, no append (checked above); concrete LIVE out-of-range pos
    # is rejected like the scalar path
    with pytest.raises(ValueError, match="outside the cache"):
        bdec(kc_b2, vc_b2, q, k, v, np.array([t_max, 3], np.int32),
             np.array([True, True]))
    with pytest.raises(ValueError, match="one position per row"):
        bdec(kc_b2, vc_b2, q, k, v, np.int32(3), np.ones(B, bool))


@pytest.mark.parametrize("h,g,d,wrap", [
    (20, 20, 64, False), (6, 2, 128, False), (6, 2, 8, False),
    (20, 20, 64, True), (6, 2, 128, True),
], ids=["gpt2_large_heads", "laguna_heads_grouped", "narrow_grouped",
        "gpt2_large_heads_wrapped", "laguna_heads_wrapped"])
def test_decode_over_the_stored_form_of_each_head_width(devices, h, g, d,
                                                        wrap):
    """The scalar fold over caches in their declared stored form
    (`cache_shape`: heads narrower than 128 lanes merged into rows, wider
    ones kept apart), grouped queries included, against plain causal
    attention in float32 with the cached heads repeated; on a wrapped
    ring of 8 rows (a window layer's: position p at row p mod 8) against
    attention over the last 8 positions."""
    from idc_models_tpu.ring_decode import cache_shape

    t, w = 14, 8
    rng = np.random.default_rng(h + d)
    q = jnp.asarray(rng.normal(0, 1, (B, t, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(0, 1, (B, t, g, d)), jnp.float32)
            for _ in range(2))
    mesh = meshlib.seq_mesh(1 if wrap else 2)
    rows = w if wrap else t
    kc, vc = init_cache(mesh, B, rows, g, d, dtype=jnp.float32)
    assert kc.shape == cache_shape(B, rows, g, d)
    step = make_ring_decode(mesh, wrap=wrap)
    outs = []
    for pos in range(t):
        tok = slice(pos, pos + 1)
        out, kc, vc = step(kc, vc, q[:, tok], k[:, tok], v[:, tok], pos)
        outs.append(np.asarray(out[:, 0]))
    kr, vr = (np.repeat(np.asarray(a, np.float64), h // g, axis=2)
              for a in (k, v))
    for pos in range(t):
        lo = max(0, pos - w + 1) if wrap else 0
        s = np.einsum("bhd,bkhd->bhk", np.asarray(q[:, pos], np.float64),
                      kr[:, lo:pos + 1]) * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        ref = np.einsum("bhk,bkhd->bhd", p / p.sum(-1, keepdims=True),
                        vr[:, lo:pos + 1])
        np.testing.assert_allclose(outs[pos], ref, rtol=1e-5, atol=1e-5)
    # the rows hold the positions they were given, bit for bit
    last = np.asarray(k, np.float32)[:, t - rows:]
    got = np.asarray(kc).reshape(B, rows, g, d)
    if wrap:
        got = np.roll(got, -(t % w), axis=1)
    np.testing.assert_array_equal(got, last)


def _bits(a):
    """The stored bits of a cache, whatever its dtype."""
    a = np.asarray(a)
    return a.view({1: np.int8, 2: np.uint16, 4: np.uint32}[a.itemsize])


@pytest.mark.parametrize("wrap", [False, True], ids=["plain", "wrap"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("d", [64, 128], ids=["merged", "by_head"])
def test_append_is_one_dropping_scatter(devices, d, dtype, wrap):
    """The batched fold's append (`_append_rows`) against the plain
    per-row reference (`ref[b, slot[b]] = t[b, 0]` where row b writes),
    bit for bit, over both stored forms, both cache dtypes, a contiguous
    cache and a wrapped ring: dead rows untouched, a live row at
    `slot == T - 1` written (the dropped index is T, not T - 1), dead
    rows at `pos == t_max` harmless, all rows dead = the cache as it
    was; then the same through `make_batched_ring_decode` wherever the
    fold takes the combination (a wrapped ring has no int8 form), and on
    a two-device ring, where only the owner's shard changes."""
    from idc_models_tpu import ring_decode as rd

    b, t, g = 5, 8, 2
    int8 = dtype == jnp.int8
    rng = np.random.default_rng(d + 7 * int8 + wrap)
    shape = rd.cache_shape(b, t, g, d)
    assert len(shape) == (3 if d == 64 else 4)

    def cache():
        if int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)

    def token():
        return jnp.asarray(rng.normal(0, 1, (b, 1, g, d)), jnp.float32)

    def reference(c, rows, pos, live):
        # the fold's own slot arithmetic, spelled per row on the host
        ref = np.array(c)
        for r in range(b):
            if live[r]:
                ref[r, pos[r] % t] = np.asarray(rows)[r, 0]
        return ref

    # positions: a live row at the last row, live rows elsewhere, dead
    # rows at the finished frontier (t_max) and inside the cache
    base = 2 * t if wrap else 0
    pos = np.array([base + 1, base + t - 1, t, base + 4, 3], np.int32)
    live = np.array([True, True, False, True, False])
    slot = jnp.asarray((np.maximum(pos, 0) if wrap
                        else np.clip(pos, 0, t - 1)) % t, jnp.int32)

    # 1. the append itself
    c, tok = cache(), token()
    rows = rd._rows(tok * (40 if int8 else 1), c).astype(dtype)
    append = jax.jit(rd._append_rows)
    got = append(c, rows, slot, jnp.asarray(live))
    assert got.dtype == c.dtype and got.shape == c.shape
    np.testing.assert_array_equal(_bits(got),
                                  _bits(reference(c, rows, pos, live)))
    assert (_bits(got)[1, t - 1] == _bits(rows)[1, 0]).all()
    for dead in (2, 4):
        np.testing.assert_array_equal(_bits(got)[dead], _bits(c)[dead])
    none = append(c, rows, slot, jnp.zeros(b, bool))
    np.testing.assert_array_equal(_bits(none), _bits(c))

    if wrap and int8:
        return
    # 2. through the fold, on one device and on a ring of two
    for n_dev in ((1,) if wrap else (1, 2)):
        mesh = meshlib.seq_mesh(n_dev)
        place = lambda a: jax.device_put(a, cache_sharding(mesh))
        kc0, vc0 = cache(), cache()
        q, kt, vt = token(), token(), token()
        scales = ()
        if int8:
            scales = tuple(jnp.asarray(rng.uniform(0.02, 0.05, (b, g)),
                                       jnp.float32) for _ in range(2))

        def stored(tok, c, scale=None):
            if not int8:
                return rd._rows(tok, c).astype(dtype)
            qz = np.clip(np.round(np.asarray(tok)
                                  / np.asarray(scale)[:, None, :, None]),
                         -127, 127)
            return rd._rows(jnp.asarray(qz), c).astype(dtype)

        fold = jax.jit(rd.make_batched_ring_decode(mesh, quantized=int8,
                                                   wrap=wrap))
        _, kc, vc = fold(place(kc0), place(vc0), q, kt, vt, pos, live,
                         *scales)
        for new, old, tok, sc in ((kc, kc0, kt, scales[:1]),
                                  (vc, vc0, vt, scales[1:])):
            want = reference(old, stored(tok, old, *sc), pos, live)
            np.testing.assert_array_equal(_bits(new), _bits(want))
        assert kc.sharding.is_equivalent_to(cache_sharding(mesh), kc.ndim)
        _, kc, vc = fold(place(kc0), place(vc0), q, kt, vt,
                         np.full(b, t, np.int32), np.zeros(b, bool),
                         *scales)
        np.testing.assert_array_equal(_bits(kc), _bits(kc0))
        np.testing.assert_array_equal(_bits(vc), _bits(vc0))
