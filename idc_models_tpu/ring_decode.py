"""Ring-sharded KV-cache decoding: serve the long contexts the ring trains.

The training side (`ring_attention.py`) shards the sequence over a
"seq" mesh axis and never materializes it on one device; this module
gives inference the same property. The KV cache lives sharded over the
ring — device i owns cache slots [i*T/n, (i+1)*T/n) — and a decode step
for ONE new token is:

1. append: the slot owner (pos // t_shard) writes the new k/v into its
   resident shard; every other device's shard is untouched — no
   collective, the cache never moves;
2. local attend: every device scores the (replicated, [B, 1, H, D])
   query against its OWN K/V shard, masked to global positions <= pos —
   a [B, H, t_shard] score row, never [T, T] anything;
3. merge: one numerically-stable distributed softmax combine over the
   "seq" axis — `pmax` of the local maxima, then a single `psum` of the
   corrected (l, acc) partials. Two collectives per token, both riding
   ICI; O(T/n) memory per device, exactly like training.

This is flash-attention's (m, l, acc) algebra applied ACROSS devices
instead of across ring steps: where training's ring rotates K/V blocks
through a fixed schedule, decode holds K/V still and reduces the
per-shard partials — the right shape for one-token queries, where a
rotating ring would serialize n hops for no reuse.

The cache's sharding IS the training sharding (contiguous "seq" sharding
of the positions, dimension 1), so a trained model's prompt K/V can be
placed directly: pad to t_max, `jax.device_put` under `cache_sharding`,
and decode continues from there — `prefill` does exactly this and is
pinned bit-identical to decoding the prompt token by token. The zigzag
layout is a TRAINING optimization (balancing a causal ring schedule
that decode does not run) and deliberately has no decode counterpart.

What follows the positions is the cache's STORED FORM, declared in one
place, `cache_shape`, from the width of a head: heads narrower than a
TPU tile's 128 lanes are merged into rows `[B, T, G*D]` (kept apart they
are padded wherever a program indexes them, and re-laid between that
form and the runtime's compact one at every program's edge); heads of
whole tiles stay `[B, T, G, D]`. New keys and values always arrive as
`[B, C, G, D]`, and `_scores` / `_weighted` contract each form as the
chip reads it fastest: one token over merged rows block-diagonally and
in place, everything else head by head. `init_cache`, `prefill`,
`as_cache` and `grow_cache` build and pad either form. The paged pools
(`make_paged_*`) keep `[n_pages, page_size, H, D]`.

Exactness: every step equals the last row of full causal attention over
the sequence so far, fp tolerance, pinned by tests/test_ring_decode.py.
The reference has no serving path at all (SURVEY.md §2 ends at training
+ eval), so this is beyond-parity capability.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from idc_models_tpu import collectives
from idc_models_tpu import mesh as meshlib

_MASKED = -1e30  # same finite sentinel as ring_attention._MASKED


# lanes of a TPU tile: a head of a multiple of them fills whole tiles by
# itself, a narrower one only together with its neighbours
_LANES = 128


def cache_shape(batch: int, t_max: int, heads: int, dim: int) -> tuple:
    """The declared shape of a contiguous cache of `heads` cached heads
    of `dim`: THE one place that decides the stored form, from the one
    thing that decides what the chip reads in place, the width of a head
    (PERF.md section 6, PR 30, has the readings):

    - narrower than a tile's 128 lanes: `[batch, t_max, heads * dim]`,
      the heads merged into rows that fill whole tiles. Kept apart,
      (heads, dim) = (20, 64) is padded to (24, 128) wherever a program
      indexes it, 2.4 times the bytes, and re-laid between that form and
      the compact one the runtime keeps at every program's edge;
    - whole tiles: `[batch, t_max, heads, dim]`. Nothing is padded, and
      the products over (head, dim) read it faster than any contraction
      of merged rows measured.

    Positions are dimension 1 either way (the sequence ring shards it, a
    window layer wraps over it), and every program that carries a cache
    across its edge declares this shape, so none re-lays what another
    wrote."""
    if dim % _LANES:
        return (batch, t_max, heads * dim)
    return (batch, t_max, heads, dim)


def grow_cache(c, t_max: int):
    """A cache of either stored form, zeros appended behind its rows up
    to `t_max` positions."""
    return jnp.pad(c, ((0, 0), (0, t_max - c.shape[1]))
                   + ((0, 0),) * (c.ndim - 2))


def as_cache(kv, t_max: int, dtype):
    """Keys or values [B, P, G, D] as the first P rows of a fresh cache
    of `cache_shape(B, t_max, G, D)`, zeros behind them."""
    return grow_cache(
        jnp.asarray(kv, dtype).reshape(cache_shape(*kv.shape)), t_max)


def _rows(t, cache):
    """New keys or values [B, C, G, D], as a layer's projection hands
    them over, as C rows of `cache`: the one reshape between heads and
    merged rows, of the new tokens and never of the cache."""
    return t.reshape(*t.shape[:2], *cache.shape[2:])


def _append_rows(c, t, slot, mine):
    """Append of one token to every row of the batch, as ONE scatter:
    row b of the cache `c` ([B, T, ...], either stored form, any dtype)
    takes `t[b, 0]` at position `slot[b]` where `mine[b]`. A row that
    writes nothing is given the index one past the end, `T`, which
    `mode="drop"` discards: it is bit-untouched, and nothing is read
    back or selected. A live row at `slot == T - 1` writes the last
    position. (Spelled as a read, a select and a `dynamic_update_slice`
    per row under `vmap`, XLA expanded the scatter into a loop of one
    trip a row and four small operations a trip, which ran whether a
    row wrote or not: a third of `gpt2-large`'s window. PERF.md section
    6, PRs 30 and 33.)"""
    at = jnp.where(mine, slot, c.shape[1])
    return c.at[np.arange(c.shape[0]), at].set(
        t[:, 0].astype(c.dtype), mode="drop", unique_indices=True,
        indices_are_sorted=True)


def _splice_rows(cache, tok, src, take_new):
    """One request's chunk into its cache: row j takes the chunk's token
    `src[j]` where `take_new[j]` ([T] each), else keeps what it holds."""
    gathered = jnp.take(_rows(tok, cache), src, axis=1).astype(cache.dtype)
    return jnp.where(take_new.reshape((1, -1) + (1,) * (cache.ndim - 2)),
                     gathered, cache)


def _own_lanes(h: int, g: int):
    """[H, G] bool: query head h reads cached head h // (H / G), the
    lanes [that * D, that * D + D) of a merged row of G * D lanes. A
    numpy constant of the program: traced as an iota compare it becomes
    a kernel that jaxlib's CPU loader does not find again in a
    deserialized executable (serve/compile_cache.py)."""
    return (np.arange(h)[:, None] // (h // g)) == np.arange(g)[None, :]


def _by_head(c, d: int):
    """Rows kept by head [B, K, G, D] as they are; merged rows
    [B, K, G*D] (a block of the cache, never the cache) split."""
    return c if c.ndim == 4 else c.reshape(*c.shape[:2], -1, d)


def _scores(q, kc):
    """q.k over the cache in float32: q [B, H, D] or [B, C, H, D] against
    kc [B, K, G, D] or merged rows [B, K, G*D] -> [B, H, K] or
    [B, H, C, K]. With G < H (grouped queries) query head h reads cached
    head h // (H / G); the cache is never repeated to H heads.

    ONE token over merged rows is spread block-diagonally over the row's
    lanes, its D values in its cached head's lanes and exact zeros in
    the others, and contracted against the rows as they are stored: the
    matrix unit multiplies by zeros G times over, which a fold bound by
    the bytes of the cache does not feel, and nothing is re-laid. A
    CHUNK of C queries would feel it (C * H query rows for every row
    read: compute binds), so the merged rows it was handed, one block,
    are split into heads and contracted head by head like rows kept by
    head."""
    d = q.shape[-1]
    if q.ndim == 3 and kc.ndim == 3:
        h, g = q.shape[1], kc.shape[-1] // d
        qx = jnp.where(_own_lanes(h, g)[:, :, None], q[:, :, None, :], 0)
        return jnp.einsum("bhc,bkc->bhk", qx.reshape(q.shape[0], h, g * d),
                          kc, preferred_element_type=jnp.float32)
    kc = _by_head(kc, d)
    h, g = q.shape[-2], kc.shape[-2]
    if q.ndim == 3:
        if h == g:
            return jnp.einsum("bhd,bkhd->bhk", q, kc,
                              preferred_element_type=jnp.float32)
        b = q.shape[0]
        s = jnp.einsum("bgrd,bkgd->bgrk", q.reshape(b, g, h // g, d), kc,
                       preferred_element_type=jnp.float32)
        return s.reshape(b, h, kc.shape[1])
    if h == g:
        return jnp.einsum("bchd,bkhd->bhck", q, kc,
                          preferred_element_type=jnp.float32)
    b, c = q.shape[:2]
    s = jnp.einsum("bcgrd,bkgd->bgrck", q.reshape(b, c, g, h // g, d), kc,
                   preferred_element_type=jnp.float32)
    return s.reshape(b, h, c, kc.shape[1])


def _weighted(p, vc, d: int):
    """Probabilities times cached values in float32: p [B, H, K] or
    [B, H, C, K] against vc [B, K, G, D] or merged rows [B, K, G*D] of
    heads of `d` -> [B, H, D] or [B, H, C, D]; the counterpart of
    `_scores`, case by case. One token's product against merged rows is
    taken over all G * D lanes and each head keeps the D lanes of its
    own cached head (the others, another head's values under this
    head's probabilities, are dropped)."""
    if p.ndim == 3 and vc.ndim == 3:
        h, g = p.shape[1], vc.shape[-1] // d
        o = jnp.einsum("bhk,bkc->bhc", p, vc,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jnp.where(_own_lanes(h, g)[:, :, None],
                                 o.reshape(p.shape[0], h, g, d), 0.0),
                       axis=2)
    vc = _by_head(vc, d)
    h, g = p.shape[1], vc.shape[-2]
    if p.ndim == 3:
        if h == g:
            return jnp.einsum("bhk,bkhd->bhd", p, vc,
                              preferred_element_type=jnp.float32)
        b, _, k = p.shape
        o = jnp.einsum("bgrk,bkgd->bgrd", p.reshape(b, g, h // g, k), vc,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, h, d)
    if h == g:
        return jnp.einsum("bhck,bkhd->bhcd", p, vc,
                          preferred_element_type=jnp.float32)
    b, _, c, k = p.shape
    o = jnp.einsum("bgrck,bkgd->bgrcd", p.reshape(b, g, h // g, c, k), vc,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, c, d)


def _fold_block(t_shard: int, target: int) -> int:
    """Rows a frontier fold reads at a time: the largest power of two
    that divides the shard and is no more than `target`; the whole shard
    when it is no longer than that (one block, no loop), or when no such
    power of two of at least 8 rows divides it."""
    if t_shard <= target:
        return t_shard
    blk = 1 << (target.bit_length() - 1)
    while t_shard % blk:
        blk //= 2
    return blk if blk >= 8 else t_shard


# rows a block holds in the one-token fold and in the chunk fold, fixed
# from chip runs of both served models (PERF.md section 6, PR 28)
_DECODE_BLOCK = 256
_CHUNK_BLOCK = 512


def _decode_frontier(posc, live):
    """One past the furthest position a live row attends (its own, just
    appended): 0 without a live row. A dead row may sit at t_max and
    sets nothing."""
    return jnp.max(jnp.where(live, posc, -1)) + 1


def _trips(frontier, row0, t_shard: int, blk: int):
    """Blocks of `blk` rows a shard that starts at global row `row0`
    holds below `frontier`."""
    return (jnp.clip(frontier - row0, 0, t_shard) + blk - 1) // blk


def _attend_to_frontier(q, kc, vc, see, frontier, blk: int, *, scale,
                        row0=0, k_scale=None, row=None):
    """Local attend of q [B, H, D] or [B, C, H, D] over the resident
    shard kc / vc [B, t_shard, ...] (either form of `cache_shape`), read
    in blocks of `blk` rows up to `frontier` (a global row count,
    traced; the shard starts at global row `row0`): -> float32 partials
    (m, l, acc) for the ring merge.

    `see(g)` gives the visibility of global rows g [blk], broadcastable
    against the scores [B, H, (C,) blk]; rows at or beyond `frontier`
    must be invisible to every query, so leaving them unread drops only
    terms that are exactly 0. `k_scale` (int8 caches) multiplies each
    block's scores. A shard no longer than `blk` is ONE pass over all of
    it with no loop, whatever the frontier: the fold as it was before
    blocks existed, bit for bit. Otherwise blocks are merged by the
    algebra that merges the shards of a ring: running maximum, both
    sides rescaled by exp(m - m_new). With `row` (traced) the caches are
    a batch's and q is ONE request's: only batch row `row` is read,
    block by block, and never copied out whole."""
    t_shard, d = kc.shape[1], q.shape[-1]

    def take(c, at, n):
        if row is None:
            return lax.dynamic_slice_in_dim(c, at, n, axis=1)
        return lax.dynamic_slice(c, (row, at) + (0,) * (c.ndim - 2),
                                 (1, n) + c.shape[2:])

    def block(kb, vb, g):
        # f32 accumulation by preferred_element_type, NOT astype:
        # upcasting a bf16 cache would materialize a 2x-size f32 copy
        s = _scores(q, kb) * scale
        if k_scale is not None:
            s = s * k_scale
        vis = see(g)
        s = jnp.where(vis, s, _MASKED)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        # a fully-masked block contributes p = exp(0) = 1 garbage: zero
        # it explicitly so the merge is exact rather than relying on the
        # exp(_MASKED - m) == 0 underflow
        p = jnp.where(vis, p, 0.0)
        return m, jnp.sum(p, axis=-1), _weighted(p, vb, d)

    rows = jnp.arange(blk, dtype=jnp.int32)
    if t_shard <= blk:
        if row is not None:
            kc, vc = take(kc, 0, t_shard), take(vc, 0, t_shard)
        return block(kc, vc, row0 + rows)
    if t_shard % blk:
        raise ValueError(f"block of {blk} rows does not divide the "
                         f"shard's {t_shard}")
    trips = _trips(frontier, row0, t_shard, blk)

    def body(j, carry):
        m, l, acc = carry
        kb, vb = take(kc, j * blk, blk), take(vc, j * blk, blk)
        mb, lb, ab = block(kb, vb, row0 + j * blk + rows)
        m_new = jnp.maximum(m, mb)
        old, new = jnp.exp(m - m_new), jnp.exp(mb - m_new)
        return (m_new, l * old + lb * new,
                acc * old[..., None] + ab * new[..., None])

    lead = (q.shape[0], q.shape[-2]) + q.shape[1:-2]     # [B, H, (C)]
    init = (jnp.full(lead, _MASKED, jnp.float32),
            jnp.zeros(lead, jnp.float32),
            jnp.zeros(lead + (d,), jnp.float32))
    return lax.fori_loop(0, trips, body, init)


def _check_wrap(wrap: bool, n: int, axis: str) -> None:
    """A window layer's cache is a ring over POSITIONS (position p at
    row p mod T): it lives whole on one device, so a sequence ring of
    more than one device cannot carry it."""
    if wrap and n > 1:
        raise ValueError(
            f"a window-attention cache (position p at row p mod W) "
            f"cannot shard over a sequence ring of {n} devices on mesh "
            f"axis {axis!r} — serve window layers on a one-device seq "
            f"mesh")


def cache_sharding(mesh: Mesh, axis: str = meshlib.SEQ_AXIS) -> NamedSharding:
    """Sharding of a cache of either stored form (`cache_shape`): batch
    and positions as in the training-side q/k/v sharding
    (`mesh.batch_seq_sharding`, the one construction site), so trained
    K/V drops in with no relayout across devices; whatever follows the
    positions (merged rows, or heads and their width) stays whole."""
    return meshlib.batch_seq_sharding(mesh, axis, trailing=0)


def init_cache(mesh: Mesh, batch: int, t_max: int, heads: int, dim: int,
               *, dtype=jnp.bfloat16, axis: str = meshlib.SEQ_AXIS):
    """Zero-initialized (k, v) caches of `cache_shape(batch, t_max,
    heads, dim)`, sharded over the ring."""
    n = mesh.shape[axis]
    if t_max % n:
        raise ValueError(f"t_max {t_max} not divisible by the ring size "
                         f"{n} over mesh axis {axis!r}")
    sh = cache_sharding(mesh, axis)
    # put_with_sharding, not device_put: on a multi-host mesh each
    # process materializes only its addressable shards (mesh.py)
    mk = functools.partial(np.zeros, cache_shape(batch, t_max, heads, dim),
                           jnp.dtype(dtype))
    return (meshlib.put_with_sharding(mk(), sh),
            meshlib.put_with_sharding(mk(), sh))


def make_ring_decode(mesh: Mesh, *, axis: str = meshlib.SEQ_AXIS,
                     scale: float | None = None, jit: bool = True,
                     wrap: bool = False):
    """Build ``fn(k_cache, v_cache, q_t, k_t, v_t, pos) ->
    (out_t, k_cache, v_cache)``.

    q_t/k_t/v_t are the ONE new token's projections, [B, 1, H, D]
    (replicated over `axis`); `pos` is its global position (int32
    scalar; cache slots > pos must still be zero/garbage-masked). The
    returned function is jitted with both caches donated — the decode
    loop updates in place, O(1) HBM traffic per step beyond the shard
    writes.

    ``jit=False`` returns the same function un-jitted, for callers that
    trace it into a LARGER jitted program (the LM's fused scan decode
    loop, models/lm.py) — a nested jit would discard the donation with
    a warning, and the caller's top-level jit owns donation anyway.
    Traced callers also own the `pos` bound (see below).

    The cache may hold FEWER heads than the query (grouped queries:
    q_t [B, 1, H, D] over caches of G heads, H a multiple of G). With
    ``wrap=True`` the cache is a window layer's ring over positions:
    its T rows hold the latest T positions, position p at row p mod T,
    so `pos` may exceed T and every row written so far is visible
    (one-device rings only)."""
    n = mesh.shape[axis]
    _check_wrap(wrap, n, axis)

    def per_device(kc, vc, q, kt, vt, pos):
        t_shard, d = kc.shape[1], q.shape[-1]
        kt, vt = _rows(kt, kc), _rows(vt, vc)
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        pos = jnp.asarray(pos, jnp.int32)
        owner = 0 if wrap else pos // t_shard
        slot = pos % t_shard
        # 1. append — O(1) traffic: read the ONE slot, select the new
        # token on the owner (non-owners write their existing value
        # back), one single-slot update that donation lowers in place —
        # never a whole-shard copy
        mine = (owner == i)
        at = (0, slot) + (0,) * (kc.ndim - 2)
        old_k = lax.dynamic_slice(kc, at, kt.shape)
        old_v = lax.dynamic_slice(vc, at, vt.shape)
        kc = lax.dynamic_update_slice(
            kc, jnp.where(mine, kt.astype(kc.dtype), old_k), at)
        vc = lax.dynamic_update_slice(
            vc, jnp.where(mine, vt.astype(vc.dtype), old_v), at)
        # 2. local attend against the resident shard, f32 accumulation
        # (preferred_element_type, NOT astype: upcasting a 64k-slot bf16
        # cache would materialize a 2x-size f32 copy per step — the MXU
        # accumulates in f32 natively, same as ring_attention's blocks)
        s = _scores(q[:, 0], kc) * scale_
        visible = (i * t_shard + jnp.arange(t_shard)) <= pos
        s = jnp.where(visible[None, None, :], s, _MASKED)
        m_loc = jnp.max(s, axis=-1)                       # [B, H]
        p = jnp.exp(s - m_loc[..., None])
        # a fully-masked shard (all slots beyond pos) contributes
        # p = exp(0) = 1 garbage — zero it explicitly so the psum is
        # exact rather than relying on the corr ~ exp(_MASKED - m) == 0
        # underflow
        p = jnp.where(visible[None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)                       # [B, H]
        acc_loc = _weighted(p, vc, d)
        # 3. one stable softmax merge across the ring
        m_glob = lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = collectives.psum(l_loc * corr, axis)
        acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]  # [B,H,D]
        return out[:, None].astype(q.dtype), kc, vc  # [B,1,H,D]

    bo = meshlib.batch_axes(mesh, axis)   # "model" stays weight-only
    cache_spec = P(bo, axis)      # either stored form: the rest whole
    tok_spec = P(bo, None, None, None)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(cache_spec, cache_spec, tok_spec, tok_spec, tok_spec,
                  P()),
        out_specs=(tok_spec, cache_spec, cache_spec),
        check_vma=False,
    )

    def checked(kc, vc, q_t, k_t, v_t, pos):
        if q_t.shape[1] != 1:
            raise ValueError(
                f"ring decode takes ONE token per step: q_t has "
                f"sequence length {q_t.shape[1]} (batch prefill goes "
                f"through `prefill` / the training ring)")
        if kc.shape[1] % n:
            raise ValueError(
                f"cache length {kc.shape[1]} not divisible by the ring "
                f"size {n} over mesh axis {axis!r}")
        return mapped(kc, vc, q_t, k_t, v_t, pos)

    if not jit:
        return checked

    jitted = jax.jit(checked, donate_argnums=(0, 1))

    def fn(kc, vc, q_t, k_t, v_t, pos):
        # pos >= t_max would silently drop the append (no shard owns
        # the slot) and return attention that excludes the new token —
        # reject ANY concrete out-of-range position here: python and
        # numpy ints, numpy scalars, and already-materialized jax
        # scalars (a jnp.int32(t_max) must fail the same way, not
        # silently vanish). Callers tracing pos (their own jit/scan
        # loop) own the bound as a contract.
        import numpy as _np

        concrete = None
        if isinstance(pos, (int, _np.integer)):
            concrete = int(pos)
        elif (isinstance(pos, (jax.Array, _np.ndarray))
              and jnp.ndim(pos) == 0):
            try:
                concrete = int(pos)
            except jax.errors.ConcretizationTypeError:
                pass   # traced: the caller's jit/scan owns the bound
        if (concrete is not None and not wrap
                and not (0 <= concrete < kc.shape[1])):
            raise ValueError(
                f"pos {concrete} outside the cache (t_max {kc.shape[1]})"
                f" — grow the cache at init/prefill time; decode cannot "
                f"append past it")
        return jitted(kc, vc, q_t, k_t, v_t, pos)

    return fn


def make_batched_ring_decode(mesh: Mesh, *, axis: str = meshlib.SEQ_AXIS,
                             scale: float | None = None,
                             jit: bool = False,
                             quantized: bool = False,
                             wrap: bool = False):
    """Per-slot decode fold for the continuous-batching engine
    (serve/engine.py): ``fn(k_cache, v_cache, q_t, k_t, v_t, pos, live)
    -> (out_t, k_cache, v_cache)`` where every batch row is an
    INDEPENDENT sequence at its OWN position.

    With ``quantized=True`` the caches hold int8 K/V and the signature
    grows per-(row, head) dequantization scales: ``fn(kc, vc, q_t, k_t,
    v_t, pos, live, k_scale, v_scale)`` with both scales float32 [B, H].
    Because a scale is constant over the slot dimension and head_dim,
    dequantization FACTORS OUT of both einsums — scores multiply by
    k_scale and the value accumulator by v_scale AFTER the contraction —
    so the int8 cache is never materialized as a float copy (the whole
    point: the HBM win is capacity AND bandwidth). Appends quantize the
    new token's K/V with the row's existing scale (clipped to ±127):
    scales are set once at insert from the prefill content, so decode
    tokens whose activations outgrow the prompt's range clip — the
    documented int8 accuracy caveat (docs/LONG_CONTEXT.md).

    `pos` is int32 [B] (row b's new token sits at global position
    pos[b]) and `live` is bool [B]: rows with live=False append NOTHING
    — their cache shard is bit-untouched, which is what lets a finished
    serving slot idle through decode windows without corrupting the
    cache a recycled request will overwrite. The append is one scatter
    over the batch for each of the two caches (`_append_rows`): a live
    row writes its one position on the shard that owns it, every other
    row and every other shard an index one past the end, which is
    dropped; no row is read back. The attend/merge algebra is
    the scalar `make_ring_decode` fold applied row-wise (same einsums,
    same masking, same two-collective softmax merge), and the attend
    stops at the live frontier: each device reads its shard in blocks
    (`_fold_block`: a power of two derived from the shard's length) up
    to one past the furthest position of a LIVE row, not all of its
    rows (`_attend_to_frontier`; `decode_rows_read` counts them). While
    the shard fits one block a live row's output is bit-identical to
    the scalar path at the same position; beyond that it is equal up to
    the float32 rounding of the block merge, the merge the ring already
    applies across devices. A dead row's output is whatever lies below
    the frontier (zeros when no row is live) and is the caller's to
    discard.

    Rows where live=False may carry pos == t_max (one past the end, the
    natural "finished" frontier); positions are clamped internally for
    the attend and the append drops them. Defaults to
    ``jit=False`` because the intended caller is the engine's fused
    decode window, whose top-level jit owns donation.

    Grouped queries and ``wrap=True`` (a window layer's ring over
    positions) behave as in `make_ring_decode`; a wrapped cache takes
    no int8 rows."""
    n = mesh.shape[axis]
    _check_wrap(wrap, n, axis)
    if wrap and quantized:
        raise ValueError("a window-attention ring cache has no int8 form")

    def per_device(kc, vc, q, kt, vt, pos, live, k_scale=None,
                   v_scale=None):
        t_shard, d = kc.shape[1], q.shape[-1]
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.asarray(live, jnp.bool_)
        # finished rows legitimately sit at pos == t_max; clamp so the
        # owner/slot arithmetic and visibility mask stay in range (the
        # append is gated on `live`, never on the clamp). A wrapped
        # cache's positions run past its length by design.
        posc = (jnp.maximum(pos, 0) if wrap
                else jnp.clip(pos, 0, n * t_shard - 1))
        owner = 0 if wrap else posc // t_shard
        slot = posc % t_shard
        mine = (owner == i) & live

        if quantized:
            # quantize the incoming token with the ROW's frozen scale
            # (insert-time absmax); a dead row's zero scale divides to
            # inf but clips finitely and the live gate discards it
            kt = jnp.clip(jnp.round(
                kt.astype(jnp.float32) / k_scale[:, None, :, None]),
                -127, 127)
            vt = jnp.clip(jnp.round(
                vt.astype(jnp.float32) / v_scale[:, None, :, None]),
                -127, 127)

        kc = _append_rows(kc, _rows(kt, kc), slot, mine)
        vc = _append_rows(vc, _rows(vt, vc), slot, mine)
        # row-wise local attend + the same stable merge as the scalar
        # fold (see make_ring_decode); visibility is per ROW now. A
        # wrapped ring is all live once it has wrapped: one pass over
        # it. A contiguous shard is read in blocks up to the furthest
        # live position of the batch. int8: dequantize by FACTORING the
        # per-(row, head) scale out of the contractions — no float copy
        # of the cache exists
        m_loc, l_loc, acc_loc = _attend_to_frontier(
            q[:, 0], kc, vc,
            lambda g: (g[None, :] <= posc[:, None])[:, None, :],
            _decode_frontier(posc, live),
            t_shard if wrap else _fold_block(t_shard, _DECODE_BLOCK),
            scale=scale_, row0=i * t_shard,
            k_scale=k_scale[:, :, None] if quantized else None)
        if quantized:
            acc_loc = acc_loc * v_scale[..., None]
        m_glob = lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = collectives.psum(l_loc * corr, axis)
        acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]
        return out[:, None].astype(q.dtype), kc, vc

    bo = meshlib.batch_axes(mesh, axis)   # "model" stays weight-only
    cache_spec = P(bo, axis)      # either stored form: the rest whole
    tok_spec = P(bo, None, None, None)
    # scales are per (row, head): the batch dim shards with the caches'
    # over the non-seq axes (P() would mis-shape the per-device divide
    # on any mesh with a non-trivial non-seq axis)
    scale_specs = (P(bo, None), P(bo, None)) if quantized else ()
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(cache_spec, cache_spec, tok_spec, tok_spec, tok_spec,
                  P(), P()) + scale_specs,
        out_specs=(tok_spec, cache_spec, cache_spec),
        check_vma=False,
    )

    def checked(kc, vc, q_t, k_t, v_t, pos, live, *scales):
        if quantized and len(scales) != 2:
            raise ValueError("quantized fold needs (k_scale, v_scale)")
        if not quantized and scales:
            raise ValueError("scales passed to a non-quantized fold")
        if q_t.shape[1] != 1:
            raise ValueError(
                f"batched ring decode takes ONE token per row per step: "
                f"q_t has sequence length {q_t.shape[1]}")
        if kc.shape[1] % n:
            raise ValueError(
                f"cache length {kc.shape[1]} not divisible by the ring "
                f"size {n} over mesh axis {axis!r}")
        if jnp.shape(pos) != (kc.shape[0],):
            raise ValueError(
                f"pos must be one position per row, shape "
                f"({kc.shape[0]},); got {jnp.shape(pos)}")
        # reject concrete out-of-range LIVE positions, same contract as
        # the scalar path (a silently dropped append is the failure mode)
        if (not wrap and isinstance(pos, (np.ndarray, list, tuple))
                and isinstance(live, (np.ndarray, list, tuple))):
            p_arr = np.asarray(pos)
            bad = p_arr[(np.asarray(live)) & ((p_arr < 0)
                                              | (p_arr >= kc.shape[1]))]
            if bad.size:
                raise ValueError(
                    f"live pos {bad.tolist()} outside the cache "
                    f"(t_max {kc.shape[1]})")
        return mapped(kc, vc, q_t, k_t, v_t, pos, live, *scales)

    if not jit:
        return checked
    return jax.jit(checked, donate_argnums=(0, 1))


def decode_rows_read(mesh: Mesh, t_max: int, pos, live, *,
                     axis: str = meshlib.SEQ_AXIS):
    """Cache rows one token step of the contiguous batched fold
    (`make_batched_ring_decode`, wrap=False) reads from a layer's
    [B, t_max, ...] keys, over every batch row and ring device: the
    fold's own frontier and block arithmetic (int32, traced with `pos`
    and `live`), B x t_max when each shard is one block."""
    n = mesh.shape[axis]
    t_shard = t_max // n
    blk = _fold_block(t_shard, _DECODE_BLOCK)
    pos = jnp.asarray(pos, jnp.int32)
    if blk == t_shard:
        return jnp.int32(pos.shape[0] * t_max)
    frontier = _decode_frontier(jnp.clip(pos, 0, t_max - 1), live)
    trips = sum(_trips(frontier, i * t_shard, t_shard, blk)
                for i in range(n))
    return (pos.shape[0] * blk * trips).astype(jnp.int32)


def make_batched_chunk_ring_decode(mesh: Mesh, *,
                                   axis: str = meshlib.SEQ_AXIS,
                                   scale: float | None = None,
                                   jit: bool = False,
                                   quantized: bool = False):
    """Per-slot chunk fold for SPECULATIVE VERIFICATION
    (serve/engine.py): ``fn(k_cache, v_cache, q, k, v, pos, live)
    -> (out, k_cache, v_cache)`` runs C draft tokens per batch row
    against the row's ring cache in ONE dispatch, each row an
    independent sequence at its OWN position — the chunk-query algebra
    of `make_chunk_ring_decode` crossed with the per-row masking of
    `make_batched_ring_decode`.

    q/k/v are [B, C, H, D] (replicated over `axis`); `pos` is int32 [B]
    (row b's chunk occupies global positions [pos[b], pos[b] + C)) and
    `live` is bool [B]: rows with live=False append NOTHING — their
    cache shard is bit-untouched, exactly like the one-token batched
    fold's dead rows, which is what lets non-speculating slots ride
    through a verify dispatch as bit-level no-ops. Per live row:

    1. splice the chunk's K/V into the row's resident shard slots
       (positions outside [pos_b, pos_b + C), and every slot of a dead
       row, keep their stored value);
    2. attend every chunk query against the row's WHOLE updated shard
       with per-query causal visibility (cache position <= query
       position — covers the cached history AND causality inside the
       chunk, since the chunk's own K/V landed in step 1);
    3. merge across the ring with the same stable (m, l, acc) softmax
       algebra as every other fold — two collectives per CHUNK.

    A live row's per-query outputs are therefore exactly what C
    successive one-token decode folds would produce IF every query's
    preceding chunk tokens were the tokens actually decoded — which is
    precisely the speculative accept rule's job to check. Callers own
    the bound pos[b] + C <= t_max for live rows (an out-of-range splice
    slot silently drops, the same contract as the scalar fold's traced
    positions).

    With ``quantized=True`` the caches hold int8 K/V and the signature
    grows per-(row, head) float32 [B, H] dequant scales, factored out
    of the contractions exactly as in `make_batched_ring_decode`;
    appends quantize with the row's frozen insert-time scale. Defaults
    to ``jit=False`` for tracing into the engine's verify program,
    whose top-level jit owns donation."""
    n = mesh.shape[axis]

    def per_device(kc, vc, q, kt, vt, pos, live, k_scale=None,
                   v_scale=None):
        t_shard, c, d = kc.shape[1], q.shape[1], q.shape[-1]
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.asarray(live, jnp.bool_)
        # finished/riding rows may sit at pos == t_max; clamp keeps the
        # slot arithmetic in range (the splice is gated on `live`)
        posc = jnp.clip(pos, 0, n * t_shard - 1)
        g = i * t_shard + jnp.arange(t_shard, dtype=jnp.int32)

        if quantized:
            kt = jnp.clip(jnp.round(
                kt.astype(jnp.float32) / k_scale[:, None, :, None]),
                -127, 127)
            vt = jnp.clip(jnp.round(
                vt.astype(jnp.float32) / v_scale[:, None, :, None]),
                -127, 127)

        # 1. per-row splice: this shard's slots inside the row's
        # [pos_b, pos_b + C) span take the chunk row at (g - pos_b);
        # everything else — including every slot of a dead row —
        # rewrites itself with itself, bit-untouched
        take_new = ((g[None, :] >= posc[:, None])
                    & (g[None, :] < posc[:, None] + c)
                    & live[:, None])                      # [B, t_shard]
        src = jnp.clip(g[None, :] - posc[:, None], 0, c - 1)

        def splice(cache, tok):
            tail = (1,) * (cache.ndim - 2)
            gathered = jnp.take_along_axis(
                _rows(tok, cache), src.reshape(src.shape + tail),
                axis=1).astype(cache.dtype)
            return jnp.where(take_new.reshape(take_new.shape + tail),
                             gathered, cache)

        kc = splice(kc, kt)
        vc = splice(vc, vt)
        # 2. per-row, per-query local attend against the resident
        # shard: one pass over all of it (the shard is the block)
        qpos = posc[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        m_loc, l_loc, acc_loc = _attend_to_frontier(      # [B, H, C]
            q, kc, vc,
            lambda rows: (rows[None, None, :] <= qpos[:, :, None])[:, None],
            n * t_shard, t_shard, scale=scale_, row0=i * t_shard,
            k_scale=k_scale[:, :, None, None] if quantized else None)
        if quantized:
            acc_loc = acc_loc * v_scale[:, :, None, None]
        # 3. one stable softmax merge across the ring (per chunk)
        m_glob = lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = collectives.psum(l_loc * corr, axis)
        acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype), kc, vc

    bo = meshlib.batch_axes(mesh, axis)   # "model" stays weight-only
    cache_spec = P(bo, axis)      # either stored form: the rest whole
    tok_spec = P(bo, None, None, None)
    scale_specs = (P(bo, None), P(bo, None)) if quantized else ()
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(cache_spec, cache_spec, tok_spec, tok_spec, tok_spec,
                  P(), P()) + scale_specs,
        out_specs=(tok_spec, cache_spec, cache_spec),
        check_vma=False,
    )

    def checked(kc, vc, q, k, v, pos, live, *scales):
        if quantized and len(scales) != 2:
            raise ValueError("quantized fold needs (k_scale, v_scale)")
        if not quantized and scales:
            raise ValueError("scales passed to a non-quantized fold")
        if q.ndim != 4 or q.shape[1] < 1:
            raise ValueError(f"batched chunk fold expects [B, C, H, D] "
                             f"queries, got shape {jnp.shape(q)}")
        if kc.shape[1] % n:
            raise ValueError(
                f"cache length {kc.shape[1]} not divisible by the ring "
                f"size {n} over mesh axis {axis!r}")
        if jnp.shape(pos) != (kc.shape[0],):
            raise ValueError(
                f"pos must be one position per row, shape "
                f"({kc.shape[0]},); got {jnp.shape(pos)}")
        # reject concrete out-of-range LIVE chunk spans, same contract
        # as every other fold (a silent dropped splice is the failure)
        if (isinstance(pos, (np.ndarray, list, tuple))
                and isinstance(live, (np.ndarray, list, tuple))):
            p_arr = np.asarray(pos)
            bad = p_arr[(np.asarray(live))
                        & ((p_arr < 0)
                           | (p_arr + q.shape[1] > kc.shape[1]))]
            if bad.size:
                raise ValueError(
                    f"live chunk start {bad.tolist()} + chunk "
                    f"{q.shape[1]} outside the cache "
                    f"(t_max {kc.shape[1]})")
        return mapped(kc, vc, q, k, v, pos, live, *scales)

    if not jit:
        return checked
    return jax.jit(checked, donate_argnums=(0, 1))


def _paged_specs(mesh, axis, quantized):
    """shard_map specs shared by the paged folds: pools shard over the
    PHYSICAL page dim (device i owns pages [i*P/n, (i+1)*P/n)), page
    tables and dequant scales replicate — a slot's logical pages may
    land on any device, so the table must be readable everywhere and
    the per-(page, head) scales are tiny."""
    pool_spec = P(axis, None, None, None)
    rep = P()
    scale_specs = (rep, rep) if quantized else ()
    return pool_spec, rep, scale_specs


def _page_view(pool, pt, i, p_loc):
    """Gather a pool shard into each slot's LOGICAL view: pt [S, L]
    physical page ids (-1 = unallocated) -> [S, L*ps, H, D] laid out in
    logical position order, plus the [S, L] this-shard ownership mask.
    Rows gathered through a clamped foreign/unallocated id hold garbage
    the caller's visibility mask discards — exactly like the contiguous
    folds' beyond-pos cache slots."""
    local = jnp.clip(pt - i * p_loc, 0, p_loc - 1)         # [S, L]
    mine = (pt >= i * p_loc) & (pt < (i + 1) * p_loc)      # [S, L]
    view = pool[local]                                     # [S,L,ps,H,D]
    s, l, ps, h, d = view.shape
    return view.reshape(s, l * ps, h, d), mine


def make_paged_batched_ring_decode(mesh: Mesh, *, page_size: int,
                                   axis: str = meshlib.SEQ_AXIS,
                                   scale: float | None = None,
                                   jit: bool = False,
                                   quantized: bool = False):
    """Page-table-indirect variant of `make_batched_ring_decode` — the
    one-token-per-row fold of the PAGED serving engine:
    ``fn(k_pool, v_pool, page_table, q_t, k_t, v_t, pos, live)
    -> (out_t, k_pool, v_pool)``.

    The caches are a POOL of fixed-size pages `[n_pages, page_size, H,
    D]` shared by every slot (sharded over the page dim across the
    ring) plus an int32 page table `[S, L]` mapping slot b's logical
    page j to a physical page (-1 = unallocated). Per live row the fold

    1. appends the new token into the ONE physical page owning its
       position — a unique-index scatter; rows whose target page lives
       on another device (or that are dead) are dropped outright, so a
       dead row's pages are bit-untouched;
    2. gathers the row's logical view from the resident shard and runs
       the SAME per-row attend as the contiguous fold, with visibility
       = (position <= pos) AND the page is physically here — pages on
       other devices (and unallocated -1 entries) contribute nothing;
    3. merges across the ring with the identical two-collective
       (m, l, acc) softmax algebra.

    On a 1-device mesh the gathered view presents exactly the
    contiguous cache's values in the same reduction order, so a live
    row's output is BIT-IDENTICAL to the contiguous batched fold
    (gated by test); on a multi-device ring the per-device partition
    differs (pages vs position ranges), so parity is fp-close +
    argmax-equal — the same contract chunked prefill already carries.

    With ``quantized=True`` pools hold int8 pages and the signature
    grows PER-(PAGE, HEAD) float32 ``[n_pages, H]`` dequant scales
    (replicated): scores and value accumulations dequantize through a
    per-page gather of the scales (a scale varies along the position
    axis here, so it multiplies the per-page score/probability blocks
    instead of factoring fully out); appends quantize with the target
    page's existing scale. Callers own the bound pos[b] < L*page_size
    AND that the owning page is allocated for live rows — an
    unallocated append drops silently, the same traced-position
    contract as every other fold."""
    n = mesh.shape[axis]

    def per_device(kp, vp, pt, q, kt, vt, pos, live, k_scale=None,
                   v_scale=None):
        p_loc, ps, h, d = kp.shape
        s_rows, l_pages = pt.shape
        n_pages = p_loc * n
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.asarray(live, jnp.bool_)
        posc = jnp.clip(pos, 0, l_pages * ps - 1)
        lpage = posc // ps
        slot_in = posc % ps
        phys = jnp.take_along_axis(pt, lpage[:, None], axis=1)[:, 0]
        writer = (phys >= i * p_loc) & (phys < (i + 1) * p_loc) & live
        if quantized:
            ksr = k_scale[jnp.clip(phys, 0, n_pages - 1)]    # [S, H]
            vsr = v_scale[jnp.clip(phys, 0, n_pages - 1)]
            kt = jnp.clip(jnp.round(
                kt.astype(jnp.float32) / ksr[:, None, :, None]),
                -127, 127)
            vt = jnp.clip(jnp.round(
                vt.astype(jnp.float32) / vsr[:, None, :, None]),
                -127, 127)
        # append: one (page, slot) cell per live row. Non-writers are
        # redirected past the shard and DROPPED — never a masked
        # rewrite, so collisions with real writers are impossible and
        # dead rows leave the pool bit-untouched. Pages are exclusively
        # owned by one slot, hence unique indices.
        pl = jnp.where(writer, phys - i * p_loc, p_loc)
        kp = kp.at[pl, slot_in].set(kt[:, 0].astype(kp.dtype),
                                    mode="drop", unique_indices=True)
        vp = vp.at[pl, slot_in].set(vt[:, 0].astype(vp.dtype),
                                    mode="drop", unique_indices=True)
        # per-row attend over the gathered logical view — the same
        # einsums/masking/merge as the contiguous batched fold
        kv_view, mine = _page_view(kp, pt, i, p_loc)
        vv_view, _ = _page_view(vp, pt, i, p_loc)
        s = jnp.einsum("bhd,bkhd->bhk", q[:, 0], kv_view,
                       preferred_element_type=jnp.float32) * scale_
        if quantized:
            ptc = jnp.clip(pt, 0, n_pages - 1)
            ks_view = k_scale[ptc]                       # [S, L, H]
            s = (s.reshape(s_rows, h, l_pages, ps)
                 * jnp.moveaxis(ks_view, 2, 1)[..., None]
                 ).reshape(s_rows, h, l_pages * ps)
        g = (jnp.arange(l_pages, dtype=jnp.int32)[:, None] * ps
             + jnp.arange(ps, dtype=jnp.int32)[None, :]).reshape(-1)
        visible = (jnp.repeat(mine, ps, axis=1)
                   & (g[None, :] <= posc[:, None]))       # [S, L*ps]
        s = jnp.where(visible[:, None, :], s, _MASKED)
        m_loc = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(visible[:, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        if quantized:
            vs_view = v_scale[jnp.clip(pt, 0, n_pages - 1)]
            p_v = (p.reshape(s_rows, h, l_pages, ps)
                   * jnp.moveaxis(vs_view, 2, 1)[..., None]
                   ).reshape(s_rows, h, l_pages * ps)
        else:
            p_v = p
        acc_loc = jnp.einsum("bhk,bkhd->bhd", p_v, vv_view,
                             preferred_element_type=jnp.float32)
        m_glob = lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = collectives.psum(l_loc * corr, axis)
        acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]
        return out[:, None].astype(q.dtype), kp, vp

    pool_spec, rep, scale_specs = _paged_specs(mesh, axis, quantized)
    tok_spec = P(meshlib.batch_axes(mesh, axis),
                 None, None, None)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(pool_spec, pool_spec, rep, tok_spec, tok_spec,
                  tok_spec, rep, rep) + scale_specs,
        out_specs=(tok_spec, pool_spec, pool_spec),
        check_vma=False,
    )

    def checked(kp, vp, pt, q_t, k_t, v_t, pos, live, *scales):
        _check_paged_pool(kp, pt, n, page_size, quantized, scales)
        if q_t.shape[1] != 1:
            raise ValueError(
                f"paged batched decode takes ONE token per row per "
                f"step: q_t has sequence length {q_t.shape[1]}")
        if jnp.shape(pos) != (pt.shape[0],):
            raise ValueError(
                f"pos must be one position per page-table row, shape "
                f"({pt.shape[0]},); got {jnp.shape(pos)}")
        return mapped(kp, vp, pt, q_t, k_t, v_t, pos, live, *scales)

    if not jit:
        return checked
    return jax.jit(checked, donate_argnums=(0, 1))


def _check_paged_pool(kp, pt, n, page_size, quantized, scales):
    """The one pool/table contract shared by every paged fold."""
    if quantized and len(scales) != 2:
        raise ValueError("quantized paged fold needs (k_scale, v_scale)")
    if not quantized and scales:
        raise ValueError("scales passed to a non-quantized paged fold")
    if kp.shape[1] != page_size:
        raise ValueError(f"pool page dim {kp.shape[1]} != the fold's "
                         f"page_size {page_size}")
    if kp.shape[0] % n:
        raise ValueError(
            f"page pool size {kp.shape[0]} not divisible by the ring "
            f"size {n}")
    if pt.ndim != 2:
        raise ValueError(f"page table must be [S, L] int32, got shape "
                         f"{jnp.shape(pt)}")


def make_paged_chunk_ring_decode(mesh: Mesh, *, page_size: int,
                                 axis: str = meshlib.SEQ_AXIS,
                                 scale: float | None = None,
                                 jit: bool = False,
                                 quantized: bool = False):
    """Page-table-indirect variant of `make_chunk_ring_decode` — the
    chunked-prefill fold of the paged engine: ``fn(k_pool, v_pool,
    page_table, q, k, v, start, p_end) -> (out, k_pool, v_pool)``
    runs C prompt tokens against the request's OWN pages, writing
    positions [start, p_end) straight into the pool (no contiguous
    single-request cache ever exists on the paged path).

    `page_table` is the request's row(s), [B, L]; callers align chunks
    to the page grid (page_size | chunk, enforced by the engine) so a
    chunk fills whole pages and a completed chunk boundary's pages are
    NEVER written again — the invariant that lets prefix-cache
    snapshots share pages with live slots zero-copy.

    With ``quantized=True`` the signature grows [n_pages, H] per-page
    scale arrays which the fold UPDATES and returns: ``fn(..., start,
    p_end, k_scale, v_scale) -> (out, k_pool, v_pool, k_scale,
    v_scale)``. Each page this chunk fills gets a fresh per-head scale
    (absmax of its REAL tokens / 127, floor 1e-8) before its content
    quantizes with it — per-page scales are FINER than the contiguous
    engine's per-slot ones, so int8 paged output is gated on bounded
    drift + determinism rather than bit parity (docs/LONG_CONTEXT.md).
    """
    n = mesh.shape[axis]

    def per_device(kp, vp, pt, q, kt, vt, start, p_end, k_scale=None,
                   v_scale=None):
        p_loc, ps, h, d = kp.shape
        b, c = q.shape[:2]
        l_pages = pt.shape[1]
        n_pages = p_loc * n
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        start = jnp.asarray(start, jnp.int32)
        p_end = jnp.asarray(p_end, jnp.int32)
        cpos = start + jnp.arange(c, dtype=jnp.int32)       # [C]
        real = cpos < p_end                                  # [C]
        lpage = jnp.clip(cpos // ps, 0, l_pages - 1)
        phys = jnp.take_along_axis(
            pt, jnp.broadcast_to(lpage[None, :], (b, c)), axis=1)

        if quantized:
            # fresh per-(page, head) scales for the pages this chunk
            # fills: absmax over the page's REAL tokens. The update is
            # identical on every device (the chunk K/V is replicated),
            # so the replicated scale arrays stay consistent.
            cpp = c // ps                                    # chunks are
            #                       page-aligned: whole pages per chunk

            def page_scales(t):
                tf = jnp.abs(t.astype(jnp.float32))
                tf = jnp.where(real[None, :, None, None], tf, 0.0)
                m = jnp.max(tf.reshape(b, cpp, ps, h, d), axis=(0, 2, 4))
                return jnp.maximum(m, 1e-8) / 127.0          # [cpp, H]

            k_new, v_new = page_scales(kt), page_scales(vt)
            page_real = jnp.max(real.reshape(cpp, ps), axis=1)
            dst = jnp.take_along_axis(
                pt[0], jnp.clip(start // ps, 0, l_pages - 1)
                + jnp.arange(cpp, dtype=jnp.int32), axis=0)
            dst = jnp.where(page_real & (dst >= 0), dst, n_pages)
            k_scale = k_scale.at[dst].set(k_new, mode="drop",
                                          unique_indices=True)
            v_scale = v_scale.at[dst].set(v_new, mode="drop",
                                          unique_indices=True)
            ksc = jnp.repeat(k_new, ps, axis=0)              # [C, H]
            vsc = jnp.repeat(v_new, ps, axis=0)
            kt = jnp.clip(jnp.round(
                kt.astype(jnp.float32) / ksc[None, :, :, None]),
                -127, 127)
            vt = jnp.clip(jnp.round(
                vt.astype(jnp.float32) / vsc[None, :, :, None]),
                -127, 127)

        # splice: scatter each REAL chunk position into its page cell;
        # non-real / not-resident positions redirect past the shard
        # and DROP. Unique: one owner per (page, slot-in-page).
        writer = real[None, :] & (phys >= i * p_loc) & (phys
                                                        < (i + 1) * p_loc)
        pl = jnp.where(writer, phys - i * p_loc, p_loc).reshape(-1)
        sl = jnp.broadcast_to((cpos % ps)[None, :], (b, c)).reshape(-1)
        kp = kp.at[pl, sl].set(
            kt.reshape(-1, h, d).astype(kp.dtype), mode="drop",
            unique_indices=True)
        vp = vp.at[pl, sl].set(
            vt.reshape(-1, h, d).astype(vp.dtype), mode="drop",
            unique_indices=True)
        # per-query attend over the gathered logical view(s)
        out_rows = []
        for rb in range(b):          # prefill runs B=1; keep it general
            kv_view, mine = _page_view(kp, pt[rb:rb + 1], i, p_loc)
            vv_view, _ = _page_view(vp, pt[rb:rb + 1], i, p_loc)
            s = jnp.einsum("bchd,bkhd->bhck", q[rb:rb + 1], kv_view,
                           preferred_element_type=jnp.float32) * scale_
            if quantized:
                ptc = jnp.clip(pt[rb:rb + 1], 0, n_pages - 1)
                ks_view = k_scale[ptc]                    # [1, L, H]
                s = (s.reshape(1, h, c, l_pages, ps)
                     * jnp.moveaxis(ks_view, 2, 1)[:, :, None, :, None]
                     ).reshape(1, h, c, l_pages * ps)
            g = (jnp.arange(l_pages, dtype=jnp.int32)[:, None] * ps
                 + jnp.arange(ps, dtype=jnp.int32)[None, :]).reshape(-1)
            visible = (jnp.repeat(mine, ps, axis=1)[:, None, :]
                       & (g[None, None, :] <= cpos[None, :, None]))
            s = jnp.where(visible[:, None], s, _MASKED)
            m_loc = jnp.max(s, axis=-1)                   # [1, H, C]
            p = jnp.exp(s - m_loc[..., None])
            p = jnp.where(visible[:, None], p, 0.0)
            l_loc = jnp.sum(p, axis=-1)
            if quantized:
                vs_view = v_scale[jnp.clip(pt[rb:rb + 1], 0,
                                           n_pages - 1)]
                p_v = (p.reshape(1, h, c, l_pages, ps)
                       * jnp.moveaxis(vs_view, 2, 1)[:, :, None, :,
                                                     None]
                       ).reshape(1, h, c, l_pages * ps)
            else:
                p_v = p
            acc_loc = jnp.einsum("bhck,bkhd->bhcd", p_v, vv_view,
                                 preferred_element_type=jnp.float32)
            m_glob = lax.pmax(m_loc, axis)
            corr = jnp.exp(m_loc - m_glob)
            l_glob = collectives.psum(l_loc * corr, axis)
            acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
            out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]
            out_rows.append(jnp.moveaxis(out, 1, 2))
        out = jnp.concatenate(out_rows, axis=0).astype(q.dtype)
        if quantized:
            return out, kp, vp, k_scale, v_scale
        return out, kp, vp

    pool_spec, rep, scale_specs = _paged_specs(mesh, axis, quantized)
    tok_spec = P(meshlib.batch_axes(mesh, axis),
                 None, None, None)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(pool_spec, pool_spec, rep, tok_spec, tok_spec,
                  tok_spec, rep, rep) + scale_specs,
        out_specs=((tok_spec, pool_spec, pool_spec) + scale_specs),
        check_vma=False,
    )

    def checked(kp, vp, pt, q, k, v, start, p_end, *scales):
        _check_paged_pool(kp, pt, n, page_size, quantized, scales)
        if q.ndim != 4 or q.shape[1] < 1:
            raise ValueError(f"paged chunk fold expects [B, C, H, D] "
                             f"queries, got shape {jnp.shape(q)}")
        if q.shape[1] % page_size:
            raise ValueError(
                f"chunk {q.shape[1]} must be a multiple of the page "
                f"size {page_size} — chunk boundaries must land on the "
                f"page grid so completed pages are never rewritten")
        return mapped(kp, vp, pt, q, k, v, start, p_end, *scales)

    if not jit:
        return checked
    return jax.jit(checked, donate_argnums=(0, 1))


def make_paged_batched_chunk_ring_decode(mesh: Mesh, *, page_size: int,
                                         axis: str = meshlib.SEQ_AXIS,
                                         scale: float | None = None,
                                         jit: bool = False,
                                         quantized: bool = False):
    """Page-table-indirect variant of `make_batched_chunk_ring_decode`
    — the SPECULATIVE-VERIFY fold of the paged engine: ``fn(k_pool,
    v_pool, page_table, q, k, v, pos, live) -> (out, k_pool,
    v_pool)`` runs C draft tokens per slot against the slot's pages,
    each row at its OWN position; rows with live=False append nothing
    and their pages are bit-untouched. Callers (the engine's room
    check) own the bound that live rows' pages cover [pos_b, pos_b+C).
    With ``quantized=True`` appends quantize with the target pages'
    EXISTING scales (decode-region pages are stamped at grant time)
    and the signature grows the two replicated [n_pages, H] scale
    reads — scales are NOT updated here."""
    n = mesh.shape[axis]

    def per_device(kp, vp, pt, q, kt, vt, pos, live, k_scale=None,
                   v_scale=None):
        p_loc, ps, h, d = kp.shape
        s_rows, c = q.shape[:2]
        l_pages = pt.shape[1]
        n_pages = p_loc * n
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.asarray(live, jnp.bool_)
        posc = jnp.clip(pos, 0, l_pages * ps - 1)
        qpos = jnp.clip(posc[:, None]
                        + jnp.arange(c, dtype=jnp.int32)[None, :],
                        0, l_pages * ps - 1)               # [S, C]
        lpage = qpos // ps
        phys = jnp.take_along_axis(pt, lpage, axis=1)      # [S, C]
        if quantized:
            ksr = k_scale[jnp.clip(phys, 0, n_pages - 1)]  # [S, C, H]
            vsr = v_scale[jnp.clip(phys, 0, n_pages - 1)]
            kt = jnp.clip(jnp.round(
                kt.astype(jnp.float32) / ksr[..., None]), -127, 127)
            vt = jnp.clip(jnp.round(
                vt.astype(jnp.float32) / vsr[..., None]), -127, 127)
        writer = (live[:, None] & (phys >= i * p_loc)
                  & (phys < (i + 1) * p_loc))
        pl = jnp.where(writer, phys - i * p_loc, p_loc).reshape(-1)
        sl = (qpos % ps).reshape(-1)
        kp = kp.at[pl, sl].set(
            kt.reshape(-1, h, d).astype(kp.dtype), mode="drop",
            unique_indices=True)
        vp = vp.at[pl, sl].set(
            vt.reshape(-1, h, d).astype(vp.dtype), mode="drop",
            unique_indices=True)
        kv_view, mine = _page_view(kp, pt, i, p_loc)
        vv_view, _ = _page_view(vp, pt, i, p_loc)
        s = jnp.einsum("bchd,bkhd->bhck", q, kv_view,
                       preferred_element_type=jnp.float32) * scale_
        if quantized:
            ptc = jnp.clip(pt, 0, n_pages - 1)
            ks_view = k_scale[ptc]                         # [S, L, H]
            s = (s.reshape(s_rows, h, c, l_pages, ps)
                 * jnp.moveaxis(ks_view, 2, 1)[:, :, None, :, None]
                 ).reshape(s_rows, h, c, l_pages * ps)
        g = (jnp.arange(l_pages, dtype=jnp.int32)[:, None] * ps
             + jnp.arange(ps, dtype=jnp.int32)[None, :]).reshape(-1)
        visible = (jnp.repeat(mine, ps, axis=1)[:, None, :]
                   & (g[None, None, :] <= qpos[:, :, None]))
        s = jnp.where(visible[:, None], s, _MASKED)
        m_loc = jnp.max(s, axis=-1)                        # [S, H, C]
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(visible[:, None], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        if quantized:
            vs_view = v_scale[jnp.clip(pt, 0, n_pages - 1)]
            p_v = (p.reshape(s_rows, h, c, l_pages, ps)
                   * jnp.moveaxis(vs_view, 2, 1)[:, :, None, :, None]
                   ).reshape(s_rows, h, c, l_pages * ps)
        else:
            p_v = p
        acc_loc = jnp.einsum("bhck,bkhd->bhcd", p_v, vv_view,
                             preferred_element_type=jnp.float32)
        m_glob = lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = collectives.psum(l_loc * corr, axis)
        acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype), kp, vp

    pool_spec, rep, scale_specs = _paged_specs(mesh, axis, quantized)
    tok_spec = P(meshlib.batch_axes(mesh, axis),
                 None, None, None)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(pool_spec, pool_spec, rep, tok_spec, tok_spec,
                  tok_spec, rep, rep) + scale_specs,
        out_specs=(tok_spec, pool_spec, pool_spec),
        check_vma=False,
    )

    def checked(kp, vp, pt, q, k, v, pos, live, *scales):
        _check_paged_pool(kp, pt, n, page_size, quantized, scales)
        if q.ndim != 4 or q.shape[1] < 1:
            raise ValueError(f"paged batched chunk fold expects "
                             f"[S, C, H, D] queries, got shape "
                             f"{jnp.shape(q)}")
        if jnp.shape(pos) != (pt.shape[0],):
            raise ValueError(
                f"pos must be one position per page-table row, shape "
                f"({pt.shape[0]},); got {jnp.shape(pos)}")
        return mapped(kp, vp, pt, q, k, v, pos, live, *scales)

    if not jit:
        return checked
    return jax.jit(checked, donate_argnums=(0, 1))


def make_chunk_ring_decode(mesh: Mesh, *, axis: str = meshlib.SEQ_AXIS,
                           scale: float | None = None,
                           jit: bool = False, wrap: bool = False):
    """Chunked-prefill fold (Sarathi-style): ``fn(k_cache, v_cache, q, k,
    v, start, p_end) -> (out, k_cache, v_cache)`` runs C prompt tokens
    at once against an EXISTING ring cache — the middle ground between
    the one-token decode fold and the whole-prompt training ring.

    q/k/v are the chunk's projections, [B, C, H, D] (replicated over
    `axis`); the chunk occupies global positions [start, start + C) and
    only positions < `p_end` are REAL (both int32 scalars, traced — so
    one compiled program serves every chunk of a prompt AND the ragged
    final chunk). The fold:

    1. appends the chunk's real K/V into the cache — each device
       rewrites its resident shard through a gather + where (positions
       outside [start, p_end) keep their stored value). This is
       O(t_shard) traffic per chunk rather than the decode fold's O(1)
       per token, but it runs once per C tokens and XLA keeps the
       rewrite in place under donation;
    2. attends every chunk query against the updated cache UP TO THE
       CHUNK'S END (start + C: no query sees beyond it), read in blocks
       (`_attend_to_frontier`), with a per-query causal visibility mask
       (cache position <= query position — which covers both the
       already-cached prefix and causality INSIDE the chunk, since the
       chunk's own K/V are in the cache by step 1). A shard that fits
       one block is one pass over all of it, as before blocks existed;
       a longer one equals that pass up to the float32 rounding of the
       block merge;
    3. merges across the ring with the same stable (m, l, acc) softmax
       algebra as the decode folds — two collectives per CHUNK instead
       of per token.

    Query rows at positions >= p_end (the ragged tail's padding) append
    nothing and their outputs are garbage the caller discards; they
    cannot NaN (their visibility set is non-empty). Requires
    start + C <= t_max (the caller sizes chunks so a chunk never hangs
    past the cache). Defaults to ``jit=False`` for tracing into the
    chunk-prefill program (models/lm.py), whose top-level jit owns
    donation.

    Grouped queries behave as in `make_ring_decode`. With ``wrap=True``
    the cache is a window layer's ring of W rows (position p at row
    p mod W) and a query at position p sees the W positions
    (p - W, p]: the chunk attends over the ring AS IT STOOD before the
    chunk (its rows hold the latest positions below `start`) joined
    with the chunk's own keys, and only then do the chunk's last W real
    positions overwrite their rows — a chunk of W or more tokens
    replaces the whole ring. `start` may lie anywhere below t_max; the
    ring's length bounds nothing."""
    n = mesh.shape[axis]
    _check_wrap(wrap, n, axis)

    def per_device_wrap(kc, vc, q, kt, vt, start, p_end):
        w = kc.shape[1]
        c, d = q.shape[1], q.shape[-1]
        scale_ = scale if scale is not None else d ** -0.5
        start = jnp.asarray(start, jnp.int32)
        p_end = jnp.asarray(p_end, jnp.int32)
        j = jnp.arange(w, dtype=jnp.int32)
        ci = jnp.arange(c, dtype=jnp.int32)
        # row j holds the latest position below `start` that is
        # congruent to j mod W — or nothing yet (a negative position)
        held = (start - 1) - ((start - 1 - j) % w)              # [W]
        qpos = start + ci                                       # [C]
        see_ring = ((held >= 0)[None, :]
                    & (held[None, :] > qpos[:, None] - w))      # [C, W]
        see_own = ((ci[None, :] <= ci[:, None])
                   & (ci[None, :] > ci[:, None] - w))           # [C, C]
        visible = jnp.concatenate([see_ring, see_own], axis=1)
        k_all = jnp.concatenate([kc, _rows(kt, kc).astype(kc.dtype)],
                                axis=1)
        v_all = jnp.concatenate([vc, _rows(vt, vc).astype(vc.dtype)],
                                axis=1)
        s = _scores(q, k_all) * scale_
        s = jnp.where(visible[None, None], s, _MASKED)
        m = jnp.max(s, axis=-1)
        p = jnp.where(visible[None, None], jnp.exp(s - m[..., None]), 0.0)
        out = (_weighted(p, v_all, d)
               / jnp.maximum(jnp.sum(p, axis=-1), 1e-37)[..., None])
        # the write comes last: row j takes the latest REAL position of
        # the chunk congruent to j, when the chunk has one
        latest = (p_end - 1) - ((p_end - 1 - j) % w)            # [W]
        take_new = latest >= start
        src = jnp.clip(latest - start, 0, c - 1)

        return (jnp.moveaxis(out, 1, 2).astype(q.dtype),
                _splice_rows(kc, kt, src, take_new),
                _splice_rows(vc, vt, src, take_new))

    def per_device(kc, vc, q, kt, vt, start, p_end):
        if wrap:
            return per_device_wrap(kc, vc, q, kt, vt, start, p_end)
        t_shard, c, d = kc.shape[1], q.shape[1], q.shape[-1]
        i = collectives.axis_index(axis)
        scale_ = scale if scale is not None else d ** -0.5
        start = jnp.asarray(start, jnp.int32)
        p_end = jnp.asarray(p_end, jnp.int32)
        g = i * t_shard + jnp.arange(t_shard, dtype=jnp.int32)  # [t_shard]
        # 1. append: this shard's slots that fall inside [start, p_end)
        # take the chunk row at (g - start); everything else keeps its
        # stored value. A shard fully outside the chunk's span rewrites
        # itself with itself — bit-untouched.
        take_new = (g >= start) & (g < p_end)                 # [t_shard]
        src = jnp.clip(g - start, 0, c - 1)                   # [t_shard]

        kc = _splice_rows(kc, kt, src, take_new)
        vc = _splice_rows(vc, vt, src, take_new)
        # 2. per-query local attend against the resident shard, in
        # blocks up to the chunk's last query: nothing beyond it is
        # visible to any of them
        qpos = start + jnp.arange(c, dtype=jnp.int32)         # [C]
        m_loc, l_loc, acc_loc = _attend_to_frontier(          # [B, H, C]
            q, kc, vc,
            lambda rows: (rows[None, :] <= qpos[:, None])[None, None],
            start + c, _fold_block(t_shard, _CHUNK_BLOCK),
            scale=scale_, row0=i * t_shard)
        # 3. one stable softmax merge across the ring (per chunk, not
        # per token)
        m_glob = lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = collectives.psum(l_loc * corr, axis)
        acc_glob = collectives.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]  # [B,H,C,D]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype), kc, vc  # [B,C,H,D]

    bo = meshlib.batch_axes(mesh, axis)   # "model" stays weight-only
    cache_spec = P(bo, axis)      # either stored form: the rest whole
    tok_spec = P(bo, None, None, None)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(cache_spec, cache_spec, tok_spec, tok_spec, tok_spec,
                  P(), P()),
        out_specs=(tok_spec, cache_spec, cache_spec),
        check_vma=False,
    )

    def checked(kc, vc, q, k, v, start, p_end):
        if q.ndim != 4 or q.shape[1] < 1:
            raise ValueError(f"chunk fold expects [B, C, H, D] queries, "
                             f"got shape {jnp.shape(q)}")
        if kc.shape[1] % n:
            raise ValueError(
                f"cache length {kc.shape[1]} not divisible by the ring "
                f"size {n} over mesh axis {axis!r}")
        # concrete out-of-range starts are caller bugs, same contract as
        # the scalar fold (a chunk hanging past t_max would silently
        # drop its tail's append)
        if not wrap and isinstance(start, (int, np.integer)):
            if not 0 <= int(start) <= kc.shape[1] - q.shape[1]:
                raise ValueError(
                    f"chunk start {int(start)} + chunk {q.shape[1]} "
                    f"outside the cache (t_max {kc.shape[1]})")
        return mapped(kc, vc, q, k, v, start, p_end)

    if not jit:
        return checked
    return jax.jit(checked, donate_argnums=(0, 1))


# -- learned sparse attention: an indexer picks the positions a query
# attends, from index keys cached beside K/V --------------------------

# rows of index keys one block of the indexer's scores reads
_INDEX_BLOCK = 2048

# live rows the sparse decode fold selects, gathers and attends for in
# one trip. On a v5e `lax.top_k` over 32k positions costs the same for
# 4, 8 and 16 rows and the gather of 4 rows what 8 cost, so trips of 4
# made a full batch a quarter slower than one pass over all its rows;
# two trips of 8 cost what that pass did (PERF.md section 6, PR 35)
_FOLD_GROUP = 8


def _one_device(mesh: Mesh, axis: str) -> None:
    if mesh.shape[axis] > 1:
        raise ValueError(
            f"the sparse-attention folds select over a slot's whole "
            f"cache on one device; the sequence ring on mesh axis "
            f"{axis!r} has {mesh.shape[axis]}")


def index_cache_shape(batch: int, t_max: int, dim: int) -> tuple:
    """The declared shape of a layer's index-key cache, one key of `dim`
    a position: `[batch, dim, t_max]`, the POSITIONS in the lanes. An
    index key is narrower than a tile's 128 lanes (64 in the served
    model) and there is one a position, so `cache_shape`'s merged rows
    do not exist for it: stored `[batch, t_max, dim]` the compiler keeps
    it transposed at every program's edge and re-lays all of it, twice a
    window and once a token step (read off the window program compiled
    for a v5e, PERF.md section 6, PR 34). With the positions last the
    score product reads it as it rests, a block of positions is a block
    of lanes, and one token's key is a column."""
    return (batch, dim, t_max)


def _take_index(ic, at, n: int, row=None):
    """`n` positions of an index cache from `at`: of every batch row, or
    (with `row`, traced) of that one alone, [1, DI, n]."""
    if row is None:
        return lax.dynamic_slice_in_dim(ic, at, n, axis=2)
    return lax.dynamic_slice(ic, (row, 0, at), (1, ic.shape[1], n))


def _append_index(ic, kit, slot, mine):
    """Append of one token's index key to every row of an index cache
    [B, DI, T]: row b takes kit[b] ([B, 1, 1, DI]) as the COLUMN at
    position `slot[b]` where `mine[b]`, else keeps what it holds. One
    small read, select and `dynamic_update_slice` a row, in a loop over
    the rows: as ONE scatter (`_append_rows`' way) the column is the
    scatter's window, the compiler wants the window in the lanes, and
    it re-lays the whole cache to `[B, T, DI]` and back on every token
    step (read off the compiled window, PERF.md section 6, PR 34). A
    column is 64 values; the loop's 16 trips a layer cost less than one
    such copy."""
    cols = jnp.swapaxes(kit[:, 0], 1, 2).astype(ic.dtype)       # [B, DI, 1]

    def row(b, ic):
        at = (b, 0, slot[b])
        new = lax.dynamic_slice_in_dim(cols, b, 1, axis=0)
        old = lax.dynamic_slice(ic, at, new.shape)
        return lax.dynamic_update_slice(ic, jnp.where(mine[b], new, old), at)

    return lax.fori_loop(0, ic.shape[0], row, ic)


def _index_scores(qi, w, ib):
    """The indexer's scores of a block of positions, float32: index
    queries qi [B, J, DI] or [B, C, J, DI], heads' weights w [B, (C,) J]
    float32 and index keys ib [B, DI, K] -> ``sum_j w_j relu(qi_j . k)``
    [B, (C,) K]. The weighted sum over the heads is taken elementwise:
    as a product on the matrix unit it would round the scores to
    bfloat16."""
    eq = "bjd,bdk->bjk" if qi.ndim == 3 else "bcjd,bdk->bcjk"
    s = jnp.einsum(eq, qi, ib, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=-2)


def _scores_to_frontier(qi, w, ic, see, frontier, blk: int, row=None):
    """Index scores [B, (C,) T] float32 of every position of the index
    cache ic [B, DI, T], read in blocks of `blk` positions up to `frontier`
    (traced): -inf where `see(g)` (g [blk] global rows, broadcastable to
    the block's scores) is False and beyond the last block read. With
    `row` only that batch row of the cache is read (qi, w are one
    request's). A cache of one block is one pass, whatever the
    frontier."""
    t = ic.shape[2]

    def one(ib, g):
        return jnp.where(see(g), _index_scores(qi, w, ib), -jnp.inf)

    rows = jnp.arange(blk, dtype=jnp.int32)
    if t <= blk:
        return one(_take_index(ic, 0, t, row) if row is not None else ic,
                   rows)

    def body(j, buf):
        sc = one(_take_index(ic, j * blk, blk, row), j * blk + rows)
        return lax.dynamic_update_slice_in_dim(buf, sc, j * blk, axis=-1)

    return lax.fori_loop(
        0, _trips(frontier, 0, t, blk), body,
        jnp.full(w.shape[:-1] + (t,), -jnp.inf, jnp.float32))


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 0, u | jnp.uint32(0x80000000), ~u)


def _bisect(count, want, bits: int):
    """Per row, the largest v < 2**bits (uint32) with ``count(v) >=
    want`` where `count(v)` [N] falls as v grows and ``count(0) >= want``:
    found bit by bit, `bits` counting passes and no sort."""
    def bit(i, cur):
        cand = cur | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(count(cand) >= want, cand, cur)

    return lax.fori_loop(0, bits, bit, jnp.zeros(want.shape, jnp.uint32))


def _select_topk(score, k: int, frontier, blk: int):
    """[N, T] bool: per row of score [N, T] float32 (-inf where a
    position is not visible, and everywhere at or beyond `frontier`)
    the k best-scoring visible positions, all of them where there are no
    more than k; equal scores go to the lower position, as `lax.top_k`
    breaks them. No sort: the k-th best score is found bit by bit over
    the scores' sortable keys (32 counting passes over the blocks below
    `frontier`), and where several positions tie at it, the position
    of the last one that still fits likewise (log2 T passes)."""
    n, t = score.shape
    keys = _sortable(score)
    pos = jnp.arange(t, dtype=jnp.uint32)

    def count(test):
        """Per row, how many columns below the frontier pass `test(keys
        block, positions of the block)`."""
        if t <= blk:
            return jnp.sum(test(keys, pos), axis=1, dtype=jnp.int32)

        def body(j, acc):
            kb = lax.dynamic_slice_in_dim(keys, j * blk, blk, axis=1)
            pb = lax.dynamic_slice_in_dim(pos, j * blk, blk)
            return acc + jnp.sum(test(kb, pb), axis=1, dtype=jnp.int32)

        return lax.fori_loop(0, _trips(frontier, 0, t, blk), body,
                             jnp.zeros(n, jnp.int32))

    want = jnp.full(n, k, jnp.int32)
    kth = _bisect(lambda v: count(lambda kb, _: kb >= v[:, None]), want, 32)
    # of the positions that tie at the k-th best score, the lowest
    # `room` join those that score more: all below the largest position
    # v that has fewer than `room` ties below it, and v itself
    room = k - count(lambda kb, _: kb > kth[:, None])
    last = _bisect(
        lambda v: -count(lambda kb, pb: (kb == kth[:, None])
                         & (pb[None, :] < v[:, None])),
        1 - room, max(1, (t - 1).bit_length()))
    return (((keys > kth[:, None])
             | ((keys == kth[:, None]) & (pos[None, :] <= last[:, None])))
            & (score > -jnp.inf))


def pack_bits(mask):
    """[.., T] bool -> [.., ceil(T / 32)] uint32: bit b of word m is
    position 32 m + b."""
    t = mask.shape[-1]
    mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, -t % 32)])
    bits = mask.reshape(*mask.shape[:-1], -1, 32).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def make_sparse_decode(mesh: Mesh, *, topk: int,
                       axis: str = meshlib.SEQ_AXIS,
                       scale: float | None = None):
    """Per-slot decode fold of a layer whose indexer picks the positions
    a query attends: ``fn(k_cache, v_cache, i_cache, q_t, k_t, v_t,
    index, pos, live) -> (out_t, k_cache, v_cache, i_cache, account)``.

    Beside K/V the layer caches ONE index key a position (`i_cache`, of
    `index_cache_shape(B, T, DI)`). `index = (qi [B, 1, J, DI], ki
    [B, 1, 1, DI], w [B, 1, J] float32)` are the new token's index
    queries, index key and head weights. Per row, as in
    `make_batched_ring_decode` (own position `pos[b]`, dead rows append
    nothing):

    1. append the token's key, value and index key at its position;
    2. score every visible position ``sum_j w_j relu(qi_j . ki[s])``,
       reading the INDEX cache in blocks up to the furthest live
       position (named scope `dsa_index`);
    3. take the `topk` best (`dsa_select`; all visible ones while there
       are no more than that);
    4. gather those rows of K and V, and attend over them alone
       (`attn_sparse`).

    Steps 3 and 4 cost by the row (a sort of the whole length, 2 x
    `topk` gathered rows), so they run for the LIVE rows alone: their
    ids in slot order, `_FOLD_GROUP` at a time, one trip of one loop
    body a group (none when no row is live). A dead row is never sorted
    for, gathered for or attended for: its `out_t` is zero.

    K and V are read at the selected rows only: of a 32k-token slot
    whose layer caches 2 KiB a position, 4 MiB of index keys and 4 MiB
    of selected rows instead of 64 MiB. `account` holds ``selected``
    [B, topk] int32 (the positions, -1 where a row sees fewer and in
    every dead row), ``sel_share`` [B] float32 (selected over visible,
    0 for a dead row), ``sel_rows`` [B] int32 (1 for a live row) and
    ``fold_rows`` int32 (the rows steps 3 and 4 ran for: the trips
    times the group). One device only."""
    _one_device(mesh, axis)

    def fn(kc, vc, ic, q, kt, vt, index, pos, live):
        qi, kit, w = index
        b, t, d = kc.shape[0], kc.shape[1], q.shape[-1]
        k, grp = min(topk, t), min(_FOLD_GROUP, b)
        scale_ = scale if scale is not None else d ** -0.5
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.asarray(live, jnp.bool_)
        posc = jnp.clip(pos, 0, t - 1)
        with jax.named_scope("dsa_index"):
            ic = _append_index(ic, kit, posc, live)
            score = _scores_to_frontier(
                qi[:, 0], w[:, 0], ic,
                lambda g: g[None, :] <= posc[:, None],
                _decode_frontier(posc, live), _fold_block(t, _INDEX_BLOCK))
        with jax.named_scope("attn_sparse"):
            kc = _append_rows(kc, _rows(kt, kc), posc, live)
            vc = _append_rows(vc, _rows(vt, vc), posc, live)
        # the live rows' ids first, in slot order; behind the last live
        # one a group is filled up with the index one past the batch,
        # which reads the last row and writes nothing
        n = jnp.sum(live, dtype=jnp.int32)
        order = jnp.pad(
            jnp.where(jnp.arange(b) < n, jnp.argsort(~live, stable=True), b),
            (0, -b % grp), constant_values=b).astype(jnp.int32)

        def group(j, carry):
            out, selected = carry
            ids = lax.dynamic_slice_in_dim(order, j * grp, grp)
            at = jnp.minimum(ids, b - 1)
            with jax.named_scope("dsa_select"):
                top, idx = lax.top_k(score[at], k)
                valid = top > -jnp.inf
            with jax.named_scope("attn_sparse"):
                s = _scores(q[at, 0], kc[at[:, None], idx]) * scale_
                s = jnp.where(valid[:, None, :], s, _MASKED)
                p = jnp.where(
                    valid[:, None, :],
                    jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
                o = (_weighted(p, vc[at[:, None], idx], d)
                     / jnp.maximum(jnp.sum(p, axis=-1), 1e-37)[..., None])
                out = out.at[ids].set(o.astype(out.dtype), mode="drop")
            return out, selected.at[ids].set(
                jnp.where(valid, idx, -1).astype(jnp.int32), mode="drop")

        trips = (n + grp - 1) // grp
        out, selected = lax.fori_loop(
            0, trips, group,
            (jnp.zeros(q.shape[:1] + q.shape[2:], q.dtype),
             jnp.full((b, k), -1, jnp.int32)))
        account = {
            "selected": selected,
            "sel_share": jnp.where(
                live, jnp.sum(selected >= 0, axis=-1) / (posc + 1.0), 0.0
            ).astype(jnp.float32),
            "sel_rows": live.astype(jnp.int32),
            "fold_rows": trips * grp}
        return out[:, None], kc, vc, ic, account

    return fn


def make_sparse_chunk_decode(mesh: Mesh, *, topk: int,
                             axis: str = meshlib.SEQ_AXIS,
                             scale: float | None = None):
    """Prefill-chunk fold of ONE request into row `row` of a BATCH's
    caches, in place: ``fn(k_cache, v_cache, i_cache, q, k, v, index,
    start, row) -> (out, k_cache, v_cache, i_cache, account)`` with
    q/k/v [1, C, H, D] the chunk's projections at positions
    [start, start + C) and `index = (qi [1, C, J, DI], ki [1, C, 1, DI],
    w [1, C, J])`.

    The chunk's C keys, values and index keys are written over the
    row's positions [start, start + C) (one `dynamic_update_slice`
    each: no other row and no other position is touched, and nothing is
    read back); a ragged last chunk writes its padding too, which lies
    beyond the request's frontier, where no fold reads before a decode
    step has written. Each of the C queries then scores the row's index
    keys up to the chunk's end (`dsa_index`), marks its own `topk` best
    (`dsa_select`: counting passes, no sort; `_select_topk`) and attends
    the positions marked, by a mask over block-wise
    dense scores (`attn_sparse`): the same function as gathering them,
    at a chunk's arithmetic intensity. ``account["selected_bits"]``
    [C, ceil(T / 32)] uint32 holds each query's choice (`pack_bits`).
    Requires start + C <= T. One device only."""
    _one_device(mesh, axis)

    def fn(kc, vc, ic, q, kt, vt, index, start, row):
        t, c, d = kc.shape[1], q.shape[1], q.shape[-1]
        scale_ = scale if scale is not None else d ** -0.5
        start = jnp.asarray(start, jnp.int32)
        row = jnp.asarray(row, jnp.int32)

        def write(cache, new):
            return lax.dynamic_update_slice(
                cache, _rows(new, cache).astype(cache.dtype),
                (row, start) + (0,) * (cache.ndim - 2))

        qpos = start + jnp.arange(c, dtype=jnp.int32)
        causal = lambda g: g[None, :] <= qpos[:, None]          # [C, blk]
        qi, kit, w = index
        blk = _fold_block(t, _INDEX_BLOCK)
        with jax.named_scope("dsa_index"):
            ic = lax.dynamic_update_slice(
                ic, jnp.swapaxes(kit[:, :, 0], 1, 2).astype(ic.dtype),
                (row, 0, start))
            score = _scores_to_frontier(
                qi, w, ic, lambda g: causal(g)[None], start + c, blk,
                row=row)[0]                                      # [C, T]
        with jax.named_scope("dsa_select"):
            chosen = _select_topk(score, min(topk, t), start + c, blk)
            account = {"selected_bits": pack_bits(chosen)}
        see = lambda g: lax.dynamic_slice_in_dim(
            chosen, g[0], g.shape[0], axis=1)[None, None]
        with jax.named_scope("attn_sparse"):
            kc, vc = write(kc, kt), write(vc, vt)
            _, l, acc = _attend_to_frontier(
                q, kc, vc, see, start + c, _fold_block(t, _CHUNK_BLOCK),
                scale=scale_, row=row)
            out = acc / jnp.maximum(l, 1e-37)[..., None]        # [1,H,C,D]
        return (jnp.moveaxis(out, 1, 2).astype(q.dtype), kc, vc, ic,
                account)

    return fn


def prefill(mesh: Mesh, k_prompt, v_prompt, t_max: int, *,
            axis: str = meshlib.SEQ_AXIS, dtype=jnp.bfloat16):
    """Place a prompt's [B, P, H, D] K/V directly into a fresh ring
    cache of `cache_shape(B, t_max, H, D)` (pad to t_max, shard) —
    bit-identical to decoding the prompt
    token by token (pinned by test), without the O(P) python loop.
    Returns (k_cache, v_cache); attention outputs for the prompt itself
    come from the training ring (`make_ring_attention`), which shares
    this sharding."""
    p_len = k_prompt.shape[1]
    if p_len > t_max:
        raise ValueError(f"prompt length {p_len} exceeds t_max {t_max}")
    sh = cache_sharding(mesh, axis)
    n = mesh.shape[axis]
    if t_max % n:
        raise ValueError(f"t_max {t_max} not divisible by the ring size "
                         f"{n} over mesh axis {axis!r}")
    kc = as_cache(k_prompt, t_max, dtype)
    vc = as_cache(v_prompt, t_max, dtype)
    return (meshlib.put_with_sharding(kc, sh),
            meshlib.put_with_sharding(vc, sh))
