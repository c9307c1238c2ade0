"""Pallas TPU kernel: each row's k best positions of a score buffer, as
packed bits or as the positions themselves, every counting pass over
the row's keys in VMEM.

A prefill chunk of a layer with an indexer (`ring_decode.
make_sparse_chunk_decode`) scores each of its C queries against every
position up to the chunk's end, [C, T] float32, and each query then
attends its `k` best. Sorting 32,768 positions per query is what
`lax.top_k` would do; the k-th best score can instead be found bit by
bit, 32 counting passes over the scores' sortable keys, and where
several positions tie at it, the position of the last one that still
fits likewise (log2 T passes more). As XLA operations each pass re-read
its [C, 2,048] blocks from HBM: 48 passes of up to 16 blocks a layer and
chunk, all reading the same bytes (`PERF.md` section 6).

Here one grid cell takes `rows` queries. Their scores below the
frontier (a scalar prefetched into SMEM) are copied from HBM once, a
group of 4,096 positions a DMA, and turned into int32 keys whose signed
order is the floats' total order (-0.0 below +0.0) in VMEM as each
group lands; every pass then runs over that copy, `rows x 128` keys a
column, `_SPAN` keys a loop trip. The tie passes run only where some
row of the cell has more positions tied at its k-th best key than it
has room for; elsewhere every tied position is taken, which is what
they would find. The cell writes its rows' choice as packed bits (bit b
of word m is position 32 m + b) and never a [C, T] mask: a group's 0/1
choice, in bfloat16, times a constant [4,096, 256] matrix of powers of
two on the matrix unit gives each of its 128 words in two exact halves
of 16 bits. On a v5e the probe (`experiments/dsa_probe.py select`)
chose 64 rows a cell and 32k keys a trip (`PERF.md` section 6).

A decode step (`ring_decode.make_sparse_decode`) selects for a trip of
8 live rows and gathers K/V rows by position, so it needs the positions,
not a mask: `select_topk_positions` runs the same kernel (one cell of 8
rows, traced as `decode_topk` beside the chunk's `chunk_topk`) and
compacts its [8, T / 32] words into [8, k] ascending positions in XLA
(`_positions`): which word holds a row's j-th position is a count of
word ends at or below j, taken on the matrix unit; the word and the
count before it are picked by a one-hot product, and the bit by five
halvings: no sort, no XLA gather and no [8, T] mask. On a v5e the trip
costs 25-38 us at frontiers of 2k-28k, the compaction 13 of them, where
`lax.top_k` sorted all 32,768 positions in 200 (`PERF.md` section 6).

Testing contract (`ops/fused_conv.py`'s): `interpret=None` runs the
same kernel body under the Pallas interpreter off the TPU and through
Mosaic on it (`mesh.pallas_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from idc_models_tpu import mesh as meshlib

_LANES = 128
# positions whose words fill one lane row of the output: 32 columns
_GROUP = 32 * _LANES
# keys one trip of a counting pass reads, whatever the rows
_SPAN = 32 * 1024
# query rows a grid cell takes (a power of two of at least 8)
ROWS = 64

_INT_MIN = -(1 << 31)
_INT_MAX = (1 << 31) - 1
_NEG_INF_KEY = -2139095041          # the key of -inf: no position below it


def _keys(x):
    """float32 -> the int32 key whose signed order is the floats' total
    order (-0.0 below +0.0), held in float32's bits."""
    i = lax.bitcast_convert_type(x, jnp.int32)
    return lax.bitcast_convert_type(jnp.where(i < 0, i ^ _INT_MAX, i),
                                    jnp.float32)


def _pack_matrix():
    """[GROUP, 2 x 128] bfloat16: a group's 0/1 choice times it gives,
    exactly, the low and the high 16 bits of each of its 128 words (bit
    b of word m is position 32 m + b)."""
    p = lax.broadcasted_iota(jnp.int32, (_GROUP, 2 * _LANES), 0)
    j = lax.broadcasted_iota(jnp.int32, (_GROUP, 2 * _LANES), 1)
    mine = (p // 32 == j % _LANES) & ((p // 16) % 2 == j // _LANES)
    return jnp.where(mine, lax.shift_left(1, p % 16), 0).astype(
        jnp.float32).astype(jnp.bfloat16)


def _kernel(front_ref, score_hbm, out_ref, keys_ref, pack_ref, sems, *, k,
            slices, pos_bits):
    rows = keys_ref.shape[0]
    front = front_ref[0]
    width = slices * _LANES
    trips = (front + width - 1) // width
    groups = (front + _GROUP - 1) // _GROUP
    row0 = pl.program_id(0) * rows
    col = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)

    def keys(at, n):
        return lax.bitcast_convert_type(keys_ref[:, pl.ds(at, n)], jnp.int32)

    # the rows' scores below the frontier, one copy a group of positions,
    # turned into keys in place as each lands
    def copy(g):
        at = pl.multiple_of(g * _GROUP, _GROUP)
        return pltpu.make_async_copy(
            score_hbm.at[pl.ds(row0, rows), pl.ds(at, _GROUP)],
            keys_ref.at[:, pl.ds(at, _GROUP)], sems.at[g])

    def start(g, carry):
        copy(g).start()
        return carry

    def land(g, carry):
        copy(g).wait()
        at = pl.multiple_of(g * _GROUP, _GROUP)
        keys_ref[:, pl.ds(at, _GROUP)] = _keys(keys_ref[:, pl.ds(at, _GROUP)])
        return carry

    lax.fori_loop(0, groups, start, 0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        pack_ref[...] = _pack_matrix()

    lax.fori_loop(0, groups, land, 0)

    def count(test):
        """[rows, 1]: how many keys below the last trip's end pass
        `test(keys [rows, 128], offset of their column)`; `slices`
        columns a trip, each into an accumulator of its own."""
        def trip(j, accs):
            out = []
            for i, acc in enumerate(accs):
                at = pl.multiple_of(j * width + i * _LANES, _LANES)
                hit = test(keys(at, _LANES), at)
                out.append(acc + hit.astype(jnp.int32))
            return tuple(out)

        zero = jnp.zeros((rows, _LANES), jnp.int32)
        accs = lax.fori_loop(0, trips, trip, (zero,) * slices)
        return jnp.sum(functools.reduce(jnp.add, accs), axis=1,
                       keepdims=True)

    def wide(v):
        return jnp.broadcast_to(v, (rows, _LANES))

    # the k-th best key, bit by bit from the top: the largest v (the
    # bits unsigned, compared signed as v ^ INT_MIN) that at least k keys
    # reach; n_ge counts the keys at or above it
    def kth_bit(i, carry):
        cur, n_cur = carry
        cand = cur | lax.shift_left(jnp.int32(1), 31 - i)
        thr = wide(cand ^ _INT_MIN)
        n = count(lambda kb, _: kb >= thr)
        ok = n >= k
        return jnp.where(ok, cand, cur), jnp.where(ok, n, n_cur)

    kth, n_ge = lax.fori_loop(
        0, 32, kth_bit, (jnp.zeros((rows, 1), jnp.int32),
                         jnp.full((rows, 1), trips * width, jnp.int32)))
    # fewer than k keys counted: every visible one is taken
    kth = jnp.maximum(kth ^ _INT_MIN, _NEG_INF_KEY)
    kth_w = wide(kth)
    n_gt = count(lambda kb, _: kb > kth_w)
    # of the positions tied at the k-th best key the lowest `room` join
    # those above it: all up to `last`, the largest position with fewer
    # than `room` ties below it, found bit by bit where a row has more
    # ties than room and they are visible
    room = k - n_gt
    need = ((n_ge - n_gt > room) & (kth != _NEG_INF_KEY)).astype(jnp.int32)

    def tie_bit(i, cur):
        cand = wide(cur | lax.shift_left(jnp.int32(1), pos_bits - 1 - i))
        n = count(lambda kb, at: (kb == kth_w) & (col < cand - at))
        return jnp.where(n < room, cand[:, :1], cur)

    last = lax.cond(
        jnp.max(need) > 0,
        lambda: lax.fori_loop(0, pos_bits, tie_bit,
                              jnp.zeros((rows, 1), jnp.int32)),
        lambda: jnp.full((rows, 1), _INT_MAX, jnp.int32))
    # no tie is taken where the k-th best is -inf: those are not visible
    last = jnp.where(kth == _NEG_INF_KEY, -1, last)

    # the choice as bits: a group's 0/1 choice times the pack matrix on
    # the matrix unit gives its 128 words, in two halves of 16 bits
    pos = lax.broadcasted_iota(jnp.int32, (rows, _GROUP), 1)
    out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)

    def group(g, carry):
        at = pl.multiple_of(g * _GROUP, _GROUP)
        kb = keys(at, _GROUP)
        take = (kb > kth) | ((kb == kth) & (pos <= last - at))
        half = jnp.dot(jnp.where(take, 1.0, 0.0).astype(jnp.bfloat16),
                       pack_ref[...], preferred_element_type=jnp.float32
                       ).astype(jnp.int32)
        out_ref[:, pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)] = (
            lax.bitcast_convert_type(
                half[:, :_LANES] | lax.shift_left(half[:, _LANES:], 16),
                jnp.uint32))
        return carry

    lax.fori_loop(0, groups, group, 0)


def _select_words(score, k: int, frontier, interpret, name: str):
    """The kernel's packed choice for score [N, T], [N_pad, T_pad / 32]
    uint32 (rows and positions padded to whole cells and groups)."""
    if interpret is None:
        interpret = meshlib.pallas_interpret()
    n, t = score.shape
    rows = min(ROWS, max(8, 1 << (n - 1).bit_length()))
    n_pad, t_pad = -(-n // rows) * rows, -(-t // _GROUP) * _GROUP
    if (n_pad, t_pad) != (n, t):
        score = jnp.pad(score, ((0, n_pad - n), (0, t_pad - t)),
                        constant_values=-jnp.inf)
    front = jnp.clip(jnp.asarray(frontier, jnp.int32), 0, t).reshape(1)
    kernel = functools.partial(_kernel, k=k,
                               slices=max(1, _SPAN // (rows * _LANES)),
                               pos_bits=max(1, (t - 1).bit_length()))
    vmem = rows * t_pad * 4 + _GROUP * 2 * _LANES * 2 + rows * t_pad // 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, t_pad // 32), lambda i, f: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, t_pad), jnp.float32),
                            pltpu.VMEM((_GROUP, 2 * _LANES), jnp.bfloat16),
                            pltpu.SemaphoreType.DMA((t_pad // _GROUP,))]),
        out_shape=jax.ShapeDtypeStruct((n_pad, t_pad // 32), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (4 << 20)),
        interpret=interpret,
        name=name,
    )(front, score)


def select_topk_bits(score, k: int, frontier, *, interpret=None):
    """[N, ceil(T / 32)] uint32, bit b of word m being position 32 m + b:
    per row of score [N, T] float32 (-inf where a position is not
    visible, and everywhere at or beyond `frontier`, traced) the k best
    visible positions, all of them where there are no more than k;
    equal scores go to the lower position, as `lax.top_k` breaks them.
    Exact: integer comparisons of the scores' own bits. `ROWS` query
    rows a grid cell, fewer where N is smaller; `interpret=None` follows
    `mesh.pallas_interpret`."""
    n, t = score.shape
    words = _select_words(score, k, frontier, interpret, "chunk_topk")
    return words[:n, :-(-t // 32)]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def select_topk_positions(score, k: int, frontier, *, interpret=None):
    """[N, k] int32: the positions `select_topk_bits` chooses (the same
    set, exactly) in ascending order, -1 after the last where a row sees
    fewer than k. The kernel is traced as `decode_topk`. Jitted, so that
    the calls of a program's layers share ONE lowering of the kernel:
    lowered once a call, a decode window of eight selecting layers took
    5 s longer to trace and lower on the host (`PERF.md` section 6)."""
    n = score.shape[0]
    return _positions(_select_words(score, k, frontier, interpret,
                                    "decode_topk")[:n], k)


# outputs a block of `_positions`' word count: the thermometer's width
_OUT_BLOCK = 64


def _positions(words, k: int):
    """[N, W] uint32 packed choice, W a multiple of 128 -> [N, k] int32:
    each row's chosen positions ascending, -1 beyond its count. Exact
    integer arithmetic throughout: every product takes 0/1 and integer
    operands under 2^24 and sums them in float32 to values under 2^24.
    Running sums are products too (XLA's own takes 13 us a trip for
    1,024 words on a v5e, `PERF.md` section 6)."""
    n, w = words.shape
    hi = dict(precision=lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32)
    lanes = jnp.arange(_LANES)
    pop = lax.population_count(words).astype(jnp.int32)
    # positions chosen up to each word's end: within a tile of 128 words
    # by a triangle of ones, across tiles by the tiles' running sum
    inside = jnp.einsum(
        "ngl,lm->ngm", pop.reshape(n, -1, _LANES).astype(jnp.bfloat16),
        (lanes[:, None] <= lanes).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    tiles = inside[..., -1]
    ends = (inside + (jnp.cumsum(tiles, axis=1) - tiles)[..., None]
            ).reshape(n, w).astype(jnp.int32)
    j = jnp.arange(k, dtype=jnp.int32)
    # the j-th position lies in word #{m : ends[m] <= j}; for j = 64 a + b
    # that is the words ending in an earlier block of 64 outputs plus
    # those ending in block a at or below b: a one-hot of the block times
    # a thermometer of the rest, counted on the matrix unit
    blocks = -(-k // _OUT_BLOCK)
    onehot = ends[..., None] // _OUT_BLOCK == jnp.arange(blocks)
    therm = ends[..., None] % _OUT_BLOCK <= jnp.arange(_OUT_BLOCK)
    inside = jnp.einsum("nma,nmb->nab", onehot.astype(jnp.bfloat16),
                        therm.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    full = inside[..., -1]
    word = (jnp.cumsum(full, axis=1) - full)[..., None] + inside
    word = word.reshape(n, -1)[:, :k].astype(jnp.int32)
    # that word and the positions chosen before it, as two planes of 24
    # bits (a half of the word, a byte of the count), picked by a
    # one-hot of its column of 32 words, then of its place there
    cols = w // 32
    before = (ends - pop).astype(jnp.uint32)
    planes = jnp.stack([(words & 0xFFFF) | ((before & 0xFF) << 16),
                        (words >> 16) | ((before >> 8) << 16)], axis=1)
    planes = planes.reshape(n, 2, cols, 32).transpose(0, 2, 1, 3)
    picked = jnp.einsum(
        "njc,ncx->njx",
        (word[..., None] // 32 == jnp.arange(cols)).astype(jnp.float32),
        planes.reshape(n, cols, 64).astype(jnp.float32), **hi)
    place = word[..., None] % 32 == jnp.arange(32)
    low, high = (jnp.sum(jnp.where(place, picked[..., 32 * i:32 * i + 32],
                                   0.0), axis=-1).astype(jnp.int32)
                 for i in range(2))
    # the bit: the (j - before)-th set bit of the word, by five halvings
    x = lax.bitcast_convert_type((low & 0xFFFF) | (high << 16), jnp.uint32)
    rank = j - ((low >> 16) | ((high >> 16) << 8))
    bit = jnp.zeros_like(rank)
    for half in (16, 8, 4, 2, 1):
        below = lax.population_count(x & ((1 << half) - 1)).astype(
            jnp.int32)
        up = rank >= below
        rank = jnp.where(up, rank - below, rank)
        x = jnp.where(up, x >> half, x)
        bit = jnp.where(up, bit + half, bit)
    return jnp.where(j < ends[:, -1:], 32 * word + bit, -1)
