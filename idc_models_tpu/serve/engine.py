"""Fixed-slot continuous-batching decode engine — the device half of the
serving subsystem.

PR 1's `Generator` (models/lm.py) serves ONE request start-to-finish:
ring prefill, then a fused scan emitting that request's tokens. Under
concurrent traffic that leaves every other request queued head-of-line
and the decode batch at 1. This engine applies iteration-level
scheduling (Orca) with slot recycling (vLLM): `n_slots` requests decode
TOGETHER, one batch row each, and whenever a row finishes (EOS, budget,
deadline) the scheduler drops a freshly prefilled request into the
vacated row while the other rows keep decoding — the batch never drains
to refill.

ALL per-slot state is device-resident and donated through the whole
serve loop: per-block ring caches `[S, t_max, ...]` (one row per slot,
in the stored form `ring_decode.cache_shape` gives the layer's heads,
the training-layout ring sharding), last-token logits `[S, V]`, rng key
data `[S, 2]`, positions `[S]`, remaining token budgets `[S]`, and stop
ids `[S]`. The host keeps a SHADOW of positions/budgets it can update by
pure arithmetic from the fetched tokens — no per-window state fetch.
Three compiled programs drive the device:

- **masked fused window** — ONE dispatch emits up to W tokens for every
  slot: per scan step, each live slot splits its OWN rng stream, samples
  with the exact serial `pick` math (a `[1, V]` row per slot), and runs
  the shared per-token forward (`models/lm._token_forward`) with the
  batched ring fold (`ring_decode.make_batched_ring_decode`) — finished
  slots emit `pad_id` and their cache rows are bit-untouched. Budgets
  count down and EOS hits zero them ON DEVICE, so rows retire mid-window
  with no host in the loop; positions advance only while live.
- **prefill** — the SAME bucketed program the serial `Generator` runs
  (`models/lm._serving_fns`): prompts pad to `prefill_bucket` shapes
  with the true length traced, so admitting arbitrary prompt lengths
  compiles nothing new after warmup AND a request's prefill is
  bit-identical to a serial call's.
- **insert** — a jitted batch-axis scatter admitting one request: the
  fresh `[1, t_max, ...]` caches, `[1, V]` logits, and the slot's
  position/budget/stop-id/key rows all land via `dynamic_update_slice`
  with the slot index TRACED — one executable for every slot, zero
  recompilation on recycle.

The window API is a TWO-DEEP PIPELINE: `begin_window` dispatches a
window and returns immediately (jax dispatch is async); `collect` blocks
on the PREVIOUS window's tokens. The scheduler admits and does its host
bookkeeping while the in-flight window computes — this hides the
per-dispatch host cost the same way the PR-1 fused scan hides per-token
dispatch. `step_window` (= begin + collect) keeps the
synchronous contract for direct use.

Token parity (gated by tests/test_serve.py): because prefill, the
per-token forward, the fold (row-wise bit-equal to the scalar fold), and
the sampling rule are all the serial definitions, a request's output
through this engine is bit-identical to a serial `Generator` call with
the same prompt/seed — including a request admitted into a slot another
request vacated mid-run.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models import moe
from idc_models_tpu.observe import trace
from idc_models_tpu.models.lm import (
    _attn_residual, _chunk_batch_forward, _chunk_forward, _ffn_residual,
    _final_logits, _make_pick, _place_params, _project_qkv, _serve_config,
    _serving_fns, _token_forward, check_prefill_chunk, chunk_picks,
    make_adapter_head_hook, prefill_bucket, prefill_buckets,
    sparse_window_stats,
)
from idc_models_tpu.ring_decode import (
    cache_shape, decode_rows_read, grow_cache, index_cache_shape,
    latent_cache_shape, make_batched_chunk_ring_decode,
    make_batched_ring_decode,
    make_paged_batched_chunk_ring_decode, make_paged_batched_ring_decode,
    make_paged_chunk_ring_decode, make_sparse_chunk_decode,
    make_sparse_decode,
)
from idc_models_tpu.serve.pages import PageAllocator, PageExhausted


def _key_data(rng) -> np.ndarray:
    """A request's rng as host uint32 key data. Integer seeds take the
    host fast path — bit-identical to `key_data(jax.random.key(seed))`
    under the default threefry2x32 impl (verified on first use), without
    the per-admission device dispatch + fetch an eager key build
    costs."""
    if isinstance(rng, (int, np.integer)):
        seed = int(rng)
        if seed < 0:
            raise ValueError(f"need a non-negative seed, got {seed}")
        if not _key_data._checked:
            probe = np.asarray(
                jax.random.key_data(jax.random.key(0x12345)))
            want = np.array([0, 0x12345], np.uint32)
            if not np.array_equal(probe, want):
                raise RuntimeError(
                    "non-threefry2x32 default PRNG: pass explicit "
                    "jax.random keys instead of integer seeds")
            _key_data._checked = True
        return np.array([(seed >> 32) & 0xffffffff, seed & 0xffffffff],
                        np.uint32)
    return np.asarray(jax.random.key_data(rng))


_key_data._checked = False


class _AotWindow:
    """AOT decode-window executable plus the static step count it was
    compiled at. The jit dispatch site passes the step count as the
    last positional argument (a `static_argnums` entry); a Compiled
    executable takes only the array arguments, so this shim drops it —
    after checking it MATCHES. A different window size is a different
    program: it falls through to the jitted function (which compiles
    it), exactly what a compile-cache miss means."""

    def __init__(self, exe, n_steps: int, base):
        self._exe = exe
        self._n = int(n_steps)
        self._base = base

    def __call__(self, *args):
        if int(args[-1]) != self._n:
            return self._base(*args)
        return self._exe(*args[:-1])


class _AotPrograms:
    """Per-engine dispatch-table proxy installed by a cache-backed
    warmup: program names the persistent compile cache covered resolve
    to AOT executables; everything else falls through to the shared
    jitted namespace. A proxy — never a mutation — because the
    underlying `_engine_fns`/`_serving_fns` namespaces are
    `lru_cache`-shared across every engine with the same config
    (canary clones, cluster replicas on one device): planting one
    engine's device-bound executables there would corrupt its
    siblings. Introspection (`cache_sizes`, `program_costs`) reaches
    the jitted originals through `_base`."""

    def __init__(self, base, overlay: dict):
        self._base = base
        self._overlay = dict(overlay)

    def __getattr__(self, name):
        ov = self.__dict__["_overlay"].get(name)
        if ov is not None:
            return ov
        return getattr(self.__dict__["_base"], name)


class _PendingPrefill:
    """Host-side record of one chunked prefill in flight: the prompt,
    the single-request caches being extended chunk by chunk, and where
    the next chunk starts (past any prefix-cache hit). On a PAGED
    engine `caches` is None (chunks write the slot's granted pool
    pages directly) and `pages` holds the grant, of which the first
    `shared` ids are prefix-cache pages this request only references."""

    __slots__ = ("prompt", "budget", "rng", "eos_id", "caches", "logits",
                 "next_start", "tag", "pages", "shared", "tid")

    def __init__(self, *, prompt, budget, rng, eos_id, caches, logits,
                 next_start, tag=None, pages=None, shared=0, tid=0):
        self.pages = pages
        self.shared = shared
        self.tid = tid
        self.prompt = prompt
        self.budget = budget
        self.rng = rng
        self.eos_id = eos_id
        self.caches = caches
        self.logits = logits
        self.next_start = next_start
        self.tag = tag


class _EngineFns(NamedTuple):
    init_caches: object
    init_scales: object
    window: object    # (params, caches, logits, kd, pos, rem, eos,
    #                    kscales, vscales, W); paged engines take the
    #                    page table after the pools
    insert: object    # (state..., new_caches, new_logits, slot, ...);
    #                   paged engines scatter scalars/logits only (the
    #                   prompt K/V is already in the pool)
    health: object    # (logits) -> [S] int32 fault code
    verify: object    # (params, state..., drafts, vlive) ->
    #                   (toks, n_emit, n_acc, state...); None unless
    #                   the engine was built with draft_k
    # paged-mode programs (None on contiguous engines): rewrite one
    # slot's page-table row, stamp granted decode pages' dequant
    # scales from a source page (int8), and the direct-to-pool chunk
    # prefill
    page_row: object = None
    stamp_scales: object = None
    prefill_chunk: object = None
    # in-place mode (a contiguous engine whose spec has an indexer): the
    # chunk program above writes the reserved slot's own rows, `insert`
    # scatters scalars and logits alone, and `kill` zeroes one slot's
    # device budget before its rows are written
    kill: object = None


# a last-token logit past this magnitude is corruption, not a model
# output: real logits live within a few hundred even on poorly scaled
# models, and the finite-garbage fault class (bit flips, a blown-up
# matmul) is exactly what a pure isfinite check is blind to
_HEALTH_LOGIT_LIMIT = 1e30
HEALTH_KINDS = {1: "nonfinite_logits", 2: "logit_magnitude"}


def _window_core(cfg, pick, pad_id, params, caches, logits, kd, pos,
                 remaining, eos, n_steps, step_fn, pin_state,
                 eff=None, rows_read=None):
    """THE masked fused-window scan — sampling rule, rng advance,
    budget/EOS retirement — shared verbatim by the contiguous and the
    paged engines (only `step_fn`, the per-token forward + cache fold,
    differs), so paged token streams are bit-identical to contiguous
    ones by construction rather than by parallel maintenance.

    `step_fn` returns (logits, caches, the layers' statistics of the
    live rows); the window's last result but one is the expert layers'
    sum over its steps (`moe.window_stats`) joined with the indexer
    layers' account (`lm.sparse_window_stats`), () for a model with
    neither.
    The last is the cache rows the window's attention read, summed over
    its steps: `rows_read(pos, live)` counts one step's (the contiguous
    engine's `ring_decode.decode_rows_read`); () without it.

    `eff` (None = identity) maps each step's base logits to the
    EFFECTIVE pick logits — the per-tenant adapter hook
    (models/lm.make_adapter_head_hook): the delta is applied at the
    token pick only, while the carried logits state stays base, so
    every stored row remains tenant-agnostic."""
    def body(carry, _):
        caches, logits, kd, pos, remaining = carry
        live = remaining > 0
        pl = logits if eff is None else eff(logits)
        if cfg.temperature == 0.0:
            # greedy consumes NO randomness (serial pick ignores its
            # key too) — skip the S per-slot threefry splits, which
            # otherwise dominate the per-step cost at small batch
            toks = jax.vmap(lambda lg: pick(lg[None, :], None)[0])(
                pl)
        else:
            pair = jax.vmap(jax.random.split)(
                jax.random.wrap_key_data(kd))        # [S, 2] keys
            # per-slot sampling over a [1, V] row — the EXACT serial
            # pick call shape, so seeded sampling matches bit-for-bit
            toks = jax.vmap(lambda lg, k: pick(lg[None, :], k)[0])(
                pl, pair[:, 1])
        toks = jnp.where(live, toks, pad_id).astype(jnp.int32)
        if cfg.temperature > 0.0:
            # the stream advances once per EMITTED token, same as the
            # serial decode loop's one split per step
            kd = jnp.where(live[:, None],
                           jax.random.key_data(pair[:, 0]), kd)
        new_logits, caches, stats = step_fn(params, caches, toks, pos,
                                            live)
        rows = rows_read(pos, live) if rows_read else ()
        logits = jnp.where(live[:, None], new_logits, logits)
        pos = jnp.where(live, pos + 1, pos)
        remaining = jnp.where(live, remaining - 1, remaining)
        hit = live & (eos >= 0) & (toks == eos)
        remaining = jnp.where(hit, 0, remaining)
        return (caches, logits, kd, pos, remaining), (toks, stats, rows)

    (caches, logits, kd, pos, remaining), (toks, stats, rows) = lax.scan(
        body, (caches, logits, kd, pos, remaining), None,
        length=n_steps)
    caches, logits = pin_state(caches, logits)
    # the expert layers' account of the window (() without them): what
    # each held expert was sent, summed over the steps on the device;
    # and the indexer layers' (nothing without them): what was selected
    account = moe.window_stats(tuple(st for st in stats if "held" in st))
    selected = sparse_window_stats(stats)
    if selected:
        account = {**dict(account or {}), **selected}
    return (jnp.moveaxis(toks, 0, 1), caches, logits, kd, pos,
            remaining, account,
            jnp.sum(rows) if rows_read else ())


def _verify_core(cfg, pick, pad_id, K, t_max, params, caches, logits,
                 kd, pos, remaining, eos, drafts, vlive, chunk_forward,
                 tok_forward, pin_state, eff=None):
    # SPECULATIVE VERIFY — one dispatch turns K drafted tokens per
    # slot into between 1 and K+1 EMITTED tokens per participating
    # slot:
    #   1. run all K drafts through the per-token forward widened to
    #      K positions (the batched chunk fold appends their K/V and
    #      attends with per-query causality), yielding the model's
    #      next-token logits after each draft prefix;
    #   2. accept the longest draft prefix the model itself would
    #      have emitted (the pick rule per position — greedy argmax,
    #      or the seeded sample along the request's exact key chain),
    #      then take the model's OWN pick at the first disagreement
    #      as a bonus token — so even a total draft miss emits
    #      exactly the token a 1-step window would, bit-identically;
    #   3. run ONE masked token step for the bonus (its K/V lands at
    #      pos + accepted, overwriting the rejected draft's row) —
    #      the logits every slot decodes from next, restoring the
    #      window invariant exactly.
    # Rejected-suffix cache rows beyond each slot's new frontier hold
    # dead draft K/V, masked out of every later attend by the
    # positional visibility rule and overwritten before they ever
    # become visible — the same discipline as the batched decode
    # path's dead rows. All accept/budget/EOS bookkeeping happens ON
    # DEVICE; the host learns the outcome from the fetched
    # (toks, n_emit, n_acc) rows. Shared verbatim by the contiguous
    # and paged engines — only the two forwards' cache folds differ.
    s_rows = drafts.shape[0]
    live = jnp.asarray(vlive, jnp.bool_) & (remaining > 0)
    L, caches = chunk_forward(params, caches, drafts, pos, live)
    # K+1 candidate distributions along the accepted path:
    # cand[:, 0] is the slot's incoming logits (predicting the first
    # draft position), cand[:, j] the logits after drafts[:, :j]
    cand = jnp.concatenate(
        [logits.astype(L.dtype)[:, None], L], axis=1)
    # the per-tenant adapter hook, applied to the CANDIDATE
    # distributions the picks see ([S, K+1, V] — one gather for all
    # K+1 positions); the stored state (`after`, the bonus logits)
    # stays base, same discipline as the window's per-step pick
    cand_p = cand if eff is None else eff(cand)
    if cfg.temperature == 0.0:
        flat = cand_p.reshape(-1, cand_p.shape[-1])
        g = jax.vmap(lambda lg: pick(lg[None, :], None)[0])(
            flat).reshape(s_rows, K + 1).astype(jnp.int32)
        kd_chain = None
    else:
        # the request's exact serial key chain: one split per
        # candidate step, token j sampled with split j's sub —
        # identical math and order to the fused window's per-step
        # vmapped split + pick
        def samp(kd_c, lg_j):
            pair = jax.vmap(jax.random.split)(
                jax.random.wrap_key_data(kd_c))
            t = jax.vmap(
                lambda lg, kk: pick(lg[None, :], kk)[0])(
                lg_j, pair[:, 1])
            kd_n = jax.random.key_data(pair[:, 0])
            return kd_n, (t, kd_n)

        _, (g_t, chain) = lax.scan(samp, kd,
                                   jnp.moveaxis(cand_p, 0, 1))
        g = jnp.moveaxis(g_t, 0, 1).astype(jnp.int32)
        kd_chain = jnp.moveaxis(chain, 0, 1)     # [S, K+1, 2]
    # accepted prefix length m, the bonus pick g[m], and the emitted
    # count n_f after budget + EOS truncation
    matches = drafts.astype(jnp.int32) == g[:, :K]
    m = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1),
                axis=1)
    b = jnp.take_along_axis(g, m[:, None], axis=1)[:, 0]
    cand_n = jnp.where(live,
                       jnp.minimum(m + 1, remaining), 0)
    ar = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
    drafts_ext = jnp.concatenate(
        [drafts.astype(jnp.int32),
         jnp.zeros((s_rows, 1), jnp.int32)], axis=1)
    emitted = jnp.where(
        ar < m[:, None], drafts_ext,
        jnp.where(ar == m[:, None], b[:, None], pad_id))
    is_eos = ((eos[:, None] >= 0) & (emitted == eos[:, None])
              & (ar < cand_n[:, None]))
    any_eos = jnp.any(is_eos, axis=1)
    first = jnp.argmax(is_eos, axis=1).astype(cand_n.dtype)
    n_f = jnp.where(any_eos, first + 1, cand_n)
    n_acc = jnp.minimum(m, n_f)
    toks = jnp.where(ar < n_f[:, None], emitted,
                     pad_id).astype(jnp.int32)
    # the bonus token's own masked step (appends at pos + m)
    bonus_live = live & (n_f == m + 1)
    bpos = jnp.clip(pos + m, 0, t_max - 1)
    b_logits, caches, _ = tok_forward(params, caches, b, bpos,
                                      bonus_live)
    after = jnp.take_along_axis(
        cand, jnp.clip(n_f, 0, K)[:, None, None], axis=1)[:, 0]
    new_logits = jnp.where(bonus_live[:, None],
                           b_logits.astype(logits.dtype),
                           after.astype(logits.dtype))
    logits = jnp.where(live[:, None], new_logits, logits)
    pos = jnp.where(live, pos + n_f, pos)
    remaining = jnp.where(
        live, jnp.where(any_eos, 0, remaining - n_f), remaining)
    if kd_chain is not None:
        kd_take = jnp.take_along_axis(
            kd_chain, jnp.clip(n_f - 1, 0, K)[:, None, None],
            axis=1)[:, 0]
        kd = jnp.where(live[:, None], kd_take, kd)
    caches, logits = pin_state(caches, logits)
    return (toks, n_f.astype(jnp.int32), n_acc.astype(jnp.int32),
            caches, logits, kd, pos, remaining)


def _sparse_kw(spec, l) -> dict:
    """The sparse folds' shape of a layer that attends a selection: the
    `topk` of the indexer that selects for it, and for a latent layer
    the latent form and its scale; `shared` where another layer
    selects."""
    kw = {"topk": (l.indexer or spec.layers[l.shares].indexer).topk}
    if l.latent is not None:
        kw.update(latent=True, scale=l.latent.scale)
    if l.shares is not None:
        kw["shared"] = True
    return kw


@functools.lru_cache(maxsize=16)
def _engine_fns(cfg, pad_id: int, quant: bool = False,
                draft_k: int | None = None) -> _EngineFns:
    """Compile-once engine programs per decode configuration — the same
    process-wide sharing discipline as `models/lm._serving_fns`: params
    are explicit arguments, so two engines with one config share every
    executable. With ``quant`` the batch caches hold int8 K/V plus
    per-(slot, head) float32 scales (one pair of [S, H] arrays per
    block): insert quantizes the prefilled float caches (absmax/127 per
    head) and the window's fold dequantizes by factoring the scales out
    of the contractions — see `ring_decode.make_batched_ring_decode`."""
    mesh, t_max, spec = cfg.mesh, cfg.t_max, cfg.spec
    # one fold per cache class: a window layer's rows wrap (position p
    # at row p mod W), a full layer's do not
    wraps = [l.window is not None for l in spec.layers]
    folds = {w: make_batched_ring_decode(mesh, jit=False, quantized=quant,
                                         wrap=w) for w in set(wraps)}
    # a layer with an indexer folds over three caches (K, V, index keys),
    # a latent one over its rows (and index keys, where it selects)
    sparse_folds = {i: make_sparse_decode(mesh, **_sparse_kw(spec, l))
                    for i, l in enumerate(spec.layers) if l.selects}
    pick = _make_pick(cfg)
    # the TRAILING-NONE-FREE spelling of the ring cache layout: jit
    # normalizes trailing Nones out of output PartitionSpecs, and the
    # jit cache keys on spec EQUALITY — P(None, "seq", None, None) and
    # P(None, "seq") describe one layout but are different keys, which
    # would recompile the window once when its input caches switch from
    # init_cache's spelling to a previous program's output (observed)
    cache_sh = meshlib.batch_seq_sharding(mesh, trailing=0)
    rep = meshlib.replicated(mesh)

    def pin_state(caches, logits):
        # every program returns the engine state under ONE canonical
        # sharding: jit executables are cached per input sharding, so
        # letting GSPMD re-derive layouts per program would make e.g.
        # insert-output caches a different cache key than init_cache's
        # and recompile the window once per producer (observed)
        caches = tuple(
            tuple(lax.with_sharding_constraint(c, cache_sh) for c in cache)
            for cache in caches)
        return caches, lax.with_sharding_constraint(logits, rep)

    def init_caches(n_slots: int):
        # same zeroed layout as ring_decode.init_cache, but placed under
        # the engine's canonical (normalized) sharding spelling; int8
        # when quantized — HALF the HBM of the bf16 rows, which is what
        # lets n_slots scale at a fixed budget
        # a layer's rows follow its spec: t_max of them, or a window
        # layer's ring; stored as the width of its own heads asks
        def mk(i, l):
            return meshlib.put_with_sharding(
                np.zeros(cache_shape(n_slots, spec.cache_len(i, t_max),
                                     l.kv_heads, l.head_dim),
                         jnp.int8 if quant
                         else jnp.dtype(cfg.cache_dtype)), cache_sh)

        def mk_index(l):
            return meshlib.put_with_sharding(
                np.zeros(index_cache_shape(n_slots, t_max, l.indexer.dim),
                         jnp.dtype(cfg.cache_dtype)), cache_sh)

        def mk_latent(l):
            return meshlib.put_with_sharding(
                np.zeros(latent_cache_shape(n_slots, t_max, l.latent.row),
                         jnp.dtype(cfg.cache_dtype)), cache_sh)

        # beside K and V (a latent layer: its one row a position), an
        # indexer's layer caches one index key a position
        return tuple(((mk_latent(l),) if l.latent else (mk(i, l), mk(i, l)))
                     + ((mk_index(l),) if l.indexer else ())
                     for i, l in enumerate(spec.layers))

    def init_scales(n_slots: int):
        # per-(slot, head) dequant scales, one (k, v) pair per block;
        # () on the float path so every signature stays uniform
        if not quant:
            return ()

        def mk():
            return meshlib.put_with_sharding(
                np.zeros((n_slots, cfg.num_heads), np.float32), rep)

        return tuple((mk(), mk()) for _ in range(cfg.num_blocks))

    def masked_step(params, caches, tok, pos, live, scales):
        def block_fold(i, *args):
            if i in sparse_folds:
                # (caches..., q, k, v, index) or a latent layer's
                # (caches..., q, row, index)
                return sparse_folds[i](*args, pos, live)
            kc, vc, q, k, v = args
            extra = (scales[i] if quant else ())
            return folds[wraps[i]](kc, vc, q, k, v, pos, live, *extra)

        return _token_forward(cfg, params, caches, tok, pos, block_fold,
                              live)

    # what one token step's attention reads of a full layer's cache (the
    # full layers all read alike; a window layer's ring is read whole, and
    # a layer with an indexer reads its selection, not up to a frontier)
    rows_read = (None if all(wraps) or spec.sparse else functools.partial(
        decode_rows_read, mesh, t_max))

    def window_body(params, caches, logits, kd, pos, remaining, eos,
                    scales, adapters, tslot, n_steps):
        # the whole window is ONE device program, like the serial fused
        # scan — but each slot carries its own position, budget, and rng
        # stream, and dead slots ride along as bit-level no-ops.
        # `adapters` is () (no tenancy — the historical program, pytree
        # structure keeps the jit cache keys distinct) or the stacked
        # (u [T, V, r], v [T, r, V]) tenant adapter bank, gathered by
        # the traced per-slot tenant ids `tslot` — tenant ARRIVAL
        # PATTERNS are values, never shapes, so a mixed-tenant batch
        # stays one executable (gated by test)
        def step_fn(params, caches, toks, pos, live):
            return masked_step(params, caches, toks, pos, live, scales)

        eff = (make_adapter_head_hook(*adapters, tslot) if adapters
               else None)
        return _window_core(cfg, pick, pad_id, params, caches, logits,
                            kd, pos, remaining, eos, n_steps, step_fn,
                            pin_state, eff=eff, rows_read=rows_read)

    # eos (argnum 6), the dequant scales (argnum 7), the adapter bank
    # (argnum 8) and the tenant-slot ids (argnum 9) are read-only
    # across windows and deliberately NOT donated — the same device
    # arrays feed every window until an admission replaces them
    window = jax.jit(window_body, static_argnums=(10,),
                     donate_argnums=(1, 2, 3, 4, 5))

    def insert_body(caches, logits, kd, pos, rem, eos, tslot, scales,
                    new_caches, new_logits, slot, p_len, budget, eos_id,
                    tid, kd_row):
        # batch-axis scatter with the slot index (and every per-slot
        # scalar) TRACED: one compiled program admits any request into
        # any slot
        out, out_scales = [], []
        for i, ((kc, vc), (nk, nv)) in enumerate(zip(caches,
                                                     new_caches)):
            if quant:
                ks_row, vs_row = scales[i]
                nk, k_s = _quantize_row(nk)
                nv, v_s = _quantize_row(nv)
                ks_row = lax.dynamic_update_slice(ks_row, k_s[None],
                                                  (slot, 0))
                vs_row = lax.dynamic_update_slice(vs_row, v_s[None],
                                                  (slot, 0))
                out_scales.append((
                    lax.with_sharding_constraint(ks_row, rep),
                    lax.with_sharding_constraint(vs_row, rep)))
            at = (slot,) + (0,) * (kc.ndim - 1)
            kc = lax.dynamic_update_slice(kc, nk.astype(kc.dtype), at)
            vc = lax.dynamic_update_slice(vc, nv.astype(vc.dtype), at)
            out.append((kc, vc))
        logits = lax.dynamic_update_slice(
            logits, new_logits.astype(logits.dtype), (slot, 0))
        kd = lax.dynamic_update_slice(kd, kd_row[None], (slot, 0))
        pos = pos.at[slot].set(p_len)
        rem = rem.at[slot].set(budget)
        eos = eos.at[slot].set(eos_id)
        tslot = tslot.at[slot].set(tid)
        caches, logits = pin_state(tuple(out), logits)
        return (caches, logits, kd, pos, rem, eos, tslot,
                tuple(out_scales) if quant else ())

    def _quantize_row(x):
        # one request's row of a cache, float -> (int8 values, [H]
        # per-head scale): absmax/127 over every (position, dim) of the
        # row's heads (a [1, t_max, H, D] view of this one row, not of
        # the batch cache), clamped so an all-zero row (fresh cache
        # tail) divides safely
        xf = x.astype(jnp.float32).reshape(*x.shape[:2], cfg.num_heads, -1)
        s = jnp.maximum(jnp.max(jnp.abs(xf), axis=(0, 1, 3)),
                        1e-8) / 127.0                      # [H]
        q = jnp.clip(jnp.round(xf / s[None, None, :, None]),
                     -127, 127).astype(jnp.int8)
        return q.reshape(x.shape), s

    insert = jax.jit(insert_body,
                     donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))

    prefill_chunk = kill = None
    if spec.sparse:
        # IN-PLACE admission: a request's chunks are written straight
        # into the reserved slot's rows of the batch caches (the slot is
        # out of `free_slots`, its device budget is 0, and no fold reads
        # a row beyond its slot's frontier), so no pending prefill owns
        # a cache row of its own and the insert moves no cache at all
        if any(wraps) or quant or not all(l.selects for l in spec.layers):
            raise ValueError(
                "a spec with an indexer prefills into the slot's own "
                "rows: every layer attends a selection (its own or "
                "another layer's), over full-length float caches, no "
                "window layer's ring and no int8 rows")
        chunk_folds = [make_sparse_chunk_decode(mesh, **_sparse_kw(spec, l))
                       for l in spec.layers]

        def chunk_body(params, caches, slot, tokens, start, p_end):
            # `slot` is traced: one executable serves every slot and
            # every chunk, the ragged last one included. Results: the
            # last real position's logits, the caches, the router's
            # picks (() without expert layers) and each query's selected
            # positions as bits, [indexer layers, C, t_max / 32]
            logits, caches, stats = _chunk_forward(
                cfg, params, caches, tokens, start, p_end,
                lambda i, *cache_and_chunk: chunk_folds[i](
                    *cache_and_chunk, start, slot))
            caches, logits = pin_state(caches, logits)
            bits = [st["selected_bits"] for st in stats
                    if "selected_bits" in st]
            return (logits, caches, chunk_picks(stats, *tokens.shape),
                    jnp.stack(bits))

        prefill_chunk = jax.jit(chunk_body, donate_argnums=(1,))
        insert = jax.jit(_insert_scalars(rep),
                         donate_argnums=(0, 1, 2, 3, 4, 5))
        # a slot released with device budget left (a deadline cancel)
        # would ride along appending at its old position: into rows the
        # next tenant's chunks are writing. Its budget goes first
        kill = jax.jit(lambda rem, slot: rem.at[slot].set(0),
                       donate_argnums=(0,))

    def health_body(logits):
        # per-slot fault codes in ONE tiny reduce + fetch ([S] int32):
        # 1 = non-finite logits, 2 = finite but magnitude-blown, 0 = ok.
        # Runs once per scheduler cycle when health checks are armed,
        # on the last-token logits every window reads next — the state
        # a poisoned slot corrupts first.
        lf = logits.astype(jnp.float32)
        nonfinite = jnp.any(~jnp.isfinite(lf), axis=1)
        huge = jnp.any(jnp.abs(lf) > _HEALTH_LOGIT_LIMIT, axis=1)
        return jnp.where(nonfinite, 1,
                         jnp.where(huge, 2, 0)).astype(jnp.int32)

    health = jax.jit(health_body)

    verify = None
    if draft_k is not None:
        K = int(draft_k)
        chunk_fold = make_batched_chunk_ring_decode(mesh, jit=False,
                                                    quantized=quant)

        def verify_body(params, caches, logits, kd, pos, remaining,
                        eos, scales, adapters, tslot, drafts, vlive):
            def chunk_forward(params, caches, drafts, pos, live):
                def block_chunk_fold(i, kc, vc, q, k, v):
                    extra = (scales[i] if quant else ())
                    return chunk_fold(kc, vc, q, k, v, pos, live,
                                      *extra)

                return _chunk_batch_forward(cfg, params, caches,
                                            drafts, pos,
                                            block_chunk_fold)

            def tok_forward(params, caches, b, bpos, bonus_live):
                def block_tok_fold(i, kc, vc, q, k, v):
                    extra = (scales[i] if quant else ())
                    return folds[False](kc, vc, q, k, v, bpos,
                                        bonus_live, *extra)

                return _token_forward(cfg, params, caches, b, bpos,
                                      block_tok_fold)

            eff = (make_adapter_head_hook(*adapters, tslot)
                   if adapters else None)
            return _verify_core(cfg, pick, pad_id, K, t_max, params,
                                caches, logits, kd, pos, remaining,
                                eos, drafts, vlive, chunk_forward,
                                tok_forward, pin_state, eff=eff)

        verify = jax.jit(verify_body, donate_argnums=(1, 2, 3, 4, 5))

    return _EngineFns(init_caches, init_scales, window, insert, health,
                      verify, prefill_chunk=prefill_chunk, kill=kill)


def _insert_scalars(rep):
    """The admission scatter that touches NO cache state: the prompt's
    K/V already sits where the windows read it (a paged engine's granted
    pages, an in-place engine's slot rows), so admitting a request is a
    handful of scalar/row updates, the slot index traced."""
    def insert_body(logits, kd, pos, rem, eos, tslot, new_logits, slot,
                    p_len, budget, eos_id, tid, kd_row):
        logits = lax.dynamic_update_slice(
            logits, new_logits.astype(logits.dtype), (slot, 0))
        kd = lax.dynamic_update_slice(kd, kd_row[None], (slot, 0))
        pos = pos.at[slot].set(p_len)
        rem = rem.at[slot].set(budget)
        eos = eos.at[slot].set(eos_id)
        tslot = tslot.at[slot].set(tid)
        return (lax.with_sharding_constraint(logits, rep), kd, pos,
                rem, eos, tslot)

    return insert_body


class _DrafterFns(NamedTuple):
    init_caches: object   # (n_slots) -> per-block ring pairs
    insert: object        # (dcaches, new_caches, slot) — row scatter
    ingest: object        # (dparams, dcaches, toks, pos0, live)
    propose: object       # (dparams, dcaches, adapters, tslot, toks,
    #                        n_new, pos0, live) -> (dcaches, drafts)


@functools.lru_cache(maxsize=16)
def _drafter_fns(dcfg, pad_id: int, draft_k: int) -> _DrafterFns:
    """Compile-once LEARNED-DRAFTER programs (models/draft_lm.py) — the
    device half of batched proposal. The drafter keeps its own small
    per-slot ring KV caches (`cache_shape` at the DRAFT model's
    dims, positions mirroring the target's), and `propose` turns every
    running slot's un-ingested emitted tokens into `draft_k` greedy
    proposals in ONE dispatch: a chunk ingest of the pending tokens
    (`_chunk_batch_forward` + the batched chunk fold) followed by a
    K-1-step autoregressive scan of the shared per-token forward.

    The ingest chunk width is FIXED at C = draft_k + 1 — the most a
    verify emits per slot per cycle, so the steady state is one
    propose dispatch per cycle; a backlog (plain windows wider than C,
    a fresh admission's deferred token) drains through `ingest`
    rounds first. C also bounds the ring writes: the scheduler only
    proposes for slots with verify room (pos + K + 1 <= t_max), so
    every chunk splice and speculative append lands inside t_max, and
    positions past a slot's committed frontier hold dead K/V that the
    next ingest overwrites before the visibility mask could ever
    reveal it — the same dead-row discipline as the decode window.

    `adapters`/`tslot` are the per-tenant drafter HEADS (the PR 14
    traced-tid gather, models/lm.make_adapter_head_hook): tenant mixes
    steer a gather by VALUE, so mixed-tenant batches stay one
    executable. Greedy only — a draft is a proposal, not a sample, and
    the verify re-picks with the request's real rule either way."""
    mesh, t_max = dcfg.mesh, dcfg.t_max
    head_dim = dcfg.embed_dim // dcfg.num_heads
    C = int(draft_k) + 1
    K = int(draft_k)
    fold = make_batched_ring_decode(mesh, jit=False)
    chunk_fold = make_batched_chunk_ring_decode(mesh, jit=False)
    cache_sh = meshlib.batch_seq_sharding(mesh, trailing=0)

    def pin(caches):
        # same canonical-sharding discipline as _engine_fns.pin_state:
        # one spelling for every producer keeps one jit cache key
        return tuple(
            (lax.with_sharding_constraint(kc, cache_sh),
             lax.with_sharding_constraint(vc, cache_sh))
            for kc, vc in caches)

    def init_caches(n_slots: int):
        def mk():
            return meshlib.put_with_sharding(
                np.zeros(cache_shape(n_slots, t_max, dcfg.num_heads,
                                     head_dim),
                         jnp.dtype(dcfg.cache_dtype)), cache_sh)

        return tuple((mk(), mk()) for _ in range(dcfg.num_blocks))

    def chunk_step(params, caches, toks, pos0, live):
        def block_fold(i, kc, vc, q, k, v):
            return chunk_fold(kc, vc, q, k, v, pos0, live)

        return _chunk_batch_forward(dcfg, params, caches, toks,
                                    pos0, block_fold)

    def ingest_body(params, caches, toks, pos0, live):
        # backlog drain: splice one C-chunk of pending tokens per live
        # row, logits discarded (only the FINAL chunk's feed a draft)
        _, caches = chunk_step(params, caches, toks, pos0, live)
        return pin(caches)

    ingest = jax.jit(ingest_body, donate_argnums=(1,))

    def propose_body(params, caches, adapters, tslot, toks, n_new,
                     pos0, live):
        # final chunk + autoregressive rollout, one program: the chunk
        # forward yields logits at EVERY position, so the last REAL
        # pending token's logits (index n_new - 1) seed draft 0 with
        # no extra dispatch; K - 1 masked token steps then extend the
        # drafter's own stream speculatively
        L, caches = chunk_step(params, caches, toks, pos0, live)
        idx = jnp.clip(n_new - 1, 0, C - 1)
        lg = jnp.take_along_axis(L, idx[:, None, None], axis=1)[:, 0]
        eff = (make_adapter_head_hook(*adapters, tslot) if adapters
               else None)

        def pick_tok(row):
            pl = row if eff is None else eff(row)
            return jnp.argmax(pl, axis=-1).astype(jnp.int32)

        d0 = pick_tok(lg)
        front = pos0 + n_new

        def step(carry, j):
            caches, cur = carry
            p = jnp.clip(front + j, 0, t_max - 1)

            def block_fold(i, kc, vc, q, k, v):
                return fold(kc, vc, q, k, v, p, live)

            lg2, caches, _ = _token_forward(dcfg, params, caches,
                                            cur, p, block_fold)
            return (caches, pick_tok(lg2)), cur

        (caches, last), ys = lax.scan(
            step, (caches, d0), jnp.arange(K - 1, dtype=jnp.int32))
        drafts = jnp.concatenate(
            [jnp.moveaxis(ys, 0, 1).astype(jnp.int32),
             last[:, None]], axis=1)
        return pin(caches), drafts

    propose = jax.jit(propose_body, donate_argnums=(1,))

    def insert_body(caches, new_caches, slot):
        # admission row scatter, slot TRACED — one executable for
        # every slot, the same recycle discipline as the target insert
        out = []
        for (kc, vc), (nk, nv) in zip(caches, new_caches):
            at = (slot,) + (0,) * (kc.ndim - 1)
            kc = lax.dynamic_update_slice(kc, nk.astype(kc.dtype), at)
            vc = lax.dynamic_update_slice(vc, nv.astype(vc.dtype), at)
            out.append((kc, vc))
        return pin(tuple(out))

    insert = jax.jit(insert_body, donate_argnums=(0,))

    return _DrafterFns(init_caches, insert, ingest, propose)


@functools.lru_cache(maxsize=16)
def _paged_engine_fns(cfg, pad_id: int, quant: bool, draft_k,
                      page_size: int, n_pages: int,
                      n_slots: int) -> _EngineFns:
    """Compile-once programs for a PAGED engine configuration — the
    paged twin of `_engine_fns`, same process-wide sharing discipline.
    The cache state is a per-block page POOL `[n_pages, page_size, H,
    D]` (K and V) shared by every slot plus ONE `[S, t_max/page_size]`
    int32 page table; the window/verify/chunk programs resolve slot
    positions through the table via gather (the page-table-indirect
    folds in ring_decode.py), and the sampling/retirement/accept math
    is the SAME `_window_core`/`_verify_core` the contiguous programs
    run — paged outputs are bit-identical to contiguous ones on a
    1-device mesh because only the cache indirection differs. With
    ``quant`` the pools hold int8 pages with per-(page, head) float32
    scales: finer-grained than the contiguous per-slot scales, so int8
    parity is gated on determinism + bounded drift, not bits
    (docs/LONG_CONTEXT.md "Paged KV")."""
    mesh, t_max = cfg.mesh, cfg.t_max
    head_dim = cfg.embed_dim // cfg.num_heads
    l_pages = t_max // page_size
    fold = make_paged_batched_ring_decode(mesh, page_size=page_size,
                                          jit=False, quantized=quant)
    pchunk_fold = make_paged_chunk_ring_decode(
        mesh, page_size=page_size, jit=False, quantized=quant)
    spec = cfg.spec
    pick = _make_pick(cfg)
    pool_sh = meshlib.sharding(mesh, meshlib.SEQ_AXIS)
    rep = meshlib.replicated(mesh)

    def pin_state(pools, logits):
        # one canonical sharding spelling for every program's outputs,
        # same jit-cache-stability discipline as the contiguous
        # pin_state
        pools = tuple(
            (lax.with_sharding_constraint(kp, pool_sh),
             lax.with_sharding_constraint(vp, pool_sh))
            for kp, vp in pools)
        return pools, lax.with_sharding_constraint(logits, rep)

    def pin_scales(scales):
        return tuple((lax.with_sharding_constraint(ks, rep),
                      lax.with_sharding_constraint(vs, rep))
                     for ks, vs in scales)

    def init_caches(_n_slots: int):
        # the POOL replaces the per-slot rows: page count — not slot
        # count — is what a fixed HBM budget buys, which is the whole
        # capacity story
        def mk():
            return meshlib.put_with_sharding(
                np.zeros((n_pages, page_size, cfg.num_heads, head_dim),
                         jnp.int8 if quant
                         else jnp.dtype(cfg.cache_dtype)), pool_sh)

        return tuple((mk(), mk()) for _ in range(cfg.num_blocks))

    def init_scales(_n_slots: int):
        if not quant:
            return ()

        def mk():
            return meshlib.put_with_sharding(
                np.zeros((n_pages, cfg.num_heads), np.float32), rep)

        return tuple((mk(), mk()) for _ in range(cfg.num_blocks))

    def masked_step(params, pools, pt, tok, pos, live, scales):
        def block_fold(i, kp, vp, q, k, v):
            extra = (scales[i] if quant else ())
            return fold(kp, vp, pt, q, k, v, pos, live, *extra)

        return _token_forward(cfg, params, pools, tok, pos, block_fold)

    def window_body(params, pools, pt, logits, kd, pos, remaining,
                    eos, scales, adapters, tslot, n_steps):
        def step_fn(params, pools, toks, pos, live):
            return masked_step(params, pools, pt, toks, pos, live,
                               scales)

        eff = (make_adapter_head_hook(*adapters, tslot) if adapters
               else None)
        return _window_core(cfg, pick, pad_id, params, pools, logits,
                            kd, pos, remaining, eos, n_steps, step_fn,
                            pin_state, eff=eff)

    # pt (argnum 2), eos, the scales, the adapter bank and the tenant-
    # slot ids are read-only across windows and NOT donated —
    # page-table rewrites go through the page_row program at grant
    # time only
    window = jax.jit(window_body, static_argnums=(11,),
                     donate_argnums=(1, 3, 4, 5, 6))

    # the prompt's K/V was written into the slot's granted pages by the
    # direct-to-pool chunk program
    insert = jax.jit(_insert_scalars(rep), donate_argnums=(0, 1, 2, 3, 4, 5))

    def page_row_body(pt, slot, row, rem, kill):
        # one program serves both grant-time rewrites (kill=0) and the
        # release-time KILL (kill=1, row=-1s): a released slot's device
        # budget must hit zero IN THE SAME dispatch its page-table row
        # clears, because its freed pages may be re-granted before the
        # row's leftover device budget runs out — a still-live zombie
        # row appending through a stale table would corrupt the new
        # owner's pages (the contiguous mode's harmless-ride-along
        # contract does NOT transfer to a shared pool)
        pt = lax.dynamic_update_slice(pt, row[None].astype(pt.dtype),
                                      (slot, 0))
        rem = jnp.where(kill > 0, rem.at[slot].set(0), rem)
        return lax.with_sharding_constraint(pt, rep), rem

    page_row = jax.jit(page_row_body, donate_argnums=(0, 3))

    stamp_scales = None
    if quant:
        def stamp_body(scales, src, dst):
            # copy the source page's per-head scale onto freshly
            # granted decode pages (dst padded with n_pages = OOB,
            # dropped): decode appends quantize with their page's
            # scale, and a fresh page has no content to derive one
            # from yet
            out = []
            for ks, vs in scales:
                kv = jnp.broadcast_to(ks[src][None],
                                      (dst.shape[0], ks.shape[1]))
                vv = jnp.broadcast_to(vs[src][None],
                                      (dst.shape[0], vs.shape[1]))
                out.append((ks.at[dst].set(kv, mode="drop",
                                           unique_indices=True),
                            vs.at[dst].set(vv, mode="drop",
                                           unique_indices=True)))
            return pin_scales(tuple(out))

        stamp_scales = jax.jit(stamp_body, donate_argnums=(0,))

    def chunk_body(params, pools, pt, scales, slot, tokens, start,
                   p_end):
        # one prompt CHUNK through every block, written STRAIGHT into
        # the slot's granted pool pages — the paged engine's admission
        # path never materializes a contiguous [1, t_max] cache.
        # Structure mirrors models/lm.chunk_body with the paged chunk
        # fold (page splice + gathered per-query attend + ring merge)
        # in place of the contiguous one; `slot` is traced, so one
        # executable serves every slot and every chunk incl. the
        # ragged tail.
        b, c = tokens.shape
        pt_row = lax.dynamic_slice(pt, (slot, 0), (1, l_pages))
        pos_tab = lax.dynamic_slice_in_dim(params["pos"], start, c,
                                           axis=0)
        h = jnp.take(params["embed"], tokens, axis=0) + pos_tab
        new_pools, new_scales = [], []
        for i, l in enumerate(spec.layers):
            p = params[f"block{i}"]
            kp, vp = pools[i]
            q, k, v, _ = _project_qkv(spec, l, p, h, (c,), None)
            if quant:
                ks, vs = scales[i]
                o, kp, vp, ks, vs = pchunk_fold(kp, vp, pt_row, q, k,
                                                v, start, p_end, ks,
                                                vs)
                new_scales.append((ks, vs))
            else:
                o, kp, vp = pchunk_fold(kp, vp, pt_row, q, k, v,
                                        start, p_end)
            h = _attn_residual(p, h, o)
            h = _ffn_residual(spec, l, p, h)[0]
            new_pools.append((kp, vp))
        h_last = lax.dynamic_slice_in_dim(h, p_end - start - 1, 1,
                                          axis=1)[:, 0]
        logits = _final_logits(spec, params, h_last)
        pools, logits = pin_state(tuple(new_pools), logits)
        return (logits, pools,
                pin_scales(tuple(new_scales)) if quant else ())

    prefill_chunk = jax.jit(chunk_body, donate_argnums=(1, 3))

    def health_body(logits):
        lf = logits.astype(jnp.float32)
        nonfinite = jnp.any(~jnp.isfinite(lf), axis=1)
        huge = jnp.any(jnp.abs(lf) > _HEALTH_LOGIT_LIMIT, axis=1)
        return jnp.where(nonfinite, 1,
                         jnp.where(huge, 2, 0)).astype(jnp.int32)

    health = jax.jit(health_body)

    verify = None
    if draft_k is not None:
        K = int(draft_k)
        pbchunk_fold = make_paged_batched_chunk_ring_decode(
            mesh, page_size=page_size, jit=False, quantized=quant)

        def verify_body(params, pools, pt, logits, kd, pos, remaining,
                        eos, scales, adapters, tslot, drafts, vlive):
            def chunk_forward(params, pools, drafts, pos, live):
                def block_chunk_fold(i, kp, vp, q, k, v):
                    extra = (scales[i] if quant else ())
                    return pbchunk_fold(kp, vp, pt, q, k, v, pos,
                                        live, *extra)

                return _chunk_batch_forward(cfg, params, pools,
                                            drafts, pos,
                                            block_chunk_fold)

            def tok_forward(params, pools, b, bpos, bonus_live):
                def block_tok_fold(i, kp, vp, q, k, v):
                    extra = (scales[i] if quant else ())
                    return fold(kp, vp, pt, q, k, v, bpos, bonus_live,
                                *extra)

                return _token_forward(cfg, params, pools, b, bpos,
                                      block_tok_fold)

            eff = (make_adapter_head_hook(*adapters, tslot)
                   if adapters else None)
            return _verify_core(cfg, pick, pad_id, K, t_max, params,
                                pools, logits, kd, pos, remaining,
                                eos, drafts, vlive, chunk_forward,
                                tok_forward, pin_state, eff=eff)

        verify = jax.jit(verify_body, donate_argnums=(1, 3, 4, 5, 6))

    return _EngineFns(init_caches, init_scales, window, insert, health,
                      verify, page_row, stamp_scales, prefill_chunk)


class SlotEngine:
    """`n_slots` concurrent decode rows over one parameter tree.

    The host-side contract: `free_slots()` lists vacant rows;
    `admit(slot, prompt, budget, ...)` prefills and scatters a request
    into a row; `begin_window`/`collect` run the two-deep pipelined
    masked windows (`step_window` is the synchronous pair); `finished`/
    `release` recycle rows. Scheduling policy (queueing, deadlines,
    interleave) lives in serve/scheduler.py — this class owns only the
    device state machine.

    The host never fetches per-slot state: positions and budgets are
    shadowed by arithmetic on the fetched token rows (the device rule —
    live steps are a prefix, EOS zeroes the budget — is replayed
    exactly), so a window costs ONE host transfer: its tokens.
    """

    def __init__(self, params, *, embed_dim: int | None = None,
                 num_heads: int | None = None,
                 num_blocks: int | None = None, t_max: int,
                 n_slots: int = 4, spec=None,
                 mesh=None, cache_dtype=jnp.bfloat16,
                 block_impl: str = "jnp", temperature: float = 0.0,
                 top_k: int | None = None, pad_id: int = 0,
                 eos_id: int | None = None,
                 prefill_chunk: int | None = None,
                 prefix_cache=None, kv_dtype: str | None = None,
                 draft_k: int | None = None,
                 kv_page_size: int | None = None,
                 kv_pages: int | None = None,
                 kv_decode_reserve: int | None = None,
                 adapter_bank=None, partition_rules=None,
                 draft_model=None, draft_partition_rules=None):
        if n_slots < 1:
            raise ValueError(f"need n_slots >= 1, got {n_slots}")
        # paged KV mode (ISSUE 11): the per-slot [t_max, ...] ring
        # rows are replaced by a pool of kv_pages fixed-size pages plus
        # per-slot page tables — HBM holds tokens actually resident,
        # not slots' worst cases. kv_decode_reserve bounds how many
        # decode tokens are PRE-reserved at admission (default: the
        # full budget — never exhausts mid-decode); a smaller reserve
        # admits more optimistically and grows grants mid-decode,
        # which can exhaust honestly (scheduler quarantine).
        if (kv_page_size is None) != (kv_pages is None):
            raise ValueError(
                "paged KV needs BOTH kv_page_size and kv_pages (or "
                "neither for the contiguous per-slot ring rows)")
        self.paged = kv_page_size is not None
        if self.paged:
            kv_page_size, kv_pages = int(kv_page_size), int(kv_pages)
            if prefill_chunk is None:
                raise ValueError(
                    "paged KV needs chunked prefill (prefill_chunk=C):"
                    " prompts stream straight into pool pages chunk by"
                    " chunk — there is no monolithic [1, t_max] cache "
                    "to insert from")
            if kv_page_size < 1 or t_max % kv_page_size:
                raise ValueError(
                    f"kv_page_size {kv_page_size} must be >= 1 and "
                    f"divide t_max {t_max} so logical pages tile the "
                    f"position space")
            if int(prefill_chunk) % kv_page_size:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must be a multiple"
                    f" of kv_page_size {kv_page_size}: chunk "
                    f"boundaries must land on the page grid so "
                    f"completed pages are never rewritten (the prefix-"
                    f"cache sharing invariant)")
            if kv_pages * kv_page_size < t_max:
                raise ValueError(
                    f"kv_pages {kv_pages} x kv_page_size "
                    f"{kv_page_size} < t_max {t_max}: one full-length "
                    f"request could never be admitted")
            if kv_decode_reserve is not None and kv_decode_reserve < 1:
                raise ValueError(f"need kv_decode_reserve >= 1, got "
                                 f"{kv_decode_reserve}")
        elif kv_decode_reserve is not None:
            raise ValueError("kv_decode_reserve needs paged KV "
                             "(kv_page_size/kv_pages)")
        self.kv_page_size = kv_page_size
        self.kv_pages = kv_pages
        self.kv_decode_reserve = kv_decode_reserve
        # draft_k arms speculative decoding: the engine compiles ONE
        # extra fixed-shape program (verify at exactly K draft tokens
        # per slot) and exposes begin_verify as an alternative window
        # dispatch; None keeps the historical engine bit-for-bit
        if draft_k is not None:
            draft_k = int(draft_k)
            if not 1 <= draft_k <= t_max - 2:
                raise ValueError(
                    f"draft_k {draft_k} outside [1, t_max - 2]: a "
                    f"verify needs room for K drafts + the bonus "
                    f"token inside the {t_max}-slot cache")
        self.draft_k = draft_k
        # kv_dtype: None/"bf16" keeps the float ring cache rows
        # (cache_dtype, the historical path bit-for-bit); "int8" stores
        # quantized rows + per-(slot, head) scales — ~2x the slots per
        # HBM byte, with the accuracy caveat documented in
        # docs/LONG_CONTEXT.md
        if kv_dtype not in (None, "bf16", "int8"):
            raise ValueError(f"kv_dtype must be None, 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_int8 = kv_dtype == "int8"
        if prefix_cache is not None and prefill_chunk is None:
            raise ValueError(
                "a prefix cache needs chunked prefill (prefill_chunk=C):"
                " snapshots live on chunk boundaries and only the chunk "
                "program can extend a cached prefix")
        # the model: attention_lm's three numbers, or a ModelSpec
        # (models/lm.py) — one forward serves both
        self._cfg = _serve_config(
            params, embed_dim=embed_dim, num_heads=num_heads,
            num_blocks=num_blocks, spec=spec, t_max=t_max, mesh=mesh,
            cache_dtype=cache_dtype, block_impl=block_impl,
            temperature=temperature, top_k=top_k)
        # what a spec beyond attention_lm's block cannot express on a
        # path makes that path refuse, here, by name — none may run
        # and answer wrongly
        for armed, mechanism in (
                (self.paged, "paged KV (kv_page_size)"),
                (self.kv_int8, "int8 KV (kv_dtype='int8')"),
                (draft_k is not None,
                 "speculative decoding (draft_k / spec_decode)"),
                (prefix_cache is not None, "the prefix cache"),
                (prefill_chunk is None, "the monolithic ring prefill "
                                        "(prefill_chunk=None)")):
            if armed:
                self._cfg.spec.require_classic(mechanism)
        self.prefill_chunk = (None if prefill_chunk is None
                              else check_prefill_chunk(prefill_chunk,
                                                       t_max))
        self.prefix_cache = prefix_cache
        if (prefix_cache is not None
                and prefix_cache.chunk != self.prefill_chunk):
            raise ValueError(
                f"prefix cache chunk {prefix_cache.chunk} != engine "
                f"prefill_chunk {self.prefill_chunk}")
        cache_is_paged = bool(getattr(prefix_cache, "is_paged", False))
        if prefix_cache is not None and cache_is_paged != self.paged:
            raise ValueError(
                "prefix-cache flavor must match the engine: a paged "
                "engine shares pool pages with PagedPrefixCache "
                "snapshots; a contiguous engine stores array snapshots "
                "in PrefixCache")
        if prefix_cache is not None and not cache_is_paged:
            # store snapshots TRUNCATED to the prefix length (positions
            # past it are zeros by construction — storing the full
            # [1, t_max] row would inflate every snapshot's budget cost
            # by t_max/prefix); a hit pads back and re-places under the
            # ring sharding, so the chunk program sees exactly the
            # layout it was warmed with (fresh arrays — never the
            # stored master) and the resume is bit-identical
            from idc_models_tpu.ring_decode import cache_sharding

            sh = cache_sharding(self._cfg.mesh)
            pad_to = t_max

            def _pack(caches, n_tokens):
                return jax.tree.map(lambda a: a[:, :n_tokens], caches)

            def _unpack(caches):
                def grow(a):
                    return meshlib.put_with_sharding(
                        grow_cache(jnp.asarray(a), pad_to), sh)

                return jax.tree.map(grow, caches)

            prefix_cache.set_packer(_pack, _unpack)
        # the "model" axis is legal WITH partition rules: weights shard
        # over it (registry.LM_RULES) while batch_seq_spec keeps the
        # slot/KV layout off it — params and KV shard independently.
        # Batch-bearing axes stay banned: requests prefill one at a
        # time and [1, P] batches cannot shard.
        non_seq = [a for a in self._cfg.mesh.axis_names
                   if a not in (meshlib.SEQ_AXIS, meshlib.MODEL_AXIS)
                   and self._cfg.mesh.shape[a] > 1]
        if non_seq:
            raise ValueError(
                f"serving mesh must be seq-only (plus an optional "
                f"'model' weight axis): requests prefill one at a time "
                f"([1, P] batches cannot shard over axes {non_seq}); "
                f"build the engine on mesh.seq_mesh(n) or "
                f"mesh.fsdp_tp_mesh(1, tp, seq)")
        if (meshlib.MODEL_AXIS in self._cfg.mesh.axis_names
                and self._cfg.mesh.shape[meshlib.MODEL_AXIS] > 1
                and partition_rules is None):
            raise ValueError(
                "a 'model' mesh axis without partition_rules would "
                "idle every device past the first ring: pass the "
                "model's rule set (models/registry.py "
                "get_partition_rules) so the params actually shard "
                "over it")
        self._sfns = _serving_fns(self._cfg)
        self._n_ring = self._cfg.mesh.shape[meshlib.SEQ_AXIS]
        if self.paged:
            if self.kv_pages % self._n_ring:
                raise ValueError(
                    f"kv_pages {self.kv_pages} must divide by the ring"
                    f" size {self._n_ring}: the pool shards over the "
                    f"page dim")
            self._efns = _paged_engine_fns(
                self._cfg, int(pad_id), self.kv_int8, self.draft_k,
                self.kv_page_size, self.kv_pages, n_slots)
        else:
            self._efns = _engine_fns(self._cfg, int(pad_id),
                                     self.kv_int8, self.draft_k)
        # a contiguous engine of a spec with an indexer prefills into the
        # reserved slot's own rows (`_engine_fns`): no pending prefill
        # owns a cache row
        self.in_place = not self.paged and self._cfg.spec.sparse
        self._params = _place_params(params, self._cfg.mesh,
                                     rules=partition_rules)
        # kept for hot weight swap (swap_params): a candidate tree is
        # placed under the SAME mesh/rules so the swapped-in leaves
        # carry identical shardings and no program recompiles
        self._partition_rules = partition_rules
        self.t_max = t_max
        self.n_slots = n_slots
        self.pad_id = int(pad_id)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        vocab = params["head"]["kernel"].shape[1]
        # the serving vocab, public: the scheduler's draft validation
        # bounds proposed ids by it, the CLI's --draft-ckpt gate
        # compares against it
        self.vocab = int(vocab)
        # the head multiplies into float32 whatever the weights are
        # (models/lm._final_logits)
        ldtype = jnp.float32
        rep = meshlib.replicated(self._cfg.mesh)
        # per-tenant adapter bank (serve/tenancy.py, ISSUE 14): the
        # stacked [T, V, r]/[T, r, V] logit-adapter factors, placed
        # replicated ONCE and fed read-only to every window/verify —
        # the programs gather each slot's tenant row by the traced
        # tslot ids, so tenant mixes are values, never shapes
        self._adapters = ()
        self.n_tenants = 0
        if adapter_bank is not None:
            u = np.asarray(adapter_bank.u, np.float32)
            v = np.asarray(adapter_bank.v, np.float32)
            if (u.ndim != 3 or v.ndim != 3 or u.shape[1] != vocab
                    or v.shape != (u.shape[0], u.shape[2], vocab)):
                raise ValueError(
                    f"adapter bank shapes must be u [T, V, r] / "
                    f"v [T, r, V] with V = the model vocab {vocab}, "
                    f"got {u.shape} / {v.shape} — a tenant adapter "
                    f"trained against a different head cannot serve "
                    f"this model")
            self.n_tenants = u.shape[0]
            self._adapters = (meshlib.put_with_sharding(u, rep),
                              meshlib.put_with_sharding(v, rep))
        # learned drafter (models/draft_lm.py, ROADMAP 2): its own
        # small per-slot ring caches + the batched propose/ingest
        # programs, riding the same insert/recycle/export-import
        # lifecycle as the target's state
        self._dcfg = self._dfns = self._dsfns = None
        self._draft_partition_rules = draft_partition_rules
        if draft_model is None:
            if draft_partition_rules is not None:
                raise ValueError(
                    "draft_partition_rules without draft_model: the "
                    "rules shard the learned drafter's params — pass "
                    "draft_model (models/draft_lm.DraftLM.learned) or "
                    "drop the rules")
        else:
            if self.draft_k is None:
                raise ValueError(
                    "a draft_model needs draft_k: its proposals feed "
                    "the speculative verify program, which only exists "
                    "on a spec-armed engine — build with draft_k=K")
            dparams = draft_model.params
            dconfig = draft_model.config
            dvocab = int(dparams["embed"].shape[0])
            if dvocab != vocab:
                raise ValueError(
                    f"draft model vocab {dvocab} != target vocab "
                    f"{vocab}: speculation verifies draft token IDS "
                    f"against the target's own picks, so the two "
                    f"models must share one tokenizer/vocab — distill "
                    f"the drafter from THIS target "
                    f"(models/draft_lm.distill_draft_lm)")
            d_seq = int(dparams["pos"].shape[0])
            if d_seq < t_max:
                raise ValueError(
                    f"draft model position table {d_seq} < engine "
                    f"t_max {t_max}: the drafter's ring mirrors the "
                    f"target's positions up to t_max — distill with "
                    f"draft_config(seq_len >= t_max)")
            self._dcfg = _serve_config(
                dparams, embed_dim=dconfig["embed_dim"],
                num_heads=dconfig["num_heads"],
                num_blocks=dconfig["num_blocks"], t_max=t_max,
                mesh=self._cfg.mesh, cache_dtype=cache_dtype,
                block_impl=block_impl, temperature=0.0, top_k=None)
            self._dfns = _drafter_fns(self._dcfg, int(pad_id),
                                      self.draft_k)
            self._dsfns = _serving_fns(self._dcfg)
            self._dparams = _place_params(dparams, self._dcfg.mesh,
                                          rules=draft_partition_rules)
            self._dadapters = ()
            dad = getattr(draft_model, "adapters", None)
            if dad is not None:
                du = np.asarray(dad[0], np.float32)
                dv = np.asarray(dad[1], np.float32)
                if self.n_tenants and du.shape[0] != self.n_tenants:
                    raise ValueError(
                        f"drafter adapter bank has {du.shape[0]} "
                        f"tenant rows but the engine serves "
                        f"{self.n_tenants} tenants — the traced-tid "
                        f"gather indexes both banks by the same slot "
                        f"tenant ids")
                self._dadapters = (meshlib.put_with_sharding(du, rep),
                                   meshlib.put_with_sharding(dv, rep))
            self._dcaches = self._dfns.init_caches(n_slots)
            # host-side drafter stream bookkeeping: _dfront[s] tokens
            # of slot s's history are ingested into the drafter ring;
            # _dpend[s] holds emitted-but-not-yet-ingested tokens
            # (invariant: _dfront + len(_dpend) == the slot's history
            # length == its target position)
            self._dpend: list[list[int]] = [[] for _ in range(n_slots)]
            self._dfront = np.zeros(n_slots, np.int64)
        # device state — placed under the canonical shardings every
        # engine program pins its outputs to (one jit cache key for the
        # whole loop), donated through every window/insert
        self._caches = self._efns.init_caches(n_slots)
        self._logits = meshlib.put_with_sharding(
            np.zeros((n_slots, vocab), ldtype), rep)
        self._kd = meshlib.put_with_sharding(
            np.zeros((n_slots, 2), np.uint32), rep)
        self._pos = meshlib.put_with_sharding(
            np.zeros(n_slots, np.int32), rep)
        self._rem = meshlib.put_with_sharding(
            np.zeros(n_slots, np.int32), rep)
        self._eos = meshlib.put_with_sharding(
            np.full(n_slots, -1, np.int32), rep)
        # per-slot tenant ids ([S] int32, tid 0 = the default tenant):
        # always present (a tiny row) so the insert scatter has ONE
        # signature; it only steers the adapter gather when a bank is
        # armed
        self._tslot = meshlib.put_with_sharding(
            np.zeros(n_slots, np.int32), rep)
        self._scales = self._efns.init_scales(n_slots)
        # host shadows (never fetched back from device)
        self._pos_h = np.zeros(n_slots, np.int64)
        self._rem_h = np.zeros(n_slots, np.int64)
        self._eos_h = np.full(n_slots, -1, np.int64)
        self._occupied = np.zeros(n_slots, bool)
        self._pending = None     # (toks_dev, rem_snapshot, occ_snapshot)
        # rollup of the most recently COLLECTED verify dispatch
        # ({drafted, accepted, emitted, slots}); None after a plain
        # window — the scheduler's metrics hook reads it per collect
        self.last_spec = None
        # expert-layer accounts (models/moe.py; None for a model without
        # expert layers): `last_moe` is the most recently COLLECTED
        # window's {held, touched, assigned, steps} as host arrays — the
        # scheduler's metrics hook reads it per collect, like last_spec —
        # and the router's picks of the last window and the last prefill
        # chunk stay on the device until `router_picks` asks for them
        self.last_moe = None
        self._moe_pending = None
        # cache rows the most recently COLLECTED window's attention read
        # (one full layer's, summed over its steps, on the device:
        # ring_decode.decode_rows_read) beside the rows a window that
        # stopped nowhere would have read; None after a verify and on a
        # paged engine. The scheduler's metrics hook reads it per
        # collect, like last_moe
        self.last_attn_rows = None
        self._rows_pending = None
        self._picks = {"window": None, "prefill": None}
        # indexer layers' accounts (None without them): `last_dsa` is the
        # most recently COLLECTED window's {share_sum, rows, fold_rows}
        # (selected over visible positions, summed over its live (step,
        # slot) pairs, their count, and the rows the fold ran for),
        # read per collect like last_moe; the
        # positions selected by the last window and the last prefill
        # chunk stay on the device until `selected_positions` asks
        self.last_dsa = None
        self._selected = {"window": None, "prefill": None}
        # in-place engines: slots released with device budget left,
        # whose ride-along has to stop before their rows are written
        self._riding = np.zeros(n_slots, bool)
        # in-progress chunked prefills: slot -> _PendingPrefill. These
        # slots are RESERVED (excluded from free_slots, not yet decoded
        # by windows) until the final chunk lands and insert scatters
        # the request into the batch row.
        self._prefills: dict[int, _PendingPrefill] = {}
        # paged-mode state: the host free-list allocator, the device
        # page table ([S, t_max/page_size] int32, -1 = unallocated),
        # and per-slot grant bookkeeping (page ids + token capacity)
        self._alloc = None
        if self.paged:
            self._alloc = PageAllocator(self.kv_pages,
                                        self.kv_page_size)
            self._l_pages = t_max // self.kv_page_size
            self._pt = meshlib.put_with_sharding(
                np.full((n_slots, self._l_pages), -1, np.int32), rep)
            self._slot_pages: dict[int, list[int]] = {}
            self._alloc_tokens = np.zeros(n_slots, np.int64)
            if prefix_cache is not None:
                prefix_cache.bind(self._alloc, self.kv_page_bytes())

    # -- slot lifecycle -------------------------------------------------

    def free_slots(self) -> list[int]:
        """Slots safe to admit into NOW. A slot released after a window
        was dispatched (deadline cancel) stays excluded until that
        window is collected — its in-flight tokens would otherwise be
        attributed to the newly admitted request."""
        in_flight = (self._pending[1][1] if self._pending is not None
                     else None)
        return [s for s in range(self.n_slots)
                if not self._occupied[s]
                and s not in self._prefills
                and (in_flight is None or not in_flight[s])]

    def occupancy(self) -> float:
        return float(self._occupied.sum()) / self.n_slots

    def finished(self, slot: int) -> bool:
        return bool(self._occupied[slot]) and self._rem_h[slot] == 0

    def release(self, slot: int) -> None:
        """Vacate a slot (EOS/budget done, or a deadline cancel). The
        row's device state is left as-is: a cancelled row at worst
        decodes its bounded remaining budget as a dead ride-along, and
        the next admit's insert overwrites the full row (dead rows never
        append or influence live ones — gated by test). On a paged
        engine the slot is first KILLED on device (page-table row
        cleared + device budget zeroed in one dispatch) and only then
        are its page references returned — the freed pages may be
        re-granted immediately, and a cancelled row with leftover
        device budget writing through a stale table would corrupt the
        new owner (the contiguous ride-along contract does not
        transfer to a shared pool; gated by test). Pages a
        prefix-cache snapshot still holds survive via their
        refcounts."""
        self._occupied[slot] = False
        if self.in_place and self._rem_h[slot] > 0:
            self._riding[slot] = True
        self._rem_h[slot] = 0
        if self._dfns is not None:
            # the drafter row's dead K/V stays, like the target row's:
            # the next admission's _draft_admit insert overwrites it
            self._dpend[slot] = []
            self._dfront[slot] = 0
        if self.paged:
            if slot in self._slot_pages:
                self._set_page_row(slot, [], kill=True)
            self._alloc.release(self._slot_pages.pop(slot, []))
            self._alloc_tokens[slot] = 0

    # -- mid-decode slot migration (elastic drain, ROADMAP 3) -----------

    @property
    def supports_slot_migration(self) -> bool:
        """True when a RUNNING slot's state can travel to a peer engine
        bit-exactly: contiguous float-KV rows only. Paged engines have
        no slot-granular KV export (pages belong to one shared pool and
        land through grant-time scatter, not a row insert), and int8
        rows would pass back through the insert path's quantization —
        neither can honor the bit-identity contract, so a drain on them
        finishes requests in place instead of migrating."""
        return (not self.paged and not self.kv_int8
                and self._cfg.spec.classic)

    def export_slot(self, slot: int) -> dict:
        """Snapshot a RUNNING slot as host numpy — the prefix
        registry's packed-KV handoff generalized past chunk boundaries
        to mid-decode: per-block K/V rows truncated to the slot's
        position, the last-token logits row, and the slot's raw rng KEY
        DATA mid-chain. The key data — not a seed — is the point: a
        seeded stream must resume exactly where the source's per-token
        splits left it for the migrated output to stay bit-identical to
        an unmigrated run (greedy consumes no randomness either way).
        A peer engine's `import_slot` resumes the request; the caller
        (scheduler/router) owns releasing this slot and the journal
        protocol around the gap.

        Needs the engine dispatch-idle (`Scheduler.quiesce()` is the
        safe point): after `begin_window` the host shadows lag the
        donated device state by one window, and a snapshot taken in
        that gap would pair post-window caches with pre-window
        positions."""
        self._cfg.spec.require_classic("slot migration (export_slot / "
                                       "import_slot)")
        if not self.supports_slot_migration:
            raise RuntimeError(
                "slot export needs a contiguous float-KV engine: paged "
                "pools have no slot-granular export program and int8 "
                "rows would re-quantize on import, breaking the "
                "bit-identity contract — drain this replica to "
                "completion instead of migrating")
        if self._pending is not None:
            raise RuntimeError(
                "export_slot with a window in flight would snapshot "
                "post-window caches against pre-window host shadows — "
                "quiesce() the scheduler first")
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.n_slots})")
        if not self._occupied[slot]:
            raise ValueError(f"slot {slot} is not occupied — only a "
                             f"running request has state to export")
        p = int(self._pos_h[slot])
        head_dim = self._cfg.embed_dim // self._cfg.num_heads
        snap = {
            "pos": p,
            "rem": int(self._rem_h[slot]),
            "eos": int(self._eos_h[slot]),
            "num_heads": self._cfg.num_heads,
            "head_dim": head_dim,
            "kd": np.asarray(self._kd[slot]).astype(np.uint32),
            "logits": np.asarray(self._logits[slot]),
            # truncated to the written positions (the packer idiom):
            # everything past `pos` in the source row is zeros the
            # import's pad re-creates, and masked out regardless
            "caches": tuple((np.asarray(kc[slot:slot + 1, :p]),
                             np.asarray(vc[slot:slot + 1, :p]))
                            for kc, vc in self._caches),
        }
        if self._dfns is not None:
            # the learned drafter's shadow state rides the same
            # handoff: ring rows truncated to the DRAFTER frontier
            # (everything past it is dead K/V) plus the host-side
            # frontier/pending-token shadows, so a migrated slot's
            # proposals are bit-identical to an unmigrated run
            df = int(self._dfront[slot])
            snap["draft"] = {
                "front": df,
                "pend": [int(t) for t in self._dpend[slot]],
                "num_heads": self._dcfg.num_heads,
                "head_dim": self._dcfg.embed_dim // self._dcfg.num_heads,
                "caches": tuple((np.asarray(kc[slot:slot + 1, :df]),
                                 np.asarray(vc[slot:slot + 1, :df]))
                                for kc, vc in self._dcaches),
            }
        return snap

    def import_slot(self, slot: int, snap: dict, *, tid: int = 0) -> None:
        """Adopt an exported slot snapshot into free `slot` through the
        NORMAL admission insert: the K/V rows pad back to `[1, t_max]`
        (`jnp.pad`, zeros past the position — exactly the layout the
        source row held) and land under this engine's ring sharding, so
        the executable is the one admission already compiled — zero new
        programs — and the resumed decode is bit-identical to never
        having moved (gated by test). The snapshot's position stands in
        for a fresh prefill's prompt length and its remaining budget
        for max_new_tokens; the raw key data resumes the rng chain
        mid-stream."""
        self._cfg.spec.require_classic("slot migration (export_slot / "
                                       "import_slot)")
        if not self.supports_slot_migration:
            raise RuntimeError(
                "slot import needs a contiguous float-KV engine (same "
                "restriction as export_slot) — this replica cannot "
                "adopt migrated slots")
        if self._pending is not None:
            raise RuntimeError(
                "import_slot with a window in flight — the caches were "
                "donated to the dispatch; quiesce()/collect first")
        if self._occupied[slot] or slot in self._prefills:
            raise ValueError(f"slot {slot} is not free")
        pos, rem, eos = int(snap["pos"]), int(snap["rem"]), int(snap["eos"])
        if rem < 1:
            raise ValueError(
                "snapshot has no remaining budget — the request already "
                "finished; deliver its Result instead of migrating it")
        if pos < 1 or pos + rem > self.t_max:
            raise ValueError(
                f"snapshot position {pos} + remaining budget {rem} does "
                f"not fit this engine's t_max {self.t_max} — migrate to "
                f"a replica with a cache at least as long as the source")
        head_dim = self._cfg.embed_dim // self._cfg.num_heads
        if (len(snap["caches"]) != self._cfg.num_blocks
                or snap["num_heads"] != self._cfg.num_heads
                or snap["head_dim"] != head_dim):
            raise ValueError(
                f"snapshot geometry (blocks={len(snap['caches'])}, "
                f"heads={snap['num_heads']}, head_dim="
                f"{snap['head_dim']}) does not match this engine "
                f"(blocks={self._cfg.num_blocks}, "
                f"heads={self._cfg.num_heads}, head_dim={head_dim}) — "
                f"slots only migrate between config-identical replicas")
        dsnap = snap.get("draft")
        if dsnap is None and self._dfns is not None:
            raise ValueError(
                "snapshot carries no learned-drafter state but this "
                "engine has a draft_model armed — resuming here would "
                "propose from an empty drafter cache and silently "
                "change acceptance; migrate between replicas with the "
                "same drafter configuration (or export from an engine "
                "with the drafter armed)")
        if dsnap is not None and self._dfns is None:
            raise ValueError(
                "snapshot carries learned-drafter state but this "
                "engine has no draft_model — its frontier and ring "
                "rows would be dropped and the resumed request would "
                "stop speculating; migrate between replicas with the "
                "same drafter configuration")
        if dsnap is not None:
            dhd = self._dcfg.embed_dim // self._dcfg.num_heads
            if (len(dsnap["caches"]) != self._dcfg.num_blocks
                    or dsnap["num_heads"] != self._dcfg.num_heads
                    or dsnap["head_dim"] != dhd):
                raise ValueError(
                    f"snapshot drafter geometry (blocks="
                    f"{len(dsnap['caches'])}, heads="
                    f"{dsnap['num_heads']}, head_dim="
                    f"{dsnap['head_dim']}) does not match this "
                    f"engine's draft model (blocks="
                    f"{self._dcfg.num_blocks}, heads="
                    f"{self._dcfg.num_heads}, head_dim={dhd}) — "
                    f"slots only migrate between config-identical "
                    f"replicas, drafter included")
        self._check_tid(tid)
        from idc_models_tpu.ring_decode import cache_sharding
        sh = cache_sharding(self._cfg.mesh)

        def _grow(a):
            a = jnp.asarray(np.asarray(a), self._cfg.cache_dtype)
            return meshlib.put_with_sharding(grow_cache(a, self.t_max), sh)

        caches1 = tuple((_grow(kc), _grow(vc))
                        for kc, vc in snap["caches"])
        logits1 = meshlib.put_with_sharding(
            np.asarray(snap["logits"])[None],
            meshlib.replicated(self._cfg.mesh))
        kd_row = np.asarray(snap["kd"], np.uint32).reshape(2)
        (self._caches, self._logits, self._kd, self._pos, self._rem,
         self._eos, self._tslot, self._scales) = self._efns.insert(
            self._caches, self._logits, self._kd, self._pos,
            self._rem, self._eos, self._tslot, self._scales,
            caches1, logits1, np.int32(slot), np.int32(pos),
            np.int32(rem), np.int32(eos), np.int32(tid), kd_row)
        self._pos_h[slot] = pos
        self._rem_h[slot] = rem
        self._eos_h[slot] = eos
        self._occupied[slot] = True
        if dsnap is not None:
            def _dgrow(a):
                a = jnp.asarray(np.asarray(a), self._dcfg.cache_dtype)
                return meshlib.put_with_sharding(
                    grow_cache(a, self.t_max), sh)

            drow = tuple((_dgrow(kc), _dgrow(vc))
                         for kc, vc in dsnap["caches"])
            self._dcaches = self._dfns.insert(self._dcaches, drow,
                                              np.int32(slot))
            self._dfront[slot] = int(dsnap["front"])
            self._dpend[slot] = [int(t) for t in dsnap["pend"]]

    def _validate_admit(self, slot, prompt, max_new_tokens, rng):
        """The one admission contract, shared by the monolithic and
        chunked paths: [1, P] int32 prompt, within-budget lengths, an
        rng when sampling, a genuinely free slot."""
        if self._occupied[slot]:
            raise ValueError(f"slot {slot} is occupied")
        if slot in self._prefills:
            raise ValueError(f"slot {slot} has a prefill in progress")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        if prompt.ndim != 2 or prompt.shape[0] != 1 or prompt.shape[1] < 1:
            raise ValueError(f"admit takes ONE non-empty [1, P] prompt, "
                             f"got shape {prompt.shape}")
        p_len = prompt.shape[1]
        if p_len > self.t_max:
            raise ValueError(f"prompt length {p_len} exceeds t_max "
                             f"{self.t_max}")
        if max_new_tokens < 1:
            raise ValueError(f"need max_new_tokens >= 1, got "
                             f"{max_new_tokens}")
        if p_len + max_new_tokens > self.t_max:
            raise ValueError(
                f"prompt {p_len} + max_new_tokens {max_new_tokens} "
                f"exceeds t_max {self.t_max} — the cache cannot grow at "
                f"decode time")
        if self.temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng "
                             "key (or integer seed) per request")
        return prompt

    def _check_tid(self, tid: int) -> None:
        """With an adapter bank armed, an out-of-range tenant id would
        gather a CLAMPED tenant's adapter (jnp.take clamps OOB
        indices) — silently serving the wrong tenant's head; caught at
        admission instead. Without a bank the tslot row steers nothing
        and any id is inert bookkeeping."""
        if self.n_tenants and not 0 <= tid < self.n_tenants:
            raise ValueError(
                f"tenant id {tid} out of range [0, {self.n_tenants}): "
                f"the adapter bank was built with {self.n_tenants} "
                f"tenants")

    def _insert(self, slot, caches1, logits1, p_len, max_new_tokens,
                eos_id, rng, tid: int = 0, prompt=None, tag=None) -> None:
        """Scatter a fully prefilled request into the batch row — the
        shared tail of both admission paths. `tid` is the request's
        tenant id (0 = default): a traced scalar into the tslot row,
        steering the window/verify adapter gather for this slot.
        `prompt` (the [P] token row) seeds the learned drafter's state
        for this slot when one is armed — both admission paths pass
        it; `import_slot` restores drafter state from its snapshot
        instead. `tag` (the rid) is stamped on the `serve.insert` span,
        which covers the host's side of the scatter: its dispatch."""
        with trace.span("serve.insert", slot=slot, rid=tag):
            eos = self.eos_id if eos_id is None else eos_id
            eos = -1 if eos is None else int(eos)
            kd_row = (_key_data(rng) if rng is not None
                      else np.zeros(2, np.uint32))
            if self.paged or self.in_place:
                # the prompt K/V already lives in the slot's pages (or,
                # in place, in its rows) — the insert is a scalar/row
                # scatter only
                (self._logits, self._kd, self._pos, self._rem,
                 self._eos, self._tslot) = self._efns.insert(
                    self._logits, self._kd, self._pos, self._rem,
                    self._eos, self._tslot, logits1, np.int32(slot),
                    np.int32(p_len), np.int32(max_new_tokens),
                    np.int32(eos), np.int32(tid), kd_row)
            else:
                (self._caches, self._logits, self._kd, self._pos,
                 self._rem, self._eos, self._tslot,
                 self._scales) = self._efns.insert(
                    self._caches, self._logits, self._kd, self._pos,
                    self._rem, self._eos, self._tslot, self._scales,
                    caches1, logits1, np.int32(slot), np.int32(p_len),
                    np.int32(max_new_tokens), np.int32(eos),
                    np.int32(tid), kd_row)
            self._pos_h[slot] = p_len
            self._rem_h[slot] = max_new_tokens
            self._eos_h[slot] = eos
            self._occupied[slot] = True
            if self._dfns is not None and prompt is not None:
                self._draft_admit(slot,
                                  np.asarray(prompt, np.int32).ravel())

    def _draft_admit(self, slot: int, prompt: np.ndarray) -> None:
        """Seed the learned drafter's row for a fresh admission: prefill
        the prompt MINUS its last token through the drafter's own
        bucketed prefill (the draft-dim `_serving_fns` — compile-once,
        any length), scatter the row in, and leave the last prompt
        token PENDING. Deferring that token is what makes the drafter
        stateless beyond its ring: the propose program's chunk ingest
        always has >= 1 pending token whose position-indexed logits
        seed draft 0, so no per-slot drafter logits row exists to
        carry, migrate, or invalidate."""
        p_len = prompt.shape[0]
        if p_len <= 1:
            row = self._dsfns.init_caches(1)
            front = 0
        else:
            bucket = prefill_bucket(p_len - 1, self.t_max, self._n_ring)
            padded = np.zeros((1, bucket), np.int32)
            padded[:, :p_len - 1] = prompt[None, :p_len - 1]
            _, row = self._dsfns.prefill(self._dparams, padded,
                                         np.int32(p_len - 1))
            front = p_len - 1
        self._dcaches = self._dfns.insert(self._dcaches, row,
                                          np.int32(slot))
        self._dfront[slot] = front
        self._dpend[slot] = [int(prompt[-1])]

    def admit(self, slot: int, prompt, max_new_tokens: int, *,
              rng=None, eos_id: int | None = None, tag=None,
              tid: int = 0) -> None:
        """Prefill `prompt` ([P] or [1, P]) and scatter it into `slot`,
        while every other slot's state stays put. `rng` seeds this
        REQUEST's sampling stream — an integer seed or the exact key a
        serial `Generator.decode` call would take. May be called while a
        window is in flight: the insert lands after it, and the slot
        (vacant in the flying window) starts decoding on the next one.

        Without `prefill_chunk` this is one bucketed prefill dispatch +
        one insert. With it, the whole prompt still lands in ONE call —
        ceil(P/C) chunk dispatches driven to completion here — which is
        the convenience path; a scheduler that wants to interleave
        chunks with decode windows drives `start_prefill`/`prefill_step`
        itself.

        `tag` is an opaque request label (the scheduler passes the rid)
        stamped onto the prefill spans, tying them into the request's
        lifecycle chain; the span TREE parenting (under serve.admit)
        is unchanged."""
        if self.prefill_chunk is not None:
            self.start_prefill(slot, prompt, max_new_tokens, rng=rng,
                               eos_id=eos_id, tag=tag, tid=tid)
            while not self.prefill_step(slot):
                pass
            return
        prompt = self._validate_admit(slot, prompt, max_new_tokens, rng)
        self._check_tid(tid)
        p_len = prompt.shape[1]
        # host-side prompt prep (the eager-jnp equivalent costs ~6 tiny
        # device dispatches per ADMISSION — measured to be a third of
        # the whole serve loop's wall at smoke scale): numpy pad to the
        # prefill bucket, hand the jitted prefill the numpy array
        bucket = prefill_bucket(p_len, self.t_max, self._n_ring)
        with trace.span("serve.prefill", slot=slot, p_len=p_len,
                        bucket=bucket, rid=tag):
            padded = np.zeros((1, bucket), np.int32)
            padded[:, :p_len] = prompt
            logits1, caches1 = self._sfns.prefill(self._params, padded,
                                                  np.int32(p_len))
            self._insert(slot, caches1, logits1, p_len, max_new_tokens,
                         eos_id, rng, tid, prompt=prompt[0], tag=tag)

    # -- chunked prefill --------------------------------------------------

    def start_prefill(self, slot: int, prompt, max_new_tokens: int, *,
                      rng=None, eos_id: int | None = None,
                      tag=None, tid: int = 0) -> None:
        """Reserve `slot` and register a chunked prefill for `prompt`
        WITHOUT dispatching anything: each later `prefill_step(slot)`
        runs exactly one chunk (the scheduler interleaves one per decode
        window, so a 16k-token prompt no longer stalls in-flight decodes
        behind one monolithic dispatch). Consults the prefix cache for
        the longest cached prefix — the suffix is all that will prefill.
        The slot is excluded from `free_slots` until the final chunk's
        insert (or `cancel_prefill`)."""
        if self.prefill_chunk is None:
            raise RuntimeError("engine built without prefill_chunk")
        # the span covers what admission costs the host before any
        # chunk runs: validation, the prefix lookup, and the request's
        # own cache row (contiguous) or page grant (paged)
        with trace.span("serve.start_prefill", slot=slot, rid=tag):
            prompt = self._validate_admit(slot, prompt, max_new_tokens,
                                          rng)
            self._check_tid(tid)
            if self.paged:
                self._start_prefill_paged(slot, prompt, max_new_tokens,
                                          rng, eos_id, tag, tid)
                return
            if self.in_place:
                # the chunks will write the slot's own rows: no cache
                # row is built, on the host or anywhere. A slot that
                # was released mid-budget stops riding along first
                if self._riding[slot]:
                    self._rem = self._efns.kill(self._rem, np.int32(slot))
                    self._riding[slot] = False
                self._prefills[slot] = _PendingPrefill(
                    prompt=prompt, budget=int(max_new_tokens), rng=rng,
                    eos_id=eos_id, caches=None, logits=None, next_start=0,
                    tag=tag, tid=tid)
                return
            start, caches, logits = 0, None, None
            if self.prefix_cache is not None:
                start, caches, logits = self.prefix_cache.lookup(
                    prompt[0])
                start = min(start, prompt.shape[1])
            if caches is None:
                caches = self._sfns.init_caches(1)
            self._prefills[slot] = _PendingPrefill(
                prompt=prompt, budget=int(max_new_tokens), rng=rng,
                eos_id=eos_id, caches=caches, logits=logits,
                next_start=start, tag=tag, tid=tid)

    def _pages_for(self, p_len: int, budget: int) -> int:
        """Pages an admission reserves: the prompt plus the decode
        reservation (the full budget unless kv_decode_reserve bounds
        it), on the page grid."""
        eff = (budget if self.kv_decode_reserve is None
               else min(budget, self.kv_decode_reserve))
        tokens = min(p_len + eff, self.t_max)
        return -(-tokens // self.kv_page_size)

    def pages_for_admission(self, p_len: int, budget: int) -> int:
        """Pages an admission of (p_len, budget) would reserve — 0 on
        contiguous engines. The scheduler's per-tenant page-budget
        accounting unit (serve/tenancy.py): exact under the default
        full-budget decode reserve, the admission-time floor under an
        optimistic `kv_decode_reserve` (mid-decode grant growth is not
        re-charged — documented in docs/MULTITENANCY.md)."""
        if not self.paged:
            return 0
        return self._pages_for(p_len, budget)

    def can_admit_pages(self, p_len: int, budget: int) -> bool:
        """The scheduler's page-aware admission gate: True when pages
        for `p_len` prompt tokens plus the decode reservation exist
        (reclaiming LRU prefix-cache snapshots if the free list alone
        is short). Conservative — a prefix-cache hit at the actual
        admission can only REDUCE the fresh-page need — so a True here
        guarantees `start_prefill` succeeds. Always True on a
        contiguous engine (slot availability is the only gate there).

        Evictions only happen when they can actually make the head
        admissible: a blocked head re-asking every cycle must not
        grind the whole cache away for zero admission benefit, so the
        gate first checks how many pages eviction could genuinely
        free (snapshot pages no live slot shares)."""
        if not self.paged:
            return True
        need = self._pages_for(p_len, budget)
        free = self._alloc.free_count()
        if free >= need:
            return True
        if (self.prefix_cache is None
                or free + self.prefix_cache.reclaimable_pages() < need):
            return False
        self.prefix_cache.reclaim(need - free)
        return self._alloc.free_count() >= need

    def _set_page_row(self, slot: int, pages: list[int], *,
                      kill: bool = False) -> None:
        row = np.full(self._l_pages, -1, np.int32)
        row[:len(pages)] = pages
        self._pt, self._rem = self._efns.page_row(
            self._pt, np.int32(slot), row, self._rem,
            np.int32(1 if kill else 0))

    def _stamp_decode_scales(self, pages: list[int], src: int) -> None:
        """int8 pools: freshly granted decode pages inherit the slot's
        last content-bearing page's per-head scale — a fresh page has
        no content to derive one from, and the append path quantizes
        with its target page's scale."""
        if not self.kv_int8 or not pages:
            return
        dst = np.full(self._l_pages, self.kv_pages, np.int32)
        dst[:len(pages)] = pages
        self._scales = self._efns.stamp_scales(self._scales,
                                               np.int32(src), dst)

    def _start_prefill_paged(self, slot, prompt, max_new_tokens, rng,
                             eos_id, tag, tid=0) -> None:
        """Paged admission: grant pages for prompt + reservation (the
        prefix-cache hit contributes its pages SHARED — refcounted,
        read-only, zero-copy), write the slot's page-table row, and
        register the pending prefill; chunks then stream straight into
        the granted pages."""
        p_len = prompt.shape[1]
        start, shared, logits = 0, [], None
        if self.prefix_cache is not None:
            start, shared, logits = self.prefix_cache.lookup(prompt[0])
            shared = list(shared or [])
        if shared:
            # the slot takes its OWN reference on the snapshot's pages:
            # release() drops it symmetrically whether or not the
            # snapshot is evicted while this request runs
            self._alloc.retain(shared)
        fresh_n = self._pages_for(p_len, max_new_tokens) - len(shared)
        fresh = self._alloc.alloc(fresh_n)
        if (fresh is None and self.prefix_cache is not None
                and (self._alloc.free_count()
                     + self.prefix_cache.reclaimable_pages())
                >= fresh_n):
            self.prefix_cache.reclaim(fresh_n
                                      - self._alloc.free_count())
            fresh = self._alloc.alloc(fresh_n)
        if fresh is None:
            if shared:
                self._alloc.release(shared)
            raise PageExhausted(
                f"admission needs {fresh_n} fresh pages, only "
                f"{self._alloc.free_count()} free — gate admissions "
                f"on can_admit_pages()")
        pages = shared + fresh
        self._set_page_row(slot, pages)
        self._alloc_tokens[slot] = len(pages) * self.kv_page_size
        self._prefills[slot] = _PendingPrefill(
            prompt=prompt, budget=int(max_new_tokens), rng=rng,
            eos_id=eos_id, caches=None, logits=logits,
            next_start=start, tag=tag, pages=pages,
            shared=len(shared), tid=tid)

    def prefill_step(self, slot: int) -> bool:
        """Advance `slot`'s pending prefill by ONE chunk dispatch;
        returns True when the request is fully admitted (final chunk +
        insert happen together — the insert is a cheap scatter). Each
        completed full-chunk boundary snapshots into the prefix cache,
        so the NEXT request sharing the prefix prefills only its
        suffix."""
        pend = self._prefills.get(slot)
        if pend is None:
            raise ValueError(f"slot {slot} has no prefill in progress")
        p_len = pend.prompt.shape[1]
        c = self.prefill_chunk
        if pend.next_start >= p_len:
            # whole prompt served from the prefix cache (p_len on a
            # chunk boundary): nothing to prefill, insert directly
            done = True
        else:
            end = min(pend.next_start + c, p_len)
            with trace.span("serve.prefill_chunk", slot=slot,
                            start=pend.next_start, end=end,
                            p_len=p_len, rid=pend.tag):
                padded = np.zeros((1, c), np.int32)
                padded[:, :end - pend.next_start] = pend.prompt[
                    :, pend.next_start:end]
                if self.paged:
                    # direct-to-pool: the chunk program resolves the
                    # slot's pages through the table and writes K/V
                    # straight into them — no [1, t_max] intermediate
                    pend.logits, self._caches, new_scales = (
                        self._efns.prefill_chunk(
                            self._params, self._caches, self._pt,
                            self._scales, np.int32(slot), padded,
                            np.int32(pend.next_start), np.int32(end)))
                    if self.kv_int8:
                        self._scales = new_scales
                elif self.in_place:
                    (pend.logits, self._caches, self._picks["prefill"],
                     self._selected["prefill"]) = self._efns.prefill_chunk(
                        self._params, self._caches, np.int32(slot),
                        padded, np.int32(pend.next_start), np.int32(end))
                else:
                    (pend.logits, pend.caches,
                     self._picks["prefill"]) = self._sfns.prefill_chunk(
                        self._params, pend.caches, padded,
                        np.int32(pend.next_start), np.int32(end))
                pend.next_start = end
                if (self.prefix_cache is not None and end % c == 0):
                    if self.paged:
                        # the snapshot IS the slot's pages [0, end):
                        # page-aligned, fully written, never written
                        # again — sharing them costs refcounts, not
                        # copies
                        self.prefix_cache.insert(
                            pend.prompt[0, :end],
                            pend.pages[:end // self.kv_page_size],
                            pend.logits)
                    else:
                        self.prefix_cache.insert(pend.prompt[0, :end],
                                                 pend.caches,
                                                 pend.logits)
            done = pend.next_start >= p_len
        if done:
            del self._prefills[slot]
            if self.paged:
                self._slot_pages[slot] = pend.pages
                n_prompt = -(-p_len // self.kv_page_size)
                self._stamp_decode_scales(pend.pages[n_prompt:],
                                          pend.pages[n_prompt - 1])
            self._insert(slot, pend.caches, pend.logits, p_len,
                         pend.budget, pend.eos_id, pend.rng, pend.tid,
                         prompt=pend.prompt, tag=pend.tag)
        return done

    def cancel_prefill(self, slot: int) -> None:
        """Drop a pending prefill (deadline hit while still chunking):
        the partial caches are discarded and the slot returns to
        free_slots immediately — nothing ever reached the batch row.
        A paged engine returns the grant to the allocator (snapshot-
        shared pages survive via their cache refs)."""
        pend = self._prefills.pop(slot, None)
        if pend is not None and self.paged and pend.pages:
            # the slot's device row is already dead (it never reached
            # insert), but its table row points at the dying grant —
            # clear it before the pages can be re-granted
            self._set_page_row(slot, [], kill=True)
            self._alloc.release(pend.pages)
            self._alloc_tokens[slot] = 0

    def prefilling(self) -> list[int]:
        """Slots with a chunked prefill in progress, admission order."""
        return list(self._prefills)

    # -- decode ---------------------------------------------------------

    def begin_window(self, n_steps: int) -> None:
        """Dispatch ONE fused masked window (async) — up to `n_steps`
        tokens per slot. `collect` returns its tokens; at most one
        window may be in flight."""
        if self._pending is not None:
            raise RuntimeError("a window is already in flight — "
                               "collect() it first")
        if n_steps < 1:
            raise ValueError(f"need n_steps >= 1, got {n_steps}")
        snapshot = (self._rem_h.copy(), self._occupied.copy(),
                    self._eos_h.copy())
        if self.paged:
            (toks, self._caches, self._logits, self._kd, self._pos,
             self._rem, _, _) = self._efns.window(
                self._params, self._caches, self._pt, self._logits,
                self._kd, self._pos, self._rem, self._eos,
                self._scales, self._adapters, self._tslot, n_steps)
        else:
            (toks, self._caches, self._logits, self._kd, self._pos,
             self._rem, stats, rows) = self._efns.window(
                self._params, self._caches, self._logits, self._kd,
                self._pos, self._rem, self._eos, self._scales,
                self._adapters, self._tslot, n_steps)
            if not isinstance(rows, tuple):   # () without full layers
                self._rows_pending = (
                    rows, n_steps * self.n_slots * self.t_max)
            if stats:
                # handed back with the window's tokens: collect()
                # fetches the counts, the picks and the selected
                # positions stay where they are
                self._moe_pending = dict(stats)
                self._picks["window"] = self._moe_pending.pop("picks", None)
                self._selected["window"] = self._moe_pending.pop(
                    "dsa_selected", None)
        self._pending = (toks, snapshot)

    def spec_room(self, slot: int) -> bool:
        """True when `slot` has cache room for a full verify — K draft
        appends plus the bonus token's append all land inside t_max.
        Slots without room (within draft_k tokens of the cache edge,
        hence within draft_k + 1 of finishing) must decode through
        plain windows instead; the scheduler's policy falls back for
        the whole batch so no slot starves behind its speculating
        neighbors."""
        if self.draft_k is None:
            return False
        return bool(self._pos_h[slot] + self.draft_k + 1 <= self.t_max)

    def propose_all(self):
        """LEARNED proposals for every speculating slot in ONE device
        round-trip: drain each slot's pending emitted tokens (queued by
        collect(), see `_note_emitted`) into the drafter's ring caches,
        then roll the drafter `draft_k` greedy steps for ALL qualifying
        slots in a single jitted dispatch. Returns `(drafts, live)` —
        int32 [n_slots, draft_k] proposals plus the bool mask of rows
        they are real for — or None when no slot qualifies this cycle.

        The steady state (every slot emitted <= draft_k + 1 tokens
        last cycle, the verify maximum) is exactly one `propose`
        dispatch; a deeper backlog (plain-window fallback cycles, a
        fresh admission's deferred prompt token) drains through
        fixed-width `ingest` rounds first, REMAINDER-FIRST per slot so
        every live slot's final chunk lands in the single shared final
        round with 1..C real tokens. Only slots with `spec_room` are
        proposed for — beyond keeping proposals useful, that bound is
        what keeps every chunk splice inside t_max (pos0 + C <= t_max
        needs pos + draft_k + 1 <= t_max)."""
        if self._dfns is None:
            raise RuntimeError(
                "propose_all() requires a learned drafter: build the "
                "engine with draft_model= (a models/draft_lm.DraftLM) "
                "— host-side drafters (NGramDrafter) propose via "
                "their own propose(history) instead")
        if self._pending is not None:
            raise RuntimeError("a window is already in flight — "
                               "collect() it first")
        C = self.draft_k + 1
        live = np.array([
            bool(self._occupied[s]) and self._rem_h[s] >= 1
            and len(self._dpend[s]) > 0 and self.spec_room(s)
            for s in range(self.n_slots)])
        if not live.any():
            return None
        pend = {int(s): np.asarray(self._dpend[s], np.int32)
                for s in np.flatnonzero(live)}
        offs = dict.fromkeys(pend, 0)
        rounds = max(-(-len(p) // C) for p in pend.values())
        with trace.span("serve.propose", slots=int(live.sum()),
                        rounds=rounds):
            for r in range(rounds - 1):
                left = rounds - r
                toks = np.zeros((self.n_slots, C), np.int32)
                pos0 = np.zeros(self.n_slots, np.int32)
                rlive = np.zeros(self.n_slots, bool)
                for s, p in pend.items():
                    remaining = len(p) - offs[s]
                    if remaining <= (left - 1) * C:
                        continue
                    n = remaining - (left - 1) * C
                    toks[s, :n] = p[offs[s]:offs[s] + n]
                    pos0[s] = self._dfront[s] + offs[s]
                    rlive[s] = True
                    offs[s] += n
                self._dcaches = self._dfns.ingest(
                    self._dparams, self._dcaches, toks, pos0, rlive)
            toks = np.zeros((self.n_slots, C), np.int32)
            pos0 = np.zeros(self.n_slots, np.int32)
            n_new = np.zeros(self.n_slots, np.int32)
            for s, p in pend.items():
                n = len(p) - offs[s]
                toks[s, :n] = p[offs[s]:]
                pos0[s] = self._dfront[s] + offs[s]
                n_new[s] = n
            self._dcaches, drafts = self._dfns.propose(
                self._dparams, self._dcaches, self._dadapters,
                self._tslot, toks, n_new, pos0, live)
            drafts = np.asarray(drafts)
        for s in pend:
            self._dfront[s] += len(self._dpend[s])
            self._dpend[s] = []
        return drafts.astype(np.int32), live

    def ensure_decode_room(self, n_tokens: int) -> list[int]:
        """Paged engines only (contiguous rooms are sized at admission
        — returns []): grow every occupied slot's page grant so the
        next dispatch can emit up to min(n_tokens, remaining budget)
        tokens without writing an unallocated page. Returns the slots
        that could NOT be granted after exhausting the free list and
        the prefix cache's reclaimable snapshots — the scheduler
        quarantines those (finish or retry honestly) BEFORE
        dispatching, so a starved slot can never corrupt a neighbor's
        pages (an unallocated append would be dropped, not misplaced,
        but the emitted token would be attention-blind to it — hence
        the hard gate). With the default full-budget reservation this
        is a no-op; it only grants when kv_decode_reserve admitted
        optimistically."""
        if not self.paged:
            return []
        failed = []
        ps = self.kv_page_size
        for slot in range(self.n_slots):
            if not self._occupied[slot] or self._rem_h[slot] < 1:
                continue
            target = int(self._pos_h[slot]
                         + min(int(n_tokens), int(self._rem_h[slot])))
            if target <= self._alloc_tokens[slot]:
                continue
            need = -(-(target - int(self._alloc_tokens[slot])) // ps)
            fresh = self._alloc.alloc(need)
            if (fresh is None and self.prefix_cache is not None
                    and (self._alloc.free_count()
                         + self.prefix_cache.reclaimable_pages())
                    >= need):
                self.prefix_cache.reclaim(need
                                          - self._alloc.free_count())
                fresh = self._alloc.alloc(need)
            if fresh is None:
                failed.append(slot)
                continue
            pages = self._slot_pages[slot]
            self._stamp_decode_scales(fresh, pages[-1])
            pages.extend(fresh)
            self._set_page_row(slot, pages)
            self._alloc_tokens[slot] = len(pages) * ps
        return failed

    def begin_verify(self, drafts, vlive, proposed=None) -> None:
        """Dispatch ONE speculative verify (async, collected like a
        window): `drafts` is int32 [n_slots, draft_k] and `vlive` bool
        [n_slots] marks the participating rows. Every vlive row must
        be occupied, have budget left, and satisfy `spec_room`;
        non-participating rows ride along bit-untouched. Each vlive
        row emits between 1 and draft_k + 1 tokens — the accepted
        draft prefix plus the model's own pick at the first
        disagreement — so a row whose drafts all miss still advances
        exactly one (bit-identical) token.

        `proposed` (bool [n_slots], default = vlive, must be a subset
        of it) marks the rows whose drafts came from a REAL drafter
        proposal rather than the scheduler's ride-along placeholder —
        only those rows enter the `last_spec` drafted/accepted ledger,
        so acceptance rate and tokens-per-dispatch score speculation
        itself, undiluted by slots that merely rode along for their
        one window-equivalent token."""
        if self.draft_k is None:
            raise RuntimeError("engine built without draft_k — "
                               "speculative decoding is not armed")
        if self._pending is not None:
            raise RuntimeError("a window is already in flight — "
                               "collect() it first")
        drafts = np.asarray(drafts, np.int32)
        vlive = np.asarray(vlive, bool)
        if drafts.shape != (self.n_slots, self.draft_k):
            raise ValueError(
                f"drafts must be [{self.n_slots}, {self.draft_k}], "
                f"got {drafts.shape}")
        if vlive.shape != (self.n_slots,):
            raise ValueError(f"vlive must be [{self.n_slots}], got "
                             f"{vlive.shape}")
        proposed = (vlive if proposed is None
                    else np.asarray(proposed, bool))
        if proposed.shape != vlive.shape or (proposed & ~vlive).any():
            raise ValueError("proposed must be a [n_slots] subset of "
                             "vlive")
        for s in np.flatnonzero(vlive):
            if not self._occupied[s] or self._rem_h[s] < 1:
                raise ValueError(f"verify slot {int(s)} is not "
                                 f"occupied with budget left")
            if not self.spec_room(int(s)):
                raise ValueError(
                    f"verify slot {int(s)} at pos {self._pos_h[s]} "
                    f"lacks room for {self.draft_k} drafts + the "
                    f"bonus before t_max {self.t_max}")
        snapshot = (self._rem_h.copy(), self._occupied.copy(),
                    self._eos_h.copy())
        if self.paged:
            (toks, n_emit, n_acc, self._caches, self._logits, self._kd,
             self._pos, self._rem) = self._efns.verify(
                self._params, self._caches, self._pt, self._logits,
                self._kd, self._pos, self._rem, self._eos,
                self._scales, self._adapters, self._tslot, drafts,
                vlive)
        else:
            (toks, n_emit, n_acc, self._caches, self._logits, self._kd,
             self._pos, self._rem) = self._efns.verify(
                self._params, self._caches, self._logits, self._kd,
                self._pos, self._rem, self._eos, self._scales,
                self._adapters, self._tslot, drafts, vlive)
        self._pending = (toks, snapshot, (n_emit, n_acc, vlive,
                                          proposed))

    def abort_window(self) -> None:
        """Discard an in-flight window without collecting it — the
        failure-cleanup hook (scheduler._abort_running): after an
        engine error the window's results are lost either way, but a
        window still marked in flight would wedge idle()/collect()
        forever. The host budget/position shadows keep their
        pre-dispatch values (the window never 'happened')."""
        self._pending = None
        self._moe_pending = None
        self._rows_pending = None

    def collect(self) -> dict[int, list[int]]:
        """Block on the in-flight window's tokens ({} if none) and
        replay the device retirement rule onto the host shadows: live
        steps are a prefix of the window (budgets only count down), and
        an EOS hit zeroes the remaining budget after emitting. Returns
        {slot: tokens emitted} for slots occupied when the window was
        dispatched."""
        # reset FIRST: a no-op collect (or a window's) must not leave a
        # previous verify's rollup answering for it — warmup's dead
        # verify would otherwise leak a zero-slot record into the
        # first real cycle's metrics
        self.last_spec = None
        self.last_moe = None
        self.last_dsa = None
        self.last_attn_rows = None
        if self._pending is None:
            return {}
        toks, (rem_before, occupied, eos_h), *spec = self._pending
        self._pending = None
        moe_stats, self._moe_pending = self._moe_pending, None
        rows, self._rows_pending = self._rows_pending, None
        # the ONE host transfer — and the point where the serve loop
        # BLOCKS on the in-flight window's device execution, so it is
        # bracketed as device.sync for step-time attribution
        # (observe/profile.py DeviceTimeline; no-op span when no
        # tracer is armed)
        with trace.span("device.sync"):
            toks = np.asarray(toks)
            if moe_stats is not None:
                got = jax.device_get(moe_stats)
                if "dsa_rows" in got:
                    self.last_dsa = {"share_sum": got.pop("dsa_share_sum"),
                                     "rows": got.pop("dsa_rows"),
                                     "fold_rows": got.pop("dsa_fold_rows")}
                self.last_moe = got or None
            if rows is not None:
                self.last_attn_rows = (int(rows[0]), rows[1])
            if spec:
                n_emit = np.asarray(spec[0][0])
                n_acc = np.asarray(spec[0][1])
        out = {}
        if spec:
            # verify collect: the device already applied budget + EOS
            # truncation (n_emit is the exact emitted count, EOS
            # inclusive); the host replays the same retirement rule on
            # its shadows from the fetched counts
            vlive, proposed = spec[0][2], spec[0][3]
            # ledger over PROPOSED rows only: ride-along placeholders
            # would dilute the acceptance figures operators tune by
            self.last_spec = {
                "drafted": int(proposed.sum()) * self.draft_k,
                "accepted": int(n_acc[proposed].sum()),
                "emitted": int(n_emit[proposed].sum()),
                "slots": int(proposed.sum()),
            }
            for s in range(self.n_slots):
                if not occupied[s]:
                    continue
                if not vlive[s]:
                    out[s] = []          # rode along bit-untouched
                    continue
                n = int(n_emit[s])
                row = [int(t) for t in toks[s, :n]]
                if eos_h[s] >= 0 and eos_h[s] in row:
                    self._rem_h[s] = 0
                else:
                    self._rem_h[s] = rem_before[s] - n
                self._pos_h[s] += n
                out[s] = row
            self._note_emitted(out)
            return out
        for s in range(self.n_slots):
            if not occupied[s]:
                continue
            n = int(min(rem_before[s], toks.shape[1]))
            row = [int(t) for t in toks[s, :n]]
            if eos_h[s] >= 0 and eos_h[s] in row:
                row = row[:row.index(int(eos_h[s])) + 1]
                self._rem_h[s] = 0
            else:
                self._rem_h[s] = rem_before[s] - len(row)
            self._pos_h[s] += len(row)
            out[s] = row
        self._note_emitted(out)
        return out

    def _note_emitted(self, out: dict[int, list[int]]) -> None:
        """Queue this cycle's emitted tokens for the learned drafter.
        The drafter's ring caches ingest them lazily — one chunked
        dispatch for ALL slots at the start of the next propose_all()
        — so collect() never touches the device on the drafter's
        behalf and spec-off serving pays nothing."""
        if self._dfns is None:
            return
        for s, row in out.items():
            if row:
                self._dpend[s].extend(row)

    def step_window(self, n_steps: int) -> dict[int, list[int]]:
        """Synchronous window: begin + collect in one call."""
        self.begin_window(n_steps)
        return self.collect()

    # -- resilience hooks -----------------------------------------------

    def slot_health(self) -> np.ndarray:
        """Per-slot fault codes ([n_slots] int32, see `HEALTH_KINDS`):
        0 healthy, 1 non-finite last-token logits, 2 finite but
        magnitude-blown. One tiny jitted reduce + one [S]-int fetch —
        the scheduler runs it once per cycle when health checks are
        armed, BEFORE the next window dispatch, so a poisoned slot is
        quarantined before a single token is sampled from its
        corrupted logits."""
        return np.asarray(self._efns.health(self._logits))

    def slot_invariants_ok(self, slot: int) -> bool:
        """Host-shadow sanity for one slot: position within the cache,
        budget non-negative. Free (no device traffic) — the scheduler
        folds it into the same per-cycle health pass."""
        return bool(0 <= self._pos_h[slot] <= self.t_max
                    and self._rem_h[slot] >= 0)

    def inject_slot_fault(self, slot: int, kind: str) -> None:
        """Fault-injection hook (serve/faults.py, default-off): corrupt
        `slot`'s last-token logits row in place — NaN for
        ``nan_logits``, huge-but-finite (1e32, past the health bound
        but inside every float dtype's range) for ``garbage_logits``.
        The host round-trip is fine here: this runs only when a fault
        plan fires, never on the clean path."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.n_slots})")
        try:
            val = {"nan_logits": float("nan"),
                   "garbage_logits": 1e32}[kind]
        except KeyError:
            raise ValueError(
                f"inject_slot_fault kind must be 'nan_logits' or "
                f"'garbage_logits', got {kind!r}") from None
        rep = meshlib.replicated(self._cfg.mesh)
        logits = np.array(self._logits)      # blocks on any in-flight window
        logits[slot, :] = val
        self._logits = meshlib.put_with_sharding(logits, rep)

    # -- hot weight rollout (ROADMAP 4) ---------------------------------

    def swap_params(self, params) -> None:
        """Hot-swap the serving weights. The candidate tree must match
        the live one leaf-for-leaf in name/shape/dtype — it is placed
        under the SAME mesh and partition rules, so every compiled
        program keys identically and the swap costs zero recompiles.
        Safe with a dispatch in flight: the dispatched window holds
        immutable references to the old leaves and lands its tokens
        untouched; the NEXT dispatch reads the new weights. In-flight
        slots keep their KV caches — their remaining tokens decode
        under the new weights (the zero-downtime contract: no slot
        dropped, no request re-prefilled)."""
        from idc_models_tpu import partition

        live = {n: (tuple(a.shape), jnp.result_type(a.dtype))
                for n, a in partition.tree_paths(self._params)}
        cand = {n: (tuple(np.shape(a)),
                    jnp.result_type(getattr(a, "dtype", np.asarray(a).dtype)))
                for n, a in partition.tree_paths(params)}
        if live != cand:
            only_live = sorted(set(live) - set(cand))
            only_cand = sorted(set(cand) - set(live))
            diff = sorted(n for n in set(live) & set(cand)
                          if live[n] != cand[n])
            raise ValueError(
                f"swap_params candidate does not match the serving "
                f"tree: live-only leaves {only_live}, candidate-only "
                f"{only_cand}, shape/dtype mismatches "
                f"{[(n, live[n], cand[n]) for n in diff]} — a rollout "
                f"swaps WEIGHTS, not architectures; rebuild the server "
                f"for a different model")
        self._params = _place_params(params, self._cfg.mesh,
                                     rules=self._partition_rules)

    def swap_adapters(self, u, v) -> None:
        """Per-tenant adapter hot-swap — the cheap first rung of a
        rollout: replace the [T, V, r]/[T, r, V] logit-adapter bank.
        Safe mid-dispatch for the same reason as swap_params (the
        in-flight window holds the old bank by reference). T must
        equal the serving tenant count (the bank rows are gathered by
        registered tenant id) and V/r must match the armed bank's
        shapes (shapes are jit cache keys — a different rank would
        recompile every window mid-traffic)."""
        if self.n_tenants == 0:
            raise ValueError(
                "adapter hot-swap needs a multi-tenant server: this "
                "engine was built without an adapter bank (tenancy), "
                "so there are no adapter rows to replace — roll out "
                "full params instead (swap_params)")
        u = np.asarray(u, np.float32)
        v = np.asarray(v, np.float32)
        old_u, old_v = self._adapters
        if u.shape != old_u.shape or v.shape != old_v.shape:
            raise ValueError(
                f"adapter swap shapes {u.shape} / {v.shape} must equal "
                f"the armed bank's {tuple(old_u.shape)} / "
                f"{tuple(old_v.shape)} (T = registered tenants, V = "
                f"model vocab, r = adapter rank are all compiled "
                f"shapes) — retrain/re-export at the serving shapes, "
                f"or rebuild the server to change them")
        rep = meshlib.replicated(self._cfg.mesh)
        self._adapters = (meshlib.put_with_sharding(u, rep),
                          meshlib.put_with_sharding(v, rep))

    def spot_check_params(self, params) -> dict:
        """Greedy spot-check of CANDIDATE weights on this engine's
        already-compiled prefill program and scratch state — no live
        slot, cache row, or logit is touched (paged engines replay the
        warmup's bit-level no-op chunk, p_end=0, so every pool write
        drops). The staging gate of a rollout: bad weights (NaN/inf,
        blown magnitudes) are caught HERE, before a single client
        request routes onto them. Returns {"ok", "code", "max_abs"}
        with codes mirroring slot_health: 0 healthy, 1 non-finite
        logits, 2 finite but magnitude-blown (> 1e30). On a PAGED
        engine the check replays the pool-state chunk program, so it
        needs the engine dispatch-idle (Scheduler.quiesce() collects
        the in-flight window without starting another)."""
        placed = _place_params(params, self._cfg.mesh,
                               rules=self._partition_rules)
        if self.paged:
            if self._pending is not None:
                raise RuntimeError(
                    "spot_check_params on a paged engine needs the "
                    "in-flight dispatch collected first (the pool "
                    "caches were donated to it) — call the "
                    "scheduler's quiesce() and retry")
            c = self.prefill_chunk
            logits, self._caches, sc = self._efns.prefill_chunk(
                placed, self._caches, self._pt, self._scales,
                np.int32(0), np.zeros((1, c), np.int32),
                np.int32(0), np.int32(0))
            if self.kv_int8:
                self._scales = sc
        elif self.in_place:
            raise ValueError(
                "the rollout spot-check runs a chunk into a scratch "
                "cache row; an engine that prefills in place has none")
        elif self.prefill_chunk is not None:
            c = self.prefill_chunk
            caches1 = self._sfns.init_caches(1)
            logits, _, _ = self._sfns.prefill_chunk(
                placed, caches1, np.zeros((1, c), np.int32),
                np.int32(0), np.int32(c))
        else:
            b = prefill_buckets(self.t_max, self._n_ring)[0]
            logits, _ = self._sfns.prefill(
                placed, np.zeros((1, b), np.int32), np.int32(b))
        row = np.asarray(jax.device_get(logits)).astype(np.float64)
        max_abs = float(np.max(np.abs(row[np.isfinite(row)]))
                        if np.isfinite(row).any() else np.inf)
        if not np.isfinite(row).all():
            return {"ok": False, "code": 1, "max_abs": max_abs}
        if max_abs > 1e30:
            return {"ok": False, "code": 2, "max_abs": max_abs}
        return {"ok": True, "code": 0, "max_abs": max_abs}

    # -- observability --------------------------------------------------

    @property
    def _efns_jit(self):
        """The shared jitted engine namespace, through any AOT overlay
        — introspection (`_cache_size`, `.lower`) lives on the jitted
        functions, not on deserialized executables."""
        return getattr(self._efns, "_base", self._efns)

    @property
    def _sfns_jit(self):
        return getattr(self._sfns, "_base", self._sfns)

    @property
    def _dfns_jit(self):
        return getattr(self._dfns, "_base", self._dfns)

    @property
    def _dsfns_jit(self):
        return getattr(self._dsfns, "_base", self._dsfns)

    def cache_sizes(self) -> dict:
        """Jit-cache entry counts for the no-recompile contract: after
        warmup, admitting requests of ANY prompt length/budget into any
        slot must not grow these (gated by test). With an AOT compile
        cache armed the overlaid programs never enter the jit cache at
        all — their counts stay 0 and the no-growth contract holds
        trivially."""
        efns, sfns = self._efns_jit, self._sfns_jit
        out = {"window": efns.window._cache_size(),
               "insert": efns.insert._cache_size(),
               "health": efns.health._cache_size()}
        if self.paged:
            # the paged admission path: direct-to-pool chunks + the
            # grant-path programs (no bucketed monolithic prefill)
            out["prefill_chunk"] = efns.prefill_chunk._cache_size()
            out["page_row"] = efns.page_row._cache_size()
            if self.kv_int8:
                out["stamp_scales"] = (
                    efns.stamp_scales._cache_size())
        else:
            out["prefill"] = sfns.prefill._cache_size()
            if self.prefill_chunk is not None:
                out["prefill_chunk"] = (
                    sfns.prefill_chunk._cache_size())
        if self.draft_k is not None:
            out["verify"] = efns.verify._cache_size()
        if self._dfns is not None:
            # the learned drafter's programs ride the same contract:
            # mixed draft-hit patterns (deep backlogs, fresh
            # admissions, all-miss cycles) must not grow these
            out["propose"] = self._dfns_jit.propose._cache_size()
            out["draft_ingest"] = self._dfns_jit.ingest._cache_size()
            out["draft_insert"] = self._dfns_jit.insert._cache_size()
            out["draft_prefill"] = self._dsfns_jit.prefill._cache_size()
        return out

    def program_costs(self, window: int) -> dict:
        """Cost/memory accounts of the engine's compiled programs
        (observe/profile.py ProgramCost): the fused masked decode
        window at `window` steps and the admission prefill (the chunk
        program when chunked, else the full-bucket monolithic shape).
        Lowers ACCOUNTING copies against the live state shapes —
        suppressed from the compile watchdog, registered in the
        process PROGRAMS table. The profile CLI verb's serve mode
        feeds these into its roofline verdicts."""
        from idc_models_tpu.observe import profile as prof

        out = {}
        with prof.compiling(None):
            if self.paged:
                # paged programs register under their own names so the
                # profile serve verb can put the gather-indirection
                # cost NEXT TO the contiguous serve.window figure
                out["serve.window_paged"] = prof.register_program(
                    "serve.window_paged",
                    self._efns_jit.window.lower(
                        self._params, self._caches, self._pt,
                        self._logits, self._kd, self._pos, self._rem,
                        self._eos, self._scales, self._adapters,
                        self._tslot, window).compile())
                out["serve.insert_paged"] = prof.register_program(
                    "serve.insert_paged",
                    self._efns_jit.insert.lower(
                        self._logits, self._kd, self._pos, self._rem,
                        self._eos, self._tslot,
                        jnp.zeros((1, self._logits.shape[1]),
                                  self._logits.dtype),
                        np.int32(0), np.int32(0), np.int32(0),
                        np.int32(-1), np.int32(0),
                        np.zeros(2, np.uint32)).compile())
                c = self.prefill_chunk
                out["serve.prefill_chunk_paged"] = prof.register_program(
                    "serve.prefill_chunk_paged",
                    self._efns_jit.prefill_chunk.lower(
                        self._params, self._caches, self._pt,
                        self._scales, np.int32(0),
                        np.zeros((1, c), np.int32), np.int32(0),
                        np.int32(c)).compile())
                if self.draft_k is not None:
                    out["lm.verify"] = prof.register_program(
                        "lm.verify",
                        self._efns_jit.verify.lower(
                            self._params, self._caches, self._pt,
                            self._logits, self._kd, self._pos,
                            self._rem, self._eos, self._scales,
                            self._adapters, self._tslot,
                            np.zeros((self.n_slots, self.draft_k),
                                     np.int32),
                            np.zeros(self.n_slots, bool)).compile())
                self._register_propose_cost(out, prof)
                return out
            out["serve.window"] = prof.register_program(
                "serve.window",
                self._efns_jit.window.lower(
                    self._params, self._caches, self._logits, self._kd,
                    self._pos, self._rem, self._eos, self._scales,
                    self._adapters, self._tslot, window).compile())
            if self.in_place:
                c = self.prefill_chunk
                out["serve.prefill_chunk"] = prof.register_program(
                    "serve.prefill_chunk",
                    self._efns_jit.prefill_chunk.lower(
                        self._params, self._caches, np.int32(0),
                        np.zeros((1, c), np.int32), np.int32(0),
                        np.int32(c)).compile())
            elif self.prefill_chunk is not None:
                c = self.prefill_chunk
                caches1 = self._sfns.init_caches(1)
                out["serve.prefill_chunk"] = prof.register_program(
                    "serve.prefill_chunk",
                    self._sfns_jit.prefill_chunk.lower(
                        self._params, caches1,
                        np.zeros((1, c), np.int32), np.int32(0),
                        np.int32(c)).compile())
            else:
                out["serve.prefill"] = prof.register_program(
                    "serve.prefill",
                    self._sfns_jit.prefill.lower(
                        self._params,
                        np.zeros((1, self.t_max), np.int32),
                        np.int32(self.t_max)).compile())
            if self.draft_k is not None:
                # the speculative verify — the model-level draft-check
                # forward (models/lm._chunk_batch_forward + the bonus
                # token step), named alongside lm.prefill/lm.decode so
                # the profile verb's roofline verdicts cover it
                out["lm.verify"] = prof.register_program(
                    "lm.verify",
                    self._efns_jit.verify.lower(
                        self._params, self._caches, self._logits,
                        self._kd, self._pos, self._rem, self._eos,
                        self._scales, self._adapters, self._tslot,
                        np.zeros((self.n_slots, self.draft_k),
                                 np.int32),
                        np.zeros(self.n_slots, bool)).compile())
            self._register_propose_cost(out, prof)
        return out

    def _register_propose_cost(self, out: dict, prof) -> None:
        """Register the learned drafter's batched propose program
        (when armed) alongside window/verify — the profile serve
        verb's roofline verdicts then cover the drafter's per-cycle
        overhead with the same accounting as the programs it rides
        between. No-op without a draft model. Caller holds the
        `prof.compiling(None)` suppression."""
        if self._dfns is None:
            return
        zc = np.zeros((self.n_slots, self.draft_k + 1), np.int32)
        zi = np.zeros(self.n_slots, np.int32)
        zb = np.zeros(self.n_slots, bool)
        out["serve.propose"] = prof.register_program(
            "serve.propose",
            self._dfns_jit.propose.lower(
                self._dparams, self._dcaches, self._dadapters,
                self._tslot, zc, zi, zi, zb).compile())

    def cache_fingerprint(self) -> dict:
        """The identity an AOT-serialized executable is valid for: the
        full compiled-program config (every `_ServeConfig` field plus
        the engine knobs that reach tracing) AND the mesh's device
        assignment — a serialized executable replays onto the exact
        devices it was compiled against, so a different device set must
        read as a cache MISS, never a mis-placed load. compile_cache.py
        layers program name + jax/jaxlib/backend versions on top."""
        mesh = self._cfg.mesh
        return {
            "embed_dim": self._cfg.embed_dim,
            "num_heads": self._cfg.num_heads,
            "num_blocks": self._cfg.num_blocks,
            "spec": repr(self._cfg.spec),
            "t_max": self.t_max,
            "n_slots": self.n_slots,
            "vocab": int(self._logits.shape[1]),
            "cache_dtype": str(jnp.dtype(self._cfg.cache_dtype)),
            "logits_dtype": str(self._logits.dtype),
            "block_impl": self._cfg.block_impl,
            "temperature": self._cfg.temperature,
            "top_k": self._cfg.top_k,
            "pad_id": self.pad_id,
            "kv_int8": self.kv_int8,
            "draft_k": self.draft_k,
            "prefill_chunk": self.prefill_chunk,
            "kv_page_size": self.kv_page_size,
            "kv_pages": self.kv_pages,
            "n_tenants": self.n_tenants,
            "adapter_rank": (int(self._adapters[0].shape[2])
                             if self._adapters else 0),
            "partition_rules": repr(self._partition_rules),
            # the learned drafter compiles its own programs against
            # its own dims — a same-target engine with a different
            # (or no) drafter must read as a MISS for them
            "draft_model": (None if self._dcfg is None else {
                "embed_dim": self._dcfg.embed_dim,
                "num_heads": self._dcfg.num_heads,
                "num_blocks": self._dcfg.num_blocks,
                "cache_dtype": str(jnp.dtype(self._dcfg.cache_dtype)),
                "partition_rules": repr(self._draft_partition_rules),
            }),
            "mesh_axes": {str(k): int(v)
                          for k, v in self._cfg.mesh.shape.items()},
            "devices": [f"{d.platform}:{d.id}"
                        for d in mesh.devices.flat],
        }

    def _warm_aot(self, n_steps: int, cache) -> None:
        """Load-or-compile the serve loop's fixed-shape programs
        through a persistent `CompileCache` and install them as this
        engine's dispatch table (`_AotPrograms`). Warm replica spin-up:
        a fresh process deserializes executables instead of re-running
        XLA. Cold path honesty: a miss compiles AOT via
        `.lower().compile()` — the same route a hit replays — and
        stores the result, so cold-vs-warm comparisons measure the
        cache, not the in-process jit memo. Compiles that do happen
        here are attributed to ``replica.spinup`` in the compile
        watchdog.

        Covered programs: the masked window at `n_steps`, the
        admission insert, and the prefill chunk (when chunked) — the
        fixed-shape programs that dominate spin-up. Monolithic bucketed
        prefill shapes and the speculative verify still jit-compile in
        the warmup dispatches below."""
        from idc_models_tpu.observe import profile as prof

        fp = self.cache_fingerprint()
        fp["window_steps"] = int(n_steps)
        efns, sfns = self._efns_jit, self._sfns_jit
        vocab = int(self._logits.shape[1])
        logits1 = jnp.zeros((1, vocab), self._logits.dtype)
        kd0 = np.zeros(2, np.uint32)

        def undonated(jitted, static_argnums=()):
            # The cached executables must NOT donate: on jaxlib's CPU
            # backend, chaining deserialized executables whose donated
            # outputs feed the next dispatch's donated inputs (the
            # chunk->chunk->insert->window steady state) intermittently
            # frees live buffers — glibc heap aborts and, worse,
            # silently wrong tokens. The donation metadata itself
            # round-trips (a single deserialized donating program is
            # fine); only the chained replay is unsound. So the cache
            # stores donation-free twins of the jitted bodies — an
            # extra buffer copy per dispatch on the AOT path, bounded
            # by the engine state size, in exchange for executables
            # that are safe to replay from any process. The in-process
            # jit path (no cache, or a window-size fallthrough) keeps
            # donation.
            return jax.jit(jitted.__wrapped__,
                           static_argnums=static_argnums)

        plans = []
        if self.paged:
            c = self.prefill_chunk
            w_nd = undonated(efns.window, (11,))
            i_nd = undonated(efns.insert)
            p_nd = undonated(efns.prefill_chunk)
            plans = [
                ("window", "e", "window", lambda: w_nd.lower(
                    self._params, self._caches, self._pt, self._logits,
                    self._kd, self._pos, self._rem, self._eos,
                    self._scales, self._adapters, self._tslot, n_steps)),
                ("insert", "e", "insert", lambda: i_nd.lower(
                    self._logits, self._kd, self._pos, self._rem,
                    self._eos, self._tslot, logits1, np.int32(0),
                    np.int32(1), np.int32(1), np.int32(-1), np.int32(0),
                    kd0)),
                ("prefill_chunk", "e", "prefill_chunk", lambda: p_nd.lower(
                    self._params, self._caches, self._pt, self._scales,
                    np.int32(0), np.zeros((1, c), np.int32),
                    np.int32(0), np.int32(0))),
            ]
        else:
            w_nd = undonated(efns.window, (10,))
            plans = [("window", "e", "window", lambda: w_nd.lower(
                self._params, self._caches, self._logits, self._kd,
                self._pos, self._rem, self._eos, self._scales,
                self._adapters, self._tslot, n_steps))]
            if self.prefill_chunk is not None:
                c = self.prefill_chunk
                caches1 = sfns.init_caches(1)
                p_nd = undonated(sfns.prefill_chunk)
                i_nd = undonated(efns.insert)
                plans.append(
                    ("prefill_chunk", "s", "prefill_chunk",
                     lambda: p_nd.lower(
                        self._params, caches1, np.zeros((1, c), np.int32),
                        np.int32(0), np.int32(c))))
                plans.append(("insert", "e", "insert", lambda: i_nd.lower(
                    self._caches, self._logits, self._kd, self._pos,
                    self._rem, self._eos, self._tslot, self._scales,
                    caches1, logits1, np.int32(0), np.int32(1),
                    np.int32(1), np.int32(-1), np.int32(0), kd0)))
        if self._dfns is not None:
            # the learned drafter's per-cycle programs: propose +
            # backlog ingest, cached under DRAFTER-distinct names (the
            # target's "insert" already claims that key under this
            # fingerprint). The draft insert stays in-process jit like
            # the bucketed prefills — its inputs come from two
            # producers (drafter prefill, init_caches) whose layouts
            # an AOT executable could only match one of.
            dfns = self._dfns_jit
            zc = np.zeros((self.n_slots, self.draft_k + 1), np.int32)
            zi = np.zeros(self.n_slots, np.int32)
            zb = np.zeros(self.n_slots, bool)
            pr_nd = undonated(dfns.propose)
            g_nd = undonated(dfns.ingest)
            plans.append(("propose", "d", "propose",
                          lambda: pr_nd.lower(
                              self._dparams, self._dcaches,
                              self._dadapters, self._tslot, zc, zi,
                              zi, zb)))
            plans.append(("draft_ingest", "d", "ingest",
                          lambda: g_nd.lower(
                              self._dparams, self._dcaches, zc, zi,
                              zb)))
        overlay_e, overlay_s, overlay_d = {}, {}, {}
        with prof.naming_compiles("replica.spinup"):
            for name, ns, attr, lower in plans:
                key = cache.key(program=name, fingerprint=fp)
                exe = cache.load(
                    key, devices=self._cfg.mesh.devices.flat)
                if exe is None:
                    exe = cache.compile_and_store(key, lower())
                if name == "window":
                    exe = _AotWindow(exe, n_steps, efns.window)
                {"e": overlay_e, "s": overlay_s,
                 "d": overlay_d}[ns][attr] = exe
        if overlay_e:
            self._efns = _AotPrograms(efns, overlay_e)
        if overlay_s:
            self._sfns = _AotPrograms(sfns, overlay_s)
        if overlay_d:
            self._dfns = _AotPrograms(self._dfns_jit, overlay_d)

    def warmup(self, n_steps: int, compile_cache=None) -> None:
        """Compile every program the serve loop will touch — so
        admission traffic after this triggers ZERO XLA compilations:
        the prefill shapes the admission path uses (every bucket length
        monolithically, the ONE chunk shape when chunked — both
        chunk-from-fresh and chunk-from-chunk chains), the insert, and
        the masked window at `n_steps`. Runs on the real (empty) engine
        state with a ZERO budget, so every row stays dead and the
        warmup dispatches are bit-level no-ops.

        With `compile_cache` (serve/compile_cache.py) the fixed-shape
        programs AOT-load from disk first (`_warm_aot`) and the warmup
        dispatches below run through the loaded executables — a warm
        process skips their XLA compiles entirely."""
        if compile_cache is not None:
            if self.in_place:
                raise ValueError(
                    "the AOT compile cache knows the chunk program of a "
                    "request's own cache row; an engine that prefills in "
                    "place compiles in process")
            self._warm_aot(n_steps, compile_cache)
        if self.paged:
            # two chunk steps against the live pool with an
            # all-unallocated page table and p_end == start == 0:
            # every page write drops, so the dispatches are bit-level
            # no-ops that compile the chunk-from-fresh AND the
            # chunk-from-chunk chains (pools flow through EVERY paged
            # program under one pinned sharding)
            c = self.prefill_chunk
            logits1 = None
            for _ in range(2):
                logits1, self._caches, sc = self._efns.prefill_chunk(
                    self._params, self._caches, self._pt, self._scales,
                    np.int32(0), np.zeros((1, c), np.int32),
                    np.int32(0), np.int32(0))
                if self.kv_int8:
                    self._scales = sc
            caches1 = None
        elif self.in_place:
            # two chunk steps into the rows of slot 0, which is free
            # (what they write lies beyond every frontier): the first
            # consumes init_caches' arrays, the second the chunk
            # program's own (pinned) outputs; and the budget kill
            c = self.prefill_chunk
            for start in (0, c if 2 * c <= self.t_max else 0):
                logits1, self._caches, _, _ = self._efns.prefill_chunk(
                    self._params, self._caches, np.int32(0),
                    np.zeros((1, c), np.int32), np.int32(start),
                    np.int32(start + c))
            self._rem = self._efns.kill(self._rem, np.int32(0))
            caches1 = None
        elif self.prefill_chunk is not None:
            c = self.prefill_chunk
            caches1 = self._sfns.init_caches(1)
            # two chunk steps: the first consumes init_caches' arrays,
            # the second the chunk program's own (pinned) outputs — the
            # steady-state chain every multi-chunk prompt runs
            logits1, caches1, _ = self._sfns.prefill_chunk(
                self._params, caches1, np.zeros((1, c), np.int32),
                np.int32(0), np.int32(c))
            if 2 * c <= self.t_max:
                logits1, caches1, _ = self._sfns.prefill_chunk(
                    self._params, caches1, np.zeros((1, c), np.int32),
                    np.int32(c), np.int32(2 * c))
        else:
            logits1 = caches1 = None
            for b in prefill_buckets(self.t_max, self._n_ring):
                logits1, caches1 = self._sfns.prefill(
                    self._params, np.zeros((1, b), np.int32), np.int32(b))
        # two full insert->window cycles: the steady-state inputs of
        # each program are the (sharding-pinned) OUTPUTS of the others,
        # so the second cycle warms exactly the executables the serve
        # loop reuses forever
        for _ in range(2):
            if self.paged or self.in_place:
                (self._logits, self._kd, self._pos, self._rem,
                 self._eos, self._tslot) = self._efns.insert(
                    self._logits, self._kd, self._pos, self._rem,
                    self._eos, self._tslot, logits1, np.int32(0),
                    np.int32(1), np.int32(0), np.int32(-1),
                    np.int32(0), np.zeros(2, np.uint32))
            else:
                (self._caches, self._logits, self._kd, self._pos,
                 self._rem, self._eos, self._tslot,
                 self._scales) = self._efns.insert(
                    self._caches, self._logits, self._kd, self._pos,
                    self._rem, self._eos, self._tslot, self._scales,
                    caches1, logits1, np.int32(0), np.int32(1),
                    np.int32(0), np.int32(-1), np.int32(0),
                    np.zeros(2, np.uint32))
            self.step_window(n_steps)
            if self.draft_k is not None:
                # the verify program at its ONE fixed shape, chained
                # off both the insert's and the window's (pinned)
                # outputs; every row dead, so the dispatch is a
                # bit-level no-op like the warmup windows
                self.begin_verify(
                    np.zeros((self.n_slots, self.draft_k), np.int32),
                    np.zeros(self.n_slots, bool))
                self.collect()
        if self.paged:
            # the grant/release-path program: a page-row rewrite with
            # the unallocated row slot 0 already holds (and the kill
            # branch exercised — slot 0's budget is already 0) plus,
            # int8, the scale stamp with every target out of bounds —
            # all bit-level no-ops at the real executables' shapes
            self._set_page_row(0, [], kill=True)
            if self.kv_int8:
                self._scales = self._efns.stamp_scales(
                    self._scales, np.int32(0),
                    np.full(self._l_pages, self.kv_pages, np.int32))
        if self._dfns is not None:
            # the learned drafter's chain, interleaved like the target
            # loop above so every program sees every producer's
            # (pinned) outputs: admission rows from BOTH producers (a
            # fresh init_caches row for <=1-token prompts, a
            # prefill-bucket row for the rest) scattered into state
            # that has flowed through ingest AND propose — the serve
            # loop's steady state admits into propose-output caches.
            # Every row is dead (live all-False, slot 0 free), so the
            # dispatches are bit-level no-ops and slot 0's garbage row
            # is overwritten by any real admission's insert.
            zc = np.zeros((self.n_slots, self.draft_k + 1), np.int32)
            zi = np.zeros(self.n_slots, np.int32)
            zb = np.zeros(self.n_slots, bool)
            drow = self._dsfns.init_caches(1)
            self._dcaches = self._dfns.insert(self._dcaches, drow,
                                              np.int32(0))
            for b in prefill_buckets(self.t_max, self._n_ring):
                _, drow = self._dsfns.prefill(
                    self._dparams, np.zeros((1, b), np.int32),
                    np.int32(b))
                self._dcaches = self._dfns.ingest(
                    self._dparams, self._dcaches, zc, zi, zb)
                self._dcaches, _ = self._dfns.propose(
                    self._dparams, self._dcaches, self._dadapters,
                    self._tslot, zc, zi, zi, zb)
                self._dcaches = self._dfns.insert(
                    self._dcaches, drow, np.int32(0))
            self._dcaches = self._dfns.ingest(
                self._dparams, self._dcaches, zc, zi, zb)
            self._dcaches, _ = self._dfns.propose(
                self._dparams, self._dcaches, self._dadapters,
                self._tslot, zc, zi, zi, zb)
        # the health reduce is part of the armed serve loop's steady
        # state (one dispatch per cycle) — warm it with everything else
        self.slot_health()

    def kv_bytes_per_slot(self) -> int:
        """HBM bytes of ring-cache state per decode slot (K + V rows
        across blocks, plus dequant scales when int8) — the denominator
        of the int8 capacity claim: slots_at_budget = budget // this.
        On a PAGED engine this is the WORST CASE (a full-t_max
        request's pages); the live figure is `kv_bytes_resident`,
        because short requests no longer reserve t_max."""
        if self.paged:
            return self._l_pages * self.kv_page_bytes()
        per = 0
        for cache in self._caches:
            per += sum(c.nbytes for c in cache) // self.n_slots
        for pair in self._scales:
            for s in pair:
                per += s.nbytes // self.n_slots
        return per

    def kv_bytes_by_kind(self) -> dict:
        """HBM bytes of the cache rows of all slots, by the kind of
        layer that owns them: ``full`` (t_max rows a slot) and
        ``window`` (a ring of W rows a slot); and, for a model with
        such layers alone, ``index``: the index keys cached beside K/V,
        and ``latent``: the latent-attention layers' rows (one a
        position, in place of K and V)."""
        out = {"full": 0, "window": 0}
        for l, cache in zip(self._cfg.spec.layers, self._caches):
            if l.latent is not None:
                out["latent"] = out.get("latent", 0) + cache[0].nbytes
            else:
                out["full" if l.window is None else "window"] += (
                    cache[0].nbytes + cache[1].nbytes)
            if l.indexer is not None:
                out["index"] = out.get("index", 0) + cache[-1].nbytes
        return out

    def slot_logits(self, slot: int) -> np.ndarray:
        """The float32 logits `slot`'s next token will be picked from
        (after its admission: the last prompt position's; after a
        collected window: the last emitted token's). A device fetch, for
        checks and debugging; no serving path calls it."""
        return np.asarray(self._logits[slot])

    def router_picks(self, where: str) -> np.ndarray | None:
        """The experts the router chose (global ids), for a model with
        expert layers, else None: ``"window"``, the last dispatched
        window's, [steps, expert layers, n_slots, k]; ``"prefill"``, the
        last prefill chunk's, [expert layers, 1, chunk, k] (the rows
        past the prompt's end are padding's). A device fetch, for
        checks and debugging."""
        picks = self._picks[where]
        return (None if picks is None or isinstance(picks, tuple)
                else np.asarray(picks))

    def selected_positions(self, where: str) -> np.ndarray | None:
        """The positions the indexer layers chose, for a model with such
        layers, else None: ``"window"``, the last dispatched window's,
        [steps, indexer layers, n_slots, topk] int32 (-1 where a slot
        saw fewer); ``"prefill"``, the last prefill chunk's, as bits
        (bit b of word m is position 32 m + b),
        [indexer layers, chunk, t_max / 32] uint32 (the rows past the
        prompt's end are padding's). A device fetch, for checks and
        debugging, beside `router_picks`."""
        sel = self._selected[where]
        return None if sel is None else np.asarray(sel)

    def kv_page_bytes(self) -> int:
        """HBM bytes ONE page costs across every block's K + V pools,
        plus its per-(page, head) dequant scales when int8 — the unit
        the tokens-per-HBM-byte capacity claim divides by."""
        head_dim = self._cfg.embed_dim // self._cfg.num_heads
        item = (1 if self.kv_int8
                else jnp.dtype(self._cfg.cache_dtype).itemsize)
        per = (self._cfg.num_blocks * 2 * self.kv_page_size
               * self._cfg.num_heads * head_dim * item)
        if self.kv_int8:
            per += self._cfg.num_blocks * 2 * self._cfg.num_heads * 4
        return per

    def kv_bytes_resident(self) -> int:
        """HBM bytes of KV state currently RESERVED: the paged
        counterpart of `kv_bytes_per_slot` — used pages times page
        bytes. A contiguous engine reserves every slot's full row up
        front, so its figure is constant at n_slots * per-slot bytes;
        the ratio of the two under mixed-length traffic IS the paged
        capacity win."""
        if not self.paged:
            return self.n_slots * self.kv_bytes_per_slot()
        return self._alloc.used_count() * self.kv_page_bytes()

    def tokens_resident(self) -> int:
        """Tokens of KV actually held on device right now: decoded
        positions of occupied slots plus prefilled positions of
        pending chunked admissions. tokens_resident /
        kv_bytes_resident is the tokens-per-HBM-byte figure the paged
        engine exists to raise."""
        toks = int(sum(int(self._pos_h[s]) for s in range(self.n_slots)
                       if self._occupied[s]))
        toks += int(sum(p.next_start for p in self._prefills.values()))
        return toks

    def page_stats(self) -> dict:
        """The per-cycle page/occupancy rollup the scheduler feeds to
        ServingMetrics.on_pages (paged engines only — None tells the
        caller the engine is contiguous)."""
        if not self.paged:
            return None
        return {
            "pages_total": self.kv_pages,
            "pages_used": self._alloc.used_count(),
            "pages_cached": (self.prefix_cache.cached_pages()
                             if self.prefix_cache is not None else 0),
            "resident_tokens": self.tokens_resident(),
            "resident_bytes": self.kv_bytes_resident(),
        }
