"""Time the decode fold and the chunk fold alone over the stored forms of
a contiguous KV cache, at the served models' shapes. One process, one chip:

    git archive 524720c | tar -x -C .bench_scratch/parent   # any 4-D tree
    chiprun --chips 1 -- python experiments/cache_layout_probe.py \\
        --parent .bench_scratch/parent gpt2 laguna

Variants: `4d` (`[B, T, G, D]` caches and the einsums spelled over heads:
the `ring_decode.py` of a tree from before PR 30, loaded from a copy of
it; this tree keeps that form for heads of whole lane tiles); `flat`
(merged rows `[B, T, G*D]`, one token spread block-diagonally over the
row's lanes, a chunk's block split into heads: this tree's form for
narrower heads); `flat_split` (merged rows with the ONE-token query also
contracted head by head over the block, split as the chunk fold splits
it); `flat_heads` (merged rows, each cached head's lanes sliced out in
place and contracted with its own query heads). The last two are what a
single stored form would have meant for 128-wide heads, and are why
there are two. Those three run with the append PR 30 had
(`per_row_vmap_append`), so they reproduce its table. `drop` is this
tree as it is: its own stored form at each width and the batched fold's
append as ONE scatter that drops the rows that write nothing (no read
back, no select; `ring_decode._append_rows` since PR 33); `vmap` is the
same stored form with the old append, each row's read, select and
`dynamic_update_slice` under `vmap`: the pair is PR 33's table.
`--variants a,b` runs only those, `decode` / `chunk` only that fold. us a
layer and token step (decode) or a layer and chunk, median of 8 calls.
Lines go to chiprun_out/cache_layout_probe.jsonl (PERF.md section 6,
PRs 30 and 33, has the readings). `--tiny` rehearses the control flow
on the CPU; its times mean nothing.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax import lax             # noqa: E402

from idc_models_tpu import mesh as meshlib      # noqa: E402
from idc_models_tpu import ring_decode as rd    # noqa: E402


def load_parent(root):
    """The 4-D folds: `ring_decode.py` of a tree from before PR 30."""
    spec = importlib.util.spec_from_file_location(
        "ring_decode_4d", Path(root) / "idc_models_tpu" / "ring_decode.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def contraction(scores, weighted):
    """Trace this tree's folds with other contractions of merged rows."""
    was = rd._scores, rd._weighted
    rd._scores, rd._weighted = scores, weighted
    try:
        yield
    finally:
        rd._scores, rd._weighted = was


def one_token_split():
    """The one-token query contracted the way a chunk of one is: the
    block split into heads."""
    scores, weighted = rd._scores, rd._weighted
    return contraction(
        lambda q, kc: (scores(q, kc) if q.ndim == 4
                       else scores(q[:, None], kc)[:, :, 0]),
        lambda p, vc, d: (weighted(p, vc, d) if p.ndim == 4
                          else weighted(p[:, :, None], vc, d)[:, :, 0]))


def sliced_lanes():
    """Each cached head's lanes sliced out of the merged rows in place
    and contracted with its own H / G query heads, token or chunk."""
    f32 = dict(preferred_element_type=jnp.float32)

    def scores(q, kc):
        d = q.shape[-1]
        g = kc.shape[-1] // d
        r = q.shape[-2] // g
        eq = "brd,bkd->brk" if q.ndim == 3 else "bcrd,bkd->brck"
        return jnp.concatenate(
            [jnp.einsum(eq, q[..., j * r:(j + 1) * r, :],
                        kc[..., j * d:(j + 1) * d], **f32)
             for j in range(g)], axis=1)

    def weighted(p, vc, d):
        g = vc.shape[-1] // d
        r = p.shape[1] // g
        eq = "brk,bkd->brd" if p.ndim == 3 else "brck,bkd->brcd"
        return jnp.concatenate(
            [jnp.einsum(eq, p[:, j * r:(j + 1) * r],
                        vc[..., j * d:(j + 1) * d], **f32)
             for j in range(g)], axis=1)

    return contraction(scores, weighted)


@contextlib.contextmanager
def per_row_vmap_append():
    """Trace the batched fold with the append it had until PR 33: each
    row reads its one position back, selects between old and new and
    `dynamic_update_slice`s, under `vmap` (XLA expands the resulting
    scatter into a loop of one trip a row)."""
    def append(c, t, slot, mine):
        def row_append(c, t, s, m):
            at = (s,) + (0,) * (c.ndim - 1)
            old = lax.dynamic_slice(c, at, t.shape)
            return lax.dynamic_update_slice(
                c, jnp.where(m, t.astype(c.dtype), old), at)

        return jax.vmap(row_append)(c, t, slot, mine)

    was, rd._append_rows = rd._append_rows, append
    try:
        yield
    finally:
        rd._append_rows = was


def with_old_append(contraction=contextlib.nullcontext):
    """`contraction` traced together with PR 30's append."""
    @contextlib.contextmanager
    def ctx():
        with contraction(), per_row_vmap_append():
            yield

    return ctx


def variants(parent, only=None, chunk=False):
    """(name, module, cache shape of (b, t, g, d), tracing context). The
    folds take the contraction from the cache's rank, so merged rows
    are tried at every head width. The chunk fold splices and does not
    append, so it skips the variants that differ in one token's path
    alone."""
    def flat(b, t, g, d):
        return (b, t, g * d)

    out = []
    if parent is not None:
        out.append(("4d", parent, lambda b, t, g, d: (b, t, g, d),
                    contextlib.nullcontext))
    out.append(("flat", rd, flat, with_old_append()))
    out.append(("flat_heads", rd, flat, with_old_append(sliced_lanes)))
    out.append(("drop", rd, rd.cache_shape, contextlib.nullcontext))
    if not chunk:
        out.append(("vmap", rd, rd.cache_shape, with_old_append()))
        out.append(("flat_split", rd, flat,
                    with_old_append(one_token_split)))
    return [v for v in out if only is None or v[0] in only]


def _caches(layers, shape):
    """Random bfloat16 (k, v) pairs, drawn on the device."""
    keys = jax.random.split(jax.random.key(0), 2 * layers)
    mk = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))
    return tuple((mk(keys[2 * i]), mk(keys[2 * i + 1]))
                 for i in range(layers))


def timed(fn, args, n=8):
    args = fn(*args)            # compile + warm
    args = fn(*args)
    jax.block_until_ready(args)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        args = fn(*args)
        jax.block_until_ready(args)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(min(ts)), args


def emit(out, row):
    print(json.dumps(row), flush=True)
    out.write(json.dumps(row) + "\n")
    out.flush()


def probe_decode(out, parent, name, *, slots, t_max, h, g, d, qdtype,
                 layers, frontiers, wrap=False, steps=8, only=None):
    mesh = meshlib.seq_mesh(1)
    for vname, mod, shape, ctx in variants(parent, only):
        rng = np.random.default_rng(0)
        caches = _caches(layers, shape(slots, t_max, g, d))
        fold = mod.make_batched_ring_decode(mesh, jit=False, wrap=wrap)

        def prog(caches, q, kt, vt, pos, live):
            def step(caches, _):
                new, acc = [], jnp.float32(0)
                for kc, vc in caches:
                    o, kc, vc = fold(kc, vc, q, kt, vt, pos, live)
                    acc = acc + jnp.sum(o.astype(jnp.float32))
                    new.append((kc, vc))
                return tuple(new), acc
            caches, acc = lax.scan(step, caches, None, length=steps)
            return caches, q + (jnp.sum(acc) * 0).astype(q.dtype), kt, vt, \
                pos, live

        jp = jax.jit(prog, donate_argnums=(0,))
        for f in frontiers:
            q = jnp.asarray(rng.normal(0, 1, (slots, 1, h, d)), qdtype)
            kt = jnp.asarray(rng.normal(0, 1, (slots, 1, g, d)), qdtype)
            vt = jnp.asarray(rng.normal(0, 1, (slots, 1, g, d)), qdtype)
            if f == 0:
                pos = np.full(slots, t_max, np.int32)
                live = np.zeros(slots, bool)
            else:
                pos = rng.integers(0, f, slots).astype(np.int32)
                pos[0] = f - 1
                live = np.ones(slots, bool)
            with ctx():
                med, best, (caches, *_) = timed(
                    jp, (caches, q, kt, vt, jnp.asarray(pos),
                         jnp.asarray(live)))
            emit(out, {"probe": "decode", "model": name, "variant": vname,
                       "frontier": f,
                       "us_per_layer_step": med / (steps * layers) * 1e6,
                       "best_us": best / (steps * layers) * 1e6})
        del caches


def probe_chunk(out, parent, name, *, c, t_max, h, g, d, qdtype, layers,
                starts, wrap=False, only=None):
    mesh = meshlib.seq_mesh(1)
    for vname, mod, shape, ctx in variants(parent, only, chunk=True):
        rng = np.random.default_rng(1)
        caches = _caches(layers, shape(1, t_max, g, d))
        fold = mod.make_chunk_ring_decode(mesh, jit=False, wrap=wrap)

        def prog(caches, q, kt, vt, start):
            new, acc = [], jnp.float32(0)
            for kc, vc in caches:
                o, kc, vc = fold(kc, vc, q, kt, vt, start, start + c)
                acc = acc + jnp.sum(o.astype(jnp.float32))
                new.append((kc, vc))
            return tuple(new), q + (acc * 0).astype(q.dtype), kt, vt, start

        jp = jax.jit(prog, donate_argnums=(0,))
        for s0 in starts:
            q = jnp.asarray(rng.normal(0, 1, (1, c, h, d)), qdtype)
            kt = jnp.asarray(rng.normal(0, 1, (1, c, g, d)), qdtype)
            vt = jnp.asarray(rng.normal(0, 1, (1, c, g, d)), qdtype)
            with ctx():
                med, best, (caches, *_) = timed(
                    jp, (caches, q, kt, vt, jnp.int32(s0)))
            emit(out, {"probe": "chunk", "model": name, "variant": vname,
                       "start": s0, "us_per_layer": med / layers * 1e6,
                       "best_us": best / layers * 1e6})
        del caches


def main():
    argv = sys.argv[1:]
    tiny = "--tiny" in argv
    parent = None
    if "--parent" in argv:
        parent = load_parent(argv[argv.index("--parent") + 1])
    only = None
    if "--variants" in argv:
        only = argv[argv.index("--variants") + 1].split(",")
    models = [a for a in argv if a in ("gpt2", "laguna")]
    folds = [a for a in argv if a in ("decode", "chunk")] or [
        "decode", "chunk"]
    run = {"decode": probe_decode, "chunk": probe_chunk}

    def probe(fold, *a, **kw):
        if fold in folds:
            run[fold](*a, only=only, **kw)

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    path = Path("chiprun_out")
    path.mkdir(exist_ok=True)
    name = "cache_layout_probe.tiny.jsonl" if tiny else \
        "cache_layout_probe.jsonl"
    with open(path / name, "a") as out:
        if "gpt2" in models:
            k = 16 if tiny else 1
            shape = (dict(t_max=64, h=4, g=4, d=8, layers=2) if tiny
                     else dict(t_max=1024, h=20, g=20, d=64, layers=4))
            probe(
                "decode", out, parent, "gpt2-large", qdtype=jnp.float32,
                slots=2 if tiny else 10, **shape,
                frontiers=[f // k for f in (0, 100, 250, 400, 600, 1024)])
            probe(
                "chunk", out, parent, "gpt2-large", qdtype=jnp.float32,
                c=8 if tiny else 128, **shape,
                starts=[s // k for s in (0, 256, 512, 896)])
        if "laguna" in models:
            k = 128 if tiny else 1
            full = (dict(t_max=64, h=6, g=2, d=8, layers=1) if tiny
                    else dict(t_max=8192, h=48, g=8, d=128, layers=2))
            ring = (dict(t_max=16, h=6, g=2, d=8, layers=1) if tiny
                    else dict(t_max=512, h=72, g=8, d=128, layers=3))
            probe(
                "decode", out, parent, "laguna-s-2.1 full",
                qdtype=jnp.bfloat16,
                slots=2 if tiny else 48, **full,
                frontiers=[f // k for f in (0, 1500, 4000, 5770, 8192)])
            # a ring that has wrapped is read whole: one reading
            probe(
                "decode", out, parent, "laguna-s-2.1 window",
                qdtype=jnp.bfloat16,
                slots=2 if tiny else 48, **ring, wrap=True,
                frontiers=[ring["t_max"] * 4])
            probe(
                "chunk", out, parent, "laguna-s-2.1 full",
                qdtype=jnp.bfloat16,
                c=8 if tiny else 512, **full,
                starts=[s // k for s in (0, 1024, 4096, 7680)])
            probe(
                "chunk", out, parent, "laguna-s-2.1 window",
                qdtype=jnp.bfloat16,
                c=8 if tiny else 512, **ring, wrap=True,
                starts=[s // k for s in (0, 4096)])


if __name__ == "__main__":
    main()
