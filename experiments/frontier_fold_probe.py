"""Time the two frontier folds alone, at the served models' cache shapes:
the one-pass fold (one block a shard: the parent's code) and the traced
loop over blocks at several block sizes. One process, one chip:

    chiprun --chips 1 -- python experiments/frontier_fold_probe.py gpt2 laguna

Lines go to chiprun_out/frontier_fold_probe.jsonl (PERF.md section 6, PR
28, has the readings the block sizes were fixed from). `--tiny` rehearses the
control flow on the CPU; its times mean nothing.
"""

from __future__ import annotations

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

ONE_PASS = 1 << 30


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


def probe_decode(out, name, *, slots, t_max, h, g, d, qdtype, layers,
                 frontiers, variants, steps=8):
    mesh = meshlib.seq_mesh(1)
    rng = np.random.default_rng(0)
    caches = _caches(layers, rd.cache_shape(slots, t_max, g, d))
    for vname, blk in variants:
        rd._DECODE_BLOCK = blk
        fold = rd.make_batched_ring_decode(mesh, jit=False)

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
        t0 = time.perf_counter()
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
            med, best, (caches, *_) = timed(
                jp, (caches, q, kt, vt, jnp.asarray(pos), jnp.asarray(live)))
            row = {"probe": "decode", "model": name, "variant": vname,
                   "blk": blk, "frontier": f,
                   "us_per_layer_step": med / (steps * layers) * 1e6,
                   "best_us": best / (steps * layers) * 1e6,
                   "since_variant_s": round(time.perf_counter() - t0, 1)}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
            out.flush()


def probe_chunk(out, name, *, c, t_max, h, g, d, qdtype, layers, starts,
                variants):
    mesh = meshlib.seq_mesh(1)
    rng = np.random.default_rng(1)
    caches = _caches(layers, rd.cache_shape(1, t_max, g, d))
    for vname, blk in variants:
        rd._CHUNK_BLOCK = blk
        fold = rd.make_chunk_ring_decode(mesh, jit=False)

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
            med, best, (caches, *_) = timed(
                jp, (caches, q, kt, vt, jnp.int32(s0)))
            row = {"probe": "chunk", "model": name, "variant": vname,
                   "blk": blk, "start": s0,
                   "us_per_layer": med / layers * 1e6,
                   "best_us": best / layers * 1e6}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
            out.flush()


def main():
    tiny = "--tiny" in sys.argv
    models = [a for a in sys.argv[1:] if not a.startswith("--")]
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    path = Path("chiprun_out")
    path.mkdir(exist_ok=True)
    with open(path / "frontier_fold_probe.jsonl", "a") as out:
        if "gpt2" in models:
            shape = (dict(slots=2, t_max=64, h=4, g=4, d=8, layers=2)
                     if tiny else
                     dict(slots=10, t_max=1024, h=20, g=20, d=64, layers=4))
            k = 16 if tiny else 1
            probe_decode(
                out, "gpt2-large", qdtype=jnp.float32, **shape,
                frontiers=[f // k for f in (0, 100, 250, 400, 600, 1024)],
                variants=[("one_pass", ONE_PASS),
                          ("loop", 128 // k), ("loop", 256 // k),
                          ("loop", 512 // k)])
            cshape = (dict(c=8, t_max=64, h=4, g=4, d=8, layers=2) if tiny
                      else dict(c=128, t_max=1024, h=20, g=20, d=64,
                                layers=4))
            probe_chunk(
                out, "gpt2-large", qdtype=jnp.float32, **cshape,
                starts=[s // k for s in (0, 256, 512, 896)],
                variants=[("one_pass", ONE_PASS), ("loop", 128 // k),
                          ("loop", 256 // k), ("loop", 512 // k)])
        if "laguna" in models:
            shape = (dict(slots=2, t_max=64, h=6, g=2, d=8, layers=1)
                     if tiny else
                     dict(slots=48, t_max=8192, h=48, g=8, d=128, layers=2))
            k = 128 if tiny else 1
            probe_decode(
                out, "laguna-s-2.1", qdtype=jnp.bfloat16, **shape,
                frontiers=[f // k for f in (0, 1500, 4000, 5770, 8192)],
                variants=[("one_pass", ONE_PASS)] + [
                    ("loop", max(b // k, 8))
                    for b in ((1024, 2048) if tiny
                              else (128, 256, 512, 1024, 2048))])
            cshape = (dict(c=8, t_max=64, h=6, g=2, d=8, layers=1) if tiny
                      else dict(c=512, t_max=8192, h=48, g=8, d=128,
                                layers=2))
            probe_chunk(
                out, "laguna-s-2.1", qdtype=jnp.bfloat16, **cshape,
                starts=[s // k for s in (0, 1024, 4096, 7680)],
                variants=[("one_pass", ONE_PASS)] + [
                    ("loop", max(b // k, 8))
                    for b in ((1024, 2048) if tiny
                              else (128, 256, 512, 1024, 2048))])


if __name__ == "__main__":
    main()
