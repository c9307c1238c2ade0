"""Times, on the chip, ONE layer's latent attention over one prefill
chunk of 512 queries of the served model (64 heads, nope 192 + rope 64,
v 256, a latent of 512, the cache's rows of 640 lanes), at frontiers of
2k, 8k, 16k and 28k positions, in both forms, with a selection of 2,048
positions a query as the chunk fold's packed bits:

- absorbed: each head's nope query taken into the latent, q_lat =
  q_nope W_UK^T, and the rows read as they rest, key and value at once
  (`ring_decode._attend_to_frontier` over the one cache, what the
  engine's chunk program runs), the latent output out through W_UV;
- expanded: every block of rows decompressed, [k_nope | v] = c_kv W_UKV,
  and attended head by head beside q_pe . k_pe.

Both outputs are compared, and each form's µs a layer-chunk printed as
one JSON line a frontier; PERF.md section 6 has the readings. On a TPU:

    python experiments/mla_probe.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from idc_models_tpu import ring_decode as rd  # noqa: E402

T, C, H, NOPE, ROPE, V, R, K, BLK = 32768, 512, 64, 192, 64, 256, 512, 2048, 512
LANES = rd.latent_cache_shape(1, T, R + ROPE)[-1]
SCALE = (NOPE + ROPE) ** -0.5


def timed(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6, out


def absorbed(q_nope, q_pe, lc, w_kv, bits, frontier):
    q_lat = jnp.einsum("bchn,rhn->bchr", q_nope, w_kv[..., :NOPE],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, LANES - R - ROPE),))
    see = lambda g: rd._bit_block(bits, g[0], g.shape[0])[None, None]
    _, l, acc = rd._attend_to_frontier(q, lc, lc, see, frontier, BLK,
                                       scale=SCALE, row=jnp.int32(0))
    o = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)   # [1,H,C,W]
    return jnp.einsum("bhcr,rhv->bchv", o[..., :R], w_kv[..., NOPE:])


def expanded(q_nope, q_pe, lc, w_kv, bits, frontier):
    rows = jnp.arange(BLK, dtype=jnp.int32)
    qpos = frontier - C + jnp.arange(C, dtype=jnp.int32)

    def body(j, carry):
        m, l, acc = carry
        lb = lax.dynamic_slice(lc, (0, j * BLK, 0), (1, BLK, LANES))[0]
        kv = jnp.einsum("kr,rhn->khn", lb[:, :R], w_kv)          # [blk,H,N+V]
        s = (jnp.einsum("chn,khn->hck", q_nope[0], kv[..., :NOPE],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("chp,kp->hck", q_pe[0], lb[:, R:R + ROPE],
                          preferred_element_type=jnp.float32)) * SCALE
        g = j * BLK + rows
        vis = rd._bit_block(bits, g[0], BLK)[None] & (g[None, :] <= qpos[:, None])[None]
        s = jnp.where(vis, s, -1e30)
        mb = jnp.max(s, axis=-1)
        p = jnp.where(vis, jnp.exp(s - mb[..., None]), 0.0)
        ab = jnp.einsum("hck,khv->hcv", p.astype(kv.dtype), kv[..., NOPE:],
                        preferred_element_type=jnp.float32)
        m_new = jnp.maximum(m, mb)
        old, new = jnp.exp(m - m_new), jnp.exp(mb - m_new)
        return (m_new, l * old + jnp.sum(p, -1) * new,
                acc * old[..., None] + ab * new[..., None])

    init = (jnp.full((H, C), -1e30, jnp.float32), jnp.zeros((H, C), jnp.float32),
            jnp.zeros((H, C, V), jnp.float32))
    _, l, acc = lax.fori_loop(0, (frontier + BLK - 1) // BLK, body, init)
    o = acc / jnp.maximum(l, 1e-37)[..., None]
    return jnp.moveaxis(o, 0, 1)[None].astype(q_nope.dtype)


def main(frontiers=(2048, 8192, 16384, 28672)):
    ks = jax.random.split(jax.random.key(0), 5)
    bf = jnp.bfloat16
    lc = jnp.pad(jax.random.normal(ks[0], (1, T, R + ROPE), bf),
                 ((0, 0), (0, 0), (0, LANES - R - ROPE)))
    w_kv = (jax.random.normal(ks[1], (R, H, NOPE + V), jnp.float32) / np.sqrt(R)
            ).astype(bf)
    q_nope = jax.random.normal(ks[2], (1, C, H, NOPE), bf)
    q_pe = jax.random.normal(ks[3], (1, C, H, ROPE), bf)
    device = jax.devices()[0]
    for frontier in frontiers:
        # 2,048 positions a query among those it sees, packed as the
        # chunk fold's bits
        qpos = frontier - C + np.arange(C)
        rng = np.random.default_rng(frontier)
        mask = np.zeros((C, T), bool)
        for i, p in enumerate(qpos):
            mask[i, rng.choice(p + 1, min(K, p + 1), replace=False)] = True
        bits = jnp.asarray((mask.reshape(C, -1, 32).astype(np.uint32)
                            << np.arange(32, dtype=np.uint32)).sum(
                                -1, dtype=np.uint32))
        f = jnp.int32(frontier)
        row = {"frontier": frontier, "chunk": C, "device": device.device_kind}
        outs = {}
        for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
            us, outs[name] = timed(jax.jit(fn), q_nope, q_pe, lc, w_kv,
                                   bits, f)
            row[f"{name}_us"] = round(us, 1)
        a, b = (np.asarray(outs[n], np.float32) for n in ("absorbed", "expanded"))
        row["max_rel_diff"] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
