"""Sparse expert feed-forward layer that is TOLD which experts it holds.

A deployment divides a layer's routed experts over the chips that share
the layer (expert parallelism); this chip holds the contiguous range
``[first, first + count)`` of the ``n_experts`` the router scores. The
layer routes over ALL experts at the published width — softmax over
every router output in float32, the `top_k` largest, their weights
renormalised and scaled — and computes only its own experts' terms for
the tokens routed to them. What an absent expert would have added is
left out; nothing stands in for the other chips or their exchange, and
the partial sum is what goes on to the next layer (with every expert
held, it is the whole layer).

The product over held experts is a GROUPED matrix product over the
(token, expert) assignments sorted by expert: one shape-stable program
whatever the routing, which reads a held expert's weights only when some
token went to it. A dense pass over every held expert with a mask would
spend `count / top_k` times the arithmetic, and a per-token gather of
expert weights would read every assignment's 3 matrices separately.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Experts(NamedTuple):
    """The expert layer's shape, as data: hashable, part of a layer
    spec (models/lm.py)."""
    n_experts: int          # router outputs (the published count)
    top_k: int              # experts per token
    first: int              # the held range: experts [first, first + count)
    count: int
    routed_scale: float = 1.0   # multiplies the renormalised weights
    shared: bool = True         # one always-on SwiGLU expert beside them


def swiglu(p, x):
    """``(silu(x W_gate) * (x W_up)) W_down`` without bias."""
    g = x @ p["w_gate"].astype(x.dtype)
    u = x @ p["w_up"].astype(x.dtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(x.dtype)


def route(x, w_router, e: Experts):
    """Router over all `n_experts`: x [N, E] -> (weights [N, k] float32,
    experts [N, k] int32). Scores are a float32 softmax over every
    output; the k largest are renormalised to sum to 1 and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(scores, e.top_k)
    weights = e.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)
    return weights, idx.astype(jnp.int32)


# rows a grouped-product tile holds: M is padded to a multiple of it
_TILE_M = 128


def grouped_matmul(lhs, rhs, sizes, *, interpret: bool):
    """Rows of `lhs` [M, K] in consecutive groups of `sizes` [G], group g
    times `rhs[g]` [K, N]; float32 out; M a multiple of 128. jax's
    megablox Pallas kernel: it visits one (group, row tile) pair after
    another, so a group without rows costs nothing and a group's
    weights are read once per row tile it spans. Rows past the groups'
    total are not computed. Tiles: 128 rows, all of K up to 3072, 512
    of N — the fastest of the tilings tried at the Laguna shapes on a
    v5e (PERF.md, PR 27)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    k, n = rhs.shape[1], rhs.shape[2]
    return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
               tiling=(_TILE_M, min(k, 3072), min(n, 512)),
               interpret=interpret)


def expert_ffn(p, x, e: Experts, live=None, *, interpret: bool = False):
    """The layer on tokens x [N, E]: shared expert (every chip alike)
    plus this chip's routed terms. Returns (y [N, E], stats) where
    `stats` counts what the layer did for the LIVE tokens (`live` [N]
    bool, default all): ``held`` [count] int32 assignments per held
    expert, ``assigned`` int32 assignments to any expert, held or not,
    and ``picks`` [N, k] int32, the router's choice for every token.
    `interpret` runs the grouped product's kernel in the Pallas
    interpreter (`mesh.pallas_interpret`: off the TPU)."""
    n, k = x.shape[0], e.top_k
    with jax.named_scope("moe_router"):
        weights, picks = route(x, p["router"], e)
        local = picks - e.first
        mine = (local >= 0) & (local < e.count)
        # absent experts sort behind every held one and join no group
        group = jnp.where(mine, local, e.count).reshape(-1)     # [N*k]
        order = jnp.argsort(group, stable=True)
        token = order // k
        sizes = jnp.bincount(group, length=e.count + 1)[:e.count]
        sizes = sizes.astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        ex = p["experts"]
        pad = -(n * k) % _TILE_M
        order = jnp.pad(order, (0, pad))      # padding rows: token 0's,
        token = jnp.pad(token, (0, pad))      # past every group, kept out
        rows = jnp.take(x, token, axis=0)                       # [M, E]
        mm = lambda a, w: grouped_matmul(a, w.astype(a.dtype), sizes,
                                         interpret=interpret)
        h = (jax.nn.silu(mm(rows, ex["w_gate"]))
             * mm(rows, ex["w_up"])).astype(x.dtype)
        out = mm(h, ex["w_down"])                               # f32
        # rows past the last group are no expert's and hold whatever
        # the kernel left there: keep them out by value, not by a zero
        # weight (0 x garbage is not 0)
        keep = (jnp.arange(n * k + pad) < jnp.sum(sizes))[:, None]
        w_sorted = jnp.take(weights.reshape(-1), order)[:, None]
        out = jnp.where(keep, out * w_sorted, 0.0)
        y = jnp.zeros((n, x.shape[1]), jnp.float32).at[token].add(out)
    if e.shared:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(p["shared"], x).astype(jnp.float32)
    if live is None:
        held, n_live = sizes, n
    else:
        counted = jnp.where(live[:, None], group.reshape(n, k), e.count)
        held = jnp.bincount(counted.reshape(-1),
                            length=e.count + 1)[:e.count].astype(jnp.int32)
        n_live = jnp.sum(live)
    stats = {"held": held, "assigned": jnp.asarray(n_live * k, jnp.int32),
             "picks": picks}
    return y.astype(x.dtype), stats


def window_stats(stats):
    """A decode window's account of its expert layers, from the
    per-step `expert_ffn` statistics a scan stacked ([W, ...] leaves,
    one record per expert layer): ``held`` [layers, count] assignments
    each held expert was sent, ``touched`` [layers] held experts with
    at least one token, summed over the steps, ``assigned`` the
    assignments to any expert, ``steps`` the steps that had a live row,
    ``picks`` [W, layers, S, k] the router's choices. () for no expert
    layers."""
    if not stats:
        return ()
    held = jnp.stack([st["held"] for st in stats], axis=1)  # [W, L, count]
    return {
        "held": jnp.sum(held, axis=0),
        "touched": jnp.sum(jnp.sum(held > 0, axis=-1), axis=0),
        "assigned": sum(jnp.sum(st["assigned"]) for st in stats),
        "steps": jnp.sum(stats[0]["assigned"] > 0),
        "picks": jnp.stack([st["picks"] for st in stats], axis=1),
    }
