"""Thin, well-tested wrappers over XLA collectives.

This module is the framework's entire "communication backend" — the
replacement for NCCL, which the reference uses implicitly through
`MirroredStrategy`'s default CrossDeviceOps (SURVEY.md D5; no explicit
collective code exists anywhere in the reference). On TPU these lower to
ICI ring reductions within a pod slice and DCN across hosts; the choice is
made by the XLA compiler at compile time, not by a runtime library.

All functions are meant to be called *inside* `shard_map`-ed (or otherwise
axis-bound) functions, where `axis_name` is in scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def psum(tree, axis_name: str):
    """Sum a pytree across an axis (gradient allreduce; mask cancellation)."""
    return lax.psum(tree, axis_name)


def pmean(tree, axis_name: str):
    """Mean a pytree across an axis (FedAvg unweighted aggregate)."""
    return lax.pmean(tree, axis_name)


def weighted_pmean(tree, weight, axis_name: str):
    """Example-weighted mean across an axis.

    The reference's TFF FedAvg is example-weighted while its hand-rolled
    secure server is an unweighted mean (quirk Q7, secure_fed_model.py:160-168);
    we expose the weighted form as the primitive and let callers pass
    weight=1 to recover the unweighted behavior.

    Failure-tolerance semantics: negative weights are treated as 0, and
    zero-weight members are excluded even if their values are non-finite
    (a crashed/diverged client would otherwise poison the aggregate
    through NaN * 0 == NaN). If EVERY member has weight 0 the result is
    a zero tree, not NaN — callers that must distinguish "no
    contributors" should check psum(weight) themselves (the FedAvg round
    keeps its previous state in that case).
    """
    return weighted_pmean_local(
        jax.tree.map(lambda x: jnp.asarray(x)[None], tree),
        jnp.asarray(weight, jnp.float32).reshape(1), axis_name)


def weighted_pmean_local(tree, weights, axis_name: str):
    """Weighted mean over members stacked on each leaf's LEADING axis and
    over the mesh axis — the k-clients-per-device round boundary
    (`weights` has shape [k], leaves [k, ...]). Same failure-tolerance
    semantics as `weighted_pmean`, of which this is the general form.
    """
    weights = jnp.maximum(jnp.asarray(weights, jnp.float32), 0.0)
    total = lax.psum(weights.sum(), axis_name)
    safe_total = jnp.maximum(total, jnp.float32(1e-30))

    def contrib(x):
        w = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        masked = jnp.where(w > 0, x * w, jnp.zeros_like(x)).sum(axis=0)
        return lax.psum(masked, axis_name) / safe_total.astype(x.dtype)

    return jax.tree.map(contrib, tree)


def all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = False):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute(x, axis_name: str, perm):
    """Point-to-point permutation — the primitive behind ring schedules and
    pairwise-mask key agreement (secure aggregation)."""
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)


def ring_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    """Source->dest pairs for a ring shift of `shift` over n devices."""
    return [(i, (i + shift) % n) for i in range(n)]


def reduce_scatter(x, axis_name: str, *, scatter_dimension: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                            tiled=True)


def ring_psum(x, axis_name: str):
    """All-reduce as an EXPLICIT bandwidth-optimal ring: a chunked
    reduce-scatter followed by an all-gather, each built from n-1
    neighbor `ppermute` shifts.

    `psum` compiles to this same schedule on a TPU ICI ring, so the
    normal hot path should just use `psum` and let XLA pick; this
    explicit form exists because it is the schedule under *user*
    control — the building block for programs that need to interleave
    per-hop compute with the transfers (ring/blockwise schedules over a
    sequence axis, e.g. ring attention, stage exactly this loop with the
    block compute fused between hops), which SURVEY.md §5 calls out as
    the future-facing reason this module exposes `ppermute`.

    Equal to `psum` up to summation order: bit-exact for integer dtypes
    (the secure-aggregation masks rely on int32 wrap-around, which is
    order-free), within fp tolerance for floats.

    Compile-time scaling: the 2(n-1) hops are unrolled in Python, so HLO
    size (and the dynamic-index `.at[].set` chain) grows linearly with
    ring size — fine for ICI-scale rings (n <= 64), deliberate for
    per-hop fusion control. A pod-of-pods ring would want the loop
    restructured as `lax.fori_loop` over rotating blocks; do that when
    such a ring becomes a real use case, not before.
    """
    n = int(axis_size(axis_name))
    if n == 1:
        return x
    me = lax.axis_index(axis_name)
    fwd = ring_perm(n)
    flat = x.reshape(-1)
    chunk = -(-flat.size // n)
    blocks = jnp.pad(flat, (0, chunk * n - flat.size)).reshape(n, chunk)

    # Reduce-scatter: after step s the carry holds s+2 devices' partial
    # sum; after n-1 steps device i owns the full sum of block (i+1)%n.
    carry = blocks[me]
    for s in range(n - 1):
        carry = lax.ppermute(carry, axis_name, fwd)
        carry = carry + blocks[jnp.mod(me - s - 1, n)]

    # All-gather: circulate the n reduced blocks back around the ring.
    out = jnp.zeros_like(blocks).at[jnp.mod(me + 1, n)].set(carry)
    for s in range(n - 1):
        carry = lax.ppermute(carry, axis_name, fwd)
        out = out.at[jnp.mod(me - s, n)].set(carry)
    return out.reshape(-1)[: flat.size].reshape(x.shape)
