"""Pairwise one-time-mask secure aggregation primitives (TPU fast path).

The reference's secure aggregation is Paillier homomorphic encryption of a
fraction of the weight tensors (secure_fed_model.py:109-129): the server
averages ciphertexts it cannot read. Pure-Python bignum crypto does not map
to XLA, so the TPU-native design (SURVEY.md D4) is Bonawitz-style pairwise
masking: every ordered client pair (i, j) shares a PRG seed; client i adds
`+mask_ij` for j > i and `-mask_ij` for j < i to its update before the
`psum`. Each device's contribution is indistinguishable from random to the
aggregator, but the masks cancel *exactly* in the sum.

Exact cancellation requires integer arithmetic (fp addition of large masks
would destroy precision): updates are quantized to int32 fixed-point,
masks are uniform int32, and addition wraps mod 2^32 (two's-complement),
so `psum` of masked updates == `psum` of plain quantized updates bit-for-bit.

The reference's `percent` knob — encrypt the first `int(num_tensors *
percent)` weight tensors (secure_fed_model.py:115-121) — maps to a boolean
selection pytree over the same flatten order (`first_fraction_selection`).

Seed agreement: the reference generates one global keypair visible to all
parties (quirk Q9); the analogous simplification here is deriving the
pairwise seed from a shared base key via `fold_in(fold_in(key, lo),
hi)` — both endpoints of a pair compute the same seed with no exchange. A
deployment would replace `pair_key` with a Diffie-Hellman-agreed seed; the
cancellation algebra is unchanged.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_SCALE_BITS = 20  # fixed-point fractional bits
DEFAULT_CLIP_ABS = 64.0  # quantization clipping range for weights


def choose_scale_bits(n_clients: int,
                      clip_abs: float = DEFAULT_CLIP_ABS) -> int:
    """Largest scale_bits such that the un-masked sum over `n_clients`
    values of magnitude <= clip_abs cannot overflow int32 — strictly
    2^scale * clip_abs * n_clients <= 2^31 - 1 (2^31 itself wraps to
    INT32_MIN and sign-flips a fully saturated element). Mask wraparound
    is mod-2^32 by design and cancels; it is the *unwrapped* sum of
    quantized values that must stay in range for dequantize to be
    correct."""
    n = max(n_clients, 1)
    bits = 31 - math.ceil(math.log2(n * clip_abs))
    while bits > 0 and (2.0 ** bits) * clip_abs * n > 2**31 - 1:
        bits -= 1
    if bits < 1:
        raise ValueError(
            f"no int32 headroom for {n_clients} clients at clip {clip_abs}")
    return min(bits, DEFAULT_SCALE_BITS)


def quantize(x: jax.Array, scale_bits: int = DEFAULT_SCALE_BITS, *,
             clip_abs: float | None = DEFAULT_CLIP_ABS) -> jax.Array:
    """fp32 -> int32 fixed point (round-to-nearest), clipped to
    +-clip_abs so the value always fits its headroom budget (see
    `choose_scale_bits`) instead of silently wrapping."""
    x = x.astype(jnp.float32)
    if clip_abs is not None:
        x = jnp.clip(x, -clip_abs, clip_abs)
    return jnp.round(x * (2.0 ** scale_bits)).astype(jnp.int32)


def dequantize(q: jax.Array, scale_bits: int = DEFAULT_SCALE_BITS,
               *, count: jax.Array | float = 1.0) -> jax.Array:
    """int32 fixed point -> fp32, dividing by `count` (for the mean).

    Evaluated in two exact pieces: the integer part (|q| < 2^31 -> below
    2^(31-scale_bits)) and the fractional part (< 2^scale_bits <= 2^23)
    are each exactly representable in fp32, so rounding happens only in
    the final add/divide — a few ulps of the *result*. A straight
    `q.astype(f32)` would instead drop low bits of any sum above 2^24
    (reachable with clip_abs=64, scale_bits=20, 8 clients), losing the
    advertised 2^-scale_bits resolution even when the mean is small.
    """
    scale = 1 << scale_bits
    hi = q // scale                  # floor division: exact, lo stays >= 0
    lo = q - hi * scale              # in [0, scale)
    return (hi.astype(jnp.float32)
            + lo.astype(jnp.float32) / jnp.float32(scale)) / count


def pair_key(base: jax.Array, i: jax.Array, j: jax.Array) -> jax.Array:
    """The shared PRG key for the unordered pair {i, j}: both endpoints
    compute fold_in(fold_in(base, min), max) and get the same key."""
    lo = jnp.minimum(i, j)
    hi = jnp.maximum(i, j)
    return jax.random.fold_in(jax.random.fold_in(base, lo), hi)


def pairwise_mask(base: jax.Array, my_id: jax.Array, n_clients: int,
                  shape, round_index: jax.Array | int = 0) -> jax.Array:
    """Client `my_id`'s total mask: sum over peers j of sign(i,j)*PRG(i,j).

    Signs are antisymmetric (+ for j > i, - for j < i) and the PRG stream
    for a pair is identical at both endpoints, so summing all clients'
    masks gives exactly zero mod 2^32. `round_index` is folded in so masks
    are one-time per round.

    Implemented as a `fori_loop` so the traced program is O(1) in client
    count (one PRG op, n iterations at runtime) instead of unrolling
    n_clients full-tensor streams per protected tensor.
    """
    base = jax.random.fold_in(base, round_index)
    iinfo = jnp.iinfo(jnp.int32)
    my_id = jnp.asarray(my_id, jnp.int32)

    def body(j, total):
        j = jnp.asarray(j, jnp.int32)
        k = pair_key(base, my_id, j)
        m = jax.random.randint(k, shape, iinfo.min, iinfo.max,
                               dtype=jnp.int32)
        sign = jnp.sign(j - my_id)
        return total + sign * m

    return lax.fori_loop(0, n_clients, body, jnp.zeros(shape, jnp.int32))


# Keras get_weights() enumerates each layer's variables in creation order:
# kernel before bias (Conv2D/Dense), gamma(scale) -> beta(bias) -> moving
# mean -> moving var (BatchNorm). jax's dict flatten is alphabetical, so
# ordered selection must re-rank within a layer too.
_WITHIN_LAYER_RANK = {"kernel": 0, "depthwise_kernel": 0, "scale": 0,
                      "bias": 1, "mean": 2, "var": 3}


def first_fraction_selection(tree, percent: float,
                             layer_order: tuple[str, ...] | None = None):
    """Boolean pytree: True for the first int(L * percent) tensors — the
    reference's partial-encryption selection (secure_fed_model.py:115-121
    slices `self.weights[:num_enc]`, i.e. Keras get_weights() order).

    With `layer_order` (a Module's `layer_names`), "first" follows the
    model's layer order with Keras within-layer variable order — matching
    the reference's get_weights() enumeration for Sequential models.
    Without it, jax's (alphabetical) flatten order is used; that is a
    well-defined deterministic order but NOT the reference's, so callers
    wanting parity must pass the order.

    For models with mutable state (BatchNorm), use
    `first_fraction_selection_weights` — the reference slices the FULL
    get_weights() list, which interleaves moving statistics.
    """
    return first_fraction_selection_weights(tree, {}, percent,
                                            layer_order)[0]


# Auto-selection threshold for the fused Pallas mask kernel (secure
# fedavg mask_impl="auto"): measured in round 4 on a v5 lite chip,
# through a runtime that no longer exists (not in the ledger), with
# dispatch overhead amortized INSIDE one jit
# (experiments/mask_crossover.jsonl), the fused kernel never lost — 1.04x at 262k elements rising to 2.48x
# at 33.5M — but below ~4M elements the win is ~0.1 ms (noise) while
# the round path pays one kernel call per local client; above it the
# win is >=1.5x of a cost that actually matters. Off-TPU, interpret
# mode makes the kernel unusable, so auto always resolves to threefry.
MASK_PALLAS_MIN_ELEMS = 4_194_304


def first_fraction_selection_weights(params, state, percent: float,
                                     layer_order: tuple[str, ...] | None
                                     = None):
    """`first_fraction_selection` over the FULL get_weights() enumeration:
    trainable params AND mutable state (BN moving statistics) interleaved
    in model layer order, which is what the reference actually slices —
    Keras get_weights() yields gamma, beta, moving_mean, moving_var per
    BatchNorm layer and `self.weights[:num_enc]` cuts across that list
    (secure_fed_model.py:115-121). Selecting over params alone would
    protect a different tensor set for any BN-bearing model.

    Returns ``(params_flags, state_flags)`` boolean pytrees; the count of
    True flags across both is ``int((P + S) * percent)``. For stateless
    models this degrades to exactly `first_fraction_selection(params)`.
    """
    p_paths = leaf_paths(params)
    s_paths = leaf_paths(state)
    paths = p_paths + s_paths
    n_enc = int(len(paths) * percent)
    flags = [False] * len(paths)
    for i in ranked_indices(paths, layer_order)[:n_enc]:
        flags[i] = True
    _, p_def = jax.tree.flatten(params)
    _, s_def = jax.tree.flatten(state)
    return (jax.tree.unflatten(p_def, flags[:len(p_paths)]),
            jax.tree.unflatten(s_def, flags[len(p_paths):]))


def ranked_indices(paths: list[tuple[str, ...]],
                   layer_order: tuple[str, ...] | None) -> list[int]:
    """Permutation of range(len(paths)) ranking leaf paths in model layer
    order (Keras get_weights() enumeration); identity without an order.

    `layer_order` entries may be dotted paths ("backbone.block1_conv1") as
    produced by `core.classifier`; a leaf is assigned the longest matching
    prefix of its own dotted path, so nested composites rank by their true
    layer order rather than collapsing to the top-level key.
    """
    if not layer_order:
        return list(range(len(paths)))
    order_index = {name: i for i, name in enumerate(layer_order)}

    def rank(path):
        li = len(layer_order)
        # longest-prefix match, INCLUDING the full path (a length-1 path's
        # only prefix is itself)
        for k in range(len(path), 0, -1):
            hit = order_index.get(".".join(path[:k]))
            if hit is not None:
                li = hit
                break
        wi = _WITHIN_LAYER_RANK.get(path[-1], 1)
        return (li, wi, path)

    return sorted(range(len(paths)), key=lambda i: rank(paths[i]))


def leaf_paths(tree) -> list[tuple[str, ...]]:
    """Key paths of a pytree's leaves in jax flatten order."""
    paths_and_leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [tuple(k.key for k in p) for p, _ in paths_and_leaves]


def pack_leaves(leaves, dtype=jnp.float32, *, lead_axes: int = 0):
    """Concatenate arrays into ONE flat vector (+ static split metadata).

    The round boundary uses this to turn per-tensor collectives into a
    single psum/pmean over one buffer — O(1) collectives per round
    instead of O(tensors), and one PRG stream covers every protected
    element. Returns (flat, meta); `unpack_leaves(flat, meta)` inverts.

    `lead_axes=n` treats each leaf's first n axes as batch dims (the
    k-clients-per-device round stacks client updates on a leading axis):
    the result is [*lead, P] and the meta describes the per-item tail
    shapes, so `unpack_leaves` recovers single-item leaves.
    """
    shapes = [tuple(x.shape[lead_axes:]) for x in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    dtypes = [x.dtype for x in leaves]
    if not leaves:
        return jnp.zeros((0,), dtype), (sizes, shapes, dtypes)
    lead = leaves[0].shape[:lead_axes]
    flat = jnp.concatenate(
        [x.reshape(lead + (-1,)).astype(dtype) for x in leaves],
        axis=lead_axes)
    return flat, (sizes, shapes, dtypes)


def unpack_leaves(flat, meta):
    sizes, shapes, dtypes = meta
    out, off = [], 0
    for size, shape, dt in zip(sizes, shapes, dtypes):
        out.append(flat[off:off + size].reshape(shape).astype(dt))
        off += size
    return out
