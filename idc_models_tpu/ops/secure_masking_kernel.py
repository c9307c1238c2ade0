"""Pallas TPU kernel: fused clip+quantize+pairwise-mask for secure
aggregation.

The hot op of a secure FedAvg round boundary (D4) is, per protected
tensor: clip -> fixed-point quantize -> add n_clients pairwise PRG mask
streams. Unfused (secure/masking.py), that is one quantize pass plus a
fori_loop of full-tensor PRG generations — each a separate HBM
read/write. This kernel does the whole chain in ONE pass: the tensor is
read into VMEM once, the mask streams are generated in-register from a
counter-based hash PRG (two rounds of the murmur3 finalizer over the
global element index), and the masked int32 tensor is written once.

The PRG is an explicit integer hash rather than the TPU hardware PRNG
(`pltpu.prng_random_bits`) for a correctness reason: pairwise masks must
be bit-identical at both endpoints of a pair *and* reproducible by any
backend that joins the aggregation (CPU simulation, interpret mode,
different TPU generations). A counter-based hash makes the stream a pure
function of (pair seed, element index) — `masked_quantize_reference`
computes the identical values with plain jnp, and the tests pin them
against each other.

Mask cancellation: signs are antisymmetric per pair and addition wraps
mod 2^32 (int32 two's complement), exactly like secure/masking.py.

Status: integrated into `secure.make_secure_fedavg_round` behind the
explicit opt-in ``mask_impl="auto"``: pallas on TPU once the protected
buffer reaches `masking.MASK_PALLAS_MIN_ELEMS` (4.2M elements),
threefry below it and off-TPU. The round DEFAULT remains threefry
because the masks are a privacy primitive and this hash PRG is not
cryptographic (see make_secure_fedavg_round's threat-model note) —
"auto" buys throughput where that trade is acceptable.
The crossover was measured in round 4, through a runtime that no longer
exists (not in the ledger; `experiments/mask_crossover.jsonl`, sweep
with dispatch amortized inside one jit on a v5 lite chip): the fused
pass never lost —
1.04x at 262k elements, 1.48x at 4.2M, 1.89x at VGG16's 14.7M, 2.48x
at 33.5M — but below the threshold the absolute win (~0.1 ms) is
noise while the round pays one kernel call per local client, and
threefry is also the cryptographically stronger PRG. (Round 3's
"threefry wins small" reading came from per-call timings dominated by
a ~10 ms per-call dispatch; the in-jit sweep replaces it.)
Both impls aggregate bit-identically (tests/test_secure.py pins this).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 512  # 512x128 int32 = 256 KiB per VMEM buffer
_GOLDEN = 0x9E3779B1  # plain int: jnp constants would be captured by the kernel trace


def _fmix32(h):
    """murmur3 finalizer — a full-avalanche 32-bit mixer (public domain
    constants)."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _mask_stream(seed_u32, idx_u32):
    """The pairwise PRG: mask element = fmix32(fmix32(seed ^ idx*GOLDEN))."""
    return _fmix32(_fmix32(seed_u32 ^ (idx_u32 * jnp.uint32(_GOLDEN))))


def pair_seeds_and_signs(base_seed, my_id, n_clients: int, round_index=0):
    """Per-peer (seeds [n], signs [n]) for client `my_id`.

    seeds[j] is a pure function of (base_seed, round, {min(i,j),
    max(i,j)}) so both endpoints derive the same stream; signs[j] =
    sign(j - i) gives the antisymmetric cancellation. Plain jnp — callable
    inside shard_map with a traced my_id.
    """
    js = jnp.arange(n_clients, dtype=jnp.int32)
    my_id = jnp.asarray(my_id, jnp.int32)
    lo = jnp.minimum(js, my_id).astype(jnp.uint32)
    hi = jnp.maximum(js, my_id).astype(jnp.uint32)
    base = jnp.asarray(base_seed, jnp.uint32) + jnp.uint32(round_index) * jnp.uint32(_GOLDEN)
    seeds = _fmix32(_fmix32(base ^ (lo * jnp.uint32(_GOLDEN))) ^ (hi * jnp.uint32(0x85EBCA77)))
    signs = jnp.sign(js - my_id)
    return seeds, signs


def _kernel(seeds_ref, signs_ref, x_ref, out_ref, *, n_clients, scale,
            clip_abs, total_rows):
    block = pl.program_id(0)
    rows, lanes = x_ref.shape
    x = jnp.clip(x_ref[:], -clip_abs, clip_abs)
    acc = jnp.round(x * scale).astype(jnp.int32)
    row0 = block * rows
    idx = (jnp.uint32(row0) * jnp.uint32(lanes)
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
           * jnp.uint32(lanes)
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1))
    for j in range(n_clients):
        mask = _mask_stream(seeds_ref[j], idx)
        acc = acc + signs_ref[j] * jax.lax.bitcast_convert_type(
            mask, jnp.int32)
    out_ref[:] = acc


def fused_masked_quantize(x, seeds, signs, *, scale_bits: int,
                          clip_abs: float, interpret: bool = False):
    """Quantize `x` (any shape, fp) to int32 fixed point and add this
    client's total pairwise mask — one fused pass.

    `seeds`/`signs` come from `pair_seeds_and_signs`. Output has x's
    shape; the mask stream is indexed over the padded flat layout, so all
    clients must use identical tensor shapes (they do: model replicas).
    """
    n_clients = seeds.shape[0]
    orig_shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = -(-n // _LANES)
    pad_rows = -(-rows // 8) * 8  # f32 tile: 8 sublanes
    padded = jnp.zeros((pad_rows * _LANES,), jnp.float32).at[:n].set(flat)
    grid_rows = min(_BLOCK_ROWS, pad_rows)
    n_blocks = -(-pad_rows // grid_rows)
    if pad_rows % grid_rows:
        extra = n_blocks * grid_rows - pad_rows
        padded = jnp.concatenate(
            [padded, jnp.zeros((extra * _LANES,), jnp.float32)])
        pad_rows = n_blocks * grid_rows
    x2 = padded.reshape(pad_rows, _LANES)

    kernel = functools.partial(
        _kernel, n_clients=n_clients, scale=float(2.0 ** scale_bits),
        clip_abs=float(clip_abs), total_rows=pad_rows)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((grid_rows, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((grid_rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pad_rows, _LANES), jnp.int32),
        interpret=interpret,
    )(seeds.astype(jnp.uint32), signs.astype(jnp.int32), x2)
    return out.reshape(-1)[:n].reshape(orig_shape)


def masked_quantize_reference(x, seeds, signs, *, scale_bits: int,
                              clip_abs: float):
    """Bit-identical plain-jnp implementation of the kernel (the
    cross-backend contract: any participant computing this joins the same
    aggregation)."""
    orig_shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    q = jnp.round(jnp.clip(flat, -clip_abs, clip_abs)
                  * (2.0 ** scale_bits)).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.uint32)
    acc = q
    for j in range(seeds.shape[0]):
        mask = _mask_stream(seeds[j].astype(jnp.uint32), idx)
        acc = acc + signs[j].astype(jnp.int32) * jax.lax.bitcast_convert_type(
            mask, jnp.int32)
    return acc.reshape(orig_shape)
