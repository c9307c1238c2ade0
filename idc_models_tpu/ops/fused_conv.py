"""Pallas TPU kernel: fused depthwise-conv + folded-BN + ReLU6 (ISSUE 16).

MobileNetV2's hot chains lower as three separate XLA ops — depthwise
conv, batchnorm, relu6 — each materializing the full activation tensor
in HBM between them. A depthwise conv does ~9 FLOPs per activation
byte (no channel contraction, nothing for the MXU to reduce), so every
unfused boundary roughly doubles the bytes per useful FLOP; XLA's cost
account of the compiled step (`profile --model mobile`, PR 14: FLOPs
over bytes) puts the whole train step at arithmetic intensity 3.5
against the v5e ridge of ~240 and named these
chains as the implicated lowering. This kernel keeps the activation
tile in VMEM across all three ops: one grid cell loads an image's
padded activation once, runs the kh*kw shifted multiply-accumulates
(the same taps formulation `core.depthwise_conv2d(impl="taps")` pins
against XLA's grouped lowering), applies the FOLDED batchnorm as one
scale/shift, clamps to [0, 6], and writes the output tile — HBM
traffic is x in + y out, nothing between.

BN folding happens OUTSIDE the kernel (and outside the custom_vjp), in
plain jnp, so it stays differentiable for free:

    mul = scale * rsqrt(var + eps)
    add = bias - mean * mul
    y   = relu6(dwconv(x) * mul + add)

which is exactly the inference / frozen-BN composition — the paths the
transfer-learning recipe runs (`bn_frozen_below` freezes every BN
below the fine-tune boundary, and phase-1 freezes all of them). In
unfrozen train mode BN needs batch statistics, so callers fall back to
the unfused chain there (models/mobilenet.py does this per-layer,
statically).

Grid/tiling: one grid cell per (image, channel tile). Spatial tiling
is deliberately NOT done — a 3x3 conv's spatial tiles overlap by a
halo, and Pallas BlockSpecs cannot express overlapping blocks, so the
per-cell block is the full padded image. Channels, by contrast, are
fully independent in a depthwise conv, so the channel axis is the free
tiling axis that bounds VMEM: `channel_tile` splits C when the full
image does not fit (`_pick_channel_tile`: it must divide C and, for
Mosaic, be a multiple of 128 lanes). At the paper's 50x50 patches
every activation fits untiled (largest: 25x25x96 f32 = 240 KB/image).

Gradients: `_fused` carries a custom_vjp whose backward differentiates
the pure-jnp reference at the saved inputs — the flash_block_kernel
pattern — so `depthwise_impl="fused"` trains (the depthwise kernels
above the fine-tune boundary still receive gradients even while their
BNs are frozen). The backward is ordinary XLA code and fuses fine; the
forward is where the unfused chain paid.

Testing contract: `interpret=True` runs the SAME kernel body under the
Pallas interpreter on CPU, so tier-1 parity tests exercise the real
code path, not a stand-in; `interpret=None` (the default) resolves to
the interpreter off-TPU and to Mosaic on TPU (`mesh.pallas_interpret`).
XLA's `cost_analysis` cannot see inside a Pallas custom call, so
`depthwise_chain_cost` provides the analytic FLOPs/bytes the profile
verb merges into its ProgramCost (observe/profile.py `augment_cost` /
`register_cost`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from idc_models_tpu import mesh as meshlib

# What ONE copy of a cell's input + output blocks may occupy in VMEM
# before the kernel insists on channel tiling. The v5e core has 128 MiB
# of VMEM (jax's pallas tpu_info), but Mosaic gives a kernel 16 MiB of
# scoped VMEM unless `vmem_limit_bytes` raises it — nothing here does —
# and the BlockSpec pipeline double-buffers every block. Compiled for
# v5e, a cell of 6.7 MiB fits (12.6 MiB allocated) and one of 10.0 MiB
# is refused ("Scoped allocation with size 18.88M and limit 16.00M").
VMEM_BUDGET_BYTES = 6 * 1024 * 1024
_LANES = 128


def fold_bn(scale, bias, mean, var, eps):
    """Fold inference-mode batchnorm into one (mul, add) affine pair:
    ``bn(y) = (y - mean) * rsqrt(var + eps) * scale + bias
            = y * mul + add``.
    Plain jnp on purpose — it runs outside the kernel (and outside the
    custom_vjp), so scale/bias gradients come from ordinary autodiff."""
    mul = scale * lax.rsqrt(var + eps)
    return mul, bias - mean * mul


def _same_pad(x, kh, kw, sh, sw):
    """TF-SAME padding (lo = total//2, hi = rest — matches XLA and the
    core.py taps impl) plus the padded/output spatial sizes."""
    _, h_in, w_in, _ = x.shape
    h_out, w_out = -(-h_in // sh), -(-w_in // sw)
    ph = max((h_out - 1) * sh + kh - h_in, 0)
    pw = max((w_out - 1) * sw + kw - w_in, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    return xp, h_out, w_out


def reference_impl(x, w, mul, add, *, stride=1, clamp6=True):
    """Pure-jnp mirror of the kernel: taps depthwise conv (TF-SAME),
    folded-BN affine, optional ReLU6. The parity target for the Pallas
    path and the function the custom_vjp backward differentiates."""
    kh, kw = int(w.shape[0]), int(w.shape[1])
    sh, sw = (stride, stride) if isinstance(stride, int) else stride
    xp, h_out, w_out = _same_pad(x, kh, kw, sh, sw)
    wf = w.reshape(kh, kw, -1)
    y = None
    for i in range(kh):
        for j in range(kw):
            xs = xp[:, i:i + (h_out - 1) * sh + 1:sh,
                    j:j + (w_out - 1) * sw + 1:sw, :]
            t = xs.astype(jnp.float32) * wf[i, j]
            y = t if y is None else y + t
    y = y * mul + add
    if clamp6:
        y = jnp.clip(y, 0.0, 6.0)
    return y.astype(x.dtype)


def _phase_split(xp, sh, sw, hh, wh):
    """Space-to-depth of the padded input for a strided conv: plane
    (p, q) holds ``xp[:, p::sh, q::sw, :]``, so tap (i, j) of a
    stride-(sh, sw) conv is a UNIT-stride window of plane
    (i % sh, j % sw) at offset (i // sh, j // sw). Mosaic can then load
    every tap plainly: it refuses a strided slice of a loaded value
    (`vector.extract_strided_slice` takes stride 1 only), and its
    strided loads from a ref need 32-bit data in exactly 128 lanes.
    Returns [N, sh*sw, hh, wh, C]; at stride 1 this is a reshape."""
    n, h_p, w_p, c = xp.shape
    xp = jnp.pad(xp, ((0, 0), (0, hh * sh - h_p), (0, wh * sw - w_p),
                      (0, 0)))
    planes = xp.reshape(n, hh, sh, wh, sw, c).transpose(0, 2, 4, 1, 3, 5)
    return planes.reshape(n, sh * sw, hh, wh, c)


def _kernel(xph_ref, w_ref, mul_ref, add_ref, out_ref, *,
            kh, kw, sh, sw, h_out, w_out, clamp6):
    """One (image, channel-tile) cell: taps MAC + affine + clamp, all
    on the VMEM-resident tile; each tap is a unit-stride window load of
    one phase plane (`_phase_split`)."""
    acc = None
    for i in range(kh):
        for j in range(kw):
            xs = xph_ref[0, (i % sh) * sw + j % sw,
                         pl.ds(i // sh, h_out), pl.ds(j // sw, w_out), :]
            t = xs.astype(jnp.float32) * w_ref[i * kw + j, :]
            acc = t if acc is None else acc + t
    y = acc * mul_ref[0] + add_ref[0]
    if clamp6:
        y = jnp.clip(y, 0.0, 6.0)
    out_ref[0] = y.astype(out_ref.dtype)


def _vmem_bytes(rows, w, c, itemsize):
    """Bytes a [rows, w, c] block occupies in VMEM's tiled layout: the
    last dim pads to 128 lanes, the one before it to a whole sublane
    tile (8 rows of 32-bit data; narrower dtypes pack more rows)."""
    sublanes = 8 * max(4 // itemsize, 1)
    return (rows * -(-w // sublanes) * sublanes
            * -(-c // _LANES) * _LANES * itemsize)


def _pick_channel_tile(in_rows, w_in, h_out, w_out, c, itemsize,
                       channel_tile):
    """Resolve the channel-tile size: an explicit request must divide C
    (Pallas itself refuses, on TPU, one that is neither C nor a
    multiple of 128 lanes); `None` means whole-C unless the cell's
    input + output blocks bust the VMEM budget, in which case the
    largest budget-fitting divisor of C that is a multiple of 128 is
    chosen — and a shape with no such divisor is refused."""
    if channel_tile is not None:
        if c % channel_tile:
            raise ValueError(f"channel_tile {channel_tile} must divide "
                             f"channel count {c}")
        return channel_tile

    def cell_bytes(ct):
        return (_vmem_bytes(in_rows, w_in, ct, itemsize)
                + _vmem_bytes(h_out, w_out, ct, itemsize))

    if cell_bytes(c) <= VMEM_BUDGET_BYTES:
        return c
    fits = [d for d in range(_LANES, c, _LANES)
            if c % d == 0 and cell_bytes(d) <= VMEM_BUDGET_BYTES]
    if not fits:
        raise ValueError(
            f"fused depthwise kernel: one image's [{in_rows}, {w_in}, "
            f"{c}] input + [{h_out}, {w_out}, {c}] output blocks need "
            f"{cell_bytes(c)} bytes of VMEM (budget "
            f"{VMEM_BUDGET_BYTES}) and {c} channels have no divisor "
            f"that is a multiple of {_LANES} lanes and fits; use the "
            f"grouped depthwise lowering at this resolution")
    return max(fits)


def _pallas_impl(x, w, mul, add, *, stride, clamp6, interpret,
                 channel_tile):
    kh, kw = int(w.shape[0]), int(w.shape[1])
    sh, sw = stride
    n, _, _, c = x.shape
    xp, h_out, w_out = _same_pad(x, kh, kw, sh, sw)
    # rows/cols one phase plane must offer: the largest tap offset plus
    # the output extent
    hh, wh = h_out + (kh - 1) // sh, w_out + (kw - 1) // sw
    xph = _phase_split(xp, sh, sw, hh, wh)
    ct = _pick_channel_tile(sh * sw * hh, wh, h_out, w_out, c,
                            jnp.dtype(x.dtype).itemsize, channel_tile)
    wf = w.reshape(kh * kw, c).astype(jnp.float32)
    mul2 = mul.reshape(1, c).astype(jnp.float32)
    add2 = add.reshape(1, c).astype(jnp.float32)
    kern = functools.partial(_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                             h_out=h_out, w_out=w_out, clamp6=clamp6)
    return pl.pallas_call(
        kern,
        grid=(n, c // ct),
        in_specs=[
            pl.BlockSpec((1, sh * sw, hh, wh, ct),
                         lambda i, j: (i, 0, 0, 0, j)),
            pl.BlockSpec((kh * kw, ct), lambda i, j: (0, j)),
            pl.BlockSpec((1, ct), lambda i, j: (0, j)),
            pl.BlockSpec((1, ct), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, h_out, w_out, ct),
                               lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, h_out, w_out, c), x.dtype),
        interpret=interpret,
    )(xph, wf, mul2, add2)


@functools.lru_cache(maxsize=None)
def _make_fused(stride, clamp6, interpret, channel_tile):
    """custom_vjp closure over the static config: Pallas forward,
    backward = jax.vjp of the jnp reference at the saved inputs (the
    flash_block_kernel pattern — exact w.r.t. the reference math)."""

    @jax.custom_vjp
    def fused(x, w, mul, add):
        return _pallas_impl(x, w, mul, add, stride=stride,
                            clamp6=clamp6, interpret=interpret,
                            channel_tile=channel_tile)

    def fwd(x, w, mul, add):
        return fused(x, w, mul, add), (x, w, mul, add)

    def bwd(res, g):
        x, w, mul, add = res
        _, vjp = jax.vjp(
            lambda x_, w_, m_, a_: reference_impl(
                x_, w_, m_, a_, stride=stride, clamp6=clamp6),
            x, w, mul, add)
        return vjp(g)

    fused.defvjp(fwd, bwd)
    return fused


def fused_depthwise_affine(x, w, mul, add, *, stride=1, clamp6=True,
                           interpret=None, channel_tile=None):
    """Fused `clamp6(dwconv(x) * mul + add)` (TF-SAME padding).

    x: [N, H, W, C]; w: [kh, kw, 1, C] (the core.depthwise_conv2d param
    layout); mul/add: [C] folded-BN affine (identity: ones/zeros).
    Differentiable in all four array arguments via the reference-vjp
    backward. `interpret=None` follows the one platform rule
    (`mesh.pallas_interpret`): Mosaic on TPU devices, the Pallas
    interpreter (same kernel body) everywhere else — the
    tier-1-on-CPU testing contract. No mesh is in scope inside a
    model's apply, so the devices are the process's default ones.
    """
    if interpret is None:
        interpret = meshlib.pallas_interpret()
    strides = (stride, stride) if isinstance(stride, int) else stride
    return _make_fused(tuple(strides), bool(clamp6), bool(interpret),
                       channel_tile)(x, w, mul, add)


def fused_depthwise_bn_relu6(x, w, scale, bias, mean, var, *, eps,
                             stride=1, interpret=None,
                             channel_tile=None):
    """The MobileNetV2 chain: depthwise conv -> inference-mode BN ->
    ReLU6, one kernel. `scale`/`bias` are BN params, `mean`/`var` the
    moving statistics — folding happens here, outside the kernel's
    custom_vjp, so their gradients flow through ordinary autodiff."""
    mul, add = fold_bn(scale, bias, mean, var, eps)
    return fused_depthwise_affine(x, w, mul, add, stride=stride,
                                  clamp6=True, interpret=interpret,
                                  channel_tile=channel_tile)


# ---------------------------------------------------------------------------
# analytic cost — XLA cost_analysis cannot see inside a Pallas call
# ---------------------------------------------------------------------------


def depthwise_call_cost(n, h_in, w_in, c, *, stride=1, kernel_size=3,
                        itemsize=4):
    """Analytic (flops, bytes_accessed) of ONE fused call: kh*kw MACs +
    the affine + the clamp per output element; HBM bytes are the padded
    input + output + the (tiny) weight/affine operands."""
    k = kernel_size
    sh, sw = (stride, stride) if isinstance(stride, int) else stride
    h_out, w_out = -(-h_in // sh), -(-w_in // sw)
    out_elems = n * h_out * w_out * c
    flops = float(out_elems * (2 * k * k + 3))
    h_p = (h_out - 1) * sh + k
    w_p = (w_out - 1) * sw + k
    bytes_accessed = float(
        (n * h_p * w_p * c + out_elems) * itemsize
        + (k * k * c + 2 * c) * 4)
    return flops, bytes_accessed


def depthwise_chain_cost(calls, *, itemsize=4):
    """Sum `depthwise_call_cost` over `calls` — an iterable of dicts of
    its keyword arguments (models/mobilenet.py `fused_call_shapes`
    produces the schedule). Returns (flops, bytes_accessed)."""
    flops = bytes_accessed = 0.0
    for call in calls:
        f, b = depthwise_call_cost(itemsize=itemsize, **call)
        flops += f
        bytes_accessed += b
    return flops, bytes_accessed
