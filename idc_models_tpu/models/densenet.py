"""DenseNet201 backbone + transfer-learning head.

Capability parity with the reference's dense preset
(dist_model_tf_dense.py:131-141): DenseNet201 without top, GAP, Dense(10)
softmax-logits head for CIFAR-10, fine_tune_at=150
(dist_model_tf_dense.py:158).

Architecture follows keras.applications DenseNet201: stem conv(64,7x7,s2)
-> maxpool -> dense blocks [6,12,48,32] (growth 32; each layer is
BN-ReLU-conv1x1(128) -> BN-ReLU-conv3x3(32) -> concat) with 0.5-compression
transitions, final BN+ReLU. All convs bias-free; BN eps=1.001e-5. Total
params (incl. BN moving stats) = 18,321,984, matching Keras
include_top=False.

Dense blocks are CONCAT-FREE by default (`block_impl="packed"`, ISSUE
16): the literal `concat(h, f(h))` re-reads and re-writes the whole
growing feature map at every layer — the PR 14 MFU attribution measured
2.3 GB moved for 4.7 GFLOP, arithmetic intensity 2.0 against the v5e
ridge of ~240 — so instead the block's full [N, H, W, C_final] buffer
is allocated ONCE at the block's first layer and each layer
`dynamic_update_slice`s its 32-channel output into the next free
channel range, reading its input as a static slice of the buffer.
Channel layout ([input, y_1, y_2, ...]) is exactly the iterated-concat
layout, and every conv/BN sees bit-identical inputs, so pretrained
weight loading, golden outputs, and param counts are unchanged —
pinned by tests/test_fused_conv.py against `block_impl="concat"`, the
reference implementation kept for that parity test (and allowlisted as
such by the test_static_robustness concat ban).

`KERAS_LAYER_INDEX` reproduces Keras' flat layer numbering so the
reference's `fine_tune_at=150` (an index into `base_model.layers`, landing
inside conv4_block2) selects the same parameters here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from idc_models_tpu.models import core

_BLOCKS = [6, 12, 48, 32]
_GROWTH = 32
_BN = dict(eps=1.001e-5, momentum=0.99)

KERAS_LAYER_INDEX: dict[str, int] = {}


def _build_index():
    i = 0
    idx = {}

    def layer(name=None):
        nonlocal i
        if name is not None:
            idx[name] = i
        i += 1

    layer()                       # InputLayer
    layer()                       # ZeroPadding2D
    layer("conv1_conv")
    layer("conv1_bn")
    layer()                       # conv1_relu
    layer()                       # ZeroPadding2D
    layer()                       # pool1
    for stage, n_layers in enumerate(_BLOCKS, start=2):
        for l in range(1, n_layers + 1):
            p = f"conv{stage}_block{l}"
            layer(f"{p}_0_bn")
            layer()               # 0_relu
            layer(f"{p}_1_conv")
            layer(f"{p}_1_bn")
            layer()               # 1_relu
            layer(f"{p}_2_conv")
            layer()               # concat
        if stage < 5:
            layer(f"pool{stage}_bn")
            layer()               # pool relu
            layer(f"pool{stage}_conv")
            layer()               # avgpool
    layer("bn")
    layer()                       # relu
    return idx


KERAS_LAYER_INDEX = _build_index()


FREEZE_ALL = 10**9


def _units(in_channels: int, bn_frozen_below: int,
           block_impl: str = "packed"):
    """The backbone as topology units (stem, one unit per dense layer,
    one per transition, final BN) over the flat Keras-layer-name params:
    a dense layer is `h -> concat(h, f(h))` semantically — a pure
    function of its input — so every unit edge is a valid split point
    for the frozen-backbone feature cache despite the dense topology.
    Module-level (like mobilenet._units) so per-stage attribution
    microbenches (experiments/backbone_mfu.py) can build stage
    sub-models from unit ranges.

    `block_impl` picks the dense-block data movement, same values
    either way:

    - "packed" (default): the block's [N, H, W, C_final] buffer is
      allocated once at the block's first layer; each layer reads the
      static slice [:, :, :, :c_in] and dynamic_update_slices its
      32-channel output at c_in. Between the block's unit edges the
      activation carries C_final channels with the not-yet-written
      tail zero-filled — downstream layers never read it, and by the
      last layer the buffer is exactly full, so transitions and split
      points see the ordinary fully-valid tensor. (A mid-block split
      caches the partially-filled buffer; prefix-then-suffix
      composition stays bit-exact since each layer touches only its
      static channel ranges.)
    - "concat": the literal `concat(h, f(h))` — the parity reference
      the packed path is pinned bit-close against
      (tests/test_fused_conv.py). Not for production use: it re-materializes the whole
      growing feature map every layer.
    """
    if block_impl not in ("packed", "concat"):
        raise ValueError(
            f"block_impl must be packed|concat, got {block_impl!r}")
    specs: list[tuple[str, core.Module]] = []

    def reg(m) -> str:
        specs.append((m.name, m))
        return m.name

    def bn(c, name):
        frozen = KERAS_LAYER_INDEX[name] < bn_frozen_below
        return core.batch_norm(c, name=name, frozen=frozen, **_BN)

    units: list[tuple[list[str], object]] = []

    # Keras stem: ZeroPadding2D((3,3)) + valid 7x7/2 conv, then
    # ZeroPadding2D((1,1)) + valid 3x3/2 pool — symmetric padding, which
    # lax SAME (lo<=hi asymmetric) would shift by one pixel.
    stem_names = [
        reg(core.conv2d(in_channels, 64, 7, stride=2, use_bias=False,
                        padding=((3, 3), (3, 3)), name="conv1_conv")),
        reg(bn(64, "conv1_bn")),
    ]

    def stem(run, x):
        h = jax.nn.relu(run("conv1_bn", run("conv1_conv", x)))
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 3, 3, 1), (1, 2, 2, 1),
                                     [(0, 0), (1, 1), (1, 1), (0, 0)])

    units.append((stem_names, stem))

    def bottleneck(run, x, *, p):
        """One dense layer's BN-relu-conv1x1-BN-relu-conv3x3 trunk —
        shared by both block impls; they differ only in how its
        32-channel output joins the feature map."""
        y = jax.nn.relu(run(f"{p}_0_bn", x))
        y = run(f"{p}_1_conv", y)
        y = jax.nn.relu(run(f"{p}_1_bn", y))
        return run(f"{p}_2_conv", y)

    c = 64
    for stage, n_layers in enumerate(_BLOCKS, start=2):
        for l in range(1, n_layers + 1):
            p = f"conv{stage}_block{l}"
            names = [
                reg(bn(c + (l - 1) * _GROWTH, f"{p}_0_bn")),
                reg(core.conv2d(c + (l - 1) * _GROWTH, 4 * _GROWTH, 1,
                                use_bias=False, name=f"{p}_1_conv")),
                reg(bn(4 * _GROWTH, f"{p}_1_bn")),
                reg(core.conv2d(4 * _GROWTH, _GROWTH, 3, use_bias=False,
                                name=f"{p}_2_conv")),
            ]

            def dense_layer_packed(run, h, *, p=p,
                                   c_in=c + (l - 1) * _GROWTH,
                                   c_final=c + n_layers * _GROWTH,
                                   first=(l == 1)):
                # all channel offsets are static, so reads/writes lower
                # to in-place slices instead of whole-map concat copies
                if first:
                    buf = jnp.zeros(h.shape[:3] + (c_final,), h.dtype)
                    buf = jax.lax.dynamic_update_slice_in_dim(
                        buf, h, 0, axis=3)
                else:
                    buf = h
                y = bottleneck(
                    run, jax.lax.slice_in_dim(buf, 0, c_in, axis=3), p=p)
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, y.astype(buf.dtype), c_in, axis=3)

            def dense_layer_concat(run, h, *, p=p):
                # parity reference ONLY (test_static_robustness bans
                # concatenate in this file outside this function)
                return jnp.concatenate([h, bottleneck(run, h, p=p)],
                                       axis=-1)

            units.append((names, dense_layer_packed
                          if block_impl == "packed"
                          else dense_layer_concat))
        c = c + n_layers * _GROWTH
        if stage < 5:
            names = [
                reg(bn(c, f"pool{stage}_bn")),
                reg(core.conv2d(c, c // 2, 1, use_bias=False,
                                name=f"pool{stage}_conv")),
            ]

            def transition(run, h, *, stage=stage):
                h = jax.nn.relu(run(f"pool{stage}_bn", h))
                h = run(f"pool{stage}_conv", h)
                return jax.lax.reduce_window(h, 0.0, jax.lax.add,
                                             (1, 2, 2, 1), (1, 2, 2, 1),
                                             "VALID") / 4.0

            units.append((names, transition))
            c = c // 2
    units.append(([reg(bn(c, "bn"))],
                  lambda run, h: jax.nn.relu(run("bn", h))))
    return units, dict(specs)


def densenet201_backbone(in_channels: int = 3, *,
                         bn_frozen_below: int = 0,
                         block_impl: str = "packed") -> core.Module:
    """`bn_frozen_below`: BN layers with Keras index < this run in
    permanent inference mode (Keras trainable=False semantics).
    `block_impl`: dense-block data movement — "packed" (concat-free
    default) or "concat" (the parity-reference copy chain); see
    `_units`."""
    units, modules = _units(in_channels, bn_frozen_below, block_impl)
    # layer_names in Keras creation order (see mobilenet.py) so secure
    # percent-selection keeps get_weights() order for this backbone
    sec = core.unit_backbone(units, modules, "densenet201",
                             KERAS_LAYER_INDEX)
    assert sec.layer_names == tuple(KERAS_LAYER_INDEX)
    return sec


DENSENET201_FEATURES = 1920


def densenet201(num_outputs: int = 10, in_channels: int = 3, *,
                bn_frozen_below: int = 0,
                block_impl: str = "packed") -> core.Module:
    backbone = densenet201_backbone(in_channels,
                                    bn_frozen_below=bn_frozen_below,
                                    block_impl=block_impl)
    return core.classifier(backbone, DENSENET201_FEATURES, num_outputs,
                           name="densenet201_classifier")


head_only_mask = core.head_only_mask


def fine_tune_mask(params, fine_tune_at: int = 150):
    return core.keras_fine_tune_mask(params, KERAS_LAYER_INDEX, fine_tune_at)
