"""ISSUE 16 parity suite: fused Pallas backbone paths vs their unfused
references.

Everything here runs the REAL kernel body: on CPU `interpret=None`
resolves to the Pallas interpreter (mesh.pallas_interpret),
which executes the same `_kernel` the TPU lowers through Mosaic — the
tier-1-on-CPU testing contract. Tolerances match the taps-parity suite
(tests/test_core_layers.py): rtol=1e-5 / atol=1e-6 for forward paths
accumulating in f32.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models import core, densenet, mobilenet
from idc_models_tpu.ops import fused_conv

RTOL, ATOL = 1e-5, 1e-6


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(0, scale, shape), jnp.float32)


# ---------------------------------------------------------------------------
# op level: Pallas kernel vs the jnp reference, and vs XLA's grouped conv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride,size,c", [
    (1, 8, 6), (2, 7, 6), (2, 25, 32), (1, 25, 96),
])
@pytest.mark.parametrize("clamp6", [True, False])
def test_fused_op_matches_reference(stride, size, c, clamp6):
    rng = np.random.default_rng(0)
    x = _rand(rng, (2, size, size, c))
    w = _rand(rng, (3, 3, 1, c), 0.3)
    mul = _rand(rng, (c,), 0.5) + 1.0
    add = _rand(rng, (c,), 0.5)
    got = fused_conv.fused_depthwise_affine(x, w, mul, add,
                                            stride=stride, clamp6=clamp6)
    want = fused_conv.reference_impl(x, w, mul, add, stride=stride,
                                     clamp6=clamp6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("stride,size", [(1, 8), (2, 7), (2, 25)])
def test_fused_module_matches_grouped(stride, size):
    """core.depthwise_conv2d(impl="fused") (identity affine inside the
    kernel) against XLA's grouped lowering — same contract the taps
    parity test pins."""
    c = 16
    mods = {impl: core.depthwise_conv2d(c, 3, stride=stride,
                                        use_bias=False, impl=impl,
                                        name="dw")
            for impl in ("grouped", "fused")}
    v = mods["grouped"].init(jax.random.key(0))
    x = _rand(np.random.default_rng(1), (2, size, size, c))
    outs = {}
    for impl, m in mods.items():
        outs[impl], _ = m.apply(v.params, v.state, x)
    np.testing.assert_allclose(np.asarray(outs["fused"]),
                               np.asarray(outs["grouped"]),
                               rtol=RTOL, atol=ATOL)


def test_fused_module_rejects_valid_padding():
    with pytest.raises(ValueError, match="SAME"):
        core.depthwise_conv2d(8, 3, impl="fused", padding="VALID")


def test_channel_tile_must_divide():
    rng = np.random.default_rng(0)
    x = _rand(rng, (1, 5, 5, 6))
    w = _rand(rng, (3, 3, 1, 6), 0.3)
    one = jnp.ones((6,), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        fused_conv.fused_depthwise_affine(x, w, one, one * 0,
                                          channel_tile=4)
    # a dividing tile is numerically identical to whole-C
    got = fused_conv.fused_depthwise_affine(x, w, one, one * 0,
                                            channel_tile=2)
    want = fused_conv.fused_depthwise_affine(x, w, one, one * 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_fused_full_mobilenet_channel_schedule():
    """Every (spatial, channels, stride) the fused chain actually sees
    in MobileNetV2 at the paper's 50x50 patches — the full schedule
    from `fused_call_shapes`, including the odd 25x25 and 13x13 edges."""
    rng = np.random.default_rng(2)
    for call in mobilenet.fused_call_shapes(1, 50):
        c, s = call["c"], call["stride"]
        x = _rand(rng, (1, call["h_in"], call["w_in"], c))
        w = _rand(rng, (3, 3, 1, c), 0.3)
        mul = _rand(rng, (c,), 0.5) + 1.0
        add = _rand(rng, (c,), 0.5)
        got = fused_conv.fused_depthwise_affine(x, w, mul, add, stride=s)
        want = fused_conv.reference_impl(x, w, mul, add, stride=s)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=RTOL, atol=ATOL,
            err_msg=f"schedule entry {call} diverged")


# ---------------------------------------------------------------------------
# model level: MobileNetV2 fused chain vs the grouped composition
# ---------------------------------------------------------------------------


def _mobile_pair(size=25, *, bn_frozen_below=0):
    m_f = mobilenet.mobilenet_v2_backbone(
        3, bn_frozen_below=bn_frozen_below, depthwise_impl="fused")
    m_g = mobilenet.mobilenet_v2_backbone(
        3, bn_frozen_below=bn_frozen_below, depthwise_impl="grouped")
    v = m_f.init(jax.random.key(0))
    x = _rand(np.random.default_rng(3), (2, size, size, 3))
    return m_f, m_g, v, x


def test_mobilenet_eval_fused_matches_grouped():
    m_f, m_g, v, x = _mobile_pair()
    y_f, _ = m_f.apply(v.params, v.state, x, train=False)
    y_g, _ = m_g.apply(v.params, v.state, x, train=False)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_g),
                               rtol=1e-4, atol=1e-4)


def test_mobilenet_frozen_train_fused_parity_and_static_state():
    """With every BN frozen the fused chain engages even in train mode;
    outputs must match the grouped composition and the returned state
    must be bitwise-identical to the input (frozen BN never updates —
    the bypass contract unit_backbone's `run` attributes document)."""
    m_f, m_g, v, x = _mobile_pair(bn_frozen_below=mobilenet.FREEZE_ALL)
    y_f, s_f = m_f.apply(v.params, v.state, x, train=True)
    y_g, _ = m_g.apply(v.params, v.state, x, train=True)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_g),
                               rtol=1e-4, atol=1e-4)
    flat_in = jax.tree_util.tree_leaves_with_path(v.state)
    flat_out = dict(jax.tree_util.tree_leaves_with_path(s_f))
    for path, leaf in flat_in:
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(flat_out[path]),
            err_msg=f"frozen-train fused state drifted at {path}")


def test_mobilenet_fused_grad_parity():
    """The custom_vjp backward (jax.vjp of the jnp reference) against
    the grouped path's ordinary autodiff, through the whole backbone."""
    m_f, m_g, v, x = _mobile_pair(size=13,
                                  bn_frozen_below=mobilenet.FREEZE_ALL)

    def loss(m):
        def f(params):
            y, _ = m.apply(params, v.state, x, train=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return f

    g_f = jax.grad(loss(m_f))(v.params)
    g_g = jax.grad(loss(m_g))(v.params)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_f),
            jax.tree_util.tree_leaves_with_path(g_g)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-3,
            err_msg=f"grad diverged at {path}")


def test_mobilenet_fused_through_keras_h5(tmp_path):
    """The full pretrained round trip: a Keras-layout h5 whose depthwise
    kernel is stored (kh, kw, C, 1) — exercising load_keras_h5's
    (kh, kw, in, 1) -> (kh, kw, 1, in) swap — merged into fused and
    grouped builds, which must then agree on a forward pass."""
    h5py = pytest.importorskip("h5py")

    rng = np.random.default_rng(4)
    dw_keras = rng.normal(0, 0.3, (3, 3, 32, 1)).astype(np.float32)
    gamma = (rng.normal(0, 0.2, (32,)) + 1.0).astype(np.float32)
    beta = rng.normal(0, 0.2, (32,)).astype(np.float32)
    mean = rng.normal(0, 0.2, (32,)).astype(np.float32)
    var = (rng.random(32) + 0.5).astype(np.float32)
    path = tmp_path / "weights.h5"
    with h5py.File(path, "w") as f:
        g = f.create_group("expanded_conv_depthwise")
        g.attrs["weight_names"] = [
            b"expanded_conv_depthwise/depthwise_kernel:0"]
        g.create_dataset("expanded_conv_depthwise/depthwise_kernel:0",
                         data=dw_keras)
        g = f.create_group("expanded_conv_depthwise_BN")
        g.attrs["weight_names"] = [
            b"expanded_conv_depthwise_BN/gamma:0",
            b"expanded_conv_depthwise_BN/beta:0",
            b"expanded_conv_depthwise_BN/moving_mean:0",
            b"expanded_conv_depthwise_BN/moving_variance:0"]
        for nm, arr in (("gamma:0", gamma), ("beta:0", beta),
                        ("moving_mean:0", mean),
                        ("moving_variance:0", var)):
            g.create_dataset(f"expanded_conv_depthwise_BN/{nm}", data=arr)

    from idc_models_tpu.models.pretrained import maybe_load_pretrained

    m_f, m_g, v, x = _mobile_pair()
    params, state = maybe_load_pretrained(v.params, path, state=v.state,
                                          subtree=None)
    # the swap actually happened: our layout is (kh, kw, 1, C)
    loaded = np.asarray(params["expanded_conv_depthwise"]["kernel"])
    assert loaded.shape == (3, 3, 1, 32)
    np.testing.assert_array_equal(loaded,
                                  np.transpose(dw_keras, (0, 1, 3, 2)))
    np.testing.assert_array_equal(
        np.asarray(state["expanded_conv_depthwise_BN"]["mean"]), mean)
    y_f, _ = m_f.apply(params, state, x, train=False)
    y_g, _ = m_g.apply(params, state, x, train=False)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_g),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# DenseNet: packed (concat-free) blocks vs the concat reference
# ---------------------------------------------------------------------------


def test_densenet_packed_matches_concat():
    m_p = densenet.densenet201_backbone(3, block_impl="packed")
    m_c = densenet.densenet201_backbone(3, block_impl="concat")
    v = m_p.init(jax.random.key(0))
    x = _rand(np.random.default_rng(5), (1, 64, 64, 3))
    y_p, _ = m_p.apply(v.params, v.state, x, train=False)
    y_c, _ = m_c.apply(v.params, v.state, x, train=False)
    assert y_p.shape == (1, 2, 2, 1920)
    # same channel layout, same conv inputs -> bit-identical is the bar
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_c),
                               rtol=0, atol=0)


def test_densenet_rejects_unknown_block_impl():
    with pytest.raises(ValueError, match="packed|concat"):
        densenet.densenet201_backbone(3, block_impl="fused")


# ---------------------------------------------------------------------------
# docs structural gate
# ---------------------------------------------------------------------------


def test_docs_cover_fused_kernels():
    """Satellite doc gate: the DESIGN section must exist."""
    root = Path(__file__).parent.parent
    design = (root / "docs" / "DESIGN.md").read_text()
    assert "Fused backbone kernels" in design
    assert "interpret" in design
