"""Secure aggregation: mask cancellation, Paillier round-trips, and the
secure FedAvg round (SURVEY.md §4: "masks cancel: psum of masked == psum
of plain; Paillier enc→agg→dec == plain mean")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import collectives
from idc_models_tpu import mesh as meshlib
from jax import shard_map
from idc_models_tpu.data import synthetic
from idc_models_tpu.data.idc import ArrayDataset
from idc_models_tpu.data.partition import partition_clients
from idc_models_tpu.federated import initialize_server, make_fedavg_round
from idc_models_tpu.models import small_cnn
from idc_models_tpu.secure import (
    dequantize, first_fraction_selection, make_secure_fedavg_round,
    pairwise_mask, quantize,
)
from idc_models_tpu.secure.fedavg import PaillierClient, PaillierServer
from idc_models_tpu.secure.paillier import generate_paillier_keypair
from idc_models_tpu.train import rmsprop
from idc_models_tpu.train.losses import binary_cross_entropy

N_CLIENTS = 8


def test_masks_cancel_exactly():
    """Sum over all clients of the pairwise masks is exactly zero."""
    key = jax.random.key(7)
    shape = (33, 5)
    total = jnp.zeros(shape, jnp.int32)
    for i in range(N_CLIENTS):
        total = total + pairwise_mask(key, jnp.int32(i), N_CLIENTS, shape)
    np.testing.assert_array_equal(np.asarray(total), 0)


def test_masked_psum_equals_plain_psum():
    """psum of masked quantized updates == psum of plain ones, bit-exact,
    while each individual masked contribution is (pseudo)random."""
    mesh = meshlib.client_mesh(N_CLIENTS)
    key = jax.random.key(3)
    vals = np.random.default_rng(0).normal(size=(N_CLIENTS, 17)).astype(
        np.float32)

    def body(x):
        cid = collectives.axis_index(meshlib.CLIENT_AXIS)
        q = quantize(x[0])
        m = pairwise_mask(key, cid, N_CLIENTS, q.shape)
        masked_sum = collectives.psum(q + m, meshlib.CLIENT_AXIS)
        plain_sum = collectives.psum(q, meshlib.CLIENT_AXIS)
        return masked_sum, plain_sum, (q + m)[None]

    from jax.sharding import PartitionSpec as P
    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(meshlib.CLIENT_AXIS),
        out_specs=(P(), P(), P(meshlib.CLIENT_AXIS)), check_vma=False))
    masked_sum, plain_sum, contributions = f(vals)
    np.testing.assert_array_equal(np.asarray(masked_sum),
                                  np.asarray(plain_sum))
    # each device's masked contribution differs from its plain quantized
    # update. NOTE: this is a simulation-level property only — the round
    # key that derives the pairwise masks is held by the driver, so a
    # party with that key could regenerate the masks (masking.py
    # docstring; reference quirk Q9 keeps both Paillier keys global too).
    q_plain = np.asarray(quantize(jnp.asarray(vals)))
    assert not np.array_equal(np.asarray(contributions), q_plain)
    # and the dequantized mean matches the true mean to quantization error
    mean = np.asarray(dequantize(masked_sum, count=N_CLIENTS))
    np.testing.assert_allclose(mean, vals.mean(0), atol=2e-6)


def test_quantize_roundtrip():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(100,)) * 5)
    back = dequantize(quantize(x))
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-6)


def test_dequantize_keeps_resolution_for_large_sums():
    """Sums past 2^24 (reachable with clip 64, scale 20, 8 clients) must
    not lose low bits: the split evaluation matches a float64 reference
    exactly for power-of-two counts (one rounding, at the result)."""
    s = 20
    q_np = np.asarray([2**24 + 1, -(2**24 + 1), 2**29 + 3, (1 << 31) - 1,
                       -(1 << 31), 12345, 0], np.int64)
    got = np.asarray(dequantize(jnp.asarray(q_np, jnp.int32), s, count=8))
    want = (q_np.astype(np.float64) / 2**s / 8).astype(np.float32)
    np.testing.assert_array_equal(got, want)
    # non-power-of-two count: one extra rounding, still ~ulp accurate
    got3 = np.asarray(dequantize(jnp.asarray(q_np, jnp.int32), s, count=3))
    np.testing.assert_allclose(
        got3, (q_np.astype(np.float64) / 2**s / 3).astype(np.float32),
        rtol=1e-7)


def test_paillier_exponent_gap_overflow_raises(keypair):
    """Aligning exponents across a huge magnitude gap would wrap the
    mantissa mod n and decrypt to garbage; it must raise instead."""
    pub, _ = keypair
    big = pub.encrypt(1e100)
    tiny = pub.encrypt(1e-100)
    with pytest.raises(ValueError, match="overflow"):
        _ = big + tiny
    # scalar multiplication grows the tracked mantissa bound (106 bits
    # here); a fixed 53-bit-mantissa assumption would wave this through
    # and the sum would wrap mod n and decrypt to garbage
    a = pub.encrypt(1e100) * 0.3
    b = pub.encrypt(1e-30) * 0.7
    with pytest.raises(ValueError, match="overflow"):
        _ = a + b
    # ordinary same-scale arithmetic is untouched by the guard
    _ = pub.encrypt(1e10) + pub.encrypt(1e-10) * 0.5


def test_quantize_clips_instead_of_wrapping():
    from idc_models_tpu.secure import choose_scale_bits

    big = jnp.asarray([1e9, -1e9, 10.0])
    q = quantize(big, 20, clip_abs=64.0)
    back = dequantize(q, 20)
    np.testing.assert_allclose(np.asarray(back), [64.0, -64.0, 10.0],
                               atol=1e-5)
    # headroom budget: sum of n fully saturated values must fit int32
    # STRICTLY (2^31 exactly would wrap to INT32_MIN)
    for n in (2, 8, 32, 1024):
        bits = choose_scale_bits(n, 64.0)
        assert (2.0 ** bits) * 64.0 * n <= 2 ** 31 - 1
    assert choose_scale_bits(8, 64.0) <= 21


def test_first_fraction_selection():
    tree = {"a": 1, "b": {"c": 2, "d": 3}, "e": 4}
    sel = first_fraction_selection(tree, 0.5)
    flags = jax.tree.leaves(sel)
    assert flags == [True, True, False, False]  # int(4*0.5)=2
    assert jax.tree.leaves(first_fraction_selection(tree, 0.0)) == [False] * 4
    assert jax.tree.leaves(first_fraction_selection(tree, 1.0)) == [True] * 4


def test_first_fraction_selection_layer_order():
    """With a model's layer_names, "first N tensors" follows Keras
    get_weights() order (layer creation order, kernel before bias), not
    jax's alphabetical flatten (secure_fed_model.py:115-121 parity)."""
    model = small_cnn(10, 3, 1)
    params = model.init(jax.random.key(0)).params
    # small_cnn layer order: conv1 -> fc1 -> head; get_weights() order is
    # conv1/kernel, conv1/bias, fc1/kernel, fc1/bias, head/kernel, head/bias.
    sel = first_fraction_selection(params, 0.5, model.layer_names)
    assert sel == {
        "conv1": {"kernel": True, "bias": True},
        "fc1": {"kernel": True, "bias": False},
        "head": {"kernel": False, "bias": False},
    }
    # alphabetical order would instead have protected conv1/bias,
    # conv1/kernel, fc1/bias — a different set
    sel_flat = first_fraction_selection(params, 0.5)
    assert sel_flat["fc1"] == {"kernel": False, "bias": True}


def test_first_fraction_selection_nested_classifier():
    """classifier() models rank backbone layers in creation order via
    dotted layer_names (not alphabetically), head last."""
    from idc_models_tpu.models import core

    backbone = core.sequential(
        [core.conv2d(3, 4, 3, name="z_first"),   # alphabetically LAST
         core.conv2d(4, 4, 3, name="a_second")],  # alphabetically FIRST
        name="bb")
    model = core.classifier(backbone, 4, 1)
    assert model.layer_names == ("backbone.z_first", "backbone.a_second",
                                 "head")
    params = model.init(jax.random.key(0)).params
    # first 3 of 6 tensors: z_first kernel+bias, a_second kernel
    sel = first_fraction_selection(params, 0.5, model.layer_names)
    assert sel == {
        "backbone": {"z_first": {"kernel": True, "bias": True},
                     "a_second": {"kernel": True, "bias": False}},
        "head": {"kernel": False, "bias": False},
    }


@pytest.fixture(scope="module")
def keypair():
    return generate_paillier_keypair(n_length=512)


class TestPaillier:
    def test_roundtrip(self, keypair):
        pub, priv = keypair
        for v in [0.0, 1.5, -2.75, 1e-8, -1e8, 123456.789]:
            assert priv.decrypt(pub.encrypt(v)) == pytest.approx(v, rel=1e-12)

    def test_homomorphic_add(self, keypair):
        pub, priv = keypair
        a, b = 3.25, -1.125
        s = pub.encrypt(a) + pub.encrypt(b)
        assert priv.decrypt(s) == pytest.approx(a + b, rel=1e-12)

    def test_scalar_mul_div(self, keypair):
        pub, priv = keypair
        c = pub.encrypt(7.5) * 0.125
        assert priv.decrypt(c) == pytest.approx(0.9375, rel=1e-9)
        d = pub.encrypt(10.0) / 8
        assert priv.decrypt(d) == pytest.approx(1.25, rel=1e-9)

    def test_ciphertext_mean_equals_plain_mean(self, keypair):
        pub, priv = keypair
        vals = [0.5, -1.5, 2.25, 3.0]
        enc = [pub.encrypt(v) for v in vals]
        acc = enc[0]
        for e in enc[1:]:
            acc = acc + e
        mean = acc / len(vals)
        assert priv.decrypt(mean) == pytest.approx(
            sum(vals) / len(vals), rel=1e-9)


def _client_data(n_per_client=32, seed=0):
    imgs, labels = synthetic.make_idc_like(n_per_client * N_CLIENTS, size=10,
                                           seed=seed)
    return partition_clients(ArrayDataset(imgs, labels), N_CLIENTS, iid=True,
                             seed=seed)


def test_secure_round_matches_plain_round(devices):
    """percent=1.0 secure round == plain unweighted FedAvg round up to
    quantization error (same rng, same local training)."""
    mesh = meshlib.client_mesh(N_CLIENTS)
    model = small_cnn(10, 3, 1)
    opt = rmsprop(1e-3)
    imgs, labels = _client_data()
    rng = jax.random.key(11)

    server_a = initialize_server(model, jax.random.key(0))
    secure_rnd = make_secure_fedavg_round(
        model, opt, binary_cross_entropy, mesh, percent=1.0,
        local_epochs=1, batch_size=16)
    sa, ma = secure_rnd(server_a, imgs, labels, rng)

    server_b = initialize_server(model, jax.random.key(0))
    plain_rnd = make_fedavg_round(model, opt, binary_cross_entropy, mesh,
                                  local_epochs=1, batch_size=16)
    sb, mb = plain_rnd(server_b, imgs, labels,
                       np.ones((N_CLIENTS,), np.float32), rng)

    for a, b in zip(jax.tree.leaves(jax.device_get(sa.params)),
                    jax.tree.leaves(jax.device_get(sb.params))):
        np.testing.assert_allclose(a, b, atol=3e-6)
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-5)


def test_secure_round_recovers_diverged_client(devices):
    """Failure recovery on the masked path, where a client cannot simply
    be dropped (its pairwise masks would stay uncancelled): the diverged
    client's update is replaced with the incoming global weights before
    masking. Expected aggregate = (7 healthy updates + old weights) / 8;
    the healthy updates come from the plain round with the dead client
    auto-dropped (identical rng derivation, proven by
    test_secure_round_matches_plain_round)."""
    mesh = meshlib.client_mesh(N_CLIENTS)
    model = small_cnn(10, 3, 1)
    opt = rmsprop(1e-3)
    imgs, labels = _client_data(seed=17)
    poisoned = np.array(imgs)
    poisoned[3] = np.nan
    rng = jax.random.key(23)

    server = initialize_server(model, jax.random.key(0))
    old_params = jax.device_get(server.params)
    secure_rnd = make_secure_fedavg_round(
        model, opt, binary_cross_entropy, mesh, percent=1.0,
        local_epochs=1, batch_size=16)
    sa, ma = secure_rnd(server, poisoned, labels, rng)
    assert int(ma["clients_recovered"]) == 1
    assert np.isfinite(float(ma["loss"]))
    assert all(np.all(np.isfinite(l))
               for l in jax.tree.leaves(jax.device_get(sa.params)))

    # healthy-only mean via the plain round's failure detection
    plain_rnd = make_fedavg_round(model, opt, binary_cross_entropy, mesh,
                                  local_epochs=1, batch_size=16)
    sb, mb = plain_rnd(initialize_server(model, jax.random.key(0)),
                       poisoned, labels, np.ones((N_CLIENTS,), np.float32),
                       rng)
    for a, healthy_mean, old in zip(
            jax.tree.leaves(jax.device_get(sa.params)),
            jax.tree.leaves(jax.device_get(sb.params)),
            jax.tree.leaves(old_params)):
        want = (healthy_mean * (N_CLIENTS - 1) + old) / N_CLIENTS
        np.testing.assert_allclose(a, want, atol=5e-6)
    # metrics average only the clients that actually trained
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-5)

    # recovery can be disabled: the diverged client then poisons the
    # masked aggregate (why the default is on)
    rnd_off = make_secure_fedavg_round(
        model, opt, binary_cross_entropy, mesh, percent=0.5,
        local_epochs=1, batch_size=16, recover_nonfinite=False)
    s_off, _ = rnd_off(initialize_server(model, jax.random.key(0)),
                       poisoned, labels, rng)
    assert not all(np.all(np.isfinite(l))
                   for l in jax.tree.leaves(jax.device_get(s_off.params)))


def test_secure_round_layout_invariant(devices):
    """k clients per device: the same 8 clients on an 8-device mesh
    (k=1), a 4-device mesh (k=2), and a 1-device mesh (k=8) produce the
    same aggregate — the protected int32 path bit-for-bit (mod-2^32
    addition is layout-independent), the f32 path to fp tolerance.

    Skipped where the BACKEND itself is not layout-deterministic for
    the local-training program shape (see tests/_layout_probe.py): the
    divergence is in the clients' LOCAL training lowering, upstream of
    everything the secure protocol adds."""
    from _layout_probe import LAYOUT_SKIP_REASON, layout_invariant

    if not layout_invariant():
        pytest.skip(LAYOUT_SKIP_REASON)
    model = small_cnn(10, 3, 1)
    ci, cl = _client_data(seed=13)
    rng = jax.random.key(21)

    def run(n_dev, impl="threefry"):
        mesh = meshlib.client_mesh(n_dev)
        server = initialize_server(model, jax.random.key(0))
        rnd = make_secure_fedavg_round(
            model, rmsprop(1e-3), binary_cross_entropy, mesh, percent=0.5,
            local_epochs=1, batch_size=16, mask_impl=impl)
        server, m = rnd(server, ci, cl, rng)
        return jax.device_get(server.params), float(m["loss"])

    p8, l8 = run(8)
    p4, l4 = run(4)
    p1, l1 = run(1)
    # the pallas impl's masks differ but cancel identically, so even a
    # k=2 pallas layout must land on the same aggregate (exercises the
    # per-client kernel loop with k > 1)
    p4p, l4p = run(4, impl="pallas")
    for ref in (p4, p1, p4p):
        for a, b in zip(jax.tree.leaves(p8), jax.tree.leaves(ref)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose([l4, l1, l4p], l8, rtol=1e-5)
    # a non-dividing layout pads the client axis with mask-participating
    # dummy clients and runs on the FULL mesh — same aggregate (8 real
    # clients + 1 dummy over 3 devices)
    mesh3 = meshlib.client_mesh(3)
    rnd3 = make_secure_fedavg_round(
        model, rmsprop(1e-3), binary_cross_entropy, mesh3, percent=0.5,
        local_epochs=1, batch_size=16)
    s3, m3 = rnd3(initialize_server(model, jax.random.key(0)), ci, cl, rng)
    for a, b in zip(jax.tree.leaves(p8),
                    jax.tree.leaves(jax.device_get(s3.params))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m3["loss"]), l8, rtol=1e-5)


def test_secure_round_full_mesh_for_any_client_count(devices):
    """VERDICT r2 #6: 10 clients on an 8-device mesh must use all 8
    devices (6 mask-participating dummies, k=2) and produce the
    BIT-IDENTICAL aggregate to the same 10 clients on the 5-device mesh
    `largest_dividing_mesh` would have picked — dummies contribute
    exact zeros to the int32 sum and the divisor stays 10."""
    n_clients = 10
    model = small_cnn(10, 3, 1)
    imgs, labels = synthetic.make_idc_like(n_clients * 16, size=10, seed=5)
    ci = imgs.reshape(n_clients, 16, 10, 10, 3)
    cl = labels.reshape(n_clients, 16)
    rng = jax.random.key(31)

    def run(n_dev):
        mesh = meshlib.client_mesh(n_dev)
        server = initialize_server(model, jax.random.key(0))
        # percent=1.0: EVERY tensor rides the masked int32 path, so the
        # whole aggregate must be bit-identical across layouts
        rnd = make_secure_fedavg_round(
            model, rmsprop(1e-3), binary_cross_entropy, mesh, percent=1.0,
            local_epochs=1, batch_size=16)
        server, m = rnd(server, ci, cl, rng)
        return jax.device_get(server.params), float(m["loss"])

    assert meshlib.largest_dividing_mesh(n_clients, 8) == 5
    p8, l8 = run(8)   # pads to 16 client slots over all 8 devices
    p5, l5 = run(5)   # exact fit, no dummies
    for a, b in zip(jax.tree.leaves(p8), jax.tree.leaves(p5)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(l8, l5, rtol=1e-6)


def test_mobilenet_selection_follows_keras_order():
    """Zoo backbones carry layer_names, so percent-selection follows the
    Keras get_weights() enumeration (VERDICT r1 weak #4): creation order
    with kernel -> scale -> bias within a layer, head last."""
    from idc_models_tpu.models.mobilenet import mobilenet_v2
    from idc_models_tpu.secure.masking import leaf_paths, ranked_indices

    model = mobilenet_v2(1)
    assert model.layer_names[0] == "backbone.Conv1"
    assert model.layer_names[-1] == "head"
    shapes = jax.eval_shape(lambda: dict(p=model.init(jax.random.key(0))
                                         .params))["p"]
    paths = leaf_paths(shapes)
    ordered = [paths[i] for i in ranked_indices(paths, model.layer_names)]
    assert ordered[0] == ("backbone", "Conv1", "kernel")
    assert ordered[1] == ("backbone", "bn_Conv1", "scale")
    assert ordered[2] == ("backbone", "bn_Conv1", "bias")
    assert ordered[3] == ("backbone", "expanded_conv_depthwise", "kernel")
    assert ordered[-2:] == [("head", "kernel"), ("head", "bias")]
    # densenet too: first parameterized layer is conv1_conv
    from idc_models_tpu.models.densenet import densenet201

    dn = densenet201(10)
    assert dn.layer_names[0] == "backbone.conv1_conv"
    assert dn.layer_names[-1] == "head"


def _bn_cnn():
    """Tiny BN-bearing classifier with a hand-checkable get_weights()
    enumeration: c1(k,b) b1(scale,bias,mean,var) c2(k,b) b2(...) head(k,b)
    = 14 tensors."""
    from idc_models_tpu.models import core

    backbone = core.sequential(
        [core.conv2d(3, 4, 3, name="c1"),
         core.batch_norm(4, name="b1"),
         core.relu(name="r1"),
         core.conv2d(4, 4, 3, name="c2"),
         core.batch_norm(4, name="b2"),
         core.relu(name="r2")],
        name="bb")
    return core.classifier(backbone, 4, 1)


def _protected_paths(params, state, percent, layer_names):
    from idc_models_tpu.secure import first_fraction_selection_weights
    from idc_models_tpu.secure.masking import leaf_paths

    p_flags, s_flags = first_fraction_selection_weights(
        params, state, percent, layer_names)
    return ({p for p, f in zip(leaf_paths(params),
                               jax.tree.leaves(p_flags)) if f}
            | {p for p, f in zip(leaf_paths(state),
                                 jax.tree.leaves(s_flags)) if f})


def test_selection_weights_interleaves_bn_state(keypair):
    """The percent knob slices the FULL get_weights() list — BN moving
    statistics interleave with the weights (secure_fed_model.py:115-121:
    `self.weights[:num_enc]` over Keras get_weights()). int(14*0.5)=7 →
    b1's mean/var (STATE) are protected while c2's bias (a PARAM) is not.
    The same enumeration must drive PaillierClient.enc_model."""
    model = _bn_cnn()
    variables = model.init(jax.random.key(0))
    protected = _protected_paths(variables.params, variables.state, 0.5,
                                 model.layer_names)
    assert protected == {
        ("backbone", "c1", "kernel"), ("backbone", "c1", "bias"),
        ("backbone", "b1", "scale"), ("backbone", "b1", "bias"),
        ("backbone", "b1", "mean"), ("backbone", "b1", "var"),
        ("backbone", "c2", "kernel"),
    }
    # cross-check against the host-side Paillier path: enc_model encrypts
    # exactly the first 7 tensors of the same enumeration (object arrays),
    # in the same order and shapes
    pub, priv = keypair
    imgs, labels = synthetic.make_idc_like(8, size=10, seed=0)
    client = PaillierClient(model, rmsprop(1e-3), binary_cross_entropy,
                            imgs, labels, client_id=0, percent=0.5,
                            public_key=pub, private_key=priv)
    out = client.enc_model()
    assert len(out) == 14 and client._num_encrypted() == 7
    enc_shapes = [t.shape for t in out[:7]]
    assert all(t.dtype == object for t in out[:7])
    assert not any(t.dtype == object for t in out[7:])
    assert enc_shapes == [(3, 3, 3, 4), (4,), (4,), (4,), (4,), (4,),
                          (3, 3, 4, 4)]


def test_masked_selection_matches_paillier_enumeration_mobilenet():
    """VERDICT r2 #2: on a real BN zoo model the masked path's protected
    set must equal the PaillierClient enumeration's first int(L*percent)
    — params and moving stats interleaved, not params-only."""
    from idc_models_tpu.models.mobilenet import mobilenet_v2
    from idc_models_tpu.secure.masking import leaf_paths, ranked_indices

    model = mobilenet_v2(1)
    def init_shapes():
        v = model.init(jax.random.key(0))
        return dict(p=v.params, s=v.state)

    shapes = jax.eval_shape(init_shapes)
    params, state = shapes["p"], shapes["s"]
    percent = 0.25
    protected = _protected_paths(params, state, percent, model.layer_names)

    # PaillierClient._flat_weights enumeration: combined paths ranked by
    # model layer order; _num_encrypted = int((P+S) * percent)
    paths = leaf_paths(params) + leaf_paths(state)
    order = ranked_indices(paths, model.layer_names)
    n_enc = int(len(paths) * percent)
    assert protected == {paths[i] for i in order[:n_enc]}
    # the interleaving is real: the stem BN's moving stats are protected
    assert ("backbone", "bn_Conv1", "mean") in protected
    assert ("backbone", "bn_Conv1", "var") in protected
    # and a params-only selection would be a DIFFERENT set
    p_only = first_fraction_selection(params, percent, model.layer_names)
    p_only_set = {p for p, f in zip(leaf_paths(params),
                                    jax.tree.leaves(p_only)) if f}
    assert p_only_set != protected


def test_secure_round_bn_model_matches_plain_round(devices):
    """A masked round over a BN model (percent=0.5: protected set spans
    params AND moving stats) aggregates both to the plain unweighted
    mean, up to quantization error on the masked half."""
    mesh = meshlib.client_mesh(N_CLIENTS)
    model = _bn_cnn()
    opt = rmsprop(1e-3)
    imgs, labels = _client_data()
    rng = jax.random.key(17)

    server_a = initialize_server(model, jax.random.key(0))
    secure_rnd = make_secure_fedavg_round(
        model, opt, binary_cross_entropy, mesh, percent=0.5,
        local_epochs=1, batch_size=16)
    sa, ma = secure_rnd(server_a, imgs, labels, rng)

    server_b = initialize_server(model, jax.random.key(0))
    plain_rnd = make_fedavg_round(model, opt, binary_cross_entropy, mesh,
                                  local_epochs=1, batch_size=16)
    sb, mb = plain_rnd(server_b, imgs, labels,
                       np.ones((N_CLIENTS,), np.float32), rng)

    for a, b in zip(jax.tree.leaves(jax.device_get(sa.params)),
                    jax.tree.leaves(jax.device_get(sb.params))):
        np.testing.assert_allclose(a, b, atol=3e-6)
    # protected moving stats ride the int path at 1/256 prescale (range
    # for ImageNet-scale variances), so their resolution is 256 * 2^-sb
    for a, b in zip(jax.tree.leaves(jax.device_get(sa.model_state)),
                    jax.tree.leaves(jax.device_get(sb.model_state))):
        np.testing.assert_allclose(a, b, atol=1e-3)
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-5)


def test_secure_round_bn_large_variance_not_clipped(devices):
    """ImageNet-scale BN moving variances (hundreds to thousands) exceed
    the +-64 weight clipping range; the protected-state prescale must
    carry them through the masked int path undamaged (the code-review r3
    finding: without it the server's BN state silently clips to 64)."""
    mesh = meshlib.client_mesh(N_CLIENTS)
    model = _bn_cnn()
    opt = rmsprop(1e-3)
    imgs, labels = _client_data()
    rng = jax.random.key(23)

    def with_big_var(server):
        state = jax.tree.map(lambda x: x, server.model_state)
        state["backbone"]["b1"]["var"] = jnp.full_like(
            state["backbone"]["b1"]["var"], 3000.0)
        state["backbone"]["b1"]["mean"] = jnp.full_like(
            state["backbone"]["b1"]["mean"], -200.0)
        return server.replace(model_state=state)

    # percent=1.0: the b1 moving stats are protected (masked int path)
    secure_rnd = make_secure_fedavg_round(
        model, opt, binary_cross_entropy, mesh, percent=1.0,
        local_epochs=1, batch_size=16)
    sa, _ = secure_rnd(with_big_var(initialize_server(model,
                                                      jax.random.key(0))),
                       imgs, labels, rng)

    plain_rnd = make_fedavg_round(model, opt, binary_cross_entropy, mesh,
                                  local_epochs=1, batch_size=16)
    sb, _ = plain_rnd(with_big_var(initialize_server(model,
                                                     jax.random.key(0))),
                      imgs, labels, np.ones((N_CLIENTS,), np.float32), rng)

    a = jax.device_get(sa.model_state)["backbone"]["b1"]
    b = jax.device_get(sb.model_state)["backbone"]["b1"]
    # aggregated var stays ~3000 (momentum 0.99 barely moves it) and must
    # match the plain mean to prescaled-quantization resolution
    assert float(np.min(a["var"])) > 2900.0
    np.testing.assert_allclose(a["var"], b["var"], rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(a["mean"], b["mean"], rtol=1e-5, atol=1e-2)


def test_pack_unpack_roundtrip():
    from idc_models_tpu.secure.masking import pack_leaves, unpack_leaves

    leaves = [jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              jnp.asarray(2.5, jnp.float32),
              jnp.ones((4,), jnp.bfloat16)]
    flat, meta = pack_leaves(leaves)
    assert flat.shape == (11,) and flat.dtype == jnp.float32
    back = unpack_leaves(flat, meta)
    for a, b in zip(leaves, back):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # empty pack (percent=1.0 with empty state) round-trips too
    flat0, meta0 = pack_leaves([])
    assert flat0.shape == (0,) and unpack_leaves(flat0, meta0) == []


def test_secure_round_pallas_impl_bit_identical(devices):
    """threefry and pallas mask streams differ, but both cancel exactly
    under psum — the aggregated round results must be bit-identical."""
    mesh = meshlib.client_mesh(N_CLIENTS)
    model = small_cnn(10, 3, 1)
    opt = rmsprop(1e-3)
    imgs, labels = _client_data(seed=2)
    rng = jax.random.key(13)

    results = {}
    for impl in ("threefry", "pallas"):
        server = initialize_server(model, jax.random.key(0))
        rnd = make_secure_fedavg_round(
            model, opt, binary_cross_entropy, mesh, percent=0.5,
            local_epochs=1, batch_size=16, mask_impl=impl)
        s, m = rnd(server, imgs, labels, rng)
        results[impl] = (jax.device_get(s.params), float(m["loss"]))

    for a, b in zip(jax.tree.leaves(results["threefry"][0]),
                    jax.tree.leaves(results["pallas"][0])):
        np.testing.assert_array_equal(a, b)
    assert results["threefry"][1] == results["pallas"][1]


def test_secure_fedavg_loss_decreases(devices):
    mesh = meshlib.client_mesh(N_CLIENTS)
    model = small_cnn(10, 3, 1)
    opt = rmsprop(1e-3)
    imgs, labels = _client_data(seed=4)
    secure_rnd = make_secure_fedavg_round(
        model, opt, binary_cross_entropy, mesh, percent=0.5,
        local_epochs=2, batch_size=16)
    server = initialize_server(model, jax.random.key(0))
    key = jax.random.key(5)
    losses = []
    for _ in range(10):
        key, sub = jax.random.split(key)
        server, m = secure_rnd(server, imgs, labels, sub)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.95, losses


def test_paillier_clients_full_protocol(keypair):
    """The host-side parity protocol end-to-end with 3 clients on tiny
    shards: fit -> encrypt -> aggregate(ciphertext) -> decrypt -> update;
    the aggregate equals the plain mean of the clients' weights."""
    pub, priv = keypair
    model = small_cnn(10, 3, 1)
    opt = rmsprop(1e-3)
    imgs, labels = synthetic.make_idc_like(24, size=10, seed=9)
    clients = [
        PaillierClient(model, opt, binary_cross_entropy,
                       imgs[i::3], labels[i::3], i, percent=0.4,
                       public_key=pub, private_key=priv,
                       local_epochs=1, batch_size=8, seed=0)
        for i in range(3)
    ]
    packages = []
    for c in clients:
        pkg, _ = c.client_fit()
        packages.append(pkg)
    expected = [
        np.mean([np.asarray(x, np.float64)
                 for x in [jax.tree.leaves(c.params)[i] for c in clients]],
                axis=0)
        for i in range(len(jax.tree.leaves(clients[0].params)))
    ]
    agg = PaillierServer.aggregate(packages)
    for c in clients:
        c.client_update(agg)
    for c in clients:
        got = [np.asarray(x) for x in jax.tree.leaves(c.params)]
        for g, e in zip(got, expected):
            np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-7)
    m = clients[0].evaluate(imgs, labels, binary_cross_entropy)
    assert np.isfinite(m["loss"]) and 0 <= m["accuracy"] <= 1


def test_resolve_mask_impl_auto():
    """mask_impl="auto" picks the fused kernel exactly when (a) the
    round's mesh is made of TPU devices and (b) the protected buffer
    reaches the measured crossover (masking.MASK_PALLAS_MIN_ELEMS) —
    threefry everywhere else, including always off-TPU (interpret mode
    is unusable)."""
    import types

    from idc_models_tpu.models.vgg import vgg16
    from idc_models_tpu.secure import resolve_mask_impl

    def mesh_on(platform):
        # the rule reads only the mesh's devices (mesh.pallas_interpret)
        dev = types.SimpleNamespace(platform=platform)
        return types.SimpleNamespace(devices=np.array([dev], dtype=object))

    big = vgg16(1)           # ~14.7M params >> 4.2M crossover
    small = small_cnn(10, 3, 1)
    tpu = mesh_on("tpu")
    assert resolve_mask_impl(big, 1.0, mesh=tpu) == "pallas"
    # a small protected slice of a big model stays under the crossover
    assert resolve_mask_impl(big, 0.05, mesh=tpu) == "threefry"
    assert resolve_mask_impl(small, 1.0, mesh=tpu) == "threefry"
    # off-TPU: always threefry, regardless of size
    assert resolve_mask_impl(big, 1.0, mesh=mesh_on("cpu")) == "threefry"
    # this suite runs on the CPU pod, so "auto" rounds build threefry
    assert resolve_mask_impl(big, 1.0) == "threefry"
