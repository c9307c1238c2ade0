"""Byzantine-robust aggregation (federated/robust.py): order-statistic
correctness against numpy, the influence bound of norm clipping, the
secure-path compatibility gate, and THE acceptance scenario — 3 of 10
clients Byzantine (sign-flip x1000) diverge the weighted mean while
trimmed mean and median keep the server finite and strictly better."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from idc_models_tpu import collectives, faults
from idc_models_tpu import mesh as meshlib
from jax import shard_map
from idc_models_tpu.data import synthetic
from idc_models_tpu.data.idc import ArrayDataset
from idc_models_tpu.data.partition import pad_clients, partition_clients
from idc_models_tpu.federated import (
    Median, NormClip, TrimmedMean, WeightedMean, get_aggregator,
    initialize_server, make_fedavg_round, make_federated_eval,
)
from idc_models_tpu.models import core, small_cnn
from idc_models_tpu.train import rmsprop
from idc_models_tpu.train.losses import binary_cross_entropy


def _apply_agg(agg, values, weights, n_mesh=4, server=None):
    """Run one aggregator over stacked per-client leaves [C, ...] inside
    the same shard_map environment the round uses."""
    mesh = meshlib.client_mesh(n_mesh)
    if server is None:
        server = jax.tree.map(lambda v: jnp.zeros(v.shape[1:], v.dtype),
                              values)

    def body(vals, w):
        out, metrics = agg(vals, w, server, meshlib.CLIENT_AXIS)
        return out, metrics

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(meshlib.CLIENT_AXIS), P(meshlib.CLIENT_AXIS)),
        out_specs=(P(), P()), check_vma=False)
    out, metrics = jax.jit(mapped)(values,
                                   jnp.asarray(weights, jnp.float32))
    return jax.device_get(out), jax.device_get(metrics)


def test_trimmed_mean_matches_numpy(devices):
    rng = np.random.default_rng(0)
    vals = {"w": rng.normal(size=(8, 5, 3)).astype(np.float32)}
    w = np.array([1, 1, 1, 1, 1, 1, 0, 0], np.float32)  # 2 dead clients
    out, metrics = _apply_agg(TrimmedMean(trim=1), vals, w)
    alive = vals["w"][:6]
    srt = np.sort(alive, axis=0)
    want = srt[1:-1].mean(axis=0)                        # trim 1 per side
    np.testing.assert_allclose(out["w"], want, rtol=1e-6)
    assert "clients_trimmed" in metrics


def test_trimmed_mean_degenerate_band_keeps_server(devices):
    """2*trim >= total slots can NEVER work: rejected at build/trace.
    A live population that dips to n_alive <= 2*trim keeps the incoming
    server state (never the silent all-zero 'mean') and flags it."""
    rng = np.random.default_rng(7)
    vals = {"w": rng.normal(size=(8, 3)).astype(np.float32)}
    with pytest.raises(ValueError, match="can never keep"):
        _apply_agg(TrimmedMean(trim=4), vals, np.ones((8,), np.float32))
    # statically fine (8 slots > 2*2) but only 4 alive at runtime
    w = np.array([1, 1, 1, 1, 0, 0, 0, 0], np.float32)
    server = {"w": jnp.full((3,), 7.0, jnp.float32)}
    out, metrics = _apply_agg(TrimmedMean(trim=2), vals, w,
                              server=server)
    np.testing.assert_array_equal(out["w"], np.full((3,), 7.0))
    assert int(metrics["trim_degenerate"]) == 1
    # and the healthy case reports 0
    _, m_ok = _apply_agg(TrimmedMean(trim=1), vals,
                         np.ones((8,), np.float32))
    assert int(m_ok["trim_degenerate"]) == 0


def test_median_matches_numpy(devices):
    rng = np.random.default_rng(1)
    for n_alive in (5, 6):                               # odd AND even
        vals = {"w": rng.normal(size=(8, 4)).astype(np.float32)}
        w = np.zeros((8,), np.float32)
        w[:n_alive] = 1.0
        out, _ = _apply_agg(Median(), vals, w)
        want = np.median(vals["w"][:n_alive], axis=0)
        np.testing.assert_allclose(out["w"], want, rtol=1e-6)


def test_trimmed_mean_ignores_nonfinite_attackers(devices):
    """With drop_nonfinite unavailable (e.g. the caller disabled it), a
    NaN/Inf client sorts past the kept band: the trimmed mean stays
    finite and equals the honest trimmed mean."""
    rng = np.random.default_rng(2)
    vals = {"w": rng.normal(size=(8, 6)).astype(np.float32)}
    vals["w"][3] = np.inf
    vals["w"][5] = np.nan
    w = np.ones((8,), np.float32)
    out, _ = _apply_agg(TrimmedMean(trim=2), vals, w)
    assert np.all(np.isfinite(out["w"]))
    honest = np.delete(vals["w"], [3, 5], axis=0)
    # 8 alive, trim 2/side -> ranks 2..5; the two non-finite rows occupy
    # the top ranks, so the kept band is ranks 2..5 of the sorted honest
    # values with the worst honest value at rank 5
    srt = np.sort(np.concatenate([honest, np.full((2, 6), np.inf,
                                                  np.float32)]), axis=0)
    np.testing.assert_allclose(out["w"], srt[2:6].mean(axis=0), rtol=1e-6)


def test_norm_clip_bounds_influence(devices):
    """A scaled attacker's delta is clipped to max_norm exactly; honest
    updates below the bound are bit-untouched; the metric counts the
    clipped client."""
    rng = np.random.default_rng(3)
    honest = rng.normal(scale=0.01, size=(8, 10)).astype(np.float32)
    vals = {"w": honest.copy()}
    vals["w"][2] = 100.0                                 # huge delta
    w = np.ones((8,), np.float32)
    out, metrics = _apply_agg(NormClip(max_norm=1.0), vals, w)
    assert int(metrics["clients_clipped"]) == 1
    clipped = vals["w"][2] / np.linalg.norm(vals["w"][2])  # renormed to 1
    want = (honest.sum(0) - honest[2] + clipped) / 8.0
    np.testing.assert_allclose(out["w"], want, rtol=1e-5)


def test_weighted_mean_is_default_and_exact(devices):
    rng = np.random.default_rng(4)
    vals = {"w": rng.normal(size=(8, 3)).astype(np.float32)}
    w = np.array([1, 2, 3, 4, 0, 0, 0, 0], np.float32)
    out, metrics = _apply_agg(WeightedMean(), vals, w)
    want = (vals["w"][:4] * w[:4, None]).sum(0) / w.sum()
    np.testing.assert_allclose(out["w"], want, rtol=1e-6)
    assert metrics == {}
    assert isinstance(get_aggregator(None), WeightedMean)
    with pytest.raises(ValueError, match="unknown aggregator"):
        get_aggregator("krum")


def _tiny_model():
    """Deterministic (dropout-free) tiny model: the Byzantine scenario
    needs speed, not capacity."""
    return core.sequential(
        [
            core.conv2d(3, 8, 3, stride=2, name="conv1"),
            core.relu(),
            core.flatten(),
            core.dense(8 * 5 * 5, 1, name="head"),
        ],
        name="tiny",
    )


def test_byzantine_robustness_acceptance(devices):
    """THE acceptance scenario: 3 of 10 clients Byzantine (sign-flip,
    scale 1000). Under the IDENTICAL fault plan, the weighted mean
    degrades massively while trimmed-mean (trim=3) and median keep the
    server params finite and reach strictly better eval loss; the
    trimmed run replays bit-identically across two builds."""
    n_clients, n_byz = 10, 3
    imgs, labels = synthetic.make_idc_like(n_clients * 16, size=10,
                                           seed=0)
    ci, cl = partition_clients(ArrayDataset(imgs, labels), n_clients,
                               iid=True, seed=0)
    w = np.full((n_clients,), 16.0, np.float32)
    ci, cl, w = pad_clients(ci, cl, w, multiple=8)    # 10 clients, 8 dev
    mesh = meshlib.client_mesh(8)
    model = _tiny_model()
    plan = faults.FaultPlan.byzantine(n_clients, n_byz, kind="sign_flip",
                                      scale=1000.0, seed=7)
    eval_fn = make_federated_eval(model, binary_cross_entropy, mesh)

    def run(agg):
        server = initialize_server(model, jax.random.key(0))
        rnd = make_fedavg_round(model, rmsprop(1e-3),
                                binary_cross_entropy, mesh,
                                local_epochs=1, batch_size=16,
                                aggregator=agg, faults=plan)
        metrics = {}
        for r in range(3):
            server, metrics = rnd(server, ci, cl, w,
                                  jax.random.fold_in(jax.random.key(1),
                                                     r))
        loss = float(eval_fn(server, ci, cl, w)["loss"])
        return jax.device_get(server.params), metrics, loss

    p_mean, _, loss_mean = run(None)
    p_trim, m_trim, loss_trim = run(TrimmedMean(trim=n_byz))
    p_med, _, loss_med = run(Median())

    # robust aggregates stay finite AND strictly beat the mean
    for p in (p_trim, p_med):
        assert all(np.all(np.isfinite(l)) for l in jax.tree.leaves(p))
    assert loss_trim < loss_mean, (loss_trim, loss_mean)
    assert loss_med < loss_mean, (loss_med, loss_mean)
    # the mean demonstrably degraded: orders of magnitude off a sane
    # binary cross entropy (the attackers steered it)
    assert loss_mean > 10 * max(loss_trim, loss_med), loss_mean
    # the trim metric notices at least one attacker
    assert float(m_trim["clients_trimmed"]) >= 1

    # identical fault plan, identical seeds -> bit-identical replay
    p_trim2, _, loss_trim2 = run(TrimmedMean(trim=n_byz))
    assert loss_trim == loss_trim2
    for a, b in zip(jax.tree.leaves(p_trim), jax.tree.leaves(p_trim2)):
        np.testing.assert_array_equal(a, b)


def test_secure_round_aggregator_gate(devices):
    """The masked path rejects plaintext-order-statistic aggregators at
    build time and accepts norm_clip, whose per-client transform rides
    the masked mean (clip metric included)."""
    from idc_models_tpu.secure import make_secure_fedavg_round

    model = small_cnn(10, 3, 1)
    mesh = meshlib.client_mesh(4)
    with pytest.raises(ValueError, match="not compatible with secure"):
        make_secure_fedavg_round(model, rmsprop(1e-3),
                                 binary_cross_entropy, mesh, percent=0.5,
                                 aggregator="trimmed_mean")
    with pytest.raises(ValueError, match="not compatible with secure"):
        make_secure_fedavg_round(model, rmsprop(1e-3),
                                 binary_cross_entropy, mesh, percent=0.5,
                                 aggregator="median")

    imgs, labels = synthetic.make_idc_like(4 * 16, size=10, seed=5)
    ci = imgs.reshape(4, 16, 10, 10, 3)
    cl = labels.reshape(4, 16)
    server = initialize_server(model, jax.random.key(0))
    rnd = make_secure_fedavg_round(
        model, rmsprop(1e-3), binary_cross_entropy, mesh, percent=0.5,
        local_epochs=1, batch_size=16,
        aggregator=NormClip(max_norm=1e-6))   # absurdly tight: clips all
    server, m = rnd(server, ci, cl, jax.random.key(1))
    assert int(m["clients_clipped"]) == 4
    assert all(np.all(np.isfinite(l))
               for l in jax.tree.leaves(jax.device_get(server.params)))
