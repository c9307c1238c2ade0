"""Population-scale federated training (federated/population.py +
federated/async_fedavg.py): lazy virtual clients, deterministic cohort
sampling, streamed hierarchical aggregation parity, and the buffered
async server — ISSUE 13's tentpole contracts."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import faults as faults_lib
from idc_models_tpu import mesh as meshlib
from idc_models_tpu.federated import (
    ClientPopulation, CohortSampler, DriverConfig, initialize_server,
    make_async_round, make_fedavg_round, make_population_round,
    run_rounds,
)
from idc_models_tpu.federated import robust
from idc_models_tpu.models import small_cnn
from idc_models_tpu.train import rmsprop
from idc_models_tpu.train.losses import binary_cross_entropy

C = 8          # cohort size shared by most tests


def _population(size=64, seed=3, **kw):
    kw.setdefault("examples_per_client", 16)
    kw.setdefault("image_size", 10)
    return ClientPopulation(size, seed=seed, **kw)


def _model():
    return small_cnn(10, 3, 1)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def _assert_bitwise(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


def _stream_round(pop, sampler, mesh, wave, **kw):
    kw.setdefault("local_epochs", 1)
    kw.setdefault("batch_size", 16)
    return make_population_round(
        _model(), rmsprop(1e-3), binary_cross_entropy, mesh, pop,
        sampler, wave_size=wave, **kw)


def _async_round(pop, sampler, **kw):
    kw.setdefault("buffer_size", 4)
    kw.setdefault("local_epochs", 1)
    kw.setdefault("batch_size", 16)
    kw.setdefault("seed", 11)
    return make_async_round(_model(), rmsprop(1e-3),
                            binary_cross_entropy, pop, sampler, **kw)


# -- virtual clients ----------------------------------------------------


def test_population_lazy_shards_deterministic():
    pop = _population(32, weight_range=(8.0, 24.0))
    im1, lb1 = pop.shard(5)
    im2, lb2 = _population(32, weight_range=(8.0, 24.0)).shard(5)
    assert im1.tobytes() == im2.tobytes()
    assert lb1.tobytes() == lb2.tobytes()
    assert im1.shape == (16, 10, 10, 3) and lb1.shape == (16,)
    # different client, different seed -> different data
    assert pop.shard(6)[0].tobytes() != im1.tobytes()
    assert _population(32, seed=9,
                       weight_range=(8.0, 24.0)).shard(5)[0].tobytes() \
        != im1.tobytes()
    # seeded weights: in range, deterministic, varied
    ws = pop.all_weights()
    assert ws.shape == (32,)
    assert (ws >= 8.0).all() and (ws <= 24.0).all()
    assert len(np.unique(ws)) > 16
    assert pop.weight(7) == _population(
        32, weight_range=(8.0, 24.0)).weight(7)
    imgs, labels, w = pop.materialize([3, 9, 30])
    assert imgs.shape == (3, 16, 10, 10, 3) and w.shape == (3,)
    np.testing.assert_array_equal(imgs[1], pop.shard(9)[0])
    with pytest.raises(ValueError, match="outside the population"):
        pop.shard(32)
    with pytest.raises(ValueError, match="population"):
        ClientPopulation(0)


def test_cohort_sampler_determinism_and_restart():
    """ISSUE-13 satellite (PR 12 style): same seed => byte-identical
    cohort id sequence across rounds AND across fresh builds (the
    process-restart stand-in; the CLI resume e2e covers a real second
    process); a different seed moves the draw."""
    pop = _population(1000)
    a = CohortSampler(pop, 64, seed=7)
    seq = [a.cohort(r) for r in range(6)]
    for ids in seq:
        assert ids.shape == (64,)
        assert len(np.unique(ids)) == 64          # without replacement
        assert ids.min() >= 0 and ids.max() < 1000
    # restart: a FRESH sampler over a FRESH population object
    b = CohortSampler(_population(1000), 64, seed=7)
    assert b"".join(x.tobytes() for x in seq) == b"".join(
        b.cohort(r).tobytes() for r in range(6))
    # rounds differ from each other, and seed moves the draw
    assert seq[0].tobytes() != seq[1].tobytes()
    moved = CohortSampler(pop, 64, seed=8).cohort(0)
    assert moved.tobytes() != seq[0].tobytes()
    with pytest.raises(ValueError, match="cannot exceed"):
        CohortSampler(pop, 1001)
    # the continuous dispatch stream is deterministic too
    assert [a.client_at(i) for i in range(16)] == \
        [b.client_at(i) for i in range(16)]


def test_weighted_sampler_biases_toward_heavy_clients():
    pop = _population(32, weight_range=(1.0, 16.0))
    s = CohortSampler(pop, 8, seed=5, weighted=True)
    counts = np.zeros(32)
    for r in range(150):
        ids = s.cohort(r)
        assert len(np.unique(ids)) == 8
        counts[ids] += 1
    w = pop.all_weights()
    heavy = counts[w >= np.percentile(w, 75)].mean()
    light = counts[w <= np.percentile(w, 25)].mean()
    assert heavy > 1.5 * light, (heavy, light)
    # deterministic replay
    np.testing.assert_array_equal(
        s.cohort(3), CohortSampler(_population(32, weight_range=(
            1.0, 16.0)), 8, seed=5, weighted=True).cohort(3))


# -- streamed hierarchical aggregation ---------------------------------


def test_streamed_single_wave_bitwise_parity(devices):
    """A single wave covering the cohort runs the IDENTICAL masked-sum
    reduction as the one-shot round: params and model_state come out
    bit-for-bit equal on the same cohort."""
    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    mesh = meshlib.client_mesh(1)
    rng = jax.random.key(7)
    ids = sampler.cohort(0)
    imgs, labels, w = pop.materialize(ids)
    oneshot = make_fedavg_round(_model(), rmsprop(1e-3),
                                binary_cross_entropy, mesh,
                                local_epochs=1, batch_size=16)
    s1, m1 = oneshot(initialize_server(_model(), jax.random.key(0)),
                     imgs, labels, w, rng)
    stream = _stream_round(pop, sampler, mesh, wave=C)
    s2, m2 = stream(initialize_server(_model(), jax.random.key(0)),
                    None, None, None, rng, round_idx=0)
    _assert_bitwise(s1.params, s2.params)
    _assert_bitwise(s1.model_state, s2.model_state)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                              rel=1e-6)
    assert int(m2["waves"]) == 1 and int(m2["participants"]) == C


def test_streamed_multiwave_fp_close_and_replays(devices):
    """Splitting the cohort into waves changes only the cross-wave
    ADDITION ORDER: fp-close to the one-shot mean (never a different
    estimator), while the round itself replays bit-identically from
    (seed, round) — the hard ISSUE-13 requirement."""
    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    mesh = meshlib.client_mesh(1)
    rng = jax.random.key(7)
    ids = sampler.cohort(0)
    imgs, labels, w = pop.materialize(ids)
    oneshot = make_fedavg_round(_model(), rmsprop(1e-3),
                                binary_cross_entropy, mesh,
                                local_epochs=1, batch_size=16)
    s1, _ = oneshot(initialize_server(_model(), jax.random.key(0)),
                    imgs, labels, w, rng)
    stream = _stream_round(pop, sampler, mesh, wave=C // 4)
    s2, m2 = stream(initialize_server(_model(), jax.random.key(0)),
                    None, None, None, rng, round_idx=0)
    assert int(m2["waves"]) == 4
    for a, b in zip(_leaves(s1.params), _leaves(s2.params)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    # bit-identical replay from (seed, round), fresh build
    replay = _stream_round(pop, CohortSampler(pop, C, seed=5), mesh,
                           wave=C // 4)
    s3, _ = replay(initialize_server(_model(), jax.random.key(0)),
                   None, None, None, rng, round_idx=0)
    _assert_bitwise(s2.params, s3.params)
    _assert_bitwise(s2.model_state, s3.model_state)


def test_streamed_norm_clip_composes_exact(devices):
    """NormClip is a per-client transform + weighted mean, so it
    streams losslessly: single-wave streamed == one-shot, bit for bit,
    including the clipped-client count."""
    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    mesh = meshlib.client_mesh(1)
    rng = jax.random.key(9)
    ids = sampler.cohort(0)
    imgs, labels, w = pop.materialize(ids)
    oneshot = make_fedavg_round(
        _model(), rmsprop(1e-3), binary_cross_entropy, mesh,
        local_epochs=1, batch_size=16,
        aggregator=robust.NormClip(0.05))
    s1, m1 = oneshot(initialize_server(_model(), jax.random.key(0)),
                     imgs, labels, w, rng)
    stream = _stream_round(pop, sampler, mesh, wave=C,
                           aggregator=robust.NormClip(0.05))
    s2, m2 = stream(initialize_server(_model(), jax.random.key(0)),
                    None, None, None, rng, round_idx=0)
    _assert_bitwise(s1.params, s2.params)
    assert float(m1["clients_clipped"]) == float(m2["clients_clipped"])


def test_streamed_trimmed_runs_per_wave(devices):
    """TrimmedMean streams with PER-WAVE semantics: each wave trims its
    own extremes. Under sign-flip attackers the streamed trimmed round
    stays near the honest trajectory while the streamed mean is
    steered far away."""
    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    mesh = meshlib.client_mesh(1)
    rng = jax.random.key(3)
    ids = sampler.cohort(0)
    # two attackers that ARE in round 0's cohort
    plan = faults_lib.PopulationFaultPlan(pop.size, [
        faults_lib.PopulationFault("sign_flip",
                                   clients=tuple(ids[:2]),
                                   fraction=None, scale=1000.0)])

    def run(agg, faults):
        rnd = _stream_round(pop, CohortSampler(pop, C, seed=5), mesh,
                            wave=C, aggregator=agg, faults=faults)
        s, m = rnd(initialize_server(_model(), jax.random.key(0)),
                   None, None, None, rng, round_idx=0)
        return _leaves(s.params), m

    honest, _ = run(None, None)
    attacked_mean, _ = run(None, plan)
    attacked_trim, mt = run(robust.TrimmedMean(trim=2), plan)
    d_mean = max(np.abs(a - b).max()
                 for a, b in zip(honest, attacked_mean))
    d_trim = max(np.abs(a - b).max()
                 for a, b in zip(honest, attacked_trim))
    assert all(np.isfinite(x).all() for x in attacked_trim)
    assert d_mean > 10 * d_trim, (d_mean, d_trim)
    assert float(mt["trim_degenerate"]) == 0.0


def test_streamed_aggregator_build_teaching_errors():
    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    mesh = meshlib.client_mesh(1)
    with pytest.raises(ValueError, match="Median cannot stream"):
        _stream_round(pop, sampler, mesh, wave=4,
                      aggregator=robust.Median())
    with pytest.raises(ValueError, match="PER WAVE|per wave|grow "
                                         "wave_size"):
        _stream_round(pop, sampler, mesh, wave=4,
                      aggregator=robust.TrimmedMean(trim=2))
    with pytest.raises(ValueError, match="must divide the cohort"):
        _stream_round(pop, sampler, mesh, wave=3)
    with pytest.raises(ValueError, match="participation mask"):
        rnd = _stream_round(pop, sampler, mesh, wave=4)
        rnd(initialize_server(_model(), jax.random.key(0)), None, None,
            np.ones(5, np.float32), jax.random.key(0), round_idx=0)


def test_streamed_crash_fault_equals_manual_mask(devices):
    """A population-plan crash on a cohort member equals zeroing that
    member's participation mask: the virtual-id fault lands on exactly
    the right positional slot. Held to 1e-6 absolute, not bitwise: the
    round built WITH a fault plan and the round built without one are
    two different XLA programs whose reductions may associate
    differently (on the CPU backend they differ by one or two ulp: at
    most 3.0e-8 on weights of 0.1-0.25, 2.3e-10 on values of 1e-3),
    while a victim that was NOT dropped moves these parameters by
    3.4e-4 at the median and 7.9e-4 at most. Bitwise equality is owed
    between runs of ONE program, which is what every `_assert_bitwise`
    in this file compares."""
    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    mesh = meshlib.client_mesh(1)
    rng = jax.random.key(5)
    ids = sampler.cohort(0)
    victim = int(ids[3])
    plan = faults_lib.PopulationFaultPlan(pop.size, [
        faults_lib.PopulationFault("crash", clients=(victim,),
                                   fraction=None)])
    faulted = _stream_round(pop, CohortSampler(pop, C, seed=5), mesh,
                            wave=C, faults=plan)
    s_f, m_f = faulted(initialize_server(_model(), jax.random.key(0)),
                       None, None, None, rng, round_idx=0)
    mask = np.ones((C,), np.float32)
    mask[3] = 0.0
    plain = _stream_round(pop, CohortSampler(pop, C, seed=5), mesh,
                          wave=C)
    s_m, _ = plain(initialize_server(_model(), jax.random.key(0)),
                   None, None, mask, rng, round_idx=0)
    for a, b in zip(_leaves(s_f.params), _leaves(s_m.params)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    assert int(m_f["clients_dropped"]) == 0    # crash != divergence


def test_streamed_round_allocates_by_the_wave_not_the_population(devices):
    """The O(wave) memory contract, by the host allocations a warm
    round makes (tracemalloc sees every numpy buffer the population
    and the round build): the same cohort of 256 in waves of 32 peaks
    at the same few megabytes over 10,000 virtual clients as over
    1,000. One float per client and shard example would be 190 MB at
    10,000; a whole cohort's shards are 4.9 MB, a wave's 0.6 MB."""
    import tracemalloc

    cohort, wave = 256, 32
    mesh = meshlib.client_mesh(8)

    def build(n):
        pop = _population(n, seed=0)
        return _stream_round(pop, CohortSampler(pop, cohort, seed=0),
                             mesh, wave)

    def peak_mb(rnd, round_idx):
        srv = jax.device_put(initialize_server(_model(),
                                               jax.random.key(0)),
                             meshlib.replicated(mesh))
        tracemalloc.start()
        try:
            srv, m = rnd(srv, None, None, None, jax.random.key(1),
                         round_idx=round_idx)
            jax.block_until_ready(srv.params)
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        assert int(m["participants"]) == cohort
        assert int(m["waves"]) == cohort // wave
        return peak / 2**20

    rnd_1k, rnd_10k = build(1_000), build(10_000)
    peak_mb(rnd_1k, 0)                       # cold: pays the compiles
    p_1k, p_10k = peak_mb(rnd_1k, 5), peak_mb(rnd_10k, 5)
    assert p_10k < 32.0, p_10k
    assert p_10k < max(2.0 * p_1k, 8.0), (p_10k, p_1k)


def test_streamed_through_driver_checkpoint_resume(devices, tmp_path):
    """ISSUE-13 satellite: the sampler is a pure function of (seed,
    round), so a checkpoint/resume at round r regenerates rounds
    r..R-1's cohorts byte-identically and the resumed run lands on the
    SAME final params as the uninterrupted one — with fresh builder
    objects on the resume side (the process-restart stand-in)."""
    from idc_models_tpu.train import restore_checkpoint

    pop = _population()
    mesh = meshlib.client_mesh(2)

    def builder():
        return _stream_round(_population(), CohortSampler(_population(),
                                                          C, seed=5),
                             mesh, wave=4)

    w = np.ones((C,), np.float32)
    full = run_rounds(builder(),
                      initialize_server(_model(), jax.random.key(0)),
                      None, None, w, config=DriverConfig(rounds=4),
                      seed=1)
    path = tmp_path / "server"
    run_rounds(builder(),
               initialize_server(_model(), jax.random.key(0)),
               None, None, w,
               config=DriverConfig(rounds=2, checkpoint_path=path,
                                   checkpoint_every=2), seed=1)
    restored = restore_checkpoint(
        path, jax.device_get(initialize_server(_model(),
                                               jax.random.key(9))))
    assert int(restored.round) == 2
    resumed = run_rounds(builder(), restored, None, None, w,
                         config=DriverConfig(rounds=4), seed=1)
    assert [h["round"] for h in resumed.history] == [2, 3]
    _assert_bitwise(full.server.params, resumed.server.params)
    _assert_bitwise(full.server.model_state, resumed.server.model_state)


def test_streamed_logs_fed_cohort_events(tmp_path):
    from idc_models_tpu.observe import JsonlLogger

    pop = _population()
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        rnd = _stream_round(pop, CohortSampler(pop, C, seed=5),
                            meshlib.client_mesh(1), wave=4,
                            logger=logger)
        srv = initialize_server(_model(), jax.random.key(0))
        for r in range(2):
            srv, _ = rnd(srv, None, None, None, jax.random.key(r),
                         round_idx=r)
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    cohorts = [r for r in recs if r["event"] == "fed_cohort"]
    assert [r["round"] for r in cohorts] == [0, 1]
    assert cohorts[0]["mode"] == "sync"
    assert cohorts[0]["waves"] == 2 and cohorts[0]["wave_size"] == 4


# -- async buffered FedAvg ---------------------------------------------


def _run_async(rounds=3, pop_kw=(), **kw):
    pop = _population(**dict(pop_kw))
    rf = _async_round(pop, CohortSampler(pop, C, seed=5), **kw)
    srv = initialize_server(_model(), jax.random.key(0))
    history = []
    for r in range(rounds):
        srv, m = rf(srv, None, None, None, None, round_idx=r)
        history.append(m)
    return srv, history, rf


def test_async_full_run_replays_bit_identically():
    s1, h1, _ = _run_async()
    s2, h2, _ = _run_async()
    _assert_bitwise(s1.params, s2.params)
    _assert_bitwise(s1.model_state, s2.model_state)
    assert [m["updates"] for m in h1] == [m["updates"] for m in h2]
    assert [m["staleness_mean"] for m in h1] == \
        [m["staleness_mean"] for m in h2]


def test_async_buffer_and_staleness_semantics():
    # cohort 8, buffer 4: two updates per round, zero leftover; the
    # staleness discount changes the trajectory
    s1, h1, _ = _run_async(staleness_decay=1.0)
    assert all(m["updates"] == 2 for m in h1)
    assert all(m["buffer_fill"] == 0 for m in h1)
    assert h1[-1]["staleness_max"] >= 1       # pipelined in-flight work
    s2, _, _ = _run_async(staleness_decay=0.5)
    different = any(
        (a != b).any() for a, b in zip(_leaves(s1.params),
                                       _leaves(s2.params)))
    assert different, "staleness decay must reweight stale updates"
    # a buffer that does not divide the cohort carries fill across
    # rounds instead of forcing a barrier flush
    pop = _population()
    rf = _async_round(pop, CohortSampler(pop, C, seed=5), buffer_size=5)
    srv = initialize_server(_model(), jax.random.key(0))
    srv, m0 = rf(srv, None, None, None, None, round_idx=0)
    assert m0["updates"] == 1 and m0["buffer_fill"] == 3
    srv, m1 = rf(srv, None, None, None, None, round_idx=1)
    assert m1["updates"] == 2 and m1["buffer_fill"] == 1


def test_async_absorbs_straggler_wall_clock():
    """The acceptance mechanism at unit scale: with an injected
    straggler delay, the sync round's wall is the BARRIER (max delay)
    while the async server processes the fast arrivals — asserted on
    real clocks, driven entirely by the injected sleeps."""
    import time

    pop = _population()
    ids0 = CohortSampler(pop, C, seed=5).cohort(0)
    plan = faults_lib.PopulationFaultPlan(
        pop.size,
        [faults_lib.PopulationFault("straggler",
                                    clients=(int(ids0[0]),),
                                    fraction=None, staleness=2)],
        delay_unit_s=0.3)
    mesh = meshlib.client_mesh(1)
    sync = _stream_round(pop, CohortSampler(pop, C, seed=5), mesh,
                         wave=C, faults=plan, barrier_sleep=True)
    srv = initialize_server(_model(), jax.random.key(0))
    sync(srv, None, None, None, jax.random.key(0), round_idx=0)  # warm
    t0 = time.monotonic()
    srv2 = initialize_server(_model(), jax.random.key(0))
    sync(srv2, None, None, None, jax.random.key(0), round_idx=0)
    sync_wall = time.monotonic() - t0
    assert sync_wall >= 0.6, sync_wall          # 2 lag units slept

    rf = _async_round(pop, CohortSampler(pop, C, seed=5), faults=plan,
                      realtime=True, base_latency_s=(0.001, 0.005))
    srv3 = initialize_server(_model(), jax.random.key(0))
    # round 0 pays the train/apply compiles (the sync side was warmed
    # the same way); round 1 is the steady-state wall the barrier
    # comparison is about
    srv3, _ = rf(srv3, None, None, None, None, round_idx=0)
    t0 = time.monotonic()
    _, m = rf(srv3, None, None, None, None, round_idx=1)
    async_wall = time.monotonic() - t0
    assert m["participants"] == C
    assert async_wall < sync_wall, (async_wall, sync_wall)


def test_async_reaches_the_sync_loss_under_stragglers():
    """Under one straggler plan (a quarter of the population two lag
    units late) the buffered-async server reaches the evaluation loss
    the synchronous streamed round has after six rounds, within four
    times as many rounds: discounting stale updates slows learning per
    completion, it does not stop it. Virtual time, no sleeps."""
    from idc_models_tpu.federated import make_federated_eval

    pop = _population(seed=0)
    mesh = meshlib.client_mesh(1)
    plan = faults_lib.PopulationFaultPlan(
        pop.size, [faults_lib.PopulationFault("straggler", fraction=0.25,
                                              staleness=2)], seed=3)
    ev = make_federated_eval(_model(), binary_cross_entropy, mesh)
    e_imgs, e_labels, e_w = pop.materialize(
        CohortSampler(pop, 8, seed=999).cohort(0))

    def loss(server):
        return float(ev(server, e_imgs, e_labels, e_w)["loss"])

    w = np.ones((C,), np.float32)
    sync = run_rounds(
        _stream_round(pop, CohortSampler(pop, C, seed=11), mesh, wave=C,
                      faults=plan),
        jax.device_put(initialize_server(_model(), jax.random.key(0)),
                       meshlib.replicated(mesh)),
        None, None, w, config=DriverConfig(rounds=6), seed=1)
    target = loss(sync.server)

    rf = _async_round(pop, CohortSampler(pop, C, seed=11), faults=plan,
                      seed=1)
    server, rounds = initialize_server(_model(), jax.random.key(0)), 0
    while rounds < 24:
        server = run_rounds(rf, server, None, None, w,
                            config=DriverConfig(rounds=rounds + 1),
                            seed=1).server
        rounds += 1
        if loss(server) <= target:
            break
    assert loss(server) <= target, (loss(server), target, rounds)


def test_async_crash_clients_are_refilled():
    plan = faults_lib.PopulationFaultPlan(
        64, [faults_lib.PopulationFault("crash", fraction=0.25)],
        seed=2)
    _, h, _ = _run_async(faults=plan)
    assert all(m["participants"] == C for m in h)   # slots refilled
    # crashed is a PER-ROUND count, not a lifetime total
    assert sum(m["crashed"] for m in h) > 0
    assert max(m["crashed"] for m in h) < 3 * C


def test_async_retry_discards_the_failed_attempts_inflight_work():
    """Driver rollback isolation: a retried round must NOT apply
    buffered/in-flight updates trained against the discarded attempt's
    params — the async server resets its pool when the round index
    stops advancing."""
    pop = _population()
    rf = _async_round(pop, CohortSampler(pop, C, seed=5),
                      buffer_size=5)   # 5 !| 8: leaves a partial buffer
    calls = []

    def flaky(server, images, labels, weights, rng, *, round_idx=None):
        s, m = rf(server, images, labels, weights, rng,
                  round_idx=round_idx)
        calls.append(round_idx)
        if round_idx == 1 and calls.count(1) == 1:
            s = s.replace(params=jax.tree.map(
                lambda x: x * jnp.nan, s.params))
        return s, m

    res = run_rounds(flaky,
                     initialize_server(_model(), jax.random.key(0)),
                     None, None, np.ones((C,), np.float32),
                     config=DriverConfig(rounds=3), seed=1)
    statuses = [(e["round"], e["status"]) for e in res.events]
    assert (1, "diverged") in statuses
    assert int(res.server.round) == 3
    assert all(np.isfinite(x).all() for x in _leaves(res.server.params))
    # the sharp part: round 0 leaves fill 3 (8 completions, buffer 5).
    # The failed round-1 attempt consumes it (3+8 -> 2 updates, fill
    # 1). The RETRY runs the driver's reseeded subset (6 of 8) and
    # must start from an EMPTY buffer: 6 completions -> 1 update,
    # fill 1; had the discarded attempt's leftover fill carried over,
    # the retry would end at fill 2 — the off-by-the-poisoned-work
    # signature
    assert res.history[0]["updates"] == 1
    assert res.history[0]["buffer_fill"] == 3
    assert res.history[1]["participants"] == 6
    assert res.history[1]["updates"] == 1
    assert res.history[1]["buffer_fill"] == 1


def test_async_through_driver_with_health_events(tmp_path):
    from idc_models_tpu.observe import JsonlLogger

    pop = _population()
    rf = _async_round(pop, CohortSampler(pop, C, seed=5))
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        res = run_rounds(rf,
                         initialize_server(_model(), jax.random.key(0)),
                         None, None, np.ones((C,), np.float32),
                         config=DriverConfig(rounds=2), seed=1,
                         logger=logger)
    assert int(res.server.round) == 2
    assert all(e["status"] == "ok" for e in res.events)
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    assert sum(r["event"] == "round_health" for r in recs) == 2
    assert rf.last_participants.shape == (C,)


def test_async_build_validation_and_secure_rejection():
    from idc_models_tpu.federated import ensure_async_compatible

    pop = _population()
    sampler = CohortSampler(pop, C, seed=5)
    with pytest.raises(ValueError, match="secure"):
        ensure_async_compatible(secure=True)
    with pytest.raises(ValueError, match="secure"):
        _async_round(pop, sampler, secure_aggregation=True)
    with pytest.raises(ValueError, match="TrimmedMean"):
        _async_round(pop, sampler, aggregator=robust.TrimmedMean(1))
    with pytest.raises(ValueError, match="Median"):
        _async_round(pop, sampler, aggregator=robust.Median())
    with pytest.raises(ValueError, match="buffer_size"):
        _async_round(pop, sampler, buffer_size=0)
    with pytest.raises(ValueError, match="staleness_decay"):
        _async_round(pop, sampler, staleness_decay=1.5)
    with pytest.raises(ValueError, match="never fill"):
        _async_round(pop, sampler, buffer_size=C + 1)
    # norm_clip composes (exact per-client transform)
    _async_round(pop, sampler, aggregator=robust.NormClip(1.0))


def test_fed_client_markers_carry_virtual_ids(tmp_path):
    """PR 7 wiring: population rounds stamp fed.client markers with
    VIRTUAL client ids (participant_ids_fn) and the population plan's
    fault outcome."""
    from idc_models_tpu.observe import tracing

    pop = _population(8)
    sampler = CohortSampler(pop, 8, seed=5)     # cohort == population
    ids = sampler.cohort(0)
    straggler = int(ids[2])
    plan = faults_lib.PopulationFaultPlan(
        8, [faults_lib.PopulationFault("straggler",
                                       clients=(straggler,),
                                       fraction=None, staleness=2)])
    rnd = _stream_round(pop, sampler, meshlib.client_mesh(1), wave=8,
                        faults=plan)
    out = tmp_path / "trace.jsonl"
    with tracing(jsonl_path=out):
        run_rounds(rnd, initialize_server(_model(), jax.random.key(0)),
                   None, None, np.ones((8,), np.float32),
                   config=DriverConfig(rounds=1), seed=1,
                   fault_plan=plan,
                   participant_ids_fn=lambda r: sampler.cohort(r))
    recs = [json.loads(l) for l in out.read_text().splitlines()]
    clients = [r for r in recs
               if r.get("name") == "fed.client"]
    got = sorted(r["attrs"]["client"] for r in clients)
    assert got == sorted(int(c) for c in ids)
    marked = [r for r in clients
              if r["attrs"]["client"] == straggler]
    assert marked and marked[0]["attrs"]["fault"] == "straggler"
    assert marked[0]["attrs"]["staleness"] == 2
    ok = [r for r in clients if r["attrs"]["client"] != straggler]
    assert all(r["attrs"]["fault"] == "ok" for r in ok)
