"""Population-scale federated training: virtual clients, cohort
sampling, and streamed hierarchical aggregation.

`make_fedavg_round` materializes EVERY client as a stacked
[C, S, ...] array and aggregates the whole round in one dispatch — the
right shape for the 10–32 clients the reference simulates, and a dead
end at the ROADMAP's "millions of users" scale: memory grows with the
population and a synchronous barrier waits on its slowest member.
Production FL systems (Bonawitz et al., *Towards Federated Learning at
Scale*) instead SELECT a small cohort from a huge population each round
and aggregate it in a streamed, hierarchical fashion. This module is
that layer:

- `ClientPopulation` — 10k+ *virtual* clients whose data shards are
  derived lazily from `(seed, client_id)`. No population-sized array
  ever exists (statically gated by the AST scan in
  test_static_robustness.py); memory is bounded by whatever cohort is
  materialized.
- `CohortSampler` — deterministic per-round cohort selection, uniform
  (Floyd's algorithm, O(cohort) memory) or weighted-by-size (rejection
  sampling against the population's known weight bound). The cohort is
  a pure function of `(seed, round)`: there is no sampler state to
  checkpoint — a driver resume at round r regenerates round r's cohort
  byte-identically (gated).
- `make_population_round` — a driver-compatible round function that
  streams the cohort through fixed-size WAVES: each wave materializes
  O(wave) client data, trains its clients fused (the same vmapped
  local program as `make_fedavg_round`), reduces over the device shard
  (level 1, `psum`), and folds into a running weighted aggregate
  (level 2, cross-wave). Server memory is O(wave) client data plus one
  accumulator tree — constant in BOTH population and cohort size.

Aggregation parity contract (the chunk-prefill precedent): wave
partial sums use the IDENTICAL masked-sum reduction as
`collectives.weighted_pmean_local`, so a single wave covering the
cohort is bit-identical to the one-shot `make_fedavg_round` (gated),
and splitting the cohort into waves that mirror a device-sharded
one-shot layout reproduces its psum association (gated on the 2-wave /
2-device pair). Any other wave split changes only the cross-wave
ADDITION ORDER — fp-close, never a different estimator — while the
round itself replays bit-identically from `(seed, round)` (gated, the
hard requirement every drill in this tree shares).

Robust aggregators (`federated/robust.py`) compose as follows:

- `WeightedMean` / `NormClip` — exact: both are per-client transforms
  followed by a weighted mean, and weighted sums stream losslessly.
- `TrimmedMean` — runs PER WAVE: each wave trims its own extremes and
  the wave aggregates combine by alive-count-weighted running mean.
  The guarantee becomes "up to `trim` Byzantine clients *per wave*"
  (documented in docs/ROBUSTNESS.md); a wave too small to ever keep a
  value (wave clients <= 2*trim) is rejected at build.
- `Median` — rejected at build with a teaching error: cross-cohort
  order statistics need every client's value at once, which is exactly
  what streaming gives up; per-wave median-of-means is a DIFFERENT
  estimator, so refusing beats silently running one.
"""

from __future__ import annotations

import time
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from idc_models_tpu import collectives
from idc_models_tpu import mesh as meshlib
from idc_models_tpu.federated.fedavg import (
    ServerState, copy_tree, finite_clients, make_local_trainer,
)
from idc_models_tpu.models import core
from idc_models_tpu.observe import metrics_registry as mreg


class ClientPopulation:
    """`size` virtual clients, each a pure function of (seed, id).

    `shard(cid)` synthesizes the client's data lazily —
    `data.synthetic.make_idc_like` seeded by `(seed, 1, cid)` unless a
    custom ``make_shard(cid) -> (imgs [S,H,W,3], labels [S])`` is
    given — and `weight(cid)` is the client's aggregation weight /
    dataset-size proxy, seeded uniform in `weight_range`. Shards are
    fixed-shape ([examples_per_client] each) so cohorts stack; the
    WEIGHT models differing client dataset sizes (it drives both the
    weighted sampler and the round's example weighting). Nothing here
    allocates O(population): the only population-sized helper is the
    explicitly documented `all_weights` (validation only), and the
    static scan in test_static_robustness.py keeps it that way.
    """

    def __init__(self, size: int, *, examples_per_client: int = 16,
                 image_size: int = 10, seed: int = 0,
                 weight_range: tuple[float, float] = (1.0, 1.0),
                 make_shard: Callable[[int], tuple] | None = None):
        if size < 1:
            raise ValueError(f"need a population of >= 1 virtual "
                             f"clients, got {size}")
        if examples_per_client < 1:
            raise ValueError(f"need examples_per_client >= 1, got "
                             f"{examples_per_client}")
        lo, hi = float(weight_range[0]), float(weight_range[1])
        if not (0.0 < lo <= hi):
            raise ValueError(f"weight_range must satisfy 0 < lo <= hi, "
                             f"got {weight_range}")
        self.size = int(size)
        self.examples_per_client = int(examples_per_client)
        self.image_size = int(image_size)
        self.seed = int(seed)
        self.weight_range = (lo, hi)
        self._make_shard = make_shard

    @property
    def weight_max(self) -> float:
        """The known upper bound the weighted sampler rejects against."""
        return self.weight_range[1]

    def _check_cid(self, cid: int) -> int:
        cid = int(cid)
        if not 0 <= cid < self.size:
            raise ValueError(f"virtual client id {cid} outside the "
                             f"population (0..{self.size - 1})")
        return cid

    def shard(self, cid: int) -> tuple[np.ndarray, np.ndarray]:
        """(imgs [S,H,W,3] f32, labels [S] i32), derived lazily —
        byte-identical on every call (gated)."""
        cid = self._check_cid(cid)
        if self._make_shard is not None:
            return self._make_shard(cid)
        from idc_models_tpu.data import synthetic

        return synthetic.make_idc_like(
            self.examples_per_client, size=self.image_size,
            seed=(self.seed, 1, cid))

    def weight(self, cid: int) -> float:
        cid = self._check_cid(cid)
        lo, hi = self.weight_range
        if lo == hi:
            return lo
        u = np.random.default_rng((self.seed, 2, cid)).random()
        return lo + (hi - lo) * u

    def materialize(self, ids) -> tuple[np.ndarray, np.ndarray,
                                        np.ndarray]:
        """Stack a cohort/wave: (imgs [C,S,...], labels [C,S],
        weights [C]) — O(len(ids)) memory, the ONLY way client data
        ever exists on the host."""
        ids = np.asarray(ids, np.int64)
        imgs, labels, weights = [], [], []
        for cid in ids:
            im, lb = self.shard(int(cid))
            imgs.append(im)
            labels.append(lb)
            weights.append(self.weight(int(cid)))
        return (np.stack(imgs), np.stack(labels),
                np.asarray(weights, np.float32))

    def all_weights(self) -> np.ndarray:
        """[size] weights — the one deliberately O(population) helper,
        for validating the weighted sampler's distribution on SMALL
        populations in tests. Never on the training path (the static
        scan allowlists exactly this function)."""
        out = np.empty((self.size,), np.float32)
        for cid in range(self.size):
            out[cid] = self.weight(cid)
        return out

    def same_config(self, other: "ClientPopulation") -> bool:
        """True when `other` derives the SAME virtual clients — the
        compatibility check between a sampler and a round builder
        (identity is too strict: a process restart rebuilds both)."""
        return (self.size == other.size
                and self.examples_per_client == other.examples_per_client
                and self.image_size == other.image_size
                and self.seed == other.seed
                and self.weight_range == other.weight_range
                and self._make_shard is other._make_shard)

    def __repr__(self) -> str:
        return (f"ClientPopulation(size={self.size}, "
                f"examples_per_client={self.examples_per_client}, "
                f"seed={self.seed}, weight_range={self.weight_range})")


class CohortSampler:
    """Deterministic per-round cohort selection over a
    `ClientPopulation`.

    `cohort(r)` is a pure function of `(seed, r)` — there is NO mutable
    sampler state, which is the whole checkpoint/resume story: the
    driver checkpoints only `ServerState.round`, and a resumed run
    regenerates every later round's cohort byte-identically (gated).
    Uniform sampling is Floyd's algorithm (O(cohort) memory, no
    population-sized permutation); `weighted=True` samples without
    replacement proportional to `population.weight(cid)` by rejection
    against the population's `weight_max` bound — still O(cohort)
    memory, expected O(cohort * w_max / w_mean) draws.
    """

    def __init__(self, population: ClientPopulation, cohort_size: int,
                 *, seed: int = 0, weighted: bool = False):
        if not 1 <= cohort_size <= population.size:
            raise ValueError(
                f"cohort_size must be in [1, population={population.size}"
                f"], got {cohort_size} — a cohort cannot exceed the "
                f"population it samples from")
        self.population = population
        self.cohort_size = int(cohort_size)
        self.seed = int(seed)
        self.weighted = bool(weighted)

    def cohort(self, round_idx: int) -> np.ndarray:
        """[cohort_size] sorted unique virtual-client ids for one round
        — byte-identical across calls, processes, and resumes."""
        rng = np.random.default_rng((self.seed, 3, int(round_idx)))
        if self.weighted:
            return self._weighted(rng)
        return self._uniform(rng)

    def _uniform(self, rng) -> np.ndarray:
        n, k = self.population.size, self.cohort_size
        chosen: set[int] = set()
        for j in range(n - k, n):
            t = int(rng.integers(0, j + 1))
            if t in chosen:
                t = j
            chosen.add(t)
        return np.sort(np.fromiter(chosen, np.int64, len(chosen)))

    def _weighted(self, rng) -> np.ndarray:
        n, k = self.population.size, self.cohort_size
        w_max = self.population.weight_max
        chosen: set[int] = set()
        draws, limit = 0, max(10_000, 1_000 * k)
        while len(chosen) < k:
            draws += 1
            if draws > limit:
                raise RuntimeError(
                    f"weighted cohort sampling did not converge after "
                    f"{limit} draws (cohort {k} of {n}; is weight_max "
                    f"{w_max} far above the typical weight?)")
            c = int(rng.integers(0, n))
            if c in chosen:
                continue
            if rng.random() * w_max <= self.population.weight(c):
                chosen.add(c)
        return np.sort(np.fromiter(chosen, np.int64, len(chosen)))

    def client_at(self, i: int) -> int:
        """The i-th client of the CONTINUOUS sampled dispatch stream —
        the async server's unit of selection (with replacement over
        time, like repeated cohort draws). Pure function of
        `(seed, i)`."""
        rng = np.random.default_rng((self.seed, 4, int(i)))
        n = self.population.size
        if not self.weighted:
            return int(rng.integers(0, n))
        w_max = self.population.weight_max
        for _ in range(100_000):
            c = int(rng.integers(0, n))
            if rng.random() * w_max <= self.population.weight(c):
                return c
        raise RuntimeError("weighted stream sampling did not converge")

    def __repr__(self) -> str:
        return (f"CohortSampler(population={self.population.size}, "
                f"cohort_size={self.cohort_size}, seed={self.seed}, "
                f"weighted={self.weighted})")


def _teach_aggregator(agg) -> str:
    from idc_models_tpu.federated import robust

    if isinstance(agg, robust.Median):
        return (
            "Median cannot stream: the coordinate-wise median needs "
            "every cohort member's value at once, and a per-wave "
            "median of means is a DIFFERENT estimator with weaker "
            "guarantees. Use trimmed_mean (runs per wave with the "
            "documented per-wave tolerance) or the one-shot "
            "make_fedavg_round for exact cross-cohort order statistics.")
    return (
        f"aggregator {agg!r} has no streaming strategy: streamed "
        f"rounds support mean/norm_clip (exact — per-client transform "
        f"+ weighted mean) and trimmed_mean (per-wave, documented in "
        f"docs/ROBUSTNESS.md).")


def make_population_round(
    model: core.Module,
    optimizer,
    loss_fn,
    mesh: Mesh,
    population: ClientPopulation,
    sampler: CohortSampler,
    *,
    wave_size: int,
    local_epochs: int = 1,
    batch_size: int = 32,
    compute_dtype=jnp.float32,
    drop_nonfinite: bool = True,
    aggregator=None,
    faults=None,
    barrier_sleep: bool = False,
    logger=None,
    log_from_round: int = -1,
    rules=None,
):
    """Build the streamed population round.

    Returns ``round_fn(server, images, labels, weights, rng, *,
    round_idx=None) -> (server, metrics)`` — driver-compatible
    (`federated/driver.py run_rounds`): `images`/`labels` are unused
    (the population synthesizes wave data lazily) and `weights`, when
    given, is a [cohort_size] participation MASK over cohort positions
    (the driver's reseeded-subset retry drops members by zeroing it);
    pass None (or ones) for full participation. Each round:

    1. `sampler.cohort(r)` draws the round's virtual clients —
       replayable from `(seed, r)`;
    2. the cohort streams through `cohort_size / wave_size` waves: each
       wave materializes O(wave) data, trains fused, device-shard
       reduces (`psum`), and folds into the running aggregate (one
       fixed-shape jitted program, zero recompiles after the first
       wave);
    3. a finalize program divides the accumulated sums and applies the
       all-dead guard exactly like the one-shot round.

    `faults` is a `faults.PopulationFaultPlan`: codes address VIRTUAL
    ids and are evaluated per cohort (O(cohort)); straggler staleness
    replays the server state from round r-k via the same history the
    one-shot fault path keeps. With `barrier_sleep=True` the round
    also SLEEPS max(plan delay) — the synchronous barrier a straggler
    imposes, which the async buffered server (async_fedavg.py) is
    built to remove; leave False to run drills at full speed.

    `logger` (observe.JsonlLogger) gets one ``fed_cohort`` event per
    round (frozen schema, test_observability.py) for rounds >
    `log_from_round` — the same append-only-resume contract as the
    CLI's round records.
    """
    from idc_models_tpu import faults as faults_lib
    from idc_models_tpu.federated import robust

    agg = robust.get_aggregator(aggregator)
    cohort_size = sampler.cohort_size
    if not population.same_config(sampler.population):
        raise ValueError(
            "sampler and round must draw from the same virtual "
            "population (size/seed/shape differ) — they would train "
            "different clients than they sampled")
    n_devices = mesh.shape[meshlib.CLIENT_AXIS]
    if wave_size < 1 or cohort_size % wave_size:
        raise ValueError(
            f"wave_size {wave_size} must divide the cohort "
            f"({cohort_size}) — waves are fixed-shape so one compiled "
            f"program serves every wave")
    if wave_size % n_devices:
        raise ValueError(
            f"wave_size {wave_size} must be a multiple of the "
            f"{n_devices}-device client mesh (each device trains "
            f"wave_size/devices clients per wave)")
    per_wave_mode = isinstance(agg, robust.TrimmedMean)
    if isinstance(agg, robust.Median) or not isinstance(
            agg, (robust.WeightedMean, robust.NormClip,
                  robust.TrimmedMean)):
        raise ValueError(_teach_aggregator(agg))
    if per_wave_mode and wave_size <= 2 * agg.trim:
        raise ValueError(
            f"trim={agg.trim} can never keep a value inside a "
            f"{wave_size}-client wave (2*trim are always dropped) — "
            f"trimmed_mean runs PER WAVE when streamed, so lower trim "
            f"below {wave_size / 2:.0f} or grow wave_size")
    with_faults = faults is not None
    if with_faults and faults.population != population.size:
        raise ValueError(
            f"fault plan covers a population of {faults.population} "
            f"but the round trains {population.size} virtual clients")

    local_train = make_local_trainer(
        model, optimizer, loss_fn, local_epochs=local_epochs,
        batch_size=batch_size, compute_dtype=compute_dtype)
    k = wave_size // n_devices

    m_cohort = mreg.REGISTRY.gauge(
        "fed_cohort_size", "virtual clients sampled into the last "
        "federated round's cohort")
    m_sampled = mreg.REGISTRY.counter(
        "fed_clients_sampled_total", "virtual clients sampled into "
        "round cohorts, cumulative")

    def per_device(params, model_state, acc, acc_w, acc_m, imgs, labels,
                   weight, pos, rng, *fault_args):
        # one wave's device block: k clients. Per-client rng streams
        # fold the round rng by COHORT POSITION, matching the one-shot
        # round's dev*k+arange(k) stream on the materialized cohort —
        # the parity gates ride on this.
        rngs = jax.vmap(lambda p: jax.random.fold_in(rng, p))(pos)
        new_params, new_ms, (losses, accs) = jax.vmap(
            local_train, in_axes=(None, None, 0, 0, 0))(
            params, model_state, imgs, labels, rngs)

        if with_faults:
            codes, scales, stale_params, stale_state = fault_args
            new_params, new_ms, weight = faults_lib.apply_faults(
                codes, scales, new_params, new_ms, weight,
                params, model_state, stale_params, stale_state)

        w = jnp.maximum(weight, 0.0)
        dropped = jnp.zeros((), jnp.float32)
        if drop_nonfinite:
            ok = finite_clients(k, new_params, new_ms, losses)
            dropped = collectives.psum(
                jnp.sum((w > 0) & ~ok).astype(jnp.float32),
                meshlib.CLIENT_AXIS)
            w = jnp.where(ok, w, 0.0)

        updates = {"params": new_params, "model_state": new_ms}
        server_tree = {"params": params, "model_state": model_state}
        updates, pc_metrics = agg.per_client(updates, server_tree)

        # weighted per-client stats, accumulated as (sum, total) pairs
        # and divided once at finalize — same weighting as the
        # one-shot's weighted_pmean_local metrics
        wave_w = collectives.psum(w.sum(), meshlib.CLIENT_AXIS)
        cl_loss = jnp.mean(losses, axis=tuple(range(1, losses.ndim)))
        cl_acc = jnp.mean(accs, axis=tuple(range(1, accs.ndim)))
        wloss = collectives.psum(
            jnp.where(w > 0, w * cl_loss, 0.0).sum(),
            meshlib.CLIENT_AXIS)
        wacc = collectives.psum(
            jnp.where(w > 0, w * cl_acc, 0.0).sum(),
            meshlib.CLIENT_AXIS)
        new_m = dict(acc_m)
        new_m["wloss"] = acc_m["wloss"] + wloss
        new_m["wacc"] = acc_m["wacc"] + wacc
        new_m["wtotal"] = acc_m["wtotal"] + wave_w
        new_m["dropped"] = acc_m["dropped"] + dropped
        for key, vals in pc_metrics.items():
            new_m[key] = acc_m[key] + collectives.psum(
                jnp.sum(jnp.where(w > 0, vals, 0.0)),
                meshlib.CLIENT_AXIS)

        if per_wave_mode:
            # level 1b: trimmed aggregate OVER THIS WAVE (all-gather
            # inside — the wave bounds its scale), level 2: alive-
            # count-weighted running mean of wave aggregates; a
            # degenerate wave (kept band empty) contributes weight 0
            # instead of smuggling the incoming server state into the
            # average
            wave_agg, agg_m = agg.combine(
                updates, w, server_tree, meshlib.CLIENT_AXIS)
            n_alive = collectives.psum(
                (w > 0).sum().astype(jnp.float32), meshlib.CLIENT_AXIS)
            band_ok = 1.0 - agg_m["trim_degenerate"]
            vw = n_alive * band_ok
            acc = jax.tree.map(
                lambda a, x: a + vw.astype(x.dtype) * x, acc, wave_agg)
            acc_w = acc_w + vw
            new_m["degenerate_waves"] = (new_m["degenerate_waves"]
                                         + agg_m["trim_degenerate"])
            if "clients_trimmed" in agg_m:
                new_m["clients_trimmed"] = (new_m["clients_trimmed"]
                                            + agg_m["clients_trimmed"])
        else:
            # level 1: the IDENTICAL masked weighted sum + device-shard
            # psum as weighted_pmean_local; level 2: running sums. The
            # division happens once, at finalize.
            def wsum(a, x):
                wb = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(
                    x.dtype)
                s = jnp.where(wb > 0, x * wb, jnp.zeros_like(x)).sum(
                    axis=0)
                return a + collectives.psum(s, meshlib.CLIENT_AXIS)

            acc = jax.tree.map(wsum, acc, updates)
            acc_w = acc_w + wave_w
        return acc, acc_w, new_m

    fault_specs = ((P(meshlib.CLIENT_AXIS), P(meshlib.CLIENT_AXIS),
                    P(), P()) if with_faults else ())
    mapped = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(meshlib.CLIENT_AXIS),
                  P(meshlib.CLIENT_AXIS), P(meshlib.CLIENT_AXIS),
                  P(meshlib.CLIENT_AXIS), P()) + fault_specs,
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    # acc buffers are donated (wave N+1 reuses wave N's memory, so the
    # aggregation footprint is one accumulator tree no matter how many
    # waves stream through) and every sharding is PINNED: without
    # explicit in/out shardings the accumulator's sharding drifts
    # between wave 0 (fresh zeros) and wave 1 (program output), which
    # recompiles the wave program mid-round — minutes per round on a
    # big model. The server-shaped pins (params, model_state, and the
    # wave ACCUMULATORS mirroring them) resolve through the shared
    # partition layer when `rules` is given — the accumulators inherit
    # the rules' shardings instead of a pinned ad-hoc replicate; on the
    # 1-D client mesh every rule adapts to replicated (bit-identical).
    rep = meshlib.replicated(mesh)
    csh = meshlib.sharding(mesh, meshlib.CLIENT_AXIS)
    _jits: dict[str, object] = {}

    def _server_shardings(server):
        if rules is None:
            return rep, rep
        sh = rules.shardings(
            mesh, {"params": server.params,
                   "model_state": server.model_state})
        return sh["params"], sh["model_state"]

    def _get_jits(server):
        # built on FIRST use: rules resolve against the server's tree
        # structure, which the builder does not hold
        if "wave" not in _jits:
            p_sh, m_sh = _server_shardings(server)
            acc_sh = {"params": p_sh, "model_state": m_sh}
            wave_in_sh = (p_sh, m_sh, acc_sh, rep, rep, csh, csh, csh,
                          csh, rep) + ((csh, csh, p_sh, m_sh)
                                       if with_faults else ())
            _jits["wave"] = jax.jit(
                mapped, in_shardings=wave_in_sh,
                out_shardings=(acc_sh, rep, rep),
                donate_argnums=(2, 3, 4))
            _jits["finalize"] = jax.jit(
                finalize, in_shardings=(p_sh, m_sh, acc_sh, rep, rep),
                out_shardings=(p_sh, m_sh, rep), donate_argnums=(2,))
            # the placement tree too: resolved once, reused per round
            _jits["place_sh"] = acc_sh if rules is not None else None
        return _jits["wave"], _jits["finalize"]

    def finalize(params, model_state, acc, acc_w, acc_m):
        total = jnp.maximum(acc_w, jnp.float32(1e-30))
        old = {"params": params, "model_state": model_state}
        new = jax.tree.map(
            lambda a: a / total.astype(a.dtype), acc)
        any_alive = acc_w > 0
        metrics = {
            "loss": acc_m["wloss"] / jnp.maximum(
                acc_m["wtotal"], jnp.float32(1e-30)),
            "accuracy": acc_m["wacc"] / jnp.maximum(
                acc_m["wtotal"], jnp.float32(1e-30)),
        }
        metrics = jax.tree.map(
            lambda x: jnp.where(any_alive, x, jnp.float32(jnp.nan)),
            metrics)
        metrics["clients_dropped"] = acc_m["dropped"]
        for key in acc_m:
            if key not in ("wloss", "wacc", "wtotal", "dropped"):
                metrics[key] = acc_m[key]
        if per_wave_mode:
            metrics["trim_degenerate"] = (
                acc_m["degenerate_waves"] > 0).astype(jnp.float32)
        new = jax.tree.map(
            lambda n, o: jnp.where(any_alive, n, o), new, old)
        return new["params"], new["model_state"], metrics

    def _acc_metrics_init():
        m = {"wloss": jnp.zeros((), jnp.float32),
             "wacc": jnp.zeros((), jnp.float32),
             "wtotal": jnp.zeros((), jnp.float32),
             "dropped": jnp.zeros((), jnp.float32)}
        if isinstance(agg, robust.NormClip):
            m["clients_clipped"] = jnp.zeros((), jnp.float32)
        if per_wave_mode:
            m["degenerate_waves"] = jnp.zeros((), jnp.float32)
            if agg.track_clients:
                m["clients_trimmed"] = jnp.zeros((), jnp.float32)
        return m

    n_waves = cohort_size // wave_size
    history: dict[int, Any] = {}
    logged_rounds: set[int] = set()

    def round_fn(server: ServerState, images=None, labels=None,
                 weights=None, rng=None, *, round_idx: int | None = None):
        wave_jit, finalize_jit = _get_jits(server)
        if rules is not None:
            # placement through the shared resolution point's CACHED
            # shardings (no-op once the server carries the layout)
            placed = jax.tree.map(
                meshlib.put_with_sharding,
                {"params": server.params,
                 "model_state": server.model_state},
                _jits["place_sh"])
            server = server.replace(params=placed["params"],
                                    model_state=placed["model_state"])
        r = int(server.round) if round_idx is None else int(round_idx)
        ids = sampler.cohort(r)
        mask = (np.ones((cohort_size,), np.float32) if weights is None
                else np.asarray(jax.device_get(weights), np.float32))
        if mask.shape != (cohort_size,):
            raise ValueError(
                f"weights must be a [{cohort_size}] cohort-position "
                f"participation mask, got shape {mask.shape}")
        codes = scales = None
        stale = None
        if with_faults:
            codes, scales = faults.codes_for(r, ids)
            if faults.max_staleness > 0:
                # straggler history: the server state ENTERING each
                # round, keyed by round index (the one-shot fault
                # path's scheme). Clamped to the oldest RETAINED entry
                # on early rounds — which, after a checkpoint/resume,
                # is the resume round itself: the first max_staleness
                # resumed rounds replay with shallower staleness than
                # the uninterrupted run (in-memory history is not part
                # of the checkpoint; documented resume semantics, same
                # as make_fedavg_round's)
                history[r] = copy_tree(
                    (server.params, server.model_state))
                for old_r in [x for x in history
                              if x < r - max(faults.max_staleness, 1)]:
                    del history[old_r]
                want = r - faults.staleness(r)
                stale = history.get(want, history[min(history)])
            else:
                # no straggler in the plan: STRAGGLER codes cannot
                # occur, so the stale operands are never selected —
                # alias the live server trees instead of copying a
                # full model snapshot per round for nothing
                stale = (server.params, server.model_state)
            if barrier_sleep and faults.delay_unit_s > 0:
                # the synchronous barrier: the round is not done until
                # its slowest participating member reports
                delay = faults.delay_s(r, ids)
                wait = float(np.max(delay * (mask > 0), initial=0.0))
                if wait > 0:
                    time.sleep(wait)

        acc = jax.tree.map(
            jnp.zeros_like,
            {"params": server.params, "model_state": server.model_state})
        acc_w = jnp.zeros((), jnp.float32)
        acc_m = _acc_metrics_init()
        participants = int((mask > 0).sum())
        for wv in range(n_waves):
            sl = slice(wv * wave_size, (wv + 1) * wave_size)
            wave_ids = ids[sl]
            imgs_w, labels_w, w_w = population.materialize(wave_ids)
            w_w = w_w * (mask[sl] > 0)
            pos = np.arange(sl.start, sl.stop, dtype=np.int32)
            args = [server.params, server.model_state, acc, acc_w,
                    acc_m,
                    jax.device_put(imgs_w, csh),
                    jax.device_put(labels_w, csh),
                    jax.device_put(w_w, csh),
                    jax.device_put(pos, csh), rng]
            if with_faults:
                args += [jax.device_put(jnp.asarray(codes[sl]), csh),
                         jax.device_put(jnp.asarray(scales[sl]), csh),
                         *stale]
            acc, acc_w, acc_m = wave_jit(*args)

        params, model_state, metrics = finalize_jit(
            server.params, server.model_state, acc, acc_w, acc_m)
        new_server = server.replace(
            round=server.round + 1, params=params,
            model_state=model_state)
        metrics = dict(metrics)
        metrics["cohort"] = cohort_size
        metrics["participants"] = participants
        metrics["waves"] = n_waves
        m_cohort.set(cohort_size)
        m_sampled.inc(participants)
        if (logger is not None and r > log_from_round
                and r not in logged_rounds):
            # one record per ROUND: a driver retry re-runs the round
            # but must not append a duplicate to the append-only log
            logged_rounds.add(r)
            logger.log(event="fed_cohort", round=r, mode="sync",
                       population=population.size, cohort=cohort_size,
                       participants=participants, waves=n_waves,
                       wave_size=wave_size)
        return new_server, metrics

    round_fn.sampler = sampler
    round_fn.population = population
    return round_fn
