"""Async buffered FedAvg (FedBuff): stragglers stop gating the round.

The synchronous round — one-shot or streamed (population.py) — is a
BARRIER: the server cannot update until its slowest cohort member
reports, so one straggler sets the round's wall-clock (exactly the
failure mode the PR 3 fault plans inject and the PR 7 round-latency
SLOs observe). The buffered-asynchronous server (Nguyen et al.,
*FedBuff*) removes the barrier:

- a CONTINUOUS sampled dispatch stream keeps `concurrency` virtual
  clients in flight; each trains against the server params of its
  dispatch moment and completes after a seeded duration (base latency
  + the fault plan's straggler delay);
- completions fill a buffer of size K; a full buffer triggers ONE
  staleness-weighted server update (weight x `staleness_decay**s`,
  where s = server updates since the client's dispatch) instead of a
  round barrier;
- a straggler's slot is simply refilled — its update lands rounds
  later with a high staleness discount, while the server keeps moving
  on everyone else's work.

Mapped onto `federated/driver.py run_rounds`, one driver "round" =
dispatch-and-process `cohort_size` completions (however many buffered
updates that triggers), so the self-healing loop, round-latency SLOs,
`fed.client` markers, checkpoints, and `round_health` events all apply
unchanged. Under an injected straggler plan the sync round's wall is
max(delay) per round and its latency SLO burns; the async round's wall
is set by the K earliest arrivals and the same SLO stays silent
(tests/test_population.py holds the walls against each other on
injected sleeps; examples/11_slo_alerts.py the alert under a straggler
wave).

Memory: in-flight state is (arrival, client id, version) tuples plus
one retained param snapshot per server version still referenced —
O(concurrency) bookkeeping and O(ceil(concurrency/K) + staleness span)
model-sized snapshots, independent of the population size.

Determinism: every choice — dispatch stream, durations, fault codes,
per-client rng — is a pure function of (seed, dispatch index), and
arrivals pop in (arrival time, dispatch index) order, so a full run
replays bit-identically (gated). A RESUMED run restarts with an empty
in-flight pool at the checkpointed round boundary (in-flight work is
not checkpointed — the honest analogue of a real server restart,
documented in docs/ROBUSTNESS.md).

Secure aggregation CANNOT compose with buffering: the pairwise masks
cancel only when the full round cohort sums together, and a K-of-N
buffered update leaves unmatched masks in the aggregate —
`ensure_async_compatible` rejects the combination at build with that
explanation (gated in tests and at the CLI).
"""

from __future__ import annotations

import heapq
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from idc_models_tpu import faults as faults_lib
from idc_models_tpu.federated.fedavg import (
    ServerState, copy_tree, finite_clients, make_local_trainer,
)
from idc_models_tpu.federated.population import (
    ClientPopulation, CohortSampler,
)
from idc_models_tpu.observe import metrics_registry as mreg

# staleness histogram buckets for the fed_cohort event: updates at lag
# 0,1,2,3,4 and a 5+ tail — frozen with the event schema
STALENESS_BUCKETS = 6


def ensure_async_compatible(*, secure: bool, aggregator=None) -> None:
    """Reject compositions the buffered server cannot honor, at build.

    Secure aggregation: each client's pairwise masks cancel only in the
    sum over the FULL round cohort; a buffered K-of-N update would
    carry every unmatched mask straight into the server params —
    silently destroying the model while "working". Trimmed/median
    aggregation: order statistics need a synchronized cohort view,
    which is the barrier async removes — use norm_clip (per-client,
    composes exactly) or the sync streamed round.
    """
    from idc_models_tpu.federated import robust

    if secure:
        raise ValueError(
            "async buffered FedAvg cannot compose with secure "
            "aggregation: pairwise masks cancel only when the FULL "
            "cohort sums together in one round, and a buffered K-of-N "
            "update leaves unmatched masks in the aggregate — run "
            "secure rounds synchronously, or drop --async-buffer")
    if aggregator is not None and isinstance(
            aggregator, (robust.TrimmedMean, robust.Median)):
        raise ValueError(
            f"{type(aggregator).__name__} cannot compose with async "
            f"buffering: coordinate-wise order statistics need a "
            f"synchronized cohort view, which is exactly the barrier "
            f"the buffer removes — use norm_clip (per-client bound, "
            f"composes exactly) or the sync streamed round")


def make_async_round(
    model,
    optimizer,
    loss_fn,
    population: ClientPopulation,
    sampler: CohortSampler,
    *,
    buffer_size: int,
    staleness_decay: float = 0.9,
    concurrency: int | None = None,
    local_epochs: int = 1,
    batch_size: int = 32,
    compute_dtype=jnp.float32,
    drop_nonfinite: bool = True,
    aggregator=None,
    faults=None,
    base_latency_s: tuple[float, float] = (0.0, 0.0),
    realtime: bool = False,
    seed: int = 0,
    secure_aggregation: bool = False,
    logger=None,
    log_from_round: int = -1,
):
    """Build the buffered-async round (driver-compatible signature).

    ``round_fn(server, images, labels, weights, rng, *, round_idx=None)``
    processes `cohort_size` client completions: dispatches keep
    `concurrency` (default: the sampler's cohort size) clients in
    flight from the continuous sampled stream, every `buffer_size`
    completions trigger one staleness-weighted server update, and the
    returned metrics carry the buffered-mode observability
    (updates/staleness/buffer fill). `weights`, when given, only sets
    how many completions the attempt processes (the driver's
    reseeded-subset retry shrinks it) — the stream itself is a pure
    function of (seed, dispatch index).

    `aggregator` may be None/WeightedMean (plain staleness-weighted
    mean) or a NormClip instance (each buffered delta is L2-clipped
    before weighting — exact composition); trimmed/median and secure
    mode are rejected by `ensure_async_compatible` at build.

    `realtime=True` maps simulated arrival times onto the wall clock
    (sleeping until each processed completion's arrival) — the mode
    the wall-clock drills run; leave False for full-speed unit tests.
    """
    from idc_models_tpu.federated import robust

    ensure_async_compatible(secure=secure_aggregation,
                            aggregator=robust.get_aggregator(aggregator)
                            if aggregator is not None else None)
    agg = robust.get_aggregator(aggregator)
    clip_norm = agg.max_norm if isinstance(agg, robust.NormClip) else None
    if buffer_size < 1:
        raise ValueError(f"need buffer_size >= 1, got {buffer_size}")
    if not 0.0 < staleness_decay <= 1.0:
        raise ValueError(
            f"staleness_decay must be in (0, 1], got {staleness_decay} "
            f"(1.0 = no discount; smaller discounts staler updates "
            f"harder)")
    concurrency = (sampler.cohort_size if concurrency is None
                   else int(concurrency))
    if concurrency < 1:
        raise ValueError(f"need concurrency >= 1, got {concurrency}")
    if buffer_size > concurrency:
        raise ValueError(
            f"buffer_size {buffer_size} > concurrency {concurrency}: "
            f"the buffer could never fill — shrink the buffer or raise "
            f"concurrency")
    lo, hi = float(base_latency_s[0]), float(base_latency_s[1])
    if not 0.0 <= lo <= hi:
        raise ValueError(f"base_latency_s must be 0 <= lo <= hi, got "
                         f"{base_latency_s}")
    if faults is not None and faults.population != population.size:
        raise ValueError(
            f"fault plan covers a population of {faults.population} "
            f"but the server trains {population.size} virtual clients")
    if not population.same_config(sampler.population):
        raise ValueError(
            "sampler and server must draw from the same virtual "
            "population (size/seed/shape differ) — the server would "
            "train different clients than it sampled")

    local_train = make_local_trainer(
        model, optimizer, loss_fn, local_epochs=local_epochs,
        batch_size=batch_size, compute_dtype=compute_dtype)

    def train_one(params, model_state, imgs, labels, rng):
        new_p, new_ms, (losses, accs) = local_train(
            params, model_state, imgs, labels, rng)
        return new_p, new_ms, jnp.mean(losses), jnp.mean(accs)

    train_jit = jax.jit(train_one)
    K = int(buffer_size)

    def apply_buffer(params, model_state, cl, snap, wts, decays,
                     codes, scales):
        """One buffered server update: staleness-decayed weighted mean
        of K client deltas, each taken against ITS OWN dispatch-time
        snapshot. `wts` are the RAW client weights and `decays` the
        per-update staleness factors — the denominator normalizes by
        the raw weights so the discount attenuates a stale update's
        contribution ABSOLUTELY (normalizing by decayed weights would
        cancel a uniform discount: a buffer of equally-stale updates
        must still take a smaller step, not a full one). `decay=1`
        recovers the plain weighted mean bit-for-bit. Fault codes
        transform the deltas exactly like the sync path's
        `apply_faults` (straggler codes are inert here — async
        staleness IS the fault model)."""
        server = (params, model_state)
        ok = jnp.ones((K,), bool)
        if drop_nonfinite:
            ok = finite_clients(K, cl)

        def leafwise(new, old):
            shape = (K,) + (1,) * (new.ndim - 1)
            if not jnp.issubdtype(new.dtype, jnp.inexact):
                return new
            c = codes.reshape(shape)
            s = scales.reshape(shape).astype(new.dtype)
            delta = new - old
            out = jnp.where(c == faults_lib.NAN,
                            jnp.asarray(jnp.nan, new.dtype), new)
            out = jnp.where(c == faults_lib.INF,
                            jnp.asarray(jnp.inf, new.dtype), out)
            out = jnp.where(c == faults_lib.SCALE, old + s * delta, out)
            out = jnp.where(c == faults_lib.SIGN_FLIP,
                            old - s * delta, out)
            return out

        cl = jax.tree.map(leafwise, cl, snap)
        if drop_nonfinite:
            ok = ok & finite_clients(K, cl)
        w = jnp.where(ok, jnp.maximum(wts, 0.0), 0.0)
        dropped = jnp.sum((jnp.maximum(wts, 0.0) > 0) & ~ok).astype(
            jnp.float32)

        if clip_norm is not None:
            sq = jnp.zeros((K,), jnp.float32)
            for new, old in zip(jax.tree.leaves(cl),
                                jax.tree.leaves(snap)):
                if not jnp.issubdtype(new.dtype, jnp.inexact):
                    continue
                d = (new - old).astype(jnp.float32)
                sq = sq + jnp.sum(d * d,
                                  axis=tuple(range(1, d.ndim)))
            factor = jnp.minimum(
                1.0, clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))
            clipped = jnp.sum(
                jnp.where(w > 0, (jnp.sqrt(sq)
                                  > clip_norm).astype(jnp.float32),
                          0.0))
        else:
            factor = jnp.ones((K,), jnp.float32)
            clipped = jnp.zeros((), jnp.float32)

        total = jnp.maximum(jnp.sum(w), jnp.float32(1e-30))
        any_alive = jnp.sum(w) > 0
        aw = w * decays

        def combine(cur, new, old):
            if not jnp.issubdtype(new.dtype, jnp.inexact):
                return cur
            shape = (K,) + (1,) * (new.ndim - 1)
            f = factor.reshape(shape).astype(new.dtype)
            wb = aw.reshape(shape).astype(new.dtype)
            delta = f * (new - old)
            step = jnp.where(wb > 0, wb * delta,
                             jnp.zeros_like(delta)).sum(axis=0)
            out = cur + step / total.astype(cur.dtype)
            return jnp.where(any_alive, out, cur)

        new_server = jax.tree.map(combine, server, cl, snap)
        return new_server[0], new_server[1], dropped, clipped

    apply_jit = jax.jit(apply_buffer, donate_argnums=(0, 1))

    m_buffer = mreg.REGISTRY.gauge(
        "fed_buffer_fill", "client updates currently buffered by the "
        "async federated server")
    m_updates = mreg.REGISTRY.counter(
        "fed_async_updates_total", "staleness-weighted buffered server "
        "updates applied")
    m_staleness = mreg.REGISTRY.histogram(
        "fed_update_staleness", "server-update lag (server versions) "
        "of buffered client updates when applied",
        buckets=(0.5, 1.5, 2.5, 3.5, 4.5))

    # --- simulation state (closure; survives across driver rounds) ----
    state: dict[str, Any] = {
        "version": 0,            # server updates applied so far
        "dispatch_i": 0,         # continuous dispatch-stream index
        "heap": [],              # (arrival_s, dispatch_i, cid, version)
        "buffer": [],            # completed-but-unapplied updates
        "snapshots": {},         # version -> (params, ms) copy
        "refs": {},              # version -> in-flight + buffered count
        "sim_t": 0.0,
        "wall_t0": None,
        "crashed": 0,
        "last_round": None,      # retry/rollback detector
        "logged_rounds": set(),  # ONE fed_cohort record per round
    }

    def _reset_inflight() -> None:
        """Drop every in-flight dispatch and buffered update. Called
        when the driver RETRIES or rolls back a round (round index not
        advancing): the pool's pending work was trained against the
        discarded attempt's params, and re-applying it to the restored
        server would re-poison exactly what the rollback threw away."""
        state["heap"].clear()
        state["buffer"].clear()
        state["snapshots"] = {
            v: s for v, s in state["snapshots"].items()
            if v == state["version"]}
        state["refs"] = {v: 0 for v in state["snapshots"]}

    def _duration(i: int, cid: int, round_idx: int) -> float:
        d = lo if lo == hi else float(
            lo + (hi - lo) * np.random.default_rng((seed, 5, i)).random())
        if faults is not None:
            d += float(faults.delay_s(round_idx, np.asarray([cid]))[0])
        return d

    def _retain(server: ServerState):
        v = state["version"]
        if v not in state["snapshots"]:
            state["snapshots"][v] = copy_tree(
                (server.params, server.model_state))
            state["refs"][v] = 0
        state["refs"][v] += 1
        return v

    def _release(v: int):
        state["refs"][v] -= 1
        if state["refs"][v] == 0 and v != state["version"]:
            del state["snapshots"][v], state["refs"][v]

    def _dispatch(server: ServerState, round_idx: int) -> bool:
        """Sample + dispatch one client; False when it crashed (no
        completion will ever arrive — its sampled slot is simply
        refilled, which is what a real server sees)."""
        i = state["dispatch_i"]
        state["dispatch_i"] += 1
        cid = sampler.client_at(i)
        code = faults_lib.OK
        scale = 1.0
        if faults is not None:
            c, s = faults.codes_for(round_idx, np.asarray([cid]))
            code, scale = int(c[0]), float(s[0])
        if code == faults_lib.CRASH:
            state["crashed"] += 1
            return False
        v = _retain(server)
        heapq.heappush(state["heap"],
                       (state["sim_t"] + _duration(i, cid, round_idx),
                        i, cid, v, code, scale))
        return True

    def _fill(server: ServerState, round_idx: int) -> None:
        misses = 0
        while len(state["heap"]) < concurrency:
            if not _dispatch(server, round_idx):
                misses += 1
                if misses > 1_000 * concurrency:
                    raise RuntimeError(
                        f"could not keep {concurrency} clients in "
                        f"flight after {misses} crashed dispatches — "
                        f"the fault plan crashes (nearly) the whole "
                        f"population")

    def round_fn(server: ServerState, images=None, labels=None,
                 weights=None, rng=None, *, round_idx: int | None = None):
        r = int(server.round) if round_idx is None else int(round_idx)
        n_process = sampler.cohort_size
        if weights is not None:
            mask = np.asarray(jax.device_get(weights), np.float32)
            n_process = max(int((mask > 0).sum()), 1)
        if state["last_round"] is not None and r <= state["last_round"]:
            # the driver is retrying (or rolled back past) this round:
            # everything in flight belongs to the discarded attempt
            _reset_inflight()
        state["last_round"] = r
        # cleared at ENTRY: if this attempt raises mid-round, the
        # driver's fed.client markers must not name the PREVIOUS
        # attempt's completions as this attempt's participants
        round_fn.last_participants = np.zeros((0,), np.int64)
        if state["wall_t0"] is None:
            state["wall_t0"] = time.monotonic()
        params, model_state = server.params, server.model_state
        # the incoming server IS the current version's params: refresh
        # the live snapshot so dispatches reference what the driver
        # actually handed us (a rollback re-anchors here)
        state["snapshots"].setdefault(state["version"], None)
        state["refs"].setdefault(state["version"], 0)
        state["snapshots"][state["version"]] = copy_tree(
            (params, model_state))

        processed_ids: list[int] = []
        stalenesses: list[int] = []
        updates_applied = 0
        dropped_total = 0.0
        clipped_total = 0.0
        crashed_before = state["crashed"]
        wloss = wacc = wtot = 0.0
        _fill(server, r)
        for _ in range(n_process):
            arrival, i, cid, v, code, scale = heapq.heappop(
                state["heap"])
            state["sim_t"] = max(state["sim_t"], arrival)
            if realtime:
                ahead = (state["wall_t0"] + state["sim_t"]
                         - time.monotonic())
                if ahead > 0:
                    time.sleep(ahead)
            snap_p, snap_ms = state["snapshots"][v]
            imgs, lbls = population.shard(cid)
            crng = jax.random.fold_in(jax.random.key(seed), i)
            new_p, new_ms, loss, acc = train_jit(
                snap_p, snap_ms, jnp.asarray(imgs), jnp.asarray(lbls),
                crng)
            s = state["version"] - v
            cw = population.weight(cid)
            state["buffer"].append(
                ((new_p, new_ms), (snap_p, snap_ms), cw,
                 staleness_decay ** s, code, scale))
            stalenesses.append(s)
            m_staleness.observe(float(s))
            processed_ids.append(cid)
            wloss += cw * float(loss)
            wacc += cw * float(acc)
            wtot += cw
            _release(v)
            _fill(server.replace(params=params,
                                 model_state=model_state), r)

            if len(state["buffer"]) >= K:
                buf, state["buffer"] = state["buffer"][:K], \
                    state["buffer"][K:]
                cl = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[b[0] for b in buf])
                snap = jax.tree.map(lambda *xs: jnp.stack(xs),
                                    *[b[1] for b in buf])
                wts = jnp.asarray([b[2] for b in buf], jnp.float32)
                decays = jnp.asarray([b[3] for b in buf], jnp.float32)
                codes = jnp.asarray([b[4] for b in buf], jnp.int32)
                scales = jnp.asarray([b[5] for b in buf], jnp.float32)
                params, model_state, dropped, clipped = apply_jit(
                    params, model_state, cl, snap, wts, decays, codes,
                    scales)
                dropped_total += float(dropped)
                clipped_total += float(clipped)
                state["version"] += 1
                state["snapshots"][state["version"]] = copy_tree(
                    (params, model_state))
                state["refs"].setdefault(state["version"], 0)
                updates_applied += 1
                m_updates.inc()
                # prune the superseded snapshot if nothing references it
                for old_v in [vv for vv, n in state["refs"].items()
                              if n == 0 and vv != state["version"]]:
                    del state["snapshots"][old_v], state["refs"][old_v]

        m_buffer.set(len(state["buffer"]))
        new_server = server.replace(
            round=server.round + 1, params=params,
            model_state=model_state)
        st = np.asarray(stalenesses, np.float64)
        hist = np.bincount(
            np.minimum(st.astype(np.int64), STALENESS_BUCKETS - 1),
            minlength=STALENESS_BUCKETS).tolist() if len(st) else \
            [0] * STALENESS_BUCKETS
        safe = max(wtot, 1e-30)
        metrics = {
            "loss": wloss / safe if wtot > 0 else float("nan"),
            "accuracy": wacc / safe if wtot > 0 else float("nan"),
            "clients_dropped": dropped_total,
            "clients_clipped": clipped_total,
            "cohort": sampler.cohort_size,
            "participants": len(processed_ids),
            "updates": updates_applied,
            "buffer_fill": len(state["buffer"]),
            "staleness_mean": float(st.mean()) if len(st) else 0.0,
            "staleness_max": int(st.max()) if len(st) else 0,
            "crashed": state["crashed"] - crashed_before,
        }
        round_fn.last_participants = np.asarray(processed_ids, np.int64)
        if (logger is not None and r > log_from_round
                and r not in state["logged_rounds"]):
            # one record per ROUND: a driver retry re-runs the round
            # but must not re-log (same contract as the CLI's
            # append-only round records)
            state["logged_rounds"].add(r)
            logger.log(event="fed_cohort", round=r, mode="async",
                       population=population.size,
                       cohort=sampler.cohort_size,
                       participants=len(processed_ids),
                       buffer=K, updates=updates_applied,
                       staleness_mean=metrics["staleness_mean"],
                       staleness_max=metrics["staleness_max"],
                       staleness_hist=hist)
        return new_server, metrics

    round_fn.last_participants = np.zeros((0,), np.int64)
    round_fn.sampler = sampler
    round_fn.population = population
    round_fn.buffer_size = K
    round_fn.staleness_decay = float(staleness_decay)
    return round_fn
