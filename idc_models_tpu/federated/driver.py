"""Self-healing multi-round federated driver.

`make_fedavg_round` hardens ONE round (non-finite detection, robust
aggregation); this module hardens the RUN: R rounds with per-round wall
budget, bounded retry with a reseeded client subset on a failed round,
divergence detection with automatic rollback to the last good server
state, periodic atomic checkpoints, and per-round health events through
`observe.JsonlLogger` — the loop the reference writes by hand with zero
failure handling (fed_model.py:225-233, SURVEY.md §5).

Failure semantics, per round:

- **timeout** — a round whose wall-clock (dispatch through the blocking
  metrics fetch) exceeds `timeout_s` is treated as straggled: its
  result is DISCARDED and the round is retried with a reseeded rng and
  a freshly-drawn client subset (`retry_subset_fraction` of the
  positive-weight clients). A jitted round cannot be preempted
  mid-flight, so the budget is enforced at the round boundary — the
  right granularity for a synchronous-rounds protocol.
- **diverged** — the candidate server params contain a non-finite
  value, the round's training loss is non-finite (e.g. every client was
  dropped), or the loss spiked past `loss_spike_ratio` x the last
  healthy round's loss. The candidate is discarded — rollback to the
  last good state is implicit, since the good state was never
  overwritten — and the round retries reseeded.
- **error** — the round function raised; retried like the others, with
  the final exception chained into `RoundFailure`.

After `max_attempts` failures of the SAME round the driver raises
`RoundFailure`: a round that cannot be healed by reseeding is a
systemic problem (bad data, broken aggregator, hostile majority) that
silent retries would only hide.

Determinism: attempt a of round r uses
``fold_in(fold_in(key(seed), r), a)`` and a subset drawn from
``default_rng((seed, r, a))`` — resumed or replayed runs reproduce the
exact stream, and a fault plan (faults.py) replays bit-identically
through the driver too.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from idc_models_tpu.federated.fedavg import ServerState, copy_tree
from idc_models_tpu.observe import metrics_registry as mreg
from idc_models_tpu.observe import profile as prof
from idc_models_tpu.observe import trace


class RoundFailure(RuntimeError):
    """A federated round kept failing after the configured retries."""


@dataclasses.dataclass(frozen=True)
class DriverConfig:
    """Knobs for `run_rounds`. `timeout_s=None` disables the wall
    budget; `loss_spike_ratio=None` disables spike detection (non-finite
    divergence detection is always on)."""

    rounds: int
    timeout_s: float | None = None
    # the driver's chronologically FIRST attempt pays every XLA compile
    # in its wall time (minutes for a big model — nothing to do with
    # straggling); exempting it keeps timeout_s meaningful as a
    # steady-state round budget. Set False to budget the compile too.
    timeout_exempt_first: bool = True
    max_attempts: int = 3
    loss_spike_ratio: float | None = 10.0
    retry_subset_fraction: float = 0.7
    checkpoint_path: str | os.PathLike | None = None
    checkpoint_every: int = 10

    def __post_init__(self):
        if self.rounds < 1:
            raise ValueError(f"need rounds >= 1, got {self.rounds}")
        if self.max_attempts < 1:
            raise ValueError(f"need max_attempts >= 1, got "
                             f"{self.max_attempts}")
        if not 0.0 < self.retry_subset_fraction <= 1.0:
            raise ValueError(f"retry_subset_fraction must be in (0, 1], "
                             f"got {self.retry_subset_fraction}")
        if self.loss_spike_ratio is not None and self.loss_spike_ratio <= 1:
            raise ValueError(f"loss_spike_ratio must be > 1, got "
                             f"{self.loss_spike_ratio}")


@dataclasses.dataclass
class DriverResult:
    server: ServerState          # the last GOOD server state
    history: list[dict]          # one entry per completed round
    events: list[dict]           # one entry per attempt (health log)


def reseeded_subset(weights, seed: int, round_idx: int, attempt: int,
                    fraction: float) -> np.ndarray:
    """A deterministic retry population: keep `fraction` of the
    positive-weight clients (at least 1), drawn from
    default_rng((seed, round, attempt)) — a straggling or poisoned
    participant from the failed attempt has a fresh chance of being
    excluded, without the driver having to know who it was."""
    w = np.asarray(jax.device_get(weights), np.float32).copy()
    pos = np.flatnonzero(w > 0)
    if len(pos) == 0:
        return w
    keep = max(1, int(round(fraction * len(pos))))
    chosen = np.random.default_rng((seed, round_idx, attempt)).choice(
        pos, size=keep, replace=False)
    out = np.zeros_like(w)
    out[chosen] = w[chosen]
    return out


def run_rounds(round_fn, server: ServerState, images, labels, weights, *,
               config: DriverConfig, seed: int = 0, eval_fn=None,
               on_round=None, logger=None, clock=time.monotonic,
               verbose: bool = False, log_from_round: int = -1,
               log_round_records: bool = True, fault_plan=None,
               slo=None, participant_ids_fn=None) -> DriverResult:
    """Run `config.rounds` federated rounds with self-healing.

    `round_fn` is a `make_fedavg_round` product (or anything with the
    same signature); `eval_fn(server) -> metrics` is an optional
    per-round evaluation folded into history/logging; `on_round(entry)`
    is called after each HEALTHY round with its history entry (live
    progress printing without the driver owning a format). Starts at
    `int(server.round)`, so a restored checkpoint resumes where it left
    off. `log_from_round` suppresses logger records for rounds <= it
    (resume replay must not double-append to an append-only jsonl);
    `log_round_records=False` leaves the per-round ``round`` records to
    the caller (e.g. a CLI preserving its historical field names) while
    the driver still emits ``round_health``.

    `fault_plan` (faults.FaultPlan, usually the same plan the round_fn
    injects) labels the per-client ``fed.client`` trace spans with each
    participant's fault outcome for the round. When a tracer is armed,
    every attempt's ``fed.round`` span gains one nested ``fed.client``
    marker per participating client (attrs: client, weight, fault —
    markers, not timings: clients run fused inside one jitted dispatch,
    so no per-client host interval exists to measure).

    `slo` (observe.slo.SLOEngine) receives ``round_seconds`` (latency,
    wall seconds per attempt) and ``round_failure_rate`` (rate, bad =
    attempt status != ok) for whichever of the two it declares, with a
    burn-rate evaluation after every attempt — `slo_alert` jsonl events
    go through the engine's own logger.

    `participant_ids_fn(round_idx) -> ids` overrides which client ids
    the ``fed.client`` markers name: population-scale rounds
    (federated/population.py, async_fedavg.py) participate by VIRTUAL
    client id, not by position in a materialized weight vector — the
    hook is called after the attempt completes, so an async round can
    report the completions it actually processed. A fault plan exposing
    ``codes_for(round, ids)`` (faults.PopulationFaultPlan) is queried
    per-id; the materialized-plan ``codes(round)`` path is unchanged.
    Returns the last good server state + per-round history + per-attempt
    health events; raises `RoundFailure` when a round exhausts its
    attempts (the last good state is the exception's `.server`).
    """
    import inspect

    # a fault-injecting round_fn takes round_idx= to skip its own
    # blocking int(server.round) fetch (a host round-trip per round)
    # — the driver already knows r, so thread it through
    takes_round_idx = False
    try:
        takes_round_idx = ("round_idx"
                           in inspect.signature(round_fn).parameters)
    except (TypeError, ValueError):
        pass
    finite_fn = jax.jit(lambda t: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(l)) for l in jax.tree.leaves(t)
         if jnp.issubdtype(l.dtype, jnp.inexact)] or [jnp.asarray(True)])))

    good = server
    ref_loss = None
    first_attempt_done = False
    history: list[dict] = []
    events: list[dict] = []
    start = int(server.round)
    if start >= config.rounds:
        # a fully-trained restore is a no-op run, not an error (the
        # resume path hits this when --rounds already completed)
        return DriverResult(server=server, history=[], events=[])
    if prof.accounting_enabled():
        # opt-in program accounting (observe/profile.py): register the
        # round program's cost/memory report under "fed.round" before
        # the loop (lowering neither executes nor donates, so `good`
        # is safe to pass); best-effort — a host-side wrapper round_fn
        # warns and skips
        kw = {"round_idx": start} if takes_round_idx else {}
        prof.register_jit("fed.round", round_fn, good, images, labels,
                          weights, jax.random.key(seed), **kw)

    def health(record):
        events.append(record)
        if logger is not None and record["round"] > log_from_round:
            logger.log(event="round_health", **record)

    # process-wide registry instruments (idempotent — resumed runs and
    # multiple drivers share them); the jsonl `round`/`round_health`
    # record schemas above are the back-compat contract and unchanged
    m_attempts = mreg.REGISTRY.counter(
        "fed_round_attempts_total", "federated round attempts by "
        "outcome", labels=("status",))
    m_seconds = mreg.REGISTRY.histogram(
        "fed_round_seconds", "wall seconds per round attempt")
    m_loss = mreg.REGISTRY.gauge(
        "fed_train_loss", "last healthy round's training loss")

    last_error: Exception | None = None
    for r in range(start, config.rounds):
        for attempt in range(config.max_attempts):
            rng = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(seed), r), attempt)
            w = (weights if attempt == 0 else reseeded_subset(
                weights, seed, r, attempt, config.retry_subset_fraction))
            # fresh buffers (copy_tree): the anchor survives round_fn's
            # donation of its input state — rollback is keeping `good`
            anchor = copy_tree(good)
            t0 = clock()
            status, tm_host = "ok", {}
            candidate = None
            # the with-block (not paired __enter__/__exit__ calls)
            # guarantees the span closes even on exits the except below
            # does not catch (KeyboardInterrupt, an error materializing
            # the record) — a leaked open span would corrupt the
            # parenting of every later span on this thread
            with trace.span("fed.round", round=r,
                            attempt=attempt) as att_span:
                try:
                    kw = {"round_idx": r} if takes_round_idx else {}
                    candidate, tm = round_fn(anchor, images, labels, w,
                                             rng, **kw)
                    # ONE blocking fetch: materializes the round's
                    # metrics AND fences the wall-clock window (the
                    # dispatch alone returns before the device
                    # finishes) — bracketed as device.sync so a
                    # DeviceTimeline splits fed.round into device-wait
                    # vs host gap
                    with trace.span("device.sync"):
                        tm_host = {k: float(v)
                                   for k, v in jax.device_get(tm).items()}
                    params_ok = bool(finite_fn(candidate.params)) and bool(
                        finite_fn(candidate.model_state))
                    if not params_ok or not np.isfinite(
                            tm_host.get("loss", np.nan)):
                        status = "diverged"
                    elif (config.loss_spike_ratio is not None
                          and ref_loss is not None
                          and tm_host["loss"]
                          > config.loss_spike_ratio * ref_loss):
                        status = "diverged"
                except Exception as e:  # noqa: BLE001 — chained into RoundFailure
                    last_error = e
                    status = "error"
                    tm_host = {"error": f"{type(e).__name__}: {e}"}
                elapsed = clock() - t0
                timeout_exempt = (config.timeout_exempt_first
                                  and not first_attempt_done)
                first_attempt_done = True
                if (status == "ok" and config.timeout_s is not None
                        and not timeout_exempt
                        and elapsed > config.timeout_s):
                    status = "timeout"
                w_host = np.asarray(jax.device_get(w))
                record = {"round": r, "attempt": attempt,
                          "status": status,
                          "seconds": round(elapsed, 4),
                          "participants": int((w_host > 0).sum()),
                          **{k: v for k, v in tm_host.items()
                             if k in ("loss", "accuracy",
                                      "clients_dropped",
                                      "clients_clipped",
                                      "clients_trimmed",
                                      "trim_degenerate", "error")}}
                att_span.set(status=status,
                             participants=record["participants"])
                if trace.get_tracer() is not None:
                    ids = (participant_ids_fn(r)
                           if participant_ids_fn is not None else None)
                    _client_spans(att_span, w_host, r, attempt,
                                  fault_plan, ids=ids)
            m_attempts.inc(status=status)
            m_seconds.observe(elapsed)
            health(record)
            if slo is not None:
                if slo.has("round_seconds"):
                    slo.observe("round_seconds", elapsed)
                if slo.has("round_failure_rate"):
                    slo.record("round_failure_rate", ok=status == "ok")
                slo.evaluate()
            if status == "ok":
                good = candidate
                ref_loss = tm_host["loss"]
                m_loss.set(ref_loss)
                entry = {"round": r, "attempts": attempt + 1, **{
                    k: v for k, v in tm_host.items()}}
                if eval_fn is not None:
                    entry.update(eval_fn(good))
                history.append(entry)
                if (log_round_records and logger is not None
                        and r > log_from_round):
                    logger.log(event="round", **entry)
                if on_round is not None:
                    on_round(entry)
                break
            if verbose:
                import sys

                print(f"[idc_models_tpu] round {r} attempt {attempt} "
                      f"{status} after {elapsed:.2f}s — "
                      f"{'rolling back and ' if candidate is not None else ''}"
                      f"retrying with a reseeded client subset",
                      file=sys.stderr)
        else:
            err = RoundFailure(
                f"round {r} failed {config.max_attempts} attempt(s) "
                f"(last status: {events[-1]['status']}); last good "
                f"server state is at round {int(good.round)}")
            err.server = good           # the rollback anchor, recoverable
            raise err from last_error
        if (config.checkpoint_path is not None
                and (r + 1) % max(config.checkpoint_every, 1) == 0):
            _save(config.checkpoint_path, good)
    if (config.checkpoint_path is not None
            and int(good.round) % max(config.checkpoint_every, 1) != 0):
        _save(config.checkpoint_path, good)
    return DriverResult(server=good, history=history, events=events)


def _client_spans(att_span, weights, round_idx: int, attempt: int,
                  fault_plan, ids=None) -> None:
    """One `fed.client` marker span per participating client, nested
    under the attempt's fed.round span, carrying the client's fault
    outcome for the round (from the plan's pure (plan, round) function
    — the same codes the jitted round program branched on). Markers,
    not timings: the clients execute fused inside one dispatch.
    `weights` is the attempt's already host-fetched array; `ids`, when
    given, are VIRTUAL client ids from a population-scale round (the
    weight attr is then omitted — the positional weight vector does
    not describe them)."""
    from idc_models_tpu import faults as faults_lib

    w = np.asarray(weights)
    by_position = ids is None
    ids = np.flatnonzero(w > 0) if by_position else np.asarray(ids)
    if not by_position and len(ids) == len(w):
        # sync population rounds: `ids` are the cohort's virtual ids,
        # position-aligned with the [cohort] participation mask the
        # driver's reseeded retry zeroes — a masked-out client did not
        # participate in this attempt and gets no marker
        ids = ids[w > 0]
    codes = scales = None
    if fault_plan is not None:
        if hasattr(fault_plan, "codes_for"):
            codes, scales = fault_plan.codes_for(round_idx, ids)
        else:
            codes, scales = fault_plan.codes(round_idx)
    for i, cid in enumerate(ids):
        cid = int(cid)
        attrs = {"round": round_idx, "attempt": attempt, "client": cid}
        if by_position:
            attrs["weight"] = float(w[cid])
        # population plans align codes to the ids array; materialized
        # plans index by client position
        ci = i if (fault_plan is not None
                   and hasattr(fault_plan, "codes_for")) else cid
        if codes is not None and ci < len(codes):
            code = int(codes[ci])
            attrs["fault"] = faults_lib.kind_of(code)
            if code in (faults_lib.SCALE, faults_lib.SIGN_FLIP):
                attrs["fault_scale"] = float(scales[ci])
            elif code == faults_lib.STRAGGLER:
                attrs["staleness"] = fault_plan.staleness(round_idx)
        trace.point("fed.client", parent=att_span.span_id, **attrs)


def _save(path, server: ServerState) -> None:
    from idc_models_tpu.train.checkpoint import save_checkpoint

    save_checkpoint(path, jax.device_get(server))
