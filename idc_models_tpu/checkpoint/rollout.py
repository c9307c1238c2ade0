"""Zero-downtime weight rollout: stage -> canary -> promote | rollback.

The only way to change serving weights used to be killing the server.
`RolloutController` replaces that with a state machine over one live
`LMServer`:

1. **staging** — the candidate (a params tree, or a sharded-checkpoint
   path restored against the live engine's mesh + partition rules) is
   spot-checked on the engine's ALREADY-COMPILED programs
   (`SlotEngine.spot_check_params`): NaN/inf or magnitude-blown logits
   roll back HERE, before a single client request ever routes onto the
   new weights — the forced-bad-candidate gate.
2. **canary** — a config-identical second server over the candidate
   (`LMServer.canary_clone`; zero new compiles, the process-wide jit
   cache serves both — and when the live server carries a persistent
   `CompileCache`, the clone config carries it too, so a canary in a
   FRESH process spins warm off the serialized executables instead of
   re-running XLA) takes a controlled fraction of submits. Routing
   is TENANT-AFFINE (the PR 14 placement idea): a tenant's whole
   traffic hashes onto one side, so its prefix locality and quota
   accounting never straddle the split; tenant-less requests hash
   per-id to approximate the fraction. Canary requests FINISH on the
   canary — never dropped, never re-run — so the client sees exactly
   one Result per id whichever way the rollout ends.
3. **decide** — after `canary_requests` canary finishes, SLO burn is
   compared: canary error statuses against `error_budget`, canary TTFT
   p95 against live p95 x `ttft_slack` (the same signals a cluster
   replica's health document carries). Healthy -> **promote**:
   `swap_params` on the live engine (in-flight slots keep decoding
   their old window, zero recompiles), canary drained and closed.
   Unhealthy -> **rollback**: canary drained (its outputs passed the
   staging spot-check — they are valid results, not casualties) and
   closed; the live weights were never touched.

Every transition lands a frozen-schema `serve_rollout` jsonl event and
moves the `serve_rollout_stage_code` gauge (serve/metrics.py).

`run_with_rollout` replays a trace through the controller — the
LMServer.run loop with rollout routing — starting the rollout a
configurable fraction into the trace so the live baseline has real
TTFT samples to compare against. It is the acceptance drill (zero
dropped or duplicated requests, NaN candidate auto-rolled-back with no
client-visible error) in one call; tests/test_rollout.py asserts all
of it.
"""

from __future__ import annotations

import hashlib
import time
from pathlib import Path

_STAGES = ("idle", "staging", "canary", "promoted", "rolled_back")


class RolloutError(RuntimeError):
    """Rollout API misuse (wrong stage, re-used controller) — the
    message teaches the correct sequence."""


class RolloutController:
    """Drives ONE candidate-weights rollout over a live LMServer.

    `candidate` is a params pytree, or a sharded-checkpoint directory
    (checkpoint/sharded.py) restored against the live engine's mesh
    and partition rules — a checkpoint saved from an FSDP training
    mesh canaries straight onto a TP serving mesh, re-sharded by rule
    re-resolution, never materialized on one host.

    `canary_fraction` is the traffic share routed onto the candidate
    while the canary stage is open (tenant-affine: whole tenants land
    on one side). `canary_requests` finishes are required before the
    promote/rollback comparison; a trace that ends earlier ROLLS BACK
    — insufficient evidence is not health. `ttft_slack` bounds canary
    TTFT p95 at slack x live p95; `error_budget` is the tolerated
    canary error-status fraction (default 0: any canary error rolls
    back)."""

    def __init__(self, server, candidate, *,
                 canary_fraction: float = 0.25, canary_requests: int = 4,
                 ttft_slack: float = 2.0, error_budget: float = 0.0,
                 logger=None):
        if not 0.0 < canary_fraction <= 1.0:
            raise ValueError(
                f"canary_fraction must be in (0, 1], got "
                f"{canary_fraction!r} — a zero fraction starves the "
                f"canary of evidence forever, and promoting without "
                f"evidence is not a rollout")
        if canary_requests < 1:
            raise ValueError(f"canary_requests must be >= 1, got "
                             f"{canary_requests!r}")
        self.live = server
        self.canary = None
        self.canary_fraction = float(canary_fraction)
        self.canary_requests = int(canary_requests)
        self.ttft_slack = float(ttft_slack)
        self.error_budget = float(error_budget)
        self.stage = "idle"
        self.reason: str | None = None
        self._canary_done: list = []
        if isinstance(candidate, (str, Path)):
            from idc_models_tpu.checkpoint.sharded import restore_sharded

            engine = server.engine
            rules = engine._partition_rules
            candidate = restore_sharded(
                candidate,
                mesh=engine._cfg.mesh if rules is not None else None,
                rules=rules, logger=logger)
        self.candidate = candidate

    @property
    def canary_finishes(self) -> int:
        """Canary results banked toward the verdict so far."""
        return len(self._canary_done)

    # -- state machine ---------------------------------------------------

    def _transition(self, stage: str, *, outcome=None,
                    reason=None) -> None:
        self.stage = stage
        self.reason = reason
        self.live.metrics.on_rollout(
            stage=stage, outcome=outcome,
            canary_requests=len(self._canary_done), reason=reason)

    def start(self) -> bool:
        """Stage the candidate: spot-check it on the live engine's
        compiled programs, then open the canary. False = the candidate
        failed staging and the rollout is already rolled_back — the
        live server never stopped serving and no client request ever
        touched the bad weights."""
        if self.stage != "idle":
            raise RolloutError(
                f"start() in stage {self.stage!r} — a controller "
                f"drives ONE rollout; build a fresh one for the next "
                f"candidate")
        self._transition("staging")
        engine = self.live.engine
        if engine.paged and engine._pending is not None:
            # the paged spot-check replays through the pool caches,
            # which an in-flight window owns — collect it first
            self.live.quiesce()
        check = engine.spot_check_params(self.candidate)
        if not check["ok"]:
            detail = {1: "non-finite logits",
                      2: (f"magnitude-blown logits "
                          f"(max |x| = {check['max_abs']:.3g})")}
            self._transition(
                "rolled_back", outcome="rolled_back",
                reason=f"staging spot-check failed: "
                       f"{detail[check['code']]}")
            return False
        self.canary = self.live.canary_clone(self.candidate)
        self._transition("canary")
        return True

    def routes_to_canary(self, request) -> bool:
        """The tenant-affine split: deterministic in the tenant name
        (or the request id when tenant-less), so a tenant's traffic
        never straddles the two prefix caches / quota ledgers."""
        if self.canary is None or self.stage != "canary":
            return False
        key = (request.tenant if request.tenant is not None
               else request.id)
        h = int(hashlib.sha256(key.encode()).hexdigest()[:8], 16)
        return h / 0x100000000 < self.canary_fraction

    def _target(self, request):
        return self.canary if self.routes_to_canary(request) else self.live

    def submit(self, request) -> bool:
        """Route one submit: the canary fraction onto the candidate
        while the canary stage is open, everything else — and
        everything before staging or after the decision — onto the
        live server. Same False-on-backpressure contract as
        LMServer.submit."""
        return self._target(request).submit(request)

    def step(self) -> list:
        """One cycle of both sides, merged; runs the promote/rollback
        decision as soon as the canary has `canary_requests`
        finishes."""
        out = self.live.step()
        if self.canary is not None and self.stage == "canary":
            done = self.canary.step()
            self._canary_done.extend(done)
            out.extend(done)
            if len(self._canary_done) >= self.canary_requests:
                self._decide()
        return out

    def poll(self, rid: str):
        r = self.live.poll(rid)
        if r is None and self.canary is not None:
            r = self.canary.poll(rid)
        return r

    def idle(self) -> bool:
        return (self.live.scheduler.idle()
                and (self.canary is None
                     or self.canary.scheduler.idle()))

    def finish(self) -> str:
        """End-of-trace settlement: a canary still open decides NOW.
        With `canary_requests` finishes banked the normal comparison
        runs; with fewer, the rollout ROLLS BACK — a trace that ended
        before the canary earned its evidence does not get promoted on
        vibes. Returns the terminal stage."""
        if self.stage == "canary":
            if len(self._canary_done) >= self.canary_requests:
                self._decide()
            else:
                self._rollback(
                    f"trace ended with {len(self._canary_done)} canary "
                    f"finishes < canary_requests="
                    f"{self.canary_requests} — not enough evidence to "
                    f"promote")
        return self.stage

    def results(self) -> list:
        """Every finished Result from both sides — exactly one per
        request id (the router sends each id to exactly one side)."""
        merged = {r.id: r for r in self.live.results()}
        if self.canary is not None:
            for r in self.canary.results():
                merged.setdefault(r.id, r)
        return list(merged.values())

    # -- decision --------------------------------------------------------

    def _decide(self) -> None:
        bad = [r for r in self._canary_done
               if r.status not in ("ok", "timeout")]
        if len(bad) > self.error_budget * len(self._canary_done):
            first = f"{bad[0].status} {bad[0].error or ''}".strip()
            self._rollback(
                f"canary error burn: {len(bad)}/"
                f"{len(self._canary_done)} finishes errored (budget "
                f"{self.error_budget:.0%}); first: {first}")
            return
        lp95 = self.live.summary().get("serve_ttft_ms_p95")
        cp95 = self.canary.summary().get("serve_ttft_ms_p95")
        if (lp95 is not None and cp95 is not None and lp95 > 0
                and cp95 > self.ttft_slack * lp95):
            self._rollback(
                f"canary SLO burn: TTFT p95 {cp95:.1f} ms > "
                f"{self.ttft_slack:.1f}x live {lp95:.1f} ms")
            return
        self._promote()

    def _drain_canary(self) -> None:
        # finish every in-flight canary request ON the canary — its
        # weights passed the spot-check, so the outputs are valid
        # results, not casualties. Zero drops on either verdict.
        if self.canary is None:
            return
        while not self.canary.scheduler.idle():
            self._canary_done.extend(self.canary.step())
        self.canary.close()

    def _promote(self) -> None:
        self._drain_canary()
        self.live.swap_params(self.candidate)
        self._transition("promoted", outcome="promoted")

    def _rollback(self, reason: str) -> None:
        self._drain_canary()
        self._transition("rolled_back", outcome="rolled_back",
                         reason=reason)


def run_with_rollout(server, trace, candidate, *,
                     start_after: float = 0.25, realtime: bool = False,
                     on_full: str = "block", **controller_kw):
    """Replay `[(arrival_s, Request), ...]` while rolling `candidate`
    out mid-trace — LMServer.run with the controller in the submit
    path. The rollout starts once `start_after` of the trace has been
    offered (the live baseline needs real TTFT samples to judge the
    canary against); the trace then drains through promote or rollback
    either way. Returns `(results, controller)`; results carry exactly
    one Result per trace id — zero dropped, zero duplicated."""
    from idc_models_tpu.serve.api import Result

    if on_full not in ("block", "reject"):
        raise ValueError(f"on_full must be 'block' or 'reject', got "
                         f"{on_full!r}")
    if not 0.0 <= start_after < 1.0:
        raise ValueError(f"start_after must be in [0, 1), got "
                         f"{start_after!r} — starting at/after the end "
                         f"of the trace means the canary never sees a "
                         f"request")
    ctl = RolloutController(server, candidate, **controller_kw)
    trace = sorted(trace, key=lambda tr: tr[0])
    start_idx = int(len(trace) * start_after)
    clock = server.scheduler.clock
    t0 = clock()
    out, i = [], 0
    while i < len(trace) or not ctl.idle():
        now = clock() - t0
        while i < len(trace) and (not realtime or trace[i][0] <= now):
            # open the rollout the moment the trace position crosses
            # start_after — INSIDE the offer loop, because a burst
            # trace (all arrivals at 0) submits everything in one tick
            if ctl.stage == "idle" and i >= start_idx:
                ctl.start()
            req = trace[i][1]
            target = ctl._target(req)
            # same block-mode etiquette as LMServer.run: don't OFFER a
            # request the target queue cannot take (a refused submit
            # counts as a rejection in its metrics)
            shedding = (target.brownout is not None
                        and target.brownout.shedding)
            if (on_full == "block" and not shedding
                    and len(target.scheduler.queue)
                    >= target.scheduler.queue.max_depth):
                break                   # blocked: re-offer next tick
            if ctl.submit(req):
                i += 1
                continue
            shed = ctl.poll(req.id)
            if shed is not None and shed.status == "shed":
                out.append(shed)
                i += 1
            elif on_full == "reject":
                r = Result(id=req.id, tokens=[], status="rejected")
                target._results[r.id] = r
                out.append(r)
                i += 1
            else:
                break                   # blocked: re-offer next tick
        if realtime and ctl.idle() and i < len(trace):
            time.sleep(min(max(trace[i][0] - (clock() - t0), 0.0),
                           0.005))
            continue
        out.extend(ctl.step())
    ctl.finish()
    # canary requests that finished inside the promote/rollback drain
    # never came back through step() — reconcile so the return carries
    # exactly one Result per trace id
    have = {r.id for r in out}
    out.extend(r for r in ctl.results() if r.id not in have)
    return out, ctl
