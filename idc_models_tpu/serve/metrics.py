"""Serving observability: TTFT, per-token latency, throughput, queue
depth, slot occupancy.

Counters accumulate in memory and stream — when a logger is given —
through the same `observe.JsonlLogger` jsonl record shape every other
loop in the framework writes, so a serving run's timeline sits next to
its training runs' in one machine-comparable format. `summary()` is the
serving record (`serve_*` fields) the CLI epilogue prints and the
benchmark's per-layer readers take their counts from.
"""

from __future__ import annotations

import time

import numpy as np

from idc_models_tpu.observe import metrics_registry as mreg


def _pct(values, q) -> float | None:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def _load_skew(load) -> float | None:
    """Busiest held expert's assignments over the mean held expert's,
    mean over the expert layers (`load` [layers, held experts]); None
    until every layer has had a token."""
    mean = load.mean(axis=1)
    if not (mean > 0).all():
        return None
    return float((load.max(axis=1) / mean).mean())


class ServingMetrics:
    """Per-request and per-cycle serving counters.

    Hooks are called by the scheduler: `on_submit`/`on_reject` at the
    queue, `on_first_token` when a request's first decode window lands
    (TTFT), `on_finish` with the whole request's timing, and `on_cycle`
    once per engine cycle with queue depth / slot occupancy / tokens
    emitted. All times are seconds on the caller's clock.

    Every hook ALSO updates the process-wide metrics registry
    (observe/metrics_registry.py: serve_* counters/gauges/histograms)
    — additive instrumentation only; the jsonl records this class has
    always written keep their exact keys (gated by test).

    `slo` is an optional `observe.slo.SLOEngine`: the hooks feed it the
    declared subset of ``ttft`` / ``queue_wait`` (latency samples,
    seconds) and ``error_rate`` (bad = rejected, or a finish reason of
    error/timeout/deadline), and `on_cycle` runs one burn-rate
    evaluation per scheduler cycle.
    """

    def __init__(self, logger=None, prefix_cache=None, registry=None,
                 slo=None, tenancy=None):
        self.logger = logger
        self.slo = slo
        # tenancy (serve/tenancy.py, ISSUE 14): when armed, the hooks
        # also maintain tenant-labeled series (every registration
        # carries the tenant label — enforced by the static scan), a
        # per-tenant rollup under summary()["serve_tenants"], and the
        # NEW serve_tenant_* jsonl events (frozen from day one; every
        # historical event schema stays byte-identical). TTFT samples
        # feed the tenant's own ttft:<name> SLO objective.
        self.tenancy = tenancy
        # when a PrefixCache is attached its serve_prefix_* counters
        # roll into summary() next to the serving fields
        self.prefix_cache = prefix_cache
        reg = registry if registry is not None else mreg.REGISTRY
        # submissions and terminal outcomes are SEPARATE counters: a
        # single status-labeled counter would count every completed
        # request twice (once as "submitted", once at finish), doubling
        # any sum(rate(...)) a Prometheus consumer runs over the labels
        self._m_submitted = reg.counter(
            "serve_requests_submitted_total", "requests submitted")
        self._m_requests = reg.counter(
            "serve_requests_total",
            "requests by terminal outcome", labels=("status",))
        self._m_tokens = reg.counter(
            "serve_tokens_emitted_total", "decode tokens emitted")
        self._m_ttft = reg.histogram(
            "serve_ttft_seconds", "submit -> first token")
        # inter-token latency (ISSUE 20): the request's mean seconds
        # per decoded token after the first — TTFT covers the prefill
        # side of the latency SLO, this histogram covers the decode
        # side (its p95 is what the fleet view alerts on)
        self._m_itl = reg.histogram(
            "serve_itl_seconds",
            "per-request mean inter-token latency (decode seconds "
            "per token after the first)")
        self._m_queue = reg.gauge(
            "serve_queue_depth", "admission queue depth (last cycle)")
        self._m_occ = reg.gauge(
            "serve_slot_occupancy",
            "fraction of decode slots running (last cycle)")
        self._m_compiles = reg.counter(
            "serve_compiles_total",
            "XLA compiles observed as jit cache-size growth after the "
            "first cycle")
        # the /healthz freshness anchor (observe/exporter.py): stamped
        # with time.monotonic() once per scheduler cycle so a scrape
        # can tell a healthy-but-idle server from a wedged one
        self._m_last_tick = reg.gauge(
            "serve_last_tick_monotonic_seconds",
            "time.monotonic() stamp of the last scheduler cycle — "
            "/healthz reports now minus this as last_tick_age_s")
        # resilience instruments (ISSUE 8): quarantines, retries, shed
        # submits, brownout clamps, and injected drill faults
        self._m_slot_faults = reg.counter(
            "serve_slot_faults_total",
            "slots quarantined by the per-cycle health checks, by "
            "fault kind", labels=("kind",))
        self._m_retries = reg.counter(
            "serve_retries_total",
            "quarantined requests re-admitted after backoff")
        self._m_shed = reg.counter(
            "serve_shed_total",
            "submits refused by the brownout controller's shed stage")
        self._m_clamped = reg.counter(
            "serve_clamped_total",
            "admissions whose max_new_tokens the brownout clamp "
            "shortened")
        self._m_faults_injected = reg.counter(
            "serve_faults_injected_total",
            "declarative serve faults fired by an armed ServeFaultPlan,"
            " by kind", labels=("kind",))
        # speculative decoding (ISSUE 10): decode dispatches by kind
        # (window vs verify) and the drafted/accepted token ledger —
        # acceptance rate and tokens-per-dispatch derive from these
        self._m_dispatches = reg.counter(
            "serve_decode_dispatches_total",
            "decode dispatches by kind: 'window' (fused one-token-per-"
            "step scan) or 'verify' (speculative draft-and-verify)",
            labels=("kind",))
        self._m_spec_drafted = reg.counter(
            "serve_spec_drafted_tokens_total",
            "draft tokens submitted to speculative verify dispatches")
        self._m_spec_accepted = reg.counter(
            "serve_spec_accepted_tokens_total",
            "draft tokens the verify accepted (emitted as-is)")
        # paged KV (ISSUE 11): pool occupancy gauges — the live
        # tokens-resident-per-HBM-byte capacity signals — plus the
        # page-exhaustion backpressure counter
        self._m_pages_used = reg.gauge(
            "serve_kv_pages_used",
            "KV pool pages currently allocated (slots + prefix-cache "
            "snapshots), last cycle")
        self._m_pages_total = reg.gauge(
            "serve_kv_pages_total",
            "total KV pool pages the paged engine was built with")
        self._m_pages_cached = reg.gauge(
            "serve_kv_pages_cached",
            "distinct KV pool pages pinned by prefix-cache snapshots "
            "(a subset of serve_kv_pages_used; shared zero-copy with "
            "the slots that wrote them), last cycle")
        self._m_page_exhausted = reg.counter(
            "serve_page_exhaustions_total",
            "cycles the paged engine refused work for lack of free "
            "pages (admission gate or mid-decode growth)")
        # hot weight rollout (ROADMAP 4): terminal outcomes plus the
        # live stage gauge an operator watches during a canary
        self._m_rollouts = reg.counter(
            "serve_rollouts_total",
            "weight rollouts by terminal outcome: 'promoted' (canary "
            "healthy, live weights swapped) or 'rolled_back' (staging "
            "spot-check or canary SLO comparison failed)",
            labels=("outcome",))
        self._m_rollout_stage = reg.gauge(
            "serve_rollout_stage_code",
            "current rollout stage: 0 idle, 1 staging, 2 canary, "
            "3 promoted, 4 rolled_back")
        # tenant-labeled instruments, registered only when tenancy is
        # armed so tenant-less servers' registries stay byte-identical
        # (the /metrics exposition equality gates)
        if tenancy is not None:
            self._m_t_requests = reg.counter(
                "serve_tenant_requests_total",
                "requests by tenant and terminal outcome",
                labels=("tenant", "status"))
            self._m_t_tokens = reg.counter(
                "serve_tenant_tokens_emitted_total",
                "decode tokens emitted per tenant", labels=("tenant",))
            self._m_t_ttft = reg.histogram(
                "serve_tenant_ttft_seconds",
                "submit -> first token per tenant", labels=("tenant",))
            self._m_t_queue = reg.gauge(
                "serve_tenant_queue_depth",
                "admission-queue entries each tenant holds (last "
                "cycle)", labels=("tenant",))
            self._m_t_slots = reg.gauge(
                "serve_tenant_slots_used",
                "decode slots (running + prefilling) each tenant "
                "holds (last cycle)", labels=("tenant",))
            self._m_t_pages = reg.gauge(
                "serve_tenant_kv_pages_used",
                "KV pool pages each tenant's admissions have reserved "
                "(last cycle; paged engines)", labels=("tenant",))
            self._m_t_shed = reg.counter(
                "serve_tenant_shed_total",
                "submits refused by the tenant's own brownout shed "
                "stage", labels=("tenant",))
            self._m_t_quota = reg.counter(
                "serve_tenant_quota_rejections_total",
                "submits refused by a per-tenant quota, by quota kind",
                labels=("tenant", "kind"))
        # per-tenant rollup (all keyed by tenant name; empty dicts
        # when tenancy is off)
        self.tenant_ttft_s: dict[str, list] = {}
        self.tenant_finished: dict[str, int] = {}
        self.tenant_tokens: dict[str, int] = {}
        self.tenant_shed: dict[str, int] = {}
        self.tenant_quota_rejections: dict[str, int] = {}
        self._jit_cache_seen: int | None = None
        self.compiles_observed = 0
        # compile-cache rollup (serve/compile_cache.py): gauges are
        # registered lazily by on_compile_cache, so a cache-less
        # server's /metrics exposition stays byte-identical (the
        # equality gates)
        self._reg = reg
        self.compile_cache_summary: dict | None = None
        self._g_cc: dict | None = None
        # expert-layer rollup (on_moe; instruments registered on its
        # first call) and the cache rows by layer kind (on_kv_layout)
        self._moe: dict | None = None
        self.moe_assigned = 0
        self.moe_touched = 0
        self.moe_layer_steps = 0
        # indexer layers' selected over visible positions, and the rows
        # their decode fold ran for over the live ones (on_dsa)
        self._m_dsa_share = None
        self._m_dsa_folded = None
        self.dsa_share_sum = 0.0
        self.dsa_rows = 0
        self.dsa_fold_rows = 0
        # decode attention's rows read of the rows there (on_attn_rows)
        self._m_attn_share = None
        self.attn_rows_read = 0
        self.attn_rows_whole = 0
        self.kv_bytes_by_kind: dict = {}
        # rollout rollup: stage trail + terminal outcomes
        self.rollout_stage: str | None = None
        self.rollout_outcomes: list[str] = []
        # paged-KV rollup (all zero/None on contiguous engines)
        self.kv_pages_total: int | None = None
        self.kv_pages_used_peak = 0
        self.kv_resident_tokens_peak = 0
        self.kv_resident_bytes_peak = 0
        self.kv_tokens_per_byte_peak: float | None = None
        self.page_exhaustions = 0
        # speculative rollup: dispatch counts by kind plus the draft
        # ledger (slot_verifies = per-slot participations, the
        # denominator of the per-slot tokens-per-dispatch figure)
        self.window_dispatches = 0
        self.verify_dispatches = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_slot_verifies = 0
        # drafting-pass wall time (host scans + the learned drafter's
        # batched dispatch) — the draft-overhead numerator
        self.propose_s = 0.0
        self.propose_calls = 0
        self.submitted = 0
        self.rejected = 0
        self.timed_out = 0
        self.slot_faults = 0
        self.retries = 0
        self.shed = 0
        self.clamped = 0
        self.faults_injected = 0
        self.finished = 0
        self.tokens_out = 0
        self.cycles = 0
        self.ttft_s: list[float] = []
        self.queue_wait_s: list[float] = []  # submit -> slot claimed
        self.prefill_s: list[float] = []     # slot claimed -> first token
        self.token_s: list[float] = []      # per-token decode latency
        self.queue_depths: list[int] = []
        self.occupancies: list[float] = []
        self.cycle_tokens: list[int] = []
        self.cycle_prefill_s: list[float] = []  # per-cycle decode stall
        self._t_first: float | None = None
        self._t_last: float | None = None

    # -- request lifecycle ----------------------------------------------

    def on_submit(self, rid, t: float, *, tenant=None) -> None:
        self.submitted += 1
        if self._t_first is None:
            self._t_first = t
        self._m_submitted.inc()
        self._log(event="serve_submit", id=rid)

    def on_reject(self, rid, t: float) -> None:
        self.rejected += 1
        self._m_requests.inc(status="rejected")
        if self.slo is not None and self.slo.has("error_rate"):
            self.slo.record("error_rate", ok=False)
        self._log(event="serve_reject", id=rid)

    def on_admit(self, rid, wait_s: float) -> None:
        """A request claimed a slot `wait_s` seconds after submit — the
        QUEUE-WAIT half of its eventual TTFT (the other half, from slot
        claim to first token, is prefill compute + window wait). New
        event type, new keys only: existing serve.jsonl consumers see
        an unchanged record schema for the events they already parse."""
        self.queue_wait_s.append(wait_s)
        if self.slo is not None and self.slo.has("queue_wait"):
            self.slo.observe("queue_wait", wait_s)
        self._log(event="serve_admit", id=rid, queue_wait_ms=wait_s * 1e3)

    def on_first_token(self, rid, ttft_s: float, *, tenant=None,
                       queue_s: float | None = None,
                       reserved_s: float | None = None,
                       prefill_s: float | None = None) -> None:
        """A request's first decode window landed `ttft_s` seconds after
        submit, of which `queue_s` queued, `reserved_s` in a reserved
        slot before its first prefill dispatch and `prefill_s` from that
        dispatch on (the scheduler's `Entry.phases()`: they add up to
        `ttft_s`). The event carries all three; since they arrived its
        `prefill_ms` is the third alone, and what it used to hold, slot
        claimed -> first token, is `reserved_ms + prefill_ms` (the same
        number on an unchunked engine), which `summary()`'s
        `serve_prefill_ms_*` still reports."""
        self._m_ttft.observe(ttft_s)
        if self.slo is not None and self.slo.has("ttft"):
            self.slo.observe("ttft", ttft_s)
        if tenant is not None and self.tenancy is not None:
            # the tenant's own ttft:<name> objective — THE per-tenant
            # admission/brownout signal (SLOEngine.breached)
            self.tenancy.observe_ttft(tenant, ttft_s)
            self._m_t_ttft.observe(ttft_s, tenant=tenant)
            self.tenant_ttft_s.setdefault(tenant, []).append(ttft_s)
        self.ttft_s.append(ttft_s)
        if prefill_s is not None:
            self.prefill_s.append(reserved_s + prefill_s)
        self._log(event="serve_first_token", id=rid,
                  ttft_ms=ttft_s * 1e3, queue_ms=_r(queue_s, 1e3, 6),
                  reserved_ms=_r(reserved_s, 1e3, 6),
                  prefill_ms=_r(prefill_s, 1e3, 6))

    def on_finish(self, rid, *, n_tokens: int, ttft_s: float | None,
                  decode_s: float, reason: str, t: float,
                  tenant=None) -> None:
        self.finished += 1
        if reason in ("timeout", "deadline"):
            self.timed_out += 1
        self._m_requests.inc(status=str(reason))
        if self.slo is not None and self.slo.has("error_rate"):
            self.slo.record("error_rate", ok=reason not in (
                "error", "timeout", "deadline"))
        if n_tokens:
            self._m_tokens.inc(n_tokens)
        self.tokens_out += n_tokens
        self._t_last = t
        if n_tokens > 1 and decode_s > 0:
            itl = decode_s / (n_tokens - 1)
            self.token_s.append(itl)
            self._m_itl.observe(itl)
        self._log(event="serve_finish", id=rid, tokens=n_tokens,
                  reason=reason,
                  ttft_ms=None if ttft_s is None else ttft_s * 1e3)
        if tenant is not None and self.tenancy is not None:
            # the tenant-attributed finish is a NEW event type (frozen
            # from day one), never a reshaped serve_finish — the
            # historical schema stays byte-identical
            self.tenant_finished[tenant] = (
                self.tenant_finished.get(tenant, 0) + 1)
            self.tenant_tokens[tenant] = (
                self.tenant_tokens.get(tenant, 0) + n_tokens)
            self._m_t_requests.inc(tenant=tenant, status=str(reason))
            if n_tokens:
                self._m_t_tokens.inc(n_tokens, tenant=tenant)
            self._log(event="serve_tenant_finish", id=rid,
                      tenant=tenant, tokens=n_tokens, reason=reason,
                      ttft_ms=None if ttft_s is None else ttft_s * 1e3)

    # -- resilience ------------------------------------------------------

    def on_slot_fault(self, rid, *, kind: str, slot=None) -> None:
        """A running/prefilling slot was quarantined: `kind` is the
        detector that fired (nonfinite_logits / logit_magnitude /
        invariant / prefill_error). New event type only — the frozen
        serve.jsonl schema is untouched."""
        self.slot_faults += 1
        self._m_slot_faults.inc(kind=kind)
        self._log(event="serve_slot_fault", id=rid, kind=kind,
                  slot=slot)

    def on_retry(self, rid, *, attempt: int, delay_s: float) -> None:
        """A quarantined request was scheduled for re-admission
        `delay_s` seconds out; `attempt` is the total attempt count it
        re-enters with."""
        self.retries += 1
        self._m_retries.inc()
        self._log(event="serve_retry", id=rid, attempt=attempt,
                  delay_ms=delay_s * 1e3)

    def on_shed(self, rid, *, tenant=None) -> None:
        """A submit was refused by the brownout shed stage (the
        server-wide controller OR — `tenant` set with tenancy armed —
        that tenant's own). Counted as its own terminal outcome —
        deliberately NOT fed to the error-rate SLO: shedding is the
        controller's intended action, and scoring it as an error
        would make shedding beget more shedding."""
        self.shed += 1
        self._m_shed.inc()
        self._m_requests.inc(status="shed")
        self._log(event="serve_shed", id=rid)
        if tenant is not None and self.tenancy is not None:
            self.tenant_shed[tenant] = (
                self.tenant_shed.get(tenant, 0) + 1)
            self._m_t_shed.inc(tenant=tenant)
            self._m_t_requests.inc(tenant=tenant, status="shed")
            self._log(event="serve_tenant_shed", id=rid, tenant=tenant)

    def on_tenant_quota(self, rid, *, tenant: str, kind: str) -> None:
        """A submit was refused by a per-tenant quota (`kind` =
        "queued" today; page/slot quotas block IN the queue instead of
        refusing). Counted as a rejection for the aggregate figures
        but — like shed — never fed to the error-rate SLO: the
        refusal IS the isolation mechanism protecting the other
        tenants, not the service failing."""
        self.rejected += 1
        self._m_requests.inc(status="rejected")
        self.tenant_quota_rejections[tenant] = (
            self.tenant_quota_rejections.get(tenant, 0) + 1)
        self._m_t_quota.inc(tenant=tenant, kind=kind)
        self._m_t_requests.inc(tenant=tenant, status="rejected")
        self._log(event="serve_tenant_quota_reject", id=rid,
                  tenant=tenant, kind=kind)

    def on_tenant_cycle(self, names, *, depths: dict, slots: dict,
                        pages: dict) -> None:
        """Per-cycle tenant occupancy gauges — every registered tenant
        gets an explicit point (zero included) so a tenant that just
        drained reads 0, not its stale last value."""
        for name in names:
            self._m_t_queue.set(depths.get(name, 0), tenant=name)
            self._m_t_slots.set(slots.get(name, 0), tenant=name)
            self._m_t_pages.set(pages.get(name, 0), tenant=name)

    def on_clamp(self, rid, *, asked: int, clamp: int) -> None:
        """The brownout clamp shortened an admission's budget."""
        self.clamped += 1
        self._m_clamped.inc()
        self._log(event="serve_clamp", id=rid, max_new_tokens=clamp,
                  asked=asked)

    def on_fault_injected(self, kind: str, *, tick: int = 0) -> None:
        """A declarative drill fault fired (ServeFaultPlan)."""
        self.faults_injected += 1
        self._m_faults_injected.inc(kind=kind)
        self._log(event="serve_fault_injected", kind=kind, tick=tick)

    # -- hot weight rollout ----------------------------------------------

    def on_rollout(self, *, stage: str, outcome: str | None = None,
                   canary_requests: int = 0,
                   reason: str | None = None) -> None:
        """One rollout state-machine transition (checkpoint/rollout.py
        drives these: staging -> canary -> promoted | rolled_back).
        `outcome` is set only on the terminal transitions; `reason`
        explains a rollback (spot-check code, SLO comparison). New
        event type only — every historical schema stays
        byte-identical."""
        codes = {"idle": 0, "staging": 1, "canary": 2, "promoted": 3,
                 "rolled_back": 4}
        if stage not in codes:
            raise ValueError(f"unknown rollout stage {stage!r} "
                             f"(one of {sorted(codes)})")
        self.rollout_stage = stage
        self._m_rollout_stage.set(codes[stage])
        if outcome is not None:
            self.rollout_outcomes.append(outcome)
            self._m_rollouts.inc(outcome=outcome)
        self._log(event="serve_rollout", stage=stage, outcome=outcome,
                  canary_requests=canary_requests, reason=reason)

    # -- speculative decoding --------------------------------------------

    def on_dispatch(self, kind: str) -> None:
        """One decode dispatch was COLLECTED: kind is 'window' (the
        fused one-token-per-step scan) or 'verify' (speculative
        draft-and-verify). Counted at collect, not at dispatch, so an
        aborted in-flight dispatch (engine failure mid-drill) whose
        tokens never land does not skew the denominator. The shared
        tokens-per-dispatch definition (summary) divides emitted
        tokens by this count, so spec-on and spec-off runs compare on
        one denominator."""
        if kind == "verify":
            self.verify_dispatches += 1
        else:
            self.window_dispatches += 1
        self._m_dispatches.inc(kind=kind)

    def on_spec(self, *, drafted: int, accepted: int, emitted: int,
                slots: int) -> None:
        """A verify dispatch was collected: `drafted` tokens proposed
        across `slots` genuinely PROPOSING rows (ride-along slots the
        drafter declined are excluded — they would dilute the rates
        operators tune by), `accepted` of them emitted as-is,
        `emitted` those rows' total tokens out (accepted + one bonus
        pick per row that had budget for it). New event type only —
        the frozen serve.jsonl schemas are untouched."""
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.spec_emitted += emitted
        self.spec_slot_verifies += slots
        if drafted:
            self._m_spec_drafted.inc(drafted)
        if accepted:
            self._m_spec_accepted.inc(accepted)
        self._log(event="serve_spec_verify", drafted=drafted,
                  accepted=accepted, emitted=emitted, slots=slots)

    def on_propose(self, seconds: float) -> None:
        """One drafting pass completed (scheduler._propose_drafts):
        `seconds` of wall time spent producing proposals — the n-gram
        scans and/or the learned drafter's batched device dispatch.
        Rollup only (one call per cycle; no per-cycle event spam, no
        new exposition lines — the /metrics byte-equality gates
        stay)."""
        self.propose_s += float(seconds)
        self.propose_calls += 1

    # -- expert layers ----------------------------------------------------

    def on_moe(self, *, held, touched, assigned, steps) -> None:
        """A decode window of a model with expert layers was collected
        (engine.last_moe, models/moe.window_stats): `held` [layers,
        count] assignments each held expert was sent, `touched`
        [layers] held experts with a token, summed over the window's
        `steps` live steps, `assigned` the assignments to all experts,
        held or absent. The instruments are registered on the first
        call, so a server of a model without expert layers exposes
        none of them."""
        held = np.asarray(held, np.int64)
        if self._moe is None:
            reg = self._reg
            self._moe = {
                "load": np.zeros_like(held),
                "assigned": reg.counter(
                    "serve_moe_assignments_total",
                    "token-to-expert assignments the router made in "
                    "decode windows, over ALL experts (held here or on "
                    "another chip)"),
                "held": reg.counter(
                    "serve_moe_assignments_held_total",
                    "of those, assignments to experts this chip holds "
                    "(the rows of the grouped expert product)"),
                "touched": reg.gauge(
                    "serve_moe_experts_touched",
                    "held experts that got at least one token, mean "
                    "over the expert layers and token steps of the "
                    "last decode window"),
                "skew": reg.gauge(
                    "serve_moe_load_max_over_mean",
                    "busiest held expert's assignments over the mean "
                    "held expert's, mean over the expert layers, since "
                    "the server started"),
            }
        m = self._moe
        m["load"] += held
        m["assigned"].inc(int(assigned))
        m["held"].inc(int(held.sum()))
        self.moe_assigned += int(assigned)
        self.moe_touched += int(np.sum(touched))
        self.moe_layer_steps += int(steps) * held.shape[0]
        if steps:
            m["touched"].set(float(np.sum(touched))
                             / (int(steps) * held.shape[0]))
        skew = _load_skew(m["load"])
        if skew is not None:
            m["skew"].set(skew)

    def on_dsa(self, *, share_sum, rows, fold_rows) -> None:
        """A decode window of a model with indexer layers was collected
        (engine.last_dsa, models/lm.sparse_window_stats): `share_sum`
        the positions a query attended over the positions it could see,
        summed over the window's `rows` live (step, slot) pairs, and
        `fold_rows` the rows the fold sorted, gathered and attended for
        (the live ones in whole groups). The gauges are registered on
        the first call."""
        if self._m_dsa_share is None:
            self._m_dsa_share = self._reg.gauge(
                "serve_dsa_selected_share",
                "positions the indexer selected over the positions "
                "visible, mean over the live slots and steps of the "
                "decode windows since the server started")
            self._m_dsa_folded = self._reg.gauge(
                "serve_dsa_folded_over_live",
                "rows the sparse decode fold sorted, gathered and "
                "attended for over the live (step, slot) pairs of the "
                "decode windows since the server started: 1.0 = no "
                "dead row paid for")
        self.dsa_share_sum += float(share_sum)
        self.dsa_rows += int(rows)
        self.dsa_fold_rows += int(fold_rows)
        if self.dsa_rows:
            self._m_dsa_share.set(self.dsa_share_sum / self.dsa_rows)
            self._m_dsa_folded.set(self.dsa_fold_rows / self.dsa_rows)

    def on_attn_rows(self, read: int, whole: int) -> None:
        """A decode window of the contiguous engine was collected
        (engine.last_attn_rows): `read` cache rows of one full layer its
        attention read, summed over the window's steps and slots, of the
        `whole` steps x slots x t_max a fold that reads every row of
        every slot reads. The gauge is registered on the first call."""
        if self._m_attn_share is None:
            self._m_attn_share = self._reg.gauge(
                "serve_attn_read_share",
                "cache rows the decode windows' attention read (it "
                "stops at the furthest live position of the batch) "
                "over all rows of all slots, since the server started")
        self.attn_rows_read += int(read)
        self.attn_rows_whole += int(whole)
        self._m_attn_share.set(self.attn_rows_read / self.attn_rows_whole)

    def on_kv_layout(self, by_kind: dict) -> None:
        """The engine's cache rows by layer kind (engine.
        kv_bytes_by_kind), once at construction. Gauges only for a
        model that has window layers: every other server's exposition
        stays as it was."""
        self.kv_bytes_by_kind = dict(by_kind)
        if by_kind.get("index"):
            self._reg.gauge(
                "serve_index_cache_bytes",
                "HBM bytes of the index keys the indexer layers cache "
                "beside K/V, over all slots").set(by_kind["index"])
        if by_kind.get("latent"):
            self._reg.gauge(
                "serve_latent_cache_bytes",
                "HBM bytes of the rows the latent-attention layers cache "
                "(one a position, in place of K and V), over all "
                "slots").set(by_kind["latent"])
        if by_kind.get("window"):
            for kind, nbytes in by_kind.items():
                self._reg.gauge(
                    f"serve_kv_bytes_{kind}",
                    f"HBM bytes of the {kind}-attention layers' cache "
                    f"rows over all slots").set(nbytes)

    # -- paged KV ---------------------------------------------------------

    def on_pages(self, *, pages_total: int, pages_used: int,
                 pages_cached: int, resident_tokens: int,
                 resident_bytes: int) -> None:
        """Per-cycle page-pool occupancy from the paged engine
        (engine.page_stats): gauges for live scraping plus the peak
        rollup the summary reports — peak resident tokens over the
        bytes backing them is the tokens-per-HBM-byte capacity claim.
        Logs nothing per cycle (one gauge set per cycle, no event
        spam)."""
        if self.kv_pages_total is None:
            self._m_pages_total.set(pages_total)
        self.kv_pages_total = int(pages_total)
        self._m_pages_used.set(pages_used)
        self._m_pages_cached.set(pages_cached)
        self.kv_pages_used_peak = max(self.kv_pages_used_peak,
                                      int(pages_used))
        if resident_tokens > self.kv_resident_tokens_peak:
            self.kv_resident_tokens_peak = int(resident_tokens)
            if resident_bytes > 0:
                self.kv_tokens_per_byte_peak = (resident_tokens
                                                / resident_bytes)
        self.kv_resident_bytes_peak = max(self.kv_resident_bytes_peak,
                                          int(resident_bytes))

    def on_page_exhausted(self, *, rid=None, needed: int = 0) -> None:
        """The paged engine could not grant pages this cycle —
        admission held the queue head back, or a running slot's
        mid-decode growth failed. New event type only; the frozen
        historical schemas are untouched."""
        self.page_exhaustions += 1
        self._m_page_exhausted.inc()
        self._log(event="serve_page_exhausted", id=rid, needed=needed)

    # -- engine cycle ----------------------------------------------------

    def on_cycle(self, *, queue_depth: int, occupancy: float,
                 tokens: int = 0, prefill_s: float = 0.0) -> None:
        self.cycles += 1
        self._m_queue.set(queue_depth)
        self._m_occ.set(occupancy)
        self._m_last_tick.set(time.monotonic())
        if self.slo is not None:
            self.slo.evaluate()
        self.queue_depths.append(int(queue_depth))
        self.occupancies.append(float(occupancy))
        self.cycle_tokens.append(int(tokens))
        self.cycle_prefill_s.append(float(prefill_s))

    def on_jit_cache(self, total_entries: int) -> None:
        """Called once per cycle with the summed jit-cache entry count
        of the engine's compiled programs; any growth AFTER the first
        observation is a compile the serve loop paid for mid-traffic
        (the no-recompile contract says zero after warmup)."""
        if self._jit_cache_seen is not None:
            delta = total_entries - self._jit_cache_seen
            if delta > 0:
                self._m_compiles.inc(delta)
                self.compiles_observed += delta
        self._jit_cache_seen = total_entries

    def on_compile_cache(self, cache) -> None:
        """Snapshot a `CompileCache`'s counters after warmup: first
        call registers the serve_compile_cache_* gauges (lazily — see
        `_g_cc`), every call re-reads `cache.summary()` into them and
        the rollup, so warm-vs-cold spin-up is visible in the `stats`
        epilogue."""
        if self._g_cc is None:
            reg = self._reg
            self._g_cc = {
                "hits": reg.gauge(
                    "serve_compile_cache_hits",
                    "persistent compile-cache hits (executables "
                    "deserialized from disk instead of compiled)"),
                "misses": reg.gauge(
                    "serve_compile_cache_misses",
                    "persistent compile-cache misses (programs XLA-"
                    "compiled and stored; includes corrupt evictions)"),
                "deserialize_s": reg.gauge(
                    "serve_compile_cache_deserialize_seconds",
                    "cumulative seconds spent deserializing cached "
                    "executables (the warm spin-up cost)"),
            }
        s = cache.summary()
        self.compile_cache_summary = s
        self._g_cc["hits"].set(s["hits"])
        self._g_cc["misses"].set(s["misses"])
        self._g_cc["deserialize_s"].set(s["deserialize_s"])
        self._log(event="serve_compile_cache", **s)

    # -- rollup -----------------------------------------------------------

    def summary(self) -> dict:
        """The serving scenario record: aggregate throughput over the
        span from first submit to last finish, TTFT percentiles, and
        mean queue/occupancy — the `serve_*` fields."""
        span = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else None)
        out = {
            "serve_requests": self.finished,
            "serve_rejected": self.rejected,
            "serve_timed_out": self.timed_out,
            "serve_tokens": self.tokens_out,
            "serve_tokens_per_sec": (
                round(self.tokens_out / span, 2)
                if span and span > 0 else None),
            "serve_ttft_ms_p50": _r(_pct(self.ttft_s, 50), 1e3),
            "serve_ttft_ms_p95": _r(_pct(self.ttft_s, 95), 1e3),
            # TTFT decomposed: time queued (submit -> slot claimed) vs
            # time computing (slot claimed -> first token, i.e. prefill
            # + first decode window) — which half dominates tells an
            # operator whether to add slots or shrink prompts/chunks
            "serve_queue_wait_ms_p50": _r(_pct(self.queue_wait_s, 50),
                                          1e3),
            "serve_queue_wait_ms_p95": _r(_pct(self.queue_wait_s, 95),
                                          1e3),
            "serve_prefill_ms_p50": _r(_pct(self.prefill_s, 50), 1e3),
            "serve_prefill_ms_p95": _r(_pct(self.prefill_s, 95), 1e3),
            "serve_token_ms_p50": _r(_pct(self.token_s, 50), 1e3),
            # decode-side tail (additive key, ISSUE 20): the p95 over
            # requests of each one's MEAN seconds a token after its
            # first, which a long request's slow cycles hardly move. Not
            # the gap between two deliveries a stream feels: that is
            # the benchmark's `delivery_gap_p95_ms`, off the
            # `serve.tick` spans. The fleet SLO reads this side of the
            # request, TTFT the prefill side
            "serve_token_ms_p95": _r(_pct(self.token_s, 95), 1e3),
            "serve_slot_occupancy": (
                round(float(np.mean(self.occupancies)), 4)
                if self.occupancies else None),
            "serve_queue_depth_mean": (
                round(float(np.mean(self.queue_depths)), 2)
                if self.queue_depths else None),
            "serve_queue_depth_max": (
                max(self.queue_depths) if self.queue_depths else None),
            "serve_window_tokens_mean": (
                round(float(np.mean(self.cycle_tokens)), 2)
                if self.cycle_tokens else None),
            # host time per cycle spent admitting/prefilling before the
            # next window dispatch — the decode stall chunking bounds
            "serve_prefill_stall_ms_mean": (
                _r(float(np.mean(self.cycle_prefill_s)), 1e3)
                if self.cycle_prefill_s else None),
            "serve_prefill_stall_ms_max": (
                _r(float(np.max(self.cycle_prefill_s)), 1e3)
                if self.cycle_prefill_s else None),
            # NEW key (additive — existing consumers unaffected): jit
            # cache-size growth seen after the first cycle; nonzero
            # means admission traffic compiled something mid-serve
            "serve_compiles_observed": self.compiles_observed,
            # resilience rollup (additive, ISSUE 8): quarantines by
            # the health checks, bounded re-admissions, brownout sheds
            # and clamps, and drill faults fired
            "serve_slot_faults": self.slot_faults,
            "serve_retries": self.retries,
            "serve_shed": self.shed,
            "serve_clamped": self.clamped,
            "serve_faults_injected": self.faults_injected,
            # speculative rollup (additive, ISSUE 10). The SHARED
            # tokens-per-dispatch definition — emitted tokens over
            # decode dispatches of EITHER kind — so spec-on and
            # spec-off runs compare on one denominator; the spec-only
            # figures isolate the verify path: accept rate over
            # drafted tokens, and emitted tokens per participating
            # SLOT per verify (>1 means speculation beat one-token-
            # per-step decode for the slots that ran it)
            "serve_decode_dispatches": (self.window_dispatches
                                        + self.verify_dispatches),
            "serve_tokens_per_dispatch": (
                round(self.tokens_out
                      / (self.window_dispatches
                         + self.verify_dispatches), 3)
                if self.window_dispatches + self.verify_dispatches
                else None),
            "serve_spec_verify_dispatches": self.verify_dispatches,
            "serve_spec_drafted": self.spec_drafted,
            "serve_spec_accepted": self.spec_accepted,
            "serve_spec_accept_rate": (
                round(self.spec_accepted / self.spec_drafted, 4)
                if self.spec_drafted else None),
            "serve_spec_tokens_per_dispatch": (
                round(self.spec_emitted / self.spec_slot_verifies, 3)
                if self.spec_slot_verifies else None),
            # draft-model overhead: total drafting-pass wall seconds
            # (None when speculation never drafted — spec-off runs
            # keep their summary shape unchanged)
            "serve_spec_propose_s": (
                round(self.propose_s, 6) if self.propose_calls
                else None),
            # paged-KV rollup (additive, ISSUE 11): pool size and peak
            # occupancy, the peak tokens-resident-per-HBM-byte the
            # capacity claim is stated in, and how often the pool ran
            # dry — all None/0 on contiguous engines
            "serve_kv_pages_total": self.kv_pages_total,
            "serve_kv_pages_used_peak": (
                self.kv_pages_used_peak
                if self.kv_pages_total is not None else None),
            "serve_kv_resident_tokens_peak": (
                self.kv_resident_tokens_peak
                if self.kv_pages_total is not None else None),
            "serve_kv_resident_bytes_peak": (
                self.kv_resident_bytes_peak
                if self.kv_pages_total is not None else None),
            "serve_kv_tokens_per_hbm_byte": (
                None if self.kv_tokens_per_byte_peak is None
                else round(self.kv_tokens_per_byte_peak, 6)),
            "serve_page_exhaustions": self.page_exhaustions,
            # rollout rollup (additive, ROADMAP 4): terminal outcome
            # count, the last outcome, and the stage the machine ended
            # in — None/0 on servers that never rolled anything out
            "serve_rollouts": len(self.rollout_outcomes),
            "serve_rollout_outcome": (self.rollout_outcomes[-1]
                                      if self.rollout_outcomes
                                      else None),
            "serve_rollout_stage": self.rollout_stage,
        }
        if self._moe is not None:
            # expert-layer rollup (additive; a model with expert layers
            # only): assignments to all experts and to the held ones,
            # held experts touched per expert layer and token step, and
            # the busiest held expert's load over the mean one's
            load = self._moe["load"]
            out["serve_moe_assignments"] = self.moe_assigned
            out["serve_moe_assignments_held"] = int(load.sum())
            out["serve_moe_experts_held"] = int(load.shape[1])
            out["serve_moe_experts_touched_mean"] = (
                self.moe_touched / self.moe_layer_steps
                if self.moe_layer_steps else None)
            out["serve_moe_load_max_over_mean"] = _load_skew(load)
        if self.attn_rows_whole:
            # how far the decode windows' attention read (additive; the
            # contiguous engine only): 1.0 = every row of every slot
            out["serve_attn_read_share"] = (self.attn_rows_read
                                            / self.attn_rows_whole)
        if self.dsa_rows:
            # what the indexer layers kept (additive; a model with such
            # layers only): 1.0 = every visible position attended
            out["serve_dsa_selected_share"] = (self.dsa_share_sum
                                               / self.dsa_rows)
            # rows their decode fold ran for over the live ones: 1.0 =
            # no dead row was sorted, gathered or attended for
            out["serve_dsa_folded_over_live"] = (self.dsa_fold_rows
                                                 / self.dsa_rows)
        if self.kv_bytes_by_kind.get("index"):
            out["serve_index_cache_bytes"] = self.kv_bytes_by_kind["index"]
        if self.kv_bytes_by_kind.get("latent"):
            out["serve_latent_cache_bytes"] = self.kv_bytes_by_kind["latent"]
        if self.kv_bytes_by_kind.get("window"):
            out["serve_kv_bytes_full"] = self.kv_bytes_by_kind["full"]
            out["serve_kv_bytes_window"] = self.kv_bytes_by_kind["window"]
        if self.tenancy is not None:
            # per-tenant rollup (additive key, ISSUE 14): one record
            # per REGISTERED tenant — zeros included, so "tenant B was
            # untouched by A's flood" is readable straight off the
            # summary
            out["serve_tenants"] = {
                name: {
                    "requests": self.tenant_finished.get(name, 0),
                    "tokens": self.tenant_tokens.get(name, 0),
                    "ttft_ms_p50": _r(
                        _pct(self.tenant_ttft_s.get(name, []), 50),
                        1e3),
                    "ttft_ms_p95": _r(
                        _pct(self.tenant_ttft_s.get(name, []), 95),
                        1e3),
                    "shed": self.tenant_shed.get(name, 0),
                    "quota_rejections":
                        self.tenant_quota_rejections.get(name, 0),
                    "slo_breached": self.tenancy.breached(name),
                }
                for name in self.tenancy.names()}
        if self.compile_cache_summary is not None:
            # additive key (PR 18): the persistent compile-cache
            # rollup of THIS server's warmup — absent on servers that
            # spun up without one
            out["serve_compile_cache"] = dict(self.compile_cache_summary)
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.summary())
        return out

    def _log(self, **record) -> None:
        if self.logger is not None:
            self.logger.log(**record)


def _r(v, scale, digits: int = 2) -> float | None:
    return None if v is None else round(v * scale, digits)


def aggregate_summaries(metrics_list) -> dict:
    """The CLUSTER rollup over N replicas' `ServingMetrics` — the
    record the router's `summary()` reports.

    Percentiles are computed over the POOLED per-request samples (every
    replica's raw ttft/queue-wait lists concatenated), never by
    averaging per-replica percentiles — a p95 of p95s is not a p95.
    Aggregate throughput spans from the earliest first-submit to the
    latest last-finish across the fleet: the wall-clock window a user
    of the whole cluster actually experienced."""
    metrics_list = list(metrics_list)
    ttft, queue_wait, itl = [], [], []
    tokens = finished = rejected = timed_out = shed = 0
    t_first, t_last = None, None
    for m in metrics_list:
        ttft.extend(m.ttft_s)
        queue_wait.extend(m.queue_wait_s)
        itl.extend(m.token_s)
        tokens += m.tokens_out
        finished += m.finished
        rejected += m.rejected
        timed_out += m.timed_out
        shed += m.shed
        if m._t_first is not None:
            t_first = (m._t_first if t_first is None
                       else min(t_first, m._t_first))
        if m._t_last is not None:
            t_last = (m._t_last if t_last is None
                      else max(t_last, m._t_last))
    span = (t_last - t_first
            if t_first is not None and t_last is not None else None)
    return {
        "cluster_replicas": len(metrics_list),
        "cluster_requests": finished,
        "cluster_rejected": rejected,
        "cluster_timed_out": timed_out,
        "cluster_shed": shed,
        "cluster_tokens": tokens,
        "cluster_tokens_per_sec": (round(tokens / span, 2)
                                   if span and span > 0 else None),
        "cluster_ttft_ms_p50": _r(_pct(ttft, 50), 1e3),
        "cluster_ttft_ms_p95": _r(_pct(ttft, 95), 1e3),
        "cluster_queue_wait_ms_p95": _r(_pct(queue_wait, 95), 1e3),
        # pooled decode-side tail (additive, ISSUE 20): p95 of the
        # per-request mean inter-token latencies across the fleet
        "cluster_itl_ms_p95": _r(_pct(itl, 95), 1e3),
    }
