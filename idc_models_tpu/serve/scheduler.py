"""Iteration-level scheduling over the slot engine: admission queue,
deadlines, prefill/decode interleave, slot recycling.

The engine (serve/engine.py) is a device-state machine with no opinion
about WHICH request runs where or when; this module is the policy:

- **FIFO admission with backpressure** — `AdmissionQueue` holds at most
  `max_depth` waiting requests; a submit beyond that is REFUSED (the
  caller sees `False` and decides: retry, shed, or block). Bounded
  queues are the backpressure contract: an unbounded queue converts
  overload into unbounded tail latency instead of an explicit signal.
- **Deadlines** — a request may carry a deadline (seconds from submit).
  Queued requests past it are dropped without ever occupying a slot;
  RUNNING requests past it are cancelled mid-generation (partial tokens
  returned, the slot recycled for the next request).
- **Prefill-vs-decode interleave** — each `tick()` admits at most
  `max_prefills_per_cycle` queued requests into free slots before
  running one decode window. Prefill is the long-pole dispatch (O(P)
  work vs the window's O(W)); capping admissions per cycle bounds how
  long running requests stall behind a deep queue, while still refilling
  vacated slots within a cycle of them freeing.
- **Slot recycling** — EOS, budget exhaustion, and deadline cancels all
  route through `SlotEngine.release`; the vacated row is eligible for
  admission on the SAME tick the finish is observed, so slots never
  idle a full cycle between requests.
- **Resilience (ISSUE 8)** — per-cycle slot health checks quarantine a
  poisoned slot (non-finite/blown logits, violated invariants) and
  recover the REQUEST instead of failing the server: with a
  `RetryPolicy` armed the entry re-queues after an exponential backoff
  (keeping its original deadline and trace_id; `attempts`/`retried`
  surface on the Result), otherwise it finishes with an honest
  ``error``/``slot_fault`` status. A `ServeFaultPlan`
  (serve/faults.py) drives deterministic failure drills behind a
  default-off hook; a `RequestJournal` (serve/journal.py) WALs
  accepted work for crash recovery; a `BrownoutController`
  (serve/brownout.py) sheds load in stages when the SLO burns.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import time
from collections import deque

import numpy as np

from idc_models_tpu.observe import profile as prof
from idc_models_tpu.observe import trace
from idc_models_tpu.serve.engine import HEALTH_KINDS
from idc_models_tpu.serve.faults import (
    InjectedEngineCrash, InjectedPrefillError,
)

# process-unique request trace ids (pid + monotone counter): cheap
# enough to stamp on EVERY request whether or not a tracer is armed, so
# a rid's identity is stable across the jsonl log, the span export, and
# the user-facing Result
_TRACE_IDS = itertools.count(1)


def _next_trace_id() -> str:
    return f"{os.getpid():x}-{next(_TRACE_IDS):x}"


def _ms(seconds: float | None) -> float | None:
    return None if seconds is None else round(seconds * 1e3, 3)


@dataclasses.dataclass(eq=False)     # identity eq: prompts are arrays
class Entry:
    """One request's lifetime record inside the scheduler: identity and
    limits in, timestamps/tokens/finish state out. The api layer wraps
    this into the user-facing `Result`."""
    rid: object
    prompt: object                   # int32 [P]
    budget: int
    eos_id: int | None = None
    rng: object = None               # per-request sampling key
    trace_id: str | None = None      # assigned at submit if not given
    # the cluster hop context (ISSUE 20): the router's cluster.request
    # root span id, threaded down so this request's serve.request span
    # opens as its CHILD and the cross-replica export stitches into one
    # tree. None = no router above (a direct server submit).
    parent_span: object = None
    # request-lifecycle span handles (observe/trace.py DETACHED spans —
    # they outlive any one tick, so they never sit on a thread's
    # open-span stack): the whole submit->finish interval, and the
    # queued segment inside it. The shared no-op handle when tracing
    # is disabled.
    span: object = None
    queue_span: object = None
    # RELATIVE seconds-from-submit when handed to submit(); rewritten to
    # the absolute clock time there
    deadline: float | None = None
    t_submit: float = 0.0
    t_admit: float | None = None
    # the clock when the first prefill dispatch was issued for this
    # admission (`_step_prefills`' first `prefill_step`; an unchunked
    # engine prefills inside `admit`, so = t_admit) and the prefill
    # dispatches spent on it: between t_admit and t_chunk0 the slot
    # stood reserved behind the prompts admitted before it
    t_chunk0: float | None = None
    chunks: int = 0
    t_first: float | None = None
    t_done: float | None = None
    slot: int | None = None
    tokens: list = dataclasses.field(default_factory=list)
    # pending|running|retrying|ok|timeout|rejected|shed|error
    status: str = "pending"
    # eos|budget|deadline|slot_fault|shed|error|None
    finish_reason: str | None = None
    error: str | None = None         # engine failure detail (status=error)
    # retry bookkeeping (RetryPolicy): total admission attempts (1 =
    # never faulted), whether any retry happened, and the absolute
    # clock time before which a quarantined entry must not re-queue
    attempts: int = 1
    retried: bool = False
    not_before: float = 0.0
    clamped: bool = False            # brownout shortened the budget
    # tenancy (serve/tenancy.py, ISSUE 14): the resolved tenant name
    # (None = no tenancy armed), its engine gather index, and the
    # admission-time page reservation the per-tenant KV budget charges
    tenant: str | None = None
    tid: int = 0
    pages_reserved: int = 0

    def phases(self) -> tuple:
        """(queue_s, reserved_s, prefill_s): submit -> admit, admit ->
        first prefill dispatch, first prefill dispatch -> first token
        (its own chunks, the cycles between them, the insert and the
        first window). One clock, shared instants: they add up to the
        time to the first token. None from the first phase the request
        never reached."""
        marks = (self.t_submit, self.t_admit, self.t_chunk0, self.t_first)
        out, reached = [], True
        for a, b in zip(marks, marks[1:]):
            reached = reached and b is not None
            out.append(b - a if reached else None)
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded re-admission for requests recovered from a quarantined
    slot or a failed prefill dispatch. A retried request re-enters the
    queue FRONT after `backoff_s * backoff_factor**k` (k = prior
    retries), keeps its original deadline and trace_id, and restarts
    from its prompt — the engine's serial-parity contract then makes
    the recovered greedy/seeded output bit-identical to an unfaulted
    run. A retry whose backoff would land past the deadline finishes
    immediately with the honest timeout/deadline status instead."""

    max_retries: int = 2
    backoff_s: float = 0.05
    backoff_factor: float = 2.0

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"need max_retries >= 0, got "
                             f"{self.max_retries}")
        if self.backoff_s < 0:
            raise ValueError(f"need backoff_s >= 0, got "
                             f"{self.backoff_s}")
        if self.backoff_factor < 1.0:
            raise ValueError(f"need backoff_factor >= 1, got "
                             f"{self.backoff_factor}")

    def delay(self, prior_retries: int) -> float:
        return self.backoff_s * self.backoff_factor ** prior_retries


class AdmissionQueue:
    """Bounded FIFO. `push` returns False at max_depth — the
    backpressure signal — instead of growing without bound."""

    def __init__(self, max_depth: int = 64):
        if max_depth < 1:
            raise ValueError(f"need max_depth >= 1, got {max_depth}")
        self.max_depth = max_depth
        self._q: deque[Entry] = deque()

    def __len__(self) -> int:
        return len(self._q)

    def push(self, entry: Entry) -> bool:
        if len(self._q) >= self.max_depth:
            return False
        self._q.append(entry)
        return True

    def pop(self) -> Entry:
        return self._q.popleft()

    def peek(self) -> Entry:
        """The head entry without popping it — the page-aware
        admission gate inspects the head's demand before committing to
        take it."""
        return self._q[0]

    def push_front(self, entry: Entry) -> None:
        """Head-of-line insertion for RETRIED entries only: they were
        already admitted once (so they do not cheat the backpressure
        bound — the in-flight population is unchanged) and recovery
        latency beats FIFO fairness for a request that already waited
        its backoff."""
        self._q.appendleft(entry)

    def entries(self) -> tuple:
        """FIFO-order snapshot for the tenancy-aware admission scan: a
        quota-blocked HEAD must not starve other tenants (the whole
        point of per-tenant quotas), so admission may look past it —
        FIFO order is preserved WITHIN each tenant because the scan
        always takes the earliest admissible entry."""
        return tuple(self._q)

    def take(self, entry: Entry) -> None:
        """Remove a specific entry the admission scan picked (identity
        match — entries are identity-eq dataclasses)."""
        self._q.remove(entry)

    def expire(self, now: float) -> list[Entry]:
        """Drop queued entries past their deadline (they never reach a
        slot); returns them for result bookkeeping."""
        expired = [e for e in self._q
                   if e.deadline is not None and now >= e.deadline]
        if expired:
            self._q = deque(e for e in self._q if e not in expired)
        return expired


class Scheduler:
    """Continuous-batching loop: one `tick()` = expire deadlines, admit
    up to `max_prefills_per_cycle` requests into free slots, run ONE
    fused decode window of `window` tokens, recycle finished slots.
    Returns the entries that finished this tick."""

    def __init__(self, engine, *, window: int = 8, max_queue_depth: int = 64,
                 max_prefills_per_cycle: int = 1, metrics=None,
                 clock=time.monotonic, retry=None, fault_plan=None,
                 health_checks: bool | None = None, journal=None,
                 brownout=None, drafter=None, tenancy=None):
        if window < 1:
            raise ValueError(f"need window >= 1, got {window}")
        self.engine = engine
        self.window = window
        # speculative window mode (ISSUE 10): with a drafter AND an
        # engine built with draft_k, each cycle's decode dispatch may
        # be a VERIFY (k drafted tokens + the model's own correction
        # per slot, one dispatch) instead of the one-token-per-step
        # fused window — the policy lives in _propose_drafts
        self.drafter = drafter
        self._spec = (drafter is not None
                      and getattr(engine, "draft_k", None) is not None)
        if drafter is not None and not self._spec:
            raise ValueError(
                "a drafter needs an engine built with draft_k — the "
                "verify program is compiled at that fixed draft length")
        self.queue = AdmissionQueue(max_queue_depth)
        self.max_prefills_per_cycle = max(int(max_prefills_per_cycle), 1)
        self.metrics = metrics
        # resilience wiring (all default-off; see the module docstring):
        # retry = RetryPolicy, fault_plan = serve/faults.ServeFaultPlan,
        # journal = serve/journal.RequestJournal, brownout =
        # serve/brownout.BrownoutController. Health checks default to
        # armed exactly when quarantine could act on them.
        self.retry = retry
        self.fault_plan = fault_plan
        self.journal = journal
        self.brownout = brownout
        # tenancy (serve/tenancy.py): per-tenant quotas gate admission,
        # per-tenant brownouts shed one tenant's flood while its
        # neighbors stay normal, and each tenant's ttft:<name> SLO is
        # evaluated once per cycle — the built Tenancy runtime
        self.tenancy = tenancy
        if health_checks is None:
            health_checks = retry is not None or fault_plan is not None
        self.health_checks = bool(health_checks)
        self._retrying: list[Entry] = []
        # cumulative wall seconds spent in the drafting pass (host
        # scans + the learned drafter's batched device dispatch):
        # summary()'s serve_spec_propose_s
        self.propose_seconds = 0.0
        self._cycle = 0
        self._closed = False
        # drain mode (elastic scale-down / SIGTERM): submits refuse
        # with the honest terminal shed status while accepted work
        # finishes or migrates — see begin_drain()
        self._draining = False
        self._prefill_error_pending = 0
        # paged-KV backpressure: set when admission stalls on page
        # exhaustion this cycle, consumed (and cleared) by the
        # brownout evaluation — ISSUE 11's exhaustion -> brownout wire
        self._page_pressure = False
        self.clock = clock
        self._running: dict[int, Entry] = {}
        # chunked-prefill engines: entries whose prompt is still being
        # chunked into a reserved slot (slot -> Entry); they join
        # _running when the engine's final chunk + insert land
        self._prefilling: dict[int, Entry] = {}
        # entries killed by an engine failure mid-tick: tick() re-raises
        # the engine error, so the caller collects them here (pop_failed)
        self._failed: list[Entry] = []
        self._chunked = getattr(engine, "prefill_chunk", None) is not None
        # quiesce(): one-shot suppression of the end-of-tick window
        # dispatch, so a caller can reach the engine dispatch-idle
        # (rollout spot-checks on paged engines) without losing the
        # collect/finish bookkeeping of a normal tick
        self._skip_dispatch = False

    # -- admission -------------------------------------------------------

    def close(self) -> None:
        """Shut the admission surface down: every later `submit()`
        raises RuntimeError instead of enqueueing into a loop nobody
        will ever tick again (previously undefined behavior — the
        request would sit queued forever). Already-accepted work can
        still be ticked/drained by the caller before discarding the
        scheduler."""
        self._closed = True

    def submit(self, entry: Entry) -> bool:
        """Validate + enqueue. Returns False (backpressure, or a
        brownout shed — distinguishable by `entry.status == "shed"`)
        when the request is refused; raises on requests that could
        NEVER be served (too long for t_max, missing rng for sampling)
        — those are caller errors, not load — and RuntimeError after
        `close()`."""
        if self._closed:
            raise RuntimeError(
                "Scheduler.submit() after close(): the serving loop "
                "has shut down and would never tick this request — "
                "build a new server instead of submitting to a dead "
                "queue")
        p_len = len(entry.prompt)
        if p_len < 1:
            raise ValueError("empty prompt")
        if entry.budget < 1:
            raise ValueError(f"need max_new_tokens >= 1, got "
                             f"{entry.budget}")
        if p_len + entry.budget > self.engine.t_max:
            raise ValueError(
                f"prompt {p_len} + max_new_tokens {entry.budget} exceeds "
                f"t_max {self.engine.t_max}")
        if self.engine.temperature > 0.0 and entry.rng is None:
            raise ValueError("sampling (temperature > 0) needs a "
                             "per-request rng key")
        # resolve the EFFECTIVE stop token now (request override, else
        # the engine default; -1 opts out) so the finish_reason below
        # and the engine agree on what "eos" means for this request
        if entry.eos_id is None:
            entry.eos_id = self.engine.eos_id
        if entry.eos_id is not None and entry.eos_id < 0:
            entry.eos_id = None
        tenant_bc = None
        if self.tenancy is not None:
            # an unknown tenant tag is a caller error taught loudly —
            # silently billing the default tenant would charge one
            # tenant's quota for another's traffic
            t = self.tenancy.resolve(entry.tenant)
            entry.tenant, entry.tid = t.name, t.tid
            tenant_bc = self.tenancy.brownouts.get(t.name)
        entry.t_submit = self.clock()
        # brownout shed beats backpressure: an explicit, honest
        # refusal (Result.status == "shed") the client can act on,
        # recorded BEFORE the queue is consulted so shedding actually
        # relieves the queue instead of racing it. The TENANT's own
        # controller sheds first: one tenant's flood refuses that
        # tenant's submits while every other tenant stays normal.
        # drain mode sheds exactly like a brownout: an honest terminal
        # refusal, never a silent queue into a replica that is leaving
        shedding = self._draining or (self.brownout is not None
                                      and self.brownout.shedding)
        tenant_shed = tenant_bc is not None and tenant_bc.shedding
        if shedding or tenant_shed:
            entry.status, entry.finish_reason = "shed", "shed"
            entry.t_done = entry.t_submit
            if entry.trace_id is None:
                entry.trace_id = _next_trace_id()
            kw = ({"tenant": entry.tenant}
                  if entry.tenant is not None else {})
            trace.point("serve.shed", rid=entry.rid,
                        trace_id=entry.trace_id, **kw)
            if self.metrics:
                # tenant attribution ONLY when the tenant's OWN
                # controller shed it: billing a server-wide shed to
                # the per-tenant "own brownout" counters would make a
                # victim tenant read as degraded by its own flood
                self.metrics.on_shed(
                    entry.rid,
                    tenant=entry.tenant if tenant_shed else None)
            return False
        if self.tenancy is not None and entry.tenant is not None:
            # per-tenant queue quota: refused WITHOUT touching the
            # shared queue budget, so a flooding tenant cannot fill
            # the FIFO other tenants admit from. Deliberately not fed
            # to the error-rate SLO — like shed, the refusal IS the
            # isolation mechanism working, and scoring it as an error
            # would make protection look like failure. (On a tenancy-
            # LESS server a request's tenant tag is inert bookkeeping
            # — the cluster router still uses it for affinity.)
            q = self.tenancy.quota(entry.tenant).max_queued
            if q is not None and self._tenant_queued(entry.tenant) >= q:
                entry.status = "rejected"
                if self.metrics:
                    self.metrics.on_tenant_quota(
                        entry.rid, tenant=entry.tenant, kind="queued")
                return False
        deadline_rel = entry.deadline
        if entry.deadline is not None:
            entry.deadline = entry.t_submit + entry.deadline
        if not self.queue.push(entry):
            entry.status = "rejected"
            if self.metrics:
                self.metrics.on_reject(entry.rid, entry.t_submit)
            return False
        if entry.trace_id is None:
            entry.trace_id = _next_trace_id()
        if self.journal is not None:
            self.journal.record_submit(entry, deadline_s=deadline_rel)
        # the request-lifecycle chain: a detached serve.request span
        # covering submit->finish (it spans many ticks, so it must not
        # enter any thread's open-span stack), with the queued segment
        # as a detached child closed at admission. Every span in the
        # chain carries rid, so one grep over the export reconstructs
        # the request's full timeline.
        tkw = ({"tenant": entry.tenant}
               if entry.tenant is not None else {})
        entry.span = trace.start_span("serve.request",
                                      parent=entry.parent_span,
                                      rid=entry.rid,
                                      trace_id=entry.trace_id, **tkw)
        entry.queue_span = trace.start_span(
            "serve.queued", parent=entry.span.span_id, rid=entry.rid,
            trace_id=entry.trace_id)
        if self.metrics:
            self.metrics.on_submit(entry.rid, entry.t_submit,
                                   tenant=entry.tenant)
        return True

    def _tenant_queued(self, tenant: str) -> int:
        """Queued entries a tenant holds right now — derived from the
        queue itself (never an incrementally maintained counter, so
        there is nothing to drift out of sync)."""
        return sum(1 for e in self.queue.entries()
                   if e.tenant == tenant)

    def _page_gate(self, entry: Entry, eff: int) -> bool:
        """The ONE page-aware admission gate both the FIFO-head path
        and the tenancy scan consult: True when the paged engine can
        grant pages for (prompt, effective budget) right now; on False
        records the exhaustion backpressure (the brownout 'pages'
        signal + the serve_page_exhausted event). Always True on
        contiguous engines."""
        can_admit = getattr(self.engine, "can_admit_pages", None)
        if can_admit is None or can_admit(len(entry.prompt), eff):
            return True
        self._page_pressure = True
        on_exh = getattr(self.metrics, "on_page_exhausted", None)
        if on_exh is not None:
            on_exh(rid=entry.rid,
                   needed=len(entry.prompt) + entry.budget)
        return False

    def _tenant_residency(self) -> tuple[dict, dict]:
        """(slots, pages) each tenant holds across running +
        prefilling entries — derived on demand from the live tracking
        dicts, O(n_slots), the per-tenant admission-quota ledger."""
        slots: dict[str, int] = {}
        pages: dict[str, int] = {}
        for e in list(self._running.values()) + list(
                self._prefilling.values()):
            if e.tenant is None:
                continue
            slots[e.tenant] = slots.get(e.tenant, 0) + 1
            pages[e.tenant] = pages.get(e.tenant, 0) + e.pages_reserved
        return slots, pages

    def _admit_free_slots(self) -> int:
        """Pop queued entries into free slots, at most
        max_prefills_per_cycle — the ONE admission bookkeeping path for
        both tick() passes. On a chunked engine admission only RESERVES
        the slot (start_prefill dispatches nothing); the prompt is fed
        chunk by chunk by `_step_prefills`, one chunk per cycle, so a
        long prompt never stalls the decode windows behind one
        monolithic dispatch."""
        admitted = 0
        free = self.engine.free_slots()
        clamp = (self.brownout.token_clamp if self.brownout is not None
                 else None)
        if self.tenancy is not None:
            slots_used, pages_used = self._tenant_residency()
        while (admitted < self.max_prefills_per_cycle and free
               and len(self.queue)):
            # page-aware admission (paged engines): the HEAD request
            # must fit — pages for its prompt plus the decode
            # reservation — before it leaves the queue. FIFO holds
            # (no skipping ahead of a starved head: that would starve
            # long requests forever); the exhaustion is recorded as
            # backpressure and feeds the brownout signal below.
            # With TENANCY armed the scan may look past entries whose
            # TENANT-LOCAL quota (resident slots, page budget) blocks
            # them — a flooding tenant's backlog must not starve its
            # neighbors, and FIFO holds within each tenant — but a
            # GLOBAL page exhaustion still freezes the whole scan:
            # skipping past it would starve long requests forever.
            e = t_clamp = None
            if self.tenancy is None:
                head = self.queue.peek()
                # gate on the EFFECTIVE budget: brownout stage 2 clamps
                # it at admission below, and the clamp is exactly the
                # smaller-reservations lever the pages-pressure
                # escalation exists to pull — gating on the unclamped
                # ask would wedge admission at the stage meant to
                # unwedge it
                eff = (head.budget if clamp is None
                       else min(head.budget, clamp))
                if not self._page_gate(head, eff):
                    break
                e = self.queue.pop()
            else:
                stop = False
                for cand in self.queue.entries():
                    quota = self.tenancy.quota(cand.tenant)
                    if (quota.max_resident_slots is not None
                            and slots_used.get(cand.tenant, 0)
                            >= quota.max_resident_slots):
                        continue         # tenant-local: skip, no HOL
                    bc = self.tenancy.brownouts.get(cand.tenant)
                    cand_clamp = (bc.token_clamp if bc is not None
                                  else None)
                    eff = cand.budget
                    for c in (clamp, cand_clamp):
                        if c is not None:
                            eff = min(eff, c)
                    need = self.engine.pages_for_admission(
                        len(cand.prompt), eff)
                    if (quota.kv_page_budget is not None
                            and pages_used.get(cand.tenant, 0) + need
                            > quota.kv_page_budget):
                        continue         # tenant-local page budget:
                        #                  waits for its own releases
                    if not self._page_gate(cand, eff):
                        stop = True      # GLOBAL exhaustion freezes
                        break            # the scan — no skipping
                    e, t_clamp = cand, cand_clamp
                    break
                if stop or e is None:
                    break
                self.queue.take(e)
            slot = free.pop(0)
            eff_clamp = clamp
            if t_clamp is not None:
                eff_clamp = (t_clamp if eff_clamp is None
                             else min(eff_clamp, t_clamp))
            if eff_clamp is not None and e.budget > eff_clamp:
                # brownout stage 2 (server-wide AND/OR the tenant's
                # own): shorter answers for everyone beats no answers
                # for some — recorded per request so the truncated
                # budget is visible next to the finish
                if self.metrics:
                    self.metrics.on_clamp(e.rid, asked=e.budget,
                                          clamp=eff_clamp)
                e.budget, e.clamped = eff_clamp, True
            if self.tenancy is not None:
                e.pages_reserved = self.engine.pages_for_admission(
                    len(e.prompt), e.budget)
                slots_used[e.tenant] = slots_used.get(e.tenant, 0) + 1
                pages_used[e.tenant] = (pages_used.get(e.tenant, 0)
                                        + e.pages_reserved)
            eos = e.eos_id if e.eos_id is not None else -1
            e.slot, e.status, e.t_admit = slot, "running", self.clock()
            # registered BEFORE the engine call: if the engine raises
            # mid-admission, tick's failure handler finds this entry in
            # the tracking dict and fails it with the others instead of
            # silently dropping it
            if self._chunked:
                self._prefilling[slot] = e
                self.engine.start_prefill(slot, e.prompt, e.budget,
                                          rng=e.rng, eos_id=eos,
                                          tag=e.rid, tid=e.tid)
            else:
                self._running[slot] = e
                e.t_chunk0, e.chunks = e.t_admit, 1
                self.engine.admit(slot, e.prompt, e.budget, rng=e.rng,
                                  eos_id=eos, tag=e.rid, tid=e.tid)
            # recorded only AFTER the engine accepted the request — an
            # admit that raises must not leave a phantom queue-wait
            # sample behind
            if e.queue_span is not None:
                e.queue_span.close(
                    queue_wait_ms=_ms(e.t_admit - e.t_submit))
            if self.metrics:
                self.metrics.on_admit(e.rid, e.t_admit - e.t_submit)
            admitted += 1
        return admitted

    def _step_prefills(self, done) -> int:
        """Advance pending chunked prefills: at most
        max_prefills_per_cycle chunk DISPATCHES per cycle, oldest
        pending prefill first (FIFO completes a long prompt before
        starting to chunk the next — TTFT order follows admission
        order). Entries whose final chunk lands move to _running and
        decode from the next window. Returns chunk dispatches spent.

        A chunk dispatch that raises is REQUEST-scoped when a retry
        policy is armed (the dispatch's inputs are that request's own
        caches): the prefilling entry is quarantined — retried or
        failed honestly — and every other slot keeps serving. Without
        a retry policy the historical contract holds: the error
        propagates and the tick's failure cleanup aborts the batch."""
        steps = 0
        while steps < self.max_prefills_per_cycle and self._prefilling:
            slot = next(iter(self._prefilling))
            try:
                if self._prefill_error_pending:
                    self._prefill_error_pending -= 1
                    raise InjectedPrefillError(
                        f"injected prefill-chunk failure (slot {slot})")
                e = self._prefilling[slot]
                if e.t_chunk0 is None:
                    e.t_chunk0 = self.clock()
                e.chunks += 1
                finished = self.engine.prefill_step(slot)
            except Exception as exc:
                if self.retry is None:
                    raise
                e = self._prefilling.pop(slot)
                self.engine.cancel_prefill(slot)
                self._quarantine(e, "prefill_error", self.clock(), done,
                                 detail=f"{type(exc).__name__}: {exc}")
                steps += 1
                continue
            if finished:
                self._running[slot] = self._prefilling.pop(slot)
            steps += 1
        return steps

    def _quarantine(self, e: Entry, kind: str, now: float, done,
                    *, detail: str | None = None) -> None:
        """Recover ONE faulted request: re-queue it after the retry
        backoff when the policy and its deadline allow, else finish it
        with an honest status. Emits the `serve.slot_fault` (and
        `serve.retry`) lifecycle points so one rid grep shows
        fault -> quarantine -> retry -> finish under the request's
        trace_id."""
        detail = detail or f"slot fault: {kind}"
        parent = e.span.span_id if e.span is not None else None
        trace.point("serve.slot_fault", parent=parent, rid=e.rid,
                    kind=kind, slot=e.slot, trace_id=e.trace_id)
        if self.metrics:
            self.metrics.on_slot_fault(e.rid, kind=kind, slot=e.slot)
        e.slot = None
        prior = e.attempts - 1
        can_retry = (self.retry is not None
                     and prior < self.retry.max_retries)
        delay = self.retry.delay(prior) if can_retry else 0.0
        deadline_blocks = (e.deadline is not None
                           and now + delay >= e.deadline)
        if can_retry and not deadline_blocks:
            # restart from the prompt: the tokens emitted so far came
            # from (or raced) the poisoned state, and a clean re-run
            # re-derives the exact stream (serial-parity contract), so
            # discarding is what makes recovery bit-identical
            e.attempts += 1
            e.retried = True
            e.tokens = []
            e.t_first = e.t_chunk0 = None
            e.chunks = 0
            e.status = "retrying"
            e.not_before = now + delay
            self._retrying.append(e)
            trace.point("serve.retry", parent=parent, rid=e.rid,
                        attempt=e.attempts,
                        delay_ms=round(delay * 1e3, 3),
                        trace_id=e.trace_id)
            if self.metrics:
                self.metrics.on_retry(e.rid, attempt=e.attempts,
                                      delay_s=delay)
            return
        if deadline_blocks or (e.deadline is not None
                               and now >= e.deadline):
            e.status, e.finish_reason = "timeout", "deadline"
        else:
            e.status, e.finish_reason = "error", "slot_fault"
            e.error = f"{detail} (attempt {e.attempts})"
        e.t_done = now
        self._finish(e, done)

    # -- the cycle -------------------------------------------------------

    def idle(self) -> bool:
        return (not self._running and not self._prefilling
                and not len(self.queue) and not self._retrying
                and self.engine._pending is None)

    def load(self) -> int:
        """Requests this scheduler is responsible for right now —
        queued + prefilling + running + quarantined-awaiting-retry.
        The cluster router's least-loaded placement signal
        (serve/cluster/router.py): one integer, no device traffic."""
        return (len(self.queue) + len(self._running)
                + len(self._prefilling) + len(self._retrying))

    def _apply_faults(self, cycle: int) -> None:
        """Fire the plan's non-burst faults scheduled for this cycle —
        pure function of (plan, cycle), so drills replay exactly.
        Burst arrivals are injected by the api layer (they are
        submits, not engine events)."""
        for f in self.fault_plan.at(cycle):
            if self.metrics:
                self.metrics.on_fault_injected(f.kind, tick=cycle)
            if f.kind == "stall":
                # a straggling dispatch / GC pause / noisy neighbor:
                # the tick simply takes longer — the latency fault the
                # TTFT SLO burn is supposed to catch
                time.sleep(f.seconds)
            elif f.kind == "crash":
                exc = InjectedEngineCrash(
                    f"injected engine crash at cycle {cycle}")
                self._abort_running(exc)
                raise exc
            elif f.kind in ("nan_logits", "garbage_logits"):
                self.engine.inject_slot_fault(f.slot, f.kind)
            elif f.kind == "prefill_error":
                self._prefill_error_pending += 1

    def _requeue_retries(self, now: float, done) -> None:
        """Move quarantined entries whose backoff elapsed back to the
        queue FRONT (oldest first); entries whose deadline died while
        they waited finish honestly instead of burning a slot."""
        due, waiting = [], []
        for e in self._retrying:
            if e.deadline is not None and now >= e.deadline:
                e.status, e.finish_reason = "timeout", "deadline"
                e.t_done = now
                self._finish(e, done)
            elif now >= e.not_before:
                e.status = "pending"
                due.append(e)
            else:
                waiting.append(e)
        self._retrying = waiting
        for e in reversed(due):
            self.queue.push_front(e)

    def _check_slot_health(self, now: float, got, done) -> list:
        """Per-cycle health pass over the RUNNING slots: one tiny
        jitted reduce + [S]-int fetch (engine.slot_health) plus the
        free host-shadow invariants. Runs after collect and BEFORE the
        next window dispatch, so a slot whose logits a fault poisoned
        this cycle is quarantined before a single token is sampled
        from them. Returns `got` with the quarantined entries' just-
        collected tokens dropped (they were computed from, or raced,
        the corrupted state)."""
        codes = self.engine.slot_health()
        quarantined = set()
        for slot, e in list(self._running.items()):
            kind = HEALTH_KINDS.get(int(codes[slot]))
            if kind is None and not self.engine.slot_invariants_ok(slot):
                kind = "invariant"
            if kind is None:
                continue
            self.engine.release(slot)
            del self._running[slot]
            quarantined.add(id(e))
            self._quarantine(e, kind, now, done)
        if not quarantined:
            return got
        return [(e, t) for e, t in got if id(e) not in quarantined]

    def tick(self) -> list[Entry]:
        """One pipelined cycle. Host work (admission prefills, result
        bookkeeping) runs WHILE the previously begun window executes on
        device; the tick ends by dispatching the next window. Slot
        availability seen by admissions is one window stale — a row
        freed by the in-flight window refills next tick.

        Traced (observe/trace.py, no-op unless a tracer is active):
        one `serve.tick` span per cycle with `serve.admit` (the
        admission pass that overlaps the window in flight),
        `serve.collect`, `serve.refill` (the pass after collect, while
        the device has nothing to run) and `serve.window` nested under
        it, and the engine's `serve.start_prefill` / `serve.prefill` /
        `serve.prefill_chunk` / `serve.insert` spans nested under the
        two passes. `serve.turnaround` (detached, under the tick) runs
        from collect's return to the next dispatch's return: the host's
        side of the device's idle time between two windows; it closes
        with `admitted`, the refill pass's admissions, beside `slots`
        and `dispatched`. The tick span itself closes with the cycle's
        record (`_tick`, step 8: `slots`, `decoding`, `prefilling`,
        `free`, `queue`, `admitted`, `chunk_steps`, `tokens`,
        `dispatched`), so what a cycle did, and in what state the slots
        stood, is read off the span, cut to any stretch of time, and
        not off a second store. ACROSS
        ticks, each request's detached `serve.request` span (opened at
        submit) accumulates its lifecycle chain — see the Entry fields
        above."""
        with trace.span("serve.tick") as tick_span:
            return self._tick(tick_span)

    def quiesce(self) -> list[Entry]:
        """One normal cycle with the end-of-tick window dispatch
        suppressed: the in-flight window is collected and finalized
        exactly as tick() would, but nothing new launches, leaving the
        engine dispatch-idle. The safe point for operations that
        replay engine programs over the live device state — a paged
        engine's rollout spot-check (`spot_check_params`) needs it
        before candidate weights can be staged. Costs one window of
        decode idleness; the next tick() resumes dispatching."""
        self._skip_dispatch = True
        try:
            with trace.span("serve.tick", quiesce=True) as tick_span:
                return self._tick(tick_span)
        finally:
            self._skip_dispatch = False

    def _tick(self, tick_span) -> list[Entry]:
        now = self.clock()
        done: list[Entry] = []
        # 0. declarative fault drills (default-off): stall/crash/
        #    poison/prefill-error faults scheduled for this cycle fire
        #    before any real work, so the cycle index a fault names is
        #    exactly the cycle it perturbs
        cycle = self._cycle
        self._cycle += 1
        if self.fault_plan is not None:
            self._apply_faults(cycle)
        # 1. queued requests past deadline never occupy a slot
        for e in self.queue.expire(now):
            e.status, e.finish_reason, e.t_done = "timeout", "deadline", now
            self._finish(e, done)
        # 1.5 quarantined entries whose backoff elapsed re-queue at the
        #     head; ones whose deadline died waiting finish honestly
        if self._retrying:
            self._requeue_retries(now, done)
        # 2. interleave policy: refill known-free slots and (chunked
        #    engines) advance pending prefills by at most
        #    max_prefills_per_cycle chunk dispatches — all of it
        #    overlapping the in-flight window's execution. The host
        #    time this section takes is the per-cycle decode STALL a
        #    monolithic prefill inflates, so it is measured and
        #    reported (serve_chunked_prefill_decode_stall_ms).
        #    An engine failure DURING admission/chunking gets the same
        #    cleanup contract as collect()/begin_window() below: every
        #    in-flight entry is failed + released, then the error
        #    propagates — without this, a chunk dispatch that raises
        #    would leave _prefilling populated (with caches already
        #    donated to the dead dispatch) and wedge every later tick
        t_pf = self.clock()
        # naming_compiles: when the compile watchdog (observe/profile)
        # is armed, any XLA compile the admission path triggers — the
        # no-recompile contract says NONE after warmup — is recorded
        # under this name; with no watchdog it is the shared no-op
        # handle (one module-global read, same cost class as a
        # disabled trace span)
        with trace.span("serve.admit") as _sp, \
                prof.naming_compiles("serve.admit"):
            try:
                admitted = self._admit_free_slots()
                chunk_steps = (self._step_prefills(done) if self._chunked
                               else 0)
            except Exception as e:
                self._failed.extend(done)
                self._abort_running(e)
                raise
            _sp.set(admitted=admitted, chunk_steps=chunk_steps)
        prefill_stall_s = self.clock() - t_pf
        # 3. collect the in-flight window; recycle on EOS / budget.
        #    Only the recycle decisions happen here — per-token
        #    bookkeeping is deferred past the next dispatch (step 6) so
        #    the device never idles behind host accounting.
        #    An engine failure (device OOM, poisoned program, runtime
        #    loss) must not leak the in-flight slots: every running
        #    entry is failed + released, THEN the error propagates —
        #    the queue stays serviceable for a caller that recovers
        with trace.span("serve.collect") as _sp:
            try:
                out = self.engine.collect()
            except Exception as e:
                # step-1 expiries were already finalized into `done`,
                # which this raise would otherwise discard — surface
                # them through pop_failed alongside the aborted entries
                self._failed.extend(done)
                self._abort_running(e)
                raise
            _sp.set(slots=len(out),
                    tokens=sum(len(t) for t in out.values()))
            # dispatch accounting happens HERE, at collect, not at
            # dispatch: a dispatch aborted mid-flight (engine failure,
            # crash drill) never lands tokens, so counting it would
            # permanently skew tokens-per-dispatch and break the
            # "spec events == verify dispatches" invariant. A window
            # over a non-empty running set always returns rows, so
            # `out or spec` detects exactly the collected dispatches.
            spec = getattr(self.engine, "last_spec", None)
            # a dispatch was collected: from here to the next one's
            # return the device has nothing to run (detached: the
            # refill / propose / window spans below stay children of
            # the tick and split it; the dispatch, the decision not to
            # dispatch and the failure paths each close it)
            turnaround = (trace.start_span("serve.turnaround",
                                           parent=tick_span.span_id)
                          if out or spec else None)
            if (out or spec) and self.metrics:
                self.metrics.on_dispatch("verify" if spec else "window")
            # a collected VERIFY reports its accept bookkeeping
            # (drafted/accepted/emitted, fetched with the tokens)
            if spec and self.metrics:
                self.metrics.on_spec(**spec)
            # a collected window of a model with expert layers reports
            # what its held experts were sent (fetched with the tokens)
            moe_stats = getattr(self.engine, "last_moe", None)
            if moe_stats is not None and self.metrics:
                self.metrics.on_moe(**moe_stats)
            # a collected contiguous window reports how far its
            # attention read (an on-device count, fetched with the tokens)
            dsa = getattr(self.engine, "last_dsa", None)
            if dsa is not None and self.metrics:
                self.metrics.on_dsa(**dsa)
            attn_rows = getattr(self.engine, "last_attn_rows", None)
            if attn_rows is not None and self.metrics:
                self.metrics.on_attn_rows(*attn_rows)
        t_now = self.clock()
        got: list[tuple[Entry, list]] = []
        finished: list[Entry] = []
        for slot, toks in out.items():
            e = self._running.get(slot)
            if e is None:            # cancelled while the window flew
                continue
            got.append((e, toks))
            if self.engine.finished(slot):
                self.engine.release(slot)
                del self._running[slot]
                finished.append(e)
        # 3.5 per-window slot health: quarantine poisoned slots (and
        #     drop their just-collected tokens) BEFORE the next window
        #     dispatches — the request recovers, the server keeps
        #     serving every other slot
        if self.health_checks and self._running:
            got = self._check_slot_health(now, got, done)
        # 4. running requests past deadline are cancelled mid-generation
        #    (after collect, so the partial tokens reach the result);
        #    prefilling requests past deadline drop their partial chunks
        #    and free the reserved slot immediately
        cancelled: list[Entry] = []
        for slot, e in list(self._running.items()):
            if e.deadline is not None and now >= e.deadline:
                self.engine.release(slot)
                del self._running[slot]
                cancelled.append(e)
        for slot, e in list(self._prefilling.items()):
            if e.deadline is not None and now >= e.deadline:
                self.engine.cancel_prefill(slot)
                del self._prefilling[slot]
                cancelled.append(e)
        # 5. second admission pass: slots freed by the JUST-collected
        #    window refill before the next window dispatches, so a
        #    recycle costs one window of idleness, not two. This pass's
        #    prefill dispatches sit squarely in the device-idle gap, so
        #    its host time joins the measured decode stall (on a
        #    monolithic engine THIS is where recycle-refill prefills
        #    land)
        t_pf2 = self.clock()
        try:
            with trace.span("serve.refill") as _sp:
                n2 = self._admit_free_slots()
                _sp.set(admitted=n2)
            admitted += n2
        except Exception as e:
            self._end_turnaround(turnaround, False, e)
            # same salvage as a begin_window failure: the entries
            # the just-collected window completed are real results
            # — finalize them (and the step-1 expiries) into the
            # pop_failed channel before aborting the rest
            self._finalize_window(got, finished, cancelled, t_now,
                                  now, self._failed)
            self._failed.extend(done)
            self._abort_running(e)
            raise
        prefill_stall_s += self.clock() - t_pf2
        # 5.5 paged engines: grow page grants so every running slot
        #     can emit the next dispatch's worth of tokens; slots the
        #     pool cannot cover even after prefix-cache reclaim are
        #     quarantined NOW (retry or honest finish — never a
        #     dispatch that would decode blind past its last page).
        #     Their just-collected tokens are dropped like a health
        #     quarantine's: a retry restarts from the prompt and
        #     re-derives the exact stream.
        if self._running:
            need = self.window
            if self._spec:
                need = max(need, self.engine.draft_k + 1)
            starved = self.engine.ensure_decode_room(need)
            if starved:
                self._page_pressure = True
                on_exh = (getattr(self.metrics, "on_page_exhausted",
                                  None) if self.metrics else None)
                quarantined = set()
                for slot in starved:
                    e = self._running.pop(slot, None)
                    if e is None:
                        continue
                    if on_exh is not None:
                        on_exh(rid=e.rid, needed=need)
                    self.engine.release(slot)
                    quarantined.add(id(e))
                    self._quarantine(e, "page_exhausted", now, done)
                got = [(e, t) for e, t in got
                       if id(e) not in quarantined]
        # 6. dispatch the next window over every occupied slot — the
        #    plain fused window, or (speculative mode, when the
        #    drafter proposed and every running slot has verify room)
        #    ONE draft-and-verify dispatch emitting up to draft_k + 1
        #    tokens per slot
        n_slots = self.engine.n_slots
        decoding, prefilling = len(self._running), len(self._prefilling)
        queued = len(self.queue)
        occupancy = decoding / n_slots
        dispatched = 0
        if self._running and not self._skip_dispatch:
            try:
                proposal = (self._propose_drafts(got) if self._spec
                            else None)
                # the spans cover the (async) DISPATCH — device
                # execution overlaps the deferred bookkeeping below and
                # is paid for inside the NEXT tick's serve.collect
                if proposal is not None:
                    drafts, vlive, proposed = proposal
                    with trace.span("serve.verify",
                                    k=self.engine.draft_k,
                                    slots=int(vlive.sum()),
                                    hits=int(proposed.sum())) as _wsp:
                        if trace.get_tracer() is not None:
                            _wsp.set(rids=[e.rid for e
                                           in self._running.values()])
                        self.engine.begin_verify(drafts, vlive,
                                                 proposed)
                else:
                    with trace.span("serve.window", window=self.window,
                                    slots=len(self._running)) as _wsp:
                        if trace.get_tracer() is not None:
                            # the decode-window leg of each rid's
                            # lifecycle chain — the list is built only
                            # when a tracer is armed (disabled-path
                            # cost stays one global read)
                            _wsp.set(rids=[e.rid for e
                                           in self._running.values()])
                        self.engine.begin_window(self.window)
                dispatched = 1
                self._end_turnaround(turnaround, True, admitted=n2)
            except Exception as e:
                self._end_turnaround(turnaround, False, e, admitted=n2)
                # entries the just-collected window COMPLETED (EOS/
                # budget/deadline) are real results, not casualties:
                # finalize them with their true statuses — plus the
                # step-1 expiries — into the pop_failed channel this
                # raise would otherwise discard, then abort the rest
                self._finalize_window(got, finished, cancelled, t_now,
                                      now, self._failed)
                self._failed.extend(done)
                self._abort_running(e)
                raise
        else:
            self._end_turnaround(turnaround, False, admitted=n2)
        # 7. deferred bookkeeping — runs WHILE the new window computes.
        #    Cycles that only admitted/prefilled (nothing decoding yet —
        #    e.g. a long prompt's chunk-by-chunk admission) STILL record:
        #    those are exactly the cycles whose stall the
        #    serve_prefill_stall_* metric exists to expose; only truly
        #    empty drain ticks are skipped.
        emitted = self._finalize_window(got, finished, cancelled, t_now,
                                        now, done)
        # brownout runs EVERY cycle (drain ticks included — recovery
        # hysteresis needs to see the queue empty out); page
        # exhaustion joins the SLO/queue signals so a pool running dry
        # degrades the server instead of wedging admissions silently
        page_pressure, self._page_pressure = self._page_pressure, False
        if self.brownout is not None:
            self.brownout.evaluate(queue_depth=len(self.queue),
                                   pressure=page_pressure)
        # per-tenant brownouts run every cycle like the global one
        # (drain ticks included — recovery hysteresis needs to watch
        # each tenant's queue empty out), each fed only ITS tenant's
        # queue depth and ttft:<name> SLO; one tenant escalating
        # leaves its neighbors' controllers at normal (gated by test)
        if self.tenancy is not None:
            depths: dict[str, int] = {}
            for e in self.queue.entries():
                if e.tenant is not None:
                    depths[e.tenant] = depths.get(e.tenant, 0) + 1
            for name, bc in self.tenancy.brownouts.items():
                bc.evaluate(queue_depth=depths.get(name, 0))
            self.tenancy.evaluate()
            if self.metrics:
                slots_used, pages_used = self._tenant_residency()
                on_tc = getattr(self.metrics, "on_tenant_cycle", None)
                if on_tc is not None:
                    on_tc(self.tenancy.names(), depths=depths,
                          slots=slots_used, pages=pages_used)
        if (self._running or admitted or chunk_steps) and self.metrics:
            self.metrics.on_cycle(queue_depth=len(self.queue),
                                  occupancy=occupancy, tokens=emitted,
                                  prefill_s=prefill_stall_s)
            on_pages = getattr(self.metrics, "on_pages", None)
            stats_fn = getattr(self.engine, "page_stats", None)
            if on_pages is not None and stats_fn is not None:
                stats = stats_fn()
                if stats is not None:
                    on_pages(**stats)
            # compiles observed via jit cache-size deltas: after warmup
            # this total must never move (the no-recompile contract);
            # when it does, the registry counter says exactly when
            on_jit = getattr(self.metrics, "on_jit_cache", None)
            sizes = getattr(self.engine, "cache_sizes", None)
            if on_jit is not None and sizes is not None:
                on_jit(sum(sizes().values()))
        # 8. the cycle's record, on its span (ints only; a tick that
        #    raised closed without it): the slots' states and the queue
        #    as they stood at the dispatch decision, where `occupancy`
        #    is taken, and the cycle's work. `free` is what neither
        #    decodes nor is reserved BY THE SCHEDULER'S OWN BOOKS: a
        #    slot released this cycle while its last window flew counts
        #    as free here, a cycle before the engine hands it out again
        tick_span.set(slots=n_slots, decoding=decoding,
                      prefilling=prefilling,
                      free=n_slots - decoding - prefilling, queue=queued,
                      admitted=admitted, chunk_steps=chunk_steps,
                      tokens=emitted, dispatched=dispatched)
        return done

    def _end_turnaround(self, span, dispatched: bool, error=None, *,
                        admitted: int = 0) -> None:
        """Close a tick's `serve.turnaround` (None: no window was
        collected, so nothing was opened). `admitted`: the refill
        pass's admissions, whose host work lies inside the span."""
        if span is None:
            return
        if error is not None:
            span.set(error=type(error).__name__)
        span.close(slots=len(self._running), dispatched=dispatched,
                   admitted=admitted)

    def _propose_drafts(self, got):
        """The speculative policy pass — pure host work in the
        device-idle gap before the next dispatch. Builds each running
        slot's FULL stream (prompt + bookkept tokens + this cycle's
        just-collected window, exactly the device state the next
        dispatch continues from), asks the drafter for k-token
        proposals, and returns (drafts [S, k], vlive [S],
        proposed [S]) for a verify dispatch — `proposed` marking the
        rows with a REAL proposal, so the engine's accept ledger
        scores speculation undiluted by ride-alongs — or None to fall
        back to the plain fused window, bit-identically, when:

        - no slot proposed (verifying nothing but bonus picks emits
          one token per slot — strictly worse than a W-token window
          on adversarially unpredictable traffic), or
        - ANY running slot lacks verify room (`engine.spec_room`): it
          would emit nothing while its neighbors speculate. Such a
          slot is within draft_k + 1 tokens of its cache edge — and
          admission bounds prompt + budget by t_max, so it is about
          to finish; the fallback is brief by construction.

        Slots that have room but no proposal still participate
        (vlive) with zeroed drafts: a verify row whose drafts all
        miss emits exactly the one token a window step would.

        Drafters advertising `propose_batched` (the learned
        models/draft_lm.DraftLM, ChainedDrafter wrapping one) get ONE
        call covering every running slot — the engine-resident path
        dispatches a single jitted propose program for the whole
        batch. Host drafters keep the per-slot scan. Either way, every
        proposal flows through the `_check_proposal` choke point, and
        the wall time of the whole drafting pass accrues to
        `propose_seconds` (summary()'s `serve_spec_propose_s`)."""
        eng = self.engine
        k = eng.draft_k
        # room check FIRST, across every slot: one slot without room
        # vetoes the whole verify, so drafting before knowing that
        # would throw completed history scans away
        for slot in self._running:
            if not eng.spec_room(slot):
                return None
        just = {id(e): t for e, t in got}
        drafts = np.zeros((eng.n_slots, k), np.int32)
        vlive = np.zeros(eng.n_slots, bool)
        proposed = np.zeros(eng.n_slots, bool)
        slots, hists = [], []
        for slot, e in self._running.items():
            vlive[slot] = True
            slots.append(slot)
            hists.append(np.concatenate([
                np.asarray(e.prompt, np.int64).ravel(),
                np.asarray(e.tokens + just.get(id(e), []), np.int64)]))
        t0 = self.clock()
        batched = getattr(self.drafter, "propose_batched", None)
        if batched is not None:
            props = batched(eng, slots, hists)
        else:
            props = {s: self.drafter.propose(h)
                     for s, h in zip(slots, hists)}
        dt = self.clock() - t0
        self.propose_seconds += dt
        if self.metrics:
            on_prop = getattr(self.metrics, "on_propose", None)
            if on_prop is not None:
                on_prop(dt)
        for slot in slots:
            prop = self._check_proposal(props.get(slot), k)
            if prop is None:
                continue
            drafts[slot] = prop
            proposed[slot] = True
        if not proposed.any():
            return None
        return drafts, vlive, proposed

    def _check_proposal(self, prop, k: int):
        """The ONE validation choke point between any `propose()`
        return and `begin_verify`: a malformed proposal raises a
        teaching error here, naming the drafter class and the
        contract, instead of flowing raw into the verify dispatch
        (where a float dtype jit-misses a new program, a 2-D shape
        trips an opaque reshape, and an out-of-vocab id is silently
        CLAMPED by the embedding gather — verified as a different
        token than proposed). None passes through: it is the
        contract's honest nothing-to-verify answer."""
        if prop is None:
            return None
        name = type(self.drafter).__name__
        contract = (f"the models/draft.py contract: propose(history) "
                    f"-> np.ndarray [k={k}] integer token ids in "
                    f"[0, {self.engine.vocab}), or None")
        arr = np.asarray(prop)
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"{name}.propose returned dtype {arr.dtype}: draft "
                f"tokens are ids the verify program compares against "
                f"the target's own integer picks — {contract}")
        if arr.ndim != 1:
            raise ValueError(
                f"{name}.propose returned shape {tuple(arr.shape)}: "
                f"the verify program takes ONE flat row of drafts per "
                f"slot — {contract}")
        if arr.shape[0] != k:
            raise ValueError(
                f"{name}.propose returned {arr.shape[0]} tokens; the "
                f"verify program is compiled at exactly k={k} — "
                f"{contract}")
        vocab = self.engine.vocab
        if (arr < 0).any() or (arr >= vocab).any():
            bad = arr[(arr < 0) | (arr >= vocab)][0]
            raise ValueError(
                f"{name}.propose returned out-of-vocab id {int(bad)} "
                f"(vocab is {vocab}): the verify embedding gather "
                f"would silently clamp it and accept-check a "
                f"DIFFERENT token than proposed — {contract}")
        return arr.astype(np.int32)

    def drain(self) -> list[Entry]:
        """Tick until every queued and running request has finished."""
        done = []
        while not self.idle():
            done.extend(self.tick())
        return done

    # -- drain-and-migrate (elastic scale-down / SIGTERM) ----------------

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Enter drain mode: every later submit refuses with the honest
        terminal ``shed`` status (stop admitting NEW work) while
        everything already accepted keeps ticking to completion —
        unless the caller moves it off first (`drain_pending` for
        queued work, `export_running` for mid-decode slots). Sticky for
        the scheduler's life: a draining replica never re-opens (the
        cluster's live→draining→dead state machine is forward-only)."""
        self._draining = True

    def running_ids(self) -> list[str]:
        """Request ids currently DECODING in a slot (not queued, not
        prefilling) — the candidates for mid-decode migration."""
        return [e.rid for e in self._running.values()]

    def export_running(self, rid: str):
        """Detach one RUNNING request for mid-decode migration:
        returns ``(entry, snapshot)`` — the live Entry itself (its
        emitted tokens, timestamps, spans and identity travel with it)
        plus the engine slot's packed device snapshot
        (`SlotEngine.export_slot`). The slot is released WITHOUT
        finishing the entry: no Result is produced and the journal
        deliberately records NOTHING here — the source journal's
        still-open submit covers the export→import gap, so a crash
        inside it replays the request from this WAL, bit-identically by
        the serial-parity contract. The caller (the cluster router)
        writes the terminal ``migrated`` finish only after the peer's
        import lands. Needs the engine dispatch-idle — `quiesce()`
        first."""
        for slot, e in self._running.items():
            if e.rid == rid:
                break
        else:
            raise ValueError(f"request {rid!r} is not running here — "
                             f"only decoding slots export "
                             f"(running_ids() lists them)")
        snap = self.engine.export_slot(slot)
        del self._running[slot]
        self.engine.release(slot)
        e.slot = None
        return e, snap

    def import_running(self, entry: "Entry", snap: dict) -> bool:
        """The peer half of a mid-decode migration: claim a free slot,
        re-insert the exported snapshot (`SlotEngine.import_slot`), and
        adopt the Entry as running — its decode resumes on this
        replica's next window, bit-identical to never having moved.
        Returns False (and consumes nothing) when this scheduler cannot
        take it right now (closed, itself draining, or no free slot) —
        the router keeps the snapshot and the source request intact.
        On success the adopted request is journaled as a NORMAL submit
        here, so a crash after this point recovers it from THIS
        replica's WAL."""
        if self._closed or self._draining:
            return False
        free = self.engine.free_slots()
        if not free:
            return False
        slot = free[0]
        self.engine.import_slot(slot, snap, tid=entry.tid)
        entry.slot = slot
        entry.status = "running"
        self._running[slot] = entry
        if self.journal is not None:
            deadline_rel = (None if entry.deadline is None else
                            max(entry.deadline - self.clock(), 0.0))
            self.journal.record_submit(entry, deadline_s=deadline_rel)
        return True

    def drain_pending(self) -> list[Entry]:
        """Pop everything accepted but NOT yet decoding — queued
        entries, retry-backoff waiters, and chunked prefills in
        progress (their partial chunks are discarded: re-prefilling on
        a peer re-derives the exact same stream, so restarting from the
        prompt is the bit-identical move) — for the router to re-place
        on surviving replicas. Each entry resets to pending with no
        slot and its lifecycle spans closed here (re-placement opens a
        fresh chain under the peer's scheduler). Running slots are
        `export_running`'s job."""
        out: list[Entry] = []
        while len(self.queue):
            out.append(self.queue.pop())
        out.extend(self._retrying)
        self._retrying = []
        for slot, e in list(self._prefilling.items()):
            self.engine.cancel_prefill(slot)
            del self._prefilling[slot]
            out.append(e)
        for e in out:
            e.status, e.slot = "pending", None
            e.tokens = []
            e.t_first = e.t_chunk0 = None
            e.chunks = 0
            if e.queue_span is not None:
                e.queue_span.close(migrated=True)
                e.queue_span = None
            if e.span is not None:
                e.span.close(status="migrated", reason="drain")
                e.span = None
        return out

    def pop_failed(self) -> list[Entry]:
        """Entries finalized by a tick that raised, since the last call
        — the caller's hook to turn them into Results after tick()
        re-raised. Holds both the engine-failure casualties
        (status="error") and entries the failed tick had already
        completed normally (EOS/budget/deadline), whose true statuses
        are preserved."""
        out, self._failed = self._failed, []
        return out

    def _finalize_window(self, got, finished, cancelled, t_now, now,
                         sink) -> int:
        """The per-window result bookkeeping (token extension, first-
        token stamps, finish statuses) — one implementation for the
        normal deferred pass AND the engine-failure salvage path, so
        the two cannot drift. Returns the emitted-token count."""
        emitted = 0
        progress = {} if self.journal is not None else None
        for e, toks in got:
            if toks and e.t_first is None:
                e.t_first = t_now
                queue_s, reserved_s, prefill_s = e.phases()
                trace.point(
                    "serve.first_token",
                    parent=(e.span.span_id if e.span is not None
                            else None),
                    rid=e.rid,
                    ttft_ms=_ms(t_now - e.t_submit),
                    queue_ms=_ms(queue_s), reserved_ms=_ms(reserved_s),
                    prefill_ms=_ms(prefill_s), chunks=e.chunks,
                    prompt_len=len(e.prompt))
                if self.metrics:
                    self.metrics.on_first_token(
                        e.rid, t_now - e.t_submit, tenant=e.tenant,
                        queue_s=queue_s, reserved_s=reserved_s,
                        prefill_s=prefill_s)
            e.tokens.extend(toks)
            emitted += len(toks)
            if progress is not None and toks:
                progress[e.rid] = len(e.tokens)
        if progress:
            # one batched (and journal-strided) record per cycle — the
            # per-slot-per-cycle write pattern was the armed clean
            # path's dominant cost
            self.journal.record_progress(progress)
        for e in finished:
            e.status, e.t_done = "ok", t_now
            e.finish_reason = (
                "eos" if (e.eos_id is not None and e.tokens
                          and e.tokens[-1] == e.eos_id)
                else "budget")
            self._finish(e, sink)
        # deadline cancels finish AFTER the token extension above folded
        # in anything the flying window carried
        for e in cancelled:
            e.status, e.finish_reason = "timeout", "deadline"
            e.t_done = now
            self._finish(e, sink)
        return emitted

    def _abort_running(self, exc: Exception) -> None:
        """Engine failure cleanup: mark every in-flight entry failed and
        release its slot so the engine/queue are not wedged when the
        caller survives the re-raised error."""
        now = self.clock()
        detail = f"{type(exc).__name__}: {exc}"
        for slot, e in list(self._running.items()):
            try:
                self.engine.release(slot)
            except Exception:  # noqa: S110 — engine already failed;
                pass           # cleanup must reach every slot regardless
            e.status, e.finish_reason = "error", "error"
            e.error, e.t_done = detail, now
            self._finish(e, self._failed)
        self._running.clear()
        for slot, e in list(self._prefilling.items()):
            try:
                self.engine.cancel_prefill(slot)
            except Exception:  # noqa: S110 — same: reach every slot
                pass
            e.status, e.finish_reason = "error", "error"
            e.error, e.t_done = detail, now
            self._finish(e, self._failed)
        self._prefilling.clear()
        # a window the failed engine still considers in flight would
        # wedge idle()/collect(); the device work is lost either way
        self.engine.abort_window()

    def _finish(self, e: Entry, done: list[Entry]) -> None:
        done.append(e)
        if self.journal is not None:
            self.journal.record_finish(e.rid, e.status,
                                       reason=e.finish_reason)
        # close the lifecycle chain: the queued child first (a no-op if
        # admission already closed it — `expired` only lands on entries
        # that died IN the queue; Span.close applies attrs on the first
        # close only), then the whole serve.request span with the
        # terminal state
        if e.queue_span is not None:
            e.queue_span.close(expired=True)
        if e.span is not None:
            e.span.close(status=e.status, reason=e.finish_reason,
                         tokens=len(e.tokens))
        if self.metrics:
            ttft = (e.t_first - e.t_submit
                    if e.t_first is not None else None)
            decode_s = (e.t_done - e.t_first
                        if e.t_first is not None and e.t_done is not None
                        else 0.0)
            self.metrics.on_finish(
                e.rid, n_tokens=len(e.tokens), ttft_s=ttft,
                decode_s=decode_s,
                reason=(e.finish_reason or e.status), t=e.t_done,
                tenant=e.tenant)
