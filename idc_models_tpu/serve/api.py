"""User-facing serving surface: `Request`/`Result`, the synchronous
`submit()`/`poll()` API, and `run(trace)` trace replay.

`LMServer` composes the three serving layers — `SlotEngine` (device
state machine), `Scheduler` (admission queue, deadlines, interleave,
recycling), `ServingMetrics` (TTFT/throughput/occupancy) — behind the
smallest API that exercises them end to end:

    server = LMServer(params, embed_dim=..., num_heads=...,
                      num_blocks=..., t_max=..., n_slots=4, window=8)
    server.submit(Request(id="a", prompt=(1, 2, 3), max_new_tokens=16))
    while server.poll("a") is None:
        server.step()                  # one scheduler tick
    print(server.poll("a").tokens)

Traces replay real arrival processes without a network frontend:
`poisson_trace` synthesizes open-loop Poisson arrivals (the standard
serving-benchmark arrival model) and `load_trace`/`save_trace` move the
same `(arrival_s, Request)` list through a JSONL file, one request per
line. `run(trace)` replays either kind — by wall clock (`realtime=True`,
the honest TTFT measurement) or as a burst (deterministic tests).
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. `seed` derives the request's PRIVATE
    sampling stream (identical to passing `jax.random.key(seed)` to a
    serial `Generator` call — token parity is per-request, not
    per-batch); `deadline_s` is seconds from submit after which the
    request is dropped (queued) or cancelled mid-generation (running);
    `eos_id` overrides the server default stop token (None = server's,
    -1 = never stop early); `trace_id` labels the request's lifecycle
    spans in exported traces (None = the scheduler assigns a
    process-unique one at submit — it comes back on the Result);
    `tenant` names the registered tenant this request bills against on
    a multi-tenant server (serve/tenancy.py — None = the default
    tenant; an unknown name is a loud caller error)."""
    id: str
    prompt: tuple
    max_new_tokens: int
    eos_id: int | None = None
    seed: int | None = None
    deadline_s: float | None = None
    trace_id: str | None = None
    tenant: str | None = None


@dataclasses.dataclass
class Result:
    """What came back: `tokens` are the GENERATED ids only (prompt not
    echoed), truncated at EOS (inclusive) when one is configured.
    `status` is "ok" (ran to EOS/budget), "timeout" (deadline hit —
    possibly with partial tokens), "rejected" (queue full at submit
    with on_full="reject"), "shed" (refused by the brownout
    controller's shed stage — explicit overload, retry elsewhere/later),
    or "error" (the engine failed mid-flight, or a quarantined slot
    exhausted its retries — `error` carries the detail and `tokens`
    whatever clean prefix was generated). `attempts`/`retried` expose
    the retry policy's work: a request recovered from a poisoned slot
    finishes with attempts > 1 and its output bit-identical to an
    unfaulted run (the engine's serial-parity contract).
    `queue_ms` + `reserved_ms` + `prefill_ms` = `ttft_ms`, the request's
    way to its first token on the scheduler's clock: queued (submit ->
    a slot claimed), reserved (the slot claimed -> its first prefill
    dispatch: behind the prompts admitted before it; 0 on an unchunked
    engine) and prefilling (that dispatch -> first token: its own
    chunks, the cycles between them, the insert and the first window).
    None from the first phase the request never reached. Which one
    dominates says what to add: slots (queue), prefill chunks a cycle
    (reserved), or shorter prompts and chunks (prefill)."""
    id: str
    tokens: list
    status: str
    finish_reason: str | None = None
    ttft_ms: float | None = None
    latency_ms: float | None = None
    error: str | None = None
    # the id stamped on every span of this request's lifecycle chain in
    # an exported trace (serve.request/queued/first_token + rid attrs)
    trace_id: str | None = None
    attempts: int = 1
    retried: bool = False
    # ttft_ms by phase (see the docstring)
    queue_ms: float | None = None
    reserved_ms: float | None = None
    prefill_ms: float | None = None


class LMServer:
    """Continuous-batching server over one parameter tree: an
    `attention_lm`'s (embed_dim / num_heads / num_blocks) or, with
    `spec=` (a `models.lm.ModelSpec`), any model the one serving
    forward expresses. Construction compiles (or reuses from the process-wide cache)
    every program the serve loop touches when `warmup=True`, so the
    first request pays no XLA latency and later requests of ANY prompt
    length/budget compile nothing (gated by test)."""

    def __init__(self, params, *, embed_dim: int | None = None,
                 num_heads: int | None = None,
                 num_blocks: int | None = None, t_max: int,
                 n_slots: int = 4, spec=None,
                 window: int = 8, mesh=None, cache_dtype=None,
                 block_impl: str = "jnp", temperature: float = 0.0,
                 top_k: int | None = None, pad_id: int = 0,
                 eos_id: int | None = None, max_queue_depth: int = 64,
                 max_prefills_per_cycle: int = 1, logger=None,
                 warmup: bool = True, clock=time.monotonic,
                 prefill_chunk: int | None = None,
                 prefix_cache_mb: float = 0.0,
                 kv_dtype: str | None = None, slo=None,
                 retry=None, fault_plan=None,
                 health_checks: bool | None = None, journal=None,
                 brownout=None, prefix_cache=None,
                 spec_decode: bool = False, draft_k: int = 8,
                 draft_order: int = 3, drafter=None,
                 kv_page_size: int | None = None,
                 kv_pages: int | None = None,
                 kv_decode_reserve: int | None = None,
                 registry=None, tenancy=None, partition_rules=None,
                 draft_partition_rules=None, compile_cache=None):
        import jax.numpy as jnp

        from idc_models_tpu.serve.engine import SlotEngine
        from idc_models_tpu.serve.metrics import ServingMetrics
        from idc_models_tpu.serve.prefix_cache import (
            PagedPrefixCache, PrefixCache,
        )
        from idc_models_tpu.serve.scheduler import Scheduler

        # prefix reuse rides the chunk grid: snapshots are taken at
        # chunk boundaries and extended by the chunk program, so the
        # knob implies chunked admission. An EXISTING PrefixCache may be
        # passed instead of a budget — the warm-restart path: a server
        # rebuilt after an engine crash reuses the dead engine's
        # snapshots and recovered requests re-prefill only their
        # uncached suffix (gated by test). With paged KV
        # (kv_page_size/kv_pages) the budget builds a PagedPrefixCache
        # instead — snapshots are refcounted page lists in the pool,
        # and the MB budget converts to pages when the engine binds
        # its allocator.
        paged = kv_page_size is not None or kv_pages is not None
        # everything canary_clone needs to build a config-identical
        # second server over candidate weights (same shapes/mesh ->
        # the process-wide jit cache serves both, zero new compiles)
        self._clone_cfg = dict(
            embed_dim=embed_dim, num_heads=num_heads,
            num_blocks=num_blocks, spec=spec, t_max=t_max,
            n_slots=n_slots,
            window=window, mesh=mesh, cache_dtype=cache_dtype,
            block_impl=block_impl, temperature=temperature,
            top_k=top_k, pad_id=pad_id, eos_id=eos_id,
            max_queue_depth=max_queue_depth,
            max_prefills_per_cycle=max_prefills_per_cycle, clock=clock,
            prefill_chunk=prefill_chunk, kv_dtype=kv_dtype,
            spec_decode=spec_decode, draft_k=draft_k,
            draft_order=draft_order, drafter=drafter,
            draft_partition_rules=draft_partition_rules,
            kv_page_size=kv_page_size,
            kv_pages=kv_pages, kv_decode_reserve=kv_decode_reserve,
            partition_rules=partition_rules,
            compile_cache=compile_cache)
        self._clone_logger = logger
        # compile_cache: a serve.compile_cache.CompileCache — warmup
        # then AOT-loads (or compiles-and-stores) the serve programs
        # from disk, so a replica spin-up on a warmed cache is a
        # deserialize, not an XLA run (cluster elasticity; cloned into
        # canaries via _clone_cfg so a rollout's second server spins
        # warm too)
        self.compile_cache = compile_cache
        # registry: an observe MetricsRegistry for this server's
        # instruments (None = the process-wide default). A multi-
        # replica process (serve/cluster) gives each replica its OWN
        # registry so the serve_* gauges don't stomp each other and
        # each replica's /healthz stays an honest per-replica document.
        self.registry = registry
        if prefix_cache is not None and prefix_cache_mb:
            raise ValueError("pass prefix_cache OR prefix_cache_mb, "
                             "not both")
        if prefix_cache is None and prefix_cache_mb and prefix_cache_mb > 0:
            if prefill_chunk is None:
                raise ValueError("prefix_cache_mb needs prefill_chunk")
            if paged:
                prefix_cache = PagedPrefixCache(
                    prefill_chunk, budget_mb=prefix_cache_mb,
                    logger=logger, registry=registry)
            else:
                prefix_cache = PrefixCache(
                    prefill_chunk, int(prefix_cache_mb * 1024 * 1024),
                    logger=logger, registry=registry)
        # speculative decoding (ISSUE 10): spec_decode compiles the
        # fixed-k verify program into the engine and arms the
        # scheduler's draft-and-verify window mode. The default
        # drafter is n-gram prompt-lookup (models/draft.py) — no
        # second model; pass `drafter` (any object with
        # propose(history) -> k tokens | None) to plug in a draft LM
        if drafter is not None and not spec_decode:
            raise ValueError("a custom drafter needs spec_decode=True")
        if spec_decode and drafter is None:
            from idc_models_tpu.models.draft import NGramDrafter

            drafter = NGramDrafter(draft_k, order=draft_order)
        # a LEARNED drafter (models/draft_lm.DraftLM, or a
        # ChainedDrafter wrapping one) exposes `.learned` — the model
        # handle that arms the engine's device-resident drafter state
        # (per-slot ring caches + the batched propose program); host
        # drafters leave it None and the engine builds spec-off-cheap
        draft_model = getattr(drafter, "learned", None)
        if draft_model is None and draft_partition_rules is not None:
            raise ValueError(
                "draft_partition_rules without a learned drafter: the "
                "rules place models/draft_lm.DraftLM params — pass "
                "drafter=DraftLM(...) (or a ChainedDrafter containing "
                "one), or drop the rules")
        # tenancy (serve/tenancy.py, ISSUE 14): accept a built Tenancy
        # runtime OR a TenantRegistry (built here against THIS model's
        # vocab with the server's logger/registry/clock — adapter
        # shape mismatches fail the build, not the first request)
        if tenancy is not None and hasattr(tenancy, "build"):
            tenancy = tenancy.build(
                vocab=params["head"]["kernel"].shape[1],
                logger=logger, registry=registry, clock=clock)
        self.tenancy = tenancy
        self.engine = SlotEngine(
            params, embed_dim=embed_dim, num_heads=num_heads,
            num_blocks=num_blocks, spec=spec, t_max=t_max,
            n_slots=n_slots, mesh=mesh,
            cache_dtype=(jnp.bfloat16 if cache_dtype is None
                         else cache_dtype),
            block_impl=block_impl, temperature=temperature, top_k=top_k,
            pad_id=pad_id, eos_id=eos_id, prefill_chunk=prefill_chunk,
            prefix_cache=prefix_cache, kv_dtype=kv_dtype,
            draft_k=draft_k if spec_decode else None,
            kv_page_size=kv_page_size, kv_pages=kv_pages,
            kv_decode_reserve=kv_decode_reserve,
            adapter_bank=(tenancy.bank if tenancy is not None
                          else None),
            partition_rules=partition_rules, draft_model=draft_model,
            draft_partition_rules=draft_partition_rules)
        # slo: an optional observe.slo.SLOEngine — the metrics hooks
        # feed its declared objectives (ttft/queue_wait/error_rate) and
        # evaluate burn rates once per scheduler cycle
        self.metrics = ServingMetrics(logger, prefix_cache=prefix_cache,
                                      slo=slo, registry=registry,
                                      tenancy=tenancy)
        # journal: a RequestJournal or a path — the WAL of accepted
        # work a rebuilt server recovers in-flight requests from
        # (resubmit_pending / serve/journal.py)
        if journal is not None and not hasattr(journal, "record_submit"):
            from idc_models_tpu.serve.journal import RequestJournal

            journal = RequestJournal(journal)
        self.journal = journal
        # brownout: a BrownoutController; it degrades the prefix cache
        # first, so hand it ours unless the caller wired its own
        if (brownout is not None and brownout.prefix_cache is None
                and prefix_cache is not None):
            brownout.prefix_cache = prefix_cache
        self.brownout = brownout
        self._fault_plan = fault_plan
        self.scheduler = Scheduler(
            self.engine, window=window, max_queue_depth=max_queue_depth,
            max_prefills_per_cycle=max_prefills_per_cycle,
            metrics=self.metrics, clock=clock, retry=retry,
            fault_plan=fault_plan, health_checks=health_checks,
            journal=journal, brownout=brownout, drafter=drafter,
            tenancy=tenancy)
        self._results: dict[str, Result] = {}
        self._inflight: set[str] = set()
        self.metrics.on_kv_layout(self.engine.kv_bytes_by_kind())
        if warmup:
            self.engine.warmup(window, compile_cache=compile_cache)
        if compile_cache is not None:
            self.metrics.on_compile_cache(compile_cache)

    # -- synchronous API -------------------------------------------------

    def submit(self, request: Request, *,
               parent_span=None) -> bool:
        """Enqueue a request. False = backpressure (queue at max depth);
        raises ValueError for requests that could never be served.
        `parent_span` (a span id) parents this request's serve.request
        span under a caller-owned span — the cluster router passes its
        cluster.request root so the cross-replica export is one tree."""
        from idc_models_tpu.serve.scheduler import Entry

        prior = self._results.get(request.id)
        if ((prior is not None and prior.status != "shed")
                or request.id in self._inflight):
            # includes QUEUED/RUNNING ids: a duplicate in flight would
            # silently overwrite the other's Result at finish. A SHED
            # id is the one exception — the brownout refused it without
            # serving anything, and its docstring tells the client to
            # retry later, so the same id may try again
            raise ValueError(f"request id {request.id!r} already used")
        entry = Entry(
            rid=request.id,
            prompt=np.asarray(request.prompt, np.int32),
            budget=int(request.max_new_tokens),
            eos_id=request.eos_id,
            # integer seeds ride through as-is: the engine derives the
            # key data on the host (identical to jax.random.key(seed))
            rng=request.seed,
            deadline=request.deadline_s,
            trace_id=request.trace_id,
            parent_span=parent_span,
            tenant=request.tenant)
        ok = self.scheduler.submit(entry)
        if not ok:
            if entry.status == "shed":
                # a brownout shed is a TERMINAL outcome, not transient
                # backpressure: record the honest Result so poll()
                # answers for it
                r = _to_result(entry)
                self._results[r.id] = r
                return False
            # backpressure: leave no Result — the caller may retry the
            # same id later
            return False
        # a resubmit after a terminal shed/rejection must not leave the
        # stale Result answering poll() while the request actually
        # queues — poll's contract is None until it finishes
        self._results.pop(request.id, None)
        self._inflight.add(request.id)
        return True

    def close(self) -> None:
        """Shut the server down: submit() afterwards raises
        RuntimeError (the scheduler's close contract) and the request
        journal, if any, is flushed closed. Accepted work can still be
        drained first."""
        self.scheduler.close()
        if self.journal is not None:
            self.journal.close()

    def resubmit_pending(self, journal_path) -> list[str]:
        """Crash recovery: re-admit every request `journal_path` shows
        accepted but unfinished (in original submit order) through the
        NORMAL admission path — chunked prefill and prefix-cache reuse
        included — and return the re-admitted ids. Each recovered
        request keeps its journaled id, seed, deadline, trace_id, and
        tenant tag, and its greedy/seeded output is bit-identical to
        what an uncrashed run would have produced (the engine's
        serial-parity contract; gated by test).

        A journaled request the REBUILT server can never serve — a
        tenant since decommissioned from the registry, a prompt past a
        shrunken t_max — is SKIPPED with a warning instead of aborting
        the whole recovery: one stale entry must not block every other
        tenant's requests from coming back (the entry stays in the
        WAL, so a rerun against a fixed configuration still recovers
        it)."""
        import warnings

        from idc_models_tpu.serve.journal import pending_requests

        out = []
        for req in pending_requests(journal_path):
            try:
                ok = self.submit(req)
            except ValueError as e:
                warnings.warn(
                    f"journal recovery skipped request {req.id!r}: "
                    f"{e} — it remains in the WAL; rerun against a "
                    f"configuration that can serve it",
                    stacklevel=2)
                continue
            if ok:
                out.append(req.id)
        return out

    def _fire_bursts(self) -> None:
        """Inject the fault plan's burst arrivals scheduled for the
        NEXT scheduler cycle — synthetic overload waves, submitted
        through the normal (backpressure/shed-visible) path. Runs once
        per step(), and the cycle counter strictly increments per tick,
        so each burst fires exactly once."""
        cycle = self.scheduler._cycle
        for f in self._fault_plan.bursts_at(cycle):
            self.metrics.on_fault_injected("burst", tick=cycle)
            vocab = self.engine._logits.shape[1]
            for req in self._fault_plan.burst_requests(
                    f, vocab=vocab, t_max=self.engine.t_max):
                self.submit(req)

    def step(self) -> list[Result]:
        """One scheduler tick (admissions + one fused decode window);
        returns the requests that finished on it. If the ENGINE fails
        mid-tick the error propagates, but the in-flight requests are
        first recorded as status="error" Results (slots released, queue
        intact) so poll() answers for them and a recovering caller can
        keep serving."""
        if self._fault_plan is not None:
            self._fire_bursts()
        return self._cycle(self.scheduler.tick)

    def quiesce(self) -> list[Result]:
        """One cycle that collects the in-flight decode window without
        dispatching another (Scheduler.quiesce) — the dispatch-idle
        point a paged engine's rollout spot-check needs. Same
        result/failure bookkeeping as step()."""
        return self._cycle(self.scheduler.quiesce)

    def _cycle(self, tick_fn) -> list[Result]:
        finished = []
        try:
            ticked = tick_fn()
        except Exception:
            for e in self.scheduler.pop_failed():
                r = _to_result(e)
                self._results[r.id] = r
                self._inflight.discard(r.id)
            raise
        for e in ticked:
            r = _to_result(e)
            self._results[r.id] = r
            self._inflight.discard(r.id)
            finished.append(r)
        return finished

    # -- hot weight rollout (ROADMAP 4) ----------------------------------

    def swap_params(self, params) -> None:
        """Promote candidate weights onto THIS server's engine — see
        `SlotEngine.swap_params` for the zero-recompile/zero-drop
        contract. The rollout metrics hook is the caller's job
        (checkpoint/rollout.py owns the state machine)."""
        self.engine.swap_params(params)

    def swap_adapters(self, u, v) -> None:
        """Hot-swap the per-tenant adapter bank — the cheap first rung
        of a rollout (no full-tree placement, no canary needed: the
        base weights are untouched). See `SlotEngine.swap_adapters`
        for the shape contract and the tenant-less teaching error."""
        self.engine.swap_adapters(u, v)

    def canary_clone(self, params, *, registry=None,
                     logger=None) -> "LMServer":
        """A second, config-identical server over CANDIDATE weights —
        the canary a rollout routes a controlled traffic fraction
        onto. Same shapes, mesh, and programs, so the process-wide jit
        cache serves both and construction compiles NOTHING new (the
        cluster tier's N-replicas-one-process pattern).

        Deliberately NOT shared: the prefix cache (its KV snapshots
        were computed under the LIVE weights — resuming them under
        candidate weights would silently mix two models' caches), the
        journal (one WAL system of record; canary requests are
        journaled by the controller against the live server), fault
        plan, brownout, and the metrics registry (a fresh one per
        canary, like cluster replicas, so live gauges are never
        stomped). Tenancy IS shared: quotas and per-tenant SLOs bill
        across both sides of the split."""
        if registry is None:
            from idc_models_tpu.observe.metrics_registry import (
                MetricsRegistry,
            )

            registry = MetricsRegistry()
        return LMServer(
            params, tenancy=self.tenancy, registry=registry,
            logger=self._clone_logger if logger is None else logger,
            **self._clone_cfg)

    def poll(self, rid: str) -> Result | None:
        """The finished Result for `rid`, or None while it is still
        queued/running."""
        return self._results.get(rid)

    def results(self) -> list[Result]:
        """Snapshot of every finished Result so far — what a caller
        salvages when run() is interrupted by an engine crash (the
        in-flight requests were already finalized as error Results by
        the failure cleanup)."""
        return list(self._results.values())

    def drain(self) -> list[Result]:
        """Tick until idle; returns everything that finished."""
        out = []
        while not self.scheduler.idle():
            out.extend(self.step())
        return out

    # -- trace replay ----------------------------------------------------

    def run(self, trace, *, realtime: bool = False,
            on_full: str = "block") -> list[Result]:
        """Replay `[(arrival_s, Request), ...]` and drain. With
        `realtime=True` requests are held until their arrival offset on
        the wall clock (the honest open-loop TTFT measurement); with
        False the trace is replayed as fast as the engine drains it —
        arrival ORDER kept, deterministic for tests. `on_full` is the
        client-side backpressure policy: "block" re-offers the head
        request every tick until the queue accepts it; "reject" records
        a rejected Result and moves on."""
        if on_full not in ("block", "reject"):
            raise ValueError(f"on_full must be 'block' or 'reject', "
                             f"got {on_full!r}")
        trace = sorted(trace, key=lambda tr: tr[0])
        clock = self.scheduler.clock
        t0 = clock()
        out, i = [], 0
        while i < len(trace) or not self.scheduler.idle():
            now = clock() - t0
            while i < len(trace) and (not realtime
                                      or trace[i][0] <= now):
                # in block mode, don't OFFER a request the queue cannot
                # take: every refused submit() counts as a rejection in
                # the metrics, and a head request re-offered for 50
                # ticks is one blocked request, not 50 rejected ones.
                # While the brownout SHEDS, offer anyway — a shed is a
                # terminal answer, not a queue race to wait out.
                shedding = (self.brownout is not None
                            and self.brownout.shedding)
                if (on_full == "block" and not shedding
                        and len(self.scheduler.queue)
                        >= self.scheduler.queue.max_depth):
                    break               # blocked: re-offer next tick
                if self.submit(trace[i][1]):
                    i += 1
                    continue
                shed = self._results.get(trace[i][1].id)
                if shed is not None and shed.status == "shed":
                    out.append(shed)
                    i += 1
                elif on_full == "reject":
                    r = Result(id=trace[i][1].id, tokens=[],
                               status="rejected")
                    self._results[r.id] = r
                    out.append(r)
                    i += 1
                else:
                    break               # blocked: re-offer next tick
            if (realtime and self.scheduler.idle() and i < len(trace)):
                # nothing running and the next arrival is in the future
                time.sleep(min(max(trace[i][0] - (clock() - t0), 0.0),
                               0.005))
                continue
            out.extend(self.step())
        return out

    def summary(self) -> dict:
        return self.metrics.summary()


def _to_result(e) -> Result:
    queue_ms, reserved_ms, prefill_ms = (
        None if s is None else s * 1e3 for s in e.phases())
    return Result(
        id=e.rid, tokens=list(e.tokens), status=e.status,
        finish_reason=e.finish_reason, error=e.error,
        trace_id=e.trace_id, attempts=e.attempts, retried=e.retried,
        ttft_ms=(None if e.t_first is None
                 else (e.t_first - e.t_submit) * 1e3),
        queue_ms=queue_ms, reserved_ms=reserved_ms, prefill_ms=prefill_ms,
        latency_ms=(None if e.t_done is None
                    else (e.t_done - e.t_submit) * 1e3))


# -- traces ---------------------------------------------------------------


def poisson_trace(n_requests: int, *, rate_per_s: float, vocab: int,
                  t_max: int, prompt_lens=(4, 16), budgets=(4, 16),
                  eos_id: int | None = None,
                  deadline_s: float | None = None, seed: int = 0,
                  sampled: bool = False, tenants=None):
    """Synthetic open-loop arrivals: exponential inter-arrival times at
    `rate_per_s`, prompt lengths and budgets uniform over the given
    inclusive ranges (clamped so prompt + budget <= t_max). With
    `sampled=True` each request carries its own seed (for temperature>0
    servers). `tenants` (a sequence of names) tags arrivals round-robin
    for a multi-tenant server — round-robin, not random, so every
    tenant's sub-trace is a deterministic function of the trace alone.
    Returns `[(arrival_s, Request), ...]`."""
    rng = np.random.default_rng(seed)
    t, trace = 0.0, []
    lo_p, hi_p = prompt_lens
    lo_b, hi_b = budgets
    tenants = list(tenants) if tenants else None
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate_per_s))
        p_len = int(rng.integers(lo_p, hi_p + 1))
        p_len = min(p_len, t_max - 1)
        budget = int(rng.integers(lo_b, hi_b + 1))
        budget = min(budget, t_max - p_len)
        prompt = tuple(int(x) for x in rng.integers(0, vocab, p_len))
        trace.append((t, Request(
            id=f"r{i}", prompt=prompt, max_new_tokens=budget,
            eos_id=eos_id, deadline_s=deadline_s,
            seed=(int(rng.integers(0, 2**31)) if sampled else None),
            tenant=(tenants[i % len(tenants)] if tenants else None))))
    return trace


def save_trace(path, trace) -> str:
    """Write `[(arrival_s, Request), ...]` as JSONL, one request per
    line — the interchange format `run`/`load_trace` and the CLI's
    `serve --trace` share."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        for t, r in trace:
            rec = {
                "t": t, "id": r.id, "prompt": list(r.prompt),
                "max_new_tokens": r.max_new_tokens, "eos_id": r.eos_id,
                "seed": r.seed, "deadline_s": r.deadline_s}
            if r.tenant is not None:
                # written only when tagged: untagged traces stay
                # byte-identical to every file this format ever wrote
                rec["tenant"] = r.tenant
            f.write(json.dumps(rec) + "\n")
    return str(path)


def load_trace(path):
    """Read a `save_trace` JSONL file back into `[(t, Request), ...]`."""
    trace = []
    for line in Path(path).read_text().splitlines():
        if not line.strip():
            continue
        d = json.loads(line)
        trace.append((float(d.get("t", 0.0)), Request(
            id=str(d["id"]), prompt=tuple(d["prompt"]),
            max_new_tokens=int(d["max_new_tokens"]),
            eos_id=d.get("eos_id"), seed=d.get("seed"),
            deadline_s=d.get("deadline_s"), tenant=d.get("tenant"))))
    return trace
