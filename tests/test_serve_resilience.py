"""The ISSUE-8 serving resilience layer against its hard contracts:

1. RECOVERY PARITY — greedy/seeded outputs are bit-identical across a
   poisoned-slot quarantine + retry AND across an injected mid-run
   engine crash + journal recovery (the serial `Generator` is the
   oracle, exactly as in tests/test_serve.py). The retry restarts from
   the prompt and the journal re-runs through the normal admission
   path, so the engine's serial-parity contract does all the work —
   these tests gate that the recovery paths actually preserve it.
2. DETERMINISTIC DRILLS — a `ServeFaultPlan` is a pure function of
   (plan, tick), so two runs of the same plan against the same trace
   produce identical failures, recoveries, and outputs.
3. HONEST DEGRADATION — the brownout controller escalates through its
   documented stages under sustained signal, restores with hysteresis,
   and every refusal is an explicit `shed` Result, never a silent drop.

Plus the satellites: submit-after-close raises, serve fault-spec parse
errors teach their own grammar, and prefix-cache warm restart across a
crash + rebuild serves hits that stay bit-identical.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.serve import (
    BrownoutController, InjectedEngineCrash, LMServer, PrefixCache,
    Request, RetryPolicy, ServeFault, ServeFaultPlan, SlotEngine,
    load_journal, parse_serve_fault_spec, pending_requests,
)
from idc_models_tpu.serve.journal import RequestJournal

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _kw():
    return dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                t_max=SEQ, mesh=None, cache_dtype=jnp.float32)


def _serial_tokens(gen, prompt, steps, *, rng=None):
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps, rng=rng)
    return toks.tolist()[0]


# ---------------------------------------------------------------------------
# fault plan + spec grammar
# ---------------------------------------------------------------------------


def test_fault_plan_validation_and_burst_determinism():
    with pytest.raises(ValueError, match="unknown serve fault kind"):
        ServeFault("meteor", 1)
    with pytest.raises(ValueError, match="tick"):
        ServeFault("crash", -1)
    with pytest.raises(ValueError, match="seconds"):
        ServeFault("stall", 1, seconds=0.0)
    with pytest.raises(TypeError, match="ServeFault"):
        ServeFaultPlan(["crash:1"])
    plan = ServeFaultPlan([ServeFault("crash", 4),
                           ServeFault("burst", 2, n=3)], seed=7)
    assert [f.kind for f in plan.at(4)] == ["crash"]
    assert plan.at(2) == []                 # bursts are arrivals
    assert [f.kind for f in plan.bursts_at(2)] == ["burst"]
    assert plan.max_tick == 4
    # burst prompts are a pure function of (seed, tick, i): same plan
    # parameters -> the identical arrival wave, request for request
    plan2 = ServeFaultPlan([ServeFault("burst", 2, n=3)], seed=7)
    a = plan.burst_requests(plan.bursts_at(2)[0], vocab=VOCAB, t_max=SEQ)
    b = plan2.burst_requests(plan2.bursts_at(2)[0], vocab=VOCAB,
                             t_max=SEQ)
    assert [(r.id, r.prompt, r.max_new_tokens) for r in a] \
        == [(r.id, r.prompt, r.max_new_tokens) for r in b]
    assert all(r.id.startswith("!burst-") for r in a)
    # a different seed is a different wave
    c = ServeFaultPlan([ServeFault("burst", 2, n=3)], seed=8)
    assert [r.prompt for r in
            c.burst_requests(c.bursts_at(2)[0], vocab=VOCAB,
                             t_max=SEQ)] != [r.prompt for r in a]


def test_parse_serve_fault_spec_grammar_and_errors():
    """Satellite: every parse failure enumerates the valid kinds and
    shows the grammar — a mistyped drill flag teaches its own syntax."""
    plan = parse_serve_fault_spec(
        "nan_logits:3:1,stall:5-7:0.02,burst:2:16,crash:40", seed=3)
    kinds = sorted((f.kind, f.tick) for f in plan.faults)
    assert kinds == [("burst", 2), ("crash", 40), ("nan_logits", 3),
                     ("stall", 5), ("stall", 6), ("stall", 7)]
    assert plan.seed == 3
    nan = next(f for f in plan.faults if f.kind == "nan_logits")
    assert nan.slot == 1
    assert all(f.seconds == 0.02 for f in plan.faults
               if f.kind == "stall")
    assert next(f for f in plan.faults if f.kind == "burst").n == 16
    # +-joined tick lists
    assert [f.tick for f in
            parse_serve_fault_spec("crash:1+5").faults] == [1, 5]
    for bad, why in [
        ("meteor:3", "unknown fault kind"),
        ("nan_logits", "want kind:ticks"),
        ("crash:2:7", "takes no parameter"),
        ("stall:2:fast", "bad seconds parameter"),
        ("nan_logits:one:0", "bad ticks field"),
        # out-of-range values teach the same way as syntax errors
        ("stall:2:0", "seconds must be > 0"),
        ("burst:2:0", ">= 1"),
        ("nan_logits:3:-2", "slot must be >= 0"),
    ]:
        with pytest.raises(ValueError) as ei:
            parse_serve_fault_spec(bad)
        msg = str(ei.value)
        assert why in msg, (bad, msg)
        # the teaching part: all valid kinds + the grammar, every time
        for kind in ("nan_logits", "garbage_logits", "prefill_error",
                     "stall", "crash", "burst"):
            assert kind in msg, (bad, kind)
        assert "kind:ticks[:param]" in msg


# ---------------------------------------------------------------------------
# slot health + quarantine + retry
# ---------------------------------------------------------------------------


def test_engine_slot_health_codes_and_injection(devices, params):
    eng = SlotEngine(params, n_slots=2, **_kw())
    eng.warmup(2)
    eng.admit(0, (1, 2, 3), 4)
    assert eng.slot_health().tolist() == [0, 0]
    assert eng.slot_invariants_ok(0) and eng.slot_invariants_ok(1)
    eng.inject_slot_fault(0, "nan_logits")
    assert eng.slot_health().tolist()[0] == 1     # nonfinite_logits
    eng.inject_slot_fault(1, "garbage_logits")
    assert eng.slot_health().tolist()[1] == 2     # logit_magnitude
    with pytest.raises(ValueError, match="out of range"):
        eng.inject_slot_fault(9, "nan_logits")
    with pytest.raises(ValueError, match="kind"):
        eng.inject_slot_fault(0, "gremlins")


def test_poisoned_slot_quarantine_retry_bit_identical(devices, params):
    """The acceptance pair: a nan_logits fault poisons a running slot;
    the per-window health check quarantines ONLY that request, the
    retry policy re-admits it, and its final greedy output is
    bit-identical to an unfaulted serial run — while the other slot's
    request streams on untouched."""
    plan = ServeFaultPlan([ServeFault("nan_logits", 1, slot=0)])
    server = LMServer(params, n_slots=2, window=4, fault_plan=plan,
                      retry=RetryPolicy(max_retries=2, backoff_s=0.0),
                      **_kw())
    rng = np.random.default_rng(23)
    reqs = [Request(id=f"r{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + 2 * i)),
                    max_new_tokens=8)
            for i in range(2)]
    server.run([(0.0, r) for r in reqs])
    gen = Generator(params, **_kw())
    r0, r1 = server.poll("r0"), server.poll("r1")
    # the faulted request recovered: retried once, finished ok, output
    # identical to a run where the fault never happened
    assert r0.status == "ok" and r0.retried and r0.attempts == 2
    assert r0.tokens == _serial_tokens(gen, reqs[0].prompt, 8)
    # the innocent bystander never noticed
    assert r1.status == "ok" and not r1.retried and r1.attempts == 1
    assert r1.tokens == _serial_tokens(gen, reqs[1].prompt, 8)
    s = server.summary()
    assert s["serve_slot_faults"] == 1
    assert s["serve_retries"] == 1
    assert s["serve_faults_injected"] == 1


def test_quarantine_without_retry_finishes_honest_error(devices, params):
    """A fault plan with NO retry policy still arms the health checks:
    the poisoned request finishes with an explicit error/slot_fault
    status (never a silent wrong answer) and the server keeps
    serving."""
    plan = ServeFaultPlan([ServeFault("garbage_logits", 1, slot=0)])
    server = LMServer(params, n_slots=1, window=4, fault_plan=plan,
                      **_kw())
    server.run([(0.0, Request(id="a", prompt=(1, 2, 3),
                              max_new_tokens=8))])
    a = server.poll("a")
    assert a.status == "error" and a.finish_reason == "slot_fault"
    assert "logit_magnitude" in a.error and a.attempts == 1
    # still serviceable, still bit-exact
    gen = Generator(params, **_kw())
    server.submit(Request(id="b", prompt=(4, 5), max_new_tokens=5))
    server.drain()
    assert server.poll("b").tokens == _serial_tokens(gen, (4, 5), 5)


def test_retry_exhaustion_and_attempt_accounting(devices, params):
    """A slot poisoned on EVERY window exhausts its bounded retries and
    finishes error/slot_fault with the full attempt count on the
    Result — bounded recovery, not an infinite requeue loop."""
    plan = ServeFaultPlan([ServeFault("nan_logits", t, slot=0)
                           for t in range(1, 40)])
    server = LMServer(params, n_slots=1, window=4, fault_plan=plan,
                      retry=RetryPolicy(max_retries=2, backoff_s=0.0),
                      **_kw())
    server.run([(0.0, Request(id="doomed", prompt=(1, 2, 3),
                              max_new_tokens=6))])
    r = server.poll("doomed")
    assert r.status == "error" and r.finish_reason == "slot_fault"
    assert r.attempts == 3 and r.retried
    assert "attempt 3" in r.error
    assert server.summary()["serve_slot_faults"] == 3


def test_retry_respects_original_deadline(devices, params):
    """A retry whose backoff would land past the request's ORIGINAL
    deadline finishes timeout/deadline immediately instead of burning
    a slot on work the caller already gave up on."""
    now = [0.0]
    plan = ServeFaultPlan([ServeFault("nan_logits", 1, slot=0)])
    server = LMServer(params, n_slots=1, window=4, fault_plan=plan,
                      retry=RetryPolicy(max_retries=3, backoff_s=10.0),
                      clock=lambda: now[0], **_kw())
    server.submit(Request(id="late", prompt=(1, 2), max_new_tokens=8,
                          deadline_s=1.0))
    server.step()                       # admit, first window in flight
    server.step()                       # fault fires -> quarantine
    r = server.poll("late")
    assert r is not None, "deadline-blocked retry should finish now"
    assert r.status == "timeout" and r.finish_reason == "deadline"
    assert not r.retried                # the retry never happened


def test_prefill_error_quarantines_request_not_server(devices, params):
    """An injected prefill-chunk failure with a retry policy armed is
    REQUEST-scoped: the chunking request is quarantined and retried
    (output still bit-identical), nothing else dies."""
    plan = ServeFaultPlan([ServeFault("prefill_error", 0)])
    server = LMServer(params, n_slots=2, window=4, prefill_chunk=4,
                      fault_plan=plan,
                      retry=RetryPolicy(max_retries=1, backoff_s=0.0),
                      **_kw())
    prompt = tuple(range(1, 11))        # 3 chunks of 4
    server.run([(0.0, Request(id="p", prompt=prompt,
                              max_new_tokens=5))])
    r = server.poll("p")
    assert r.status == "ok" and r.retried and r.attempts == 2
    gen = Generator(params, **_kw())
    assert r.tokens == _serial_tokens(gen, prompt, 5)
    assert server.summary()["serve_slot_faults"] == 1


def test_fault_plan_replays_bit_identically(devices, params):
    """Same plan + same trace -> the same failures at the same cycles
    with the same recoveries and the same tokens, across two fresh
    servers (the whole point of declarative, tick-indexed faults)."""
    def one_run():
        plan = parse_serve_fault_spec(
            "nan_logits:1:0,stall:2:0.001,prefill_error:0")
        server = LMServer(params, n_slots=2, window=4, prefill_chunk=4,
                          fault_plan=plan,
                          retry=RetryPolicy(max_retries=2,
                                            backoff_s=0.0), **_kw())
        rng = np.random.default_rng(31)
        reqs = [Request(id=f"d{i}",
                        prompt=tuple(int(x) for x in
                                     rng.integers(0, VOCAB, 5 + 4 * i)),
                        max_new_tokens=6)
                for i in range(3)]
        server.run([(0.0, r) for r in reqs])
        summary = server.summary()
        return ([(r.id, server.poll(r.id).tokens,
                  server.poll(r.id).status, server.poll(r.id).attempts)
                 for r in reqs],
                {k: summary[k] for k in ("serve_slot_faults",
                                         "serve_retries",
                                         "serve_faults_injected")})
    first, second = one_run(), one_run()
    assert first == second


# ---------------------------------------------------------------------------
# journal + crash recovery
# ---------------------------------------------------------------------------


def test_journal_records_and_load_semantics(tmp_path):
    p = tmp_path / "wal.jsonl"

    class _E:
        rid, prompt, budget = "x", np.array([1, 2, 3]), 7
        eos_id, rng, trace_id = 4, 9, "t-1"

    with RequestJournal(p, progress_every=1) as j:
        j.record_submit(_E(), deadline_s=2.5)
        j.record_progress({"x": 3})
        j.record_progress({})                 # empty cycle: no record
        j.record_finish("x", "ok", reason="eos")
    loaded = load_journal(p)
    assert loaded["pending"] == [] and loaded["finished"] == {"x": "ok"}
    assert loaded["progress"] == {"x": 3}
    # an ENGINE-death finish (error/error) is recoverable; a shed or
    # slot_fault error is the request's honest final answer
    with RequestJournal(p) as j:
        j.record_submit(_E(), deadline_s=None)      # re-submit reopens
        j.record_finish("x", "error", reason="error")
    pend = pending_requests(p)
    assert [r.id for r in pend] == ["x"]
    r = pend[0]
    assert r.prompt == (1, 2, 3) and r.max_new_tokens == 7
    assert r.eos_id == 4 and r.seed == 9 and r.deadline_s is None
    assert r.trace_id == "t-1"
    with RequestJournal(p) as j:
        j.record_finish("x", "error", reason="slot_fault")
    assert pending_requests(p) == []
    # a torn WAL is a real error, not something to skip silently
    bad = tmp_path / "torn.jsonl"
    bad.write_text('{"event": "journal_submit", "id": "a"}\n{oops\n')
    with pytest.raises(ValueError, match="line 2"):
        load_journal(bad)
    with pytest.raises(ValueError, match="progress_every"):
        RequestJournal(tmp_path / "x.jsonl", progress_every=0)


def test_journal_progress_batches_and_strides(tmp_path):
    p = tmp_path / "wal.jsonl"
    with RequestJournal(p, progress_every=3) as j:
        for k in range(7):
            j.record_progress({"a": k + 1, "b": 2 * (k + 1)})
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    assert [r["event"] for r in recs] == ["journal_progress"] * 2
    # the stride drops intermediate cycles, never the per-rid mapping
    assert recs[-1]["tokens"] == {"a": 6, "b": 12}
    assert load_journal(p)["progress"] == {"a": 6, "b": 12}


def test_crash_journal_recovery_bit_identical(devices, params, tmp_path):
    """The tentpole acceptance: a hard mid-decode engine crash kills
    the server; a REBUILT server re-admits the journal's in-flight
    requests through the normal path and every request's greedy output
    — finished before or after the crash — is bit-identical to a run
    where the crash never happened."""
    wal = tmp_path / "journal.jsonl"
    plan = ServeFaultPlan([ServeFault("crash", 4)])
    a = LMServer(params, n_slots=2, window=4, fault_plan=plan,
                 journal=wal, **_kw())
    rng = np.random.default_rng(41)
    reqs = [Request(id=f"c{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + i)),
                    max_new_tokens=(4 if i == 0 else 16))
            for i in range(4)]
    with pytest.raises(InjectedEngineCrash):
        a.run([(0.0, r) for r in reqs])
    a.close()
    # c0 (one-window budget) finished before tick 4; the crash turned
    # the in-flight requests into honest error Results
    assert a.poll("c0").status == "ok"
    crashed = [r for r in a.results() if r.status == "error"]
    assert crashed and all("injected engine crash" in r.error
                           for r in crashed)
    # the journal knows exactly what to re-run: everything but c0
    pending = pending_requests(wal)
    assert sorted(r.id for r in pending) == ["c1", "c2", "c3"]

    b = LMServer(params, n_slots=2, window=4, journal=wal, **_kw())
    readmitted = b.resubmit_pending(wal)
    assert sorted(readmitted) == ["c1", "c2", "c3"]
    b.drain()
    b.close()
    gen = Generator(params, **_kw())
    for r in reqs:
        got = b.poll(r.id) or a.poll(r.id)
        assert got.status == "ok", r.id
        assert got.tokens == _serial_tokens(gen, r.prompt,
                                            r.max_new_tokens), r.id
    # recovery was journaled too: a second recovery finds nothing
    assert pending_requests(wal) == []


def test_prefix_cache_warm_restart_after_crash(devices, params,
                                               tmp_path):
    """Satellite: a server rebuilt after a crash can inherit the dead
    engine's prefix cache — recovered requests sharing a cached system
    prefix re-prefill only their suffix (hit-rate > 0) and the hits
    stay bit-identical to full recomputation."""
    wal = tmp_path / "journal.jsonl"
    sys_p = tuple(int(x) for x in
                  np.random.default_rng(43).integers(0, VOCAB, 8))
    reqs = [Request(id=f"w{i}", prompt=sys_p + (i,), max_new_tokens=4)
            for i in range(3)]
    plan = ServeFaultPlan([ServeFault("crash", 3)])
    a = LMServer(params, n_slots=1, window=4, prefill_chunk=8,
                 prefix_cache_mb=16.0, fault_plan=plan, journal=wal,
                 **_kw())
    with pytest.raises(InjectedEngineCrash):
        a.run([(0.0, r) for r in reqs])
    a.close()
    cache = a.engine.prefix_cache
    assert cache.nbytes > 0, "no snapshot survived to warm-restart from"
    hits_at_crash = cache.hits

    with pytest.raises(ValueError, match="prefix_cache OR"):
        LMServer(params, prefill_chunk=8, prefix_cache=cache,
                 prefix_cache_mb=1.0, **_kw())
    b = LMServer(params, n_slots=1, window=4, prefill_chunk=8,
                 prefix_cache=cache, journal=wal, **_kw())
    b.resubmit_pending(wal)
    b.drain()
    b.close()
    assert cache.hits > hits_at_crash, "warm restart never hit"
    gen = Generator(params, **_kw())
    for r in reqs:
        got = b.poll(r.id) or a.poll(r.id)
        assert got.status == "ok", r.id
        assert got.tokens == _serial_tokens(gen, r.prompt, 4), r.id


# ---------------------------------------------------------------------------
# brownout controller
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_brownout_escalates_and_restores_with_hysteresis():
    from idc_models_tpu.observe.metrics_registry import MetricsRegistry

    clk = _FakeClock()
    b = BrownoutController(queue_high=8, queue_low=2, clamp_tokens=4,
                           escalate_dwell_s=1.0, clear_after_s=5.0,
                           clock=clk, registry=MetricsRegistry())
    assert b.stage == 0 and not b.shedding and b.token_clamp is None
    # escalation: one stage per dwell while the signal fires
    assert b.evaluate(queue_depth=10) == 1      # pause_cache_writes
    clk.t = 0.5
    assert b.evaluate(queue_depth=10) == 1      # dwell not elapsed
    clk.t = 1.0
    assert b.evaluate(queue_depth=10) == 2      # clamp_tokens
    assert b.token_clamp == 4 and not b.shedding
    clk.t = 2.0
    assert b.evaluate(queue_depth=10) == 3      # shed
    assert b.shedding and b.max_stage_seen == 3
    clk.t = 3.0
    assert b.evaluate(queue_depth=10) == 3      # already at the top
    # queue below HIGH but above LOW: signal clear, but no clear timer
    clk.t = 4.0
    assert b.evaluate(queue_depth=5) == 3
    clk.t = 20.0
    assert b.evaluate(queue_depth=5) == 3, "restored into live load"
    # below the low watermark: the clear timer starts, one stage per
    # sustained clear_after_s
    clk.t = 21.0
    assert b.evaluate(queue_depth=1) == 3
    clk.t = 26.0
    assert b.evaluate(queue_depth=1) == 2
    clk.t = 27.0
    assert b.evaluate(queue_depth=1) == 2       # not another 5 s yet
    clk.t = 31.0
    assert b.evaluate(queue_depth=1) == 1
    # a re-fire mid-recovery resets the clear timer
    clk.t = 32.0
    assert b.evaluate(queue_depth=9) == 2
    clk.t = 40.0
    b.evaluate(queue_depth=0)
    directions = [t["direction"] for t in b.transitions]
    assert directions.count("escalate") == 4
    assert directions.count("restore") == 2
    assert all(t["stage_name"] in ("normal", "pause_cache_writes",
                                   "clamp_tokens", "shed")
               for t in b.transitions)


def test_brownout_validation_and_prefix_cache_pause():
    from idc_models_tpu.observe.metrics_registry import MetricsRegistry

    with pytest.raises(ValueError, match="at least one signal"):
        BrownoutController(registry=MetricsRegistry())
    with pytest.raises(ValueError, match="queue_low < queue_high"):
        BrownoutController(queue_high=4, queue_low=4,
                           registry=MetricsRegistry())
    with pytest.raises(ValueError, match="clamp_tokens"):
        BrownoutController(queue_high=4, clamp_tokens=0,
                           registry=MetricsRegistry())
    cache = PrefixCache(max_bytes=1 << 20, chunk=8,
                        registry=MetricsRegistry())
    clk = _FakeClock()
    b = BrownoutController(queue_high=2, queue_low=0, clock=clk,
                           escalate_dwell_s=0.0, clear_after_s=1.0,
                           prefix_cache=cache,
                           registry=MetricsRegistry())
    b.evaluate(queue_depth=5)
    assert cache.writes_paused                  # stage 1 side effect
    assert not cache.insert(np.arange(8), caches=(), logits=None)
    clk.t = 10.0
    b.evaluate(queue_depth=0)                   # clear timer starts
    clk.t = 12.0
    b.evaluate(queue_depth=0)                   # sustained clear
    assert b.stage == 0 and not cache.writes_paused


def test_brownout_sheds_submits_and_clamps_budget(devices, params):
    """The server-level loop: a queue-watermark brownout refuses new
    submits with an explicit `shed` Result (poll() answers for it, the
    run completes, nothing hangs) and clamps admitted budgets at stage
    2, with both visible in the summary rollup."""
    clk = _FakeClock()
    b = BrownoutController(queue_high=3, queue_low=0, clamp_tokens=2,
                           escalate_dwell_s=0.0, clear_after_s=1e9,
                           clock=clk)
    server = LMServer(params, n_slots=1, window=4, brownout=b,
                      clock=clk, max_queue_depth=64, **_kw())
    # drive the controller to shed by hand (deterministic), then submit
    for _ in range(3):
        b.evaluate(queue_depth=10)
    assert b.shedding
    assert not server.submit(Request(id="s0", prompt=(1, 2),
                                     max_new_tokens=4))
    shed = server.poll("s0")
    assert shed.status == "shed" and shed.finish_reason == "shed"
    assert shed.tokens == []
    # run() treats a shed as terminal, not backpressure to wait out
    out = server.run([(0.0, Request(id="s1", prompt=(3,),
                                    max_new_tokens=4))])
    assert [r.status for r in out] == ["shed"]
    s = server.summary()
    assert s["serve_shed"] == 2
    # step back to clamp_tokens: admissions get the shortened budget
    b._transition(2, clk(), "test")
    server.submit(Request(id="s2", prompt=(1, 2, 3), max_new_tokens=9))
    server.drain()
    r = server.poll("s2")
    assert r.status == "ok" and len(r.tokens) == 2
    assert server.summary()["serve_clamped"] == 1
    # and the clamped stream is the serial stream, truncated
    gen = Generator(params, **_kw())
    assert r.tokens == _serial_tokens(gen, (1, 2, 3), 2)
    # a SHED id may retry once the brownout clears (the one terminal
    # state that consumed no engine work): the stale shed Result stops
    # answering poll() the moment the resubmit is accepted
    b._transition(0, clk(), "test")
    assert server.submit(Request(id="s0", prompt=(1, 2),
                                 max_new_tokens=3))
    assert server.poll("s0") is None        # queued now, not shed
    server.drain()
    assert server.poll("s0").status == "ok"
    # every OTHER terminal state still refuses id reuse
    with pytest.raises(ValueError, match="already used"):
        server.submit(Request(id="s2", prompt=(1,), max_new_tokens=2))


def test_every_feature_armed_and_no_fault_is_a_clean_run(devices, params,
                                                        tmp_path):
    """The clean-path half of the drills: retry policy, an EMPTY fault
    plan (which arms the per-cycle slot health checks), the journal, a
    brownout controller and a TTFT SLO all on, nothing injected — every
    request finishes ok with its serial stream, no slot is ever
    quarantined, the health codes of a running engine read all-clear,
    and the journal holds nothing pending at drain."""
    from idc_models_tpu.observe import SLO, SLOEngine
    from idc_models_tpu.observe.metrics_registry import MetricsRegistry

    wal = tmp_path / "wal.jsonl"
    server = LMServer(
        params, n_slots=2, window=4, retry=RetryPolicy(max_retries=2),
        fault_plan=ServeFaultPlan([]), journal=str(wal),
        brownout=BrownoutController(queue_high=10_000),
        slo=SLOEngine([SLO.latency("ttft", threshold_s=60.0)],
                      registry=MetricsRegistry()), **_kw())
    assert server.scheduler.health_checks
    gen = Generator(params, **_kw())
    reqs = [Request(id=f"c{i}", prompt=(1 + i, 2, 3),
                    max_new_tokens=5 + i % 3) for i in range(6)]
    results = server.run([(0.0, r) for r in reqs])
    assert len(results) == 6
    for r in reqs:
        got = server.poll(r.id)
        assert got.status == "ok"
        assert got.tokens == _serial_tokens(gen, r.prompt,
                                            r.max_new_tokens), r.id
    s = server.summary()
    assert s["serve_slot_faults"] == 0 and s["serve_retries"] == 0
    assert s["serve_shed"] == 0
    # mid-decode, every slot of the armed engine reads healthy
    for i in range(2):
        server.submit(Request(id=f"w{i}", prompt=(1, 2, 3, 4),
                              max_new_tokens=SEQ - 8))
    server.step()
    server.step()
    eng = server.engine
    assert eng.slot_health().tolist() == [0, 0]
    assert all(eng.slot_invariants_ok(i) for i in range(2))
    server.drain()
    server.close()
    assert pending_requests(str(wal)) == []


def test_burst_fault_floods_and_brownout_sheds(devices, params):
    """End to end: declarative burst arrivals flood the queue, the
    watermark brownout escalates to shed, and every refused request is
    an explicit shed Result — the clean requests still finish ok."""
    plan = ServeFaultPlan([ServeFault("burst", t, n=6, prompt_len=3,
                                      budget=12)
                           for t in range(1, 5)])
    b = BrownoutController(queue_high=6, queue_low=1, clamp_tokens=4,
                           escalate_dwell_s=0.0, clear_after_s=0.02)
    server = LMServer(params, n_slots=2, window=4, fault_plan=plan,
                      brownout=b, max_queue_depth=64, **_kw())
    results = server.run([(0.0, Request(id=f"b{i}", prompt=(1 + i, 2),
                                        max_new_tokens=6))
                          for i in range(4)])
    s = server.summary()
    assert s["serve_faults_injected"] == 4          # the burst ticks
    assert s["serve_shed"] > 0 and b.max_stage_seen == 3
    by_id = {r.id: r for r in results}
    assert all(by_id[f"b{i}"].status in ("ok", "shed")
               for i in range(4))
    assert any(by_id[f"b{i}"].status == "ok" for i in range(4))
    shed_bursts = [r for r in server.results()
                   if r.id.startswith("!burst") and r.status == "shed"]
    assert shed_bursts, "the flood itself never got shed"


# ---------------------------------------------------------------------------
# close() satellite
# ---------------------------------------------------------------------------


def test_submit_after_close_raises(devices, params, tmp_path):
    """Satellite: submit() after close() raises a clean RuntimeError
    instead of enqueueing into a loop nobody will ever tick again —
    and close() flushes the journal."""
    server = LMServer(params, n_slots=1, window=4,
                      journal=tmp_path / "wal.jsonl", **_kw())
    server.submit(Request(id="a", prompt=(1, 2), max_new_tokens=3))
    server.drain()
    server.close()
    with pytest.raises(RuntimeError, match="close"):
        server.submit(Request(id="b", prompt=(3,), max_new_tokens=3))
    # the WAL closed with the finish on disk
    assert load_journal(tmp_path / "wal.jsonl")["finished"] == {
        "a": "ok"}
