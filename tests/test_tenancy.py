"""Multi-tenant serving (serve/tenancy.py, ISSUE 14) against its
contracts:

1. PARITY — a tenant's greedy/seeded stream under MIXED-tenant load is
   bit-identical to the same requests on a single-tenant server, at
   the engine level (SlotEngine + adapter bank, window AND verify
   programs, contiguous AND paged) and the server level (LMServer +
   TenantRegistry). The adapter gather is slot-indexed inside the
   fused programs, so this is parity by construction — these tests
   gate that the construction holds.
2. ZERO RECOMPILATION — tenant arrival patterns are VALUES, not
   shapes: after warmup, any mix of tenants admits with no jit cache
   growth.
3. ISOLATION — per-tenant quotas (slots, queued, KV pages) bound one
   tenant without starving its neighbors (the admission scan skips a
   quota-blocked entry instead of head-of-line blocking everyone),
   per-tenant SLOs breach independently, and a tenant's brownout
   sheds only that tenant.
4. TEACHING ERRORS — unknown tenants, bad quotas, duplicate
   registration, and adapter-shape mismatches fail loudly at build,
   never at the first request.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models.lm import attention_lm
from idc_models_tpu.serve import (
    LMServer, Request, SlotEngine, TenantQuota, TenantRegistry,
)
from idc_models_tpu.serve.journal import RequestJournal, pending_requests
from idc_models_tpu.serve.tenancy import AdapterBank

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2
RANK = 3


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _kw(**over):
    kw = dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
              t_max=SEQ, cache_dtype=jnp.float32)
    kw.update(over)
    return kw


def _adapter(seed, scale=0.5):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, scale, (VOCAB, RANK)).astype(np.float32),
            rng.normal(0, scale, (RANK, VOCAB)).astype(np.float32))


def _bank(*adapters):
    """Stack explicit (u, v) pairs (None = zero rows) into an
    AdapterBank — the engine-level fixture, registry-free."""
    u = np.zeros((len(adapters), VOCAB, RANK), np.float32)
    v = np.zeros((len(adapters), RANK, VOCAB), np.float32)
    for i, a in enumerate(adapters):
        if a is not None:
            u[i], v[i] = a
    return AdapterBank(u=u, v=v, rank=RANK, vocab=VOCAB)


def _registry(*, quotas=None, slos=None, adapters=None):
    reg = TenantRegistry()
    for name in ("acme", "globex"):
        reg.register(
            name,
            adapter=(adapters or {}).get(name),
            quota=(quotas or {}).get(name),
            slo_ttft_p95_ms=(slos or {}).get(name))
    return reg


# -- registry / build teaching errors ----------------------------------


def test_registry_validation_teaching_errors():
    reg = TenantRegistry()
    reg.register("acme")
    with pytest.raises(ValueError, match="already registered"):
        reg.register("acme")
    with pytest.raises(ValueError, match="non-empty string"):
        reg.register("")
    with pytest.raises(ValueError, match="admit nothing ever"):
        TenantQuota(max_resident_slots=0)
    with pytest.raises(ValueError, match="admit nothing ever"):
        TenantQuota(max_queued=-1)
    with pytest.raises(ValueError, match="slo_ttft_p95_ms"):
        reg.register("b", slo_ttft_p95_ms=0)
    with pytest.raises(ValueError, match="TenantQuota"):
        reg.register("c", quota=3)
    with pytest.raises(ValueError, match="no tenants"):
        TenantRegistry().build()
    bad = TenantRegistry(default="ghost")
    bad.register("x")
    with pytest.raises(ValueError, match="default tenant"):
        bad.build()
    built = TenantRegistry()
    built.register("only")
    built.build()
    with pytest.raises(ValueError, match="already built"):
        built.register("late")


def test_adapter_shape_mismatch_rejected_at_build():
    u, v = _adapter(0)
    reg = TenantRegistry()
    with pytest.raises(ValueError, match=r"\(u, v\) pair"):
        reg.register("a", adapter=u)
    with pytest.raises(ValueError, match="transposes"):
        reg.register("a", adapter=(u, v.T))
    reg.register("a", adapter=(u, v))
    rng = np.random.default_rng(9)
    other = (rng.normal(size=(VOCAB, RANK + 2)).astype(np.float32),
             rng.normal(size=(RANK + 2, VOCAB)).astype(np.float32))
    with pytest.raises(ValueError, match="share one \\[V, r\\]"):
        reg.register("b", adapter=other)
    # vocab mismatch surfaces at BUILD against the model's head
    with pytest.raises(ValueError, match="model vocab"):
        reg.build(vocab=VOCAB + 5)


def test_engine_rejects_wrong_vocab_bank_and_bad_tid(params):
    bank = AdapterBank(
        u=np.zeros((2, VOCAB + 1, RANK), np.float32),
        v=np.zeros((2, RANK, VOCAB + 1), np.float32),
        rank=RANK, vocab=VOCAB + 1)
    with pytest.raises(ValueError, match="model vocab"):
        SlotEngine(params, n_slots=2, adapter_bank=bank, **_kw())
    eng = SlotEngine(params, n_slots=2,
                     adapter_bank=_bank(_adapter(0), None), **_kw())
    with pytest.raises(ValueError, match="out of range"):
        eng.admit(0, [1, 2, 3], 4, tid=2)


def test_unknown_tenant_is_a_loud_caller_error(params):
    server = LMServer(params, n_slots=2, tenancy=_registry(), **_kw())
    with pytest.raises(ValueError, match="unknown tenant"):
        server.submit(Request(id="x", prompt=(1, 2), max_new_tokens=2,
                              tenant="ghost"))


# -- parity: engine level (window + verify, contiguous + paged) ---------


def _engine_tokens(eng, prompt, budget, tid, *, rng=None):
    eng.admit(0, prompt, budget, tid=tid, rng=rng)
    out = []
    while not eng.finished(0):
        out.extend(eng.step_window(4).get(0, []))
    eng.release(0)
    return out


def test_engine_mixed_vs_single_tenant_parity_greedy_and_sampled(
        params, devices):
    """The acceptance gate at ENGINE level: tenant A's stream through
    a 2-tenant bank (A = tid 1, gathered) is bit-identical to a
    1-tenant bank's (A = tid 0) — greedy and seeded top-k — and the
    adapter genuinely changes the stream vs the base model."""
    a = _adapter(7)
    mixed = SlotEngine(params, n_slots=2,
                       adapter_bank=_bank(_adapter(3), a), **_kw())
    solo = SlotEngine(params, n_slots=2, adapter_bank=_bank(a),
                      **_kw())
    base = SlotEngine(params, n_slots=2, **_kw())
    prompt = [1, 4, 2, 7, 5]
    want = _engine_tokens(solo, prompt, 8, 0)
    assert _engine_tokens(mixed, prompt, 8, 1) == want
    assert _engine_tokens(base, prompt, 8, 0) != want

    m_s = SlotEngine(params, n_slots=2, temperature=0.9, top_k=5,
                     adapter_bank=_bank(_adapter(3), a), **_kw())
    s_s = SlotEngine(params, n_slots=2, temperature=0.9, top_k=5,
                     adapter_bank=_bank(a), **_kw())
    assert (_engine_tokens(m_s, prompt, 8, 1, rng=123)
            == _engine_tokens(s_s, prompt, 8, 0, rng=123))


def test_engine_verify_program_applies_adapter_identically(params):
    """The VERIFY program's adapter path: same scripted drafts into a
    mixed-bank engine (tid 1) and a solo-bank engine (tid 0) emit
    bit-identical accept/bonus tokens."""
    a = _adapter(11)
    outs = []
    for bank, tid in ((_bank(_adapter(5), a), 1), (_bank(a), 0)):
        eng = SlotEngine(params, n_slots=2, draft_k=3,
                         adapter_bank=bank, **_kw())
        eng.admit(0, [2, 6, 1], 10, tid=tid)
        drafts = np.zeros((2, 3), np.int32)
        drafts[0] = [3, 1, 4]
        vlive = np.array([True, False])
        eng.begin_verify(drafts, vlive)
        outs.append(eng.collect()[0])
    assert outs[0] == outs[1] and outs[0]


def test_server_mixed_vs_single_tenant_parity_paged(params, devices):
    """Server-level parity on the PAGED engine: mixed two-tenant load
    vs a single-tenant paged server, bit-identical per request (the
    PR 11 one-device paged==contiguous contract composes with the
    adapter gather)."""
    a, g = _adapter(21), _adapter(22)
    paged = dict(prefill_chunk=4, kv_page_size=4, kv_pages=24)
    mixed = LMServer(
        params, n_slots=3, window=4,
        tenancy=_registry(adapters={"acme": a, "globex": g}),
        **_kw(), **paged)
    reqs = [Request(id=f"r{i}",
                    prompt=tuple([1 + i, 2, 3 + i, 4, 5][:3 + i % 3]),
                    max_new_tokens=5 + i % 4,
                    tenant=("acme" if i % 2 else "globex"))
            for i in range(6)]
    got = {r.id: r.tokens for r in mixed.run([(0.0, r) for r in reqs])}
    for name, adapter in (("acme", a), ("globex", g)):
        reg = TenantRegistry()
        reg.register(name, adapter=adapter)
        solo = LMServer(params, n_slots=3, window=4, tenancy=reg,
                        **_kw(), **paged)
        for r in reqs:
            if r.tenant != name:
                continue
            want = solo.run([(0.0, Request(
                id=r.id, prompt=r.prompt,
                max_new_tokens=r.max_new_tokens, tenant=name))])[0]
            assert got[r.id] == want.tokens, (r.id, got[r.id],
                                              want.tokens)


def test_zero_recompile_across_tenant_arrival_patterns(params):
    """The acceptance gate: after warmup + a first mixed wave, ANY
    tenant arrival pattern admits with zero jit cache growth — tenant
    ids are traced values, never shapes."""
    server = LMServer(
        params, n_slots=3, window=4,
        tenancy=_registry(adapters={"acme": _adapter(1),
                                    "globex": _adapter(2)}),
        **_kw())
    rng = np.random.default_rng(3)

    def wave(tag, tenants):
        return [(0.0, Request(
            id=f"{tag}{i}",
            prompt=tuple(int(x) for x in
                         rng.integers(0, VOCAB, 3 + i % 5)),
            max_new_tokens=3 + i % 4, tenant=t))
            for i, t in enumerate(tenants)]

    server.run(wave("w", ["acme", "globex"]))
    sizes = server.engine.cache_sizes()
    # bursts of one tenant, alternation, reversed mixes — all values
    server.run(wave("a", ["acme"] * 4))
    server.run(wave("b", ["globex"] * 4))
    server.run(wave("c", ["globex", "acme", "acme", "globex"]))
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)


# -- isolation: quotas, SLOs, per-tenant brownout -----------------------


def test_slot_quota_caps_tenant_without_starving_neighbor(params):
    """acme is capped at 1 resident slot on a 3-slot engine; a burst
    of acme work must never hold >1 slot while globex fills the rest
    — the admission scan skips the quota-blocked backlog instead of
    head-of-line blocking it."""
    server = LMServer(
        params, n_slots=3, window=4,
        tenancy=_registry(
            quotas={"acme": TenantQuota(max_resident_slots=1)}),
        **_kw())
    reqs = ([Request(id=f"a{i}", prompt=(1, 2, 3), max_new_tokens=8,
                     tenant="acme") for i in range(4)]
            + [Request(id=f"g{i}", prompt=(4, 5), max_new_tokens=8,
                       tenant="globex") for i in range(4)])
    for r in reqs:
        assert server.submit(r)
    peak_acme = 0
    while not server.scheduler.idle():
        server.step()
        slots, _ = server.scheduler._tenant_residency()
        peak_acme = max(peak_acme, slots.get("acme", 0))
        # with acme capped at 1, globex must reach >= 2 of 3 slots
    assert peak_acme == 1
    assert all(server.poll(r.id).status == "ok" for r in reqs)
    # quotas released everything at drain
    slots, pages = server.scheduler._tenant_residency()
    assert slots == {} and pages == {}


def test_queue_quota_rejects_flood_without_touching_neighbors(params):
    server = LMServer(
        params, n_slots=1, window=4,
        tenancy=_registry(quotas={"acme": TenantQuota(max_queued=2)}),
        **_kw())
    acc = [server.submit(Request(id=f"a{i}", prompt=(1, 2),
                                 max_new_tokens=4, tenant="acme"))
           for i in range(6)]
    # the first fills the free slot path... all queue until a step;
    # at most 2 queued acme accepted beyond, rest refused
    assert sum(acc) < 6 and acc.count(False) >= 3
    # globex is untouched by acme's refusals
    assert server.submit(Request(id="g0", prompt=(3,),
                                 max_new_tokens=4, tenant="globex"))
    server.drain()
    s = server.summary()["serve_tenants"]
    assert s["acme"]["quota_rejections"] == acc.count(False)
    assert s["globex"]["quota_rejections"] == 0
    assert s["globex"]["requests"] == 1


def test_flood_is_refused_by_quota_compiles_nothing_and_spares_neighbor(
        params):
    """The noisy-neighbor drill by its counts: acme's background traffic
    plus a flood far past its quota (2 of 6 slots, 8 queued) arrives
    beside globex's trace and is replayed by a client that takes a
    refusal for an answer. The flood is refused at acme's own quota,
    every globex request finishes ok, no globex request is refused, and
    the whole mixed run compiles nothing after the warm wave (tenant
    mixes, refusals and quota stalls are values, not shapes)."""
    server = LMServer(
        params, n_slots=6, window=4, max_prefills_per_cycle=6,
        tenancy=_registry(quotas={"acme": TenantQuota(
            max_resident_slots=2, max_queued=8)}), **_kw())
    rng = np.random.default_rng(5)

    def reqs(tag, tenant, n, budget):
        return [Request(id=f"{tag}{i}",
                        prompt=tuple(int(x) for x in
                                     rng.integers(0, VOCAB, 3 + i % 4)),
                        max_new_tokens=budget, tenant=tenant)
                for i in range(n)]

    server.run([(0.0, r) for r in reqs("w", "acme", 2, 4)
                + reqs("v", "globex", 2, 4)])
    sizes = server.engine.cache_sizes()
    globex = reqs("b", "globex", 12, 6)
    flood = reqs("a", "acme", 6, 6) + reqs("f", "acme", 40, SEQ // 2)
    # the flood first: every globex request arrives behind it
    results = server.run([(0.0, r) for r in flood + globex],
                         on_full="reject")
    assert server.engine.cache_sizes() == sizes, (
        server.engine.cache_sizes(), sizes)
    by_id = {r.id: r for r in results}
    assert all(by_id[r.id].status == "ok" for r in globex)
    refused = [r for r in results if r.status == "rejected"]
    assert refused and all(r.id[0] in "af" for r in refused)
    s = server.summary()["serve_tenants"]
    assert s["acme"]["quota_rejections"] == len(refused)
    assert s["globex"]["quota_rejections"] == 0
    assert s["globex"]["requests"] == len(globex) + 2
    slots, pages = server.scheduler._tenant_residency()
    assert slots == {} and pages == {}      # all released at drain


def test_page_quota_bounds_tenant_kv_reservations(params):
    """Paged engine: acme's admissions may hold at most 3 pool pages;
    its second request waits for its own releases while globex keeps
    admitting from the same pool."""
    server = LMServer(
        params, n_slots=3, window=4, prefill_chunk=4, kv_page_size=4,
        kv_pages=24,
        tenancy=_registry(
            quotas={"acme": TenantQuota(kv_page_budget=3)}),
        **_kw())
    # each request: prompt 4 + budget 8 -> 12 tokens -> 3 pages
    reqs = ([Request(id=f"a{i}", prompt=(1, 2, 3, 4),
                     max_new_tokens=8, tenant="acme")
             for i in range(3)]
            + [Request(id=f"g{i}", prompt=(5, 6, 7, 8),
                       max_new_tokens=8, tenant="globex")
               for i in range(3)])
    for r in reqs:
        assert server.submit(r)
    peak_acme_pages = 0
    while not server.scheduler.idle():
        server.step()
        _, pages = server.scheduler._tenant_residency()
        peak_acme_pages = max(peak_acme_pages, pages.get("acme", 0))
    assert peak_acme_pages == 3          # exactly one resident at a time
    assert all(server.poll(r.id).status == "ok" for r in reqs)


def test_per_tenant_slo_breach_and_brownout_are_tenant_scoped():
    """The admission signal: only the burning tenant's ttft:<name>
    objective breaches, and only ITS brownout escalates — evaluated
    on a fake clock, no serving needed."""
    t = {"now": 0.0}
    clock = lambda: t["now"]    # noqa: E731
    reg = _registry(slos={"acme": 100.0, "globex": 100.0})
    ten = reg.build(clock=clock, slo_short_window_s=10.0,
                    slo_min_samples=5, brownout_dwell_s=0.0)
    for i in range(20):
        t["now"] += 0.1
        ten.observe_ttft("acme", 0.5)       # 5x the 100ms objective
        ten.observe_ttft("globex", 0.01)
    ten.evaluate()
    assert ten.breached("acme") and not ten.breached("globex")
    for _ in range(4):
        ten.brownouts["acme"].evaluate(queue_depth=0)
        ten.brownouts["globex"].evaluate(queue_depth=0)
        t["now"] += 1.0
    assert ten.brownouts["acme"].shedding
    assert ten.brownouts["globex"].stage == 0


def test_tenant_shed_refuses_only_that_tenant(params):
    reg = _registry(quotas={"acme": TenantQuota(max_queued=8)})
    ten = reg.build()
    ten.brownouts["acme"].force_stage(3, reason="drill")
    server = LMServer(params, n_slots=2, tenancy=ten, **_kw())
    assert not server.submit(Request(id="a0", prompt=(1, 2),
                                     max_new_tokens=2, tenant="acme"))
    assert server.poll("a0").status == "shed"
    assert server.submit(Request(id="g0", prompt=(1, 2),
                                 max_new_tokens=2, tenant="globex"))
    server.drain()
    assert server.poll("g0").status == "ok"
    s = server.summary()["serve_tenants"]
    assert s["acme"]["shed"] == 1 and s["globex"]["shed"] == 0


# -- journal / trace tag preservation -----------------------------------


def test_journal_preserves_tenant_tags(params, tmp_path):
    """Recovery bills the SAME tenant: journaled submits carry the
    tenant tag, pending_requests reconstructs it, and a rebuilt
    server's resubmission lands under that tenant's rollup."""
    path = tmp_path / "wal.jsonl"
    server = LMServer(params, n_slots=2, tenancy=_registry(),
                      journal=str(path), **_kw())
    for i, tenant in enumerate(["acme", "globex", "acme"]):
        assert server.submit(Request(id=f"r{i}", prompt=(1, 2, 3),
                                     max_new_tokens=3, tenant=tenant))
    server.close()                       # crash stand-in: nothing ran
    pend = pending_requests(path)
    assert [r.tenant for r in pend] == ["acme", "globex", "acme"]
    server2 = LMServer(params, n_slots=2, tenancy=_registry(),
                       journal=str(path), **_kw())
    assert server2.resubmit_pending(path) == ["r0", "r1", "r2"]
    server2.drain()
    s = server2.summary()["serve_tenants"]
    assert s["acme"]["requests"] == 2 and s["globex"]["requests"] == 1


def test_journal_without_tenants_stays_byte_identical(tmp_path):
    """Tenant-less journals must not grow a tenant key — old files and
    old consumers see the exact historical record shape."""
    import json

    from idc_models_tpu.serve.scheduler import Entry

    path = tmp_path / "wal.jsonl"
    j = RequestJournal(path)
    j.record_submit(Entry(rid="r0", prompt=np.array([1, 2]), budget=3),
                    deadline_s=None)
    j.close()
    rec = json.loads(path.read_text().splitlines()[0])
    assert "tenant" not in rec


def test_recovery_skips_decommissioned_tenant_without_aborting(
        params, tmp_path):
    """A WAL entry for a tenant the REBUILT server no longer registers
    must not abort the whole recovery: it is skipped with a warning
    (staying in the WAL for a rerun) while every other tenant's
    requests come back."""
    path = tmp_path / "wal.jsonl"
    server = LMServer(params, n_slots=2, tenancy=_registry(),
                      journal=str(path), **_kw())
    for i, tenant in enumerate(["acme", "globex", "acme"]):
        assert server.submit(Request(id=f"r{i}", prompt=(1, 2, 3),
                                     max_new_tokens=3, tenant=tenant))
    server.close()
    reg = TenantRegistry()
    reg.register("acme")                 # globex decommissioned
    server2 = LMServer(params, n_slots=2, tenancy=reg,
                       journal=str(path), **_kw())
    with pytest.warns(UserWarning, match="skipped request 'r1'"):
        recovered = server2.resubmit_pending(path)
    assert recovered == ["r0", "r2"]
    server2.drain()
    assert server2.summary()["serve_tenants"]["acme"]["requests"] == 2
    # the skipped entry is still pending in the WAL for a fixed rerun
    server2.close()
    assert [r.id for r in pending_requests(path)] == ["r1"]
