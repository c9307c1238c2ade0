"""ISSUE 20: fleet observability — the cluster telemetry plane.

1. TRACING — an autoscaled 1→2→1 run with a mid-decode live slot
   migration renders, from the MERGED per-process jsonl logs alone, a
   single wall-ordered `stats --request RID` timeline: placement,
   prefill handoff, migration, and finish hops under ONE trace_id with
   per-hop latency attribution.
2. TELEMETRY — `ClusterTelemetry` folds every replica registry into
   one replica-labeled fleet exposition whose rollup series equal the
   sum of the per-replica scrapes at the same instant, and the fleet
   /healthz embeds every replica health document plus autoscaler and
   compile-cache state. The non-cluster /healthz shape is untouched.
3. SKEW — the router's pooled SLO engine fires on a fleet-wide breach
   that no single replica's engine can see (each below min_samples).
4. WATCHDOGS — each anomaly detector fires once on its injected fault,
   stays silent on a clean fleet, and emits the frozen-schema
   ``cluster_anomaly`` record.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.observe import JsonlLogger, MetricsExporter
from idc_models_tpu.observe.metrics_registry import MetricsRegistry
from idc_models_tpu.observe.slo import SLO, SLOEngine
from idc_models_tpu.observe.stats import (
    format_request_timeline, summarize_jsonl,
)
from idc_models_tpu.serve import (
    AutoscaleConfig, Autoscaler, ClusterTelemetry, ClusterWatchdog,
    CompileCache, PrefixRegistry, Request, Router, WatchdogConfig,
    build_replica,
)

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2
CHUNK = 8


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _model_kw():
    return dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                t_max=SEQ)


def _replica(params, rid, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("window", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    return build_replica(params, replica_id=rid, **_model_kw(), **kw)


def _serial_tokens(params, prompt, steps):
    gen = Generator(params, mesh=None, cache_dtype=jnp.float32,
                    **_model_kw())
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps)
    return toks.tolist()[0]


def _records(paths):
    recs = []
    for p in paths:
        for line in p.read_text().splitlines():
            if line.strip():
                recs.append(json.loads(line))
    return recs


def _schemas(recs, event):
    return {frozenset(r) for r in recs if r.get("event") == event}


# -- 1. the acceptance drill: merged cross-replica timeline -----------------


def test_autoscaled_migration_renders_one_merged_timeline(devices,
                                                          params,
                                                          tmp_path):
    """1→2→1 under the real autoscaler with every process writing its
    OWN jsonl: a short burst scales the fleet up, two long requests
    (one per decode replica, prefilled on the dedicated prefill
    replica) ride into the scale-down, and the victim's running slot
    migrates live. Merging the four logs yields ONE timeline for the
    migrated rid — place, handoff, migrate, finish — under one
    trace_id, with per-hop deltas in the rendered view. The manual
    clock makes the scaling sequence deterministic: time only moves
    when the test advances it, so each decision fires exactly where
    injected."""
    logs = {name: JsonlLogger(tmp_path / f"{name}.jsonl")
            for name in ("router", "rp", "r0", "auto1")}
    registry = PrefixRegistry(CHUNK, 64 * 1024 * 1024,
                              logger=logs["router"])
    prefix_kw = dict(prefill_chunk=CHUNK, prefix_cache_mb=8.0,
                     shared_prefix=registry)
    rp = _replica(params, "rp", role="prefill", logger=logs["rp"],
                  **prefix_kw)
    r0 = _replica(params, "r0", window=2, logger=logs["r0"],
                  **prefix_kw)
    t = [0.0]
    auto = Autoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=2, queue_high=2.0, queue_low=1.5,
        dwell_s=0.5, cooldown_s=2.0), logger=logs["router"])
    router = Router(
        [r0, rp], prefix_registry=registry, clock=lambda: t[0],
        logger=logs["router"], autoscaler=auto,
        replica_factory=lambda rid: _replica(
            params, rid, window=2, logger=logs["auto1"], **prefix_kw))

    # phase 1: a burst of shorts trips the up signal; advancing the
    # clock past the dwell lets it fire
    shorts = [Request(id=f"s{i}", prompt=(1, 2, 3, 4),
                      max_new_tokens=2) for i in range(6)]
    for q in shorts:
        assert router.submit(q)
    router.step()                       # up signal registered at t=0
    t[0] = 1.0
    router.step()                       # dwell elapsed -> scale up
    grown = {r.replica_id for r in router.replicas} - {"r0", "rp"}
    assert len(grown) == 1
    auto_id = grown.pop()               # autoN: the router names it

    # phase 2: drain the shorts with TIME FROZEN — the down signal
    # accrues no dwell and the cooldown never elapses, so the fleet
    # deterministically stays at two decode replicas
    for _ in range(200):
        if all(router.poll(q.id) is not None for q in shorts):
            break
        router.step()
    assert all(router.poll(q.id).status == "ok" for q in shorts)

    # phase 3: two long prompts (>= one chunk: they handoff through
    # the prefill replica) land one per decode replica
    longs = [Request(id=f"big{i}", prompt=tuple(range(1, 17)),
                     max_new_tokens=12) for i in range(2)]
    for q in longs:
        assert router.submit(q)
    owners = {q.id: router._owner[q.id].replica_id for q in longs}
    assert set(owners.values()) == {"r0", auto_id}
    for _ in range(2):                  # both longs decode mid-stream
        router.step()

    # phase 4: release the clock — cooldown and dwell are instantly
    # ancient, the down decision fires, and the victim (r0: load tie,
    # lowest fleet index) slot-migrates its RUNNING request to auto1
    t[0] = 11.0
    router.step()
    assert router.slot_migrations, "the scale-down must migrate live"
    mig = router.slot_migrations[0]
    assert mig["from"] == "r0" and mig["to"] == auto_id
    rid = mig["rid"]
    for _ in range(200):
        if all(router.poll(q.id) is not None for q in longs):
            break
        router.step()
    res = {q.id: router.poll(q.id) for q in longs}
    assert all(r.status == "ok" for r in res.values())
    # the migrated stream stayed bit-identical to a serial run
    prompt = next(q.prompt for q in longs if q.id == rid)
    assert res[rid].tokens == _serial_tokens(params, prompt, 12)

    # the fleet health document embeds the autoscaler's clocks
    doc = ClusterTelemetry(router).health()
    assert set(doc["autoscaler"]) >= {
        "min_replicas", "max_replicas", "dwell_s", "cooldown_s",
        "decisions"}
    assert set(doc["replicas"]) == {"rp", "r0", auto_id}

    for lg in logs.values():
        lg.close()
    paths = [lg.path for lg in logs.values()]
    merged = summarize_jsonl(paths)
    tl = merged["requests"][rid]
    whats = [e["what"] for e in tl]
    assert {"cluster_place", "cluster_handoff", "cluster_slot_migrate",
            "serve_finish"} <= set(whats)
    # the migration hop precedes the finish in the merged wall order
    assert whats.index("cluster_slot_migrate") < whats.index(
        "serve_finish")
    # ONE trace identity across every router hop, matching the Result
    tids = {e["detail"]["trace_id"] for e in tl
            if e["what"].startswith("cluster_")}
    assert tids == {res[rid].trace_id}
    # hop counters grow monotonically along the merged timeline
    hops = [e["detail"]["hop"] for e in tl if "hop" in e["detail"]]
    assert hops == sorted(hops) and len(set(hops)) == len(hops)
    text = format_request_timeline(merged, rid)
    assert "cluster_slot_migrate" in text
    assert "(+" in text                 # per-hop latency attribution

    # frozen trace-hop schemas: the cross-replica grep contract
    recs = _records(paths)
    assert _schemas(recs, "cluster_place") == {frozenset(
        {"ts", "event", "id", "replica", "attempt", "trace_id",
         "hop"})}
    assert _schemas(recs, "cluster_handoff") == {frozenset(
        {"ts", "event", "id", "replica", "prefix_tokens", "cached",
         "trace_id", "hop"})}
    assert _schemas(recs, "cluster_slot_migrate") == {frozenset(
        {"ts", "event", "id", "src", "dst", "trace_id", "hop"})}
    assert _schemas(recs, "cluster_scale_up") == {frozenset(
        {"ts", "event", "replica", "live"})}
    assert _schemas(recs, "cluster_drain") == {frozenset(
        {"ts", "event", "replica"})}
    assert _schemas(recs, "autoscale_decision") == {frozenset(
        {"ts", "event", "action", "reason", "live", "queued", "t"})}
    assert _schemas(recs, "cluster_prefix_publish") == {frozenset(
        {"ts", "event", "prefix_tokens", "nbytes"})}


# -- 2. merged fleet metrics + rollups --------------------------------------


def _series(reg, name):
    inst = reg.get(name)
    if inst is None:
        return {}
    return {tuple(sorted(labels.items())): val
            for labels, val in inst._series()}


def test_fleet_metrics_rollups_equal_per_replica_sums(devices, params):
    """The merged exposition carries every replica's series under a
    ``replica`` label, VERBATIM — and each fleet rollup equals the sum
    of those per-replica series in the same scrape. Both sides come
    from one registry snapshot, so the equality is exact, not
    approximately-concurrent."""
    reps = [_replica(params, f"r{i}") for i in range(2)]
    router = Router(reps, registry=MetricsRegistry())
    rng = np.random.default_rng(3)
    # budget > window so decode spans several cycles: the first token
    # and the finish land in different cycles and the inter-token
    # latency samples exist deterministically, not by scheduler luck
    reqs = [Request(id=f"q{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + i)),
                    max_new_tokens=9) for i in range(4)]
    out = router.run([(0.0, q) for q in reqs])
    assert {r.status for r in out} == {"ok"}

    tele = ClusterTelemetry(router)
    merged = tele.merged_registry()
    # per-replica series survive the merge byte-for-byte, modulo the
    # added replica label
    for rep in reps:
        own = _series(rep.registry, "serve_requests_total")
        lifted = {
            tuple(kv for kv in key if kv[0] != "replica"): val
            for key, val in _series(merged,
                                    "serve_requests_total").items()
            if ("replica", rep.replica_id) in key}
        assert lifted == own and own, rep.replica_id
    # rollup == sum of the per-replica series in the SAME exposition
    qsum = sum(val for key, val
               in _series(merged, "serve_queue_depth").items()
               if any(k == "replica" for k, _ in key))
    assert merged.get("cluster_fleet_queue_depth") is not None
    assert _series(merged, "cluster_fleet_queue_depth") == {(): qsum}
    # ... and of the live per-replica scrapes at the same instant
    # (the fleet is idle, so the instant is stable)
    assert qsum == sum(
        rep.registry.get("serve_queue_depth").value() for rep in reps)
    # histogram state merges without re-observation: fleet count is
    # the sum of replica counts
    fleet_ttft = sum(
        st["count"] for _, st in
        merged.get("serve_ttft_seconds")._series())
    assert fleet_ttft == sum(
        st["count"] for rep in reps
        for _, st in rep.registry.get("serve_ttft_seconds")._series())
    assert fleet_ttft == len(reqs)
    # the router's own cluster_* series ride along unlabeled
    assert _series(merged, "cluster_placements_total")
    # the pooled decode-side tail joins the cluster rollup (ISSUE 20)
    s = router.summary()
    assert s["cluster_itl_ms_p95"] is not None
    assert s["cluster_ttft_ms_p95"] is not None
    text = tele.prometheus_text()
    assert 'replica="r0"' in text
    assert "cluster_fleet_queue_depth" in text


# -- 3. the fleet health surface --------------------------------------------


def test_fleet_healthz_embeds_replicas_and_compile_cache(devices,
                                                         params,
                                                         tmp_path):
    """Cluster-armed /healthz: every replica's own health document
    embedded verbatim, fleet aggregates, and the shared compile
    cache's counters — served over the same exporter whose non-cluster
    document keeps its historical shape."""
    reps = [_replica(params, f"r{i}") for i in range(2)]
    router = Router(reps, registry=MetricsRegistry())
    # a little traffic so the health/metrics gauges have honest series
    out = router.run([(0.0, Request(id=f"h{i}", prompt=(1, 2, 3),
                                    max_new_tokens=2))
                      for i in range(2)])
    assert {r.status for r in out} == {"ok"}
    cache = CompileCache(tmp_path / "cc")
    tele = ClusterTelemetry(router, compile_cache=cache)
    doc = tele.health()
    assert doc["status"] == "ok"
    assert set(doc["replicas"]) == {"r0", "r1"}
    for rid, h in doc["replicas"].items():
        assert set(h) == set(reps[0].health()), rid
    assert set(doc["fleet"]) == {
        "replicas_live", "replicas_draining", "replicas_dead",
        "queue_depth", "load", "kv_pages_used", "kv_pages_total"}
    assert doc["fleet"]["replicas_live"] == 2
    assert doc["compile_cache"] == {"hits": 0, "misses": 0,
                                    "stores": 0}
    assert "autoscaler" not in doc      # absent when not armed
    assert "slo" not in doc

    # a dead replica degrades the fleet without hiding the survivors
    router.kill_replica("r1")
    doc = tele.health()
    assert doc["status"] == "degraded"
    assert doc["fleet"]["replicas_dead"] == 1
    assert doc["replicas"]["r1"]["state"] == "dead"

    with MetricsExporter(router.registry, port=0,
                         cluster=tele) as exp:
        with urllib.request.urlopen(exp.url + "/healthz") as resp:
            served = json.loads(resp.read())
        assert set(served) == set(doc)
        assert set(served["replicas"]) == {"r0", "r1"}
        with urllib.request.urlopen(exp.url + "/metrics") as resp:
            body = resp.read().decode()
        assert "cluster_fleet_queue_depth" in body
        assert 'replica="r0"' in body
    # the single-process surface is untouched: same keys as ever,
    # no fleet block
    solo = MetricsExporter(MetricsRegistry()).health()
    assert set(solo) == {"status", "last_tick_age_s", "queue_depth",
                         "slot_occupancy", "kv_pages_used",
                         "kv_pages_total", "brownout_stage"}


def test_fleet_slo_fires_on_skew_no_single_replica_sees(devices,
                                                        params):
    """The cluster-level SLO engine pools every replica's samples, so
    a fleet-wide breach SPREAD across replicas — each one below its
    own engine's min_samples — still fires. Each per-replica engine
    stays silent; the router's pooled engine breaches; the fleet
    health document says degraded while every embedded replica doc
    stays clean."""
    mk = lambda: SLOEngine(
        [SLO.latency("ttft", threshold_s=1e-9)], min_samples=10,
        registry=MetricsRegistry())
    reps = [_replica(params, f"r{i}", slo=mk()) for i in range(2)]
    fleet_slo = mk()
    router = Router(reps, slo=fleet_slo, registry=MetricsRegistry())
    reqs = [Request(id=f"w{i}", prompt=(1, 2, 3), max_new_tokens=2)
            for i in range(16)]
    out = router.run([(0.0, q) for q in reqs])
    assert {r.status for r in out} == {"ok"}
    fleet_slo.evaluate()
    assert fleet_slo.breached("ttft")   # 16 pooled samples: fires
    healths = {h["replica"]: h for h in router.healths()}
    # ~8 samples per replica: below min_samples, every engine silent
    assert not any(healths[f"r{i}"]["slo_breached"] for i in range(2))
    doc = ClusterTelemetry(router).health()
    assert doc["status"] == "degraded"
    assert doc["slo"]["ttft"]["breached"], doc["slo"]
    assert not any(h["slo_breached"] for h in doc["replicas"].values())


# -- 4. the anomaly watchdogs (unit: fakes drive each detector) -------------


class _FakeMetrics:
    def __init__(self):
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.compiles_observed = 0


class _FakeReplica:
    def __init__(self, rid):
        self.replica_id = rid
        self.state = "live"
        self.role = "mixed"
        self.server = type("S", (), {})()
        self.server.metrics = _FakeMetrics()
        self.breached = False

    def health(self):
        return {"slo_breached": self.breached}


class _FakeRouter:
    def __init__(self, reps):
        self.replicas = reps
        self.migrations = []
        self.slot_migrations = []
        self.registry = MetricsRegistry()
        self.rollout_canary = None


def test_watchdog_detectors_fire_once_and_stay_silent_when_clean(
        tmp_path):
    """Each detector: silent on a healthy fleet, fires exactly once on
    the transition into its injected fault (hysteresis), clears on
    recovery and can fire again, and every firing is one frozen-schema
    ``cluster_anomaly`` record plus a labeled counter bump."""
    reps = [_FakeReplica("a"), _FakeReplica("b")]
    fr = _FakeRouter(reps)
    wt = [0.0]
    log = JsonlLogger(tmp_path / "wd.jsonl")
    wd = ClusterWatchdog(
        fr, WatchdogConfig(window_s=5.0, accept_rate_floor=0.2,
                           accept_min_drafted=10,
                           compile_churn_limit=2,
                           migration_spike_limit=2),
        logger=log, clock=lambda: wt[0])

    def tick(dt=1.0):
        wt[0] += dt
        return wd.check()

    # clean fleet: quiet across the whole window
    for _ in range(6):
        assert tick() == []

    # 1. accept-rate collapse — healthy drafting first, then collapse
    reps[0].server.metrics.spec_drafted += 100
    reps[0].server.metrics.spec_accepted += 60
    assert tick() == []                 # rate 0.6: healthy
    reps[1].server.metrics.spec_drafted += 400
    reps[1].server.metrics.spec_accepted += 2
    fired = tick()
    assert [f["kind"] for f in fired] == ["accept_collapse"]
    assert fired[0]["replica"] is None  # fleet-wide kind
    reps[1].server.metrics.spec_drafted += 100
    assert tick() == []                 # still collapsed: no re-fire
    # recovery clears the alert; a fresh collapse fires again
    wt[0] += 10.0                       # rebase past the bad window
    wd.check()
    reps[0].server.metrics.spec_drafted += 100
    reps[0].server.metrics.spec_accepted += 90
    assert tick() == []
    reps[0].server.metrics.spec_drafted += 400
    fired = tick()                      # window rate 90/500 = 0.18
    assert [f["kind"] for f in fired] == ["accept_collapse"]

    # too little drafting neither fires nor clears: state HOLDS
    wt[0] += 10.0
    wd.check()
    reps[0].server.metrics.spec_drafted += 3
    assert tick() == []

    # 2. compile churn is per replica
    reps[1].server.metrics.compiles_observed += 5
    fired = tick()
    assert [(f["kind"], f["replica"]) for f in fired] == [
        ("compile_churn", "b")]

    # 3. migration spike is fleet-wide across both migration paths
    fr.migrations.extend([{}, {}])
    fr.slot_migrations.append({})
    fired = tick()
    assert [f["kind"] for f in fired] == ["migration_spike"]

    # 4. canary divergence: only when the canary ALONE is burning
    fr.rollout_canary = reps[1]
    reps[1].breached = True
    reps[0].breached = True             # baseline burning too: organic
    assert tick() == []
    reps[0].breached = False
    fired = tick()
    assert [(f["kind"], f["replica"]) for f in fired] == [
        ("canary_divergence", "b")]
    assert tick() == []                 # hysteresis
    fr.rollout_canary = None            # rollout closed: state clears
    tick()
    fr.rollout_canary = reps[1]         # the NEXT rollout fires fresh
    fired = tick()
    assert [f["kind"] for f in fired] == ["canary_divergence"]

    # frozen record schema + the labeled counter
    log.close()
    recs = _records([log.path])
    assert recs and _schemas(recs, "cluster_anomaly") == {frozenset(
        {"ts", "event", "kind", "replica", "value", "threshold",
         "window_s"})}
    counts = _series(fr.registry, "cluster_anomalies_total")
    assert counts[(("kind", "accept_collapse"),)] == 2
    assert counts[(("kind", "canary_divergence"),)] == 2
    assert counts[(("kind", "compile_churn"),)] == 1
    assert counts[(("kind", "migration_spike"),)] == 1
    assert len(wd.anomalies) == 6


def test_watchdog_on_a_real_fleet_clean_then_each_fault(devices, params,
                                                       tmp_path):
    """The same detectors wired to a REAL two-replica journaled fleet
    (the router drives one `check()` per step): a clean burst and a
    quiet fleet fire nothing; then each kind fires exactly once on its
    own fault under a fake watchdog clock — the cumulative counters the
    serve hooks maintain (accept collapse, compile churn), a real
    rollout whose canary alone is burn-breached, and a real kill whose
    journaled in-flight work migrates onto the survivor, which still
    finishes every request."""
    wt = [0.0]

    def mk(rid, i):
        return _replica(params, rid, device=devices[i],
                        max_queue_depth=64,
                        journal_path=str(tmp_path / f"{rid}.jsonl"))

    def burst(tag, n=8):
        rng = np.random.default_rng(len(tag))
        return [Request(id=f"{tag}{i}",
                        prompt=tuple(int(x) for x in
                                     rng.integers(0, VOCAB, 3 + i % 4)),
                        max_new_tokens=10) for i in range(n)]

    router = Router([mk("w0", 0), mk("w1", 1)])
    cfg = WatchdogConfig(window_s=5.0, accept_min_drafted=64,
                         accept_rate_floor=0.2, compile_churn_limit=8,
                         migration_spike_limit=2)
    wd = ClusterWatchdog(router, cfg, clock=lambda: wt[0])
    router.watchdog = wd

    # clean: an armed healthy fleet stays silent, busy and idle
    out = router.run([(0.0, r) for r in burst("c")])
    assert all(r.status == "ok" for r in out)
    assert all(wd.check() == [] for _ in range(20))
    assert wd.anomalies == []

    # fault 1: speculative accept-rate collapse (5% << the 20% floor)
    wt[0] += 10.0
    wd.check()                              # rebase every window
    m0 = router.replicas[0].server.metrics
    m0.spec_drafted += 200
    m0.spec_accepted += 10
    wt[0] += 1.0
    assert [a["kind"] for a in wd.check()] == ["accept_collapse"]
    assert wd.check() == []                 # no re-fire while anomalous

    # fault 2: compile churn on one replica
    router.replicas[1].server.metrics.compiles_observed += 20
    wt[0] += 1.0
    assert [(a["kind"], a["replica"]) for a in wd.check()] == [
        ("compile_churn", "w1")]

    # fault 3: a real rollout whose canary ALONE burns its TTFT budget
    canary_slo = SLOEngine([SLO.latency("ttft", threshold_s=1e-4)],
                           short_window_s=60.0, long_window_s=300.0,
                           min_samples=1, registry=MetricsRegistry())
    assert router.start_rollout(params, replica_id="w1") == "w1"
    router.replicas[1].server.metrics.slo = canary_slo
    for _ in range(8):
        canary_slo.observe("ttft", 1.0)
    canary_slo.evaluate()
    assert canary_slo.breached()
    wt[0] += 1.0
    assert [(a["kind"], a["replica"]) for a in wd.check()] == [
        ("canary_divergence", "w1")]
    router.finish_rollout()
    # detached, or placement would avoid the breached replica and
    # leave the kill below nothing to strand
    router.replicas[1].server.metrics.slo = None

    # fault 4: a real kill of a loaded replica
    wt[0] += 10.0
    wd.check()
    reqs = burst("m")
    for q in reqs:
        assert router.submit(q)
    router.step()
    n_before = len(wd.anomalies)
    migrated = router.kill_replica("w1")
    assert len(migrated) > cfg.migration_spike_limit, migrated
    wt[0] += 1.0
    router.drain()                          # step() drives wd.check()
    assert [a["kind"] for a in wd.anomalies[n_before:]] == [
        "migration_spike"]
    for q in reqs:
        got = router.poll(q.id)
        assert got is not None and got.status == "ok", q.id
        assert got.tokens == _serial_tokens(params, q.prompt, 10), q.id
    assert [a["kind"] for a in wd.anomalies] == [
        "accept_collapse", "compile_churn", "canary_divergence",
        "migration_spike"]
    router.close()


def test_watchdog_config_validates():
    with pytest.raises(ValueError, match="window_s"):
        WatchdogConfig(window_s=0)
    with pytest.raises(ValueError, match="accept_rate_floor"):
        WatchdogConfig(accept_rate_floor=1.5)
    with pytest.raises(ValueError, match="accept_min_drafted"):
        WatchdogConfig(accept_min_drafted=0)
    with pytest.raises(ValueError, match="limits"):
        WatchdogConfig(compile_churn_limit=-1)


# -- 5. remaining trace-hop event schemas -----------------------------------


def test_canary_and_shed_events_carry_the_trace_schema(devices, params,
                                                       tmp_path):
    """The rollout-canary placement marker and the cluster-wide shed
    Result both ride the trace chain: frozen schemas, rid-joinable,
    trace_id-stamped — so `stats --request` shows WHY a request landed
    on a canary or never ran at all."""
    log = JsonlLogger(tmp_path / "router.jsonl")
    reps = [_replica(params, f"r{i}") for i in range(2)]
    router = Router(reps, logger=log, registry=MetricsRegistry())
    cid = router.start_rollout(params)
    assert cid in {"r0", "r1"}
    reqs = [Request(id=f"c{i}", prompt=(1, 2, 3), max_new_tokens=2)
            for i in range(4)]
    for q in reqs:
        assert router.submit(q)
    router.drain()
    router.finish_rollout()
    router.kill_replica("r0")
    router.kill_replica("r1")
    assert not router.submit(Request(id="lost", prompt=(1, 2),
                                     max_new_tokens=2))
    log.close()
    recs = _records([log.path])
    assert _schemas(recs, "cluster_canary") == {frozenset(
        {"ts", "event", "id", "replica", "trace_id", "hop"})}
    canaried = {r["id"] for r in recs
                if r.get("event") == "cluster_canary"}
    assert canaried <= {q.id for q in reqs} and canaried
    # every canary marker shares its request's placement trace_id
    by_rid = {}
    for r in recs:
        if r.get("event") == "cluster_place":
            by_rid[r["id"]] = r["trace_id"]
    for r in recs:
        if r.get("event") == "cluster_canary":
            assert r["trace_id"] == by_rid[r["id"]]
    assert _schemas(recs, "cluster_shed") == {frozenset(
        {"ts", "event", "id", "trace_id", "reason"})}
    shed = [r for r in recs if r.get("event") == "cluster_shed"]
    assert shed[0]["id"] == "lost"
    assert shed[0]["reason"] == "no_live_replica"
    base = {"ts", "event", "stage", "replica"}
    assert _schemas(recs, "cluster_rollout") <= {
        frozenset(base), frozenset(base | {"reason"})}
    assert _schemas(recs, "cluster_rollout")
