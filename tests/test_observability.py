"""ISSUE 5 observability layer: span tracer export formats, registry
export formats, Timer routing, the offline stats rollup — and the
back-compat gate that every PRE-EXISTING jsonl key/event still emits
unchanged now that the loops also feed the tracer/registry.
"""

import json
import re
import threading

import numpy as np
import pytest

from idc_models_tpu.observe import (
    JsonlLogger, MetricsRegistry, Timer, Tracer, summarize_jsonl, trace,
)


@pytest.fixture()
def tracer():
    tr = Tracer()
    prev = trace.set_tracer(tr)
    yield tr
    trace.set_tracer(prev)


def _nested_work(tracer):
    with trace.span("outer", kind="test"):
        with trace.span("inner.a", i=0):
            pass
        with trace.span("inner.a", i=1):
            with trace.span("leaf"):
                pass
    with trace.span("sibling"):
        pass


# -- tracer ----------------------------------------------------------------


def test_span_ids_and_nesting_roundtrip_jsonl(tracer, tmp_path):
    _nested_work(tracer)
    path = tracer.export_jsonl(tmp_path / "spans.jsonl")
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 5
    ids = [r["id"] for r in recs]
    assert len(set(ids)) == 5                       # process-unique ids
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    outer = by_name["outer"][0]
    assert outer["parent"] is None
    assert outer["attrs"] == {"kind": "test"}
    assert by_name["sibling"][0]["parent"] is None
    for r in by_name["inner.a"]:
        assert r["parent"] == outer["id"]           # nesting via parent
    leaf = by_name["leaf"][0]
    inner1 = [r for r in by_name["inner.a"] if r["attrs"]["i"] == 1][0]
    assert leaf["parent"] == inner1["id"]
    # children fit inside their parent's interval; both clocks present
    for r in recs:
        assert r["dur_ms"] >= 0 and r["t_ms"] >= 0 and r["wall"] > 0
        if r["parent"] is not None:
            p = [x for x in recs if x["id"] == r["parent"]][0]
            assert p["t_ms"] <= r["t_ms"] + 1e-6
            assert (r["t_ms"] + r["dur_ms"]
                    <= p["t_ms"] + p["dur_ms"] + 1e-6)


def test_chrome_trace_export_is_perfetto_valid(tracer, tmp_path):
    """The exported file meets the trace-event format's expectations:
    `ph:"X"` complete events with numeric microsecond ts/dur, pid/tid
    ints, and the same containment the jsonl carries."""
    _nested_work(tracer)
    path = tracer.export_chrome(tmp_path / "trace.json")
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 5
    assert any(e["ph"] == "M" for e in evs)         # process metadata
    by_id = {}
    for e in xs:
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] >= 0       # microseconds
        by_id[e["args"]["span_id"]] = e
    for e in xs:
        parent = e["args"]["parent_id"]
        if parent is not None:
            p = by_id[parent]
            assert p["ts"] <= e["ts"] + 1e-3
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3


def test_disabled_tracer_is_noop():
    assert trace.get_tracer() is None
    h1 = trace.span("x", a=1)
    h2 = trace.span("y")
    assert h1 is h2                      # the shared no-op handle
    with h1 as s:
        s.set(b=2)                       # every op accepted, no state


def test_spans_are_per_thread(tracer):
    """Concurrent threads each get their own open-span stack: a span
    opened on thread B must not parent under thread A's open span."""
    ready = threading.Barrier(2)

    def work(tag):
        ready.wait()
        with trace.span(f"t.{tag}"):
            with trace.span(f"t.{tag}.child"):
                pass

    ts = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = tracer.records()
    by_name = {r["name"]: r for r in recs}
    for i in range(2):
        child = by_name[f"t.{i}.child"]
        assert child["parent"] == by_name[f"t.{i}"]["id"]
        assert child["tid"] == by_name[f"t.{i}"]["tid"]


def test_timer_routes_through_tracer(tracer, capsys):
    """Satellite: a legacy Timer shows up in the exported trace while
    its print line stays byte-identical to the reference format."""
    with Timer("Pre-training for 10 epochs") as t:
        pass
    out = capsys.readouterr().out
    assert out == f"Pre-training for 10 epochs took {t.seconds} seconds\n"
    spans = tracer.records()
    assert [s["name"] for s in spans] == ["Pre-training for 10 epochs"]
    assert spans[0]["attrs"] == {"timer": True}


def test_detached_spans_explicit_parenting_and_close(tracer):
    """ISSUE-7 request-lifecycle primitives: a detached span never
    touches the thread's open-span stack, parents explicitly, closes
    idempotently with late attrs, and `point` records a marker."""
    with trace.span("tick"):
        req = trace.start_span("request", rid="r0")
        child = trace.start_span("queued", parent=req.span_id, rid="r0")
        # stack parenting is unaffected: a normal span opened while the
        # detached ones are live still parents under "tick"
        with trace.span("inner") as inner:
            pass
        inner.close(bogus=True)   # stray close on a stack span: no-op
        child.close(queue_wait_ms=1.5)
        child.close(queue_wait_ms=999.0)       # second close: no-op
        trace.point("first_token", parent=req.span_id, rid="r0")
        req.close(status="ok")
    recs = {r["name"]: r for r in tracer.records()}
    assert recs["request"]["parent"] is None
    assert recs["queued"]["parent"] == recs["request"]["id"]
    assert recs["queued"]["attrs"]["queue_wait_ms"] == 1.5
    assert recs["first_token"]["parent"] == recs["request"]["id"]
    assert recs["inner"]["parent"] == recs["tick"]["id"]
    assert "bogus" not in recs["inner"]["attrs"]
    assert recs["request"]["attrs"]["status"] == "ok"
    # exactly one record per span despite the double close
    assert len(tracer.records()) == 5


def test_detached_spans_disabled_are_the_noop_handle():
    assert trace.get_tracer() is None
    h = trace.start_span("request", rid="r0")
    assert h is trace.point("x") is trace.span("y")
    # the chained-call-site contract: the no-op handle's span_id is the
    # "no parent" value, so rid chains need no enabled/disabled branch
    assert h.span_id is None
    h.close(status="ok")                       # accepted, no state


def test_tracing_context_installs_and_exports(tmp_path):
    chrome = tmp_path / "t.json"
    with trace.tracing(chrome_path=chrome) as tr:
        assert trace.get_tracer() is tr
        with trace.span("inside"):
            pass
    assert trace.get_tracer() is None
    assert json.load(open(chrome))["traceEvents"]
    # no paths -> true no-op, nothing installed
    with trace.tracing() as tr2:
        assert tr2 is None and trace.get_tracer() is None


# -- registry --------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labels=("status",))
    c.inc(status="ok")
    c.inc(2, status="ok")
    c.inc(status="err")
    assert c.value(status="ok") == 3 and c.value(status="err") == 1
    with pytest.raises(ValueError):
        c.inc(-1, status="ok")           # counters only go up
    with pytest.raises(ValueError):
        c.inc(status="ok", extra="x")    # undeclared label
    g = reg.gauge("depth", "queue depth")
    g.set(4)
    g.dec()
    assert g.value() == 3
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    # idempotent re-registration returns the SAME instrument
    assert reg.counter("reqs_total", labels=("status",)) is c
    # type / label conflicts are loud
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):
        reg.counter("reqs_total", labels=("other",))
    # bucket conflicts are as loud as type/label conflicts — a silent
    # first-wins would file the second caller's observations into +Inf
    with pytest.raises(ValueError):
        reg.histogram("lat_seconds", buckets=(10.0, 20.0))
    assert reg.histogram("lat_seconds", buckets=(0.1, 1.0)) is h
    snap = {(r["name"], tuple(sorted(r["labels"].items()))): r
            for r in reg.snapshot()}
    assert snap[("reqs_total", (("status", "ok"),))]["value"] == 3
    hrec = snap[("lat_seconds", ())]
    assert hrec["count"] == 3 and hrec["min"] == 0.05 and hrec["max"] == 5.0
    assert hrec["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("jobs_total", "jobs run", labels=("kind",)).inc(
        3, kind="a b")
    reg.gauge("temp", "gauge").set(1.5)
    h = reg.histogram("dur_seconds", "d", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(3.0)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE jobs_total counter" in lines
    assert "# HELP jobs_total jobs run" in lines
    assert 'jobs_total{kind="a b"} 3' in lines
    assert "# TYPE temp gauge" in lines and "temp 1.5" in lines
    assert "# TYPE dur_seconds histogram" in lines
    assert 'dur_seconds_bucket{le="0.5"} 1' in lines
    assert 'dur_seconds_bucket{le="2"} 1' in lines     # cumulative
    assert 'dur_seconds_bucket{le="+Inf"} 2' in lines  # == _count
    assert "dur_seconds_count 2" in lines
    assert any(l.startswith("dur_seconds_sum ") for l in lines)
    # every sample line parses as <name>[{labels}] <number>
    sample = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
                        r"-?[0-9.e+-]+$")
    for l in lines:
        if not l.startswith("#"):
            assert sample.match(l), l
    # non-finite values render as Prometheus's legal spellings instead
    # of crashing the whole exposition on int() overflow
    reg.gauge("hot").set(float("inf"))
    reg.gauge("cold").set(float("-inf"))
    reg.gauge("broken").set(float("nan"))
    text2 = reg.prometheus_text()
    assert "hot +Inf" in text2 and "cold -Inf" in text2
    assert "broken NaN" in text2


def test_registry_jsonl_snapshot_and_stats(tmp_path):
    reg = MetricsRegistry()
    reg.counter("widgets_total").inc(7)
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        logger.log(event="epoch", epoch=0, loss=1.0, accuracy=0.5)
        reg.log_snapshot(logger)
    recs = [json.loads(l) for l in open(log)]
    snaps = [r for r in recs if r["event"] == "metrics_snapshot"]
    assert len(snaps) == 1
    assert snaps[0]["metrics"][0] == {
        "name": "widgets_total", "type": "counter", "labels": {},
        "value": 7}
    # the offline stats rollup reads the same file
    s = summarize_jsonl(log)
    assert s["records"] == 2
    assert s["events"]["epoch"]["fields"]["loss"]["mean"] == 1.0
    assert s["metrics"][0]["name"] == "widgets_total"
    assert reg.write_snapshot(tmp_path / "snap.jsonl")


# -- jsonl back-compat gates ----------------------------------------------
#
# The acceptance bar: every jsonl key/event the pre-ISSUE-5 loops wrote
# still emits with the same names now that the tracer/registry ride
# along. These freeze the schemas at the metrics-hook level (cheap, no
# engine compile); the CLI e2e tests cover the full wiring.


def test_serving_metrics_jsonl_schema_unchanged(tmp_path):
    from idc_models_tpu.serve.metrics import ServingMetrics

    log = tmp_path / "serve.jsonl"
    reg = MetricsRegistry()
    with JsonlLogger(log) as logger:
        m = ServingMetrics(logger, registry=reg)
        m.on_submit("r0", 10.0)
        m.on_reject("r1", 10.1)
        m.on_admit("r0", 0.02)
        m.on_first_token("r0", 0.05)
        m.on_cycle(queue_depth=1, occupancy=0.5, tokens=3,
                   prefill_s=0.01)
        m.on_finish("r0", n_tokens=3, ttft_s=0.05, decode_s=0.1,
                    reason="budget", t=10.3)
        # ISSUE-8 resilience hooks: NEW event types only — the
        # historical five keep their exact shapes below
        m.on_slot_fault("r2", kind="nonfinite_logits", slot=1)
        m.on_retry("r2", attempt=2, delay_s=0.05)
        m.on_shed("r3")
        m.on_clamp("r4", asked=64, clamp=8)
        m.on_fault_injected("stall", tick=3)
        # ISSUE-10 speculative hooks: one NEW event type, frozen from
        # day one; dispatch counting logs nothing
        m.on_dispatch("window")
        m.on_dispatch("verify")
        m.on_spec(drafted=8, accepted=5, emitted=7, slots=2)
        # ISSUE-11 paged-KV hooks: one NEW event type (exhaustion),
        # frozen from day one; on_pages sets gauges + peaks, no event
        m.on_pages(pages_total=32, pages_used=10, pages_cached=3,
                   resident_tokens=150, resident_bytes=40960)
        m.on_pages(pages_total=32, pages_used=7, pages_cached=3,
                   resident_tokens=90, resident_bytes=28672)
        m.on_page_exhausted(rid="r9", needed=48)
    recs = [json.loads(l) for l in open(log)]
    by_event = {r["event"]: r for r in recs}
    # the historical event set + per-event keys, byte-for-byte names
    assert set(by_event) == {"serve_submit", "serve_reject",
                             "serve_admit", "serve_first_token",
                             "serve_finish", "serve_slot_fault",
                             "serve_retry", "serve_shed",
                             "serve_clamp", "serve_fault_injected",
                             "serve_spec_verify",
                             "serve_page_exhausted"}
    assert set(by_event["serve_submit"]) == {"ts", "event", "id"}
    assert set(by_event["serve_admit"]) == {"ts", "event", "id",
                                            "queue_wait_ms"}
    # ISSUE 36: two additive keys, the phases before `prefill_ms`
    assert set(by_event["serve_first_token"]) == {
        "ts", "event", "id", "ttft_ms", "prefill_ms", "queue_ms",
        "reserved_ms"}
    assert set(by_event["serve_finish"]) == {"ts", "event", "id",
                                             "tokens", "reason",
                                             "ttft_ms"}
    # the ISSUE-8 events are frozen from day one, same discipline
    assert set(by_event["serve_slot_fault"]) == {"ts", "event", "id",
                                                 "kind", "slot"}
    assert set(by_event["serve_retry"]) == {"ts", "event", "id",
                                            "attempt", "delay_ms"}
    assert set(by_event["serve_shed"]) == {"ts", "event", "id"}
    assert set(by_event["serve_clamp"]) == {"ts", "event", "id",
                                            "max_new_tokens", "asked"}
    assert set(by_event["serve_fault_injected"]) == {"ts", "event",
                                                     "kind", "tick"}
    # the ISSUE-10 speculative event, frozen from day one
    assert set(by_event["serve_spec_verify"]) == {"ts", "event",
                                                  "drafted", "accepted",
                                                  "emitted", "slots"}
    # the ISSUE-11 paged-KV event, frozen from day one
    assert set(by_event["serve_page_exhausted"]) == {"ts", "event",
                                                     "id", "needed"}
    # the historical summary keys all still present
    s = m.summary()
    for k in ("serve_requests", "serve_rejected", "serve_timed_out",
              "serve_tokens", "serve_tokens_per_sec",
              "serve_ttft_ms_p50", "serve_ttft_ms_p95",
              "serve_queue_wait_ms_p50", "serve_queue_wait_ms_p95",
              "serve_prefill_ms_p50", "serve_prefill_ms_p95",
              # ISSUE-20 additive ITL tail next to the existing p50
              "serve_token_ms_p50", "serve_token_ms_p95",
              "serve_slot_occupancy",
              "serve_queue_depth_mean", "serve_queue_depth_max",
              "serve_window_tokens_mean",
              "serve_prefill_stall_ms_mean",
              "serve_prefill_stall_ms_max",
              # the ISSUE-8 additive resilience rollup
              "serve_slot_faults", "serve_retries", "serve_shed",
              "serve_clamped", "serve_faults_injected",
              # the ISSUE-10 additive speculative rollup (incl. the
              # SHARED tokens-per-dispatch definition both modes use)
              "serve_decode_dispatches", "serve_tokens_per_dispatch",
              "serve_spec_verify_dispatches", "serve_spec_drafted",
              "serve_spec_accepted", "serve_spec_accept_rate",
              "serve_spec_tokens_per_dispatch",
              # the ISSUE-11 additive paged-KV rollup, frozen from
              # day one
              "serve_kv_pages_total", "serve_kv_pages_used_peak",
              "serve_kv_resident_tokens_peak",
              "serve_kv_resident_bytes_peak",
              "serve_kv_tokens_per_hbm_byte",
              "serve_page_exhaustions"):
        assert k in s, k
    assert s["serve_slot_faults"] == 1 and s["serve_retries"] == 1
    assert s["serve_shed"] == 1 and s["serve_clamped"] == 1
    assert s["serve_decode_dispatches"] == 2
    assert s["serve_tokens_per_dispatch"] == 1.5   # 3 tokens / 2
    assert s["serve_spec_accept_rate"] == 0.625    # 5 / 8 drafted
    assert s["serve_spec_tokens_per_dispatch"] == 3.5  # 7 / 2 slots
    # paged rollup keeps PEAKS (the capacity claim is stated at peak
    # residency), and tokens-per-byte is taken AT the peak
    assert s["serve_kv_pages_total"] == 32
    assert s["serve_kv_pages_used_peak"] == 10
    assert s["serve_kv_resident_tokens_peak"] == 150
    assert s["serve_kv_resident_bytes_peak"] == 40960
    assert s["serve_kv_tokens_per_hbm_byte"] == round(150 / 40960, 6)
    assert s["serve_page_exhaustions"] == 1
    # ISSUE-20: inter-token latency rides next to TTFT — a histogram
    # on the registry (the fleet view merges its state) and a p95
    # summary tail. One finish, 3 tokens over 0.1s decode: the mean
    # ITL is 0.1 / 2 = 50ms.
    assert s["serve_token_ms_p95"] == 50.0
    itl = reg.get("serve_itl_seconds")
    assert itl is not None and itl.kind == "histogram"
    (_, st), = itl._series()
    assert st["count"] == 1 and abs(st["sum"] - 0.05) < 1e-9


def test_fed_driver_round_health_schema_unchanged(tmp_path):
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.federated.driver import DriverConfig, run_rounds
    from idc_models_tpu.federated.fedavg import ServerState

    def round_fn(server, images, labels, weights, rng):
        new = ServerState(round=server.round + 1, params=server.params,
                          model_state=server.model_state)
        return new, {"loss": jnp.float32(0.5),
                     "accuracy": jnp.float32(0.9),
                     "clients_dropped": jnp.int32(0)}

    server = ServerState(round=jnp.zeros((), jnp.int32),
                         params={"w": jnp.ones((2,))}, model_state={})
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        res = run_rounds(round_fn, server, None, None,
                         np.ones(3, np.float32),
                         config=DriverConfig(rounds=2), logger=logger)
    assert len(res.history) == 2
    recs = [json.loads(l) for l in open(log)]
    health = [r for r in recs if r["event"] == "round_health"]
    rounds = [r for r in recs if r["event"] == "round"]
    assert len(health) == 2 and len(rounds) == 2
    assert {"ts", "event", "round", "attempt", "status", "seconds",
            "participants", "loss", "accuracy",
            "clients_dropped"} <= set(health[0])
    assert health[0]["status"] == "ok"
    assert {"round", "attempts", "loss", "accuracy"} <= set(rounds[0])


def test_fed_cohort_jsonl_schema_frozen(tmp_path):
    """ISSUE-13 satellite: the NEW `fed_cohort` event's key sets (sync
    and async shapes) are frozen from day one; the historical fed
    events (`round`, `round_health`) stay byte-identical — gated by
    test_fed_driver_round_health_schema_unchanged above and re-checked
    here against a population-mode run log."""
    import jax

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.federated import (
        ClientPopulation, CohortSampler, initialize_server,
        make_async_round, make_population_round,
    )
    from idc_models_tpu.models import small_cnn
    from idc_models_tpu.train import rmsprop
    from idc_models_tpu.train.losses import binary_cross_entropy

    pop = ClientPopulation(32, examples_per_client=8, image_size=10,
                           seed=0)
    model = small_cnn(10, 3, 1)
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        sync = make_population_round(
            model, rmsprop(1e-3), binary_cross_entropy,
            meshlib.client_mesh(1), pop, CohortSampler(pop, 4, seed=1),
            wave_size=2, local_epochs=1, batch_size=8, logger=logger)
        srv = initialize_server(model, jax.random.key(0))
        sync(srv, None, None, None, jax.random.key(1), round_idx=0)
        a = make_async_round(
            model, rmsprop(1e-3), binary_cross_entropy, pop,
            CohortSampler(pop, 4, seed=1), buffer_size=2,
            local_epochs=1, batch_size=8, seed=2, logger=logger)
        srv = initialize_server(model, jax.random.key(0))
        a(srv, None, None, None, None, round_idx=0)
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    cohorts = [r for r in recs if r["event"] == "fed_cohort"]
    assert len(cohorts) == 2
    sync_rec = next(r for r in cohorts if r["mode"] == "sync")
    async_rec = next(r for r in cohorts if r["mode"] == "async")
    # FROZEN key sets — extending is a new event, not a reshaped one
    assert set(sync_rec) == {"ts", "event", "round", "mode",
                             "population", "cohort", "participants",
                             "waves", "wave_size"}
    assert set(async_rec) == {"ts", "event", "round", "mode",
                              "population", "cohort", "participants",
                              "buffer", "updates", "staleness_mean",
                              "staleness_max", "staleness_hist"}
    assert async_rec["staleness_hist"] == list(async_rec[
        "staleness_hist"])
    assert len(async_rec["staleness_hist"]) == 6
    assert sum(async_rec["staleness_hist"]) == \
        async_rec["participants"]


def test_stats_fed_cohorts_section(tmp_path):
    """`stats` renders the per-round cohort/buffer/staleness story from
    fed_cohort events — the ISSUE-13 'fed cohorts' section."""
    from idc_models_tpu.observe.stats import format_summary

    log = tmp_path / "run.jsonl"
    recs = [
        {"ts": 1.0, "event": "fed_cohort", "round": 0, "mode": "sync",
         "population": 10000, "cohort": 256, "participants": 256,
         "waves": 8, "wave_size": 32},
        {"ts": 2.0, "event": "fed_cohort", "round": 1, "mode": "async",
         "population": 10000, "cohort": 256, "participants": 256,
         "buffer": 8, "updates": 32, "staleness_mean": 1.25,
         "staleness_max": 4,
         "staleness_hist": [100, 80, 40, 20, 10, 6]},
    ]
    log.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    s = summarize_jsonl(log)
    assert len(s["fed_cohorts"]) == 2
    assert s["fed_cohorts"][0]["mode"] == "sync"
    assert s["fed_cohorts"][1]["staleness_hist"] == \
        [100, 80, 40, 20, 10, 6]
    text = format_summary(s)
    assert "fed cohorts (per round)" in text
    assert "cohort=256 of 10000" in text
    assert "waves=8x32" in text
    assert "buffer=8 updates=32" in text
    assert "[100, 80, 40, 20, 10, 6]" in text


def test_stats_request_timeline_from_events_and_spans(tmp_path):
    """ISSUE-7 satellite: `summarize_jsonl` groups serve_* events AND
    rid-stamped span records into per-request timelines; the --request
    renderer orders them and a missing rid is loud."""
    from idc_models_tpu.observe import format_request_timeline

    log = tmp_path / "mixed.jsonl"
    recs = [
        {"ts": 100.0, "event": "serve_submit", "id": "r0"},
        {"ts": 100.1, "event": "serve_admit", "id": "r0",
         "queue_wait_ms": 100.0},
        {"event": "span", "name": "serve.prefill_chunk", "id": 7,
         "parent": 3, "tid": 1, "t_ms": 150.0, "dur_ms": 30.0,
         "wall": 100.15, "attrs": {"rid": "r0", "slot": 1}},
        {"ts": 100.3, "event": "serve_first_token", "id": "r0",
         "ttft_ms": 300.0, "prefill_ms": 200.0},
        {"ts": 100.5, "event": "serve_finish", "id": "r0", "tokens": 4,
         "reason": "budget", "ttft_ms": 300.0},
        {"ts": 100.2, "event": "serve_submit", "id": "r1"},
        # rid-less span: belongs to no request
        {"event": "span", "name": "serve.tick", "id": 9, "parent": None,
         "tid": 1, "t_ms": 0.0, "dur_ms": 1.0, "wall": 100.0,
         "attrs": {}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    s = summarize_jsonl(log)
    assert set(s["requests"]) == {"r0", "r1"}
    r0 = s["requests"]["r0"]
    assert [e["what"] for e in r0] == [
        "serve_submit", "serve_admit", "serve.prefill_chunk",
        "serve_first_token", "serve_finish"]
    assert r0[0]["t_s"] == 0.0
    assert r0[2]["dur_ms"] == 30.0 and r0[2]["detail"]["slot"] == 1
    assert r0[4]["t_s"] == pytest.approx(0.5)
    text = format_request_timeline(s, "r0")
    assert "request r0" in text and "serve.prefill_chunk" in text
    assert "serve_finish" in text and "reason=budget" in text
    with pytest.raises(KeyError):
        format_request_timeline(s, "nope")


def test_stats_covers_train_and_fed_jsonl(tmp_path):
    """ISSUE-7 satellite: the stats rollup over train/fed-SHAPED run
    logs (epoch + round + round_health + timer records), not just the
    serve path — field percentiles, timer table, and no spurious
    request table."""
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        for e in range(3):
            logger.log(event="epoch", epoch=e, loss=1.0 - 0.2 * e,
                       accuracy=0.5 + 0.1 * e, val_loss=1.1 - 0.2 * e,
                       val_accuracy=0.45 + 0.1 * e)
        for r in range(4):
            logger.log(event="round", round=r, train_loss=0.9 - 0.1 * r,
                       train_acc=0.6 + 0.05 * r, test_loss=1.0,
                       test_acc=0.55)
            logger.log(event="round_health", round=r, attempt=0,
                       status="ok", seconds=0.05, participants=8,
                       loss=0.9 - 0.1 * r)
        logger.log(event="timer", name="Federated training",
                   seconds=1.25)
    s = summarize_jsonl(log)
    assert s["events"]["epoch"]["count"] == 3
    assert s["events"]["epoch"]["fields"]["loss"]["min"] == 0.6
    assert s["events"]["round"]["count"] == 4
    assert s["events"]["round"]["fields"]["train_loss"]["max"] == 0.9
    assert s["events"]["round_health"]["fields"]["seconds"]["mean"] \
        == 0.05
    assert s["timers"]["Federated training"]["count"] == 1
    assert s["requests"] == {}        # nothing serve-shaped in the log


def test_profile_program_jsonl_schema_frozen(tmp_path, devices):
    """ISSUE-9: the `profile_program` event's key set is frozen from
    day one (NEW event; the ten historical event schemas are gated
    above/by their own tests). The record is built through the ONE
    construction site (profile.program_record) the CLI uses."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.observe import profile as prof

    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    cost = prof.program_report(compiled, name="sch.prog")
    roofline = prof.roofline_verdict(
        cost, 0.001, spec=prof.RooflineSpec("x", 100.0, 1000.0))
    log = tmp_path / "profile.jsonl"
    with JsonlLogger(log) as logger:
        logger.log(event="profile_program",
                   **prof.program_record(cost, roofline, step_ms=1.0,
                                         device_kind="cpu"))
    rec = json.loads(log.read_text().splitlines()[0])
    assert set(rec) == {
        "ts", "event", "program", "flops", "bytes_accessed",
        "arithmetic_intensity", "argument_bytes", "output_bytes",
        "temp_bytes", "peak_hbm_bytes", "generated_code_bytes",
        "available", "step_ms", "verdict", "achieved_tflops",
        "achieved_hbm_gbps", "mfu", "hbm_utilization",
        "bound_fraction", "ridge_intensity", "peak_tflops",
        "peak_hbm_gbps", "device_kind"}
    assert rec["event"] == "profile_program"
    assert rec["program"] == "sch.prog" and rec["available"] is True
    assert rec["verdict"] in ("compute-bound", "bandwidth-bound")
    # a verdict-less (unknown-backend) record keeps the SAME keys
    with JsonlLogger(log) as logger:
        logger.log(event="profile_program",
                   **prof.program_record(cost))
    rec2 = json.loads(log.read_text().splitlines()[-1])
    assert set(rec2) == set(rec)
    assert rec2["verdict"] == "unknown" and rec2["mfu"] is None


def test_profile_step_jsonl_schema_frozen(tmp_path):
    """ISSUE-9: the `profile_step` event's key set is frozen, built
    through profile.step_record from a real DeviceTimeline report."""
    from idc_models_tpu.observe import MetricsRegistry
    from idc_models_tpu.observe import profile as prof

    records = [
        {"event": "span", "name": "profile.step", "id": 1,
         "parent": None, "tid": 1, "t_ms": 0.0, "dur_ms": 10.0,
         "wall": 0.0, "attrs": {}},
        {"event": "span", "name": "device.sync", "id": 2, "parent": 1,
         "tid": 1, "t_ms": 1.0, "dur_ms": 6.0, "wall": 0.0,
         "attrs": {}},
    ]
    tl = prof.DeviceTimeline(registry=MetricsRegistry()).consume(records)
    log = tmp_path / "profile.jsonl"
    with JsonlLogger(log) as logger:
        for loop, st in tl.report().items():
            logger.log(event="profile_step",
                       **prof.step_record(loop, st))
    rec = json.loads(log.read_text().splitlines()[0])
    assert set(rec) == {"ts", "event", "loop", "steps", "wall_ms",
                        "device_ms", "host_gap_ms",
                        "device_busy_fraction", "host_gap_fraction",
                        "step_ms_mean"}
    assert rec["loop"] == "profile.step"
    assert rec["device_busy_fraction"] == pytest.approx(0.6)
    assert (rec["device_busy_fraction"] + rec["host_gap_fraction"]
            == pytest.approx(1.0))


def test_stats_span_self_time_table(tmp_path):
    """ISSUE-9 satellite: per-span-name EXCLUSIVE time from any span
    export — parent self-time excludes direct children; --top bounds
    the rendered table."""
    from idc_models_tpu.observe import format_summary

    recs = [
        {"event": "span", "name": "tick", "id": 1, "parent": None,
         "tid": 1, "t_ms": 0.0, "dur_ms": 10.0, "wall": 1.0,
         "attrs": {}},
        {"event": "span", "name": "collect", "id": 2, "parent": 1,
         "tid": 1, "t_ms": 1.0, "dur_ms": 4.0, "wall": 1.0,
         "attrs": {}},
        {"event": "span", "name": "window", "id": 3, "parent": 1,
         "tid": 1, "t_ms": 6.0, "dur_ms": 3.0, "wall": 1.0,
         "attrs": {}},
        {"event": "span", "name": "tick", "id": 4, "parent": None,
         "tid": 1, "t_ms": 11.0, "dur_ms": 5.0, "wall": 1.0,
         "attrs": {}},
    ]
    log = tmp_path / "spans.jsonl"
    log.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    s = summarize_jsonl(log)
    self_t = s["span_self"]
    # tick inclusive 15, children 7 -> self 8; leaves keep their dur
    assert self_t["tick"]["count"] == 2
    assert self_t["tick"]["total_ms"] == 15.0
    assert self_t["tick"]["self_ms"] == 8.0
    assert self_t["collect"]["self_ms"] == 4.0
    assert self_t["window"]["self_ms"] == 3.0
    assert self_t["tick"]["self_pct"] == pytest.approx(
        100.0 * 8 / 15, abs=0.01)
    text = format_summary(s, top=1)
    assert "span self-time (exclusive, top 1 of 3):" in text
    assert "tick" in text.split("span self-time")[1]
    # negative-self clamping: a child longer than its parent
    recs2 = [
        {"event": "span", "name": "p", "id": 1, "parent": None,
         "tid": 1, "t_ms": 0.0, "dur_ms": 2.0, "wall": 1.0,
         "attrs": {}},
        {"event": "span", "name": "c", "id": 2, "parent": 1, "tid": 1,
         "t_ms": 0.0, "dur_ms": 3.0, "wall": 1.0, "attrs": {}},
    ]
    log2 = tmp_path / "spans2.jsonl"
    log2.write_text("\n".join(json.dumps(r) for r in recs2) + "\n")
    assert summarize_jsonl(log2)["span_self"]["p"]["self_ms"] == 0.0
    # append-mode logs hold MULTIPLE runs whose span ids restart per
    # process — a repeated id starts a new segment, so run 2's children
    # must not subtract from run 1's same-id parents
    two_runs = recs + recs          # same ids twice = two runs appended
    log3 = tmp_path / "spans3.jsonl"
    log3.write_text("\n".join(json.dumps(r) for r in two_runs) + "\n")
    st = summarize_jsonl(log3)["span_self"]
    assert st["tick"]["count"] == 4
    assert st["tick"]["self_ms"] == 16.0      # 2x the single-run 8.0
    assert st["collect"]["self_ms"] == 8.0


def test_fit_epoch_jsonl_schema_unchanged(tmp_path, devices):
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data.idc import ArrayDataset
    from idc_models_tpu.models import small_cnn
    from idc_models_tpu.train import TrainState, fit, rmsprop
    from idc_models_tpu.train.losses import binary_cross_entropy

    rng = np.random.default_rng(0)
    ds = ArrayDataset(rng.random((16, 10, 10, 3)).astype(np.float32),
                      (rng.random(16) > 0.5).astype(np.int32))
    model = small_cnn(10, 3, 1)
    opt = rmsprop(1e-3)
    import jax

    variables = model.init(jax.random.key(0))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        fit(model, opt, binary_cross_entropy, state, ds, ds,
            meshlib.data_mesh(), epochs=1, batch_size=8, logger=logger,
            verbose=False)
    recs = [json.loads(l) for l in open(log)]
    eps = [r for r in recs if r["event"] == "epoch"]
    assert len(eps) == 1
    assert set(eps[0]) == {"ts", "event", "epoch", "loss", "accuracy",
                           "val_loss", "val_accuracy"}


def test_tenant_jsonl_schemas_frozen_from_day_one(tmp_path):
    """ISSUE-14: the four tenant-labeled event shapes — finish, shed,
    quota rejection, per-tenant brownout transition — are frozen from
    day one, and every HISTORICAL serve event stays byte-untouched
    (the hooks above prove that; here the tenant twins prove theirs).
    The summary grows ONE additive key: serve_tenants, one record per
    registered tenant with zeros included."""
    from idc_models_tpu.observe.stats import format_summary
    from idc_models_tpu.serve.metrics import ServingMetrics
    from idc_models_tpu.serve.tenancy import TenantQuota, TenantRegistry

    reg = TenantRegistry()
    reg.register("acme", quota=TenantQuota(max_queued=4),
                 slo_ttft_p95_ms=200.0)
    reg.register("globex")
    log = tmp_path / "serve.jsonl"
    with JsonlLogger(log) as logger:
        mreg = MetricsRegistry()
        ten = reg.build(logger=logger, registry=mreg,
                        brownout_dwell_s=0.0)
        m = ServingMetrics(logger, registry=mreg, tenancy=ten)
        m.on_submit("r0", 10.0, tenant="acme")
        m.on_first_token("r0", 0.05, tenant="acme")
        m.on_finish("r0", n_tokens=3, ttft_s=0.05, decode_s=0.1,
                    reason="budget", t=10.3, tenant="acme")
        m.on_shed("r1", tenant="acme")
        m.on_tenant_quota("r2", tenant="acme", kind="queued")
        m.on_tenant_cycle(["acme", "globex"], depths={"acme": 2},
                          slots={"acme": 1}, pages={})
        ten.brownouts["acme"].force_stage(1, reason="drill")
    recs = [json.loads(l) for l in open(log)]
    by_event = {r["event"]: r for r in recs}
    # tenant events are NEW types; the historical serve_* shapes they
    # ride next to keep their exact frozen key sets
    assert set(by_event["serve_submit"]) == {"ts", "event", "id"}
    assert set(by_event["serve_finish"]) == {"ts", "event", "id",
                                             "tokens", "reason",
                                             "ttft_ms"}
    assert set(by_event["serve_shed"]) == {"ts", "event", "id"}
    # the ISSUE-14 tenant events, frozen from day one
    assert set(by_event["serve_tenant_finish"]) == {
        "ts", "event", "id", "tenant", "tokens", "reason", "ttft_ms"}
    assert set(by_event["serve_tenant_shed"]) == {"ts", "event", "id",
                                                  "tenant"}
    assert set(by_event["serve_tenant_quota_reject"]) == {
        "ts", "event", "id", "tenant", "kind"}
    assert set(by_event["serve_tenant_brownout"]) == {
        "ts", "event", "tenant", "stage", "stage_name", "direction",
        "reason"}
    # the additive summary key: one record per REGISTERED tenant,
    # zeros included (globex untouched reads as explicit zeros)
    s = m.summary()
    assert set(s["serve_tenants"]) == {"acme", "globex"}
    assert s["serve_tenants"]["acme"] == {
        "requests": 1, "tokens": 3, "ttft_ms_p50": 50.0,
        "ttft_ms_p95": 50.0, "shed": 1, "quota_rejections": 1,
        "slo_breached": False}
    assert s["serve_tenants"]["globex"]["requests"] == 0
    # the offline stats rollup reads the tenant events into its own
    # per-tenant table
    st = summarize_jsonl(log)
    assert st["tenants"]["acme"]["requests"] == 1
    assert st["tenants"]["acme"]["shed"] == 1
    assert st["tenants"]["acme"]["quota_rejections"] == 1
    assert st["tenants"]["acme"]["by_reason"] == {"budget": 1}
    rendered = format_summary(st)
    assert "tenants:" in rendered and "acme" in rendered


def test_checkpoint_rollout_jsonl_schemas_frozen(tmp_path, devices):
    """ISSUE-17: the three new event shapes — ckpt_save, ckpt_restore,
    serve_rollout — are frozen from day one; the summary grows three
    additive keys (serve_rollouts / serve_rollout_outcome /
    serve_rollout_stage) and the offline stats rollup reads the events
    into its checkpoints/rollouts sections."""
    from idc_models_tpu.checkpoint import restore_sharded, save_sharded
    from idc_models_tpu.observe.stats import format_summary
    from idc_models_tpu.serve.metrics import ServingMetrics

    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        mreg = MetricsRegistry()
        save_sharded(tmp_path / "ck",
                     {"w": np.arange(8, dtype=np.float32)}, step=2,
                     logger=logger, registry=mreg)
        restore_sharded(tmp_path / "ck", logger=logger, registry=mreg)
        m = ServingMetrics(logger, registry=mreg)
        m.on_rollout(stage="staging")
        m.on_rollout(stage="canary")
        m.on_rollout(stage="promoted", outcome="promoted",
                     canary_requests=5)
    recs = [json.loads(l) for l in open(log)]
    by_event = {r["event"]: r for r in recs}
    # the ISSUE-17 events, frozen from day one
    assert set(by_event["ckpt_save"]) == {
        "ts", "event", "path", "step", "leaves", "shards", "bytes",
        "seconds", "background"}
    assert set(by_event["ckpt_restore"]) == {
        "ts", "event", "path", "leaves", "shards_read", "bytes_read",
        "peak_host_bytes", "seconds", "sharded"}
    assert set(by_event["serve_rollout"]) == {
        "ts", "event", "stage", "outcome", "canary_requests", "reason"}
    # the additive summary keys: rollout count, terminal outcome, the
    # stage the machine ended in
    s = m.summary()
    assert s["serve_rollouts"] == 1
    assert s["serve_rollout_outcome"] == "promoted"
    assert s["serve_rollout_stage"] == "promoted"
    # registry instruments from day one
    names = {rec["name"] for rec in mreg.snapshot()}
    assert {"ckpt_saves_total", "ckpt_restores_total",
            "ckpt_bytes_written_total", "ckpt_bytes_read_total",
            "serve_rollouts_total", "serve_rollout_stage_code"} <= names
    # the offline stats rollup: transfer totals + the transition list
    st = summarize_jsonl(log)
    assert st["checkpoints"]["saves"] == 1
    assert st["checkpoints"]["restores"] == 1
    assert st["checkpoints"]["save_bytes"] == 32
    assert st["checkpoints"]["restore_bytes"] == 32
    assert st["checkpoints"]["restore_peak_host_bytes"] > 0
    assert [r["stage"] for r in st["rollouts"]] == [
        "staging", "canary", "promoted"]
    assert st["rollouts"][-1]["outcome"] == "promoted"
    rendered = format_summary(st)
    assert "checkpoints:" in rendered and "rollouts" in rendered


# -- ISSUE 20: every emitted event name is pinned or allowlisted ------------


def test_prefix_and_compile_cache_event_schemas_frozen(tmp_path):
    """The remaining serve-side cache events, frozen from their first
    pinning: prefix hit/miss/evict, the cluster-registry adoption
    marker, and the compile-cache epilogue snapshot (whose payload IS
    `CompileCache.summary()` — one source of truth for both)."""
    from idc_models_tpu.serve.compile_cache import CompileCache
    from idc_models_tpu.serve.metrics import ServingMetrics
    from idc_models_tpu.serve.prefix_cache import PrefixCache
    from idc_models_tpu.serve.cluster import PrefixRegistry

    log = tmp_path / "cache.jsonl"
    chunk = 4
    snap = lambda: {"k": np.zeros((chunk, 4), np.float32)}
    logits = np.zeros(4, np.float32)
    shared = PrefixRegistry(chunk, 1 << 20)
    with JsonlLogger(log) as logger:
        # a sibling cache publishes a prefix into the cluster registry
        feeder = PrefixCache(chunk, 1 << 20, shared=shared)
        assert feeder.insert(np.arange(chunk), snap(), logits)
        # budget fits ONE snapshot: the second insert LRU-evicts
        one = PrefixCache(chunk, 96, logger=logger,
                          registry=MetricsRegistry())
        assert one.insert(np.arange(chunk), snap(), logits)
        one.lookup(np.arange(2 * chunk))               # hit
        assert one.insert(np.arange(chunk) + 1, snap(), logits)
        one.lookup(np.arange(chunk) + 3)               # miss
        # an EMPTY local cache adopts the registry's longer prefix
        adopter = PrefixCache(chunk, 1 << 20, logger=logger,
                              registry=MetricsRegistry(),
                              shared=shared)
        n, caches, _ = adopter.lookup(np.arange(2 * chunk))
        assert n == chunk and caches is not None
        cc = CompileCache(tmp_path / "cc")
        m = ServingMetrics(logger, registry=MetricsRegistry())
        m.on_compile_cache(cc)
    recs = [json.loads(l) for l in open(log)]
    by_event = {}
    for r in recs:
        by_event.setdefault(r["event"], set()).add(frozenset(r))
    assert by_event["serve_prefix_hit"] == {frozenset(
        {"ts", "event", "prefix_tokens", "prompt_tokens"})}
    assert by_event["serve_prefix_miss"] == {frozenset(
        {"ts", "event", "prompt_tokens"})}
    assert by_event["serve_prefix_evict"] == {frozenset(
        {"ts", "event", "freed_bytes"})}
    assert by_event["serve_prefix_shared_hit"] == {frozenset(
        {"ts", "event", "prefix_tokens", "prompt_tokens"})}
    assert by_event["serve_compile_cache"] == {frozenset(
        {"ts", "event"} | set(cc.summary()))}


# one contract line per jsonl event name the package can emit — either
# "pin:" the test that freezes its schema, or "allow:" WHY no frozen
# per-event schema applies. The scan below fails on any event emitted
# but missing here (new events must be pinned or documented before
# they ship) AND on any entry no longer emitted (stale contracts rot).
EVENT_CONTRACTS = {
    # serving metrics events (serve/metrics.py)
    **dict.fromkeys(
        ["serve_submit", "serve_reject", "serve_admit",
         "serve_first_token", "serve_finish", "serve_slot_fault",
         "serve_retry", "serve_shed", "serve_clamp",
         "serve_fault_injected", "serve_spec_verify",
         "serve_page_exhausted"],
        "pin:test_serving_metrics_jsonl_schema_unchanged"),
    **dict.fromkeys(
        ["serve_tenant_finish", "serve_tenant_quota_reject",
         "serve_tenant_shed"],
        "pin:test_tenant_jsonl_schemas_frozen_from_day_one"),
    **dict.fromkeys(
        ["serve_rollout", "ckpt_save", "ckpt_restore"],
        "pin:test_checkpoint_rollout_jsonl_schemas_frozen"),
    **dict.fromkeys(
        ["serve_prefix_hit", "serve_prefix_miss", "serve_prefix_evict",
         "serve_prefix_shared_hit", "serve_compile_cache"],
        "pin:test_prefix_and_compile_cache_event_schemas_frozen"),
    "profile_program": "pin:test_profile_program_jsonl_schema_frozen",
    "profile_step": "pin:test_profile_step_jsonl_schema_frozen",
    "fed_cohort": "pin:test_fed_cohort_jsonl_schema_frozen",
    "round_health": "pin:test_fed_driver_round_health_schema_unchanged",
    "epoch": "pin:test_fit_epoch_jsonl_schema_unchanged",
    "metrics_snapshot": "pin:test_registry_jsonl_snapshot_and_stats",
    # cluster trace-hop + fleet events (ISSUE 20)
    **dict.fromkeys(
        ["cluster_place", "cluster_handoff", "cluster_slot_migrate",
         "cluster_scale_up", "cluster_drain", "cluster_prefix_publish",
         "autoscale_decision"],
        "pin:test_fleet_observability.py::"
        "test_autoscaled_migration_renders_one_merged_timeline"),
    **dict.fromkeys(
        ["cluster_canary", "cluster_shed", "cluster_rollout"],
        "pin:test_fleet_observability.py::"
        "test_canary_and_shed_events_carry_the_trace_schema"),
    "cluster_anomaly": (
        "pin:test_fleet_observability.py::"
        "test_watchdog_detectors_fire_once_and_stay_silent_when_clean"),
    **dict.fromkeys(
        ["cluster_migrate", "cluster_replica_dead"],
        "pin:test_cluster.py::"
        "test_failover_keeps_trace_id_in_merged_timeline"),
    "cluster_hedge": (
        "pin:test_cluster.py::"
        "test_hedge_first_result_wins_and_survives_owner_death"),
    # journal WAL records (serve/journal.py)
    **dict.fromkeys(
        ["journal_submit", "journal_finish"],
        "pin:test_cluster.py::"
        "test_kill_drill_migrates_journal_bit_identical"),
    "journal_migrate": "pin:test_elastic.py (drain/migration drills)",
    "journal_progress": ("pin:test_serve_resilience.py (journal "
                         "replay drills)"),
    "compile_cache": "pin:test_elastic.py (warm spin-up drills)",
    "slo_alert": "pin:test_slo.py",
    "slo_resolved": "pin:test_slo.py",
    # dynamic-payload records: their keys are METRIC sets, not fixed
    # schemas — the corresponding summary-key tests freeze the keys
    "serve_summary": ("allow: payload is LMServer.summary() — keys "
                      "frozen by the summary-key assertions in "
                      "test_serving_metrics_jsonl_schema_unchanged"),
    "cluster_summary": ("allow: payload is Router.summary() — the "
                        "cluster rollup keys, asserted in "
                        "test_cluster.py"),
    "step": "allow: training-loop record; metric keys are preset-defined",
    "round": "allow: fed-loop record; metric keys are preset-defined",
    "val": "allow: eval-loop record; metric keys are preset-defined",
    "test": "allow: eval-loop record; metric keys are preset-defined",
    "generate": "allow: sampling demo record (cli), free-form text",
    "timer": ("allow: {name, seconds} utility record — behavior "
              "covered by test_timer_routes_through_tracer"),
}


def _emitted_event_names():
    """AST scan: every constant ``event=`` kwarg passed to a ``.log``
    or ``._log`` call anywhere in the package."""
    import ast
    from pathlib import Path

    import idc_models_tpu

    root = Path(idc_models_tpu.__file__).parent
    names = set()
    for p in sorted(root.rglob("*.py")):
        tree = ast.parse(p.read_text(), filename=str(p))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            attr = (fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else None)
            if attr not in ("log", "_log"):
                continue
            for kw in node.keywords:
                if (kw.arg == "event"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)):
                    names.add(kw.value.value)
    return names


def test_every_emitted_event_name_is_pinned_or_allowlisted():
    """The frozen-jsonl discipline, enforced structurally: a NEW event
    name cannot ship without either a schema-pinning test or a
    documented allowlist reason, and a contract for an event that no
    longer exists fails loudly instead of rotting."""
    emitted = _emitted_event_names()
    assert emitted, "the scan found no events — scanner broken?"
    unpinned = emitted - set(EVENT_CONTRACTS)
    assert not unpinned, (
        f"events emitted without a schema pin or allowlist entry: "
        f"{sorted(unpinned)} — add a frozen-schema test (preferred) "
        f"or a documented allow: entry to EVENT_CONTRACTS")
    stale = set(EVENT_CONTRACTS) - emitted
    assert not stale, (
        f"EVENT_CONTRACTS entries no longer emitted anywhere: "
        f"{sorted(stale)} — delete them (or the event was renamed "
        f"without updating its pin)")
    for name, contract in EVENT_CONTRACTS.items():
        assert contract.startswith(("pin:", "allow:")), (name, contract)
