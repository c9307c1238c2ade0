"""The readers of a span's attributes (PR 36) on hand-made span records:
the `where` operators, an empty population, the weights, the rate's
first span left out; a program whose spans carry no attributes (the
parent of the PR that set them) reads nothing and raises nothing; and
every metric that PR brought has its file, its entry, its reader and
cells that report what it moves."""

import json

import pytest
from test_rehearsal import M, ROOT

from benchmark.lib import harness, spans
from benchmark.readers import (
    span_attr_quantile, span_attr_rate, span_attr_ratio, span_gap_quantile,
)

CAPACITY = ["gpt2l_chat_decode", "laguna_code_decode", "keye_longctx_decode"]
SERVE = ["gpt2l_chat_decode", "gpt2l_doc_prefill", "laguna_code_decode",
         "keye_longctx_decode"]
NEW = {
    "slot_prefilling_share": CAPACITY, "slot_free_share": CAPACITY,
    "chunks_per_cycle_mean": CAPACITY, "prefill_reserved_p50_ms": CAPACITY,
    "prefill_run_p50_ms": CAPACITY, "emitted_tokens_per_s": CAPACITY,
    "ttft_reserved_p50_ms": ["gpt2l_doc_prefill"],
    "ttft_prefill_p50_ms": ["gpt2l_doc_prefill"],
    # only where the refill pass admits in every run: above the knee
    "turnaround_admitting_p50_ms": ["laguna_code_decode",
                                    "keye_longctx_decode"],
    "delivery_gap_p95_ms": SERVE,
    "dsa_folded_over_live": ["keye_longctx_decode"],
}


def _ctx(records, files=None, counters=None):
    return harness.Context(
        cell={}, config={}, traffic={}, peaks={}, counters=counters or {},
        span_records=records, trace=None, window=None,
        metric_files=files or {})


def _tick(t, dur, **attrs):
    return {"name": "serve.tick", "t_ms": t, "dur_ms": dur, "attrs": attrs}


# five cycles of a 4-slot server; the third holds a free slot beside a
# queue, the last neither queue nor prefill
TICKS = [
    _tick(0.0, 10.0, slots=4, decoding=2, prefilling=2, free=0, queue=3,
          chunk_steps=4, tokens=7),
    _tick(10.0, 30.0, slots=4, decoding=3, prefilling=1, free=0, queue=2,
          chunk_steps=2, tokens=16),
    _tick(40.0, 10.0, slots=4, decoding=2, prefilling=1, free=1, queue=1,
          chunk_steps=0, tokens=0),
    _tick(50.0, 50.0, slots=4, decoding=1, prefilling=0, free=3, queue=0,
          chunk_steps=1, tokens=8),
    _tick(100.0, 20.0, slots=4, decoding=1, prefilling=0, free=3, queue=0,
          chunk_steps=0, tokens=4),
]
OLD = [{"name": "serve.tick", "t_ms": 10.0 * i, "dur_ms": 10.0, "attrs": {}}
       for i in range(3)] + [{"name": "serve.tick", "t_ms": 30.0,
                              "dur_ms": 5.0}]


@pytest.mark.parametrize("where,want", [
    ({"queue": [">", 0]}, [0, 1, 2]),
    ({"queue": [">=", 2]}, [0, 1]),
    ({"queue": ["==", 0], "tokens": [">", 4]}, [3]),          # all of a dict
    ([{"prefilling": [">", 0]}, {"chunk_steps": [">", 0]}],   # any of a list
     [0, 1, 2, 3]),
    ({"never_set": [">", 0]}, []),
    (None, [0, 1, 2, 3, 4]),
], ids=["gt", "ge", "and", "or", "absent_attr", "none"])
def test_where_chooses_the_population(where, want):
    got = spans.select(TICKS + [{"name": "serve.admit", "t_ms": 0.0,
                                 "dur_ms": 1.0, "attrs": {"queue": 9}}],
                       "serve.tick", where)
    assert got == [TICKS[i] for i in want]


def test_an_unknown_operator_is_refused():
    with pytest.raises(KeyError):
        spans.select(TICKS, "serve.tick", {"queue": ["<", 1]})


@pytest.mark.parametrize("args,want", [
    (dict(num="prefilling", den="slots", scale=100.0), 20.0),
    (dict(num="free", den="slots", scale=100.0,
          where={"queue": [">", 0]}), 100.0 / 12),
    (dict(num="chunk_steps",                      # over the spans' number
          where=[{"prefilling": [">", 0]}, {"chunk_steps": [">", 0]}]), 1.75),
    (dict(num="free", den="slots", where={"queue": [">", 9]}), None),
    (dict(num="free", den="slots", where={"queue": [">", 9]},
          otherwise=0.0), 0.0),
    (dict(num="free", den="never_set"), None),
], ids=["share", "where", "count", "nothing_meets", "otherwise", "no_attr"])
def test_ratio(args, want):
    got = span_attr_ratio.read(_ctx(TICKS), span="serve.tick", **args)
    assert got == want if want is None else got == pytest.approx(want)


def test_ratio_of_an_older_program_is_nothing_even_with_otherwise():
    assert span_attr_ratio.read(
        _ctx(OLD), span="serve.tick", num="free", den="slots",
        where={"queue": [">", 0]}, otherwise=0.0) is None


@pytest.mark.parametrize("args,want", [
    (dict(q=50), 20.0),                                   # durations
    (dict(q=50, where={"tokens": [">", 0]}), 25.0),
    (dict(q=50, attr="tokens"), 7.0),
    (dict(q=100, attr="tokens", where={"queue": ["==", 0]}), 8.0),
    # each cycle's tokens as often as slots decoded: 7 7 16 16 16 0 0 8 4
    (dict(q=50, attr="tokens", weight="decoding"), 7.0),
    (dict(q=75, attr="tokens", weight="decoding"), 16.0),
    (dict(q=50, attr="tokens", where={"queue": [">", 5]}), None),
    (dict(q=50, attr="never_set"), None),
], ids=["dur", "dur_where", "attr", "attr_where", "weighted_p50",
        "weighted_p75", "nothing_meets", "no_attr"])
def test_quantile(args, want):
    got = span_attr_quantile.read(_ctx(TICKS), span="serve.tick", **args)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("args,want", [
    # ends at 10, 40, 50, 100, 120: gaps 30, 10, 50, 20
    (dict(q=100), 50.0),
    (dict(q=50), 25.0),
    # deliveries end at 10, 40, 100, 120: gaps 30, 60, 20
    (dict(q=100, where={"tokens": [">", 0]}), 60.0),
    # weighed by the streams the span that starts a gap left live
    # (2, 3, 1): 30 30 60 60 60 20
    (dict(q=50, weight="decoding", where={"tokens": [">", 0]}), 30.0),
    (dict(q=95, weight="decoding", where={"tokens": [">", 0]}), 60.0),
    (dict(q=50, where={"tokens": [">", 8]}), None),       # one span: no gap
], ids=["all_p100", "all_p50", "where", "weighted_p50", "weighted_p95",
        "one_span"])
def test_gap_quantile(args, want):
    got = span_gap_quantile.read(_ctx(TICKS), span="serve.tick", **args)
    assert got == want if want is None else got == pytest.approx(want)


def test_a_gap_nobody_waited_through_weighs_nothing():
    idle = [_tick(0.0, 10.0, decoding=0, tokens=3),
            _tick(500.0, 10.0, decoding=2, tokens=2),
            _tick(540.0, 10.0, decoding=2, tokens=16)]
    read = span_gap_quantile.read
    assert read(_ctx(idle), span="serve.tick", q=95, weight="decoding",
                where={"tokens": [">", 0]}) == 40.0
    assert read(_ctx(idle[:2]), span="serve.tick", q=95, weight="decoding",
                where={"tokens": [">", 0]}) is None


def test_rate_leaves_the_first_span_out():
    # 16 + 0 + 8 + 4 tokens between the first end (10) and the last (120)
    got = span_attr_rate.read(_ctx(TICKS), span="serve.tick", attr="tokens")
    assert got == pytest.approx(28 / 0.110)
    got = span_attr_rate.read(_ctx(TICKS), span="serve.tick", attr="tokens",
                              where={"queue": ["==", 0]})
    assert got == pytest.approx(4 / 0.020)
    assert span_attr_rate.read(_ctx(TICKS[:1]), span="serve.tick",
                               attr="tokens") is None
    assert span_attr_rate.read(_ctx(TICKS), span="serve.tick",
                               attr="never_set") is None


def test_weighted_percentile_without_weight_is_nothing():
    assert spans.weighted_percentile([1.0, 2.0], [0, 0], 50) is None
    assert spans.weighted_percentile([3.0, 1.0], [1, 1], 100) == 3.0


def _spec(name):
    return json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())


def test_each_metric_has_its_file_its_entry_its_reader_and_its_cells():
    entries = {m["name"]: m for m in M["per_layer"]}
    reports = {m["name"]: set(m["workloads"]) for m in M["end_to_end"]
               if "workloads" in m}
    for name, cells in NEW.items():
        entry, spec = entries[name], _spec(name)
        assert entry["workloads"] == cells, name
        assert set(cells) <= reports[entry["moves"]], name
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            name, entry["unit"], entry["layer"], entry["moves"])
        assert (ROOT / "benchmark" / "readers"
                / f"{spec['reader']}.py").exists()
        assert entry["source"] == ("program_counter"
                                   if spec["reader"] == "counter"
                                   else "program_span")
        if spec["reader"] != "counter":
            assert spec["args"]["span"] in (
                "serve.tick", "serve.turnaround", "serve.first_token")


def test_the_parents_spans_leave_every_metric_out():
    """Spans without the attributes, and a `summary()` without the key:
    every new metric reads nothing, and none raises."""
    old = OLD + [{"name": "serve.turnaround", "t_ms": 1.0, "dur_ms": 2.0,
                  "attrs": {"slots": 3, "dispatched": True}},
                 {"name": "serve.first_token", "t_ms": 2.0, "dur_ms": 0.0,
                  "attrs": {"rid": "a", "ttft_ms": 9.0}}]
    ctx = _ctx(old, files={n: _spec(n) for n in NEW})
    assert {n: ctx.metric(n) for n in NEW} == dict.fromkeys(NEW)


def test_this_programs_records_give_every_metric_a_value():
    recs = TICKS + [
        {"name": "serve.turnaround", "t_ms": 12.0, "dur_ms": 25.0,
         "attrs": {"slots": 3, "dispatched": True, "admitted": 1}},
        {"name": "serve.turnaround", "t_ms": 52.0, "dur_ms": 2.0,
         "attrs": {"slots": 1, "dispatched": True, "admitted": 0}},
        {"name": "serve.first_token", "t_ms": 39.0, "dur_ms": 0.0,
         "attrs": {"rid": "a", "ttft_ms": 30.0, "queue_ms": 4.0,
                   "reserved_ms": 6.0, "prefill_ms": 20.0, "chunks": 3,
                   "prompt_len": 40}}]
    ctx = _ctx(recs, files={n: _spec(n) for n in NEW},
               counters={"summary.serve_dsa_folded_over_live": 1.25})
    got = {n: ctx.metric(n) for n in NEW}
    assert got == pytest.approx({
        "slot_prefilling_share": 20.0, "slot_free_share": 100.0 / 12,
        "chunks_per_cycle_mean": 1.75, "prefill_reserved_p50_ms": 6.0,
        "prefill_run_p50_ms": 20.0, "emitted_tokens_per_s": 28 / 0.110,
        "ttft_reserved_p50_ms": 6.0, "ttft_prefill_p50_ms": 20.0,
        "turnaround_admitting_p50_ms": 25.0, "delivery_gap_p95_ms": 60.0,
        "dsa_folded_over_live": 1.25})
