"""What the scheduler accounts for on the spans it already opens (ISSUE
36): the cycle's record on `serve.tick` (the slots' states and the queue
at the dispatch decision, and the cycle's work), `admitted` on
`serve.turnaround`, and a request's way to its first token by phase on
`serve.first_token` and on `Result`. With no tracer the same requests
give the same tokens and the same phases, and no span is handed a list.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models.lm import attention_lm
from idc_models_tpu.observe import trace
from idc_models_tpu.serve import LMServer, Request

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2
RECORD = {"slots", "decoding", "prefilling", "free", "queue", "admitted",
          "chunk_steps", "tokens", "dispatched"}
CHUNKED = pytest.mark.parametrize("chunk", [4, None],
                                  ids=["chunked", "unchunked"])


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _requests():
    """Five prompts of 13-17 tokens (four or five chunks of 4) for two
    slots: the second is reserved behind the first, the rest queue."""
    rng = np.random.default_rng(1)
    return [Request(id=f"r{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 13 + i)),
                    max_new_tokens=6 + i)
            for i in range(5)]


def _serve(params, chunk, tracer=None):
    with trace.tracing(tracer=tracer):
        server = LMServer(params, n_slots=2, window=4, prefill_chunk=chunk,
                          embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                          t_max=SEQ, cache_dtype=jnp.float32)
        results = server.run([(0.0, r) for r in _requests()])
    assert [r.status for r in results] == ["ok"] * 5
    return server, {r.id: r for r in results}


@pytest.fixture(scope="module")
def traced(params):
    out = {}
    for chunk in (4, None):
        tracer = trace.Tracer()
        _, results = _serve(params, chunk, tracer)
        out[chunk] = (tracer.records(), results)
    return out


def _named(records, name):
    return [r for r in records if r["name"] == name]


@CHUNKED
def test_every_tick_accounts_for_every_slot_and_every_token(traced, chunk):
    records, results = traced[chunk]
    ticks = _named(records, "serve.tick")
    assert ticks
    for t in ticks:
        a = t["attrs"]
        assert set(a) == RECORD
        assert all(type(v) is int for v in a.values()), a
        assert a["slots"] == 2
        assert a["decoding"] + a["prefilling"] + a["free"] == a["slots"]
        assert a["dispatched"] == (1 if a["decoding"] else 0)
    assert (sum(t["attrs"]["tokens"] for t in ticks)
            == sum(len(r.tokens) for r in results.values()))
    assert sum(t["attrs"]["admitted"] for t in ticks) == 5
    # every chunk dispatch is some cycle's work and some request's chunk
    firsts = _named(records, "serve.first_token")
    assert (sum(t["attrs"]["chunk_steps"] for t in ticks)
            == (sum(f["attrs"]["chunks"] for f in firsts) if chunk else 0))
    # a reserved slot is neither decoding nor free, and a request waits
    # beside it: only a chunked engine reserves
    assert any(t["attrs"]["prefilling"] and t["attrs"]["queue"]
               for t in ticks) == (chunk is not None)


@CHUNKED
def test_the_phases_add_up_to_the_time_to_the_first_token(traced, chunk):
    records, results = traced[chunk]
    firsts = {f["attrs"]["rid"]: f["attrs"]
              for f in _named(records, "serve.first_token")}
    assert set(firsts) == set(results)
    for req in _requests():
        a, r = firsts[req.id], results[req.id]
        assert a["prompt_len"] == len(req.prompt)
        assert a["chunks"] == (-(-len(req.prompt) // chunk) if chunk else 1)
        # rounded to a microsecond each on the span
        assert (a["queue_ms"] + a["reserved_ms"] + a["prefill_ms"]
                == pytest.approx(a["ttft_ms"], abs=0.003))
        assert (r.queue_ms + r.reserved_ms + r.prefill_ms
                == pytest.approx(r.ttft_ms, abs=1e-6))
        assert a["ttft_ms"] == pytest.approx(r.ttft_ms, abs=0.001)
        assert a["reserved_ms"] == pytest.approx(r.reserved_ms, abs=0.001)
        assert min(r.queue_ms, r.reserved_ms) >= 0 and r.prefill_ms > 0
    if chunk:
        # r1 was admitted with r0 and stood reserved while all the
        # cycle's chunk dispatches went to r0
        assert results["r1"].reserved_ms > results["r0"].reserved_ms > 0
        assert firsts["r1"]["chunks"] > 1
    else:
        assert {r.reserved_ms for r in results.values()} == {0.0}
    # the queue's tail waited for a slot, the head did not
    assert results["r4"].queue_ms > results["r0"].queue_ms


@CHUNKED
def test_turnaround_carries_the_refill_passs_admissions(traced, chunk):
    records, _ = traced[chunk]
    turns = _named(records, "serve.turnaround")
    assert turns
    for t in turns:
        assert {"slots", "dispatched", "admitted"} <= set(t["attrs"])
    # a turnaround holds its own cycle's refill pass (both are children
    # of the tick) and carries that pass's count
    refills = {r["parent"]: r["attrs"]["admitted"]
               for r in _named(records, "serve.refill")}
    for t in turns:
        assert t["attrs"]["admitted"] == refills[t["parent"]]
    assert any(t["attrs"]["admitted"] for t in turns)


@CHUNKED
def test_without_a_tracer_same_tokens_same_phases_and_no_list(
        traced, params, chunk, monkeypatch):
    handed = []
    real = {n: getattr(trace, n) for n in ("span", "start_span", "point")}

    def spy(name):
        def call(*args, **kw):
            handed.append(kw)
            return real[name](*args, **kw)
        return call

    for n in real:
        monkeypatch.setattr(trace, n, spy(n))
    monkeypatch.setattr(trace._NullSpan, "set",
                        lambda self, **kw: handed.append(kw) or self)
    monkeypatch.setattr(trace._NullSpan, "close",
                        lambda self, **kw: handed.append(kw))
    assert trace.get_tracer() is None
    server, results = _serve(params, chunk)
    assert any("decoding" in kw for kw in handed)     # the spies saw it
    assert not [kw for kw in handed
                if any(isinstance(v, (list, tuple, dict, set))
                       for v in kw.values())]
    _, with_tracer = traced[chunk]
    for rid, r in results.items():
        assert r.tokens == with_tracer[rid].tokens
        assert (r.queue_ms + r.reserved_ms + r.prefill_ms
                == pytest.approx(r.ttft_ms, abs=1e-6))
        assert (r.reserved_ms > 0) == (chunk is not None)
    # the one source of the queue wait and of summary()'s prefill figure
    m = server.metrics
    assert not hasattr(m, "_wait_by_rid")
    assert len(m.queue_wait_s) == len(m.prefill_s) == 5
    assert (sorted(m.prefill_s) == pytest.approx(sorted(
        (r.reserved_ms + r.prefill_ms) / 1e3 for r in results.values())))


def test_a_request_that_never_got_a_slot_has_no_phase_it_never_reached(
        params):
    t = [0.0]
    server = LMServer(params, n_slots=1, window=4, prefill_chunk=4,
                      embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                      t_max=SEQ, cache_dtype=jnp.float32,
                      clock=lambda: t[0])
    long_, late = _requests()[:2]
    server.submit(long_)
    server.submit(Request(id="late", prompt=late.prompt, max_new_tokens=4,
                          deadline_s=0.5))
    server.step()                       # `long_` takes the one slot
    t[0] = 1.0
    while server.poll("late") is None:
        server.step()
    r = server.poll("late")
    assert r.status == "timeout" and r.ttft_ms is None
    assert (r.queue_ms, r.reserved_ms, r.prefill_ms) == (None, None, None)
    t[0] = 2.0
    server.drain()
    done = server.poll(long_.id)
    # the clock stood at 0 through its admission and its first chunk
    assert (done.queue_ms, done.reserved_ms) == (0.0, 0.0)
    assert done.prefill_ms == done.ttft_ms > 0


def test_the_first_token_event_logs_the_three_phases(tmp_path):
    import json

    from idc_models_tpu.observe import JsonlLogger
    from idc_models_tpu.observe.metrics_registry import MetricsRegistry
    from idc_models_tpu.serve.metrics import ServingMetrics

    log = tmp_path / "serve.jsonl"
    with JsonlLogger(log) as logger:
        m = ServingMetrics(logger, registry=MetricsRegistry())
        m.on_first_token("a", 0.05, queue_s=0.01, reserved_s=0.015,
                         prefill_s=0.025)
        m.on_first_token("b", 0.05)            # a caller without them
    a, b = (json.loads(line) for line in open(log))
    assert (a["queue_ms"], a["reserved_ms"], a["prefill_ms"]) == (
        10.0, 15.0, 25.0)
    assert (b["queue_ms"], b["reserved_ms"], b["prefill_ms"]) == (
        None, None, None)
    # summary()'s figure stays slot claimed -> first token
    assert m.prefill_s == [pytest.approx(0.04)]
