import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.lib import stats, traffic_gen as tg

TRAFFIC = Path(__file__).resolve().parent.parent / "traffic"


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())["open_loop"]


def test_same_seed_same_trace_other_seed_other_trace():
    mix = _mix("chat_decode")
    kw = dict(segments=[(0.0, 5.0), (5.0, 20.0)], vocab=50257, t_max=1024)
    a = tg.open_loop_trace(mix, seed=3, **kw)
    assert a == tg.open_loop_trace(mix, seed=3, **kw)
    assert a != tg.open_loop_trace(mix, seed=4, **kw)


def test_chat_lengths_have_the_stated_quantiles():
    mix = _mix("chat_decode")
    rng = np.random.default_rng(0)
    prompts = tg.draw_lengths(rng, mix["prompt_len"], 10_000)
    outputs = tg.draw_lengths(rng, mix["output_len"], 10_000)
    assert stats.median(prompts) == pytest.approx(48, rel=0.03)
    assert stats.median(outputs) == pytest.approx(160, rel=0.03)
    assert prompts.min() >= 8 and prompts.max() <= 256
    assert outputs.min() >= 32 and outputs.max() <= 512
    # a tail: the 95th percentile is well over twice the median
    assert stats.percentile(prompts, 95) > 2 * 48


def test_doc_lengths_are_uniform_over_their_range():
    mix = _mix("doc_prefill")
    x = tg.draw_lengths(np.random.default_rng(1), mix["prompt_len"], 10_000)
    assert x.min() == 384 and x.max() == 960
    assert stats.median(x) == pytest.approx(672, rel=0.03)


def test_arrivals_keep_the_count_in_every_segment_and_the_start_burst():
    mix = dict(_mix("chat_decode"), rate_per_s=10.0, burst_at_start=24)
    for seed in (0, 1):
        tr = tg.open_loop_trace(mix, seed=seed, segments=[(0.0, 5.0), (5.0, 35.0)],
                                vocab=100, t_max=1024)
        assert sum(1 for a in tr if a.due_s == 0.0) == 24
        assert sum(1 for a in tr if 0.0 < a.due_s < 5.0) == 50
        assert sum(1 for a in tr if 5.0 <= a.due_s < 35.0) == 300
        due = [a.due_s for a in tr]
        assert due == sorted(due) and len({a.rid for a in tr}) == len(tr)
    # gaps are a Poisson stream's: their spread is about their mean
    gaps = np.diff([a.due_s for a in tr if a.due_s >= 5.0])
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.2)


def test_every_seed_draws_the_same_amount_of_work():
    mix = _mix("doc_prefill")
    totals = []
    for seed in range(4):
        tr = tg.open_loop_trace(mix, seed=seed, segments=[(0.0, 30.0)],
                                vocab=100, t_max=1024)
        totals.append(sum(len(a.prompt) for a in tr))
    assert max(totals) / min(totals) < 1.01
    assert len(set(totals)) > 1


def test_lengths_fit_the_context():
    mix = dict(_mix("doc_prefill"),
               output_len={"dist": "fixed", "value": 200})
    tr = tg.open_loop_trace(mix, seed=0, segments=[(0.0, 30.0)], vocab=100, t_max=1024)
    assert all(len(a.prompt) + a.max_new_tokens <= 1024 for a in tr)
    assert all(0 <= t < 100 for a in tr for t in a.prompt)


def test_unused_fields_are_read_so_a_later_mix_needs_no_code():
    mix = dict(_mix("chat_decode"), rate_per_s=5.0, burst_at_start=0,
               bursts={"every_s": 2.0, "size": 3},
               prefix_sharing={"share": 1.0, "prefix_len": 6, "n_prefixes": 1})
    tr = tg.open_loop_trace(mix, seed=0, segments=[(0.0, 9.0)], vocab=1000, t_max=1024)
    assert sum(1 for a in tr if a.due_s in (2.0, 4.0, 6.0, 8.0)) == 12
    heads = {a.prompt[:6] for a in tr if len(a.prompt) > 6}
    assert len(heads) == 1


def test_stratified_arrivals_put_one_in_every_stretch():
    mix = dict(_mix("doc_prefill"), rate_per_s=4.0, arrivals="stratified")
    assert _mix("doc_prefill")["arrivals"] == "stratified"
    tr = tg.open_loop_trace(mix, seed=7, segments=[(0.0, 3.0), (3.0, 33.0)],
                            vocab=100, t_max=1024)
    window = [a.due_s for a in tr if a.due_s >= 3.0]
    assert len(window) == 120
    assert [int((t - 3.0) * 4.0) for t in window] == list(range(120))
    assert tr != tg.open_loop_trace(mix, seed=8, segments=[(0.0, 3.0), (3.0, 33.0)],
                                    vocab=100, t_max=1024)
    with pytest.raises(ValueError):
        tg.arrival_times(np.random.default_rng(0), dict(mix, arrivals="x"), 0, 1)
