"""`staging_wait_share` (PR 25): the producer's wait for a slot of the
loader's staging ring, `data.recycle`, over `train.epoch`, through the
`span_ratio` reader that was there. A data file and an entry, listed for
the two train cells; the traced rehearsal of a train cell prints it, and a
program without the span (the parent of the PR that added it) reads 0."""

import json

import pytest
from test_rehearsal import M, ROOT, run_cell

NAME = "staging_wait_share"
TRAIN = ["vgg16_fit_1chip", "vgg16_fit_dp4"]
SPEC = json.loads(
    (ROOT / "benchmark" / "layer_metrics" / f"{NAME}.json").read_text())


def _read(records):
    from benchmark.lib import harness

    ctx = harness.Context(
        cell={}, config={}, traffic={}, peaks={}, counters={},
        span_records=records, trace=None, window=None,
        metric_files={NAME: SPEC})
    return ctx.metric(NAME)


def test_the_file_and_the_entry_agree():
    entry = next(m for m in M["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_span", "layer": "input pipeline",
                     "moves": "train_patches_per_s_chip", "workloads": TRAIN}
    assert M["per_layer"][-1] is entry          # appended, nothing moved
    assert SPEC["reader"] == "span_ratio"
    assert SPEC["args"] == {"num": "data.recycle", "den": "train.epoch"}
    assert (SPEC["unit"], SPEC["layer"], SPEC["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])


@pytest.mark.parametrize("records,want", [
    # two epochs of 1,500 ms; the ring engaged eight times, 3 + 27 ms
    ([{"name": "train.epoch", "t_ms": 0.0, "dur_ms": 1500.0},
      {"name": "train.epoch", "t_ms": 1500.0, "dur_ms": 1500.0}]
     + [{"name": "data.recycle", "t_ms": 10.0 * i, "dur_ms": d}
        for i, d in enumerate([0.0] * 6 + [3.0, 27.0])]
     + [{"name": "data.load", "t_ms": 0.0, "dur_ms": 500.0}], 1.0),
    # the parent: epochs, and no such span
    ([{"name": "train.epoch", "t_ms": 0.0, "dur_ms": 5.0},
      {"name": "data.load", "t_ms": 0.0, "dur_ms": 4.0}], 0.0),
    # no epoch in the window: nothing to divide by, left out
    ([{"name": "data.recycle", "t_ms": 0.0, "dur_ms": 5.0}], None),
], ids=["recorded", "numerator_absent", "denominator_absent"])
def test_read_from_a_recorded_span_list(records, want):
    got = _read(records)
    assert got == want if want is None else got == pytest.approx(want)


def test_traced_rehearsal_of_a_train_cell_prints_it():
    p = run_cell("--workload", "vgg16_fit_1chip", "--seed", "2500000011",
                 "--seconds", "3", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = line["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 <= got["value"] < 100.0
