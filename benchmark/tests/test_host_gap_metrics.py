"""The per-layer metrics that read the host's side of an idle gap (PR
24): each has its file and its list of cells, and the traced rehearsal
of every cell, on the CPU, prints each one listed for it with a value.
A program without the spans (the parent of the PR that added them)
leaves the quantiles out and does not raise."""

import json

import pytest
from test_rehearsal import M, ROOT, run_cell

TRAIN = ["vgg16_fit_1chip", "vgg16_fit_dp4"]
SERVE = ["gpt2l_chat_decode", "gpt2l_doc_prefill"]
NEW = {
    "input_wait_share": ("data.wait", TRAIN),
    "loader_batch_p50_ms": ("data.load", TRAIN),
    "h2d_transfer_p50_ms": ("data.transfer", TRAIN),
    "prefetch_full_share": ("data.full", TRAIN),
    "window_turnaround_p50_ms": ("serve.turnaround", SERVE),
    "insert_host_p50_ms": ("serve.insert", ["gpt2l_doc_prefill"]),
    "start_prefill_host_p50_ms": ("serve.start_prefill", SERVE),
}


def _spec(name):
    return json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())


def test_each_new_metric_has_its_file_and_its_cells():
    entries = {m["name"]: m for m in M["per_layer"]}
    for name, (span, cells) in NEW.items():
        entry, spec = entries[name], _spec(name)
        assert entry["source"] == "program_span"
        assert entry["workloads"] == cells
        assert spec["reader"] in ("span_ratio", "span_quantile")
        assert span in spec["args"].values()
        assert (spec["unit"], spec["layer"]) == (entry["unit"], entry["layer"])


@pytest.mark.parametrize("cell", TRAIN + SERVE)
def test_traced_rehearsal_prints_every_new_metric(cell):
    p = run_cell("--workload", cell, "--seed", "7", "--seconds", "3",
                 "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    for name, (_, cells) in NEW.items():
        if cell in cells:
            assert isinstance(line["metrics"][name]["value"], float), name
        else:
            assert name not in line["metrics"]
    # what the cell reported before is still there
    assert {"compile_s", "hbm_peak_gb"} <= set(line["metrics"])
    assert ("loop_sync_share" if cell in TRAIN
            else "prefill_stall_mean_ms") in line["metrics"]


def test_a_program_without_the_spans_leaves_the_metrics_out():
    from benchmark.lib import harness

    old = [{"name": "train.epoch", "t_ms": 0.0, "dur_ms": 5.0},
           {"name": "serve.tick", "t_ms": 0.0, "dur_ms": 5.0}]
    ctx = harness.Context(
        cell={}, config={}, traffic={}, peaks={}, counters={},
        span_records=old, trace=None, window=None,
        metric_files={n: _spec(n) for n in NEW})
    got = {n: ctx.metric(n) for n in NEW}
    assert all(v is None for n, v in got.items() if n.endswith("_ms")), got
    assert got["input_wait_share"] == got["prefetch_full_share"] == 0.0
