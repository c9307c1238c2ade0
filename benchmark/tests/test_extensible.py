"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric (with a reader of its own) by new files and new entries alone:
done here in a temporary copy, and run on the CPU."""

import json
import shutil
from pathlib import Path

from test_rehearsal import ROOT, run_cell


def test_new_cell_config_mix_and_metric_need_only_files_and_entries(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "idc_models_tpu").symlink_to(ROOT / "idc_models_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    bench = tmp_path / "benchmark"

    config = json.loads((bench / "configs" / "gpt2-large.json").read_text())
    config["name"] = "tiny-lm"
    config["model"] = dict(config["model"], **config["rehearsal"]["model"])
    (bench / "configs" / "tiny-lm.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "doc_prefill.json").read_text())
    mix["name"] = "bursty"
    mix["rehearsal"]["open_loop"]["bursts"] = {"every_s": 1.0, "size": 2}
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "readers" / "span_count.py").write_text(
        "def read(ctx, *, span):\n"
        "    return float(sum(1 for r in ctx.span_records"
        " if r['name'] == span))\n")
    (bench / "layer_metrics" / "ticks_in_window.json").write_text(json.dumps({
        "name": "ticks_in_window", "layer": "serve scheduler",
        "unit": "count", "moves": "tpot_p50_ms", "reader": "span_count",
        "args": {"span": "serve.tick"}}))

    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-lm", "source": "none: a throw-away",
                         "file": "benchmark/configs/tiny-lm.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny_bursty", "config": "tiny-lm",
                           "traffic": "bursty", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] in ("ttft_mean_ms", "tpot_p50_ms"):
            e["workloads"].append("tiny_bursty")
    m["per_layer"].append({"name": "ticks_in_window", "unit": "count",
                           "better": "higher", "source": "program_span",
                           "layer": "serve scheduler", "moves": "tpot_p50_ms",
                           "workloads": ["tiny_bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    for trace in (0, 1):
        p = run_cell("--workload", "tiny_bursty", "--seed", "2", "--seconds",
                     "3", "--trace", str(trace), "--rehearse", root=tmp_path)
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"] is True
        if trace:
            assert line["metrics"]["ticks_in_window"]["value"] > 0
        else:
            assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms",
                                            "setup_s"}
    # nothing that was there was edited
    assert all(p.read_bytes() == b for p, b in before.items())
