"""Each runner end to end on the CPU at the tiny sizes the data files
give under `rehearsal`: the last line is the contract's, and without the
flag a machine without a TPU gets no result. Slow for unit tests (VGG16
compiles on the CPU), so one run per runner and trace mode."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
M = json.loads((ROOT / "BENCHMARK.json").read_text())
REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}
IGNORED = {"breakdown", "checks", "counts"}


def run_cell(*args, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def names(group, cell):
    return {m["name"] for m in M[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell,trace", [
    ("vgg16_fit_1chip", 0), ("vgg16_fit_dp4", 1),
    ("gpt2l_chat_decode", 0), ("gpt2l_doc_prefill", 1)])
def test_rehearsal_prints_the_contracts_line(cell, trace):
    p = run_cell("--workload", cell, "--seed", "5", "--seconds", "3",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert REQUIRED <= set(line) <= REQUIRED | IGNORED
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    chips = next(w["chips"] for w in M["workloads"] if w["name"] == cell)
    assert line["device"]["platform"] == "cpu"      # never a device's name
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and isinstance(v["value"], float)
    if trace:
        # what needs the device's trace or its peaks has nothing to read
        # on the CPU, and is left out, not made up
        assert set(line["metrics"]) <= names("per_layer", cell)
        assert "compile_s" in line["metrics"]
        assert not any(k.endswith("_roofline") or "idle" in k
                       for k in line["metrics"])
    else:
        assert set(line["metrics"]) == names("end_to_end", cell)
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_no_tpu_no_result():
    p = run_cell("--workload", "gpt2l_chat_decode", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr
