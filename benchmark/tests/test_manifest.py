"""`BENCHMARK.json` against the rules of its contract that can be checked
without a run: a file outside them is refused before any run."""

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(_line(w) for w in M["command"])
    assert 1 <= len(M["paths"]) <= 16
    # a full check with the full 24 cells fits into its 43,200 seconds
    assert ((2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200


def test_configs():
    assert 1 <= len(M["configs"]) <= 24
    names = [c["name"] for c in M["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])


def test_workloads():
    cells = M["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in M["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json").exists()
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_metrics():
    cells = {w["name"] for w in M["workloads"]}
    e2e, layers = M["end_to_end"], M["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    where = {}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
        where[m["name"]] = set(m.get("workloads", cells))
    assert where["setup_s"] == cells
    for cell in cells:                 # setup_s and at least one other
        assert sum(1 for n, w in where.items() if cell in w) >= 2
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= where[m["moves"]]
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{m['name']}.json").read_text())
        assert (ROOT / "benchmark" / "readers" / f"{spec['reader']}.py").exists()
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
    for cell in cells:                 # at least one per-layer metric each
        assert any(cell in m.get("workloads", cells) for m in layers)
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        assert set(m.get("workloads", [])) <= cells


def test_files_under_paths_have_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in M["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert ok.match(str(f.relative_to(ROOT))), f
