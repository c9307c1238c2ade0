"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json`; everything that
belongs to it is data this program finds by name: the configuration's
file, `benchmark/traffic/<traffic>.json`, the runner named in the
configuration (`benchmark/runners/<runner>.py`) and, in a traced run, one
`benchmark/layer_metrics/<name>.json` per per-layer metric with its
reader (`benchmark/readers/<reader>.py`). The last line of standard
output is the result; without a TPU there is none, and the exit code is
not 0. `--rehearse` (for the tests, on the CPU) runs the same code at the
tiny sizes the data files give under `rehearsal`; its numbers mean
nothing and its device line says `cpu`.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent
sys.path.insert(0, str(REPO_DIR))


def merged(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, dict by dict."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merged(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def load_cell(workload: str, *, rehearse: bool):
    """The cell's manifest entries and its data files."""
    manifest = json.loads((REPO_DIR / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = json.loads((REPO_DIR / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    if rehearse:
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    return manifest, cell, config, traffic


def metrics_for(manifest: dict, group: str, workload: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def traced_metrics(manifest, cell, config, traffic, out, device, compile_log):
    """The cell's per-layer metrics, the device's busy time and the
    breakdown, from the traced run."""
    from benchmark.lib import harness, peaks, reduce_trace

    names = metrics_for(manifest, "per_layer", cell["name"])
    files = {m["name"]: json.loads(
        (BENCH_DIR / "layer_metrics" / f"{m['name']}.json").read_text())
        for m in names}
    profiler, tracer = out["profiler"], out["tracer"]
    harness.note("reading the profiler's trace")
    trace = profiler.load() if profiler is not None else None
    harness.note("trace read")
    if not trace or not trace["devices"]:
        if device["platform"] == "tpu":
            raise SystemExit("traced run: no operation ran on the device "
                             "inside the profiled slice")
        trace = trace or {"devices": {}, "host": []}
    records = tracer.records() if tracer is not None else []
    in_window = [r for r in records if out["t_open"] <= tracer.mono_t0
                 + r["t_ms"] / 1e3 < out["t_close"]]
    host_spans = [(n, s, s + d) for n, s, d in trace["host"]]
    offset = reduce_trace.clock_offset_ns(trace, profiler.sync_clock_s)
    if offset is not None:
        # spans of a request's life (queued, whole request) run across
        # many cycles and say nothing about what the host did in a gap
        stack = [r for r in records if r["name"] not in out.get("life_spans", ())]
        host_spans += harness.program_spans_on_profiler_clock(
            stack, tracer.mono_t0, offset)
    window = reduce_trace.window_of(trace) if trace["devices"] else None
    counters = dict(out["counters"])
    counters["memory.peak_bytes"] = out["memory_peak_bytes"]
    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic,
        peaks=(peaks.peaks_for(device["kind"])
               if device["platform"] == "tpu" else {}),
        counters=counters, span_records=in_window, trace=trace,
        window=window, metric_files=files)
    metrics = {}
    for m in names:
        value = ctx.metric(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {}
    if window is not None:
        t0, t1 = window
        devs = trace["devices"]
        busy = [reduce_trace.busy_seconds(d["ops"], t0, t1)
                for d in devs.values()]
        extra["busy_s"] = sum(busy) / len(busy)
        extra["window_s"] = (t1 - t0) / 1e9
        first = devs[sorted(devs)[0]]
        extra["breakdown"] = {
            "device_ops": reduce_trace.top_ops(first["ops"], t0, t1),
            "idle_gaps": reduce_trace.name_gaps(
                reduce_trace.idle_gaps(first["ops"], t0, t1), host_spans)}
    return metrics, extra


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    manifest, cell, config, traffic = load_cell(args.workload,
                                                rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")

    from benchmark.lib import harness

    harness.keep_every_compile()
    device = harness.require_tpu(cell["chips"], rehearse=args.rehearse)
    compile_log = harness.CompileLog()

    scratch = REPO_DIR / ".bench_scratch" / cell["name"]
    scratch.mkdir(parents=True, exist_ok=True)
    job = SimpleNamespace(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), scratch=scratch)
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    out = runner.run(job)
    out["counters"]["compile.setup_s"] = compile_log.seconds_before(out["t_open"])
    out["counters"]["compile.in_window"] = compile_log.count_between(
        out["t_open"], out["t_close"])

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        metrics, extra = traced_metrics(manifest, cell, config, traffic, out,
                                        device, compile_log)
        result["metrics"] = metrics
        for k in ("busy_s", "window_s"):
            if k in extra:
                device[k] = extra[k]
        if "breakdown" in extra:
            result["breakdown"] = extra["breakdown"]
    else:
        values = dict(out["end_to_end"])
        values["setup_s"] = out["t_open"] - T_PROCESS_START
        metrics = {}
        for m in metrics_for(manifest, "end_to_end", cell["name"]):
            if values.get(m["name"]) is None:
                raise SystemExit(f"no value for end-to-end metric "
                                 f"{m['name']!r} in cell {cell['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        if out["counters"]["compile.in_window"]:
            result["correct"] = False      # something compiled in the window
    result["device"] = device
    # Not read by the driver: what the checks measured, and the counts
    # behind the metrics, for whoever reads a run by hand.
    result["checks"] = out["checks"]
    result["counts"] = {k: v for k, v in out["counters"].items()
                        if k.startswith(("runner.", "compile."))}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
