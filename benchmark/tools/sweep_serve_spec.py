"""`sweep_serve.py` for a configuration whose runner builds its model from
the whole configuration file (`serve_moe_open_loop`): the same server
under one mix at several fixed rates, one after another in one process,
one JSON line per rate, appended to `chiprun_out/sweep_<mix>.jsonl`.

    python3 benchmark/tools/sweep_serve_spec.py --config laguna-s-2.1 \
        --traffic code_decode --rates 3,4,5,6,8 --seconds 20

The knee is the highest rate at which completed work keeps up with
offered work and the time to first token does not grow through the run.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from benchmark.lib import harness, traffic_gen
    from benchmark.runners.serve_open_loop import Replay, reduce_rows

    harness.keep_every_compile()
    harness.require_tpu(1, rehearse=False)
    config = json.loads((BENCH_DIR / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{args.traffic}.json").read_text())
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    engine = config["engine"]
    params = runner.make_params(config, args.seed)
    out_path = BENCH_DIR.parent / "chiprun_out" / f"sweep_{args.traffic}.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    with open(out_path, "a") as f:
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(traffic["open_loop"], rate_per_s=rate)
            server = runner.build_server(params, config, engine)
            t_open = mix["warmup_s"]
            t_close = t_open + args.seconds
            arrivals = traffic_gen.open_loop_trace(
                mix, seed=args.seed,
                segments=runner.arrival_segments(t_open, t_close, t_close),
                vocab=config["vocab_size"], t_max=engine["t_max"])
            replay = Replay(server, arrivals)
            replay.run_until(t_close)
            red = reduce_rows(
                replay.rows(), arrivals, t_open=t_open, t_close=t_close,
                judge=traffic["judge"],
                slice_s=(t_open + args.seconds / 3, t_open + 2 * args.seconds / 3))
            summary = server.summary()
            row = {"traffic": args.traffic, "rate_per_s": rate,
                   "n_slots": engine["n_slots"],
                   "offered_req_per_s": sum(
                       1 for a in arrivals if t_open <= a.due_s < t_close)
                   / args.seconds,
                   "done_req_per_s": red["attempted"] / args.seconds,
                   "slot_occupancy": summary.get("serve_slot_occupancy"),
                   "queue_depth_mean": summary.get("serve_queue_depth_mean"),
                   "prefill_stall_ms_mean": summary.get("serve_prefill_stall_ms_mean"),
                   "experts_touched_mean": summary.get("serve_moe_experts_touched_mean"),
                   "memory_peak_bytes": harness.memory_peak_bytes(1),
                   **{k: red[k] for k in (
                       "out_tokens_per_s", "tpot_p50_ms", "ttft_p50_ms",
                       "ttft_p90_ms", "ttft_p50_first_half_ms",
                       "ttft_p50_second_half_ms", "ttft_missing",
                       "ttft_samples", "gen_late_p95_ms", "bad_requests",
                       "live_tokens_mean", "live_slots_mean")}}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
            del replay, server
            gc.collect()


if __name__ == "__main__":
    main()
