"""How far a statistic of the time to first token would move from run to
run, from the rows of one run: a circular block bootstrap over the
requests due in the window, in due order (neighbours share a queue, so
they are resampled together). It overstates what two seeds differ by
where the generator stratifies lengths; it cannot see what differs
between machines. Use it to choose a statistic and a bound for a latency
cell before spending two sets of runs on it.

    python3 benchmark/tools/ttft_spread.py .bench_scratch/<cell>/rows.json \
        [--warmup 3 --seconds 51 --block 8]
"""

from __future__ import annotations

import argparse
import json

import numpy as np

STATISTICS = {"mean": np.mean, "p50": np.median,
              "p90": lambda x: np.percentile(x, 90)}


def bootstrap_se(x: np.ndarray, fn, *, block: int, draws: int = 2000,
                 seed: int = 0) -> float:
    rng = np.random.default_rng(seed)
    n = len(x)
    idx = (rng.integers(0, n, (draws, -(-n // block), 1))
           + np.arange(block)).reshape(draws, -1)[:, :n] % n
    return float(np.std([fn(x[i]) for i in idx]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("rows", nargs="+")
    ap.add_argument("--warmup", type=float, default=3.0)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--block", type=int, default=8)
    args = ap.parse_args()
    for path in args.rows:
        with open(path) as f:
            rows = json.load(f)
        x = np.array([(r["first_s"] - r["due_s"]) * 1e3 for r in rows
                      if args.warmup <= r["due_s"] < args.warmup + args.seconds
                      and r["first_s"] is not None])
        print(f"{path}: {len(x)} requests, sd {x.std():.1f} ms, "
              f"lag-1 correlation {np.corrcoef(x[:-1], x[1:])[0, 1]:.2f}")
        for name, fn in STATISTICS.items():
            se = bootstrap_se(x, fn, block=args.block)
            print(f"  {name:5s} {fn(x):8.1f} ms   standard error {se:5.1f} ms "
                  f"({100 * se / fn(x):.1f}%)")


if __name__ == "__main__":
    main()
