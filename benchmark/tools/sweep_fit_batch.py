"""Throughput and peak memory of `fit()` against the per-chip batch, once,
on the chip: decides the batch of the train cells. Batches go up, since
the device's peak-memory counter never comes down. One JSON line a batch,
also to `chiprun_out/sweep_fit_batch.jsonl`.

    python3 benchmark/tools/sweep_fit_batch.py --batches 2048,4096,8192 --seconds 8
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--examples", type=int, default=131072)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()

    from benchmark.lib import harness, stats
    from benchmark.runners import train_fit as tf
    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data.idc import ArrayDataset
    from idc_models_tpu.train import fit

    harness.keep_every_compile()
    harness.require_tpu(args.chips, rehearse=False)
    cfg = json.loads((BENCH_DIR / "configs" / "vgg16-idc.json").read_text())["model"]
    mesh = meshlib.data_mesh(args.chips)
    images, labels = tf.make_patches(args.examples, cfg["image_size"], 0, 0.5,
                                     tf.GEN_THREADS)
    _, fit_args, fresh_state = tf.build_trainer(cfg, 0)
    out_path = BENCH_DIR.parent / "chiprun_out" / "sweep_fit_batch.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    with open(out_path, "a") as f:
        for per_chip in (int(b) for b in args.batches.split(",")):
            batch = per_chip * args.chips
            clock = tf.EpochClock(warmup_epochs=2, seconds=args.seconds,
                                  profiler=None)
            try:
                fit(state=fresh_state(), train_ds=ArrayDataset(images, labels),
                    val_ds=None, mesh=mesh, epochs=10 ** 9, batch_size=batch,
                    logger=clock, verbose=False, **fit_args)
            except tf._WindowDone:
                pass
            walls = clock.epoch_walls()
            steps = args.examples // batch
            rates = [steps * batch / w / args.chips for w in walls]
            row = {"chips": args.chips, "batch_per_chip": per_chip,
                   "steps_per_epoch": steps, "epochs": len(walls),
                   "patches_per_s_chip_median": stats.median(rates),
                   "patches_per_s_chip_min": min(rates),
                   "patches_per_s_chip_max": max(rates),
                   "memory_peak_bytes": harness.memory_peak_bytes(args.chips)}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
