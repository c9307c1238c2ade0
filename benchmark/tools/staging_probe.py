"""The loader's staging ring on the chip's host, once: how fast a batch of
the train cells' size is assembled, and whether a recycled slot can hurt a
batch that is already on its way to the device. One JSON line a reading,
also to `chiprun_out/staging_probe.jsonl`.

    python3 benchmark/tools/staging_probe.py --chips 1 --batches 100

`rates`: the gather the loader did before (`images[idx]`, a fresh batch
each time), a contiguous copy into fresh pages, then `np.take(out=)` into
a slot of the loader's own ring: by one thread, by pools of a few widths,
and by the ring as the loader builds it (its derived width).

`refill`: `--batches` batches through the ring, each slot overwritten the
moment `_StagingRing.acquire` hands it back (the arrays placed from it are
ready), the ring one slot deep so that every refill hits the batch placed
just before. Every placed batch is then reduced on the device to one
wrapping uint32 sum a row and compared with the same sum of the source
rows: `refill.mismatched` must be 0. `at_return` repeats it without the
wait, overwriting as soon as `device_put` returns: it may fail, and says
whether the runtime is done with the host buffer by then.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def _ms(fn, repeats: int) -> list[float]:
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return out


def rates(loader, widths: list[int], repeats: int) -> dict:
    from idc_models_tpu.data import pipeline

    images = loader.ds.images
    batches = list(loader._index_batches(0))
    idx, b = batches[0], loader.batch_size
    mb = b * images[0].nbytes / 1e6
    ring = loader._staging(4)
    slot = ring.slots[0]
    row = {"reading": "rates", "batch_mb": mb, "derived_width": ring.width}

    def report(name, times):
        row[name + "_ms"] = times
        row[name + "_gb_per_s"] = mb / min(times)

    report("fancy_index_fresh", _ms(lambda: images[idx], repeats))
    report("contiguous_copy_fresh", _ms(lambda: images[:b].copy(), repeats))
    report("take_out_first_fill",
           _ms(lambda: np.take(images, idx, axis=0, out=slot.images,
                               mode="clip"), 1))
    report("take_out_one_thread",
           _ms(lambda: np.take(images, idx, axis=0, out=slot.images,
                               mode="clip"), repeats))
    for w in widths:        # the ring's own sliced fill, at other widths
        wide = pipeline._StagingRing(1, b, loader.ds)
        wide.slots[0] = slot
        wide.width = w
        with ThreadPoolExecutor(w) as wide._pool:
            report(f"take_out_pool_{w}",
                   _ms(lambda: wide.fill(slot, loader.ds, idx), repeats))
    # the ring as the loader uses it, over different batches and slots
    fills = []
    for i in range(2 * len(ring.slots)):
        s = ring.slots[i % len(ring.slots)]
        t0 = time.perf_counter()
        ring.fill(s, loader.ds, batches[i % len(batches)])
        fills.append((time.perf_counter() - t0) * 1e3)
    row["ring_fill_first_round_ms"] = fills[:len(ring.slots)]
    report("ring_fill", fills[len(ring.slots):])
    return row


def refill(loader, mesh, n_batches: int, *, wait: bool) -> dict:
    """`n_batches` batches through a ring one slot deep; see the module's
    docstring."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data import pipeline

    ds = loader.ds
    sh = meshlib.sharding(mesh, meshlib.DATA_AXIS)
    row_sums = jax.jit(lambda x: jnp.sum(
        jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(x.shape[0], -1),
        axis=1, dtype=jnp.uint32))
    want_rows = ds.images.view(np.uint32).reshape(len(ds), -1).sum(
        axis=1, dtype=np.uint32)
    ring = pipeline._StagingRing(1, loader.batch_size, ds)
    mismatched, waits, puts, done = 0, [], [], 0
    pending = None                      # (device sums, labels, idx)

    def settle():
        nonlocal mismatched
        sums, labels, idx = pending
        ok = (np.array_equal(np.asarray(sums), want_rows[idx])
              and np.array_equal(np.asarray(labels), ds.labels[idx]))
        mismatched += not ok

    epoch = 0
    while done < n_batches:
        for idx in loader._index_batches(epoch):
            if done == n_batches:
                break
            t0 = time.perf_counter()
            slot = ring.acquire() if wait else ring.slots[0]
            t1 = time.perf_counter()
            x, y = ring.fill(slot, ds, idx)     # the refill, at once
            t2 = time.perf_counter()
            if pending is not None:
                settle()                        # the batch just overwritten
            placed = tuple(meshlib.put_with_sharding(a, sh, may_alias=False)
                           for a in (x, y))
            slot.placed = placed
            puts.append((time.perf_counter() - t2) * 1e3)
            waits.append((t1 - t0) * 1e3)
            pending = (row_sums(placed[0]), placed[1], idx)
            done += 1
        epoch += 1
    # the last batch is overwritten too, with rows of another order
    if wait:
        ring.acquire()
    ring.fill(ring.slots[0], ds, np.arange(loader.batch_size)[::-1])
    settle()
    return {"reading": "refill" if wait else "at_return", "batches": done,
            "mismatched": int(mismatched),
            "slot_wait_ms_p50": float(np.median(waits)),
            "slot_wait_ms_max": float(np.max(waits)),
            "put_call_ms_p50": float(np.median(puts))}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--batches", type=int, default=100)
    ap.add_argument("--widths", default="2,3,4,6,8,12")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--mix", help="traffic file whose batch to assemble "
                    "(default: the train cell of --chips)")
    ap.add_argument("--rates-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever device is there")
    args = ap.parse_args()

    from benchmark.lib import harness
    from benchmark.runners import train_fit as tf
    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data import Loader
    from idc_models_tpu.data.idc import ArrayDataset

    harness.require_tpu(args.chips, rehearse=args.rehearse)
    mix = json.loads((BENCH_DIR / "traffic" / ((args.mix or (
        "fit_1chip" if args.chips == 1 else "fit_dp4")) + ".json")).read_text())
    spec = dict(mix["fit_epochs"],
                **(mix["rehearsal"]["fit_epochs"] if args.rehearse else {}))
    cfg = json.loads(
        (BENCH_DIR / "configs" / "vgg16-idc.json").read_text())["model"]
    images, labels = tf.make_patches(spec["examples"], cfg["image_size"], 0,
                                     spec["pos_fraction"], tf.GEN_THREADS)
    loader = Loader(ArrayDataset(images, labels),
                    spec["batch_per_chip"] * spec["chips"], shuffle=True,
                    seed=0)
    mesh = meshlib.data_mesh(args.chips)

    def readings():
        yield rates(loader, [int(w) for w in args.widths.split(",")],
                    args.repeats)
        if not args.rates_only:
            yield refill(loader, mesh, args.batches, wait=True)
            yield refill(loader, mesh, args.batches, wait=False)

    out_path = BENCH_DIR.parent / "chiprun_out" / "staging_probe.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    ok = True
    with open(out_path, "a") as f:
        for row in readings():
            row["chips"] = args.chips
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            ok = ok and not (row["reading"] == "refill" and row["mismatched"])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
