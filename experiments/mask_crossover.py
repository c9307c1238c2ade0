"""Measure the threefry-vs-pallas crossover for the secure mask op.

The secure round's hot op per client is clip -> quantize -> add
n_clients pairwise mask streams over the flat protected buffer. Two
impls exist (secure/fedavg.py mask_impl): XLA threefry
(masking.quantize + masking.pairwise_mask) and the fused Pallas
hash-PRG kernel (ops.secure_masking_kernel). Round 3 left the kernel
non-default with a known near-tie at VGG16 size; this experiment sweeps
buffer sizes on the real chip to find the crossover that
`mask_impl="auto"` selects on (recorded in BASELINE.md and
secure/masking.py::MASK_PALLAS_MIN_ELEMS).

Methodology: the op is chained INSIDE one jit (each iteration's input
depends on the previous output through one scalar, so iterations
serialize but per-call dispatch — ~10 ms when recorded, bigger than
the op itself below ~8M elements — vanishes), best-of-3
windows, host fetch of a dependent scalar. n_clients=8 (the
suite/bench default). Run: python experiments/mask_crossover.py
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import jax
import jax.numpy as jnp
import numpy as np

from idc_models_tpu.ops import secure_masking_kernel as smk
from idc_models_tpu.secure import masking

N_CLIENTS = 8
ITERS = 100
SB, CLIP = 14, 4.0


def main():
    key = jax.random.key(0)
    my_id = jnp.int32(3)
    rows = []
    for n in (1 << 18, 1 << 20, 1 << 22, 1 << 23, 14_700_000, 1 << 25):
        x = jax.random.normal(jax.random.key(1), (n,), jnp.float32)

        def threefry(x):
            q = masking.quantize(x, SB, clip_abs=CLIP)
            return q + masking.pairwise_mask(key, my_id, N_CLIENTS, (n,))

        seeds, signs = smk.pair_seeds_and_signs(
            jax.random.bits(key, (), jnp.uint32), my_id, N_CLIENTS)

        def pallas(x):
            return smk.fused_masked_quantize(x, seeds, signs,
                                             scale_bits=SB, clip_abs=CLIP)

        def chained(op):
            @jax.jit
            def run(x):
                def body(_, acc):
                    out = op(acc)
                    # scalar-only dependency: serializes iterations
                    # without a full extra pass over the buffer
                    return x + out[0].astype(jnp.float32) * 1e-30
                return jax.lax.fori_loop(0, ITERS, body, x)
            return run

        row = {"elements": int(n)}
        for name, fn in (("threefry", chained(threefry)),
                         ("pallas", chained(pallas))):
            out = fn(x)
            _ = float(jnp.sum(out))
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                acc = fn(x)
                _ = float(jnp.sum(acc))
                best = min(best, (time.perf_counter() - t0) / ITERS)
            row[name] = best
        row["pallas_speedup"] = row["threefry"] / row["pallas"]
        rows.append(row)
        print(f"n={n:>10,}: threefry {row['threefry']*1e3:7.2f} ms  "
              f"pallas {row['pallas']*1e3:7.2f} ms  "
              f"ratio {row['pallas_speedup']:.2f}x", flush=True)
    out_path = pathlib.Path(__file__).parent / "mask_crossover.jsonl"
    with out_path.open("w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
