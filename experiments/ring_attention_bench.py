"""Ring attention block-impl comparison on the real chip (the numbers
quoted in ops/flash_block_kernel.py's docstring).

Methodology: 20 CHAINED calls per timing window (the output feeds back
as q), so per-dispatch overhead (~90 ms when recorded) is amortized;
single-call timings at these sizes are pure dispatch noise.
Run: python experiments/ring_attention_bench.py
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import time
import jax, jax.numpy as jnp, numpy as np
from idc_models_tpu import mesh as meshlib
from idc_models_tpu.ring_attention import make_ring_attention

B, H, D = 1, 8, 64
ITERS = 20
mesh = meshlib.seq_mesh(1)
for T in (4096, 8192, 16384):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, T, H, D)), jnp.bfloat16)
               for _ in range(3))
    row = {}
    for impl in ("jnp", "pallas"):
        fn = make_ring_attention(mesh, causal=True, block_impl=impl)
        out = fn(q, k, v)
        _ = float(jnp.sum(out.astype(jnp.float32)))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            o = q
            for _ in range(ITERS):          # chained: o feeds back as q
                o = fn(o, k, v).astype(jnp.bfloat16)
            f = float(jnp.sum(o.astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / ITERS)
        row[impl] = best
    print(f"T={T}: jnp {row['jnp']*1e3:.2f} ms/call  pallas "
          f"{row['pallas']*1e3:.2f} ms/call  speedup "
          f"{row['jnp']/row['pallas']:.2f}x", flush=True)
