"""KV-cache decode latency on the chip, per context length.

The serving-side record for ring_decode.py: single-token decode steps
against a resident cache at several context lengths (ring of 1, so one
chip holds the whole cache — the per-device work of an n-device ring at
n× the context). Methodology as everywhere in this repo: chained jitted
steps per timing window (pos advances, caches donated in place), best
of 3 windows, host fetch of a dependent scalar as the fence.

Run: python experiments/decode_bench.py
Appends one JSON line per context length to experiments/decode_bench.jsonl.
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import jax
import jax.numpy as jnp
import numpy as np

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.ring_decode import init_cache, make_ring_decode, prefill

B, H, D = 1, 8, 64
ITERS = 32          # per-call decode steps per timing window
SCAN_ITERS = 512    # in-jit chained steps (amortizes the per-call round-trip)
OUT = pathlib.Path(__file__).parent / "decode_bench.jsonl"


def main():
    mesh = meshlib.seq_mesh(1)
    dev = jax.devices()[0]
    step = make_ring_decode(mesh)
    rng = np.random.default_rng(0)
    with OUT.open("a") as f:
        for t_max in (4096, 16384, 65536):
            p_len = t_max - SCAN_ITERS - 1
            kp, vp = (jnp.asarray(rng.normal(0, 1, (B, p_len, H, D)),
                                  jnp.bfloat16) for _ in range(2))
            kc, vc = prefill(mesh, kp, vp, t_max)
            toks = [jnp.asarray(rng.normal(0, 1, (B, 1, H, D)),
                                jnp.bfloat16) for _ in range(3)]
            q_t, k_t, v_t = toks
            # warm (compile)
            out, kc, vc = step(kc, vc, q_t, k_t, v_t, p_len)
            _ = float(jnp.sum(out.astype(jnp.float32)))
            best = 1e9
            for w in range(3):
                # fresh cache region each window: restart pos at p_len
                # is fine (slots just overwrite; timing is unaffected)
                t0 = time.perf_counter()
                o = q_t
                for s in range(ITERS):
                    o, kc, vc = step(kc, vc, o, k_t, v_t, p_len + s)
                    o = o.astype(jnp.bfloat16)
                _ = float(jnp.sum(o.astype(jnp.float32)))
                best = min(best, (time.perf_counter() - t0) / ITERS)
            # per-call latency above is dispatch bound (~3.5 ms flat vs
            # context when recorded); the in-jit scan below chains ITERS
            # steps inside ONE executable — the device-side cost of the
            # decode op itself (real serving interleaves the model
            # forward between steps, so this is the op's floor, not an
            # end-to-end tokens/s claim)
            @jax.jit
            def scan_steps(kc, vc, q, k, v, pos0):
                def body(carry, s):
                    kc, vc, o = carry
                    o, kc, vc = _inner(kc, vc, o, k, v, pos0 + s)
                    return (kc, vc, o.astype(jnp.bfloat16)), ()

                (kc, vc, o), _ = jax.lax.scan(
                    body, (kc, vc, q), jnp.arange(SCAN_ITERS))
                return o, kc, vc

            _inner = make_ring_decode(mesh)
            o, kc2, vc2 = scan_steps(kc, vc, q_t, k_t, v_t, p_len)
            _ = float(jnp.sum(o.astype(jnp.float32)))
            best_scan = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                o, kc2, vc2 = scan_steps(kc2, vc2, q_t, k_t, v_t, p_len)
                _ = float(jnp.sum(o.astype(jnp.float32)))
                best_scan = min(best_scan,
                                (time.perf_counter() - t0) / SCAN_ITERS)

            row = {"t_max": t_max, "prefill": p_len,
                   "decode_step_ms": round(best * 1e3, 3),
                   "tokens_per_s": round(1.0 / best, 1),
                   "decode_step_injit_ms": round(best_scan * 1e3, 3),
                   "injit_tokens_per_s": round(1.0 / best_scan, 1),
                   "device_kind": dev.device_kind}
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()


if __name__ == "__main__":
    main()
