"""Zigzag vs contiguous causal ring schedule, measured on the real chip.

One chip cannot host a real n-device ring, so this measures what the
layout actually changes: the per-device COMPUTE schedule. In SPMD
lockstep every device executes the same kernel calls per ring step and
the wall clock is the per-step max, so one device's schedule timed on
one chip is the ring's compute time (the ppermute hops, which both
layouts issue identically — n-1 neighbor hops of the same bytes — are
excluded for both).

  contiguous: n full-block causal flash updates (t_local x t_local);
              ~half land on fully masked blocks but are paid anyway.
  zigzag:     3 quarter attends (2 stripe diagonals + 1 full) plus
              2 unmasked quarter attends per remaining hop
              = (2n+1)/(4n) of the contiguous score work.

Methodology follows ring_attention_bench.py: chained calls per timing
window (output feeds back as q) to amortize per-dispatch overhead
(~90 ms when recorded), best-of-3 windows, and the timing ends with a
host fetch of a scalar that data-depends on the result (under the
runtime of the time block_until_ready could return early; see
BASELINE.md).
Run: python experiments/zigzag_bench.py
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import jax
import jax.numpy as jnp
import numpy as np

from idc_models_tpu.ops import flash_block_kernel as fbk

B, H, D = 1, 8, 64
N = 8          # emulated ring size
ITERS = 6
ME = N - 1     # any device works: the schedule length is identical


def make_schedule(layout, t_local, *, interpret=False):
    """One device's compute for a full causal ring pass, as fn(q, kv)
    with kv [N, 2, B, t_local, H, D] stacking the visiting blocks in
    visit order."""
    scale = D ** -0.5
    diag = fbk.make_flash_block_update(scale=scale, causal=True,
                                       interpret=interpret)
    full = fbk.make_flash_block_update(scale=scale, causal=False,
                                       interpret=interpret)
    th = t_local // 2

    def contiguous(q, kv):
        m = jnp.full((B, H, t_local), -1e30, jnp.float32)
        l = jnp.zeros((B, H, t_local), jnp.float32)
        acc = jnp.zeros((B, t_local, H, D), jnp.float32)
        for s in range(N):
            c = (ME - s) % N
            offs = jnp.asarray([ME * t_local, c * t_local], jnp.int32)
            m, l, acc = diag(q, kv[s, 0], kv[s, 1], m, l, acc, offs)
        return acc / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-37)

    def zigzag(q, kv):
        m = jnp.full((B, H, t_local), -1e30, jnp.float32)
        l = jnp.zeros((B, H, t_local), jnp.float32)
        acc = jnp.zeros((B, t_local, H, D), jnp.float32)
        lo_off, hi_off = ME * th, (2 * N - 1 - ME) * th

        def quarter(m, l, acc, row0, qh, kh, vh, qo, ko, is_diag):
            ms, ls = m[:, :, row0:row0 + th], l[:, :, row0:row0 + th]
            accs = acc[:, row0:row0 + th]
            upd = diag if is_diag else full
            offs = jnp.asarray([qo, ko], jnp.int32)
            ms, ls, accs = upd(qh, kh, vh, ms, ls, accs, offs)
            return (m.at[:, :, row0:row0 + th].set(ms),
                    l.at[:, :, row0:row0 + th].set(ls),
                    acc.at[:, row0:row0 + th].set(accs))

        q_lo, q_hi = q[:, :th], q[:, th:]
        for s in range(N):
            k_lo, k_hi = kv[s, 0, :, :th], kv[s, 0, :, th:]
            v_lo, v_hi = kv[s, 1, :, :th], kv[s, 1, :, th:]
            c = (ME - s) % N
            c_lo, c_hi = c * th, (2 * N - 1 - c) * th
            if s == 0:
                m, l, acc = quarter(m, l, acc, 0, q_lo, k_lo, v_lo,
                                    lo_off, lo_off, True)
                m, l, acc = quarter(m, l, acc, th, q_hi, k_hi, v_hi,
                                    hi_off, hi_off, True)
                m, l, acc = quarter(m, l, acc, th, q_hi, k_lo, v_lo,
                                    hi_off, lo_off, False)
            else:
                m, l, acc = quarter(m, l, acc, th, q_hi, k_lo, v_lo,
                                    hi_off, c_lo, False)
                if c < ME:
                    m, l, acc = quarter(m, l, acc, 0, q_lo, k_lo, v_lo,
                                        lo_off, c_lo, False)
                else:
                    m, l, acc = quarter(m, l, acc, th, q_hi, k_hi, v_hi,
                                        hi_off, c_hi, False)
        return acc / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-37)

    return jax.jit(contiguous if layout == "contiguous" else zigzag)


def main():
    out_path = pathlib.Path(__file__).parent / "zigzag_bench.jsonl"
    rows = []
    for t_local in (4096, 8192, 16384):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(0, 1, (B, t_local, H, D)), jnp.bfloat16)
        kv = jnp.asarray(rng.normal(0, 1, (N, 2, B, t_local, H, D)),
                         jnp.bfloat16)
        row = {"t_local": t_local, "ring": N}
        for layout in ("contiguous", "zigzag"):
            fn = make_schedule(layout, t_local)
            o = fn(q, kv)
            _ = float(jnp.sum(o.astype(jnp.float32)))  # warm + sync
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                o = q
                for _ in range(ITERS):
                    o = fn(o, kv).astype(jnp.bfloat16)
                _ = float(jnp.sum(o.astype(jnp.float32)))
                best = min(best, (time.perf_counter() - t0) / ITERS)
            row[layout] = best
        row["speedup"] = row["contiguous"] / row["zigzag"]
        rows.append(row)
        print(f"t_local={t_local} ring={N}: contiguous "
              f"{row['contiguous']*1e3:.1f} ms  zigzag "
              f"{row['zigzag']*1e3:.1f} ms  speedup "
              f"{row['speedup']:.2f}x  (ideal {4*N/(2*N+1):.2f}x)",
              flush=True)
    with out_path.open("w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
