"""ISSUE-16 tuning harness: fused Pallas backbone layout/batch sweep.

Three sweeps, one JSONL record each, all on the real fine-tune train
step (the backbone_mfu.py `measure_train` methodology — phase-2 model,
bf16, honest host-fetch fence):

1. MobileNetV2 depthwise lowering x batch: `depthwise_impl` in
   {grouped, taps, fused} at batch 1024/2048/4096 — the fused rows
   carry the ANALYTIC Pallas kernel FLOPs/bytes merged into XLA's
   accounting (cost_analysis cannot see inside a pallas_call), so
   their intensity/hbm columns are comparable with the unfused rows.
2. DenseNet201 block data movement x batch: `block_impl` in
   {packed, concat} at batch 512/1024/2048 — packed preallocates the
   block buffer and dynamic_update_slices each layer's 32 channels;
   concat is the re-materializing baseline the MFU attribution blamed.
3. The fused kernel standalone at the stem/block depthwise shapes,
   whole-C per grid cell. There is no channel-tile sweep at these
   widths: Mosaic takes a channel block only if it is all of C or a
   multiple of 128 lanes, and 32/96/144 have no such divisor (every
   50x50-scale activation fits VMEM whole anyway).

Usage (results are only perf-meaningful on the chip; on CPU the Pallas
rows run the interpreter and measure correctness, not speed):

    python experiments/fused_backbone.py            # run everything
    python experiments/fused_backbone.py mobile_fused_2048 tile_25x96_none
    python experiments/fused_backbone.py --list

Appends one JSON line per experiment to experiments/fused_backbone.jsonl.
`*_base`-style unfused rows bracket the fused rows (shared-chip drift
is +/-10% over minutes — BASELINE.md).
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from backbone_mfu import _peak_gbps, measure_train  # noqa: E402
from mfu_matrix import _timed  # noqa: E402  (shared honest-timing loop)

OUT = Path(__file__).resolve().parent / "fused_backbone.jsonl"


def measure_mobile(batch: int, impl: str):
    """MobileNetV2 fine-tune step with one depthwise lowering; fused
    rows get the analytic Pallas cost merged in (same accounting as
    `profile --model mobile --depthwise-impl fused`, cli.py)."""
    r = measure_train("mobile", batch=batch,
                      build_kwargs={"depthwise_impl": impl})
    if impl == "fused":
        import jax

        from idc_models_tpu.models import mobilenet
        from idc_models_tpu.ops import fused_conv

        n_dev = len(jax.devices())
        total = batch * n_dev
        k_flops, k_bytes = fused_conv.depthwise_chain_cost(
            mobilenet.fused_call_shapes(total, 50))
        steps, dt = r["steps"], r["best_dt"]
        r["flops_per_patch"] = (r["flops_per_patch"] or 0.0) \
            + k_flops / total
        r["bytes_per_patch"] = (r["bytes_per_patch"] or 0.0) \
            + k_bytes / total
        r["tflops_per_s"] = (r["flops_per_patch"] * total * steps
                             / dt / 1e12 / n_dev)
        r["hbm_gbytes_per_s"] = (r["bytes_per_patch"] * total * steps
                                 / dt / 1e9 / n_dev)
        r["pallas_cost_merged"] = True
    r["depthwise_impl"] = impl
    return r


def measure_dense(batch: int, impl: str):
    """DenseNet201 fine-tune step with one block data-movement impl —
    both are ordinary XLA ops, fully cost-accounted."""
    r = measure_train("dense", batch=batch,
                      build_kwargs={"block_impl": impl})
    r["block_impl"] = impl
    return r


def measure_tile(*, batch=256, size=25, c=96, stride=1,
                 channel_tile=None):
    """One fused depthwise+BN+relu6 call at a MobileNetV2 activation
    shape, timed standalone."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.ops import fused_conv

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, size, size, c), np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (3, 3, 1, c)), jnp.float32)
    scale = jnp.ones((c,), jnp.float32)
    bias = jnp.zeros((c,), jnp.float32)
    mean = jnp.asarray(rng.normal(0, 0.1, (c,)), jnp.float32)
    var = jnp.abs(jnp.asarray(rng.random((c,)), jnp.float32)) + 0.5

    fn = jax.jit(lambda a: fused_conv.fused_depthwise_bn_relu6(
        a, w, scale, bias, mean, var, eps=1e-3, stride=stride,
        channel_tile=channel_tile))
    box = {}

    def dispatch(n):
        for _ in range(n):
            box["y"] = fn(x)

    def fence():
        return float(jnp.sum(box["y"].astype(jnp.float32)))

    steps, dt, dts = _timed(dispatch, fence)
    flops, bytes_accessed = fused_conv.depthwise_call_cost(
        batch, size, size, c, stride=stride)
    call_s = dt / steps
    return {
        "shape": [batch, size, size, c], "stride": stride,
        "channel_tile": channel_tile,
        "steps": steps, "best_dt": dt, "window_dts": dts,
        "call_ms": call_s * 1e3,
        "gflops_per_s": flops / call_s / 1e9,
        "hbm_gbytes_per_s": bytes_accessed / call_s / 1e9,
    }


EXPERIMENTS = {
    # ---- sweep 1: mobile depthwise lowering x batch ----
    **{f"mobile_{impl}_{b}": partial(measure_mobile, b, impl)
       for b in (1024, 2048, 4096)
       for impl in ("grouped", "taps", "fused")},
    # ---- sweep 2: dense block movement x batch ----
    **{f"dense_{impl}_{b}": partial(measure_dense, b, impl)
       for b in (512, 1024, 2048)
       for impl in ("packed", "concat")},
    # ---- sweep 3: the kernel standalone at the hot fused shapes ----
    "tile_25x96_none": partial(measure_tile, size=25, c=96),
    "tile_13x144_none": partial(measure_tile, size=13, c=144, stride=2),
    "tile_25x32_stem": partial(measure_tile, size=25, c=32),
}


def main():
    names = [a for a in sys.argv[1:] if not a.startswith("-")]
    if "--list" in sys.argv:
        print("\n".join(EXPERIMENTS))
        return
    if not names:
        names = list(EXPERIMENTS)

    import jax

    from idc_models_tpu.observe.profile import roofline_for

    dev = jax.devices()[0]
    roof = roofline_for(dev)
    peak = roof.peak_tflops if roof else None
    bw = _peak_gbps(dev)
    print(f"device: {dev.device_kind} peak={peak} TF/s bf16, "
          f"HBM {bw} GB/s; writing {OUT}", file=sys.stderr)
    with OUT.open("a") as f:
        for name in names:
            t0 = time.time()
            try:
                r = EXPERIMENTS[name]()
                if (bw and peak and r.get("flops_per_patch")
                        and r.get("bytes_per_patch")):
                    intensity = (r["flops_per_patch"]
                                 / r["bytes_per_patch"])
                    r["arithmetic_intensity"] = round(intensity, 3)
                    r["roofline_mfu_ceiling"] = min(
                        1.0, intensity * bw * 1e9 / (peak * 1e12))
                if bw and r.get("hbm_gbytes_per_s"):
                    r["hbm_utilization"] = r["hbm_gbytes_per_s"] / bw
            except Exception as e:  # record OOMs etc. as data, keep going
                r = {"error": f"{type(e).__name__}: {e}"[:500]}
            r.update(name=name, ts=round(t0, 1),
                     wall_s=round(time.time() - t0, 1),
                     device_kind=dev.device_kind)
            line = json.dumps(r)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()


if __name__ == "__main__":
    main()
