"""Round-5 perf record: MobileNetV2 + DenseNet201 on the chip.

VERDICT r4's top ask: two of the reference's three DP training workloads
(dist_model_tf_mobile.py:119-129 — MobileNetV2 on 50x50 IDC patches;
dist_model_tf_dense.py:131-158 — DenseNet201 on 32x32 CIFAR-10) had no
throughput/MFU record; only VGG16 did.  This matrix gives each the
mfu_matrix methodology: the real fine-tune train step (phase-2 model with
bn_frozen_below=fine_tune_at, RMSprop(lr/10) under the Keras-index
fine-tune mask, bf16), XLA cost-analysis FLOPs, per-stage forward
attribution, and the levers that could plausibly move each number.

Unlike VGG (dense 3x3 convs -> MXU-bound, MFU 0.62), both of these
backbones are expected to be HBM-bandwidth-bound on TPU:

  MobileNetV2  depthwise 3x3s have NO channel contraction — nothing for
               the systolic array to reduce — and the surrounding 1x1s
               at 50x50-scale spatial dims are low-arithmetic-intensity
               matmuls.  The record therefore carries bytes-accessed and
               a roofline ceiling next to MFU: for a bandwidth-bound
               step the honest ceiling is flops/bytes * BW / peak, not
               1.0.  Lever measured: depthwise lowering (grouped conv vs
               explicit 9-tap elementwise MAC, core.depthwise_conv2d
               impl="taps").
  DenseNet201  48-deep concat stages at 2x2/1x1 spatial after CIFAR's
               32x32 input collapses — 3x3 convs with K=288..., N=32
               tiles mostly padding, and the concat chain re-reads an
               ever-growing activation.  Levers: batch, the fwd/bwd
               split, per-stage attribution.

Usage (real chip; each entry compiles fresh, ~20-40 s):

    python experiments/backbone_mfu.py             # run everything
    python experiments/backbone_mfu.py mobile_base dense_base
    python experiments/backbone_mfu.py --list

Appends one JSON line per experiment to experiments/backbone_mfu.jsonl.
`*_base` entries are measured first and last (drift bracket: the shared
chip drifts +/-10 percent over minutes — BASELINE.md).
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

from mfu_matrix import _timed  # noqa: E402  (shared honest-timing loop)

from idc_models_tpu.observe.profile import program_report  # noqa: E402

OUT = Path(__file__).resolve().parent / "backbone_mfu.jsonl"

def _peak_gbps(device) -> float | None:
    """Nominal peak HBM GB/s per chip — the per-backend roofline
    registry (observe/profile.py BACKEND_ROOFS, seeded from the table
    that used to live here) is the one source of truth."""
    from idc_models_tpu.observe.profile import roofline_for

    spec = roofline_for(device)
    return spec.peak_hbm_gbps if spec else None


# ---------------------------------------------------------------------------
# the fine-tune train-step measurement, parameterized by backbone
# ---------------------------------------------------------------------------

_PRESET = {
    # model/eval shapes from the reference files cited in the module
    # docstring; lr is the phase-2 client rate (preset lr / 10).
    "mobile": dict(model_name="mobilenet_v2", image_size=50, num_outputs=1,
                   fine_tune_at=100, lr=1e-4),
    "dense": dict(model_name="densenet201", image_size=32, num_outputs=10,
                  fine_tune_at=150, lr=1e-4),
}


def measure_train(preset: str, *, batch=1024, fwd_only=False,
                  compute_dtype="bfloat16", build_kwargs=None):
    import jax
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models import registry
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_eval_step, make_train_step,
        replicate, rmsprop, shard_batch,
    )
    from idc_models_tpu.train.losses import (
        binary_cross_entropy, sparse_categorical_cross_entropy,
    )

    cfg = _PRESET[preset]
    dtype = getattr(jnp, compute_dtype)
    mesh = meshlib.data_mesh()
    n_dev = len(jax.devices())
    spec = registry.get_model(cfg["model_name"])
    # the phase-2 model exactly as train.loop._build_model makes it:
    # BN below the fine-tune boundary permanently in inference mode
    model = spec.build(cfg["num_outputs"], 3,
                       bn_frozen_below=cfg["fine_tune_at"],
                       **(build_kwargs or {}))
    variables = model.init(jax.random.key(0))
    opt = rmsprop(cfg["lr"] / 10.0,
                  trainable_mask=spec.fine_tune_mask(variables.params,
                                                     cfg["fine_tune_at"]))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    loss_fn = (binary_cross_entropy if cfg["num_outputs"] == 1
               else sparse_categorical_cross_entropy)

    rng = np.random.default_rng(0)
    total = batch * n_dev
    s = cfg["image_size"]
    imgs = rng.random((total, s, s, 3), np.float32)
    labels = (rng.integers(0, max(cfg["num_outputs"], 2), total)
              .astype(np.int32))
    state = replicate(mesh, state)
    x, y = shard_batch(mesh, imgs, labels)

    if fwd_only:
        step = make_eval_step(model, loss_fn, compute_dtype=dtype)
        jitted = jit_data_parallel(step, mesh, donate_state=False)
        compiled = jitted.lower(state, x, y).compile()
        box = {}

        def dispatch(n):
            for _ in range(n):
                box["m"] = compiled(state, x, y)

        def fence():
            return float(box["m"]["loss"])
    else:
        step = make_train_step(model, opt, loss_fn, compute_dtype=dtype)
        jitted = jit_data_parallel(step, mesh)
        compiled = jitted.lower(state, x, y, jax.random.key(1)).compile()
        digest = jax.jit(lambda st: jnp.sum(
            st.params["head"]["kernel"].astype(jnp.float32)))
        box = {"s": state, "k": jax.random.key(1)}

        def dispatch(n):
            st, k = box["s"], box["k"]
            for _ in range(n):
                k, sub = jax.random.split(k)
                st, _ = compiled(st, x, y, sub)
            box["s"], box["k"] = st, k

        def fence():
            return float(digest(box["s"]))

    rep = program_report(compiled, name=f"{preset}.train_step")
    flops_per_step = rep.flops or 0.0
    bytes_per_step = rep.bytes_accessed or 0.0
    steps, dt, dts = _timed(dispatch, fence)
    step_s = dt / steps
    return {
        "patches_per_sec_per_chip": steps * total / dt / n_dev,
        "steps": steps, "best_dt": dt, "window_dts": dts,
        "batch_per_chip": batch,
        "flops_per_patch": flops_per_step / total if flops_per_step else None,
        "bytes_per_patch": bytes_per_step / total if bytes_per_step else None,
        "tflops_per_s": (flops_per_step * steps / dt / 1e12 / n_dev
                         if flops_per_step else None),
        "hbm_gbytes_per_s": (bytes_per_step * steps / dt / 1e9 / n_dev
                             if bytes_per_step else None),
        "step_ms": step_s * 1e3,
    }


# ---------------------------------------------------------------------------
# per-stage forward attribution (unit-range sub-models)
# ---------------------------------------------------------------------------

def _range_model(units, modules, lo, hi):
    """Minimal forward-only composition of units[lo:hi] (the experiment-
    side mirror of core.unit_backbone's internal section)."""
    import jax

    from idc_models_tpu.models import core

    names = [n for ns, _ in units[lo:hi] for n in ns]

    def init(rng):
        rngs = jax.random.split(rng, len(names))
        params, state = {}, {}
        for n, r in zip(names, rngs):
            v = modules[n].init(r)
            if v.params:
                params[n] = v.params
            if v.state:
                state[n] = v.state
        return core.Variables(params, state)

    def apply(params, state, x):
        def run(n, h):
            y, _ = modules[n].apply(params.get(n, {}), state.get(n, {}), h,
                                    train=False)
            return y

        for _, unit_fn in units[lo:hi]:
            x = unit_fn(run, x)
        return x

    return init, apply


# (group, unit range, input spatial, input channels) — shapes follow the
# topology at each preset's reference input size (50x50 mobile, 32 dense)
_MOBILE_GROUPS = {
    "stem_25": (0, 1, 50, 3),       # Conv1 s2 + block0 @25
    "blocks_13": (1, 3, 25, 16),    # blocks 1-2
    "blocks_7": (3, 6, 13, 24),     # blocks 3-5
    "blocks_4": (6, 13, 7, 32),     # blocks 6-12
    "blocks_2": (13, 17, 4, 96),    # blocks 13-16
    "top_2": (17, 18, 2, 320),      # Conv_1 1280
}
_DENSE_GROUPS = {
    "stem_8": (0, 1, 32, 3),        # 7x7 s2 + pool -> 8x8x64
    "stage2_8": (1, 8, 8, 64),      # 6 layers + transition
    "stage3_4": (8, 21, 4, 128),    # 12 layers + transition
    "stage4_2": (21, 70, 2, 256),   # 48 layers + transition
    "stage5_1": (70, 103, 1, 896),  # 32 layers + final BN
}


def measure_group(preset: str, group: str, *, batch=1024):
    import jax
    import jax.numpy as jnp

    if preset == "mobile":
        from idc_models_tpu.models import mobilenet as zoo
        groups = _MOBILE_GROUPS
        freeze = zoo.FREEZE_ALL
    else:
        from idc_models_tpu.models import densenet as zoo
        groups = _DENSE_GROUPS
        freeze = zoo.FREEZE_ALL
    lo, hi, size, c_in = groups[group]
    units, modules = zoo._units(3, freeze)  # all-BN-frozen: fused affine
    init, apply = _range_model(units, modules, lo, hi)
    variables = init(jax.random.key(0))
    x = jnp.asarray(np.random.default_rng(0)
                    .random((batch, size, size, c_in), np.float32),
                    dtype=jnp.bfloat16)

    @jax.jit
    def fwd(params, state, x):
        return jnp.sum(apply(params, state, x).astype(jnp.float32))

    compiled = fwd.lower(variables.params, variables.state, x).compile()
    rep = program_report(compiled, name=f"{preset}.{group}_fwd")
    flops_per_step = rep.flops or 0.0
    bytes_per_step = rep.bytes_accessed or 0.0
    box = {}

    def dispatch(n):
        for _ in range(n):
            box["y"] = compiled(variables.params, variables.state, x)

    def fence():
        return float(box["y"])

    steps, dt, dts = _timed(dispatch, fence)
    return {
        "patches_per_sec_per_chip": steps * batch / dt,
        "steps": steps, "best_dt": dt, "window_dts": dts,
        "batch_per_chip": batch,
        "flops_per_patch": flops_per_step / batch if flops_per_step else None,
        "bytes_per_patch": bytes_per_step / batch if bytes_per_step else None,
        "tflops_per_s": (flops_per_step * steps / dt / 1e12
                         if flops_per_step else None),
        "hbm_gbytes_per_s": (bytes_per_step * steps / dt / 1e9
                             if bytes_per_step else None),
    }


EXPERIMENTS = {
    # ---- MobileNetV2 (50x50 IDC, fine_tune_at=100) ----
    "mobile_base": partial(measure_train, "mobile", batch=2048),
    "mobile_batch_1024": partial(measure_train, "mobile", batch=1024),
    "mobile_batch_4096": partial(measure_train, "mobile", batch=4096),
    "mobile_batch_8192": partial(measure_train, "mobile", batch=8192),
    "mobile_taps": partial(measure_train, "mobile", batch=2048,
                           build_kwargs={"depthwise_impl": "taps"}),
    "mobile_taps_8192": partial(measure_train, "mobile", batch=8192,
                                build_kwargs={"depthwise_impl": "taps"}),
    "mobile_f32": partial(measure_train, "mobile", batch=2048,
                          compute_dtype="float32"),
    "mobile_fwd_only": partial(measure_train, "mobile", batch=2048,
                               fwd_only=True),
    **{f"mobile_{g}_fwd": partial(measure_group, "mobile", g, batch=2048)
       for g in _MOBILE_GROUPS},
    "mobile_base_again": partial(measure_train, "mobile", batch=2048),
    # ---- DenseNet201 (32x32 CIFAR-10, fine_tune_at=150) ----
    "dense_base": partial(measure_train, "dense", batch=1024),
    "dense_batch_256": partial(measure_train, "dense", batch=256),
    "dense_batch_512": partial(measure_train, "dense", batch=512),
    "dense_batch_2048": partial(measure_train, "dense", batch=2048),
    "dense_f32": partial(measure_train, "dense", batch=1024,
                         compute_dtype="float32"),
    "dense_fwd_only": partial(measure_train, "dense", batch=1024,
                              fwd_only=True),
    **{f"dense_{g}_fwd": partial(measure_group, "dense", g, batch=1024)
       for g in _DENSE_GROUPS},
    "dense_base_again": partial(measure_train, "dense", batch=1024),
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
                r["mfu"] = (r["tflops_per_s"] / peak
                            if peak and r.get("tflops_per_s") else None)
                # roofline: achievable MFU if the step were perfectly
                # HBM-bound at spec bandwidth — the honest ceiling for
                # low-arithmetic-intensity backbones
                if (bw and peak and r.get("flops_per_patch")
                        and r.get("bytes_per_patch")):
                    intensity = r["flops_per_patch"] / r["bytes_per_patch"]
                    r["roofline_mfu_ceiling"] = min(
                        1.0, intensity * bw * 1e9 / (peak * 1e12))
                    r["hbm_utilization"] = (r["hbm_gbytes_per_s"] / bw
                                            if r.get("hbm_gbytes_per_s")
                                            else None)
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
