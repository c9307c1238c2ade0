"""Round-3 MFU ceiling experiment matrix for the headline benchmark.

The headline (VGG16 fine-tune, 50x50 patches, bf16, batch 2048/chip) has
measured MFU ~0.60-0.61 for two rounds.  BASELINE.md argues the step is
conv-bound from one profile; this matrix attacks the ceiling lever by
lever and RECORDS every number so "conv-bound at 0.61" becomes a
demonstrated ceiling (or falls).  Levers, mapped to the reference
workload's shape (dist_model_tf_vgg.py:119-129: VGG16, 50x50x3 IDC
patches, fine_tune_at=15):

  batch sweep      1024 / 2048 / 3072 / 4096 per chip
  first conv       input-channel zero-pad 3 -> 4 / 8 (the classic
                   3-channel MXU under-utilization probe)
  layout           logical NCHW vs NHWC dimension_numbers
  precision        default bf16 vs matmul_precision=highest vs f32
  spatial          64x64 input diagnostic (are the odd 50->25->12->6->3
                   dims the efficiency loss?)  NOT the headline workload;
                   scored by its own cost analysis.
  attribution      forward-only step + per-block forward microbenches,
                   each with its own XLA cost analysis -> per-block MFU
  cached suffix    batch 32768 / 65536 / 131072 sweep

Usage (on the real chip; each entry compiles fresh, ~20-40 s):

    python experiments/mfu_matrix.py            # run everything
    python experiments/mfu_matrix.py base pad8  # subset
    python experiments/mfu_matrix.py --list

Appends one JSON line per experiment to experiments/mfu_matrix.jsonl.
`base` is measured first and again last so the shared chip's multi-minute
drift band (+/-10%, see BASELINE.md) brackets the matrix.  MFU numbers
are drift-honest (measured flops/s over peak); cross-variant ratios are
only trustworthy to the drift band.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from idc_models_tpu.observe.profile import program_report  # noqa: E402

OUT = Path(__file__).resolve().parent / "mfu_matrix.jsonl"


# ---------------------------------------------------------------------------
# generic honest timing (every window ends with a host fetch that
# data-depends on the result)
# ---------------------------------------------------------------------------

def _timed(dispatch, fence, *, min_seconds=1.0, start_steps=20,
           max_steps=400, windows=4):
    """dispatch(n) enqueues n steps; fence() host-fetches a scalar that
    data-depends on the last step.  Returns (steps, best_dt, all_dts)."""
    dispatch(3)
    fence()
    steps = start_steps
    while True:
        t0 = time.perf_counter()
        dispatch(steps)
        fence()
        dt = time.perf_counter() - t0
        if dt >= min_seconds or steps >= max_steps:
            break
        steps = min(max_steps, max(steps * 2,
                                   int(steps * 1.5 * min_seconds / dt)))
    dts = [dt]
    for _ in range(windows - 1):
        t0 = time.perf_counter()
        dispatch(steps)
        fence()
        dts.append(time.perf_counter() - t0)
    return steps, min(dts), dts


# ---------------------------------------------------------------------------
# NCHW variant of the VGG16 classifier (same param tree as models.vgg so
# fine_tune_mask applies unchanged; only dimension_numbers/layout differ)
# ---------------------------------------------------------------------------

def _conv2d_nchw(features_in, features_out, name):
    import jax.numpy as jnp
    from jax import lax

    from idc_models_tpu.models import core

    def init(rng):
        fan_in = 9 * features_in
        fan_out = 9 * features_out
        k = core.glorot_uniform(rng, (3, 3, features_in, features_out),
                                fan_in, fan_out)
        return core.Variables({"kernel": k,
                               "bias": jnp.zeros((features_out,))}, {})

    def apply(params, state, x, *, train=False, rng=None):
        y = lax.conv_general_dilated(
            x, params["kernel"].astype(x.dtype), (1, 1), "SAME",
            dimension_numbers=("NCHW", "HWIO", "NCHW"))
        return y + params["bias"].astype(y.dtype)[None, :, None, None], state

    return core.Module(init, apply, name)


def _max_pool_nchw(name):
    import jax.numpy as jnp
    from jax import lax

    from idc_models_tpu.models import core

    def apply(params, state, x, *, train=False, rng=None):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 2, 2),
                                 (1, 1, 2, 2), "VALID"), state

    return core.Module(lambda rng: core.Variables({}, {}), apply, name)


def vgg16_nchw(num_outputs: int = 1):
    from idc_models_tpu.models import core
    from idc_models_tpu.models.vgg import _CFG

    layers = []
    c_in = 3
    for block, filters, n_convs in _CFG:
        for conv in range(1, n_convs + 1):
            layers.append(_conv2d_nchw(c_in, filters,
                                       f"block{block}_conv{conv}"))
            layers.append(core.relu(name=f"block{block}_relu{conv}"))
            c_in = filters
        layers.append(_max_pool_nchw(f"block{block}_pool"))
    backbone = core.sequential(layers, name="vgg16")
    head = core.dense(512, num_outputs, name="head")

    def init(rng):
        r1, r2 = core._split(rng, 2)
        bb, hd = backbone.init(r1), head.init(r2)
        return core.Variables({"backbone": bb.params, "head": hd.params},
                              {"backbone": bb.state})

    def apply(params, state, x, *, train=False, rng=None):
        h, bb_state = backbone.apply(params["backbone"],
                                     state.get("backbone", {}), x,
                                     train=train, rng=rng)
        h = h.mean(axis=(2, 3))  # GAP over NCHW spatial
        y, _ = head.apply(params["head"], {}, h, train=train)
        return y, {"backbone": bb_state}

    return core.Module(init, apply, "vgg16_classifier_nchw")


# ---------------------------------------------------------------------------
# the parameterized fine-tune train-step measurement
# ---------------------------------------------------------------------------

def measure_train(*, batch=2048, in_channels=3, image_size=50,
                  compute_dtype="bfloat16", matmul_precision=None,
                  layout="NHWC", fwd_only=False):
    import jax
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models.vgg import fine_tune_mask, vgg16
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_eval_step, make_train_step,
        replicate, rmsprop, shard_batch,
    )
    from idc_models_tpu.train.losses import binary_cross_entropy

    dtype = getattr(jnp, compute_dtype)
    mesh = meshlib.data_mesh()
    n_dev = len(jax.devices())
    model = vgg16_nchw(1) if layout == "NCHW" else vgg16(1, in_channels)
    variables = model.init(jax.random.key(0))
    opt = rmsprop(1e-4, trainable_mask=fine_tune_mask(variables.params, 15))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))

    rng = np.random.default_rng(0)
    total = batch * n_dev
    if layout == "NCHW":
        imgs = rng.random((total, in_channels, image_size, image_size),
                          np.float32)
    else:
        imgs = rng.random((total, image_size, image_size, in_channels),
                          np.float32)
        if in_channels > 3:  # the zero-pad probe: channels 3.. are zero
            imgs[..., 3:] = 0.0
    labels = (rng.random(total) > 0.5).astype(np.int32)
    state = replicate(mesh, state)
    x, y = shard_batch(mesh, imgs, labels)

    import contextlib
    ctx = (jax.default_matmul_precision(matmul_precision)
           if matmul_precision else contextlib.nullcontext())
    with ctx:
        if fwd_only:
            step = make_eval_step(model, binary_cross_entropy,
                                  compute_dtype=dtype)
            jitted = jit_data_parallel(step, mesh, donate_state=False)
            compiled = jitted.lower(state, x, y).compile()
            box = {}

            def dispatch(n):
                for _ in range(n):
                    box["m"] = compiled(state, x, y)

            def fence():
                return float(box["m"]["loss"])
        else:
            step = make_train_step(model, opt, binary_cross_entropy,
                                   compute_dtype=dtype)
            jitted = jit_data_parallel(step, mesh)
            compiled = jitted.lower(state, x, y, jax.random.key(1)).compile()
            digest = jax.jit(lambda s: jnp.sum(
                s.params["head"]["kernel"].astype(jnp.float32)))
            box = {"s": state, "k": jax.random.key(1)}

            def dispatch(n):
                s, k = box["s"], box["k"]
                for _ in range(n):
                    k, sub = jax.random.split(k)
                    s, _ = compiled(s, x, y, sub)
                box["s"], box["k"] = s, k

            def fence():
                return float(digest(box["s"]))

    flops_per_step = program_report(compiled,
                                    name="mfu_matrix.step").flops or 0.0
    steps, dt, dts = _timed(dispatch, fence)
    return {
        "patches_per_sec_per_chip": steps * total / dt / n_dev,
        "steps": steps, "best_dt": dt, "window_dts": dts,
        "flops_per_patch": flops_per_step / total if flops_per_step else None,
        "tflops_per_s": (flops_per_step * steps / dt / 1e12 / n_dev
                         if flops_per_step else None),
    }


# ---------------------------------------------------------------------------
# per-block forward microbenches (MFU attribution)
# ---------------------------------------------------------------------------

def measure_block_fwd(block: int, *, batch=2048):
    """Forward of one VGG block (convs+relus+pool) at its in-network input
    shape, bf16 — per-block MFU shows WHICH convs XLA runs inefficiently."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models import core
    from idc_models_tpu.models.vgg import _CFG

    sizes = {1: 50, 2: 25, 3: 12, 4: 6, 5: 3}
    cins = {1: 3, 2: 64, 3: 128, 4: 256, 5: 512}
    _, filters, n_convs = _CFG[block - 1]
    layers = []
    c_in = cins[block]
    for conv in range(1, n_convs + 1):
        layers.append(core.conv2d(c_in, filters, 3,
                                  name=f"block{block}_conv{conv}"))
        layers.append(core.relu(name=f"block{block}_relu{conv}"))
        c_in = filters
    layers.append(core.max_pool(2, name=f"block{block}_pool"))
    model = core.sequential(layers)
    variables = model.init(jax.random.key(0))
    s = sizes[block]
    x = jnp.asarray(
        np.random.default_rng(0).random((batch, s, s, cins[block]),
                                        np.float32).astype(np.float32),
        dtype=jnp.bfloat16)

    @jax.jit
    def fwd(params, x):
        y, _ = model.apply(params, variables.state, x)
        return jnp.sum(y.astype(jnp.float32))

    compiled = fwd.lower(variables.params, x).compile()
    flops_per_step = program_report(
        compiled, name=f"mfu_matrix.block{block}_fwd").flops or 0.0
    box = {}

    def dispatch(n):
        for _ in range(n):
            box["y"] = compiled(variables.params, x)

    def fence():
        return float(box["y"])

    steps, dt, dts = _timed(dispatch, fence)
    return {
        "patches_per_sec_per_chip": steps * batch / dt,
        "steps": steps, "best_dt": dt, "window_dts": dts,
        "flops_per_patch": flops_per_step / batch if flops_per_step else None,
        "tflops_per_s": (flops_per_step * steps / dt / 1e12
                         if flops_per_step else None),
    }


def measure_cached(*, batch):
    """The --cache-features suffix step at a given per-chip batch."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models import registry
    from idc_models_tpu.models.vgg import KERAS_LAYER_INDEX, vgg16
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_train_step, replicate, rmsprop,
        shard_batch,
    )
    from idc_models_tpu.train import feature_cache as fc
    from idc_models_tpu.train.losses import binary_cross_entropy

    n_dev = len(jax.devices())
    total = batch * n_dev
    mesh = meshlib.data_mesh()
    model = vgg16(num_outputs=1)
    spec = registry.get_model("vgg16")
    plan = fc.plan_feature_cache(model, KERAS_LAYER_INDEX, 15, 512, 1)
    variables = model.init(jax.random.key(0))
    sp, ss = fc.suffix_variables(plan, variables.params, variables.state)
    opt = rmsprop(1e-4, trainable_mask=spec.fine_tune_mask(sp, 15))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=sp,
                       model_state=ss, opt_state=opt.init(sp))
    step = jit_data_parallel(
        make_train_step(plan.suffix_model, opt, binary_cross_entropy,
                        compute_dtype=jnp.bfloat16), mesh)
    rng = np.random.default_rng(0)
    feats = rng.random((total, 3, 3, 512)).astype(np.float32)
    labels = (rng.random(total) > 0.5).astype(np.int32)
    state = replicate(mesh, state)
    x, y = shard_batch(mesh, feats, labels)
    compiled = step.lower(state, x, y, jax.random.key(1)).compile()
    flops_per_step = program_report(compiled,
                                    name="mfu_matrix.cached").flops or 0.0
    digest = jax.jit(lambda s: jnp.sum(
        s.params["head"]["kernel"].astype(jnp.float32)))
    box = {"s": state, "k": jax.random.key(1)}

    def dispatch(n):
        s, k = box["s"], box["k"]
        for _ in range(n):
            k, sub = jax.random.split(k)
            s, _ = compiled(s, x, y, sub)
        box["s"], box["k"] = s, k

    def fence():
        return float(digest(box["s"]))

    steps, dt, dts = _timed(dispatch, fence)
    return {
        "patches_per_sec_per_chip": steps * total / dt / n_dev,
        "steps": steps, "best_dt": dt, "window_dts": dts,
        "flops_per_patch": flops_per_step / total if flops_per_step else None,
        "tflops_per_s": (flops_per_step * steps / dt / 1e12 / n_dev
                         if flops_per_step else None),
    }


EXPERIMENTS = {
    # headline configuration, measured first and last (drift bracket)
    "base": partial(measure_train),
    "batch_1024": partial(measure_train, batch=1024),
    "batch_3072": partial(measure_train, batch=3072),
    "batch_4096": partial(measure_train, batch=4096),
    "pad4": partial(measure_train, in_channels=4),
    "pad8": partial(measure_train, in_channels=8),
    "nchw": partial(measure_train, layout="NCHW"),
    "precision_highest": partial(measure_train, matmul_precision="highest"),
    "f32": partial(measure_train, compute_dtype="float32"),
    "input64": partial(measure_train, image_size=64),
    "fwd_only": partial(measure_train, fwd_only=True),
    "block1_fwd": partial(measure_block_fwd, 1),
    "block2_fwd": partial(measure_block_fwd, 2),
    "block3_fwd": partial(measure_block_fwd, 3),
    "block4_fwd": partial(measure_block_fwd, 4),
    "block5_fwd": partial(measure_block_fwd, 5),
    "cached_32768": partial(measure_cached, batch=32768),
    "cached_65536": partial(measure_cached, batch=65536),
    "cached_131072": partial(measure_cached, batch=131072),
    "base_again": partial(measure_train),
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
    print(f"device: {dev.device_kind} peak={peak} TF/s bf16; "
          f"writing {OUT}", file=sys.stderr)
    with OUT.open("a") as f:
        for name in names:
            t0 = time.time()
            try:
                r = EXPERIMENTS[name]()
                r["mfu"] = (r["tflops_per_s"] / peak
                            if peak and r.get("tflops_per_s") else None)
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
