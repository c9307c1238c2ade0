"""Runner for `fit_epochs` traffic: one `train.fit` call over synthetic
patches, epochs timed by the benchmark's own logger object until the
window is spent.

`fit` calls `logger.log(event="epoch")` after the epoch-mean fetch, which
fences the device, so the time between two calls is an epoch's wall time:
loader, transfers, steps and the fetch. The first `warmup_epochs` epochs
(compilation is in the first) are set-up. The window ends at the first
epoch boundary after `--seconds`; the logger ends the call by raising.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import harness, stats
from benchmark.reference import vgg16_ref

GEN_CHUNK = 2048
GEN_THREADS = 12


class _WindowDone(Exception):
    pass


class EpochClock:
    """The `logger` handed to `fit`: stamps every epoch's end, opens the
    window after the warm-up epochs, starts the profiler slice at the
    first epoch boundary past two fifths of the window, and ends the call
    once the window is spent."""

    def __init__(self, *, warmup_epochs: int, seconds: float,
                 profiler: harness.ProfilerSlice | None):
        self.warmup, self.seconds = warmup_epochs, seconds
        self.profiler = profiler
        self.stamps: list[float] = []
        self.losses: list[float] = []
        self.t_open: float | None = None

    def log(self, **rec):
        if rec.get("event") != "epoch":
            return
        now = time.perf_counter()
        self.stamps.append(now)
        self.losses.append(float(rec["loss"]))
        if len(self.stamps) == self.warmup:
            self.t_open = now
            harness.note("warm-up epochs done, window open")
        if self.t_open is None:
            return
        elapsed = now - self.t_open
        p = self.profiler
        if p is not None and not p.started and elapsed >= 0.4 * self.seconds:
            p.start()
        if elapsed >= self.seconds:
            raise _WindowDone

    def epoch_walls(self) -> list[float]:
        """Wall seconds of each measured epoch."""
        s = self.stamps
        return [s[i] - s[i - 1] for i in range(self.warmup, len(s))]


def make_patches(n: int, size: int, seed: int, pos_fraction: float,
                 threads: int) -> tuple[np.ndarray, np.ndarray]:
    """`n` IDC-like patches in host memory, where `fit`'s loader wants
    them, drawn in bulk from the seed: uniform noise in [0, 0.5], plus a
    centred Gaussian blob of height 0.4 on the positive ones (what
    `data/synthetic.py::make_idc_like` draws). Chunks of `GEN_CHUNK`
    patches, each from a generator of its own keyed by (seed, chunk), are
    filled in place by a few threads: numpy's generators release the
    interpreter lock, and the result does not depend on the thread count.
    (Drawing on the device and fetching measured 0.16 GB/s, my chip run,
    PR 22: the fetch, not the drawing, set that pace.)"""
    from concurrent.futures import ThreadPoolExecutor

    images = np.empty((n, size, size, 3), np.float32)
    labels = (np.random.default_rng([seed, 0]).random(n)
              < pos_fraction).astype(np.int32)
    yy, xx = np.mgrid[0:size, 0:size]
    c = (size - 1) / 2
    blob = 0.4 * np.exp(-((yy - c) ** 2 + (xx - c) ** 2) / (2 * (size / 4) ** 2))
    blob = blob.astype(np.float32)[None, :, :, None]

    def fill(i):
        out = images[i * GEN_CHUNK:(i + 1) * GEN_CHUNK]
        np.random.default_rng([seed, 1, i]).random(out=out, dtype=np.float32)
        out *= 0.5
        on = labels[i * GEN_CHUNK:(i + 1) * GEN_CHUNK].astype(np.float32)
        out += on[:, None, None, None] * blob
        np.clip(out, 0.0, 1.0, out=out)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(-(-n // GEN_CHUNK))))
    return images, labels


def _close(a, b, tol: float) -> tuple[bool, float]:
    """Largest difference over the largest reference magnitude."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
    return bool(np.isfinite(err) and err <= tol), err


def check_against_reference(model, loss_fn, params, model_state, images,
                            labels, spec: dict) -> dict:
    """System against `vgg16_ref` on one seeded batch, outside the window:
    logits, loss and head gradient in float32 at precision 'highest', and
    the bf16 forward's loss within a band of the reference's."""
    import jax
    import jax.numpy as jnp

    def system_loss(params, x, y):
        logits, _ = model.apply(params, model_state, x, train=False)
        logits = logits.astype(jnp.float32)
        return loss_fn(logits, y), logits

    def head_grad(params, x, y):
        # everything is an argument: a closed-over array would be baked
        # into the program as a constant, folded at compile time, and
        # would key the compile cache by the seed
        def of_head(head):
            return system_loss({"backbone": params["backbone"], "head": head},
                               x, y)

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(of_head, has_aux=True)(params["head"])

    # The gradient is taken with every label set to 1: with real labels
    # on fresh weights the residuals sigmoid(z) - y nearly cancel over a
    # balanced batch, and what is left is mostly rounding (one seed in
    # seven then differed by 5e-3 of its own size; my chip run, PR 22).
    ones = jnp.ones_like(labels)
    (loss, logits), _ = jax.jit(head_grad)(params, images, labels)
    _, grad = jax.jit(head_grad)(params, images, ones)
    ref_logits = jax.jit(vgg16_ref.forward)(params, images)
    ref_loss, _ = jax.jit(vgg16_ref.loss_and_head_grad)(params, images, labels)
    _, ref_grad = jax.jit(vgg16_ref.loss_and_head_grad)(params, images, ones)
    loss_bf16, _ = jax.jit(system_loss)(params, images.astype(jnp.bfloat16),
                                        labels)
    out = {}
    ok_l, out["logits_err"] = _close(logits, ref_logits, spec["f32_tol"])
    ok_s, out["loss_err"] = _close(loss, ref_loss, spec["f32_tol"])
    flat = lambda g: np.concatenate([np.ravel(g["kernel"]), np.ravel(g["bias"])])
    ok_g, out["head_grad_err"] = _close(flat(grad), flat(ref_grad),
                                        spec["f32_tol"])
    out["bf16_loss_diff"] = abs(float(loss_bf16) - float(ref_loss))
    ok_h = out["bf16_loss_diff"] <= spec["bf16_loss_band"]
    out["ok"] = bool(ok_l and ok_s and ok_g and ok_h)
    return out


def check_data_parallel(fit_args: dict, fresh_state, images, labels,
                        chips: int, spec: dict) -> dict:
    """Three steps through `fit` on one device and on `chips`: the same
    batches, so the losses agree up to the order of the gradient sum.
    `fit` donates its state, so each call gets a fresh one."""
    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data.idc import ArrayDataset
    from idc_models_tpu.train import fit

    ds = ArrayDataset(np.asarray(images), np.asarray(labels))
    losses = []
    for n in (1, chips):
        _, hist = fit(state=fresh_state(), train_ds=ds, val_ds=None,
                      mesh=meshlib.data_mesh(n), epochs=3,
                      batch_size=len(ds), verbose=False, **fit_args)
        losses.append(hist["loss"])
    ok, err = _close(losses[1], losses[0], spec["dp_rtol"])
    return {"ok": ok, "dp_loss_err": err, "dp_losses": losses}


def build_trainer(model_cfg: dict, seed: int):
    """The model, and what `fit` needs beside data and a mesh: its
    arguments, and a maker of fresh train states on the device (one
    jitted call from the seed; `fit` donates the state it is given)."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models.vgg import fine_tune_mask, vgg16
    from idc_models_tpu.train import create_train_state, rmsprop
    from idc_models_tpu.train.losses import binary_cross_entropy

    model = vgg16(num_outputs=model_cfg["num_outputs"])
    shapes = jax.eval_shape(lambda k: model.init(k).params, jax.random.key(0))
    opt = rmsprop(model_cfg["lr"], trainable_mask=fine_tune_mask(
        shapes, model_cfg["fine_tune_at"]))
    init = jax.jit(lambda k: create_train_state(model, opt, k))
    fit_args = dict(model=model, optimizer=opt, loss_fn=binary_cross_entropy,
                    seed=seed,
                    compute_dtype=jnp.dtype(model_cfg["compute_dtype"]))
    return model, fit_args, lambda: init(jax.random.key(seed))


def run(job) -> dict:
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data.idc import ArrayDataset
    from idc_models_tpu.observe import trace as ptrace
    from idc_models_tpu.train import fit
    from idc_models_tpu.train.losses import binary_cross_entropy

    model_cfg, mix = job.config["model"], job.traffic["fit_epochs"]
    chips = job.cell["chips"]
    if mix["chips"] != chips:
        raise SystemExit(f"traffic {job.traffic['name']!r} is for "
                         f"{mix['chips']} chip(s), the cell asks for {chips}")
    size = model_cfg["image_size"]
    batch = mix["batch_per_chip"] * chips
    steps_per_epoch = mix["examples"] // batch
    mesh = meshlib.data_mesh(chips)

    harness.note(f"drawing {mix['examples']} patches")
    images, labels = make_patches(mix["examples"], size, job.seed,
                                  mix["pos_fraction"], GEN_THREADS)
    harness.note("patches in host memory")
    model, fit_args, fresh_state = build_trainer(model_cfg, job.seed)

    profiler = (harness.ProfilerSlice(job.scratch / "profile",
                                      mix["profile_s"], host=False)
                if job.trace else None)
    clock = EpochClock(warmup_epochs=mix["warmup_epochs"],
                       seconds=job.seconds, profiler=profiler)
    tracer = ptrace.Tracer() if job.trace else None
    prev = ptrace.set_tracer(tracer) if tracer is not None else None
    failed = 0
    harness.note(f"fit: batch {batch}, {steps_per_epoch} steps an epoch")
    try:
        fit(state=fresh_state(), train_ds=ArrayDataset(images, labels),
            val_ds=None,
            mesh=mesh, epochs=10 ** 9, batch_size=batch, logger=clock,
            verbose=False, **fit_args)
    except _WindowDone:
        pass
    except FloatingPointError:
        failed = 1                         # a non-finite epoch ends the run
    finally:
        if tracer is not None:
            ptrace.set_tracer(prev)
    t_end = time.perf_counter()
    memory_peak = harness.memory_peak_bytes(chips)
    harness.note(f"window closed after {len(clock.stamps)} epochs")

    walls = clock.epoch_walls()
    rates = [steps_per_epoch * batch / w / chips for w in walls]
    failed += sum(1 for l in clock.losses[clock.warmup:] if not np.isfinite(l))
    counters = {
        "runner.steps_per_epoch": steps_per_epoch,
        "runner.epochs": len(walls),
        "runner.patches_per_s_chip": stats.median(rates) if rates else None,
        "runner.seconds_per_patch_chip": (1.0 / stats.median(rates)
                                          if rates else None),
        "runner.epoch_wall_spread": stats.spread(walls) if len(walls) > 1 else 0.0,
    }

    # Correctness, outside the window, on fresh weights of the same seed.
    del images, labels
    state = fresh_state()
    cx, cy = make_patches(job.config["check"]["batch"], size, job.seed + 1,
                          mix["pos_fraction"], 1)
    checks = check_against_reference(
        model, binary_cross_entropy, state.params, state.model_state,
        jnp.asarray(cx), jnp.asarray(cy), job.config["check"])
    if chips > 1:
        checks["dp"] = check_data_parallel(fit_args, fresh_state, cx, cy,
                                           chips, job.config["check"])
        checks["ok"] = bool(checks["ok"] and checks["dp"]["ok"])
    harness.note("checked against the reference")

    return {
        "correct": bool(checks["ok"] and failed == 0 and len(walls) > 0),
        "attempted": len(walls), "failed": failed,
        "end_to_end": {"train_patches_per_s_chip":
                       counters["runner.patches_per_s_chip"]},
        "t_open": clock.t_open, "t_close": t_end,
        "memory_peak_bytes": memory_peak,
        "counters": counters, "checks": checks,
        "tracer": tracer, "profiler": profiler,
    }
