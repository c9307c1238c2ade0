"""Training orchestration: epoch loops, evaluation, and the two-phase
transfer-learning schedule.

Parity target (SURVEY.md C7, dist_model_tf_vgg.py:130-160): compile with
RMSprop + from-logits loss -> `evaluate` the un-trained floor on a few
validation batches -> fit N epochs with the backbone frozen -> unfreeze
above `fine_tune_at`, recompile at lr/10 -> fit the remaining epochs
continuing the epoch counter. The reference hides the loop inside
`model.fit`; here it is explicit: host loader -> HBM prefetch -> jitted
DP train step -> per-epoch validation metrics -> Keras-style history
dicts, with named Timers (C17), jsonl records, and the training-curve
plot artifact (C18).

Freeze/unfreeze is an optimizer mask (core.trainability_mask via the
registry's mask builders) instead of the reference's recompile dance
(quirk Q6); recompiling at lr/10 maps to a fresh optimizer (and fresh
optimizer state, matching Keras recompile) over the same params.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.data.idc import ArrayDataset
from idc_models_tpu.data.pipeline import (
    Loader, prefetch_eval_batches, prefetch_to_mesh,
)
from idc_models_tpu.models import core, registry
from idc_models_tpu.observe import Timer, plot_history
from idc_models_tpu.observe import metrics_registry as mreg
from idc_models_tpu.observe import profile as prof
from idc_models_tpu.observe import trace
from idc_models_tpu.train import metrics as metrics_lib
from idc_models_tpu.train import step as step_mod
from idc_models_tpu.train.state import TrainState, create_train_state, rmsprop
from idc_models_tpu.train.step import (
    jit_data_parallel, make_eval_step, make_train_step, place_state,
    replicate, shard_batch,
)

History = dict[str, list[float]]


class Evaluator:
    """Holds one jitted eval step so repeated (per-epoch) evaluation does
    not recompile. Call with (state, ds) -> metrics dict.

    `steps` limits evaluation to the first `steps` batches — the
    reference's `validation_steps=20` floor sample (quirk Q3,
    dist_model_tf_vgg.py:15,134); None means the exact full set (padded
    final batch, every example counted once).
    """

    def __init__(self, model: core.Module, loss_fn, mesh: Mesh, *,
                 batch_size: int = 32, compute_dtype=jnp.float32,
                 with_auroc: bool = False, rules=None):
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.batch_size = batch_size
        self.with_auroc = with_auroc
        self.rules = rules
        # under partition rules the state keeps its placed (sharded)
        # layout — FOLLOW leaves the eval step's state pin to placement
        self._step = jit_data_parallel(
            make_eval_step(model, loss_fn, compute_dtype=compute_dtype),
            mesh, donate_state=False,
            state_shardings=(step_mod.FOLLOW if rules is not None
                             else None))
        # multi-host: batch-sharded logits span other processes' devices
        # and cannot be fetched directly; this identity jit re-places them
        # fully replicated (XLA all-gather over ICI/DCN) first
        self._gather = jax.jit(lambda x: x,
                               out_shardings=meshlib.replicated(mesh))

    def __call__(self, state: TrainState, ds: ArrayDataset, *,
                 steps: int | None = None) -> dict[str, float]:
        state = place_state(self.mesh, state, rules=self.rules)
        logits = jnp.asarray(batched_forward(
            self.mesh, self._gather, ds, self.batch_size, steps,
            lambda x, y: self._step(state, x, y)["logits"]))
        # the kept rows are exactly the first len(logits) examples
        labels = jnp.asarray(ds.labels[:len(logits)])
        out = {
            "loss": float(self.loss_fn(logits, labels)),
            "accuracy": float(metrics_lib.auto_accuracy(logits, labels)),
        }
        if self.with_auroc:
            out["auroc"] = float(metrics_lib.auroc(
                jax.nn.sigmoid(logits.reshape(-1)), labels))
        return out


def batched_forward(mesh: Mesh, gather, ds: ArrayDataset, batch_size: int,
                    steps: int | None, run) -> np.ndarray:
    """Shared eval/predict logits loop: batches of `ds` through `run(x, y)
    -> logits` on the sharded pipeline, padding rows dropped, results
    concatenated in order. `gather` is the identity jit with replicated
    out_shardings that makes batch-sharded logits fetchable on multi-host
    meshes (see Evaluator.__init__)."""
    parts = []
    for x, y, size in prefetch_eval_batches(ds, mesh, batch_size,
                                            steps=steps):
        logits = run(x, y)
        if not logits.is_fully_addressable:
            logits = gather(logits)
        parts.append(np.asarray(logits)[:size])
    return np.concatenate(parts)


def evaluate(model: core.Module, state: TrainState, ds: ArrayDataset,
             loss_fn, mesh: Mesh, *, batch_size: int = 32,
             steps: int | None = None, compute_dtype=jnp.float32,
             with_auroc: bool = False, rules=None) -> dict[str, float]:
    """One-shot evaluation (builds a throwaway Evaluator)."""
    ev = Evaluator(model, loss_fn, mesh, batch_size=batch_size,
                   compute_dtype=compute_dtype, with_auroc=with_auroc,
                   rules=rules)
    return ev(state, ds, steps=steps)


def predict(model: core.Module, state: TrainState, images, mesh: Mesh, *,
            batch_size: int = 32, compute_dtype=jnp.float32) -> np.ndarray:
    """Inference over a batch-sharded dataset: logits for every example,
    in order (the `model.predict` convenience of the Keras surface the
    reference's users come from). Runs the same sharded eval pipeline as
    the Evaluator — transfers overlapped, final batch padded to the mesh
    and the padding rows dropped — and works on DP, client, and
    ("data", "model") TP meshes alike."""
    images = np.asarray(images)
    if len(images) == 0:
        # Keras model.predict returns an empty array, not a crash; the
        # trailing shape comes from an abstract single-example eval
        # (batch 0 itself would break flatten's reshape(-1) inference)
        shape = jax.eval_shape(
            lambda x: model.apply(state.params, state.model_state, x,
                                  train=False)[0],
            jax.ShapeDtypeStruct((1,) + images.shape[1:],
                                 jnp.float32)).shape
        return np.zeros((0,) + shape[1:], np.float32)
    ds = ArrayDataset(images, np.zeros((len(images),), np.int32))
    placed = place_state(mesh, state)
    step = jit_data_parallel(
        lambda s, x, y: model.apply(s.params, s.model_state,
                                    x.astype(compute_dtype),
                                    train=False)[0].astype(jnp.float32),
        mesh, donate_state=False)
    gather = jax.jit(lambda x: x, out_shardings=meshlib.replicated(mesh))
    return batched_forward(mesh, gather, ds, batch_size, None,
                           lambda x, y: step(placed, x, y))


def fit(model: core.Module, optimizer: optax.GradientTransformation,
        loss_fn, state: TrainState, train_ds: ArrayDataset,
        val_ds: ArrayDataset | None, mesh: Mesh, *, epochs: int,
        batch_size: int = 32, initial_epoch: int = 0, seed: int = 0,
        logger=None, verbose: bool = True, central_storage: bool = False,
        compute_dtype=jnp.float32, repeats: int = 1,
        checkpoint_dir: str | None = None, checkpoint_every: int = 1,
        rules=None) -> tuple[TrainState, History]:
    """Keras-`fit`-shaped epoch loop over the jitted DP train step.

    Returns the final state and a Keras-style history dict
    ({"loss", "accuracy", "val_loss", "val_accuracy"} per epoch).
    `initial_epoch` continues a previous schedule's epoch numbering
    (dist_model_tf_vgg.py:159 `initial_epoch=history.epoch[-1]`).

    `checkpoint_dir` enables epoch-granular resume (SURVEY.md §5 build
    target: checkpoint every loop, not just the pretrainer): the full
    TrainState + history are saved every `checkpoint_every` epochs
    (plus always after the final one — a blocking orbax save per epoch
    can dominate short epochs), and a restart picks up at the epoch
    after the last save. Per-step rng keys are derived by folding the
    epoch into the seed, so a resumed run consumes the exact stream a
    straight-through run would have.

    `central_storage=True` is the parity toggle for the reference's
    `CentralStorageStrategy` variant (D2, dist_model_tf_dense.py:18,21-24):
    the master copy of the state lives in HOST memory between steps and is
    broadcast to the devices each step, with the updated state fetched
    back — numerically identical to the mirrored mode, paying a host
    round-trip per step exactly like variables-on-CPU compute-on-device.

    `rules` (partition.PartitionRules) shards the FULL state — params,
    BN stats, optimizer moments — by the regex->PartitionSpec policy
    (FSDP over "data", TP over "model"; models/registry.py holds the
    per-model defaults). The resolved shardings pin the step's state in
    AND out, so the layout is stable across donated steps (zero jit
    growth, gated by test) and the optimizer state shards with its
    param.
    """
    state_sh = (rules.shardings(mesh, state) if rules is not None
                else None)
    base_step = jit_data_parallel(
        make_train_step(model, optimizer, loss_fn,
                        compute_dtype=compute_dtype), mesh,
        state_shardings=state_sh)
    if central_storage:
        if rules is not None:
            raise NotImplementedError(
                "central_storage broadcasts a host-resident replica "
                "each step and cannot keep a rule-sharded (FSDP/TP) "
                "layout; drop partition rules or central_storage")
        if jax.process_count() > 1:
            raise NotImplementedError(
                "central_storage is a single-host parity mode (the "
                "reference's CentralStorageStrategy, "
                "dist_model_tf_dense.py:18, is single-host too); use the "
                "default mirrored mode on multi-host pods")
        from idc_models_tpu import tp

        if tp.has_model_axis(mesh):
            raise NotImplementedError(
                "central_storage broadcasts a host-resident replica each "
                "step and cannot keep a model-sharded layout; drop "
                "model parallelism or central_storage")
        state = jax.device_get(state)

        def step_fn(host_state, x, y, rng):
            out, m = base_step(replicate(mesh, host_state), x, y, rng)
            return jax.device_get(out), m
    else:
        step_fn = base_step
        state = place_state(mesh, state, rules=rules)
    # repeats>1 reproduces the reference CIFAR pipeline's `.repeat(2)`
    # (dist_model_tf_dense.py:122-123): each epoch passes over the train
    # set `repeats` times, freshly shuffled per pass. A Loader-shaped
    # stream (data.pipeline.FileStream) may be passed instead of an
    # ArrayDataset; it keeps its decode configuration but fit imposes
    # the FULL schedule (batch/shuffle/seed/repeat) so both paths train
    # identically for the same arguments (e.g. phase 2's seed+1).
    if isinstance(train_ds, ArrayDataset):
        loader = Loader(train_ds, batch_size, shuffle=True, seed=seed,
                        repeat=repeats)
    else:
        loader = train_ds.replace(batch_size=batch_size, shuffle=True,
                                  seed=seed, repeat=repeats)
    evaluator = (Evaluator(model, loss_fn, mesh, batch_size=batch_size,
                           compute_dtype=compute_dtype, rules=rules)
                 if val_ds is not None else None)
    history: History = {"loss": [], "accuracy": [],
                        "val_loss": [], "val_accuracy": []}
    start_epoch = initial_epoch
    fingerprint = None
    if checkpoint_dir is not None:
        # the loader's own knobs (== the fit args for ArrayDataset; the
        # stream's configuration otherwise) identify the data schedule
        fingerprint = _fit_fingerprint(state, loader.seed,
                                       loader.batch_size, loader.repeat,
                                       initial_epoch)
        restored = _restore_fit_checkpoint(checkpoint_dir, state, epochs,
                                           fingerprint)
        if restored is not None:
            state, history, start_epoch = restored
            start_epoch = max(start_epoch, initial_epoch)
            if verbose and start_epoch > initial_epoch:
                print(f"resuming fit from epoch {start_epoch + 1}")
    # process-wide instruments (idempotent; observe/metrics_registry.py)
    # — the history dict / jsonl epoch records above stay the schema
    # contract, the registry adds the operational rollup
    m_steps = mreg.REGISTRY.counter("train_steps_total",
                                    "optimizer steps taken")
    m_epochs = mreg.REGISTRY.counter("train_epochs_total",
                                     "epochs completed")
    m_loss = mreg.REGISTRY.gauge("train_loss",
                                 "last completed epoch's train loss")
    # program accounting only when a profile driver armed it (it costs
    # one extra compile of the step); central_storage's step_fn is a
    # host wrapper around base_step, so base_step is registered either
    # way — same executable, honest account
    accounted = not prof.accounting_enabled()
    for epoch in range(start_epoch, epochs):
        # epoch folded into the seed (not a running split) so a resumed
        # run reproduces the straight-through rng stream
        key = jax.random.fold_in(jax.random.key(seed), epoch)
        losses, accs = [], []
        with trace.span("train.epoch", epoch=epoch) as ep_span:
            for x, y in prefetch_to_mesh(loader.epoch(epoch), mesh):
                key, sub = jax.random.split(key)
                # the span covers the async step DISPATCH alone: the
                # wait for the batch is prefetch_to_mesh's data.wait,
                # and the device time the dispatch hides is fenced by
                # the epoch-mean fetch below, inside train.epoch
                with trace.span("train.step"):
                    state, m = step_fn(state, x, y, sub)
                if not accounted:
                    # opt-in program accounting (profile.py): one
                    # AOT accounting compile, named in PROGRAMS +
                    # program_* gauges; never on by default
                    accounted = True
                    prof.register_jit("train.step", base_step, state,
                                      x, y, sub)
                losses.append(m["loss"])
                accs.append(m["accuracy"])
            m_steps.inc(len(losses))
            # the epoch-mean fetch is where this loop BLOCKS on the
            # device — bracketed as device.sync: its share of
            # train.epoch is the benchmark's loop_sync_share, and
            # train.epoch minus data.wait, train.step and this span is
            # the loop's own overhead
            with trace.span("device.sync"):
                ep = {
                    "loss": float(jnp.mean(jnp.stack(losses))),
                    "accuracy": float(jnp.mean(jnp.stack(accs))),
                }
            ep_span.set(steps=len(losses), loss=ep["loss"])
        if not np.isfinite(ep["loss"]):
            # fail FAST and loudly: a NaN here would silently poison
            # every remaining epoch AND the saved checkpoint (the
            # optimizer state is already corrupt) — find the first bad
            # step so the error names where training went over the edge
            bad = next((i for i, l in enumerate(losses)
                        if not np.isfinite(float(l))), None)
            where = (f"epoch {epoch + 1}, step {bad + 1}/{len(losses)}"
                     if bad is not None else f"epoch {epoch + 1}")
            raise FloatingPointError(
                f"non-finite training loss ({ep['loss']}) at {where}: "
                f"the parameters and optimizer state are corrupt from "
                f"that step on, so continuing (or checkpointing) would "
                f"only persist garbage — lower the lr, check the input "
                f"data for NaN/Inf, or enable loss scaling")
        if evaluator is not None:
            with trace.span("train.eval", epoch=epoch):
                vm = evaluator(state, val_ds)
            ep["val_loss"] = vm["loss"]
            ep["val_accuracy"] = vm["accuracy"]
        for k, v in ep.items():
            history[k].append(v)
        m_epochs.inc()
        m_loss.set(ep["loss"])
        if verbose:
            msg = " ".join(f"{k}={v:.4f}" for k, v in ep.items())
            print(f"epoch {epoch + 1}/{epochs} {msg}")
        if logger is not None:
            logger.log(event="epoch", epoch=epoch, **ep)
        if checkpoint_dir is not None and (
                (epoch + 1) % max(checkpoint_every, 1) == 0
                or epoch + 1 == epochs):
            _save_fit_checkpoint(checkpoint_dir, state, history, epoch + 1,
                                 fingerprint)
    return state, history


def _fit_fingerprint(state: TrainState, seed: int, batch_size: int,
                     repeats: int, initial_epoch: int) -> str:
    """Identifies the training run a checkpoint belongs to: the rng/data
    schedule knobs plus a digest of the STARTING parameters (so e.g. a
    re-trained upstream phase invalidates a downstream phase's
    checkpoint instead of silently restoring stale state). The optimizer
    is not captured — changing lr between runs is not detected."""
    import hashlib

    h = hashlib.sha1(
        f"{seed}/{batch_size}/{repeats}/{initial_epoch}".encode())
    for leaf in jax.tree.leaves(jax.device_get(state.params)):
        a = np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(np.float64(a.astype(np.float64).sum()).tobytes())
    return h.hexdigest()


def _save_fit_checkpoint(ckpt_dir, state: TrainState, history: History,
                         next_epoch: int, fingerprint: str) -> None:
    """Commit protocol: the epoch-versioned orbax save lands first, then
    meta.json is atomically renamed to point at it. A crash between the
    two leaves meta pointing at the previous consistent (state, epoch)
    pair, so resume retrains at most the one interrupted epoch — never a
    state/counter mismatch. orbax save is a collective (it opens with an
    all-host barrier), so EVERY process calls it — orbax itself elects
    the writing host; only the tiny meta.json commit is process-0-gated
    (the checkpoint dir is assumed shared on pods)."""
    import json
    import shutil
    from pathlib import Path

    from idc_models_tpu.train.checkpoint import save_checkpoint

    d = Path(ckpt_dir)
    name = f"state_e{next_epoch}"
    save_checkpoint(d / name, jax.device_get(state))
    if jax.process_index() != 0:
        return
    tmp = d / "meta.json.tmp"
    tmp.write_text(json.dumps({"epoch": next_epoch, "state": name,
                               "fingerprint": fingerprint,
                               "history": history}))
    tmp.replace(d / "meta.json")
    for old in d.glob("state_e*"):
        if old.name != name:
            shutil.rmtree(old, ignore_errors=True)


def _restore_fit_checkpoint(ckpt_dir, target: TrainState, epochs: int,
                            fingerprint: str):
    import json
    import warnings
    from pathlib import Path

    from idc_models_tpu.train.checkpoint import (
        checkpoint_exists, restore_checkpoint,
    )

    d = Path(ckpt_dir)
    meta = d / "meta.json"
    if not meta.exists():
        return None
    info = json.loads(meta.read_text())
    if info.get("fingerprint") != fingerprint:
        warnings.warn(
            f"checkpoint {d} belongs to a different run (seed/batch/"
            f"repeats or starting parameters changed); ignoring it and "
            f"training from scratch", stacklevel=2)
        return None
    epoch = int(info["epoch"])
    if epoch > epochs:
        raise ValueError(
            f"checkpoint {d} was trained for {epoch} epochs but this run "
            f"asks for {epochs}; refusing to silently return the longer "
            f"run — delete the checkpoint dir or raise --epochs")
    state_dir = d / info.get("state", "state")
    if not checkpoint_exists(state_dir):
        return None
    state = restore_checkpoint(state_dir, jax.device_get(target))
    return state, dict(info["history"]), epoch


@dataclasses.dataclass(frozen=True)
class TwoPhaseConfig:
    """The reference's training hyperparameters in one place (its
    module-level constants, e.g. dist_model_tf_vgg.py:8-17)."""

    lr: float = 1e-3
    epochs: int = 10               # phase-1 (frozen backbone) epochs
    fine_tune_epochs: int = 10     # additional phase-2 epochs
    batch_size: int = 32
    fine_tune_at: int | None = None  # None -> registry default
    eval_steps: int | None = 20    # baseline-floor sample size (quirk Q3)
    repeats: int = 1               # dataset passes per epoch (dense: 2,
    #                                dist_model_tf_dense.py:122-123)
    cache_features: bool = False   # phase 2 on cached frozen-prefix
    #                                activations (train/feature_cache.py)
    seed: int = 0
    compute_dtype: Any = jnp.float32
    central_storage: bool = False  # D2: host-resident params per step


@dataclasses.dataclass
class TwoPhaseResult:
    state: TrainState
    model: core.Module             # the phase-2 model (for inference)
    history: History
    history_fine: History
    baseline: dict[str, float]
    pretrain_seconds: float
    fine_tune_seconds: float


def _build_model(spec: registry.ModelSpec, num_outputs: int,
                 in_channels: int, bn_frozen_below: int) -> core.Module:
    """Build with BN-freeze config when the model supports it (BN-bearing
    backbones must run frozen BN in inference mode — SURVEY.md §7
    'hard parts')."""
    params = inspect.signature(spec.build).parameters
    if "bn_frozen_below" in params:
        return spec.build(num_outputs, in_channels,
                          bn_frozen_below=bn_frozen_below)
    return spec.build(num_outputs, in_channels)


_FREEZE_ALL = 10_000  # larger than any Keras layer index


def two_phase_fit(model_name: str, num_outputs: int, train_ds: ArrayDataset,
                  val_ds: ArrayDataset, mesh: Mesh,
                  config: TwoPhaseConfig = TwoPhaseConfig(), *,
                  in_channels: int = 3, loss_fn=None,
                  pretrained_params=None, pretrained_state=None,
                  pretrained_weights: str | None = None,
                  artifact_path: str | None = None,
                  checkpoint_dir: str | None = None,
                  checkpoint_every: int = 1,
                  logger=None) -> TwoPhaseResult:
    """The reference's full two-phase transfer-learning program (C7).

    Phase 1: head-only training at `lr` with the backbone frozen
    (dist_model_tf_vgg.py:122,130-138). Phase 2: layers with Keras index
    >= fine_tune_at unfrozen, fresh RMSprop at lr/10, epoch counter
    continued (dist_model_tf_vgg.py:141-160). Saves the C18 plot artifact
    under `artifact_path` when given. `checkpoint_dir` enables
    epoch-granular resume of both phases (per-phase subdirectories).
    """
    from idc_models_tpu.train.losses import (
        binary_cross_entropy, sparse_categorical_cross_entropy,
    )

    if loss_fn is None:
        loss_fn = (binary_cross_entropy if num_outputs == 1
                   else sparse_categorical_cross_entropy)
    spec = registry.get_model(model_name)
    fine_tune_at = (config.fine_tune_at if config.fine_tune_at is not None
                    else spec.default_fine_tune_at)

    model1 = _build_model(spec, num_outputs, in_channels, _FREEZE_ALL)
    model2 = _build_model(spec, num_outputs, in_channels, fine_tune_at)

    init_rng = jax.random.key(config.seed)
    variables = model1.init(init_rng)
    params = pretrained_params if pretrained_params is not None else variables.params
    model_state = (pretrained_state if pretrained_state is not None
                   else variables.state)
    if pretrained_weights is not None:
        # ImageNet-backbone start (dist_model_tf_vgg.py:119-121): graft a
        # converted weight artifact onto the fresh init before phase 1.
        from idc_models_tpu.models.pretrained import maybe_load_pretrained

        params, model_state = maybe_load_pretrained(
            params, pretrained_weights, state=model_state)

    # Phase 1: head-only mask at lr
    opt1 = rmsprop(config.lr, trainable_mask=spec.head_only_mask(params))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       model_state=model_state, opt_state=opt1.init(params))

    baseline = evaluate(model1, state, val_ds, loss_fn, mesh,
                        batch_size=config.batch_size,
                        steps=config.eval_steps,
                        compute_dtype=config.compute_dtype)
    # 4 decimals: one head-only epoch from random features moves the
    # loss in the 4th (chip_smoke.py compares against this line)
    print(f"initial loss: {baseline['loss']:.4f}")
    print(f"initial accuracy: {baseline['accuracy']:.2f}")

    with Timer(f"Pre-training for {config.epochs} epochs",
               logger=logger) as t1:
        state, history = fit(
            model1, opt1, loss_fn, state, train_ds, val_ds, mesh,
            epochs=config.epochs, batch_size=config.batch_size,
            seed=config.seed, logger=logger,
            central_storage=config.central_storage,
            compute_dtype=config.compute_dtype, repeats=config.repeats,
            checkpoint_dir=(f"{checkpoint_dir}/phase1"
                            if checkpoint_dir else None),
            checkpoint_every=checkpoint_every)

    # Phase 2: "recompile" = fresh optimizer (and state) at lr/10 with the
    # fine-tune mask; BN below fine_tune_at stays in inference mode.
    mask2 = spec.fine_tune_mask(state.params, fine_tune_at)
    opt2 = rmsprop(config.lr / 10.0, trainable_mask=mask2)
    state = TrainState(step=state.step, params=state.params,
                       model_state=state.model_state,
                       opt_state=opt2.init(state.params))

    plan = None
    if config.cache_features:
        if not isinstance(train_ds, ArrayDataset):
            raise ValueError(
                "cache_features needs a materialized ArrayDataset (the "
                "cache runs the frozen prefix over the whole train set); "
                "drop --stream or --cache-features")
        from idc_models_tpu.train import feature_cache as fc

        plan = fc.plan_feature_cache(model2, spec.layer_index or {},
                                     fine_tune_at, spec.feature_dim,
                                     num_outputs)
        if plan is None:
            print(f"[idc_models_tpu] {model_name} is not splittable at "
                  f"fine_tune_at={fine_tune_at}; feature cache disabled")

    total_epochs = config.epochs + config.fine_tune_epochs
    with Timer(f"Fine tuning for {config.fine_tune_epochs} epochs",
               logger=logger) as t2:
        phase2_ckpt = f"{checkpoint_dir}/phase2" if checkpoint_dir else None
        if plan is not None:
            state, history_fine = _fit_cached_phase2(
                plan, spec, state, train_ds, val_ds, mesh, config,
                fine_tune_at, loss_fn, total_epochs, logger,
                checkpoint_dir=phase2_ckpt,
                checkpoint_every=checkpoint_every)
        else:
            state, history_fine = fit(
                model2, opt2, loss_fn, state, train_ds, val_ds, mesh,
                epochs=total_epochs, batch_size=config.batch_size,
                initial_epoch=config.epochs, seed=config.seed + 1,
                logger=logger, central_storage=config.central_storage,
                compute_dtype=config.compute_dtype, repeats=config.repeats,
                checkpoint_dir=phase2_ckpt,
                checkpoint_every=checkpoint_every)

    print(history)
    print(history_fine)
    if artifact_path is not None:
        plot_history(artifact_path, history, history_fine,
                     mesh.devices.size, initial_epochs=config.epochs)

    return TwoPhaseResult(
        state=state, model=model2, history=history,
        history_fine=history_fine, baseline=baseline,
        pretrain_seconds=t1.seconds, fine_tune_seconds=t2.seconds)


def _fit_cached_phase2(plan, spec, state: TrainState, train_ds, val_ds,
                       mesh: Mesh, config: TwoPhaseConfig,
                       fine_tune_at: int, loss_fn, total_epochs: int,
                       logger,
                       checkpoint_dir: str | None = None,
                       checkpoint_every: int = 1
                       ) -> tuple[TrainState, History]:
    """Phase 2 on cached frozen-prefix features (train/feature_cache.py):
    run the prefix once over train/val, fit the suffix model on the
    features with the same mask/optimizer/seed schedule the uncached path
    would use, then graft the trained suffix back into the full trees.

    Returns a TrainState for the FULL model; its optimizer state is
    freshly initialized (the suffix moments live only inside this phase).
    """
    from idc_models_tpu.train import feature_cache as fc

    with Timer("Caching frozen-backbone features", logger=logger):
        feat_train = fc.compute_features(
            plan, state.params, state.model_state, train_ds, mesh,
            batch_size=config.batch_size, compute_dtype=config.compute_dtype)
        feat_val = (fc.compute_features(
            plan, state.params, state.model_state, val_ds, mesh,
            batch_size=config.batch_size, compute_dtype=config.compute_dtype)
            if val_ds is not None else None)

    sp, ss = fc.suffix_variables(plan, state.params, state.model_state)
    opt = rmsprop(config.lr / 10.0,
                  trainable_mask=spec.fine_tune_mask(sp, fine_tune_at))
    sstate = TrainState(step=state.step, params=sp, model_state=ss,
                        opt_state=opt.init(sp))
    sstate, history_fine = fit(
        plan.suffix_model, opt, loss_fn, sstate, feat_train, feat_val,
        mesh, epochs=total_epochs, batch_size=config.batch_size,
        initial_epoch=config.epochs, seed=config.seed + 1, logger=logger,
        central_storage=config.central_storage,
        compute_dtype=config.compute_dtype, repeats=config.repeats,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every)

    params, model_state = fc.merge_suffix_variables(
        plan, state.params, state.model_state,
        jax.device_get(sstate.params), jax.device_get(sstate.model_state))
    mask2 = spec.fine_tune_mask(params, fine_tune_at)
    opt2 = rmsprop(config.lr / 10.0, trainable_mask=mask2)
    full = TrainState(step=sstate.step, params=params,
                      model_state=model_state,
                      opt_state=opt2.init(params))
    return full, history_fine

