"""The jitted train/eval step — the framework owns it explicitly.

In the reference the step function is hidden inside `model.fit` and
MirroredStrategy (forward+backward per replica, NCCL allreduce, mirrored
update — SURVEY.md §3.1 "HOT LOOP"). Here it is one pure function:

    loss -> grad -> (XLA-inserted allreduce over the "data" mesh axis) ->
    optax update -> new TrainState

Data parallelism uses the modern jit-with-shardings style: the global batch
is sharded over the mesh's "data" axis, parameters are replicated, and XLA
lowers the gradient reduction onto ICI automatically — there is no pmap and
no hand-written collective in the hot path. (The explicit-collective style
still exists in this framework where per-device control genuinely matters:
federated and secure aggregation use `shard_map` + `collectives`.)
"""

from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models import core
from idc_models_tpu.train import metrics as metrics_lib
from idc_models_tpu.train.state import TrainState

LossFn = Callable[[jax.Array, jax.Array], jax.Array]


def make_train_step(model: core.Module, optimizer: optax.GradientTransformation,
                    loss_fn: LossFn, *, compute_dtype=jnp.float32):
    """Returns train_step(state, images, labels, rng) -> (state, metrics)."""

    def train_step(state: TrainState, images, labels, rng):
        # integer inputs (LM token ids) skip the compute-dtype cast: a
        # bf16 round-trip would silently corrupt ids > 256 before the
        # model's int32 cast-back (attention_lm), and integer inputs
        # never benefit from a low-precision matmul dtype anyway
        if not jnp.issubdtype(jnp.asarray(images).dtype, jnp.integer):
            images = images.astype(compute_dtype)

        def loss_of(params):
            logits, new_model_state = model.apply(
                params, state.model_state, images, train=True, rng=rng)
            logits = logits.astype(jnp.float32)
            return loss_fn(logits, labels), (logits, new_model_state)

        (loss, (logits, new_model_state)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params)
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        out = state.replace(
            step=state.step + 1,
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt_state,
        )
        m = {"loss": loss, "accuracy": metrics_lib.auto_accuracy(logits, labels)}
        return out, m

    return train_step


def make_eval_step(model: core.Module, loss_fn: LossFn, *,
                   compute_dtype=jnp.float32):
    """Returns eval_step(state, images, labels) -> metrics (loss/acc/logits)."""

    def eval_step(state: TrainState, images, labels):
        if not jnp.issubdtype(jnp.asarray(images).dtype, jnp.integer):
            images = images.astype(compute_dtype)  # ids stay exact
        logits, _ = model.apply(state.params, state.model_state, images,
                                train=False)
        logits = logits.astype(jnp.float32)
        return {
            "loss": loss_fn(logits, labels),
            "accuracy": metrics_lib.auto_accuracy(logits, labels),
            "logits": logits,
        }

    return eval_step



# ---------------------------------------------------------------------------
# data-parallel jit wrappers
# ---------------------------------------------------------------------------

#: sentinel for `jit_data_parallel(state_shardings=...)`: leave the
#: state's shardings unpinned so the step follows whatever layout
#: `place_state` installed (the eval path under partition rules).
FOLLOW = "follow"


def jit_data_parallel(step_fn, mesh: Mesh, *, donate_state: bool = True,
                      axis: str | None = None, state_shardings=None):
    """Jit `step_fn(state, images, labels, *rest)` with DP shardings.

    State replicated; images/labels sharded on their leading axis over
    `axis` (default: the mesh's "data" axis, or its only axis when 1-D —
    so eval works on a "client" mesh too). This is the whole
    MirroredStrategy replacement for D1.

    `state_shardings` overrides the state pin: a NamedSharding pytree
    (from `partition.PartitionRules.shardings`, resolved over the full
    TrainState so optimizer moments shard with their params) pins the
    state in AND out — FSDP/TP layouts stay stable across donated
    steps; the `FOLLOW` sentinel leaves the state unpinned to follow
    its placement. On a 2-D ("data", "model") mesh without an explicit
    override the state follows its `place_state` channel layout
    (tp.py), as before.
    """
    from idc_models_tpu import tp

    repl = meshlib.replicated(mesh)
    if state_shardings is None:
        state_sh = None if tp.has_model_axis(mesh) else repl
    else:
        state_sh = (None if isinstance(state_shardings, str)
                    and state_shardings == FOLLOW else state_shardings)
    batch = meshlib.sharding(mesh, _batch_axis(mesh, axis))
    in_shardings = (state_sh, batch, batch)
    # Pin the RETURNED state to the same layout as the input state:
    # without this, GSPMD may shard an updated param over whatever axis
    # its gradient arrived on (e.g. a positional embedding over "seq"
    # when the model runs ring attention in-step), and the next call
    # rejects the now-mismatched donated input. Only train-shaped steps
    # ((state, metrics) returns) donate state; eval-shaped steps return
    # arbitrary pytrees and stay unconstrained.
    return jax.jit(
        step_fn,
        in_shardings=in_shardings + (repl,) if _wants_rng(step_fn) else in_shardings,
        out_shardings=(state_sh, None) if donate_state else None,
        donate_argnums=(0,) if donate_state else (),
    )


def _wants_rng(fn) -> bool:
    import inspect

    try:
        return "rng" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def shard_batch(mesh: Mesh, *arrays, axis: str | None = None):
    """Put host arrays on `mesh` sharded over the batch axis."""
    sh = meshlib.sharding(mesh, _batch_axis(mesh, axis))
    out = tuple(meshlib.put_with_sharding(a, sh) for a in arrays)
    return out if len(out) > 1 else out[0]


_batch_axis = meshlib.batch_axis


def replicate(mesh: Mesh, tree):
    """Put a pytree on `mesh` fully replicated (multi-process safe)."""
    sh = meshlib.replicated(mesh)
    if sh.is_fully_addressable:
        return jax.device_put(tree, sh)
    return jax.tree.map(lambda a: meshlib.put_with_sharding(a, sh), tree)


def place_state(mesh: Mesh, tree, rules=None):
    """Put a TrainState (or any param-shaped tree) on `mesh` in the
    layout the jitted step expects: under `rules`
    (partition.PartitionRules — the FSDP/TP path) when given, else
    channel-wise model-sharded on a ("data", "model") mesh (tp.py),
    else replicated (DP/client meshes)."""
    from idc_models_tpu import partition, tp

    if rules is not None:
        return partition.shard_tree(mesh, rules, tree)
    if tp.has_model_axis(mesh):
        return tp.place(mesh, tree)
    return replicate(mesh, tree)
