"""Explicit-pytree neural-network layer library (the Keras replacement).

Every layer is a `Module`: a pair of pure functions

    init(rng)                         -> Variables{"params", "state"}
    apply(params, state, x, train, rng) -> (y, new_state)

Parameters and mutable state (BatchNorm moving statistics) are plain nested
dicts of jnp arrays — ordinary pytrees that `jit`, `grad`, `shard_map`,
optax, and orbax all consume directly. There is no module instance holding
tensors, so "clone the model per graph context" (the reference's
fed_model.py:196-205 contortion) is just... reusing the pytree.

Layout is NHWC with HWIO conv kernels — the layout XLA:TPU prefers for
feeding the MXU. Initializers match Keras defaults (glorot_uniform kernels,
zero biases) so parity runs start from the same distribution family as the
reference models (e.g. secure_fed_model.py:84-98).

Trainability is expressed as a boolean pytree mask consumed by
`train.state.freeze_where` (see `trainability_mask`) instead of the
reference's freeze/recompile dance (quirk Q6, dist_model_tf_vgg.py:141-154).
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Callable, Sequence
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

Params = Any  # nested dict pytree of jnp arrays
State = Any


@dataclasses.dataclass(frozen=True)
class Variables:
    params: Params
    state: State


@dataclasses.dataclass(frozen=True)
class Module:
    """A pure init/apply pair. `name` is used as the pytree key in Sequential.

    `layer_names` records the model's layer order (the order Keras
    `get_weights()` would enumerate) for composites built by `sequential` /
    `classifier`; consumers that need ordered-tensor semantics (the secure
    `percent`-of-tensors knob) use it instead of jax's alphabetical
    flatten order.
    """

    init: Callable[[jax.Array], Variables]
    apply: Callable[..., tuple[jax.Array, State]]
    name: str = "module"
    layer_names: tuple[str, ...] = ()
    # (param_key, child Module) pairs for composites built by `sequential`
    # / `classifier`; lets consumers re-compose sub-programs (e.g. the
    # frozen-backbone feature cache splits a backbone at fine_tune_at).
    # Empty for leaf layers and hand-rolled composites.
    children: tuple[tuple[str, "Module"], ...] = ()
    # Optional model-provided split for backbones whose topology is not a
    # plain sequential (residual adds, dense concats): called with a
    # Keras fine_tune_at index, returns (prefix, suffix) Modules sharing
    # the parent's flat param keys — each section's layer_names lists the
    # param keys it consumes — or None when no frozen prefix exists.
    splitter: Callable[[int], tuple["Module", "Module"] | None] | None = None


def _split(rng, n):
    return jax.random.split(rng, n)


# ---------------------------------------------------------------------------
# initializers (Keras-default parity)
# ---------------------------------------------------------------------------

def glorot_uniform(rng, shape, fan_in, fan_out, dtype=jnp.float32):
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def he_normal(rng, shape, fan_in, dtype=jnp.float32):
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(rng, shape, dtype) * std


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def dense(features_in: int, features_out: int, *, use_bias: bool = True,
          name: str = "dense") -> Module:
    def init(rng):
        k = glorot_uniform(rng, (features_in, features_out),
                           features_in, features_out)
        p = {"kernel": k}
        if use_bias:
            p["bias"] = jnp.zeros((features_out,))
        return Variables(p, {})

    def apply(params, state, x, *, train=False, rng=None):
        y = x @ params["kernel"]
        if use_bias:
            y = y + params["bias"]
        return y, state

    return Module(init, apply, name)


def conv2d(features_in: int, features_out: int, kernel_size: int | tuple = 3,
           *, stride: int | tuple = 1,
           padding: str | tuple = "SAME",
           use_bias: bool = True, name: str = "conv") -> Module:
    """2-D convolution. `padding` is "SAME"/"VALID" or explicit
    ((lo_h, hi_h), (lo_w, hi_w)) pairs — the explicit form is needed where
    Keras uses symmetric ZeroPadding2D + valid conv (e.g. the DenseNet
    stem), which lax SAME (asymmetric lo<=hi split) does not reproduce."""
    kh, kw = ((kernel_size, kernel_size) if isinstance(kernel_size, int)
              else kernel_size)
    strides = (stride, stride) if isinstance(stride, int) else stride

    def init(rng):
        fan_in = kh * kw * features_in
        fan_out = kh * kw * features_out
        k = glorot_uniform(rng, (kh, kw, features_in, features_out),
                           fan_in, fan_out)
        p = {"kernel": k}
        if use_bias:
            p["bias"] = jnp.zeros((features_out,))
        return Variables(p, {})

    pad = padding if isinstance(padding, str) else [tuple(p) for p in padding]
    # MXU input-tile fill: a 3-channel contraction (the RGB stem conv,
    # contraction depth kh*kw*3) under-fills the systolic array; zero-
    # padding input AND kernel to 4 channels measured +4% whole-step
    # throughput on TPU v5e (experiments/mfu_matrix.jsonl: pad4 vs base)
    # with identical output — the padded taps contribute exact zeros, and
    # params keep their Keras-parity (kh, kw, 3, out) shape.
    pad_c = 4 - features_in if 0 < features_in < 4 else 0

    def apply(params, state, x, *, train=False, rng=None):
        k = params["kernel"].astype(x.dtype)
        if pad_c:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_c), (0, 0)))
        y = lax.conv_general_dilated(
            x, k, strides, pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if use_bias:
            y = y + params["bias"].astype(y.dtype)
        return y, state

    return Module(init, apply, name)


def depthwise_conv2d(features: int, kernel_size: int | tuple = 3, *,
                     stride: int | tuple = 1, padding: str = "SAME",
                     use_bias: bool = False, impl: str = "grouped",
                     name: str = "dwconv") -> Module:
    """Depthwise conv (MobileNetV2 building block).

    `impl` picks the lowering, same math either way (equality pinned by
    tests/test_core_layers.py):

    - "grouped": `lax.conv_general_dilated` with
      feature_group_count=features — XLA's native depthwise path.
    - "taps": explicit kh*kw shifted elementwise multiply-accumulates.
      A depthwise conv has no channel contraction, so there is nothing
      for the MXU's systolic array to reduce — this formulation hands
      XLA the pure-VPU form directly: kh*kw strided slices of one
      padded copy of x, fused into one elementwise loop. Measured in
      round 4 through a runtime that no longer exists (not in the
      ledger; experiments/backbone_mfu.jsonl, one MobileNetV2 step
      re-fed a resident batch of 2048 on TPU v5e): the native grouped
      lowering WINS — 234k vs 138k patches/s — so "grouped" stays the
      default; "taps" is queued for deletion (ROADMAP.md C4).
    - "fused": the Pallas kernel (ops/fused_conv.py) — the taps math
      computed on a VMEM-resident tile (interpreted off-TPU, so the
      same code path runs in tier-1 on CPU). Standalone it runs with an
      identity affine; its point is the cross-LAYER fusion
      models/mobilenet.py drives through it (depthwise+BN+relu6 in one
      kernel, see unit_backbone's `run` attributes). Stays opt-in until
      the perf gate holds on TPU (ISSUE 16 acceptance).
    """
    kh, kw = ((kernel_size, kernel_size) if isinstance(kernel_size, int)
              else kernel_size)
    strides = (stride, stride) if isinstance(stride, int) else stride
    if impl not in ("grouped", "taps", "fused"):
        raise ValueError(f"impl must be grouped|taps|fused, got {impl!r}")
    if impl in ("taps", "fused") and padding != "SAME":
        raise ValueError(f"impl={impl!r} implements SAME padding only")

    def init(rng):
        fan_in = kh * kw
        k = glorot_uniform(rng, (kh, kw, 1, features), fan_in, fan_in)
        p = {"kernel": k}
        if use_bias:
            p["bias"] = jnp.zeros((features,))
        return Variables(p, {})

    def apply(params, state, x, *, train=False, rng=None):
        w = params["kernel"].astype(x.dtype)
        if impl == "fused":
            from idc_models_tpu.ops import fused_conv

            ones = jnp.ones((features,), jnp.float32)
            add = (params["bias"].astype(jnp.float32) if use_bias
                   else jnp.zeros((features,), jnp.float32))
            y = fused_conv.fused_depthwise_affine(
                x, w, ones, add, stride=strides, clamp6=False)
            return y, state
        if impl == "taps":
            sh, sw = strides
            _, h_in, w_in, _ = x.shape
            h_out, w_out = -(-h_in // sh), -(-w_in // sw)
            # TF-SAME split: lo = total//2, hi = rest (matches XLA)
            ph = max((h_out - 1) * sh + kh - h_in, 0)
            pw = max((w_out - 1) * sw + kw - w_in, 0)
            xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                             (pw // 2, pw - pw // 2), (0, 0)))
            y = None
            for i in range(kh):
                for j in range(kw):
                    xs = xp[:, i:i + (h_out - 1) * sh + 1:sh,
                            j:j + (w_out - 1) * sw + 1:sw, :]
                    t = xs * w[i, j, 0]
                    y = t if y is None else y + t
        else:
            y = lax.conv_general_dilated(
                x, w, strides, padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=features)
        if use_bias:
            y = y + params["bias"].astype(y.dtype)
        return y, state

    return Module(init, apply, name)


def batch_norm(features: int, *, momentum: float = 0.99, eps: float = 1e-3,
               axis_name: str | None = None, frozen: bool = False,
               name: str = "bn") -> Module:
    """BatchNorm with explicit moving statistics.

    In train mode, batch statistics are computed over the local batch; if
    `axis_name` is given (when running under shard_map) they are averaged
    cross-replica with `lax.pmean`, making global-batch statistics explicit —
    the decision the reference leaves implicit to Keras (SURVEY.md §7 "hard
    parts": BN under freeze/fine-tune). In eval mode the stored moving
    stats are used.

    `frozen=True` reproduces Keras' `trainable=False` BN semantics: the
    layer always runs in inference mode (moving stats, no updates) even
    when the model is applied with train=True — required so a frozen
    pretrained backbone's function does not drift under a training head.
    """

    def init(rng):
        p = {"scale": jnp.ones((features,)), "bias": jnp.zeros((features,))}
        s = {"mean": jnp.zeros((features,)), "var": jnp.ones((features,))}
        return Variables(p, s)

    def apply(params, state, x, *, train=False, rng=None):
        if train and not frozen:
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x.astype(jnp.float32), axes)
            second = jnp.mean(jnp.square(x.astype(jnp.float32)), axes)
            if axis_name is not None:
                # Average the raw moments, not per-shard variances: global
                # var must come from global moments or it is underestimated
                # whenever shard means differ (e.g. non-IID client shards).
                mean = lax.pmean(mean, axis_name)
                second = lax.pmean(second, axis_name)
            var = second - mean**2
            new_state = {
                "mean": momentum * state["mean"] + (1 - momentum) * mean,
                "var": momentum * state["var"] + (1 - momentum) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + eps) * params["scale"]
        y = (x.astype(jnp.float32) - mean) * inv + params["bias"]
        return y.astype(x.dtype), new_state

    return Module(init, apply, name)


def layer_norm(features: int, *, eps: float = 1e-6,
               name: str = "ln") -> Module:
    """LayerNorm over the trailing feature axis (Keras
    LayerNormalization defaults: scale+bias, trailing-axis stats).
    Unlike batch_norm it carries no cross-replica state, so it is the
    normalization of choice for sequence models running under
    sequence-sharded meshes (ring_attention): every position normalizes
    itself."""

    def init(rng):
        return Variables({"scale": jnp.ones((features,)),
                          "bias": jnp.zeros((features,))}, {})

    def apply(params, state, x, *, train=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + eps)
        y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype), state

    return Module(init, apply, name)


def relu(name: str = "relu") -> Module:
    return _stateless(lambda x: jax.nn.relu(x), name)


def relu6(name: str = "relu6") -> Module:
    return _stateless(lambda x: jnp.minimum(jax.nn.relu(x), 6.0), name)


def _stateless(fn, name):
    def init(rng):
        return Variables({}, {})

    def apply(params, state, x, *, train=False, rng=None):
        return fn(x), state

    return Module(init, apply, name)


def max_pool(window: int = 2, stride: int | None = None, *,
             padding: str = "VALID", name: str = "maxpool") -> Module:
    stride = window if stride is None else stride

    def apply_fn(x):
        return lax.reduce_window(
            x, -jnp.inf, lax.max,
            (1, window, window, 1), (1, stride, stride, 1), padding)

    return _stateless(apply_fn, name)


def avg_pool(window: int = 2, stride: int | None = None, *,
             padding: str = "VALID", name: str = "avgpool") -> Module:
    stride = window if stride is None else stride

    def apply_fn(x):
        dims = (1, window, window, 1)
        strides = (1, stride, stride, 1)
        s = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
        if padding == "VALID":
            return s / (window * window)
        # SAME: divide by the count of real (non-padded) elements per
        # window, matching Keras AveragePooling2D edge behavior.
        ones = jnp.ones(x.shape[1:3], x.dtype)[None, :, :, None]
        count = lax.reduce_window(ones, 0.0, lax.add, dims, strides, padding)
        return s / count

    return _stateless(apply_fn, name)


def global_avg_pool(name: str = "gap") -> Module:
    """GlobalAveragePooling2D — the head junction in every reference model
    (e.g. dist_model_tf_vgg.py:125-129)."""
    return _stateless(lambda x: jnp.mean(x, axis=(1, 2)), name)


def flatten(name: str = "flatten") -> Module:
    return _stateless(lambda x: x.reshape(x.shape[0], -1), name)


def dropout(rate: float, name: str = "dropout") -> Module:
    if not 0.0 <= rate < 1.0:
        raise ValueError(
            f"dropout rate must be in [0, 1), got {rate} — negative "
            f"rates silently rescale activations and rate >= 1 zeroes "
            f"the branch entirely")

    def init(rng):
        return Variables({}, {})

    def apply(params, state, x, *, train=False, rng=None):
        if not train or rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError(f"dropout({name}) needs an rng in train mode")
        keep = 1.0 - rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state

    return Module(init, apply, name)


# ---------------------------------------------------------------------------
# composition
# ---------------------------------------------------------------------------

def _keyed_sequential(keys: list[str], layers: list[Module],
                      name: str) -> Module:
    """The one sequential-composition body: params/state are dicts under
    the given per-layer keys. Shared by `sequential` (which derives fresh
    unique keys) and `subsequence` (which KEEPS a parent's keys)."""

    def init(rng):
        rngs = _split(rng, len(layers))
        params, state = {}, {}
        for key, m, r in zip(keys, layers, rngs):
            v = m.init(r)
            if v.params:
                params[key] = v.params
            if v.state:
                state[key] = v.state
        return Variables(params, state)

    def apply(params, state, x, *, train=False, rng=None):
        new_state = dict(state)
        rngs = _split(rng, len(layers)) if rng is not None else [None] * len(layers)
        for key, m, r in zip(keys, layers, rngs):
            p = params.get(key, {})
            s = state.get(key, {})
            x, s2 = m.apply(p, s, x, train=train, rng=r)
            if key in state:
                new_state[key] = s2
        return x, new_state

    return Module(init, apply, name, layer_names=tuple(keys),
                  children=tuple(zip(keys, layers)))


def sequential(layers: Sequence[Module], name: str = "sequential") -> Module:
    """Compose modules; params/state are dicts keyed by unique layer names."""
    keys: list[str] = []
    used: set[str] = set()
    for m in layers:
        n = m.name
        i = 0
        while n in used:
            n = f"{m.name}_{i}"
            i += 1
        used.add(n)
        keys.append(n)
    return _keyed_sequential(keys, list(layers), name)


def subsequence(seq: Module, keys_subset: Sequence[str],
                name: str | None = None) -> Module:
    """A sequential over a contiguous run of `seq`'s children, KEEPING the
    parent's param keys (so the sub-module consumes/produces the matching
    subtree of the parent's params/state directly). `keys_subset` must be
    a contiguous in-order slice of the parent's child keys (possibly
    empty: the identity module) — anything else would silently compute a
    different function than the parent."""
    parent_keys = [k for k, _ in seq.children]
    if not parent_keys:
        raise ValueError(f"{seq.name} has no children to slice")
    keys = list(keys_subset)
    if keys:
        try:
            start = parent_keys.index(keys[0])
        except ValueError:
            raise KeyError(f"{seq.name} has no child {keys[0]!r}")
        if parent_keys[start:start + len(keys)] != keys:
            raise ValueError(
                f"keys_subset must be a contiguous in-order run of "
                f"{seq.name}'s children; got {keys}")
    child_map = dict(seq.children)
    default = (f"{seq.name}[{keys[0]}:{keys[-1]}]" if keys
               else f"{seq.name}[empty]")
    return _keyed_sequential(keys, [child_map[k] for k in keys],
                             name or default)


def split_sequential(seq: Module, at_key: str) -> tuple[Module, Module]:
    """Split a sequential composite into (prefix, suffix) at `at_key`
    (the suffix starts with `at_key`). Param/state keys are preserved, so
    `suffix.apply(subset_of_params, ...)` composes with
    `prefix.apply(...)` to reproduce `seq.apply` exactly."""
    keys = [k for k, _ in seq.children]
    if at_key not in keys:
        raise KeyError(f"{seq.name} has no child {at_key!r}; have {keys}")
    i = keys.index(at_key)
    return (subsequence(seq, keys[:i], name=f"{seq.name}[:{at_key}]"),
            subsequence(seq, keys[i:], name=f"{seq.name}[{at_key}:]"))


def unit_backbone(units: Sequence[tuple[list[str], Callable]],
                  modules: dict[str, Module], name: str,
                  layer_index: dict[str, int]) -> Module:
    """Compose a backbone from topology *units* over a FLAT param/state
    namespace (Keras layer names), with a fine-tune splitter at unit
    granularity.

    `units` is a list of (param_names, apply_fn) where `apply_fn(run, h)`
    threads the activation through the unit's layers via
    `run(layer_name, h)`. A unit must be a pure function of its input
    activation — residual adds / dense concats live entirely inside one
    unit — so every unit edge is a valid frozen-prefix cache point. The
    returned Module's `splitter(fine_tune_at)` cuts at the first unit
    containing a layer with Keras index >= fine_tune_at (indices are
    monotone in creation order, so everything before it is frozen).

    `run` exposes the section's traced trees as attributes —
    `run.params`, `run.state`, `run.train` — so a unit may implement a
    lowering that SPANS layer boundaries (e.g. mobilenet's fused
    depthwise+BN+relu6 Pallas chain, which needs the BN layer's
    params/stats alongside the conv kernel) while the param/state
    namespace stays flat per-layer (pretrained loading, masks, and
    summary never see the fusion). A unit taking that path must be
    value-equivalent to the per-layer `run` composition and may only
    bypass `run` for layers whose state it provably leaves unchanged
    (frozen/eval BN returns its state untouched).
    """

    def section(lo: int, hi: int, sec_name: str, splitter=None) -> Module:
        names = [n for ns, _ in units[lo:hi] for n in ns]

        def init(rng):
            rngs = _split(rng, len(names))
            params, state = {}, {}
            for n, r in zip(names, rngs):
                v = modules[n].init(r)
                if v.params:
                    params[n] = v.params
                if v.state:
                    state[n] = v.state
            return Variables(params, state)

        def apply(params, state, x, *, train=False, rng=None):
            new_state = dict(state)

            def run(n, h):
                y, s2 = modules[n].apply(params.get(n, {}),
                                         state.get(n, {}), h,
                                         train=train, rng=None)
                if n in state:
                    new_state[n] = s2
                return y

            run.params, run.state, run.train = params, state, train
            for _, unit_fn in units[lo:hi]:
                x = unit_fn(run, x)
            return x, new_state

        return Module(init, apply, sec_name, layer_names=tuple(names),
                      splitter=splitter)

    def boundary_unit(fine_tune_at: int):
        for k, (names, _) in enumerate(units):
            if any(layer_index[n] >= fine_tune_at for n in names):
                return k if k > 0 else None
        return len(units)  # nothing live: cache everything

    def split(fine_tune_at: int):
        k = boundary_unit(fine_tune_at)
        if k is None:
            return None
        return (section(0, k, f"{name}[:{k}]"),
                section(k, len(units), f"{name}[{k}:]"))

    return section(0, len(units), name, splitter=split)


def classifier(backbone: Module, feature_dim: int, num_outputs: int,
               name: str | None = None) -> Module:
    """Backbone + GlobalAveragePooling + Dense head — the model shape every
    reference workload shares (SURVEY.md §3.5, e.g. dist_model_tf_vgg.py:
    125-129). Params = {"backbone": ..., "head": ...}.
    """
    head = dense(feature_dim, num_outputs, name="head")

    def init(rng):
        r1, r2 = _split(rng, 2)
        bb = backbone.init(r1)
        hd = head.init(r2)
        return Variables({"backbone": bb.params, "head": hd.params},
                         {"backbone": bb.state})

    def apply(params, state, x, *, train=False, rng=None):
        h, bb_state = backbone.apply(params["backbone"],
                                     state.get("backbone", {}), x,
                                     train=train, rng=rng)
        h = h.mean(axis=(1, 2))  # GlobalAveragePooling2D
        y, _ = head.apply(params["head"], {}, h, train=train)
        return y, {"backbone": bb_state}

    # Propagate the backbone's internal layer order as dotted paths so
    # ordered-tensor consumers (secure `percent` selection) see the true
    # get_weights()-style enumeration, not just the two top-level keys.
    bb_names = (tuple(f"backbone.{n}" for n in backbone.layer_names)
                if backbone.layer_names else ("backbone",))
    return Module(init, apply, name or f"{backbone.name}_classifier",
                  layer_names=bb_names + ("head",),
                  children=(("backbone", backbone), ("head", head)))


# ---------------------------------------------------------------------------
# trainability masks (replaces Keras freeze/recompile — quirk Q6)
# ---------------------------------------------------------------------------

def trainability_mask(params: Params,
                      predicate: Callable[[tuple[str, ...]], bool]):
    """Boolean pytree over `params`: True where trainable.

    `predicate` receives the path as a tuple of dict keys, e.g.
    ("backbone", "conv1", "kernel"). Feed the result to
    `train.state.freeze_where(optimizer, mask)` so frozen parameters
    receive zero updates — the explicit form of the reference's
    `base_model.trainable=False` + recompile (dist_model_tf_vgg.py:122,
    141-154). (Do NOT use bare `optax.masked`: it passes raw gradients
    through False leaves instead of zeroing them.)
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, _: predicate(tuple(p.key for p in path)), params)


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def summary(module: Module, variables: Variables | None = None, *,
            trainable_mask=None) -> str:
    """A Keras-`model.summary()`-style table: one row per layer (in
    `layer_names` order when the module records it, flat param-tree
    order otherwise) with parameter shapes and counts, plus the
    trainable/non-trainable totals when a mask is given.

    The explicit-pytree analogue of the inspection surface Keras users
    lean on (`Sequential.summary()`); purely host-side.
    """
    if variables is None:
        # abstract init: shapes/sizes without allocating a real model
        # (Variables itself is not a pytree, so trace to a (p, s) pair)
        p, s = jax.eval_shape(
            lambda rng: (lambda v: (v.params, v.state))(module.init(rng)),
            jax.random.key(0))
        variables = Variables(p, s)

    def leaf_rows(tree, mask):
        rows: dict[str, list] = {}  # layer -> [n_params, shapes, n_trainable]
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        mask_leaves = (jax.tree.leaves(mask) if mask is not None
                       else [True] * len(flat))
        for (path, leaf), trainable in zip(flat, mask_leaves,
                                           strict=True):
            keys = tuple(p.key for p in path)
            layer, var = ".".join(keys[:-1]) or keys[-1], keys[-1]
            row = rows.setdefault(layer, [0, [], 0])
            row[0] += leaf.size
            row[1].append(f"{var}{list(leaf.shape)}")
            row[2] += leaf.size if trainable else 0
        return rows

    rows = leaf_rows(variables.params, trainable_mask)
    state_rows = leaf_rows(variables.state, None)
    order = list(rows)
    if module.layer_names:
        ranked = {n: i for i, n in enumerate(module.layer_names)}
        order.sort(key=lambda l: ranked.get(l, len(ranked)))

    name_w = max([len(l) for l in order + list(state_rows)] + [5]) + 2
    lines = [f"Model: {module.name}",
             f"{'Layer':<{name_w}}{'Params':>10}  Variables"]
    total = trainable = 0
    for layer in order:
        n, shapes, n_train = rows[layer]
        total += n
        trainable += n_train
        suffix = ("" if trainable_mask is None or n_train == n
                  else "  (frozen)" if n_train == 0
                  else f"  ({n_train:,} trainable)")
        lines.append(f"{layer:<{name_w}}{n:>10,}  "
                     f"{', '.join(shapes)}{suffix}")
    state_total = 0
    for layer, (n, shapes, _) in state_rows.items():
        state_total += n
        lines.append(f"{layer:<{name_w}}{n:>10,}  "
                     f"{', '.join(shapes)}  (state)")
    lines.append(f"Total params: {total:,}")
    if trainable_mask is not None:
        lines.append(f"Trainable params: {trainable:,}")
        lines.append(f"Non-trainable params: {total - trainable:,}")
    if state_total:
        lines.append(f"State (BN statistics): {state_total:,}")
    return "\n".join(lines)


def head_only_mask(params: Params):
    """Phase-1 transfer-learning mask: only the "head" subtree trains."""
    return trainability_mask(params, lambda p: p[0] == "head")


def keras_fine_tune_mask(params: Params, index_map: dict[str, int],
                         fine_tune_at: int):
    """Phase-2 mask: head + backbone layers whose Keras layer index (from
    the model's KERAS_LAYER_INDEX map) is >= fine_tune_at — the exact
    semantics of the reference's `for layer in model.layers[:fine_tune_at]:
    layer.trainable = False` (dist_model_tf_vgg.py:144-147)."""

    def pred(path):
        if path[0] == "head":
            return True
        return index_map.get(path[1], -1) >= fine_tune_at

    return trainability_mask(params, pred)
