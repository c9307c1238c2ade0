"""Model registry: name -> (builder, head-only mask, fine-tune mask,
partition rules).

Gives the CLI/configs one lookup for the reference's model zoo
(keras.applications in the reference; SURVEY.md C5/C6), and — since the
rule-based sharding layer (partition.py, ISSUE 15) — each model's
DEFAULT partition-rule set: the regex->PartitionSpec policy train,
federated, and serve all resolve placement through.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable

from jax.sharding import PartitionSpec as P

from idc_models_tpu import mesh as meshlib, partition
from idc_models_tpu.models import densenet, mobilenet, small_cnn as small_cnn_mod, vgg
from idc_models_tpu.models.core import Module

# The classifier zoo replicates by default — DP alone is fastest at the
# reference's 50x50 scale (tp.py docstring), and replicated rules are
# bit-compatible with the pre-rules layout.
REPLICATED_RULES = partition.PartitionRules.replicated()

_D, _M = meshlib.DATA_AXIS, meshlib.MODEL_AXIS

# The decoder-only LM (models/lm.py attention_lm): FSDP over "data"
# (params AND the rmsprop moments mirroring them — re.search matches
# the nu/... suffix paths), tensor parallelism over "model" in the
# Megatron orientation (qkv/fc1/head column-parallel, wo/fc2
# row-parallel), biases riding their kernel's output sharding. On a
# mesh without one of the axes the rules degrade to the other; on a
# seq-only serve mesh they degrade to replicated. Order matters: first
# match wins, the catch-all replicates LN scales/biases and the rest.
# docs/SHARDING.md walks every rule.
_LM_RULE_PAIRS = (
    (r"mha/w[qkv]$", P(_D, _M)),       # [E, E] column-parallel
    (r"mha/wo$", P(_M, _D)),           # [E, E] row-parallel
    (r"fc1/kernel$", P(_D, _M)),       # [E, mlp] column-parallel
    (r"fc1/bias$", P(_M)),             # [mlp] rides fc1's out shard
    (r"fc2/kernel$", P(_M, _D)),       # [mlp, E] row-parallel
    (r"head/kernel$", P(_D, _M)),      # [E, vocab] column-parallel
    (r"head/bias$", P(_M)),            # [vocab] rides the head shard
    (r"embed$", P(None, _D)),          # [vocab, E] FSDP on E
    (r"pos$", P(None, _D)),            # [T, E] FSDP on E
    (r".*", P()),                      # LN scale/bias, bo, fc2/bias,
    #                                    step counter: replicated
)
LM_RULES = partition.PartitionRules(_LM_RULE_PAIRS)

# The learned drafter (models/draft_lm.py) is a scaled-down
# attention_lm — same param-tree schema — so the same regex policy
# applies verbatim. It still gets its OWN named rule set: the drafter's
# placement is tuned independently of the target's (a 2-block student
# rarely wants the target's TP split; swapping its rules must not
# perturb the target), and serve/engine.py + the draft-LM checkpoint
# path resolve through this name.
DRAFT_LM_RULES = partition.PartitionRules(_LM_RULE_PAIRS)

# name -> default rule set; "lm" serves attention_lm trees (train AND
# serve resolve through it), "draft_lm" the learned drafter,
# classifier names alias their ModelSpec's rules so both lookups agree.
PARTITION_RULES: dict[str, partition.PartitionRules] = {
    "replicated": REPLICATED_RULES,
    "lm": LM_RULES,
    "draft_lm": DRAFT_LM_RULES,
}


def get_partition_rules(name: str) -> partition.PartitionRules:
    """Default partition rules for a registered model (or the "lm" /
    "replicated" rule-set names)."""
    if name in PARTITION_RULES:
        return PARTITION_RULES[name]
    if name in REGISTRY:
        return REGISTRY[name].partition_rules
    raise KeyError(
        f"no partition rules for {name!r}; have "
        f"{sorted(set(PARTITION_RULES) | set(REGISTRY))}")


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    build: Callable[..., Module]          # (num_outputs, in_channels) -> Module
    head_only_mask: Callable              # params -> bool pytree
    fine_tune_mask: Callable              # (params, fine_tune_at) -> bool pytree
    default_fine_tune_at: int
    feature_dim: int
    # Keras layer index per parameterized backbone layer (the zoo's
    # KERAS_LAYER_INDEX); consumers: fine-tune boundary lookups such as
    # the frozen-prefix feature cache. None for models without one.
    layer_index: dict[str, int] | None = None
    # the model's default regex->PartitionSpec policy (partition.py);
    # replicated for the zoo — see LM_RULES for a sharded example
    partition_rules: partition.PartitionRules = REPLICATED_RULES


def _always_trainable(params, fine_tune_at=0):
    import jax

    return jax.tree.map(lambda _: True, params)


REGISTRY: dict[str, ModelSpec] = {
    "vgg16": ModelSpec(vgg.vgg16, vgg.head_only_mask, vgg.fine_tune_mask,
                       default_fine_tune_at=15, feature_dim=512,
                       layer_index=vgg.KERAS_LAYER_INDEX),
    "mobilenet_v2": ModelSpec(mobilenet.mobilenet_v2,
                              mobilenet.head_only_mask,
                              mobilenet.fine_tune_mask,
                              default_fine_tune_at=100, feature_dim=1280,
                              layer_index=mobilenet.KERAS_LAYER_INDEX),
    "densenet201": ModelSpec(densenet.densenet201, densenet.head_only_mask,
                             densenet.fine_tune_mask,
                             default_fine_tune_at=150, feature_dim=1920,
                             layer_index=densenet.KERAS_LAYER_INDEX),
    "small_cnn": ModelSpec(
        lambda num_outputs=1, in_channels=3: small_cnn_mod.small_cnn(
            10, in_channels, num_outputs),
        _always_trainable, _always_trainable,
        default_fine_tune_at=0, feature_dim=8),
}


def get_model(name: str) -> ModelSpec:
    if name not in REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name]


# ISSUE 16: the one place defining what "fused backbone" means per
# model, so the profile verb, experiments/fused_backbone.py and
# tests/test_fused_conv.py build the same variants. For
# mobilenet the fused Pallas depthwise chain is OPT-IN (default
# "grouped" until the TPU perf gate holds — ISSUE 16 acceptance);
# for densenet the concat-free packed blocks ARE the default (parity
# is bit-exact, pinned on CPU), so its "unfused" baseline opts back
# into the concat reference.
FUSED_BUILD_KWARGS: dict[str, dict] = {
    "mobilenet_v2": {"depthwise_impl": "fused"},
    "densenet201": {"block_impl": "packed"},
}
UNFUSED_BUILD_KWARGS: dict[str, dict] = {
    "mobilenet_v2": {"depthwise_impl": "grouped"},
    "densenet201": {"block_impl": "concat"},
}
