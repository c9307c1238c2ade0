"""Least work of the Keye cell's decode window, from shapes alone: the
numerators of its roofline shares. Every function reads the
configuration file's published keys (and `num_experts_published`, the
router's width), so it counts the same work whatever implements it.

What the mechanism saves is in `selected_rows`: a layer whose indexer
picks `sa_config.topk` positions a query reads that many rows of K and
V a slot, not the slot's whole length; what it costs is one index key a
cached position, read to each slot's length on every step."""

from __future__ import annotations

# one routed expert's weights and a layer's K/V bytes a position are
# counted from the same keys as in the Laguna cell
from benchmark.lib.moe_work import (_DTYPE_BYTES, expert_params,  # noqa: F401
                                    kv_bytes_per_position)


def attention_params(config: dict) -> int:
    """One layer's q, k, v and o projections."""
    e, d = config["hidden_size"], config["head_dim"]
    h, g = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * e * h * d + 2 * e * g * d


def indexer_params(config: dict) -> int:
    """One layer's index-query, index-key and head-weight projections."""
    sa, e = config["sa_config"], config["hidden_size"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return e * j * di + e * di * sa["indexer_num_kv_heads"] + e * j


def params_outside_experts(config: dict) -> int:
    """Matmul weights a token step multiplies by whatever the routing:
    per layer attention, indexer and router, and the output head's
    slice. The embedding is a row gather and the norms are vectors:
    neither counts."""
    e = config["hidden_size"]
    per_layer = (attention_params(config) + indexer_params(config)
                 + e * config["num_experts_published"])
    return config["num_hidden_layers"] * per_layer + e * config["vocab_size"]


def index_bytes_per_position(config: dict, engine: dict) -> int:
    """The index key(s) ONE layer caches for one position."""
    sa = config["sa_config"]
    return (sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
            * _DTYPE_BYTES[engine["cache_dtype"]])


def selected_rows(config: dict, live_tokens: float, live_slots: float) -> float:
    """Cached positions one layer's attention reads on one token step:
    `topk` a request, or its whole length while that is shorter (taken
    at the mean request's length)."""
    mean_len = live_tokens / live_slots if live_slots else 0.0
    return live_slots * min(mean_len, config["sa_config"]["topk"])


def sparse_attention_step(config: dict, engine: dict, live_tokens: float,
                          live_slots: float) -> float:
    """Least bytes the attention sublayers of ONE token step move, over
    all layers: the attention and indexer weights once (the batch shares
    them), the index keys of every live cached position, the selected
    rows of K and V, and each request's new row of all three caches."""
    item = _DTYPE_BYTES[config["param_dtype"]]
    kv = kv_bytes_per_position(config, engine)
    ix = index_bytes_per_position(config, engine)
    per_layer = ((attention_params(config) + indexer_params(config)) * item
                 + ix * live_tokens
                 + kv * selected_rows(config, live_tokens, live_slots)
                 + (kv + ix) * live_slots)
    return config["num_hidden_layers"] * per_layer


def decode_window(config: dict, engine: dict, live_tokens: float,
                  live_slots: float, touched: float) -> float:
    """Least bytes one fused decode window moves: on each of its `window`
    token steps what `sparse_attention_step` counts, the routers and the
    head's slice, and the `touched` held experts of each layer (mean
    per layer and step: an expert no live token went to is not read)."""
    item = _DTYPE_BYTES[config["param_dtype"]]
    layers, e = config["num_hidden_layers"], config["hidden_size"]
    per_step = (sparse_attention_step(config, engine, live_tokens, live_slots)
                + (layers * e * config["num_experts_published"]
                   + e * config["vocab_size"]) * item
                + layers * touched * expert_params(config) * item)
    return engine["window"] * per_step
