"""Least work of the GLM-5.2 cell's device programs, from shapes alone:
the numerators of its roofline shares. Every function reads the
configuration file's published keys (and `n_routed_experts_published`,
the router's width), so it counts the same work whatever implements it.

Latent attention keeps ONE row a position and layer: the KV latent
(`kv_lora_rank`) beside the rotated key part (`qk_rope_head_dim`), read
as key and as value by every head. A decode step's attention reads
`index_topk` such rows a request and layer (fewer while the request is
shorter), and each layer that selects for itself reads one index key a
cached position."""

from __future__ import annotations

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def latent_params(config: dict) -> int:
    """One layer's latent-attention projections: q_a, q_b, kv_a, kv_b
    and o."""
    e, h = config["hidden_size"], config["num_attention_heads"]
    rq, r = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return (e * rq + rq * h * (nope + rope) + e * (r + rope)
            + r * h * (nope + v) + h * v * e)


def indexer_params(config: dict) -> int:
    """One selecting layer's index-query (from the query latent),
    index-key and head-weight projections."""
    e, rq = config["hidden_size"], config["q_lora_rank"]
    j, di = config["index_n_heads"], config["index_head_dim"]
    return rq * j * di + e * di + e * j


def expert_params(config: dict) -> int:
    """Weights of ONE routed or shared expert (gate, up and down)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _layers(config: dict):
    """(selects for itself, has experts) for each served layer."""
    for i in range(config["num_hidden_layers"]):
        yield (config["indexer_types"][i] == "full",
               config["mlp_layer_types"][i] == "sparse")


def params_outside_experts(config: dict) -> int:
    """Matmul weights a token step multiplies by whatever the routing:
    per layer the latent attention, the indexer where the layer selects,
    then the dense MLP or the router and the shared experts; and the
    output head's slice. The embedding is a row gather and the norms and
    the routers' correction biases are vectors: none counts."""
    e = config["hidden_size"]
    total = e * config["vocab_size"]
    for selects, sparse in _layers(config):
        total += latent_params(config)
        if selects:
            total += indexer_params(config)
        if sparse:
            total += (e * config["n_routed_experts_published"]
                      + config["n_shared_experts"] * expert_params(config))
        else:
            total += 3 * e * config["intermediate_size"]
    return total


def latent_row_bytes(config: dict, engine: dict) -> int:
    """The row ONE layer caches for one position: 1,152 bytes in
    bfloat16 at the published widths."""
    return ((config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _DTYPE_BYTES[engine["cache_dtype"]])


def index_bytes_per_position(config: dict, engine: dict) -> int:
    """The index key ONE selecting layer caches for one position."""
    return config["index_head_dim"] * _DTYPE_BYTES[engine["cache_dtype"]]


def decode_window(config: dict, engine: dict, live_tokens: float,
                  live_slots: float, touched: float) -> float:
    """Least bytes one fused decode window moves: on each of its `window`
    token steps every weight outside the routed experts once (the batch
    shares them), the `touched` held experts of each MoE layer (mean per
    layer and step: an expert no live token went to is not read), in
    every layer min(length, index_topk) latent rows a live request (at
    the mean request's length), in every selecting layer the index keys
    of every live cached position, and each request's new row and index
    keys."""
    item = _DTYPE_BYTES[config["param_dtype"]]
    row = latent_row_bytes(config, engine)
    ix = index_bytes_per_position(config, engine)
    mean_len = live_tokens / live_slots if live_slots else 0.0
    gathered = live_slots * min(mean_len, config["index_topk"])
    per_step = params_outside_experts(config) * item
    for selects, sparse in _layers(config):
        per_step += row * (gathered + live_slots)
        if selects:
            per_step += ix * (live_tokens + live_slots)
        if sparse:
            per_step += touched * expert_params(config) * item
    return engine["window"] * per_step
