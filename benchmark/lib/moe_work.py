"""Least work of the Laguna cell's device programs, from shapes alone:
the numerators of its roofline shares. Every function reads the
configuration file's published keys, so it counts the same work whatever
implements it."""

from __future__ import annotations

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _layers(config: dict):
    for i in range(config["num_hidden_layers"]):
        yield (config["layer_types"][i] == "full_attention",
               config["mlp_layer_types"][i] == "sparse",
               config["num_attention_heads_per_layer"][i])


def expert_params(config: dict) -> int:
    """Weights of ONE routed expert (gate, up and down projections)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def params_outside_experts(config: dict) -> int:
    """Matmul weights a token step multiplies by whatever the routing:
    per layer q, k, v, o and the head gate, then the dense MLP or the
    router and the shared expert; and the output head's slice. The
    embedding is a row gather and the norms are vectors: neither counts."""
    e, d, g = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    total = e * config["vocab_size"]
    for _, sparse, h in _layers(config):
        total += e * h * d + 2 * e * g * d + h * d * e + e * h
        if sparse:
            total += (e * config["num_experts_published"]
                      + 3 * e * config["shared_expert_intermediate_size"])
        else:
            total += 3 * e * config["intermediate_size"]
    return total


def kv_bytes_per_position(config: dict, engine: dict) -> int:
    """Keys and values ONE layer caches for one position."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * _DTYPE_BYTES[engine["cache_dtype"]])


def decode_window(config: dict, engine: dict, live_tokens: float,
                  live_slots: float, touched: float) -> float:
    """Least bytes one fused decode window moves: on each of its `window`
    token steps every weight outside the experts once (the batch shares
    them), the `touched` held experts of each sparse layer (mean per
    layer and step: an expert no live token went to is not read), the
    cached keys and values the live requests can see (all `live_tokens`
    positions in a full layer; in a sliding layer at most
    `sliding_window` a request, taken at the mean request's length), and
    the new row of each of the `live_slots` requests in every layer."""
    item = _DTYPE_BYTES[config["param_dtype"]]
    per_pos = kv_bytes_per_position(config, engine)
    mean_len = live_tokens / live_slots if live_slots else 0.0
    seen = 0.0
    sparse_layers = 0
    for full, sparse, _ in _layers(config):
        seen += live_tokens if full else live_slots * min(
            mean_len, config["sliding_window"])
        sparse_layers += sparse
    per_step = (params_outside_experts(config) * item
                + sparse_layers * touched * expert_params(config) * item
                + per_pos * seen
                + per_pos * live_slots * config["num_hidden_layers"])
    return engine["window"] * per_step


def expert_product_bytes(config: dict, touched: float, rows: float) -> float:
    """Least bytes of ONE grouped expert product (one sparse layer, one
    batch of tokens): the weights of the `touched` held experts, and for
    each of the `rows` (token, held expert) assignments the token's
    hidden state in and the expert's output out. The product of the two
    up-projections stays on the chip."""
    item = _DTYPE_BYTES[config["param_dtype"]]
    return (touched * expert_params(config) * item
            + rows * 2 * config["hidden_size"] * item)


def expert_product_flops(config: dict, rows: float) -> float:
    """Multiply-adds x 2 of one grouped expert product over `rows`
    (token, held expert) assignments: three projections each."""
    return 2.0 * rows * expert_params(config)


def expert_product_seconds(config: dict, peaks: dict, touched: float,
                           rows: float) -> float:
    """Least seconds of one grouped product: the larger of its bytes over
    the memory roof and its operations over the compute roof."""
    return max(expert_product_bytes(config, touched, rows)
               / peaks["hbm_bytes_per_s"],
               expert_product_flops(config, rows) / peaks["bf16_flops_per_s"])
