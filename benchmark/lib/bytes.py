"""Bytes the algorithm has to move, from shapes alone: the numerator of a
bandwidth roofline share."""

from __future__ import annotations

from benchmark.lib.flops import lm_matmul_params

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kv_bytes_per_token(model: dict, engine: dict) -> int:
    """Keys and values one cached position holds across all blocks."""
    return (2 * model["num_blocks"] * model["embed_dim"]
            * _DTYPE_BYTES[engine["cache_dtype"]])


def decode_window(model: dict, engine: dict, live_tokens: float,
                  live_slots: float) -> float:
    """Least bytes one fused decode window reads and writes from HBM:
    on each of its `window` token steps every matmul weight once (the
    batch shares them), the cached keys and values of the `live_tokens`
    positions that live requests hold, and the new key and value of each
    of the `live_slots` rows. Activations stay on chip."""
    weights = lm_matmul_params(model) * _DTYPE_BYTES[model["param_dtype"]]
    per_tok = kv_bytes_per_token(model, engine)
    per_step = weights + per_tok * live_tokens + per_tok * live_slots
    return engine["window"] * per_step
