"""Plain reference for the `gpt2-large` configuration: GPT-2's decoder
(Radford et al. 2019; `openai-community/gpt2-large` config.json) as a
full forward pass with no cache, no batching tricks and no kernels.
Float32 at `default_matmul_precision("highest")`, independent of
`idc_models_tpu/models`; it only reads the parameter tree's names.

Block, as published: x + Attn(LN(x)), then x + MLP(LN(x)); learned
positions; `gelu_new` (the tanh approximation); final LayerNorm; a
vocabulary head. Departures, which are the configuration's own and are
listed in `benchmark/configs/gpt2-large.json`: no q/k/v bias, an output
head of its own with a bias (not tied to the embedding), LayerNorm
epsilon 1e-6."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames="num_heads")
def _block(p, x, *, num_heads: int):
    """One decoder block over x [T, E], causal."""
    with jax.default_matmul_precision("highest"):
        t, e = x.shape
        a = _layer_norm(p["ln1"], x)
        heads = lambda y: y.reshape(t, num_heads, e // num_heads).transpose(1, 0, 2)
        q, k, v = (heads(a @ p["mha"][w]) for w in ("wq", "wk", "wv"))
        s = q @ k.transpose(0, 2, 1) / math.sqrt(e // num_heads)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = (jax.nn.softmax(s, axis=-1) @ v).transpose(1, 0, 2).reshape(t, e)
        x = x + o @ p["mha"]["wo"] + p["mha"]["bo"]
        m = _gelu_new(_layer_norm(p["ln2"], x) @ p["fc1"]["kernel"]
                      + p["fc1"]["bias"])
        return x + m @ p["fc2"]["kernel"] + p["fc2"]["bias"]


@jax.jit
def _head(params, x):
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(params["ln_f"], x)
        return x @ params["head"]["kernel"] + params["head"]["bias"]


def forward(params, tokens, *, num_heads: int, num_blocks: int,
            rows: tuple[int, int]):
    """tokens int32 [T] -> logits [stop - start, vocab] of the positions
    `rows = (start, stop)`. Causal, so tokens after `stop` (padding to a
    common length) change nothing. One block program serves all layers:
    the reference compiles two small programs whatever the depth."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = (jnp.take(params["embed"], tokens, axis=0)
         + params["pos"][:tokens.shape[0]]).astype(jnp.float32)
    for i in range(num_blocks):
        x = _block(params[f"block{i}"], x, num_heads=num_heads)
    return _head(params, x[rows[0]:rows[1]])
