"""Causal language model over the ring: train long contexts, then SERVE
them — the model-level composition of `ring_attention` (training) and
`ring_decode` (KV-cache inference) sharing one parameter tree.

The reference has no sequence models at all (its models are the CNN
backbones, SURVEY.md §3.5), so this is beyond-parity: it exists to
close the loop the round-5 pieces opened. `attention_lm` is the
smallest honest decoder-only LM — token embedding + learned positions,
the SAME pre-LN ring-attention blocks as the classifier
(`models/attention.py::transformer_block`), final LN, per-position
vocab head — and the serving side drives the SAME parameters:
`make_lm_decoder` exposes single-token KV-cache steps (per block,
project this token's q/k/v, fold against the block's ring-sharded
cache (`ring_decode`), residual + MLP — exactly the block forward
restricted to one position) plus a ring prefill, and `Generator` is
the compiled serving object: one ring-sharded prefill dispatch over
the prompt (O(P/n) per device, same `make_ring_attention` as
training) and ONE fused `lax.scan` dispatch emitting all requested
tokens with the caches donated through the loop — compiled once per
decode configuration, process-wide, zero recompilation on reuse.

Incremental == full: teacher-forcing the decoder over a sequence
reproduces the training-path logits at every position to fp tolerance
(tests/test_lm.py gates it on the 2-D mesh, non-power-of-2 rings, and
both block engines' training weights). Because the zigzag layout is an
internal training-schedule permutation that does not change the
function (gated in test_zigzag.py), weights trained under
``layout="zigzag"`` decode identically through this (natural-order)
path — layout is a training knob, not a serving constraint.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.models import core, moe
from idc_models_tpu.models.attention import _seq_pin, transformer_block
from idc_models_tpu.observe import trace
from idc_models_tpu.ring_decode import (
    as_cache, cache_sharding, init_cache, make_chunk_ring_decode,
    make_ring_decode,
)


def attention_lm(vocab_size: int, seq_len: int, *,
                 embed_dim: int = 64, num_heads: int = 4,
                 mlp_dim: int = 128, num_blocks: int = 2,
                 mesh: Mesh | None = None,
                 block_impl: str = "jnp",
                 layout: str = "contiguous",
                 dropout_rate: float = 0.0,
                 remat: bool = False) -> core.Module:
    """Decoder-only LM: int32 tokens [B, T] -> logits [B, T, vocab].

    Causal by construction; `layout`/`block_impl`/`remat`/`mesh` behave
    exactly as on `attention_classifier` (the blocks are shared). The
    zigzag permutation, when used, moves the TOKEN ids and positions
    before embedding (per-position embed commutes with it) and the
    output logits are permuted back — training-path logits are always
    in natural order, so the loss/labels need no layout awareness."""
    from idc_models_tpu.ring_attention import from_zigzag, to_zigzag

    blocks = [transformer_block(embed_dim, num_heads, mlp_dim, mesh=mesh,
                                causal=True, block_impl=block_impl,
                                layout=layout,
                                dropout_rate=dropout_rate,
                                name=f"block{i}")
              for i in range(num_blocks)]
    ln_f = core.layer_norm(embed_dim, name="ln_f")
    head = core.dense(embed_dim, vocab_size, name="head")
    n_ring = mesh.shape[meshlib.SEQ_AXIS] if mesh is not None else 1
    zig = layout == "zigzag"
    pin = _seq_pin(mesh)

    def init(rng):
        rngs = jax.random.split(rng, num_blocks + 4)
        params = {
            "embed": 0.02 * jax.random.normal(
                rngs[0], (vocab_size, embed_dim)),
            "pos": 0.02 * jax.random.normal(rngs[1],
                                            (seq_len, embed_dim)),
        }
        for i, (blk, r) in enumerate(zip(blocks, rngs[2:2 + num_blocks])):
            params[f"block{i}"] = blk.init(r).params
        params["ln_f"] = ln_f.init(rngs[-2]).params
        params["head"] = head.init(rngs[-1]).params
        return core.Variables(params, {})

    def apply(params, state, tokens, *, train=False, rng=None):
        # the shared train step casts inputs to its compute dtype;
        # token ids must come back to int before the table gather
        tokens = tokens.astype(jnp.int32)
        pos = params["pos"]
        if zig:
            tokens = to_zigzag(tokens, n_ring)
            pos = to_zigzag(pos[None], n_ring)[0]
        h = jnp.take(params["embed"], tokens, axis=0) + pos
        h = pin(h)
        rngs = (jax.random.split(rng, num_blocks) if rng is not None
                else [None] * num_blocks)
        for i, blk in enumerate(blocks):
            def run_block(p, h, _blk=blk, _r=rngs[i]):
                return _blk.apply(p, {}, h, train=train, rng=_r)[0]

            if remat:
                run_block = jax.checkpoint(run_block)
            h = pin(run_block(params[f"block{i}"], h))
        h, _ = ln_f.apply(params["ln_f"], {}, h, train=train)
        logits, _ = head.apply(params["head"], {}, h, train=train)
        if zig:
            logits = from_zigzag(logits, n_ring)
        return logits, state

    names = (("embed", "pos")
             + tuple(f"block{i}" for i in range(num_blocks))
             + ("ln_f", "head"))
    return core.Module(init, apply, "attention_lm", layer_names=names,
                       children=tuple((f"block{i}", b)
                                      for i, b in enumerate(blocks)))


def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean cross-entropy of logits[:, :-1] predicting tokens[:, 1:] —
    the standard shifted LM objective, usable as the train step's
    loss_fn with the raw token batch as labels."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    tgt = tokens[:, 1:]
    ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


class Rotary(NamedTuple):
    """Rotary positions of one layer kind over the first `dims` of each
    head, the rest passed through: rotate-half pairing (dim i with dim
    i + dims/2), or with `interleave` adjacent pairs (dim 2i with dim
    2i + 1, DeepSeek's `rope_interleave`). `factor` > 1 turns on YaRN's
    frequency blend (`transformers`' `_compute_yarn_parameters`);
    `attention_factor` multiplies cos and sin both."""
    theta: float
    dims: int
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    interleave: bool = False


class Indexer(NamedTuple):
    """A layer's learned choice of the positions it attends: `heads`
    index queries of `dim` over ONE shared index key a position (cached
    beside K/V), scored ``sum_j w_j relu(q_j . k)``; a query attends the
    `topk` best-scoring positions at or before its own, all of them
    while there are no more than that. The index queries project the
    normed hidden state (`query` "hidden") or a latent layer's query
    latent (`query` "q_latent"); key and weights the normed state."""
    heads: int
    dim: int
    topk: int
    rotary: Rotary | None = None    # on index query and index key
    query: str = "hidden"


class Latent(NamedTuple):
    """Latent attention (DeepSeek-V3's MLA) as data. Queries pass
    through a latent of `q_rank`; keys and values come from ONE cached
    row a position: `kv_rank` latent values (normed) beside `rope`
    rotated key values that every head shares. A head's query and key
    are `nope` values from the latent beside the `rope` rotated ones;
    its value is `v` values from the latent."""
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int

    @property
    def row(self) -> int:
        """Values of the cached row: the latent and the rotated key."""
        return self.kv_rank + self.rope

    @property
    def scale(self) -> float:
        """The scores' scale, 1 / sqrt(a head's query-key width)."""
        return (self.nope + self.rope) ** -0.5


class LayerSpec(NamedTuple):
    """One block of the served model, as data."""
    heads: int                      # query heads
    kv_heads: int                   # cached heads (heads % kv_heads == 0)
    head_dim: int
    window: int | None = None       # None: full causal; W: the last W
    gate: bool = False              # per-head sigmoid gate on the output
    rotary: Rotary | None = None
    ffn: str = "gelu"               # "gelu" (biased MLP) | "swiglu" | "experts"
    experts: moe.Experts | None = None
    qk_norm: bool = False           # RMSNorm over each head of q and k
    indexer: Indexer | None = None  # None: attends every visible position
    latent: Latent | None = None    # latent attention over one row a position
    shares: int | None = None       # attends the positions layer `shares`
    #                                 selected for the same query (a
    #                                 latent layer with no indexer and no
    #                                 index cache of its own)

    @property
    def selects(self) -> bool:
        """True when the layer attends a selection of positions, its own
        indexer's or another layer's."""
        return self.indexer is not None or self.shares is not None


class ModelSpec(NamedTuple):
    """What shapes the serving programs of one model: hashable, so one
    spec maps to one compiled program set. `attention_spec` builds
    `attention_lm`'s instance; a path that cannot carry some other spec
    (paged KV, int8 KV, speculative verify, the prefix cache, slot
    migration, a sequence ring of several devices, the monolithic ring
    prefill) refuses it by name at construction."""
    embed_dim: int
    layers: tuple
    norm: str = "layernorm"         # | "rmsnorm" (scale only)
    norm_eps: float = 1e-6
    learned_pos: bool = True        # a trained position table params["pos"]
    param_dtype: str = "float32"

    @property
    def classic(self) -> bool:
        """True for `attention_lm`'s own block: what every serving path
        was written for."""
        return (self.norm == "layernorm" and self.learned_pos
                and all(l.heads == l.kv_heads and l.window is None
                        and not l.gate and l.rotary is None
                        and l.ffn == "gelu" and not l.qk_norm
                        and not l.selects and l.latent is None
                        and l.heads * l.head_dim == self.embed_dim
                        for l in self.layers))

    def require_classic(self, mechanism: str) -> None:
        if not self.classic:
            raise ValueError(
                f"{mechanism} cannot serve this layer spec: it holds one "
                f"cache class of one head count at learned positions "
                f"(attention_lm's block); window layers, grouped-query "
                f"heads, rotary positions, expert layers, latent "
                f"attention and an indexer's choice of positions run on "
                f"the contiguous chunked-prefill engine only (ROADMAP "
                f"queue B)")

    @property
    def sparse(self) -> bool:
        """True when some layer attends a selection of positions (its
        indexer's, whose keys its cache holds beside K/V, or another
        layer's): the engine prefills such a model into the reserved
        slot's own rows."""
        return any(l.selects for l in self.layers)

    def require_dense(self, program: str) -> None:
        """The serial `Generator`'s programs (`_serving_fns`) hold a
        request's OWN cache row of two arrays a layer and know no
        indexer: it refuses such a spec at its door, by name."""
        if self.sparse:
            raise ValueError(
                f"{program} serves no layer with an indexer: such a "
                f"model runs on the slot engine's window and in-place "
                f"chunk programs only (serve.LMServer, serve.SlotEngine)")

    def cache_len(self, i: int, t_max: int) -> int:
        """Rows of layer i's cache: t_max, or a window layer's ring."""
        w = self.layers[i].window
        return t_max if w is None else min(w, t_max)


def attention_spec(embed_dim: int, num_heads: int,
                   num_blocks: int) -> ModelSpec:
    """`attention_lm`'s block as a spec."""
    if embed_dim % num_heads:
        raise ValueError(f"embed_dim {embed_dim} not divisible by "
                         f"num_heads {num_heads}")
    layer = LayerSpec(num_heads, num_heads, embed_dim // num_heads)
    return ModelSpec(embed_dim, (layer,) * num_blocks)


def laguna_spec(config: dict, *, held: tuple | None = None,
                param_dtype: str = "bfloat16") -> ModelSpec:
    """The spec of a `model_type: laguna` checkpoint from its
    `config.json` keys (poolside/Laguna-S-2.1): RMSNorm, per-layer query
    head counts over shared KV heads, YaRN rotary on half of each head
    in full layers and plain rotary in sliding ones, a per-head output
    gate, a dense SwiGLU layer where `mlp_layer_types` says so and
    softmax-routed experts with one shared expert elsewhere.
    `held = (first, count)` is this chip's share of each expert layer
    (default: all `num_experts`). What `config.json` does not state, and
    this takes as read: the gate is a sigmoid of the normed hidden
    state, the router scores by softmax, the shared expert is added
    ungated, q and k are not normed."""
    for key, want in (("gating", "per-head"), ("norm_topk_prob", True),
                      ("moe_router_logit_softcapping", 0),
                      ("moe_apply_router_weight_on_input", False),
                      ("attention_bias", False)):
        if config.get(key, want) != want:
            raise ValueError(f"laguna_spec knows {key}={want!r} alone, "
                             f"the config states {config[key]!r}")
    d = config["head_dim"]
    n = config["num_experts"]
    first, count = held if held is not None else (0, n)
    if not 0 <= first <= first + count <= n:
        raise ValueError(f"held experts [{first}, {first + count}) lie "
                         f"outside the router's {n}")
    experts = moe.Experts(
        n, config["num_experts_per_tok"], first, count,
        routed_scale=float(config["moe_routed_scaling_factor"]),
        shared=config.get("shared_expert_intermediate_size", 0) > 0)

    def rotary(r):
        return Rotary(float(r["rope_theta"]),
                      int(d * r.get("partial_rotary_factor", 1)),
                      factor=float(r.get("factor", 1.0)),
                      original_max=int(r.get(
                          "original_max_position_embeddings", 0)),
                      beta_fast=float(r.get("beta_fast", 32)),
                      beta_slow=float(r.get("beta_slow", 1)),
                      attention_factor=float(r.get("attention_factor", 1.0)))

    layers = []
    for i in range(config["num_hidden_layers"]):
        full = config["layer_types"][i] == "full_attention"
        sparse = config["mlp_layer_types"][i] == "sparse"
        layers.append(LayerSpec(
            config["num_attention_heads_per_layer"][i],
            config["num_key_value_heads"], d,
            window=None if full else config["sliding_window"], gate=True,
            rotary=rotary(config["rope_parameters"][
                "full_attention" if full else "sliding_attention"]),
            ffn="experts" if sparse else "swiglu",
            experts=experts if sparse else None))
    return ModelSpec(config["hidden_size"], tuple(layers), norm="rmsnorm",
                     norm_eps=config["rms_norm_eps"], learned_pos=False,
                     param_dtype=param_dtype)


def keye_spec(config: dict, *, held: tuple | None = None,
              param_dtype: str = "bfloat16") -> ModelSpec:
    """The spec of the language model of a `model_type: KeyeVL2`
    checkpoint from its `config.json` keys (Kwai-Keye/Keye-VL-2.0-
    30B-A3B): identical layers of RMSNorm, grouped-query heads with a
    per-head RMSNorm on q and k, plain rotary over the whole head (text
    positions make `mrope_section` plain), `sa_config`'s indexer, and
    softmax-routed SwiGLU experts with no shared one. `held = (first,
    count)` is this chip's share of each expert layer (default: all
    `num_experts`). What `config.json` does not state, and this takes as
    read: q and k are normed per head, the indexer reads the normed
    hidden state, its key is LayerNormed, and index query and key are
    rotated over their whole width at the model's theta."""
    for key, want in (("norm_topk_prob", True), ("attention_bias", False),
                      ("mlp_only_layers", []), ("decoder_sparse_step", 1),
                      ("use_sliding_window", False)):
        if config.get(key, want) != want:
            raise ValueError(f"keye_spec knows {key}={want!r} alone, "
                             f"the config states {config[key]!r}")
    sa = config["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("keye_spec knows one shared index key alone")
    d, n = config["head_dim"], config["num_experts"]
    first, count = held if held is not None else (0, n)
    if not 0 <= first <= first + count <= n:
        raise ValueError(f"held experts [{first}, {first + count}) lie "
                         f"outside the router's {n}")
    theta = float(config["rope_theta"])
    layer = LayerSpec(
        config["num_attention_heads"], config["num_key_value_heads"], d,
        rotary=Rotary(theta, d), ffn="experts", qk_norm=True,
        experts=moe.Experts(n, config["num_experts_per_tok"], first, count,
                            routed_scale=1.0, shared=False),
        indexer=Indexer(sa["indexer_num_heads"], sa["indexer_head_dim"],
                        sa["topk"],
                        rotary=Rotary(theta, sa["indexer_head_dim"])))
    return ModelSpec(config["hidden_size"],
                     (layer,) * config["num_hidden_layers"], norm="rmsnorm",
                     norm_eps=config["rms_norm_eps"], learned_pos=False,
                     param_dtype=param_dtype)


def glm_spec(config: dict, *, held: tuple | None = None,
             param_dtype: str = "bfloat16") -> ModelSpec:
    """The spec of a `model_type: glm_moe_dsa` checkpoint from its
    `config.json` keys (zai-org/GLM-5.2): RMSNorm, latent attention
    (`Latent`) with interleaved rotary, a DeepSeek-V3.2 indexer on the
    layers `indexer_types` calls "full", and on the "shared" ones the
    selection of the nearest "full" layer before it; a dense SwiGLU or
    sigmoid-routed experts with a correction bias and one shared expert,
    as `mlp_layer_types` says. `held = (first, count)` is this chip's
    share of each expert layer (default: all `n_routed_experts`). What
    `config.json` does not state, and this takes as read: the indexer's
    queries read the query latent, its key and weights the normed hidden
    state, its key is LayerNormed, and its rotary covers the first
    `qk_rope_head_dim` of index query and key."""
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("attention_bias", False), ("norm_topk_prob", True),
                      ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"glm_spec knows {key}={want!r} alone, "
                             f"the config states {config[key]!r}")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"glm_spec knows rope_type='default' alone, the "
                         f"config states {rope['rope_type']!r}")
    if config["n_shared_experts"] not in (0, 1):
        raise ValueError(f"glm_spec knows at most one shared expert, the "
                         f"config states {config['n_shared_experts']}")
    lat = Latent(config["q_lora_rank"], config["kv_lora_rank"],
                 config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                 config["v_head_dim"])
    if config["qk_head_dim"] != lat.nope + lat.rope:
        raise ValueError(f"qk_head_dim {config['qk_head_dim']} is not "
                         f"qk_nope_head_dim + qk_rope_head_dim")
    n = config["n_routed_experts"]
    first, count = held if held is not None else (0, n)
    if not 0 <= first <= first + count <= n:
        raise ValueError(f"held experts [{first}, {first + count}) lie "
                         f"outside the router's {n}")
    theta = float(rope["rope_theta"])
    experts = moe.Experts(n, config["num_experts_per_tok"], first, count,
                          routed_scale=float(config["routed_scaling_factor"]),
                          shared=config["n_shared_experts"] == 1,
                          scoring="sigmoid")
    indexer = Indexer(config["index_n_heads"], config["index_head_dim"],
                      config["index_topk"],
                      rotary=Rotary(theta, lat.rope,
                                    interleave=config["indexer_rope_interleave"]),
                      query="q_latent")
    layers, source = [], None
    for i in range(config["num_hidden_layers"]):
        kind = config["indexer_types"][i]
        if kind not in ("full", "shared"):
            raise ValueError(f"glm_spec knows indexer_types 'full' and "
                             f"'shared' alone, layer {i} is {kind!r}")
        if kind == "full":
            source = i
        elif source is None:
            raise ValueError(f"layer {i} shares a selection, and no layer "
                             f"before it makes one")
        sparse = config["mlp_layer_types"][i] == "sparse"
        layers.append(LayerSpec(
            config["num_attention_heads"], 1, lat.nope + lat.rope,
            rotary=Rotary(theta, lat.rope,
                          interleave=config["rope_interleave"]),
            ffn="experts" if sparse else "swiglu",
            experts=experts if sparse else None,
            indexer=indexer if kind == "full" else None,
            latent=lat, shares=None if kind == "full" else source))
    return ModelSpec(config["hidden_size"], tuple(layers), norm="rmsnorm",
                     norm_eps=config["rms_norm_eps"], learned_pos=False,
                     param_dtype=param_dtype)


def init_params(spec: ModelSpec, vocab_size: int, rng, *,
                seq_len: int = 0, mlp_dim: int = 0,
                expert_dim: int = 0):
    """Seeded random parameters for `spec`, in the tree the serving
    programs read and in `spec.param_dtype`: projections at
    1 / sqrt(fan in), norm scales near 1, biases (where attention_lm's
    block has them) near 0. `seq_len` sizes a learned position table,
    `mlp_dim` the dense feed-forward layers, `expert_dim` each routed
    and shared expert. Traceable: jit it to make the tree in one
    device program."""
    dt = jnp.dtype(spec.param_dtype)
    e = spec.embed_dim
    keys = iter(jax.random.split(rng, 16 * len(spec.layers) + 8))
    if any(l.qk_norm or l.indexer for l in spec.layers):
        # a stream of its own for what those layers add, so that every
        # other spec draws the keys it always drew
        more = iter(jax.random.split(jax.random.fold_in(rng, 1),
                                     8 * len(spec.layers)))
    if any(l.latent or (l.experts and l.experts.scoring == "sigmoid")
           for l in spec.layers):
        # and one for latent attention and the router's correction bias
        lat = iter(jax.random.split(jax.random.fold_in(rng, 2),
                                    8 * len(spec.layers)))

    def mat(*shape, of=None):
        return (jax.random.normal(next(of or keys), shape, jnp.float32)
                / np.sqrt(shape[-2])).astype(dt)

    def near(value, n, of=None):
        return (value + 0.02 * jax.random.normal(next(of or keys), (n,),
                                                 jnp.float32)).astype(dt)

    def norm():
        p = {"scale": near(1.0, e)}
        if spec.norm == "layernorm":
            p["bias"] = near(0.0, e)
        return p

    def swiglu(width, *lead):
        return {"w_gate": mat(*lead, e, width), "w_up": mat(*lead, e, width),
                "w_down": mat(*lead, width, e)}

    params = {"embed": jax.random.normal(next(keys), (vocab_size, e),
                                         jnp.float32).astype(dt)}
    if spec.learned_pos:
        params["pos"] = (0.02 * jax.random.normal(
            next(keys), (seq_len, e), jnp.float32)).astype(dt)
    for i, l in enumerate(spec.layers):
        if l.latent is not None:
            m = l.latent
            block = {"ln1": norm(), "ln2": norm(), "mla": {
                "wq_a": mat(e, m.q_rank, of=lat),
                "q_a_norm": near(1.0, m.q_rank, of=lat),
                "wq_b": mat(m.q_rank, l.heads * (m.nope + m.rope), of=lat),
                "wkv_a": mat(e, m.row, of=lat),
                "kv_a_norm": near(1.0, m.kv_rank, of=lat),
                "wkv_b": mat(m.kv_rank, l.heads * (m.nope + m.v), of=lat),
                "wo": mat(l.heads * m.v, e, of=lat)}}
        else:
            hd = l.heads * l.head_dim
            mha = {"wq": mat(e, hd), "wk": mat(e, l.kv_heads * l.head_dim),
                   "wv": mat(e, l.kv_heads * l.head_dim), "wo": mat(hd, e)}
            if l.gate:
                mha["wg"] = mat(e, l.heads)
            block = {"ln1": norm(), "mha": mha, "ln2": norm()}
            if l.qk_norm:
                mha["q_norm"] = near(1.0, l.head_dim, of=more)
                mha["k_norm"] = near(1.0, l.head_dim, of=more)
        if l.indexer is not None:
            x = l.indexer
            q_from = l.latent.q_rank if x.query == "q_latent" else e
            block["idx"] = {
                "wq": mat(q_from, x.heads * x.dim, of=more),
                "wk": mat(e, x.dim, of=more), "ww": mat(e, x.heads, of=more),
                "k_norm": {"scale": near(1.0, x.dim, of=more),
                           "bias": near(0.0, x.dim, of=more)}}
        if l.ffn == "gelu":
            mha["bo"] = near(0.0, e)
            block["fc1"] = {"kernel": mat(e, mlp_dim),
                            "bias": near(0.0, mlp_dim)}
            block["fc2"] = {"kernel": mat(mlp_dim, e), "bias": near(0.0, e)}
        elif l.ffn == "swiglu":
            block["mlp"] = swiglu(mlp_dim)
        else:
            x = l.experts
            block["moe"] = {"router": mat(e, x.n_experts),
                            "experts": swiglu(expert_dim, x.count)}
            if x.shared:
                block["moe"]["shared"] = swiglu(expert_dim)
            if x.scoring == "sigmoid":
                # the correction bias stays float32 whatever the weights
                block["moe"]["bias"] = 0.02 * jax.random.normal(
                    next(lat), (x.n_experts,), jnp.float32)
        params[f"block{i}"] = block
    params["ln_f"] = norm()
    params["head"] = {"kernel": mat(e, vocab_size)}
    if spec.norm == "layernorm":
        params["head"]["bias"] = near(0.0, vocab_size)
    return params


class _ServeConfig(NamedTuple):
    """Everything that shapes the compiled serving programs — and
    NOTHING that doesn't (parameters are explicit arguments, prompt
    length and step count are jit shape keys). Hashable, so one config
    maps to one compiled program set for the life of the process."""
    mesh: Mesh
    spec: ModelSpec
    t_max: int
    cache_dtype: object          # np.dtype (normalized, hashable)
    block_impl: str
    temperature: float
    top_k: int | None

    # the classic instance's three numbers, for the paths that serve
    # nothing else (drafter, paged pools, slot export)
    @property
    def embed_dim(self) -> int:
        return self.spec.embed_dim

    @property
    def num_heads(self) -> int:
        return self.spec.layers[0].heads

    @property
    def num_blocks(self) -> int:
        return len(self.spec.layers)


def _place_params(params, mesh, rules=None):
    """Bind a parameter tree to the SERVING mesh: replicated by
    default, or under partition `rules` (regex->PartitionSpec,
    models/registry.py) — the tensor-parallel serving path, where
    params shard over "model" while activations and the KV ring keep
    their own (independent) axes.

    Host (numpy) trees are fine to pass in — e.g. a checkpoint straight
    from device_get/restore — and so are device trees living on a
    DIFFERENT topology (a training state replicated over the full pod,
    served on a sub-mesh): the serving programs pin activations to the
    serving mesh, so the parameters must live there too, not wherever
    training left them."""
    if rules is not None:
        # raw leaves straight into their SHARDED placements — an
        # asarray pass first would commit every param whole to one
        # device, transiently needing the replicated footprint the
        # rules path exists to avoid (put_with_sharding takes host
        # arrays directly)
        from idc_models_tpu import partition

        return partition.shard_tree(mesh, rules, params)
    sh = meshlib.replicated(mesh)
    return jax.tree.map(
        lambda a: meshlib.put_with_sharding(jnp.asarray(a), sh), params)


class _ServeFns(NamedTuple):
    init_caches: object
    step: object          # (params, caches, tok, pos) -> (logits, caches)
    prefill: object       # (params, tokens) -> (logits, caches)
    decode_loop: object   # (params, caches, logits, rng, offsets)
    #                       -> (tokens, logits, caches)
    prefill_chunk: object  # (params, caches, tokens, start, p_end)
    #                        -> (logits, caches, router picks | ())


def _serve_config(params, *, embed_dim=None, num_heads=None,
                  num_blocks=None, spec: ModelSpec | None = None, t_max,
                  mesh, cache_dtype, block_impl="jnp",
                  temperature=0.0, top_k=None) -> _ServeConfig:
    """The model comes as `attention_lm`'s three numbers or as a
    `ModelSpec`, never both."""
    if (spec is None) == (embed_dim is None):
        raise ValueError("name the model once: embed_dim / num_heads / "
                         "num_blocks (attention_lm's block) or spec=")
    if spec is None:
        # attention_lm's block serves whatever dtype the tree holds
        spec = attention_spec(embed_dim, num_heads, num_blocks)._replace(
            param_dtype=str(jnp.dtype(params["embed"].dtype)))
    elif num_heads is not None or num_blocks is not None:
        raise ValueError("spec= already states heads and blocks")
    for l in spec.layers:
        if l.heads % l.kv_heads:
            raise ValueError(f"{l.heads} query heads do not divide over "
                             f"{l.kv_heads} KV heads")
        if (l.ffn == "experts") != (l.experts is not None):
            raise ValueError("ffn='experts' comes with an Experts shape, "
                             "and nothing else does")
    for i, l in enumerate(spec.layers):
        if l.shares is not None and not (
                0 <= l.shares < i and l.indexer is None
                and l.latent is not None
                and spec.layers[l.shares].indexer is not None):
            raise ValueError(f"layer {i} shares the selection of layer "
                             f"{l.shares}: a latent layer without an "
                             f"indexer shares an earlier layer's")
    if spec.learned_pos and params["pos"].shape[0] < t_max:
        raise ValueError(
            f"cache t_max {t_max} exceeds the trained position table "
            f"({params['pos'].shape[0]}) — positions past it have no "
            f"embedding")
    if jnp.dtype(params["embed"].dtype) != jnp.dtype(spec.param_dtype):
        raise ValueError(
            f"the parameters are {jnp.dtype(params['embed'].dtype)}, the "
            f"spec states {spec.param_dtype}")
    mesh = mesh if mesh is not None else meshlib.seq_mesh(1)
    n = mesh.shape[meshlib.SEQ_AXIS]
    if t_max % n:
        raise ValueError(f"t_max {t_max} not divisible by the ring size "
                         f"{n} over mesh axis {meshlib.SEQ_AXIS!r}")
    if n > 1:
        spec.require_classic(f"a sequence ring of {n} devices")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    return _ServeConfig(mesh, spec, t_max, jnp.dtype(cache_dtype),
                        block_impl, float(temperature), top_k)


def _check_prompt(tokens, t_max: int):
    """The one prompt contract for every prefill entry point: non-empty
    int32 [B, P] with P <= t_max."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.ndim != 2 or tokens.shape[1] < 1:
        raise ValueError(f"prefill expects non-empty [B, P] tokens, "
                         f"got shape {tokens.shape}")
    if tokens.shape[1] > t_max:
        raise ValueError(f"prompt length {tokens.shape[1]} exceeds "
                         f"t_max {t_max}")
    return tokens


def prefill_bucket(p_len: int, t_max: int, n_ring: int) -> int:
    """The padded prompt length the prefill program actually runs at:
    the smallest `n_ring * 2**k` >= p_len, capped at t_max.

    Prompt length is a jit SHAPE key — an engine admitting arbitrary
    user prompt lengths would otherwise compile a fresh prefill per
    length. Bucketing maps every length onto O(log(t_max)) compiled
    shapes, and because the true length rides through the program as a
    TRACED scalar (see `_serving_fns`), two prompts in the same bucket
    share one executable bit-for-bit."""
    if not 1 <= p_len <= t_max:
        raise ValueError(f"prompt length {p_len} outside [1, {t_max}]")
    b = n_ring
    while b < p_len:
        b *= 2
    return min(b, t_max)


def prefill_buckets(t_max: int, n_ring: int) -> tuple[int, ...]:
    """Every bucket `prefill_bucket` can return — the complete compile
    set a serving engine warms up (O(log(t_max / n_ring)) shapes)."""
    out, b = [], n_ring
    while b < t_max:
        out.append(b)
        b *= 2
    out.append(t_max)
    return tuple(out)


def check_prefill_chunk(chunk: int, t_max: int) -> int:
    """The one chunk-length contract: chunks tile the cache exactly, so
    chunk k always starts at k*chunk and never hangs past t_max (the
    ragged FINAL chunk is handled by the traced true length, not by a
    different shape — one compiled chunk program serves every prompt)."""
    chunk = int(chunk)
    if not 1 <= chunk <= t_max:
        raise ValueError(f"prefill_chunk {chunk} outside [1, {t_max}]")
    if t_max % chunk:
        raise ValueError(f"prefill_chunk {chunk} must divide t_max "
                         f"{t_max} so chunk boundaries tile the cache")
    return chunk


def _pad_prompt(tokens, t_max: int, n_ring: int):
    """[B, P] -> ([B, bucket] zero-padded, true length P). Pad tokens
    embed position >= P but are masked out of the cache and, causally,
    cannot influence any real position's logits."""
    p_len = tokens.shape[1]
    bucket = prefill_bucket(p_len, t_max, n_ring)
    if bucket != p_len:
        tokens = jnp.pad(tokens, ((0, 0), (0, bucket - p_len)))
    return tokens, p_len


def _make_pick(cfg: _ServeConfig):
    """The sampling rule for one decode config: greedy argmax at
    temperature 0, else temperature softmax optionally restricted to the
    top_k most likely tokens. Module-level so the serving ENGINE
    (serve/engine.py) applies the exact same math per slot — bit parity
    with a serial `Generator` hinges on sharing this definition."""
    def pick(logits, key):
        lg = logits.astype(jnp.float32)
        if cfg.top_k is not None and cfg.top_k < lg.shape[-1]:
            kth = jax.lax.top_k(lg, cfg.top_k)[0][:, -1]
            lg = jnp.where(lg >= kth[:, None], lg, -jnp.inf)
        if cfg.temperature == 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / cfg.temperature,
                                      axis=-1).astype(jnp.int32)

    return pick


def rotary_inv_freq(rot: Rotary) -> np.ndarray:
    """The `dims / 2` rotary frequencies of one layer kind, float64.
    Plain: ``theta ** (-2i / dims)``. YaRN (`factor` > 1): each
    frequency blends the plain one with the one interpolated by
    `factor`, along a linear ramp between the dimensions that turn
    `beta_fast` and `beta_slow` times over `original_max` positions."""
    half = rot.dims // 2
    i = np.arange(half, dtype=np.float64)
    extra = rot.theta ** (-2.0 * i / rot.dims)
    if rot.factor <= 1.0:
        return extra

    def turns_dim(turns):
        return (rot.dims * np.log(rot.original_max / (turns * 2 * np.pi))
                / (2 * np.log(rot.theta)))

    low = max(np.floor(turns_dim(rot.beta_fast)), 0)
    high = min(np.ceil(turns_dim(rot.beta_slow)), rot.dims - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / rot.factor) * ramp + extra * (1.0 - ramp)


def _rope(x, pos, rot: Rotary):
    """Rotate x [..., H, D] at integer positions `pos` (broadcastable to
    x's leading axes): rotate-half (or, `rot.interleave`, adjacent
    pairs) over the first `rot.dims` of each head, in float32, the
    remaining dims passed through."""
    r, half = rot.dims, rot.dims // 2
    inv = jnp.asarray(rotary_inv_freq(rot), jnp.float32)
    ang = (jnp.broadcast_to(pos, x.shape[:-2]).astype(jnp.float32)[..., None]
           * inv)
    cos = (jnp.cos(ang) * rot.attention_factor)[..., None, :]
    sin = (jnp.sin(ang) * rot.attention_factor)[..., None, :]
    xf = x[..., :r].astype(jnp.float32)
    if rot.interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(xf.shape).astype(x.dtype)
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x[..., r:]], axis=-1) if r < x.shape[-1] \
        else out


def _norm(spec: ModelSpec, p, x):
    """The spec's normalisation over the feature axis, statistics in
    float32: LayerNorm (scale and bias) or RMSNorm (scale alone)."""
    if spec.norm == "layernorm":
        return core.layer_norm(spec.embed_dim,
                               eps=spec.norm_eps).apply(p, {}, x)[0]
    return _head_norm(x, p["scale"], spec.norm_eps)


def _head_norm(x, scale, eps):
    """RMSNorm over the last axis (one head's width), statistics in
    float32: the per-head norm of q and k."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _project_qkv(spec: ModelSpec, l: LayerSpec, p, h, seq_shape: tuple,
                 pos):
    """Pre-norm q/k/v projection of one block — THE single definition
    shared by the one-token decode forward (seq_shape=(1,)), the chunk
    prefill (seq_shape=(C,)), and the monolithic ring prefill
    (seq_shape=(P',)). A dtype/bias/reshape fix lands in every path at
    once or not at all — the bit-parity contracts between them hinge on
    this sharing. `pos` holds the tokens' positions (broadcastable to
    [B, *seq_shape]) for a layer with rotary positions. Returns
    (q [.., H, D], k, v [.., G, D], gate): `gate` is the per-head output
    gate [.., H, 1] of a gated layer, else None."""
    b = h.shape[0]
    a = _norm(spec, p["ln1"], h)
    split = lambda y, n: y.reshape(b, *seq_shape, n, l.head_dim)
    q = split(a @ p["mha"]["wq"].astype(a.dtype), l.heads)
    k = split(a @ p["mha"]["wk"].astype(a.dtype), l.kv_heads)
    v = split(a @ p["mha"]["wv"].astype(a.dtype), l.kv_heads)
    if l.qk_norm:
        q = _head_norm(q, p["mha"]["q_norm"], spec.norm_eps)
        k = _head_norm(k, p["mha"]["k_norm"], spec.norm_eps)
    if l.rotary is not None:
        q, k = _rope(q, pos, l.rotary), _rope(k, pos, l.rotary)
    gate = None
    if l.gate:
        gate = jax.nn.sigmoid(
            (a @ p["mha"]["wg"].astype(a.dtype)).astype(jnp.float32))
        gate = gate.reshape(b, *seq_shape, l.heads, 1)
    return q, k, v, gate


def _project_index(spec: ModelSpec, l: LayerSpec, p, h, seq_shape: tuple,
                   pos, c_q=None):
    """The indexer's projections of one block, from the same normed
    state q/k/v read (the index queries from the query latent `c_q`
    where the indexer says so): index queries [.., J, DI] and the one
    index key [.., 1, DI] (LayerNormed, statistics in float32), both
    rotated at `pos`, and the heads' weights [.., J] in float32. One
    definition for the decode step and the prefill chunk."""
    x = l.indexer
    b = h.shape[0]
    a = _norm(spec, p["ln1"], h)
    qi = ((a if x.query == "hidden" else c_q)
          @ p["idx"]["wq"].astype(a.dtype)).reshape(
        b, *seq_shape, x.heads, x.dim)
    ki = core.layer_norm(x.dim, eps=1e-6).apply(
        p["idx"]["k_norm"], {}, a @ p["idx"]["wk"].astype(a.dtype))[0]
    ki = ki.reshape(b, *seq_shape, 1, x.dim)
    if x.rotary is not None:
        qi, ki = _rope(qi, pos, x.rotary), _rope(ki, pos, x.rotary)
    w = (a @ p["idx"]["ww"].astype(a.dtype)).astype(jnp.float32)
    return qi, ki, w.reshape(b, *seq_shape, x.heads)


def _project_latent(spec: ModelSpec, l: LayerSpec, p, h, seq_shape: tuple,
                    pos):
    """A latent layer's down-projections, norms and rotary (scope
    `mla_latent`), one definition for the decode step and the prefill
    chunk: the query latent c_q [.., q_rank] = RMSNorm(a W_DQ), the
    heads' query parts q_nope [.., H, nope] and q_pe [.., H, rope]
    (rotated) from c_q W_UQ, and the row the cache keeps for this
    position [.., 1, kv_rank + rope]: RMSNorm of the KV latent beside
    the rotated key part that every head shares."""
    m = l.latent
    b = h.shape[0]
    a = _norm(spec, p["ln1"], h)
    c_q = _head_norm(a @ p["mla"]["wq_a"].astype(a.dtype),
                     p["mla"]["q_a_norm"], spec.norm_eps)
    q = (c_q @ p["mla"]["wq_b"].astype(a.dtype)).reshape(
        b, *seq_shape, l.heads, m.nope + m.rope)
    kv = a @ p["mla"]["wkv_a"].astype(a.dtype)
    c_kv = _head_norm(kv[..., :m.kv_rank], p["mla"]["kv_a_norm"],
                      spec.norm_eps)
    k_pe = _rope(kv[..., m.kv_rank:].reshape(b, *seq_shape, 1, m.rope), pos,
                 l.rotary)
    row = jnp.concatenate(
        [c_kv.reshape(b, *seq_shape, 1, m.kv_rank), k_pe], axis=-1)
    return c_q, q[..., :m.nope], _rope(q[..., m.nope:], pos, l.rotary), row


def _latent_attention(spec: ModelSpec, l: LayerSpec, p, h, seq_shape, pos,
                      attend, taken):
    """A latent layer's attention and residual in ABSORBED form: each
    head's nope query is taken into the latent, q_lat = q_nope W_UK^T
    [.., H, kv_rank], and beside q_pe it meets the cached rows as they
    are, one key of kv_rank + rope values that every head shares;
    the weighted sum of the rows' latent values leaves through W_UV and
    W_O. `attend(q, row, index) -> (o, *caches, account)` is the cache
    fold (it names dsa_index, dsa_select and attn_latent), `index` the
    indexer's projections, or for a layer that shares another's
    selection, that layer's account `taken`."""
    m = l.latent
    with jax.named_scope("mla_latent"):
        c_q, q_nope, q_pe, row = _project_latent(spec, l, p, h, seq_shape,
                                                 pos)
    with jax.named_scope("attn_latent"):
        w_kv = p["mla"]["wkv_b"].reshape(m.kv_rank, l.heads, m.nope + m.v)
        q_lat = jnp.einsum("...hn,rhn->...hr", q_nope,
                           w_kv[..., :m.nope].astype(q_nope.dtype),
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)
    index = taken
    if l.indexer is not None:
        with jax.named_scope("dsa_index"):
            index = _project_index(spec, l, p, h, seq_shape, pos, c_q)
    o, *caches, account = attend(q, row, index)
    with jax.named_scope("attn_latent"):
        o = jnp.einsum("...hr,rhv->...hv", o[..., :m.kv_rank],
                       w_kv[..., m.nope:].astype(o.dtype))
        h = _attn_residual({"mha": p["mla"]}, h, o)
    return h, caches, account


def _attn_residual(p, h, o, gate=None):
    """(Gated) out-projection + residual, one definition for every
    path: o [B, S, H, D] are the heads' outputs for h [B, E] (S = 1) or
    [B, S, E]; a bias is added where the tree holds one."""
    if gate is not None:
        o = (o.astype(jnp.float32) * gate).astype(o.dtype)
    o = o.reshape(*h.shape[:-1], -1)
    y = o @ p["mha"]["wo"].astype(o.dtype)
    if "bo" in p["mha"]:
        y = y + p["mha"]["bo"].astype(o.dtype)
    return h + y


def _ffn_residual(spec: ModelSpec, l: LayerSpec, p, h, live=None,
                  interpret: bool = False):
    """Pre-norm feed-forward + residual, one definition for every path:
    the biased GELU MLP, a SwiGLU MLP, or the expert layer (which also
    hands back its routing statistics over the `live` rows, [B] on
    the one-token path; None for the others; `interpret` is `mesh.pallas_interpret` of the serving
    mesh, for its kernel)."""
    a = _norm(spec, p["ln2"], h)
    if l.ffn == "gelu":
        m = jax.nn.gelu(a @ p["fc1"]["kernel"] + p["fc1"]["bias"])
        return h + (m @ p["fc2"]["kernel"] + p["fc2"]["bias"]), None
    if l.ffn == "swiglu":
        return h + moe.swiglu(p["mlp"], a), None
    y, stats = moe.expert_ffn(p["moe"], a.reshape(-1, a.shape[-1]),
                              l.experts, live, interpret=interpret)
    return h + y.reshape(a.shape), stats


def _layer_forward(cfg, l: LayerSpec, p, h, seq_shape, pos, attend,
                   live=None, taken=None):
    """One block on h [B, E] (seq_shape (1,)) or [B, C, E] ((C,)):
    projection, the cache fold `attend` (closed over the layer's
    caches), the two residuals. `attend(q, k, v) -> (o, *caches)`; for a
    layer with an indexer `attend(q, k, v, index) -> (o, *caches,
    account)`, `index` its projections (`_project_index`) and `account`
    what the fold says of its selection, which joins the layer's
    statistics. A latent layer's fold is `attend(q, row, index)`
    (`_latent_attention`), and a layer that shares another's selection
    is handed that layer's statistics as `taken`. Returns (h, caches,
    stats)."""
    spec = cfg.spec
    if l.latent is not None:
        h, caches, account = _latent_attention(spec, l, p, h, seq_shape, pos,
                                               attend, taken)
    elif l.indexer is None:
        account = None
        with jax.named_scope("attn_full" if l.window is None
                             else "attn_window"):
            q, k, v, gate = _project_qkv(spec, l, p, h, seq_shape, pos)
            o, *caches = attend(q, k, v)
            h = _attn_residual(p, h, o, gate)
    else:
        # the fold names its own scopes: dsa_index, dsa_select and,
        # like the projections around it, attn_sparse
        with jax.named_scope("attn_sparse"):
            q, k, v, gate = _project_qkv(spec, l, p, h, seq_shape, pos)
        with jax.named_scope("dsa_index"):
            index = _project_index(spec, l, p, h, seq_shape, pos)
        o, *caches, account = attend(q, k, v, index)
        with jax.named_scope("attn_sparse"):
            h = _attn_residual(p, h, o, gate)
    h, stats = _ffn_residual(spec, l, p, h, live,
                             meshlib.pallas_interpret(cfg.mesh))
    if account is not None:
        stats = {**(stats or {}), **account}
    return h, tuple(caches), stats


def sparse_window_stats(stats):
    """A decode window's account of its indexer layers, from the
    per-step records a scan stacked ([W, ...] leaves): ``dsa_selected``
    [W, layers, S, topk] the positions every slot attended (-1 where it
    saw fewer), ``dsa_share_sum`` the sum over live (step, slot) pairs
    of selected over visible positions, ``dsa_rows`` how many pairs,
    ``dsa_fold_rows`` the rows the fold selected, gathered and attended
    for, the live ones in whole groups (all three of the first such
    layer: they all select alike). {} without such layers."""
    stats = [st for st in stats if "selected" in st]
    if not stats:
        return {}
    return {"dsa_selected": jnp.stack([st["selected"] for st in stats],
                                      axis=1),
            "dsa_share_sum": jnp.sum(stats[0]["sel_share"]),
            "dsa_rows": jnp.sum(stats[0]["sel_rows"]),
            "dsa_fold_rows": jnp.sum(stats[0]["fold_rows"])}


def _final_logits(spec: ModelSpec, params, h):
    """Final norm + vocab head in float32, one definition for every
    path; a bias is added where the tree holds one."""
    h = _norm(spec, params["ln_f"], h)
    y = jnp.dot(h, params["head"]["kernel"],
                preferred_element_type=jnp.float32)
    if "bias" in params["head"]:
        y = y + params["head"]["bias"]
    return y


def _embed(spec: ModelSpec, params, tok, pos_rows):
    """Token embedding, plus the rows `pos_rows()` of the learned
    position table where the spec has one."""
    h = jnp.take(params["embed"], tok, axis=0)
    return h + pos_rows() if spec.learned_pos else h


def make_adapter_head_hook(u, v, tslot):
    """The per-tenant ADAPTER-DELTA forward hook (serve/tenancy.py) —
    the one definition the fused window AND verify programs apply at
    sampling time.

    `u [T, V, r]` / `v [T, r, V]` stack every tenant's low-rank
    logit-space adapter factors; `tslot [S]` (int32, traced VALUES not
    shapes — tenant arrival patterns compile nothing) names each
    slot's tenant. The returned hook maps base logits to effective
    pick logits:

        eff[s] = logits[s] + (logits[s] @ u[tslot[s]]) @ v[tslot[s]]

    i.e. an effective head ``W (I + U_t V_t)`` per tenant. Because the
    delta is a pure function of the BASE logits, all stored state —
    prefill outputs, the engine's per-slot logits rows, prefix-cache
    boundary snapshots — stays tenant-agnostic and shareable; only
    the token PICK sees the tenant's head. Adapter-less tenants hold
    zero rows, so their delta is exactly zero and they decode the
    base model through the same gathered program. Accepts logits of
    shape [S, V] (the window's per-step rows) or [S, K+1, V] (the
    verify's candidate distributions) — the gather broadcasts over
    any middle axes. An adapter that must touch attention/MLP
    projections cannot take this form; that is the full-checkpoint-
    per-tenant boundary (docs/MULTITENANCY.md)."""
    ug = jnp.take(u, tslot, axis=0)          # [S, V, r]
    vg = jnp.take(v, tslot, axis=0)          # [S, r, V]

    def hook(logits):
        z = jnp.einsum("s...v,svr->s...r", logits.astype(u.dtype), ug)
        d = jnp.einsum("s...r,srv->s...v", z, vg)
        return logits + d.astype(logits.dtype)

    return hook


def _token_forward(cfg: _ServeConfig, params, caches, tok, pos, fold,
                   live=None):
    """One token per row through every block — the single definition of
    the decode-time forward: embed (+position), then per block
    [pre-norm -> q/k/v projection of THIS token -> cache fold ->
    out-projection residual -> pre-norm feed-forward residual], final
    norm, vocab head. `pos` may be a scalar (serial decode: every row
    at the same position) or an int32 [B] vector (the serving engine's
    per-slot positions) — the position-table gather and the rotary
    angles broadcast either way.
    `fold(block_idx, kc, vc, q, k, v) -> (o, kc, vc)` supplies the
    cache fold (for a layer with an indexer: `fold(block_idx, kc, vc,
    ic, q, k, v, index) -> (o, kc, vc, ic, account)`, see
    `_layer_forward`), so the serial scalar-pos path and the engine's masked
    per-row path share every other op bit-for-bit. The fold contract
    is deliberately cache-layout-agnostic: the PAGED engine passes
    per-block (k_pool, v_pool) pairs and a page-table-indirect fold
    (`ring_decode.make_paged_batched_ring_decode`, with the table
    closed over) through the same signature — which is why paged token
    streams are bit-identical to contiguous ones on a 1-device mesh:
    everything outside the fold IS this one definition. Returns
    (logits, caches, stats): `stats` holds one record per expert layer
    (models/moe.py, counted over the `live` rows) and is () for a model
    without them."""
    spec = cfg.spec
    h = _embed(spec, params, tok, lambda: params["pos"][pos])   # [B, E]
    rows = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
    new_caches, stats, by_layer = [], [], {}
    for i, l in enumerate(spec.layers):
        h, cache, st = _layer_forward(
            cfg, l, params[f"block{i}"], h, (1,), rows,
            lambda *qkv, _i=i: fold(_i, *caches[_i], *qkv), live,
            by_layer.get(l.shares))
        new_caches.append(cache)
        if st is not None:
            stats.append(st)
            by_layer[i] = st
    logits = _final_logits(spec, params, h)
    return logits, tuple(new_caches), tuple(stats)


def _chunk_batch_forward(cfg: _ServeConfig, params, caches, toks, pos,
                         fold):
    """C tokens per row through every block — `_token_forward` WIDENED
    to C positions with PER-ROW start positions: the model half of the
    speculative verify program. Row b's tokens occupy global positions
    [pos[b], pos[b] + C); embedding gathers each row's slice of the
    position table, then per block [pre-norm -> q/k/v projection of the
    C tokens -> chunk cache fold -> out-projection residual -> pre-norm
    feed-forward residual], final norm, vocab head at EVERY position
    (the verify needs all C next-token distributions, not just the
    last). `fold(block_idx, kc, vc, q, k, v) -> (o [B,C,H,D], kc, vc)`
    supplies the cache fold (the batched chunk fold — contiguous or
    page-table-indirect, with liveness and positions closed over by
    the caller), so this shares every other op with
    `_token_forward`/`chunk_body` bit-for-bit — the speculative parity
    contract, paged and contiguous alike, hinges on that sharing."""
    spec = cfg.spec
    b, c = toks.shape
    idx = pos[:, None] + jnp.arange(c, dtype=jnp.int32)
    h = _embed(spec, params, toks, lambda: params["pos"][
        jnp.clip(idx, 0, params["pos"].shape[0] - 1)])
    new_caches = []
    for i, l in enumerate(spec.layers):
        h, cache, _ = _layer_forward(
            cfg, l, params[f"block{i}"], h, (c,), idx,
            lambda q, k, v, _i=i: fold(_i, *caches[_i], q, k, v))
        new_caches.append(cache)
    logits = _final_logits(spec, params, h)              # [B, C, V]
    return logits, tuple(new_caches)


def _chunk_forward(cfg: _ServeConfig, params, caches, tokens, start, p_end,
                   fold):
    """One prompt CHUNK of one request through every block: `tokens`
    [B, C] at positions [start, start + C), of which those below `p_end`
    are real (both traced). `fold` as in `_token_forward`, closed over
    whatever else it needs (the chunk's span, the batch row it writes).
    Returns (logits of the last real position [B, V], caches, the
    layers' statistics): the one definition behind the chunk program of
    a request's own cache row (`_serving_fns`) and the engine's chunk
    program that writes a slot's rows in place."""
    spec = cfg.spec
    c = tokens.shape[1]
    h = _embed(spec, params, tokens,
               lambda: lax.dynamic_slice_in_dim(params["pos"], start,
                                                c, axis=0))
    rows = (start + jnp.arange(c, dtype=jnp.int32))[None, :]
    new_caches, stats, by_layer = [], [], {}
    for i, l in enumerate(spec.layers):
        h, cache, st = _layer_forward(
            cfg, l, params[f"block{i}"], h, (c,), rows,
            lambda *qkv, _i=i: fold(_i, *caches[_i], *qkv),
            taken=by_layer.get(l.shares))
        new_caches.append(cache)
        if st is not None:
            stats.append(st)
            by_layer[i] = st
    # logits of the LAST REAL position in this chunk (p_end is
    # traced -> dynamic gather); intermediate chunks' logits are
    # discarded by the caller, the final chunk's seed decode
    h_last = lax.dynamic_slice_in_dim(h, p_end - start - 1, 1,
                                      axis=1)[:, 0]
    return _final_logits(spec, params, h_last), tuple(new_caches), stats


def chunk_picks(stats, b: int, c: int):
    """The router's picks of a chunk [expert layers, B, C, k] from the
    layers' statistics, () without expert layers."""
    picks = [st["picks"].reshape(b, c, -1) for st in stats if "picks" in st]
    return jnp.stack(picks) if picks else ()


@functools.lru_cache(maxsize=16)
def _serving_fns(cfg: _ServeConfig) -> _ServeFns:
    """The compile-once serving programs for one decode configuration.

    Every program takes the parameter tree as an EXPLICIT argument
    instead of closing over it, so the jitted executables — cached here
    by config and inside jax.jit by shape — are shared across
    `Generator` instances and repeated `generate` calls: a second
    request with the same config and shapes performs zero XLA
    recompilation (ADVICE round 5; gated by
    tests/test_lm.py::test_generator_reuses_compilation)."""
    from idc_models_tpu.ring_attention import make_ring_attention

    mesh, t_max, spec = cfg.mesh, cfg.t_max, cfg.spec
    # un-jitted decode folds: traced INTO the jitted step and the fused
    # scan below, whose top-level jit owns donation. A window layer's
    # cache wraps (position p at row p mod W), a full layer's does not.
    wraps = [l.window is not None for l in spec.layers]
    decode = {w: make_ring_decode(mesh, jit=False, wrap=w)
              for w in set(wraps)}
    chunk_fold = {w: make_chunk_ring_decode(mesh, jit=False, wrap=w)
                  for w in set(wraps)}
    pin = _seq_pin(mesh)

    def init_caches(batch: int):
        return tuple(init_cache(mesh, batch, spec.cache_len(i, t_max),
                                l.kv_heads, l.head_dim,
                                dtype=cfg.cache_dtype)
                     for i, l in enumerate(spec.layers))

    def step_body(params, caches, tok, pos):
        logits, caches, _ = _token_forward(
            cfg, params, caches, tok, pos,
            lambda i, kc, vc, q, k, v: decode[wraps[i]](kc, vc, q, k, v,
                                                        pos))
        return logits, caches

    # one dispatch per token for callers driving single steps: without
    # this, every token pays ~15 eager host-side op dispatches per
    # block around the cache fold — each a host dispatch, together
    # swamping the device's own time for the step (0.15-0.35 ms on a
    # v5 lite chip in round 4, through a runtime that no longer exists;
    # not in the ledger). Caches are donated (a serving loop only ever holds the
    # returned ones).
    step = jax.jit(step_body, donate_argnums=(1,))

    def prefill_body(params, tokens, p_len):
        # the prompt runs through the SAME ring the model trained with:
        # per device a [P/n, P/n]-tiled causal fold instead of a
        # replicated [B, H, P, P] score tensor — prefill keeps the
        # O(T/n) property the ring cache exists for. `tokens` arrives
        # padded to a prefill BUCKET (`prefill_bucket`: n_ring * 2**k,
        # capped at t_max) and `p_len` — the TRUE prompt length — is a
        # traced scalar, so every prompt length in a bucket runs the
        # same executable: prompt length stops being a compile key.
        # Causality makes the padding exact (pad positions cannot
        # influence real ones) and the pad K/V is masked out of the
        # cache below. The training ring knows attention_lm's block
        # alone, so this program serves the classic spec only.
        spec.require_classic("the monolithic ring prefill "
                             "(prefill_chunk=None)")
        ring = make_ring_attention(mesh, causal=True,
                                   block_impl=cfg.block_impl)
        b, p_pad = tokens.shape
        h = (jnp.take(params["embed"], tokens, axis=0)
             + params["pos"][:p_pad])                    # [B, P', E]
        h = pin(h)
        kvs = []
        for i, l in enumerate(spec.layers):
            p = params[f"block{i}"]
            q, k, v, _ = _project_qkv(spec, l, p, h, (p_pad,), None)
            o = ring(q, k, v)
            h = pin(_attn_residual(p, h, o))
            h = pin(_ffn_residual(spec, l, p, h)[0])
            kvs.append((k, v))
        # last REAL position's activations — p_len is traced, so this is
        # a dynamic gather, not a static index
        h_last = lax.dynamic_slice_in_dim(h, p_len - 1, 1, axis=1)[:, 0]
        logits = _final_logits(spec, params, h_last)
        sh = cache_sharding(mesh)
        keep = (jnp.arange(p_pad) < p_len)[None, :, None, None]

        def to_cache(x):                 # K/V -> fresh ring cache slot
            # zero pad positions (traced mask): decode's visibility
            # masking relies on slots past the prompt staying zero
            x = as_cache(jnp.where(keep, x, 0), t_max, cfg.cache_dtype)
            return lax.with_sharding_constraint(x, sh)

        return logits, tuple((to_cache(k), to_cache(v)) for k, v in kvs)

    prefill = jax.jit(prefill_body)

    def chunk_body(params, caches, tokens, start, p_end):
        # one prompt CHUNK through every block, consuming and extending
        # an existing ring cache: the admission-path complement of the
        # monolithic `prefill_body`. `tokens` is [B, C] at fixed C (the
        # chunk length is a shape key; ONE length -> one executable);
        # `start` is the chunk's first global position and `p_end` the
        # prompt's true end within this chunk (both traced), so the
        # ragged final chunk runs the same program. Structure per block
        # mirrors `_token_forward` widened to C positions, with the
        # chunk fold (append + per-query causal attend over the whole
        # cache + ring merge) in place of the one-token fold. The third
        # result is () or, for a model with expert layers, the router's
        # picks at the chunk's positions, [expert layers, B, C, k].
        b, c = tokens.shape
        logits, new_caches, stats = _chunk_forward(
            cfg, params, caches, tokens, start, p_end,
            lambda i, kc, vc, q, k, v: chunk_fold[wraps[i]](
                kc, vc, q, k, v, start, p_end))
        sh = cache_sharding(mesh)
        # pin the outgoing caches to the canonical sharding spelling so
        # chunk -> chunk -> insert chains reuse one jit cache entry per
        # program (same discipline as the engine's pin_state)
        new_caches = tuple(
            tuple(lax.with_sharding_constraint(c_, sh) for c_ in cache)
            for cache in new_caches)
        return logits, new_caches, chunk_picks(stats, b, c)

    prefill_chunk = jax.jit(chunk_body, donate_argnums=(1,))

    pick = _make_pick(cfg)

    def decode_body(params, caches, logits, rng, offsets):
        # the WHOLE decode of len(offsets) tokens is one device
        # program: sample -> embed -> blocks -> ring cache append ->
        # logits, rolled by lax.scan. One host dispatch total, vs one
        # (or more) per token in a host loop — the per-token
        # dispatch overhead is amortized over the run. The
        # final carry logits correspond to the last sampled token, so
        # chained windows continue exactly where this one stopped.
        def body(carry, off):
            caches, logits, rng = carry
            rng, sub = jax.random.split(rng)
            tok = pick(logits, sub)
            logits, caches = step_body(params, caches, tok, off)
            return (caches, logits, rng), tok

        (caches, logits, _), toks = lax.scan(
            body, (caches, logits, rng), offsets)
        return jnp.moveaxis(toks, 0, 1), logits, caches

    decode_loop = jax.jit(decode_body, donate_argnums=(1,))

    return _ServeFns(init_caches, step, prefill, decode_loop,
                     prefill_chunk)


def make_lm_decoder(params, *, embed_dim: int, num_heads: int,
                    num_blocks: int, t_max: int,
                    mesh: Mesh | None = None,
                    cache_dtype=jnp.bfloat16, block_impl: str = "jnp"):
    """Serving loop for an `attention_lm` parameter tree.

    Returns ``(init_caches, step, prefill_tokens)``:

    - ``init_caches(batch) -> caches`` — one ring-sharded (k, v) cache
      per block (`ring_decode.init_cache`; t_max bounds the context).
    - ``step(caches, tok, pos) -> (logits, caches)`` — tok int32 [B],
      pos the global position: embeds the token, runs every block's
      single-position forward (q/k/v projections of THIS token, the
      block's cache fold, out-projection, residual, MLP), and returns
      the next-token logits [B, vocab].
    - ``prefill_tokens(tokens) -> (logits, caches)`` — the whole prompt
      [B, P] in ONE jitted pass THROUGH THE RING
      (`make_ring_attention` on this mesh, `block_impl` selectable):
      per block a causal ring fold over the seq-sharded prompt — O(P/n)
      score memory per device, never a replicated [B, H, P, P] tensor —
      with the block's K/V placed straight into a fresh ring cache
      (`ring_decode` layout, built in-jit under `cache_sharding`),
      returning the LAST position's logits. Equal to feeding the prompt
      through `step` token by token to fp tolerance, at batch speed
      instead of P dispatches; prompts not divisible by the ring are
      end-padded internally (causal ⇒ exact).

    The compiled programs come from a process-wide cache keyed on the
    decode configuration (`_serving_fns`), with the parameter tree an
    explicit argument — building a second decoder for the same config
    recompiles NOTHING. The per-position math reuses the very parameter
    tree training produced — no export step, no weight transform.
    Dropout is inference-off by construction (decode is eval)."""
    cfg = _serve_config(params, embed_dim=embed_dim,
                        num_heads=num_heads, num_blocks=num_blocks,
                        t_max=t_max, mesh=mesh, cache_dtype=cache_dtype,
                        block_impl=block_impl)
    fns = _serving_fns(cfg)
    params = _place_params(params, cfg.mesh)

    n_ring = cfg.mesh.shape[meshlib.SEQ_AXIS]

    def step(caches, tok, pos):
        return fns.step(params, caches, tok, pos)

    def prefill_tokens(tokens):
        padded, p_len = _pad_prompt(_check_prompt(tokens, t_max),
                                    t_max, n_ring)
        return fns.prefill(params, padded, np.int32(p_len))

    return fns.init_caches, step, prefill_tokens


def chunked_prefill(fns: _ServeFns, params, tokens: np.ndarray,
                    chunk: int, caches=None, start: int = 0):
    """Drive the chunk program over `tokens[:, start:]`: ceil((P-start)/
    chunk) dispatches at ONE compiled shape, each consuming the previous
    chunk's caches (donated) and extending them in place. `caches=None`
    starts from fresh zeroed ring caches; passing caches + a chunk-
    aligned `start` resumes from a prefix snapshot (the prefix-cache hit
    path). Returns (last-real-position logits, caches) — bit-identical
    whether the prefix came from a snapshot or was recomputed, because
    both run the same executables over the same values."""
    b, p_len = tokens.shape
    if start % chunk or not 0 <= start < p_len:
        raise ValueError(f"chunk resume start {start} must be a chunk "
                         f"multiple inside the prompt (P={p_len})")
    if caches is None:
        caches = fns.init_caches(b)
    logits = None
    c0 = start
    while c0 < p_len:
        end = min(c0 + chunk, p_len)
        padded = np.zeros((b, chunk), np.int32)
        padded[:, :end - c0] = tokens[:, c0:end]
        logits, caches, _ = fns.prefill_chunk(
            params, caches, padded, np.int32(c0), np.int32(end))
        c0 += chunk
    return logits, caches


class Generator:
    """Reusable compiled serving path: ring prefill + fused scan decode.

    Build ONCE per parameter tree and decode configuration, then serve
    repeated requests: ``gen(prompt, steps, rng=...) -> [B, P + steps]``
    runs the whole generation in two device dispatches — one ring
    prefill over the prompt, one `lax.scan` emitting all `steps` tokens
    (embed → blocks → ring cache append → logits → temperature/top_k
    sample entirely on device, caches donated through the scan).

    The underlying XLA programs live in a process-wide cache keyed on
    the decode configuration with parameters passed explicitly, so a
    second `Generator` (fresh checkpoint, same shapes) or a repeated
    call reuses the compiled executables outright — zero recompilation
    (gated by test). `temperature=0` (default) is greedy argmax;
    `temperature > 0` samples from softmax(logits / temperature)
    (requires `rng` per call), optionally restricted to the `top_k`
    most likely tokens.

    Bounds contract: the Generator owns `pos` — `__call__`/`decode`
    reject any request past `t_max` BEFORE dispatch, because inside the
    fused scan positions are traced and an out-of-range append would
    otherwise be silently dropped (`ring_decode` can only guard
    concrete positions)."""

    def __init__(self, params, *, embed_dim: int | None = None,
                 num_heads: int | None = None,
                 num_blocks: int | None = None, t_max: int,
                 mesh: Mesh | None = None,
                 cache_dtype=jnp.bfloat16, block_impl: str = "jnp",
                 temperature: float = 0.0, top_k: int | None = None,
                 prefill_chunk: int | None = None,
                 partition_rules=None, spec: ModelSpec | None = None):
        # the model is attention_lm's three numbers OR a ModelSpec
        self._cfg = _serve_config(
            params, embed_dim=embed_dim, num_heads=num_heads,
            num_blocks=num_blocks, spec=spec, t_max=t_max, mesh=mesh,
            cache_dtype=cache_dtype, block_impl=block_impl,
            temperature=temperature, top_k=top_k)
        if prefill_chunk is None:
            self._cfg.spec.require_classic("the monolithic ring prefill "
                                           "(prefill_chunk=None)")
        self._cfg.spec.require_dense("the serial Generator")
        self._fns = _serving_fns(self._cfg)
        # partition_rules shard the params over the mesh's weight axes
        # ("model"/"data" — registry.LM_RULES) while the KV caches keep
        # their seq-ring layout: params and KV shard INDEPENDENTLY
        self._params = _place_params(params, self._cfg.mesh,
                                     rules=partition_rules)
        self.t_max = t_max
        self.temperature = float(temperature)
        # chunked prefill: the prompt runs through the chunk program C
        # tokens at a time instead of one monolithic bucketed dispatch.
        # None (default) keeps the historical single-dispatch path
        # bit-for-bit; an int selects the Sarathi-style path the serving
        # ENGINE uses, so engine-vs-serial parity can be asserted with
        # both sides prefilling identically.
        self.prefill_chunk = (None if prefill_chunk is None
                              else check_prefill_chunk(prefill_chunk,
                                                       t_max))

    def init_caches(self, batch: int):
        """Fresh zeroed ring caches (one (k, v) pair per block)."""
        return self._fns.init_caches(batch)

    def prefill(self, prompt):
        """Prompt [B, P] -> (last-position logits [B, vocab], caches).

        Default (`prefill_chunk=None`): one ring-sharded pass (O(P/n)
        per device), prompts padded to a prefill bucket
        (`prefill_bucket`) with the true length traced, so distinct
        prompt lengths share compiled programs.

        With `prefill_chunk=C`: ceil(P/C) chunk-program dispatches, each
        extending the same ring caches — the path a chunked-admission
        serving engine runs, exposed here so serial reference outputs
        can be produced through the IDENTICAL programs."""
        if self.prefill_chunk is None:
            n_ring = self._cfg.mesh.shape[meshlib.SEQ_AXIS]
            padded, p_len = _pad_prompt(_check_prompt(prompt, self.t_max),
                                        self.t_max, n_ring)
            with trace.span("lm.prefill", p_len=p_len,
                            bucket=padded.shape[1]):
                return self._fns.prefill(self._params, padded,
                                         np.int32(p_len))
        tokens = np.asarray(_check_prompt(prompt, self.t_max))
        with trace.span("lm.prefill", p_len=tokens.shape[1],
                        chunk=self.prefill_chunk):
            return chunked_prefill(self._fns, self._params,
                                   tokens, self.prefill_chunk)

    def decode(self, caches, logits, pos0: int, steps: int, *, rng=None):
        """Emit `steps` tokens in ONE dispatch from (caches, logits) at
        global position `pos0` (the position the next sampled token
        occupies). Returns ``(tokens [B, steps], logits, caches)`` —
        the logits/caches continue a chained window exactly. Donates
        `caches`."""
        if steps < 1:
            raise ValueError(f"decode needs steps >= 1, got {steps}")
        if pos0 < 0:
            raise ValueError(f"decode pos {pos0} must be >= 0 — inside "
                             f"the fused scan a negative append matches "
                             f"no owner shard and would be silently "
                             f"dropped")
        if pos0 + steps > self.t_max:
            raise ValueError(f"decode at pos {pos0} + steps {steps} "
                             f"exceeds t_max {self.t_max} — the cache "
                             f"cannot grow at decode time")
        if self.temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng "
                             "key")
        if rng is None:
            rng = jax.random.key(0)      # greedy never consumes it
        offsets = jnp.arange(pos0, pos0 + steps, dtype=jnp.int32)
        # span covers the fused-scan DISPATCH (decode is async; the
        # caller's token fetch is the execution fence)
        with trace.span("lm.decode", pos0=pos0, steps=steps):
            return self._fns.decode_loop(self._params, caches, logits,
                                         rng, offsets)

    def __call__(self, prompt, steps: int, *, rng=None):
        prompt = jnp.asarray(prompt, jnp.int32)
        p_len = prompt.shape[1] if prompt.ndim == 2 else 0
        if steps < 1 or p_len < 1:
            raise ValueError(f"generate needs a non-empty prompt and "
                             f"steps >= 1, got prompt length {p_len}, "
                             f"steps {steps}")
        if p_len + steps > self.t_max:
            raise ValueError(f"prompt {p_len} + steps {steps} exceeds "
                             f"t_max {self.t_max}")
        if self.temperature > 0.0 and rng is None:
            # before the prefill dispatch: a 16k-token prompt must not
            # compile and run just to throw away the work on this
            raise ValueError("sampling (temperature > 0) needs an rng "
                             "key")
        logits, caches = self.prefill(prompt)
        toks, _, _ = self.decode(caches, logits, p_len, steps, rng=rng)
        return jnp.concatenate([prompt, toks], axis=1)

    def cache_sizes(self) -> dict:
        """Per-program jit-cache entry counts — observability for the
        zero-recompilation contract (a second same-shape call must not
        grow any of these)."""
        return {"step": self._fns.step._cache_size(),
                "prefill": self._fns.prefill._cache_size(),
                "prefill_chunk": self._fns.prefill_chunk._cache_size(),
                "decode_loop": self._fns.decode_loop._cache_size()}

    def program_costs(self, *, batch: int = 1, steps: int = 8) -> dict:
        """Cost/memory accounts of the serial serving programs
        (observe/profile.py ProgramCost): the full-bucket ring prefill
        and the fused `steps`-token decode scan. Lowers ACCOUNTING
        copies (suppressed from the compile watchdog — lowering
        neither executes nor donates) and registers them in the
        process PROGRAMS table under ``lm.prefill`` / ``lm.decode``."""
        from idc_models_tpu.observe import profile as prof

        vocab = self._params["embed"].shape[0]
        with prof.compiling(None):
            toks = np.zeros((batch, self.t_max), np.int32)
            prefill = prof.register_program(
                "lm.prefill",
                self._fns.prefill.lower(self._params, toks,
                                        np.int32(self.t_max)).compile())
            caches = self._fns.init_caches(batch)
            logits = jnp.zeros((batch, vocab), jnp.float32)
            offsets = jnp.arange(0, steps, dtype=jnp.int32)
            decode = prof.register_program(
                "lm.decode",
                self._fns.decode_loop.lower(
                    self._params, caches, logits, jax.random.key(0),
                    offsets).compile())
        return {"lm.prefill": prefill, "lm.decode": decode}


def generate(params, prompt, steps: int, *, embed_dim: int,
             num_heads: int, num_blocks: int, t_max: int,
             mesh: Mesh | None = None, cache_dtype=jnp.bfloat16,
             temperature: float = 0.0, top_k: int | None = None,
             rng=None, block_impl: str = "jnp"):
    """One-shot convenience around `Generator`: one-pass ring prefill,
    then `steps` tokens in a single fused dispatch. `temperature=0`
    (default) is greedy argmax; `temperature > 0` samples from
    softmax(logits / temperature) (requires `rng`), optionally
    restricted to the `top_k` most likely tokens. Returns int32
    [B, P + steps] (prompt included).

    Repeated calls are cheap: the compiled programs are cached
    process-wide per decode config (see `_serving_fns`), so only the
    first call with a given config + shape pays XLA compilation. Hot
    serving loops should still hold a `Generator` to skip the per-call
    validation and tree re-asserting."""
    gen = Generator(params, embed_dim=embed_dim, num_heads=num_heads,
                    num_blocks=num_blocks, t_max=t_max, mesh=mesh,
                    cache_dtype=cache_dtype, block_impl=block_impl,
                    temperature=temperature, top_k=top_k)
    return gen(prompt, steps, rng=rng)
