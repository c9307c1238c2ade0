"""Plain reference of the Laguna-S-2.1 forward pass, for one sequence.

Straightforward `jax.numpy` in float32 at matmul precision "highest": no
cache, no batching, no kernels, nothing imported from the system under
test. It reads the published `config.json` keys (`cfg`, as the
benchmark's configuration file holds them) and the parameter tree the
system serves, each weight upcast to float32 where it is used (a whole
float32 copy of the tree would not fit beside the weights themselves):

  embed [V, E]; block{i}: ln1.scale, mha.{wq, wk, wv, wo, wg}, ln2.scale,
  and mlp.{w_gate, w_up, w_down} (a dense layer) or moe.{router,
  experts.{w_gate, w_up, w_down} [held, ..], shared.{...}}; ln_f.scale;
  head.kernel [E, V].

`held = (first, count)` is the chip's share of each expert layer: the
router scores all of its experts (the router weight's width) and picks
`num_experts_per_tok` of them, and only the held experts' terms are
added; what an absent expert would have added is left out, here as in
the system. Per layer (x a token's hidden state at position p):

  a  = RMSNorm(x; ln1);  q = a Wq (H_l heads of D), k = a Wk, v = a Wv (G heads)
  rotary on q, k at p: rotate-half over the first r dims of each head
      (sliding layers: r = D, theta 10000; full layers: r = D/2, YaRN,
      cos and sin times attention_factor)
  head h attends with KV head h // (H_l / G), scores q.k / sqrt(D), causal;
      a sliding layer also masks keys at p_key <= p - sliding_window
  g  = sigmoid(a Wg) (one gate a head); x1 = x + concat(g_h * head_h) Wo
  b  = RMSNorm(x1; ln2)
  dense layer:   x2 = x1 + (silu(b W_gate) * (b W_up)) W_down
  sparse layer:  s = softmax(b Wr) over all experts; T = the k largest;
      w_e = routed_scaling * s_e / sum_{j in T} s_j;
      x2 = x1 + shared(b) + sum_{e in T, e held} w_e * expert_e(b)

Long sequences are computed in blocks of `block` query rows (`lax.map`),
so that an 8k-token check fits on a chip beside the weights alone. `tests/laguna_ref.py`
is a byte-for-byte copy, kept equal by a test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rotary_inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    """Frequencies of one `rope_parameters` entry, as `transformers`
    computes them ("default" and "yarn")."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    i = np.arange(0, r, 2, dtype=np.float64)
    extra = 1.0 / rope["rope_theta"] ** (i / r)
    if rope["rope_type"] == "default":
        return extra
    inter = extra / rope["factor"]

    def correction_dim(rotations):
        return (r * math.log(rope["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _rotate(x, positions, rope: dict):
    """x [T, heads, D] at integer `positions` [T]."""
    inv = rotary_inv_freq(rope, x.shape[-1])
    r = 2 * len(inv)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    scale = rope.get("attention_factor", 1.0)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :] * scale
    xr = x[..., :r]
    half = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], axis=-1)
    return jnp.concatenate([xr * cos + half * sin, x[..., r:]], axis=-1)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def _swiglu(p, x, dot):
    return dot(jax.nn.silu(dot(x, p["w_gate"])) * dot(x, p["w_up"]),
               p["w_down"])


def _attention(q, k, v, *, window, block):
    """q [T, H, D], k, v [T, G, D] -> [T, H, D]; causal, and with
    `window` W only the keys at positions (p - W, p]. T is a multiple
    of `block`; one block of query rows at a time."""
    t, h, d = q.shape
    g = k.shape[1]
    qg = q.reshape(t // block, block, g, h // g, d)
    kpos = jnp.arange(t)

    def rows(args):
        qb, r0 = args
        qpos = r0 + jnp.arange(block)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k,
                       precision="highest") / math.sqrt(d)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v,
                          precision="highest").reshape(block, h, d)

    return jax.lax.map(rows, (qg, jnp.arange(0, t, block))).reshape(t, h, d)


def _experts(p, b, cfg, held, picks, dot, block):
    """The routed part of a sparse layer on b [T, E] (T a multiple of
    `block`): every held expert on every row, weighted by the router (0
    where it was not picked). `picks` [T, k] replaces the router's own
    choice of experts where an entry is >= 0; the weights are still the
    router's scores of the experts used. Returns (y [T, E], router
    logits [T, n])."""
    first, count = held
    k = cfg["num_experts_per_tok"]
    logits = dot(b, p["router"])
    s = jax.nn.softmax(logits, axis=-1)
    own = jax.lax.top_k(s, k)[1]
    if picks is not None:
        own = jnp.where(picks >= 0, picks, own)
    chosen = jnp.take_along_axis(s, own, axis=-1)                # [T, k]
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    chosen = chosen * cfg["moe_routed_scaling_factor"]
    local = own - first
    w = jnp.zeros((b.shape[0], count + 1), jnp.float32)
    w = w.at[jnp.arange(b.shape[0])[:, None],
             jnp.where((local >= 0) & (local < count), local, count)].add(chosen)
    t, e = b.shape
    n = math.gcd(count, 16)            # so many held experts at a time
    some = lambda m: m.reshape(count // n, n, *m.shape[1:])
    w = jnp.moveaxis(w[:, :count].reshape(t // block, block, count // n, n),
                     2, 0)                                # [groups, blocks, block, n]
    xs = b.reshape(t // block, block, e)

    def group(args):
        w_gate, w_up, w_down, wg = (_f32(a) for a in args)

        def rows(xw):
            x, wr = xw
            gate = jnp.einsum("te,hef->thf", x, w_gate, precision="highest")
            up = jnp.einsum("te,hef->thf", x, w_up, precision="highest")
            each = jnp.einsum("thf,hfe->the", jax.nn.silu(gate) * up, w_down,
                              precision="highest")
            return jnp.einsum("the,th->te", each, wr, precision="highest")

        return jax.lax.map(rows, (xs, wg))

    ex = p["experts"]
    y = jax.lax.map(group, (some(ex["w_gate"]), some(ex["w_up"]),
                            some(ex["w_down"]), w))
    return jnp.sum(y, axis=0).reshape(t, e), logits


def forward(params, tokens, cfg: dict, held, *, rows=None, block: int = 512,
            picks=None, dot=None):
    """Logits [rows, V] float32 of the sequence `tokens` [T], and the
    router's logits at every position, [sparse layers, T, n].

    `rows` = (r0, r1) restricts the logits that come back (the forward
    itself runs over the whole sequence, padded at its end to a multiple
    of `block`: a causal model's real rows do not see the padding).
    `picks` [sparse layers, T, k] int, or None: the listed experts
    replace the router's own choice where an entry is >= 0 (see the
    module text of whoever calls this for when that is allowed).
    `dot(x, w)` replaces the product of an activation with a weight of
    the tree (default: the weight upcast, float32 at precision
    "highest"): the hook by which a lower-precision product is shown to
    fail the comparison. Traceable: `jax.jit` it with `cfg`, `held`,
    `rows` and `block` closed over."""
    with jax.default_matmul_precision("highest"):
        if dot is None:
            dot = lambda x, w: jnp.matmul(x, _f32(w), precision="highest")
        tokens = jnp.asarray(tokens, jnp.int32)
        n_real = tokens.shape[0]
        r0, r1 = rows if rows is not None else (0, n_real)
        tokens = jnp.pad(tokens, (0, -n_real % block))
        t = tokens.shape[0]
        eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
        g = cfg["num_key_value_heads"]
        positions = jnp.arange(t)
        x = _f32(params["embed"][tokens])
        routers, sparse = [], 0
        for i in range(cfg["num_hidden_layers"]):
            p = params[f"block{i}"]
            full = cfg["layer_types"][i] == "full_attention"
            rope = cfg["rope_parameters"][
                "full_attention" if full else "sliding_attention"]
            h = cfg["num_attention_heads_per_layer"][i]
            a = _rms(x, p["ln1"]["scale"], eps)
            q = _rotate(dot(a, p["mha"]["wq"]).reshape(t, h, d), positions, rope)
            k = _rotate(dot(a, p["mha"]["wk"]).reshape(t, g, d), positions, rope)
            v = dot(a, p["mha"]["wv"]).reshape(t, g, d)
            o = _attention(q, k, v, block=block,
                           window=None if full else cfg["sliding_window"])
            gate = jax.nn.sigmoid(dot(a, p["mha"]["wg"]))          # [T, H]
            x = x + dot((o * gate[:, :, None]).reshape(t, h * d),
                        p["mha"]["wo"])
            b = _rms(x, p["ln2"]["scale"], eps)
            if cfg["mlp_layer_types"][i] == "dense":
                x = x + _swiglu(p["mlp"], b, dot)
                continue
            forced = None
            if picks is not None:
                forced = jnp.pad(jnp.asarray(picks[sparse], jnp.int32),
                                 ((0, t - n_real), (0, 0)), constant_values=-1)
            y, logits = _experts(p["moe"], b, cfg, held, forced, dot, block)
            x = x + _swiglu(p["moe"]["shared"], b, dot) + y
            routers.append(logits[:n_real])
            sparse += 1
        out = dot(_rms(x[r0:r1], params["ln_f"]["scale"], eps),
                  params["head"]["kernel"])
        return out, (jnp.stack(routers) if routers else None)
