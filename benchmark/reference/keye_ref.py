"""Plain reference of the Keye-VL-2.0-30B-A3B language model's forward
pass, for one sequence of text tokens.

Straightforward `jax.numpy` in float32 at matmul precision "highest": no
cache, no batching, no kernels, nothing imported from the system under
test. It reads the published `config.json` keys (`cfg`, as the
benchmark's configuration file holds them) and the parameter tree the
system serves, each weight upcast to float32 where it is used:

  embed [V, E]; block{i}: ln1.scale, mha.{wq, wk, wv, wo, q_norm, k_norm},
  idx.{wq [E, J*DI], wk [E, DI], ww [E, J], k_norm.{scale, bias}},
  ln2.scale, moe.{router [E, n], experts.{w_gate, w_up, w_down} [held, ..]};
  ln_f.scale; head.kernel [E, V].

`held = (first, count)` is the chip's share of each expert layer: the
router scores all of its experts and picks `num_experts_per_tok` of
them, and only the held experts' terms are added; what an absent expert
would have added is left out, here as in the system. Per layer, x_t the
hidden state at position t (D = head_dim 128, J = 16 index heads of
DI = 64, K = sa_config.topk 2048):

  a   = RMSNorm(x; ln1)
  q_h = rope(RMSNorm_D(a Wq)_h)  h < 32;   k_g = rope(RMSNorm_D(a Wk)_g),
  v_g = (a Wv)_g  g < 4                    (rotate-half, theta 1e7, all of D)
  qI_j = rope(a WIq)_j  j < J;  kI = rope(LayerNorm(a WIk));  w = a WIw
  I[t,s] = sum_j w[t,j] * relu(qI[t,j] . kI[s])          for s <= t
  S_t = the K positions s <= t with the largest I[t,s]  (all s <= t while t < K)
  o_h = softmax_{s in S_t}(q_h . k_{h//8}[s] / sqrt(D)) v_{h//8}[s]
  x1  = x + concat(o) Wo
  b   = RMSNorm(x1; ln2);  p = softmax(b Wr) over all experts;  T = top 8
  x2  = x1 + sum_{e in T, e held} (p_e / sum_T p) expert_e(b)

Departures from the published model, each stated in the configuration
file's `assumed`: text positions only (the three position ids of
`mrope_section` are equal, so the rotary is plain); per-head RMSNorm on
q and k, the indexer reading the normed state, LayerNorm and whole-width
rotary on the index key are what the config does not state and the
Qwen3-MoE block and the published DSA indexer do; the positive constant
scales of the index score change no choice and are left out; the
selection is computed as a mask over dense scores (the same function as
gathering the K rows), one block of query rows at a time (`lax.map`), so
that 24k tokens fit on a chip beside the weights.

Two choices hang on a rounding, and both can be FORCED to the system's
own, with the reference's own scores then judging the forced choice:
`picks` (the router's 8th against its 9th expert) and `select` (the
indexer's K-th against its K+1-th position). `tests/keye_ref.py` is a
byte-for-byte copy, kept equal by a test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _rotate(x, positions, theta: float):
    """x [T, heads, D] at integer `positions` [T]: rotate-half over all D."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def unpack_bits(words, n: int):
    """[.., ceil(n / 32)] uint32 -> [.., n] bool, bit b of word m being
    position 32 m + b."""
    bits = (words[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :n].astype(bool)


def pack_bits(mask: np.ndarray) -> np.ndarray:
    """[.., n] bool -> [.., ceil(n / 32)] uint32, the inverse (numpy)."""
    n = mask.shape[-1]
    pad = np.zeros(mask.shape[:-1] + (-n % 32,), bool)
    m = np.concatenate([mask, pad], axis=-1).reshape(*mask.shape[:-1], -1, 32)
    return (m.astype(np.uint32) << np.arange(32, dtype=np.uint32)).sum(
        -1, dtype=np.uint32)


def _sparse_attention(q, k, v, qi, ki, w, *, topk, block, forced, idx_dot):
    """q [T, H, D], k, v [T, G, D], index query qi [T, J, DI], index key
    ki [T, DI], head weights w [T, J] -> (o [T, H, D], deficit [T],
    count [T], swapped [T]). T is a multiple of `block`; one block of
    query rows at a time. `forced` [T, ceil(T / 32)] uint32 packed, or
    None: the positions attended replace the indexer's own choice, and
    `deficit` is how far the lowest-scoring forced position lies below
    the reference's own `topk`-th best score (0 without `forced`),
    `count` how many positions were attended, `swapped` how many of them
    are not among the reference's own choice."""
    t, h, d = q.shape
    g = k.shape[1]
    kk = min(topk, t)
    kpos = jnp.arange(t)
    blocks = lambda a: a.reshape(t // block, block, *a.shape[1:])
    args = [blocks(q.reshape(t, g, h // g, d)), blocks(qi), blocks(w),
            jnp.arange(0, t, block)]
    if forced is not None:
        args.append(blocks(forced))

    def rows(args):
        qb, qib, wb, r0 = args[:4]
        qpos = r0 + jnp.arange(block)
        causal = kpos[None, :] <= qpos[:, None]
        si = jnp.maximum(idx_dot(qib, ki), 0.0)              # [q, J, T]
        score = jnp.where(causal, jnp.einsum(
            "qjk,qj->qk", si, wb, precision="highest"), -jnp.inf)
        best, at = jax.lax.top_k(score, kk)      # ties: the lower position
        kth = best[:, -1]
        own = jnp.zeros(score.shape, bool).at[
            jnp.arange(block)[:, None], at].set(True) & causal
        if forced is None:
            use, deficit = own, jnp.zeros(block, jnp.float32)
        else:
            use = unpack_bits(args[4], t)
            lowest = jnp.min(jnp.where(use, score, jnp.inf), axis=-1)
            # fewer than topk visible: every visible position is the
            # reference's own choice, whatever it scores
            floor = jnp.where(qpos < topk, -jnp.inf, kth)
            deficit = jnp.maximum(floor - lowest, 0.0)
            deficit = jnp.where(jnp.any(use & ~causal, axis=-1), jnp.inf,
                                deficit)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k,
                       precision="highest") / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(use[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", p, v,
                       precision="highest").reshape(block, h, d)
        return (o, deficit, jnp.sum(use, axis=-1),
                jnp.sum(use & ~own, axis=-1))

    o, deficit, count, swapped = jax.lax.map(rows, tuple(args))
    return (o.reshape(t, h, d), deficit.reshape(t), count.reshape(t),
            swapped.reshape(t))


def _experts(p, b, cfg, held, picks, dot, block):
    """The routed part of a layer on b [T, E] (T a multiple of `block`):
    every held expert on every row, weighted by the router (0 where it
    was not picked). `picks` [T, k] replaces the router's own choice of
    experts where an entry is >= 0; the weights are still the router's
    scores of the experts used. Returns (y [T, E], router logits
    [T, n])."""
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


def layer(p, x, cfg: dict, held, positions, *, block, picks=None,
          select=None, dot=None, idx_dot=None, index_rotary: bool = True):
    """One block on x [T, E] (T a multiple of `block`) -> (x2, router
    logits [T, n], (deficit, count, swapped) [T] each)."""
    t = x.shape[0]
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta, sa = float(cfg["rope_theta"]), cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    a = _rms(x, p["ln1"]["scale"], eps)
    q = _rms(dot(a, p["mha"]["wq"]).reshape(t, h, d), p["mha"]["q_norm"], eps)
    k = _rms(dot(a, p["mha"]["wk"]).reshape(t, g, d), p["mha"]["k_norm"], eps)
    q, k = _rotate(q, positions, theta), _rotate(k, positions, theta)
    v = dot(a, p["mha"]["wv"]).reshape(t, g, d)
    qi = dot(a, p["idx"]["wq"]).reshape(t, j, di)
    ki = _layer_norm(dot(a, p["idx"]["wk"]), p["idx"]["k_norm"], 1e-6)
    if index_rotary:
        qi = _rotate(qi, positions, theta)
        ki = _rotate(ki[:, None, :], positions, theta)[:, 0]
    w = dot(a, p["idx"]["ww"])
    o, *account = _sparse_attention(
        q, k, v, qi, ki, w, topk=sa["topk"], block=block, forced=select,
        idx_dot=idx_dot)
    x = x + dot(o.reshape(t, h * d), p["mha"]["wo"])
    b = _rms(x, p["ln2"]["scale"], eps)
    y, logits = _experts(p["moe"], b, cfg, held, picks, dot, block)
    return x + y, logits, tuple(account)


def forward(params, tokens, cfg: dict, held, *, rows=None, block: int = 512,
            picks=None, select=None, dot=None, idx_dot=None,
            index_rotary: bool = True):
    """Logits [rows, V] float32 of the sequence `tokens` [T], the
    router's logits at every position [layers, T, n], and the indexer's
    account of the selection, three [layers, T] arrays (see
    `_sparse_attention`): deficit, count, swapped.

    `rows` = (r0, r1) restricts the logits that come back (the forward
    itself runs over the whole sequence, padded at its end to a multiple
    of `block`: a causal model's real rows do not see the padding).
    `picks` [layers, T, k] int, or None: the listed experts replace the
    router's own choice where an entry is >= 0. `select`
    [layers, T, ceil(T / 32)] uint32 (`pack_bits`), or None: the listed
    positions replace the indexer's own choice. `dot(x, w)` replaces the
    product of an activation with a weight of the tree and
    `idx_dot(qi, ki)` the product of a block of index queries
    [q, J, DI] with the index keys [T, DI] -> [q, J, T] (default:
    float32 at precision "highest"): the hooks by which a
    lower-precision product is shown to fail the comparison.
    `index_rotary=False` leaves the rotary off index query and key (a
    fault to plant). Traceable: `jax.jit` it with `cfg`, `held`, `rows`
    and `block` closed over."""
    with jax.default_matmul_precision("highest"):
        if dot is None:
            dot = lambda x, w: jnp.matmul(x, _f32(w), precision="highest")
        if idx_dot is None:
            idx_dot = lambda qi, ki: jnp.einsum("qjd,kd->qjk", qi, ki,
                                                precision="highest")
        tokens = jnp.asarray(tokens, jnp.int32)
        n_real = tokens.shape[0]
        r0, r1 = rows if rows is not None else (0, n_real)
        tokens = jnp.pad(tokens, (0, -n_real % block))
        t = tokens.shape[0]
        positions = jnp.arange(t)
        x = _f32(params["embed"][tokens])
        routers, accounts = [], []
        for i in range(cfg["num_hidden_layers"]):
            forced_picks = forced_sel = None
            if picks is not None:
                forced_picks = jnp.pad(jnp.asarray(picks[i], jnp.int32),
                                       ((0, t - n_real), (0, 0)),
                                       constant_values=-1)
            if select is not None:
                # padding rows attend to themselves alone
                words = -(-t // 32)
                sel = jnp.asarray(select[i], jnp.uint32)
                sel = jnp.pad(sel, ((0, t - n_real),
                                    (0, words - sel.shape[1])))
                pad_rows = jnp.arange(n_real, t)
                sel = sel.at[pad_rows, pad_rows // 32].set(
                    jnp.uint32(1) << (pad_rows % 32).astype(jnp.uint32))
                forced_sel = sel
            x, logits, account = layer(
                params[f"block{i}"], x, cfg, held, positions, block=block,
                picks=forced_picks, select=forced_sel, dot=dot,
                idx_dot=idx_dot, index_rotary=index_rotary)
            routers.append(logits[:n_real])
            accounts.append(jnp.stack([a[:n_real].astype(jnp.float32)
                                       for a in account]))
        out = dot(_rms(x[r0:r1], params["ln_f"]["scale"], cfg["rms_norm_eps"]),
                  params["head"]["kernel"])
        deficit, count, swapped = jnp.moveaxis(jnp.stack(accounts), 1, 0)
        return out, jnp.stack(routers), (deficit, count, swapped)
