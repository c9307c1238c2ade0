"""Plain reference of the GLM-5.2 language model's forward pass (model
type `glm_moe_dsa`), for one sequence of text tokens.

Straightforward `jax.numpy` in float32 at matmul precision "highest": no
cache, no batching, no kernels, nothing imported from the system under
test. It reads the published `config.json` keys (`cfg`, as the
benchmark's configuration file holds them) and the parameter tree the
system serves, each weight upcast to float32 where it is used:

  embed [V, E]; block{i}: ln1.scale, ln2.scale,
  mla.{wq_a [E, Rq], q_a_norm, wq_b [Rq, H*(N+P)], wkv_a [E, R+P],
       kv_a_norm, wkv_b [R, H*(N+V')], wo [H*V', E]},
  idx.{wq [Rq, J*DI], wk [E, DI], ww [E, J], k_norm.{scale, bias}} (layers
  whose `indexer_types` entry is "full"),
  mlp.{w_gate, w_up, w_down} (dense layers) or
  moe.{router [E, n], bias [n] float32, experts.{..} [held, ..], shared.{..}};
  ln_f.scale; head.kernel [E, V].

With Rq = q_lora_rank, R = kv_lora_rank, N = qk_nope_head_dim,
P = qk_rope_head_dim, V' = v_head_dim, H heads, J index heads of DI, K =
index_topk, eps = rms_norm_eps, per layer on x [T, E] at positions t:

  a     = RMSNorm(x; ln1)
  c_q   = RMSNorm(a W_DQ; q_a_norm)              q = c_q W_UQ -> [q_nope | q_pe]
  [c|r] = a W_DKV,  c_kv = RMSNorm(c; kv_a_norm), k_pe = RoPE(r) (one head)
  [k_nope | v] = c_kv W_UKV                      (the EXPANDED form)
  s_h[t,u] = (q_nope_h . k_nope_h[u] + RoPE(q_pe)_h . k_pe[u]) / sqrt(N + P)
  o_h   = softmax_{u in S_t}(s_h[t, u]) v_h[u];  x1 = x + concat(o) W_O
  layer that selects ("full"):
    qI = RoPE(c_q W_IQ), kI = RoPE(LayerNorm(a W_IK)), w = a W_IW
    I[t,u] = sum_j w[t,j] relu(qI[t,j] . kI[u]),  S_t = the K positions
    u <= t of largest I (all of them while t < K; ties to the lower)
  layer that shares ("shared"): S_t of the nearest "full" layer before it
  b     = RMSNorm(x1; ln2)
  dense: x2 = x1 + SwiGLU(b)
  MoE:   sigma = sigmoid(b W_R) over all n;  T = top k of sigma + bias;
         x2 = x1 + SwiGLU_shared(b) + sum_{e in T, held} 2.5 sigma_e /
              sum_T sigma . SwiGLU_e(b)

RoPE pairs adjacent dims (2i, 2i+1) (`rope_interleave`,
`indexer_rope_interleave`) at theta rope_parameters.rope_theta, over all P
dims of q_pe and k_pe and over the first P of the DI dims of qI and kI.
The positive constant scales of the index score and DeepSeek-V3.2's
Hadamard rotation of qI and kI (orthogonal: every product is unchanged)
are left out; each departure is stated in the configuration file's
`assumed`. Queries are taken a block at a time (`lax.map`), so that 16k
tokens fit on a chip beside the weights.

Two choices hang on a rounding, and both can be FORCED to the system's
own, with the reference's own scores then judging the forced choice:
`picks` (the router's 8th against its 9th expert, judged in the biased
score sigma + bias that picks them) and `select` (the indexer's K-th
against its K+1-th position, for every layer that selects).
`tests/glm_ref.py` is a byte-for-byte copy, kept equal by a test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# planted faults (`forward(fault=...)`): a reference that departs from the
# model in one known way, which the comparison has to catch
FAULTS = ("no_k_pe", "shared_own", "no_bias", "rotate_half")

# heads whose keys and values the expanded attention decompresses at a time
_HEADS = 16


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def rotate(x, positions, theta: float, dims: int, interleave: bool = True):
    """x [T, heads, D] at integer `positions` [T]: the first `dims` dims
    rotated, adjacent pairs (2i, 2i+1) at frequency i (`interleave`), or
    dim i with dim i + dims/2 (rotate-half); the rest passed through."""
    inv = 1.0 / theta ** (np.arange(0, dims, 2, dtype=np.float64) / dims)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    r = x[..., :dims]
    if interleave:
        x1, x2 = r[..., 0::2], r[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(r.shape)
    else:
        x1, x2 = r[..., :dims // 2], r[..., dims // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return jnp.concatenate([out, x[..., dims:]], axis=-1)


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


def _select(qi, ki, w, *, topk, block, forced, prod):
    """The indexer's choice: index queries qi [T, J, DI], keys ki
    [T, DI], head weights w [T, J] -> (use [T, T] bool, deficit [T],
    count [T], swapped [T]). `forced` [T, ceil(T / 32)] uint32 or None:
    the positions attended replace the indexer's own choice, and
    `deficit` is how far the lowest-scoring forced position lies below
    the reference's own `topk`-th best score (inf for a position ahead
    of its query), `count` how many positions are attended, `swapped`
    how many of them are not among the reference's own choice."""
    t = qi.shape[0]
    kk = min(topk, t)
    kpos = jnp.arange(t)
    blocks = lambda a: a.reshape(t // block, block, *a.shape[1:])
    args = [blocks(qi), blocks(w), jnp.arange(0, t, block)]
    if forced is not None:
        args.append(blocks(forced))

    def rows(args):
        qib, wb, r0 = args[:3]
        qpos = r0 + jnp.arange(block)
        causal = kpos[None, :] <= qpos[:, None]
        si = jnp.maximum(prod("qjd,kd->qjk", qib, ki), 0.0)  # [q, J, T]
        score = jnp.where(causal, prod("qjk,qj->qk", si, wb), -jnp.inf)
        best, at = jax.lax.top_k(score, kk)      # ties: the lower position
        own = jnp.zeros(score.shape, bool).at[
            jnp.arange(block)[:, None], at].set(True) & causal
        if forced is None:
            return own, jnp.zeros(block, jnp.float32), own.sum(-1), 0 * own.sum(-1)
        use = unpack_bits(args[3], t)
        lowest = jnp.min(jnp.where(use, score, jnp.inf), axis=-1)
        # fewer than topk visible: every visible position is the
        # reference's own choice, whatever it scores
        floor = jnp.where(qpos < topk, -jnp.inf, best[:, -1])
        deficit = jnp.maximum(floor - lowest, 0.0)
        deficit = jnp.where(jnp.any(use & ~causal, axis=-1), jnp.inf, deficit)
        return use, deficit, use.sum(-1), jnp.sum(use & ~own, axis=-1)

    use, deficit, count, swapped = jax.lax.map(rows, tuple(args))
    return (use.reshape(t, t), deficit.reshape(t), count.reshape(t),
            swapped.reshape(t))


def _attention(q_nope, q_pe, c_kv, k_pe, w_kv, use, *, block, scale, prod):
    """Softmax attention over the positions `use` [T, T] selects, in the
    EXPANDED form: the keys' and values' head parts decompressed from
    the latent, [k_nope | v] = c_kv W_UKV (w_kv [R, H, N + V']), and per
    head (q_nope . k_nope + q_pe . k_pe) * scale; `_HEADS` heads and a
    block of query rows at a time. -> o [T, H, V']."""
    t, h, n = q_nope.shape
    r, hg = w_kv.shape[0], math.gcd(h, _HEADS)
    heads = lambda a, axis: jnp.moveaxis(
        a.reshape(*a.shape[:axis], h // hg, hg, *a.shape[axis + 1:]), axis, 0)
    blocks = lambda a: a.reshape(t // block, block, *a.shape[1:])

    def group(args):
        qn, qp, w = args                         # [T, hg, N], [T, hg, P], [R, hg, N+V']
        kv = prod("tr,rf->tf", c_kv, w.reshape(r, -1)).reshape(t, hg, -1)
        k_nope, v = kv[..., :n], kv[..., n:]

        def rows(args):
            qnb, qpb, ub = args
            s = (prod("qhn,khn->hqk", qnb, k_nope)
                 + prod("qhp,kp->hqk", qpb, k_pe))
            p = jax.nn.softmax(jnp.where(ub[None], s * scale, -jnp.inf), axis=-1)
            return prod("hqk,khv->qhv", p, v)

        return jax.lax.map(rows, (blocks(qn), blocks(qp), blocks(use)))

    o = jax.lax.map(group, (heads(q_nope, 1), heads(q_pe, 1), heads(w_kv, 1)))
    return jnp.moveaxis(o.reshape(h // hg, t, hg, -1), 0, 1).reshape(t, h, -1)


def _mm(prod, x, w):
    """x [T, E] times a weight of the tree [E, F]."""
    return prod("te,ef->tf", x, w)


def _swiglu(p, x, prod):
    return _mm(prod, jax.nn.silu(_mm(prod, x, p["w_gate"]))
               * _mm(prod, x, p["w_up"]), p["w_down"])


def _experts(p, b, cfg, held, picks, prod, block, fault):
    """The MoE part of a layer on b [T, E] (T a multiple of `block`):
    the shared expert, and every held expert on every row weighted by
    the router (0 where it was not picked). `picks` [T, k] replaces the
    router's own choice where an entry is >= 0; the weights are still
    the router's sigmoid scores of the experts used. Returns (y [T, E],
    the biased scores sigma + bias that pick [T, n])."""
    first, count = held
    k = cfg["num_experts_per_tok"]
    sigma = jax.nn.sigmoid(_mm(prod, b, p["router"]))
    biased = sigma if fault == "no_bias" else sigma + _f32(p["bias"])
    own = jax.lax.top_k(biased, k)[1]
    if picks is not None:
        own = jnp.where(picks >= 0, picks, own)
    chosen = jnp.take_along_axis(sigma, own, axis=-1)            # [T, k]
    chosen = (cfg["routed_scaling_factor"] * chosen
              / jnp.sum(chosen, axis=-1, keepdims=True))
    local = own - first
    w = jnp.zeros((b.shape[0], count + 1), jnp.float32)
    w = w.at[jnp.arange(b.shape[0])[:, None],
             jnp.where((local >= 0) & (local < count), local, count)].add(chosen)
    t, e = b.shape
    n = math.gcd(count, 4)             # so many held experts at a time
    some = lambda m: m.reshape(count // n, n, *m.shape[1:])
    w = jnp.moveaxis(w[:, :count].reshape(t // block, block, count // n, n),
                     2, 0)                                # [groups, blocks, block, n]
    xs = b.reshape(t // block, block, e)

    def group(args):
        w_gate, w_up, w_down, wg = args

        def rows(xw):
            x, wr = xw
            gate = prod("te,hef->thf", x, w_gate)
            up = prod("te,hef->thf", x, w_up)
            each = prod("thf,hfe->the", jax.nn.silu(gate) * up, w_down)
            return prod("the,th->te", each, wr)

        return jax.lax.map(rows, (xs, wg))

    ex = p["experts"]
    y = jax.lax.map(group, (some(ex["w_gate"]), some(ex["w_up"]),
                            some(ex["w_down"]), w))
    y = jnp.sum(y, axis=0).reshape(t, e)
    if cfg["n_shared_experts"]:
        y = y + _swiglu(p["shared"], b, prod)
    return y, biased


def layer(p, x, cfg: dict, held, positions, *, block, picks=None, use=None,
          select=None, prod, fault=None):
    """One block on x [T, E] (T a multiple of `block`). `use` [T, T] is
    the source layer's selection for a layer that shares one; `select`
    the forced choice of a layer that makes one. -> (x2, the router's
    biased scores [T, n] or None, use, (deficit, count, swapped) [T]
    each or None)."""
    t = x.shape[0]
    eps, h = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    inter = fault != "rotate_half"
    a = _rms(x, p["ln1"]["scale"], eps)
    m = p["mla"]
    c_q = _rms(_mm(prod, a, m["wq_a"]), m["q_a_norm"], eps)
    q = _mm(prod, c_q, m["wq_b"]).reshape(t, h, nope + rope)
    q_pe = rotate(q[..., nope:], positions, theta, rope, inter)
    kv = _mm(prod, a, m["wkv_a"])
    c_kv = _rms(kv[:, :r], m["kv_a_norm"], eps)
    k_pe = rotate(kv[:, None, r:], positions, theta, rope, inter)[:, 0]
    account = None
    if "idx" in p:
        j, di = cfg["index_n_heads"], cfg["index_head_dim"]
        qi = rotate(_mm(prod, c_q, p["idx"]["wq"]).reshape(t, j, di),
                    positions, theta, rope, inter)
        ki = rotate(_layer_norm(_mm(prod, a, p["idx"]["wk"]),
                                p["idx"]["k_norm"], 1e-6)[:, None, :],
                    positions, theta, rope, inter)[:, 0]
        use, *account = _select(qi, ki, _mm(prod, a, p["idx"]["ww"]),
                                topk=cfg["index_topk"], block=block,
                                forced=select, prod=prod)
    if fault == "no_k_pe":
        q_pe = jnp.zeros_like(q_pe)
    o = _attention(q[..., :nope], q_pe, c_kv, k_pe,
                   m["wkv_b"].reshape(r, h, nope + vd), use, block=block,
                   scale=(nope + rope) ** -0.5, prod=prod)
    x = x + _mm(prod, o.reshape(t, h * vd), m["wo"])
    b = _rms(x, p["ln2"]["scale"], eps)
    if "moe" in p:
        y, biased = _experts(p["moe"], b, cfg, held, picks, prod, block, fault)
    else:
        y, biased = _swiglu(p["mlp"], b, prod), None
    return x + y, biased, use, account


def _own_use(p_source, x, cfg, positions, *, block, prod):
    """What a shared layer would select with the source layer's indexer
    weights on its OWN hidden state (the `shared_own` fault)."""
    t = x.shape[0]
    eps, rope = cfg["rms_norm_eps"], cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    a = _rms(x, p_source["ln1"]["scale"], eps)
    c_q = _rms(_mm(prod, a, p_source["mla"]["wq_a"]),
               p_source["mla"]["q_a_norm"], eps)
    qi = rotate(_mm(prod, c_q, p_source["idx"]["wq"]).reshape(t, j, di),
                positions, theta, rope)
    ki = rotate(_layer_norm(_mm(prod, a, p_source["idx"]["wk"]),
                            p_source["idx"]["k_norm"], 1e-6)[:, None, :],
                positions, theta, rope)[:, 0]
    return _select(qi, ki, _mm(prod, a, p_source["idx"]["ww"]),
                   topk=cfg["index_topk"], block=block, forced=None,
                   prod=prod)[0]


def forward(params, tokens, cfg: dict, held, *, rows=None, block: int = 256,
            picks=None, select=None, prod=None, fault=None):
    """Logits [rows, V] float32 of the sequence `tokens` [T], the
    router's biased scores at every position [MoE layers, T, n], the
    indexer's account of the selection, three [selecting layers, T]
    arrays (deficit, count, swapped: see `_select`), over the layers
    whose `indexer_types` entry is "full".

    `rows` = (r0, r1) restricts the logits that come back (the forward
    itself runs over the whole sequence, padded at its end to a multiple
    of `block`: a causal model's real rows do not see the padding).
    `picks` [MoE layers, T, k] int, or None: the listed experts replace
    the router's own choice where an entry is >= 0. `select`
    [selecting layers, T, ceil(T / 32)] uint32 (`pack_bits`), or None:
    the listed positions replace the indexer's own choice.
    `prod(subscripts, a, b)` takes EVERY product of the forward pass, an
    einsum of two operands over one contracted index (projections, the
    attention's scores and weighted sums, the experts, the index scores
    and their weighted sum, the head), and returns it in float32
    (default: float32 operands at precision "highest"): the hook by which
    a lower-precision product is shown to fail the comparison. `fault`
    (one of `FAULTS`, or None)
    plants a departure from the model. Traceable: `jax.jit` it with
    `cfg`, `held`, `rows`, `block` and `fault` closed over."""
    with jax.default_matmul_precision("highest"):
        if prod is None:
            prod = lambda s, a, b: jnp.einsum(s, _f32(a), _f32(b),
                                              precision="highest")
        tokens = jnp.asarray(tokens, jnp.int32)
        n_real = tokens.shape[0]
        r0, r1 = rows if rows is not None else (0, n_real)
        tokens = jnp.pad(tokens, (0, -n_real % block))
        t = tokens.shape[0]
        positions = jnp.arange(t)
        x = _f32(params["embed"][tokens])
        routers, accounts, source = [], [], {}
        for i in range(cfg["num_hidden_layers"]):
            kind = cfg["indexer_types"][i]
            p = params[f"block{i}"]
            forced_picks = forced_sel = use = None
            if picks is not None and "moe" in p:
                forced_picks = jnp.pad(
                    jnp.asarray(picks[len(routers)], jnp.int32),
                    ((0, t - n_real), (0, 0)), constant_values=-1)
            if kind == "full" and select is not None:
                # padding rows attend to themselves alone
                words = -(-t // 32)
                sel = jnp.asarray(select[len(accounts)], jnp.uint32)
                sel = jnp.pad(sel, ((0, t - n_real),
                                    (0, words - sel.shape[1])))
                pad_rows = jnp.arange(n_real, t)
                forced_sel = sel.at[pad_rows, pad_rows // 32].set(
                    jnp.uint32(1) << (pad_rows % 32).astype(jnp.uint32))
            if kind == "shared":
                use = (_own_use(params[f"block{source['at']}"], x, cfg,
                                positions, block=block, prod=prod)
                       if fault == "shared_own" else source["use"])
            x, biased, use, account = layer(
                p, x, cfg, held, positions, block=block, picks=forced_picks,
                use=use, select=forced_sel, prod=prod, fault=fault)
            if kind == "full":
                source = {"at": i, "use": use}
                accounts.append(jnp.stack([a[:n_real].astype(jnp.float32)
                                           for a in account]))
            if biased is not None:
                routers.append(biased[:n_real])
        out = _mm(prod, _rms(x[r0:r1], params["ln_f"]["scale"],
                             cfg["rms_norm_eps"]), params["head"]["kernel"])
        deficit, count, swapped = jnp.moveaxis(jnp.stack(accounts), 1, 0)
        return out, jnp.stack(routers), (deficit, count, swapped)
