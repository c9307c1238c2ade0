"""Secure-aggregation FedAvg: the TPU pairwise-mask round and the
host-side Paillier parity classes.

Capability parity with the reference's secure federated stack (SURVEY.md
C12-C15, D4; secure_fed_model.py:101-236):

- each client trains E local epochs on its private shard,
- "encrypts" a `percent` fraction of its weight tensors,
- the server aggregates an (unweighted, quirk Q7) elementwise mean while
  only ever seeing ciphertext for the protected tensors,
- clients decrypt the aggregate and adopt it,
- per-round evaluation on a global held-out set (loss / BinaryAccuracy /
  AUROC — C16) is the caller's step (see cli.secure_fed).

The TPU fast path replaces Paillier with pairwise one-time masks
(`secure.masking`): inside one jitted `shard_map` program the protected
tensors are quantized to int32, masked with antisymmetric pairwise PRG
streams, and `psum`-ed — the sum the "server" observes per device is
uniformly random, but the masks cancel bit-for-bit and the dequantized
result equals the plain mean to quantization precision (2^-scale_bits).
Unprotected tensors ride a plain `pmean`, mirroring the reference's
partial encryption.

The host-side `PaillierClient` / `PaillierServer` classes reproduce the
reference's object-level protocol (Client.client_fit / enc_model /
client_update, Server.aggregate — secure_fed_model.py:101-168) with the
from-scratch `secure.paillier` in place of `phe`, kept as the
cross-checkable reference mode for the masking path.
"""

from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from idc_models_tpu import collectives
from idc_models_tpu import mesh as meshlib
from idc_models_tpu.federated.fedavg import (
    ServerState, finite_clients, make_local_trainer,
)
from idc_models_tpu.models import core
from idc_models_tpu.secure import masking
from idc_models_tpu.secure.paillier import (
    PaillierPrivateKey, PaillierPublicKey,
)

LossFn = Callable[[jax.Array, jax.Array], jax.Array]

# Protected model_state tensors (BN moving statistics) are prescaled by
# 1/256 before quantization and rescaled after aggregation: ImageNet-scale
# BN moving variances run in the hundreds-to-thousands, far outside the
# +-clip_abs=64 weight clipping range, and clipping them would silently
# corrupt the server's BN state. The power-of-two prescale is exact in
# fp32, identical on every client (so the mask algebra and layout
# invariance are untouched), extends the state range to +-16384, and
# costs state resolution only (256 * 2^-scale_bits ~ 1e-4 absolute —
# noise-level for moving statistics). Weights keep full resolution.
_STATE_PRESCALE = 256.0


def resolve_mask_impl(model: core.Module, percent: float, *,
                      mesh: Mesh | None = None) -> str:
    """Resolve ``mask_impl="auto"``: the fused Pallas kernel iff the
    round's devices are TPU (`meshlib.pallas_interpret` — the mesh's
    devices, or the process default devices without one) AND the
    protected buffer (the first `percent` of the full get_weights()
    enumeration) reaches `masking.MASK_PALLAS_MIN_ELEMS` — the
    crossover measured in experiments/mask_crossover.jsonl (see the
    constant's comment). Pure and cheap: element counts come from
    `jax.eval_shape`, no arrays are materialized. "auto" is an explicit
    opt-in, not the round default: it trades threefry's cryptographic
    mask stream for the hash-PRG kernel's throughput (see
    make_secure_fedavg_round's docstring for the threat-model
    caveat)."""
    if meshlib.pallas_interpret(mesh):
        return "threefry"
    p, s = jax.eval_shape(
        lambda rng: (lambda v: (v.params, v.state))(model.init(rng)),
        jax.random.key(0))
    pf, sf = masking.first_fraction_selection_weights(
        p, s, percent, model.layer_names)
    n_prot = sum(
        leaf.size for leaf, flag in zip(
            jax.tree.leaves(p) + jax.tree.leaves(s),
            jax.tree.leaves(pf) + jax.tree.leaves(sf)) if flag)
    return ("pallas" if n_prot >= masking.MASK_PALLAS_MIN_ELEMS
            else "threefry")


def make_secure_fedavg_round(
    model: core.Module,
    optimizer: optax.GradientTransformation,
    loss_fn: LossFn,
    mesh: Mesh,
    *,
    percent: float,
    local_epochs: int = 5,
    batch_size: int = 32,
    scale_bits: int | None = None,
    clip_abs: float = masking.DEFAULT_CLIP_ABS,
    compute_dtype=jnp.float32,
    mask_impl: str = "threefry",
    recover_nonfinite: bool = True,
    aggregator=None,
):
    """Build the jitted one-round secure-FedAvg program.

    Returns ``round_fn(server_state, images [C,S,...], labels [C,S], rng)
    -> (server_state, metrics)``. The aggregate is the unweighted mean
    (reference parity, quirk Q7); the first `percent` fraction of the
    model's weight tensors — params AND mutable state interleaved in
    model layer order, Keras get_weights() enumeration, matching both
    the reference's slice (secure_fed_model.py:115-121) and this
    module's PaillierClient — go through the masked integer path.

    The round boundary packs the protected tensors into ONE flat int32
    buffer (single masked psum) and everything else — unprotected params
    and model state — into ONE flat f32 buffer (single pmean): exactly
    two weight collectives per round regardless of model depth.

    `mask_impl` selects how the flat protected buffer is quantized+masked:
    ``"threefry"`` (default) is XLA's threefry PRG via
    `masking.pairwise_mask`; ``"pallas"`` is the fused single-pass Pallas
    kernel (`ops.secure_masking_kernel.fused_masked_quantize`, hash-PRG,
    interpret mode off-TPU); ``"auto"`` resolves at build time via
    `resolve_mask_impl` — pallas on TPU when the protected buffer
    reaches `masking.MASK_PALLAS_MIN_ELEMS` (the measured crossover,
    BASELINE.md), threefry otherwise. The default stays threefry ON
    PURPOSE: the Pallas kernel's murmur-style hash PRG is fast but NOT
    cryptographic, and mask unpredictability against a curious
    aggregator — not just exact cancellation — is the property the
    protocol exists for. Opt into "auto"/"pallas" only where the threat
    model tolerates a non-cryptographic mask stream (e.g. benchmarking,
    or aggregators trusted not to attack masks). Both impls cancel
    exactly under psum; they produce different (each internally
    consistent) mask streams, so all clients of one aggregation must use
    the same impl — guaranteed here since the whole round is one
    program.

    `scale_bits` defaults to the largest fixed-point precision whose
    cross-client sum of clipped (+-clip_abs) values cannot overflow int32
    (`masking.choose_scale_bits`) — overflow would silently corrupt the
    aggregate, so the headroom is budgeted, not assumed.

    ``recover_nonfinite`` (default on) is failure handling for a path
    where DROPPING a participant is cryptographically hard: removing a
    client from the unweighted masked mean would leave its pairwise
    masks uncancelled (full Bonawitz dropout recovery needs
    secret-shared mask reconstruction — out of scope). Instead, a client
    whose local update goes non-finite has its update replaced with the
    incoming global weights BEFORE quantization/masking — a no-op
    contribution that keeps the mask algebra and the divisor intact —
    and is excluded from the training metrics;
    ``metrics["clients_recovered"]`` reports the count. The reference
    has no failure handling at all (SURVEY.md §5).

    ``aggregator`` (federated/robust.py) must be SECURE-COMPATIBLE: the
    masked path sums quantized per-client contributions, so only
    aggregators that are a per-client transform followed by a mean can
    ride it — "mean" (default) and "norm_clip" (clip each client's
    update delta before quantization/masking; the Byzantine-influence
    bound then holds against the masked aggregate too, and
    ``metrics["clients_clipped"]`` reports the count). trimmed_mean /
    median need plaintext cross-client views per coordinate — exactly
    what the protocol forbids — and are rejected at build time.
    """
    from idc_models_tpu.federated import robust

    agg = robust.get_aggregator(aggregator)
    if not agg.secure_compatible:
        raise ValueError(
            f"aggregator {agg!r} is not compatible with secure "
            f"aggregation: the masked path sums quantized per-client "
            f"contributions, so only per-client-transform + mean "
            f"aggregators (mean, norm_clip) can ride it; trimmed_mean/"
            f"median need plaintext cross-client views, which the "
            f"protocol exists to prevent — use the plain "
            f"make_fedavg_round for those")
    if mask_impl not in ("auto", "threefry", "pallas"):
        raise ValueError(f"unknown mask_impl {mask_impl!r}")
    # platform decisions key on the MESH's devices, not the process
    # default backend — a CPU-device client mesh in a TPU-backed
    # process must neither auto-select the Mosaic kernel nor lower it
    # uninterpreted (the one rule: mesh.pallas_interpret)
    interp = meshlib.pallas_interpret(mesh)
    if mask_impl == "auto":
        mask_impl = resolve_mask_impl(model, percent, mesh=mesh)
    n_devices = mesh.shape[meshlib.CLIENT_AXIS]
    local_train = make_local_trainer(
        model, optimizer, loss_fn, local_epochs=local_epochs,
        batch_size=batch_size, compute_dtype=compute_dtype)

    def make_per_device(n_total: int, n_real: int, k: int, sb: int):
        def per_device(params, model_state, imgs, labels, rng, mask_key):
            # [k, S, ...] block: this device's k clients. Masks belong to
            # CLIENTS (global ids), so the cancellation algebra — and the
            # aggregate, bit-for-bit on the int32 path — is invariant to
            # how clients are laid out over devices.
            #
            # Clients with id >= n_real are mesh-padding DUMMIES
            # (VERDICT r2 #6): they participate fully in mask generation
            # — every pairwise stream must appear at both endpoints or
            # nothing cancels — but their quantized update is forced to
            # zero and the divisor stays n_real, so the aggregate is
            # bit-identical (int32 path) to the same clients run on a
            # mesh that divides their count, while using every device.
            dev = collectives.axis_index(meshlib.CLIENT_AXIS)
            cids = dev * k + jnp.arange(k)
            real = cids < n_real
            rngs = jax.vmap(lambda c: jax.random.fold_in(rng, c))(cids)

            new_params, new_model_state, (losses, accs) = jax.vmap(
                local_train, in_axes=(None, None, 0, 0, 0))(
                params, model_state, imgs, labels, rngs)

            ok = jnp.ones((k,), bool)
            recovered = jnp.zeros((), jnp.float32)
            if recover_nonfinite:
                # failure recovery: a diverged client contributes the
                # incoming global weights instead of garbage (see the
                # factory docstring — dropping would break the masks)
                ok = finite_clients(k, new_params, new_model_state, losses)
                recovered = collectives.psum(
                    jnp.sum(~ok & real).astype(jnp.float32),
                    meshlib.CLIENT_AXIS)

                def keep(new, old):
                    okr = ok.reshape((k,) + (1,) * (new.ndim - 1))
                    return jnp.where(okr, new, old[None])

                new_params = jax.tree.map(keep, new_params, params)
                new_model_state = jax.tree.map(keep, new_model_state,
                                               model_state)

            # secure-compatible robustness: the per-client transform
            # (e.g. norm_clip's delta clipping) runs BEFORE quantization
            # and masking, so the aggregate the server unmasks is
            # already influence-bounded; metrics count real live clients
            upd, per_client_m = agg.per_client(
                {"params": new_params, "model_state": new_model_state},
                {"params": params, "model_state": model_state})
            new_params = upd["params"]
            new_model_state = upd["model_state"]
            agg_metrics = {
                key: collectives.psum(
                    jnp.sum(jnp.where(ok & real, vals, 0.0)),
                    meshlib.CLIENT_AXIS)
                for key, vals in per_client_m.items()}

            # "First fraction" follows the model's layer order over the
            # FULL get_weights() enumeration — params and BN moving
            # statistics interleaved, exactly the list the reference
            # slices (secure_fed_model.py:115-121) — not jax's
            # alphabetical flatten and not params alone.
            p_protect, s_protect = masking.first_fraction_selection_weights(
                new_params, new_model_state, percent, model.layer_names)
            leaves, treedef = jax.tree.flatten(new_params)
            state_leaves, state_def = jax.tree.flatten(new_model_state)
            all_leaves = leaves + state_leaves
            all_flags = (jax.tree.leaves(p_protect)
                         + jax.tree.leaves(s_protect))

            is_state = [False] * len(leaves) + [True] * len(state_leaves)
            # protected state rides the int path at 1/256 scale (see
            # _STATE_PRESCALE above) so BN moving variances clear the
            # clip range that is sized for weights
            prot = [x / _STATE_PRESCALE if s else x
                    for x, f, s in zip(all_leaves, all_flags, is_state)
                    if f]
            prot_scales = [s for s, f in zip(is_state, all_flags) if f]
            plain = [x for x, f in zip(all_leaves, all_flags) if not f]

            # -- protected: quantize+mask per client, local int32 sum
            #    (mod 2^32, exactly like psum), then ONE psum ----------
            prot_agg: list = []
            clip_saturated = jnp.zeros((), jnp.float32)
            if prot:
                flat_k, meta = masking.pack_leaves(prot, lead_axes=1)
                # dummies contribute exactly zero (quantize(0) == 0), so
                # only their masks enter the sum — and those cancel
                flat_k = jnp.where(real[:, None], flat_k, 0.0)
                # Saturation detection (advisor r3): a protected value at
                # the clip boundary — e.g. a BN moving variance beyond
                # clip_abs * _STATE_PRESCALE on unnormalized inputs — is
                # silently truncated into the aggregate; count and
                # surface it so callers can raise clip_abs/prescale
                # instead of debugging corrupted server BN state.
                clip_saturated = collectives.psum(
                    jnp.sum(jnp.abs(flat_k) >= clip_abs)
                    .astype(jnp.float32), meshlib.CLIENT_AXIS)
                if mask_impl == "pallas":
                    from idc_models_tpu.ops import secure_masking_kernel as smk

                    seed = jax.random.bits(mask_key, (), jnp.uint32)
                    masked_total = jnp.zeros((flat_k.shape[1],), jnp.int32)
                    for i in range(k):  # k is static and small
                        seeds, signs = smk.pair_seeds_and_signs(
                            seed, cids[i], n_total)
                        masked_total = masked_total + smk.fused_masked_quantize(
                            flat_k[i], seeds, signs, scale_bits=sb,
                            clip_abs=clip_abs, interpret=interp)
                else:
                    q = masking.quantize(flat_k, sb, clip_abs=clip_abs)
                    masks = jax.vmap(
                        lambda c: masking.pairwise_mask(
                            mask_key, c, n_total, (flat_k.shape[1],)))(cids)
                    masked_total = (q + masks).sum(axis=0)
                summed = collectives.psum(masked_total, meshlib.CLIENT_AXIS)
                deq = masking.dequantize(summed, sb, count=n_real)
                prot_agg = [x * _STATE_PRESCALE if s else x
                            for x, s in zip(masking.unpack_leaves(deq, meta),
                                            prot_scales)]

            # -- everything else (unprotected params + state): local sum
            #    then ONE psum / C_real (the unweighted mean, quirk Q7) --
            plain_agg: list = []
            if plain:
                flat_k, meta = masking.pack_leaves(plain, lead_axes=1)
                flat_k = jnp.where(real[:, None], flat_k, 0.0)
                mean = collectives.psum(flat_k.sum(axis=0),
                                        meshlib.CLIENT_AXIS) / n_real
                plain_agg = masking.unpack_leaves(mean, meta)

            prot_it, plain_it = iter(prot_agg), iter(plain_agg)
            agg_all = [next(prot_it) if f else next(plain_it)
                       for f in all_flags]
            agg_params = jax.tree.unflatten(treedef, agg_all[:len(leaves)])
            agg_state = jax.tree.unflatten(state_def, agg_all[len(leaves):])
            # training metrics over the clients that actually trained
            # (weighted_pmean_local masks dead clients' NaNs exactly
            # like the plain round); NaN — not a perfect-looking 0.0 —
            # if every client diverged
            live = ok & real
            alive = collectives.psum(
                live.astype(jnp.float32).sum(), meshlib.CLIENT_AXIS)
            metrics = collectives.weighted_pmean_local(
                jax.tree.map(
                    lambda x: jnp.mean(x, axis=tuple(range(1, x.ndim))),
                    {"loss": losses, "accuracy": accs}),
                live.astype(jnp.float32), meshlib.CLIENT_AXIS)
            metrics = jax.tree.map(
                lambda x: jnp.where(alive > 0, x, jnp.float32(jnp.nan)),
                metrics)
            metrics["clients_recovered"] = recovered
            # same all-dead masking as the trained metrics: a round where
            # no real client survives reports NaN across the board, not a
            # lone finite 0 that a finite-filtering consumer would keep
            metrics["clip_saturated"] = jnp.where(
                alive > 0, clip_saturated, jnp.float32(jnp.nan))
            metrics.update(agg_metrics)
            return agg_params, agg_state, metrics

        return per_device

    def make_round(n_total: int, n_real: int, sb: int):
        mapped = shard_map(
            make_per_device(n_total, n_real, n_total // n_devices, sb),
            mesh=mesh,
            in_specs=(P(), P(), P(meshlib.CLIENT_AXIS),
                      P(meshlib.CLIENT_AXIS), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )

        def round_fn(server: ServerState, images, labels, rng):
            # One-time masks: the mask key is derived from the fresh
            # per-round rng (distinct fold from the training rng), so
            # streams are never reused across rounds.
            params, model_state, metrics = mapped(
                server.params, server.model_state, images, labels, rng,
                jax.random.fold_in(rng, jnp.int32(-1)))
            new_server = server.replace(
                round=server.round + 1, params=params,
                model_state=model_state)
            return new_server, metrics

        return jax.jit(round_fn, donate_argnums=(0,))

    rounds: dict[int, Callable] = {}
    warned_pad: list = []  # one-time flag for the host-resident pad path

    def round_fn(server: ServerState, images, labels, rng, *,
                 n_real: int | None = None):
        # Non-dividing client counts run on the FULL mesh by padding the
        # client axis with dummy clients: they train on zero shards (the
        # vmap lane is there either way), join mask generation so every
        # pairwise stream cancels, and contribute a forced-zero quantized
        # update with divisor n_real — the aggregate is bit-identical
        # (int32 path) to a run on a dividing mesh, on all devices.
        #
        # Callers with device-resident data should pre-pad ONCE and pass
        # `n_real` (see cli._run_secure): the convenience pad below
        # concatenates fresh arrays every round, which re-uploads the
        # whole stacked dataset on host-resident inputs.
        if n_real is None:
            n_real = images.shape[0]
        pad = -images.shape[0] % n_devices
        if pad:
            if not isinstance(images, jax.Array) and not warned_pad:
                import warnings

                warnings.warn(
                    f"secure round_fn is padding {images.shape[0]} "
                    f"host-resident clients to {images.shape[0] + pad} "
                    f"every round, re-uploading the stacked dataset "
                    f"each call; pre-pad once on device and pass "
                    f"n_real={n_real} (see cli._run_secure) for the "
                    f"steady-state path", stacklevel=2)
                warned_pad.append(True)
            images = jnp.asarray(images)  # settles host dtypes (f64->f32)
            labels = jnp.asarray(labels)
            images = jnp.concatenate(
                [images,
                 jnp.zeros((pad,) + tuple(images.shape[1:]),
                           images.dtype)])
            labels = jnp.concatenate(
                [labels,
                 jnp.zeros((pad,) + tuple(labels.shape[1:]),
                           labels.dtype)])
        n_total = images.shape[0]  # post-pad client-slot count
        if (n_total, n_real) not in rounds:
            # headroom is budgeted over the REAL contributions; dummies
            # add exact zeros
            sb = (scale_bits if scale_bits is not None
                  else masking.choose_scale_bits(n_real, clip_abs))
            rounds[(n_total, n_real)] = make_round(n_total, n_real, sb)
        return rounds[(n_total, n_real)](server, images, labels, rng)

    return round_fn


# ---------------------------------------------------------------------------
# Host-side Paillier parity mode (the reference's actual mechanism)
# ---------------------------------------------------------------------------

class PaillierClient:
    """Object-level parity with the reference's `Client`
    (secure_fed_model.py:101-154): owns a model replica and a private
    shard; trains locally, encrypts the first `int(L * percent)` weight
    tensors scalar-by-scalar, decrypts aggregates, and adopts them."""

    def __init__(self, model: core.Module,
                 optimizer: optax.GradientTransformation, loss_fn: LossFn,
                 images: np.ndarray, labels: np.ndarray, client_id: int,
                 percent: float, public_key: PaillierPublicKey,
                 private_key: PaillierPrivateKey, *,
                 local_epochs: int = 5, batch_size: int = 32, seed: int = 0):
        self.model = model
        self.percent = percent
        self.public_key = public_key
        self.private_key = private_key
        self.images = images
        self.labels = labels
        self.client_id = client_id
        variables = model.init(jax.random.key(seed))
        self.params = variables.params
        self.model_state = variables.state
        self._trainer = jax.jit(make_local_trainer(
            model, optimizer, loss_fn, local_epochs=local_epochs,
            batch_size=batch_size))
        self._rng = jax.random.fold_in(jax.random.key(seed + 1), client_id)

    def _flat_weights(self):
        """All model weights — params AND mutable state (BN moving stats),
        like Keras get_weights() (the reference exchanges and averages the
        full list, secure_fed_model.py:115,160-168) — as float64 ndarrays
        in model layer order. Returns (ordered leaves, restore fn)."""
        p_leaves, p_def = jax.tree.flatten(self.params)
        s_leaves, s_def = jax.tree.flatten(self.model_state)
        paths = (masking.leaf_paths(self.params)
                 + masking.leaf_paths(self.model_state))
        order = masking.ranked_indices(paths, self.model.layer_names)
        combined = [np.asarray(x, np.float64)
                    for x in jax.device_get(p_leaves + s_leaves)]
        ordered = [combined[i] for i in order]

        def restore(ordered_tensors):
            flat = [None] * len(combined)
            for slot, t in zip(order, ordered_tensors):
                flat[slot] = jnp.asarray(np.asarray(t), jnp.float32)
            params = jax.tree.unflatten(p_def, flat[:len(p_leaves)])
            state = jax.tree.unflatten(s_def, flat[len(p_leaves):])
            return params, state

        return ordered, restore

    def _num_encrypted(self) -> int:
        n = len(jax.tree.leaves(self.params)) + len(
            jax.tree.leaves(self.model_state))
        return int(n * self.percent)

    def client_fit(self):
        """Local epochs, then (possibly partially encrypted) weights out
        (secure_fed_model.py:131-141)."""
        self._rng, sub = jax.random.split(self._rng)
        self.params, self.model_state, stats = self._trainer(
            self.params, self.model_state, jnp.asarray(self.images),
            jnp.asarray(self.labels), sub)
        return self.enc_model(), jax.device_get(stats)

    def enc_model(self):
        """Flat list of weight tensors in model layer order; the first
        `int(L*percent)` are object arrays of EncryptedNumber
        (secure_fed_model.py:115-121)."""
        leaves, _ = self._flat_weights()
        n_enc = self._num_encrypted()
        enc = np.vectorize(self.public_key.encrypt, otypes=[object])
        return [enc(leaf) if i < n_enc else leaf
                for i, leaf in enumerate(leaves)]

    def dec_model(self, tensors):
        n_enc = self._num_encrypted()
        dec = np.vectorize(self.private_key.decrypt, otypes=[np.float64])
        return [dec(t) if i < n_enc else t for i, t in enumerate(tensors)]

    def client_update(self, aggregated):
        """Decrypt + adopt the aggregate — params and moving statistics
        both (secure_fed_model.py:143-149)."""
        plain = self.dec_model(aggregated)
        _, restore = self._flat_weights()
        self.params, self.model_state = restore(plain)

    def evaluate(self, images: np.ndarray, labels: np.ndarray, loss_fn: LossFn):
        """loss / binary accuracy / AUROC on a held-out set
        (secure_fed_model.py:152-154 with the C16 AUROC metric)."""
        from idc_models_tpu.train import metrics as metrics_lib

        logits, _ = self.model.apply(self.params, self.model_state,
                                     jnp.asarray(images), train=False)
        logits = logits.astype(jnp.float32)
        return {
            "loss": float(loss_fn(logits, jnp.asarray(labels))),
            "accuracy": float(metrics_lib.binary_accuracy(
                logits, jnp.asarray(labels))),
            "auroc": float(metrics_lib.auroc(
                jax.nn.sigmoid(logits), jnp.asarray(labels))),
        }


class PaillierServer:
    """Parity with the reference's stateless `Server.aggregate`
    (secure_fed_model.py:156-168): elementwise unweighted mean per tensor,
    operating transparently on EncryptedNumber object arrays (homomorphic
    add + scalar divide) and plain ndarrays alike."""

    @staticmethod
    def aggregate(client_weights):
        n = len(client_weights)
        out = []
        for tensors in zip(*client_weights):
            acc = tensors[0]
            for t in tensors[1:]:
                acc = acc + t
            out.append(acc / n)
        return out
