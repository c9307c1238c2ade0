"""Rule-based GSPMD sharding (ISSUE 15, partition.py).

Four layers of gates:

1. resolution semantics — ordering, right-alignment, mesh adaptation,
   scalar replication, and the teaching errors (unmatched param, dead
   rule, over-rank spec);
2. equivalences — tp.state_shardings through the rules layer matches
   the historical channel_spec exactly; replicated rules reproduce the
   pre-rules layout;
3. golden param paths — every registered model's param key paths are
   frozen (count + digest; the LM's full list inline since LM_RULES
   regexes name those paths), so a rename cannot silently turn a rule
   dead: this is the CI half, the runtime half is the dead-rule
   teaching error;
4. the ROADMAP item 2 acceptance gate — an LM config whose params +
   optimizer state exceed one device's budget trains AND serves on a
   sharded mesh: per-device `peak_hbm_bytes` (observe/profile.py
   program accounting; XLA memory_analysis is per-device) strictly
   below the replicated figure, losses fp-close across layouts, serve
   tokens bit-identical, zero jit-cache growth across steps.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from idc_models_tpu import mesh as meshlib, partition, tp
from idc_models_tpu.models import registry
from idc_models_tpu.models.lm import attention_lm, next_token_loss
from idc_models_tpu.observe import profile as prof
from idc_models_tpu.train import (
    TrainState, jit_data_parallel, make_train_step, rmsprop, shard_batch,
)
from idc_models_tpu.train.step import place_state

# -- 1. resolution semantics ------------------------------------------------


def _mesh22():
    return meshlib.make_mesh({meshlib.DATA_AXIS: 2,
                              meshlib.MODEL_AXIS: 2})


def test_first_match_wins_and_right_alignment():
    rules = partition.PartitionRules((
        (r"special/kernel$", P(None, meshlib.DATA_AXIS)),
        (r"kernel$", P(meshlib.MODEL_AXIS)),
        (r".*", P()),
    ))
    tree = {"special": {"kernel": np.zeros((8, 8))},
            "other": {"kernel": np.zeros((4, 8)), "bias": np.zeros((8,))}}
    specs = rules.specs(tree, mesh=_mesh22())
    assert specs["special"]["kernel"] == P(None, "data")
    # right-aligned: a rank-1 spec on a rank-2 leaf shards the LAST dim
    assert specs["other"]["kernel"] == P(None, "model")
    assert specs["other"]["bias"] == P()          # catch-all


def test_mesh_adaptation_drops_missing_and_nondividing_axes():
    rules = partition.PartitionRules((
        (r".*", P(meshlib.DATA_AXIS, meshlib.MODEL_AXIS)),))
    tree = {"a": np.zeros((4, 6)),     # 6 % 2 == 0 on both axes
            "b": np.zeros((4, 7)),     # 7 % 2 != 0 -> model dropped
            "c": np.zeros(())}         # scalar -> replicated
    specs = rules.specs(tree, mesh=_mesh22())
    assert specs["a"] == P("data", "model")
    assert specs["b"] == P("data")     # trailing None stripped
    assert specs["c"] == P()
    # a mesh without the axes degenerates to replicated everywhere
    client = meshlib.make_mesh({meshlib.CLIENT_AXIS: 4})
    specs = rules.specs(tree, mesh=client)
    assert all(s == P() for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))


def test_unmatched_param_teaches():
    rules = partition.PartitionRules(((r"kernel$", P()),))
    with pytest.raises(partition.PartitionError,
                       match="no partition rule matches.*catch-all"):
        rules.specs({"bias": np.zeros((4,))})
    # scalars never need a rule: they replicate, matched or not — a
    # rule set without a catch-all must not trip over TrainState.step
    specs = rules.specs({"kernel": np.zeros((4, 4)),
                         "step": np.zeros(())})
    assert specs["step"] == P()


def test_dead_rule_teaches_and_check_dead_opt_out():
    rules = partition.PartitionRules((
        (r"ghost$", P(meshlib.DATA_AXIS)), (r".*", P())))
    tree = {"kernel": np.zeros((4, 4))}
    with pytest.raises(partition.PartitionError, match="dead partition"):
        rules.specs(tree)
    # deliberate partial trees opt out
    assert rules.specs(tree, check_dead=False)["kernel"] == P()


def test_over_rank_spec_teaches():
    rules = partition.PartitionRules((
        (r".*", P(meshlib.DATA_AXIS, meshlib.MODEL_AXIS)),))
    with pytest.raises(partition.PartitionError, match="right-align"):
        rules.specs({"bias": np.zeros((4,))}, mesh=_mesh22())


def test_constructor_validation_teaches():
    with pytest.raises(partition.PartitionError, match="at least one"):
        partition.PartitionRules(())
    with pytest.raises(partition.PartitionError, match="PartitionSpec"):
        partition.PartitionRules(((r".*", "data"),))
    with pytest.raises(partition.PartitionError, match="does not"):
        partition.PartitionRules(((r"[", P()),))
    with pytest.raises(partition.PartitionError, match="twice"):
        partition.PartitionRules(((r".*", P("data", "data")),))


def test_optimizer_state_shards_with_its_param():
    """The FSDP contract: the rmsprop `nu` tree mirrors the params, its
    key paths carry the param path as a suffix, and re.search matches
    both — one rule shards a param AND its moments."""
    model = attention_lm(16, 32, embed_dim=8, num_heads=2, mlp_dim=16,
                         num_blocks=1)
    opt = rmsprop(1e-3)
    v = model.init(jax.random.key(0))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=v.params,
                       model_state=v.state,
                       opt_state=opt.init(v.params))
    mesh = _mesh22()
    specs = registry.LM_RULES.specs(state, mesh=mesh)
    flat = {name: s for name, s in partition.tree_paths(specs)}
    wq = [k for k in flat if k.endswith("mha/wq")]
    assert len(wq) == 2, f"param + nu moment expected, got {wq}"
    assert len({str(flat[k]) for k in wq}) == 1, (
        "optimizer moment sharded differently from its param")
    assert flat["step"] == P()


def test_shard_and_gather_tree_roundtrip(devices):
    mesh = _mesh22()
    rules = partition.PartitionRules((
        (r"w$", P(meshlib.DATA_AXIS, meshlib.MODEL_AXIS)), (r".*", P())))
    tree = {"w": np.arange(32.0).reshape(4, 8), "b": np.ones((3,))}
    placed = partition.shard_tree(mesh, rules, tree)
    assert placed["w"].sharding.spec == P("data", "model")
    gathered = partition.gather_tree(mesh, placed)
    assert gathered["w"].sharding.spec == P()
    np.testing.assert_array_equal(np.asarray(gathered["w"]), tree["w"])


# -- 2. equivalences --------------------------------------------------------


def test_tp_state_shardings_match_channel_spec(devices):
    """tp.state_shardings now resolves through partition.py; it must
    reproduce the historical shape-based channel rule EXACTLY (specs,
    not just layouts) on a representative mixed tree."""
    mesh = tp.dp_tp_mesh(4)
    n_model = mesh.shape[meshlib.MODEL_AXIS]
    tree = {"conv": np.zeros((3, 3, 3, 32)), "dense": np.zeros((512, 8)),
            "head": np.zeros((512, 1)), "bias": np.zeros((32,)),
            "odd": np.zeros((7,)), "scalar": np.zeros(()),
            "moment": {"conv": np.zeros((3, 3, 3, 32))}}
    new = tp.state_shardings(mesh, tree)
    for (name, sh) in partition.tree_paths(new):
        leaf = tree
        for part in name.split("/"):
            leaf = leaf[part]
        assert sh.spec == tp.channel_spec(leaf, n_model), name


# -- 3. golden param paths (the CI half of the dead-rule defense) -----------

# model -> (leaf count, sha256 over the sorted "/"-joined path list).
# Regenerate with tools shown in the assertion message after a
# DELIBERATE rename — and update any partition rule (registry.py) that
# named the old path, which is exactly the review moment this gate
# exists to force.
GOLDEN_PARAM_PATHS = {
    "vgg16": (28, "8bdae838ef019c5ec9955d8ad4ee850f16533b182b7b4936"
                  "08ca2792dc192a5d"),
    "mobilenet_v2": (158, "c156469a357f372eb81cdc47dd8a0071d94b0fcf27"
                          "8c8ba68f35c7cda287ec5f"),
    "densenet201": (604, "30655eff0c45e93d976b2a0cce7d239280edc865b3f"
                         "cb4e674d7d66b338a8047"),
    "small_cnn": (6, "79c36dd7b46160b8c18fec78cca771fe9a351f475234556"
                     "22b81e929a7ff51d9"),
    "lm": (32, "3336b997678bdb55e08e728b979482e60612929785f3dea64d6e5"
               "e83a943da71"),
}

# the LM's paths inline too: LM_RULES regexes name these, so a diff
# here shows EXACTLY which rule a rename would orphan
GOLDEN_LM_PATHS = [
    "block0/fc1/bias", "block0/fc1/kernel", "block0/fc2/bias",
    "block0/fc2/kernel", "block0/ln1/bias", "block0/ln1/scale",
    "block0/ln2/bias", "block0/ln2/scale", "block0/mha/bo",
    "block0/mha/wk", "block0/mha/wo", "block0/mha/wq", "block0/mha/wv",
    "block1/fc1/bias", "block1/fc1/kernel", "block1/fc2/bias",
    "block1/fc2/kernel", "block1/ln1/bias", "block1/ln1/scale",
    "block1/ln2/bias", "block1/ln2/scale", "block1/mha/bo",
    "block1/mha/wk", "block1/mha/wo", "block1/mha/wq", "block1/mha/wv",
    "embed", "head/bias", "head/kernel", "ln_f/bias", "ln_f/scale",
    "pos",
]


def _param_paths(init):
    # eval_shape: structure without allocating a single weight — the
    # zoo's big backbones stay cheap to enumerate
    params = jax.eval_shape(lambda r: init(r).params, jax.random.key(0))
    return sorted(name for name, _ in partition.tree_paths(params))


def _builders():
    out = {name: spec.build(1, 3).init
           for name, spec in registry.REGISTRY.items()}
    out["lm"] = attention_lm(16, 32, embed_dim=8, num_heads=2,
                             mlp_dim=16, num_blocks=2).init
    return out


def test_golden_param_paths_frozen():
    builders = _builders()
    assert set(builders) == set(GOLDEN_PARAM_PATHS)
    for name, init in builders.items():
        paths = _param_paths(init)
        digest = hashlib.sha256("\n".join(paths).encode()).hexdigest()
        want_n, want_digest = GOLDEN_PARAM_PATHS[name]
        assert (len(paths), digest) == (want_n, want_digest), (
            f"{name} param key paths changed — a rename can silently "
            f"turn a partition rule (models/registry.py) into a dead "
            f"rule. If deliberate: update any rule naming the old "
            f"path, then refresh GOLDEN_PARAM_PATHS to "
            f"({len(paths)}, {digest!r}). Current paths:\n" +
            "\n".join(paths))


def test_golden_lm_paths_inline():
    assert _param_paths(_builders()["lm"]) == GOLDEN_LM_PATHS


def test_no_dead_rules_against_own_model():
    """Every registered rule set resolves against its own model's param
    tree with zero dead rules (specs() raises otherwise) and at least
    one actually-sharded leaf for the LM on a 2x2 mesh."""
    mesh = _mesh22()
    for name, init in _builders().items():
        rules = registry.get_partition_rules(name)
        params = jax.eval_shape(lambda r: init(r).params,
                                jax.random.key(0))
        specs = rules.specs(params, mesh=mesh)   # raises on dead rules
        if name == "lm":
            sharded = [s for s in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P)) if s != P()]
            assert sharded, "LM rules sharded nothing on a 2x2 mesh"


# -- 4. the ROADMAP item 2 acceptance gate ----------------------------------

_VOCAB, _T, _E, _MLP, _NB = 256, 32, 128, 512, 2


def _lm_state(mesh):
    model = attention_lm(_VOCAB, _T, embed_dim=_E, num_heads=4,
                         mlp_dim=_MLP, num_blocks=_NB, mesh=mesh)
    opt = rmsprop(1e-2)
    v = model.init(jax.random.key(0))
    return model, opt, TrainState(
        step=jnp.zeros((), jnp.int32), params=v.params,
        model_state=v.state, opt_state=opt.init(v.params))


def _train_steps(mesh, rules, steps=3):
    model, opt, state = _lm_state(mesh)
    sh = rules.shardings(mesh, state) if rules is not None else None
    step = jit_data_parallel(
        make_train_step(model, opt, next_token_loss), mesh,
        axis=meshlib.DATA_AXIS, state_shardings=sh)
    state = place_state(mesh, state, rules=rules)
    rng = np.random.default_rng(0)
    x = shard_batch(
        mesh,
        jnp.asarray((rng.integers(0, _VOCAB, (8, 1))
                     + np.arange(_T)) % _VOCAB, jnp.int32),
        axis=meshlib.DATA_AXIS)
    compiled = step.lower(state, x, x, jax.random.key(2)).compile()
    cost = prof.program_report(compiled, name="gate.train")
    key, losses = jax.random.key(1), []
    for _ in range(steps):
        key, sub = jax.random.split(key)
        state, m = compiled(state, x, x, sub)
        losses.append(float(m["loss"]))
    # zero jit growth: the jitted wrapper compiles once on first call
    # and repeated calls stay on that executable
    key, sub = jax.random.split(key)
    state, _ = step(state, x, x, sub)
    n0 = step._cache_size()
    key, sub = jax.random.split(key)
    state, _ = step(state, x, x, sub)
    assert step._cache_size() == n0 == 1
    return losses, cost.peak_hbm_bytes


def test_sharded_lm_trains_under_single_device_budget(devices):
    """THE capacity gate: an LM config whose params + optimizer state
    exceed one device's (notional) budget trains on FSDP and TP meshes
    with per-device peak HBM strictly below the replicated single-
    device figure — measured by XLA program accounting, not asserted —
    and fp-close losses."""
    rules = registry.get_partition_rules("lm")
    rep_losses, rep_hbm = _train_steps(
        meshlib.fsdp_tp_mesh(1, 1, 1), None)
    assert rep_hbm is not None, "backend reported no memory analysis"
    # the replicated figure DEFINES the single-device budget this
    # config exceeds; the sharded layouts must fit strictly under it
    budget = rep_hbm * 0.9
    for name, mesh in (("fsdp", meshlib.fsdp_tp_mesh(2, 1, 1)),
                       ("tp", meshlib.fsdp_tp_mesh(1, 2, 1))):
        losses, hbm = _train_steps(mesh, rules)
        assert hbm < budget < rep_hbm, (
            f"{name}: per-device peak {hbm / 2**20:.2f} MiB not under "
            f"the budget {budget / 2**20:.2f} MiB "
            f"(replicated {rep_hbm / 2**20:.2f} MiB)")
        # fp-close across layouts (documented tolerance: bf16-free
        # f32 math, GSPMD reduction-order drift only)
        np.testing.assert_allclose(losses, rep_losses, rtol=2e-3)


def test_sharded_lm_serves_token_identical_under_budget(devices):
    """The serve half of the gate: the SAME params decode token-
    IDENTICAL through a TP-sharded Generator (params over "model", KV
    on its seq ring — independent axes) with the decode program's
    per-device peak HBM below the replicated figure."""
    from idc_models_tpu.models.lm import Generator

    model = attention_lm(_VOCAB, _T, embed_dim=_E, num_heads=4,
                         mlp_dim=_MLP, num_blocks=_NB)
    params = jax.device_get(model.init(jax.random.key(0)).params)
    rules = registry.get_partition_rules("lm")
    prompt = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)

    def serve(mesh, rules):
        g = Generator(params, embed_dim=_E, num_heads=4, num_blocks=_NB,
                      t_max=_T, mesh=mesh, partition_rules=rules)
        toks = np.asarray(g(prompt, 10))
        costs = g.program_costs(batch=1, steps=8)
        return toks, costs

    t0, c0 = serve(meshlib.fsdp_tp_mesh(1, 1, 1), None)
    t1, c1 = serve(meshlib.fsdp_tp_mesh(1, 2, 1), rules)
    np.testing.assert_array_equal(t0, t1)        # bit-identical greedy
    for prog in ("lm.prefill", "lm.decode"):
        assert (c1[prog].peak_hbm_bytes
                < c0[prog].peak_hbm_bytes), prog
    # KV kept its ring layout while params sharded: independent axes
    g = Generator(params, embed_dim=_E, num_heads=4, num_blocks=_NB,
                  t_max=_T, mesh=meshlib.fsdp_tp_mesh(1, 2, 1),
                  partition_rules=rules)
    kc, _ = g.init_caches(1)[0]
    used = [a for e in kc.sharding.spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert meshlib.MODEL_AXIS not in used, (
        "KV cache sharded over the weight axis — the independent-axes "
        "contract broke")


def test_engine_serves_identical_with_tp_rules(devices):
    """The continuous-batching engine on a ("model", "seq") mesh with
    LM rules produces bit-identical token streams to the seq-only
    replicated engine."""
    from idc_models_tpu.serve import LMServer, Request

    model = attention_lm(64, _T, embed_dim=32, num_heads=2, mlp_dim=64,
                         num_blocks=2)
    params = jax.device_get(model.init(jax.random.key(0)).params)
    rules = registry.get_partition_rules("lm")

    def serve(mesh, rules):
        s = LMServer(params, embed_dim=32, num_heads=2, num_blocks=2,
                     t_max=_T, n_slots=2, window=4, mesh=mesh,
                     partition_rules=rules)
        s.submit(Request(id="a", prompt=(1, 2, 3), max_new_tokens=10))
        s.submit(Request(id="b", prompt=(4, 5), max_new_tokens=8))
        out = {}
        for _ in range(40):
            for r in s.step():
                out[r.id] = r.tokens
            if len(out) == 2:
                break
        s.close()
        return out

    assert serve(meshlib.seq_mesh(1), None) == serve(
        meshlib.fsdp_tp_mesh(1, 2, 1), rules)


def test_paged_engine_serves_identical_with_tp_rules(devices):
    """The PAGED twin under TP rules: pool pages + page tables keep
    their seq layout (the paged folds' tok_specs ride
    mesh.batch_axes), params shard over "model" — token streams bit-
    identical to the contiguous-mesh paged engine."""
    from idc_models_tpu.serve import LMServer, Request

    model = attention_lm(64, _T, embed_dim=32, num_heads=2, mlp_dim=64,
                         num_blocks=2)
    params = jax.device_get(model.init(jax.random.key(0)).params)
    rules = registry.get_partition_rules("lm")

    def serve(mesh, rules):
        s = LMServer(params, embed_dim=32, num_heads=2, num_blocks=2,
                     t_max=_T, n_slots=2, window=4, mesh=mesh,
                     partition_rules=rules, prefill_chunk=8,
                     kv_page_size=8, kv_pages=8)
        s.submit(Request(id="a", prompt=(1, 2, 3), max_new_tokens=10))
        s.submit(Request(id="b", prompt=(4, 5), max_new_tokens=8))
        out = {}
        for _ in range(60):
            for r in s.step():
                out[r.id] = r.tokens
            if len(out) == 2:
                break
        s.close()
        return out

    assert serve(meshlib.seq_mesh(1), None) == serve(
        meshlib.fsdp_tp_mesh(1, 2, 1), rules)


def test_engine_model_axis_without_rules_teaches(devices):
    from idc_models_tpu.serve.engine import SlotEngine

    model = attention_lm(64, _T, embed_dim=32, num_heads=2, mlp_dim=64,
                         num_blocks=2)
    params = model.init(jax.random.key(0)).params
    with pytest.raises(ValueError, match="partition_rules"):
        SlotEngine(params, embed_dim=32, num_heads=2, num_blocks=2,
                   t_max=_T, mesh=meshlib.fsdp_tp_mesh(1, 2, 1))


def test_fit_identical_with_replicated_rules(devices):
    """train/loop.fit routes placement through the rules layer when
    given one; replicated rules must be BIT-identical to the historical
    no-rules path (same placement, same executables' math)."""
    from idc_models_tpu.data.idc import ArrayDataset
    from idc_models_tpu.models import small_cnn
    from idc_models_tpu.train import create_train_state, fit
    from idc_models_tpu.train.losses import binary_cross_entropy

    mesh = meshlib.data_mesh(4)
    rng = np.random.default_rng(0)
    ds = ArrayDataset(rng.random((32, 10, 10, 3)).astype(np.float32),
                      rng.integers(0, 2, 32).astype(np.int32))

    def run(rules):
        model = small_cnn(10, 3, 1)
        opt = rmsprop(1e-3)
        state = create_train_state(model, opt, jax.random.key(0))
        state, hist = fit(model, opt, binary_cross_entropy, state, ds,
                          None, mesh, epochs=1, batch_size=8,
                          verbose=False, rules=rules)
        return jax.device_get(state.params), hist["loss"]

    p0, l0 = run(None)
    p1, l1 = run(registry.REPLICATED_RULES)
    assert l0 == l1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 p0, p1)


def test_population_round_identical_with_rules(devices):
    """Federated: the streamed wave accumulators inherit the rules'
    shardings (replicated on a client mesh) — the round is bit-
    identical with and without the rules plumbing."""
    from idc_models_tpu.federated import initialize_server
    from idc_models_tpu.federated.population import (
        ClientPopulation, CohortSampler, make_population_round,
    )
    from idc_models_tpu.models import small_cnn
    from idc_models_tpu.train.losses import binary_cross_entropy

    mesh = meshlib.client_mesh(4)
    model = small_cnn(10, 3, 1)
    pop = ClientPopulation(64, examples_per_client=8, image_size=10,
                           seed=0)
    opt = rmsprop(1e-3)

    def run(rules):
        sampler = CohortSampler(pop, cohort_size=8, seed=1)
        rnd = make_population_round(
            model, opt, binary_cross_entropy, mesh, pop, sampler,
            wave_size=4, rules=rules)
        server = initialize_server(model, jax.random.key(0))
        server, metrics = rnd(server, rng=jax.random.key(2),
                              round_idx=0)
        return (jax.device_get(server.params),
                float(metrics["loss"]))

    p0, l0 = run(None)
    p1, l1 = run(registry.REPLICATED_RULES)
    assert l0 == l1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 p0, p1)


# -- docs completeness ------------------------------------------------------


def test_sharding_doc_complete():
    """docs/SHARDING.md documents every LM rule pattern, the public
    surface, and the CLI flags — so the layer stays discoverable."""
    from pathlib import Path

    doc = (Path(__file__).parent.parent / "docs"
           / "SHARDING.md").read_text()
    for pattern in registry.LM_RULES.patterns:
        assert f"`{pattern}`" in doc, (
            f"LM rule {pattern!r} undocumented in docs/SHARDING.md")
    for needle in ("PartitionRules", "shard_tree", "gather_tree",
                   "--fsdp", "--tp", "right-align", "dead rule",
                   "peak_hbm_bytes",
                   # ISSUE 17: the checkpoint/rollout section rides the
                   # same gate — the rules layer is its addressing scheme
                   "MANIFEST.json", "save_sharded", "restore_sharded",
                   "peak_host_bytes", "canary", "swap_params",
                   "swap_adapters", "--rollout", "--canary-fraction",
                   "--checkpoint-every", "--save-ckpt",
                   "--rollout-adapters"):
        assert needle in doc, (
            f"docs/SHARDING.md missing {needle!r}")
