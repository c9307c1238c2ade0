"""The contiguous window program reads its caches where they rest (PR 30).

Compiled here for a described TPU v5e (no chip: the TPU's compiler is
installed, `jax.experimental.topologies` describes the device), at a
rehearsal size with `gpt2-large`'s 20 heads of 64, a width
`ring_decode.cache_shape` merges into rows. Kept apart, (heads, 64) is
padded to whole (8, 128) tiles inside the program and re-laid at its
edges: one whole-cache `copy` in and one out for every cache array, and
temporaries larger than the caches. This test reads the compiled
program itself, which is what stops a later change to the window from
losing that again (PERF.md section 6, PR 28 and PR 30).

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and every xdist worker imports every
test file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from idc_models_tpu import mesh as meshlib
from idc_models_tpu import ring_decode as rd
from idc_models_tpu.models.lm import (_serve_config, _serving_fns,
                                      attention_lm)
from idc_models_tpu.observe import profile as prof
from idc_models_tpu.serve.engine import _engine_fns

# gpt2-large's heads (20 of 64: padded to 24 of 128 when kept apart) and
# cache length, under a rehearsal-sized model
VOCAB, T_MAX, HEADS, HEAD_DIM, BLOCKS, SLOTS, WINDOW, CHUNK = (
    512, 1024, 20, 64, 2, 8, 8, 128)


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield Mesh([topo.devices[0]], (meshlib.SEQ_AXIS,))
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def programs(chip):
    """(cfg, params, caches(n)) as shapes placed on the described chip."""
    rep = NamedSharding(chip, P())
    model = attention_lm(VOCAB, T_MAX, embed_dim=HEADS * HEAD_DIM,
                         num_heads=HEADS, mlp_dim=512, num_blocks=BLOCKS)
    shapes = jax.eval_shape(lambda k: model.init(k).params,
                            jax.random.key(0))

    def sds(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = _serve_config(shapes, embed_dim=HEADS * HEAD_DIM,
                        num_heads=HEADS, num_blocks=BLOCKS, t_max=T_MAX,
                        mesh=chip, cache_dtype=jnp.bfloat16)

    def caches(n):
        c = sds(rd.cache_shape(n, T_MAX, HEADS, HEAD_DIM), jnp.bfloat16,
                rd.cache_sharding(chip))
        return tuple((c, c) for _ in range(BLOCKS))

    return cfg, jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes), \
        caches, sds


def _whole_cache_copies(text, n):
    """`copy` instructions of the compiled program whose result holds a
    whole cache array's elements, in whatever shape."""
    want = n * T_MAX * HEADS * HEAD_DIM
    return [m.group(0) for m in
            re.finditer(r"= \w+\[([\d,]+)\]\S* copy\(", text)
            if math.prod(int(x) for x in m.group(1).split(",")) == want]


def _cache_bytes(n):
    return 2 * BLOCKS * n * T_MAX * HEADS * HEAD_DIM * 2


def _append_loops(text, n):
    """`while` instructions of the compiled program that are a scatter
    expanded into a loop over a whole cache array: either the loop's
    `op_name` names a `scatter`, or its state is what such a loop
    carries, ONE array of a whole cache's elements beside one new row a
    slot (`n * HEADS * HEAD_DIM` elements). The window's own loop
    carries every cache and the attend's loop over blocks a layer's
    two: neither matches."""
    row = n * HEADS * HEAD_DIM
    found = []
    for m in re.finditer(r"^.*? = \((.*?)\) while\(.*$", text, re.M):
        sizes = [math.prod(int(x) for x in dims.split(","))
                 for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))]
        if sizes.count(row * T_MAX) and (
                "scatter" in m.group(0)
                or (sizes.count(row * T_MAX) == 1 and row in sizes)):
            found.append(m.group(0)[:200])
    return found


def test_serve_programs_copy_no_cache_and_stage_none(programs):
    """The decode window and the prefill chunk, in ONE test: whichever
    xdist worker runs it is the one process that loads the TPU's
    compiler.

    Since PR 33 it also fails when the append's expansion is back
    (`_append_loops`). What it matches, in the window compiled at this
    size from the tree before PR 33: four instructions, one for each of
    the two layers' K and V caches,

        %while.142 = (s32[], bf16[8,1024,1280], s32[8,3],
                      bf16[8,1,1280], s32[], s32[3], s32[])
            while(...), condition=%wide.while_cond.1,
            body=%wide.while_body.1.sunk,
            op_name=".../attn_full/vmap(vmap())/scatter"

    the per-row read, select and `dynamic_update_slice` under `vmap`,
    which XLA turns into a scatter and its scatter expander into a loop
    of one trip a slot (8 here, 10 in the `gpt2-large` cells) over the
    whole cache array, four small operations a trip on each of the
    window's token steps. With the append as one dropping scatter the
    program holds none: each cache's scatter is one fusion
    (`kind=kCustom`, `op_name=".../attn_full/scatter"`), and the only
    loops left that carry a cache are the window's own and the
    attend's over blocks."""
    cfg, params, caches, sds = programs
    i32 = sds((SLOTS,), jnp.int32)
    win = _engine_fns(cfg, 0).window.lower(
        params, caches(SLOTS), sds((SLOTS, VOCAB), jnp.float32),
        sds((SLOTS, 2), jnp.uint32), i32, i32, i32, (), (), i32,
        WINDOW).compile()
    assert _whole_cache_copies(win.as_text(), SLOTS) == []
    assert _append_loops(win.as_text(), SLOTS) == []
    temp = prof.program_report(win, name="serve.window").temp_bytes
    assert temp < _cache_bytes(SLOTS) / 10, (temp, _cache_bytes(SLOTS))
    chunk = _serving_fns(cfg).prefill_chunk.lower(
        params, caches(1), sds((1, CHUNK), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32)).compile()
    assert _whole_cache_copies(chunk.as_text(), 1) == []


# a layer with an indexer at the served model's widths
# (4 KV heads of 128, 16 index heads of 64), under a rehearsal-sized
# model; two of the decode fold's groups of live rows and one slot
S_T_MAX, S_TOPK, S_KV, S_D, S_DI = 4096, 256, 4, 128, 64
S_GROUP = rd._FOLD_GROUP
S_SLOTS = 2 * S_GROUP + 1


def _instructions(text):
    """(name, result type, op, operand names, line) of every
    instruction of a compiled program's text."""
    rx = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ([\w\-]+)\((.*)$")
    for line in text.splitlines():
        m = rx.match(line)
        if m:
            yield (m.group(1), m.group(2), m.group(3),
                   re.findall(r"%[\w.\-]+", m.group(4).split("),")[0]), line)


def test_sparse_window_reads_index_keys_and_selected_rows_alone(chip):
    """The decode window of a spec with an indexer, compiled for a
    described v5e: the only operations that take a whole K or V cache as
    an operand are the append's scatter and, inside the loop over the
    live rows' groups, the gather of the selected rows (and the plumbing
    that carries the arrays through the loops); the index cache rests as
    it is stored, positions in the lanes, and no cache is copied whole,
    at the program's edges or inside it (stored `[S, T, 64]` the
    compiler re-laid every index cache twice a window and once a token
    step: PERF.md section 6). The selection and the gather are a GROUP
    of rows tall, not the batch: a fold that selects and gathers for
    every slot again, live or not, fails here without a chip (PERF.md
    section 6); and the selection is the Pallas kernel: nothing under
    `dsa_select` sorts."""
    from idc_models_tpu.models import lm

    rep = NamedSharding(chip, P())
    layer = lm.LayerSpec(
        8, S_KV, S_D, rotary=lm.Rotary(1e7, S_D), ffn="swiglu", qk_norm=True,
        indexer=lm.Indexer(16, S_DI, S_TOPK, rotary=lm.Rotary(1e7, S_DI)))
    spec = lm.ModelSpec(256, (layer, layer), norm="rmsnorm",
                        learned_pos=False, param_dtype="bfloat16")
    shapes = jax.eval_shape(lambda k: lm.init_params(spec, VOCAB, k,
                                                     mlp_dim=512),
                            jax.random.key(0))

    def sds(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = _serve_config(shapes, spec=spec, t_max=S_T_MAX, mesh=chip,
                        cache_dtype=jnp.bfloat16)
    kv = sds(rd.cache_shape(S_SLOTS, S_T_MAX, S_KV, S_D), jnp.bfloat16,
             rd.cache_sharding(chip))
    ix = sds(rd.index_cache_shape(S_SLOTS, S_T_MAX, S_DI), jnp.bfloat16,
             rd.cache_sharding(chip))
    assert kv.shape == (S_SLOTS, S_T_MAX, S_KV, S_D)
    assert ix.shape == (S_SLOTS, S_DI, S_T_MAX)
    caches = ((kv, kv, ix),) * 2
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    i32 = sds((S_SLOTS,), jnp.int32)
    efns = _engine_fns(cfg, 0)
    win = efns.window.lower(
        params, caches, sds((S_SLOTS, VOCAB), jnp.float32),
        sds((S_SLOTS, 2), jnp.uint32), i32, i32, i32, (), (), i32,
        WINDOW).compile().as_text()
    kv_type = f"bf16[{S_SLOTS},{S_T_MAX},{S_KV},{S_D}]"
    ix_type = f"bf16[{S_SLOTS},{S_DI},{S_T_MAX}]"
    ins = list(_instructions(win))
    typed = {name: t for name, t, *_ in ins}
    plumbing = {"parameter", "get-tuple-element", "tuple", "while", "call",
                "conditional", "bitcast", "optimization-barrier"}
    readers = [(op, t, line) for _, t, op, operands, line in ins
               if op not in plumbing
               and any(typed.get(o, "").startswith(kv_type) for o in operands)]
    assert readers, "the program names no K/V cache at all"
    for op, _, line in readers:
        name = re.search(r'op_name="([^"]*)"', line)
        assert name and re.search(
            r"(^|/)(attn_sparse/scatter|while/body/attn_sparse/gather)$",
            name.group(1)), line[:300]
    assert {op for op, *_ in readers} >= {"gather", "scatter"}
    # two gathers (K, V) a layer, each of one group's rows, and one
    # selection kernel (`decode_topk`) a layer over one group's scores
    gathered = [t.split("{")[0] for op, t, _ in readers if op == "gather"]
    assert gathered == [f"bf16[{S_GROUP},{S_TOPK},{S_KV},{S_D}]"] * 4
    assert not re.findall(r" sort\(.*dsa_select", win)
    selects = [[typed[o].split("{")[0] for o in operands]
               for _, t, op, operands, l in ins if op == "custom-call"
               and "decode_topk" in l and "dsa_select" in l]
    assert selects == [["s32[1]", f"f32[{S_GROUP},{S_T_MAX}]"]] * 2, selects
    # whole-cache copies: none, of K/V or of the index keys, in either
    # program; and the index cache keeps its stored layout throughout
    chunk = efns.prefill_chunk.lower(
        params, caches, sds((), jnp.int32), sds((1, CHUNK), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile().as_text()
    # the chunk's selection is ONE Mosaic kernel a layer, under
    # `dsa_select`, and its counting passes no longer read a [C, T] array
    # of sortable keys from HBM (PERF.md section 6): a fold that
    # brings the XLA passes back fails here without a chip
    chunk_ins = list(_instructions(chunk))
    kernels = [l for _, _, op, _, l in chunk_ins if op == "custom-call"
               and "tpu_custom_call" in l and "dsa_select" in l]
    assert len(kernels) == 2, [l[:300] for l in kernels]
    keys = f"u32[{CHUNK},{S_T_MAX}]"
    chunk_typed = {name: t for name, t, *_ in chunk_ins}
    assert not [l[:300] for _, t, op, operands, l in chunk_ins
                if "reduce" in op + l.split(" = ")[0] and any(
                    chunk_typed.get(o, "").startswith(keys)
                    for o in operands)]
    assert not [l[:300] for _, t, *_, l in chunk_ins if t.startswith(keys)]
    for text in (win, chunk):
        assert not [l for _, t, op, _, l in _instructions(text)
                    if op == "copy" and t.startswith((kv_type, ix_type))]
        layouts = {t.split("{")[1].split(":")[0]
                   for _, t, *_ in _instructions(text)
                   if t.startswith(ix_type)}
        assert layouts == {"2,1,0"}, layouts


def test_latent_window_keeps_its_rows_as_stored_and_copies_none(chip):
    """The decode window and the in-place chunk of a spec of latent
    attention at the published row (a latent of 512 beside 64 rotated
    key values, 576 values padded to 640 lanes by
    `ring_decode.latent_cache_shape`), one layer selecting and one
    sharing its selection, compiled for a described v5e: no latent cache
    is copied whole or kept in another layout, and the decode fold
    gathers ONE row a selected position for a group of live rows (key
    and value at once). Declared as rows of 576 lanes the compiler kept
    the caches with the positions minor at the program's edges and
    re-laid every one into rows and back around each window, 3.5 GB of
    temporaries at the served size (PERF.md section 4)."""
    from idc_models_tpu.models import lm

    rep = NamedSharding(chip, P())
    config = {
        "num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 8,
        "q_lora_rank": 64, "kv_lora_rank": 512, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 64, "qk_head_dim": 96, "v_head_dim": 32,
        "index_n_heads": 4, "index_head_dim": 128, "index_topk": S_TOPK,
        "indexer_types": ["full", "shared"],
        "mlp_layer_types": ["dense", "dense"], "n_routed_experts": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 8e6, "rope_type": "default"},
        "rope_interleave": True, "indexer_rope_interleave": True}
    spec = lm.glm_spec(config)
    shapes = jax.eval_shape(lambda k: lm.init_params(spec, VOCAB, k,
                                                     mlp_dim=512),
                            jax.random.key(0))

    def sds(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = _serve_config(shapes, spec=spec, t_max=S_T_MAX, mesh=chip,
                        cache_dtype=jnp.bfloat16)
    lat = sds(rd.latent_cache_shape(S_SLOTS, S_T_MAX, 576), jnp.bfloat16,
              rd.cache_sharding(chip))
    ix = sds(rd.index_cache_shape(S_SLOTS, S_T_MAX, 128), jnp.bfloat16,
             rd.cache_sharding(chip))
    assert lat.shape == (S_SLOTS, S_T_MAX, 640)
    caches = ((lat, ix), (lat,))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    i32 = sds((S_SLOTS,), jnp.int32)
    efns = _engine_fns(cfg, 0)
    win = efns.window.lower(
        params, caches, sds((S_SLOTS, VOCAB), jnp.float32),
        sds((S_SLOTS, 2), jnp.uint32), i32, i32, i32, (), (), i32,
        WINDOW).compile()
    chunk = efns.prefill_chunk.lower(
        params, caches, sds((), jnp.int32), sds((1, CHUNK), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    lat_type = f"bf16[{S_SLOTS},{S_T_MAX},640]"
    for text in (win.as_text(), chunk.as_text()):
        ins = list(_instructions(text))
        assert not [l[:300] for _, t, op, _, l in ins
                    if op == "copy" and t.startswith(lat_type)]
        layouts = {t.split("{")[1].split(":")[0] for _, t, *_ in ins
                   if t.startswith(lat_type)}
        assert layouts == {"2,1,0"}, layouts
    gathered = [t.split("{")[0] for _, t, op, _, _ in _instructions(win.as_text())
                if op == "gather" and t.startswith(f"bf16[{S_GROUP},{S_TOPK},")]
    assert gathered == [f"bf16[{S_GROUP},{S_TOPK},640]"] * 2, gathered
    temp = prof.program_report(win, name="serve.window").temp_bytes
    assert temp < 2 * math.prod(lat.shape) * 2 / 10, temp
