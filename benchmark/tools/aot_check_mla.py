"""`aot_check_dsa.py` for a configuration of latent attention (one cached
row a position, `ring_decode.latent_cache_shape`) whose layers select,
or share a selection: compile its in-place chunk program and its decode
window for a v5e chip that is described, not attached (no chip time),
and print what the arguments and the temporaries need.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_check_mla.py \
        --config glm-5.2 [--slots 16] [--window 8]

`--dump DIR` instead writes the compiled text of the window, chunk and
insert programs of the three configurations served before latent
attention (`gpt2-large`, `laguna-s-2.1`, `keye-vl-2.0-30b-a3b`, each at
its cell's slots), with metadata and source locations stripped, one file
a program, and prints each text's sha256; `--tree PATH` compiles the
package of another checkout (a parent commit unpacked with `git
archive`). Copy each tree to ONE path before its dump: a Mosaic kernel's
serialized payload embeds the path of its source. Two dumps that hash
alike show that this tree left those programs as they were.

A compile that passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))

# the configurations whose programs this tree has to leave alone
LEFT_ALONE = ("gpt2-large", "laguna-s-2.1", "keye-vl-2.0-30b-a3b")


def strip(text: str) -> str:
    """Compiled HLO text without what moves when only source lines move:
    every `metadata={...}` and the trailing stack-frame tables."""
    text = re.sub(r",? metadata=\{[^{}]*\}", "", text)
    return text.split("\nFileNames\n")[0]


def _setup():
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from idc_models_tpu import mesh as meshlib

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh([topo.devices[0]], (meshlib.SEQ_AXIS,))
    rep = NamedSharding(mesh, P())
    cache_sh = meshlib.batch_seq_sharding(mesh, trailing=0)
    sds = lambda shape, dtype, sh=rep: jax.ShapeDtypeStruct(shape, dtype,
                                                             sharding=sh)
    return mesh, sds, cache_sh


def _model(name: str, mesh):
    """(serve config, abstract params, vocab, engine section) of one
    configuration file."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models import lm

    config = json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())
    e = config["engine"]
    if "model" in config:                        # attention_lm's block
        m = config["model"]
        model = lm.attention_lm(m["vocab_size"], m["n_positions"],
                                embed_dim=m["embed_dim"],
                                num_heads=m["num_heads"], mlp_dim=m["mlp_dim"],
                                num_blocks=m["num_blocks"])
        shapes = jax.eval_shape(lambda k: model.init(k).params,
                                jax.random.key(0))
        cfg = lm._serve_config(shapes, embed_dim=m["embed_dim"],
                               num_heads=m["num_heads"],
                               num_blocks=m["num_blocks"], t_max=e["t_max"],
                               mesh=mesh,
                               cache_dtype=jnp.dtype(e["cache_dtype"]))
        return cfg, shapes, m["vocab_size"], e
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    spec = runner.model_spec(config)
    shapes = jax.eval_shape(lambda k: lm.init_params(
        spec, config["vocab_size"], k, mlp_dim=config.get("intermediate_size", 0),
        expert_dim=config["moe_intermediate_size"]), jax.random.key(0))
    cfg = lm._serve_config(shapes, spec=spec, t_max=e["t_max"], mesh=mesh,
                           cache_dtype=jnp.dtype(e["cache_dtype"]))
    return cfg, shapes, config["vocab_size"], e


def compile_programs(name: str, *, slots=None, window=None) -> dict:
    """The compiled window, chunk and insert programs of one
    configuration at its cell's sizes (or `slots` / `window`)."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu import ring_decode as rd
    from idc_models_tpu.models import lm
    from idc_models_tpu.serve.engine import _engine_fns

    mesh, sds, cache_sh = _setup()
    cfg, shapes, vocab, e = _model(name, mesh)
    spec, t_max = cfg.spec, e["t_max"]
    n, w, c = slots or e["n_slots"], window or e["window"], e["prefill_chunk"]
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    dt = jnp.dtype(e["cache_dtype"])

    def caches(b):
        out = []
        for i, l in enumerate(spec.layers):
            if getattr(l, "latent", None) is not None:
                layer = (sds(rd.latent_cache_shape(b, t_max, l.latent.row),
                             dt, cache_sh),)
            else:
                layer = (sds(rd.cache_shape(b, spec.cache_len(i, t_max),
                                            l.kv_heads, l.head_dim),
                             dt, cache_sh),) * 2
            if l.indexer is not None:
                layer += (sds(rd.index_cache_shape(b, t_max, l.indexer.dim),
                              dt, cache_sh),)
            out.append(layer)
        return tuple(out)

    efns = _engine_fns(cfg, 0)
    i32, s32 = sds((n,), jnp.int32), sds((), jnp.int32)
    logits, kd = sds((n, vocab), jnp.float32), sds((n, 2), jnp.uint32)
    logits1, kd1 = sds((1, vocab), jnp.float32), sds((2,), jnp.uint32)
    tokens = sds((1, c), jnp.int32)
    out, t0 = {}, time.time()
    out["window"] = efns.window.lower(params, caches(n), logits, kd, i32, i32,
                                      i32, (), (), i32, w).compile()
    if spec.sparse:
        out["chunk"] = efns.prefill_chunk.lower(
            params, caches(n), s32, tokens, s32, s32).compile()
        out["insert"] = efns.insert.lower(
            logits, kd, i32, i32, i32, i32, logits1, s32, s32, s32, s32, s32,
            kd1).compile()
    else:
        out["chunk"] = lm._serving_fns(cfg).prefill_chunk.lower(
            params, caches(1), tokens, s32, s32).compile()
        out["insert"] = efns.insert.lower(
            caches(n), logits, kd, i32, i32, i32, i32, (), caches(1), logits1,
            s32, s32, s32, s32, s32, kd1).compile()
    print(json.dumps({"config": name, "n_slots": n, "window": w,
                      "compile_s": round(time.time() - t0, 1)}), flush=True)
    return out


def _report(program, compiled, **extra):
    m = compiled.memory_analysis()
    print(json.dumps({
        "program": program, **extra,
        "argument_gb": round(m.argument_size_in_bytes / 1e9, 3),
        "output_gb": round(m.output_size_in_bytes / 1e9, 3),
        "alias_gb": round(m.alias_size_in_bytes / 1e9, 3),
        "temp_gb": round(m.temp_size_in_bytes / 1e9, 3),
        "args_plus_temp_gb": round((m.argument_size_in_bytes
                                    + m.temp_size_in_bytes) / 1e9, 3)}),
        flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="glm-5.2")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--dump", default=None,
                    help="write the stripped text of the programs of the "
                         "configurations served before latent attention")
    ap.add_argument("--tree", default=None,
                    help="compile the package of this checkout instead")
    args = ap.parse_args()
    if args.tree:
        sys.path.insert(0, str(Path(args.tree).resolve()))
    import idc_models_tpu

    print(json.dumps({"package": str(Path(idc_models_tpu.__file__).parent)}),
          flush=True)
    if args.dump:
        out = Path(args.dump)
        out.mkdir(parents=True, exist_ok=True)
        for name in LEFT_ALONE:
            for prog, compiled in compile_programs(name).items():
                text = strip(compiled.as_text())
                (out / f"{name}.{prog}.txt").write_text(text)
                print(json.dumps({"config": name, "program": prog,
                                  "sha256": hashlib.sha256(
                                      text.encode()).hexdigest()[:16]}),
                      flush=True)
        return
    import jax

    _, sds, _ = _setup()
    cfg, shapes, _, e = _model(args.config, _setup()[0])
    print(json.dumps({"weights_gb": round(sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) / 1e9, 3)}))
    progs = compile_programs(args.config, slots=args.slots, window=args.window)
    for prog in ("chunk", "window"):
        _report(prog, progs[prog], n_slots=args.slots or e["n_slots"])


if __name__ == "__main__":
    main()
