"""Compile the cells' device programs at their real sizes for a described
(not attached) `v5e:2x2`, here in the sandbox, and print what the
compiler says each needs: it refuses what the chip would refuse, at no
chip time. Nothing runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_check.py serve --slots 8,12,16
    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_check.py train --batches 2048,8192

It reaches into the program (`serve.engine._engine_fns`,
`models.lm._serving_fns`, `train.step.make_train_step`) for the jitted
bodies, because the program builds its meshes from `jax.devices()`,
which is the CPU here. The benchmark's runs never do that.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def _report(name, compiled, t0, **extra):
    ma = compiled.memory_analysis()
    row = {"program": name, "compile_s": round(time.time() - t0, 1),
           "arguments_gb": ma.argument_size_in_bytes / 1e9,
           "temp_gb": ma.temp_size_in_bytes / 1e9,
           "output_gb": ma.output_size_in_bytes / 1e9,
           "aliased_gb": ma.alias_size_in_bytes / 1e9,
           "total_gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                        + ma.output_size_in_bytes
                        - ma.alias_size_in_bytes) / 1e9, **extra}
    print(json.dumps(row), flush=True)


def serve(slots_list):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.runners import serve_open_loop as sol  # noqa: F401
    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models.lm import (_serve_config, _serving_fns,
                                          attention_lm)
    from idc_models_tpu.serve.engine import _engine_fns

    cfg_file = json.loads((BENCH_DIR / "configs" / "gpt2-large.json").read_text())
    m, e = cfg_file["model"], cfg_file["engine"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh([topo.devices[0]], (meshlib.SEQ_AXIS,))
    rep = NamedSharding(mesh, P())
    model = attention_lm(m["vocab_size"], m["n_positions"],
                         embed_dim=m["embed_dim"], num_heads=m["num_heads"],
                         mlp_dim=m["mlp_dim"], num_blocks=m["num_blocks"])
    p_shapes = jax.eval_shape(lambda k: model.init(k).params, jax.random.key(0))
    sds = lambda shape, dtype, sh=rep: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), p_shapes)
    cfg = _serve_config(p_shapes, embed_dim=m["embed_dim"],
                        num_heads=m["num_heads"], num_blocks=m["num_blocks"],
                        t_max=e["t_max"], mesh=mesh,
                        cache_dtype=jnp.dtype(e["cache_dtype"]))
    cache_sh = meshlib.batch_seq_sharding(mesh, trailing=0)
    hd = m["embed_dim"] // m["num_heads"]

    def caches(n):
        c = sds((n, e["t_max"], m["num_heads"], hd), jnp.dtype(e["cache_dtype"]),
                cache_sh)
        return tuple((c, c) for _ in range(m["num_blocks"]))

    sfns = _serving_fns(cfg)
    t0 = time.time()
    chunk = sfns.prefill_chunk.lower(
        params, caches(1), sds((1, e["prefill_chunk"]), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    _report("prefill_chunk", chunk, t0, chunk=e["prefill_chunk"])
    efns = _engine_fns(cfg, 0)
    for n in slots_list:
        i32 = sds((n,), jnp.int32)
        args = (params, caches(n), sds((n, m["vocab_size"]), jnp.float32),
                sds((n, 2), jnp.uint32), i32, i32, i32, (), (), i32)
        t0 = time.time()
        try:
            win = efns.window.lower(*args, e["window"]).compile()
            _report("window", win, t0, n_slots=n, window=e["window"])
        except Exception as err:          # the compiler's refusal is the answer
            msg = str(err)
            at = msg.find("Used ")
            print(json.dumps({"program": "window", "n_slots": n,
                              "refused": msg[at:at + 90] if at >= 0
                              else msg[:200]}), flush=True)


def train(batches):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from idc_models_tpu.models.vgg import fine_tune_mask, vgg16
    from idc_models_tpu.train import create_train_state, make_train_step, rmsprop
    from idc_models_tpu.train.losses import binary_cross_entropy

    cfg = json.loads((BENCH_DIR / "configs" / "vgg16-idc.json").read_text())["model"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    model = vgg16(num_outputs=1)
    shapes = jax.eval_shape(lambda k: model.init(k).params, jax.random.key(0))
    opt = rmsprop(cfg["lr"], trainable_mask=fine_tune_mask(shapes, cfg["fine_tune_at"]))
    st = jax.eval_shape(lambda k: create_train_state(model, opt, k), jax.random.key(0))
    step = make_train_step(model, opt, binary_cross_entropy,
                           compute_dtype=jnp.dtype(cfg["compute_dtype"]))
    for chips in (1, 4):
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
        rep, bsh = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        put = lambda t, sh: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), t)
        for per_chip in batches:
            b = per_chip * chips
            s = cfg["image_size"]
            t0 = time.time()
            c = jax.jit(step, in_shardings=(rep, bsh, bsh, rep),
                        out_shardings=(rep, None), donate_argnums=(0,)).lower(
                put(st, rep), jax.ShapeDtypeStruct((b, s, s, 3), jnp.float32, sharding=bsh),
                jax.ShapeDtypeStruct((b,), jnp.int32, sharding=bsh),
                jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)).compile()
            _report("train_step", c, t0, chips=chips, batch_per_chip=per_chip,
                    all_reduces=c.as_text().count(" all-reduce("))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("serve", "train"))
    ap.add_argument("--slots", default="8,12,16")
    ap.add_argument("--batches", default="2048,8192")
    args = ap.parse_args()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    if args.what == "serve":
        serve([int(x) for x in args.slots.split(",")])
    else:
        train([int(x) for x in args.batches.split(",")])


if __name__ == "__main__":
    main()
