"""Compile a spec-built serve configuration's chunk and decode-window
programs for a v5e chip that is described, not attached (no chip time):
does the compiler take them at these slots, and how many bytes do the
arguments and the temporaries need? `aot_check.py serve` does the same
for `gpt2-large`.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_check_spec.py \
        --config laguna-s-2.1 --slots 48,40,32 [--window 8,1]

A compile that passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def _report(program, compiled, t0, **extra):
    m = compiled.memory_analysis()
    print(json.dumps({
        "program": program, **extra, "compile_s": round(time.time() - t0, 1),
        "argument_gb": round(m.argument_size_in_bytes / 1e9, 3),
        "output_gb": round(m.output_size_in_bytes / 1e9, 3),
        "alias_gb": round(m.alias_size_in_bytes / 1e9, 3),
        "temp_gb": round(m.temp_size_in_bytes / 1e9, 3),
        "args_plus_temp_gb": round((m.argument_size_in_bytes
                                    + m.temp_size_in_bytes) / 1e9, 3)}),
        flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", default="48")
    ap.add_argument("--window", default=None)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models import lm
    from idc_models_tpu.serve.engine import _engine_fns

    jax.config.update("jax_enable_compilation_cache", False)
    config = json.loads((BENCH_DIR / "configs" / f"{args.config}.json").read_text())
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    e = config["engine"]
    spec = runner.model_spec(config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh([topo.devices[0]], (meshlib.SEQ_AXIS,))
    rep = NamedSharding(mesh, P())
    cache_sh = meshlib.batch_seq_sharding(mesh, trailing=0)
    sds = lambda shape, dtype, sh=rep: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    p_shapes = jax.eval_shape(lambda k: lm.init_params(
        spec, config["vocab_size"], k, mlp_dim=config["intermediate_size"],
        expert_dim=config["moe_intermediate_size"]), jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), p_shapes)
    print(json.dumps({"weights_gb": round(sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(p_shapes)) / 1e9, 3)}))
    cfg = lm._serve_config(p_shapes, spec=spec, t_max=e["t_max"], mesh=mesh,
                           cache_dtype=jnp.dtype(e["cache_dtype"]))

    def caches(n):
        return tuple(
            (sds((n, spec.cache_len(i, e["t_max"]), l.kv_heads, l.head_dim),
                 jnp.dtype(e["cache_dtype"]), cache_sh),) * 2
            for i, l in enumerate(spec.layers))

    sfns = lm._serving_fns(cfg)
    t0 = time.time()
    chunk = sfns.prefill_chunk.lower(
        params, caches(1), sds((1, e["prefill_chunk"]), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    _report("prefill_chunk", chunk, t0, chunk=e["prefill_chunk"])
    efns = _engine_fns(cfg, 0)
    windows = [int(w) for w in (args.window or str(e["window"])).split(",")]
    for n in (int(x) for x in args.slots.split(",")):
        i32 = sds((n,), jnp.int32)
        a = (params, caches(n), sds((n, config["vocab_size"]), jnp.float32),
             sds((n, 2), jnp.uint32), i32, i32, i32, (), (), i32)
        for w in windows:
            t0 = time.time()
            try:
                win = efns.window.lower(*a, w).compile()
                _report("window", win, t0, n_slots=n, window=w)
            except Exception as err:      # the compiler's refusal is the answer
                msg = str(err)
                at = msg.find("Used ")
                print(json.dumps({"program": "window", "n_slots": n, "window": w,
                                  "refused": msg[at:at + 90] if at >= 0
                                  else msg[:300]}), flush=True)


if __name__ == "__main__":
    main()
