"""`aot_check_spec.py` for a configuration whose layers cache index keys
beside K/V and whose engine prefills in place: compile its in-place
chunk program and its decode window for a v5e chip that is described,
not attached (no chip time), and print what the arguments and the
temporaries need.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_check_dsa.py \
        --config keye-vl-2.0-30b-a3b --slots 16 [--window 8,1] [--hlo DIR]

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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", default="16")
    ap.add_argument("--window", default=None)
    ap.add_argument("--hlo", default=None,
                    help="directory to write the compiled programs' text to")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.tools.aot_check_spec import _report
    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu import ring_decode as rd
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
        spec, config["vocab_size"], k,
        expert_dim=config["moe_intermediate_size"]), jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), p_shapes)
    print(json.dumps({"weights_gb": round(sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(p_shapes)) / 1e9, 3)}))
    cfg = lm._serve_config(p_shapes, spec=spec, t_max=e["t_max"], mesh=mesh,
                           cache_dtype=jnp.dtype(e["cache_dtype"]))
    dt = jnp.dtype(e["cache_dtype"])

    def caches(n):
        return tuple(
            (sds(rd.cache_shape(n, e["t_max"], l.kv_heads, l.head_dim), dt,
                 cache_sh),) * 2
            + (sds(rd.index_cache_shape(n, e["t_max"], l.indexer.dim), dt,
                   cache_sh),)
            for l in spec.layers)

    efns = _engine_fns(cfg, 0)
    windows = [int(w) for w in (args.window or str(e["window"])).split(",")]
    for n in (int(x) for x in args.slots.split(",")):
        t0 = time.time()
        chunk = efns.prefill_chunk.lower(
            params, caches(n), sds((), jnp.int32),
            sds((1, e["prefill_chunk"]), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32)).compile()
        _report("prefill_chunk_in_place", chunk, t0, n_slots=n,
                chunk=e["prefill_chunk"])
        i32 = sds((n,), jnp.int32)
        a = (params, caches(n), sds((n, config["vocab_size"]), jnp.float32),
             sds((n, 2), jnp.uint32), i32, i32, i32, (), (), i32)
        for w in windows:
            t0 = time.time()
            win = efns.window.lower(*a, w).compile()
            _report("window", win, t0, n_slots=n, window=w)
            if args.hlo:
                out = Path(args.hlo)
                out.mkdir(parents=True, exist_ok=True)
                (out / f"window_{n}_{w}.txt").write_text(win.as_text())
                (out / f"chunk_{n}.txt").write_text(chunk.as_text())


if __name__ == "__main__":
    main()
