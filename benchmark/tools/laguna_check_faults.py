"""Show, once, on the chip, that the Laguna cell's comparison fails what
it has to fail: the engine serves the check's prompts as in a run of the
cell, and its logits are then compared, by the cell's own comparison,
with the reference AS IT IS and with the reference made wrong in one way
at a time. Against a wrong reference the error is what a system wrong in
that way would show against the right one.

    python3 benchmark/tools/laguna_check_faults.py --seed 5 [--prompts 700,2500]

Prints one JSON line per variant and writes them to
`chiprun_out/laguna_faults.jsonl`: the limit `check.logit_tol` has to
lie above `as_it_is` and below every other line.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--prompts", default=None)
    ap.add_argument("--variants", default=None,
                    help="comma-separated prefixes of the variants to run")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import harness
    from benchmark.run import merged
    from benchmark.runners import serve_moe_open_loop as smo

    harness.keep_every_compile()
    harness.require_tpu(1, rehearse=args.rehearse)
    config = json.loads((BENCH_DIR / "configs" / "laguna-s-2.1.json").read_text())
    if args.rehearse:
        config = merged(config, config["rehearsal"])
    spec = dict(config["check"])
    if args.prompts:
        spec["prompt_lens"] = [int(x) for x in args.prompts.split(",")]
    n_dec, window = spec["decode_positions"], config["sliding_window"]
    params = smo.make_params(config, args.seed)
    engine = smo.build_server(params, config, config["engine"]).engine
    prompts = smo.check_prompts(dict(config, check=spec), args.seed + 1)
    tokens, logits, picks = smo.serve_for_check(engine, prompts, n_dec)
    del engine

    def bf16_accumulate(x, w):
        """Inputs in bfloat16, and the sum over the inner dimension kept
        in bfloat16 from one 128-wide pass of the matrix unit to the
        next: the nearest precision below the configuration's bfloat16
        products accumulated in float32."""
        acc = jnp.zeros((x.shape[0], w.shape[1]), jnp.bfloat16)
        for k0 in range(0, x.shape[1], 128):
            part = jnp.matmul(x[:, k0:k0 + 128].astype(jnp.bfloat16),
                              w[k0:k0 + 128].astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
            acc = (acc.astype(jnp.float32) + part).astype(jnp.bfloat16)
        return acc.astype(jnp.float32)

    # the held expert the compared positions use most, dropped
    used = np.bincount(np.concatenate([p.ravel() for p in picks]),
                       minlength=config["num_experts_published"])
    first, count = smo.held_range(config)
    drop = int(np.argmax(used[first:first + count]))
    dropped = copy.copy(params)
    for i in range(config["num_hidden_layers"]):
        if "moe" in params[f"block{i}"]:
            blk = copy.copy(params[f"block{i}"])
            blk["moe"] = dict(blk["moe"], experts=dict(
                blk["moe"]["experts"],
                w_down=blk["moe"]["experts"]["w_down"].at[drop].set(0)))
            dropped[f"block{i}"] = blk
    rope = config["rope_parameters"]
    variants = {
        "as_it_is": (params, config, None),
        "bf16_accumulated_products": (params, config, bf16_accumulate),
        f"held_expert_{drop}_dropped": (dropped, config, None),
        "window_511": (params, dict(config, sliding_window=window - 1), None),
        "window_513": (params, dict(config, sliding_window=window + 1), None),
        "sliding_rotary_on_full_layers": (params, dict(config, rope_parameters=dict(
            rope, full_attention=rope["sliding_attention"])), None),
        "full_rotary_on_sliding_layers": (params, dict(config, rope_parameters=dict(
            rope, sliding_attention=rope["full_attention"])), None),
    }
    out = BENCH_DIR.parent / "chiprun_out" / "laguna_faults.jsonl"
    out.parent.mkdir(exist_ok=True)
    with open(out, "a") as f:
        for name, (p, cfg, dot) in variants.items():
            if args.variants and not name.startswith(tuple(args.variants.split(","))):
                continue
            res = []
            for prompt, toks, lg, pk in zip(prompts, tokens, logits, picks):
                seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
                res.append(smo.compare_with_reference(p, cfg, seq, n_dec, lg,
                                                      pk, dot=dot))
            row = {"variant": name, "seed": args.seed,
                   "prompt_lens": spec["prompt_lens"],
                   "logit_errs": [r["logit_err"] for r in res],
                   "router_deficit": [r["router_deficit"] for r in res],
                   "swapped_share": [r["swapped_share"] for r in res],
                   "logit_tol": spec["logit_tol"],
                   "router_margin": spec["router_margin"]}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
