"""Show, once, on the chip, that the Keye cell's comparison fails what
it has to fail: the engine serves the check's prompts as in a run of the
cell, and what it handed out is then compared, by the cell's own
comparison, with the reference AS IT IS and with one thing made wrong at
a time. Against a wrong reference the readings are what a system wrong
in that way would show against the right one.

    python3 benchmark/tools/keye_check_faults.py --seed 5 [--prompts 3000,9000]

Prints one JSON line per variant and writes them to
`chiprun_out/keye_faults.jsonl`: every limit of `check` has to lie above
`as_it_is`, and every other line has to break at least one of them.
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

    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import harness
    from benchmark.run import merged
    from benchmark.runners import serve_dsa_open_loop as sdo

    harness.keep_every_compile()
    harness.require_tpu(1, rehearse=args.rehearse)
    config = json.loads((BENCH_DIR / "configs"
                         / "keye-vl-2.0-30b-a3b.json").read_text())
    if args.rehearse:
        config = merged(config, config["rehearsal"])
    spec = dict(config["check"])
    if args.prompts:
        spec["prompt_lens"] = [int(x) for x in args.prompts.split(",")]
    n_dec, sa = spec["decode_positions"], config["sa_config"]
    params = sdo.make_params(config, args.seed)
    engine = sdo.build_server(params, config, config["engine"]).engine
    prompts = sdo.check_prompts(dict(config, check=spec), args.seed + 1)
    tokens, logits, picks, chosen = sdo.serve_for_check(
        engine, prompts, n_dec, config["engine"]["window"])
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

    def bf16_index(qi, ki):
        """The index products from bfloat16 inputs, their sums rounded
        to bfloat16: an indexer's scores one precision lower."""
        s = jnp.einsum("qjd,kd->qjk", qi.astype(jnp.bfloat16),
                       ki.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return s.astype(jnp.bfloat16).astype(jnp.float32)

    def shifted(bits):
        """Every selected position moved one up: s -> s + 1."""
        carry = np.pad(bits[..., :-1] >> np.uint32(31),
                       [(0, 0)] * (bits.ndim - 1) + [(1, 0)])
        return (bits << np.uint32(1)) | carry

    # the held expert the compared positions use most, dropped
    used = np.bincount(np.concatenate([p.ravel() for p in picks]),
                       minlength=config["num_experts_published"])
    first, count = sdo.held_range(config)
    drop = int(np.argmax(used[first:first + count]))
    dropped = copy.copy(params)
    for i in range(config["num_hidden_layers"]):
        blk = copy.copy(params[f"block{i}"])
        blk["moe"] = dict(blk["moe"], experts=dict(
            blk["moe"]["experts"],
            w_down=blk["moe"]["experts"]["w_down"].at[drop].set(0)))
        dropped[f"block{i}"] = blk
    fewer = dict(config, sa_config=dict(sa, topk=sa["topk"] - 1))
    variants = {
        "as_it_is": dict(),
        "bf16_accumulated_products": dict(dot=bf16_accumulate,
                                          idx_dot=bf16_index),
        f"topk_{sa['topk'] - 1}": dict(config=fewer),
        "selection_shifted_by_one": dict(select=shifted),
        "index_key_without_rotary": dict(index_rotary=False),
        f"held_expert_{drop}_dropped": dict(params=dropped),
    }
    out = BENCH_DIR.parent / "chiprun_out" / "keye_faults.jsonl"
    out.parent.mkdir(exist_ok=True)
    limits = {k: spec[k] for k in ("logit_tol", "router_margin",
                                   "select_margin")}
    with open(out, "a") as f:
        for name, v in variants.items():
            if not name.startswith(tuple((args.variants or "").split(","))):
                continue
            res = []
            for prompt, toks, lg, pk, sel in zip(prompts, tokens, logits,
                                                 picks, chosen):
                seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
                res.append(sdo.compare_with_reference(
                    v.get("params", params), v.get("config", config), seq,
                    n_dec, lg, pk, v.get("select", lambda b: b)(sel),
                    dot=v.get("dot"), idx_dot=v.get("idx_dot"),
                    index_rotary=v.get("index_rotary", True)))
            fails = [not (r["logit_err"] <= spec["logit_tol"]
                          and r["token_gap"] <= 2 * spec["logit_tol"]
                          and r["router_deficit"] <= spec["router_margin"]
                          and r["select_deficit"] <= spec["select_margin"]
                          and r["select_count_ok"]) for r in res]
            row = {"variant": name, "seed": args.seed,
                   "prompt_lens": spec["prompt_lens"], "fails": fails,
                   **{k: [r[k] for r in res] for k in (
                       "logit_err", "token_gap", "router_deficit",
                       "swapped_share",
                       "select_deficit", "select_swapped_share",
                       "select_count_ok")}, **limits}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
