"""Show, once, on the chip, that the GLM-5.2 cell's comparison fails what
it has to fail: the engine serves the check's prompts as in a run of the
cell, and what it handed out is then compared, by the cell's own
comparison, with the reference AS IT IS and with one thing made wrong at
a time. Against a wrong reference the readings are what a system wrong
in that way would show against the right one.

    python3 benchmark/tools/glm_check_faults.py --seed 5 \
        [--prompts 4000,16000] [--variants as_it_is,bf16]

Prints one JSON line per variant: every limit of `check` has to lie above
`as_it_is`, and every other line has to break at least one of them. The
variants: every product of the reference accumulated in bfloat16 (the
control that sets each limit), the `k_pe` term dropped, the sharing
layers selecting on their own (with their source's indexer, on their own
hidden state), the correction bias ignored in the picks, rotate-half in
place of adjacent pairs, `index_topk` 2,047, and one held expert dropped.
One more line, `bf16_operands_vs_float32`, compares two references and
no system: every product's operands rounded to bfloat16 and summed in
float32 (the precision the configuration states), against the float32
reference, both with the system's experts and selections forced. Its
`logit_err` is what the stated precision alone costs this model.
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
    from benchmark.runners import serve_mla_open_loop as smo

    harness.keep_every_compile()
    harness.require_tpu(1, rehearse=args.rehearse)
    config = json.loads((BENCH_DIR / "configs" / "glm-5.2.json").read_text())
    if args.rehearse:
        config = merged(config, config["rehearsal"])
    spec = dict(config["check"])
    if args.prompts:
        spec["prompt_lens"] = [int(x) for x in args.prompts.split(",")]
    n_dec = spec["decode_positions"]
    params = smo.make_params(config, args.seed)
    engine = smo.build_server(params, config, config["engine"]).engine
    prompts = smo.check_prompts(dict(config, check=spec), args.seed + 1)
    tokens, logits, picks, chosen = smo.serve_for_check(
        engine, prompts, n_dec, config["engine"]["window"])
    del engine

    def rounded(x):
        """x rounded to bfloat16 and held in float32: a product of two
        such is exact in float32, and the sum is the one asked for."""
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def bf16_accumulate(subscripts, a, b):
        """Operands in bfloat16, and the sum over the contracted index
        kept in bfloat16 from one 128-wide pass of the matrix unit to the
        next: the nearest precision below the configuration's bfloat16
        operands summed in float32. Every product of the reference."""
        ins, out = subscripts.split("->")
        sa, sb = ins.split(",")
        (c,) = [x for x in sa if x in sb and x not in out]

        def passes(x, s):
            x = jnp.moveaxis(rounded(x), s.index(c), 0)
            x = jnp.pad(x, ((0, -x.shape[0] % 128),) + ((0, 0),) * (x.ndim - 1))
            return x.reshape(-1, 128, *x.shape[1:])

        rest = lambda s: c + s.replace(c, "")
        spec = f"{rest(sa)},{rest(sb)}->{out}"

        def step(acc, ab):
            part = jnp.einsum(spec, *ab, precision="highest")
            return (acc.astype(jnp.float32) + part).astype(jnp.bfloat16), None

        shape = jax.eval_shape(lambda: jnp.einsum(subscripts, a, b)).shape
        acc, _ = jax.lax.scan(step, jnp.zeros(shape, jnp.bfloat16),
                              (passes(a, sa), passes(b, sb)))
        return acc.astype(jnp.float32)

    def bf16_operands(subscripts, a, b):
        """Operands rounded to bfloat16, summed in float32: the precision
        the configuration states, in the reference's own order."""
        return jnp.einsum(subscripts, rounded(a), rounded(b),
                          precision="highest")

    def fp8(x):
        """x in float8 e4m3 under one scale a tensor (its largest
        magnitude at the format's largest, 448), held in float32."""
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def fp8_operands(subscripts, a, b):
        """Operands in float8 e4m3, the format below bfloat16, summed in
        float32: every product of the reference."""
        return jnp.einsum(subscripts, fp8(a), fp8(b), precision="highest")

    def reference_rows(seq, n_rows, pk, sel):
        """The float32 reference's logits at the rows the system keeps,
        with the system's experts and selections forced."""
        t = len(seq)
        own, _ = smo.selecting_layers(config)
        out = jax.jit(lambda params, seq, pk, sel: smo.glm_ref.forward(
            params, seq, config, smo.held_range(config),
            rows=(t - n_dec - 1, t), block=smo.REF_BLOCK, picks=pk,
            select=sel)[0])(params, seq, pk, sel[own])
        return np.asarray(out)[::n_dec // (n_rows - 1)]

    # the held expert the compared positions use most, dropped
    used = np.bincount(np.concatenate([p.ravel() for p in picks]),
                       minlength=config["n_routed_experts_published"])
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
    topk = config["index_topk"]
    variants = {
        "as_it_is": dict(),
        "bf16_accumulated_products": dict(prod=bf16_accumulate),
        "fp8_operands": dict(prod=fp8_operands),
        "bf16_operands_vs_float32": dict(prod=bf16_operands,
                                         against_reference=True),
        "k_pe_dropped": dict(fault="no_k_pe"),
        "shared_layers_select_on_their_own": dict(fault="shared_own"),
        "correction_bias_ignored": dict(fault="no_bias"),
        "rotate_half_for_adjacent_pairs": dict(fault="rotate_half"),
        f"topk_{topk - 1}": dict(config=dict(config, index_topk=topk - 1)),
        f"held_expert_{drop}_dropped": dict(params=dropped),
    }
    limits = {k: spec[k] for k in ("logit_tol", "router_margin",
                                   "select_margin")}
    for name, v in variants.items():
        if not name.startswith(tuple((args.variants or "").split(","))):
            continue
        res = []
        for prompt, toks, lg, pk, sel in zip(prompts, tokens, logits,
                                             picks, chosen):
            seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
            if v.get("against_reference"):
                lg = reference_rows(seq, len(lg), pk, sel)
            res.append(smo.compare_with_reference(
                v.get("params", params), v.get("config", config), seq,
                n_dec, lg, pk, sel, prod=v.get("prod"),
                fault=v.get("fault")))
        fails = [not (r["logit_err"] <= spec["logit_tol"]
                      and r["token_gap"] <= 2 * spec["logit_tol"]
                      and r["router_deficit"] <= spec["router_margin"]
                      and r["select_deficit"] <= spec["select_margin"]
                      and r["select_count_ok"] and r["shared_ok"])
                 for r in res]
        if v.get("against_reference"):
            print(json.dumps({"variant": name, "seed": args.seed,
                              "prompt_lens": spec["prompt_lens"],
                              "logit_err": [r["logit_err"] for r in res]}),
                  flush=True)
            continue
        row = {"variant": name, "seed": args.seed,
               "prompt_lens": spec["prompt_lens"], "fails": fails,
               **{k: [r[k] for r in res] for k in (
                   "logit_err", "token_gap", "router_deficit",
                   "swapped_share", "select_deficit",
                   "select_swapped_share", "select_count_ok",
                   "shared_ok")}, **limits}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
