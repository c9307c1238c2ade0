#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process, one chip. It drives the main path once through
`idc_models_tpu.cli.main` — what `python -m idc_models_tpu` calls — at
the full width of models the repo supports, on synthetic data and random
weights made from seeds:

  kernels  every Pallas kernel lowered by Mosaic (never interpreted) and
           compared with its in-tree jnp reference at its CPU test's
           tolerance
  vgg      the flagship trainer: the `vgg` preset, full VGG16 on
           50x50x3 at the preset's batch, one epoch per phase
  lm       the widest LM the repo builds, trained a few steps through
           the flash (Pallas) blocks, then decoded by the `Generator`
  serve    the continuous-batching server at the same widths; every
           request must end `ok` with the token count it asked for

It refuses to start unless jax's first device is a TPU. A phase that
fails raises — nothing is caught and turned into a line of text — so the
exit code is 0 only if every phase passed. The last line of stdout is
one JSON object naming the device as jax reports it.

    python chip_smoke.py
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import re
import sys
import tempfile
import time
from importlib import metadata
from pathlib import Path

# The widest LM configuration the repo has ever built (bench.py's serve
# and decode sections); depth is its 2 blocks.
LM = dict(vocab=1024, embed_dim=512, num_heads=8, mlp_dim=2048,
          num_blocks=2)
VGG_ARGS = ["--epochs", "1", "--fine-tune-epochs", "1"]
# seq_len: a multiple of 256 so the flash kernels tile it
LM_TRAIN = dict(seq_len=512, steps=6, block_impl="pallas", generate=32)
SERVE = dict(t_max=2048, slots=8, window=64)
SERVE_REQUESTS = 12
SERVE_PROMPT_LENS = (16, 512)
SERVE_BUDGETS = (16, 256)
MASK_ELEMS = 14_714_688          # VGG16's parameter count
FLASH_SHAPE = (2, 512, 8, 64)    # [B, T, H, D] at the LM's head width
DEPTHWISE_BATCH = 8


def _flags(**kw) -> list[str]:
    """{"t_max": 2048} -> ["--t-max", "2048"]."""
    return [x for k, v in kw.items()
            for x in (f"--{k.replace('_', '-')}", str(v))]


def _cli(args) -> str:
    """Run one verb through the entry point a user calls; returns what
    it printed (two of the trainer's checks — the initial loss, the
    test line — exist only as printed text). A non-zero exit code is a
    failure."""
    from idc_models_tpu import cli

    seen = io.StringIO()
    try:
        with contextlib.redirect_stdout(seen):
            rc = cli.main([str(a) for a in args])
    finally:
        sys.stdout.write(seen.getvalue())   # also when the verb raised
    if rc != 0:
        raise RuntimeError(f"`{args[0]}` exited with code {rc}")
    return seen.getvalue()


def _events(path) -> list[dict]:
    return [json.loads(line)
            for line in Path(path).read_text().splitlines() if line.strip()]


def _require(cond, what) -> None:
    if not cond:
        raise AssertionError(what)


# -- phases -----------------------------------------------------------------


def check_kernels() -> None:
    """Mosaic lowers each kernel and the result matches the in-tree jnp
    reference, at the tolerance the kernel's CPU test uses. Where the
    reference is a matmul, both sides run at full f32 precision: the
    comparison is of the algorithm, not of XLA's default bf16 passes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models import mobilenet
    from idc_models_tpu.ops import (
        fused_conv, fused_masked_quantize, masked_quantize_reference,
        pair_seeds_and_signs,
    )
    from idc_models_tpu.ring_attention import (
        full_attention, make_ring_attention,
    )

    interpret = meshlib.pallas_interpret()
    rng = np.random.default_rng(0)

    # secure-aggregation mask kernel, at VGG16's size: bit-exact
    x = jnp.asarray(rng.normal(size=(MASK_ELEMS,)).astype(np.float32))
    seeds, signs = pair_seeds_and_signs(123, 3, 8, round_index=5)
    got = jax.jit(lambda a: fused_masked_quantize(
        a, seeds, signs, scale_bits=20, clip_abs=64.0,
        interpret=interpret))(x)
    want = jax.jit(lambda a: masked_quantize_reference(
        a, seeds, signs, scale_bits=20, clip_abs=64.0))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print(f"kernel mask: {MASK_ELEMS} elements bit-equal to the jnp "
          f"reference")

    # fused depthwise+BN+relu6: every shape MobileNetV2 calls it at
    shapes = sorted({(c["h_in"], c["w_in"], c["c"], c["stride"])
                     for c in mobilenet.fused_call_shapes(
                         DEPTHWISE_BATCH, 50)})
    worst = 0.0
    for h, w, c, stride in shapes:
        a = jnp.asarray(rng.normal(size=(DEPTHWISE_BATCH, h, w, c)),
                        jnp.float32)
        k = jnp.asarray(rng.normal(0, 0.3, (3, 3, 1, c)), jnp.float32)
        mul = jnp.asarray(rng.normal(1, 0.1, (c,)), jnp.float32)
        add = jnp.asarray(rng.normal(0, 0.1, (c,)), jnp.float32)
        got = fused_conv.fused_depthwise_affine(a, k, mul, add,
                                                stride=stride)
        want = fused_conv.reference_impl(a, k, mul, add, stride=stride)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"{(h, w, c, stride)}")
        worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    print(f"kernel fused depthwise: {len(shapes)} MobileNetV2 shapes at "
          f"batch {DEPTHWISE_BATCH} match reference_impl (max abs err "
          f"{worst:.2e})")
    # ... and through its real caller, the whole backbone's forward
    m_f = mobilenet.mobilenet_v2_backbone(3, depthwise_impl="fused")
    m_g = mobilenet.mobilenet_v2_backbone(3, depthwise_impl="grouped")
    v = m_f.init(jax.random.key(0))
    imgs = jnp.asarray(rng.random((DEPTHWISE_BATCH, 50, 50, 3)),
                       jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_f, _ = m_f.apply(v.params, v.state, imgs, train=False)
        y_g, _ = m_g.apply(v.params, v.state, imgs, train=False)
    # its CPU test's 1e-4, against the output's largest element: from a
    # random init the features themselves are only ~3e-4
    err = float(jnp.max(jnp.abs(y_f - y_g)) / jnp.max(jnp.abs(y_g)))
    _require(err <= 1e-4, f"fused MobileNetV2 forward is {err:.2e} of "
                          f"its largest element away from the grouped "
                          f"build")
    print(f"kernel fused depthwise: MobileNetV2 forward {y_f.shape} "
          f"within {err:.2e} of the grouped build's largest element")

    # flash block kernels, forward and backward, through the ring op
    q, k, v = (jnp.asarray(rng.normal(size=FLASH_SHAPE), jnp.float32)
               for _ in range(3))
    ring = make_ring_attention(meshlib.seq_mesh(1), causal=True,
                               block_impl="pallas")
    with jax.default_matmul_precision("highest"):
        out = ring(q, k, v)
        ref = full_attention(q, k, v, causal=True)
        g_p = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) ** 2),
                       (0, 1, 2))(q, k, v)
        g_f = jax.grad(lambda a, b, c: jnp.sum(
            full_attention(a, b, c, causal=True) ** 2), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # The backward holds its CPU test's rtol (2e-4) against each
    # gradient's largest element, not element by element: on the chip
    # the flash backward (p recomputed from the saved logsumexp,
    # D = rowsum(do * o)) is ~20x further from a float64 truth than
    # XLA's autodiff — 5e-5 of the largest element against 3e-6 — so
    # the CPU test's per-element atol of 2e-5 fails on small elements.
    worst = 0.0
    for a, b, name in zip(g_p, g_f, "qkv"):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        _require(err <= 2e-4, f"flash backward d{name} is {err:.2e} of "
                              f"its largest element away from full "
                              f"attention's autodiff")
        worst = max(worst, err)
    print(f"kernel flash: forward at {FLASH_SHAPE} matches full "
          f"attention (max abs err "
          f"{float(jnp.max(jnp.abs(out - ref))):.2e}), backward within "
          f"{worst:.2e} of each gradient's largest element")


def run_vgg(work: Path) -> None:
    out = _cli(["vgg", *VGG_ARGS, "--path", work])
    epochs = [e for e in _events(work / "logs" / "run.jsonl")
              if e.get("event") == "epoch"]
    _require(len(epochs) == 2, f"expected one epoch per phase, got "
                               f"{len(epochs)} epoch records")
    for e in epochs:
        _require(math.isfinite(e["loss"]) and math.isfinite(e["val_loss"]),
                 f"non-finite loss in epoch record {e}")
    m = re.search(r"^initial loss: ([0-9.eE+-]+|nan|inf)$", out, re.M)
    _require(m is not None, "the trainer printed no initial loss")
    start, end = float(m.group(1)), epochs[0]["val_loss"]
    _require(math.isfinite(start) and end < start,
             f"phase-1 validation loss did not fall: {start} at the "
             f"start, {end} at the end")
    test = re.search(r"^test: .*loss=([0-9.eE+-]+|nan|inf)", out, re.M)
    _require(test is not None and math.isfinite(float(test.group(1))),
             "the trainer printed no finite test line")
    print(f"vgg: phase-1 val loss {start:.4f} -> {end:.4f}, phase-2 "
          f"train loss {epochs[1]['loss']:.4f}, test line printed")


def run_lm(work: Path) -> None:
    _cli(["lm", *_flags(**LM, **LM_TRAIN), "--path", work])
    events = _events(work / "logs" / "run.jsonl")
    steps = [e for e in events if e.get("event") == "step"]
    _require(steps and all(math.isfinite(e["loss"]) for e in steps),
             f"lm training loss missing or non-finite: {steps}")
    gen = [e for e in events if e.get("event") == "generate"]
    n_gen = LM_TRAIN["generate"]
    _require(len(gen) == 1 and len(gen[0]["tokens"]) == 3 + n_gen,
             f"expected a 3-token prompt + {n_gen} generated tokens, got "
             f"{gen}")
    print(f"lm: loss {steps[0]['loss']:.4f} -> {steps[-1]['loss']:.4f} "
          f"over {steps[-1]['step'] + 1} steps through the Pallas blocks, "
          f"{n_gen} tokens generated")


def run_serve(work: Path) -> None:
    from idc_models_tpu.serve import poisson_trace, save_trace

    trace = poisson_trace(
        SERVE_REQUESTS, rate_per_s=50.0, vocab=LM["vocab"],
        t_max=SERVE["t_max"], prompt_lens=SERVE_PROMPT_LENS,
        budgets=SERVE_BUDGETS, seed=0)
    trace_path = save_trace(work / "trace.jsonl", trace)
    _cli(["serve", *_flags(**LM, **SERVE), "--trace", trace_path,
          "--path", work])
    finished = {e["id"]: e
                for e in _events(work / "logs" / "serve.jsonl")
                if e.get("event") == "serve_finish"}
    for _, req in trace:
        e = finished.get(req.id)
        _require(e is not None, f"request {req.id} never finished")
        _require(e["reason"] == "budget"
                 and e["tokens"] == req.max_new_tokens,
                 f"request {req.id} asked for {req.max_new_tokens} "
                 f"tokens and ended {e['reason']!r} with {e['tokens']}")
    print(f"serve: {len(trace)} requests ok, each with the token count "
          f"it asked for ({sum(r.max_new_tokens for _, r in trace)} "
          f"tokens)")
    _observe_parity(trace)


def _observe_parity(trace) -> None:
    """Whether the engine's greedy streams are bit-equal to the serial
    `Generator` on this device — every parity gate so far ran on CPU.
    Printed as an observed fact; NOT a pass condition. The CLI exposes
    token counts, not tokens, so this one step rebuilds the server from
    the library with the CLI's seed and settings."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.models.lm import Generator, attention_lm
    from idc_models_tpu.serve import LMServer

    dims = dict(embed_dim=LM["embed_dim"], num_heads=LM["num_heads"],
                num_blocks=LM["num_blocks"], t_max=SERVE["t_max"])
    window = SERVE["window"]
    params = attention_lm(
        LM["vocab"], SERVE["t_max"], embed_dim=LM["embed_dim"],
        num_heads=LM["num_heads"], mlp_dim=LM["mlp_dim"],
        num_blocks=LM["num_blocks"]).init(jax.random.key(0)).params
    server = LMServer(params, n_slots=SERVE["slots"], window=window,
                      cache_dtype=jnp.float32, **dims)
    served = {r.id: r.tokens for r in server.run(trace)}
    server.close()
    gen = Generator(params, cache_dtype=jnp.float32, **dims)
    equal, diverged_at = 0, []
    for _, req in trace:
        logits, caches = gen.prefill(jnp.asarray([req.prompt], jnp.int32))
        toks, pos = [], len(req.prompt)
        # whole windows (one compiled decode program), then truncate:
        # greedy decode is prefix-stable
        while len(toks) < req.max_new_tokens:
            out, logits, caches = gen.decode(caches, logits, pos, window)
            toks += out.tolist()[0]
            pos += window
        toks, got = toks[:req.max_new_tokens], list(served[req.id])
        equal += toks == got
        if toks != got:
            diverged_at.append(next(i for i, (a, b)
                                    in enumerate(zip(toks, got)) if a != b))
    print(f"observed (not gated): {equal}/{len(trace)} served streams "
          f"bit-equal to the serial Generator"
          + (f"; the others first differ at generated token "
             f"{sorted(diverged_at)}" if diverged_at else ""))


# -- driver -----------------------------------------------------------------


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax found platform="
              f"{dev.platform!r} (device_kind {dev.device_kind!r}, "
              f"{len(devices)} device(s)); refusing to run",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}  jax {jax.__version__}  jaxlib "
          f"{metadata.version('jaxlib')}  libtpu "
          f"{metadata.version('libtpu')}")

    import jax.monitoring

    from idc_models_tpu import runtime
    from idc_models_tpu.observe import profile as prof

    cache = {"hits": 0, "misses": 0}

    def count(event, **kw):
        for key in cache:
            if event == f"/jax/compilation_cache/cache_{key}":
                cache[key] += 1

    jax.monitoring.register_event_listener(count)
    cache_dir = runtime.setup_compile_cache()
    watchdog = prof.arm_watchdog(limit=1_000_000)   # counts, never flags
    roof = prof.roofline_for(dev)
    print(f"roofline row for {dev.device_kind!r}: "
          f"{roof.key if roof else None}")

    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for name, phase in (("kernels", lambda w: check_kernels()),
                            ("vgg", run_vgg), ("lm", run_lm),
                            ("serve", run_serve)):
            t0 = time.perf_counter()
            print(f"--- {name} ---", flush=True)
            work = Path(tmp) / name
            work.mkdir()
            phase(work)
            print(f"--- {name} ok in {time.perf_counter() - t0:.1f} s ---",
                  flush=True)
    compiles = watchdog.report()
    print(f"peak device memory "
          f"{dev.memory_stats()['peak_bytes_in_use'] / 2**20:.0f} MiB")
    print(f"wall {time.perf_counter() - t_start:.1f} s; "
          f"{compiles['total_compiles']} XLA compile requests took "
          f"{compiles['compile_seconds_total']:.1f} s; persistent cache "
          f"{cache_dir}: {cache['hits']} hits, {cache['misses']} new "
          f"entries written")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
