"""Command-line entry points — one subcommand per reference script.

Parity target (SURVEY.md C19): the reference's five scripts take
positional sys.argv (path; fed adds NUM_ROUNDS + iid|noniid; secure adds
NUM_ROUNDS + percent). Here: proper argparse with the presets from
`configs.py` as defaults and every hyperparameter overridable.

    python -m idc_models_tpu vgg --path runs/vgg --data-dir .../balanced_IDC_30k
    python -m idc_models_tpu fed --path runs/fed --rounds 10 --noniid
    python -m idc_models_tpu secure-fed --rounds 5 --percent 0.5

Data resolution: --data-dir (a `<label>/*.png` tree) if given, else
`<path>/data/balanced_IDC_30k` if present (the reference's layout,
dist_model_tf_vgg.py:105), else a synthetic stand-in sized by
--synthetic-examples so every preset smoke-runs anywhere. Virtual devices
for laptop/test runs come from --host-devices N (the TPU-pod stand-in).
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

import numpy as np

# `serve --drafter` registry: choice name -> (module, class, story).
# Every class listed here MUST implement the models/draft.py contract
# (`propose(history) -> [k] int32 | None`) — a static scan
# (tests/test_static_robustness.py) imports each entry and asserts it,
# and asserts the argparse choices stay in lockstep with this table,
# so a drafter added to one place but not the other fails loudly.
SERVE_DRAFTERS = {
    "ngram": ("idc_models_tpu.models.draft", "NGramDrafter",
              "prompt-lookup over the slot's own stream; free, wins on "
              "repetitive/templated traffic, proposes nothing on fresh "
              "text"),
    "learned": ("idc_models_tpu.models.draft_lm", "DraftLM",
                "distilled draft LM (--draft-ckpt) with device-resident "
                "ring caches; one batched propose dispatch per cycle, "
                "wins on non-repetitive traffic"),
    "chained": ("idc_models_tpu.models.draft", "ChainedDrafter",
                "lookup-first / learned-fallback composition: the "
                "n-gram scan's free hits where streams repeat, the "
                "draft LM (--draft-ckpt) everywhere else"),
}


def main(argv: list[str] | None = None) -> int:
    ns = _parse(argv)
    if getattr(ns, "host_devices", 0):
        from idc_models_tpu import mesh as meshlib

        meshlib.force_cpu_pod(ns.host_devices)  # warns if ineffective
    # every verb says what it ran on: a libtpu that fails to initialise
    # leaves jax on the CPU with only a warning, and the run would
    # otherwise look the same (stderr: stdout carries verb output)
    from idc_models_tpu import runtime

    cache_dir = runtime.setup_compile_cache()
    dev = runtime.device_summary()
    print(f"[idc_models_tpu] devices: platform={dev['platform']} "
          f"kind={dev['kind']!r} count={dev['count']}; compile cache: "
          f"{cache_dir}", file=sys.stderr)
    runner = {"vgg": _run_dist, "mobile": _run_dist, "dense": _run_dist,
              "fed": _run_fed, "secure_fed": _run_secure,
              "attention": _run_attention, "lm": _run_lm,
              "serve": _run_serve, "serve_cluster": _run_serve_cluster,
              "stats": _run_stats, "profile": _run_profile,
              "convert_weights": _run_convert}[ns.preset_key]
    # --trace-out: ONE wiring point arms the runtime tracer for every
    # verb — the instrumented spans (serve scheduler cycles, federated
    # round attempts, train epochs/steps, Generator prefill/decode,
    # every legacy Timer) record only while this context is active and
    # export as Chrome trace-event JSON (Perfetto-loadable) on exit
    from idc_models_tpu.observe import tracing

    with tracing(chrome_path=getattr(ns, "trace_out", None)):
        # serve/serve-cluster return 1 when a request ended in error;
        # every other verb returns None and fails by raising
        return runner(ns) or 0


def _parse(argv):
    p = argparse.ArgumentParser(prog="idc_models_tpu", description=__doc__)
    sub = p.add_subparsers(dest="preset_key", required=True)

    def common(sp):
        sp.add_argument("--path", default=None,
                        help="artifact root (plots under <path>/logs, "
                             "checkpoints under <path>/pretrained, jsonl "
                             "log) — the reference's argv[1]")
        sp.add_argument("--data-dir", default=None,
                        help="directory tree <label>/*.png")
        sp.add_argument("--synthetic-examples", type=int, default=512,
                        help="synthetic dataset size when no real data")
        sp.add_argument("--seed", type=int, default=0)
        sp.add_argument("--host-devices", type=int, default=0,
                        help="force N virtual CPU devices (TPU-pod "
                             "stand-in for local runs)")
        sp.add_argument("--batch-size", type=int, default=None)
        sp.add_argument("--lr", type=float, default=None)
        sp.add_argument("--profile-dir", default=None,
                        help="write a jax.profiler trace of the training "
                             "phase here (TensorBoard-viewable)")
        sp.add_argument("--trace-out", default=None,
                        help="write a Chrome trace-event JSON of the "
                             "run's host-side spans here (load it in "
                             "Perfetto / chrome://tracing; see "
                             "docs/OBSERVABILITY.md)")

    def pretrained_flag(sp):
        sp.add_argument("--pretrained-weights", default=None,
                        help="backbone weight artifact (.npz from "
                             "convert-weights, or a Keras .h5) — the "
                             "no-egress analogue of weights='imagenet' "
                             "(dist_model_tf_vgg.py:119)")

    for key in ("vgg", "mobile", "dense"):
        sp = sub.add_parser(key, help=f"{key} two-phase DP training")
        common(sp)
        pretrained_flag(sp)
        sp.add_argument("--epochs", type=int, default=None)
        sp.add_argument("--fine-tune-epochs", type=int, default=None)
        sp.add_argument("--fine-tune-at", type=int, default=None)
        sp.add_argument("--repeats", type=int, default=None,
                        help="dataset passes per epoch (the dense "
                             "preset's repeat(2))")
        sp.add_argument("--central-storage", action="store_true",
                        help="host-resident parameter store, broadcast "
                             "per step (the reference's use_mirror=False "
                             "CentralStorageStrategy toggle)")
        sp.add_argument("--cache-features", action="store_true",
                        help="fine-tune on cached frozen-backbone "
                             "activations (prefix computed once instead "
                             "of every step; numerically equivalent)")
        sp.add_argument("--resumable", action="store_true",
                        help="checkpoint the training loop after every "
                             "epoch under <path>/dist_ckpt and resume "
                             "from there on restart (requires --path)")
        sp.add_argument("--checkpoint-every", type=int, default=1,
                        help="with --resumable: epochs between loop "
                             "checkpoints (the final epoch always "
                             "saves; a blocking orbax save per short "
                             "epoch can dominate the epoch itself)")
        sp.add_argument("--stream", action="store_true",
                        help="decode training batches from disk on the "
                             "fly (datasets larger than host RAM) "
                             "instead of materializing the train split; "
                             "needs a real --data-dir IDC tree")
        sp.add_argument("--decode-workers", type=int, default=0,
                        help="with --stream: fan batch decoding out to "
                             "N worker processes (round-robin whole "
                             "batches; bit-identical stream, scales "
                             "with host cores)")
        sp.add_argument("--model-parallel", type=int, default=1,
                        help="shard weights channel-wise over a 'model' "
                             "mesh axis of this size (tensor parallelism "
                             "via GSPMD, tp.py); composes with data "
                             "parallelism over the remaining devices")

    sp = sub.add_parser("fed", help="federated averaging (FedAvg)")
    common(sp)
    pretrained_flag(sp)
    sp.add_argument("--rounds", type=int, default=None)
    sp.add_argument("--iid", dest="iid", action="store_true", default=None)
    sp.add_argument("--noniid", dest="iid", action="store_false")
    sp.add_argument("--num-clients", type=int, default=None)
    sp.add_argument("--local-epochs", type=int, default=None)
    sp.add_argument("--pretrain-epochs", type=int, default=None)
    sp.add_argument("--checkpoint-every", type=int, default=10,
                    help="save the federated server state every N rounds "
                         "(plus once at the end); a per-round blocking "
                         "orbax save would dominate the ~50 ms round")
    sp.add_argument("--aggregator", default="mean",
                    choices=("mean", "trimmed_mean", "median",
                             "norm_clip"),
                    help="round-boundary aggregation "
                         "(federated/robust.py): mean = example-"
                         "weighted FedAvg; trimmed_mean/median bound "
                         "Byzantine influence coordinate-wise; "
                         "norm_clip L2-clips each client's update")
    sp.add_argument("--trim", type=int, default=1,
                    help="clients trimmed per side with "
                         "--aggregator trimmed_mean (tolerates that "
                         "many Byzantine clients; needs > 2*trim "
                         "participants)")
    sp.add_argument("--clip-norm", type=float, default=10.0,
                    help="per-client update L2 bound with "
                         "--aggregator norm_clip")
    sp.add_argument("--faults", default=None,
                    help="fault-injection plan (faults.py), e.g. "
                         "'sign_flip:0-2:x1000,crash:5' — deterministic "
                         "per-round client faults applied before "
                         "aggregation, for resilience drills")
    sp.add_argument("--round-timeout", type=float, default=None,
                    help="per-round wall budget in seconds; a slower "
                         "round is discarded and retried with a "
                         "reseeded client subset (federated/driver.py)")
    sp.add_argument("--max-round-retries", type=int, default=2,
                    help="retries per failed round before the run "
                         "aborts with RoundFailure")
    sp.add_argument("--loss-spike-ratio", type=float, default=10.0,
                    help="divergence detector: a round whose train loss "
                         "exceeds this multiple of the last good "
                         "round's is rolled back (0 disables)")
    sp.add_argument("--population", type=int, default=0,
                    help="population mode: train over N VIRTUAL clients "
                         "(federated/population.py) whose shards derive "
                         "lazily from (seed, id) — memory is bounded by "
                         "the cohort, not N. 0 = classic materialized "
                         "mode. Skips the pretrain phase; --faults then "
                         "takes the population grammar "
                         "(kind:rounds[:param][@c<id>,...], fractions "
                         "like crash:2:0.1%)")
    sp.add_argument("--cohort", type=int, default=32,
                    help="clients sampled per round in population mode "
                         "(deterministic per (seed, round))")
    sp.add_argument("--cohort-wave", type=int, default=0,
                    help="streamed-aggregation wave size (must divide "
                         "the cohort; 0 = one wave per cohort). Server "
                         "memory is O(wave), constant in population "
                         "and cohort size")
    sp.add_argument("--weighted-sampling", action="store_true",
                    help="sample cohorts proportional to each virtual "
                         "client's (seeded) dataset-size weight instead "
                         "of uniformly")
    sp.add_argument("--client-examples", type=int, default=16,
                    help="examples per virtual client shard in "
                         "population mode")
    sp.add_argument("--async-buffer", type=int, default=0,
                    help="population mode: buffered-async FedAvg "
                         "(FedBuff) — client completions fill a buffer "
                         "of this size, each full buffer triggers one "
                         "staleness-weighted server update instead of "
                         "a round barrier. 0 = synchronous streamed "
                         "rounds")
    sp.add_argument("--staleness-decay", type=float, default=0.9,
                    help="async mode: per-version weight discount for "
                         "stale updates (weight x decay^staleness), in "
                         "(0, 1]; 1 = no discount")
    sp.add_argument("--model", default=None,
                    choices=("vgg16", "mobilenet_v2", "densenet201",
                             "small_cnn"),
                    help="population mode: override the preset model "
                         "(small_cnn = CPU-scale population drills; "
                         "classic mode keeps the preset's backbone)")
    sp.add_argument("--fault-delay-ms", type=float, default=0.0,
                    help="population mode: wall-clock delay per "
                         "straggler staleness unit (lag k completes "
                         "k x this late) — arms the sync round "
                         "BARRIER sleep and the async arrival lag, "
                         "so straggler drills are wall-clock-real; "
                         "0 = stale-params-only stragglers (sync) / "
                         "inert stragglers (async)")

    sp = sub.add_parser("secure-fed", aliases=["secure_fed"],
                        help="secure-aggregation FedAvg")
    common(sp)
    sp.add_argument("--rounds", type=int, default=None)
    sp.add_argument("--percent", type=float, default=None)
    sp.add_argument("--num-clients", type=int, default=None)
    sp.add_argument("--local-epochs", type=int, default=None)
    sp.add_argument("--paillier", action="store_true", default=None,
                    help="host-side Paillier parity mode instead of "
                             "pairwise masks")
    sp.add_argument("--mask-impl", default="threefry",
                    choices=("threefry", "pallas", "auto"),
                    help="PRG for the pairwise masks: XLA threefry "
                         "(default; cryptographic), the fused Pallas "
                         "hash-PRG kernel, or auto (pallas on TPU above "
                         "the measured crossover — see the threat-model "
                         "note in secure.make_secure_fedavg_round)")
    sp.add_argument("--async-buffer", type=int, default=0,
                    help="rejected: buffered-async aggregation cannot "
                         "compose with the pairwise-mask protocol (the "
                         "build explains why) — exists so the drill "
                         "teaches instead of silently ignoring the "
                         "flag")

    sp = sub.add_parser("attention",
                        help="sequence-parallel transformer classifier "
                             "(beyond-reference: ring attention as a "
                             "training workload)")
    common(sp)
    sp.add_argument("--seq-len", type=int, default=128)
    sp.add_argument("--features", type=int, default=8)
    sp.add_argument("--embed-dim", type=int, default=64)
    sp.add_argument("--num-heads", type=int, default=4)
    sp.add_argument("--mlp-dim", type=int, default=128)
    sp.add_argument("--num-blocks", type=int, default=2)
    sp.add_argument("--steps", type=int, default=300)
    sp.add_argument("--seq-parallel", type=int, default=0,
                    help="ring size over the 'seq' mesh axis; remaining "
                         "devices form the 'data' axis (0 = largest "
                         "power of two that divides the device count, "
                         "capped at 4)")
    sp.add_argument("--layout", choices=("contiguous", "zigzag"),
                    default="contiguous",
                    help="causal sequence layout (zigzag balances the "
                         "causal ring schedule, ~2x fewer FLOPs)")
    sp.add_argument("--block-impl", choices=("jnp", "pallas"),
                    default="jnp",
                    help="ring block engine (pallas keeps scores in "
                         "VMEM; needs t_local multiples of 128/256)")
    sp.add_argument("--remat", action="store_true",
                    help="jax.checkpoint each transformer block: the "
                         "backward recomputes block activations instead "
                         "of storing them (long-context memory lever)")
    sp.add_argument("--dropout", type=float, default=0.0,
                    help="residual dropout rate inside each block "
                         "(after attention and after the MLP)")
    sp.add_argument("--patch-size", type=int, default=5,
                    help="with --data-dir: each image becomes a raster "
                         "sequence of patch-size^2-pixel tokens "
                         "(data.sequences.patchify); 1 = per-pixel "
                         "sequence. --seq-len/--features are then "
                         "derived from the images, not the flags")
    sp.add_argument("--image-size", type=int, default=50,
                    help="with --data-dir: decode size of the IDC "
                         "patches (the reference's 50)")

    sp = sub.add_parser("lm",
                        help="causal LM through the ring: train "
                             "next-token on the counting task, then "
                             "greedy-generate via the ring-sharded "
                             "KV-cache decoder (beyond-reference)")
    common(sp)
    sp.add_argument("--vocab", type=int, default=16)
    sp.add_argument("--seq-len", type=int, default=64)
    sp.add_argument("--embed-dim", type=int, default=64)
    sp.add_argument("--num-heads", type=int, default=4)
    sp.add_argument("--mlp-dim", type=int, default=128)
    sp.add_argument("--num-blocks", type=int, default=2)
    sp.add_argument("--steps", type=int, default=200)
    sp.add_argument("--fsdp", type=int, default=0,
                    help="FSDP degree: shard params AND optimizer "
                         "state over a 'data' mesh axis of this size "
                         "(partition.py rules, registry rule set "
                         "'lm'); 0 = off (replicated state, the "
                         "historical layout)")
    sp.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree: shard the attention/"
                         "MLP/head weights over a 'model' mesh axis of "
                         "this size (Megatron orientation, "
                         "docs/SHARDING.md); composes with --fsdp on a "
                         "('data', 'model', 'seq') mesh; 0 = off")
    sp.add_argument("--seq-parallel", type=int, default=0,
                    help="ring size over the 'seq' mesh axis (0 = "
                         "largest dividing power of two, capped at 4)")
    sp.add_argument("--layout", choices=("contiguous", "zigzag"),
                    default="contiguous")
    sp.add_argument("--block-impl", choices=("jnp", "pallas"),
                    default="jnp")
    sp.add_argument("--remat", action="store_true")
    sp.add_argument("--dropout", type=float, default=0.0)
    sp.add_argument("--generate", type=int, default=12,
                    help="tokens to generate after training through "
                         "the KV-cache decoder (0 = skip); emitted in "
                         "ONE fused device dispatch (models/lm.py "
                         "Generator)")
    sp.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for --generate "
                         "(0 = greedy argmax, the default)")
    sp.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k most likely "
                         "tokens (0 = no restriction; needs "
                         "--temperature > 0)")

    sp = sub.add_parser("serve",
                        help="continuous-batching LM serving engine: "
                             "fixed decode slots, masked fused windows, "
                             "FIFO admission with backpressure "
                             "(serve/, beyond-reference)")
    sp.add_argument("--path", default=None,
                    help="artifact root (serving events stream to "
                         "<path>/logs/serve.jsonl)")
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--host-devices", type=int, default=0,
                    help="force N virtual CPU devices (TPU stand-in)")
    sp.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace of the serve loop "
                         "here (TensorBoard-viewable)")
    sp.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the serve "
                         "loop's spans (admission, prefill chunks, "
                         "decode windows, collects) here — "
                         "Perfetto-loadable")
    sp.add_argument("--vocab", type=int, default=16)
    sp.add_argument("--t-max", type=int, default=64,
                    help="cache capacity per slot (prompt + generation)")
    sp.add_argument("--embed-dim", type=int, default=32)
    sp.add_argument("--num-heads", type=int, default=2)
    sp.add_argument("--mlp-dim", type=int, default=64)
    sp.add_argument("--num-blocks", type=int, default=2)
    sp.add_argument("--seq-parallel", type=int, default=1,
                    help="ring size over the 'seq' mesh axis for the "
                         "serving mesh (caches shard over it)")
    sp.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree: serve with the "
                         "model's weights sharded over a 'model' mesh "
                         "axis of this size (partition.py rule set "
                         "'lm') while the KV caches keep their seq-"
                         "ring layout — params and KV shard "
                         "independently; 0 = off (replicated params)")
    sp.add_argument("--fsdp", type=int, default=0,
                    help="accepted for symmetry with the lm/profile "
                         "verbs but must stay 0 here: FSDP shards the "
                         "optimizer+param state over the batch axis at "
                         "TRAIN time; a serving engine holds no "
                         "optimizer state and prefills [1, P] batches "
                         "— use --tp for serving-side param sharding")
    sp.add_argument("--train-steps", type=int, default=0,
                    help="train the counting task this many steps "
                         "before serving (0 = serve from random init; "
                         "the engine exercises identically either way)")
    sp.add_argument("--slots", type=int, default=4,
                    help="concurrent decode slots")
    sp.add_argument("--window", type=int, default=8,
                    help="tokens per fused decode dispatch")
    sp.add_argument("--requests", type=int, default=16,
                    help="synthetic trace length (ignored with --trace)")
    sp.add_argument("--rate", type=float, default=50.0,
                    help="synthetic Poisson arrival rate, requests/s")
    sp.add_argument("--trace", default=None,
                    help="JSONL request trace to replay instead of the "
                         "synthetic Poisson one (serve.load_trace "
                         "format)")
    sp.add_argument("--realtime", action="store_true",
                    help="honor trace arrival times on the wall clock "
                         "(default: replay as fast as the engine "
                         "drains, order kept)")
    sp.add_argument("--temperature", type=float, default=0.0)
    sp.add_argument("--top-k", type=int, default=0)
    sp.add_argument("--eos", type=int, default=None,
                    help="stop token id (default: none — requests run "
                         "to their token budget)")
    sp.add_argument("--max-queue-depth", type=int, default=64,
                    help="admission-queue backpressure bound")
    sp.add_argument("--max-prefills-per-cycle", type=int, default=1,
                    help="prefill-vs-decode interleave cap per cycle")
    sp.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: admit prompts C tokens per "
                         "decode window instead of one monolithic "
                         "dispatch (0 = off; must divide --t-max). "
                         "Long prompts stop stalling in-flight decodes")
    sp.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="radix prefix cache budget in MB (0 = off; "
                         "needs --prefill-chunk): requests sharing a "
                         "token prefix reuse chunk-boundary KV "
                         "snapshots instead of recomputing them")
    sp.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8"),
                    help="ring-cache K/V storage: int8 halves HBM per "
                         "slot (per-(slot,head) scales, ~2x slots per "
                         "budget) at the cost of bounded logit drift — "
                         "leave bf16 when exact parity matters")
    sp.add_argument("--kv-page-size", type=int, default=0,
                    help="paged KV (0 = off, needs --kv-pages and "
                         "--prefill-chunk): replace the per-slot "
                         "[t_max] ring rows with fixed-size cache "
                         "pages + per-slot page tables, so HBM holds "
                         "tokens actually resident instead of every "
                         "slot's worst case. Must divide "
                         "--prefill-chunk (and t-max)")
    sp.add_argument("--kv-pages", type=int, default=0,
                    help="page-pool size for --kv-page-size: the HBM "
                         "budget in pages, shared by slots and prefix-"
                         "cache snapshots (pages*size must cover at "
                         "least one t-max request)")
    sp.add_argument("--kv-decode-reserve", type=int, default=0,
                    help="decode tokens PRE-reserved per admission on "
                         "the paged engine (0 = the full budget, "
                         "never exhausts mid-decode; smaller admits "
                         "optimistically and grows grants mid-decode, "
                         "quarantining honestly on exhaustion)")
    sp.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding (models/draft.py + the "
                         "engine's fixed-k verify program): an n-gram "
                         "prompt-lookup drafter proposes --draft-k "
                         "continuation tokens per slot from the "
                         "slot's own stream, ONE batched verify "
                         "dispatch accepts the prefix the model "
                         "itself would have emitted (+ its own pick "
                         "at the first miss) — up to k+1 tokens per "
                         "dispatch on repetitive/templated traffic, "
                         "token-identical to plain decode")
    sp.add_argument("--draft-k", type=int, default=8,
                    help="draft tokens per slot per verify dispatch "
                         "(the verify program's ONE compiled shape)")
    sp.add_argument("--ngram-order", type=int, default=3,
                    help="longest trailing n-gram the prompt-lookup "
                         "drafter matches against the stream's "
                         "history (falls back to shorter n-grams "
                         "down to 1)")
    sp.add_argument("--drafter", choices=sorted(SERVE_DRAFTERS),
                    default="ngram",
                    help="which drafter proposes under --spec-decode: "
                         + "; ".join(f"'{name}' = {entry[2]}"
                                     for name, entry
                                     in sorted(SERVE_DRAFTERS.items())))
    sp.add_argument("--draft-ckpt", default=None, metavar="DIR",
                    help="distilled draft-LM checkpoint directory "
                         "(models/draft_lm.save_draft_lm: sharded "
                         "params + draft_config.json sidecar) — "
                         "required by --drafter learned/chained; the "
                         "restore re-resolves layout against the "
                         "serving mesh")
    sp.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus text "
                         "exposition of the live registry) and GET "
                         "/healthz (last-tick age, queue depth, slot "
                         "occupancy) on 127.0.0.1:PORT for the run's "
                         "duration (0 = OS-assigned port, printed; "
                         "observe/exporter.py)")
    sp.add_argument("--serve-faults", default=None,
                    help="deterministic serve fault drill "
                         "(serve/faults.py), tick-indexed: e.g. "
                         "'nan_logits:3:0,stall:5-8:0.02,burst:2:16,"
                         "crash:40' — poisons/stalls/bursts/crashes "
                         "replay bit-identically; pair with "
                         "--max-retries and --journal to watch the "
                         "recovery paths work")
    sp.add_argument("--max-retries", type=int, default=0,
                    help="bounded re-admission for requests recovered "
                         "from a quarantined slot or a failed prefill "
                         "dispatch (0 = off; arming this also turns "
                         "on the per-cycle slot health checks)")
    sp.add_argument("--retry-backoff-ms", type=float, default=50.0,
                    help="base delay between retry attempts "
                         "(exponential: doubles per retry)")
    sp.add_argument("--journal", default=None,
                    help="request-journal WAL path (serve/journal.py): "
                         "accepted requests, per-tick progress, and "
                         "finishes; at startup any in-flight requests "
                         "a previous crashed run left in the file are "
                         "re-admitted through the normal path")
    sp.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent compile cache "
                         "(serve/compile_cache.py): AOT-serialized "
                         "decode/sample executables keyed on model "
                         "config + mesh + jaxlib version. First run "
                         "compiles and stores; later runs (and warm "
                         "replica spin-ups) deserialize instead of "
                         "recompiling")
    sp.add_argument("--brownout", action="store_true",
                    help="arm the staged degradation controller "
                         "(serve/brownout.py): when a declared SLO "
                         "burns or the queue passes the watermark, "
                         "pause prefix-cache writes -> clamp "
                         "max_new_tokens -> shed new submits (status "
                         "'shed'), restoring with hysteresis")
    sp.add_argument("--brownout-queue-high", type=int, default=None,
                    help="queue-depth escalation watermark for "
                         "--brownout (default: half --max-queue-depth)")
    sp.add_argument("--brownout-clamp-tokens", type=int, default=8,
                    help="the max_new_tokens bound brownout stage 2 "
                         "applies to new admissions")
    sp.add_argument("--brownout-dwell-ms", type=float, default=250.0,
                    help="minimum time between brownout escalations "
                         "(one stage per dwell while the signal "
                         "fires; lower it for fast drills)")
    sp.add_argument("--brownout-clear-ms", type=float, default=1000.0,
                    help="how long the signal must stay clear before "
                         "each one-stage restore (the hysteresis)")
    sp.add_argument("--slo-ttft-p95-ms", type=float, default=None,
                    help="declare a TTFT SLO: p95 of submit->first-"
                         "token <= this many ms, burn-rate-alerted "
                         "over sliding windows (observe/slo.py; "
                         "slo_alert events go to the run jsonl)")
    sp.add_argument("--slo-error-rate", type=float, default=None,
                    help="declare an error-rate SLO: at most this "
                         "fraction of requests may fail (rejected, "
                         "error, or deadline/timeout)")
    sp.add_argument("--slo-window-s", type=float, default=60.0,
                    help="the SLO engine's SHORT evaluation window in "
                         "seconds (the long window is 5x this)")
    sp.add_argument("--tenants", default=None,
                    help="multi-tenant serving (serve/tenancy.py): "
                         "comma-separated tenant names, first = the "
                         "default for untagged requests; the synthetic "
                         "Poisson trace tags arrivals round-robin. "
                         "Per-tenant quotas/SLOs isolate a flooding "
                         "tenant from its neighbors")
    sp.add_argument("--tenant-quota", action="append", default=None,
                    metavar="NAME=SLOTS[:QUEUED[:PAGES]]",
                    help="per-tenant admission quota (repeatable): "
                         "resident decode slots, queued requests, and "
                         "KV page budget — each an int >= 1 or '-' "
                         "for unlimited (e.g. acme=2:8:- caps acme at "
                         "2 slots and 8 queued). Needs --tenants")
    sp.add_argument("--tenant-slo-ttft-ms", action="append",
                    default=None, metavar="[NAME=]MS",
                    help="per-tenant TTFT p95 SLO in ms (repeatable): "
                         "NAME=MS for one tenant, a bare number for "
                         "every tenant. Burn-rate alerted per tenant "
                         "(ttft:<name>) and the tenant's own brownout "
                         "trigger — one tenant's flood sheds that "
                         "tenant only. Needs --tenants")
    sp.add_argument("--save-ckpt", default=None, metavar="DIR",
                    help="export the serving params as a sharded "
                         "checkpoint (checkpoint/sharded.py) before the "
                         "trace replays: each device writes only its "
                         "own shards, MANIFEST.json commits the save "
                         "atomically. Pair with --train-steps to mint "
                         "a --rollout candidate")
    sp.add_argument("--rollout", default=None, metavar="CKPT_DIR",
                    help="zero-downtime weight rollout "
                         "(checkpoint/rollout.py): mid-trace, restore "
                         "this sharded checkpoint against the SERVING "
                         "mesh + partition rules, canary "
                         "--canary-fraction of the traffic onto it, "
                         "compare error rate and TTFT p95 against the "
                         "live fleet-of-one, then promote (hot-swap "
                         "the live weights, zero recompile) or roll "
                         "back — no request is dropped or duplicated "
                         "either way")
    sp.add_argument("--canary-fraction", type=float, default=None,
                    help="traffic share routed to the --rollout canary "
                         "while it is open, in (0, 1] (tenant-affine: "
                         "whole tenants land on one side; default "
                         "0.25)")
    sp.add_argument("--canary-requests", type=int, default=None,
                    help="canary finishes required before the "
                         "promote/rollback verdict (default 4); a "
                         "trace that drains short of this ROLLS BACK "
                         "— insufficient evidence is not health")
    sp.add_argument("--rollout-at", type=float, default=None,
                    help="fraction of the trace submitted before the "
                         "rollout opens, in [0, 1) (default 0.25: the "
                         "live side banks baseline latency first)")
    sp.add_argument("--rollout-adapters", type=int, default=None,
                    metavar="RANK",
                    help="per-tenant adapter hot-swap drill, the cheap "
                         "first rung of a rollout: register rank-RANK "
                         "logit adapters for every tenant, serve the "
                         "trace, then swap a re-seeded bank in live — "
                         "no recompile, no dropped request. Needs "
                         "--tenants")

    sp = sub.add_parser(
        "serve-cluster", aliases=["serve_cluster"],
        help="disaggregated multi-replica serving (serve/cluster/): a "
             "router places requests on N engine replicas by health/"
             "load/page headroom/SLO burn, dedicated prefill replicas "
             "hand completed KV snapshots to decode replicas through "
             "the cluster prefix registry, and a killed replica's "
             "journaled requests migrate onto survivors")
    sp.add_argument("--path", default=None,
                    help="artifact root (cluster events stream to "
                         "<path>/logs/cluster.jsonl)")
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--host-devices", type=int, default=0,
                    help="force N virtual CPU devices — each replica "
                         "takes its own device slice")
    sp.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the "
                         "cluster's spans (placements, handoffs, "
                         "migrations, every replica's serve loop) "
                         "here")
    sp.add_argument("--vocab", type=int, default=16)
    sp.add_argument("--t-max", type=int, default=64)
    sp.add_argument("--embed-dim", type=int, default=32)
    sp.add_argument("--num-heads", type=int, default=2)
    sp.add_argument("--mlp-dim", type=int, default=64)
    sp.add_argument("--num-blocks", type=int, default=2)
    sp.add_argument("--replicas", type=int, default=2,
                    help="decode-capable replicas (each its own "
                         "engine on its own device slice)")
    sp.add_argument("--prefill-replicas", type=int, default=0,
                    help="dedicated prefill replicas: they never "
                         "decode — they drive chunked prefill and "
                         "publish boundary KV snapshots into the "
                         "cluster prefix registry for decode replicas "
                         "to adopt (needs --prefill-chunk and "
                         "--prefix-cache-mb)")
    sp.add_argument("--slots", type=int, default=4,
                    help="decode slots per replica")
    sp.add_argument("--window", type=int, default=8)
    sp.add_argument("--max-queue-depth", type=int, default=64,
                    help="per-replica admission-queue bound")
    sp.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill (0 = off; must divide "
                         "--t-max) — required for prefill replicas "
                         "and the prefix registry")
    sp.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="per-replica radix prefix cache budget in MB")
    sp.add_argument("--registry-mb", type=float, default=0.0,
                    help="cluster prefix-registry budget in MB (0 = "
                         "off): chunk-boundary snapshots published by "
                         "any replica, adopted by every other — a hot "
                         "system prompt is prefilled ONCE cluster-wide")
    sp.add_argument("--requests", type=int, default=16,
                    help="synthetic trace length (ignored with "
                         "--trace)")
    sp.add_argument("--rate", type=float, default=50.0,
                    help="synthetic Poisson arrival rate, requests/s")
    sp.add_argument("--trace", default=None,
                    help="JSONL request trace to replay "
                         "(serve.load_trace format)")
    sp.add_argument("--realtime", action="store_true",
                    help="honor trace arrival times on the wall clock")
    sp.add_argument("--eos", type=int, default=None)
    sp.add_argument("--temperature", type=float, default=0.0)
    sp.add_argument("--top-k", type=int, default=0)
    sp.add_argument("--journal-dir", default=None,
                    help="directory for per-replica journal WALs "
                         "(<dir>/journal-<replica>.jsonl) — required "
                         "for the kill drill's migration")
    sp.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent compile cache shared by every "
                         "replica (serve/compile_cache.py): the first "
                         "replica compiles and stores, the rest — and "
                         "any autoscaled spin-up — deserialize warm")
    sp.add_argument("--autoscale-max", type=int, default=None,
                    metavar="N",
                    help="arm the autoscaler "
                         "(serve/cluster/autoscaler.py): scale the "
                         "decode fleet between --replicas and N from "
                         "the replicas' own health documents (queue "
                         "depth, shedding, page headroom) with dwell "
                         "+ cooldown hysteresis; scale-down drains "
                         "the least-loaded replica and live-migrates "
                         "its in-flight slots onto survivors")
    sp.add_argument("--max-retries", type=int, default=2,
                    help="router-level re-placement bound per request "
                         "(migrations + hedges)")
    sp.add_argument("--hedge-after-ms", type=float, default=None,
                    help="duplicate a still-unfinished request onto a "
                         "second replica this long after placement "
                         "(first result wins; off by default)")
    sp.add_argument("--brownout-queue-high", type=int, default=None,
                    help="arm a per-replica brownout controller at "
                         "this queue-depth watermark (also the drain "
                         "mechanism: a draining replica jumps to its "
                         "shed stage)")
    sp.add_argument("--kill-replica", type=int, default=None,
                    help="failover drill: hard-kill replica INDEX "
                         "after --kill-after-steps router steps and "
                         "migrate its journaled requests onto the "
                         "survivors (needs --journal-dir)")
    sp.add_argument("--kill-after-steps", type=int, default=4,
                    help="router steps before the --kill-replica "
                         "drill fires")
    sp.add_argument("--drain-replica", type=int, default=None,
                    help="drain drill: gracefully drain replica INDEX "
                         "after --kill-after-steps router steps "
                         "(placement stops, in-flight work completes)")
    sp.add_argument("--metrics-port", type=int, default=None,
                    help="serve the FLEET observability surfaces on "
                         "127.0.0.1:PORT for the run's duration: GET "
                         "/metrics merges every replica's registry "
                         "into one replica-labeled exposition plus "
                         "fleet rollups, GET /healthz embeds every "
                         "replica's health document with autoscaler "
                         "and compile-cache state (0 = OS-assigned "
                         "port, printed; serve/cluster/telemetry.py)")
    sp.add_argument("--watchdog", action="store_true",
                    help="arm the cluster anomaly watchdogs "
                         "(speculative accept-rate collapse, per-"
                         "replica compile churn, migration-rate "
                         "spikes, canary-vs-baseline SLO divergence): "
                         "one detector pass per router step, each "
                         "firing emits a frozen cluster_anomaly jsonl "
                         "record and bumps cluster_anomalies_total")

    sp = sub.add_parser(
        "profile",
        help="performance attribution over a subsystem's hot loop "
             "(observe/profile.py): run N steps, report every compiled "
             "program's XLA cost/memory account, a compute-bound vs "
             "bandwidth-bound roofline verdict, device-wait vs "
             "host-gap step-time attribution, and the compile-churn "
             "watchdog's findings; writes frozen-schema "
             "profile_program/profile_step jsonl (rendered by `stats`)")
    sp.add_argument("--model", required=True,
                    choices=("vgg", "mobile", "dense", "small", "serve",
                             "lm"),
                    help="which hot loop to profile: a backbone's "
                         "fine-tune train step (vgg/mobile/dense, at "
                         "configs.BENCH_TRAIN_CONFIGS; `small` is the tiny "
                         "CPU-smoke CNN), the continuous-batching "
                         "serve decode loop, or the LM train step "
                         "(`lm` — composes with --fsdp/--tp to "
                         "account the SHARDED step's per-device peak "
                         "HBM against the replicated figure)")
    sp.add_argument("--fsdp", type=int, default=0,
                    help="with --model lm: FSDP degree (params + "
                         "optimizer state shard over a 'data' axis of "
                         "this size; partition.py rule set 'lm'); the "
                         "epilogue reports per-device peak HBM from "
                         "XLA program accounting")
    sp.add_argument("--tp", type=int, default=0,
                    help="with --model lm: tensor-parallel degree "
                         "(weights shard over a 'model' axis); "
                         "composes with --fsdp")
    sp.add_argument("--steps", type=int, default=None,
                    help="measured steps/windows (default: 30 on an "
                         "accelerator, 4 on CPU)")
    sp.add_argument("--batch-size", type=int, default=None,
                    help="per-chip batch for the train loops (default: "
                         "the configs.BENCH_TRAIN_CONFIGS batch on an "
                         "accelerator, 8 on CPU)")
    sp.add_argument("--path", default=None,
                    help="artifact root (profile events stream to "
                         "<path>/logs/profile.jsonl)")
    sp.add_argument("--out", default=None,
                    help="explicit profile jsonl path (overrides "
                         "--path's default location)")
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--host-devices", type=int, default=0,
                    help="force N virtual CPU devices (TPU stand-in)")
    sp.add_argument("--compile-limit", type=int, default=5,
                    help="compile-churn watchdog: flag any program "
                         "compiled more than this many times during "
                         "the run")
    sp.add_argument("--peak-tflops", type=float, default=None,
                    help="override/declare the backend's peak dense "
                         "bf16 TFLOP/s (required with --peak-gbps for "
                         "roofline verdicts on backends the table "
                         "does not know, e.g. CPU)")
    sp.add_argument("--peak-gbps", type=float, default=None,
                    help="override/declare the backend's peak memory "
                         "bandwidth in GB/s")
    sp.add_argument("--depthwise-impl", default="grouped",
                    choices=("grouped", "taps", "fused"),
                    help="with --model mobile: the depthwise lowering "
                         "(models/core.py depthwise_conv2d). 'fused' "
                         "runs the Pallas depthwise+BN+relu6 chain "
                         "(ops/fused_conv.py) and merges its analytic "
                         "FLOPs/bytes into the train.step account — "
                         "Pallas calls are opaque to XLA "
                         "cost_analysis, so without the merge the "
                         "roofline verdict would read from "
                         "under-counted zeros")
    sp.add_argument("--churn-drill", action="store_true",
                    help="end the run with a deliberately "
                         "shape-varying jitted loop so the "
                         "compile-churn watchdog demonstrably fires "
                         "(drill; a clean run stays silent)")
    sp.add_argument("--trace-out", default=None,
                    help="also export the run's spans as Chrome "
                         "trace-event JSON (Perfetto-loadable)")

    sp = sub.add_parser("stats",
                        help="offline summary of any run jsonl (train, "
                             "fed, or serve): per-event counts, "
                             "percentiles over every numeric field, "
                             "timer/span timing tables, and the last "
                             "metrics snapshot — no re-run needed")
    sp.add_argument("jsonl", nargs="+",
                    help="path(s) to run.jsonl / serve.jsonl / "
                         "exported span jsonl — several files (e.g. "
                         "every replica's log plus the router's) "
                         "merge into ONE summary, so --request "
                         "renders a cross-replica timeline")
    sp.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object instead "
                         "of the human table (includes the per-request "
                         "timeline table under 'requests')")
    sp.add_argument("--request", default=None, metavar="RID",
                    help="render ONE request's timeline (every serve_* "
                         "event and rid-stamped span for that id, "
                         "time-ordered) instead of the whole-run "
                         "summary")
    sp.add_argument("--top", type=int, default=15,
                    help="rows in the span self-time (exclusive-time) "
                         "table — the flame-style 'where does the "
                         "time go' answer from any span export")

    sp = sub.add_parser("convert-weights", aliases=["convert_weights"],
                        help="one-time offline conversion of a Keras "
                             "save_weights .h5 into the framework's .npz "
                             "pytree artifact")
    sp.add_argument("input", help="Keras .h5 weights file")
    sp.add_argument("output", help="destination .npz")
    sp.add_argument("--model", default=None,
                    choices=("vgg16", "mobilenet_v2", "densenet201"),
                    help="validate converted tensors against this "
                         "backbone's shapes")

    ns = p.parse_args(argv)
    ns.preset_key = ns.preset_key.replace("-", "_")
    return ns


def _apply_overrides(preset, ns, fields):
    kw = {}
    for f in fields:
        v = getattr(ns, f, None)
        if v is not None:
            kw[f] = v
    return dataclasses.replace(preset, **kw) if kw else preset


def _logger(ns):
    from idc_models_tpu.observe import JsonlLogger

    if ns.path is None:
        return None
    return JsonlLogger(Path(ns.path) / "logs" / "run.jsonl")


def _finish_logger(logger) -> None:
    """The shared tail of every logged run: append ONE metrics_snapshot
    record (the process-wide registry's counters/gauges/histograms —
    a NEW additive event type the `stats` verb renders) and close."""
    if not logger:
        return
    from idc_models_tpu.observe import REGISTRY

    REGISTRY.log_snapshot(logger)
    logger.close()


class _DrainRequested(Exception):
    """Raised from the SIGTERM handler to unwind the serve loop into
    the graceful-drain path (admissions stop, in-flight work
    finishes, the journal flushes)."""


def _arm_sigterm():
    """Install a SIGTERM handler that raises _DrainRequested in the
    main thread. Returns the previous handler so the caller can
    restore it, or None when installation is impossible (non-main
    thread — e.g. a test harness driving the verb from a worker)."""
    import signal

    def _handler(signum, frame):
        raise _DrainRequested()

    try:
        return signal.signal(signal.SIGTERM, _handler)
    except ValueError:
        return None


def _disarm_sigterm(prev) -> None:
    import signal

    if prev is None:
        return
    try:
        signal.signal(signal.SIGTERM, prev)
    except ValueError:
        pass


def _data_root(ns):
    """--data-dir > <path>/data/balanced_IDC_30k > None (synthetic)."""
    root = ns.data_dir
    if root is None and ns.path is not None:
        cand = Path(ns.path) / "data" / "balanced_IDC_30k"
        if cand.exists():
            root = cand
    return root


def _load_idc(ns, image_size, limit):
    from idc_models_tpu.data import synthetic
    from idc_models_tpu.data.idc import ArrayDataset, load_directory

    root = _data_root(ns)
    if root is not None:
        return load_directory(root, image_size=image_size, limit=limit,
                              seed=ns.seed)
    print(f"[idc_models_tpu] no IDC data found; using "
          f"{ns.synthetic_examples} synthetic {image_size}x{image_size} "
          f"patches", file=sys.stderr)
    imgs, labels = synthetic.make_idc_like(ns.synthetic_examples,
                                           size=image_size, seed=ns.seed)
    return ArrayDataset(imgs, labels)


def _streamed_idc_splits(ns, preset, global_batch):
    """80/10/10 split at the FILE level: train as a FileStream (decoded
    per batch), val/test materialized (they are small and eval needs
    ArrayDatasets)."""
    import numpy as np

    from idc_models_tpu.data.idc import (
        ArrayDataset, decode_pairs, list_shuffled_pairs,
    )
    from idc_models_tpu.data.pipeline import FileStream

    root = _data_root(ns)
    if root is None:
        return None
    pairs = list_shuffled_pairs(root, seed=ns.seed,
                                limit=preset.dataset_limit)
    n = len(pairs)
    n_tr, n_va = int(0.8 * n), int(0.1 * n)
    if n_tr < global_batch or n_va == 0 or n - n_tr - n_va == 0:
        sys.exit(f"--stream: {n} files are too few for an 80/10/10 split "
                 f"at global batch {global_batch}")
    train = FileStream(pairs[:n_tr], preset.image_size, global_batch,
                       seed=ns.seed, repeat=preset.repeats,
                       decode_workers=ns.decode_workers)

    def materialize(subset):
        labels = np.asarray([l for _, l in subset], np.int32)
        return ArrayDataset(decode_pairs(subset, preset.image_size), labels)

    val = materialize(pairs[n_tr:n_tr + n_va])
    test = materialize(pairs[n_tr + n_va:])
    return train, val, test


def _fetch_scalars(tree):
    """Fetch a pytree of device scalars in ONE host transfer.

    Every individual device->host fetch is a synchronous round-trip
    (1.8 ms for one scalar on the v5e host, PR 21; 50-90 ms under the
    remote runtime this was written on), and `jax.device_get` of a
    metrics dict fetches leaf by leaf. Stacking on device first makes
    the whole fetch one transfer."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    if _fetch_scalars._stack is None:
        import jax.numpy as jnp

        _fetch_scalars._stack = jax.jit(
            lambda ls: jnp.stack([jnp.float32(x).reshape(()) for x in ls]))
    vals = np.asarray(_fetch_scalars._stack(leaves))
    return jax.tree.unflatten(treedef, [float(v) for v in vals])


_fetch_scalars._stack = None


def _run_stats(ns):
    """Offline run-log rollup (observe/stats.py): works on any jsonl
    the framework writes — train/fed run.jsonl, serve.jsonl, or a
    tracer's exported span jsonl."""
    import json

    from idc_models_tpu.observe import (
        format_request_timeline, format_summary, summarize_jsonl,
    )

    paths = [Path(p) for p in ns.jsonl]
    for p in paths:
        if not p.exists():
            sys.exit(f"stats: no such file: {p}")
    summary = summarize_jsonl(paths[0] if len(paths) == 1 else paths)
    if ns.request is not None:
        # format_request_timeline owns the unknown-rid message (KeyError)
        # — rendering even on the --json path keeps one validation site
        try:
            text = format_request_timeline(summary, ns.request)
        except KeyError as e:
            sys.exit(f"stats: {e.args[0]}")
        if ns.json:
            print(json.dumps(
                {ns.request: summary["requests"][ns.request]}))
        else:
            print(text)
    elif ns.json:
        print(json.dumps(summary))
    else:
        if ns.top < 1:
            sys.exit(f"stats: --top {ns.top} must be >= 1")
        print(format_summary(summary, top=ns.top))


def _run_profile(ns):
    """Performance attribution over one subsystem's hot loop (ISSUE 9,
    observe/profile.py): program cost/memory accounting through the
    single `program_report` extraction point, a roofline verdict
    (compute-bound vs bandwidth-bound with achieved-fraction-of-roof
    numbers), device-wait vs host-gap step-time attribution from
    `device.sync`-bracketed spans, and the compile-churn watchdog's
    process-wide findings — printed human-readable and written as
    frozen-schema `profile_program`/`profile_step` jsonl events."""
    import json  # noqa: F401  (parity with sibling runners)

    import jax

    from idc_models_tpu.observe import JsonlLogger, REGISTRY, trace
    from idc_models_tpu.observe import profile as prof

    if ns.steps is not None and ns.steps < 1:
        sys.exit(f"profile: --steps {ns.steps} must be >= 1")
    if ns.batch_size is not None and ns.batch_size < 1:
        sys.exit(f"profile: --batch-size {ns.batch_size} must be >= 1")
    if ns.compile_limit < 1:
        sys.exit(f"profile: --compile-limit {ns.compile_limit} must "
                 f"be >= 1")
    if (ns.peak_tflops is None) != (ns.peak_gbps is None):
        sys.exit("profile: --peak-tflops and --peak-gbps declare the "
                 "two axes of one roofline — pass both or neither")
    if ns.fsdp < 0 or ns.tp < 0:
        sys.exit(f"profile: --fsdp/--tp must be >= 0 (0 = off), got "
                 f"{ns.fsdp}/{ns.tp}")
    if (ns.fsdp > 1 or ns.tp > 1) and ns.model != "lm":
        sys.exit(f"profile: --fsdp/--tp shard the LM's rule-based "
                 f"partition layout (--model lm); the {ns.model} "
                 f"model's default rules are replicated")
    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    if ns.peak_tflops is not None:
        try:
            prof.register_roof(dev.device_kind, ns.peak_tflops,
                               ns.peak_gbps)
        except ValueError as e:
            sys.exit(f"profile: {e}")
    wd = prof.arm_watchdog(limit=ns.compile_limit)
    # the main() --trace-out context may already have armed a tracer
    # (then the full run, warmups included, lands in the export); the
    # timeline below only consumes the measured region either way
    own = trace.get_tracer() is None
    prev = trace.set_tracer(trace.Tracer()) if own else None
    tr = trace.get_tracer()
    try:
        if ns.model == "serve":
            progs, mark = _profile_serve(ns, on_accel)
        elif ns.model == "lm":
            progs, mark = _profile_lm(ns, on_accel, dev)
        else:
            progs, mark = _profile_train_step(ns, on_accel, dev)
        if ns.churn_drill:
            _profile_churn_drill(ns.compile_limit)
        records = prof.records_since(tr, mark)
    finally:
        prof.disarm_watchdog()
        if own:
            trace.set_tracer(prev)

    timeline = prof.DeviceTimeline().consume(records)
    step_stats = timeline.report()
    print("programs (performance attribution):")
    recs = []
    for name, (cost, roofline, step_ms) in progs.items():
        rec = prof.program_record(cost, roofline, step_ms=step_ms,
                                  device_kind=dev.device_kind)
        recs.append(rec)
        print(prof.format_program(rec))
    print("step-time attribution (device-wait vs host-gap):")
    print(timeline.format_report(step_stats))
    rep = wd.report()
    line = (f"compiles: {rep['total_compiles']} observed, "
            f"{rep['compile_seconds_total']} s total")
    if rep["flagged"]:
        line += (f"; CHURN flagged: {', '.join(rep['flagged'])} "
                 f"(> {rep['limit']} compiles each — a shape/dtype is "
                 f"varying per call)")
    else:
        line += "; churn: none"
    print(line)

    out_path = ns.out or (Path(ns.path) / "logs" / "profile.jsonl"
                          if ns.path else None)
    if out_path:
        with JsonlLogger(out_path) as logger:
            for rec in recs:
                logger.log(event="profile_program", **rec)
            for loop, st in step_stats.items():
                logger.log(event="profile_step",
                           **prof.step_record(loop, st))
            REGISTRY.log_snapshot(logger)
        print(f"profile events written to {out_path}")


def _profile_train_step(ns, on_accel, dev):
    """Profile one backbone's fine-tune train step at its
    `configs.BENCH_TRAIN_CONFIGS` entry (smoke scale on CPU). Two
    measured passes: a throughput window (k dispatches, ONE data-
    dependent fence — per-step fencing would put a host round-trip
    into every step of the MFU number) for the roofline verdict, then
    a FENCED pass (one `device.sync` fetch per `profile.step`) for the
    device-wait vs host-gap split. The step re-fed one resident batch
    is not what `fit()` delivers: the train cells of `benchmark/`
    measure that (PERF.md)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models import registry, small_cnn
    from idc_models_tpu.observe import profile as prof
    from idc_models_tpu.observe import trace
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_train_step, replicate,
        rmsprop, shard_batch,
    )
    from idc_models_tpu.train.losses import (
        binary_cross_entropy, sparse_categorical_cross_entropy,
    )

    from idc_models_tpu.configs import BENCH_TRAIN_CONFIGS

    if ns.model == "small":
        cfg = dict(model=None, image=10, outputs=1, ft=None,
                   lr=1e-3, batch=64)
    else:
        # the one table of per-backbone profile configurations
        name = {"vgg": "vgg16", "mobile": "mobilenet_v2",
                "dense": "densenet201"}[ns.model]
        bc = BENCH_TRAIN_CONFIGS[name]
        cfg = dict(model=name, image=bc["image_size"],
                   outputs=bc["num_outputs"], ft=bc["fine_tune_at"],
                   lr=bc["lr"], batch=bc["batch_per_chip"])
    n_dev = len(jax.devices())
    batch = ns.batch_size or (cfg["batch"] if on_accel else 8)
    steps = ns.steps or (30 if on_accel else 4)
    total = batch * n_dev
    if cfg["model"] is None:
        model = small_cnn(cfg["image"], 3, cfg["outputs"])
        variables = model.init(jax.random.key(ns.seed))
        opt = rmsprop(cfg["lr"])
    else:
        spec = registry.get_model(cfg["model"])
        # BN-freeze only exists on the BN backbones (VGG has none)
        build_kw = ({"bn_frozen_below": cfg["ft"]}
                    if ns.model in ("mobile", "dense") else {})
        if ns.model == "mobile":
            build_kw["depthwise_impl"] = ns.depthwise_impl
        model = spec.build(cfg["outputs"], 3, **build_kw)
        variables = model.init(jax.random.key(ns.seed))
        opt = rmsprop(cfg["lr"],
                      trainable_mask=spec.fine_tune_mask(
                          variables.params, cfg["ft"]))
    loss_fn = (binary_cross_entropy if cfg["outputs"] == 1
               else sparse_categorical_cross_entropy)
    mesh = meshlib.data_mesh()
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    step = jit_data_parallel(
        make_train_step(model, opt, loss_fn,
                        compute_dtype=jnp.bfloat16), mesh)
    rng = np.random.default_rng(ns.seed)
    s = cfg["image"]
    imgs = rng.random((total, s, s, 3)).astype(np.float32)
    labels = rng.integers(0, max(cfg["outputs"], 2),
                          total).astype(np.int32)
    state = replicate(mesh, state)
    x, y = shard_batch(mesh, imgs, labels)
    with prof.compiling("train.step"):
        compiled = step.lower(state, x, y,
                              jax.random.key(ns.seed + 1)).compile()
    cost = prof.program_report(compiled, name="train.step")
    if ns.model == "mobile" and ns.depthwise_impl == "fused":
        # the fused depthwise chains run as Pallas custom calls, which
        # XLA's cost_analysis reports at zero — merge their analytic
        # account so the roofline verdict reads real intensity instead
        # of silently under-counted figures
        from idc_models_tpu.models import mobilenet
        from idc_models_tpu.ops import fused_conv

        k_flops, k_bytes = fused_conv.depthwise_chain_cost(
            mobilenet.fused_call_shapes(total, cfg["image"]))
        cost = prof.augment_cost(cost, flops=k_flops,
                                 bytes_accessed=k_bytes)
    cost = prof.register_cost("train.step", cost)
    digest = jax.jit(
        lambda st: jnp.sum(jax.tree.leaves(
            st.params)[0].astype(jnp.float32)))
    box = {"s": state, "k": jax.random.key(ns.seed + 1)}

    def one_step():
        box["k"], sub = jax.random.split(box["k"])
        box["s"], _ = compiled(box["s"], x, y, sub)

    def fence():
        return float(digest(box["s"]))

    one_step()
    one_step()
    fence()                                  # warm + fence
    mark = prof.trace_mark(trace.get_tracer())
    t0 = time.perf_counter()                 # throughput window
    for _ in range(steps):
        one_step()
    fence()
    step_s = (time.perf_counter() - t0) / steps
    for _ in range(steps):                   # fenced attribution pass
        with trace.span("profile.step"):
            one_step()
            with trace.span("device.sync"):
                fence()
    roofline = prof.roofline_verdict(cost, step_s, dev, n_dev=n_dev)
    pps = total / step_s / n_dev
    print(f"profile: train.step ({cfg['model'] or 'small_cnn'}, batch "
          f"{batch}/chip x {n_dev} device(s), {steps} steps)")
    print(f"  throughput {pps:.1f} patches/sec/chip, "
          f"{step_s * 1e3:.2f} ms/step")
    return {"train.step": (cost, roofline, step_s * 1e3)}, mark


def _profile_lm(ns, on_accel, dev):
    """Profile the LM train step — replicated or rule-sharded
    (--fsdp/--tp, partition.py): the acceptance surface for ROADMAP
    item 2, driveable from the command line. The epilogue's
    per-device peak-HBM line comes from XLA program accounting
    (memory_analysis reports the PER-DEVICE argument/temp footprint,
    so a sharded step's figure drops below the replicated one on the
    same config — capacity, not wall-clock, per the CPU measurement
    policy)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models import registry
    from idc_models_tpu.models.lm import attention_lm, next_token_loss
    from idc_models_tpu.observe import profile as prof
    from idc_models_tpu.observe import trace
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_train_step, rmsprop,
        shard_batch,
    )
    from idc_models_tpu.train.step import place_state

    if on_accel:
        vocab, e, mlp, heads, blocks, seq_len = 8192, 1024, 4096, 8, 4, 512
    else:
        vocab, e, mlp, heads, blocks, seq_len = 512, 128, 512, 4, 2, 64
    sharded = ns.fsdp > 1 or ns.tp > 1
    f, t = max(ns.fsdp, 1), max(ns.tp, 1)
    n_dev = len(jax.devices())
    if f * t > n_dev:
        sys.exit(f"profile: --fsdp {f} x --tp {t} needs {f * t} "
                 f"devices, have {n_dev} (use --host-devices)")
    mesh = meshlib.fsdp_tp_mesh(f, t, 1)
    rules = registry.get_partition_rules("lm") if sharded else None
    batch = ns.batch_size or (8 if on_accel else 4)
    if batch % f:
        sys.exit(f"profile: --batch-size {batch} must divide by "
                 f"--fsdp {f} (the batch shards over the same 'data' "
                 f"axis the params shard over)")
    steps = ns.steps or (30 if on_accel else 4)
    model = attention_lm(vocab, seq_len, embed_dim=e, num_heads=heads,
                         mlp_dim=mlp, num_blocks=blocks, mesh=mesh)
    opt = rmsprop(3e-3)
    variables = model.init(jax.random.key(ns.seed))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    step = jit_data_parallel(
        make_train_step(model, opt, next_token_loss), mesh,
        axis=meshlib.DATA_AXIS,
        state_shardings=(rules.shardings(mesh, state)
                         if rules is not None else None))
    state = place_state(mesh, state, rules=rules)
    rng = np.random.default_rng(ns.seed + 1)
    seqs = jnp.asarray((rng.integers(0, vocab, (batch, 1))
                        + np.arange(seq_len)) % vocab, jnp.int32)
    x = shard_batch(mesh, seqs, axis=meshlib.DATA_AXIS)
    with prof.compiling("train.step"):
        compiled = step.lower(state, x, x,
                              jax.random.key(ns.seed + 2)).compile()
    cost = prof.register_program("train.step", compiled)
    digest = jax.jit(lambda st: jnp.sum(
        st.params["embed"].astype(jnp.float32)))
    box = {"s": state, "k": jax.random.key(ns.seed + 2)}

    def one_step():
        box["k"], sub = jax.random.split(box["k"])
        box["s"], _ = compiled(box["s"], x, x, sub)

    def fence():
        return float(digest(box["s"]))

    one_step()
    one_step()
    fence()                                  # warm + fence
    mark = prof.trace_mark(trace.get_tracer())
    t0 = time.perf_counter()                 # throughput window
    for _ in range(steps):
        one_step()
    fence()
    step_s = (time.perf_counter() - t0) / steps
    for _ in range(steps):                   # fenced attribution pass
        with trace.span("profile.step"):
            one_step()
            with trace.span("device.sync"):
                fence()
    roofline = prof.roofline_verdict(cost, step_s, dev,
                                     n_dev=mesh.devices.size)
    layout = (f"fsdp={f}, tp={t} (rule set 'lm': params + optimizer "
              f"state sharded)" if sharded else "replicated")
    print(f"profile: train.step (lm {e}x{blocks}, vocab {vocab}, seq "
          f"{seq_len}, batch {batch} global, {steps} steps) — {layout}")
    print(f"  {step_s * 1e3:.2f} ms/step")
    if cost.peak_hbm_bytes is not None:
        # THE acceptance line: per-device resident footprint of the
        # compiled step (args + outputs + temps - donated aliases)
        print(f"  per-device peak HBM: "
              f"{cost.peak_hbm_bytes / 2**20:.2f} MiB over "
              f"{mesh.devices.size} device(s)")
    return {"train.step": (cost, roofline, step_s * 1e3)}, mark


def _profile_serve(ns, on_accel):
    """Profile the continuous-batching decode loop: slots saturated
    with long-budget requests, steady-state windows timed through the
    scheduler (collect's token fetch is the `device.sync` fence), the
    engine's compiled programs accounted via AOT accounting copies."""
    import time

    import jax
    import jax.numpy as jnp

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models.lm import attention_lm
    from idc_models_tpu.observe import profile as prof
    from idc_models_tpu.observe import trace
    from idc_models_tpu.serve import LMServer, Request

    if on_accel:
        vocab, e, heads, blocks, mlp = 1024, 512, 8, 2, 2048
        t_max, n_slots, window = 2048, 8, 64
    else:
        vocab, e, heads, blocks, mlp = 32, 32, 2, 2, 64
        t_max, n_slots, window = 128, 4, 8
    dev = jax.devices()[0]
    mesh = meshlib.seq_mesh(1)
    model = attention_lm(vocab, t_max, embed_dim=e, num_heads=heads,
                         mlp_dim=mlp, num_blocks=blocks, mesh=mesh)
    params = model.init(jax.random.key(ns.seed)).params
    # the server's warmup compiles ~20 DISTINCT programs once each —
    # they stay in the unnamed bucket, which the churn detector
    # exempts for exactly this reason (one bucket of one-shot
    # compiles is not one program recompiling)
    from idc_models_tpu.models.draft_lm import (
        DraftLM, draft_config, draft_lm,
    )

    dcfg = draft_config(vocab, t_max)
    dparams = draft_lm(dcfg, mesh=mesh).init(
        jax.random.key(ns.seed + 1)).params

    class _NoDraft:
        # arms the engine's fixed-k verify program AND the drafter's
        # device state (via `learned`) so lm.verify and serve.propose
        # are both ACCOUNTED (cost/roofline), while never proposing —
        # the measured loop stays pure fused windows, so window_s
        # times exactly the program the serve.window verdict is
        # paired with
        learned = DraftLM(min(8, window), dparams, dcfg)

        def propose(self, history):
            return None

    server = LMServer(params, embed_dim=e, num_heads=heads,
                      num_blocks=blocks, t_max=t_max, n_slots=n_slots,
                      window=window, mesh=mesh,
                      cache_dtype=jnp.bfloat16,
                      spec_decode=True, draft_k=min(8, window),
                      drafter=_NoDraft())
    budget = t_max - 8
    for i in range(n_slots):
        server.submit(Request(id=f"p{i}", prompt=(1, 2, 3, 4),
                              max_new_tokens=budget))
    server.step()                            # admissions + first window
    server.step()                            # steady state
    costs = server.engine.program_costs(window)
    steps = ns.steps or max(budget // window - 4, 2)
    mark = prof.trace_mark(trace.get_tracer())
    t0 = time.perf_counter()
    n = 0
    for _ in range(steps):
        if server.scheduler.idle():
            break
        server.step()
        n += 1
    window_s = (time.perf_counter() - t0) / max(n, 1)
    server.close()
    # the PAGED twin at the same decode configuration: saturate, time
    # steady-state windows, and account serve.window_paged — so the
    # report shows the page-table gather indirection's cost NEXT TO
    # the contiguous serve.window figure (ISSUE 11)
    page_size = max(t_max // 16, 1)
    paged_server = LMServer(
        params, embed_dim=e, num_heads=heads, num_blocks=blocks,
        t_max=t_max, n_slots=n_slots, window=window, mesh=mesh,
        cache_dtype=jnp.bfloat16, prefill_chunk=page_size,
        kv_page_size=page_size,
        kv_pages=n_slots * (t_max // page_size))
    for i in range(n_slots):
        paged_server.submit(Request(id=f"g{i}", prompt=(1, 2, 3, 4),
                                    max_new_tokens=budget))
    for _ in range(n_slots + 2):   # chunked admissions settle (one
        paged_server.step()        # chunk dispatch per cycle)
    paged_costs = paged_server.engine.program_costs(window)
    t0 = time.perf_counter()
    np_ = 0
    for _ in range(steps):
        if paged_server.scheduler.idle():
            break
        paged_server.step()
        np_ += 1
    paged_window_s = (time.perf_counter() - t0) / max(np_, 1)
    paged_server.close()
    wcost = costs["serve.window"]
    roofline = prof.roofline_verdict(wcost, window_s, dev)
    progs = {"serve.window": (wcost, roofline, window_s * 1e3)}
    pw = paged_costs.pop("serve.window_paged")
    progs["serve.window_paged"] = (
        pw, prof.roofline_verdict(pw, paged_window_s, dev),
        paged_window_s * 1e3)
    for name, c in list(costs.items()) + list(paged_costs.items()):
        if name in progs:
            continue
        # untimed programs (admission prefill, the speculative verify)
        # still get an intensity-based compute-vs-bandwidth verdict —
        # achieved fractions need a measured step and stay None
        progs[name] = (c, prof.roofline_verdict(c, None, dev), None)
    print(f"profile: serve decode loop ({n_slots} slots x {window} "
          f"tokens/window, {n} measured windows)")
    print(f"  {window_s * 1e3:.2f} ms/window, "
          f"{n_slots * window / window_s:.1f} tokens/sec at full "
          f"occupancy")
    print(f"  paged: {paged_window_s * 1e3:.2f} ms/window "
          f"({np_} measured) — indirection overhead "
          f"{(paged_window_s / window_s - 1) * 100:+.1f}% vs "
          f"contiguous")
    return progs, mark


def _profile_churn_drill(limit: int) -> None:
    """The injected recompile loop: a jitted reduction called with a
    DIFFERENT shape every iteration, so the watchdog's churn detector
    demonstrably fires (`churn.drill` exceeds the limit) while a clean
    warm run stays silent."""
    import jax
    import jax.numpy as jnp

    from idc_models_tpu.observe import profile as prof

    f = jax.jit(lambda t: jnp.sum(t * 2.0))
    with prof.compiling("churn.drill"):
        for n in range(limit + 2):
            float(f(jnp.zeros((n + 1,), jnp.float32)))


def _run_convert(ns):
    """Keras .h5 -> framework .npz (SURVEY.md §7 'hard parts': one-time
    offline ImageNet weight conversion, no TF at runtime)."""
    import numpy as np

    from idc_models_tpu.models.pretrained import (
        _flatten, load_pretrained_file, save_npz,
    )

    params, state = load_pretrained_file(ns.input)
    if ns.model:
        import jax

        from idc_models_tpu.models import registry

        spec = registry.get_model(ns.model)

        # shapes only — no need to materialize a DenseNet-sized init
        def _init_shapes():
            v = spec.build(1, 3).init(jax.random.key(0))
            return {"params": v.params, "state": v.state}

        shapes = jax.eval_shape(_init_shapes)

        def check(loaded, target, what):
            flat_t = _flatten(target)
            mis = [k for k, v in _flatten(loaded).items()
                   if k not in flat_t
                   or tuple(np.shape(v)) != tuple(flat_t[k].shape)]
            n = len(_flatten(loaded)) - len(mis)
            print(f"validated {what} against {ns.model}: {n} tensors "
                  f"match, {len(mis)} mismatches")
            for m in mis[:10]:
                print(" ", m)
            return len(mis)

        bad = check(params, shapes["params"]["backbone"], "params")
        if state:
            bad += check(state, shapes["state"].get("backbone", {}),
                         "state")
        if bad:
            print(f"[idc_models_tpu] WARNING: {bad} tensors will not load "
                  f"into {ns.model}", file=sys.stderr)
    tree = {"params": params, "state": state} if state else {"params": params}
    save_npz(ns.output, tree)
    print(f"wrote {ns.output} ({len(params)} layers, "
          f"{len(_flatten(params))} tensors)")


def _run_dist(ns):
    import jax

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.configs import get_preset
    from idc_models_tpu.data.cifar10 import load_cifar10
    from idc_models_tpu.data.idc import train_val_test_split
    from idc_models_tpu.train import TwoPhaseConfig, evaluate, two_phase_fit

    if ns.resumable and ns.path is None:
        sys.exit("--resumable requires --path (checkpoints live under it)")
    if ns.checkpoint_every < 1:
        sys.exit(f"--checkpoint-every {ns.checkpoint_every} must be "
                 f">= 1: saving every 0 epochs is never, and never "
                 f"checkpointing is what --resumable exists to fix")
    if ns.checkpoint_every != 1 and not ns.resumable:
        sys.exit("--checkpoint-every needs --resumable: it paces the "
                 "resume checkpoints, and without --resumable none "
                 "are written")
    preset = _apply_overrides(
        get_preset(ns.preset_key), ns,
        ["batch_size", "lr", "epochs", "fine_tune_epochs", "fine_tune_at",
         "repeats"])
    if getattr(ns, "model_parallel", 1) > 1:
        if ns.central_storage:
            sys.exit("--central-storage broadcasts a host-resident "
                     "replica each step and cannot keep a model-sharded "
                     "layout; drop one of the two flags")
        from idc_models_tpu import tp

        try:
            mesh = tp.dp_tp_mesh(ns.model_parallel)
        except ValueError as e:
            sys.exit(str(e))
    else:
        mesh = meshlib.data_mesh()
    n_dev = mesh.shape.get(meshlib.DATA_AXIS, mesh.devices.size)
    global_batch = (preset.batch_size * n_dev if preset.per_replica_batch
                    else preset.batch_size)
    print(f"Number of devices: {mesh.devices.size}")

    # Synthetic fallback must yield at least one full global batch after
    # the train split, or the Loader rightly refuses to run.
    ns.synthetic_examples = max(ns.synthetic_examples, 2 * global_batch)
    streamed = None
    if ns.stream:
        if preset.dataset != "idc":
            sys.exit("--stream needs an IDC directory preset (vgg/mobile)")
        streamed = _streamed_idc_splits(ns, preset, global_batch)
        if streamed is None:
            print("[idc_models_tpu] --stream: no real data dir found; "
                  "falling back to the materialized synthetic path",
                  file=sys.stderr)
    if streamed is not None:
        train, val, test = streamed
    elif preset.dataset == "cifar10":
        ds = load_cifar10(ns.path, split="train",
                          synthetic_size=ns.synthetic_examples, seed=ns.seed)
        test = load_cifar10(ns.path, split="test",
                            synthetic_size=max(ns.synthetic_examples // 5, 64),
                            seed=ns.seed)
        train, val, _ = train_val_test_split(ds, (0.9, 0.1, 0.0),
                                             seed=ns.seed)
    else:
        ds = _load_idc(ns, preset.image_size, preset.dataset_limit)
        train, val, test = train_val_test_split(ds, seed=ns.seed)

    from idc_models_tpu.observe import profile_trace

    logger = _logger(ns)
    with profile_trace(ns.profile_dir):
        result = two_phase_fit(
            preset.model, preset.num_outputs, train, val, mesh,
            TwoPhaseConfig(lr=preset.lr, epochs=preset.epochs,
                           fine_tune_epochs=preset.fine_tune_epochs,
                           batch_size=global_batch,
                           fine_tune_at=preset.fine_tune_at,
                           repeats=preset.repeats, seed=ns.seed,
                           central_storage=ns.central_storage,
                           cache_features=ns.cache_features),
            pretrained_weights=ns.pretrained_weights,
            artifact_path=ns.path,
            checkpoint_dir=(str(Path(ns.path) / "dist_ckpt")
                            if ns.resumable and ns.path else None),
            checkpoint_every=ns.checkpoint_every,
            logger=logger)
    test_metrics = evaluate(result.model, result.state, test,
                            _loss_for(preset.num_outputs), mesh,
                            batch_size=global_batch,
                            with_auroc=preset.num_outputs == 1)
    print("test:", " ".join(f"{k}={v:.4f}" for k, v in test_metrics.items()))
    if logger:
        logger.log(event="test", **test_metrics)
    _finish_logger(logger)


def _loss_for(num_outputs):
    from idc_models_tpu.train.losses import (
        binary_cross_entropy, sparse_categorical_cross_entropy,
    )

    return (binary_cross_entropy if num_outputs == 1
            else sparse_categorical_cross_entropy)


def _run_attention(ns):
    """Beyond-reference workload: the ring-attention transformer
    classifier over a ("data", "seq") mesh — sequence parallelism from
    the command line, under the same step/eval/logging machinery as
    every other preset. Trains on the position-sensitive synthetic
    sequence task, or — with --data-dir — on the reference's own IDC
    patch tree (C1/C2), each image embedded as a raster token sequence
    (data.sequences.patchify; see docs/LONG_CONTEXT.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.data import synthetic
    from idc_models_tpu.data.idc import ArrayDataset, train_val_test_split
    from idc_models_tpu.data.sequences import patchify, sequence_shape
    from idc_models_tpu.models.attention import attention_classifier
    from idc_models_tpu.observe import Timer, profile_trace
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_train_step, replicate,
        rmsprop, shard_batch,
    )
    from idc_models_tpu.train.loop import Evaluator
    from idc_models_tpu.train.losses import binary_cross_entropy

    if not 0.0 <= ns.dropout < 1.0:
        sys.exit(f"--dropout {ns.dropout} must be in [0, 1)")
    # explicit --data-dir ONLY (not _data_root's <path>/data fallback):
    # real data overrides --seq-len/--features with the derived patch
    # sequence shape, so an artifact dir that happens to contain the
    # IDC tree must not silently turn a long-context synthetic run into
    # a 100-token IDC run
    root = ns.data_dir
    seq_len, features = ns.seq_len, ns.features
    if root is not None:
        try:
            seq_len, features = sequence_shape(ns.image_size,
                                               ns.patch_size)
        except ValueError as e:
            sys.exit(f"--patch-size: {e}")
    n_dev = len(jax.devices())
    # auto ring size: the largest power of two that DIVIDES the device
    # count (capped at 4), so the default never aborts on e.g. 6 devices
    n_seq = ns.seq_parallel or max(
        p for p in (4, 2, 1) if n_dev % p == 0)
    if n_seq < 1 or n_dev % n_seq:
        sys.exit(f"--seq-parallel {n_seq} must be a positive divisor "
                 f"of the device count ({n_dev})")
    stripes = 2 * n_seq if ns.layout == "zigzag" else n_seq
    what = ("--seq-len" if root is None
            else f"the {seq_len}-token patch sequence "
                 f"({ns.image_size}x{ns.image_size} images at "
                 f"--patch-size {ns.patch_size})")
    if seq_len % stripes:
        sys.exit(f"{what} = {seq_len} must divide into {stripes} "
                 f"equal stripes for --layout {ns.layout} at ring "
                 f"size {n_seq}")
    mesh = meshlib.data_seq_mesh(n_seq)
    print(f"Number of devices: {mesh.devices.size} "
          f"(data={mesh.shape[meshlib.DATA_AXIS]}, seq={n_seq})")

    model = attention_classifier(
        seq_len, features, embed_dim=ns.embed_dim,
        num_heads=ns.num_heads, mlp_dim=ns.mlp_dim,
        num_blocks=ns.num_blocks, num_outputs=1, mesh=mesh, causal=True,
        layout=ns.layout, block_impl=ns.block_impl, remat=ns.remat,
        dropout_rate=ns.dropout)
    batch = ns.batch_size or 64
    lr = ns.lr if ns.lr is not None else 1e-3
    if root is not None:
        # the reference's data domain through the SP path: decode the
        # labeled tree (C1), deterministic 80/10/10 split (C4), then
        # tokenize each patch
        ds = _load_idc(ns, ns.image_size, None)
        train_ds, val_ds, _ = train_val_test_split(ds, seed=ns.seed)
        x, y = patchify(train_ds.images, ns.patch_size), train_ds.labels
        vx, vy = patchify(val_ds.images, ns.patch_size), val_ds.labels
        print(f"IDC patch sequences: {len(x)} train / {len(vx)} val, "
              f"{seq_len} tokens x {features} features per patch")
    else:
        n_train = max(ns.synthetic_examples, 4 * batch)
        x, y = synthetic.make_sequence_task(n_train, seq_len, features,
                                            seed=ns.seed)
        vx, vy = synthetic.make_sequence_task(max(n_train // 4, batch),
                                              seq_len, features,
                                              seed=ns.seed + 1)

    opt = rmsprop(lr)
    variables = model.init(jax.random.key(ns.seed))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    step = jit_data_parallel(
        make_train_step(model, opt, binary_cross_entropy), mesh,
        axis=meshlib.DATA_AXIS)
    state = replicate(mesh, state)
    logger = _logger(ns)
    key = jax.random.key(ns.seed + 1)
    sel_rng = np.random.default_rng(ns.seed + 2)
    with Timer("Attention training", logger=logger), \
            profile_trace(ns.profile_dir):
        for i in range(ns.steps):
            sel = sel_rng.integers(0, len(x), batch)
            key, sub = jax.random.split(key)
            state, m = step(state, *shard_batch(mesh, x[sel], y[sel],
                                                axis=meshlib.DATA_AXIS),
                            sub)
            if i % 50 == 0 or i == ns.steps - 1:
                m = _fetch_scalars(m)
                print(f"step {i}, loss={float(m['loss']):.4f}, "
                      f"accuracy={float(m['accuracy']):.4f}")
                if logger:
                    logger.log(event="step", step=i,
                               loss=float(m["loss"]),
                               accuracy=float(m["accuracy"]))
    ev = Evaluator(model, binary_cross_entropy, mesh, batch_size=batch,
                   with_auroc=True)
    vm = ev(state, ArrayDataset(vx, vy))
    print("val:", " ".join(f"{k}={v:.4f}" for k, v in vm.items()))
    if logger:
        logger.log(event="val", **vm)
    _finish_logger(logger)


def _run_lm(ns):
    """Beyond-reference workload: the decoder-only LM trained through
    sequence-parallel ring attention on the counting task
    (next = (tok+1) % vocab), then served through the ring-sharded
    KV-cache decoder — train and generate from one parameter tree
    (models/lm.py, docs/LONG_CONTEXT.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models.lm import attention_lm, next_token_loss
    from idc_models_tpu.observe import Timer, profile_trace
    from idc_models_tpu.train import (
        TrainState, jit_data_parallel, make_train_step, rmsprop,
        shard_batch,
    )
    from idc_models_tpu.train.step import place_state

    if not 0.0 <= ns.dropout < 1.0:
        sys.exit(f"--dropout {ns.dropout} must be in [0, 1)")
    if ns.fsdp < 0 or ns.tp < 0:
        sys.exit(f"--fsdp/--tp must be >= 0 (0 = off), got "
                 f"{ns.fsdp}/{ns.tp}")
    n_dev = len(jax.devices())
    sharded = ns.fsdp > 1 or ns.tp > 1
    if sharded:
        # rule-sharded mesh (partition.py): FSDP over "data", TP over
        # "model", the ring over "seq"; --seq-parallel defaults to 1
        # here (the three axes share the device budget)
        f, t = max(ns.fsdp, 1), max(ns.tp, 1)
        n_seq = ns.seq_parallel or 1
        if f * t * n_seq > n_dev:
            sys.exit(f"--fsdp {f} x --tp {t} x --seq-parallel {n_seq} "
                     f"needs {f * t * n_seq} devices, have {n_dev} "
                     f"(use --host-devices to grow the virtual pod)")
        batch = ns.batch_size or 32
        if batch % f:
            sys.exit(f"--batch-size {batch} must divide by --fsdp {f} "
                     f"(the batch shards over the same 'data' axis the "
                     f"params shard over)")
        mesh = meshlib.fsdp_tp_mesh(f, t, n_seq)
    else:
        n_seq = ns.seq_parallel or max(
            p for p in (4, 2, 1) if n_dev % p == 0)
        if n_seq < 1 or n_dev % n_seq:
            sys.exit(f"--seq-parallel {n_seq} must be a positive "
                     f"divisor of the device count ({n_dev})")
        mesh = meshlib.data_seq_mesh(n_seq)
    stripes = 2 * n_seq if ns.layout == "zigzag" else n_seq
    if ns.seq_len % stripes:
        sys.exit(f"--seq-len {ns.seq_len} must divide into {stripes} "
                 f"equal stripes for --layout {ns.layout} at ring "
                 f"size {n_seq}")
    rules = None
    if sharded:
        from idc_models_tpu.models import registry

        rules = registry.get_partition_rules("lm")
        print(f"Number of devices: {mesh.devices.size} "
              f"(fsdp={mesh.shape[meshlib.DATA_AXIS]}, "
              f"tp={mesh.shape[meshlib.MODEL_AXIS]}, seq={n_seq}; "
              f"params + optimizer state sharded by rule set 'lm')")
    else:
        print(f"Number of devices: {mesh.devices.size} "
              f"(data={mesh.shape[meshlib.DATA_AXIS]}, seq={n_seq})")

    model = attention_lm(
        ns.vocab, ns.seq_len, embed_dim=ns.embed_dim,
        num_heads=ns.num_heads, mlp_dim=ns.mlp_dim,
        num_blocks=ns.num_blocks, mesh=mesh, layout=ns.layout,
        block_impl=ns.block_impl, remat=ns.remat,
        dropout_rate=ns.dropout)
    batch = ns.batch_size or 32
    lr = ns.lr if ns.lr is not None else 3e-3
    opt = rmsprop(lr)
    variables = model.init(jax.random.key(ns.seed))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables.params,
                       model_state=variables.state,
                       opt_state=opt.init(variables.params))
    step = jit_data_parallel(
        make_train_step(model, opt, next_token_loss), mesh,
        axis=meshlib.DATA_AXIS,
        state_shardings=(rules.shardings(mesh, state)
                         if rules is not None else None))
    state = place_state(mesh, state, rules=rules)
    logger = _logger(ns)
    rng = np.random.default_rng(ns.seed + 1)
    key = jax.random.key(ns.seed + 2)
    with Timer("LM training", logger=logger), \
            profile_trace(ns.profile_dir):
        for i in range(ns.steps):
            starts = rng.integers(0, ns.vocab, (batch, 1))
            seqs = jnp.asarray((starts + np.arange(ns.seq_len))
                               % ns.vocab, jnp.int32)
            bx = shard_batch(mesh, seqs, axis=meshlib.DATA_AXIS)
            key, sub = jax.random.split(key)
            state, m = step(state, bx, bx, sub)
            if i % 50 == 0 or i == ns.steps - 1:
                m = _fetch_scalars(m)
                print(f"step {i}, loss={float(m['loss']):.4f}, "
                      f"next-token accuracy={float(m['accuracy']):.4f}")
                if logger:
                    logger.log(event="step", step=i,
                               loss=float(m["loss"]),
                               accuracy=float(m["accuracy"]))
    n_gen = min(ns.generate, ns.seq_len - 3)
    if ns.generate > 0 and n_gen >= 1:
        import time as _time

        from idc_models_tpu.models.lm import Generator

        if ns.temperature < 0.0:
            sys.exit(f"--temperature {ns.temperature} must be >= 0")
        if ns.top_k < 0:
            sys.exit(f"--top-k {ns.top_k} must be >= 0 (0 = no "
                     f"restriction)")
        if ns.top_k > 0 and ns.temperature == 0.0:
            print("[idc_models_tpu] --top-k has no effect at "
                  "--temperature 0 (greedy argmax already picks the "
                  "top-1 token)", file=sys.stderr)
        # the serving object compiles prefill + the fused scan decode
        # once; repeated requests against it perform zero recompilation
        gen = Generator(jax.device_get(state.params),
                        embed_dim=ns.embed_dim, num_heads=ns.num_heads,
                        num_blocks=ns.num_blocks, t_max=ns.seq_len,
                        cache_dtype=jnp.float32,
                        temperature=ns.temperature,
                        top_k=ns.top_k or None)
        prompt = jnp.asarray(
            [[i % ns.vocab for i in range(3)]], jnp.int32)
        key = (jax.random.key(ns.seed + 3) if ns.temperature > 0.0
               else None)
        out = gen(prompt, n_gen, rng=key)         # compile + generate
        t0 = _time.perf_counter()
        out = gen(prompt, n_gen, rng=key)         # compiled: 2 dispatches
        toks = out.tolist()[0]                    # fetch fences the timer
        dt = _time.perf_counter() - t0
        want = [i % ns.vocab for i in range(3 + n_gen)]
        ok = toks == want
        verdict = ("matches" if ok else "does NOT match"
                   ) if ns.temperature == 0.0 else "sampled against"
        print(f"generate: {toks[:3]} -> {toks[3:]} ({verdict} the "
              f"counting pattern; {n_gen} tokens end-to-end in "
              f"{dt * 1e3:.1f} ms, one prefill + one fused decode "
              f"dispatch)")
        if logger:
            # generate_ms_per_token is END-TO-END (prefill dispatch +
            # fused decode + host fetch) / tokens — not the pace of a
            # decode window alone
            logger.log(event="generate", tokens=toks, matches=ok,
                       generate_ms_per_token=dt * 1e3 / n_gen)
    _finish_logger(logger)


def _run_serve(ns):
    """Beyond-reference workload: the continuous-batching serving
    engine (serve/) over an `attention_lm` parameter tree — fixed decode
    slots, masked fused windows, FIFO admission with backpressure —
    replaying a request trace (JSONL or synthetic Poisson arrivals) and
    reporting throughput/TTFT/occupancy (docs/LONG_CONTEXT.md)."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models.lm import attention_lm, next_token_loss
    from idc_models_tpu.observe import JsonlLogger, Timer, profile_trace
    from idc_models_tpu.serve import LMServer, load_trace, poisson_trace

    n_dev = len(jax.devices())
    if ns.seq_parallel < 1 or n_dev < ns.seq_parallel:
        sys.exit(f"--seq-parallel {ns.seq_parallel} needs at least that "
                 f"many devices ({n_dev} available)")
    if ns.t_max % ns.seq_parallel:
        sys.exit(f"--t-max {ns.t_max} must divide by --seq-parallel "
                 f"{ns.seq_parallel}")
    if ns.fsdp not in (0, 1):
        sys.exit(f"--fsdp {ns.fsdp}: FSDP shards the optimizer+param "
                 f"state over the batch axis at TRAIN time; a serving "
                 f"engine holds no optimizer state and prefills [1, P] "
                 f"batches — use --tp for serving-side param sharding")
    if ns.tp < 0:
        sys.exit(f"--tp {ns.tp} must be >= 0 (0 = off)")
    if ns.tp > 1 and ns.tp * ns.seq_parallel > n_dev:
        sys.exit(f"--tp {ns.tp} x --seq-parallel {ns.seq_parallel} "
                 f"needs {ns.tp * ns.seq_parallel} devices, have "
                 f"{n_dev} (use --host-devices to grow the virtual "
                 f"pod)")
    if ns.temperature < 0.0:
        sys.exit(f"--temperature {ns.temperature} must be >= 0")
    # fail fast — BEFORE any --train-steps pre-training runs
    if ns.prefill_chunk and (ns.prefill_chunk < 1
                             or ns.t_max % ns.prefill_chunk):
        sys.exit(f"--prefill-chunk {ns.prefill_chunk} must be >= 1 and "
                 f"divide --t-max {ns.t_max}")
    if ns.prefix_cache_mb > 0 and not ns.prefill_chunk:
        sys.exit("--prefix-cache-mb needs --prefill-chunk (snapshots "
                 "live on chunk boundaries)")
    if bool(ns.kv_page_size) != bool(ns.kv_pages):
        sys.exit("paged KV needs BOTH --kv-page-size and --kv-pages "
                 "(or neither for the contiguous per-slot rows)")
    if ns.kv_page_size:
        if not ns.prefill_chunk:
            sys.exit("--kv-page-size needs --prefill-chunk: prompts "
                     "stream straight into pool pages chunk by chunk")
        if ns.kv_page_size < 1 or ns.t_max % ns.kv_page_size:
            sys.exit(f"--kv-page-size {ns.kv_page_size} must be >= 1 "
                     f"and divide --t-max {ns.t_max}")
        if ns.prefill_chunk % ns.kv_page_size:
            sys.exit(f"--prefill-chunk {ns.prefill_chunk} must be a "
                     f"multiple of --kv-page-size {ns.kv_page_size} "
                     f"(chunk boundaries must land on the page grid)")
        if ns.kv_pages * ns.kv_page_size < ns.t_max:
            sys.exit(f"--kv-pages {ns.kv_pages} x --kv-page-size "
                     f"{ns.kv_page_size} < --t-max {ns.t_max}: one "
                     f"full-length request could never be admitted")
    if ns.kv_decode_reserve and not ns.kv_page_size:
        sys.exit("--kv-decode-reserve needs paged KV "
                 "(--kv-page-size/--kv-pages)")
    if ns.kv_decode_reserve < 0:
        sys.exit(f"--kv-decode-reserve {ns.kv_decode_reserve} must be "
                 f">= 0 (0 = reserve the full budget)")
    if ns.spec_decode and not 1 <= ns.draft_k <= ns.t_max - 2:
        sys.exit(f"--draft-k {ns.draft_k} must be in [1, t_max - 2] "
                 f"(a verify needs room for k drafts + the bonus "
                 f"token inside the {ns.t_max}-slot cache)")
    if ns.spec_decode and ns.ngram_order < 1:
        sys.exit(f"--ngram-order {ns.ngram_order} must be >= 1")
    if ns.drafter != "ngram" and not ns.spec_decode:
        sys.exit(f"--drafter {ns.drafter} without --spec-decode: the "
                 f"drafter only runs inside the speculative loop (its "
                 f"proposals feed the engine's fixed-k verify "
                 f"program) — add --spec-decode")
    if ns.drafter in ("learned", "chained") and not ns.draft_ckpt:
        sys.exit(f"--drafter {ns.drafter} needs --draft-ckpt DIR: the "
                 f"learned drafter is a distilled draft LM restored "
                 f"from a models/draft_lm.save_draft_lm checkpoint "
                 f"(params + draft_config.json sidecar); distill one "
                 f"with models/draft_lm.distill_draft_lm, or use "
                 f"--drafter ngram which needs no model")
    if ns.draft_ckpt and ns.drafter == "ngram":
        sys.exit(f"--draft-ckpt without a learned drafter: the n-gram "
                 f"drafter loads no model, so the checkpoint would be "
                 f"silently ignored — pass --drafter learned (or "
                 f"chained) to use it")
    if ns.slo_ttft_p95_ms is not None and ns.slo_ttft_p95_ms <= 0:
        sys.exit(f"--slo-ttft-p95-ms {ns.slo_ttft_p95_ms} must be > 0")
    if (ns.slo_error_rate is not None
            and not 0.0 < ns.slo_error_rate < 1.0):
        sys.exit(f"--slo-error-rate {ns.slo_error_rate} must be a "
                 f"fraction in (0, 1)")
    if ns.slo_window_s <= 0:
        sys.exit(f"--slo-window-s {ns.slo_window_s} must be > 0")
    if ns.metrics_port is not None and not 0 <= ns.metrics_port <= 65535:
        sys.exit(f"--metrics-port {ns.metrics_port} must be in "
                 f"[0, 65535] (0 = OS-assigned)")
    if ns.max_retries < 0:
        sys.exit(f"--max-retries {ns.max_retries} must be >= 0")
    if ns.retry_backoff_ms < 0:
        sys.exit(f"--retry-backoff-ms {ns.retry_backoff_ms} must be "
                 f">= 0")
    if (ns.brownout_queue_high is not None
            and ns.brownout_queue_high < 1):
        sys.exit(f"--brownout-queue-high {ns.brownout_queue_high} "
                 f"must be >= 1")
    if ns.brownout_clamp_tokens < 1:
        sys.exit(f"--brownout-clamp-tokens {ns.brownout_clamp_tokens} "
                 f"must be >= 1")
    if ns.brownout_dwell_ms < 0 or ns.brownout_clear_ms < 0:
        sys.exit(f"--brownout-dwell-ms/--brownout-clear-ms must be "
                 f">= 0, got {ns.brownout_dwell_ms}/"
                 f"{ns.brownout_clear_ms}")
    ns.tenant_list, ns.tenant_quotas, ns.tenant_slos = (
        _parse_tenant_flags(ns))
    # rollout flags fail fast too — a bad canary fraction discovered
    # AFTER --train-steps pre-training wastes the whole warmup
    if ns.rollout is None:
        for flag, val in (("--canary-fraction", ns.canary_fraction),
                          ("--canary-requests", ns.canary_requests),
                          ("--rollout-at", ns.rollout_at)):
            if val is not None:
                sys.exit(f"{flag} needs --rollout: it tunes the canary "
                         f"stage of a weight rollout, and without a "
                         f"candidate checkpoint there is no rollout to "
                         f"tune")
    else:
        if ns.canary_fraction is None:
            ns.canary_fraction = 0.25
        if ns.canary_requests is None:
            ns.canary_requests = 4
        if ns.rollout_at is None:
            ns.rollout_at = 0.25
        if not 0.0 < ns.canary_fraction <= 1.0:
            sys.exit(f"--canary-fraction {ns.canary_fraction} must be "
                     f"in (0, 1]: a zero (or negative) fraction "
                     f"starves the canary of evidence forever, and "
                     f"promoting without evidence is not a rollout")
        if ns.canary_requests < 1:
            sys.exit(f"--canary-requests {ns.canary_requests} must be "
                     f">= 1: the verdict needs at least one canary "
                     f"finish to compare")
        if not 0.0 <= ns.rollout_at < 1.0:
            sys.exit(f"--rollout-at {ns.rollout_at} must be in [0, 1): "
                     f"at 1.0 or past it the trace drains before the "
                     f"rollout ever opens")
        from idc_models_tpu.checkpoint import (
            CheckpointError, checkpoint_info,
        )

        try:
            checkpoint_info(ns.rollout)
        except CheckpointError as e:
            sys.exit(f"--rollout: {e}")
    if ns.rollout_adapters is not None:
        if ns.tenant_list is None:
            sys.exit("--rollout-adapters needs --tenants: an adapter "
                     "rollout hot-swaps PER-TENANT logit deltas, and a "
                     "tenant-less server has no adapter bank to swap")
        if ns.rollout_adapters < 1:
            sys.exit(f"--rollout-adapters {ns.rollout_adapters} must "
                     f"be >= 1 (it is the adapter rank r in the "
                     f"[V, r] x [r, V] factors)")
    ns.serve_fault_plan = None
    if ns.serve_faults:
        from idc_models_tpu.serve import parse_serve_fault_spec

        try:
            ns.serve_fault_plan = parse_serve_fault_spec(
                ns.serve_faults, seed=ns.seed)
        except ValueError as e:
            sys.exit(f"--serve-faults: {e}")
    serve_rules = None
    if ns.tp > 1:
        # tensor-parallel serving (partition.py): weights shard over
        # "model", the KV ring keeps "seq" — independent axes
        from idc_models_tpu.models import registry as model_registry

        serve_rules = model_registry.get_partition_rules("lm")
        mesh = meshlib.fsdp_tp_mesh(1, ns.tp, ns.seq_parallel)
        print(f"serving mesh: tp={ns.tp} x seq={ns.seq_parallel} "
              f"(params sharded by rule set 'lm'; KV on the seq ring)")
    else:
        mesh = meshlib.seq_mesh(ns.seq_parallel)
    # the model trains through the SAME ring the serving mesh uses —
    # omitting mesh here would silently train single-device full
    # attention ([B, H, t_max, t_max] scores) at exactly the sizes
    # --seq-parallel exists for
    model = attention_lm(ns.vocab, ns.t_max, embed_dim=ns.embed_dim,
                         num_heads=ns.num_heads, mlp_dim=ns.mlp_dim,
                         num_blocks=ns.num_blocks,
                         mesh=mesh if ns.seq_parallel > 1 else None)
    params = model.init(jax.random.key(ns.seed)).params
    if ns.train_steps > 0:
        from idc_models_tpu.train import (
            TrainState, make_train_step, rmsprop,
        )

        opt = rmsprop(3e-3)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           model_state={}, opt_state=opt.init(params))
        step = jax.jit(make_train_step(model, opt, next_token_loss))
        rng = np.random.default_rng(ns.seed + 1)
        key = jax.random.key(ns.seed + 2)
        with Timer("Serve pre-training"):
            for _ in range(ns.train_steps):
                starts = rng.integers(0, ns.vocab, (16, 1))
                seqs = jnp.asarray(
                    (starts + np.arange(ns.t_max)) % ns.vocab, jnp.int32)
                key, sub = jax.random.split(key)
                state, m = step(state, seqs, seqs, sub)
            print(f"pre-trained {ns.train_steps} steps, "
                  f"loss={float(m['loss']):.4f}")
        params = jax.device_get(state.params)

    logger = (JsonlLogger(Path(ns.path) / "logs" / "serve.jsonl")
              if ns.path else None)
    # live exposition (observe/exporter.py): armed BEFORE the server's
    # warmup compiles so a scraper sees the process from startup, torn
    # down with the run (the finally below)
    exporter = None
    if ns.metrics_port is not None:
        from idc_models_tpu.observe import MetricsExporter

        try:
            exporter = MetricsExporter(port=ns.metrics_port).start()
        except OSError as e:
            sys.exit(f"serve: cannot bind --metrics-port "
                     f"{ns.metrics_port}: {e}")
        print(f"metrics: {exporter.url}/metrics  healthz: "
              f"{exporter.url}/healthz")
    try:
        return _serve_body(ns, mesh, params, logger, serve_rules)
    finally:
        if exporter is not None:
            exporter.close()


def _parse_tenant_flags(ns):
    """Validate the serve verb's tenancy flags into (names, {name:
    TenantQuota}, {name: ttft_ms}) — every bad spelling is a usage
    error that TEACHES the grammar, the CLI's established discipline."""
    quota_grammar = ("--tenant-quota grammar: NAME=SLOTS[:QUEUED"
                     "[:PAGES]], each an int >= 1 or '-' (unlimited), "
                     "e.g. acme=2:8:- ; NAME must be in --tenants")
    slo_grammar = ("--tenant-slo-ttft-ms grammar: NAME=MS for one "
                   "tenant or a bare MS > 0 for every tenant, e.g. "
                   "acme=250 ; NAME must be in --tenants")
    if ns.tenants is None:
        if ns.tenant_quota:
            sys.exit("--tenant-quota needs --tenants: quotas bound "
                     "REGISTERED tenants")
        if ns.tenant_slo_ttft_ms:
            sys.exit("--tenant-slo-ttft-ms needs --tenants: SLOs "
                     "attach to REGISTERED tenants")
        return None, {}, {}
    names = [t.strip() for t in ns.tenants.split(",")]
    if any(not t for t in names):
        sys.exit(f"--tenants {ns.tenants!r}: empty tenant name "
                 f"(comma-separated non-empty names, first = default)")
    if len(set(names)) != len(names):
        sys.exit(f"--tenants {ns.tenants!r}: duplicate tenant name — "
                 f"tenant names are identities")
    from idc_models_tpu.serve import TenantQuota

    def bound(tok, spec):
        if tok == "-":
            return None
        try:
            v = int(tok)
        except ValueError:
            sys.exit(f"--tenant-quota {spec!r}: {tok!r} is not an int "
                     f"or '-'. {quota_grammar}")
        if v < 1:
            sys.exit(f"--tenant-quota {spec!r}: bounds must be >= 1 "
                     f"(a 0 quota would admit nothing ever). "
                     f"{quota_grammar}")
        return v

    quotas = {}
    for spec in ns.tenant_quota or ():
        name, eq, rest = spec.partition("=")
        parts = rest.split(":") if rest else []
        if not eq or not name or not 1 <= len(parts) <= 3:
            sys.exit(f"--tenant-quota {spec!r}: malformed. "
                     f"{quota_grammar}")
        if name not in names:
            sys.exit(f"--tenant-quota {spec!r}: unknown tenant "
                     f"{name!r} (registered: {names}). {quota_grammar}")
        if name in quotas:
            sys.exit(f"--tenant-quota {spec!r}: tenant {name!r} "
                     f"already has a quota")
        parts += ["-"] * (3 - len(parts))
        quotas[name] = TenantQuota(
            max_resident_slots=bound(parts[0], spec),
            max_queued=bound(parts[1], spec),
            kv_page_budget=bound(parts[2], spec))
    slos = {}
    for spec in ns.tenant_slo_ttft_ms or ():
        name, eq, rest = spec.partition("=")
        if not eq:
            name, rest = None, spec
        try:
            ms = float(rest)
        except ValueError:
            sys.exit(f"--tenant-slo-ttft-ms {spec!r}: {rest!r} is not "
                     f"a number. {slo_grammar}")
        if ms <= 0:
            sys.exit(f"--tenant-slo-ttft-ms {spec!r}: must be > 0. "
                     f"{slo_grammar}")
        targets = [name] if name is not None else names
        for t in targets:
            if t not in names:
                sys.exit(f"--tenant-slo-ttft-ms {spec!r}: unknown "
                         f"tenant {t!r} (registered: {names}). "
                         f"{slo_grammar}")
            if t in slos:
                sys.exit(f"--tenant-slo-ttft-ms {spec!r}: tenant "
                         f"{t!r} already has a TTFT SLO")
            slos[t] = ms
    return names, quotas, slos


def _synth_adapters(names, vocab, rank, seed):
    """Deterministic rank-r logit-adapter factors per tenant ([V, r] /
    [r, V] float32) for the --rollout-adapters drill — small enough
    that the hot-swap mechanics, not the math, are the thing under
    test."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return {name: (rng.normal(0.0, 0.01, (vocab, rank))
                   .astype(np.float32),
                   rng.normal(0.0, 0.01, (rank, vocab))
                   .astype(np.float32))
            for name in names}


def _serve_body(ns, mesh, params, logger, rules=None) -> int:
    import json

    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu.observe import Timer, profile_trace
    from idc_models_tpu.serve import LMServer, load_trace, poisson_trace

    # declared SLOs (observe/slo.py): the serving metrics hooks feed
    # them and evaluate burn rates once per scheduler cycle; slo_alert
    # records stream to the same serve.jsonl
    slo = None
    slos = []
    if ns.slo_ttft_p95_ms is not None:
        from idc_models_tpu.observe import SLO

        slos.append(SLO.latency("ttft",
                                threshold_s=ns.slo_ttft_p95_ms / 1e3))
    if ns.slo_error_rate is not None:
        from idc_models_tpu.observe import SLO

        slos.append(SLO.rate("error_rate", budget=ns.slo_error_rate))
    if slos:
        from idc_models_tpu.observe import SLOEngine

        slo = SLOEngine(slos, short_window_s=ns.slo_window_s,
                        long_window_s=5.0 * ns.slo_window_s,
                        logger=logger)
    # resilience wiring (serve/faults, scheduler RetryPolicy,
    # serve/journal, serve/brownout — docs/ROBUSTNESS.md "Serving
    # resilience"): all default-off, armed by their flags
    retry = None
    if ns.max_retries > 0:
        from idc_models_tpu.serve import RetryPolicy

        retry = RetryPolicy(max_retries=ns.max_retries,
                            backoff_s=ns.retry_backoff_ms / 1e3)
    brownout = None
    if ns.brownout:
        from idc_models_tpu.serve import BrownoutController

        queue_high = (ns.brownout_queue_high
                      or max(ns.max_queue_depth // 2, 2))
        brownout = BrownoutController(
            slo=slo, queue_high=queue_high,
            clamp_tokens=ns.brownout_clamp_tokens,
            escalate_dwell_s=ns.brownout_dwell_ms / 1e3,
            clear_after_s=ns.brownout_clear_ms / 1e3, logger=logger)
    # multi-tenant serving (serve/tenancy.py, ISSUE 14): register the
    # tenant set with its quotas + per-tenant TTFT SLOs and build the
    # runtime against the serve knobs' windows/dwells. CLI tenants
    # carry no trained adapters (the synthetic model has none to
    # load) unless --rollout-adapters arms synthetic ones for the
    # hot-swap drill; quota/SLO/brownout isolation is the full drill
    # surface — docs/MULTITENANCY.md shows the adapter path in code.
    tenancy = None
    if ns.tenant_list:
        from idc_models_tpu.serve import TenantRegistry

        reg = TenantRegistry()
        # --rollout-adapters arms the bank at build time (rank is a
        # compiled shape): every tenant gets a deterministic rank-r
        # adapter the post-trace hot-swap then replaces live
        adapters = (_synth_adapters(ns.tenant_list, ns.vocab,
                                    ns.rollout_adapters, ns.seed)
                    if ns.rollout_adapters else {})
        for name in ns.tenant_list:
            reg.register(name, adapter=adapters.get(name),
                         quota=ns.tenant_quotas.get(name),
                         slo_ttft_p95_ms=ns.tenant_slos.get(name))
        tenancy = reg.build(
            vocab=ns.vocab, logger=logger,
            slo_short_window_s=ns.slo_window_s,
            brownout_dwell_s=ns.brownout_dwell_ms / 1e3,
            brownout_clear_s=ns.brownout_clear_ms / 1e3,
            brownout_clamp_tokens=ns.brownout_clamp_tokens)
    # count the journal's in-flight leftovers BEFORE the server opens
    # it for appending: these are the requests a previous crashed run
    # accepted but never finished
    n_pending = 0
    if ns.journal and Path(ns.journal).exists():
        from idc_models_tpu.serve import pending_requests

        n_pending = len(pending_requests(ns.journal))
    compile_cache = None
    if ns.compile_cache:
        from idc_models_tpu.serve import CompileCache

        compile_cache = CompileCache(ns.compile_cache, logger=logger)
    # --drafter learned/chained: restore the distilled draft LM through
    # the sharded-checkpoint path (layout re-resolved against THIS
    # mesh) and hand the drafter to the server; 'ngram' stays None so
    # LMServer builds its default prompt-lookup drafter from
    # --ngram-order. Vocab is checked HERE, at load time, because the
    # engine's own teaching error fires only after params land on
    # device — an operator typo should die before that.
    drafter = None
    draft_rules = None
    if ns.spec_decode and ns.drafter != "ngram":
        from idc_models_tpu.models.draft_lm import DraftLM, load_draft_lm
        from idc_models_tpu.models.registry import DRAFT_LM_RULES

        draft_rules = DRAFT_LM_RULES if rules is not None else None
        dparams, dcfg = load_draft_lm(ns.draft_ckpt, mesh=mesh,
                                      rules=draft_rules)
        if dcfg["vocab_size"] != ns.vocab:
            sys.exit(f"--draft-ckpt {ns.draft_ckpt} was distilled "
                     f"against a {dcfg['vocab_size']}-token vocab but "
                     f"this target serves --vocab {ns.vocab}: drafter "
                     f"and target must share one tokenizer (the verify "
                     f"program compares token IDS) — re-distill the "
                     f"drafter against this target "
                     f"(models/draft_lm.distill_draft_lm)")
        learned = DraftLM(ns.draft_k, dparams, dcfg)
        if ns.drafter == "chained":
            from idc_models_tpu.models.draft import (
                ChainedDrafter, NGramDrafter,
            )

            drafter = ChainedDrafter(
                NGramDrafter(ns.draft_k, order=ns.ngram_order), learned)
        else:
            drafter = learned
    server = LMServer(
        params, embed_dim=ns.embed_dim, num_heads=ns.num_heads,
        num_blocks=ns.num_blocks, t_max=ns.t_max, n_slots=ns.slots,
        window=ns.window, mesh=mesh, cache_dtype=jnp.float32,
        temperature=ns.temperature, top_k=ns.top_k or None,
        eos_id=ns.eos, max_queue_depth=ns.max_queue_depth,
        max_prefills_per_cycle=ns.max_prefills_per_cycle, logger=logger,
        prefill_chunk=ns.prefill_chunk or None,
        prefix_cache_mb=ns.prefix_cache_mb,
        kv_dtype=("int8" if ns.kv_dtype == "int8" else None), slo=slo,
        retry=retry, fault_plan=ns.serve_fault_plan,
        journal=ns.journal, brownout=brownout,
        spec_decode=ns.spec_decode, draft_k=ns.draft_k,
        draft_order=ns.ngram_order, drafter=drafter,
        draft_partition_rules=draft_rules,
        kv_page_size=ns.kv_page_size or None,
        kv_pages=ns.kv_pages or None,
        kv_decode_reserve=ns.kv_decode_reserve or None,
        tenancy=tenancy, partition_rules=rules,
        compile_cache=compile_cache)
    if n_pending:
        readmitted = server.resubmit_pending(ns.journal)
        line = (f"journal: re-admitted {len(readmitted)} in-flight "
                f"request(s) from a previous run")
        refused = n_pending - len(readmitted)
        if refused:
            # backpressure refusals leave no finish record, so the WAL
            # still holds them — an honest count beats claiming full
            # recovery, and a rerun picks up the remainder
            line += (f"; {refused} refused by backpressure — raise "
                     f"--max-queue-depth and rerun with the same "
                     f"--journal to recover them")
        print(line)
    if ns.save_ckpt:
        # each device writes only its own shards; the manifest is the
        # atomic completion contract (checkpoint/sharded.py). With
        # --train-steps this mints a --rollout candidate in one run.
        from idc_models_tpu.checkpoint import save_sharded

        manifest = save_sharded(ns.save_ckpt, server.engine._params,
                                step=ns.train_steps, logger=logger).wait()
        print(f"checkpoint: wrote {manifest['n_shards']} shard(s) / "
              f"{len(manifest['leaves'])} leaves to {ns.save_ckpt}")
    if ns.trace:
        trace = load_trace(ns.trace)
    else:
        trace = poisson_trace(
            ns.requests, rate_per_s=ns.rate, vocab=ns.vocab,
            t_max=ns.t_max, eos_id=ns.eos,
            prompt_lens=(2, max(ns.t_max // 4, 2)),
            budgets=(2, max(ns.t_max // 4, 2)), seed=ns.seed,
            sampled=ns.temperature > 0.0, tenants=ns.tenant_list)
    print(f"serving {len(trace)} requests on {ns.slots} slots "
          f"(window {ns.window}, t_max {ns.t_max}, ring "
          f"{ns.seq_parallel})")
    from idc_models_tpu.serve import InjectedEngineCrash

    crashed = None
    drained = False
    rollout_ctl = None
    prev_sigterm = _arm_sigterm()
    try:
        with Timer("Serving trace", logger=logger), \
                profile_trace(ns.profile_dir):
            try:
                if ns.rollout:
                    from idc_models_tpu.checkpoint import (
                        run_with_rollout,
                    )

                    results, rollout_ctl = run_with_rollout(
                        server, trace, ns.rollout,
                        start_after=ns.rollout_at,
                        realtime=ns.realtime,
                        canary_fraction=ns.canary_fraction,
                        canary_requests=ns.canary_requests,
                        logger=logger)
                else:
                    results = server.run(trace, realtime=ns.realtime)
            except InjectedEngineCrash as e:
                # the drill's hard death: the failure cleanup already
                # finalized every in-flight request as an error Result
                # — salvage them, report honestly, and point at the
                # recovery
                crashed = e
                results = server.results()
            except _DrainRequested:
                # SIGTERM: stop admitting, finish what's running, let
                # the journal's finish records land — the honest
                # graceful-shutdown contract
                drained = True
                server.scheduler.begin_drain()
                server.drain()
                results = server.results()
    finally:
        _disarm_sigterm(prev_sigterm)
    if drained:
        print("SIGTERM: drained gracefully — admissions stopped, "
              "in-flight requests finished, journal flushed"
              + (f" ({ns.journal})" if ns.journal else ""))
    if crashed is not None:
        hint = (f"; rerun with --journal {ns.journal} to recover the "
                f"in-flight requests" if ns.journal else
                "; arm --journal to make this recoverable")
        print(f"engine crashed mid-run (injected): {crashed}{hint}")
    n_ok = sum(r.status == "ok" for r in results)
    n_error = sum(r.status == "error" for r in results)
    summary = server.summary()
    print(f"served: ok={n_ok} timeout={summary['serve_timed_out']} "
          f"rejected={summary['serve_rejected']} "
          f"tokens={summary['serve_tokens']} error={n_error}")
    # TTFT decomposed so an operator can tell queueing from compute:
    # p95 TTFT = queue wait (add slots / shed load) + prefill compute
    # (shrink prompts, chunk smaller, warm the prefix cache). Absent
    # when nothing emitted a first token (all expired/rejected).
    if summary.get("serve_ttft_ms_p95") is not None:
        print(f"ttft p95 {summary['serve_ttft_ms_p95']} ms = queue-wait "
              f"{summary['serve_queue_wait_ms_p95']} ms + prefill "
              f"{summary['serve_prefill_ms_p95']} ms (p95s)")
    if summary.get("serve_prefix_hit_rate") is not None:
        print(f"prefix cache: hit rate "
              f"{summary['serve_prefix_hit_rate']} "
              f"({summary['serve_prefix_hits']} hits, "
              f"{summary['serve_prefix_evictions']} evictions, "
              f"{summary['serve_prefix_bytes']} bytes)")
    if summary.get("serve_compile_cache") is not None:
        cc = summary["serve_compile_cache"]
        print(f"compile cache: {cc['hits']} hit(s) "
              f"({cc['deserialize_s']:.3f}s deserializing), "
              f"{cc['misses']} miss(es) -> {cc['stores']} store(s) "
              f"({cc['compile_s']:.3f}s compiling), "
              f"{cc['evicted_corrupt']} corrupt eviction(s)")
    if ns.kv_page_size:
        # what paging actually bought: peak pool occupancy vs the
        # capacity the same HBM would hold as contiguous per-slot
        # rows, and the tokens-per-HBM-byte the claim is stated in
        print(f"paged kv: {summary['serve_kv_pages_used_peak']}/"
              f"{summary['serve_kv_pages_total']} pages peak "
              f"(page {ns.kv_page_size} tokens), resident peak "
              f"{summary['serve_kv_resident_tokens_peak']} tokens / "
              f"{summary['serve_kv_resident_bytes_peak']} bytes "
              f"(tokens/HBM-byte "
              f"{summary['serve_kv_tokens_per_hbm_byte']}), "
              f"exhaustion backpressure "
              f"{summary['serve_page_exhaustions']}")
    if ns.spec_decode:
        # what speculation actually bought: accept rate over drafted
        # tokens and emitted tokens per slot per verify (1.0 would
        # mean plain decode did just as well)
        line = (f"speculative ({ns.drafter}): "
                f"drafted={summary['serve_spec_drafted']} "
                f"accepted={summary['serve_spec_accepted']} "
                f"accept_rate={summary['serve_spec_accept_rate']} "
                f"tokens/dispatch="
                f"{summary['serve_spec_tokens_per_dispatch']} "
                f"({summary['serve_spec_verify_dispatches']} verify + "
                f"{summary['serve_decode_dispatches'] - summary['serve_spec_verify_dispatches']}"
                f" window dispatches)")
        if summary.get("serve_spec_propose_s") is not None:
            # the overhead speculation pays before any win: host+device
            # seconds spent PROPOSING
            line += f" propose_s={summary['serve_spec_propose_s']}"
        print(line)
    if slo is not None:
        names = sorted({a["slo"] for a in slo.alerts})
        print(f"slo: {len(slo.alerts)} alert(s)"
              + (f" ({', '.join(names)})" if names else ""))
    if rollout_ctl is not None:
        # the verdict an operator acts on: terminal stage, how much
        # canary evidence backed it, and the rollback reason if any
        line = (f"rollout: {rollout_ctl.stage} after "
                f"{rollout_ctl.canary_finishes} canary finish(es)")
        if rollout_ctl.reason:
            line += f" — {rollout_ctl.reason}"
        print(line)
    if ns.rollout_adapters and crashed is None:
        # the cheap first rung, live: replace the whole bank with
        # re-seeded factors — same compiled shapes, zero recompile
        fresh = _synth_adapters(ns.tenant_list, ns.vocab,
                                ns.rollout_adapters, ns.seed + 1)
        server.swap_adapters(
            np.stack([fresh[n][0] for n in ns.tenant_list]),
            np.stack([fresh[n][1] for n in ns.tenant_list]))
        print(f"adapter rollout: hot-swapped rank-"
              f"{ns.rollout_adapters} adapters for "
              f"{len(ns.tenant_list)} tenant(s), zero recompile")
    if tenancy is not None:
        # what isolation actually did, one line per tenant: volume,
        # tail latency, sheds/quota refusals, the tenant's own
        # brownout high-water stage, and whether its TTFT alert fired
        for name, ts in summary["serve_tenants"].items():
            bc = tenancy.brownouts.get(name)
            alerts = (len([a for a in tenancy.slo.alerts
                           if a["slo"] == f"ttft:{name}"])
                      if tenancy.slo is not None else 0)
            print(f"tenant {name}: requests={ts['requests']} "
                  f"tokens={ts['tokens']} "
                  f"ttft_p95={ts['ttft_ms_p95']}ms "
                  f"shed={ts['shed']} "
                  f"quota_rejected={ts['quota_rejections']} "
                  f"brownout_max_stage="
                  f"{bc.max_stage_seen if bc is not None else 0} "
                  f"slo_alerts={alerts}")
    # resilience epilogue: what the armed machinery actually did —
    # faults fired, quarantines, retries, brownout sheds/clamps
    if (ns.serve_fault_plan is not None or retry is not None
            or brownout is not None or summary["serve_slot_faults"]):
        line = (f"resilience: injected={summary['serve_faults_injected']}"
                f" slot_faults={summary['serve_slot_faults']}"
                f" retries={summary['serve_retries']}"
                f" shed={summary['serve_shed']}"
                f" clamped={summary['serve_clamped']}")
        if brownout is not None:
            line += (f" brownout_max_stage={brownout.max_stage_seen}"
                     f" (stage {brownout.stage} at exit)")
        print(line)
    print("serve summary:", json.dumps(summary))
    if logger:
        logger.log(event="serve_summary", **summary)
    server.close()
    _finish_logger(logger)
    return _error_exit(n_error, drill=ns.serve_fault_plan is not None)


def _error_exit(n_error: int, *, drill: bool) -> int:
    """The serve verbs' exit code: an `error` result is the engine
    failing a request (the retry policy turns engine exceptions — a
    compile failure included — into per-request errors, so the run
    itself still ends normally). Outside an injected fault drill, where
    errors are the point, that is a failed run and must not exit 0."""
    if n_error and not drill:
        print(f"[idc_models_tpu] {n_error} request(s) ended in error",
              file=sys.stderr)
        return 1
    return 0


def _run_serve_cluster(ns):
    """Disaggregated multi-replica serving (serve/cluster/, ISSUE 12):
    a router tier over N engine replicas — SLO/health-aware placement,
    prefill/decode separation over the cluster prefix registry, drain,
    and journal-backed failover (docs/LONG_CONTEXT.md "Disaggregated
    serving")."""
    import json

    import jax
    import jax.numpy as jnp

    from idc_models_tpu.observe import JsonlLogger, Timer
    from idc_models_tpu.serve import (
        PrefixRegistry, RetryPolicy, Router, build_replica, load_trace,
        poisson_trace,
    )

    if ns.replicas < 1:
        sys.exit(f"--replicas {ns.replicas} must be >= 1")
    if ns.prefill_replicas < 0:
        sys.exit(f"--prefill-replicas {ns.prefill_replicas} must be "
                 f">= 0")
    if ns.prefill_chunk and (ns.prefill_chunk < 1
                             or ns.t_max % ns.prefill_chunk):
        sys.exit(f"--prefill-chunk {ns.prefill_chunk} must be >= 1 "
                 f"and divide --t-max {ns.t_max}")
    if ns.prefix_cache_mb > 0 and not ns.prefill_chunk:
        sys.exit("--prefix-cache-mb needs --prefill-chunk")
    if ns.registry_mb > 0 and not ns.prefix_cache_mb:
        sys.exit("--registry-mb needs --prefix-cache-mb (replicas "
                 "adopt registry snapshots through their local cache)")
    if ns.prefill_replicas and not ns.registry_mb:
        sys.exit("--prefill-replicas needs --registry-mb: the handoff "
                 "artifact travels through the cluster prefix registry")
    if ns.max_retries < 0:
        sys.exit(f"--max-retries {ns.max_retries} must be >= 0")
    if ns.hedge_after_ms is not None and ns.hedge_after_ms <= 0:
        sys.exit(f"--hedge-after-ms {ns.hedge_after_ms} must be > 0")
    n_fleet = ns.replicas + ns.prefill_replicas
    for flag, idx in (("--kill-replica", ns.kill_replica),
                      ("--drain-replica", ns.drain_replica)):
        if idx is not None and not 0 <= idx < n_fleet:
            sys.exit(f"{flag} {idx} outside the fleet [0, {n_fleet})")
    if ns.kill_replica is not None and not ns.journal_dir:
        sys.exit("--kill-replica needs --journal-dir: migration "
                 "replays the dead replica's journal WAL")
    if ns.kill_after_steps < 0:
        sys.exit(f"--kill-after-steps {ns.kill_after_steps} must be "
                 f">= 0")
    if ns.autoscale_max is not None and ns.autoscale_max < ns.replicas:
        sys.exit(f"--autoscale-max {ns.autoscale_max} must be >= "
                 f"--replicas {ns.replicas} (it is the fleet ceiling)")
    if ns.metrics_port is not None and not 0 <= ns.metrics_port <= 65535:
        sys.exit(f"--metrics-port {ns.metrics_port} must be in "
                 f"[0, 65535] (0 = OS-assigned)")

    logger = (JsonlLogger(Path(ns.path) / "logs" / "cluster.jsonl")
              if ns.path else None)
    model_kw = dict(embed_dim=ns.embed_dim, num_heads=ns.num_heads,
                    num_blocks=ns.num_blocks, t_max=ns.t_max)
    from idc_models_tpu.models.lm import attention_lm

    model = attention_lm(ns.vocab, ns.t_max, embed_dim=ns.embed_dim,
                         num_heads=ns.num_heads, mlp_dim=ns.mlp_dim,
                         num_blocks=ns.num_blocks)
    params = model.init(jax.random.key(ns.seed)).params

    registry = (PrefixRegistry(ns.prefill_chunk,
                               int(ns.registry_mb * 1024 * 1024),
                               logger=logger)
                if ns.registry_mb > 0 else None)
    # always a policy: --max-retries 0 means ZERO re-placements (a
    # valid, strict budget), never "unbounded"
    retry = RetryPolicy(max_retries=ns.max_retries)
    compile_cache = None
    if ns.compile_cache:
        from idc_models_tpu.serve import CompileCache

        compile_cache = CompileCache(ns.compile_cache, logger=logger)
    devices = jax.devices()

    def _build(i, rid, role):
        return build_replica(
            params, replica_id=rid, role=role,
            device=devices[i % len(devices)],
            n_slots=ns.slots, window=ns.window,
            prefill_chunk=ns.prefill_chunk or None,
            prefix_cache_mb=ns.prefix_cache_mb,
            shared_prefix=registry,
            journal_path=(
                str(Path(ns.journal_dir) / f"journal-{rid}.jsonl")
                if ns.journal_dir else None),
            retry=retry,
            brownout_queue_high=ns.brownout_queue_high,
            max_queue_depth=ns.max_queue_depth,
            temperature=ns.temperature, top_k=ns.top_k or None,
            eos_id=ns.eos, cache_dtype=jnp.float32,
            compile_cache=compile_cache,
            logger=logger, **model_kw)

    replicas = []
    with Timer("Cluster build", logger=logger):
        for i in range(n_fleet):
            role = "prefill" if i >= ns.replicas else "mixed"
            replicas.append(_build(i, f"r{i}", role))
    autoscaler = None
    replica_factory = None
    if ns.autoscale_max is not None:
        from idc_models_tpu.serve import AutoscaleConfig, Autoscaler

        autoscaler = Autoscaler(
            AutoscaleConfig(min_replicas=ns.replicas,
                            max_replicas=ns.autoscale_max),
            logger=logger)
        # a spun-up replica inherits the fleet's build kwargs — and
        # the shared compile cache, so it deserializes warm instead
        # of recompiling
        auto_ordinal = [n_fleet]

        def replica_factory(rid):
            i = auto_ordinal[0]
            auto_ordinal[0] += 1
            return _build(i, rid, "mixed")

    router = Router(
        replicas, retry=retry,
        hedge_after_s=(None if ns.hedge_after_ms is None
                       else ns.hedge_after_ms / 1e3),
        prefix_registry=registry, logger=logger,
        autoscaler=autoscaler, replica_factory=replica_factory)
    # fleet observability (ISSUE 20, serve/cluster/telemetry.py):
    # merged replica-labeled /metrics + fleet /healthz, armed BEFORE
    # the trace so a scraper sees the fleet from its first placement
    exporter = None
    if ns.metrics_port is not None:
        from idc_models_tpu.observe import MetricsExporter
        from idc_models_tpu.serve import ClusterTelemetry

        telemetry = ClusterTelemetry(router,
                                     compile_cache=compile_cache)
        try:
            exporter = MetricsExporter(
                router.registry, port=ns.metrics_port,
                cluster=telemetry).start()
        except OSError as e:
            sys.exit(f"serve-cluster: cannot bind --metrics-port "
                     f"{ns.metrics_port}: {e}")
        print(f"fleet metrics: {exporter.url}/metrics  healthz: "
              f"{exporter.url}/healthz")
    if ns.watchdog:
        from idc_models_tpu.serve import ClusterWatchdog

        router.watchdog = ClusterWatchdog(router, logger=logger)
    if ns.trace:
        trace = load_trace(ns.trace)
    else:
        trace = poisson_trace(
            ns.requests, rate_per_s=ns.rate, vocab=ns.vocab,
            t_max=ns.t_max, eos_id=ns.eos,
            prompt_lens=(2, max(ns.t_max // 4, 2)),
            budgets=(2, max(ns.t_max // 4, 2)), seed=ns.seed,
            sampled=ns.temperature > 0.0)
    print(f"cluster: {ns.replicas} decode replica(s) + "
          f"{ns.prefill_replicas} prefill replica(s), {ns.slots} "
          f"slots each (window {ns.window}, t_max {ns.t_max}); "
          f"serving {len(trace)} requests")
    drill_at = (ns.kill_after_steps
                if (ns.kill_replica is not None
                    or ns.drain_replica is not None) else None)
    drained_on_signal = False
    prev_sigterm = _arm_sigterm()
    try:
        with Timer("Serving trace (cluster)", logger=logger):
            try:
                if drill_at is None:
                    results = router.run(trace, realtime=ns.realtime)
                else:
                    # drill mode: burst-submit (re-offering on
                    # backpressure — a refused submit leaves no Result
                    # and must not be silently dropped), step to the
                    # drill point, fire it, then drain —
                    # deterministic and journal-backed
                    steps = 0
                    for _, req in sorted(trace, key=lambda tr: tr[0]):
                        while not router.submit(req):
                            shed = router.poll(req.id)
                            if shed is not None and shed.status == "shed":
                                break   # terminal answer, not a race
                            router.step()
                            steps += 1
                    for _ in range(max(drill_at - steps, 0)):
                        router.step()
                    if ns.drain_replica is not None:
                        router.drain_replica(f"r{ns.drain_replica}")
                        print(f"drained replica r{ns.drain_replica}")
                    if ns.kill_replica is not None:
                        migrated = router.kill_replica(
                            f"r{ns.kill_replica}")
                        print(f"killed replica r{ns.kill_replica}: "
                              f"{len(migrated)} journaled request(s) "
                              f"migrated onto the survivors")
                    router.drain()
                    results = router.results()
            except _DrainRequested:
                # SIGTERM: every live replica stops admitting, the
                # router steps the fleet until in-flight work lands,
                # and each WAL carries its finish records
                drained_on_signal = True
                for rep in router.replicas:
                    if rep.state == "live":
                        rep.drain()
                router.drain()
                results = router.results()
    finally:
        _disarm_sigterm(prev_sigterm)
        if exporter is not None:
            exporter.close()
    if drained_on_signal:
        print("SIGTERM: cluster drained gracefully — admissions "
              "stopped, in-flight requests finished on every live "
              "replica, journals flushed")
    n_ok = sum(r.status == "ok" for r in results)
    n_error = sum(r.status == "error" for r in results)
    summary = router.summary()
    print(f"served: ok={n_ok} "
          f"timed_out={summary['cluster_timed_out']} "
          f"rejected={summary['cluster_rejected']} "
          f"shed={summary['cluster_shed']} "
          f"tokens={summary['cluster_tokens']} error={n_error}")
    if summary.get("cluster_ttft_ms_p95") is not None:
        print(f"ttft p95 {summary['cluster_ttft_ms_p95']} ms "
              f"(pooled across replicas)")
    print(f"placements: {summary['cluster_placements']}  "
          f"migrations={summary['cluster_migrations']} "
          f"slot_migrations={summary['cluster_slot_migrations']} "
          f"handoffs={summary['cluster_handoffs']} "
          f"hedges={summary['cluster_hedges']}  replicas "
          f"live={summary['cluster_replicas_live']} "
          f"draining={summary['cluster_replicas_draining']} "
          f"dead={summary['cluster_replicas_dead']}")
    if autoscaler is not None:
        ups = sum(1 for d in autoscaler.decisions
                  if d["action"] == "up")
        downs = sum(1 for d in autoscaler.decisions
                    if d["action"] == "down")
        print(f"autoscaler: {ups} scale-up(s), {downs} "
              f"scale-down(s), fleet "
              f"{summary['cluster_replicas_live']} live at exit "
              f"(bounds [{ns.replicas}, {ns.autoscale_max}])")
    if compile_cache is not None:
        cs = compile_cache.summary()
        print(f"compile cache: {cs['hits']} hit(s) "
              f"({cs['deserialize_s']:.3f}s deserializing), "
              f"{cs['misses']} miss(es) -> {cs['stores']} store(s) "
              f"({cs['compile_s']:.3f}s compiling)")
    if registry is not None:
        print(f"prefix registry: {summary['cluster_prefix_hits']} "
              f"hit(s), {summary['cluster_prefix_published']} "
              f"published, {summary['cluster_prefix_bytes']} bytes")
    if router.watchdog is not None:
        kinds = sorted({a["kind"]
                        for a in router.watchdog.anomalies})
        print(f"watchdog: {len(router.watchdog.anomalies)} "
              f"anomaly(ies)"
              + (f" ({', '.join(kinds)})" if kinds else ""))
    print("cluster summary:", json.dumps(summary))
    if logger:
        logger.log(event="cluster_summary", **summary)
    router.close()
    _finish_logger(logger)
    return _error_exit(n_error, drill=ns.kill_replica is not None)


def _run_fed_population(ns):
    """Population-scale federated mode: virtual clients + cohort
    sampling + streamed (or async buffered) aggregation — ROADMAP
    item 4's millions-of-users story at the CLI surface."""
    import jax

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.configs import get_preset
    from idc_models_tpu import faults as faults_lib
    from idc_models_tpu.federated import (
        ClientPopulation, CohortSampler, DriverConfig, RoundFailure,
        initialize_server, make_async_round, make_federated_eval,
        make_population_round, run_rounds,
    )
    from idc_models_tpu.federated import robust
    from idc_models_tpu.models import registry
    from idc_models_tpu.observe import Timer, profile_trace
    from idc_models_tpu.train import rmsprop

    preset = _apply_overrides(
        get_preset("fed"), ns, ["batch_size", "lr", "rounds",
                                "local_epochs"])
    n_pop = int(ns.population)
    cohort = int(ns.cohort)
    if cohort < 1:
        sys.exit(f"--cohort must be >= 1, got {cohort}")
    if cohort > n_pop:
        sys.exit(f"--cohort {cohort} exceeds --population {n_pop}: a "
                 f"round cannot sample more clients than the "
                 f"population holds")
    wave = int(ns.cohort_wave) or cohort
    use_async = int(ns.async_buffer) != 0
    if use_async and ns.async_buffer < 0:
        sys.exit(f"--async-buffer must be >= 1 (0 disables async "
                 f"mode), got {ns.async_buffer}")
    if use_async and int(ns.cohort_wave):
        sys.exit("--cohort-wave only applies to synchronous streamed "
                 "rounds; the async server buffers by --async-buffer "
                 "instead (drop one of the two flags)")
    decay = float(ns.staleness_decay)
    if not 0.0 < decay <= 1.0:
        sys.exit(f"--staleness-decay must be in (0, 1], got {decay} "
                 f"(1 = no discount; smaller discounts staler "
                 f"updates harder)")
    n_dev = len(jax.devices())
    mesh = meshlib.client_mesh(meshlib.largest_dividing_mesh(wave,
                                                             n_dev))
    model_name = getattr(ns, "model", None) or preset.model
    image_size = 10 if model_name == "small_cnn" else preset.image_size
    s = int(ns.client_examples)
    if s < 1:
        sys.exit(f"--client-examples must be >= 1, got {s} (each "
                 f"virtual client's shard size)")
    weight_range = (0.5 * s, 1.5 * s) if ns.weighted_sampling else \
        (float(s), float(s))
    population = ClientPopulation(
        n_pop, examples_per_client=s, image_size=image_size,
        seed=ns.seed, weight_range=weight_range)
    sampler = CohortSampler(population, cohort, seed=ns.seed,
                            weighted=ns.weighted_sampling)
    logger = _logger(ns)
    delay_ms = float(getattr(ns, "fault_delay_ms", 0.0))
    if delay_ms < 0:
        sys.exit(f"--fault-delay-ms must be >= 0, got {delay_ms}")
    plan = None
    if getattr(ns, "faults", None):
        try:
            plan = faults_lib.parse_population_fault_spec(
                ns.faults, n_pop, seed=ns.seed,
                delay_unit_s=delay_ms / 1000.0)
        except ValueError as e:
            sys.exit(str(e))
        print(f"[idc_models_tpu] injecting faults: {plan}",
              file=sys.stderr)
        if (use_async and delay_ms == 0.0
                and plan.max_staleness > 0):
            # without a wall delay a straggler never arrives late, and
            # async staleness IS lateness — say so instead of letting
            # the drill silently run fault-free
            print("[idc_models_tpu] straggler faults are INERT in "
                  "async mode without --fault-delay-ms: buffered "
                  "staleness comes from late arrival, and the plan's "
                  "stragglers arrive on time", file=sys.stderr)

    spec = registry.get_model(model_name)
    model = spec.build(preset.num_outputs, 3)
    loss_fn = _loss_for(preset.num_outputs)
    opt = rmsprop(preset.lr / 10.0)
    server = initialize_server(model, jax.random.key(ns.seed))
    server_ckpt = Path(ns.path) / "fed_server" if ns.path else None
    resumed = False
    from idc_models_tpu.train import checkpoint_exists, restore_checkpoint

    if server_ckpt is not None and checkpoint_exists(server_ckpt):
        server = restore_checkpoint(server_ckpt, jax.device_get(server))
        print(f"resuming federated training from round "
              f"{int(server.round)}")
        resumed = int(server.round) > 0
    if not use_async:
        # the streamed wave program wants the server replicated over
        # the client mesh; the async server is host-driven and keeps
        # default placement
        server = jax.device_put(server, meshlib.replicated(mesh))
    # separate resume high-water marks per event: fed_cohort is written
    # INSIDE round_fn while the `round` record lands after eval, so a
    # crash in between leaves them unequal — one shared max would
    # suppress the missing record's re-log forever
    logged_through = -1          # `round` records (and round_health)
    cohort_through = -1          # fed_cohort records (builder-owned)
    if resumed and logger is not None and logger.path.exists():
        import json as _json

        for line in logger.path.read_text().splitlines():
            try:
                rec = _json.loads(line)
            except ValueError:
                continue
            if rec.get("event") == "round":
                logged_through = max(logged_through, int(rec["round"]))
            elif rec.get("event") == "fed_cohort":
                cohort_through = max(cohort_through, int(rec["round"]))

    agg_name = getattr(ns, "aggregator", "mean")
    agg_kw = ({"trim": ns.trim} if agg_name == "trimmed_mean" else
              {"max_norm": ns.clip_norm} if agg_name == "norm_clip"
              else {})
    try:
        agg = robust.get_aggregator(agg_name, **agg_kw)
        if use_async:
            round_fn = make_async_round(
                model, opt, loss_fn, population, sampler,
                buffer_size=int(ns.async_buffer),
                staleness_decay=decay,
                local_epochs=preset.local_epochs,
                batch_size=preset.batch_size, aggregator=agg,
                faults=plan, seed=ns.seed, logger=logger,
                log_from_round=cohort_through)
            participant_ids_fn = lambda r: round_fn.last_participants
        else:
            round_fn = make_population_round(
                model, opt, loss_fn, mesh, population, sampler,
                wave_size=wave, local_epochs=preset.local_epochs,
                batch_size=preset.batch_size, aggregator=agg,
                faults=plan, barrier_sleep=delay_ms > 0,
                logger=logger, log_from_round=cohort_through)
            participant_ids_fn = lambda r: sampler.cohort(r)
    except ValueError as e:
        sys.exit(str(e))

    # held-out eval cohort: a fixed seeded draw, materialized once —
    # O(wave) like everything else in this mode
    eval_sampler = CohortSampler(population, wave, seed=ns.seed + 4242)
    eval_imgs, eval_labels, eval_w = population.materialize(
        eval_sampler.cohort(0))
    cshard = meshlib.sharding(mesh, meshlib.CLIENT_AXIS)
    eval_imgs = jax.device_put(eval_imgs, cshard)
    eval_labels = jax.device_put(eval_labels, cshard)
    eval_fn = make_federated_eval(model, loss_fn, mesh)

    def eval_round(sv):
        em = _fetch_scalars(eval_fn(sv, eval_imgs, eval_labels, eval_w))
        return {"test_loss": float(em["loss"]),
                "test_acc": float(em["accuracy"])}

    print("round, train_loss, train_acc, test_loss, test_acc")
    totals = {"updates": 0, "staleness_sum": 0.0, "participants": 0}

    def print_round(entry):
        print(f"{entry['round']}, {entry['loss']:.4f}, "
              f"{entry['accuracy']:.4f}, {entry['test_loss']:.4f}, "
              f"{entry['test_acc']:.4f}")
        totals["updates"] += int(entry.get("updates", 0))
        totals["staleness_sum"] += (float(entry.get("staleness_mean",
                                                    0.0))
                                    * int(entry.get("participants", 0)))
        totals["participants"] += int(entry.get("participants", 0))
        if logger and entry["round"] > logged_through:
            logger.log(event="round", round=entry["round"],
                       train_loss=entry["loss"],
                       train_acc=entry["accuracy"],
                       test_loss=entry["test_loss"],
                       test_acc=entry["test_acc"],
                       clients_dropped=int(
                           entry.get("clients_dropped", 0)))

    spike = getattr(ns, "loss_spike_ratio", 10.0)
    if spike is not None and spike != 0 and spike <= 1:
        sys.exit(f"--loss-spike-ratio {spike} must be > 1 (0 disables "
                 f"the detector)")
    config = DriverConfig(
        rounds=preset.rounds,
        timeout_s=getattr(ns, "round_timeout", None),
        max_attempts=1 + max(int(getattr(ns, "max_round_retries", 2)),
                             0),
        loss_spike_ratio=spike if spike and spike > 1 else None,
        checkpoint_path=server_ckpt,
        checkpoint_every=max(int(getattr(ns, "checkpoint_every", 10)),
                             1))
    try:
        with Timer("Federated training", logger=logger), \
                profile_trace(ns.profile_dir):
            result = run_rounds(
                round_fn, server, None, None,
                np.ones((cohort,), np.float32), config=config,
                seed=ns.seed + 1, eval_fn=eval_round,
                on_round=print_round, logger=logger, verbose=True,
                log_from_round=logged_through,
                log_round_records=False, fault_plan=plan,
                participant_ids_fn=participant_ids_fn)
    except RoundFailure as e:
        sys.exit(f"[idc_models_tpu] federated training aborted: {e}")
    mode = "weighted" if ns.weighted_sampling else "uniform"
    decomp = (f" in {cohort // wave} wave(s) of {wave}; memory "
              f"bounded by the wave, not the population" if not
              use_async else "; memory bounded by the in-flight pool, "
              "not the population")
    print(f"population: {n_pop} virtual clients, cohort {cohort} "
          f"({mode}){decomp}")
    if use_async:
        mean_st = (totals["staleness_sum"] / totals["participants"]
                   if totals["participants"] else 0.0)
        print(f"async buffer: K={int(ns.async_buffer)}, staleness "
              f"decay {decay}, {totals['updates']} buffered update(s),"
              f" mean staleness {mean_st:.2f}")
    retried = [e for e in result.events if e["status"] != "ok"]
    if retried:
        print(f"[idc_models_tpu] {len(retried)} round attempt(s) "
              f"failed and were healed (rollback/reseed); see "
              f"round_health events", file=sys.stderr)
    _finish_logger(logger)


def _run_fed(ns):
    import jax

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.configs import get_preset
    from idc_models_tpu.data.partition import (
        pad_clients, partition_clients, train_test_client_split,
    )
    from idc_models_tpu import faults as faults_lib
    from idc_models_tpu.federated import (
        DriverConfig, RoundFailure, initialize_server, make_fedavg_round,
        make_federated_eval, run_rounds, seed_server_with,
    )
    from idc_models_tpu.models import registry
    from idc_models_tpu.observe import Timer, profile_trace
    from idc_models_tpu.train import (
        TwoPhaseConfig, checkpoint_exists, restore_checkpoint,
        rmsprop, save_checkpoint, two_phase_fit,
    )

    if ns.checkpoint_every < 1:
        sys.exit(f"--checkpoint-every {ns.checkpoint_every} must be "
                 f">= 1: saving every 0 rounds is never, and a crash "
                 f"then replays the whole run")
    if getattr(ns, "population", 0):
        return _run_fed_population(ns)
    preset = _apply_overrides(
        get_preset("fed"), ns,
        ["batch_size", "lr", "rounds", "iid", "num_clients", "local_epochs",
         "pretrain_epochs"])
    n_dev = len(jax.devices())
    # client count is independent of chip count: k = ceil(C/D) clients
    # train per device (vmapped), padded with weight-0 dummies
    n_clients = preset.num_clients
    ds = _load_idc(ns, preset.image_size, preset.dataset_limit)
    logger = _logger(ns)

    # Pretrain (C8): checkpoint-gated two-phase VGG16 on the pooled data.
    spec = registry.get_model(preset.model)
    mesh_dp = meshlib.data_mesh()
    from idc_models_tpu.data.idc import train_val_test_split

    train, val, _ = train_val_test_split(ds, seed=ns.seed)
    ckpt = (Path(ns.path) / "pretrained" / "cp.ckpt" if ns.path else None)
    model = spec.build(preset.num_outputs, 3)
    if ckpt is not None and checkpoint_exists(ckpt):
        variables = model.init(jax.random.key(ns.seed))
        target = {"params": variables.params, "state": variables.state}
        restored = restore_checkpoint(ckpt, target)
        params, model_state = restored["params"], restored["state"]
        print(f"restored pretrained weights from {ckpt}")
        if ns.pretrained_weights:
            print(f"[idc_models_tpu] --pretrained-weights ignored: "
                  f"checkpoint {ckpt} takes precedence (delete it to "
                  f"re-pretrain from the artifact)", file=sys.stderr)
    else:
        result = two_phase_fit(
            preset.model, preset.num_outputs, train, val, mesh_dp,
            TwoPhaseConfig(lr=preset.lr, epochs=preset.pretrain_epochs,
                           fine_tune_epochs=0,
                           batch_size=preset.batch_size,
                           fine_tune_at=preset.fine_tune_at, seed=ns.seed),
            pretrained_weights=ns.pretrained_weights,
            artifact_path=ns.path, logger=logger)
        params, model_state = result.state.params, result.state.model_state
        if ckpt is not None:
            save_checkpoint(ckpt, {"params": jax.device_get(params),
                                   "state": jax.device_get(model_state)})

    # Federate: clients fine-tune above fine_tune_at at lr/10
    # (fed_model.py:140-147,208).
    mesh = meshlib.client_mesh(min(n_clients, n_dev))
    n_mesh = mesh.devices.size
    imgs, labels = partition_clients(ds, n_clients, iid=bool(preset.iid),
                                     seed=ns.seed)
    n_per_client = imgs.shape[1]
    train_ids, test_ids = train_test_client_split(
        n_clients, preset.test_client_fraction, seed=ns.seed)
    # train clients carry weight = examples; test clients weight 0; pad
    # the client axis to the mesh with inert weight-0 dummies
    w_train = np.zeros((n_clients,), np.float32)
    w_train[train_ids] = n_per_client
    w_test = np.zeros((n_clients,), np.float32)
    w_test[test_ids] = n_per_client
    imgs, labels, w_train, w_test = pad_clients(imgs, labels, w_train,
                                                w_test, multiple=n_mesh)
    # upload the stacked client shards to HBM once — not once per round
    cshard = meshlib.sharding(mesh, meshlib.CLIENT_AXIS)
    imgs = jax.device_put(imgs, cshard)
    labels = jax.device_put(labels, cshard)
    opt = rmsprop(preset.lr / 10.0,
                  trainable_mask=spec.fine_tune_mask(params,
                                                     preset.fine_tune_at))
    server = seed_server_with(
        initialize_server(model, jax.random.key(ns.seed)),
        params, model_state)
    # Round-loop checkpoint/resume: the reference checkpoints only the
    # pretrainer (SURVEY.md §5); here the federated loop resumes too.
    server_ckpt = Path(ns.path) / "fed_server" if ns.path else None
    resumed = False
    if server_ckpt is not None and checkpoint_exists(server_ckpt):
        server = restore_checkpoint(server_ckpt, jax.device_get(server))
        print(f"resuming federated training from round {int(server.round)}")
        resumed = int(server.round) > 0
    # restored/pretrained arrays may live on a single device; the round
    # program wants them replicated over the client mesh
    server = jax.device_put(server, meshlib.replicated(mesh))
    plan = None
    if getattr(ns, "faults", None):
        plan = faults_lib.parse_fault_spec(ns.faults, n_clients)
        print(f"[idc_models_tpu] injecting faults: {plan}",
              file=sys.stderr)
    from idc_models_tpu.federated import robust

    agg_name = getattr(ns, "aggregator", "mean")
    agg_kw = ({"trim": ns.trim} if agg_name == "trimmed_mean" else
              {"max_norm": ns.clip_norm} if agg_name == "norm_clip" else {})
    round_fn = make_fedavg_round(
        model, opt, _loss_for(preset.num_outputs), mesh,
        local_epochs=preset.local_epochs, batch_size=preset.batch_size,
        aggregator=robust.get_aggregator(agg_name, **agg_kw), faults=plan)
    eval_fn = make_federated_eval(model, _loss_for(preset.num_outputs), mesh)
    print("round, train_loss, train_acc, test_loss, test_acc")
    every = max(int(getattr(ns, "checkpoint_every", 10)), 1)
    # A resume from an every-N checkpoint deterministically replays the
    # rounds after the last save (same fold_in(round) rng). Replayed
    # rounds print again (this process really runs them) but must NOT
    # append duplicate records to the append-only run.jsonl — consumers
    # aggregating by event=round would double-count them. Only an ACTUAL
    # resume replays rounds: a fresh run pointed at a reused --log-dir
    # must log every round, not inherit the old file's high-water mark.
    logged_through = -1
    if resumed and logger is not None and logger.path.exists():
        import json as _json

        for line in logger.path.read_text().splitlines():
            try:
                rec = _json.loads(line)
            except ValueError:
                continue
            if rec.get("event") == "round":
                logged_through = max(logged_through, int(rec["round"]))
    def eval_round(sv):
        # ONE host fetch for every metric: each individual scalar
        # fetch is a full sync round-trip (see _fetch_scalars)
        em = _fetch_scalars(eval_fn(sv, imgs, labels, w_test))
        return {"test_loss": float(em["loss"]),
                "test_acc": float(em["accuracy"])}

    def print_round(entry):
        print(f"{entry['round']}, {entry['loss']:.4f}, "
              f"{entry['accuracy']:.4f}, {entry['test_loss']:.4f}, "
              f"{entry['test_acc']:.4f}")
        # the CLI owns the `round` jsonl records (driver logs only
        # round_health) so the historical field names — train_loss/
        # train_acc, consumed by existing run.jsonl tooling — survive
        # the move to the driver
        if entry.get("trim_degenerate"):
            print(f"[idc_models_tpu] round {entry['round']}: trimmed "
                  f"mean had NO kept band (live clients <= 2*trim) — "
                  f"the server state was left UNCHANGED this round; "
                  f"lower --trim or enroll more clients",
                  file=sys.stderr)
        if logger and entry["round"] > logged_through:
            logger.log(event="round", round=entry["round"],
                       train_loss=entry["loss"],
                       train_acc=entry["accuracy"],
                       test_loss=entry["test_loss"],
                       test_acc=entry["test_acc"],
                       clients_dropped=int(
                           entry.get("clients_dropped", 0)))

    spike = getattr(ns, "loss_spike_ratio", 10.0)
    if spike is not None and spike != 0 and spike <= 1:
        # only the documented 0 disables; negatives and (0, 1] are
        # configuration mistakes that must not silently turn the
        # divergence detector off
        sys.exit(f"--loss-spike-ratio {spike} must be > 1 (a round is "
                 f"rolled back when its loss exceeds ratio x the last "
                 f"good loss; 0 disables the detector)")
    config = DriverConfig(
        rounds=preset.rounds,
        timeout_s=getattr(ns, "round_timeout", None),
        max_attempts=1 + max(int(getattr(ns, "max_round_retries", 2)), 0),
        loss_spike_ratio=spike if spike and spike > 1 else None,
        checkpoint_path=server_ckpt, checkpoint_every=every)
    # the self-healing driver (federated/driver.py) owns the round loop:
    # per-round wall budget, reseeded-subset retry, divergence rollback,
    # periodic checkpoints, and round_health jsonl events
    try:
        with Timer("Federated training", logger=logger), \
                profile_trace(ns.profile_dir):
            result = run_rounds(
                round_fn, server, imgs, labels, w_train, config=config,
                seed=ns.seed + 1, eval_fn=eval_round,
                on_round=print_round, logger=logger, verbose=True,
                log_from_round=logged_through, log_round_records=False,
                fault_plan=plan)
    except RoundFailure as e:
        sys.exit(f"[idc_models_tpu] federated training aborted: {e}")
    server = result.server
    for entry in result.history:
        dropped = int(entry.get("clients_dropped", 0))
        if dropped:
            print(f"[idc_models_tpu] round {entry['round']}: dropped "
                  f"{dropped} client(s) with non-finite updates from "
                  f"the aggregate", file=sys.stderr)
    retried = [e for e in result.events if e["status"] != "ok"]
    if retried:
        print(f"[idc_models_tpu] {len(retried)} round attempt(s) "
              f"failed and were healed (rollback/reseed); see "
              f"round_health events", file=sys.stderr)
    _finish_logger(logger)


def _run_secure(ns):
    import jax

    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.configs import get_preset
    from idc_models_tpu.data.idc import ArrayDataset
    from idc_models_tpu.models import registry
    from idc_models_tpu.observe import Timer
    from idc_models_tpu.train import Evaluator, rmsprop
    from idc_models_tpu.federated import initialize_server
    from idc_models_tpu.secure import make_secure_fedavg_round

    if getattr(ns, "async_buffer", 0):
        # rejected at BUILD, with the protocol reason — not silently
        # ignored, not a bare argparse error
        from idc_models_tpu.federated import ensure_async_compatible

        try:
            ensure_async_compatible(secure=True)
        except ValueError as e:
            sys.exit(str(e))
    preset = _apply_overrides(
        get_preset("secure_fed"), ns,
        ["batch_size", "lr", "rounds", "percent", "num_clients",
         "local_epochs", "paillier"])
    n_dev = len(jax.devices())
    # full mesh for any client count: non-dividing counts are padded
    # inside the round with mask-participating dummy clients (forced-zero
    # updates, divisor = real count), so every device works
    n_clients = preset.num_clients
    n_mesh = min(n_clients, n_dev)
    ds = _load_idc(ns, preset.image_size, None)
    # take/skip split sized by the preset (24000/6000 in the reference,
    # secure_fed_model.py:219-220), scaled down when the dataset is smaller
    n_client_total = min(preset.client_examples, int(len(ds) * 0.8))
    client_ds = ds.take(n_client_total)
    test_ds = ds.skip(n_client_total).take(preset.test_examples)
    logger = _logger(ns)

    spec = registry.get_model(preset.model)
    model = spec.build(preset.num_outputs, 3)
    loss_fn = _loss_for(preset.num_outputs)
    opt = rmsprop(preset.lr)

    if preset.paillier:
        if getattr(ns, "mask_impl", "threefry") != "threefry":
            print("[idc_models_tpu] --mask-impl has no effect with "
                  "--paillier (host-side Paillier path)", file=sys.stderr)
        _run_secure_paillier(preset, n_clients, client_ds, test_ds, model,
                             opt, loss_fn, logger, ns)
        _finish_logger(logger)
        return

    # strided shard per client (secure_fed_model.py:206-210), stacked for
    # the client mesh
    shards = [client_ds.shard(n_clients, i) for i in range(n_clients)]
    size = min(len(s) for s in shards)
    imgs = np.stack([s.images[:size] for s in shards])
    labels = np.stack([s.labels[:size] for s in shards])

    mesh = meshlib.client_mesh(n_mesh)
    # pad non-dividing client counts to the mesh ONCE (the padded slots
    # become mask-participating dummies inside the round — n_real keeps
    # the divisor honest), then upload the stacked shards to HBM once —
    # never re-pad/re-upload per round
    pad = -n_clients % n_mesh
    if pad:
        imgs = np.concatenate(
            [imgs, np.zeros((pad,) + imgs.shape[1:], imgs.dtype)])
        labels = np.concatenate(
            [labels, np.zeros((pad,) + labels.shape[1:], labels.dtype)])
    cshard = meshlib.sharding(mesh, meshlib.CLIENT_AXIS)
    imgs = jax.device_put(imgs, cshard)
    labels = jax.device_put(labels, cshard)
    server = initialize_server(model, jax.random.key(ns.seed))
    round_fn = make_secure_fedavg_round(
        model, opt, loss_fn, mesh, percent=preset.percent,
        local_epochs=preset.local_epochs, batch_size=preset.batch_size,
        mask_impl=getattr(ns, "mask_impl", "threefry"))
    evaluator = Evaluator(model, loss_fn, mesh, batch_size=preset.batch_size,
                          with_auroc=True)
    from idc_models_tpu.observe import profile_trace

    key = jax.random.key(ns.seed + 1)
    with Timer("Secure fed model", logger=logger), \
            profile_trace(ns.profile_dir):
        for r in range(preset.rounds):
            key, sub = jax.random.split(key)
            server, tm = round_fn(server, imgs, labels, sub,
                                  n_real=n_clients)
            from idc_models_tpu.train import TrainState

            eval_state = TrainState(step=server.round, params=server.params,
                                    model_state=server.model_state,
                                    opt_state=None)
            em = evaluator(eval_state, test_ds)
            # one host fetch for the round metrics (see _fetch_scalars);
            # em is already host floats — Evaluator fetches internally
            tm = _fetch_scalars(tm)
            print(f"round {r}: train_loss={float(tm['loss']):.4f} "
                  f"test_loss={em['loss']:.4f} acc={em['accuracy']:.4f} "
                  f"auroc={em['auroc']:.4f}")
            recovered = int(tm.get("clients_recovered", 0))
            if recovered:
                print(f"[idc_models_tpu] round {r}: {recovered} "
                      f"client(s) diverged; their updates were replaced "
                      f"with the incoming global weights", file=sys.stderr)
            if logger:
                logger.log(event="round", round=r, train_loss=tm["loss"],
                           clients_recovered=recovered,
                           **{f"test_{k}": v for k, v in em.items()})
    _finish_logger(logger)


def _run_secure_paillier(preset, n_clients, client_ds, test_ds, model, opt,
                         loss_fn, logger, ns):
    from idc_models_tpu.observe import Timer
    from idc_models_tpu.secure.fedavg import PaillierClient, PaillierServer
    from idc_models_tpu.secure.paillier import generate_paillier_keypair

    pub, priv = generate_paillier_keypair(512)
    clients = []
    for i in range(n_clients):
        shard = client_ds.shard(n_clients, i)
        clients.append(PaillierClient(
            model, opt, loss_fn, shard.images, shard.labels, i,
            preset.percent, pub, priv, local_epochs=preset.local_epochs,
            batch_size=preset.batch_size, seed=ns.seed))
    with Timer("Secure fed model", logger=logger):
        for r in range(preset.rounds):
            packages = []
            for c in clients:
                with Timer(f"Client {c.client_id} training"):
                    pkg, _ = c.client_fit()
                packages.append(pkg)
            agg = PaillierServer.aggregate(packages)
            for c in clients:
                c.client_update(agg)
            m = clients[0].evaluate(test_ds.images, test_ds.labels, loss_fn)
            print(f"round {r}: " + " ".join(f"{k}={v:.4f}"
                                            for k, v in m.items()))
            if logger:
                logger.log(event="round", round=r, **m)


if __name__ == "__main__":
    sys.exit(main())
