"""See where a serve run spends its time: the ISSUE-5 observability
layer end to end — span tracer, metrics registry, Chrome trace export,
and the offline stats rollup.

`python examples/10_observability.py` runs on a virtual 8-device CPU
pod. A small LM serves a burst of requests through the
continuous-batching engine with a tracer armed; the run produces:

- `/tmp/idc_obs_example/trace.json` — Chrome trace-event JSON. Open it
  in Perfetto (https://ui.perfetto.dev) or chrome://tracing and you see
  the scheduler's cycles: `serve.tick` spans with `serve.admit` (and
  the chunked `serve.start_prefill` / `serve.prefill_chunk` /
  `serve.insert` host work under it), `serve.collect` (blocking on the
  in-flight window's tokens), `serve.refill` (the admission pass after
  collect) and `serve.window` (the next fused dispatch) nested inside,
  and the detached `serve.turnaround` from collect's return to that
  dispatch's return: the host's side of the device's idle gap.
- the same spans as a jsonl file, summarized by `observe.stats` — the
  library form of the `python -m idc_models_tpu stats <file>` verb.
- the process-wide metrics registry in Prometheus text exposition.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

from idc_models_tpu import mesh as meshlib

meshlib.force_cpu_pod(8)          # delete this line on real TPU hardware

import jax
import jax.numpy as jnp

from idc_models_tpu.models.lm import attention_lm
from idc_models_tpu.observe import REGISTRY, format_summary, \
    summarize_jsonl, trace
from idc_models_tpu.serve import LMServer, poisson_trace

VOCAB, T_MAX = 11, 32
out_dir = pathlib.Path("/tmp/idc_obs_example")

mesh = meshlib.seq_mesh(1)
model = attention_lm(VOCAB, T_MAX, embed_dim=32, num_heads=2,
                     mlp_dim=64, num_blocks=2, mesh=mesh)
params = model.init(jax.random.key(0)).params

# arm the tracer for the serve run; both exports land on exit
with trace.tracing(chrome_path=out_dir / "trace.json",
                   jsonl_path=out_dir / "spans.jsonl"):
    server = LMServer(params, embed_dim=32, num_heads=2, num_blocks=2,
                      t_max=T_MAX, n_slots=2, window=4, mesh=mesh,
                      cache_dtype=jnp.float32, prefill_chunk=8)
    results = server.run(poisson_trace(
        8, rate_per_s=1e9, vocab=VOCAB, t_max=T_MAX,
        prompt_lens=(4, 12), budgets=(4, 8), seed=0))

assert all(r.status == "ok" for r in results)
print(f"served {len(results)} requests; trace at {out_dir}/trace.json "
      f"(open in https://ui.perfetto.dev)")

# the offline rollup the `stats` CLI verb prints, over the span export
print()
print(format_summary(summarize_jsonl(out_dir / "spans.jsonl")))

# the process-wide registry, Prometheus-ready
print()
print("metrics registry (Prometheus text exposition):")
text = REGISTRY.prometheus_text()
print("\n".join(l for l in text.splitlines()
                if l.startswith(("#", "serve_"))
                and "_bucket" not in l))
