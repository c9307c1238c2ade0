"""End-to-end CLI smoke tests: every subcommand runs in-process on the
virtual 8-device mesh with tiny sizes.

The reference's product surface is its five entry points
(dist_model_tf_vgg.py:103, dist_model_tf_mobile.py:103,
dist_model_tf_dense.py:118, fed_model.py:168, secure_fed_model.py:212);
these tests drive the equivalent presets through `cli.main` exactly as a
user would, including the fed checkpoint gate + round resume and the
Paillier parity mode.
"""

import jax
import numpy as np
import pytest

from idc_models_tpu import cli

pytestmark = pytest.mark.filterwarnings(
    "ignore:.*synthetic.*:UserWarning")


@pytest.fixture(autouse=True)
def _restore_backend_roofs():
    """`profile --peak-tflops/--peak-gbps` registers the declared roof
    under the live device kind ("cpu" here) in the process-global
    BACKEND_ROOFS — restore it so tests/test_profile.py's
    unknown-backend assertions see the pristine table."""
    from idc_models_tpu.observe import profile as prof

    saved = dict(prof.BACKEND_ROOFS)
    yield
    prof.BACKEND_ROOFS.clear()
    prof.BACKEND_ROOFS.update(saved)


def _run(args, capsys):
    assert cli.main(args) == 0
    return capsys.readouterr().out


def test_cli_vgg_two_phase(tmp_path, capsys):
    out = _run(["vgg", "--path", str(tmp_path), "--host-devices", "8",
                "--synthetic-examples", "64", "--batch-size", "8",
                "--epochs", "1", "--fine-tune-epochs", "1"], capsys)
    assert "Number of devices: 8" in out
    assert "initial loss" in out            # the evaluate floor (quirk Q3)
    assert "epoch 1/1" in out               # phase 1
    assert "epoch 2/2" in out               # phase 2 continues the counter
    assert "test:" in out
    assert (tmp_path / "logs" / "plot_dev8.png").exists()   # C18 artifact
    assert (tmp_path / "logs" / "run.jsonl").exists()


def test_cli_vgg_model_parallel(capsys):
    """--model-parallel 2 trains on a 4x2 ("data", "model") mesh through
    the product surface; the batch scales with the DATA axis only."""
    out = _run(["vgg", "--host-devices", "8", "--synthetic-examples", "64",
                "--batch-size", "8", "--epochs", "1",
                "--fine-tune-epochs", "1", "--model-parallel", "2"], capsys)
    assert "Number of devices: 8" in out
    assert "epoch 2/2" in out
    assert "test:" in out


def test_cli_vgg_pretrained_weights(tmp_path, capsys):
    """The --pretrained-weights flag demonstrably reaches the init: the
    run reports the load and starts from a different baseline."""
    from idc_models_tpu.models import pretrained
    from idc_models_tpu.models.vgg import vgg16

    variables = vgg16(1).init(jax.random.key(0))
    rng = np.random.default_rng(0)
    noisy = jax.tree.map(
        lambda x: np.asarray(x) + rng.normal(0, 0.1, np.shape(x))
        .astype(np.float32), variables.params["backbone"])
    npz = tmp_path / "bb.npz"
    pretrained.save_npz(npz, noisy)

    args = ["vgg", "--host-devices", "8", "--synthetic-examples", "64",
            "--batch-size", "8", "--epochs", "1", "--fine-tune-epochs", "0"]
    base = _run(args, capsys)
    warm = _run(args + ["--pretrained-weights", str(npz)], capsys)
    assert "loaded pretrained weights" in warm
    assert "loaded pretrained weights" not in base

    def floor(out):
        line = [ln for ln in out.splitlines() if "initial loss" in ln][0]
        return float(line.split(":")[1])

    assert floor(base) != floor(warm)


def test_cli_vgg_streamed(tmp_path, capsys):
    """--stream decodes train batches from disk on the fly; val/test are
    materialized from the same file-level split."""
    from PIL import Image

    data = tmp_path / "idc"
    rng = np.random.default_rng(0)
    for label in ("0", "1"):
        d = data / label
        d.mkdir(parents=True)
        for i in range(40):
            arr = (rng.random((50, 50, 3)) * 200).astype(np.uint8)
            Image.fromarray(arr).save(d / f"p{i}.png")
    out = _run(["vgg", "--path", str(tmp_path), "--data-dir", str(data),
                "--host-devices", "8", "--batch-size", "8", "--stream",
                "--epochs", "1", "--fine-tune-epochs", "1"], capsys)
    assert "epoch 2/2" in out and "test:" in out


def test_cli_vgg_streamed_decode_workers(tmp_path, capsys):
    """--decode-workers 2 fans decoding over worker processes and the
    run still trains (the stream itself is pinned bit-identical in
    test_data.py; this drives the CLI wiring)."""
    from PIL import Image

    data = tmp_path / "idc"
    rng = np.random.default_rng(1)
    for label in ("0", "1"):
        d = data / label
        d.mkdir(parents=True)
        for i in range(40):
            arr = (rng.random((50, 50, 3)) * 200).astype(np.uint8)
            Image.fromarray(arr).save(d / f"p{i}.png")
    out = _run(["vgg", "--path", str(tmp_path), "--data-dir", str(data),
                "--host-devices", "8", "--batch-size", "8", "--stream",
                "--decode-workers", "2", "--epochs", "1",
                "--fine-tune-epochs", "0"], capsys)
    assert "epoch 1/1" in out and "test:" in out


def test_cli_attention(tmp_path, capsys):
    """The sequence-parallel transformer workload from the product
    surface: trains on a ("data", "seq") mesh and reports val metrics
    incl. AUROC; the zigzag layout works through the same flags."""
    out = _run(["attention", "--host-devices", "8", "--steps", "40",
                "--seq-len", "32", "--embed-dim", "16", "--num-heads",
                "2", "--mlp-dim", "32", "--num-blocks", "1",
                "--batch-size", "32", "--path", str(tmp_path)], capsys)
    assert "(data=2, seq=4)" in out
    assert "val:" in out and "auroc=" in out
    assert (tmp_path / "logs" / "run.jsonl").exists()
    out = _run(["attention", "--host-devices", "8", "--steps", "10",
                "--seq-len", "64", "--embed-dim", "16", "--num-heads",
                "2", "--mlp-dim", "32", "--num-blocks", "1",
                "--layout", "zigzag", "--batch-size", "32"], capsys)
    assert "val:" in out


def test_cli_attention_rejects_bad_ring(capsys):
    with pytest.raises(SystemExit):
        cli.main(["attention", "--host-devices", "8",
                  "--seq-parallel", "3"])
    with pytest.raises(SystemExit):
        cli.main(["attention", "--host-devices", "8", "--seq-len", "30",
                  "--layout", "zigzag"])


def test_cli_attention_idc_tree(tmp_path, capsys):
    """--data-dir routes the SP workload onto the reference's own data
    domain (VERDICT r4 #5): the labeled IDC tree decodes through C1,
    splits 80/10/10, and each patch trains as a raster token sequence —
    seq-len/features derived from --image-size/--patch-size, ring
    divisibility still enforced."""
    from PIL import Image

    data = tmp_path / "idc"
    rng = np.random.default_rng(2)
    for label in ("0", "1"):
        d = data / label
        d.mkdir(parents=True)
        for i in range(30):
            arr = (rng.random((20, 20, 3)) * 200).astype(np.uint8)
            Image.fromarray(arr).save(d / f"p{i}.png")
    out = _run(["attention", "--host-devices", "8", "--data-dir",
                str(data), "--image-size", "20", "--patch-size", "5",
                "--steps", "12", "--embed-dim", "16", "--num-heads", "2",
                "--mlp-dim", "32", "--num-blocks", "1", "--batch-size",
                "16", "--path", str(tmp_path)], capsys)
    # 20x20 at patch 5 -> 16 tokens x 75 features
    assert "16 tokens x 75 features" in out
    assert "val:" in out and "auroc=" in out
    # indivisible token count fails with the derived-shape message
    with pytest.raises(SystemExit):
        cli.main(["attention", "--host-devices", "8", "--data-dir",
                  str(data), "--image-size", "20", "--patch-size", "4",
                  "--layout", "zigzag"])   # 25 tokens, 8 stripes
    # patch size not dividing the image fails at flag validation
    with pytest.raises(SystemExit):
        cli.main(["attention", "--host-devices", "8", "--data-dir",
                  str(data), "--image-size", "20", "--patch-size", "3"])


def test_cli_mobile(capsys):
    out = _run(["mobile", "--host-devices", "8", "--synthetic-examples",
                "64", "--batch-size", "8", "--epochs", "1",
                "--fine-tune-epochs", "0"], capsys)
    assert "epoch 1/1" in out and "test:" in out


def test_cli_dense_cifar(capsys):
    out = _run(["dense", "--host-devices", "8", "--synthetic-examples",
                "64", "--batch-size", "4", "--epochs", "1",
                "--fine-tune-epochs", "0"], capsys)
    assert "epoch 1/1" in out and "test:" in out


def test_cli_fed_checkpoint_gate_and_resume(tmp_path, capsys):
    args = ["fed", "--path", str(tmp_path), "--host-devices", "8",
            "--synthetic-examples", "64", "--batch-size", "8",
            "--rounds", "2", "--num-clients", "8", "--local-epochs", "1",
            "--pretrain-epochs", "1", "--iid"]
    first = _run(args, capsys)
    assert "round, train_loss, train_acc, test_loss, test_acc" in first
    assert first.count("\n0, ") + first.count("\n1, ") == 2
    assert (tmp_path / "pretrained" / "cp.ckpt").exists()

    # Second run: pretrain gate skips training (fed_model.py:175, fixed
    # quirk Q5) and the round loop resumes past the completed rounds.
    second = _run(args + ["--rounds", "3"], capsys)
    assert "restored pretrained weights" in second
    assert "resuming federated training from round 2" in second
    assert "\n2, " in second and "\n1, " not in second

    # the append-only run.jsonl must hold exactly ONE record per round
    # across both runs (replayed rounds after an every-N checkpoint
    # resume print but do not re-log)
    import json

    recs = [json.loads(line) for line in
            (tmp_path / "logs" / "run.jsonl").read_text().splitlines()]
    rounds = [r["round"] for r in recs if r.get("event") == "round"]
    assert sorted(rounds) == [0, 1, 2]


def test_cli_fed_population_sync_and_resume(tmp_path, capsys):
    """Population mode through the product surface: virtual clients,
    cohort sampling, streamed waves, the population epilogue line, the
    fed_cohort jsonl events, and checkpoint/resume regenerating later
    cohorts in a REAL second run (the cross-process half of the
    sampler-determinism satellite)."""
    import json

    args = ["fed", "--population", "64", "--cohort", "8",
            "--cohort-wave", "4", "--rounds", "2", "--batch-size", "8",
            "--client-examples", "8", "--local-epochs", "1",
            "--model", "small_cnn", "--path", str(tmp_path)]
    first = _run(args, capsys)
    assert "round, train_loss, train_acc, test_loss, test_acc" in first
    assert ("population: 64 virtual clients, cohort 8 (uniform) in "
            "2 wave(s) of 4") in first
    second = _run(args + ["--rounds", "3"], capsys)  # last flag wins
    assert "resuming federated training from round 2" in second
    assert "\n2, " in second and "\n1, " not in second
    recs = [json.loads(line) for line in
            (tmp_path / "logs" / "run.jsonl").read_text().splitlines()]
    cohorts = [r for r in recs if r.get("event") == "fed_cohort"]
    assert [r["round"] for r in cohorts] == [0, 1, 2]
    assert all(r["mode"] == "sync" and r["population"] == 64
               and r["waves"] == 2 for r in cohorts)
    rounds = [r["round"] for r in recs if r.get("event") == "round"]
    assert sorted(rounds) == [0, 1, 2]       # resume never double-logs


def test_cli_fed_population_async(tmp_path, capsys):
    out = _run(["fed", "--population", "64", "--cohort", "8",
                "--rounds", "2", "--batch-size", "8",
                "--client-examples", "8", "--local-epochs", "1",
                "--model", "small_cnn", "--async-buffer", "4",
                "--staleness-decay", "0.8",
                "--faults", "crash:*:10%",
                "--path", str(tmp_path)], capsys)
    assert "async buffer: K=4, staleness decay 0.8" in out
    assert "buffered update(s)" in out
    import json

    recs = [json.loads(line) for line in
            (tmp_path / "logs" / "run.jsonl").read_text().splitlines()]
    cohorts = [r for r in recs if r.get("event") == "fed_cohort"]
    assert cohorts and all(r["mode"] == "async" and r["buffer"] == 4
                           for r in cohorts)
    assert all(len(r["staleness_hist"]) == 6 for r in cohorts)


def test_cli_fed_population_usage_errors(capsys):
    """ISSUE-13 satellite: every bad population knob dies as a TEACHING
    usage error, never a traceback — cohort > population, non-positive
    async buffer, staleness decay out of range, non-dividing wave, a
    bad population fault spec, and secure x async rejected at build."""
    base = ["fed", "--host-devices", "2", "--model", "small_cnn"]
    with pytest.raises(SystemExit, match="exceeds --population"):
        cli.main(base + ["--population", "10", "--cohort", "20"])
    with pytest.raises(SystemExit, match="--async-buffer must be"):
        cli.main(base + ["--population", "10", "--cohort", "5",
                         "--async-buffer", "-2"])
    with pytest.raises(SystemExit, match="--staleness-decay must be"):
        cli.main(base + ["--population", "10", "--cohort", "5",
                         "--staleness-decay", "1.5"])
    with pytest.raises(SystemExit, match="--client-examples must be"):
        cli.main(base + ["--population", "10", "--cohort", "5",
                         "--client-examples", "0"])
    with pytest.raises(SystemExit, match="--cohort-wave only applies"):
        cli.main(base + ["--population", "10", "--cohort", "4",
                         "--cohort-wave", "2", "--async-buffer", "2"])
    with pytest.raises(SystemExit, match="--fault-delay-ms must be"):
        cli.main(base + ["--population", "10", "--cohort", "4",
                         "--fault-delay-ms", "-5"])
    with pytest.raises(SystemExit, match="must divide the cohort"):
        cli.main(base + ["--population", "10", "--cohort", "6",
                         "--cohort-wave", "4"])
    with pytest.raises(SystemExit) as ei:
        cli.main(base + ["--population", "10", "--cohort", "4",
                         "--faults", "meteor:1:5%"])
    assert "grammar" in str(ei.value)        # the teaching message
    with pytest.raises(SystemExit, match="secure aggregation"):
        cli.main(["secure-fed", "--host-devices", "2",
                  "--async-buffer", "4"])


def test_cli_secure_fed_masked(capsys):
    out = _run(["secure-fed", "--host-devices", "8",
                "--synthetic-examples", "256", "--batch-size", "8",
                "--rounds", "2", "--num-clients", "8",
                "--local-epochs", "1", "--percent", "0.5"], capsys)
    assert "round 0:" in out and "round 1:" in out
    assert "auroc=" in out                   # C16 metric on the eval path


def test_cli_secure_fed_paillier(capsys):
    out = _run(["secure-fed", "--host-devices", "8",
                "--synthetic-examples", "128", "--batch-size", "8",
                "--rounds", "1", "--num-clients", "2",
                "--local-epochs", "1", "--percent", "0.25", "--paillier"],
               capsys)
    assert "round 0:" in out
    assert "Client 0 training took" in out   # C17 per-client Timers


def test_cli_serve_synthetic_trace(tmp_path, capsys):
    """The continuous-batching engine from the product surface: a
    synthetic Poisson trace through `serve` on the virtual pod — the
    summary line, the request accounting, and the jsonl artifact. Engine
    semantics (parity, recycling, backpressure) are owned by
    tests/test_serve.py; this drives the CLI wiring end to end."""
    import json

    out = _run(["serve", "--host-devices", "8", "--requests", "6",
                "--slots", "2", "--window", "4", "--t-max", "32",
                "--vocab", "11", "--embed-dim", "16", "--num-heads", "2",
                "--mlp-dim", "32", "--num-blocks", "1",
                "--path", str(tmp_path)], capsys)
    assert "serving 6 requests on 2 slots" in out
    assert "served: ok=6 timeout=0 rejected=0" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith("serve summary:")][0]
    summary = json.loads(line.split("serve summary:", 1)[1])
    assert summary["serve_requests"] == 6
    assert summary["serve_tokens_per_sec"] > 0
    log = tmp_path / "logs" / "serve.jsonl"
    assert log.exists()
    events = {json.loads(l)["event"] for l in
              log.read_text().splitlines()}
    assert {"serve_submit", "serve_finish", "serve_summary"} <= events
    # a replayed JSONL trace drives the same path (load_trace format)
    from idc_models_tpu.serve import Request, save_trace

    trace = [(0.0, Request(id="t0", prompt=(1, 2, 3), max_new_tokens=4)),
             (0.01, Request(id="t1", prompt=(4, 5), max_new_tokens=6))]
    tr = save_trace(tmp_path / "trace.jsonl", trace)
    out = _run(["serve", "--host-devices", "8", "--trace", tr,
                "--slots", "2", "--window", "4", "--t-max", "32",
                "--vocab", "11", "--embed-dim", "16", "--num-heads", "2",
                "--mlp-dim", "32", "--num-blocks", "1"], capsys)
    assert "serving 2 requests" in out and "served: ok=2" in out


def test_cli_serve_drafter_learned_and_usage_gates(tmp_path, capsys):
    """`serve --drafter chained --draft-ckpt DIR` from the product
    surface: an (untrained) distilled checkpoint loads, the chained
    drafter serves the trace, and the speculative epilogue names the
    drafter and its propose accounting. Usage misfits — a learned
    drafter without its checkpoint, a drafter outside the speculative
    loop, an orphaned checkpoint, a vocab mismatch — die as teaching
    errors before any device work."""
    import jax

    from idc_models_tpu.models import draft_lm as dlm

    cfg = dlm.draft_config(11, 32)
    dparams = dlm.draft_lm(cfg).init(jax.random.key(5)).params
    ckpt = str(tmp_path / "draft_ckpt")
    dlm.save_draft_lm(ckpt, jax.device_get(dparams),
                      config=cfg).wait()
    dims = ["--host-devices", "8", "--requests", "4", "--slots", "2",
            "--window", "4", "--t-max", "32", "--vocab", "11",
            "--embed-dim", "16", "--num-heads", "2", "--mlp-dim",
            "32", "--num-blocks", "1"]
    out = _run(["serve", *dims, "--spec-decode", "--draft-k", "3",
                "--drafter", "chained", "--draft-ckpt", ckpt], capsys)
    assert "served: ok=4" in out
    assert "speculative (chained):" in out
    assert "propose_s=" in out
    # usage gates: each one a SystemExit that says what to change
    with pytest.raises(SystemExit):
        cli.main(["serve", *dims, "--spec-decode", "--draft-k", "3",
                  "--drafter", "learned"])        # no --draft-ckpt
    with pytest.raises(SystemExit):
        cli.main(["serve", *dims, "--drafter", "learned",
                  "--draft-ckpt", ckpt])          # no --spec-decode
    with pytest.raises(SystemExit):
        cli.main(["serve", *dims, "--spec-decode", "--draft-k", "3",
                  "--draft-ckpt", ckpt])          # ckpt with ngram
    # tokenizer mismatch: vocab-11 checkpoint against a --vocab 13
    # target dies naming both vocabs
    dims13 = [a if a != "11" else "13" for a in dims]
    with pytest.raises(SystemExit) as e:
        cli.main(["serve", *dims13, "--spec-decode", "--draft-k", "3",
                  "--drafter", "learned", "--draft-ckpt", ckpt])
    assert "vocab" in str(e.value)


def test_cli_serve_chunked_prefix_int8(tmp_path, capsys):
    """The PR-4 admission knobs from the product surface: chunked
    prefill + prefix cache + int8 KV together, the TTFT decomposition
    epilogue, and the serve_prefix_* summary fields. Correctness of the
    underlying machinery is owned by tests/test_serve.py and
    tests/test_prefix_cache.py."""
    import json

    out = _run(["serve", "--host-devices", "8", "--requests", "6",
                "--slots", "2", "--window", "4", "--t-max", "32",
                "--vocab", "11", "--embed-dim", "16", "--num-heads", "2",
                "--mlp-dim", "32", "--num-blocks", "1",
                "--prefill-chunk", "8", "--prefix-cache-mb", "16",
                "--kv-dtype", "int8", "--path", str(tmp_path)], capsys)
    assert "served: ok=6" in out
    assert "ttft p95" in out and "queue-wait" in out
    assert "prefix cache: hit rate" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith("serve summary:")][0]
    summary = json.loads(line.split("serve summary:", 1)[1])
    assert "serve_prefix_hit_rate" in summary
    assert summary["serve_queue_wait_ms_p95"] is not None
    assert summary["serve_prefill_ms_p95"] is not None
    # invalid knob combinations die with a usage error, not a traceback
    with pytest.raises(SystemExit):
        cli.main(["serve", "--host-devices", "8", "--t-max", "32",
                  "--prefill-chunk", "5"])
    with pytest.raises(SystemExit):
        cli.main(["serve", "--host-devices", "8", "--t-max", "32",
                  "--prefix-cache-mb", "4"])


def test_cli_serve_paged_kv(tmp_path, capsys):
    """ISSUE-11 paged KV from the product surface: the --kv-page-size/
    --kv-pages knobs, the page-occupancy epilogue, the serve_kv_*
    summary fields, and the usage-error gates. Engine semantics are
    owned by tests/test_paged_kv.py."""
    import json

    out = _run(["serve", "--host-devices", "8", "--requests", "6",
                "--slots", "2", "--window", "4", "--t-max", "32",
                "--vocab", "11", "--embed-dim", "16", "--num-heads", "2",
                "--mlp-dim", "32", "--num-blocks", "1",
                "--prefill-chunk", "8", "--kv-page-size", "4",
                "--kv-pages", "16", "--prefix-cache-mb", "4",
                "--path", str(tmp_path)], capsys)
    assert "served: ok=6" in out
    assert "paged kv:" in out and "pages peak" in out
    assert "tokens/HBM-byte" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith("serve summary:")][0]
    summary = json.loads(line.split("serve summary:", 1)[1])
    assert summary["serve_kv_pages_total"] == 16
    assert 0 < summary["serve_kv_pages_used_peak"] <= 16
    assert summary["serve_kv_tokens_per_hbm_byte"] > 0
    # usage-error gates: each bad combination dies cleanly
    for args in (["--kv-page-size", "4"],                  # no pages
                 ["--kv-pages", "16"],                     # no size
                 ["--kv-page-size", "4", "--kv-pages", "16"],  # no chunk
                 ["--prefill-chunk", "8", "--kv-page-size", "5",
                  "--kv-pages", "16"],                     # 5 !| 32
                 ["--prefill-chunk", "8", "--kv-page-size", "16",
                  "--kv-pages", "16"],                     # 16 !| 8
                 ["--prefill-chunk", "8", "--kv-page-size", "4",
                  "--kv-pages", "4"],                      # < t_max
                 ["--kv-decode-reserve", "4"]):            # not paged
        with pytest.raises(SystemExit):
            cli.main(["serve", "--host-devices", "8", "--t-max", "32"]
                     + args)


def test_cli_serve_trace_out_and_stats(tmp_path, capsys):
    """ISSUE-5/7 observability from the product surface, one chunked
    serve run covering the whole stack: --trace-out produces a
    Perfetto-loadable Chrome trace whose admission -> prefill-chunk
    and tick -> decode-window spans nest correctly AND whose
    request-lifecycle chain (serve.request > serve.queued /
    serve.first_token, rid-stamped prefill chunks and windows)
    reconstructs every finished rid's timeline; --metrics-port serves
    a live /metrics + /healthz a scraper hits DURING the run; the SLO
    flags stay silent on this clean run; and the offline `stats`
    subcommand rolls the run's jsonl up, including the per-request
    timeline (--request RID)."""
    import json
    import socket
    import threading
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    scraped = {}

    def scrape():
        # poll until the exporter binds (it arms before the engine's
        # warmup compiles, so the window is wide), then scrape both
        # endpoints while the run is LIVE
        import time as _time

        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=2) as r:
                    scraped["metrics"] = r.read().decode()
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz",
                        timeout=2) as r:
                    scraped["healthz"] = r.read().decode()
                return
            except OSError:
                _time.sleep(0.02)

    scraper = threading.Thread(target=scrape, daemon=True)
    scraper.start()
    trace_path = tmp_path / "trace.json"
    # --realtime at ~2 req/s stretches the run over a couple of wall
    # seconds even with every program warm in the jit cache, so the
    # scraper thread deterministically lands inside the live window
    out = _run(["serve", "--host-devices", "8", "--requests", "5",
                "--slots", "2", "--window", "4", "--t-max", "32",
                "--vocab", "11", "--embed-dim", "16", "--num-heads", "2",
                "--mlp-dim", "32", "--num-blocks", "1",
                "--prefill-chunk", "8", "--path", str(tmp_path),
                "--trace-out", str(trace_path),
                "--rate", "2.0", "--realtime",
                "--metrics-port", str(port),
                "--slo-ttft-p95-ms", "60000",
                "--slo-error-rate", "0.5"], capsys)
    scraper.join(timeout=10)
    assert "served: ok=5" in out
    # the live exposition was really scraped mid-run, in the exact
    # Prometheus text shape, and /healthz parsed
    assert f"metrics: http://127.0.0.1:{port}/metrics" in out
    assert "metrics" in scraped, "scraper never reached /metrics"
    assert "# TYPE serve_requests_submitted_total counter" \
        in scraped["metrics"]
    health = json.loads(scraped["healthz"])
    assert health["status"] == "ok"
    # the clean run trips no SLO alert (the faulty side is gated in
    # tests/test_slo.py)
    assert "slo: 0 alert(s)" in out
    doc = json.loads(trace_path.read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"serve.tick", "serve.admit", "serve.collect",
            "serve.window", "serve.prefill_chunk",
            "serve.refill", "serve.turnaround", "serve.start_prefill",
            "serve.insert", "Serving trace"} <= names
    by_id = {e["args"]["span_id"]: e for e in spans}
    # Perfetto's expectations: numeric microsecond ts/dur, and children
    # contained in their parent's interval
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0
        parent = e["args"]["parent_id"]
        if parent is not None:
            p = by_id[parent]
            assert p["ts"] <= e["ts"] + 1e-3
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
    chunk_parents = {by_id[e["args"]["parent_id"]]["name"]
                     for e in spans
                     if e["name"] == "serve.prefill_chunk"}
    assert chunk_parents == {"serve.admit"}
    for name in ("serve.window", "serve.refill", "serve.turnaround"):
        assert {by_id[e["args"]["parent_id"]]["name"]
                for e in spans if e["name"] == name} == {"serve.tick"}
    assert {by_id[e["args"]["parent_id"]]["name"] for e in spans
            if e["name"] == "serve.start_prefill"} <= {"serve.admit",
                                                       "serve.refill"}
    assert all(e["args"]["rid"] for e in spans
               if e["name"] in ("serve.insert", "serve.start_prefill"))
    # every thread that opened a span is named in the export
    named = {e["tid"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {e["tid"] for e in spans} <= named

    # ISSUE-7 acceptance: for EVERY finished rid, the submit->finish
    # chain reconstructs from the exported file with correct nesting
    finished = {json.loads(l)["id"] for l in
                (tmp_path / "logs" / "serve.jsonl").read_text()
                .splitlines()
                if json.loads(l).get("event") == "serve_finish"}
    assert len(finished) == 5
    req_by_rid = {e["args"]["rid"]: e for e in spans
                  if e["name"] == "serve.request"}
    for rid in finished:
        req = req_by_rid[rid]
        assert req["args"]["status"] == "ok"
        assert req["args"]["parent_id"] is None
        mine = [e for e in spans if e["args"].get("rid") == rid]
        names = {e["name"] for e in mine}
        assert {"serve.request", "serve.queued", "serve.first_token",
                "serve.prefill_chunk"} <= names, (rid, names)
        for e in mine:
            # the whole chain shares the request's trace_id (where
            # stamped) and sits inside the request span's interval
            if "trace_id" in e["args"]:
                assert e["args"]["trace_id"] == req["args"]["trace_id"]
            assert req["ts"] <= e["ts"] + 1e-3
            assert (e["ts"] + e["dur"]
                    <= req["ts"] + req["dur"] + 1e-3)
            if e["name"] in ("serve.queued", "serve.first_token"):
                assert (e["args"]["parent_id"]
                        == req["args"]["span_id"])
        # the decode windows that carried this rid name it
        assert any(rid in (e["args"].get("rids") or [])
                   for e in spans if e["name"] == "serve.window")

    # offline stats over the run's serve.jsonl
    out = _run(["stats", str(tmp_path / "logs" / "serve.jsonl")], capsys)
    assert "serve_submit" in out and "serve_finish" in out
    assert "p95=" in out and "mean=" in out
    assert "last metrics snapshot:" in out
    assert "serve_requests_total" in out
    assert "requests: 5 with per-request timelines" in out
    out = _run(["stats", str(tmp_path / "logs" / "serve.jsonl"),
                "--json"], capsys)
    summary = json.loads(out)
    assert summary["events"]["serve_finish"]["count"] == 5
    # the per-request timeline rides the --json output too
    rid = sorted(summary["requests"])[0]
    whats = [e["what"] for e in summary["requests"][rid]]
    assert whats[0] == "serve_submit" and "serve_finish" in whats
    # ...and --request renders ONE request's timeline
    out = _run(["stats", str(tmp_path / "logs" / "serve.jsonl"),
                "--request", rid], capsys)
    assert f"request {rid}" in out
    assert "serve_submit" in out and "serve_finish" in out
    # usage error, not a traceback, for a missing file / unknown rid /
    # bad SLO or port flags
    with pytest.raises(SystemExit):
        cli.main(["stats", str(tmp_path / "nope.jsonl")])
    with pytest.raises(SystemExit):
        cli.main(["stats", str(tmp_path / "logs" / "serve.jsonl"),
                  "--request", "no-such-rid"])
    with pytest.raises(SystemExit):
        cli.main(["serve", "--host-devices", "8",
                  "--slo-error-rate", "2.0"])
    with pytest.raises(SystemExit):
        cli.main(["serve", "--host-devices", "8",
                  "--metrics-port", "-1"])


def test_cli_stats_covers_train_and_fed_jsonl(tmp_path, capsys):
    """ISSUE-7 satellite: the `stats` verb end-to-end over a train/fed-
    SHAPED run.jsonl (epoch records + the driver's real round/
    round_health stream + a metrics snapshot) — the serve path is
    covered by test_cli_serve_trace_out_and_stats; this closes the gap
    for the other two run-log families."""
    import json

    import jax.numpy as jnp
    import numpy as np

    from idc_models_tpu.federated.driver import DriverConfig, run_rounds
    from idc_models_tpu.federated.fedavg import ServerState
    from idc_models_tpu.observe import REGISTRY, JsonlLogger

    def round_fn(server, images, labels, weights, rng):
        new = ServerState(round=server.round + 1, params=server.params,
                          model_state=server.model_state)
        return new, {"loss": jnp.float32(0.4),
                     "accuracy": jnp.float32(0.9),
                     "clients_dropped": jnp.int32(0)}

    server = ServerState(round=jnp.zeros((), jnp.int32),
                         params={"w": jnp.ones((2,))}, model_state={})
    log = tmp_path / "run.jsonl"
    with JsonlLogger(log) as logger:
        for e in range(2):
            logger.log(event="epoch", epoch=e, loss=1.0 - 0.3 * e,
                       accuracy=0.5 + 0.2 * e, val_loss=1.0,
                       val_accuracy=0.5)
        run_rounds(round_fn, server, None, None,
                   np.ones(3, np.float32),
                   config=DriverConfig(rounds=3), logger=logger)
        REGISTRY.log_snapshot(logger)

    out = _run(["stats", str(log)], capsys)
    assert "epoch" in out and "round_health" in out
    assert "fed_round_attempts_total" in out    # the snapshot rendered
    out = _run(["stats", str(log), "--json"], capsys)
    s = json.loads(out)
    assert s["events"]["epoch"]["count"] == 2
    assert s["events"]["round"]["count"] == 3
    assert s["events"]["round_health"]["fields"]["seconds"]["count"] == 3
    assert s["events"]["epoch"]["fields"]["loss"]["min"] == 0.7
    assert s["requests"] == {}      # nothing serve-shaped in this log


def test_cli_profile_train_and_stats(tmp_path, capsys):
    """ISSUE-9 acceptance from the product surface: `profile` over a
    train step emits a program cost account with a roofline verdict
    (declared roof — CPU is not in the backend table), a device-vs-
    host step-time split whose fractions sum to ~1, and frozen-schema
    profile_program/profile_step jsonl the `stats` verb renders; the
    compile-churn watchdog stays SILENT on the clean run and fires on
    the injected shape-varying recompile loop (--churn-drill).
    Attribution/verdict math is owned by tests/test_profile.py; this
    drives the CLI wiring end to end."""
    import json

    out = _run(["profile", "--model", "small", "--host-devices", "8",
                "--steps", "3", "--peak-tflops", "1.0",
                "--peak-gbps", "50.0", "--path", str(tmp_path)], capsys)
    assert "profile: train.step (small_cnn" in out
    assert "programs (performance attribution):" in out
    assert "train.step" in out
    assert "-bound at" in out            # a real verdict, not unknown
    assert "step-time attribution" in out and "profile.step" in out
    assert "churn: none" in out          # clean warm run stays silent
    jsonl = tmp_path / "logs" / "profile.jsonl"
    recs = [json.loads(l) for l in jsonl.read_text().splitlines()]
    progs = [r for r in recs if r["event"] == "profile_program"]
    steps = [r for r in recs if r["event"] == "profile_step"]
    assert progs[0]["program"] == "train.step"
    assert progs[0]["verdict"] in ("compute-bound", "bandwidth-bound")
    assert progs[0]["flops"] > 0 and progs[0]["mfu"] is not None
    fr = [r for r in steps if r["loop"] == "profile.step"][0]
    assert fr["steps"] == 3
    assert (fr["device_busy_fraction"] + fr["host_gap_fraction"]
            == pytest.approx(1.0))
    assert any(r["event"] == "metrics_snapshot" for r in recs)

    # the injected recompile loop trips the watchdog (named program
    # fed a different shape every call past --compile-limit)
    out = _run(["profile", "--model", "small", "--host-devices", "8",
                "--steps", "2", "--compile-limit", "3",
                "--churn-drill"], capsys)
    assert "CHURN flagged: churn.drill" in out


@pytest.mark.slow
def test_cli_profile_mobile_fused(tmp_path, capsys):
    """ISSUE-16 satellite: `profile --model mobile --depthwise-impl
    fused` still prints a REAL roofline verdict — XLA's cost analysis
    is blind inside the Pallas calls, so the CLI merges the analytic
    kernel cost (fused_conv.depthwise_chain_cost over
    mobilenet.fused_call_shapes) into the program account before
    registering it — and the clean fused run stays churn-silent (the
    lru_cached kernel closure must not recompile per call). Marked
    slow: compiling the ~17 distinct interpret-mode Pallas configs
    (fwd + custom_vjp bwd each) costs minutes on CPU regardless of
    batch/step count; the fast fused-parity subset lives in
    test_fused_conv.py."""
    import json

    out = _run(["profile", "--model", "mobile", "--depthwise-impl",
                "fused", "--host-devices", "2", "--steps", "2",
                "--peak-tflops", "1.0", "--peak-gbps", "50.0",
                "--path", str(tmp_path)], capsys)
    assert "profile: train.step (mobilenet_v2" in out
    assert "-bound at" in out            # a real verdict, not unknown
    assert "churn: none" in out          # zero compile-churn warnings
    jsonl = tmp_path / "logs" / "profile.jsonl"
    recs = [json.loads(l) for l in jsonl.read_text().splitlines()]
    progs = [r for r in recs if r["event"] == "profile_program"]
    prog = progs[0]
    assert prog["program"] == "train.step"
    assert prog["verdict"] in ("compute-bound", "bandwidth-bound")
    # the analytic merge actually landed: the fused step must account
    # at least the kernel chain's own bytes (XLA alone reports almost
    # nothing for the custom calls)
    from idc_models_tpu.models import mobilenet
    from idc_models_tpu.ops import fused_conv

    k_flops, k_bytes = fused_conv.depthwise_chain_cost(
        mobilenet.fused_call_shapes(2 * 8, 50))
    assert prog["flops"] >= k_flops
    assert prog["bytes_accessed"] >= k_bytes

    # stats renders the profile events + the self-time table
    out = _run(["stats", str(jsonl)], capsys)
    assert "programs (performance attribution):" in out
    assert "step-time attribution:" in out
    out = _run(["stats", str(jsonl), "--json"], capsys)
    s = json.loads(out)
    assert s["events"]["profile_program"]["count"] == len(progs)
    assert s["programs"][0]["program"] == "train.step"

    # usage errors die cleanly: half a roofline, bad steps/limit/top
    with pytest.raises(SystemExit):
        cli.main(["profile", "--model", "small", "--host-devices", "8",
                  "--peak-tflops", "1.0"])
    with pytest.raises(SystemExit):
        cli.main(["profile", "--model", "small", "--host-devices", "8",
                  "--steps", "0"])
    with pytest.raises(SystemExit):
        cli.main(["profile", "--model", "small", "--host-devices", "8",
                  "--compile-limit", "0"])
    with pytest.raises(SystemExit):
        cli.main(["stats", str(jsonl), "--top", "0"])


def test_cli_profile_serve(tmp_path, capsys):
    """The `profile` verb's serve mode: engine program accounts
    (window + prefill) and the serve.tick device-vs-host split from a
    saturated decode loop, through the CLI."""
    import json

    out = _run(["profile", "--model", "serve", "--host-devices", "8",
                "--steps", "5", "--path", str(tmp_path)], capsys)
    assert "profile: serve decode loop" in out
    assert "serve.window" in out and "serve.prefill" in out
    assert "serve.propose" in out        # drafter roofline rides along
    assert "serve.tick" in out
    recs = [json.loads(l) for l in
            (tmp_path / "logs" / "profile.jsonl").read_text()
            .splitlines()]
    progs = {r["program"] for r in recs
             if r["event"] == "profile_program"}
    assert {"serve.window", "serve.prefill"} <= progs
    steps = [r for r in recs if r["event"] == "profile_step"]
    tick = [r for r in steps if r["loop"] == "serve.tick"][0]
    assert tick["steps"] >= 1
    assert (tick["device_busy_fraction"] + tick["host_gap_fraction"]
            == pytest.approx(1.0))


def test_cli_lm(tmp_path, capsys):
    """The causal-LM workload from the product surface: the CLI wiring
    only (mesh line, metric line, generate line, jsonl artifact, ring
    rejection) — convergence + pattern-match is owned by
    tests/test_lm.py::test_lm_learns_and_generates, not re-proven
    here."""
    out = _run(["lm", "--host-devices", "8", "--steps", "20",
                "--vocab", "11", "--seq-len", "32", "--embed-dim", "16",
                "--num-heads", "2", "--mlp-dim", "32", "--num-blocks",
                "1", "--batch-size", "16", "--generate", "6",
                "--path", str(tmp_path)], capsys)
    assert "(data=2, seq=4)" in out
    assert "next-token accuracy" in out
    assert "generate:" in out
    assert (tmp_path / "logs" / "run.jsonl").exists()
    with pytest.raises(SystemExit):
        cli.main(["lm", "--host-devices", "8", "--seq-len", "30",
                  "--layout", "zigzag"])


def test_cli_serve_faulted_lifecycle_and_journal_recovery(tmp_path,
                                                          capsys):
    """ISSUE-8 acceptance from the product surface, two drills:

    1. LIFECYCLE — a traced serve run with an injected nan_logits
       fault and retries armed: one rid grep of the exported trace
       reconstructs submit -> fault -> quarantine -> retry -> finish
       under the request's shared trace_id, the recovered request
       finishes ok, and the resilience epilogue reports the counts.
    2. CRASH RECOVERY — an injected mid-run engine crash with
       --journal armed kills the run honestly (salvaged results +
       recovery hint); rerunning with the same journal re-admits the
       in-flight requests and serves them.

    Recovery bit-parity is owned by tests/test_serve_resilience.py;
    this drives the CLI wiring end to end."""
    import json

    from idc_models_tpu.serve import Request, save_trace

    model = ["--host-devices", "8", "--slots", "2", "--window", "4",
             "--t-max", "32", "--vocab", "11", "--embed-dim", "16",
             "--num-heads", "2", "--mlp-dim", "32", "--num-blocks", "1"]
    trace = [(0.0, Request(id=f"f{i}", prompt=(1 + i, 2, 3),
                           max_new_tokens=12))
             for i in range(3)]
    tr = save_trace(tmp_path / "trace.jsonl", trace)
    trace_json = tmp_path / "faulted.json"
    out = _run(["serve", *model, "--trace", tr,
                "--serve-faults", "nan_logits:1:0",
                "--max-retries", "2", "--retry-backoff-ms", "0",
                "--trace-out", str(trace_json),
                "--path", str(tmp_path)], capsys)
    assert "served: ok=3" in out
    assert "resilience: injected=1 slot_faults=1 retries=1" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith("serve summary:")][0]
    summary = json.loads(line.split("serve summary:", 1)[1])
    assert summary["serve_slot_faults"] == 1
    assert summary["serve_retries"] == 1

    # ONE rid grep over the exported trace tells the whole story
    doc = json.loads(trace_json.read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    fault = next(e for e in spans if e["name"] == "serve.slot_fault")
    rid = fault["args"]["rid"]
    assert fault["args"]["kind"] == "nonfinite_logits"
    mine = [e for e in spans if e["args"].get("rid") == rid]
    names = {e["name"] for e in mine}
    assert {"serve.request", "serve.queued", "serve.slot_fault",
            "serve.retry", "serve.first_token"} <= names, names
    req = next(e for e in mine if e["name"] == "serve.request")
    assert req["args"]["status"] == "ok"
    tids = {e["args"]["trace_id"] for e in mine
            if "trace_id" in e["args"]}
    assert tids == {req["args"]["trace_id"]}
    retry = next(e for e in mine if e["name"] == "serve.retry")
    assert retry["args"]["attempt"] == 2
    # the fault/retry markers hang off the request's lifecycle span
    assert fault["args"]["parent_id"] == req["args"]["span_id"]
    # ...and the run's jsonl carries the same chain as events
    events = [json.loads(l) for l in
              (tmp_path / "logs" / "serve.jsonl").read_text()
              .splitlines()]
    chain = [r["event"] for r in events if r.get("id") == rid]
    for ev in ("serve_submit", "serve_slot_fault", "serve_retry",
               "serve_finish"):
        assert ev in chain, (ev, chain)
    assert chain.index("serve_slot_fault") \
        < chain.index("serve_retry") < chain.index("serve_finish")

    # -- drill 2: crash + journal recovery ------------------------------
    wal = tmp_path / "journal.jsonl"
    trace2 = [(0.0, Request(id=f"j{i}", prompt=(2 + i, 4),
                            max_new_tokens=16))
              for i in range(3)]
    tr2 = save_trace(tmp_path / "trace2.jsonl", trace2)
    out = _run(["serve", *model, "--trace", tr2,
                "--serve-faults", "crash:2",
                "--journal", str(wal)], capsys)
    assert "engine crashed mid-run (injected)" in out
    assert f"rerun with --journal {wal}" in out
    out = _run(["serve", *model, "--trace",
                save_trace(tmp_path / "empty.jsonl", []),
                "--journal", str(wal)], capsys)
    assert "journal: re-admitted 3 in-flight request(s)" in out
    assert "served: ok=3" in out
    # a second recovery finds a clean WAL
    from idc_models_tpu.serve import pending_requests

    assert pending_requests(wal) == []
    # usage errors die cleanly: bad fault spec (teaching message), bad
    # retry knobs
    with pytest.raises(SystemExit):
        cli.main(["serve", *model, "--serve-faults", "meteor:1"])
    with pytest.raises(SystemExit):
        cli.main(["serve", *model, "--max-retries", "-1"])


def test_cli_serve_tenants_e2e(tmp_path, capsys):
    """ISSUE-14: the multi-tenant serve verb end to end — round-robin
    tenant tagging, per-tenant quota + TTFT SLO wiring, per-tenant
    epilogue lines, the serve_tenants summary rollup, and the tenant
    events in the run jsonl."""
    import json

    out = _run([
        "serve", "--path", str(tmp_path), "--requests", "10",
        "--t-max", "32", "--vocab", "12", "--embed-dim", "16",
        "--num-heads", "2", "--mlp-dim", "32", "--num-blocks", "1",
        "--slots", "3", "--window", "4",
        "--tenants", "acme,globex",
        "--tenant-quota", "acme=2:6:-",
        "--tenant-slo-ttft-ms", "acme=200"], capsys)
    assert "tenant acme:" in out and "tenant globex:" in out
    assert "brownout_max_stage=" in out and "slo_alerts=" in out
    summary = json.loads(
        [ln for ln in out.splitlines()
         if ln.startswith("serve summary:")][0].split(":", 1)[1])
    tenants = summary["serve_tenants"]
    assert set(tenants) == {"acme", "globex"}
    assert tenants["acme"]["requests"] == 5
    assert tenants["globex"]["requests"] == 5
    recs = [json.loads(ln) for ln in
            open(tmp_path / "logs" / "serve.jsonl")]
    tenant_fin = [r for r in recs
                  if r.get("event") == "serve_tenant_finish"]
    assert len(tenant_fin) == 10
    assert {r["tenant"] for r in tenant_fin} == {"acme", "globex"}


def test_cli_serve_tenant_usage_errors(capsys):
    """ISSUE-14: every bad tenancy knob dies as a TEACHING usage error
    that states the grammar — never a traceback."""
    base = ["serve", "--requests", "1", "--t-max", "32"]
    with pytest.raises(SystemExit, match="--tenant-quota needs "
                                         "--tenants"):
        cli.main(base + ["--tenant-quota", "a=2"])
    with pytest.raises(SystemExit, match="--tenant-slo-ttft-ms needs"):
        cli.main(base + ["--tenant-slo-ttft-ms", "250"])
    with pytest.raises(SystemExit, match="duplicate tenant"):
        cli.main(base + ["--tenants", "a,a"])
    with pytest.raises(SystemExit, match="empty tenant name"):
        cli.main(base + ["--tenants", "a,,b"])
    with pytest.raises(SystemExit, match="unknown tenant 'ghost'"):
        cli.main(base + ["--tenants", "a", "--tenant-quota",
                         "ghost=2"])
    with pytest.raises(SystemExit, match="grammar"):
        cli.main(base + ["--tenants", "a", "--tenant-quota", "a=x"])
    with pytest.raises(SystemExit, match="admit nothing ever"):
        cli.main(base + ["--tenants", "a", "--tenant-quota", "a=0"])
    with pytest.raises(SystemExit, match="already has a quota"):
        cli.main(base + ["--tenants", "a", "--tenant-quota", "a=2",
                         "--tenant-quota", "a=3"])
    with pytest.raises(SystemExit, match="must be > 0"):
        cli.main(base + ["--tenants", "a", "--tenant-slo-ttft-ms",
                         "a=0"])
    with pytest.raises(SystemExit, match="already has a TTFT SLO"):
        cli.main(base + ["--tenants", "a", "--tenant-slo-ttft-ms",
                         "150", "--tenant-slo-ttft-ms", "a=250"])
    with pytest.raises(SystemExit, match="is not a number"):
        cli.main(base + ["--tenants", "a", "--tenant-slo-ttft-ms",
                         "a=fast"])


def test_cli_profile_lm_sharded(tmp_path, capsys):
    """ISSUE-15 acceptance from the product surface: `profile --model
    lm --fsdp 2` accounts the rule-sharded LM train step and prints
    the per-device peak-HBM epilogue line; the replicated run prints
    the same line so the two figures are comparable from the command
    line (the gate itself — sharded < replicated — is asserted in
    tests/test_partition.py)."""
    import json
    import re as _re

    def peak_of(out):
        m = _re.search(r"per-device peak HBM: ([0-9.]+) MiB over "
                       r"(\d+) device", out)
        assert m, out
        return float(m.group(1)), int(m.group(2))

    out = _run(["profile", "--model", "lm", "--host-devices", "8",
                "--steps", "2", "--path", str(tmp_path)], capsys)
    assert "profile: train.step (lm" in out and "replicated" in out
    rep_mib, n = peak_of(out)
    assert n == 1

    out = _run(["profile", "--model", "lm", "--host-devices", "8",
                "--steps", "2", "--fsdp", "2", "--tp", "2"], capsys)
    assert "fsdp=2, tp=2 (rule set 'lm'" in out
    sh_mib, n = peak_of(out)
    assert n == 4
    assert sh_mib < rep_mib          # the CLI surfaces the capacity win
    jsonl = tmp_path / "logs" / "profile.jsonl"
    recs = [json.loads(l) for l in jsonl.read_text().splitlines()]
    prog = [r for r in recs if r["event"] == "profile_program"][0]
    assert prog["program"] == "train.step"
    assert prog["peak_hbm_bytes"] == pytest.approx(rep_mib * 2**20,
                                                   rel=1e-3)

    # usage gates: the flags teach
    with pytest.raises(SystemExit, match="--model lm"):
        cli.main(["profile", "--model", "small", "--host-devices", "8",
                  "--fsdp", "2"])
    with pytest.raises(SystemExit, match="devices"):
        cli.main(["profile", "--model", "lm", "--host-devices", "8",
                  "--fsdp", "16"])
    with pytest.raises(SystemExit, match="must be >= 0"):
        cli.main(["profile", "--model", "lm", "--host-devices", "8",
                  "--fsdp", "-1"])
    with pytest.raises(SystemExit, match="divide by --fsdp"):
        cli.main(["profile", "--model", "lm", "--host-devices", "8",
                  "--fsdp", "2", "--batch-size", "3"])


def test_cli_lm_fsdp_tp(capsys):
    """The lm train verb on a rule-sharded ('data', 'model', 'seq')
    mesh: trains, reports the sharded mesh line, and the compiled
    serving path still generates — plus the usage gates."""
    out = _run(["lm", "--host-devices", "8", "--fsdp", "2", "--tp",
                "2", "--steps", "30", "--seq-len", "32",
                "--generate", "4"], capsys)
    assert "fsdp=2, tp=2" in out
    assert "sharded by rule set 'lm'" in out
    assert "generate:" in out
    with pytest.raises(SystemExit, match="devices"):
        cli.main(["lm", "--host-devices", "8", "--fsdp", "8", "--tp",
                  "2", "--steps", "1"])
    with pytest.raises(SystemExit, match="divide by --fsdp"):
        cli.main(["lm", "--host-devices", "8", "--fsdp", "2",
                  "--batch-size", "5", "--steps", "1"])


def test_cli_serve_tp(tmp_path, capsys):
    """The serve verb with --tp 2: params shard over 'model' (rule set
    'lm'), KV keeps the seq ring, the trace completes — and --fsdp on
    serve teaches toward --tp instead of shrugging."""
    out = _run(["serve", "--host-devices", "8", "--tp", "2",
                "--requests", "4", "--slots", "2", "--window", "4",
                "--path", str(tmp_path)], capsys)
    assert "serving mesh: tp=2 x seq=1" in out
    assert "params sharded by rule set 'lm'" in out
    assert "served: ok=4" in out
    with pytest.raises(SystemExit, match="use --tp"):
        cli.main(["serve", "--host-devices", "8", "--fsdp", "2",
                  "--requests", "1"])
    with pytest.raises(SystemExit, match="needs"):
        cli.main(["serve", "--host-devices", "8", "--tp", "16",
                  "--requests", "1"])

def test_cli_serve_save_ckpt_and_rollout(tmp_path, capsys):
    """ISSUE-17 acceptance from the product surface: one run mints a
    sharded checkpoint with --save-ckpt, the next canaries it onto live
    traffic with --rollout and promotes — every request served, the
    verdict line printed, the frozen ckpt_save/serve_rollout events in
    the jsonl. State-machine semantics are owned by
    tests/test_rollout.py; this drives the CLI wiring end to end."""
    import json

    model = ["--slots", "2", "--window", "4", "--t-max", "32",
             "--vocab", "11", "--embed-dim", "16", "--num-heads", "2",
             "--mlp-dim", "32", "--num-blocks", "1"]
    ckpt = tmp_path / "candidate"
    out = _run(["serve", "--host-devices", "8", "--requests", "4",
                "--seed", "1", "--save-ckpt", str(ckpt),
                "--path", str(tmp_path), *model], capsys)
    assert f"to {ckpt}" in out and "checkpoint: wrote" in out
    from idc_models_tpu.checkpoint import MANIFEST_NAME

    assert (ckpt / MANIFEST_NAME).exists()

    out = _run(["serve", "--host-devices", "8", "--requests", "24",
                "--rollout", str(ckpt), "--canary-fraction", "0.5",
                "--canary-requests", "3", "--rollout-at", "0.0",
                "--path", str(tmp_path), *model], capsys)
    assert "served: ok=24 timeout=0 rejected=0" in out
    assert "rollout: promoted after" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith("serve summary:")][0]
    summary = json.loads(line.split("serve summary:", 1)[1])
    assert summary["serve_rollout_outcome"] == "promoted"
    assert summary["serve_rollout_stage"] == "promoted"
    events = {json.loads(l)["event"] for l in
              (tmp_path / "logs" / "serve.jsonl").read_text()
              .splitlines()}
    assert {"ckpt_save", "ckpt_restore", "serve_rollout"} <= events


def test_cli_serve_rollout_adapters(tmp_path, capsys):
    """--rollout-adapters: the cheap first rung — synthetic per-tenant
    adapters are armed at build time and a re-seeded bank hot-swaps in
    after the trace, with the tenant isolation epilogue intact."""
    out = _run(["serve", "--host-devices", "8", "--requests", "6",
                "--slots", "2", "--window", "4", "--t-max", "32",
                "--vocab", "11", "--embed-dim", "16", "--num-heads",
                "2", "--mlp-dim", "32", "--num-blocks", "1",
                "--tenants", "acme,beta", "--rollout-adapters", "3"],
               capsys)
    assert "served: ok=6" in out
    assert ("adapter rollout: hot-swapped rank-3 adapters for "
            "2 tenant(s)") in out
    assert "tenant acme:" in out and "tenant beta:" in out


def test_cli_serve_rollout_usage_errors(tmp_path, capsys):
    """ISSUE-17: every bad rollout knob dies as a TEACHING usage error
    before any pre-training or serving runs, never a traceback."""
    base = ["serve", "--host-devices", "8"]
    with pytest.raises(SystemExit,
                       match="--canary-fraction needs --rollout"):
        cli.main(base + ["--canary-fraction", "0.5"])
    with pytest.raises(SystemExit, match="--rollout-at needs"):
        cli.main(base + ["--rollout-at", "0.5"])
    # a fake but complete checkpoint lets the knob checks run; the
    # knobs are validated before the checkpoint is ever restored
    from idc_models_tpu.checkpoint import save_sharded

    ck = tmp_path / "ck"
    save_sharded(ck, {"w": np.zeros(3, np.float32)})
    with pytest.raises(SystemExit, match="promoting without evidence"):
        cli.main(base + ["--rollout", str(ck),
                         "--canary-fraction", "-0.5"])
    with pytest.raises(SystemExit, match="promoting without evidence"):
        cli.main(base + ["--rollout", str(ck),
                         "--canary-fraction", "1.5"])
    with pytest.raises(SystemExit, match="at least one canary finish"):
        cli.main(base + ["--rollout", str(ck),
                         "--canary-requests", "0"])
    with pytest.raises(SystemExit, match="drains before the rollout"):
        cli.main(base + ["--rollout", str(ck), "--rollout-at", "1.0"])
    with pytest.raises(SystemExit, match="MANIFEST.json"):
        cli.main(base + ["--rollout", str(tmp_path / "nothing_here")])
    with pytest.raises(SystemExit,
                       match="--rollout-adapters needs --tenants"):
        cli.main(base + ["--rollout-adapters", "3"])
    with pytest.raises(SystemExit, match="adapter rank"):
        cli.main(base + ["--tenants", "a,b", "--rollout-adapters", "0"])


def test_cli_checkpoint_every_usage_errors(capsys):
    """ISSUE-17: --checkpoint-every teaches on both training verbs —
    zero is never, and pacing without --resumable writes nothing."""
    with pytest.raises(SystemExit, match="must be >= 1"):
        cli.main(["vgg", "--host-devices", "8", "--checkpoint-every",
                  "0", "--epochs", "1"])
    with pytest.raises(SystemExit, match="needs --resumable"):
        cli.main(["vgg", "--host-devices", "8", "--checkpoint-every",
                  "2", "--epochs", "1"])
    with pytest.raises(SystemExit, match="must be >= 1"):
        cli.main(["fed", "--host-devices", "8", "--checkpoint-every",
                  "0", "--rounds", "1"])
