"""The GLM-5.2 cell: its run end to end on the CPU at the configuration's
rehearsal sizes, its work functions against numbers worked by hand, its
two copies of the plain reference, what its configuration file has to
state, and the parent's quick refusal."""

import filecmp
import json
import os

import pytest

from benchmark.lib import mla_work
from test_rehearsal import IGNORED, M, REQUIRED, ROOT, names, run_cell

CELL = "glm52_longctx_decode"
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "glm-5.2.json").read_text())
NEW = {"attn_latent_device_share", "mla_latent_device_share",
       "mla_window_roofline", "latent_cache_gb"}
COUNTERS = {"dsa_selected_share", "moe_experts_touched_share",
            "moe_load_max_over_mean", "dsa_folded_over_live",
            "latent_cache_gb"}
DEVICE_METRICS = {"attn_latent_device_share", "mla_latent_device_share",
                  "mla_window_roofline", "dsa_index_device_share",
                  "dsa_select_device_share", "moe_experts_roofline"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_glm_cell(trace):
    p = run_cell("--workload", CELL, "--seed", "3000000039", "--seconds", "3",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert REQUIRED <= set(line) <= REQUIRED | IGNORED
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    checks = line["checks"]
    # float32 on the CPU: the engine's absorbed attention agrees with the
    # reference's expanded one to rounding; no router choice and no
    # selected position differs, every query attended as many positions
    # as it has to, and every sharing layer its source's
    assert checks["logit_err"] < 1e-4 and checks["router_deficit"] < 1e-4
    assert max(checks["router_swapped_share"]) == 0.0
    assert checks["select_deficit"] == 0.0 and checks["select_count_ok"]
    assert checks["shared_ok"] and checks["token_gap"] < 1e-4
    # 4 expert layers x every position fed: prompts of 5, 20, 60 + 8 each
    assert checks["router_choices"] == 4 * (5 + 20 + 60 + 3 * 8)
    if trace:
        got = set(line["metrics"])
        assert got <= names("per_layer", CELL)
        assert COUNTERS <= got                       # the counters' metrics
        assert not got & DEVICE_METRICS              # no device, no number
        assert 10 < line["metrics"]["dsa_selected_share"]["value"] < 100
        assert line["metrics"]["serve_compiles_in_window"]["value"] == 0
        # 5 layers x 5 slots x 128 positions x 128 lanes (a row of 20
        # values, padded to whole tiles) x 4 bytes
        assert line["metrics"]["latent_cache_gb"]["value"] == pytest.approx(
            5 * 5 * 128 * 128 * 4 * 1e-9)
    else:
        assert set(line["metrics"]) == names("end_to_end", CELL) == {
            "serve_out_tokens_per_s", "tpot_p50_ms", "setup_s"}


def test_every_new_metric_names_the_cell_and_has_its_files():
    per_layer = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "tpot_p50_ms"
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        assert spec["name"] == name and spec["layer"] == per_layer[name]["layer"]
        assert (ROOT / "benchmark" / "readers" / f"{spec['reader']}.py").exists()
    assert per_layer["mla_window_roofline"]["layer"] == "kernels"
    assert per_layer["mla_window_roofline"]["unit"] == "%"
    # what counts grouped-query K/V bytes, or reads scopes this model's
    # attention does not run under, cannot read it
    for name in ("dsa_window_roofline", "dsa_fold_roofline",
                 "attn_sparse_device_share", "decode_window_roofline",
                 "attn_read_share"):
        assert CELL not in per_layer[name]["workloads"]
    for name in ("moe_experts_roofline", "dsa_index_device_share",
                 "dsa_select_device_share", "window_device_ms",
                 "cache_copy_ms_per_window",
                 "serve_device_idle_share", "turnaround_admitting_p50_ms"):
        assert CELL in per_layer[name]["workloads"]
    for e in M["end_to_end"]:
        if e["name"] in ("serve_out_tokens_per_s", "tpot_p50_ms"):
            assert CELL in e["workloads"]
        elif "workloads" in e:
            assert CELL not in e["workloads"]


def test_work_functions_against_numbers_worked_by_hand():
    c, e = CONFIG, CONFIG["engine"]
    mla = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
           + 16384 * 6144)
    assert mla_work.latent_params(c) == mla == 165_019_648
    assert mla_work.indexer_params(c) == (2048 * 32 * 128 + 6144 * 128
                                          + 6144 * 32) == 9_371_648
    assert mla_work.expert_params(c) == 3 * 6144 * 2048 == 37_748_736
    outside = (5 * mla + 2 * 9_371_648 + 3 * 6144 * 12288
               + 4 * (6144 * 256 + 37_748_736) + 6144 * 19360)
    assert mla_work.params_outside_experts(c) == outside == 1_346_568_192
    assert mla_work.latent_row_bytes(c, e) == 576 * 2 == 1152
    assert mla_work.index_bytes_per_position(c, e) == 128 * 2 == 256
    # 8 requests of 12,000 positions, 3 touched experts a layer and step
    rows = 5 * 1152 * (8 * 2048 + 8) + 2 * 256 * (96_000 + 8)
    per_step = outside * 2 + rows + 4 * 3 * 37_748_736 * 2
    assert mla_work.decode_window(c, e, 96_000.0, 8.0, 3.0) == pytest.approx(
        8 * per_step, rel=1e-12)
    assert 3.7e9 < per_step < 3.8e9


def test_the_two_copies_of_the_reference_agree():
    assert filecmp.cmp(ROOT / "tests" / "glm_ref.py",
                       ROOT / "benchmark" / "reference" / "glm_ref.py",
                       shallow=False)


def test_the_configuration_states_what_it_changed():
    c = CONFIG
    entry = next(x for x in M["configs"] if x["name"] == "glm-5.2")
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "mlp_layer_types", "indexer_types"]
    assert set(c["reduced_from"]) == set(c["reduced"])
    for key in ("deployment", "assumed", "changed", "source"):
        assert c[key]
    assert "model_type glm_moe_dsa" in c["source"] and entry["source"] in c["source"]
    assert "16 chips" in c["deployment"]
    assert {"indexer_query", "index_key_norm", "index_rotary", "index_score",
            "index_share", "latent_norms", "attention_scale",
            "rotary_pairing", "router_score"} <= set(c["assumed"])
    assert {"weights", "e_score_correction_bias", "mtp"} <= set(c["changed"])
    # the published counts stand beside the held ones
    assert (c["n_routed_experts"], c["n_routed_experts_published"]) == (16, 256)
    assert (c["num_experts"], c["num_experts_published"]) == (16, 256)
    assert (c["vocab_size"], c["vocab_size_published"]) == (19360, 154880)
    assert c["layers_taken"] == [2, 3, 4, 5, 6] and c["num_hidden_layers"] == 5
    # every width as published
    assert (c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["num_attention_heads"]) == (6144, 2048, 512, 192, 64, 256, 64)
    assert (c["index_n_heads"], c["index_head_dim"], c["index_topk"]) == (
        32, 128, 2048)
    assert (c["moe_intermediate_size"], c["intermediate_size"]) == (2048, 12288)
    # every number of the catalog's row that is not in `reduced`
    row = None
    # MODEL_CATALOG names a JSON-lines catalog of published configs
    path = os.environ.get("MODEL_CATALOG", "")
    try:
        for line in open(path) if path else ():
            if '"GLM-5.2"' in line:
                row = json.loads(line)
    except OSError:
        pass
    if row is not None:
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
    # the cell, letter for letter
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5.2", "glm_longctx_decode", 1)
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "glm_longctx_decode.json").read_text())
    o = mix["open_loop"]
    assert mix["judge"] == "capacity" and o["arrivals"] == "poisson"
    assert (o["burst_at_start"], o["warmup_s"], o["drain_s"], o["profile_s"]) == (
        c["engine"]["n_slots"], 5.0, 0.0, 3.0)
    assert o["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.7, "min": 2560, "max": 28672}
    assert o["prompt_len"]["min"] > c["index_topk"]          # selection live
    assert o["prompt_len"]["max"] + o["output_len"]["max"] <= c["engine"]["t_max"]
    lens = c["check"]["prompt_lens"]
    assert lens[0] < c["index_topk"] < lens[1] and lens[-1] >= 16000


def test_every_seed_serves_the_same_lengths():
    from benchmark.lib import traffic_gen
    from benchmark.runners import serve_mla_open_loop as runner
    from benchmark.runners.serve_moe_open_loop import arrival_segments

    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "glm_longctx_decode.json").read_text())["open_loop"]
    kw = dict(segments=arrival_segments(5.0, 56.0, 56.0), vocab=19360,
              t_max=32768)
    a, b = (runner.steady_trace(mix, seed=s, **kw) for s in (3, 2**31 + 11))
    drawn = traffic_gen.open_loop_trace(mix, seed=mix["lengths_seed"], **kw)
    lengths = [(len(x.prompt), x.max_new_tokens) for x in drawn]
    assert [(len(x.prompt), x.max_new_tokens) for x in a] == lengths
    assert [(len(x.prompt), x.max_new_tokens) for x in b] == lengths
    assert a[0].prompt != b[0].prompt
    assert all(0 <= t < 19360 for x in a[:4] for t in x.prompt)


def test_a_program_without_latent_attention_ends_the_cell_at_once(monkeypatch):
    """The parent commit has no `lm.glm_spec`: the runner ends before
    any weight is made, with a message and a non-zero exit."""
    from benchmark.runners import serve_mla_open_loop as runner
    from idc_models_tpu.models import lm

    monkeypatch.delattr(lm, "glm_spec")
    with pytest.raises(SystemExit, match="glm_spec is missing"):
        runner.model_spec(CONFIG)
