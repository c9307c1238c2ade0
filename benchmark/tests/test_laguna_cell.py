"""The Laguna cell: its run end to end on the CPU at the configuration's
rehearsal sizes, its work functions against numbers worked by hand, its
two copies of the plain reference, and what its configuration file has
to state."""

import filecmp
import json

import numpy as np
import pytest

from benchmark.lib import moe_work, traffic_gen
from benchmark.runners import serve_moe_open_loop
from test_rehearsal import IGNORED, M, REQUIRED, ROOT, names, run_cell

CELL = "laguna_code_decode"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "laguna-s-2.1.json").read_text())
NEW_METRICS = {"moe_experts_touched_share", "moe_load_max_over_mean"}
DEVICE_METRICS = {"moe_window_roofline", "moe_experts_roofline",
                  "moe_experts_device_share", "attn_full_device_share",
                  "attn_window_device_share"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_laguna_cell(trace):
    p = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds", "3",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert REQUIRED <= set(line) <= REQUIRED | IGNORED
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    checks = line["checks"]
    # float32 on the CPU: the engine's chunk and window programs agree
    # with the plain reference to rounding, and no router choice differs
    assert checks["logit_err"] < 1e-4 and checks["router_deficit"] < 1e-4
    assert max(checks["router_swapped_share"]) == 0.0
    # 2 sparse layers x every position fed: prompts of 5, 20, 60 + 4 each
    assert checks["router_choices"] == 2 * (5 + 20 + 60 + 3 * 4)
    if trace:
        got = set(line["metrics"])
        assert got <= names("per_layer", CELL)
        assert NEW_METRICS <= got                    # the counters' metrics
        assert not got & DEVICE_METRICS              # no device, no number
        assert 0 < line["metrics"]["moe_experts_touched_share"]["value"] <= 100
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1
        assert line["metrics"]["serve_compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == names("end_to_end", CELL) == {
            "serve_out_tokens_per_s", "tpot_p50_ms", "setup_s"}


def test_every_new_metric_names_the_cell_and_has_its_files():
    per_layer = {m["name"]: m for m in M["per_layer"]}
    for name in NEW_METRICS | DEVICE_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        assert (ROOT / "benchmark" / "readers" / f"{spec['reader']}.py").exists()
    for name in ("moe_window_roofline", "moe_experts_roofline"):
        assert per_layer[name]["unit"] == "%" and per_layer[name]["layer"] == "kernels"
    # the accepted rooflines' work functions cannot read this model
    for name in ("decode_window_roofline", "prefill_chunk_roofline"):
        assert CELL not in per_layer[name]["workloads"]


def test_every_seed_serves_the_same_work_however_far_the_generator_gets():
    """Above the knee only a prefix of the window's arrivals is offered.
    Drawn over `arrival_segments`, any prefix that ends with a stretch
    holds the same lengths (to the jitter inside a stratum) under every
    seed; drawn as one stretch it is a random sample of them."""
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "code_decode.json").read_text())["open_loop"]
    segs = serve_moe_open_loop.arrival_segments(5.0, 56.0, 56.0)
    assert segs[:2] == [(0.0, 0.0), (0.0, 5.0)] and segs[-1] == (56.0, 56.0)
    window = segs[2:-1]
    assert len(window) == 10 and window[0][0] == 5.0 and window[-1][1] == 56.0
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
    # a rehearsal's short window is one stretch
    assert serve_moe_open_loop.arrival_segments(1.0, 4.0, 4.0) == [
        (0.0, 0.0), (0.0, 1.0), (1.0, 4.0), (4.0, 4.0)]

    def served(segments, seed, until, n_window=320):          # 6.27/s
        tr = traffic_gen.open_loop_trace(mix, seed=seed, segments=segments,
                                         vocab=7, t_max=8192)
        assert [a.due_s for a in tr] == sorted(a.due_s for a in tr)
        burst = [a for a in tr if a.due_s == 0.0]
        assert len(burst) == 48
        assert sum(5.0 <= a.due_s < 56.0 for a in tr) == n_window
        part = [a for a in tr if 5.0 <= a.due_s < until]
        return (np.mean([len(a.prompt) for a in part]),
                np.mean([a.max_new_tokens for a in part]),
                np.mean([len(a.prompt) for a in burst]))

    seeds = range(2_147_483_700, 2_147_483_716)
    until = window[5][1]                  # six of the ten stretches served
    cut = np.array([served(segs, s, until) for s in seeds])
    one = np.array([served([(0.0, 5.0), (5.0, 56.0)], s, until, 319)[:2]
                    for s in seeds])
    spread = lambda x: x.std(axis=0) / x.mean(axis=0)        # noqa: E731
    assert (spread(cut) < [0.012, 0.008, 0.02]).all(), spread(cut)
    assert (spread(one) > 2 * spread(cut)[:2]).all(), (spread(one), spread(cut))


def test_work_functions_against_numbers_worked_by_hand():
    c, e = CONFIG, CONFIG["engine"]
    assert moe_work.expert_params(c) == 3 * 3072 * 1024 == 9_437_184
    attn_full = 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 3072 * 48
    attn_window = 3072 * 9216 + 2 * 3072 * 1024 + 9216 * 3072 + 3072 * 72
    assert (attn_full, attn_window) == (44_187_648, 63_135_744)
    outside = (3072 * 25088                          # the head's slice
               + attn_full + 3 * 3072 * 12288        # layer 0, dense
               + 3 * (attn_window + 3072 * 256 + 9_437_184)
               + attn_full + 3072 * 256 + 9_437_184)
    assert moe_work.params_outside_experts(c) == outside == 508_993_536
    assert moe_work.kv_bytes_per_position(c, e) == 2 * 8 * 128 * 2 == 4096
    # 48 requests of 1,400 positions, 54.4 of 64 held experts touched a
    # layer and step: weights outside the experts, 4 sparse layers'
    # touched experts, 2 full layers' whole caches + 3 rings of 512, and
    # 5 layers' new rows; 8 token steps a window
    per_step = (508_993_536 * 2 + 4 * 54.4 * 9_437_184 * 2
                + 4096 * (2 * 67_200 + 3 * 48 * 512) + 4096 * 48 * 5)
    assert moe_work.decode_window(c, e, 67_200.0, 48.0, 54.4) == pytest.approx(
        8 * per_step, rel=1e-12)
    assert 5.9e9 < per_step < 6.0e9
    # a request shorter than the window is seen whole by a sliding layer
    short = moe_work.decode_window(c, e, 48 * 100.0, 48.0, 0.0) / 8
    assert short == pytest.approx(508_993_536 * 2 + 4096 * 5 * 4800
                                  + 4096 * 48 * 5)
    assert moe_work.expert_product_bytes(c, 54.4, 120.0) == pytest.approx(
        54.4 * 18_874_368 + 120 * 2 * 3072 * 2)
    assert moe_work.expert_product_flops(c, 120.0) == 2 * 120 * 9_437_184
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # decode: the weights bind; a prefill chunk's 1,280 rows: still the weights
    assert moe_work.expert_product_seconds(c, peaks, 54.4, 120.0) == pytest.approx(
        (54.4 * 18_874_368 + 120 * 12288) / 819e9)
    assert moe_work.expert_product_seconds(c, peaks, 0.0, 1e6) == pytest.approx(
        2e6 * 9_437_184 / 197e12)


def test_the_two_copies_of_the_reference_agree():
    assert filecmp.cmp(ROOT / "benchmark" / "reference" / "laguna_ref.py",
                       ROOT / "tests" / "laguna_ref.py", shallow=False)


def test_the_configuration_states_its_cut_and_keeps_every_width():
    c = CONFIG
    entry = next(x for x in M["configs"] if x["name"] == "laguna-s-2.1")
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(c["reduced_from"]) == set(c["reduced"])
    for key in ("deployment", "assumed", "changed", "source"):
        assert c[key]
    assert "model_type laguna" in c["source"] and entry["source"] in c["source"]
    assert set(c["assumed"]) >= {"gate", "router_score", "shared_expert",
                                 "qk_norm", "rotary_pairing", "router_precision"}
    # the published counts stand beside the held ones
    assert (c["num_experts"], c["num_experts_published"]) == (64, 256)
    assert (c["vocab_size"], c["vocab_size_published"]) == (25088, 100352)
    assert c["num_experts_per_tok"] == 10 and c["num_hidden_layers"] == 5
    # every width as published
    assert (c["hidden_size"], c["head_dim"], c["num_key_value_heads"]) == (3072, 128, 8)
    assert (c["moe_intermediate_size"], c["shared_expert_intermediate_size"],
            c["intermediate_size"], c["sliding_window"]) == (1024, 1024, 12288, 512)
    assert c["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert c["layer_types"][:5] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert c["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert len(c["layer_types"]) == len(c["mlp_layer_types"]) == 48
    full, window = (c["rope_parameters"][k] for k in ("full_attention",
                                                       "sliding_attention"))
    assert (full["rope_theta"], full["factor"], full["partial_rotary_factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"]) == (500000, 128, 0.5, 8192, 32, 1)
    assert full["attention_factor"] == 1.4852030263919618
    assert (window["rope_theta"], window["partial_rotary_factor"]) == (10000, 1)
    assert c["moe_routed_scaling_factor"] == 2.5 and c["rms_norm_eps"] == 1e-06
    # the cell, letter for letter
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", "code_decode", 1)
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "code_decode.json").read_text())
    o = mix["open_loop"]
    assert mix["judge"] == "capacity" and o["arrivals"] == "poisson"
    assert (o["burst_at_start"], o["warmup_s"], o["drain_s"], o["profile_s"]) == (
        c["engine"]["n_slots"], 5.0, 0.0, 3.0)
    assert o["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                               "min": 576, "max": 7168}
    assert o["output_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                               "min": 64, "max": 1024}
    assert o["prompt_len"]["min"] > c["sliding_window"]      # every ring wraps
    assert o["prompt_len"]["max"] + o["output_len"]["max"] == c["engine"]["t_max"]
    assert isinstance(o["rate_per_s"], float)
