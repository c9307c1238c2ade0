"""The Keye cell: its run end to end on the CPU at the configuration's
rehearsal sizes, its work functions against numbers worked by hand, its
two copies of the plain reference, and what its configuration file has
to state."""

import filecmp
import json

import pytest

from benchmark.lib import dsa_work
from test_rehearsal import IGNORED, M, REQUIRED, ROOT, names, run_cell

CELL = "keye_longctx_decode"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "keye-vl-2.0-30b-a3b.json").read_text())
COUNTERS = {"dsa_selected_share", "moe_experts_touched_share",
            "moe_load_max_over_mean"}
DEVICE_METRICS = {"dsa_index_device_share", "dsa_select_device_share",
                  "attn_sparse_device_share", "dsa_window_roofline",
                  "dsa_fold_roofline"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_keye_cell(trace):
    p = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds", "3",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert REQUIRED <= set(line) <= REQUIRED | IGNORED
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    checks = line["checks"]
    # float32 on the CPU: the engine's chunk and window programs agree
    # with the plain reference to rounding; no router choice and no
    # selected position differs, and every query attended as many
    # positions as it has to
    assert checks["logit_err"] < 1e-4 and checks["router_deficit"] < 1e-4
    assert max(checks["router_swapped_share"]) == 0.0
    assert checks["select_deficit"] == 0.0 and checks["select_count_ok"]
    assert max(checks["select_swapped_share"]) == 0.0
    # greedy, and the reference's logits are the system's to rounding:
    # every token emitted inside a window is the reference's best
    assert checks["token_gap"] < 1e-4
    # 3 layers x every position fed: prompts of 5, 20, 60 + 8 each (two
    # windows of 4, five slots live: the two left over hold fillers)
    assert checks["router_choices"] == 3 * (5 + 20 + 60 + 3 * 8)
    if trace:
        got = set(line["metrics"])
        assert got <= names("per_layer", CELL)
        assert COUNTERS <= got                       # the counters' metrics
        assert not got & DEVICE_METRICS              # no device, no number
        # rehearsal prompts of 18-60 under a topk of 16: a part selected
        assert 10 < line["metrics"]["dsa_selected_share"]["value"] < 100
        assert line["metrics"]["serve_compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == names("end_to_end", CELL) == {
            "serve_out_tokens_per_s", "tpot_p50_ms", "setup_s"}


def test_every_new_metric_names_the_cell_and_has_its_files():
    per_layer = {m["name"]: m for m in M["per_layer"]}
    for name in DEVICE_METRICS | {"dsa_selected_share"}:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "tpot_p50_ms"
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        assert (ROOT / "benchmark" / "readers" / f"{spec['reader']}.py").exists()
    for name in ("dsa_window_roofline", "dsa_fold_roofline"):
        assert per_layer[name]["unit"] == "%" and per_layer[name]["layer"] == "kernels"
    # the accepted counts of dense reads and of the other layer kinds
    # cannot read this model
    for name in ("decode_window_roofline", "prefill_chunk_roofline",
                 "moe_window_roofline", "attn_read_share",
                 "attn_full_device_share", "attn_window_device_share"):
        assert CELL not in per_layer[name]["workloads"]
    # the expert layer's metrics read this configuration's keys as they are
    for name in ("moe_experts_roofline", "moe_experts_device_share",
                 "moe_experts_touched_share", "moe_load_max_over_mean"):
        assert CELL in per_layer[name]["workloads"]
    assert CONFIG["mlp_layer_types"][:CONFIG["num_hidden_layers"]] == ["sparse"] * 8


def test_work_functions_against_numbers_worked_by_hand():
    c, e = CONFIG, CONFIG["engine"]
    assert dsa_work.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert dsa_work.attention_params(c) == (2 * 2048 * 32 * 128
                                            + 2 * 2048 * 4 * 128) == 18_874_368
    assert dsa_work.indexer_params(c) == (2048 * 16 * 64 + 2048 * 64
                                          + 2048 * 16) == 2_260_992
    outside = 8 * (18_874_368 + 2_260_992 + 2048 * 128) + 2048 * 18_992
    assert dsa_work.params_outside_experts(c) == outside == 210_075_648
    assert dsa_work.kv_bytes_per_position(c, e) == 2 * 4 * 128 * 2 == 2048
    assert dsa_work.index_bytes_per_position(c, e) == 64 * 2 == 128
    # 16 requests of 12,000 positions: every one reads topk rows
    assert dsa_work.selected_rows(c, 192_000.0, 16.0) == 16 * 2048
    assert dsa_work.selected_rows(c, 16 * 1500.0, 16.0) == 16 * 1500
    # a 16-slot step at 12k: attention and indexer weights, index keys to
    # every slot's length, 2,048 rows a slot-layer, the new rows
    attn = 8 * ((18_874_368 + 2_260_992) * 2 + 128 * 192_000
                + 2048 * 16 * 2048 + (2048 + 128) * 16)
    assert dsa_work.sparse_attention_step(c, e, 192_000.0, 16.0) == attn
    assert 1.07e9 < attn < 1.08e9
    # ... the routers and the head's slice, and 10 touched experts a layer
    per_step = attn + (8 * 2048 * 128 + 2048 * 18_992) * 2 + 8 * 10 * 4_718_592 * 2
    assert dsa_work.decode_window(c, e, 192_000.0, 16.0, 10.0) == pytest.approx(
        8 * per_step, rel=1e-12)
    assert 1.9e9 < per_step < 1.95e9           # ISSUE 34's "about 1.9 GB"
    # dense, the same step would read every cached row of K and V
    dense = 8 * 2048 * 192_000
    assert dense / (8 * 2048 * 16 * 2048) == pytest.approx(5.86, rel=1e-2)


def test_the_two_copies_of_the_reference_agree():
    assert filecmp.cmp(ROOT / "tests" / "keye_ref.py",
                       ROOT / "benchmark" / "reference" / "keye_ref.py",
                       shallow=False)


def test_the_configuration_states_what_it_changed():
    c = CONFIG
    entry = next(x for x in M["configs"] if x["name"] == "keye-vl-2.0-30b-a3b")
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(c["reduced_from"]) == set(c["reduced"])
    for key in ("deployment", "assumed", "changed", "source"):
        assert c[key]
    assert "model_type KeyeVL2" in c["source"] and entry["source"] in c["source"]
    assert "8 chips" in c["deployment"]
    assert set(c["assumed"]) >= {"qk_norm", "indexer_input", "index_key_norm",
                                 "index_rotary", "chunk_sizes", "router_score",
                                 "selection", "mrope"}
    # the published counts stand beside the held ones
    assert (c["num_experts"], c["num_experts_published"]) == (16, 128)
    assert (c["vocab_size"], c["vocab_size_published"]) == (18992, 151936)
    assert c["num_experts_per_tok"] == 8 and c["num_hidden_layers"] == 8
    # every width as published
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"]) == (2048, 128, 32, 4)
    assert (c["moe_intermediate_size"], c["intermediate_size"]) == (768, 6144)
    assert c["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert c["rope_theta"] == 10000000 and c["rms_norm_eps"] == 1e-06
    assert c["max_position_embeddings"] == 262144
    # every number of the catalog's row that is not in `reduced`
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        for line in open(path):
            if '"Keye-VL-2.0-30B-A3B"' in line:
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
        "keye-vl-2.0-30b-a3b", "longctx_decode", 1)
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "longctx_decode.json").read_text())
    o = mix["open_loop"]
    assert mix["judge"] == "capacity" and o["arrivals"] == "poisson"
    assert (o["burst_at_start"], o["warmup_s"], o["drain_s"], o["profile_s"]) == (
        c["engine"]["n_slots"], 5.0, 0.0, 3.0)
    # ISSUE 34's lengths; the outputs halved, as it allows where fewer
    # than 40 requests complete a window (PERF.md section 4, PR 34)
    assert o["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.7, "min": 2560, "max": 28672}
    assert o["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.6, "min": 64, "max": 1024}
    assert o["prompt_len"]["min"] > c["sa_config"]["topk"]   # selection live
    assert o["prompt_len"]["max"] + o["output_len"]["max"] <= c["engine"]["t_max"]
    assert isinstance(o["rate_per_s"], float)
    assert c["check"]["prompt_lens"] == [1500, 3000, 9000, 24000]


def test_every_seed_serves_the_same_lengths_in_the_same_order():
    """`steady_trace`: the lengths are the generator's own draw for the
    mix's `lengths_seed` (so ISSUE 34's distributions, stratified stretch
    by stretch), whatever the run's seed; due times and token ids are
    the seed's."""
    from benchmark.lib import traffic_gen
    from benchmark.runners import serve_dsa_open_loop as runner
    from benchmark.runners.serve_moe_open_loop import arrival_segments

    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "longctx_decode.json").read_text())["open_loop"]
    kw = dict(segments=arrival_segments(5.0, 56.0, 56.0), vocab=18992,
              t_max=32768)
    a, b = (runner.steady_trace(mix, seed=s, **kw)
            for s in (3, 2**31 + 11))
    drawn = traffic_gen.open_loop_trace(mix, seed=mix["lengths_seed"], **kw)
    own = traffic_gen.open_loop_trace(mix, seed=3, **kw)
    lengths = [(len(x.prompt), x.max_new_tokens) for x in drawn]
    assert [(len(x.prompt), x.max_new_tokens) for x in a] == lengths
    assert [(len(x.prompt), x.max_new_tokens) for x in b] == lengths
    assert [x.due_s for x in a] == [x.due_s for x in own]
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert a[0].prompt != b[0].prompt
    assert a == runner.steady_trace(mix, seed=3, **kw)
    assert all(0 <= t < 18992 for x in a[:4] for t in x.prompt)
    p = sorted(n for n, _ in lengths)
    assert p[0] >= 2560 and p[-1] <= 28672 and p[-1] > 24000
    assert 7000 < p[len(p) // 2] < 9500
