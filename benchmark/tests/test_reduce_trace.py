"""`reduce_trace` on the small trace committed beside this file (its
`about` says what it holds), and the xplane reader on a trace recorded
here on the CPU."""

import json
from pathlib import Path

import pytest

from benchmark.lib import harness, reduce_trace as rt

TRACE = json.loads((Path(__file__).parent / "data" / "small_trace.json").read_text())
OPS = TRACE["devices"]["/device:TPU:0"]["ops"]
PROGRAMS = TRACE["devices"]["/device:TPU:0"]["programs"]


def test_window_is_the_annotation():
    assert rt.window_of(TRACE) == (0, 1000)


def test_busy_union_counts_nested_ops_once():
    assert rt.busy_intervals(OPS, 0, 1000) == [(100, 200), (300, 700), (900, 1000)]
    assert rt.busy_seconds(OPS, 0, 1000) == pytest.approx(600e-9)


def test_busy_union_is_clipped_to_the_window():
    assert rt.busy_seconds(OPS, 150, 950) == pytest.approx((50 + 400 + 50) * 1e-9)


def test_gaps_are_the_complement():
    assert rt.idle_gaps(OPS, 0, 1000) == [(0, 100), (200, 300), (700, 900)]


def test_self_times_add_up_to_busy():
    got = rt.op_self_seconds(OPS, 0, 1000)
    assert got == pytest.approx({"fusion.1": 200e-9, "while": 150e-9,
                                 "fusion.2": 150e-9, "all-reduce.1": 100e-9})
    assert sum(got.values()) == pytest.approx(rt.busy_seconds(OPS, 0, 1000))
    assert rt.top_ops(OPS, 0, 1000, top=1) == [["fusion.1", pytest.approx(200e-9)]]


def test_program_time_by_name():
    secs, n = rt.program_seconds(PROGRAMS, "train_step", 0, 1000)
    assert (secs, n) == (pytest.approx(700e-9), 2)
    assert rt.program_seconds(PROGRAMS, "nothing", 0, 1000) == (0.0, 0)


def test_longest_gap_is_named_by_the_host_span_over_it():
    offset = rt.clock_offset_ns(TRACE, 2.0)
    assert offset == pytest.approx(50 - 2e9)
    # a program span that began 0.0000002 s after the tracer's epoch of
    # 1.9999998 s, i.e. at 2.0 s: on the profiler's clock at 50 ns
    spans = harness.program_spans_on_profiler_clock(
        [{"name": "train.epoch", "t_ms": 0.0002, "dur_ms": 0.0009}],
        1.9999998, offset)
    assert spans[0][0] == "train.epoch"
    assert spans[0][1] == pytest.approx(50, abs=1) and spans[0][2] == pytest.approx(950, abs=1)
    host = [(n, s, s + d) for n, s, d in TRACE["host"] if n == "bench.step"]
    named = rt.name_gaps(rt.idle_gaps(OPS, 0, 1000), host + spans)
    # [700,900) lies under bench.step (the shorter of the two spans over
    # it); [200,300) only under train.epoch; [0,100) is half under it
    assert named[0] == ["bench.step", pytest.approx(200e-9)]
    assert ["train.epoch", pytest.approx(200e-9)] in named


def test_no_sync_annotation_gives_no_offset():
    assert rt.clock_offset_ns({"devices": {}, "host": []}, 1.0) is None


def test_xplane_reader_finds_the_runner_annotations(tmp_path):
    import jax
    import jax.numpy as jnp

    import time

    prof = harness.ProfilerSlice(tmp_path / "profile", 0.5)
    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    assert prof.load() is None and not prof.started
    prof.start()
    while prof.sync_clock_s is None:        # the slice is open
        time.sleep(0.01)
    with harness.annotate("bench.step"):
        f(jnp.ones((64, 64))).block_until_ready()
    trace = prof.load()
    names = [n for n, _, _ in trace["host"]]
    assert {"bench.sync", "bench.profile_window", "bench.step"} <= set(names)
    t0, t1 = rt.window_of(trace)
    assert (t1 - t0) / 1e9 == pytest.approx(0.5, rel=0.2)
    assert rt.clock_offset_ns(trace, prof.sync_clock_s) is not None
    assert trace["devices"] == {}              # the CPU has no device plane


def test_device_ops_get_short_names_that_group_the_layers():
    a = ("%fusion.3961 = f32[10,20,64]{2,1,0:T(8,128)S(1)} fusion(bf16[10,1024,20,64]"
         "{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.20779, f32[10,20,1024]{1,2,0:"
         "T(8,128)S(1)} %get-tuple-element.17502), kind=kLoop, calls=%fused.405")
    b = a.replace("3961", "4089").replace("20779", "20811")
    assert rt.short_op_name(a) == rt.short_op_name(b) == (
        "fusion f32[10,20,64] <- bf16[10,1024,20,64], f32[10,20,1024]")
    assert rt.short_op_name("all-reduce.1") == "all-reduce.1"


def test_ops_per_program_run_counts_cut_runs_by_their_part():
    from benchmark.readers import device_op_time

    class Ctx:
        trace, window = TRACE, (0, 950)      # cuts the second step in half
    # all-reduce: 100 ns; train_step runs: 600 + 50 of mean 350 -> 1.857 runs
    got = device_op_time.read(Ctx, pattern="all-reduce", line="ops",
                              per={"programs": "train_step"})
    assert got == pytest.approx(1e3 * 100e-9 / (650 / 350))
    assert device_op_time.read(Ctx, pattern="train_step", line="programs",
                               per="count") == pytest.approx(1e3 * 350e-9)


def test_without_host_tracing_device_markers_give_window_and_clock():
    dev = {"ops": OPS, "programs": PROGRAMS + [["jit_bench_marker(9)", 40, 2],
                                                ["jit_bench_marker(9)", 980, 2]]}
    trace = {"devices": {"/device:TPU:0": dev}, "host": []}
    assert rt.window_of(trace) == (40, 980)
    assert rt.clock_offset_ns(trace, 2.0) == pytest.approx(40 - 2e9)
    assert rt.clock_offset_ns({"devices": {}, "host": []}, 2.0) is None
