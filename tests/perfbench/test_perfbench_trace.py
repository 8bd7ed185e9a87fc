"""The trace-to-metric reductions on small traces with known answers."""
import json
import types
from pathlib import Path

import pytest

from perfbench import spec, trace

DATA = json.loads((Path(__file__).parent / "data" /
                   "small_traces.json").read_text())


def cell_with(name, *, peaks=None, host=None, config=None):
    return types.SimpleNamespace(
        trace_data=trace.Trace.from_json(DATA[name]), peaks=peaks,
        host=host or {}, config=config or {}, counters={})


def test_names_of_instructions():
    op = DATA["solve"]["ops"]["0"][1][0]
    assert trace.short_name(op) == "superstep_chain.1"
    assert trace.base_name(op) == "superstep_chain"
    assert trace.base_name("%while.2 = (s32[]) while(...)") == "while"


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.length([(0, 2), (1, 3), (5, 7)]) == 5
    assert trace.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert trace.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def test_idle_share_leaves_out_the_while_container():
    # leaf ops cover 400 ns of kernel and 35 ns of fusion in a 1000 ns window
    cell = cell_with("solve")
    assert spec.metric_reader("device.idle_share.solve")(cell) == \
        pytest.approx(56.5)
    assert spec.metric_reader("device.idle_share.serve")(cell) == \
        pytest.approx(56.5)


def test_launch_gap_counts_gaps_inside_chunks_only():
    # chunk 1: 120 -> 140, chunk 2: 620 -> 650; the 240 -> 520 boundary
    # between chunks is the host's, not the loop's
    cell = cell_with("solve", host={"chunks": 2})
    assert spec.metric_reader("loop.launch_gap_us")(cell) == \
        pytest.approx(0.025)


def test_roofline_share_takes_the_larger_bound_over_kernel_time():
    # ops 2*100*10*15 = 30000 at 1e12/s = 30 ns; bytes 2*100*4*3 = 2400 at
    # 1e11 B/s = 24 ns; kernel time 400 ns -> 30/400
    cell = cell_with(
        "solve", peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        host={"chunks": 2, "cells_per_chunk": 100, "iters_per_chunk": 10},
        config={"ops_per_update": 15, "state_fields": 1, "aux_fields": 1})
    assert spec.metric_reader("stream_kernel_roofline")(cell) == \
        pytest.approx(7.5)


def test_exposed_halo_is_collective_time_no_other_op_covers():
    # device 1: permute [100, 200], fusion [150, 300] -> 50 ns alone of
    # 1000; device 0 runs no collective and does not count
    cell = cell_with("mesh")
    assert spec.metric_reader("halo.exposed_share")(cell) == \
        pytest.approx(5.0)
    assert spec.metric_reader("stream_kernel_roofline")(cell) is None


def test_breakdown_names_ops_and_gaps():
    b = trace.breakdown(trace.Trace.from_json(DATA["solve"]))
    assert b["device_ops"][0] == ["superstep_chain", pytest.approx(4e-7)]
    assert b["device_ops"][1] == ["fusion", pytest.approx(3.5e-8)]
    assert [g[0] for g in b["idle_gaps"][:2]] == ["bench.chunk"] * 2
    assert b["idle_gaps"][0][1] == pytest.approx(2.8e-7)
    assert sum(g[1] for g in b["idle_gaps"]) == pytest.approx(5.65e-7)


def test_readers_without_a_trace_read_nothing():
    cell = types.SimpleNamespace(trace_data=None, peaks=None, host={},
                                 config={}, counters={})
    for name in ("stream_kernel_roofline", "loop.launch_gap_us",
                 "device.idle_share.solve", "halo.exposed_share",
                 "serve.batch_fill", "serve.rounds_per_launch"):
        assert spec.metric_reader(name)(cell) is None


def test_read_finds_benchmark_spans_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(4)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.chunk"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.read(str(tmp_path))
    names = [s[0] for s in tr.spans]
    assert names == ["bench.window", "bench.chunk"]
    lo, hi = tr.window()
    assert hi > lo
    back = trace.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back.spans == tr.spans and back.ops == tr.ops
