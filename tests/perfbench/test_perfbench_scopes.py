"""The readers of the program's own scopes and spans, on a hand-made trace
whose answers can be worked out by hand (``data/scoped_traces.json``)."""
import json
import sys
import types
from pathlib import Path

import pytest

from perfbench import scopes, spec, trace

DATA = json.loads((Path(__file__).parent / "data" /
                   "scoped_traces.json").read_text())
#: the same program's op names from a version that names no phase
UNSCOPED = {k: v.replace("stencil.superstep/", "")
            .replace("stencil.halo_refresh/", "")
            .replace("stencil.unpad/", "")
            for k, v in DATA["op_names"].items()}


def cell_with(op_names=None, **host):
    """The hand-made solve trace, its program's op names given (as the
    readers would get them by compiling the program again)."""
    return types.SimpleNamespace(
        trace_data=trace.Trace.from_json(DATA["solve"]), peaks=None,
        host=host, config={}, counters={},
        program_op_names=DATA["op_names"] if op_names is None else op_names)


def read(metric, cell):
    return spec.metric_reader(metric)(cell)


def test_scope_is_the_innermost_stencil_name_of_the_op_name():
    names = DATA["op_names"]
    assert scopes.scope(names["superstep_chain.6"]) == "stencil.superstep"
    assert scopes.scope(names["fusion.9"]) == "stencil.halo_refresh"
    assert scopes.scope(names["slice.1"]) == "stencil.unpad"
    assert scopes.scope(names["copy.10"]) is None
    assert scopes.scope(names["add.87"]) is None


def test_op_names_are_read_from_the_hlo_text():
    hlo = """ENTRY %main.10 (gp: f32[16,384]) -> f32[16,256] {
  %copy.10 = f32[16,384]{1,0} copy(f32[16,384]{0,1} %gte.131)
  ROOT %slice.1 = f32[16,256]{0,1} slice(f32[16,384]{0,1} %gte.3), """ \
        """slice={[0:16], [64:320]}, metadata={op_name="jit(loop_body)/""" \
        """stencil.unpad/slice" source_file="ops.py" source_line=169}
}"""
    assert scopes.hlo_op_names(hlo) == {
        "copy.10": "", "slice.1": "jit(loop_body)/stencil.unpad/slice"}


def test_halo_refresh_is_its_scope_over_the_kernel_executions():
    # per chunk 2 x (gather 20 + select 10) ns, 4 kernels in the window
    assert read("loop.halo_refresh_us", cell_with()) == pytest.approx(0.030)


def test_relayout_is_the_rest_of_the_loop_but_the_kernel():
    # inside the loop, per chunk: copy.10 (no metadata) 2 x 5, copy.11 (in
    # the kernel's scope) 2 x 5, the loop's add 2 x 1 = 22 ns; the pad,
    # copy.6 before the loop and the unpad slice after it do not count
    assert read("loop.relayout_us", cell_with()) == pytest.approx(0.011)


def test_split_accounts_for_the_whole_launch_gap():
    cell = cell_with(chunks=2)
    gap = read("loop.launch_gap_us", cell)
    assert gap == pytest.approx(0.050)
    idle_in_gap_us = 0.009                  # 161 -> 170 in each gap
    assert read("loop.halo_refresh_us", cell) \
        + read("loop.relayout_us", cell) + idle_in_gap_us == \
        pytest.approx(gap)


@pytest.mark.parametrize("op_names", [UNSCOPED, {}],
                         ids=["program-without-scopes", "no-program"])
def test_program_without_scopes_reads_nothing(op_names):
    # a program that names no phase, or one that could not be compiled
    # again: no reading, and the accepted readers read what they read
    cell = cell_with(op_names, chunks=2)
    assert read("loop.halo_refresh_us", cell) is None
    assert read("loop.relayout_us", cell) is None
    assert read("loop.launch_gap_us", cell) == pytest.approx(0.050)


def test_split_needs_one_device_with_kernels():
    none = types.SimpleNamespace(trace_data=None)
    assert read("loop.halo_refresh_us", none) is None
    two = cell_with()
    two.trace_data.ops[1] = two.trace_data.ops[0]
    assert read("loop.relayout_us", two) is None


def test_the_program_compiled_again_names_its_phases():
    from repro import tracing
    from repro.api import RunConfig, StencilProblem, plan
    cfg = {**spec.config("hotspot2d-f32"), "backend": "pallas_interpret"}
    cell = types.SimpleNamespace(config=cfg, traffic={"grid": [16, 256]})
    tracing.clear()
    plan(StencilProblem("hotspot2d", (16, 256)),       # the run's own plan
         RunConfig(backend=cfg["backend"], autotune=cfg["autotune"]))
    (own,) = tracing.recorded()[:1]
    assert own.name == "stencil.plan.autotune"
    # interpret mode on the CPU: the kernel is the interpreter's loop, under
    # the same scope the chip's ``superstep_chain`` instruction carries
    found = {scopes.scope(p)
             for p in scopes.program_op_names(cell).values()}
    assert {"stencil.superstep", "stencil.halo_refresh",
            "stencil.unpad"} <= found
    # the plan made again for the names is not the run's
    assert [s.name for s in tracing.recorded()].count(
        "stencil.plan.autotune") == 2
    assert read("plan.autotune_s", cell) == \
        pytest.approx((own.end_ns - own.start_ns) / 1e9)
    tracing.clear()


def test_autotune_seconds_come_from_the_programs_span_record(monkeypatch):
    from repro import tracing
    from repro.api import RunConfig, StencilProblem, plan
    cell = types.SimpleNamespace()
    tracing.clear()
    assert read("plan.autotune_s", cell) is None
    plan(StencilProblem("diffusion2d", (32, 256)),
         RunConfig(backend="pallas_interpret", autotune="model"))
    (span,) = [s for s in tracing.recorded()
               if s.name == "stencil.plan.autotune"]
    assert read("plan.autotune_s", cell) == \
        pytest.approx((span.end_ns - span.start_ns) / 1e9)
    # a program older than its span record reads nothing and does not raise
    import repro
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert read("plan.autotune_s", cell) is None
    monkeypatch.undo()
    tracing.clear()
