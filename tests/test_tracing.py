"""The span recorder (``repro.tracing``) and the host spans of the solve path.

The device scopes (``stencil.superstep``, ``stencil.halo_refresh``, ...)
are checked in the compiled program for a described v5e, in
``tests/test_tpu_compile.py``.
"""
import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.api import RunConfig, StencilProblem, plan


@pytest.fixture(autouse=True)
def empty_record():
    tracing.clear()
    yield
    tracing.clear()


def test_nested_spans_name_their_parent_and_time_inside_it():
    with tracing.span("outer"):
        with tracing.span("inner"):
            pass
        with tracing.span("inner2"):
            pass
    inner, inner2, outer = tracing.recorded()
    assert (inner.name, inner.parent) == ("inner", "outer")
    assert (inner2.name, inner2.parent) == ("inner2", "outer")
    assert (outer.name, outer.parent) == ("outer", None)
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= inner2.start_ns <= inner2.end_ns <= outer.end_ns


def test_span_as_decorator_records_each_call_and_survives_a_raise():
    @tracing.span("call")
    def f(x):
        if x < 0:
            raise ValueError("negative")
        return 2 * x

    assert f(3) == 6
    with pytest.raises(ValueError):
        f(-1)
    assert [s.name for s in tracing.recorded()] == ["call", "call"]
    # the raise closed the span: a later one has no stale parent
    with tracing.span("after"):
        pass
    assert tracing.recorded()[-1].parent is None


def test_parents_are_per_thread():
    seen = []

    def worker():
        with tracing.span("in_thread"):
            pass
        seen.append(True)

    with tracing.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive() and seen
    by_name = {s.name: s for s in tracing.recorded()}
    assert by_name["in_thread"].parent is None
    assert by_name["main"].parent is None


def test_record_is_bounded_and_keeps_the_newest():
    n = tracing.MAX_SPANS + 10
    for i in range(n):
        with tracing.span(f"s{i}"):
            pass
    spans = tracing.recorded()
    assert len(spans) == tracing.MAX_SPANS
    assert spans[0].name == "s10" and spans[-1].name == f"s{n - 1}"
    tracing.clear()
    assert tracing.recorded() == []


def _plan_and_run():
    problem = StencilProblem("diffusion2d", (32, 256))
    p = plan(problem, RunConfig(backend="pallas_interpret", par_time=2,
                                bsize=128))
    g = jnp.ones(problem.shape, jnp.float32)
    p.run(g, 3).block_until_ready()
    p.run_batch(jnp.stack([g, g]), 3).block_until_ready()


def test_plan_and_run_record_their_spans_without_a_profiler():
    _plan_and_run()
    spans = tracing.recorded()
    parents = {s.name: s.parent for s in spans}
    assert parents["stencil.plan.autotune"] == "stencil.plan"
    assert parents["stencil.plan.build"] == "stencil.plan"
    assert parents["stencil.plan"] is None
    assert parents["stencil.run"] is None
    assert parents["stencil.run_batch"] is None


def test_host_spans_land_in_a_profiler_trace_beside_bench_spans(tmp_path):
    from jax.profiler import ProfileData
    _plan_and_run()                          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        _plan_and_run()
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    names = {n for n, _, _ in events}
    assert {"bench.window", "stencil.plan", "stencil.plan.autotune",
            "stencil.plan.build", "stencil.run",
            "stencil.run_batch"} <= names
    (lo, hi), = [(s, e) for n, s, e in events if n == "bench.window"]
    assert all(lo <= s <= e <= hi for n, s, e in events
               if n.startswith("stencil."))
