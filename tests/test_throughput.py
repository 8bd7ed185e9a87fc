"""The throughput subsystem: ``run_batch``, the fused donated super-step
loop, and the process-level executable cache.

Acceptance surface of the serving PR:
  * ``run_batch`` is bit-identical to a Python loop of ``run()`` on every
    backend, including aux-stream (Hotspot) stencils with both shared and
    per-batch aux;
  * buffer donation never invalidates caller arrays — plans stay reusable;
  * an executable-cache hit serves a compiled program without re-tracing
    (observable via the trace-counter hook), and dynamic ``iters`` means a
    plan never re-traces for a new iteration count;
  * the Pallas backends reject unsupported dtypes at ``plan()`` time with
    the supported-dtype list (satellite bugfix);
  * ``perf_model.predict(batch=...)`` shares the read-only aux stream
    across the batch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (BackendProgram, RunConfig, StencilProblem, as_program,
                       clear_exec_cache, exec_cache_stats, plan,
                       register_backend)
from repro.core import STENCILS, default_coeffs
from repro.core.perf_model import TPU_V5E, predict
from repro.kernels.ref import oracle_run

DIMS2 = (12, 20)
DIMS3 = (7, 19, 17)
B = 3


def _data(name, dims, batch=None, seed=0):
    st = STENCILS[name]
    k = jax.random.PRNGKey(seed)
    shape = ((batch,) + dims) if batch else dims
    g = jax.random.uniform(k, shape, jnp.float32, 0.5, 2.0)
    aux = None
    if st.has_aux:
        aux = jax.random.uniform(jax.random.fold_in(k, 1), shape,
                                 jnp.float32, 0.0, 0.1)
    return g, aux


def _cfg(backend, **kw):
    kw.setdefault("par_time", 2)
    kw.setdefault("bsize", 16)
    return RunConfig(backend=backend, **kw)


# --- run_batch == loop of run(), bit-identical, every backend ----------------

@pytest.mark.parametrize("backend", ["reference", "engine",
                                     "pallas_interpret"])
@pytest.mark.parametrize("name,dims", [("diffusion2d", DIMS2),
                                       ("hotspot2d", DIMS2),
                                       ("hotspot3d", DIMS3)])
def test_run_batch_matches_sequential(backend, name, dims):
    st = STENCILS[name]
    gs, auxs = _data(name, dims, batch=B)
    c = default_coeffs(st)
    p = plan(StencilProblem(name, dims),
             _cfg(backend, bsize=16 if len(dims) == 2 else (12, 12)))
    got = p.run_batch(gs, 5, c, aux=auxs)
    want = jnp.stack([p.run(gs[i], 5, c,
                            aux=None if auxs is None else auxs[i])
                      for i in range(B)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_run_batch_shared_aux_matches_sequential():
    st = STENCILS["hotspot2d"]
    gs, _ = _data("hotspot2d", DIMS2, batch=B)
    _, aux = _data("hotspot2d", DIMS2, seed=7)
    c = default_coeffs(st)
    for backend in ("reference", "engine", "pallas_interpret"):
        p = plan(StencilProblem("hotspot2d", DIMS2), _cfg(backend))
        got = p.run_batch(gs, 4, c, aux=aux)           # one aux, whole batch
        want = jnp.stack([p.run(gs[i], 4, c, aux=aux) for i in range(B)])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_run_batch_distributed_matches_engine():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    gs, _ = _data("diffusion2d", (24, 40), batch=B)
    c = default_coeffs(STENCILS["diffusion2d"])
    problem = StencilProblem("diffusion2d", (24, 40))
    dist = plan(problem, RunConfig(backend="distributed", par_time=2,
                                   bsize=24, mesh=mesh))
    eng = plan(problem, RunConfig(backend="engine", par_time=2, bsize=24))
    np.testing.assert_allclose(np.asarray(dist.run_batch(gs, 5, c)),
                               np.asarray(eng.run_batch(gs, 5, c)),
                               rtol=2e-5, atol=2e-5)


def test_run_batch_iters_zero_is_identity_and_validates():
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    p = plan(StencilProblem("diffusion2d", DIMS2), _cfg("engine"))
    np.testing.assert_array_equal(np.asarray(p.run_batch(gs, 0)),
                                  np.asarray(gs))
    with pytest.raises(ValueError, match=r"\(B, \*"):
        p.run_batch(gs[0], 2)                     # missing batch axis
    with pytest.raises(ValueError, match=r"\(B, \*"):
        p.run_batch(gs[:, :-1], 2)                # wrong grid shape
    with pytest.raises(ValueError, match="takes no aux"):
        p.run_batch(gs, 2, aux=gs)
    hs, auxs = _data("hotspot2d", DIMS2, batch=B)
    ph = plan(StencilProblem("hotspot2d", DIMS2), _cfg("engine"))
    with pytest.raises(ValueError, match="needs an aux"):
        ph.run_batch(hs, 2)
    with pytest.raises(ValueError, match="aux shape"):
        ph.run_batch(hs, 2, aux=auxs[:, :-1])


def test_run_batch_fallback_for_unbatched_custom_backend():
    """A factory returning a bare ExecuteFn (no batched entry point) still
    serves run_batch through the per-element fallback loop."""
    calls = []

    def factory(problem, config, geom):
        def execute(grid, coeffs, iters, aux=None):
            calls.append(int(iters))
            return oracle_run(problem.stencil, grid, coeffs, iters, aux)
        return execute

    register_backend("test_unbatched", factory)
    try:
        st = STENCILS["hotspot2d"]
        gs, auxs = _data("hotspot2d", DIMS2, batch=B)
        c = default_coeffs(st)
        p = plan(StencilProblem("hotspot2d", DIMS2), _cfg("test_unbatched"))
        got = p.run_batch(gs, 3, c, aux=auxs)
        assert calls == [3] * B                   # fallback looped
        want = jnp.stack([oracle_run(st, gs[i], c, 3, auxs[i])
                          for i in range(B)])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    finally:
        from repro.api import backends
        backends._REGISTRY.pop("test_unbatched", None)


def test_as_program_normalizes_and_rejects():
    prog = as_program(lambda g, c, i, a: g)
    assert isinstance(prog, BackendProgram) and prog.execute_batch is None
    assert as_program(prog) is prog
    with pytest.raises(TypeError, match="callable or BackendProgram"):
        as_program(42)


# --- the fused loop's two carry buffers: bit-identical at every count --------

def _wave():
    from repro.api import StencilProgram, StencilStage
    from repro.core.stencils import make_combine, make_star
    lap = StencilStage(make_star(2, 1), name="lapu", inputs=("u",))
    unext = StencilStage(make_combine(2, 3), name="unext",
                         inputs=("u", "u_prev", "lapu"),
                         coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.1})
    return StencilProgram((lap, unext), fields=("u", "u_prev"),
                          updates={"u": "unext", "u_prev": "u"})


def _chain():
    from repro.api import StencilStage
    from repro.core.stencils import make_star
    return [StencilStage(make_star(2, 1)), StencilStage("diffusion2d")]


#: name -> (stencil or program, grid, boundary, par_time, bsize, par_vec,
#: batch)
LOOP_CASES = {
    "chain": (_chain, (24, 18), ("clamp", "reflect"), 2, 9, 1, None),
    "wave-dag": (_wave, (21, 40), "periodic", 2, 24, 8, None),
    "hotspot-aux": ("hotspot2d", (19, 60), "clamp", 2, 24, 1, None),
    "run_batch": ("hotspot2d", (13, 33), "reflect", 2, 16, 1, B),
    "periodic-stream-tail": ("diffusion2d", (21, 40), "periodic", 2, 24, 8,
                             None),
    "diffusion3d-constant": ("diffusion3d", (7, 19, 23), "constant:0.5", 2,
                             (12, 12), 1, None),
}


def _loop_vs_reference(stencil, dims, bc, par_time, bsize, par_vec, batch,
                       iters_list):
    """The ``pallas_interpret`` fused loop against the reference backend,
    bit for bit, from one seeded input, at each iteration count."""
    problem = StencilProblem(stencil, dims, boundary=bc)
    k = jax.random.PRNGKey(3)
    lead = (batch,) if batch else ()
    g = jax.random.uniform(k, lead + problem.state_shape, jnp.float32,
                           0.5, 2.0)
    aux = None
    if problem.needs_aux:
        aux = jax.random.uniform(jax.random.fold_in(k, 1),
                                 lead + problem.shape, jnp.float32, 0.0, 0.1)
    ref = plan(problem, RunConfig(backend="reference"))
    pal = plan(problem, RunConfig(backend="pallas_interpret",
                                  par_time=par_time, bsize=bsize,
                                  par_vec=par_vec))
    for iters in iters_list:
        run = "run_batch" if batch else "run"
        want = getattr(ref, run)(g, iters, aux=aux)
        got = getattr(pal, run)(g, iters, aux=aux)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"iters={iters}")
        if iters == 0:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(g))


@pytest.mark.parametrize("iters", [0, 1, 2, 4, 5, 6, 7, 8],
                         ids=["no-super-step", "one-partial", "one", "two",
                              "three-last-partial", "three", "four",
                              "four-last-partial"])
def test_fused_loop_every_super_step_count_matches_reference(iters):
    """par_time 2: no super-step (the identity), one, an odd and an even
    number of pairs' worth, with and without a partial last super-step —
    each branch of the loop that alternates the two carry buffers."""
    _loop_vs_reference("diffusion2d", (17, 40), "clamp", 2, 24, 1, None,
                       [iters])


@pytest.mark.parametrize("case", sorted(LOOP_CASES))
def test_fused_loop_programs_match_reference(case):
    """Chains, the multi-field wave DAG, HotSpot's aux stream, a batch axis
    (the one-buffer carry), and a periodic stream extension with ``par_vec``
    tail rows, each at an odd count with a partial last super-step, an odd
    and an even count of whole ones."""
    stencil, *rest = LOOP_CASES[case]
    _loop_vs_reference(stencil() if callable(stencil) else stencil, *rest,
                       [5, 6, 8])


# --- donation never poisons caller arrays ------------------------------------

@pytest.mark.parametrize("backend", ["engine", "pallas_interpret"])
def test_donation_does_not_poison_plan_reuse(backend):
    """The fused loop donates only the backend-owned padded carry; the
    caller's grid must survive run()/run_batch() and the plan must stay
    reusable for repeated calls on the same arrays."""
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    g = gs[0]
    snapshot = np.asarray(g).copy()
    p = plan(StencilProblem("diffusion2d", DIMS2), _cfg(backend))
    out1 = p.run(g, 3)
    out2 = p.run(g, 3)                            # same input array again
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    bat1 = p.run_batch(gs, 3)
    bat2 = p.run_batch(gs, 3)
    np.testing.assert_array_equal(np.asarray(bat1), np.asarray(bat2))
    np.testing.assert_array_equal(np.asarray(g), snapshot)   # never donated


# --- executable cache --------------------------------------------------------

def test_exec_cache_hit_avoids_retrace():
    clear_exec_cache()
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    cfg = _cfg("engine")
    problem = StencilProblem("diffusion2d", DIMS2)
    p1 = plan(problem, cfg)
    p1.run(gs[0], 2)
    p1.run_batch(gs, 2)
    s1 = exec_cache_stats()
    assert s1["misses"] >= 2 and s1["traces"]["engine"] >= 2
    # a second identical plan reuses both compiled programs: hits, no traces
    p2 = plan(problem, cfg)
    p2.run(gs[0], 2)
    p2.run_batch(gs, 2)
    s2 = exec_cache_stats()
    assert s2["hits"] >= 2
    assert s2["traces"] == s1["traces"]           # nothing re-traced
    assert s2["misses"] == s1["misses"]


def test_dynamic_iters_shares_one_executable():
    """iters is a dynamic scalar: new iteration counts reuse the trace."""
    clear_exec_cache()
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    p = plan(StencilProblem("diffusion2d", DIMS2), _cfg("engine"))
    p.run(gs[0], 2)
    traces = exec_cache_stats()["traces"].copy()
    for iters in (1, 3, 7, 64):
        p.run(gs[0], iters)
    assert exec_cache_stats()["traces"] == traces


def test_exec_cache_key_separates_geometry():
    clear_exec_cache()
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    problem = StencilProblem("diffusion2d", DIMS2)
    plan(problem, _cfg("engine")).run(gs[0], 2)
    size1 = exec_cache_stats()["size"]
    plan(problem, RunConfig(backend="engine", par_time=1, bsize=16)
         ).run(gs[0], 2)                          # different schedule
    assert exec_cache_stats()["size"] > size1


def test_exec_cache_opt_out():
    clear_exec_cache()
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    problem = StencilProblem("diffusion2d", DIMS2)
    cfg = _cfg("engine", exec_cache=False)
    plan(problem, cfg).run(gs[0], 2)
    plan(problem, cfg).run(gs[0], 2)
    s = exec_cache_stats()
    assert s["size"] == 0 and s["hits"] == 0 and s["misses"] == 0
    assert s["traces"]["engine"] == 2             # private executables


def test_exec_cache_opt_out_still_memoizes_within_a_plan():
    """exec_cache=False means *private* programs, not re-trace-per-call: a
    plan must keep its own built executables across run/run_batch calls."""
    clear_exec_cache()
    gs, _ = _data("diffusion2d", DIMS2, batch=B)
    p = plan(StencilProblem("diffusion2d", DIMS2),
             _cfg("engine", exec_cache=False))
    for iters in (2, 5, 2):
        p.run(gs[0], iters)
        p.run_batch(gs, iters)
    traces = exec_cache_stats()["traces"]
    assert traces["engine"] == 2                  # one single + one batched


# --- satellite bugfix: plan-time dtype validation ----------------------------

@pytest.mark.parametrize("backend", ["pallas", "pallas_interpret"])
def test_pallas_rejects_unsupported_dtype_at_plan_time(backend):
    # bf16 joined the supported set (bf16 storage + f32 accumulation); f16
    # remains unsupported and must still fail at plan time, naming what IS
    problem = StencilProblem("diffusion2d", DIMS2, dtype="float16")
    with pytest.raises(ValueError) as ei:
        plan(problem, _cfg(backend))
    msg = str(ei.value)
    assert "float32" in msg and "bfloat16" in msg   # names what IS supported
    assert "float16" in msg


@pytest.mark.parametrize("backend", ["pallas_interpret"])
def test_pallas_accepts_bf16_at_plan_time(backend):
    problem = StencilProblem("diffusion2d", DIMS2, dtype="bfloat16")
    p = plan(problem, _cfg(backend))          # must not raise
    assert p.problem.dtype == "bfloat16"


# --- perf model: batch dimension ---------------------------------------------

def test_predict_batch_shares_aux_stream():
    st = STENCILS["hotspot2d"]
    dims, bsize, pt = (512, 512), (256,), 4
    one = predict(st, dims, 64, bsize, pt, TPU_V5E)
    four = predict(st, dims, 64, bsize, pt, TPU_V5E, batch=4)
    # aux (power) loads are shared: batched bytes < 4x single-problem bytes
    assert one.t_mem * 4 > four.t_mem > one.t_mem
    assert four.t_compute == pytest.approx(4 * one.t_compute)
    assert four.batch == 4
    # a stencil without aux scales memory exactly linearly
    st2 = STENCILS["diffusion2d"]
    one2 = predict(st2, dims, 64, bsize, pt, TPU_V5E)
    four2 = predict(st2, dims, 64, bsize, pt, TPU_V5E, batch=4)
    assert four2.t_mem == pytest.approx(4 * one2.t_mem)
    with pytest.raises(ValueError, match="batch"):
        predict(st, dims, 64, bsize, pt, TPU_V5E, batch=0)


def test_predict_batch_scales_halo_bytes():
    st = STENCILS["diffusion2d"]
    one = predict(st, (100, 512), 64, (256,), 4, TPU_V5E, n_chips=2,
                  chip_grid=(2, 1))
    four = predict(st, (100, 512), 64, (256,), 4, TPU_V5E, n_chips=2,
                   chip_grid=(2, 1), batch=4)
    assert four.t_halo == pytest.approx(4 * one.t_halo)


def test_plan_predicted_accepts_batch():
    p = plan(StencilProblem("diffusion2d", (2048, 2048)),
             RunConfig(backend="engine", autotune=True))
    single = p.predicted(100)
    batched = p.predicted(100, batch=8)
    assert batched.gcells_s >= single.gcells_s    # amortization never hurts
    assert batched.batch == 8
