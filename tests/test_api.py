"""The public plan/execute API: StencilProblem -> plan() -> StencilPlan.

Covers the acceptance surface of the API redesign: cross-backend equivalence
through one ``plan()`` call, plan reuse across iteration counts, perf-model
autotuning under the VMEM budget, the ``stencil_run`` deprecation shim, the
backend registry, and the small-grid autotune regression.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (RunConfig, StencilPlan, StencilProblem, get_backend,
                       list_backends, plan, register_backend)
from repro.core import STENCILS, default_coeffs
from repro.core.blocking import bsize_feasible, choose_bsize_candidates
from repro.core.perf_model import TPU_V5E, autotune
from repro.kernels.ref import oracle_run


def _data(stencil, dims, seed=0):
    k = jax.random.PRNGKey(seed)
    g = jax.random.uniform(k, dims, jnp.float32, 0.5, 2.0)
    aux = None
    if stencil.has_aux:
        aux = jax.random.uniform(jax.random.fold_in(k, 1), dims,
                                 jnp.float32, 0.0, 0.1)
    return g, aux


# --- cross-backend equivalence (acceptance criterion) -------------------------

@pytest.mark.parametrize("name,dims,par_time,bsize", [
    ("diffusion2d", (23, 49), 2, 24),
    ("hotspot3d", (7, 19, 17), 2, 12),
])
def test_plan_roundtrip_across_backends(name, dims, par_time, bsize):
    st = STENCILS[name]
    g, aux = _data(st, dims)
    c = default_coeffs(st)
    problem = StencilProblem(name, dims)
    cfg = RunConfig(par_time=par_time, bsize=bsize)
    outs = {}
    for backend in ("reference", "engine", "pallas_interpret"):
        p = plan(problem, dataclasses.replace(cfg, backend=backend))
        assert isinstance(p, StencilPlan)
        outs[backend] = p.run(g, 5, c, aux=aux)
    for backend in ("engine", "pallas_interpret"):
        np.testing.assert_allclose(np.asarray(outs[backend]),
                                   np.asarray(outs["reference"]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", ["star1d_r1", "star1d_r2"])
@pytest.mark.parametrize("bc", ["clamp", "periodic", "reflect"])
def test_1d_plan_roundtrip_across_backends(name, bc):
    """Satellite: 1D problems (stream axis only, no blocked dims) plan and
    run on every local backend, matching the oracle."""
    st = STENCILS[name]
    dims = (97,)
    g, _ = _data(st, dims)
    problem = StencilProblem(name, dims, boundary=bc)
    want = oracle_run(st, g, default_coeffs(st), 5, bc=problem.bc)
    for backend in ("reference", "engine", "pallas_interpret"):
        p = plan(problem, RunConfig(backend=backend, par_time=2))
        np.testing.assert_allclose(np.asarray(p.run(g, 5)),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)


def test_1d_autotune_and_batch():
    """1D geometry candidates are the trivial `()` bsize; autotune still
    ranks par_time/par_vec and run_batch round-trips."""
    problem = StencilProblem("star1d_r1", (128,))
    assert choose_bsize_candidates(1, problem.shape) == [()]
    p = plan(problem, RunConfig(backend="pallas_interpret", autotune=True))
    assert p.geometry is not None and p.geometry.ndim == 1
    g, _ = _data(STENCILS["star1d_r1"], (128,))
    gs = jnp.stack([g, g * 0.5])
    want = jnp.stack([oracle_run(STENCILS["star1d_r1"], gs[i],
                                 default_coeffs(STENCILS["star1d_r1"]), 3)
                      for i in range(2)])
    np.testing.assert_allclose(np.asarray(p.run_batch(gs, 3)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_distributed_plan_single_device_mesh_matches_engine():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (24, 40))
    c = default_coeffs(st)
    problem = StencilProblem("diffusion2d", (24, 40))
    cfg = RunConfig(backend="distributed", par_time=2, bsize=24, mesh=mesh)
    dist = plan(problem, cfg).run(g, 5, c)
    eng = plan(problem, RunConfig(backend="engine", par_time=2, bsize=24)
               ).run(g, 5, c)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(eng),
                               rtol=2e-5, atol=2e-5)


# --- plan reuse ---------------------------------------------------------------

def test_plan_reuse_across_iters():
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (19, 37))
    c = default_coeffs(st)
    p = plan(StencilProblem("diffusion2d", (19, 37)),
             RunConfig(backend="engine", par_time=2, bsize=24))
    for iters in (1, 3, 4, 9):
        want = oracle_run(st, g, c, iters)
        got = p.run(g, iters, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    # iters=0 is the identity
    np.testing.assert_array_equal(np.asarray(p.run(g, 0, c)), np.asarray(g))


# --- autotune -----------------------------------------------------------------

def test_autotune_selects_vmem_feasible_config():
    p = plan(StencilProblem("diffusion2d", (2048, 2048)),
             RunConfig(backend="engine", autotune=True))
    geom = p.geometry
    assert geom is not None
    assert min(geom.csize) > 0
    st = STENCILS["diffusion2d"]
    assert geom.vmem_bytes(4, st.has_aux) <= TPU_V5E.vmem_budget
    # the plan can introspect itself without running
    pred = p.predicted(100)
    assert pred.run_time > 0
    report = p.traffic_report(iters=100)
    assert report["traffic_accuracy"] > 0
    assert "bsize" in p.describe() or "schedule" in p.describe()


def test_autotune_respects_pinned_par_time():
    p = plan(StencilProblem("diffusion2d", (2048, 2048)),
             RunConfig(backend="engine", par_time=4, autotune=True))
    assert p.geometry.par_time == 4


def test_autotune_exposes_ranked_candidates():
    p = plan(StencilProblem("diffusion2d", (2048, 2048)),
             RunConfig(backend="engine", autotune=True))
    assert len(p.candidates) >= 2
    runtimes = [c.run_time for c in p.candidates]
    assert runtimes == sorted(runtimes)
    assert p.candidates[0].geom.bsize == p.geometry.bsize
    assert p.candidates[0].geom.par_time == p.geometry.par_time
    # pinned schedule -> nothing was swept
    pinned = plan(StencilProblem("diffusion2d", (2048, 2048)),
                  RunConfig(backend="engine", par_time=2, bsize=256))
    assert pinned.candidates == ()


def test_reference_plan_tolerates_unresolvable_schedule():
    """The oracle ignores blocking: an infeasible schedule degrades the plan
    to geometry-less instead of raising (legacy stencil_run semantics)."""
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (32, 48))
    c = default_coeffs(st)
    # par_time=128 on a 48-wide grid: no feasible bsize exists
    p = plan(StencilProblem("diffusion2d", (32, 48)),
             RunConfig(backend="reference", par_time=128))
    assert p.geometry is None
    got = p.run(g, 3, c)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(oracle_run(st, g, c, 3)))
    with pytest.raises(ValueError, match="needs a block geometry"):
        p.predicted()


def test_distributed_axis_map_accepts_bare_string_names():
    """A multi-char axis name given as a bare string is one axis, not a
    sequence of single-character names."""
    cfg = RunConfig(backend="distributed", axis_map=("data", None))
    assert cfg.axis_map == (("data",), None)


class _FakeMesh:
    """Mesh stand-in: plan-time checks only touch axis_names/devices.shape,
    so an indivisible multi-chip layout is testable on one real device."""
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.zeros(shape)


def test_distributed_plan_rejects_indivisible_grid_at_plan_time():
    """Satellite bugfix: the divisibility error must fire at plan() — not
    deep inside build_distributed_fn at the first run()."""
    mesh = _FakeMesh((3,), ("x",))
    with pytest.raises(ValueError, match="not divisible"):
        plan(StencilProblem("diffusion2d", (25, 40)),
             RunConfig(backend="distributed", par_time=2, bsize=24,
                       mesh=mesh))
    # divisible grids still plan fine (execution is deferred)
    p = plan(StencilProblem("diffusion2d", (24, 40)),
             RunConfig(backend="distributed", par_time=2, bsize=24,
                       mesh=mesh))
    assert p.n_chips == 3


def test_predict_halo_follows_chip_grid():
    """Satellite bugfix: t_halo must price the face perpendicular to each
    sharded axis, not always the streaming-axis cross-section."""
    from repro.core.perf_model import TPU_V5E, predict
    st = STENCILS["diffusion2d"]
    dims, bsize, pt = (100, 512), (256,), 4
    h = st.radius * pt
    # shard the *blocked* axis: local dims (100, 256); exchanged strips have
    # cross-section 100 (the streaming extent), width h, both directions
    p = predict(st, dims, 64, bsize, pt, TPU_V5E, 4, n_chips=2,
                chip_grid=(1, 2))
    want = 2 * (h * 100) * 4 * st.num_read / TPU_V5E.ici_bw
    assert p.t_halo == pytest.approx(want)
    # streaming-axis sharding keeps the legacy form: cross-section 512
    p0 = predict(st, dims, 64, bsize, pt, TPU_V5E, 4, n_chips=2,
                 chip_grid=(2, 1))
    want0 = 2 * (h * 512) * 4 * st.num_read / TPU_V5E.ici_bw
    assert p0.t_halo == pytest.approx(want0)
    # a 2x2 grid on a 3D problem sums one face per sharded axis
    st3 = STENCILS["diffusion3d"]
    p3 = predict(st3, (64, 64, 64), 64, (16, 16), 2, TPU_V5E, 4, n_chips=4,
                 chip_grid=(1, 2, 2))
    h3 = st3.radius * 2
    faces = 64 * 32 + 64 * 32          # perp. to y and to x, local (64,32,32)
    assert p3.t_halo == pytest.approx(2 * h3 * faces * 4 * st3.num_read
                                      / TPU_V5E.ici_bw)


# --- deprecation shim ---------------------------------------------------------

def test_stencil_run_shim_warns_and_matches():
    from repro.kernels.ops import stencil_run
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (21, 45))
    c = default_coeffs(st)
    p = plan(StencilProblem("diffusion2d", (21, 45)),
             RunConfig(backend="engine", par_time=2, bsize=24))
    want = p.run(g, 5, c)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = stencil_run(st, g, c, 5, 2, 24, backend="engine")
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stencil_run_shim_preserves_dtype():
    """Legacy stencil_run was dtype-generic; the shim must not coerce."""
    from repro.kernels.ops import stencil_run
    st = STENCILS["diffusion2d"]
    g = jnp.ones((12, 20), jnp.bfloat16)
    c = {k: jnp.asarray(v, jnp.bfloat16)
         for k, v in default_coeffs(st).items()}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        got = stencil_run(st, g, c, 2, 1, 8, backend="engine")
    assert got.dtype == jnp.bfloat16


def test_stencil_run_shim_reference_ignores_bad_geometry():
    """Legacy behavior: the oracle path never validated (par_time, bsize)."""
    from repro.kernels.ops import stencil_run
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (12, 20))
    c = default_coeffs(st)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        got = stencil_run(st, g, c, 3, 16, 8, backend="reference")
    want = oracle_run(st, g, c, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- backend registry ---------------------------------------------------------

def test_registry_lists_builtins():
    have = list_backends()
    for name in ("reference", "engine", "pallas", "pallas_interpret",
                 "distributed"):
        assert name in have


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        plan(StencilProblem("diffusion2d", (16, 16)),
             RunConfig(backend="no_such_backend", par_time=1, bsize=8))


def test_register_custom_backend():
    calls = []

    def doubling_oracle(problem, config, geom):
        def execute(grid, coeffs, iters, aux=None):
            calls.append(iters)
            return oracle_run(problem.stencil, grid, coeffs, iters, aux)
        return execute

    register_backend("test_custom", doubling_oracle)
    try:
        assert get_backend("test_custom") is doubling_oracle
        with pytest.raises(ValueError, match="already registered"):
            register_backend("test_custom", doubling_oracle)
        st = STENCILS["diffusion2d"]
        g, _ = _data(st, (11, 17))
        c = default_coeffs(st)
        p = plan(StencilProblem("diffusion2d", (11, 17)),
                 RunConfig(backend="test_custom", par_time=1, bsize=8))
        got = p.run(g, 2, c)
        assert calls == [2]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(oracle_run(st, g, c, 2)))
    finally:
        from repro.api import backends
        backends._REGISTRY.pop("test_custom", None)


# --- problem/config validation ------------------------------------------------

def test_problem_validation():
    with pytest.raises(ValueError, match="unknown stencil"):
        StencilProblem("nope", (8, 8))
    with pytest.raises(ValueError, match="2D but shape"):
        StencilProblem("diffusion2d", (8, 8, 8))
    with pytest.raises(ValueError, match="boundary"):
        StencilProblem("diffusion2d", (8, 8), boundary="bogus")
    # periodic (and friends) are first-class now — see
    # tests/test_boundary_conditions.py for the conformance matrix
    assert StencilProblem("diffusion2d", (8, 8),
                          boundary="periodic").bc.token() == "periodic"
    with pytest.raises(ValueError, match="aux"):
        StencilProblem("diffusion2d", (8, 8), aux=True)


def test_run_validates_inputs():
    p = plan(StencilProblem("hotspot2d", (16, 24)),
             RunConfig(backend="engine", par_time=1, bsize=8))
    g, aux = _data(STENCILS["hotspot2d"], (16, 24))
    with pytest.raises(ValueError, match="needs an aux"):
        p.run(g, 2)
    with pytest.raises(ValueError, match="grid shape"):
        p.run(g[:-1], 2, aux=aux)
    with pytest.raises(ValueError, match="aux shape"):
        p.run(g, 2, aux=aux[:-1])


# --- small-grid autotune regression (satellite) -------------------------------

def test_candidates_small_grid_high_par_time():
    """256-wide 2D grid at high par_time: infeasible candidates are dropped
    instead of raising inside BlockGeometry (csize would be <= 0)."""
    # the only raw 2D candidate for a 256-wide grid is bsize=(256,)
    assert choose_bsize_candidates(2, (256, 256)) == [(256,)]
    # at par_time=128 its halo (128) swallows the block: csize <= 0
    assert not bsize_feasible(1, 128, (256,))
    assert choose_bsize_candidates(2, (256, 256), rad=1, par_time=128) == []
    # autotune sweeps high par_time without ever building a bad geometry
    cands = autotune(STENCILS["diffusion2d"], (256, 256), 64,
                     par_time_max=512)
    assert cands, "feasible low-par_time configs must survive"
    for pred in cands:
        assert min(pred.geom.csize) > 0
    # and plan(autotune=True) on the small grid picks one of them
    p = plan(StencilProblem("diffusion2d", (256, 256)),
             RunConfig(backend="engine", autotune=True, par_time_max=512))
    assert min(p.geometry.csize) > 0


def test_plan_errors_clearly_when_nothing_feasible():
    with pytest.raises(ValueError, match="no VMEM-feasible"):
        plan(StencilProblem("diffusion2d", (256, 256)),
             RunConfig(backend="engine", autotune=True, par_time=128))


# --- the chip decides: device model, tiles, no interpret knob ----------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_device_resolves_from_attached_device_kind(monkeypatch):
    from repro.core import perf_model

    def attach(platform, kind):
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [_FakeDevice(platform, kind)])
    attach("tpu", "TPU v5 lite")
    assert RunConfig().resolved_device() is perf_model.TPU_V5E
    attach("tpu", "TPU v6 lite")
    assert RunConfig().resolved_device() is perf_model.TPU_V6E
    attach("tpu", "TPU v99")            # unknown chips are an error
    with pytest.raises(ValueError, match="TPU v99"):
        RunConfig().resolved_device()
    attach("cpu", "cpu")                # no chip: the named model target
    assert RunConfig().resolved_device().name == perf_model.DEFAULT_TARGET
    assert RunConfig(device="tpu_v5p").resolved_device() is perf_model.TPU_V5P
    kinds = [d.kind for d in perf_model.DEVICES.values()]
    assert len(set(kinds)) == len(kinds)
    assert all(d.source for d in perf_model.DEVICES.values())


def test_pallas_has_no_interpret_switch():
    import dataclasses
    assert "interpret" not in {f.name for f in dataclasses.fields(RunConfig)}
    with pytest.raises(TypeError):
        RunConfig(backend="pallas", interpret=True)


def test_pallas_plan_is_tile_aligned():
    p = plan(StencilProblem("diffusion2d", (64, 512)),
             RunConfig(backend="pallas", par_time=2, bsize=512))
    assert p.geometry.align == (128,) and p.geometry.pad == (128,)
    assert p.geometry.par_vec == 8          # the f32 sublane tile
    with pytest.raises(ValueError, match="par_vec=4"):
        plan(StencilProblem("diffusion2d", (64, 512)),
             RunConfig(backend="pallas", par_time=2, bsize=512, par_vec=4))
    with pytest.raises(ValueError, match="too small"):
        plan(StencilProblem("diffusion2d", (64, 512)),
             RunConfig(backend="pallas", par_time=2, bsize=256))
    # interpret mode runs the geometry as given
    q = plan(StencilProblem("diffusion2d", (64, 512)),
             RunConfig(backend="pallas_interpret", par_time=2, bsize=256))
    assert q.geometry.align == () and q.geometry.pad == (2,)


def test_compile_cache_follows_env_var(monkeypatch, tmp_path):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # sets nothing
    root = compile_cache.CHECKOUT_CACHE.parent
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (root / "src" / "repro" / "compile_cache.py").is_file()
