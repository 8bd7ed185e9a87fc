"""Cross-backend boundary-condition conformance suite.

The ``kernels/ref.py`` oracle is the single source of truth for every BC
(clamp / periodic / reflect / constant, per-axis mixes included); this file
locks every backend to it:

  * an independent numpy re-derivation pins the oracle itself,
  * a parametrized matrix checks reference / engine / pallas_interpret for
    2D and 3D stencils at radius 1 and 2 (plus a box stencil, whose corner
    reads exercise the mixed-BC corner semantics),
  * the distributed backend runs the same matrix on a 2-device mesh in a
    subprocess (``bc_distributed_check.py``),
  * ``run_batch`` and both aux (power-grid) modes are covered,
  * the schedule cache and the executable cache must key on the BC — a
    schedule tuned under clamp is never served to a periodic plan,
  * negative paths: unknown kinds, wrong arity, non-scalar constant fills,
    reflect on degenerate axes, periodic vs. mesh divisibility.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (RunConfig, StencilProblem, clear_exec_cache,
                       exec_cache_stats, plan)
from repro.core import STENCILS, default_coeffs, make_box, make_star
from repro.core.boundary import BoundaryCondition
from repro.core.stencils import Stencil
from repro.kernels.ref import oracle_run

BACKENDS = ("reference", "engine", "pallas_interpret")

#: the BC matrix: every kind uniformly, plus per-axis mixes (incl. the
#: ISSUE's periodic-in-x/clamp-in-y example and a constant mix)
BCS_2D = ["clamp", "periodic", "reflect", "constant:0.7",
          ("clamp", "periodic"), ("reflect", "periodic"),
          ("constant:2.0", "reflect")]
BCS_3D = ["periodic", "reflect", "constant:0.3",
          ("clamp", "periodic", "reflect"),
          ("periodic", "constant:1.0", "clamp")]


def _data(st, dims, seed=0):
    k = jax.random.PRNGKey(seed)
    g = jax.random.uniform(k, dims, jnp.float32, 0.5, 2.0)
    aux = (jax.random.uniform(jax.random.fold_in(k, 7), dims,
                              jnp.float32, 0.0, 0.1)
           if st.has_aux else None)
    return g, aux


def _conform(st, dims, bc_spec, backend, par_time=2, bsize=16, iters=5):
    problem = StencilProblem(st, dims, boundary=bc_spec)
    g, aux = _data(st, dims)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, iters, aux, bc=problem.bc)
    p = plan(problem, RunConfig(backend=backend, par_time=par_time,
                                bsize=bsize))
    got = p.run(g, iters, c, aux=aux)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5,
        err_msg=f"{st.name} {backend} bc={problem.bc.token()}")


# --- the oracle itself, pinned by an independent numpy re-derivation ---------

def _np_oracle_step(st, grid, coeffs, aux, bc):
    """Ground truth for the ground truth: numpy per-axis np.pad."""
    modes = {"clamp": "edge", "periodic": "wrap", "reflect": "reflect"}
    r = st.radius
    p = np.asarray(grid)
    for ax, kind in enumerate(bc.kinds):
        pads = [(0, 0)] * p.ndim
        pads[ax] = (r, r)
        if kind == "constant":
            p = np.pad(p, pads, mode="constant", constant_values=bc.value)
        else:
            p = np.pad(p, pads, mode=modes[kind])

    def get(off):
        idx = tuple(slice(r + o, r + o + n) for o, n in zip(off, grid.shape))
        return jnp.asarray(p[idx])

    return st.apply(get, coeffs, aux)


@pytest.mark.parametrize("bc_spec", BCS_2D)
def test_oracle_matches_numpy_2d(bc_spec):
    st = STENCILS["diffusion2d"]
    bc = BoundaryCondition.make(bc_spec, 2)
    g, _ = _data(st, (9, 13))
    c = default_coeffs(st)
    want = _np_oracle_step(st, np.asarray(g), c, None, bc)
    got = oracle_run(st, g, c, 1, bc=bc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_oracle_matches_numpy_3d_box_corners():
    """A box stencil reads corner neighbors: the mixed-BC corner semantics
    (per-axis rules compose; constant absorbs) must match numpy padding."""
    st = make_box(3, 1)
    bc = BoundaryCondition.make(("periodic", "constant:1.5", "reflect"), 3)
    g, _ = _data(st, (5, 6, 7))
    c = default_coeffs(st)
    want = _np_oracle_step(st, np.asarray(g), c, None, bc)
    got = oracle_run(st, g, c, 1, bc=bc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# --- conformance matrix: BC x backend x {2D,3D} x radius ---------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bc_spec", BCS_2D)
def test_conformance_2d_radius1(bc_spec, backend):
    _conform(STENCILS["diffusion2d"], (23, 49), bc_spec, backend)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bc_spec", ["periodic", ("reflect", "periodic")])
def test_conformance_2d_aux(bc_spec, backend):
    """Hotspot: the aux (power) stream rides through every BC pad path."""
    _conform(STENCILS["hotspot2d"], (17, 33), bc_spec, backend)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bc_spec", BCS_3D)
def test_conformance_3d_radius1(bc_spec, backend):
    _conform(STENCILS["diffusion3d"], (9, 21, 17), bc_spec, backend,
             bsize=(8, 8))


@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_3d_aux_mix(backend):
    _conform(STENCILS["hotspot3d"], (7, 19, 17),
             ("reflect", "periodic", "constant:1.0"), backend, bsize=(8, 8))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bc_spec", ["periodic", ("reflect", "periodic"),
                                     "constant:0.4"])
def test_conformance_2d_radius2(bc_spec, backend):
    _conform(make_star(2, 2), (21, 41), bc_spec, backend, par_time=2,
             bsize=24)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bc_spec", ["periodic",
                                     ("reflect", "periodic", "periodic")])
def test_conformance_3d_radius2(bc_spec, backend):
    _conform(make_star(3, 2), (9, 25, 25), bc_spec, backend, par_time=1,
             bsize=(12, 12))


@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_box_corners(backend):
    """Box neighborhoods read diagonal (corner) ghosts — the strictest test
    of mixed-BC corner composition on a real execution path."""
    _conform(make_box(2, 1), (15, 37), ("periodic", "reflect"), backend)


# --- run_batch: the serving path honors the BC too ---------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_run_batch_conformance(backend):
    st = STENCILS["hotspot2d"]
    dims = (16, 32)
    problem = StencilProblem(st, dims, boundary=("periodic", "reflect"))
    g, aux = _data(st, dims)
    gs = jnp.stack([g, g * 1.1, g * 0.9])
    c = default_coeffs(st)
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=16))
    want = jnp.stack([oracle_run(st, gs[i], c, 4, aux, bc=problem.bc)
                      for i in range(3)])
    got = p.run_batch(gs, 4, c, aux=aux)             # shared aux
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    auxs = jnp.stack([aux, aux * 2.0, aux * 0.5])    # batched aux
    want_b = jnp.stack([oracle_run(st, gs[i], c, 4, auxs[i], bc=problem.bc)
                        for i in range(3)])
    got_b = p.run_batch(gs, 4, c, aux=auxs)
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(want_b),
                               rtol=3e-5, atol=3e-5)


# --- distributed backend: 2-device mesh, in a subprocess ---------------------

@pytest.mark.slow
def test_distributed_conformance_2dev():
    script = os.path.join(os.path.dirname(__file__),
                          "bc_distributed_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "ALL OK" in out.stdout


# --- seam regression: stream-only stencil (radius 0 in the blocked axes) ----

def _stream_only_2d():
    """1D 3-point star embedded in 2D: offsets only along the streaming
    axis, so blocked-dim halos are never read — the zero-coupling seam case
    behind the ``_reclamp_padded`` zero-pad guard."""
    def apply(get, c, aux=None):
        return (c["c0"] * get((0, 0)) + c["cm"] * get((-1, 0))
                + c["cp"] * get((1, 0)))
    return Stencil("stream1d_in2d", 2, 1, 5, 1, 1, False,
                   ("c0", "cm", "cp"), apply,
                   offsets=((0, 0), (-1, 0), (1, 0)))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bc_spec", ["periodic", "constant:0.6",
                                     ("reflect", "periodic")])
def test_stream_only_stencil_seams(bc_spec, backend):
    st = _stream_only_2d()
    c = {"c0": jnp.float32(0.5), "cm": jnp.float32(0.25),
         "cp": jnp.float32(0.25)}
    problem = StencilProblem(st, (19, 33), boundary=bc_spec)
    g, _ = _data(st, (19, 33))
    want = oracle_run(st, g, c, 5, bc=problem.bc)
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=16))
    got = p.run(g, 5, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_reclamp_padded_skips_zero_pad_axes():
    """With a zero halo (radius-0 stencil) the padded carry equals the grid:
    the refresh must be an exact no-op — in particular the constant BC must
    NOT treat real edge columns as ghost positions."""
    from repro.core.blocking import BlockGeometry
    from repro.kernels.ops import _reclamp_padded
    st0 = make_star(2, 0)           # pure scaling stencil: radius 0
    geom = BlockGeometry(2, (6, 32), st0.radius, 4, (16,))
    assert geom.size_halo == 0 and geom.padded_dims == (32,)
    gp = jnp.arange(6 * 32, dtype=jnp.float32).reshape(6, 32)
    bc = BoundaryCondition.make("constant:9.0", 2)
    np.testing.assert_array_equal(np.asarray(_reclamp_padded(gp, geom, bc)),
                                  np.asarray(gp))


#: (grid dims, par_time, bsize, par_vec, BC, batch): every kind, a mix, 2D
#: and 3D, overhang (``bnum * csize > d``: 13 -> 16, 9 -> 12), a periodic
#: stream extension with ``par_vec`` tail rows (10 + 2*2 -> 16), and halos
#: wider than their domain (h = 4 over 3 cells, stream extension 4 over 3)
RECLAMP_CASES = [
    ((6, 13), 2, (8,), 1, "clamp", ()),
    ((6, 13), 2, (8,), 1, "reflect", ()),
    ((6, 13), 2, (8,), 1, "constant:2.5", ()),
    ((10, 13), 2, (8,), 4, "periodic", ()),
    ((10, 13), 2, (8,), 4, ("periodic", "reflect"), (2,)),
    ((6, 13), 2, (8,), 1, ("constant:2.0", "reflect"), (3,)),
    ((5, 9, 13), 2, (8, 8), 1, "clamp", ()),
    ((5, 9, 13), 2, (8, 8), 1, "constant:0.3", (2,)),
    ((7, 9, 13), 2, (8, 8), 2, ("periodic", "constant:1.5", "reflect"), ()),
    ((7, 9, 13), 2, (8, 8), 2, ("clamp", "periodic", "reflect"), (2,)),
    ((5, 3), 4, (10,), 1, "periodic", ()),
    ((5, 3), 4, (10,), 1, "reflect", ()),
    ((3, 3, 3), 4, (10, 10), 1, "periodic", ()),
    ((4, 3, 3), 4, (10, 10), 1, ("clamp", "reflect", "periodic"), (2,)),
]


@pytest.mark.parametrize(
    "dims,par_time,bsize,par_vec,bc_spec,batch", RECLAMP_CASES,
    ids=[f"{'x'.join(map(str, c[0]))}-T{c[1]}-V{c[3]}-{c[4]}-b{len(c[5])}"
         for c in RECLAMP_CASES])
def test_reclamp_padded_equals_repad(dims, par_time, bsize, par_vec, bc_spec,
                                     batch):
    """The in-place halo refresh writes exactly what slicing the real cells
    out and padding them again gives, bit for bit.  Every cell outside the
    real domain starts as NaN, so a strip built from anything but real cells
    shows; the real columns of the ``par_vec`` tail rows past the stream
    extension are left as they were (never tapped, only re-computed)."""
    from repro.core.blocking import BlockGeometry, stream_extension
    from repro.kernels.ops import _pad_blocked, _reclamp_padded, \
        _slice_blocked
    geom = BlockGeometry(len(dims), dims, 1, par_time, bsize, par_vec)
    bc = BoundaryCondition.make(bc_spec, len(dims))
    ext = stream_extension(geom, bc)
    k = jax.random.PRNGKey(11)
    grid = jax.random.uniform(k, batch + dims, jnp.float32, 0.5, 2.0)
    padded = np.asarray(_pad_blocked(grid, geom, bc))
    real = np.zeros(padded.shape, bool)
    real[(Ellipsis, slice(ext, ext + dims[0]))
         + tuple(slice(h, h + d) for h, d in zip(geom.pad, dims[1:]))] = True
    gp = jnp.asarray(np.where(real, padded, np.nan))

    got = np.asarray(jax.jit(_reclamp_padded, static_argnums=(1, 2))(
        gp, geom, bc))
    want = np.asarray(_pad_blocked(_slice_blocked(gp, geom, bc), geom, bc))
    lead = (slice(None),) * len(batch)
    head = lead + (slice(0, dims[0] + 2 * ext),)   # rows the refresh keeps
    np.testing.assert_array_equal(got[head], want[head])
    tail = lead + (slice(dims[0] + 2 * ext, None),) + tuple(
        slice(h, h + d) for h, d in zip(geom.pad, dims[1:]))
    assert np.isnan(got[tail]).all()


# --- cache keys: a clamp entry never serves a periodic plan ------------------

def test_schedule_cache_keys_on_bc(tmp_path):
    from repro.api.schedule_cache import schedule_key
    from repro.core.perf_model import TPU_V5E
    cfg = RunConfig(backend="engine", par_time=2, bsize=16)
    keys = {schedule_key(StencilProblem("diffusion2d", (32, 64), boundary=b),
                         cfg, TPU_V5E, 1, None)
            for b in ["clamp", "periodic", "reflect", "constant",
                      "constant:2.0", ("clamp", "periodic")]}
    assert len(keys) == 6   # every BC (incl. the fill value) splits the key


def test_measured_schedule_tuned_under_clamp_not_served_to_periodic(tmp_path):
    cache = str(tmp_path / "schedules.json")
    cfg = RunConfig(backend="engine", autotune="measure", cache=cache,
                    par_time=2, bsize=32, tune_warmup=0, tune_repeats=1)
    p1 = plan(StencilProblem("diffusion2d", (16, 128)), cfg)
    assert not p1.tuned_from_cache          # first tune: measured, cached
    p2 = plan(StencilProblem("diffusion2d", (16, 128)), cfg)
    assert p2.tuned_from_cache              # same key: served from cache
    p3 = plan(StencilProblem("diffusion2d", (16, 128), boundary="periodic"),
              cfg)
    assert not p3.tuned_from_cache          # clamp winner must NOT be served


def test_exec_cache_keys_on_bc():
    clear_exec_cache()
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (16, 32))
    c = default_coeffs(st)
    cfg = RunConfig(backend="engine", par_time=2, bsize=16)
    plan(StencilProblem(st, (16, 32)), cfg).run(g, 2, c)
    plan(StencilProblem(st, (16, 32), boundary="periodic"), cfg).run(g, 2, c)
    stats = exec_cache_stats()
    assert stats["misses"] >= 2 and stats["hits"] == 0, stats
    # and the same BC DOES share the compiled program
    plan(StencilProblem(st, (16, 32), boundary="periodic"), cfg).run(g, 3, c)
    assert exec_cache_stats()["hits"] >= 1


# --- negative paths ----------------------------------------------------------

def test_unknown_bc_name_raises():
    with pytest.raises(ValueError, match="unknown boundary kind"):
        StencilProblem("diffusion2d", (8, 8), boundary="dirichlet-ish")


def test_bc_arity_must_match_grid():
    with pytest.raises(ValueError, match="one per grid axis"):
        StencilProblem("diffusion2d", (8, 8),
                       boundary=("clamp", "periodic", "reflect"))
    with pytest.raises(ValueError, match="2D"):
        BoundaryCondition.make(BoundaryCondition(("clamp",)), 2)


def test_constant_bc_rejects_non_scalar_fill():
    with pytest.raises(ValueError, match="scalar"):
        BoundaryCondition(("constant", "clamp"), value=np.ones(3))
    with pytest.raises(ValueError, match="scalar"):
        BoundaryCondition(("constant", "clamp"), value=[1.0, 2.0])
    with pytest.raises(ValueError, match="conflicting constant fill"):
        BoundaryCondition.make(("constant:1.0", "constant:2.0"), 2)


def test_reflect_needs_two_cells():
    with pytest.raises(ValueError, match="extent >= 2"):
        StencilProblem("diffusion2d", (8, 1), boundary="reflect")
    # clamp on the degenerate axis is fine
    StencilProblem("diffusion2d", (8, 1), boundary=("reflect", "clamp"))


def test_constant_value_suffix_only_for_constant():
    with pytest.raises(ValueError, match="':value' suffix"):
        BoundaryCondition.make("periodic:3.0", 2)
    with pytest.raises(ValueError, match="constant fill must be a number"):
        BoundaryCondition.make("constant:hot", 2)


def test_stream_extension_single_definition():
    """predict(), traffic_report() and the kernels' DMA accounting all bill
    the periodic stream extension through ONE shared helper — and it only
    fires for a periodic *streaming* axis."""
    from repro.core.blocking import (BlockGeometry, extended_geometry,
                                     stream_extension)
    geom = BlockGeometry(2, (16, 64), 1, 2, (16,))
    per = BoundaryCondition.make("periodic", 2)
    assert stream_extension(geom, per) == geom.size_halo == 2
    assert extended_geometry(geom, per).dims == (20, 64)
    for spec in ["clamp", ("reflect", "periodic")]:   # periodic-in-x only
        bc = BoundaryCondition.make(spec, 2)
        assert stream_extension(geom, bc) == 0
        assert extended_geometry(geom, bc) is geom
