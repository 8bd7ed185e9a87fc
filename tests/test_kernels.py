"""Pallas kernels (interpret mode) vs. pure-jnp oracle — shape/param sweeps,
driven through the public ``plan()`` API; exact DMA-traffic accounting; and
end-to-end high-order (radius > 1) star and box neighborhoods."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RunConfig, StencilProblem, plan
from repro.core import STENCILS, default_coeffs, make_box, make_star
from repro.core.blocking import BlockGeometry
from repro.kernels.ops import dma_traffic_bytes
from repro.kernels.ref import oracle_run


def _data(stencil, dims, seed=0):
    k = jax.random.PRNGKey(seed)
    g = jax.random.uniform(k, dims, jnp.float32, 0.5, 2.0)
    aux = None
    if stencil.has_aux:
        aux = jax.random.uniform(jax.random.fold_in(k, 1), dims,
                                 jnp.float32, 0.0, 0.1)
    return g, aux


def _plan_run(st, g, c, iters, par_time, bsize, aux=None,
              backend="pallas_interpret"):
    p = plan(StencilProblem(st, tuple(g.shape)),
             RunConfig(backend=backend, par_time=par_time, bsize=bsize))
    return p.run(g, iters, c, aux=aux)


@pytest.mark.parametrize("name", ["diffusion2d", "hotspot2d"])
@pytest.mark.parametrize("dims,iters,par_time,bsize", [
    ((17, 40), 1, 1, 24),
    ((33, 70), 4, 4, 32),
    ((29, 61), 7, 4, 40),     # remainder -> PE forwarding
    ((12, 130), 6, 2, 128),   # lane-width block
    ((5, 33), 3, 2, 16),      # tiny stream extent
])
def test_pallas2d_matches_oracle(name, dims, iters, par_time, bsize):
    st = STENCILS[name]
    g, aux = _data(st, dims)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, iters, aux)
    got = _plan_run(st, g, c, iters, par_time, bsize, aux)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", ["diffusion3d", "hotspot3d"])
@pytest.mark.parametrize("dims,iters,par_time,bsize", [
    ((7, 19, 23), 1, 1, 12),
    ((11, 25, 17), 4, 2, 12),
    ((9, 22, 30), 5, 4, 20),  # remainder
    ((4, 15, 15), 2, 2, 10),
])
def test_pallas3d_matches_oracle(name, dims, iters, par_time, bsize):
    st = STENCILS[name]
    g, aux = _data(st, dims)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, iters, aux)
    got = _plan_run(st, g, c, iters, par_time, bsize, aux)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_backends_agree():
    st = STENCILS["diffusion2d"]
    g, _ = _data(st, (21, 45))
    c = default_coeffs(st)
    outs = [_plan_run(st, g, c, 5, 2, 24, backend=b)
            for b in ("reference", "engine", "pallas_interpret")]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=2e-5, atol=2e-5)


# --- exact DMA accounting (prefetch stops at the last real row) ---------------

@pytest.mark.parametrize("name,dims,par_time,bsize", [
    ("diffusion2d", (33, 700), 4, (256,)),
    ("hotspot3d", (11, 40, 56), 2, (16, 16)),
])
def test_dma_traffic_counts_stream_not_nticks_rows(name, dims, par_time,
                                                   bsize):
    st = STENCILS[name]
    geom = BlockGeometry(st.ndim, dims, st.radius, par_time, bsize)
    n_streams = 2 if st.has_aux else 1
    got = dma_traffic_bytes(st, geom, 4)
    reads = geom.num_blocks * geom.stream_dim * math.prod(geom.bsize)
    writes = geom.num_blocks * geom.stream_dim * math.prod(geom.csize)
    assert got == (reads * n_streams + writes) * 4
    # vs. the pre-fix schedule (nticks = stream + size_halo input DMAs per
    # block): the saving is exactly one halo's worth of rows per stream
    nticks = geom.stream_dim + geom.size_halo
    prefix_reads = geom.num_blocks * nticks * math.prod(geom.bsize)
    prefix_bytes = (prefix_reads * n_streams + writes) * 4
    assert prefix_bytes - got == (geom.size_halo * math.prod(geom.bsize)
                                  * geom.num_blocks * n_streams * 4)


def test_traffic_report_reflects_lean_schedule():
    p = plan(StencilProblem("diffusion2d", (512, 1024)),
             RunConfig(backend="engine", par_time=4, bsize=512))
    r = p.traffic_report()
    g = p.geometry
    assert r["kernel_dma_bytes_per_superstep"] == dma_traffic_bytes(
        STENCILS["diffusion2d"], g, 4)
    # the model's clipped reads can now exceed the kernel's lean reads only
    # via overlap redundancy, not via phantom drain-tick DMAs
    assert 0 < r["traffic_accuracy"] <= 1.5


@pytest.mark.parametrize("name,dims,par_time,bsize", [
    ("diffusion2d", (17, 40), 2, 24),
    ("diffusion3d", (7, 19, 23), 2, 12),
])
def test_interpret_bit_identical_to_oracle(name, dims, par_time, bsize):
    """The DMA-schedule fix must not perturb values: same arithmetic per
    cell => bit-identical interpret-mode output."""
    st = STENCILS[name]
    g, aux = _data(st, dims)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, 5, aux)
    got = _plan_run(st, g, c, 5, par_time, bsize, aux)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- high-order (radius > 1) and box neighborhoods end-to-end -----------------

@pytest.mark.parametrize("st,dims,iters,par_time,bsize", [
    (make_star(2, 2), (15, 37), 5, 2, 24),    # r=2: halo 4/side per block
    (make_star(2, 3), (11, 41), 4, 1, 16),    # r=3, superstep remainder
    (make_star(3, 2), (6, 21, 19), 3, 1, 12),
    (make_box(2, 1), (13, 33), 5, 2, 16),     # diagonals exercised
    (make_box(2, 2), (12, 44), 3, 1, 14),
    (make_box(3, 1), (5, 14, 16), 4, 2, 12),
])
def test_highorder_and_box_match_oracle(st, dims, iters, par_time, bsize):
    g, _ = _data(st, dims)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, iters)
    for backend in ("engine", "pallas_interpret"):
        got = _plan_run(st, g, c, iters, par_time, bsize, backend=backend)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"{st.name} via {backend}")


def test_box_offsets_include_diagonals():
    st = make_box(2, 1)
    assert (1, 1) in st.offsets and (-1, 1) in st.offsets
    assert len(st.offsets) == 9
    assert len(make_box(3, 1).offsets) == 27
    # star offsets stay axis-aligned, builtins included
    assert set(make_star(2, 2).offsets) == {
        (0, 0), (0, 1), (0, 2), (0, -1), (0, -2),
        (1, 0), (2, 0), (-1, 0), (-2, 0)}
    assert (1, 1) not in STENCILS["diffusion2d"].offsets
    assert len(STENCILS["hotspot3d"].offsets) == 7


def test_offsets_span_must_fit_radius():
    from repro.core.stencils import Stencil
    with pytest.raises(ValueError, match="exceeds radius"):
        Stencil("bad", 2, 1, 1, 1, 1, False, ("c",),
                lambda get, c, aux=None: get((0, 2)),
                offsets=((0, 2),))


# --- tile-aligned geometries: the compiled kernels' layout, in interpret mode -
#
# The compiled path rounds each blocked dim's halo up to its TPU tile so every
# DMA window starts on a tile (``BlockGeometry.align``).  These cases start
# from halos and block origins that are NOT tile multiples before alignment,
# and from stream extents that do not divide by ``par_vec``.

def _aligned_geom(st, dims, par_time, csize, par_vec, cell_bytes=4):
    from repro.core.blocking import tpu_tiles
    stream_tile, align = tpu_tiles(st.ndim, cell_bytes)
    assert par_vec % stream_tile == 0
    h = st.radius * par_time
    bsize = tuple(c + 2 * (-(-h // a) * a) for c, a in zip(csize, align))
    geom = BlockGeometry(st.ndim, dims, st.radius, par_time, bsize, par_vec,
                         align)
    for c, p, a in zip(geom.csize, geom.pad, align):
        assert c % a == 0 and p % a == 0 and p > h   # origins on tiles
    return geom


@pytest.mark.parametrize("bc", ["clamp", "reflect", "constant:0.5",
                                "periodic"])
@pytest.mark.parametrize("name,dims,par_time,csize,par_vec,dtype", [
    ("diffusion2d", (21, 300), 3, (128,), 8, "float32"),
    ("hotspot2d", (19, 260), 2, (128,), 16, "float32"),
    ("diffusion2d", (35, 200), 2, (128,), 16, "bfloat16"),
    ("diffusion3d", (10, 20, 140), 2, (8, 128), 3, "float32"),
    ("hotspot3d", (9, 13, 150), 3, (8, 128), 2, "float32"),
])
def test_aligned_geometry_matches_oracle(bc, name, dims, par_time, csize,
                                         par_vec, dtype):
    from repro.kernels.ops import pack_coeffs, run_pallas
    st = STENCILS[name]
    cb = jnp.dtype(dtype).itemsize
    geom = _aligned_geom(st, dims, par_time, csize, par_vec, cb)
    assert dims[0] % par_vec or name.endswith("3d")
    problem = StencilProblem(st, dims, dtype=dtype, boundary=bc)
    stage, bc_obj = problem.exec_stages[0]
    dag = problem.exec_dag
    g, aux = _data(st, dims)
    g = g.astype(dtype)
    aux = None if aux is None else aux.astype(dtype)
    c = problem.resolve_coeffs(dtype=jnp.float32)
    want = oracle_run(stage, g, c[0], 7, aux, bc=bc_obj)
    got = run_pallas(dag, geom, g, pack_coeffs(dag, c), 7, aux, True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_aligned_wave_dag_matches_oracle():
    """A two-field DAG (fan-in, per-consumer re-imposition) on the aligned
    layout, with a periodic stream extension."""
    from repro.api import StencilProgram, StencilStage
    from repro.core.stencils import make_combine
    from repro.kernels.ops import pack_coeffs, run_pallas
    from repro.kernels.ref import oracle_dag_run
    lap = StencilStage(make_star(2, 1), name="lapu", inputs=("u",),
                       coeffs={"c0": -4.0, "c_0_-1": 1.0, "c_0_1": 1.0,
                               "c_1_-1": 1.0, "c_1_1": 1.0})
    unext = StencilStage(make_combine(2, 3), name="unext",
                         inputs=("u", "u_prev", "lapu"),
                         coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.16})
    prog = StencilProgram((lap, unext), fields=("u", "u_prev"),
                          updates={"u": "unext", "u_prev": "u"})
    dims = (21, 260)
    problem = StencilProblem(prog, dims, boundary="periodic")
    dag = problem.exec_dag
    g, _ = _data(STENCILS["diffusion2d"], dims)
    state = jnp.stack([g, g[::-1]])
    coeffs = problem.resolve_coeffs(dtype=jnp.float32)
    geom = _aligned_geom(make_star(2, 1), dims, 2, (128,), 8)
    want = oracle_dag_run(dag, state, coeffs, 5, None)
    got = run_pallas(dag, geom, state, pack_coeffs(dag, coeffs), 5, None,
                     True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dma_traffic_counts_aligned_widths():
    """The aligned 16384^2 geometry moves its full (tile-rounded) block
    width in and its compute width out; the alignment's cost against the
    paper's geometry at the same compute width is the extra halo reads."""
    st = STENCILS["diffusion2d"]
    dims = (16384, 16384)
    new = BlockGeometry(2, dims, 1, 16, (1280,), 16, (128,))
    old = BlockGeometry(2, dims, 1, 16, (1056,), 16)
    assert new.pad == (128,) and new.csize == old.csize == (1024,)
    assert new.padded_dims == (16640,) and new.bnum == old.bnum == (16,)
    rows = 16384
    assert dma_traffic_bytes(st, new, 4) == (
        16 * rows * 1280 + 16 * rows * 1024) * 4
    assert dma_traffic_bytes(st, new, 4) - dma_traffic_bytes(st, old, 4) \
        == 16 * rows * (1280 - 1056) * 4


@pytest.mark.parametrize("name,dims,dtype", [
    ("diffusion2d", (16384, 16384), "float32"),
    ("hotspot2d", (16384, 16384), "bfloat16"),
    ("diffusion3d", (448, 448, 448), "float32"),
    ("hotspot3d", (448, 448, 448), "bfloat16"),
    ("diffusion2d", (40, 300), "float32"),
])
def test_aligned_autotune_windows_start_on_tiles(name, dims, dtype):
    """Every geometry the compiled path may pick has tile-aligned DMA
    origins (i*csize and i*csize + pad) and a par_vec that fills the
    stream tile; plan(backend='pallas') compiles one of them."""
    from repro.core import perf_model
    from repro.core.blocking import tpu_tiles
    st = STENCILS[name]
    cb = jnp.dtype(dtype).itemsize
    stream_tile, align = tpu_tiles(st.ndim, cb)
    cands = perf_model.autotune(st, dims, 1000, cell_bytes=cb, aligned=True)
    assert cands
    for p in cands:
        g = p.geom
        assert g.align == align and g.par_vec % stream_tile == 0
        assert all(c % a == 0 and h % a == 0
                   for c, h, a in zip(g.csize, g.pad, align))
        assert p.vmem_bytes <= perf_model.TPU_V5E.vmem_budget
    pl_ = plan(StencilProblem(name, dims, dtype=dtype),
               RunConfig(backend="pallas", autotune="model"))
    assert pl_.geometry == cands[0].geom
