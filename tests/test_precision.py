"""Mixed-precision conformance harness — dtype-aware ulp tolerances.

``repro.core.precision`` is the single owner of the storage/accumulation
policy (bf16 grids widen to f32 per stage application and round back to
storage exactly once); this file locks every backend to it:

  * an independent **f64 numpy oracle** — storage-rounded inputs promoted to
    f64, the stage DAG evaluated in f64 with *no* intermediate rounding,
    coefficients at their f32-resolved values — bounds every backend's error
    under the explicit per-dtype ulp budgets of
    ``precision.ULPS_PER_ITER`` (via ``precision.tolerance``),
  * a parametrized matrix sweeps dtype x BC x backend (incl. a vectorized
    ``par_vec=4`` Pallas column) x rank (1D/2D/3D) x radius (1, 2) x aux,
  * **f32 stays bit-identical to the pre-bf16 code**: golden digests pinned
    per backend,
  * **bf16 is bit-identical across backends** (round-once-per-stage is the
    same computation everywhere), `run_batch` included,
  * multi-stage chains and multi-field DAG programs run the same
    storage/accumulation policy,
  * the schedule cache and the executable cache key on the dtype (a bf16
    executable must never serve an f32 plan, and vice versa),
  * every dtype-spec spelling (``"bf16"``, ``jnp.bfloat16``, ``np.dtype``)
    normalizes to one canonical bucket, and a serving request inherits the
    *grid's* dtype,
  * bf16 extends the ``par_vec`` sweep to V=32 (16-sublane tiles) and
    halves the per-cell traffic/VMEM pricing,
  * the distributed backend runs the same checks on a 2-device mesh in a
    subprocess (``precision_distributed_check.py``).
"""
import hashlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (RunConfig, StencilProblem, clear_exec_cache,
                       exec_cache_stats, plan)
from repro.api.schedule_cache import schedule_key
from repro.core import STENCILS, make_star, precision
from repro.core.blocking import BlockGeometry
from repro.core.perf_model import (PAR_VEC_CANDIDATES, autotune,
                                   par_vec_candidates)
from repro.core.stencils import make_combine
from repro.programs import StencilProgram, StencilStage
from repro.serve import StencilRequest

DTYPES = ("float32", "bfloat16")


# --- the f64 numpy oracle ----------------------------------------------------
#
# Promote the storage-rounded initial state to f64 and run the whole program
# in f64 with no intermediate rounding; the difference to a backend's output
# is then exactly the backend's accumulated rounding error, which the
# per-dtype ulp budget must bound.  Stencil ``apply`` bodies are pure
# arithmetic over getter results, so numpy getters + python-float
# coefficients evaluate the same expressions in f64.

_NP_MODES = {"clamp": "edge", "periodic": "wrap", "reflect": "reflect"}


def _np_padded_getter(x, r, bc, sdtype):
    """f64 per-axis BC padding (constant fills pre-rounded through the
    storage dtype, matching the backends)."""
    p = x
    for ax, kind in enumerate(bc.kinds):
        pads = [(0, 0)] * p.ndim
        pads[ax] = (r, r)
        if kind == "constant":
            fill = float(np.asarray(bc.value, sdtype))
            p = np.pad(p, pads, mode="constant", constant_values=fill)
        else:
            p = np.pad(p, pads, mode=_NP_MODES[kind])

    def get(off):
        return p[tuple(slice(r + o, r + o + n)
                       for o, n in zip(off, x.shape))]

    return get


def _f32_resolved_coeffs(problem, coeffs=None):
    """Per-stage coefficient dicts at their f32-resolved values, as exact
    python floats: every backend resolves coefficients in the accumulation
    dtype (f32 for both supported storage dtypes), so the f64 oracle must
    use the f32-rounded values, not the unrounded literals."""
    return tuple({k: float(np.asarray(v, np.float32)) for k, v in cf.items()}
                 for cf in problem.resolve_coeffs(coeffs))


def f64_oracle_run(problem, state, iters, coeffs=None, aux=None):
    """``iters`` program iterations of ``problem``'s stage DAG in f64."""
    dag = problem.exec_dag
    cfs = _f32_resolved_coeffs(problem, coeffs)
    sdtype = problem.jnp_dtype
    s = np.asarray(state).astype(np.float64)
    aux64 = None if aux is None else np.asarray(aux).astype(np.float64)
    F = dag.n_fields
    fields = [s[k] for k in range(F)] if F > 1 else [s]
    for _ in range(iters):
        vals = [None] * len(dag.stages)
        for si in dag.topo:
            st, bc_s, refs = dag.stages[si]
            ins = [vals[r] if r >= 0 else fields[~r] for r in refs]
            gets = [_np_padded_getter(x, st.radius, bc_s, sdtype)
                    for x in ins]
            vals[si] = st.apply(tuple(gets) if st.arity > 1 else gets[0],
                                cfs[si], aux64 if st.has_aux else None)
        fields = [vals[u] if u >= 0 else fields[~u] for u in dag.updates]
    return np.stack(fields) if F > 1 else fields[0]


def _data(problem, seed=3):
    """Initial state + aux in the problem's storage dtype (generated in f32,
    rounded to storage — the storage-rounded values ARE the inputs every
    backend and the f64 oracle start from)."""
    k = jax.random.PRNGKey(seed)
    g = jax.random.uniform(k, problem.state_shape, jnp.float32, 0.5, 2.0)
    aux = (jax.random.uniform(jax.random.fold_in(k, 7), problem.shape,
                              jnp.float32, 0.0, 0.1)
           if problem.needs_aux else None)
    sd = problem.jnp_dtype
    return g.astype(sd), None if aux is None else aux.astype(sd)


# --- the conformance matrix --------------------------------------------------
#
# dtype x BC x backend(+par_vec) x rank x radius x aux, 5 iterations each,
# asserted against the f64 oracle under precision.tolerance's explicit ulp
# budget.  (id, stencil, dims, bc, par_time, bsize)

CASES = [
    ("diff2d-clamp", "diffusion2d", (24, 48), "clamp", 2, 16),
    ("diff2d-per-refl", "diffusion2d", (24, 48),
     ("periodic", "reflect"), 2, 16),
    ("diff2d-const-clamp", "diffusion2d", (24, 48),
     ("constant:0.25", "clamp"), 2, 16),
    ("star2d-r2", make_star(2, 2), (24, 48), ("clamp", "periodic"), 2, 16),
    ("diff3d-mixed", "diffusion3d", (8, 16, 16),
     ("clamp", "periodic", "reflect"), 1, 8),
    ("hotspot2d-aux", "hotspot2d", (24, 48), "clamp", 2, 16),
    ("star1d-r2", "star1d_r2", (64,), "clamp", 2, ()),
]

#: (backend, par_vec) columns — the V=4 column re-checks the matrix through
#: the vectorized kernels (2D cases only; V applies to the stream axis)
BACKEND_COLS = [("reference", 1), ("engine", 1), ("pallas_interpret", 1),
                ("pallas_interpret", 4)]

ITERS = 5


@pytest.mark.parametrize("backend,par_vec", BACKEND_COLS,
                         ids=lambda c: str(c))
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
@pytest.mark.parametrize("dtype", DTYPES)
def test_conformance_matrix(case, backend, par_vec, dtype):
    _, st, dims, bc, par_time, bsize = case
    if par_vec > 1 and len(dims) != 2:
        pytest.skip("V>1 column covers the 2D cases")
    problem = StencilProblem(st, dims, dtype=dtype, boundary=bc)
    g, aux = _data(problem)
    p = plan(problem, RunConfig(backend=backend, par_time=par_time,
                                bsize=bsize,
                                par_vec=par_vec if par_vec > 1 else None))
    got = p.run(g, ITERS, aux=aux)
    assert got.dtype == problem.jnp_dtype
    want = f64_oracle_run(problem, g, ITERS, aux=aux)
    tol = precision.tolerance(dtype, ITERS, problem.n_stages)
    np.testing.assert_allclose(
        np.asarray(got).astype(np.float64), want, **tol,
        err_msg=f"{case[0]} {backend} V={par_vec} {dtype}")


def test_tolerance_budget_shape():
    """The budget is explicit and monotone: more iterations/stages widen it
    linearly, bf16's base rtol is coarser than f32's, and ``scale`` sets
    the absolute floor for far-from-1 fields."""
    t1 = precision.tolerance("float32", 1)
    t5 = precision.tolerance("float32", 5)
    assert t5["rtol"] == pytest.approx(5 * t1["rtol"])
    assert (precision.tolerance("float32", 1, stages=3)["rtol"]
            == pytest.approx(3 * t1["rtol"]))
    assert (precision.tolerance("bfloat16", 1)["rtol"]
            > precision.tolerance("float32", 1)["rtol"])
    t = precision.tolerance("bfloat16", 2, scale=100.0)
    assert t["atol"] == pytest.approx(100.0 * t["rtol"])
    # the documented bases, not fitted fudge factors
    assert precision.tolerance("float32", 1)["rtol"] == 16.0 * 2.0 ** -23
    assert precision.tolerance("bfloat16", 1)["rtol"] == 4.0 * 2.0 ** -8


# --- f32 bit-identity with the pre-bf16 code ---------------------------------
#
# The accumulation casts are emitted ONLY for sub-32-bit storage
# (precision.needs_accum_cast); f32 traces must be byte-for-byte the same
# programs as before this feature.  Digests re-pinned under jax 0.9.0, whose
# PRNG draws different inputs than 0.4.37's; the seed tree and this one give
# the same digests, identical across reference/engine/pallas_interpret.

def _digest(a):
    return hashlib.sha256(
        np.asarray(a, np.float32).tobytes()).hexdigest()[:16]


F32_GOLDENS = {
    "diffusion2d": "afd1f20139cd7979",
    "hotspot2d": "940e74d5c338deeb",
    "diffusion3d": "8636d141bc1dcd67",
}


@pytest.mark.parametrize("backend", ("reference", "engine",
                                     "pallas_interpret"))
def test_f32_bit_identical_to_seed(backend):
    key = jax.random.PRNGKey(3)
    g2 = jax.random.uniform(key, (24, 48), jnp.float32)
    aux = jax.random.uniform(jax.random.PRNGKey(4), (24, 48), jnp.float32)
    g3 = jax.random.uniform(key, (8, 16, 16), jnp.float32)
    pv = 4 if backend == "pallas_interpret" else None

    p = plan(StencilProblem("diffusion2d", (24, 48),
                            boundary=("clamp", "periodic")),
             RunConfig(backend=backend, par_time=2, bsize=16, par_vec=pv))
    assert _digest(p.run(g2, 5)) == F32_GOLDENS["diffusion2d"], backend

    p = plan(StencilProblem("hotspot2d", (24, 48),
                            boundary=("clamp", "periodic")),
             RunConfig(backend=backend, par_time=2, bsize=16, par_vec=pv))
    assert _digest(p.run(g2, 5, aux=aux)) == F32_GOLDENS["hotspot2d"], backend

    p = plan(StencilProblem("diffusion3d", (8, 16, 16)),
             RunConfig(backend=backend, par_time=1, bsize=8))
    assert _digest(p.run(g3, 5)) == F32_GOLDENS["diffusion3d"], backend


# --- bf16 is bit-identical ACROSS backends -----------------------------------
#
# Round-once-per-stage-application makes the bf16 computation the *same*
# computation in every backend: the f32 intermediate differences that could
# distinguish them are quashed by the per-stage bf16 rounding.

def test_bf16_bit_identical_across_backends():
    problem = StencilProblem("diffusion2d", (24, 48), dtype="bfloat16",
                             boundary=("clamp", "periodic"))
    g, _ = _data(problem)
    outs = {}
    for backend, pv in BACKEND_COLS:
        p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=16,
                                    par_vec=pv if pv > 1 else None))
        out = p.run(g, ITERS)
        assert out.dtype == jnp.bfloat16
        outs[f"{backend}-V{pv}"] = np.asarray(out.astype(jnp.float32))
    ref = outs["reference-V1"]
    for name, o in outs.items():
        np.testing.assert_array_equal(o, ref, err_msg=name)


@pytest.mark.parametrize("backend", ("engine", "pallas_interpret"))
def test_bf16_run_batch(backend):
    problem = StencilProblem("diffusion2d", (16, 32), dtype="bfloat16",
                             boundary=("clamp", "reflect"))
    g, _ = _data(problem)
    gs = jnp.stack([g, (g.astype(jnp.float32) * 1.1).astype(g.dtype),
                    (g.astype(jnp.float32) * 0.9).astype(g.dtype)])
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=16))
    ref = plan(problem, RunConfig(backend="reference"))
    got = p.run_batch(gs, 4)
    assert got.dtype == jnp.bfloat16
    want = jnp.stack([ref.run(gs[i], 4) for i in range(3)])
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)), err_msg=backend)


# --- programs: chains and multi-field DAGs under the same policy -------------

def _chain_problem(dims, dtype):
    """Two-stage linear chain: smooth then sharpen-ish recombine."""
    return StencilProblem(
        (StencilStage("diffusion2d"),
         StencilStage(make_star(2, 1), coeffs={"c0": 0.6, "c_0_1": 0.1})),
        dims, dtype=dtype, boundary=("clamp", "periodic"))


def _wave_problem(dims, dtype):
    """Second-order wave equation: two fields, simultaneous rotation."""
    prog = StencilProgram(
        (StencilStage(make_star(2, 1), name="lapu", inputs=("u",)),
         StencilStage(make_combine(2, 3), name="unext",
                      inputs=("u", "u_prev", "lapu"),
                      coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.1})),
        fields=("u", "u_prev"), updates={"u": "unext", "u_prev": "u"})
    return StencilProblem(prog, dims, dtype=dtype, boundary="clamp")


@pytest.mark.parametrize("backend", ("engine", "pallas_interpret"))
@pytest.mark.parametrize("make", (_chain_problem, _wave_problem),
                         ids=("chain", "dag"))
@pytest.mark.parametrize("dtype", DTYPES)
def test_program_conformance(make, backend, dtype):
    problem = make((16, 32), dtype)
    g, _ = _data(problem)
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=16))
    ref = plan(problem, RunConfig(backend="reference"))
    got = p.run(g, ITERS)
    assert got.dtype == problem.jnp_dtype
    # ulp-budget conformance against the f64 oracle...
    want = f64_oracle_run(problem, g, ITERS)
    tol = precision.tolerance(dtype, ITERS, problem.n_stages)
    np.testing.assert_allclose(np.asarray(got).astype(np.float64), want,
                               **tol, err_msg=f"{backend} {dtype}")
    # ...and (bf16) bit-identity with the reference backend
    if dtype == "bfloat16":
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(ref.run(g, ITERS).astype(jnp.float32)),
            err_msg=backend)


# --- perf model: 16-sublane tiles, V=32 sweep, halved traffic ----------------

def test_sublanes_per_dtype():
    assert precision.sublanes_for(4) == 8
    assert precision.sublanes_for(2) == 16
    assert precision.sublanes_for(1) == 32
    assert precision.sublanes_of("float32") == 8
    assert precision.sublanes_of("bfloat16") == 16


def test_par_vec_candidates_extend_for_16bit():
    assert par_vec_candidates(4) == PAR_VEC_CANDIDATES
    assert 32 not in par_vec_candidates(4)
    assert par_vec_candidates(2) == PAR_VEC_CANDIDATES + (32,)


def test_autotune_sweeps_v32_for_bf16_only():
    st = STENCILS["diffusion2d"]
    f32 = autotune(st, (256, 512), 100, cell_bytes=4)
    b16 = autotune(st, (256, 512), 100, cell_bytes=2)
    assert f32 and b16
    assert not any(p.geom.par_vec == 32 for p in f32)
    assert any(p.geom.par_vec == 32 for p in b16)


def test_plan_autotune_bf16_candidates_include_v32():
    # V is only swept for backends that realize it (the Pallas kernels)
    cfg = RunConfig(backend="pallas_interpret", autotune="model")
    cands = plan(StencilProblem("diffusion2d", (256, 512), dtype="bfloat16"),
                 cfg).candidates
    assert any(p.geom.par_vec == 32 for p in cands)
    cands_f32 = plan(StencilProblem("diffusion2d", (256, 512)),
                     cfg).candidates
    assert cands_f32 and not any(p.geom.par_vec == 32 for p in cands_f32)


def test_bf16_halves_cell_pricing():
    """dtype-derived cell bytes: bf16 halves per-cell HBM traffic and
    shrinks the VMEM footprint; an explicit RunConfig.cell_bytes still
    overrides."""
    cfg = RunConfig()
    assert cfg.resolved_cell_bytes("float32") == 4
    assert cfg.resolved_cell_bytes("bfloat16") == 2
    assert RunConfig(cell_bytes=8).resolved_cell_bytes("bfloat16") == 8
    p32 = plan(StencilProblem("diffusion2d", (128, 256)),
               RunConfig(backend="engine", par_time=2, bsize=32))
    p16 = plan(StencilProblem("diffusion2d", (128, 256), dtype="bfloat16"),
               RunConfig(backend="engine", par_time=2, bsize=32))
    t32 = p32.traffic_report(iters=10)
    t16 = p16.traffic_report(iters=10)
    assert (t16["model_bytes_per_superstep"]
            == pytest.approx(t32["model_bytes_per_superstep"] / 2))
    assert (t16["kernel_dma_bytes_per_superstep"]
            < t32["kernel_dma_bytes_per_superstep"])
    # VMEM: thin V=1 windows pad to 16 sublanes, exactly cancelling the
    # halved cell bytes (equal footprint); once V fills the bf16 tile the
    # footprint genuinely halves
    g1 = BlockGeometry(2, (128, 256), 1, 2, (32,))
    assert g1.vmem_bytes(2, False) == g1.vmem_bytes(4, False)
    g16 = BlockGeometry(2, (128, 256), 1, 2, (32,), par_vec=16)
    assert g16.vmem_bytes(2, False) == g16.vmem_bytes(4, False) // 2


# --- cache splits ------------------------------------------------------------

def test_schedule_cache_keys_on_dtype():
    cfg = RunConfig(backend="engine", par_time=2, bsize=16)
    dev = cfg.resolved_device()
    k32 = schedule_key(StencilProblem("diffusion2d", (24, 48)),
                       cfg, dev, 1, None)
    k16 = schedule_key(StencilProblem("diffusion2d", (24, 48),
                                      dtype="bfloat16"), cfg, dev, 1, None)
    assert k32 != k16
    assert "dtype=float32" in k32 and "cb=4" in k32
    assert "dtype=bfloat16" in k16 and "cb=2" in k16


@pytest.mark.parametrize("make", (
    lambda dt: StencilProblem("diffusion2d", (16, 32), dtype=dt),
    lambda dt: _wave_problem((16, 32), dt),
), ids=("single", "dag"))
def test_exec_cache_splits_on_dtype(make):
    """One executable per dtype — a second same-dtype plan must HIT, a
    same-everything-but-dtype plan must MISS into a new entry (single-stage
    and DAG paths alike)."""
    clear_exec_cache()
    cfg = RunConfig(backend="engine", par_time=2, bsize=16)

    def run(dt):
        problem = make(dt)
        g, _ = _data(problem)
        plan(problem, cfg).run(g, 2)
        return exec_cache_stats()

    s1 = run("float32")
    assert s1["misses"] >= 1 and s1["hits"] == 0, s1
    s2 = run("float32")              # same dtype: shares the executable
    assert s2["hits"] >= 1 and s2["size"] == s1["size"], s2
    s3 = run("bfloat16")             # other dtype: new entry, no hit served
    assert s3["size"] > s2["size"], s3
    assert s3["misses"] > s2["misses"], s3
    clear_exec_cache()


# --- dtype-spec normalization ------------------------------------------------

def test_dtype_spec_normalization():
    specs = ["bfloat16", "bf16", jnp.bfloat16, np.dtype(jnp.bfloat16)]
    assert [precision.normalize_dtype(s) for s in specs] == ["bfloat16"] * 4
    assert precision.normalize_dtype(np.float32) == "float32"
    for s in specs:
        assert StencilProblem("diffusion2d", (8, 8), dtype=s).dtype \
            == "bfloat16"
    assert StencilProblem("diffusion2d", (8, 8),
                          dtype=np.dtype("float32")).dtype == "float32"


def test_request_inherits_grid_dtype():
    """A by-name request lands in the bucket of its *grid's* dtype — a bf16
    grid must never silently inherit the f32 default."""
    g16 = jnp.zeros((8, 8), jnp.bfloat16)
    g32 = jnp.zeros((8, 8), jnp.float32)
    r16 = StencilRequest("diffusion2d", g16, iters=1)
    r32 = StencilRequest("diffusion2d", g32, iters=1)
    assert r16.problem.dtype == "bfloat16"
    assert r32.problem.dtype == "float32"
    assert r16.bucket_key != r32.bucket_key


def test_pallas_supported_dtypes_documented():
    assert precision.SUPPORTED_DTYPES == ("float32", "bfloat16")
    assert precision.accum_dtype("bfloat16") == jnp.float32
    assert precision.accum_dtype("float32") == jnp.dtype("float32")
    assert precision.needs_accum_cast("bfloat16")
    assert not precision.needs_accum_cast("float32")


# --- distributed: the same policy across a 2-device mesh ---------------------

def test_distributed_precision_conformance():
    script = os.path.join(os.path.dirname(__file__),
                          "precision_distributed_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL OK" in out.stdout
