"""Multi-stage ``StencilProgram`` conformance + cache-key hygiene.

The contract under test: a program (ordered chain of stages, each with its
own coefficients and boundary condition) planned on ANY backend computes
exactly what the sequential per-stage oracle computes — while the fused
backends run the whole chain inside one super-step executable, so stage
intermediates never round-trip through HBM.  Also locks the cache keys:
programs fingerprint by their stage chain (order matters), a plain single
stage normalizes to the legacy problem (identical keys), and dtype splits
both the schedule cache and the executable cache.
"""
import os
import random
import subprocess
import sys

import pytest

try:                                 # the sweep upgrades when available; the
    import hypothesis.strategies as st   # deterministic cases always run
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (RunConfig, StencilProblem, StencilProgram,
                       StencilStage, clear_exec_cache,
                       exec_cache_stats, plan)
from repro.api.backends import _exec_key
from repro.api.schedule_cache import schedule_key, stencil_fingerprint
from repro.core.stencils import STENCILS, make_star
from repro.kernels.ref import oracle_dag_run

BACKENDS = ("reference", "engine", "pallas_interpret")


def _inputs(key, shape, needs_aux=False):
    g = jax.random.uniform(key, shape, jnp.float32, 0.5, 2.0)
    aux = (jax.random.uniform(jax.random.fold_in(key, 7), shape,
                              jnp.float32, 0.0, 0.1) if needs_aux else None)
    return g, aux


# --- fused chain == sequential per-stage oracle (acceptance criterion) -------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("shape,bsize", [((20, 17), 12), ((7, 14, 11), (12, 12))])
def test_two_stage_program_matches_oracle(backend, shape, bsize):
    ndim = len(shape)
    prog = [StencilStage(make_star(ndim, 1)),
            StencilStage(f"diffusion{ndim}d")]
    problem = StencilProblem(prog, shape, boundary="clamp")
    g, _ = _inputs(jax.random.PRNGKey(0), shape)
    want = oracle_dag_run(problem.exec_dag, g,
                              problem.resolve_coeffs(dtype=jnp.float32), 5)
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=bsize,
                                par_vec=1))
    np.testing.assert_allclose(np.asarray(p.run(g, iters=5)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_program_equals_chained_single_stage_plans(backend):
    """The fusion criterion: one 2-stage plan == two chained 1-stage plans,
    and the fused plan's traffic report bills ZERO HBM bytes for the
    intermediate."""
    shape = (24, 18)
    star = make_star(2, 1)
    problem = StencilProblem([StencilStage(star), StencilStage("diffusion2d")],
                             shape)
    g, _ = _inputs(jax.random.PRNGKey(1), shape)
    cfg = dict(par_time=1, bsize=12, par_vec=1)
    fused = plan(problem, RunConfig(backend=backend, **cfg))
    p1 = plan(StencilProblem(star, shape), RunConfig(backend=backend, **cfg))
    p2 = plan(StencilProblem("diffusion2d", shape),
              RunConfig(backend=backend, **cfg))
    seq = g
    for _ in range(4):
        seq = p2.run(p1.run(seq, iters=1), iters=1)
    np.testing.assert_allclose(np.asarray(fused.run(g, iters=4)),
                               np.asarray(seq), rtol=2e-5, atol=2e-5)
    tr = fused.traffic_report()
    assert tr["intermediate_hbm_bytes_per_superstep"] == 0
    assert tr["unfused_intermediate_bytes_per_superstep"] > 0
    assert len(tr["stages"]) == 2


def test_radius_zero_stage():
    """A pointwise (radius-0) stage — e.g. damping/reaction — chains for
    free: it adds no halo and the fused plan still matches the oracle."""
    shape = (18, 15)
    damp = StencilStage(make_star(2, 0), coeffs={"c0": 0.95}, name="damp")
    problem = StencilProblem([StencilStage("diffusion2d"), damp], shape)
    assert problem.stencil.radius == 1          # rad sums; the 0 is free
    g, _ = _inputs(jax.random.PRNGKey(2), shape)
    want = oracle_dag_run(problem.exec_dag, g,
                              problem.resolve_coeffs(dtype=jnp.float32), 6)
    for backend in BACKENDS:
        p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=8,
                                    par_vec=1))
        np.testing.assert_allclose(np.asarray(p.run(g, iters=6)),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)


def test_per_stage_coeffs_and_runtime_override():
    """Static stage coeff overrides apply; run-time coeffs are per-stage
    sequences for programs (a bare dict is rejected)."""
    shape = (16, 14)
    star = make_star(2, 1)
    problem = StencilProblem(
        [StencilStage(star, coeffs={"c0": 0.8, "c_0_1": 0.05}),
         StencilStage("diffusion2d")], shape)
    resolved = problem.resolve_coeffs(dtype=jnp.float32)
    assert float(resolved[0]["c0"]) == pytest.approx(0.8)
    assert float(resolved[0]["c_0_1"]) == pytest.approx(0.05)
    p = plan(problem, RunConfig(backend="engine", par_time=1, bsize=8))
    g, _ = _inputs(jax.random.PRNGKey(3), shape)
    override = ({"c0": 0.7}, None)
    want = oracle_dag_run(problem.exec_dag, g,
                              problem.resolve_coeffs(override,
                                                     dtype=jnp.float32), 3)
    np.testing.assert_allclose(np.asarray(p.run(g, iters=3, coeffs=override)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="sequence of per-stage"):
        p.run(g, iters=1, coeffs={"c0": 0.7})
    with pytest.raises(ValueError, match="unknown coefficients"):
        p.run(g, iters=1, coeffs=({"nope": 1.0}, None))


@pytest.mark.parametrize("backend", BACKENDS)
def test_program_run_batch(backend):
    shape = (18, 16)
    problem = StencilProblem(
        [StencilStage(make_star(2, 1)), StencilStage("diffusion2d")], shape,
        boundary=("clamp", "reflect"))
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=12,
                                par_vec=1))
    gs = jax.random.uniform(jax.random.PRNGKey(4), (3,) + shape, jnp.float32,
                            0.5, 2.0)
    cf = problem.resolve_coeffs(dtype=jnp.float32)
    want = jnp.stack([oracle_dag_run(problem.exec_dag, gs[i], cf, 4)
                      for i in range(3)])
    np.testing.assert_allclose(np.asarray(p.run_batch(gs, iters=4)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


# --- randomized chain sweep ---------------------------------------------------

def _chain_case(params):
    """ANY 1-3 stage chain (mixed radii incl. pointwise, per-axis BC mixes —
    periodicity uniform across stages, the rest varying per stage — 2D/3D,
    V in {1,4}) == the sequential per-stage oracle."""
    (ndim, n_stages, radii, periodic, kinds, par_time, par_vec, iters,
     backend, seed) = params
    if backend == "engine":
        par_vec = 1                 # a Pallas-only knob (scalar-tick backend)
    radii = radii[:n_stages]
    if ndim == 3:
        radii = [min(r, 1) for r in radii]    # keep 3D halos (and time) small
    cap = 3 if ndim == 2 else 2               # bound the fused halo
    while sum(radii) > cap:
        radii[radii.index(max(radii))] -= 1
    if sum(radii) == 0:
        radii[0] = 1                          # the chain must move data
    rad = sum(radii)
    stages = []
    for s, r in enumerate(radii):
        bc = tuple("periodic" if periodic[ax]
                   else kinds[(s * ndim + ax) % len(kinds)]
                   for ax in range(ndim))
        stages.append(StencilStage(make_star(ndim, r), boundary=bc))
    stream = 3 * rad * par_time + 5
    shape = (stream, 13) if ndim == 2 else (stream, 14, 12)
    bsize = 2 * rad * par_time + 4
    problem = StencilProblem(StencilProgram(tuple(stages)), shape,
                             boundary="clamp")
    g, _ = _inputs(jax.random.PRNGKey(seed), shape)
    want = oracle_dag_run(problem.exec_dag, g,
                              problem.resolve_coeffs(dtype=jnp.float32),
                              iters)
    p = plan(problem, RunConfig(backend=backend, par_time=par_time,
                                bsize=bsize, par_vec=par_vec))
    np.testing.assert_allclose(np.asarray(p.run(g, iters=iters)),
                               np.asarray(want), rtol=3e-5, atol=3e-5)


_NONPERIODIC = ["clamp", "reflect", "constant:0.6"]


def _draw_case(rng):
    return (
        rng.choice([2, 3]),                       # ndim
        rng.randint(1, 3),                        # n_stages
        [rng.choice([0, 1, 2]) for _ in range(3)],    # radii
        [rng.random() < 0.3 for _ in range(3)],   # per-axis periodic
        [rng.choice(_NONPERIODIC) for _ in range(9)],  # stage/axis kinds
        rng.randint(1, 2),                        # par_time
        rng.choice([1, 4]),                       # par_vec
        rng.randint(1, 4),                        # iters
        rng.choice(["engine", "pallas_interpret"]),
        rng.randint(0, 10_000),                   # prng seed
    )


_SEEDED_CASES = [_draw_case(random.Random(1000 + i)) for i in range(10)]


@pytest.mark.parametrize("params", _SEEDED_CASES,
                         ids=[f"case{i}" for i in range(len(_SEEDED_CASES))])
def test_chain_matches_oracle_seeded(params):
    _chain_case(params)


if HAVE_HYPOTHESIS:
    _chain_params = st.tuples(
        st.sampled_from([2, 3]),                  # ndim
        st.integers(1, 3),                        # n_stages
        st.lists(st.sampled_from([0, 1, 2]), min_size=3, max_size=3),
        st.lists(st.booleans(), min_size=3, max_size=3),
        st.lists(st.sampled_from(_NONPERIODIC), min_size=9, max_size=9),
        st.integers(1, 2),                        # par_time
        st.sampled_from([1, 4]),                  # par_vec
        st.integers(1, 4),                        # iters
        st.sampled_from(["engine", "pallas_interpret"]),
        st.integers(0, 10_000),                   # prng seed
    )

    @settings(max_examples=20, deadline=None)
    @given(_chain_params)
    def test_random_chain_matches_oracle(params):
        _chain_case(params)


def test_mixed_periodicity_across_stages_rejected():
    with pytest.raises(ValueError, match="periodic"):
        StencilProblem([StencilStage("diffusion2d", boundary="periodic"),
                        StencilStage("diffusion2d", boundary="clamp")],
                       (16, 16))


# --- distributed (subprocess: fake multi-device view) -------------------------

def test_distributed_program_matches_oracle():
    script = os.path.join(os.path.dirname(__file__),
                          "program_distributed_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "ALL OK" in out.stdout


# --- cache-key hygiene --------------------------------------------------------

def test_plain_single_stage_normalizes_to_legacy_problem():
    """One plain stage IS the legacy problem: same `stencil` object class,
    same fingerprint, same schedule/executable keys — nothing in any cache
    splits."""
    legacy = StencilProblem("diffusion2d", (32, 32))
    wrapped = StencilProblem([StencilStage("diffusion2d")], (32, 32))
    assert not wrapped.is_program and wrapped.n_stages == 1
    assert wrapped.stencil is STENCILS["diffusion2d"]
    assert (stencil_fingerprint(wrapped.stencil)
            == stencil_fingerprint(legacy.stencil))
    assert _exec_key("engine", wrapped, None) == _exec_key("engine", legacy,
                                                           None)


def test_program_fingerprint_is_order_and_content_sensitive():
    a, b = StencilStage("diffusion2d"), StencilStage(make_star(2, 1))
    p_ab = StencilProblem([a, b], (24, 24))
    p_ba = StencilProblem([b, a], (24, 24))
    assert (stencil_fingerprint(p_ab.stencil)
            != stencil_fingerprint(p_ba.stencil))
    # static coeff overrides change what the program computes -> new key
    p_cf = StencilProblem([StencilStage("diffusion2d", coeffs={"cc": 0.9}),
                           b], (24, 24))
    assert (stencil_fingerprint(p_ab.stencil)
            != stencil_fingerprint(p_cf.stencil))
    # a per-stage BC override does too
    p_bc = StencilProblem([StencilStage("diffusion2d", boundary="reflect"),
                           b], (24, 24))
    assert (stencil_fingerprint(p_ab.stencil)
            != stencil_fingerprint(p_bc.stencil))


# --- dtype is part of every cache key (satellite regression) ------------------

def _engine_cfg(**kw):
    kw.setdefault("backend", "engine")
    kw.setdefault("par_time", 2)
    kw.setdefault("bsize", 16)
    return RunConfig(**kw)


def test_dtype_splits_schedule_and_exec_keys():
    f32 = StencilProblem("diffusion2d", (48, 48), dtype="float32")
    b16 = StencilProblem("diffusion2d", (48, 48), dtype="bfloat16")
    cfg = _engine_cfg()
    dev = cfg.resolved_device()
    assert (schedule_key(f32, cfg, dev, 1, None, salt="s")
            != schedule_key(b16, cfg, dev, 1, None, salt="s"))
    assert _exec_key("engine", f32, None) != _exec_key("engine", b16, None)


def test_exec_cache_never_serves_across_dtypes():
    """Behavioral half of the key test: running the same problem in a second
    dtype MUST miss the executable cache (a second compile), and each run's
    output keeps its own dtype."""
    clear_exec_cache()
    try:
        shape = (32, 32)
        g32 = jax.random.uniform(jax.random.PRNGKey(5), shape, jnp.float32)
        p32 = plan(StencilProblem("diffusion2d", shape, dtype="float32"),
                   _engine_cfg())
        out32 = p32.run(g32, iters=2)
        misses_after_f32 = exec_cache_stats()["misses"]
        p16 = plan(StencilProblem("diffusion2d", shape, dtype="bfloat16"),
                   _engine_cfg())
        out16 = p16.run(g32.astype(jnp.bfloat16), iters=2)
        stats = exec_cache_stats()
        assert stats["misses"] == misses_after_f32 + 1, \
            "the f32 executable must never serve the bfloat16 plan"
        assert out32.dtype == jnp.float32 and out16.dtype == jnp.bfloat16
    finally:
        clear_exec_cache()


def test_measured_tuning_cache_never_serves_across_dtypes(tmp_path):
    """An f32-tuned schedule-cache entry never serves a different-dtype
    plan: the second dtype re-tunes (tuned_from_cache False) and the file
    ends with two entries."""
    cache = str(tmp_path / "s.json")
    cfg = dict(backend="engine", autotune="measure", iters_hint=4,
               tune_top_k=1, tune_warmup=0, tune_repeats=1, cache=cache)
    p_f32 = plan(StencilProblem("diffusion2d", (32, 96), dtype="float32"),
                 RunConfig(**cfg))
    assert not p_f32.tuned_from_cache
    # same dtype again: served from the persisted winner
    p_again = plan(StencilProblem("diffusion2d", (32, 96), dtype="float32"),
                   RunConfig(**cfg))
    assert p_again.tuned_from_cache
    # different dtype: MUST re-tune, not reuse the f32 winner
    p_b16 = plan(StencilProblem("diffusion2d", (32, 96), dtype="bfloat16"),
                 RunConfig(**cfg))
    assert not p_b16.tuned_from_cache
    import json
    entries = json.load(open(cache))["entries"]
    assert len(entries) == 2


def test_program_splits_exec_cache_from_single_stage():
    """A program and its first stage alone share shape/dtype/BC — the
    executable keys must still differ (different compiled chain)."""
    shape = (24, 24)
    single = StencilProblem("diffusion2d", shape)
    prog = StencilProblem([StencilStage("diffusion2d"),
                           StencilStage(make_star(2, 0))], shape)
    assert (_exec_key("engine", single, None)
            != _exec_key("engine", prog, None))

# --- DAG programs: conformance vs an independent topological oracle -----------
#
# The evaluator below shares NOTHING with repro.kernels.ref beyond the stage
# stencils' `apply` (which every backend shares by definition): numpy
# padding, fixpoint scheduling instead of the library's Kahn topo order, a
# plain dict of field arrays instead of DagSpec plumbing.

from repro.core.stencils import make_combine  # noqa: E402

_NP_PAD = {"clamp": "edge", "periodic": "wrap", "reflect": "reflect"}


def _np_get(x, r, bc):
    x = np.asarray(x)
    p = x
    for ax, kind in enumerate(bc.kinds):
        pads = [(0, 0)] * x.ndim
        pads[ax] = (r, r)
        if kind == "constant":
            p = np.pad(p, pads, mode="constant", constant_values=bc.value)
        else:
            p = np.pad(p, pads, mode=_NP_PAD[kind])

    def get(off):
        return p[tuple(slice(r + o, r + o + n)
                       for o, n in zip(off, x.shape))]
    return get


def _np_dag_oracle(problem, state, iters, aux=None):
    """iters program iterations, stages scheduled by *fixpoint* (re-scan
    until every stage has its inputs) — an order-free restatement of the
    topological semantics."""
    prog = problem.program
    coeffs = problem.resolve_coeffs(dtype=jnp.float32)
    F = len(prog.fields)
    state = np.asarray(state, np.float32)
    fields = [state[i] for i in range(F)] if F > 1 else [state]
    S = len(prog.stages)
    for _ in range(iters):
        vals, done = [None] * S, [False] * S
        while not all(done):
            progressed = False
            for i, stage in enumerate(prog.stages):
                if done[i]:
                    continue
                refs = prog.inputs_idx[i]
                if any(r >= 0 and not done[r] for r in refs):
                    continue
                ins = [vals[r] if r >= 0 else fields[~r] for r in refs]
                st = stage.stencil
                gets = [_np_get(x, st.radius, stage.boundary) for x in ins]
                vals[i] = np.asarray(st.apply(
                    tuple(gets) if st.arity > 1 else gets[0], coeffs[i],
                    aux if st.has_aux else None), np.float32)
                done[i] = progressed = True
            assert progressed, "cycle leaked past validation"
        fields = [vals[u] if u >= 0 else fields[~u]
                  for u in prog.updates_idx]
    return np.stack(fields) if F > 1 else fields[0]


def _wave2d_program(c=0.1):
    """Second-order wave equation: two fields, one simultaneous rotation."""
    return StencilProgram(
        (StencilStage(make_star(2, 1), name="lapu", inputs=("u",)),
         StencilStage(make_combine(2, 3), name="unext",
                      inputs=("u", "u_prev", "lapu"),
                      coeffs={"w0": 2.0, "w1": -1.0, "w2": c})),
        fields=("u", "u_prev"),
        updates={"u": "unext", "u_prev": "u"})


def _residual_program():
    """Fan-in from a field: r = u - smooth(u) reads `u` twice (raw + through
    a stage)."""
    return StencilProgram(
        (StencilStage("diffusion2d", name="Au", inputs=("u",)),
         StencilStage(make_combine(2, 2), name="resid", inputs=("u", "Au"),
                      coeffs={"w0": 1.0, "w1": -1.0})))


def _diamond_program():
    """Fan-out then fan-in: two independent views of `u` recombined."""
    s = make_star(2, 1)
    return StencilProgram(
        (StencilStage(s, name="a", inputs=("u",)),
         StencilStage(s, name="b", inputs=("u",),
                      coeffs={"c0": 0.5, "c_0_1": 0.2}),
         StencilStage(make_combine(2, 2), name="m", inputs=("a", "b"),
                      coeffs={"w0": 0.6, "w1": 0.4})))


_DAG_CASES = [
    ("wave2d", _wave2d_program, (22, 19), "periodic", 1, 4),
    ("wave2d", _wave2d_program, (26, 17), ("clamp", "reflect"), 2, 3),
    ("residual", _residual_program, (20, 16), "clamp", 2, 3),
    ("diamond", _diamond_program, (24, 15), ("periodic", "clamp"), 2, 4),
]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize(
    "name,build,shape,bc,par_vec,iters", _DAG_CASES,
    ids=[f"{c[0]}-{i}" for i, c in enumerate(_DAG_CASES)])
def test_dag_matches_topological_oracle(backend, name, build, shape, bc,
                                        par_vec, iters):
    if backend == "engine":
        par_vec = 1
    problem = StencilProblem(build(), shape, boundary=bc)
    assert problem.is_dag
    state = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(11), problem.state_shape, jnp.float32, 0.5, 2.0))
    want = _np_dag_oracle(problem, state, iters)
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=8,
                                par_vec=par_vec))
    np.testing.assert_allclose(np.asarray(p.run(state, iters=iters)),
                               want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_multi_field_run_batch(backend):
    problem = StencilProblem(_wave2d_program(), (18, 16), boundary="periodic")
    p = plan(problem, RunConfig(backend=backend, par_time=2, bsize=8,
                                par_vec=1))
    base = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(12), problem.state_shape, jnp.float32, 0.5, 2.0))
    batch = np.stack([base, base * 0.5, base + 0.1])
    want = np.stack([_np_dag_oracle(problem, batch[i], 3) for i in range(3)])
    np.testing.assert_allclose(np.asarray(p.run_batch(batch, iters=3)),
                               want, rtol=3e-5, atol=3e-5)
    with pytest.raises(ValueError, match="state"):
        p.run(base[0], iters=1)           # missing field axis


# --- randomized DAG sweep -----------------------------------------------------

def _draw_dag_case(rng):
    """A random valid 2D DAG: every stage feeds something, every field
    updates, periodicity uniform per axis."""
    n_fields = rng.choice([1, 2])
    fields = tuple(f"f{i}" for i in range(n_fields))
    n_inner = rng.randint(1, 3)
    stages, names = [], []
    for i in range(n_inner):
        arity = rng.choice([1, 1, 2])
        pool = list(fields) + names
        if arity == 1:
            r = rng.choice([0, 1, 2])
            stc = make_star(2, r)
            ins = (rng.choice(pool),)
        else:
            stc = make_combine(2, 2)
            ins = (rng.choice(pool), rng.choice(pool))
        names.append(f"s{i}")
        stages.append(StencilStage(stc, name=f"s{i}", inputs=ins))
    # terminal combine consumes every not-yet-consumed stage (+ field 0)
    consumed = {n for s in stages if s.inputs for n in s.inputs}
    tail = [n for n in names if n not in consumed] + [fields[0]]
    if len(tail) == 1:
        stages.append(StencilStage(make_star(2, 1), name="out",
                                   inputs=(tail[0],)))
    else:
        stages.append(StencilStage(make_combine(2, len(tail)), name="out",
                                   inputs=tuple(tail)))
    updates = {fields[0]: "out"}
    for k in range(1, n_fields):
        updates[fields[k]] = fields[k - 1]      # rotate
    prog = StencilProgram(tuple(stages), fields=fields, updates=updates)
    periodic = [rng.random() < 0.3 for _ in range(2)]
    bc = tuple("periodic" if p_ else rng.choice(_NONPERIODIC)
               for p_ in periodic)
    return (prog, bc, rng.randint(1, 2), rng.choice([1, 2]),
            rng.randint(1, 3), rng.choice(["engine", "pallas_interpret"]),
            rng.randint(0, 10_000))


_DAG_SEEDED = [_draw_dag_case(random.Random(2000 + i)) for i in range(8)]


@pytest.mark.parametrize("case", _DAG_SEEDED,
                         ids=[f"dag{i}" for i in range(len(_DAG_SEEDED))])
def test_random_dag_matches_oracle(case):
    prog, bc, par_time, par_vec, iters, backend, seed = case
    if backend == "engine":
        par_vec = 1
    rad = max(1, sum(s.stencil.radius for s in prog.stages))
    stream = 3 * rad * par_time + 5
    problem = StencilProblem(prog, (stream, 13), boundary=bc)
    state = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(seed), problem.state_shape, jnp.float32, 0.5, 2.0))
    want = _np_dag_oracle(problem, state, iters)
    p = plan(problem, RunConfig(backend=backend, par_time=par_time,
                                bsize=2 * rad * par_time + 4,
                                par_vec=par_vec))
    np.testing.assert_allclose(np.asarray(p.run(state, iters=iters)),
                               want, rtol=3e-5, atol=3e-5)


# --- a linear program runs as its chain DAG ---------------------------------

def test_linear_chain_bit_identical_through_dag_executor():
    """A linear program's DAG is its stage chain, and ``plan().run`` of the
    fused kernel equals the sequential DAG oracle BIT FOR BIT."""
    from repro.programs import chain_dag
    problem = StencilProblem(
        [StencilStage(make_star(2, 1)), StencilStage("diffusion2d")],
        (24, 18), boundary=("clamp", "reflect"))
    assert problem.exec_dag == chain_dag(problem.exec_stages)
    g = jax.random.uniform(jax.random.PRNGKey(13), (24, 18), jnp.float32,
                           0.5, 2.0)
    cf = problem.resolve_coeffs(dtype=jnp.float32)
    p = plan(problem, RunConfig(backend="pallas_interpret", par_time=2,
                                bsize=9, par_vec=1))
    got = p.run(g, iters=5)
    want = oracle_dag_run(problem.exec_dag, g, cf, 5)
    assert np.array_equal(np.asarray(got), np.asarray(want)), \
        "the fused chain and the DAG oracle diverged on a linear program"


# --- DAG validation: every malformed wiring fails at construction -------------

def test_dag_cycle_rejected():
    s = make_star(2, 1)
    with pytest.raises(ValueError, match="[Cc]ycle"):
        StencilProgram((StencilStage(s, name="a", inputs=("b",)),
                        StencilStage(s, name="b", inputs=("a",))))


def test_dag_dangling_input_rejected():
    with pytest.raises(ValueError, match="nope"):
        StencilProgram((StencilStage(make_star(2, 1), name="a",
                                     inputs=("nope",)),))


def test_dag_arity_mismatch_rejected():
    with pytest.raises(ValueError, match="2 .*1|1 .*2|arity|inputs"):
        StencilStage(make_combine(2, 2), name="m", inputs=("u",))


def test_dag_unused_stage_rejected():
    s = make_star(2, 1)
    with pytest.raises(ValueError, match="never consumed"):
        StencilProgram((StencilStage(s, name="dead", inputs=("u",)),
                        StencilStage(s, name="live", inputs=("u",))),
                       updates={"u": "live"})


def test_dag_bad_update_target_rejected():
    with pytest.raises(ValueError):
        StencilProgram((StencilStage(make_star(2, 1), name="a",
                                     inputs=("u",)),),
                       updates={"u": "ghost"})


def test_multi_stage_without_names_needs_explicit_inputs():
    """A multi-input stage downstream of an unnamed fan-out cannot guess its
    wiring — construction must demand explicit inputs."""
    with pytest.raises(ValueError, match="inputs"):
        StencilProgram((StencilStage(make_star(2, 1)),
                        StencilStage(make_combine(2, 2))))


def test_dag_mixed_periodicity_across_branches_rejected():
    """Periodicity is structural (wrap layout, stream extension, the ring):
    two parallel DAG branches cannot disagree on an axis' periodicity."""
    s = make_star(2, 1)
    prog = StencilProgram(
        (StencilStage(s, name="a", inputs=("u",),
                      boundary=("periodic", "clamp")),
         StencilStage(s, name="b", inputs=("u",), boundary="clamp"),
         StencilStage(make_combine(2, 2), name="m", inputs=("a", "b"))))
    with pytest.raises(ValueError, match="periodic"):
        StencilProblem(prog, (16, 16))


# --- cache hygiene for DAG programs -------------------------------------------

def _pr6_fingerprint(prog):
    """The pre-DAG hashing algorithm, verbatim: stage fingerprints + (name,
    coeffs, BC token) only.  Linear programs MUST still hash to this."""
    import hashlib
    h = hashlib.sha1()
    for s in prog.stages:
        btok = (s.boundary.token() if hasattr(s.boundary, "token")
                else repr(s.boundary))
        h.update(stencil_fingerprint(s.stencil).encode())
        h.update(repr((s.name, s.coeffs, btok)).encode())
    return h.hexdigest()[:8]


def test_linear_program_keeps_pre_dag_fingerprint():
    prob = StencilProblem(
        [StencilStage("diffusion2d"), StencilStage(make_star(2, 1))],
        (24, 24), boundary=("clamp", "reflect"))
    assert not prob.is_dag
    assert stencil_fingerprint(prob.stencil) == _pr6_fingerprint(prob.stencil)


def test_dag_wiring_splits_fingerprint():
    shape = (20, 16)
    lin = StencilProblem([StencilStage("diffusion2d"),
                          StencilStage("diffusion2d")], shape)
    dag = StencilProblem(_residual_program(), shape)
    wave = StencilProblem(_wave2d_program(), shape)
    fps = {stencil_fingerprint(p.stencil) for p in (lin, dag, wave)}
    assert len(fps) == 3
    # and the exec keys split too (different compiled graphs)
    assert (_exec_key("engine", lin, None) != _exec_key("engine", dag, None))
    assert (_exec_key("engine", dag, None) != _exec_key("engine", wave, None))


def test_dtype_splits_keys_for_dag_programs():
    """Satellite regression: dtype is part of both cache keys for *program*
    problems, DAG-shaped included."""
    f32 = StencilProblem(_wave2d_program(), (24, 24), dtype="float32")
    b16 = StencilProblem(_wave2d_program(), (24, 24), dtype="bfloat16")
    cfg = _engine_cfg()
    dev = cfg.resolved_device()
    assert (schedule_key(f32, cfg, dev, 1, None, salt="s")
            != schedule_key(b16, cfg, dev, 1, None, salt="s"))
    assert _exec_key("engine", f32, None) != _exec_key("engine", b16, None)


def test_dag_exec_cache_never_serves_across_dtypes():
    clear_exec_cache()
    try:
        problem32 = StencilProblem(_wave2d_program(), (18, 16),
                                   dtype="float32")
        problem16 = StencilProblem(_wave2d_program(), (18, 16),
                                   dtype="bfloat16")
        base = jax.random.uniform(jax.random.PRNGKey(14),
                                  problem32.state_shape, jnp.float32, 0.5, 2.0)
        p32 = plan(problem32, _engine_cfg(bsize=8))
        out32 = p32.run(base, iters=2)
        misses = exec_cache_stats()["misses"]
        p16 = plan(problem16, _engine_cfg(bsize=8))
        out16 = p16.run(base.astype(jnp.bfloat16), iters=2)
        assert exec_cache_stats()["misses"] == misses + 1
        assert out32.dtype == jnp.float32 and out16.dtype == jnp.bfloat16
    finally:
        clear_exec_cache()


# --- distributed DAG (subprocess: fake multi-device view) ---------------------

def test_distributed_dag_matches_oracle():
    script = os.path.join(os.path.dirname(__file__),
                          "dag_distributed_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "ALL OK" in out.stdout
