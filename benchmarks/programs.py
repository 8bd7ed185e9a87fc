#!/usr/bin/env python
"""Program-fusion micro-benchmark: fused stage chain vs chained plans.

The ``StencilProgram`` subsystem fuses a chain of dependent stencil stages
into one super-step executable: intermediates stay in the rolling VMEM
windows instead of round-tripping HBM, and the whole chain shares one
dispatch per super-step.  This benchmark measures exactly that claim, per
program: one super-step of the fused S-stage plan against the unfused
rendition (S single-stage plans chained step by step), reporting seconds
per super-step, amortized ns per program-iteration cell update, GCell/s,
and the fusion speedup.

Backend: ``pallas_interpret`` by default (the CI-runnable proxy); pass
``--backend pallas`` on a real TPU.

Output: ``results/bench/BENCH_programs.json`` (override with ``--out``).

CI gate (``--baseline``): every measured (program, par_time) row is compared
against the ``program_rows`` section of the committed baseline file; if its
fused per-cell time regresses by more than ``--max-regression`` (default
2x — CI runners are noisy), the process exits non-zero.  Regenerate with::

    python benchmarks/programs.py --smoke --update-baseline results/bench/baseline.json
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax

from repro.api import RunConfig, StencilProblem, StencilStage, plan
from repro.core.stencils import make_combine, make_star
from repro.data import make_stencil_inputs
from repro.programs import StencilProgram


def _advect2d():
    return StencilStage(make_star(2, 1),
                        coeffs={"c0": 0.7, "c_0_-1": 0.1, "c_0_1": 0.0,
                                "c_1_-1": 0.2, "c_1_1": 0.0},
                        name="advect")


def _damp(ndim):
    return StencilStage(make_star(ndim, 0), coeffs={"c0": 0.995},
                        name="damp")


#: name -> (stage thunks, dims, par_time, bsize); smoke = CI-sized
SMOKE_CASES = {
    "advect_diffuse2d": ([_advect2d, lambda: StencilStage("diffusion2d")],
                         (96, 256), 2, 256),
    "diffuse_damp2d": ([lambda: StencilStage("diffusion2d"),
                        lambda: _damp(2)], (96, 256), 2, 256),
}
FULL_CASES = {
    "advect_diffuse2d": ([_advect2d, lambda: StencilStage("diffusion2d")],
                         (512, 1024), 4, 512),
    "diffuse_damp2d": ([lambda: StencilStage("diffusion2d"),
                        lambda: _damp(2)], (512, 1024), 4, 512),
    "diffuse3_2d": ([lambda: StencilStage("diffusion2d")] * 3,
                    (512, 1024), 2, 512),
}


def _wave2d_program():
    """Second-order wave equation: the canonical DAG program — two fields
    (``u``, ``u_prev``), a Laplacian stage fanned into a 3-way combine,
    both fields rotated simultaneously each iteration."""
    return StencilProgram(
        (StencilStage(make_star(2, 1), name="lapu", inputs=("u",)),
         StencilStage(make_combine(2, 3), name="unext",
                      inputs=("u", "u_prev", "lapu"),
                      coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.1})),
        fields=("u", "u_prev"),
        updates={"u": "unext", "u_prev": "u"})


def _diamond_program():
    """Fan-out / fan-in: two radius-1 views of ``u`` recombined — exercises
    the per-edge window sizing the DAG unroll prices."""
    s = make_star(2, 1)
    return StencilProgram(
        (StencilStage(s, name="a", inputs=("u",)),
         StencilStage(s, name="b", inputs=("u",),
                      coeffs={"c0": 0.5, "c_0_1": 0.2}),
         StencilStage(make_combine(2, 2), name="m", inputs=("a", "b"),
                      coeffs={"w0": 0.6, "w1": 0.4})))


#: name -> (program thunk, dims, par_time, bsize)
DAG_SMOKE_CASES = {
    "wave2d": (_wave2d_program, (96, 256), 2, 256),
    "diamond2d": (_diamond_program, (96, 256), 2, 256),
}
DAG_FULL_CASES = {
    "wave2d": (_wave2d_program, (512, 1024), 4, 512),
    "diamond2d": (_diamond_program, (512, 1024), 2, 512),
}


def _time_call(fn, warmup, repeats):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_case(backend, name, stages, dims, par_time, bsize, warmup,
               repeats):
    problem = StencilProblem(stages, dims)
    cfg = dict(backend=backend, par_time=par_time, bsize=bsize)
    fused = plan(problem, RunConfig(**cfg))
    # the unfused rendition: one single-stage plan per stage, chained —
    # every stage boundary is an HBM round-trip and a dispatch
    singles = [plan(StencilProblem([s], dims), RunConfig(**cfg))
               for s in problem.stages]
    grid, aux = make_stencil_inputs(jax.random.PRNGKey(0), dims,
                                    problem.needs_aux)

    def run_fused():
        return fused.run(grid, par_time, aux=aux)   # one super-step

    def run_unfused():
        g = grid
        for _ in range(par_time):
            for p in singles:
                g = p.run(g, 1, aux=aux)
        return g

    s_fused = _time_call(run_fused, warmup, repeats)
    s_unfused = _time_call(run_unfused, warmup, repeats)
    cells = math.prod(dims) * par_time          # program iterations
    return {
        "program": name, "n_stages": len(problem.stages),
        "dims": list(dims), "par_time": par_time, "bsize": bsize,
        "s_per_superstep": s_fused,
        "ns_per_cell": s_fused / cells * 1e9,
        "gcells_s": cells / s_fused / 1e9,
        "unfused_s_per_superstep": s_unfused,
        "unfused_gcells_s": cells / s_unfused / 1e9,
        "fusion_speedup": s_unfused / s_fused,
        "intermediate_hbm_bytes_per_superstep":
            fused.traffic_report()["intermediate_hbm_bytes_per_superstep"],
    }


def bench_dag_case(backend, name, build, dims, par_time, bsize, warmup,
                   repeats):
    """One fused super-step of a DAG program (no unfused rendition exists:
    a DAG's intermediates are not expressible as chained single-stage
    plans).  Gated on fused per-cell time alone."""
    problem = StencilProblem(build(), dims)
    fused = plan(problem, RunConfig(backend=backend, par_time=par_time,
                                    bsize=bsize))
    key = jax.random.PRNGKey(0)
    state = jax.random.uniform(key, problem.state_shape, minval=0.5,
                               maxval=2.0)

    def run_fused():
        return fused.run(state, par_time)           # one super-step

    s_fused = _time_call(run_fused, warmup, repeats)
    cells = math.prod(dims) * par_time              # program iterations
    return {
        "program": name, "n_stages": len(problem.stages),
        "n_fields": len(problem.fields),
        "dims": list(dims), "par_time": par_time, "bsize": bsize,
        "s_per_superstep": s_fused,
        "ns_per_cell": s_fused / cells * 1e9,
        "gcells_s": cells / s_fused / 1e9,
    }


def check_regression(rows, baseline_path: Path, max_regression: float,
                     section: str = "program_rows"):
    """Fused per-cell time of every (program, par_time) row vs the
    baseline's ``section``.  Returns failure strings (empty = pass)."""
    try:
        base = json.loads(baseline_path.read_text())
    except (OSError, ValueError) as e:
        return [f"baseline {baseline_path} unreadable: {e}"]
    by_key = {(r["program"], r["par_time"]): r
              for r in base.get(section, [])}
    if not by_key:
        return [f"baseline {baseline_path} has no {section} section — "
                "regenerate it with --update-baseline"]
    failures = []
    for r in rows:
        b = by_key.get((r["program"], r["par_time"]))
        if b is None:
            print(f"  [gate] no program baseline for "
                  f"({r['program']}, T={r['par_time']}) — skipped")
            continue
        ratio = r["ns_per_cell"] / b["ns_per_cell"]
        status = "OK" if ratio <= max_regression else "REGRESSED"
        print(f"  [gate] {r['program']}/T={r['par_time']}: "
              f"{r['ns_per_cell']:.2f} ns/cell vs baseline "
              f"{b['ns_per_cell']:.2f} -> x{ratio:.2f} {status}")
        if ratio > max_regression:
            failures.append(
                f"{r['program']}/T={r['par_time']} fused per-cell time "
                f"regressed x{ratio:.2f} (> x{max_regression:.2f})")
    return failures


def update_baseline(rows, baseline_path: Path, dag_rows=None) -> None:
    """Write/refresh the ``program_rows`` (and ``program_dag_rows``)
    sections, preserving whatever else (kernel/throughput rows) the shared
    baseline file holds."""
    try:
        base = json.loads(baseline_path.read_text())
    except (OSError, ValueError):
        base = {}
    base["program_rows"] = rows
    if dag_rows is not None:
        base["program_dag_rows"] = dag_rows
    baseline_path.parent.mkdir(parents=True, exist_ok=True)
    baseline_path.write_text(json.dumps(base, indent=1, sort_keys=True)
                             + "\n")
    print(f"updated program_rows/program_dag_rows in {baseline_path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized grids (seconds, interpret-friendly)")
    ap.add_argument("--backend", default="pallas_interpret",
                    help="pallas_interpret (CI proxy) or pallas (real TPU)")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="results/bench/BENCH_programs.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON to gate against (CI perf-smoke)")
    ap.add_argument("--update-baseline", default=None, metavar="PATH",
                    help="write program_rows into this baseline file & exit")
    ap.add_argument("--max-regression", type=float, default=2.0,
                    help="fail if fused ns/cell exceeds baseline by this "
                         "factor")
    args = ap.parse_args(argv)

    cases = SMOKE_CASES if args.smoke else FULL_CASES
    rows = []
    print(f"{'program':18s} {'dims':>12s} {'T':>2s} {'fused ms':>9s} "
          f"{'unfused ms':>10s} {'speedup':>7s} {'GCell/s':>8s}")
    for name, (thunks, dims, par_time, bsize) in cases.items():
        stages = [t() for t in thunks]
        r = bench_case(args.backend, name, stages, dims, par_time, bsize,
                       args.warmup, args.repeats)
        rows.append(r)
        print(f"{r['program']:18s} {str(tuple(r['dims'])):>12s} "
              f"{r['par_time']:2d} {r['s_per_superstep'] * 1e3:9.2f} "
              f"{r['unfused_s_per_superstep'] * 1e3:10.2f} "
              f"x{r['fusion_speedup']:6.2f} {r['gcells_s']:8.4f}")
        assert r["intermediate_hbm_bytes_per_superstep"] == 0

    dag_cases = DAG_SMOKE_CASES if args.smoke else DAG_FULL_CASES
    dag_rows = []
    for name, (build, dims, par_time, bsize) in dag_cases.items():
        r = bench_dag_case(args.backend, name, build, dims, par_time, bsize,
                           args.warmup, args.repeats)
        dag_rows.append(r)
        print(f"{r['program']:18s} {str(tuple(r['dims'])):>12s} "
              f"{r['par_time']:2d} {r['s_per_superstep'] * 1e3:9.2f} "
              f"{'(dag)':>10s} {'':>7s} {r['gcells_s']:8.4f}")

    out = {
        "schema": 1,
        "mode": "smoke" if args.smoke else "full",
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "backend": args.backend,
        "rows": rows,
        "dag_rows": dag_rows,
    }
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    print(f"wrote {out_path}")

    if args.update_baseline:
        update_baseline(rows, Path(args.update_baseline), dag_rows)
        return 0
    if args.baseline:
        failures = check_regression(rows, Path(args.baseline),
                                    args.max_regression)
        failures += check_regression(dag_rows, Path(args.baseline),
                                     args.max_regression,
                                     section="program_dag_rows")
        if failures:
            print("PERF REGRESSION:\n  " + "\n  ".join(failures),
                  file=sys.stderr)
            return 1
        print("perf gate: OK")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
