"""Quickstart: the paper's technique in one page.

Describes Diffusion 2D as a ``StencilProblem``, lets ``plan()`` pick
(bsize, par_time) with the performance model (paper §4, §5.3), runs the
combined spatial + temporal blocked backends through the resulting
``StencilPlan``, and checks them against the unblocked oracle.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, plan, tune
from repro.core import DIFFUSION2D, default_coeffs

GRID = (512, 512)
ITERS = 12


def main():
    key = jax.random.PRNGKey(0)
    grid = jax.random.uniform(key, GRID, jnp.float32, 0.5, 2.0)
    coeffs = default_coeffs(DIFFUSION2D)
    problem = StencilProblem("diffusion2d", GRID)

    # 1. Design-space pruning with the performance model (paper §4, §5.3):
    #    plan(autotune=True) enumerates (bsize, par_time), drops configs over
    #    the VMEM budget, and compiles the best one.
    eng = plan(problem, RunConfig(backend="engine", autotune=True,
                                  iters_hint=ITERS))
    print(eng.describe())
    print("runner-up candidates (paper §5.3 pruning):")
    for p in eng.candidates[1:4]:
        print("  ", p.describe())
    bsize, par_time = eng.geometry.bsize, eng.geometry.par_time

    # 2. Run the same schedule through every backend via the one plan() call.
    cfg = RunConfig(par_time=par_time, bsize=bsize)
    ref = plan(problem, dataclasses.replace(cfg, backend="reference")
               ).run(grid, ITERS, coeffs)            # unblocked oracle
    out_eng = eng.run(grid, ITERS, coeffs)           # pure-JAX blocked engine
    out_pal = plan(problem, dataclasses.replace(cfg, backend="pallas_interpret")
                   ).run(grid, ITERS, coeffs)        # Pallas kernel (interpret)

    for name, out in [("engine", out_eng), ("pallas", out_pal)]:
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"{name:8s} max|err| vs oracle = {err:.3e}")
        assert err < 1e-4, name

    print(f"\nblocked == unblocked for bsize={bsize}, par_time={par_time} "
          f"({ITERS} iters, grid {GRID}).")
    print("model vs kernel DMA traffic:", eng.traffic_report())

    # 3. Measured autotuning (Table 4's "Measured" column): time the model's
    #    top candidates on the real backend and compile the fastest.  With a
    #    cache path (the default), the winner is persisted and later plan()
    #    calls skip the timing entirely; cache=False keeps this demo
    #    filesystem-free.
    meas = tune(problem, RunConfig(backend="engine", iters_hint=ITERS,
                                   tune_top_k=2, tune_repeats=2, cache=False))
    print("\nmeasured autotune (model shortlist, stopwatch winner):")
    for c in meas.candidates:
        print("  ", c.describe())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
