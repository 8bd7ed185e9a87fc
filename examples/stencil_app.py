"""End-to-end stencil application driver — the paper's Table 4 workflow.

Picks a stencil, lets ``plan()`` autotune (bsize, par_time) with the
performance model, runs a few hundred iterations through the resulting
``StencilPlan``, and reports measured GCell/s / GFLOP/s / GB/s next to the
model's prediction (paper §6.2 "model accuracy").

    PYTHONPATH=src python examples/stencil_app.py --stencil diffusion2d \
        --dim 1024 --iters 200

On this CPU container the measured numbers reflect the host, not a TPU;
the structure (plan -> run -> model-accuracy) is the deliverable.
"""
import argparse
import math
import time

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, plan
from repro.core import STENCILS
from repro.data import make_stencil_inputs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stencil", default="diffusion2d",
                    choices=sorted(STENCILS))
    ap.add_argument("--dim", type=int, default=1024,
                    help="grid extent per dimension")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--backend", default="engine",
                    choices=["engine", "pallas_interpret", "reference"])
    ap.add_argument("--par-time", type=int, default=None,
                    help="override autotuned par_time")
    ap.add_argument("--bsize", type=int, default=None,
                    help="override autotuned block size")
    args = ap.parse_args()

    st = STENCILS[args.stencil]
    ndim = st.ndim
    dims = (args.dim,) * ndim if ndim == 2 else \
        (max(64, args.dim // 4),) + (args.dim,) * 2
    grid, aux = make_stencil_inputs(jax.random.PRNGKey(0), dims, st.has_aux)

    # 1. one plan() call: any schedule field left unset is filled by the
    #    perf-model autotuner (paper §5.3)
    p = plan(StencilProblem(st, dims),
             RunConfig(backend=args.backend, par_time=args.par_time,
                       bsize=args.bsize, iters_hint=args.iters))
    pred = p.predicted(args.iters)
    print(p.describe())
    print(f"  predicted run_time on TPU v5e: {pred.run_time * 1e3:.2f} ms "
          f"({pred.n_super} super-steps)")

    # 2. run it (jit warm-up excluded from timing); the plan is reusable
    out = p.run(grid, args.iters, aux=aux)
    out.block_until_ready()
    t0 = time.perf_counter()
    out = p.run(grid, args.iters, aux=aux)
    out.block_until_ready()
    dt = time.perf_counter() - t0

    # 3. measured throughput (paper Table 4 columns) on THIS host
    cells = math.prod(dims) * args.iters
    gcells = cells / dt / 1e9
    gflops = cells * st.flop_pcu / dt / 1e9
    gbytes = cells * st.bytes_pcu / dt / 1e9   # effective, full-locality bytes
    print(f"  measured ({args.backend}, this host): {dt:.3f} s = "
          f"{gcells:.3f} GCell/s, {gflops:.2f} GFLOP/s, {gbytes:.2f} GB/s")
    print(f"  checksum: {float(jnp.sum(out)):.6e}")
    print("  (TPU-projected numbers come from the perf model; "
          "autotune='measure' times each candidate against it.)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
