"""Long-running stencil simulation with checkpoint/restart — the paper's
application wired to the fault-tolerance substrate.

The physics is a *program*: the physical operator chained with a pointwise
damping stage (``u *= damp`` — a radius-0 stencil), fused into every
super-step via the ``StencilProgram`` API.  ``--damp 1.0`` degrades the
chain to the bare legacy stencil (the old single-operator path, kept as
the comparison baseline).

Builds one autotuned ``StencilPlan`` and advances it in super-steps of
``par_time`` fused iterations, checkpointing the grid every N super-steps.
Kill it mid-run and start it again: it resumes from the latest snapshot
(integrity-checked, atomic). ``--inject-failure`` simulates a device loss.

    PYTHONPATH=src python examples/simulate.py --iters 400
    PYTHONPATH=src python examples/simulate.py --iters 400  # resumes
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, StencilStage, plan
from repro.checkpoint import CheckpointManager
from repro.core import STENCILS
from repro.core.stencils import make_star
from repro.data import make_stencil_inputs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stencil", default="diffusion2d",
                    choices=sorted(STENCILS))
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--iters", type=int, default=400)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_simulate")
    ap.add_argument("--ckpt-every", type=int, default=4,
                    help="checkpoint every N super-steps")
    ap.add_argument("--inject-failure", type=int, default=None,
                    help="raise at this super-step once (recovers)")
    ap.add_argument("--damp", type=float, default=0.999,
                    help="per-step damping factor chained as a pointwise "
                         "program stage; 1.0 = legacy bare-stencil path")
    args = ap.parse_args()

    st = STENCILS[args.stencil]
    dims = (args.dim,) * 2 if st.ndim == 2 else \
        (max(32, args.dim // 8), args.dim // 2, args.dim // 2)
    if args.damp != 1.0:
        # program path: operator + pointwise damping, fused per super-step
        operator = [StencilStage(st),
                    StencilStage(make_star(st.ndim, 0),
                                 coeffs={"c0": args.damp}, name="damp")]
    else:
        operator = st                # legacy single-operator comparison path
    sim = plan(StencilProblem(operator, dims),
               RunConfig(backend="engine", autotune=True,
                         iters_hint=args.iters))
    pt, bsize = sim.geometry.par_time, sim.geometry.bsize
    n_super = -(-args.iters // pt)
    print(f"{sim.problem.stencil.name} {dims}, {args.iters} iters = "
          f"{n_super} super-steps of par_time={pt}, bsize={bsize}")

    grid, aux = make_stencil_inputs(jax.random.PRNGKey(0), dims, st.has_aux)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    template = {"grid": grid, "super": jnp.zeros((), jnp.int32)}
    restored, _ = mgr.restore_latest(template)
    start = 0
    if restored is not None:
        grid = restored["grid"]
        start = int(restored["super"]) + 1
        print(f"[restart] resumed at super-step {start}")

    fails = ({args.inject_failure} if args.inject_failure is not None
             else set())
    t0 = time.time()
    s = start
    while s < n_super:
        try:
            if s in fails:
                fails.remove(s)
                raise RuntimeError(f"injected failure at super-step {s}")
            steps = min(pt, args.iters - s * pt)
            grid = sim.run(grid, steps, aux=aux)   # one super-step per call
        except RuntimeError as e:
            print(f"[failure] {e}; restoring latest checkpoint")
            restored, _ = mgr.restore_latest(template)
            if restored is not None:
                grid = restored["grid"]
                s = int(restored["super"]) + 1
            else:
                grid, _ = make_stencil_inputs(jax.random.PRNGKey(0), dims,
                                              st.has_aux)
                s = 0
            continue
        if s % args.ckpt_every == 0 or s == n_super - 1:
            mgr.save_async({"grid": grid, "super": jnp.asarray(s, jnp.int32)},
                           s)
        s += 1
    mgr.wait()
    dt = time.time() - t0
    done = n_super - start
    print(f"finished {done} super-steps in {dt:.2f}s; "
          f"checksum {float(jnp.sum(grid)):.6e}")
    print(f"checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
