"""Two-stage advection + diffusion as ONE fused StencilProgram.

The classic operator-split transport step — upwind advection followed by
diffusion — is a 2-stage :class:`~repro.programs.StencilProgram`.  Planned
as one problem, both stages run inside every fused super-step: the advected
intermediate field never round-trips HBM (the per-stage traffic breakdown
below shows it billed at zero bytes), while the result stays bit-identical
to running two chained single-stage plans.

    PYTHONPATH=src python examples/advect_diffuse.py
"""
import argparse

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, StencilStage, plan
from repro.core.stencils import make_star


def advection_stage(cx: float, cy: float) -> StencilStage:
    """First-order upwind advection (positive velocity): the cell keeps
    ``1-cx-cy`` of itself and takes ``cy``/``cx`` from its upwind neighbors.
    Built on the generic radius-1 star with every other tap zeroed."""
    return StencilStage(
        make_star(2, 1),
        coeffs={"c0": 1.0 - cx - cy,
                "c_0_-1": cy, "c_0_1": 0.0,     # axis 0 (stream/y) taps
                "c_1_-1": cx, "c_1_1": 0.0},    # axis 1 (x) taps
        name="advect")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dim", type=int, default=192)
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--backend", default="pallas_interpret")
    ap.add_argument("--par-time", type=int, default=2)
    ap.add_argument("--bsize", type=int, default=64)
    args = ap.parse_args()

    shape = (args.dim, args.dim)
    advect = advection_stage(cx=0.2, cy=0.1)
    diffuse = StencilStage("diffusion2d")
    cfg = dict(backend=args.backend, par_time=args.par_time,
               bsize=args.bsize)

    fused = plan(StencilProblem([advect, diffuse], shape), RunConfig(**cfg))
    print(fused.describe())

    grid = jax.random.uniform(jax.random.PRNGKey(0), shape, jnp.float32,
                              0.5, 2.0)
    out_fused = fused.run(grid, iters=args.iters)

    # the unfused rendition: two single-stage plans chained step by step
    p_adv = plan(StencilProblem([advect], shape), RunConfig(**cfg))
    p_dif = plan(StencilProblem("diffusion2d", shape), RunConfig(**cfg))
    out_seq = grid
    for _ in range(args.iters):
        out_seq = p_dif.run(p_adv.run(out_seq, iters=1), iters=1)

    assert bool(jnp.all(out_fused == out_seq)), \
        "fused program diverged from the chained single-stage plans"
    print(f"\nfused == chained plans (bit-identical) over {args.iters} iters"
          f"; checksum {float(jnp.sum(out_fused)):.6e}")

    tr = fused.traffic_report()
    print("\nper-stage breakdown (one super-step):")
    for i, s in enumerate(tr["stages"]):
        print(f"  stage {i}: {s['name']:12s} rad={s['radius']} "
              f"flop_pcu={s['flop_pcu']} bc={s['bc']}")
    print(f"  intermediate HBM bytes (fused):    "
          f"{tr['intermediate_hbm_bytes_per_superstep']}")
    print(f"  intermediate HBM bytes (unfused):  "
          f"{tr['unfused_intermediate_bytes_per_superstep']}")
    print(f"  model bytes/super-step:            "
          f"{tr['model_bytes_per_superstep']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
