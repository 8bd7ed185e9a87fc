"""Second-order wave equation as a multi-field DAG StencilProgram.

The leapfrog update

    u_next = 2*u - u_prev + c^2 * lap(u)

is not a chain: it reads TWO state fields (``u``, ``u_prev``), fans the
Laplacian stage and both raw fields into one combine node, and rotates both
fields simultaneously at the end of every iteration.  As a
:class:`~repro.programs.StencilProgram` with ``fields=`` and ``updates=``,
the whole graph runs inside each fused super-step on every backend —
``u_next`` and ``lap(u)`` never round-trip HBM — and the state travels as
one ``(2, ny, nx)`` stack.

    PYTHONPATH=src python examples/wave2d_program.py
"""
import argparse

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, StencilStage, plan
from repro.core.stencils import make_combine, make_star
from repro.kernels.ref import oracle_dag_run
from repro.programs import StencilProgram


def wave_program(c: float) -> StencilProgram:
    """lap = 5-point Laplacian of u; unext = 2u - u_prev + c^2*lap."""
    lap = StencilStage(
        make_star(2, 1),
        coeffs={"c0": -4.0, "c_0_-1": 1.0, "c_0_1": 1.0,
                "c_1_-1": 1.0, "c_1_1": 1.0},
        name="lapu", inputs=("u",))
    unext = StencilStage(
        make_combine(2, 3),
        coeffs={"w0": 2.0, "w1": -1.0, "w2": c * c},
        name="unext", inputs=("u", "u_prev", "lapu"))
    return StencilProgram((lap, unext), fields=("u", "u_prev"),
                          updates={"u": "unext", "u_prev": "u"})


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dim", type=int, default=192)
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--wave-speed", type=float, default=0.4)
    ap.add_argument("--backend", default="pallas_interpret")
    ap.add_argument("--par-time", type=int, default=2)
    ap.add_argument("--bsize", type=int, default=64)
    args = ap.parse_args()

    shape = (args.dim, args.dim)
    problem = StencilProblem(wave_program(args.wave_speed), shape,
                             boundary="periodic")
    assert problem.is_dag and problem.state_shape == (2,) + shape
    p = plan(problem, RunConfig(backend=args.backend,
                                par_time=args.par_time, bsize=args.bsize))
    print(p.describe())

    # a Gaussian pulse at rest: u == u_prev
    yy, xx = jnp.meshgrid(*(jnp.arange(d) for d in shape), indexing="ij")
    pulse = jnp.exp(-(((yy - shape[0] / 2) ** 2 + (xx - shape[1] / 2) ** 2)
                      / (2 * (shape[0] / 16) ** 2))).astype(jnp.float32)
    state = jnp.stack([pulse, pulse])

    out = p.run(state, iters=args.iters)
    want = oracle_dag_run(problem.exec_dag, state,
                          problem.resolve_coeffs(dtype=jnp.float32),
                          args.iters, None)
    err = float(jnp.max(jnp.abs(out - want)))
    print(f"\n{args.iters} iters on {args.backend}: "
          f"max |err| vs topological oracle = {err:.2e}")
    assert err < 1e-4

    u, u_prev = out
    print(f"u      checksum {float(jnp.sum(u)):.6e}")
    print(f"u_prev checksum {float(jnp.sum(u_prev)):.6e}")
    energy = float(jnp.sum((u - u_prev) ** 2))
    print(f"kinetic proxy sum((u - u_prev)^2) = {energy:.6e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
