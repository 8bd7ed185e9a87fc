"""Periodic-domain wave transport: a pulse that wraps around the grid.

The paper's fixed clamp boundary (§5.1) kills exactly the workloads the
ROADMAP targets next — periodic physics domains.  This demo runs an
advection-diffusion star stencil (an upwind-biased ``make_star`` — the
explicit-update skeleton of a 2D wave/transport solver) on a torus:
``StencilProblem(boundary="periodic")`` is the *only* change from a clamped
run, and every backend honors it through the same ``plan()`` call.

Two BC effects are checked numerically:
  * transport: the pulse's center of mass drifts through the +x edge and
    re-enters at x=0 (impossible under clamp, where it piles up at the wall);
  * conservation: with convex coefficients a periodic domain conserves total
    mass to float precision, while the clamped run leaks at the boundary.

Per-axis mixing works the same way — e.g. a channel flow periodic in x but
clamped in y is ``boundary=("clamp", "periodic")`` (streaming axis first).

    PYTHONPATH=src python examples/wave2d_periodic.py
"""
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, plan
from repro.core import make_star

GRID = (96, 256)
ITERS = 600
DRIFT = 0.35        # upwind bias: cells/step of +x transport


def main():
    # advection-diffusion: diffuse k on every neighbor, bias +x by DRIFT
    st = make_star(2, 1)
    k = 0.1
    coeffs = {name: jnp.float32(k) for name in st.coeff_names}
    coeffs["c0"] = jnp.float32(1.0 - 4 * k)
    # reading the x-1 neighbor with extra weight moves mass +x each step
    coeffs["c_1_-1"] = jnp.float32(k + DRIFT / 2)
    coeffs["c_1_1"] = jnp.float32(k - DRIFT / 2)

    y, x = jnp.meshgrid(jnp.arange(GRID[0]), jnp.arange(GRID[1]),
                        indexing="ij")
    pulse = jnp.exp(-(((y - 48.0) / 10.0) ** 2 + ((x - 64.0) / 10.0) ** 2)
                    ).astype(jnp.float32)

    runs = {}
    for bc in ("periodic", "clamp"):
        p = plan(StencilProblem(st, GRID, boundary=bc),
                 RunConfig(backend="engine", autotune=True, iters_hint=ITERS))
        print(p.describe())
        runs[bc] = p.run(pulse, ITERS, coeffs)

    # transport: after ITERS steps the pulse drifted DRIFT*ITERS cells in +x
    # and must have wrapped around the 256-wide domain under periodic BCs
    expect_x = (64.0 + DRIFT * ITERS) % GRID[1]
    for bc, out in runs.items():
        mass_x = out.sum(axis=0)
        com_phase = jnp.angle(jnp.sum(
            mass_x * jnp.exp(1j * 2 * jnp.pi * jnp.arange(GRID[1])
                             / GRID[1])))  # circular center of mass
        com_x = float(com_phase) % (2 * jnp.pi) / (2 * jnp.pi) * GRID[1]
        drift_err = abs((com_x - expect_x + GRID[1] / 2) % GRID[1]
                        - GRID[1] / 2)
        leak = abs(float(out.sum() - pulse.sum()))
        print(f"{bc:9s} center-of-mass x = {com_x:7.2f} "
              f"(wrap-exact: {expect_x:.2f}, |err| = {drift_err:6.2f}); "
              f"mass leak = {leak:.4f}")
        if bc == "periodic":
            assert drift_err < 2.0, "pulse failed to wrap the torus"
            assert leak < 1e-2, "periodic domain must conserve mass"
    assert abs(float(runs["clamp"].sum() - pulse.sum())) > 1.0, \
        "clamp should visibly leak mass at the +x wall for this drift"
    print("ok: periodic pulse wrapped the torus and conserved mass; "
          "clamp piled up at the wall and leaked")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
