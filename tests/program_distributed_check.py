"""Multi-device program (stage-chain) correctness check.

Run in a subprocess with 4 fake CPU devices (tests/test_programs.py) so the
main pytest process keeps its single-device view.  The distributed backend
exchanges ONE halo of width ``sum(stage radii) * par_time`` per super-step
for the whole fused chain.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import RunConfig, StencilProblem, StencilStage, plan
from repro.core.stencils import make_star
from repro.kernels.ref import oracle_dag_run


def check_program():
    mesh = jax.make_mesh((4,), ("data",))
    shape = (32, 24)
    g = jax.random.uniform(jax.random.PRNGKey(0), shape, jnp.float32,
                           0.5, 2.0)
    prob = StencilProblem(
        [StencilStage(make_star(2, 1)), StencilStage("diffusion2d")],
        shape, boundary=("clamp", "periodic"))
    want = oracle_dag_run(prob.exec_dag, g,
                          prob.resolve_coeffs(dtype=jnp.float32), 5)
    p = plan(prob, RunConfig(backend="distributed", mesh=mesh,
                             par_time=2, bsize=12))
    got = p.run(g, iters=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    print("program ok")

    gs = jax.random.uniform(jax.random.PRNGKey(1), (3,) + shape, jnp.float32,
                            0.5, 2.0)
    outs = p.run_batch(gs, iters=4)
    wants = jnp.stack([
        oracle_dag_run(prob.exec_dag, gs[i],
                       prob.resolve_coeffs(dtype=jnp.float32), 4)
        for i in range(3)])
    np.testing.assert_allclose(np.asarray(outs), np.asarray(wants),
                               rtol=3e-5, atol=3e-5)
    print("program batch ok")


if __name__ == "__main__":
    assert len(jax.devices()) == 4, jax.devices()
    check_program()
    print("ALL OK")
