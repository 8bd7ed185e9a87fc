"""Multi-device distributed-stencil correctness check.

Run in a subprocess with 8 fake CPU devices (tests/test_distributed.py) so
the main pytest process keeps its single-device view.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import RunConfig, StencilProblem, plan
from repro.core import STENCILS, default_coeffs
from repro.kernels.ref import oracle_run


def distributed(name, g, c, iters, par_time, bsize, mesh, axis_map,
                aux=None):
    """``iters`` steps of the named stencil through the distributed
    backend's plan, sharded over ``mesh`` per ``axis_map``."""
    p = plan(StencilProblem(name, g.shape),
             RunConfig(backend="distributed", par_time=par_time,
                       bsize=bsize, mesh=mesh, axis_map=axis_map))
    return p.run(g, iters, c, aux=aux)


def check_2d():
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    st = STENCILS["diffusion2d"]
    dims = (32, 64)
    g = jax.random.uniform(jax.random.PRNGKey(0), dims, jnp.float32, 0.5, 2.0)
    c = default_coeffs(st)
    for iters, pt in [(1, 1), (4, 2), (5, 2)]:
        want = oracle_run(st, g, c, iters)
        got = distributed("diffusion2d", g, c, iters, pt, (24,), mesh,
                          (("data",), ("model",)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)
    print("2d ok")


def check_2d_joint_axes():
    """Grid axis sharded over a *tuple* of mesh axes (pod+data pattern)."""
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    st = STENCILS["hotspot2d"]
    dims = (32, 48)
    k = jax.random.PRNGKey(1)
    g = jax.random.uniform(k, dims, jnp.float32, 0.5, 2.0)
    aux = jax.random.uniform(jax.random.fold_in(k, 1), dims, jnp.float32,
                             0.0, 0.1)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, 4, aux)
    got = distributed("hotspot2d", g, c, 4, 2, (16,), mesh,
                      (("pod", "data"), ("model",)), aux=aux)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    print("2d joint-axes ok")


def check_3d():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    st = STENCILS["diffusion3d"]
    dims = (16, 24, 24)
    g = jax.random.uniform(jax.random.PRNGKey(2), dims, jnp.float32, 0.5, 2.0)
    c = default_coeffs(st)
    want = oracle_run(st, g, c, 4)
    got = distributed("diffusion3d", g, c, 4, 2, (12, 12), mesh,
                      (("pod",), ("data",), ("model",)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    print("3d ok")


if __name__ == "__main__":
    assert len(jax.devices()) == 8, jax.devices()
    check_2d()
    check_2d_joint_axes()
    check_3d()
    print("ALL OK")
