"""The distributed backend's per-shard streaming kernel on four virtual CPU
devices, case by case; prints one JSON line of results.

Run in a subprocess (tests/test_mesh_kernel.py) so the main pytest process
keeps its single-device view.  Every case plans ``backend="distributed"``
and ``backend="pallas_interpret"`` with one schedule, runs both from seeded
random inputs, and reports the distributed result's max relative error
against the ``kernels/ref.py`` oracle and whether it equals the one-device
kernel's bit for bit.  ``trace`` reports the scopes of the mesh program's
instructions.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import json  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (RunConfig, StencilProblem, StencilStage,  # noqa: E402
                       plan)
from repro.core.stencils import make_combine, make_star  # noqa: E402
from repro.kernels.ref import oracle_dag_run  # noqa: E402


def _wave():
    from repro.programs import StencilProgram
    lap = StencilStage(make_star(2, 1), name="lapu", inputs=("u",))
    unext = StencilStage(make_combine(2, 3), name="unext",
                         inputs=("u", "u_prev", "lapu"),
                         coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.1})
    return StencilProgram((lap, unext), fields=("u", "u_prev"),
                          updates={"u": "unext", "u_prev": "u"})


#: name -> (stencil, shape, bc, mesh shape, axis_map, par_time, bsize,
#: par_vec, iters, batch); mesh axes are ("a", "b")
CASES = {
    "2x2-clamp": ("diffusion2d", (64, 256), "clamp", (2, 2),
                  (("a",), ("b",)), 4, 64, 8, 11, None),
    "2x2-constant": ("diffusion2d", (64, 256), "constant:0.7", (2, 2),
                     (("a",), ("b",)), 4, 64, 8, 9, None),
    "2x2-reflect-periodic": ("diffusion2d", (64, 256), ("reflect", "periodic"),
                             (2, 2), (("a",), ("b",)), 4, 64, 8, 10, None),
    "1x4-periodic-constant": ("diffusion2d", (24, 512),
                              ("periodic", "constant:1.5"), (1, 4),
                              (None, ("a", "b")), 2, 48, 8, 7, None),
    "1x4-reflect-clamp": ("diffusion2d", (24, 512), ("reflect", "clamp"),
                          (1, 4), (None, ("a", "b")), 3, 48, 1, 8, None),
    "4x1-clamp-reflect": ("diffusion2d", (64, 96), ("clamp", "reflect"),
                          (4, 1), (("a", "b"), None), 4, 64, 8, 13, None),
    "4x1-constant-periodic": ("diffusion2d", (64, 96),
                              ("constant:0.3", "periodic"), (4, 1),
                              (("a", "b"), None), 2, 32, 1, 5, None),
    "2x2-hotspot-aux": ("hotspot2d", (64, 256), "clamp", (2, 2),
                        (("a",), ("b",)), 4, 64, 8, 10, None),
    "2x2-hotspot-batch": ("hotspot2d", (32, 128), ("periodic", "reflect"),
                          (2, 2), (("a",), ("b",)), 2, 32, 8, 5, 3),
    "2x2-wave-dag": ("wave", (32, 128), "periodic", (2, 2),
                     (("a",), ("b",)), 2, 32, 8, 5, None),
    "2x2-uneven-blocks": ("diffusion2d", (40, 200), "clamp", (2, 2),
                          (("a",), ("b",)), 4, 64, 8, 7, None),
    "2x2-diffusion3d": ("diffusion3d", (8, 24, 48), "clamp", (2, 2),
                        (None, ("a",), ("b",)), 2, (12, 16), 1, 5, None),
    # five super-steps: the loop's first, two pairs of carry buffers, no
    # trailing odd one
    "2x2-five-supersteps": ("diffusion2d", (64, 256), ("reflect", "clamp"),
                            (2, 2), (("a",), ("b",)), 4, 64, 8, 20, None),
}


def _inputs(problem, batch, seed):
    k = jax.random.PRNGKey(seed)
    shape = ((batch,) if batch else ()) + problem.state_shape
    grid = jax.random.uniform(k, shape, jnp.float32, 0.5, 2.0)
    aux = None
    if problem.needs_aux:
        ashape = ((batch,) if batch else ()) + problem.shape
        aux = jax.random.uniform(jax.random.fold_in(k, 1), ashape,
                                 jnp.float32, 0.0, 0.1)
    return grid, aux


def _oracle(problem, grid, iters, aux):
    return oracle_dag_run(problem.exec_dag, grid,
                          problem.resolve_coeffs(dtype=jnp.float32), iters,
                          aux)


def run_case(name, seed):
    (stencil, shape, bc, mshape, axis_map, par_time, bsize, par_vec, iters,
     batch) = CASES[name]
    problem = StencilProblem(_wave() if stencil == "wave" else stencil,
                             shape, boundary=bc)
    mesh = jax.make_mesh(mshape, ("a", "b"))
    sched = dict(par_time=par_time, bsize=bsize, par_vec=par_vec)
    dist = plan(problem, RunConfig(backend="distributed", mesh=mesh,
                                   axis_map=axis_map, **sched))
    one = plan(problem, RunConfig(backend="pallas_interpret", **sched))
    grid, aux = _inputs(problem, batch, seed)
    if batch:
        got = dist.run_batch(grid, iters, aux=aux)
        same = one.run_batch(grid, iters, aux=aux)
        want = jnp.stack([_oracle(problem, grid[i], iters, aux[i])
                          for i in range(batch)])
        # the shared-aux form of run_batch too
        got_shared = dist.run_batch(grid, iters, aux=aux[0])
        want_shared = jnp.stack([_oracle(problem, grid[i], iters, aux[0])
                                 for i in range(batch)])
        got = jnp.concatenate([got, got_shared])
        want = jnp.concatenate([want, want_shared])
        same = jnp.concatenate([same, one.run_batch(grid, iters,
                                                    aux=aux[0])])
    else:
        got = dist.run(grid, iters, aux=aux)
        same = one.run(grid, iters, aux=aux)
        want = _oracle(problem, grid, iters, aux)
    got, want, same = (np.asarray(x) for x in (got, want, same))
    return {"rel_err": float(np.max(np.abs(got - want))
                             / np.max(np.abs(want))),
            "bit_equal": bool(np.array_equal(got, same)),
            "finite": bool(np.isfinite(got).all())}


def trace_scopes():
    """The 2x2 program's instructions: which carry the kernel's name under
    ``stencil.superstep``, and the scope of every collective-permute."""
    problem = StencilProblem("diffusion2d", (64, 256))
    mesh = jax.make_mesh((2, 2), ("a", "b"))
    p = plan(problem, RunConfig(backend="distributed", mesh=mesh,
                                axis_map=(("a",), ("b",)), par_time=4,
                                bsize=64, par_vec=8))
    hlo = p.lower(jnp.zeros((64, 256), jnp.float32)).compile().as_text()
    kernel, permutes = 0, []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = [^\n]*$", hlo, re.M):
        on = re.search(r'op_name="([^"]*)"', m.group(0))
        op_name = on.group(1) if on else ""
        if "stencil.superstep/jit(superstep_chain)/" in op_name:
            kernel += 1
        if re.search(r" collective-permute(-start)?\(", m.group(0)):
            permutes.append(op_name)
    return {"kernel_ops": kernel, "permutes": permutes}


if __name__ == "__main__":
    assert len(jax.devices()) == 4, jax.devices()
    out = {name: run_case(name, seed)
           for seed, name in enumerate(CASES, start=1500)}
    out["trace"] = trace_scopes()
    print(json.dumps(out))
