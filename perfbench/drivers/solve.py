"""Closed-loop solve: one long simulation advanced in chunks.

Each chunk is one ``plan(problem, config).run(state, iters_per_chunk)``
call that continues from the last chunk's result and ends in
``block_until_ready``.  The window runs chunks until ``--seconds`` have
passed and ends with the last chunk, so it holds whole chunks only:
``gcells_per_s`` is every cell update of those chunks over the window's
wall time.  Afterwards the last chunk is compared with the reference run
from that chunk's own input.
"""
from __future__ import annotations

import math
import time

import numpy as np

from perfbench import generate, reference


def _mesh(config):
    import jax
    m = config["mesh"]
    return jax.make_mesh(tuple(m["shape"]), tuple(m["axes"]),
                         devices=jax.devices()[:config["chips"]],
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(m["axes"]))


def _inputs(config, shape, seed, sharding):
    """State (and aux) drawn on the device from the seed in one call."""
    import jax
    import jax.numpy as jnp
    lo, hi = config["inputs"]["state"]
    has_aux = config["aux_fields"] > 0

    def make(key):
        x = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        if not has_aux:
            return (x,)
        alo, ahi = config["inputs"]["aux"]
        return x, jax.random.uniform(jax.random.fold_in(key, 1), shape,
                                     jnp.float32, alo, ahi)

    out = jax.jit(make, out_shardings=sharding)(generate.jax_key(seed))
    return out[0], (out[1] if has_aux else None)


def run(cell) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro.api import RunConfig, StencilProblem, plan
    config, traffic = cell.config, cell.traffic
    shape = tuple(int(d) for d in traffic["grid"])
    iters = int(traffic["iters_per_chunk"])
    cells = math.prod(shape)
    problem = StencilProblem(config["stencil"], shape, dtype=config["dtype"],
                             boundary=config["boundary"])
    mesh = _mesh(config) if config["chips"] > 1 else None
    if mesh is None:
        sharding = SingleDeviceSharding(jax.devices()[0])
        run_config = RunConfig(backend=config["backend"],
                               autotune=config["autotune"])
    else:
        sharding = NamedSharding(mesh, PartitionSpec(*config["mesh"]["axes"]))
        run_config = RunConfig(
            backend=config["backend"], autotune=config["autotune"],
            mesh=mesh, axis_map=tuple(tuple(a) for a in
                                      config["mesh"]["axis_map"]))
    coeffs = dict(config["coefficients"])
    with cell.span("setup"):
        state, aux = _inputs(config, shape, cell.seed, sharding)
        t = time.perf_counter()
        p = plan(problem, run_config)
        plan_wall = time.perf_counter() - t
        if cell.control and mesh is not None:
            def step(g, n):
                return reference.run_by_blocks(config, g, n, aux,
                                               cell.control)
        elif cell.control:
            def step(g, n):
                return reference.run(config, g, n, aux, cell.control)
        else:
            def step(g, n):
                return p.run(g, n, coeffs, aux=aux)
        # iters is a dynamic argument of the one compiled program, so a
        # single super-step warms exactly what a chunk runs
        t_warm = time.perf_counter()
        warm = p.geometry.par_time if p.geometry is not None else 1
        jax.block_until_ready(step(state, warm))
    t_setup = time.perf_counter()
    cell.host["plan_s"] = plan_wall + cell.compile_s(t_warm, t_setup)

    cell.start_trace()
    chunks = 0
    with cell.span("window"):
        t0 = time.perf_counter()
        while True:
            prev = state
            with cell.span("chunk"):
                state = jax.block_until_ready(step(prev, iters))
            chunks += 1
            t1 = time.perf_counter()
            if t1 - t0 >= cell.seconds:
                break
    cell.stop_trace()
    cell.host["chunks"] = chunks
    cell.host["cells_per_chunk"] = cells
    cell.host["iters_per_chunk"] = iters

    peak = cell.peak_bytes()
    del p
    if mesh is None:
        want = reference.run(config, prev, iters, aux)
        diff, mag = reference.gap(state, want)
        del want
    else:
        # free the chips of the last input before the blocks run on them
        prev = np.asarray(prev)
        aux = None if aux is None else np.asarray(aux)
        diff, mag = reference.gap_by_blocks(config, prev, state, iters, aux)
    return {
        "attempted": chunks, "failed": 0,
        "end_to_end": {"gcells_per_s": chunks * cells * iters
                       / (t1 - t0) / 1e9,
                       "setup_s": t_setup - cell.t_start},
        "memory_peak_bytes": peak,
        "compiles_in_window": cell.compiles_between(t0, t1),
        "checks": {"max_rel_err": diff / mag if mag else float("inf")},
        "window": {"chunks": chunks, "seconds": t1 - t0,
                   "chunk_s": (t1 - t0) / chunks},
    }
