"""Stencil-as-a-service: boot, submit, observe — plus the manual pattern.

The ROADMAP's "heavy traffic" north star is a process that sees a stream
of requests against a handful of problem shapes.  ``repro.serve`` packages
the whole serving pattern behind two calls::

    service = await repro.serve.from_config({...})   # booted + pre-warmed
    result  = await service.submit(StencilRequest(problem, grid, iters))

The service buckets requests by (stencil, shape, boundary, dtype),
coalesces each bucket's arrivals into one padded ``run_batch`` launch
under a (max_batch, max_wait_ms) policy, and answers every request —
served, rejected (bounded queue, 429-style retry-after), or expired —
through its future.  Results are bit-identical to a per-request
``plan().run()`` loop: padding replicates along the batch axis only.

Manual mode (the pre-service pattern, still fully supported): call
``plan()`` once per shape and ``run_batch()`` over each arriving batch
yourself — shown at the bottom for when you already hold batches and
want no event loop in the way.

    PYTHONPATH=src python examples/serve_stencil.py
"""
import asyncio
import time

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, plan
from repro.core import HOTSPOT2D, default_coeffs
from repro.serve import StencilRequest, from_config

GRID = (256, 512)
BATCH = 8          # coalescing target: requests per fused launch
ROUNDS = 4         # request waves submitted
ITERS = (10, 25, 10, 50)   # per-wave iteration counts (one shared trace)


async def serve_mode():
    key = jax.random.PRNGKey(0)
    # the chip's power map is server state, shared by every request
    power = jax.random.uniform(jax.random.fold_in(key, 1), GRID,
                               jnp.float32, 0.0, 0.1)
    problem = StencilProblem("hotspot2d", GRID)

    # one JSON-able document boots the whole service: plans built,
    # executables pre-warmed for every batch class, workers running
    service = await from_config({
        "buckets": [{
            "problem": problem,
            "run": {"backend": "engine", "autotune": True},
            "max_batch": BATCH, "max_wait_ms": 2.0, "queue_cap": 64,
        }],
    })
    print("serving buckets:", list(service.buckets))

    async with service:
        for r, iters in zip(range(ROUNDS), ITERS):
            grids = jax.random.uniform(jax.random.fold_in(key, 100 + r),
                                       (BATCH,) + GRID, jnp.float32,
                                       0.5, 2.0)
            t0 = time.perf_counter()
            results = await asyncio.gather(*[
                service.submit(StencilRequest(problem, grids[i], iters,
                                              aux=power))
                for i in range(BATCH)])
            dt = time.perf_counter() - t0
            fills = {f"{res.batch_fill:.2f}" for res in results}
            print(f"wave {r}: B={BATCH} iters={iters:3d} -> "
                  f"{dt * 1e3:7.2f} ms (fill {sorted(fills)})")

        snap = service.snapshot()
        print(f"\nserved {snap['completed']} requests in "
              f"{snap['batches']} coalesced launches; "
              f"p50 {snap['latency_ms']['p50']:.1f} ms, "
              f"p99 {snap['latency_ms']['p99']:.1f} ms, "
              f"mean fill {snap['batch_fill']:.2f}")
        assert snap["completed"] == ROUNDS * BATCH
        assert snap["rejected_total"] == 0


def manual_mode():
    """The pre-service pattern: plan once, run_batch per arriving batch.
    No admission control, no padding, no metrics — but also no loop."""
    key = jax.random.PRNGKey(0)
    coeffs = default_coeffs(HOTSPOT2D)
    power = jax.random.uniform(jax.random.fold_in(key, 1), GRID,
                               jnp.float32, 0.0, 0.1)
    p = plan(StencilProblem("hotspot2d", GRID),
             RunConfig(backend="engine", autotune=True))
    grids = jax.random.uniform(jax.random.fold_in(key, 100), (BATCH,) + GRID,
                               jnp.float32, 0.5, 2.0)
    t0 = time.perf_counter()
    out = jax.block_until_ready(p.run_batch(grids, 10, coeffs, aux=power))
    print(f"\nmanual mode: B={BATCH} iters=10 -> "
          f"{(time.perf_counter() - t0) * 1e3:7.2f} ms ({out.shape} out)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    asyncio.run(serve_mode())
    manual_mode()
