"""Open-loop serving: seeded Poisson arrivals into one ``StencilService``
bucket built by ``from_config``.

Requests are host arrays (a state grid and its own aux grid, drawn from the
seed before the window) sent on the generator's schedule whether or not
earlier ones have been answered.  Each request's latency runs from when it
was due to when its result reached the benchmark; a rejected or failed
request counts as missing, i.e. slower than any answer.  After the window
every sampled answer is compared with the reference run from its own input
for its own iteration count.
"""
from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from perfbench import generate, reference

#: latency that stands for a request that was never answered (ms)
MISSING_MS = 3.6e6


def _pool(config, shape, n, seed):
    """``n`` state grids and ``n`` aux grids, made on the device in one
    call from the seed and brought to the host, where requests come from."""
    import jax
    import jax.numpy as jnp
    lo, hi = config["inputs"]["state"]
    alo, ahi = config["inputs"]["aux"]

    def make(key):
        full = (n,) + shape
        return (jax.random.uniform(key, full, jnp.float32, lo, hi),
                jax.random.uniform(jax.random.fold_in(key, 1), full,
                                   jnp.float32, alo, ahi))

    grids, aux = jax.jit(make)(generate.jax_key(seed))
    return np.asarray(grids), np.asarray(aux)


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile (0..1) of ``values`` by nearest rank."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def run(cell) -> dict:
    return asyncio.run(_run(cell))


async def _run(cell) -> dict:
    import jax
    from repro.api import RunConfig, StencilProblem, plan
    from repro.serve import ServiceOverloaded, StencilRequest, from_config
    config, traffic = cell.config, cell.traffic
    shape = tuple(int(d) for d in traffic["grid"])
    problem = StencilProblem(config["stencil"], shape, dtype=config["dtype"],
                             boundary=config["boundary"])
    run_spec = {"backend": config["backend"], "autotune": config["autotune"]}
    coeffs = dict(config["coefficients"])
    sched = generate.open_loop(traffic, cell.seed, cell.seconds, cell.rate)
    n = len(sched["due_s"])
    with cell.span("setup"):
        grids, powers = _pool(config, shape, int(traffic["pool"]), cell.seed)
        reqs = [StencilRequest(problem, grids[k], int(it), coeffs=coeffs,
                               aux=powers[k])
                for k, it in zip(sched["pool"], sched["iters"])]
        t = time.perf_counter()
        plan(problem, RunConfig(**run_spec))
        plan_wall = time.perf_counter() - t
        t_warm = time.perf_counter()
        service = await from_config({"buckets": [{
            "problem": problem, "run": run_spec, **config["service"]}]})
        # one full and one odd-sized launch of the real mix through the
        # service's own path (staging, rounds, delivery), then forget them
        warm = reqs[:config["service"]["max_batch"] + 1]
        await asyncio.gather(*[service.submit_nowait(r) for r in warm])
        service.metrics.reset()
    t_setup = time.perf_counter()
    cell.host["plan_s"] = plan_wall + cell.compile_s(t_warm, t_setup)

    latency = np.full(n, np.inf)
    lag = np.zeros(n)
    sample = set(int(i) for i in sched["sample"])
    answers, errors = {}, []

    def done(i, due, fut):
        if fut.cancelled() or fut.exception() is not None:
            errors.append(i)
            return
        latency[i] = time.perf_counter() - due
        if i in sample:
            answers[i] = fut.result().grid

    cell.start_trace()
    futures, rejected = [], 0
    with cell.span("window"):
        t0 = time.perf_counter()
        for i in range(n):
            due = t0 + float(sched["due_s"][i])
            delay = due - time.perf_counter()
            if delay > 0:
                with cell.span("pacing"):
                    await asyncio.sleep(delay)
            with cell.span("submit"):
                lag[i] = time.perf_counter() - due
                try:
                    fut = service.submit_nowait(reqs[i])
                except ServiceOverloaded:
                    rejected += 1
                    continue
                fut.add_done_callback(lambda f, i=i, due=due: done(i, due, f))
                futures.append(fut)
        t_close = time.perf_counter()
        with cell.span("drain"):
            _, pending = await asyncio.wait(futures, timeout=60.0) \
                if futures else (set(), set())
            await asyncio.sleep(0)      # let the last callbacks run
        t1 = time.perf_counter()
    cell.stop_trace()
    snap = service.snapshot()
    await service.stop()
    cell.counters = snap
    cell.host["lag_p95_ms"] = nearest_rank(lag, 0.95) * 1e3

    peak = cell.peak_bytes()
    del reqs
    # the reference of every (pool input, iteration count) a sample needs,
    # advanced class by class from the input
    needed = sorted({(int(sched["pool"][i]), int(sched["iters"][i]))
                     for i in answers})
    want = {}
    for k in sorted({k for k, _ in needed}):
        cur, at = jax.device_put(grids[k]), 0
        aux = jax.device_put(powers[k])
        for it in sorted(it for kk, it in needed if kk == k):
            cur, at = reference.run(config, cur, it - at, aux), it
            want[(k, it)] = np.asarray(cur)
    if cell.control:
        # the control: the reference in the requested precision answers in
        # the program's place, request by request
        answers = {i: reference.run(
            config, grids[int(sched["pool"][i])], int(sched["iters"][i]),
            powers[int(sched["pool"][i])], cell.control) for i in answers}
    diff = mag = 0.0
    for i, got in answers.items():
        w = want[(int(sched["pool"][i]), int(sched["iters"][i]))]
        g = np.asarray(got, np.float32)
        d = float(np.max(np.abs(g - w))) if np.all(np.isfinite(g)) \
            else float("inf")
        diff, mag = max(diff, d), max(mag, float(np.max(np.abs(w))))
    ms = np.where(np.isfinite(latency), latency * 1e3, MISSING_MS)
    return {
        "attempted": n, "failed": rejected + len(errors) + len(pending),
        "end_to_end": {"request_p95_ms": nearest_rank(ms, 0.95),
                       "request_p50_ms": nearest_rank(ms, 0.50),
                       "setup_s": t_setup - cell.t_start},
        "memory_peak_bytes": peak,
        "compiles_in_window": cell.compiles_between(t0, t1),
        "checks": {"max_rel_err": diff / mag if answers else float("inf"),
                   "unanswered": float(len(pending))},
        "window": {"close_s": t_close - t0, "drain_s": t1 - t_close,
                   "offered_per_s": sched["rate_per_s"],
                   "rejected": rejected, "errors": len(errors),
                   "compared": len(answers),
                   "p95_first_half_ms": nearest_rank(ms[:n // 2], 0.95),
                   "p95_second_half_ms": nearest_rank(ms[n // 2:], 0.95)},
    }
