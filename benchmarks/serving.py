#!/usr/bin/env python
"""Serving benchmark: the coalescing service vs. a per-request ``run`` loop.

Each scenario boots a one-bucket :class:`repro.serve.StencilService` and
drives it with a **seeded open-loop Poisson arrival process** (arrivals do
not wait for completions — the offered load is set by ``--oversub`` times
the sequential capacity, so coalescing pressure is real and queue-full
backpressure actually triggers).  The same request mix is then replayed as
the pre-serving pattern — a sequential per-request ``plan().run()`` loop —
and the report compares delivered throughput:

  * ``seq_cells_s``    — cell-updates/s of the sequential loop;
  * ``serve_cells_s``  — delivered cell-updates/s of the service (completed
    requests over the submit->last-delivery wall clock);
  * ``speedup``        — serve/seq (the coalescing win);
  * ``p50_ms``/``p99_ms`` — end-to-end request latency percentiles;
  * ``batch_fill``     — mean real/padded launch occupancy;
  * ``rejected``       — queue-full rejections (every one answered with
    ``ServiceOverloaded`` + retry-after; nothing is silently dropped).

Output: ``results/bench/BENCH_serving.json`` (override with ``--out``).

CI gate (``--baseline``): each row's delivered ns/cell is compared against
the committed baseline row with the same (backend, stencil) under
``--max-regression`` (default 2x — CI runners are noisy), and the row must
sustain ``--min-speedup`` (default 1.5x) at ``--min-fill`` (default 0.5)
batch fill.  Regenerate the baseline rows with::

    python benchmarks/serving.py --smoke --out /tmp/serving.json
    # then merge rows into results/bench/baseline.json as "serving_rows"
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.api import RunConfig, StencilProblem, exec_cache_stats, plan
from repro.data import make_stencil_inputs
from repro.serve import (ServiceConfig, ServiceOverloaded, StencilRequest,
                         serve)

# (stencil, dims, par_time, bsize): smoke = CI-sized, full = host-benchmark.
# par_time=4 folds 4 time-steps into one super-step: each request is a
# single fused dispatch, and small grids keep per-request cost dominated by
# marshalling + dispatch — the regime coalescing exists for (large compute-
# bound grids conserve FLOPs either way; FULL_CASES measure that end).
SMOKE_CASES = [
    ("diffusion2d", (16, 64), 4, 64),
    ("hotspot2d", (16, 64), 4, 64),
]
FULL_CASES = [
    ("diffusion2d", (256, 512), 4, 256),
    ("hotspot2d", (256, 512), 4, 256),
]
#: default per-request iteration count: few iterations per request is the
#: regime coalescing exists for (per-request dispatch dominates, so one
#: fused launch amortizes it).  Uniform by default: heterogeneous mixes
#: (``--iters-mix 2,4``) exercise staged advance, but every staged round
#: re-runs the full padded batch, so early-finishing members cost throughput
#: — a policy trade-off the benchmark can measure, not hide.
DEFAULT_ITERS_MIX = (4,)


def make_requests(problem: StencilProblem, n: int, seed: int, iters_mix):
    """The seeded request mix one scenario serves: distinct per-request
    grids (plus shared aux), iteration counts drawn from ``iters_mix``.
    Grids are *host* arrays — requests arrive off the wire as host data,
    which both sides must marshal onto the device."""
    st = problem.stencil
    rng = np.random.default_rng(seed)
    iters = [int(i) for i in rng.choice(iters_mix, n)]
    key = jax.random.PRNGKey(seed)
    grid, aux = make_stencil_inputs(key, problem.shape, st.has_aux)
    base = np.asarray(grid)
    aux = np.asarray(aux) if st.has_aux else None
    reqs = []
    for i in range(n):
        g = base + np.float32(0.01 * i)
        reqs.append(StencilRequest(problem, g, iters[i], aux=aux))
    return reqs


def bench_sequential(problem, run: RunConfig, reqs) -> float:
    """The pre-serving pattern: one ``plan().run()`` per request, in
    arrival order, materializing each result on the host — the same
    per-request deliverable the service hands back (``ServeResult.grid``
    is a host array).  Without the per-request materialization the loop
    would time only async dispatch while XLA computes in the background —
    an idealized baseline no request/response server can match.  Returns
    seconds for the whole mix (after warm-up)."""
    p = plan(problem, run)
    p.prewarm(batch_sizes=(), iters=1)          # compile the single path
    np.asarray(p.run(reqs[0].grid, reqs[0].iters, aux=reqs[0].aux))
    t0 = time.perf_counter()
    for r in reqs:
        np.asarray(p.run(r.grid, r.iters, aux=r.aux))
    return time.perf_counter() - t0


async def bench_serving(problem, run: RunConfig, reqs, *, max_batch: int,
                        max_wait_ms: float, queue_cap: int, gap_s: float,
                        seed: int, concurrent: int) -> dict:
    """Open-loop pass: boot the service (pre-warmed), submit the mix with
    seeded exponential inter-arrival gaps, await every outcome.

    ``concurrent`` > 1 lets the next launch assemble (stack/pad on the
    event loop, thread dispatch) while the previous one computes — the
    coalescing overhead overlaps device time instead of serializing with
    it."""
    svc = await serve(ServiceConfig(
        buckets=[{"problem": problem, "run": run, "max_batch": max_batch,
                  "max_wait_ms": max_wait_ms, "queue_cap": queue_cap}],
        max_concurrent_batches=concurrent))
    # one full + one padded launch through the *service* path (stack, pad,
    # slice, thread pool): plan.prewarm covers the executables, not these
    warm = reqs[:min(max_batch + 1, queue_cap)]
    await asyncio.gather(*[svc.submit_nowait(r) for r in warm])
    svc.metrics.reset()         # measure steady state, not warm-up
    rng = np.random.default_rng(seed + 1)
    gaps = rng.exponential(gap_s, len(reqs))
    futures, rejected = [], 0
    t0 = time.perf_counter()
    # self-correcting pacer: each request has an absolute scheduled time;
    # sleep only the remaining difference.  asyncio.sleep overshoots by
    # ~ms, so per-gap sleeping would silently throttle the offered load —
    # here an overshoot just makes the next submits catch up immediately
    # (bursty arrivals, which open-loop load tolerates).
    sched = 0.0
    for i, (r, gap) in enumerate(zip(reqs, gaps)):
        sched += float(gap)
        delay = t0 + sched - time.perf_counter()
        if delay > 1e-3:
            await asyncio.sleep(delay)
        elif i % 8 == 0:
            await asyncio.sleep(0)      # let the workers run regardless
        try:
            futures.append(svc.submit_nowait(r))
        except ServiceOverloaded:
            rejected += 1
    results = await asyncio.gather(*futures)
    wall_s = time.perf_counter() - t0
    snap = svc.snapshot()
    await svc.stop()
    cells = sum(r.iters for r in results) * math.prod(problem.shape)
    assert snap["submitted"] == snap["completed"] + snap["rejected_total"], \
        "serving accounting leak: a request vanished without an answer"
    return {"wall_s": wall_s, "cells": cells, "snap": snap,
            "rejected": rejected, "completed": len(results)}


def bench_case(backend: str, name: str, dims, par_time: int, bsize: int, *,
               n: int, oversub: float, max_batch: int, max_wait_ms: float,
               queue_cap: int, seed: int, concurrent: int,
               iters_mix, reps: int = 3) -> dict:
    problem = StencilProblem(name, dims)
    run = RunConfig(backend=backend, par_time=par_time, bsize=bsize)
    reqs = make_requests(problem, n, seed, iters_mix)
    total_cells = sum(r.iters for r in reqs) * math.prod(dims)

    # best-of-N on both sides (the suite's _time_best idiom): one-core CI
    # runners jitter either measurement by 2x, and min is the standard
    # noise-robust estimator of the undisturbed run
    seq_s = min(bench_sequential(problem, run, reqs) for _ in range(reps))
    # offered load = oversub x the sequential capacity: batches actually
    # fill, and sustained oversubscription exercises the bounded queue
    gap_s = (seq_s / n) / oversub
    sv = None
    for _ in range(reps):
        cand = asyncio.run(bench_serving(
            problem, run, reqs, max_batch=max_batch,
            max_wait_ms=max_wait_ms, queue_cap=queue_cap, gap_s=gap_s,
            seed=seed, concurrent=concurrent))
        if sv is None or (cand["cells"] / cand["wall_s"]
                          > sv["cells"] / sv["wall_s"]):
            sv = cand

    snap = sv["snap"]
    seq_cells_s = total_cells / seq_s
    serve_cells_s = sv["cells"] / sv["wall_s"] if sv["cells"] else 0.0
    return {
        "backend": backend, "stencil": name, "dims": list(dims),
        "par_time": par_time, "bsize": bsize, "n_requests": n,
        "iters_mix": [int(i) for i in iters_mix], "oversub": oversub,
        "max_batch": max_batch, "max_wait_ms": max_wait_ms,
        "queue_cap": queue_cap, "concurrent": concurrent,
        "seq_s": seq_s, "serve_wall_s": sv["wall_s"],
        "completed": sv["completed"], "rejected": sv["rejected"],
        "batch_fill": snap["batch_fill"],
        "batches": snap["batches"],
        "p50_ms": snap["latency_ms"]["p50"],
        "p99_ms": snap["latency_ms"]["p99"],
        "seq_cells_s": seq_cells_s,
        "serve_cells_s": serve_cells_s,
        "speedup": serve_cells_s / seq_cells_s if seq_cells_s else None,
        "serve_ns_per_cell": (sv["wall_s"] / sv["cells"] * 1e9
                              if sv["cells"] else None),
    }


def check_gate(rows: list, baseline_path: Path, max_regression: float,
               min_speedup: float, min_fill: float) -> list:
    """The serving acceptance gate: delivered ns/cell vs. the committed
    baseline row with the same (backend, stencil), plus the absolute
    speedup/fill floors.  Returns failure strings."""
    failures = []
    base_rows = []
    if baseline_path is not None:
        try:
            base = json.loads(baseline_path.read_text())
            base_rows = base.get("serving_rows", base.get("rows", []))
        except (OSError, ValueError) as e:
            return [f"baseline {baseline_path} unreadable: {e}"]
    by_key = {(r["backend"], r["stencil"]): r for r in base_rows}
    for r in rows:
        tag = f"{r['backend']}/{r['stencil']}"
        b = by_key.get((r["backend"], r["stencil"]))
        if b is None:
            print(f"  [gate] no baseline row for {tag} — skipped")
        else:
            ratio = r["serve_ns_per_cell"] / b["serve_ns_per_cell"]
            status = "OK" if ratio <= max_regression else "REGRESSED"
            print(f"  [gate] {tag}: {r['serve_ns_per_cell']:.2f} ns/cell "
                  f"vs baseline {b['serve_ns_per_cell']:.2f} "
                  f"-> x{ratio:.2f} {status}")
            if ratio > max_regression:
                failures.append(f"{tag} delivered ns/cell regressed "
                                f"x{ratio:.2f} (> x{max_regression:.2f})")
        if r["speedup"] is not None and r["speedup"] < min_speedup:
            failures.append(f"{tag} serve/seq speedup {r['speedup']:.2f} "
                            f"< {min_speedup:.2f}")
        if r["batch_fill"] is not None and r["batch_fill"] < min_fill:
            failures.append(f"{tag} batch fill {r['batch_fill']:.2f} "
                            f"< {min_fill:.2f}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized grids (seconds, not minutes)")
    ap.add_argument("--backends", default="engine",
                    help="comma-separated backend list (default: engine)")
    ap.add_argument("--n", type=int, default=256,
                    help="requests per scenario")
    ap.add_argument("--oversub", type=float, default=2.5,
                    help="offered load as a multiple of sequential capacity")
    ap.add_argument("--iters-mix", default=None,
                    help="comma-separated per-request iteration counts "
                         "(default: uniform 4; a mix exercises staged "
                         "advance at a throughput cost)")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--queue-cap", type=int, default=96)
    ap.add_argument("--concurrent", type=int, default=1,
                    help="max in-flight coalesced launches (>1 overlaps "
                         "launches in threads — pays off only with cores "
                         "to spare; 1 runs compute inline on the loop)")
    ap.add_argument("--reps", type=int, default=3,
                    help="best-of-N repetitions per measurement")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="results/bench/BENCH_serving.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON to gate against (CI perf-smoke)")
    ap.add_argument("--max-regression", type=float, default=2.0)
    ap.add_argument("--min-speedup", type=float, default=1.5,
                    help="serve/seq throughput floor (acceptance)")
    ap.add_argument("--min-fill", type=float, default=0.5,
                    help="mean batch-fill floor (acceptance)")
    args = ap.parse_args(argv)

    cases = SMOKE_CASES if args.smoke else FULL_CASES
    n = args.n
    iters_mix = (tuple(int(i) for i in args.iters_mix.split(","))
                 if args.iters_mix else DEFAULT_ITERS_MIX)
    backends = tuple(args.backends.split(","))

    rows = []
    print(f"{'backend':10s} {'stencil':13s} {'n':>4s} {'rej':>4s} "
          f"{'fill':>5s} {'p50 ms':>8s} {'p99 ms':>8s} {'speedup':>8s}")
    for backend in backends:
        for name, dims, par_time, bsize in cases:
            r = bench_case(backend, name, dims, par_time, bsize, n=n,
                           oversub=args.oversub, max_batch=args.max_batch,
                           max_wait_ms=args.max_wait_ms,
                           queue_cap=args.queue_cap, seed=args.seed,
                           concurrent=args.concurrent,
                           iters_mix=iters_mix, reps=args.reps)
            rows.append(r)
            print(f"{backend:10s} {name:13s} {r['completed']:4d} "
                  f"{r['rejected']:4d} {r['batch_fill']:5.2f} "
                  f"{r['p50_ms']:8.2f} {r['p99_ms']:8.2f} "
                  f"{r['speedup']:7.2f}x")

    out = {
        "schema": 1,
        "mode": "smoke" if args.smoke else "full",
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "n_requests": n, "oversub": args.oversub, "seed": args.seed,
        "exec_cache": exec_cache_stats(),
        "rows": rows,
    }
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    print(f"wrote {out_path}")

    if args.baseline:
        failures = check_gate(rows, Path(args.baseline),
                              args.max_regression, args.min_speedup,
                              args.min_fill)
        if failures:
            print("SERVING GATE FAILED:\n  " + "\n  ".join(failures),
                  file=sys.stderr)
            return 1
        print("serving gate: OK")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
