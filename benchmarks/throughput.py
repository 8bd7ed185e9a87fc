#!/usr/bin/env python
"""Serving-throughput benchmark: ``run_batch`` vs. a sequential ``run`` loop.

The ROADMAP's north star is serving heavy traffic, so this benchmark measures
the throughput subsystem end to end, per backend:

  * **sequential** — B independent ``StencilPlan.run()`` calls (the
    pre-``run_batch`` serving pattern: B dispatches, B host round-trips);
  * **batched** — one ``StencilPlan.run_batch()`` over the same B grids
    (one fused executable; see ``repro.api.backends``);

and reports amortized nanoseconds per cell-update and GCell/s for both,
plus the batched/sequential speedup and the executable-cache statistics.

Output: ``results/bench/BENCH_throughput.json`` (override with ``--out``).

CI gate (``--baseline``): every batched row is compared against the matching
row of a committed baseline file; if its amortized per-cell time regresses
by more than ``--max-regression`` (default 2x, loose on purpose — CI runners
are noisy and heterogeneous), the process exits non-zero and the perf-smoke
job fails.  Regenerate the baseline with::

    python benchmarks/throughput.py --smoke --out results/bench/baseline.json

``--smoke`` runs tiny interpret-mode-friendly grids (CI-sized: seconds, not
minutes); the default full mode runs larger grids on every available backend.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, exec_cache_stats, plan
from repro.core import STENCILS, default_coeffs
from repro.data import make_stencil_inputs

# (stencil, dims, par_time, bsize): smoke = CI-sized, full = host-benchmark
SMOKE_CASES = [
    ("diffusion2d", (32, 128), 2, 128),
    ("hotspot2d", (32, 128), 2, 128),
]
FULL_CASES = [
    ("diffusion2d", (512, 512), 4, 256),
    ("hotspot2d", (512, 512), 4, 256),
    ("diffusion3d", (32, 96, 96), 2, 32),
]
SMOKE_BACKENDS = ("reference", "engine", "pallas_interpret")
FULL_BACKENDS = ("reference", "engine", "pallas_interpret")


def _time_best(fn, repeats: int) -> float:
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_case(backend: str, name: str, dims, par_time: int, bsize: int,
               batch: int, iters: int, repeats: int) -> dict:
    st = STENCILS[name]
    p = plan(StencilProblem(name, dims),
             RunConfig(backend=backend, par_time=par_time, bsize=bsize))
    coeffs = default_coeffs(st)
    key = jax.random.PRNGKey(0)
    grid, aux = make_stencil_inputs(key, dims, st.has_aux)
    grids = jnp.stack([grid + 0.01 * b for b in range(batch)])

    def seq():
        return [p.run(grids[b], iters, coeffs, aux=aux)
                for b in range(batch)]

    def bat():
        return p.run_batch(grids, iters, coeffs, aux=aux)

    seq(), bat()                    # warm-up: compile both paths
    seq_s = _time_best(seq, repeats)
    bat_s = _time_best(bat, repeats)
    cell_updates = batch * math.prod(dims) * iters
    return {
        "backend": backend, "stencil": name, "dims": list(dims),
        "par_time": par_time, "bsize": bsize, "batch": batch, "iters": iters,
        "seq_s": seq_s, "batch_s": bat_s,
        "speedup": seq_s / bat_s,
        "seq_ns_per_cell": seq_s / cell_updates * 1e9,
        "batch_ns_per_cell": bat_s / cell_updates * 1e9,
        "batch_gcells_s": cell_updates / bat_s / 1e9,
    }


def check_regression(rows: list, baseline_path: Path,
                     max_regression: float) -> list:
    """Amortized per-cell time of every batched row vs. the baseline row with
    the same (backend, stencil).  Returns a list of failure strings."""
    try:
        base = json.loads(baseline_path.read_text())
    except (OSError, ValueError) as e:
        return [f"baseline {baseline_path} unreadable: {e}"]
    by_key = {(r["backend"], r["stencil"]): r for r in base.get("rows", [])}
    failures = []
    for r in rows:
        b = by_key.get((r["backend"], r["stencil"]))
        if b is None:
            print(f"  [gate] no baseline row for "
                  f"({r['backend']}, {r['stencil']}) — skipped")
            continue
        ratio = r["batch_ns_per_cell"] / b["batch_ns_per_cell"]
        status = "OK" if ratio <= max_regression else "REGRESSED"
        print(f"  [gate] {r['backend']}/{r['stencil']}: "
              f"{r['batch_ns_per_cell']:.2f} ns/cell vs baseline "
              f"{b['batch_ns_per_cell']:.2f} -> x{ratio:.2f} {status}")
        if ratio > max_regression:
            failures.append(
                f"{r['backend']}/{r['stencil']} amortized per-cell time "
                f"regressed x{ratio:.2f} (> x{max_regression:.2f})")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized grids (seconds, interpret-friendly)")
    ap.add_argument("--backends", default=None,
                    help="comma-separated backend list (default per mode)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=None,
                    help="time-steps per request (default: 4 smoke, 20 full)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="results/bench/BENCH_throughput.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON to gate against (CI perf-smoke)")
    ap.add_argument("--max-regression", type=float, default=2.0,
                    help="fail if batched ns/cell exceeds baseline by this "
                         "factor (default 2.0)")
    args = ap.parse_args(argv)

    cases = SMOKE_CASES if args.smoke else FULL_CASES
    if args.iters is None:
        args.iters = 4 if args.smoke else 20
    backends = (tuple(args.backends.split(","))
                if args.backends else
                (SMOKE_BACKENDS if args.smoke else FULL_BACKENDS))

    rows = []
    print(f"{'backend':18s} {'stencil':13s} {'B':>3s} {'seq ms':>9s} "
          f"{'batch ms':>9s} {'speedup':>8s} {'GCell/s':>8s}")
    for backend in backends:
        for name, dims, par_time, bsize in cases:
            r = bench_case(backend, name, dims, par_time, bsize,
                           args.batch, args.iters, args.repeats)
            rows.append(r)
            print(f"{backend:18s} {name:13s} {r['batch']:3d} "
                  f"{r['seq_s'] * 1e3:9.2f} {r['batch_s'] * 1e3:9.2f} "
                  f"{r['speedup']:7.2f}x {r['batch_gcells_s']:8.4f}")

    out = {
        "schema": 1,
        "mode": "smoke" if args.smoke else "full",
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "batch": args.batch, "iters": args.iters,
        "exec_cache": exec_cache_stats(),
        "rows": rows,
    }
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    print(f"wrote {out_path}")

    if args.baseline:
        failures = check_regression(rows, Path(args.baseline),
                                    args.max_regression)
        if failures:
            print("PERF REGRESSION:\n  " + "\n  ".join(failures),
                  file=sys.stderr)
            return 1
        print("perf gate: OK")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
