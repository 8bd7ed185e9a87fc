#!/usr/bin/env python
"""Kernel micro-benchmark: GCell/s per super-step, V=1 vs vectorized.

The ``par_vec`` tentpole claims the streaming kernels win by advancing V
rows/planes per pipeline tick (fewer ticks, fatter DMAs, full sublanes —
paper §3.3 / DESIGN.md §2.2).  This benchmark measures exactly that, per
stencil and storage dtype: one super-step of the Pallas kernel at
``par_vec=1`` against the swept vector widths, reporting seconds per
super-step, amortized ns per cell-update, GCell/s, the per-cell DMA bytes
of the kernel's exact schedule, and the best-V speedup over V=1.

The dtype column sweeps the supported storage dtypes (f32 and bf16 —
DESIGN.md §2.2b): bf16 rows must move ~half the per-cell DMA bytes of
their f32 siblings (checked as a hard gate, not just reported); compute
time is an interpret-mode proxy, so only the traffic claim is gated.

Backend: ``pallas_interpret`` by default (the CI-runnable proxy — interpret
mode executes the same tick loop, so the ~V-fold tick reduction shows up in
wall-clock there too); pass ``--backend pallas`` on a real TPU.

Output: ``results/bench/BENCH_kernels.json`` (override with ``--out``).

CI gate (``--baseline``): every measured (stencil, dtype, par_vec) row is
compared against the ``kernel_rows`` section of the committed baseline file
(rows without a ``dtype`` field in older baselines default to f32); if its
amortized per-cell time regresses by more than ``--max-regression`` (default
2x — CI runners are noisy), the process exits non-zero and the perf-smoke
job fails.  Regenerate with::

    python benchmarks/kernels.py --smoke \
        --update-baseline results/bench/baseline.json
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax

from repro.api import RunConfig, StencilProblem, plan
from repro.core import STENCILS, default_coeffs
from repro.data import make_stencil_inputs

# (stencil, dims, par_time, bsize): smoke = CI-sized, full = host-benchmark
SMOKE_CASES = [
    ("diffusion2d", (96, 256), 2, 256),     # the 2D star acceptance case
    ("hotspot2d", (96, 256), 2, 256),
]
FULL_CASES = [
    ("diffusion2d", (512, 1024), 4, 512),
    ("hotspot2d", (512, 1024), 4, 512),
    ("diffusion3d", (32, 96, 96), 2, 32),
]
SMOKE_VECS = (1, 4, 8)
FULL_VECS = (1, 2, 4, 8, 16)
#: storage dtypes each case sweeps (f32 accumulation either way)
DTYPES = ("float32", "bfloat16")


def _time_superstep(p, grid, coeffs, aux, iters, warmup, repeats):
    for _ in range(warmup):
        jax.block_until_ready(p.run(grid, iters, coeffs, aux=aux))
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(p.run(grid, iters, coeffs, aux=aux))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_case(backend, name, dims, par_time, bsize, vecs, warmup, repeats,
               dtypes=DTYPES):
    st = STENCILS[name]
    coeffs = default_coeffs(st)
    grid, aux = make_stencil_inputs(jax.random.PRNGKey(0), dims, st.has_aux)
    rows = []
    for dtype in dtypes:
        sd = jax.numpy.dtype(dtype)
        g = grid.astype(sd)
        a = None if aux is None else aux.astype(sd)
        for V in vecs:
            p = plan(StencilProblem(name, dims, dtype=dtype),
                     RunConfig(backend=backend, par_time=par_time,
                               bsize=bsize, par_vec=V))
            # one whole super-step: par_time fused steps, the kernel's unit
            # of work
            s = _time_superstep(p, g, coeffs, a, par_time, warmup, repeats)
            cells = math.prod(dims) * par_time
            dma = p.traffic_report()["kernel_dma_bytes_per_superstep"]
            rows.append({
                "stencil": name, "dims": list(dims), "par_time": par_time,
                "bsize": bsize, "par_vec": V, "dtype": dtype,
                "s_per_superstep": s,
                "ns_per_cell": s / cells * 1e9,
                "gcells_s": cells / s / 1e9,
                "dma_bytes_per_cell": dma / cells,
            })
    return rows


def summarize(rows):
    """Per-(stencil, dtype) V=1 vs best-V table + speedups."""
    out = []
    by_st = {}
    for r in rows:
        by_st.setdefault((r["stencil"], r["dtype"]), []).append(r)
    for (name, dtype), rs in by_st.items():
        v1 = next((r for r in rs if r["par_vec"] == 1), None)
        best = min(rs, key=lambda r: r["s_per_superstep"])
        row = {
            "stencil": name,
            "dtype": dtype,
            "best_par_vec": best["par_vec"],
            "best_gcells_s": best["gcells_s"],
        }
        if v1 is not None:        # --vecs may omit the V=1 anchor
            row["v1_gcells_s"] = v1["gcells_s"]
            row["speedup_vs_v1"] = (v1["s_per_superstep"]
                                    / best["s_per_superstep"])
        out.append(row)
    return out


def check_traffic_halving(rows):
    """bf16 storage must move ~half the per-cell DMA bytes of the f32 row
    with the same (stencil, V) — the whole point of 16-bit streams.  Slab
    padding keeps the ratio from being exactly 0.5; 0.6 is the generous
    ceiling.  Returns failure strings (empty = gate passes)."""
    by_key = {(r["stencil"], r["dtype"], r["par_vec"]): r for r in rows}
    failures = []
    for r in rows:
        if r["dtype"] != "bfloat16":
            continue
        f32 = by_key.get((r["stencil"], "float32", r["par_vec"]))
        if f32 is None:
            continue
        ratio = r["dma_bytes_per_cell"] / f32["dma_bytes_per_cell"]
        status = "OK" if ratio <= 0.6 else "NOT HALVED"
        print(f"  [traffic] {r['stencil']}/V={r['par_vec']}: bf16 moves "
              f"x{ratio:.3f} of f32's DMA bytes/cell {status}")
        if ratio > 0.6:
            failures.append(
                f"{r['stencil']}/V={r['par_vec']}: bf16 DMA bytes/cell is "
                f"x{ratio:.3f} of f32 (expected ~0.5)")
    return failures


def check_regression(rows, baseline_path: Path, max_regression: float):
    """Per-cell time of every (stencil, dtype, par_vec) row vs the
    baseline's ``kernel_rows`` (pre-dtype baseline rows are f32).  Returns
    failure strings (empty = gate passes)."""
    try:
        base = json.loads(baseline_path.read_text())
    except (OSError, ValueError) as e:
        return [f"baseline {baseline_path} unreadable: {e}"]
    by_key = {(r["stencil"], r.get("dtype", "float32"), r["par_vec"]): r
              for r in base.get("kernel_rows", [])}
    if not by_key:
        return [f"baseline {baseline_path} has no kernel_rows section — "
                "regenerate it with --update-baseline"]
    failures = []
    for r in rows:
        b = by_key.get((r["stencil"], r["dtype"], r["par_vec"]))
        if b is None:
            print(f"  [gate] no kernel baseline for "
                  f"({r['stencil']}, {r['dtype']}, V={r['par_vec']}) "
                  "— skipped")
            continue
        ratio = r["ns_per_cell"] / b["ns_per_cell"]
        status = "OK" if ratio <= max_regression else "REGRESSED"
        print(f"  [gate] {r['stencil']}/{r['dtype']}/V={r['par_vec']}: "
              f"{r['ns_per_cell']:.2f} ns/cell vs baseline "
              f"{b['ns_per_cell']:.2f} -> x{ratio:.2f} {status}")
        if ratio > max_regression:
            failures.append(
                f"{r['stencil']}/{r['dtype']}/V={r['par_vec']} per-cell "
                f"time regressed x{ratio:.2f} (> x{max_regression:.2f})")
    return failures


def update_baseline(rows, baseline_path: Path) -> None:
    """Write/refresh the ``kernel_rows`` section, preserving whatever else
    (the throughput rows) the shared baseline file holds."""
    try:
        base = json.loads(baseline_path.read_text())
    except (OSError, ValueError):
        base = {}
    base["kernel_rows"] = rows
    baseline_path.parent.mkdir(parents=True, exist_ok=True)
    baseline_path.write_text(json.dumps(base, indent=1, sort_keys=True)
                             + "\n")
    print(f"updated kernel_rows in {baseline_path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized grids (seconds, interpret-friendly)")
    ap.add_argument("--backend", default="pallas_interpret",
                    help="pallas_interpret (CI proxy) or pallas (real TPU)")
    ap.add_argument("--vecs", default=None,
                    help="comma-separated par_vec sweep (default per mode)")
    ap.add_argument("--dtypes", default=None,
                    help="comma-separated storage dtypes "
                         f"(default {','.join(DTYPES)})")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="results/bench/BENCH_kernels.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON to gate against (CI perf-smoke)")
    ap.add_argument("--update-baseline", default=None, metavar="PATH",
                    help="write kernel_rows into this baseline file and exit")
    ap.add_argument("--max-regression", type=float, default=2.0,
                    help="fail if ns/cell exceeds baseline by this factor")
    args = ap.parse_args(argv)

    cases = SMOKE_CASES if args.smoke else FULL_CASES
    vecs = (tuple(int(v) for v in args.vecs.split(","))
            if args.vecs else (SMOKE_VECS if args.smoke else FULL_VECS))
    dtypes = (tuple(args.dtypes.split(",")) if args.dtypes else DTYPES)

    rows = []
    print(f"{'stencil':13s} {'dims':>14s} {'dtype':>9s} {'V':>3s} "
          f"{'ms/super':>9s} {'ns/cell':>8s} {'GCell/s':>8s} {'B/cell':>7s}")
    for name, dims, par_time, bsize in cases:
        for r in bench_case(args.backend, name, dims, par_time, bsize, vecs,
                            args.warmup, args.repeats, dtypes):
            rows.append(r)
            print(f"{r['stencil']:13s} {str(tuple(r['dims'])):>14s} "
                  f"{r['dtype']:>9s} "
                  f"{r['par_vec']:3d} {r['s_per_superstep'] * 1e3:9.2f} "
                  f"{r['ns_per_cell']:8.2f} {r['gcells_s']:8.4f} "
                  f"{r['dma_bytes_per_cell']:7.2f}")
    summary = summarize(rows)
    for s in summary:
        vs = (f"x{s['speedup_vs_v1']:.2f} vs V=1"
              if "speedup_vs_v1" in s else "(no V=1 anchor in sweep)")
        print(f"  {s['stencil']}/{s['dtype']}: best V={s['best_par_vec']} "
              f"-> {vs} ({s['best_gcells_s']:.4f} GCell/s)")
    traffic_failures = check_traffic_halving(rows)

    out = {
        "schema": 1,
        "mode": "smoke" if args.smoke else "full",
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "backend": args.backend,
        "rows": rows,
        "summary": summary,
    }
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    print(f"wrote {out_path}")

    if traffic_failures:
        print("TRAFFIC NOT HALVED:\n  " + "\n  ".join(traffic_failures),
              file=sys.stderr)
        return 1
    if args.update_baseline:
        update_baseline(rows, Path(args.update_baseline))
        return 0
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
