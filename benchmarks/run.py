"""Benchmark orchestrator: one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run --only table4

Writes machine-readable results to results/bench/<name>.json and prints the
human tables. The roofline section reads the dry-run cells
(results/dryrun/*.json — produced by ``python -m repro.launch.dryrun --all``).
"""
from __future__ import annotations

import argparse
import json
import os
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "bench")


def _save(name: str, rows):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(rows, f, indent=2, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="table2|table4|table6|fig6|roofline")
    args = ap.parse_args()

    from benchmarks import (fig6_comparison, highorder_scaling, roofline,
                            table2_characteristics, table4_stencil,
                            table6_projection)
    suites = {
        "table2": ("Paper Table 2: stencil characteristics (verified)",
                   table2_characteristics.main),
        "table4": ("Paper Table 4: tuned configs, predicted perf, "
                   "traffic accuracy", table4_stencil.main),
        "table6": ("Paper Table 6: next-gen device projection (v5p/v6e)",
                   table6_projection.main),
        "fig6": ("Paper Fig. 6: devices vs no-temporal-blocking roofline",
                 fig6_comparison.main),
        "highorder": ("Beyond-paper: high-order stencils (paper §8 future "
                      "work)", highorder_scaling.main),
        "roofline": ("Roofline terms per (arch x shape) from the dry-run",
                     roofline.main),
    }
    failures = []
    for name, (title, fn) in suites.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== {name}: {title} " + "=" * max(0, 40 - len(name)))
        t0 = time.time()
        try:
            rows = fn()
            _save(name, rows)
            print(f"[{name}] ok ({time.time() - t0:.1f}s) -> "
                  f"results/bench/{name}.json")
        except Exception as e:   # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"[{name}] FAILED: {e!r}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
