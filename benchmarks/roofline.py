"""Roofline aggregation (deliverable g): read the dry-run cells and emit the
per-(arch x shape x mesh) roofline table.

Terms (per chip, from the compiled single-pod dry-run; DESIGN.md §7):
  compute    = HLO_FLOPs / peak_bf16            (197 TFLOP/s)
  memory     = HLO_bytes / HBM_bw               (819 GB/s)
  collective = collective_bytes / ICI_bw        (~50 GB/s/link)

plus MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), the useful-compute
ratio MODEL_FLOPS/HLO_FLOPs, and the roofline fraction
(MODEL_FLOPS/peak) / max(term)).

Usage:
  python -m benchmarks.roofline            # table to stdout
  python -m benchmarks.roofline --markdown # EXPERIMENTS.md §Roofline body
  python -m benchmarks.roofline --pick     # hillclimb candidate selection
"""
from __future__ import annotations

import argparse
import json
import os

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")


def load_cells(mesh: str = "single", variant: str = "baseline") -> list[dict]:
    cells = []
    for fname in sorted(os.listdir(RESULTS_DIR)):
        if not fname.endswith(f"__{mesh}__{variant}.json"):
            continue
        with open(os.path.join(RESULTS_DIR, fname)) as f:
            cells.append(json.load(f))
    return cells


def rows_for(cells: list[dict]) -> list[dict]:
    rows = []
    for c in cells:
        base = {"arch": c["arch"], "shape": c["shape"], "mesh": c["mesh"]}
        if "skipped" in c:
            rows.append({**base, "skipped": c["skipped"].split(":")[0]})
            continue
        r = c["roofline"]
        t = [r["t_compute_s"], r["t_memory_s"], r["t_collective_s"]]
        row = {
            **base,
            "t_compute_s": r["t_compute_s"],
            "t_memory_s": r["t_memory_s"],
            "t_collective_s": r["t_collective_s"],
            "dominant": r["dominant"],
            "peak_gib": c["memory"]["peak_per_device_gib"],
        }
        if "useful_ratio" in r:
            row["useful_ratio"] = r["useful_ratio"]
            row["roofline_fraction"] = r["roofline_fraction"]
        if "autotuned" in c:
            row["autotuned"] = c["autotuned"]
            # stencil cells: roofline fraction = predicted perf vs dominant
            row["roofline_fraction"] = None
        rows.append(row)
    return rows


def _fmt(x, w=9):
    if x is None:
        return " " * w
    if x >= 100:
        return f"{x:{w}.1f}"
    return f"{x:{w}.3f}"


def print_table(rows, markdown=False):
    if markdown:
        print("| arch | shape | t_compute (s) | t_memory (s) | "
              "t_collective (s) | dominant | useful | roofline frac | "
              "peak GiB/dev |")
        print("|---|---|---|---|---|---|---|---|---|")
        for r in rows:
            if "skipped" in r:
                print(f"| {r['arch']} | {r['shape']} | — | — | — | "
                      f"skipped ({r['skipped']}) | — | — | — |")
                continue
            u = r.get("useful_ratio")
            f = r.get("roofline_fraction")
            print(f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3g} | "
                  f"{r['t_memory_s']:.3g} | {r['t_collective_s']:.3g} | "
                  f"{r['dominant']} | "
                  f"{u:.3f} |" if u is not None else "— |",
                  f"{f:.4f} |" if f is not None else "— |",
                  f"{r['peak_gib']:.2f} |")
        return
    print(f"{'arch':24s} {'shape':12s} {'t_comp':>9s} {'t_mem':>9s} "
          f"{'t_coll':>9s} {'dominant':>10s} {'useful':>7s} {'frac':>8s} "
          f"{'GiB/dev':>8s}")
    for r in rows:
        if "skipped" in r:
            print(f"{r['arch']:24s} {r['shape']:12s} "
                  f"{'skipped (' + r['skipped'] + ')':>40s}")
            continue
        u = r.get("useful_ratio")
        f = r.get("roofline_fraction")
        print(f"{r['arch']:24s} {r['shape']:12s} "
              f"{_fmt(r['t_compute_s'])} {_fmt(r['t_memory_s'])} "
              f"{_fmt(r['t_collective_s'])} {r['dominant']:>10s} "
              f"{u if u is None else round(u, 3)!s:>7s} "
              f"{f if f is None else round(f, 4)!s:>8s} "
              f"{r['peak_gib']:8.2f}")


def pick_hillclimb(rows) -> dict:
    """Choose the three hillclimb cells: worst roofline fraction, most
    collective-bound, most representative of the paper's technique."""
    lm = [r for r in rows if "skipped" not in r
          and r.get("roofline_fraction") is not None]
    worst = min(lm, key=lambda r: r["roofline_fraction"])
    coll = max(lm, key=lambda r: (r["t_collective_s"]
                                  / max(max(r["t_compute_s"],
                                            r["t_memory_s"],
                                            r["t_collective_s"]), 1e-12)))
    # most representative of the paper: the distributed stencil superstep
    stencils = [r for r in rows if r["shape"] == "superstep"]
    rep = stencils[0] if stencils else None
    return {"worst_fraction": worst, "most_collective": coll,
            "paper_representative": rep}


def run() -> list[dict]:
    out = []
    for variant in ("baseline", "optimized"):
        rows = rows_for(load_cells("single", variant))
        for r in rows:
            r["variant"] = variant
        out.extend(rows)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--variant", default=None,
                    choices=["baseline", "optimized"],
                    help="default: print both")
    ap.add_argument("--pick", action="store_true")
    args = ap.parse_args()
    variants = [args.variant] if args.variant else ["baseline", "optimized"]
    rows = []
    for v in variants:
        vr = rows_for(load_cells(args.mesh, v))
        if not vr:
            continue
        print(f"\n--- variant: {v} ---")
        print_table(vr, markdown=args.markdown)
        rows = vr   # --pick operates on the last (optimized if present)
    if args.pick:
        picks = pick_hillclimb(rows)
        print("\nhillclimb candidates:")
        for why, r in picks.items():
            if r is None:
                continue
            print(f"  {why}: {r['arch']} x {r['shape']} "
                  f"(dominant={r['dominant']}, "
                  f"frac={r.get('roofline_fraction')})")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
