import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

# Hillclimb instrumentation: compile one dry-run cell and print the top HBM /
# FLOP / collective contributors with their loop multipliers.
#
#   PYTHONPATH=src python -m repro.launch.profile_cell \
#       --arch granite-3-8b --shape train_4k

# ruff: noqa: E402
import argparse

from repro.launch import hlo_analysis
from repro.launch.dryrun import PEAK_BF16, HBM_BW, ICI_BW, build_cell, \
    build_stencil_cell
from repro.configs import STENCIL_IDS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--attn", default=None, choices=[None, "xla", "stub"])
    args = ap.parse_args()

    if args.arch in STENCIL_IDS:
        mesh, st, fn, cell_args, best = build_stencil_cell(
            args.arch, args.mesh == "multi")
    else:
        mesh, cfg, fn, cell_args = build_cell(args.arch, args.shape,
                                              args.mesh == "multi",
                                              attn_impl=args.attn)
    compiled = fn.lower(*cell_args).compile()
    an = hlo_analysis.analyze(compiled.as_text())

    print(f"== {args.arch} x {args.shape} x {args.mesh} ==")
    print(f"t_compute={an.flops / PEAK_BF16:.3f}s  "
          f"t_memory={an.hbm_bytes / HBM_BW:.3f}s  "
          f"t_collective={an.coll_bytes / ICI_BW:.3f}s")
    print(f"while trips: {an.while_trips}")

    print(f"\ntop-{args.top} HBM traffic (per device):")
    for name, (op, b, mult) in an.top_traffic(args.top):
        print(f"  {b / 1e9:12.2f} GB  x{mult:<6.0f} {op:24s} {name[:60]}")
    print(f"\ntop-{args.top} FLOPs:")
    for name, (op, f, mult) in an.top_flops(args.top):
        print(f"  {f / 1e12:12.2f} TF  x{mult:<6.0f} {op:24s} {name[:60]}")
    print(f"\ntop-{args.top} collectives (wire bytes):")
    for name, (op, b, mult) in an.top_coll(args.top):
        print(f"  {b / 1e9:12.2f} GB  x{mult:<6.0f} {op:24s} {name[:60]}")


if __name__ == "__main__":
    main()
