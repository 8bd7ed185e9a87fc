"""Paper Fig. 6 analogue: performance vs. the no-temporal-blocking roofline
across devices.

The paper's Fig. 6 compares Diffusion 3D on FPGAs vs GPUs, with each
device's "roofline" = the GFLOP/s achievable at full external-bandwidth
utilization WITHOUT temporal blocking (bytes-PCU-limited). The FPGA beats
its own roofline by several x because temporal blocking trades on-chip
storage for bandwidth — the paper's core argument.

We reproduce that chart's data for the TPU family: per device, the
bandwidth roofline (no temporal blocking), the model-predicted performance
of our combined-blocking accelerator, and the resulting "x over roofline".
Paper-reported device datapoints (Arria 10 measured, P100/V100 from the
paper's Fig. 6) are included as static reference context.
"""
from __future__ import annotations

from repro.core import STENCILS, autotune
from repro.core.perf_model import DEVICES

FULL_DIMS = {2: (16384, 16384), 3: (448, 448, 448)}
ITERS = 1000

# paper Fig. 6 reference points (GFLOP/s, Diffusion 3D, as published)
PAPER_POINTS = {
    "arria10_gx1150 (paper, measured)": dict(mem_bw=34.1e9, gflops=374.7),
    "stratix10_mx2100 (paper, projected)": dict(mem_bw=512e9, gflops=1584.8),
    "tesla_p100 (paper, measured)": dict(mem_bw=720.9e9, gflops=1100.0),
    "tesla_v100 (paper, measured)": dict(mem_bw=900.1e9, gflops=1400.0),
}


def run(benchmark: str = "diffusion3d") -> list[dict]:
    st = STENCILS[benchmark]
    dims = FULL_DIMS[st.ndim]
    rows = []
    for dev_name, dev in DEVICES.items():
        roofline = dev.mem_bw / st.bytes_pcu * st.flop_pcu   # no temp. blocking
        best = autotune(st, dims, ITERS, device=dev)[0]
        rows.append({
            "device": dev_name, "benchmark": benchmark,
            "roofline_gflops": round(roofline / 1e9, 1),
            "predicted_gflops": round(best.gflops / 1e9, 1),
            "x_over_roofline": round(best.gflops / roofline, 2),
            "par_time": best.geom.par_time,
            "bsize": best.geom.bsize,
            "source": "model (this work)",
        })
    for label, p in PAPER_POINTS.items():
        roofline = p["mem_bw"] / st.bytes_pcu * st.flop_pcu
        rows.append({
            "device": label, "benchmark": benchmark,
            "roofline_gflops": round(roofline / 1e9, 1),
            "predicted_gflops": p["gflops"],
            "x_over_roofline": round(p["gflops"] * 1e9 / roofline, 2),
            "source": "paper Fig. 6",
        })
    return rows


def main():
    rows = run()
    print(f"{'device':38s} {'roofline GF/s':>13s} {'achieved GF/s':>13s} "
          f"{'x roofline':>10s}  source")
    for r in rows:
        print(f"{r['device']:38s} {r['roofline_gflops']:13.1f} "
              f"{r['predicted_gflops']:13.1f} {r['x_over_roofline']:10.2f}  "
              f"{r['source']}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
