"""Paper Table 6 analogue: performance projection for next-generation
devices via the performance model.

The paper projects its Arria 10 results to the (then-upcoming) Stratix 10
GX 2800 and MX 2100 with a calibration factor derived from measured model
accuracy (80% 2D / 60% 3D). We project the TPU v5e-tuned accelerator to
TPU v5p and v6e the same way: re-run the autotuner with each device's
constants, apply the traffic-accuracy calibration measured in Table 4
(model vs kernel DMA schedule), and report the best configuration.

The paper's headline observation reproduces on TPU: a device's
"memory-bandwidth to compute" ratio decides the bottleneck — v5p's HBM2e
(2.7 TB/s) pushes even 3D stencils fully compute-bound, while v5e leaves
big-par_time 3D configs memory-bound.
"""
from __future__ import annotations

from repro.core import STENCILS, autotune
from repro.core.blocking import superstep_traffic_bytes
from repro.core.perf_model import DEVICES
from repro.kernels.ops import dma_traffic_bytes

FULL_DIMS = {2: (16384, 16384), 3: (448, 448, 448)}
ITERS = 5000   # paper Table 6 uses 5000 iterations


def run(calibration: dict | None = None) -> list[dict]:
    rows = []
    for dev_name in ("tpu_v5e", "tpu_v5p", "tpu_v6e"):
        dev = DEVICES[dev_name]
        for name in ("diffusion2d", "diffusion3d", "hotspot2d", "hotspot3d"):
            st = STENCILS[name]
            dims = FULL_DIMS[st.ndim]
            best = autotune(st, dims, ITERS, device=dev)[0]
            # calibration factor: measured traffic accuracy (Table 4), or
            # the kernel-DMA ratio computed directly for this geometry
            if calibration and name in calibration:
                cal = calibration[name]
            else:
                cal = (superstep_traffic_bytes(best.geom, st.num_read,
                                               st.num_write)
                       / dma_traffic_bytes(st, best.geom))
            rows.append({
                "device": dev_name, "benchmark": name,
                "bsize": best.geom.bsize, "par_time": best.geom.par_time,
                "pred_gflops": round(best.gflops / 1e9, 1),
                "calibration": round(cal, 3),
                "calibrated_gflops": round(best.gflops * cal / 1e9, 1),
                "calibrated_tflops": round(best.gflops * cal / 1e12, 3),
                "bound": best.bound,
                "vmem_mib": round(best.vmem_bytes / 2**20, 2),
                "bw_used_gbs": round(best.gbytes_s / 1e9, 1),
                "bw_util_pct": round(100 * best.gbytes_s / dev.mem_bw, 1),
            })
    return rows


def main():
    rows = run()
    print(f"{'device':9s} {'benchmark':13s} {'bsize':>11s} {'par_t':>5s} "
          f"{'pred GF/s':>10s} {'cal':>6s} {'cal GF/s':>9s} {'bound':>8s} "
          f"{'BW%':>5s}")
    for r in rows:
        print(f"{r['device']:9s} {r['benchmark']:13s} {str(r['bsize']):>11s} "
              f"{r['par_time']:5d} {r['pred_gflops']:10.1f} "
              f"{r['calibration']:6.3f} {r['calibrated_gflops']:9.1f} "
              f"{r['bound']:>8s} {r['bw_util_pct']:5.1f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
