"""Paper Table 4 analogue: per-stencil tuned configurations and throughput.

The paper reports, per stencil x board: candidate configs (bsize, par_time),
estimated performance from the model, measured performance, and model
accuracy. On this CPU container "the board" is unavailable, so the table
reports, per stencil on TPU v5e constants:

  * top candidate configs from the autotuner (paper §5.3 pruning),
  * predicted GB/s | GFLOP/s | GCell/s for each (paper "Estimated"),
  * **traffic accuracy**: the model's predicted HBM bytes per super-step vs
    the Pallas kernel's exact DMA-schedule bytes (the paper's "model
    accuracy" re-based on what is countable without hardware:
    predicted/actual *traffic* instead of predicted/actual *time*),
  * **engine HLO bytes**: counted fusion-boundary traffic of the pure-JAX
    engine for the same geometry — the ~2-orders-larger number that shows
    why the manual-DMA Pallas kernel is the production path on TPU,
  * **measured tuning** (the paper's Table 4 "Measured" + "Model Accuracy"
    columns): ``repro.api.tune`` times the model's top candidates on the
    blocked engine at reduced, host-measurable dims, reports measured
    GCell/s and model accuracy (estimated/measured time) per stencil, and
    persists the winner in the schedule cache — a second run of this
    benchmark is served from the cache without re-timing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.api import RunConfig, StencilProblem, plan, tune
from repro.core import STENCILS, autotune
from repro.core.blocking import BlockGeometry
from repro.core.engine import blocked_superstep
from repro.launch import hlo_analysis

# paper-scale dims (>= 1 GB inputs): 16384^2 (2D), 448^3-ish (3D)
FULL_DIMS = {2: (16384, 16384), 3: (448, 448, 448)}
# host-measurable dims
HOST_DIMS = {2: (512, 512), 3: (48, 96, 96)}
ITERS = 1000


def _hlo_traffic(st, geom: BlockGeometry, dims) -> float:
    """Compiled-HLO bytes of one super-step of the pure-JAX engine (CPU
    lowering, no allocation)."""
    coeffs = {k: jax.ShapeDtypeStruct((), jnp.float32)
              for k in st.coeff_names}
    g = jax.ShapeDtypeStruct(dims, jnp.float32)
    aux = jax.ShapeDtypeStruct(dims, jnp.float32) if st.has_aux else None
    fn = jax.jit(lambda gr, cf, ax: blocked_superstep(
        st, geom, gr, cf, geom.par_time, ax))
    compiled = fn.lower(g, coeffs, aux).compile()
    an = hlo_analysis.analyze(compiled.as_text())
    return an.hbm_bytes


def run(n_candidates: int = 3, with_hlo: bool = True,
        cache=None) -> list[dict]:
    """``cache``: passed through to ``RunConfig.cache`` for the measured rows
    (None = default location, False = no persistence, str = explicit path)."""
    rows = []
    for name in ("diffusion2d", "diffusion3d", "hotspot2d", "hotspot3d"):
        st = STENCILS[name]
        dims = FULL_DIMS[st.ndim]
        cands = autotune(st, dims, ITERS)[:n_candidates]
        for rank, p in enumerate(cands):
            row = {
                "benchmark": st.name, "rank": rank,
                "dims": dims, "iters": ITERS,
                "bsize": p.geom.bsize, "par_time": p.geom.par_time,
                "csize": p.geom.csize, "redundancy": round(p.geom.redundancy, 3),
                "pred_gbytes_s": round(p.gbytes_s / 1e9, 1),
                "pred_gflops": round(p.gflops / 1e9, 1),
                "pred_gcells_s": round(p.gcells_s / 1e9, 2),
                "bound": p.bound,
                "vmem_mib": round(p.vmem_bytes / 2**20, 2),
                "run_time_s": round(p.run_time, 4),
            }
            if rank == 0:
                # traffic accuracy via the plan API (model Eq. 7/8 vs. the
                # Pallas kernels' exact DMA schedule)
                tr = plan(StencilProblem(st, dims),
                          RunConfig(backend="engine",
                                    par_time=p.geom.par_time,
                                    bsize=p.geom.bsize)).traffic_report()
                model_bytes = tr["model_bytes_per_superstep"]
                kernel_bytes = tr["kernel_dma_bytes_per_superstep"]
                row["model_bytes_per_super"] = model_bytes
                row["kernel_dma_bytes_per_super"] = kernel_bytes
                row["traffic_accuracy"] = round(tr["traffic_accuracy"], 3)
                if with_hlo:
                    hlo_bytes = _hlo_traffic(st, p.geom, dims)
                    row["engine_hlo_bytes_per_super"] = hlo_bytes
                    row["engine_amplification"] = round(
                        hlo_bytes / kernel_bytes, 1) if kernel_bytes else None
            rows.append(row)

        # measured tuning at host-measurable dims (Table 4 "Measured" +
        # "Model Accuracy" columns): time the model's top candidates on the
        # blocked engine, persist the winner in the schedule cache.
        hdims = HOST_DIMS[st.ndim]
        hplan = tune(StencilProblem(st, hdims),
                     RunConfig(backend="engine", iters_hint=8,
                               tune_top_k=3, tune_warmup=1, tune_repeats=2,
                               cache=cache))
        for rank, c in enumerate(hplan.candidates):
            rows.append({
                "benchmark": st.name, "rank": f"measured-{rank}",
                "dims": hdims, "iters": 8,
                "bsize": c.geom.bsize, "par_time": c.geom.par_time,
                "measured_s_per_super": round(c.measured_s, 6),
                "measured_gcells_s": round(
                    math.prod(hdims) * c.geom.par_time
                    / c.measured_s / 1e9, 4),
                "model_accuracy": c.model_accuracy,
                "from_cache": c.from_cache,
            })
    return rows


def main():
    rows = run()
    print(f"{'benchmark':13s} {'bsize':>12s} {'par_t':>5s} {'red.':>5s} "
          f"{'GB/s':>7s} {'GFLOP/s':>8s} {'GCell/s':>8s} {'bound':>7s} "
          f"{'VMEM MiB':>8s} {'traffic acc':>11s}")
    for r in rows:
        if str(r["rank"]).startswith("measured"):
            src = "cache" if r["from_cache"] else "timed"
            print(f"{r['benchmark']:13s} {str(r['bsize']):>12s} "
                  f"{r['par_time']:5d}   measured ({src}): "
                  f"{r['measured_gcells_s']:.4f} GCell/s @ {r['dims']}, "
                  f"model_accuracy={r['model_accuracy']:.3g}")
            continue
        acc = r.get("traffic_accuracy")
        print(f"{r['benchmark']:13s} {str(r['bsize']):>12s} "
              f"{r['par_time']:5d} {r['redundancy']:5.2f} "
              f"{r['pred_gbytes_s']:7.1f} {r['pred_gflops']:8.1f} "
              f"{r['pred_gcells_s']:8.2f} {r['bound']:>7s} "
              f"{r['vmem_mib']:8.2f} "
              f"{acc if acc is not None else '':>11}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
