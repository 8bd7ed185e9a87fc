"""Paper Table 2: benchmark characteristics (FLOP / bytes per cell update).

The static columns come from the stencil zoo; the *verified* FLOP column is
counted from the compiled HLO of one unblocked time-step (XLA cost analysis
divided by grid cells) — the implementation must do exactly the paper's
arithmetic, or the ratio drifts from 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import STENCILS, default_coeffs
from repro.kernels.ref import oracle_step

GRID2D = (256, 256)
GRID3D = (32, 64, 64)


def run() -> list[dict]:
    rows = []
    for name in ("diffusion2d", "diffusion3d", "hotspot2d", "hotspot3d"):
        st = STENCILS[name]
        dims = GRID2D if st.ndim == 2 else GRID3D
        cells = 1
        for d in dims:
            cells *= d
        coeffs = default_coeffs(st)
        grid = jnp.ones(dims, jnp.float32)
        aux = jnp.ones(dims, jnp.float32) if st.has_aux else None

        compiled = jax.jit(
            lambda g, a: oracle_step(st, g, coeffs, a)).lower(
                grid, aux if aux is not None else grid).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        counted = ca.get("flops", 0.0) / cells

        rows.append({
            "benchmark": st.name,
            "flop_pcu": st.flop_pcu,
            "flop_pcu_counted_hlo": round(counted, 2),
            "bytes_pcu": st.bytes_pcu,
            "bytes_per_flop": round(st.bytes_pcu / st.flop_pcu, 3),
            "num_read": st.num_read,
            "num_write": st.num_write,
            "radius": st.radius,
        })
    return rows


PAPER = {  # paper Table 2 reference values
    "diffusion2d": dict(flop=9, bytes=8, ratio=0.889),
    "diffusion3d": dict(flop=13, bytes=8, ratio=0.615),
    "hotspot2d": dict(flop=15, bytes=12, ratio=0.800),
    "hotspot3d": dict(flop=17, bytes=12, ratio=0.706),
}


def main():
    rows = run()
    hdr = (f"{'benchmark':14s} {'FLOP PCU':>8s} {'HLO-counted':>11s} "
           f"{'Bytes PCU':>9s} {'B/FLOP':>7s} {'paper B/FLOP':>12s}")
    print(hdr)
    for r in rows:
        p = PAPER[r["benchmark"]]
        ok = (r["flop_pcu"] == p["flop"] and r["bytes_pcu"] == p["bytes"]
              and abs(r["bytes_per_flop"] - p["ratio"]) < 5e-3)
        print(f"{r['benchmark']:14s} {r['flop_pcu']:8d} "
              f"{r['flop_pcu_counted_hlo']:11.2f} {r['bytes_pcu']:9d} "
              f"{r['bytes_per_flop']:7.3f} {p['ratio']:12.3f} "
              f"{'ok' if ok else 'MISMATCH'}")
        assert ok, r
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
