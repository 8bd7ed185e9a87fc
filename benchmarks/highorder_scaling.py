"""Beyond-paper: high-order stencils under combined blocking (paper §8's
stated future work).

The paper conjectures temporal blocking weakens for high-order stencils:
halo width `rad·par_time` grows with the radius, so redundancy eats the
bandwidth savings sooner. We quantify it with the (traffic-validated)
performance model: for star stencils of radius 1-4, 2D and 3D, report the
autotuned (bsize, par_time), the redundancy, the bound, and the achieved
fraction of the no-temporal-blocking roofline.

Correctness of the high-order engine itself is covered by
tests/test_engine.py::test_high_order_star (radius-2 blocked == oracle).

Expected shape of the result (and what the model shows): optimal par_time
falls roughly as 1/rad in 2D and collapses to 1-4 in 3D, while the
x-over-roofline multiple compresses toward 1 — the paper's temporal-blocking
advantage is a low-order phenomenon unless block sizes grow with rad.
"""
from __future__ import annotations

from repro.core import autotune, make_star
from repro.core.perf_model import TPU_V5E

DIMS = {2: (16384, 16384), 3: (448, 448, 448)}
ITERS = 1000


def run() -> list[dict]:
    rows = []
    for ndim in (2, 3):
        for rad in (1, 2, 3, 4):
            st = make_star(ndim, rad)
            dims = DIMS[ndim]
            best = autotune(st, dims, ITERS)[0]
            roofline = TPU_V5E.mem_bw / st.bytes_pcu * st.flop_pcu
            rows.append({
                "stencil": st.name, "ndim": ndim, "radius": rad,
                "flop_pcu": st.flop_pcu,
                "bsize": best.geom.bsize,
                "par_time": best.geom.par_time,
                "halo": best.geom.size_halo,
                "redundancy": round(best.geom.redundancy, 3),
                "pred_gflops": round(best.gflops / 1e9, 1),
                "bound": best.bound,
                "x_over_roofline": round(best.gflops / roofline, 2),
            })
    return rows


def main():
    rows = run()
    print(f"{'stencil':12s} {'rad':>3s} {'bsize':>12s} {'par_t':>5s} "
          f"{'halo':>4s} {'red.':>6s} {'GFLOP/s':>8s} {'bound':>8s} "
          f"{'x roofline':>10s}")
    for r in rows:
        print(f"{r['stencil']:12s} {r['radius']:3d} {str(r['bsize']):>12s} "
              f"{r['par_time']:5d} {r['halo']:4d} {r['redundancy']:6.2f} "
              f"{r['pred_gflops']:8.1f} {r['bound']:>8s} "
              f"{r['x_over_roofline']:10.2f}")
    # the paper's conjecture, checked: par_time monotonically non-increasing
    # in radius within each dimensionality
    for ndim in (2, 3):
        pts = [r["par_time"] for r in rows if r["ndim"] == ndim]
        assert all(a >= b for a, b in zip(pts, pts[1:])), pts
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
