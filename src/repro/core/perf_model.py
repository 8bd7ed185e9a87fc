"""Performance model — paper §4 (Eqs. 3-9) with TPU hardware constants.

The paper's model assumes the computation is memory-bound and predicts run
time from external-memory traffic alone (Eq. 8).  On TPU the byte/FLOP
balance moves ~10x toward compute (819 GB/s HBM vs. 25-34 GB/s DDR), so we
keep the paper's traffic accounting *exactly* (Eqs. 4-7, via
``core.blocking``) but take ``time = max(t_mem, t_compute, t_halo)`` — the
deep-pipeline overlap assumption carries over (DMA prefetch overlaps VPU
compute; halo exchange overlaps the interior sweep).

Two roles, mirroring the paper:
  1. Predict throughput for a given (bsize, par_time, par_vec) — §4.
  2. Prune the design space: pick the best (bsize, par_time, par_vec) subject
     to the VMEM budget — §5.3's BRAM/DSP pruning, with VMEM as the scarce
     resource.  ``par_vec`` (paper §3.3, Eq. 6-7) is the stream-axis vector
     width: the lane dimension is pinned at the 128-lane VPU row, but V
     rows/planes per tick is a free knob the model prices two ways — 2D
     sublane utilization (a ``(V, bsize)`` tile wastes ``(8-V)/8`` of the
     f32 tile's sublanes below V=8) and per-DMA issue cost (V-row slabs cut
     the descriptor count ~V-fold; thin-row streams are issue-bound, not
     bandwidth-bound).  See DESIGN.md §2.2.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

from repro.core.blocking import (BlockGeometry, bsize_feasible,
                                 choose_bsize_candidates, extended_geometry,
                                 superstep_traffic_bytes, tpu_tiles)
from repro.core.precision import sublanes_for
from repro.core.stencils import Stencil

#: baseline ``par_vec`` sweep of :func:`autotune` — powers of two around the
#: 8-sublane f32 tile (V=8 fills every sublane; V=16 halves the DMA
#: descriptor count again at 2x the window VMEM).  16-bit dtypes extend to
#: V=32 — see :func:`par_vec_candidates`.
PAR_VEC_CANDIDATES = (1, 2, 4, 8, 16)


def par_vec_candidates(cell_bytes: int = 4):
    """The ``par_vec`` sweep for a given cell width.  Sub-4-byte dtypes get
    taller minimum tiles (16 sublanes for bf16), doubling the V that fills a
    tile's sublanes — the sweep ceiling doubles with it (V=32 for 16-bit
    cells, the bf16 analogue of f32's V=16)."""
    if cell_bytes <= 2:
        return PAR_VEC_CANDIDATES + (32,)
    return PAR_VEC_CANDIDATES


@dataclasses.dataclass(frozen=True)
class Device:
    """Per-chip hardware constants. Defaults: TPU v5e (see DESIGN.md §7)."""
    name: str = "tpu_v5e"
    #: ``jax.devices()[0].device_kind`` of this chip
    kind: str = "TPU v5 lite"
    mem_bw: float = 819e9            # HBM bytes/s
    vpu_flops: float = 12.3e12       # f32 vector FLOP/s (assumed MXU_bf16/16)
    mxu_flops_bf16: float = 197e12   # MXU peak (LM roofline uses this)
    vmem_budget: int = 32 * 2 ** 20  # usable VMEM for kernel working set
    ici_bw: float = 50e9             # bytes/s per ICI link
    hbm_bytes: int = 16 * 2 ** 30
    #: amortized cost of issuing one DMA descriptor (the reason a
    #: ``(1, bsize)`` row stream cannot saturate ``mem_bw``: at V=1 the
    #: kernels issue one descriptor per row per block per stream)
    dma_issue_s: float = 2e-8
    #: where the peaks come from (``vpu_flops``, ``vmem_budget`` and
    #: ``dma_issue_s`` are model assumptions, not published figures)
    source: str = ("Google Cloud TPU docs, 'TPU v5e': 197 TFLOP/s bf16, "
                   "16 GB HBM at 819 GB/s")

    def scaled(self, **kw) -> "Device":
        return dataclasses.replace(self, **kw)


# The one table of chips the model knows, keyed by name; ``DEVICE_KINDS``
# indexes it by the ``device_kind`` JAX reports.
TPU_V5E = Device()
TPU_V5P = Device(name="tpu_v5p", kind="TPU v5", mem_bw=2765e9,
                 vpu_flops=28.7e12, mxu_flops_bf16=459e12,
                 vmem_budget=64 * 2 ** 20, ici_bw=100e9,
                 hbm_bytes=95 * 2 ** 30,
                 source="Google Cloud TPU docs, 'TPU v5p': 459 TFLOP/s "
                        "bf16, 95 GB HBM at 2765 GB/s")
TPU_V6E = Device(name="tpu_v6e", kind="TPU v6 lite", mem_bw=1640e9,
                 vpu_flops=57.4e12, mxu_flops_bf16=918e12,
                 vmem_budget=64 * 2 ** 20, ici_bw=90e9,
                 hbm_bytes=32 * 2 ** 30,
                 source="Google Cloud TPU docs, 'TPU v6e': 918 TFLOP/s "
                        "bf16, 32 GB HBM at 1640 GB/s")

DEVICES = {d.name: d for d in (TPU_V5E, TPU_V5P, TPU_V6E)}
DEVICE_KINDS = {d.kind: d for d in DEVICES.values()}

#: the named target planning prices against where no TPU is attached (a
#: model input, not a measurement)
DEFAULT_TARGET = "tpu_v5e"


def attached_device() -> Device:
    """The :class:`Device` of the attached chip, from JAX's
    ``device_kind``; :data:`DEFAULT_TARGET` when the backend is not a TPU.
    A TPU kind missing from the table is an error, never a default."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return DEVICES[DEFAULT_TARGET]
    if dev.device_kind not in DEVICE_KINDS:
        raise ValueError(f"no Device entry for TPU kind "
                         f"{dev.device_kind!r}; known: {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[dev.device_kind]


@dataclasses.dataclass(frozen=True)
class Prediction:
    geom: BlockGeometry
    t_mem: float                 # s per super-step (memory term)
    t_compute: float             # s per super-step (compute term)
    t_halo: float                # s per super-step (collective term; 0 if single chip)
    n_super: int
    run_time: float
    gbytes_s: float              # paper Eq. 9 "throughput"
    gcells_s: float
    gflops: float
    vmem_bytes: int
    bound: str                   # "memory" | "compute" | "collective"
    batch: int = 1               # problems advanced per batched super-step

    def describe(self) -> str:
        return (f"bsize={self.geom.bsize} par_time={self.geom.par_time} "
                f"par_vec={self.geom.par_vec} "
                f"-> {self.gflops / 1e9:.1f} GFLOP/s ({self.bound}-bound, "
                f"{self.gcells_s / 1e9:.2f} GCell/s, red={self.geom.redundancy:.2f})")


def block_dims(stencil: Stencil, dims, par_time: int, n_chips: int,
                chip_grid) -> tuple:
    """The extents one chip's kernel streams: the grid on one chip, else a
    shard extended by the ``rad * par_time`` halo on each sharded side
    (core/distributed.py)."""
    if n_chips <= 1:
        return tuple(dims)
    cg = (tuple(chip_grid) if chip_grid
          else (n_chips,) + (1,) * (len(dims) - 1))
    halo = stencil.radius * par_time
    return tuple(math.ceil(d / c) + (2 * halo if c > 1 else 0)
                 for d, c in zip(dims, cg))


def predict(stencil: Stencil, dims: Sequence[int], iters: int,
            bsize, par_time: int, device: Device = TPU_V5E,
            cell_bytes: int = 4, n_chips: int = 1,
            chip_grid: Sequence[int] | None = None,
            batch: int = 1, bc=None, par_vec: int = 1,
            aligned: bool = False) -> Prediction:
    """Paper Eqs. (3)-(9) + compute/collective terms.

    ``par_vec`` (paper Eq. 7's vector width, V): the kernels stream V
    rows/planes per tick, so the idealized bytes are unchanged (up to the
    slab pad of a non-divisible stream) while the tick and DMA-descriptor
    counts shrink ~V-fold — ``t_mem`` gains a per-descriptor issue term that
    V amortizes.  For 2D grids the per-tick compute tile is ``(V, bsize)``
    whose sublane dim is V, so the VPU runs at ``min(V, 8)/8`` utilization
    below the 8-sublane f32 tile; 3D tiles put the blocked y extent on the
    sublanes and V only moves the DMA term.

    ``n_chips``: spatial distribution (core/distributed.py) — the grid is
    split over chips along the streaming axis (+x for 2D), each chip runs
    the same blocking locally and exchanges a halo of width rad*par_time
    per super-step over ICI.

    ``batch``: ``StencilPlan.run_batch`` advances ``batch`` problems per
    super-step through one executable.  Grid traffic, compute, and halo
    bytes scale with the batch; the read-only aux stream (Hotspot's power
    grid, shared by the batch) and the scalar coefficients are loaded once
    — so batched Hotspot moves fewer bytes per problem than ``batch``
    separate runs.  Per-problem metrics (``gcells_s`` etc.) are reported
    for the whole batch.

    ``bc``: the boundary condition prices into the model two ways.  A
    periodic *streaming* axis adds a ``2 * rad * par_time`` stream extension
    per super-step (the kernels materialize the wrap in HBM — extra rows
    both read and traversed).  Periodic *sharded* axes exchange on a full
    wrap-around ring: per-chip halo bytes are unchanged (interior shards
    already sent both strips, which is what ``t_halo`` prices as the
    critical path), and a sharded periodic stream axis needs no stream
    extension (the ring brings the wrap).

    ``aligned`` prices the geometry the compiled kernels run: halos and
    compute extents rounded to the TPU tiles (:func:`tpu_tiles`).
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if isinstance(bsize, int):
        bsize = (bsize,) * (len(dims) - 1)
    local_dims = tuple(dims)
    cg = (1,) * len(dims)
    if n_chips > 1:
        cg = tuple(chip_grid) if chip_grid else (n_chips,) + (1,) * (len(dims) - 1)
        local_dims = tuple(math.ceil(d / c) for d, c in zip(dims, cg))
    # bill the block each chip's kernel streams: its shard extended by the
    # halo on every sharded side
    geom = BlockGeometry(len(dims), block_dims(stencil, dims, par_time,
                                                n_chips, chip_grid),
                         stencil.radius, par_time,
                         tuple(bsize), par_vec,
                         tpu_tiles(len(dims), cell_bytes)[1] if aligned
                         else ())
    # periodic stream BC: the kernels stream 2*size_halo extra rows/planes
    # per super-step (the materialized wrap) — bill traffic/compute on the
    # extended geometry, report the caller-visible one
    geom_t = extended_geometry(geom, bc) if cg[0] == 1 else geom

    # --- memory term (paper Eq. 3: th_mem saturates at th_max = HBM bw) ----
    step_bytes = superstep_traffic_bytes(geom_t, stencil.num_read,
                                         stencil.num_write, cell_bytes)
    # per-descriptor issue cost: each block moves ceil(stream/V) slabs per
    # input stream and per output per super-step — at V=1 a thin-row stream
    # is descriptor-bound, which is what par_vec amortizes
    n_dma = (batch * geom_t.num_blocks * geom_t.stream_slabs()
             * (stencil.num_read + stencil.num_write))
    if batch > 1:
        # batched super-steps share the read-only aux stream: bill it once,
        # not `batch` times (coefficients are scalars — free either way)
        aux_bytes = (superstep_traffic_bytes(geom_t, 1, 0, cell_bytes)
                     if stencil.has_aux else 0)
        step_bytes = batch * step_bytes - (batch - 1) * aux_bytes
    t_mem = step_bytes / device.mem_bw + n_dma * device.dma_issue_s

    # --- compute term: every traversed cell is updated par_time times ------
    # sublane utilization of the per-tick compute tile: 1D/2D slabs are
    # (V,)/(V, bsize) — V sublanes of the 8-sublane f32 tile; 3D slabs are
    # (V, bsize_y, bsize_x) — the y extent fills the sublanes
    # the minimum-tile sublane count is dtype-dependent: 8 for 4-byte cells,
    # 16 for bf16 — a (V, bsize) bf16 tile needs V=16 to fill its sublanes
    sublanes = sublanes_for(cell_bytes)
    sub = bsize[0] if len(dims) == 3 else par_vec
    sub_eff = min(sub, sublanes) / sublanes
    cells_per_super = batch * geom_t.stream_dim * math.prod(
        n * b for n, b in zip(geom.bnum, geom.bsize))
    flops_per_super = cells_per_super * par_time * stencil.flop_pcu
    t_compute = flops_per_super / (device.vpu_flops * sub_eff)

    # --- collective term: halo exchange once per super-step ----------------
    # Each grid axis actually sharded by the chip grid exchanges two strips
    # of width size_halo whose face area is the shard's cross-section
    # *perpendicular to that axis* — not always the streaming-axis face the
    # 2D paper setup suggests.  A batch aggregates its members' halos into
    # one exchange (bytes scale with the batch; the per-super-step latency
    # events do not).
    t_halo = 0.0
    if n_chips > 1:
        local_cells = math.prod(local_dims)
        halo_cells = sum(geom.size_halo * local_cells // local_dims[ax]
                         for ax, c in enumerate(cg) if c > 1)
        halo_bytes = 2 * batch * halo_cells * cell_bytes * max(stencil.num_read, 1)
        t_halo = halo_bytes / device.ici_bw

    n_super = math.ceil(iters / par_time)
    t_step = max(t_mem, t_compute, t_halo)
    run_time = n_super * t_step
    total_cells = batch * math.prod(dims) * iters   # all problems, all chips
    bound = ("memory" if t_mem >= max(t_compute, t_halo)
             else "compute" if t_compute >= t_halo else "collective")
    return Prediction(
        geom=geom, t_mem=t_mem, t_compute=t_compute, t_halo=t_halo,
        n_super=n_super, run_time=run_time,
        gbytes_s=n_super * step_bytes / run_time,
        gcells_s=total_cells / run_time,
        gflops=total_cells * stencil.flop_pcu / run_time,
        vmem_bytes=geom.vmem_bytes(
            cell_bytes, stencil.has_aux,
            stage_radii=getattr(stencil, "stage_radii", None),
            dag_info=(stencil.dag_vmem_info(geom.par_time, geom.par_vec)
                      if hasattr(stencil, "dag_vmem_info") else None)),
        bound=bound, batch=batch)


def autotune(stencil: Stencil, dims: Sequence[int], iters: int,
             device: Device = TPU_V5E, cell_bytes: int = 4,
             par_time_max: int = 64, n_chips: int = 1,
             chip_grid: Sequence[int] | None = None, *,
             par_time: int | None = None,
             bsize: Sequence[int] | None = None,
             par_vec: int | None = None,
             par_vecs: Sequence[int] | None = None,
             top_k: int | None = None, bc=None,
             aligned: bool = False) -> list:
    """Design-space pruning (paper §5.3): enumerate power-of-two bsize ×
    par_time × par_vec, drop configs whose working set exceeds the VMEM
    budget, rank by predicted run time. Returns predictions sorted best-first.

    A pinned ``par_time``, ``bsize`` or ``par_vec`` constrains the sweep to
    exactly that value (the paper's tuned depths, e.g. 36, need not be powers
    of two); only the free dimension(s) are enumerated — ``par_vec`` over
    :func:`par_vec_candidates` for the cell width by default (V<=16 for
    f32, V<=32 for 16-bit cells).  ``top_k`` keeps only the
    best-ranked predictions — the shortlist the measured tuner
    (``repro.api.tuner``) times on real hardware.  May return ``[]`` when
    nothing is feasible — callers must not index blindly.

    ``aligned`` restricts the sweep to geometries the compiled kernels
    accept (:func:`tpu_tiles`): tile-aligned halos and compute extents, and
    ``par_vec`` a multiple of the stream tile."""
    if par_time is not None:
        pts = [par_time]
    else:
        pts, pt = [], 1
        while pt <= par_time_max:
            pts.append(pt)
            pt *= 2
    if par_vecs is None:
        # 16-bit cells sweep up to V=32 (the 16-sublane tile ceiling)
        par_vecs = par_vec_candidates(cell_bytes)
    pvs = [par_vec] if par_vec is not None else list(par_vecs)
    stream_tile, align = (tpu_tiles(len(dims), cell_bytes) if aligned
                          else (1, ()))
    pvs = [pv for pv in pvs if pv % stream_tile == 0]
    cands = []
    for pt in pts:
        if bsize is not None:
            # feasibility mirrors choose_bsize_candidates' filter
            bss = ([tuple(bsize)]
                   if bsize_feasible(stencil.radius, pt, bsize, align)
                   else [])
        else:
            bss = choose_bsize_candidates(
                len(dims), block_dims(stencil, dims, pt, n_chips, chip_grid),
                stencil.radius, pt, align, splits=n_chips > 1)
        for bs in bss:
            for pv in pvs:
                p = predict(stencil, dims, iters, bs, pt, device,
                            cell_bytes, n_chips, chip_grid, bc=bc,
                            par_vec=pv, aligned=aligned)
                if p.vmem_bytes <= device.vmem_budget:
                    cands.append(p)
    cands.sort(key=lambda p: p.run_time)
    return cands if top_k is None else cands[:top_k]


def model_accuracy(measured_s: float, predicted: Prediction) -> float:
    """Paper §6.2: measured/estimated performance ratio."""
    return predicted.run_time / measured_s
