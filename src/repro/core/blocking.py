"""Block/halo geometry — paper Eqs. (1)-(7), adapted to TPU lane alignment.

The paper blocks the *fastest* dimension(s) and streams the remaining one:
  * 2D stencils: 1-D spatial blocking in x, streaming in y        (paper §3.1)
  * 3D stencils: 2-D spatial blocking in (x, y), streaming in z   (paper §3.1)

Array layout convention in this repo: the streaming dimension is axis 0
(y for 2D grids ``(ny, nx)``, z for 3D grids ``(nz, ny, nx)``); blocked
dimensions are the trailing axes.

Temporal blocking widens each halo to ``size_halo = rad * par_time``
(paper Eq. 2).  Overlapped blocks (Fig. 4) of extent ``bsize`` advance by the
compute-block stride ``csize = bsize - 2*size_halo`` (Eq. 4); the number of
blocks per dimension is ``ceil(dim / csize)`` (Eq. 5), and out-of-bound
compute in the last block is discarded at write time.

TPU alignment (paper §3.3.3 analogue): the paper pads device buffers so
external accesses stay 512-bit aligned.  On TPU every HBM DMA window must
start on a tile boundary: a multiple of 128 lanes on the minor axis and of the
dtype's sublane count (8 for f32, 16 for bf16) on the second-minor one.  A
geometry built with ``align`` (see :func:`tpu_tiles`) rounds the padded
layout's leading halo up to the tile (:attr:`BlockGeometry.pad`) and keeps
``csize`` a tile multiple, so every block's input window (``i * csize``) and
output window (``i * csize + pad``) starts on a tile; the extra ``pad -
size_halo`` columns are redundant halo.  Without ``align`` (interpret mode,
the engine) ``pad == size_halo`` and the geometry is the paper's.

Stream-axis vectorization (paper §3.3 ``par_vec``): each pipeline tick
advances ``par_vec`` rows/planes instead of one, so the rolling windows hold
``win_slots`` slabs of ``par_vec`` rows, every DMA moves a ``(par_vec, ...)``
slab, and the tick count shrinks ~``par_vec``-fold.  On TPU the natural sweet
spot is the 8-sublane f32 tile: at V=1 Mosaic pads every window slot and DMA
landing buffer to 8 sublanes (waste ``vmem_bytes`` now accounts for); at V=8
each sublane carries a real row.  See DESIGN.md §2.2.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Sequence, Tuple

from repro.core.precision import sublanes_for

LANE = 128      # lanes per VREG row on TPU (dtype-independent)
SUBLANE = 8     # sublanes of the 4-byte (f32) minimum tile; 16-bit tiles
                # use 16 — see repro.core.precision.sublanes_for


@dataclasses.dataclass(frozen=True)
class BlockGeometry:
    """Static description of one combined spatial/temporal blocking plan."""
    ndim: int                      # grid rank (1, 2 or 3; streaming axis 0)
    dims: Tuple[int, ...]          # grid extents, streaming axis first
    rad: int
    par_time: int                  # fused time-steps per HBM round-trip
    bsize: Tuple[int, ...]         # block extent per *blocked* dim (trailing axes)
    par_vec: int = 1               # rows/planes advanced per pipeline tick (V)
    #: per-blocked-dim tile the layout's halo and ``csize`` round up to
    #: (``tpu_tiles``); ``()`` = no alignment
    align: Tuple[int, ...] = ()

    def __post_init__(self):
        assert self.ndim == len(self.dims)
        assert len(self.bsize) == self.ndim - 1, "streaming axis is not blocked"
        assert len(self.align) in (0, self.ndim - 1), "one tile per blocked dim"
        if self.par_vec < 1:
            raise ValueError(f"par_vec must be >= 1, got {self.par_vec}")
        if any(b <= 2 * p for b, p in zip(self.bsize, self.pad)):
            raise ValueError(
                f"bsize {self.bsize} too small for halo {self.pad} "
                f"(need bsize > 2 * pad per dim; rad*par_time = "
                f"{self.size_halo}, align = {self.align})")
        if any(b % a for b, a in zip(self.bsize, self.align)):
            raise ValueError(f"bsize {self.bsize} is not a multiple of the "
                             f"tiles {self.align}")

    # --- paper Eq. (2): halo width per side, in the last PE -----------------
    @property
    def size_halo(self) -> int:
        return self.rad * self.par_time

    @property
    def pad(self) -> Tuple[int, ...]:
        """Leading halo of the padded layout per blocked dim: ``size_halo``
        rounded up to that dim's tile (equal to it without ``align``)."""
        align = self.align or (1,) * (self.ndim - 1)
        return tuple(-(-self.size_halo // a) * a for a in align)

    # --- paper Eq. (4): compute-block extent --------------------------------
    @property
    def csize(self) -> Tuple[int, ...]:
        return tuple(b - 2 * p for b, p in zip(self.bsize, self.pad))

    # --- paper Eq. (5): blocks per blocked dimension -------------------------
    @property
    def bnum(self) -> Tuple[int, ...]:
        return tuple(math.ceil(d / c)
                     for d, c in zip(self.blocked_dims, self.csize))

    @property
    def stream_dim(self) -> int:
        return self.dims[0]

    # --- stream-axis vectorization (paper §3.3 par_vec on the TPU) ----------
    @property
    def slab_lag(self) -> int:
        """Slabs of ``par_vec`` rows each PE stage lags its producer by —
        the vector generalization of the per-stage ``rad``-row lag
        (``ceil(rad / par_vec)``; equals ``rad`` at V=1)."""
        return -(-self.rad // self.par_vec)

    @property
    def win_slots(self) -> int:
        """Slab slots per rolling stage window.  Stage ``t`` computing slab
        ``j`` taps rows ``j*V - rad .. (j+1)*V - 1 + rad`` of stage
        ``t-1``, i.e. slabs ``j - slab_lag .. j + slab_lag`` — the vector
        form of the ``2*rad + 1``-row window (which it equals at V=1)."""
        return 2 * self.slab_lag + 1

    def stream_slabs(self, stream: int | None = None) -> int:
        """Ticks needed to stream ``stream`` rows/planes, ``par_vec`` at a
        time (kernel wrappers pad the stream axis up to a slab multiple)."""
        n = self.stream_dim if stream is None else stream
        return -(-n // self.par_vec)

    @property
    def blocked_dims(self) -> Tuple[int, ...]:
        return self.dims[1:]

    # --- padded extents: bnum*csize + 2*pad (what the engine/kernels see) ---
    @property
    def padded_dims(self) -> Tuple[int, ...]:
        return tuple(n * c + 2 * p
                     for n, c, p in zip(self.bnum, self.csize, self.pad))

    @property
    def num_blocks(self) -> int:
        return math.prod(self.bnum)

    # --- paper Eq. (7): traversed cells per blocked dimension ---------------
    @property
    def trav(self) -> Tuple[int, ...]:
        """Alias of :attr:`padded_dims`: the Eq. (7) 'traversed' extent
        (``bnum * csize + 2*pad``) is exactly the padded extent the
        engine/kernels see — one definition, two paper names."""
        return self.padded_dims

    # --- paper Eq. (6): cells read from external memory per input buffer ----
    @property
    def cells_read(self) -> int:
        r = self.stream_dim
        for n, b in zip(self.bnum, self.bsize):
            r *= n * b
        return r

    @property
    def cells_written(self) -> int:
        # writes masked to in-bounds compute cells only (paper §3.2/§4)
        return math.prod(self.dims)

    @property
    def redundancy(self) -> float:
        """Read amplification from overlapped halos + out-of-bound cells."""
        return self.cells_read / math.prod(self.dims)

    # --- VMEM working set of the streaming kernels (bytes) ------------------
    def vmem_bytes(self, cell_bytes: int = 4, has_aux: bool = False,
                   double_buffer: bool = True,
                   stage_radii: Sequence[int] | None = None,
                   dag_info: tuple | None = None) -> int:
        """Rolling-window footprint of the Pallas kernel for this geometry,
        **as Mosaic tiles it**: the second-to-last dim of every VMEM buffer
        is padded to a multiple of 8 sublanes (f32 (8, 128) tiling), so a
        V=1 2D kernel's ``(2*rad+1, bsize)`` window slots and its
        ``(1, bsize)`` DMA landing buffers each occupy 8 sublanes no matter
        how few rows they hold.  That padding is exactly what ``par_vec``
        reclaims: at V=8 every sublane of the ``(V, bsize)`` slab carries a
        real row.  Counting it here keeps autotune's VMEM feasibility filter
        from admitting candidates that OOM on hardware.

        Per chain entry (program stage × temporal stage): a slab window of
        ``2*ceil(r_i/V) + 1`` slots of ``par_vec`` rows/planes each, sized
        for *that* entry's radius; plus double-buffered input/output DMA
        slabs and, for Hotspot, an aux (power) window deep enough to feed
        the last entry (``Lag_total + 1`` slabs).  ``stage_radii`` prices a
        multi-stage :class:`~repro.programs.StencilProgram`'s heterogeneous
        chain; ``None`` is the classic single-operator chain (``rad`` per
        entry).

        ``dag_info`` prices a general DAG program instead: a
        ``(win_slots, n_in, n_out, aux_slabs)`` tuple from
        :meth:`~repro.programs.StencilProgram.dag_vmem_info`.  ``win_slots``
        enumerates every live value-node window's depth (in V-slabs) over
        the *already unrolled* graph — per-edge consumer reach, not the
        chain's uniform ``2*lag+1`` — so no ``par_time`` multiplier applies;
        ``n_in``/``n_out`` count the external field streams each needing
        their own DMA slabs; ``aux_slabs`` is the aux window depth (0 = no
        aux).
        """
        V = self.par_vec
        db = 2 if double_buffer else 1
        if dag_info is not None:
            slots, n_in, n_out, aux_slabs = dag_info
            slots = [w for w in slots if w > 0]
            pt = 1
            has_aux = has_aux and aux_slabs > 0
        else:
            radii = tuple(stage_radii) if stage_radii else (self.rad,)
            lags = [-(-r // V) for r in radii]          # per program stage
            slots = [2 * lg + 1 for lg in lags]
            aux_slabs = sum(lags) * self.par_time + 1   # Lag_total + 1
            n_in = n_out = 1
            pt = self.par_time

        # Mosaic's minimum-tile sublane count is dtype-dependent: 8 for
        # 4-byte cells, 16 for bf16, 32 for 1-byte (packed tiles) — thin
        # bf16 buffers pad to 16 sublanes, so the V that stops wasting
        # sublanes doubles (mirrored by perf_model's sub_eff pricing)
        sublanes = max(8, 32 // max(1, cell_bytes))

        def pad8(n: int) -> int:
            return -(-n // sublanes) * sublanes

        def padl(n: int) -> int:
            return -(-n // LANE) * LANE

        if self.ndim == 1:
            # 1-D buffers: the stream rows are the lane dim
            win = pt * sum(padl(w * V) for w in slots)
            stream = db * padl(V) * n_in
            out = db * padl(V) * n_out
            aux = (padl(aux_slabs * V) + db * padl(V)) if has_aux else 0
        elif self.ndim == 2:
            # stream rows are the sublane dim of every buffer
            bx = self.bsize[0]
            win = pt * sum(pad8(w * V) for w in slots) * bx
            stream = db * pad8(V) * bx * n_in
            out = db * pad8(V) * self.csize[0] * n_out
            # aux = rolling window + its own DMA landing double buffer
            aux = (pad8(aux_slabs * V) * bx + db * pad8(V) * bx) \
                if has_aux else 0
        else:
            # the blocked y extent is the sublane dim; V planes stack above
            plane = pad8(self.bsize[0]) * self.bsize[1]
            win = pt * sum(slots) * V * plane
            stream = db * V * plane * n_in
            out = db * V * pad8(self.csize[0]) * self.csize[1] * n_out
            aux = (aux_slabs * V * plane + db * V * plane) if has_aux else 0
        return (win + stream + out + aux) * cell_bytes


def stream_extension(geom: BlockGeometry, bc) -> int:
    """Streaming-axis cells *per side* the Pallas path materializes for a
    periodic stream BC (0 otherwise): the rolling VMEM window cannot reach
    the far end of the stream, so the wrap is staged in HBM as ``size_halo``
    extra rows/planes, exact up to garbage creep and refreshed per
    super-step.  The single definition shared by the kernels' padding/DMA
    accounting (``kernels.ops``), the perf model (``predict``) and
    ``StencilPlan.traffic_report`` — these must never drift apart, or the
    model-vs-kernel traffic-accuracy ratio silently lies."""
    if bc is not None and bc.kinds[0] == "periodic":
        return geom.size_halo
    return 0


def extended_geometry(geom: BlockGeometry, bc) -> BlockGeometry:
    """``geom`` with the periodic stream extension applied — the extents the
    kernels actually stream (and the ones traffic/compute are billed on)."""
    ext = stream_extension(geom, bc)
    if not ext:
        return geom
    return dataclasses.replace(
        geom, dims=(geom.stream_dim + 2 * ext,) + geom.blocked_dims)


def tpu_tiles(ndim: int, cell_bytes: int = 4) -> Tuple[int, Tuple[int, ...]]:
    """``(stream_tile, align)``: the tiles the compiled kernels' HBM DMA
    windows must respect for a rank-``ndim`` grid of ``cell_bytes`` cells.

    The minor axis of every HBM array is tiled by 128 lanes and the
    second-minor one by the dtype's sublanes (:func:`sublanes_for`).  In 2D
    the stream axis is the second-minor one, so each ``(V, bsize)`` slab DMA
    needs ``V`` a sublane multiple; in 3D the blocked y axis is, so its
    halo and compute extent round to sublanes; in 1D the stream is the lane
    axis.  ``par_vec`` must be a multiple of ``stream_tile``; ``align`` is
    :attr:`BlockGeometry.align`."""
    sub = sublanes_for(cell_bytes)
    if ndim == 1:
        return LANE, ()
    if ndim == 2:
        return sub, (LANE,)
    return 1, (sub, LANE)


def bsize_feasible(rad: int, par_time: int, bsize: Sequence[int],
                   align: Sequence[int] = ()) -> bool:
    """True iff ``bsize`` yields a valid geometry after halo widening.

    Small grids at high ``par_time`` otherwise produce candidates that
    :class:`BlockGeometry` rejects: the compute block ``csize = bsize -
    2*pad`` collapses to <= 0, or (aligned) ``bsize`` is off the tile.  (No
    grid-extent check is needed: a block can never exceed the padded extent,
    since ``padded = bnum*csize + 2*pad >= bsize`` whenever csize > 0.)"""
    halo = rad * par_time
    align = tuple(align) or (1,) * len(bsize)
    return all(b > 2 * (-(-halo // a) * a) and b % a == 0
               for b, a in zip(bsize, align))


def _aligned_extents(dim: int, tile: int, cap: int,
                     splits: bool = False) -> list:
    """Compute extents for one aligned blocked dim: power-of-two tile
    multiples below the grid extent, then the extent rounded up to the
    tile (one block spanning the dim), all at most ``cap``.  ``splits``
    adds the extents that cut the dim into 2..16 near-equal blocks (a
    mesh shard's halo-extended block, just past a power of two, would
    otherwise need one nearly empty block more)."""
    full = -(-dim // tile) * tile
    out, c = [], tile
    while c < full and c <= cap:
        out.append(c)
        c *= 2
    if full <= cap:
        out.append(full)
    if splits:
        out = sorted(set(out) | {c for c in (-(-dim // (n * tile)) * tile
                                             for n in range(2, 17))
                                 if c <= cap})
    return out or [tile]


def choose_bsize_candidates(ndim: int, dims: Sequence[int], rad: int = 1,
                            par_time: int | None = None,
                            align: Sequence[int] = (),
                            splits: bool = False) -> list:
    """Power-of-two block extents, lane-aligned (paper §5.3 restrictions).

    When ``par_time`` is given, candidates infeasible for that temporal
    depth (see :func:`bsize_feasible`) are dropped; the result may be empty
    — callers autotuning a small grid must handle that, not crash.

    With ``align`` (the compiled kernels' tiles, :func:`tpu_tiles`) the
    sweep is over tile-multiple *compute* extents instead, each block
    ``csize + 2*pad`` wide, so every candidate's DMA windows start on a
    tile; this needs ``par_time``.  ``splits`` adds the compute extents
    that cut each aligned dim into near-equal blocks
    (:func:`_aligned_extents`)."""
    out = []
    if ndim == 1:
        return [()]                  # stream-only: nothing to block
    if align:
        halo = rad * (par_time or 1)
        caps = (1 << 14,) if ndim == 2 else (512, 1 << 12)
        per_dim = [[c + 2 * (-(-halo // a) * a)
                    for c in _aligned_extents(d, a, cap, splits)]
                   for d, a, cap in zip(dims[1:], align, caps)]
        return [tuple(bs) for bs in itertools.product(*per_dim)]
    if ndim == 2:
        b = LANE * 2
        while b <= max(2 * LANE, min(dims[1], 1 << 14)):
            out.append((b,))
            b *= 2
    else:
        b = 32
        while b <= max(32, min(dims[1], dims[2], 512)):
            out.append((b, b))   # square blocks for 3D (paper §5.3)
            b *= 2
    if par_time is not None:
        out = [bs for bs in out if bsize_feasible(rad, par_time, bs)]
    return out


def superstep_traffic_bytes(geom: BlockGeometry, num_read: int, num_write: int,
                            cell_bytes: int = 4) -> int:
    """External-memory bytes moved per super-step (paper Eq. 7/8 numerator).

    Reads skip fully out-of-bound columns (paper: "we avoid out-of-bound
    memory reads"): per blocked dim the traversed extent is ``trav`` but reads
    are clipped to the grid, so the read footprint per input buffer is
    ``stream_dim * prod(min(trav_d, ...)...)`` — we keep the paper's 2D form
    generalized: cells_read minus the out-of-bound band(s).
    """
    # Out-of-bound clip, generalizing paper Eq. (7) to any rank:
    read_cells = geom.stream_dim
    for n, b, c, d, p in zip(geom.bnum, geom.bsize, geom.csize,
                             geom.blocked_dims, geom.pad):
        # last block extends past the grid by (n*c + 2*pad - d) cells; those
        # reads are clipped (DMA clamp), so the per-dim read extent is:
        per_dim = n * b - max(0, (n * c + 2 * p) - d)
        read_cells *= per_dim
    return (read_cells * num_read + geom.cells_written * num_write) * cell_bytes
