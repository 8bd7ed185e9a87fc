"""Pallas dispatch + legacy entry-point shim.

The public API lives in ``repro.api`` (``StencilProblem`` -> ``plan()`` ->
``StencilPlan``); this module keeps the Pallas super-step driver that the
``pallas``/``pallas_interpret`` backends compile to, the exact DMA-traffic
accounting, and ``stencil_run`` — the deprecated pre-``plan()`` entry point,
now a thin shim.

The Pallas path mirrors the engine's super-step loop: edge-pad the blocked
dims, launch one kernel per super-step (``ceil(iters/par_time)``), slice the
compute columns back out.  ``iters % par_time`` is handled in-kernel by PE
forwarding, exactly like the paper's unused PEs.
"""
from __future__ import annotations

import math
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import boundary
from repro.core.blocking import BlockGeometry, stream_extension as _stream_ext
from repro.core.stencils import Stencil
from repro.kernels.builder import superstep_chain, superstep_dag


def pack_coeffs(stencil: Stencil, coeffs: dict) -> jnp.ndarray:
    return jnp.stack([jnp.asarray(coeffs[n], jnp.float32)
                      for n in stencil.coeff_names])


def pack_program_coeffs(stages, stage_coeffs) -> jnp.ndarray:
    """Concatenate per-stage coefficient vectors in *authored* stage order —
    the layout :func:`repro.programs.unroll_dag` assigns ``coeff_lo``
    offsets into.  ``stages`` is the static ``((stencil, bc), ...)`` tuple,
    ``stage_coeffs`` one coefficient dict per stage."""
    return jnp.concatenate([pack_coeffs(st, c)
                            for (st, _), c in zip(stages, stage_coeffs)])


def pack_dag_coeffs(dag, stage_coeffs) -> jnp.ndarray:
    """DAG variant of :func:`pack_program_coeffs`: authored stage order of a
    :class:`repro.programs.DagSpec` (evaluation order is the DAG's ``topo``
    permutation, but coefficient packing stays positional)."""
    return jnp.concatenate([pack_coeffs(st, c)
                            for (st, _, _), c in zip(dag.stages,
                                                     stage_coeffs)])


def _pad_blocked(grid: jnp.ndarray, geom: BlockGeometry,
                 bc=None) -> jnp.ndarray:
    """BC-pad the blocked (trailing) dims — halo left, halo + out-of-bound
    overhang right — plus the periodic stream extension (``_stream_ext``),
    plus edge rows padding the stream extent up to a ``par_vec`` multiple
    (the kernels tick in whole ``(V, ...)`` slabs; pad rows are computed and
    discarded but never tapped — stream reads are BC-mapped into the true
    domain first).  Leading batch axes (in front of the streaming axis) are
    left untouched.
    """
    kinds = boundary.kinds_of(bc, geom.ndim)
    fill = boundary.fill_of(bc)
    lead = grid.ndim - (geom.ndim - 1)       # batch axes + streaming axis
    out = grid
    for i, (d, p, h) in enumerate(zip(geom.blocked_dims, geom.padded_dims,
                                      geom.pad)):
        out = boundary.pad_axis(out, lead + i, h, p - d - h, kinds[i + 1],
                                fill)
    ext = _stream_ext(geom, bc)
    if ext:
        out = boundary.pad_axis(out, lead - 1, ext, ext, "periodic")
    dom = geom.stream_dim + 2 * ext
    vpad = geom.stream_slabs(dom) * geom.par_vec - dom
    if vpad:
        out = boundary.pad_axis(out, lead - 1, 0, vpad, "clamp")
    return out


def _slice_blocked(gp: jnp.ndarray, geom: BlockGeometry,
                   bc=None) -> jnp.ndarray:
    ext = _stream_ext(geom, bc)
    idx = ((Ellipsis, slice(ext, ext + geom.stream_dim))
           + tuple(slice(h, h + d) for h, d in zip(geom.pad, geom.blocked_dims)))
    return gp[idx]


def _reclamp_padded(gp: jnp.ndarray, geom: BlockGeometry,
                    bc=None, exchange=None) -> jnp.ndarray:
    """Refresh the halo + out-of-bound columns of a padded grid from its real
    columns, per each axis' BC rule.  Bit-identical to
    ``_pad_blocked(_slice_blocked(gp))``, but keeps the array in the padded
    layout so a fused super-step loop can carry it — and an enclosing ``jit``
    can donate it — without leaving the padded representation.

    Only the padding strips are written, in place (``_refresh_strips``): the
    periodic stream extension, then each blocked axis in order, so a later
    axis' strips carry the corners an earlier one refreshed.  The real cells
    are never read back and rewritten, so the refresh moves bytes in
    proportion to the strips, not to the array.  The ``par_vec`` pad rows
    beyond the stream extension are left as they are (their values are never
    tapped, only re-computed).

    Axes whose pad is zero are skipped outright: there is nothing to write
    and, for the constant BC, a ghost mask there would wrongly treat real
    edge columns as ghost positions (the zero-pad seam case — e.g. a
    stream-only stencil embedded in a higher-rank grid).

    On a shard of a mesh, ``exchange(gp, axis, i)`` (``core/distributed.py``)
    first refreshes grid axis ``i``'s halos from the neighbours, before that
    axis' own strips, so the strips and the corners follow from them."""
    kinds = boundary.kinds_of(bc, geom.ndim)
    fill = boundary.fill_of(bc)
    ext = _stream_ext(geom, bc)
    axis = gp.ndim - geom.ndim                # the streaming axis
    if exchange is not None:
        gp = exchange(gp, axis, 0)
    if ext:
        d = geom.stream_dim
        gp = _refresh_strips(gp, axis, ext, d, d + 2 * ext, "periodic", fill)
    for i, (d, p, h) in enumerate(zip(geom.blocked_dims, geom.padded_dims,
                                      geom.pad)):
        if exchange is not None:
            gp = exchange(gp, axis + 1 + i, i + 1)
        if p != d:
            gp = _refresh_strips(gp, axis + 1 + i, h, d, p, kinds[i + 1],
                                 fill)
    return gp


def _refresh_strips(gp: jnp.ndarray, axis: int, h: int, d: int, p: int,
                    kind: str, fill: float) -> jnp.ndarray:
    """Overwrite positions ``[0, h)`` and ``[h + d, p)`` of ``axis`` with the
    ``kind`` ghost values of the real cells ``[h, h + d)``, one
    ``dynamic_update_slice`` per strip.  Each strip is built from real cells
    only: the fill value for ``constant``, else the cells
    ``boundary.map_index`` names — one edge cell broadcast, a slice or a
    reversed slice where they are contiguous (always so for ``clamp``, and
    for ``periodic`` / ``reflect`` strips no wider than the domain), else a
    strip-sized ``take``."""
    for lo, hi in ((0, h), (h + d, p)):
        if hi == lo:
            continue
        shape = gp.shape[:axis] + (hi - lo,) + gp.shape[axis + 1:]
        if kind == "constant":
            strip = jnp.full(shape, fill, gp.dtype)
        else:
            with jax.ensure_compile_time_eval():
                src = np.asarray(boundary.map_index(
                    jnp.arange(lo - h, hi - h), 0, d - 1, kind)) + h
            step = np.diff(src)
            first, last = int(src[0]), int(src[-1])
            if (src == first).all():
                strip = _broadcast_edge(gp, axis, first, hi - lo)
            elif (step == 1).all():
                strip = lax.slice_in_dim(gp, first, last + 1, axis=axis)
            elif (step == -1).all():
                strip = lax.rev(lax.slice_in_dim(gp, last, first + 1,
                                                 axis=axis), (axis,))
            else:
                strip = jnp.take(gp, src, axis=axis, mode="clip")
        gp = lax.dynamic_update_slice_in_dim(gp, strip, lo, axis)
    return gp


def _broadcast_edge(gp: jnp.ndarray, axis: int, at: int,
                    width: int) -> jnp.ndarray:
    """``width`` copies of position ``at`` of ``axis``.  On the minor axis
    the edge is sliced from a 2D view (all major axes merged, a bitcast):
    XLA then reads it into one compact vector, where a slice of the
    higher-rank array lands in a lane-padded buffer that needs a relayout
    copy before the broadcast."""
    if axis == gp.ndim - 1:
        flat = gp.reshape(-1, gp.shape[-1])
        edge = lax.slice_in_dim(flat, at, at + 1, axis=1)
        return jnp.broadcast_to(edge, (flat.shape[0], width)) \
            .reshape(gp.shape[:-1] + (width,))
    edge = lax.slice_in_dim(gp, at, at + 1, axis=axis)
    return jnp.broadcast_to(edge, gp.shape[:axis] + (width,)
                            + gp.shape[axis + 1:])


def _per_member(kernel, gp: jnp.ndarray, aux_p, aux_rank: int):
    """``kernel(g, aux_p)`` over each member ``g`` of ``gp``'s leading batch
    axis (an ``aux_p`` of more than ``aux_rank`` axes is per member too).
    The batch is mapped sequentially (``lax.map``): a ``vmap`` over the
    manual-DMA kernel mis-addresses its per-block DMAs."""
    if aux_p is not None and aux_p.ndim > aux_rank:
        return jax.lax.map(lambda ga: kernel(*ga), (gp, aux_p))
    return jax.lax.map(lambda g: kernel(g, aux_p), gp)


def _fused_loop(kernel, geom: BlockGeometry, gp: jnp.ndarray, iters,
                aux_p, rank: int, refresh, unpad) -> jnp.ndarray:
    """``ceil(iters / par_time)`` super-steps of ``kernel(g, steps, aux_p,
    dst)`` over the padded state ``gp``, ``refresh`` rewriting the padding
    strips after each, ``unpad`` taking the real cells out at the end.

    The carry is two padded buffers the kernel alternates between: each
    super-step reads one and writes into the other (``dst``, aliased to the
    kernel's output), and ``refresh`` then writes that one's strips in
    place.  A kernel cannot write into the buffer it reads (its blocks read
    their neighbours' old cells as halo), and with one buffer XLA copies the
    kernel's fresh output back into the carry before every kernel.  The
    kernel rewrites every cell but the padding strips, which ``refresh``
    rewrites (``kernels/builder._superstep_dag_impl``), so nothing a buffer
    held two super-steps back survives into a result.

    The carry's positions stay fixed (a swap would bring the copy back), so
    super-steps run in pairs, A -> B then B -> A.  The first super-step's own
    output, a fresh array, is the second buffer; ``gp`` is the first; a
    trailing odd super-step and ``iters == 0`` are branches of a
    ``lax.cond``, so ``iters`` stays traced.  A ``gp`` with a leading batch
    axis (more than ``rank`` axes) keeps one buffer: the kernel runs per
    member (:func:`_per_member`), and ``lax.map`` has no buffer for it to
    write into."""
    par_time = geom.par_time
    n_super = (iters + par_time - 1) // par_time

    def step(s, src, dst=None):
        steps = jnp.minimum(par_time, iters - s * par_time)
        with jax.named_scope("stencil.superstep"):
            if src.ndim > rank:
                out = _per_member(lambda x, a: kernel(x, steps, a), src,
                                  aux_p, geom.ndim)
            else:
                out = kernel(src, steps, aux_p, dst)
        with jax.named_scope("stencil.halo_refresh"):
            return refresh(out)

    def unpadded(g):
        with jax.named_scope("stencil.unpad"):
            return unpad(g)

    if gp.ndim > rank:
        return unpadded(jax.lax.fori_loop(0, n_super, step, gp))

    def pair(k, ab):
        a, b = ab
        a = step(2 * k + 1, b, a)
        return a, step(2 * k + 2, a, b)

    def run():
        b = step(0, gp)
        a, b = jax.lax.fori_loop(0, (n_super - 1) // 2, pair, (gp, b))
        return jax.lax.cond(n_super % 2 == 0,
                            lambda: unpadded(step(n_super - 1, b, a)),
                            lambda: unpadded(b))

    return jax.lax.cond(n_super > 0, run, lambda: unpadded(gp))


def fused_chain_loop(stages, geom: BlockGeometry, gp: jnp.ndarray,
                     coeffs_packed: jnp.ndarray, iters,
                     aux_p: jnp.ndarray | None, interpret: bool,
                     block_parallel: bool = False, *, refresh=None,
                     unpad=None) -> jnp.ndarray:
    """The throughput subsystem's fused driver: the whole ``iters`` loop of a
    stage chain over the *pre-padded* grid ``gp``, returning the unpadded
    result.  ``stages`` is the static ``((stencil, bc), ...)`` tuple of the
    program (S=1 recovers the classic single-operator loop).

    Why this shape:
      * ``iters`` may be a traced scalar — the super-step trip count is
        computed in-trace and the loop lowers to a dynamic ``while``, so one
        compiled executable serves every iteration count (no per-``iters``
        re-trace in a serving loop).
      * The carry stays in the padded layout, in two buffers the kernel
        alternates between (:func:`_fused_loop`): each kernel writes into
        the buffer the previous one read, and ``refresh`` then writes only
        that buffer's padding strips, in place, instead of a slice+re-pad
        round-trip or any pass over the whole array.  ``gp`` is the first
        buffer, so a caller that jits this function with ``donate_argnums``
        on ``gp`` offers XLA the padded input for it — ``gp`` is an
        intermediate the backend owns, so donation never invalidates a
        caller-visible array.

    ``refresh`` and ``unpad`` default to one chip's layout:
    ``_reclamp_padded`` (halo, overhang and periodic stream extension under
    stage 0's BC: the BC the chain's first entry reads the carry under —
    periodicity is uniform across stages by construction, and each later
    entry re-imposes its own BC in-kernel) and ``_slice_blocked``.  A shard
    of a mesh passes its own (``core/distributed.py``): the halo exchange
    with its neighbours plus the strips of its physical edges.  A ``gp``
    with a leading batch axis runs the kernel per member between two
    refreshes (:func:`_per_member`).
    """
    bc0 = stages[0][1]
    refresh = refresh or partial(_reclamp_padded, geom=geom, bc=bc0)
    unpad = unpad or partial(_slice_blocked, geom=geom, bc=bc0)

    def kernel(g, steps, a, dst=None):
        return superstep_chain(stages, geom, g, coeffs_packed, steps, a,
                               interpret=interpret,
                               block_parallel=block_parallel, dst=dst)

    return _fused_loop(kernel, geom, gp, iters, aux_p, geom.ndim, refresh,
                       unpad)


def fused_dag_loop(dag, geom: BlockGeometry, gp: jnp.ndarray,
                   coeffs_packed: jnp.ndarray, iters,
                   aux_p: jnp.ndarray | None, interpret: bool,
                   block_parallel: bool = False, *, refresh=None,
                   unpad=None) -> jnp.ndarray:
    """DAG analogue of :func:`fused_chain_loop`: the whole ``iters`` loop of
    a stage DAG (:class:`repro.programs.DagSpec`) over the *pre-padded*
    state ``gp`` (``(ns, *padded)`` single-field, ``(F, ns, *padded)``
    multi-field — every field padded identically), returning the unpadded
    result.  The carry stays padded, in two buffers the kernel alternates
    between (:func:`_fused_loop`; every field of the buffer it writes is
    rewritten but the padding strips); by default the padding strips of all
    fields are rewritten in place by one ``_reclamp_padded`` per super-step
    under stage 0's BC (periodicity is uniform by construction; each entry
    re-imposes its own BC in-kernel); ``refresh``, ``unpad`` and a leading
    batch axis as in :func:`fused_chain_loop`."""
    bc0 = dag.stages[0][1]
    refresh = refresh or partial(_reclamp_padded, geom=geom, bc=bc0)
    unpad = unpad or partial(_slice_blocked, geom=geom, bc=bc0)

    def kernel(g, steps, a, dst=None):
        return superstep_dag(dag, geom, g, coeffs_packed, steps, a,
                             interpret=interpret,
                             block_parallel=block_parallel, dst=dst)

    rank = geom.ndim + (dag.n_fields > 1)
    return _fused_loop(kernel, geom, gp, iters, aux_p, rank, refresh, unpad)


def fused_superstep_loop(stencil: Stencil, geom: BlockGeometry,
                         gp: jnp.ndarray, coeffs_packed: jnp.ndarray, iters,
                         aux_p: jnp.ndarray | None, interpret: bool,
                         bc=None, block_parallel: bool = False) -> jnp.ndarray:
    """Single-operator special case of :func:`fused_chain_loop` (legacy
    entry point, semantics unchanged)."""
    return fused_chain_loop(((stencil, bc),), geom, gp, coeffs_packed, iters,
                            aux_p, interpret, block_parallel)


@partial(jax.jit, static_argnames=("stencil", "geom", "interpret", "bc",
                                   "block_parallel"))
def run_pallas(stencil: Stencil, geom: BlockGeometry, grid: jnp.ndarray,
               coeffs_packed: jnp.ndarray, iters,
               aux: jnp.ndarray | None, interpret: bool,
               bc=None, block_parallel: bool = False) -> jnp.ndarray:
    """``iters`` time-steps via the streaming Pallas kernels.

    ``iters`` is dynamic (traced): one executable per (stencil, geom, bc)
    serves all iteration counts — see :func:`fused_superstep_loop`."""
    with jax.named_scope("stencil.pad"):
        aux_p = _pad_blocked(aux, geom, bc) if aux is not None else None
        gp = _pad_blocked(grid, geom, bc)
    return fused_superstep_loop(stencil, geom, gp, coeffs_packed, iters,
                                aux_p, interpret, bc, block_parallel)


@partial(jax.jit, static_argnames=("stages", "geom", "interpret",
                                   "block_parallel"))
def run_pallas_chain(stages, geom: BlockGeometry, grid: jnp.ndarray,
                     coeffs_packed: jnp.ndarray, iters,
                     aux: jnp.ndarray | None, interpret: bool,
                     block_parallel: bool = False) -> jnp.ndarray:
    """``iters`` program iterations via the fused streaming chain kernel.
    ``stages`` is the static ``((stencil, bc), ...)`` tuple; padding uses
    stage 0's BC (see :func:`fused_chain_loop`)."""
    bc0 = stages[0][1]
    with jax.named_scope("stencil.pad"):
        aux_p = _pad_blocked(aux, geom, bc0) if aux is not None else None
        gp = _pad_blocked(grid, geom, bc0)
    return fused_chain_loop(stages, geom, gp, coeffs_packed, iters, aux_p,
                            interpret, block_parallel)


@partial(jax.jit, static_argnames=("dag", "geom", "interpret",
                                   "block_parallel"))
def run_pallas_dag(dag, geom: BlockGeometry, state: jnp.ndarray,
                   coeffs_packed: jnp.ndarray, iters,
                   aux: jnp.ndarray | None, interpret: bool,
                   block_parallel: bool = False) -> jnp.ndarray:
    """``iters`` program iterations via the fused streaming DAG kernel.
    ``state`` is the plain grid for single-field programs, else the
    ``(F, *shape)`` field stack (the leading field axis rides through
    ``_pad_blocked`` like a batch axis); padding uses stage 0's BC."""
    bc0 = dag.stages[0][1]
    with jax.named_scope("stencil.pad"):
        aux_p = _pad_blocked(aux, geom, bc0) if aux is not None else None
        gp = _pad_blocked(state, geom, bc0)
    return fused_dag_loop(dag, geom, gp, coeffs_packed, iters, aux_p,
                          interpret, block_parallel)


def dma_traffic_bytes(stencil: Stencil, geom: BlockGeometry,
                      cell_bytes: int = 4, bc=None) -> int:
    """Exact HBM traffic of one Pallas super-step, from its DMA schedule.

    The kernels' HBM accesses are fully explicit (manual async copies), so
    traffic is countable without hardware:
      * input: every block streams ``stream`` rows (2D) / planes (3D) of
        extent ``prod(bsize)`` — the pipeline runs ``stream + size_halo``
        ticks to drain the PE chain, but the trailing ticks fetch nothing
        (the prefetch stops at the last real row; out-of-grid reads are
        clamped window reads, not DMAs); halo columns overlap between
        adjacent blocks.
      * aux (Hotspot power): same stream per block.
      * output: every block writes ``stream`` rows/planes of the compute
        extent ``prod(csize)`` (out-of-bound columns land in padding and
        are counted — the wrapper slices them off in HBM).

    This is what the perf model's Eq. 7/8 idealizes; the ratio
    ``superstep_traffic_bytes / dma_traffic_bytes`` is the model's traffic
    accuracy for the kernel implementation.

    ``par_vec`` rounds the streamed extent up to whole ``(V, ...)`` slabs
    (the wrapper's stream-axis pad): a non-divisible stream bills the pad
    rows its DMAs actually move.
    """
    dom = geom.stream_dim + 2 * _stream_ext(geom, bc)
    stream = geom.stream_slabs(dom) * geom.par_vec
    block_in = math.prod(geom.bsize)
    block_out = math.prod(geom.csize)
    n_blocks = geom.num_blocks
    # num_read/num_write count the external streams (fields + aux / fields):
    # 1 + aux for every plain stencil and linear chain, F + aux / F for a
    # multi-field DAG — each field streams in and drains out per block
    reads = n_blocks * stream * block_in * stencil.num_read
    writes = n_blocks * stream * block_out * stencil.num_write
    return (reads + writes) * cell_bytes


def stencil_run(stencil: Stencil, grid: jnp.ndarray, coeffs: dict, iters: int,
                par_time: int, bsize, aux: jnp.ndarray | None = None,
                backend: str = "pallas_interpret") -> jnp.ndarray:
    """Deprecated: use ``repro.api.plan`` instead.

    Thin shim over ``plan(StencilProblem(...), RunConfig(...)).run(...)``,
    kept for old call sites.  Results are identical to the plan path.
    """
    warnings.warn(
        "stencil_run is deprecated; use repro.api.plan(StencilProblem(...), "
        "RunConfig(backend=...)).run(grid, iters, coeffs, aux=aux)",
        DeprecationWarning, stacklevel=2)
    from repro.api import RunConfig, StencilProblem, plan
    grid = jnp.asarray(grid)
    problem = StencilProblem(stencil, tuple(grid.shape),
                             dtype=grid.dtype.name)   # legacy: dtype-generic
    config = RunConfig(backend=backend, par_time=par_time, bsize=bsize)
    return plan(problem, config).run(grid, iters, coeffs, aux=aux)
