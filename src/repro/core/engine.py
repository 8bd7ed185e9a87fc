"""Combined spatial + temporal blocking executor — pure JAX (the algorithm).

This is the paper's accelerator expressed as data-parallel JAX: overlapped
spatial blocks are materialized as a batch and updated ``par_time`` fused
time-steps by a vmapped per-block pipeline, then the compute blocks are
stitched back (out-of-bound compute is sliced off — the paper's "control only
the flow of writes").  The Pallas kernels in ``repro.kernels`` implement the
same math with explicit VMEM streaming; this module is their semantic spec.
Every problem runs as its stage DAG (:class:`repro.programs.DagSpec`; a
single stencil is the one-stage chain).

Boundary-condition handling across fused steps: see DESIGN.md §2.1 and
``core.boundary`` — local BCs (clamp/reflect/constant) are re-imposed on
out-of-grid positions before every stage reads (``_reclamp``), and the
streaming axis uses BC-mode padding re-derived per sub-step (exact,
because it is re-computed from current values).  Periodic axes need
no re-imposition at all: the super-step padding wraps (``mode="wrap"``), and
a wrapped halo is an exact translated copy that stays exact up to the
standard ``rad``-per-sub-step garbage creep — the same argument that makes
interior block seams correct.

PE forwarding (paper §3.2): when ``iters % par_time != 0`` the trailing
sub-steps forward data unchanged — implemented as a ``where(t < steps)``
select, exactly like unused PEs passing data down the chain.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import boundary, precision
from repro.core.blocking import BlockGeometry
from repro.core.stencils import Stencil


def _pad_blocked_dims(grid: jnp.ndarray, geom: BlockGeometry,
                      bc=None) -> jnp.ndarray:
    """BC-pad trailing (blocked) dims: halo on the left, halo + out-of-bound
    overhang on the right, so every block slice is in-bounds.  Periodic axes
    wrap (their only materialization — no per-sub-step re-imposition); other
    kinds pad per their rule and are refreshed by ``_reclamp`` each sub-step.
    """
    kinds = boundary.kinds_of(bc, geom.ndim)
    out = grid
    for i, (d, p, h) in enumerate(zip(geom.blocked_dims, geom.padded_dims,
                                      geom.pad)):
        out = boundary.pad_axis(out, i + 1, h, p - d - h, kinds[i + 1],
                                boundary.fill_of(bc))
    return out


def _block_index(geom: BlockGeometry, dim_i: int) -> jnp.ndarray:
    """(bnum, bsize) gather indices into the padded grid for blocked dim i."""
    c, b, n = geom.csize[dim_i], geom.bsize[dim_i], geom.bnum[dim_i]
    return (jnp.arange(n)[:, None] * c + jnp.arange(b)[None, :])


def extract_blocks(grid: jnp.ndarray, geom: BlockGeometry,
                   bc=None) -> jnp.ndarray:
    """-> (num_blocks..., stream_dim, *bsize) overlapped blocks, any rank
    (1D: the whole stream is the single 'block')."""
    gp = _pad_blocked_dims(grid, geom, bc)
    nb = geom.ndim - 1
    for i in range(nb):
        # blocked dim i sits at axis 1 + 2*i once earlier dims are expanded
        gp = jnp.take(gp, _block_index(geom, i), axis=1 + 2 * i)
    # (stream, bn0, bs0, bn1, bs1, ..) -> (bn0, bn1, .., stream, bs0, bs1, ..)
    perm = (tuple(1 + 2 * i for i in range(nb)) + (0,)
            + tuple(2 + 2 * i for i in range(nb)))
    return jnp.transpose(gp, perm)


def stitch_blocks(blocks: jnp.ndarray, geom: BlockGeometry) -> jnp.ndarray:
    """Write-back: keep each block's compute region, discard halos and
    out-of-bound columns (paper's masked writes)."""
    nb = geom.ndim - 1
    comp = blocks[(slice(None),) * (nb + 1)
                  + tuple(slice(h, h + c)
                          for h, c in zip(geom.pad, geom.csize))]
    # (bn0, .., stream, cs0, ..) -> (stream, bn0, cs0, bn1, cs1, ..)
    perm = (nb,) + tuple(x for i in range(nb) for x in (i, nb + 1 + i))
    out = jnp.transpose(comp, perm).reshape(
        (blocks.shape[nb],) + tuple(n * c
                                    for n, c in zip(geom.bnum, geom.csize)))
    return out[(slice(None),) + tuple(slice(0, d) for d in geom.blocked_dims)]


def _mask_fill(arr: jnp.ndarray, mask1d: jnp.ndarray, axis: int,
               value: float) -> jnp.ndarray:
    """Overwrite positions selected by a 1-D mask along ``axis`` with
    ``value`` (the 'constant' BC's re-imposition)."""
    shape = [1] * arr.ndim
    shape[axis] = mask1d.shape[0]
    return jnp.where(mask1d.reshape(shape), jnp.asarray(value, arr.dtype),
                     arr)


def _reclamp(block: jnp.ndarray, bidx, geom: BlockGeometry,
             bc=None) -> jnp.ndarray:
    """Re-impose the (local) BC: overwrite out-of-grid positions per each
    axis' rule — clamp/reflect gather from the mapped in-grid coordinate,
    constant fills the scalar.  No-op for interior blocks; periodic axes are
    skipped entirely (their wrap-padded halos stay exact up to garbage
    creep — see ``core.boundary``)."""
    kinds = boundary.kinds_of(bc, geom.ndim)
    value = boundary.fill_of(bc)
    for i, (dim, b, c, h) in enumerate(zip(geom.blocked_dims, geom.bsize,
                                           geom.csize, geom.pad)):
        kind = kinds[i + 1]
        if kind == "periodic":
            continue
        axis = block.ndim - (geom.ndim - 1) + i
        gx = bidx[i] * c + jnp.arange(b) - h
        if kind == "constant":
            block = _mask_fill(block, boundary.out_of_range(gx, 0, dim - 1),
                               axis, value)
        else:
            jc = boundary.map_index(gx, 0, dim - 1, kind) + h - bidx[i] * c
            block = jnp.take(block, jnp.clip(jc, 0, b - 1), axis=axis)
    return block


def _block_getter(block: jnp.ndarray, r: int, bc=None):
    """Neighbor getter on a block: exact BC-mode pad on the streaming axis
    (the block carries the full stream extent, so wrap/reflect/constant
    padding IS the boundary condition there), garbage-tolerant edge-pad on
    blocked axes (halo shrinkage covers it)."""
    p = boundary.pad_axis(block, 0, r, r, boundary.kinds_of(bc, 1)[0],
                          boundary.fill_of(bc))
    p = jnp.pad(p, [(0, 0)] + [(r, r)] * (block.ndim - 1), mode="edge")

    def get(off):
        idx = tuple(slice(r + o, r + o + n) for o, n in zip(off, block.shape))
        return p[idx]

    return get


def _block_substep(stencil: Stencil, blocks, coeffs: dict, aux_block,
                   bc=None) -> jnp.ndarray:
    """One (possibly multi-input) stage application on pre-reclamped input
    blocks: each input is read under this stage's BC; ``arity > 1`` stencils
    receive a tuple of getters."""
    r = stencil.radius
    gets = [_block_getter(b, r, bc) for b in blocks]
    return precision.apply_stage(
        stencil, tuple(gets) if stencil.arity > 1 else gets[0],
        coeffs, aux_block, blocks[0].dtype)


@partial(jax.jit, static_argnames=("dag", "geom"))
def blocked_superstep(dag, geom: BlockGeometry, state: jnp.ndarray,
                      stage_coeffs, steps,
                      aux: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply ``steps`` (<= par_time) fused *program iterations* of a stage
    DAG (:class:`repro.programs.DagSpec`) via one HBM round-trip worth of
    overlapped blocks.

    ``state`` is the plain grid for single-field programs, else the
    ``(F, *shape)`` field stack — every field is blocked identically and
    travels through the same vmapped per-block pipeline.  Each iteration
    evaluates the stages in topological order (every input re-reclamped
    under the *consuming* stage's BC), then updates all fields
    simultaneously; partial super-steps forward each field's previous value
    (PE forwarding, generalized per field)."""
    F = dag.n_fields
    fields = [state[k] for k in range(F)] if F > 1 else [state]
    bc0 = dag.stages[0][1]
    has_aux = any(st.has_aux for st, _, _ in dag.stages)
    fblocks = tuple(extract_blocks(g, geom, bc0) for g in fields)
    aux_blocks = extract_blocks(aux, geom, bc0) if has_aux else None
    nb = geom.ndim - 1

    def one_block(blks, aux_block, *bidx):
        def substep(t, cur):
            vals: list = [None] * len(dag.stages)
            for si in dag.topo:
                st, bc_s, refs = dag.stages[si]
                ins = [cur[~r] if r < 0 else vals[r] for r in refs]
                recs = [_reclamp(x, bidx, geom, bc_s) for x in ins]
                vals[si] = _block_substep(
                    st, recs, stage_coeffs[si],
                    aux_block if st.has_aux else None, bc_s)
            out = []
            for k, u in enumerate(dag.updates):
                if u == ~k:                  # field carried unchanged
                    out.append(cur[k])
                    continue
                tgt = vals[u] if u >= 0 else cur[~u]
                out.append(jnp.where(t < steps, tgt, cur[k]))
            return tuple(out)
        return jax.lax.fori_loop(0, geom.par_time, substep, blks)

    aux_ax = 0 if aux_blocks is not None else None
    fn = one_block
    for i in range(nb - 1, -1, -1):
        fn = jax.vmap(fn, in_axes=(0, aux_ax)
                      + tuple(0 if j == i else None for j in range(nb)))
    upd = fn(fblocks, aux_blocks,
             *(jnp.arange(geom.bnum[j]) for j in range(nb)))
    outs = [stitch_blocks(u, geom) for u in upd]
    return jnp.stack(outs) if F > 1 else outs[0]


def superstep_loop(dag, geom: BlockGeometry, state: jnp.ndarray,
                   stage_coeffs, iters,
                   aux: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused whole-run driver: ``ceil(iters/par_time)`` super-steps of the
    stage DAG as one traced loop (paper Eq. 8 numerator), so an enclosing
    ``jit`` lowers the entire iteration count to a single dispatch.

    ``iters`` may be a *traced* scalar: the trip count is computed inside the
    trace and the loop lowers to a dynamic ``while``, so one compiled
    executable serves every iteration count — a serving process never
    re-traces because a request asked for a different ``iters``.  Trailing
    iterations of a partial final super-step are PE-forwarded (paper §3.2)
    exactly as in :func:`blocked_superstep`.
    """
    par_time = geom.par_time
    n_super = (iters + par_time - 1) // par_time

    def body(s, g):
        steps = jnp.minimum(par_time, iters - s * par_time)
        return blocked_superstep(dag, geom, g, stage_coeffs, steps, aux)

    return jax.lax.fori_loop(0, n_super, body, state)
