"""Pure-jnp oracle: unblocked iterated stencil (ground truth for everything).

No spatial or temporal blocking — each time-step reads the whole grid and
writes the whole grid, with the boundary condition re-imposed every step via
per-axis padding.  The default BC is the paper's clamp (edge replication,
§5.1); any :class:`~repro.core.boundary.BoundaryCondition` is honored by
padding each axis with that axis' kind, which *defines* the mixed-BC corner
semantics every other backend is conformance-tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import boundary, precision
from repro.core.stencils import Stencil
from repro.programs import chain_dag


def _padded_getter(grid: jnp.ndarray, r: int, bc=None):
    """Neighbor getter over ``grid`` BC-padded by ``r`` on every axis."""
    if bc is None or bc.is_clamp:
        p = jnp.pad(grid, r, mode="edge")
    else:
        p = grid
        for ax, kind in enumerate(bc.kinds):
            p = boundary.pad_axis(p, ax, r, r, kind, bc.value)

    def get(off):
        idx = tuple(slice(r + o, r + o + n) for o, n in zip(off, grid.shape))
        return p[idx]

    return get


def oracle_dag_step(dag, state: jnp.ndarray, stage_coeffs,
                    aux: jnp.ndarray | None = None) -> jnp.ndarray:
    """One *program iteration* of a DAG (:class:`repro.programs.DagSpec`):
    stages evaluated in topological order — each input (field or earlier
    stage) read under the consuming stage's own BC — then every field
    updated simultaneously.  ``state`` is the plain grid for single-field
    programs, else the ``(F, *shape)`` field stack.  This is the sequential
    semantics every fused DAG backend is conformance-tested against."""
    F = dag.n_fields
    fields = [state[k] for k in range(F)] if F > 1 else [state]
    vals: list = [None] * len(dag.stages)
    for si in dag.topo:
        st, bc_s, refs = dag.stages[si]
        ins = [vals[r] if r >= 0 else fields[~r] for r in refs]
        gets = [_padded_getter(x, st.radius, bc_s) for x in ins]
        vals[si] = precision.apply_stage(
            st, tuple(gets) if st.arity > 1 else gets[0],
            stage_coeffs[si], aux if st.has_aux else None, state.dtype)
    new = [vals[u] if u >= 0 else fields[~u] for u in dag.updates]
    return jnp.stack(new) if F > 1 else new[0]


def oracle_dag_run(dag, state: jnp.ndarray, stage_coeffs, iters: int,
                   aux: jnp.ndarray | None = None) -> jnp.ndarray:
    """``iters`` program iterations of the stage DAG."""
    def body(_, s):
        return oracle_dag_step(dag, s, stage_coeffs, aux)
    return jax.lax.fori_loop(0, iters, body, state)


def oracle_run(stencil: Stencil, grid: jnp.ndarray, coeffs: dict,
               iters: int, aux: jnp.ndarray | None = None, *,
               bc=None) -> jnp.ndarray:
    """``iters`` time-steps of a single stencil (the one-stage DAG
    :func:`oracle_dag_run`)."""
    return oracle_dag_run(chain_dag(((stencil, bc),)), grid, (coeffs,),
                          iters, aux)
