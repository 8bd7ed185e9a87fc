"""Multi-device spatial distribution of the blocked stencil engine.

This implements the paper's stated future work (§8: "spatial distribution of
large stencils on multiple FPGAs") on a TPU mesh: the grid is domain-
decomposed over mesh axes via ``shard_map``; each device runs the *same*
combined spatial+temporal blocking locally; halos of width
``rad * par_time`` are exchanged with ``lax.ppermute`` **once per
super-step** — temporal blocking divides the number of exchanges (and thus
ICI latency events) by ``par_time``. That communication aggregation is the
distributed-optimization payoff of the paper's technique.

Key correctness points:
  * Received halos make a shard's local run exact up to ``rad*par_time``
    cells from its extended edge — exactly the overlapped-blocking argument
    one level up; the polluted rim is discarded at write-back.
  * Shards at true grid boundaries pass ``bounds`` to the engine so the
    boundary condition is re-imposed at the *global* edge (not the shard
    edge) every fused sub-step (DESIGN.md §2.1, ``core.boundary``): clamp/
    reflect gather from the mapped in-shard coordinate, constant fills the
    scalar.  Edge shards receive zero-filled halos from ``ppermute``
    (non-wrapping) — harmless, as bounds re-imposition makes those
    positions unread.
  * A **periodic** axis has no physical edge: its halo exchange runs on a
    wrap-around ``ppermute`` ring (the last shard's trailing strip is the
    first shard's leading halo and vice versa), every shard's bounds span
    the whole extended shard, and the local engine treats the axis as an
    internal seam (no re-imposition; the wrapped halo is an exact
    translated copy covered by garbage creep).
  * Elasticity: the decomposition is a pure function of (mesh, grid shape);
    restarting on a different mesh re-shards automatically.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.blocking import BlockGeometry
from repro.core.engine import (blocked_superstep, blocked_superstep_chain,
                               blocked_superstep_dag)
from repro.core.stencils import Stencil
from repro.programs import DagSpec, dag_radius
from repro.resilience.faults import fault_point, register_point

#: fires when a halo exchange is *built* — i.e. at trace time, once per
#: compiled program per sharded axis, NOT once per super-step (the exchange
#: itself runs inside jit).  An injected raise here models a mesh/collective
#: setup failure, which is how ICI faults actually surface to the host.
FP_EXCHANGE = register_point(
    "distributed.exchange", "at halo-exchange build (trace) time — models a "
    "collective/mesh setup failure")


def _linear_index(axis_names: Tuple[str, ...]) -> jnp.ndarray:
    """Linearized shard index over (possibly several) mesh axes."""
    idx = jax.lax.axis_index(axis_names[0])
    for name in axis_names[1:]:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def _axis_total(axis_names: Tuple[str, ...]) -> int:
    n = 1
    for name in axis_names:
        n *= jax.lax.axis_size(name)
    return n


def _exchange_halo(x: jnp.ndarray, grid_axis: int,
                   axis_names: Tuple[str, ...], h: int,
                   periodic: bool = False) -> jnp.ndarray:
    """Extend ``x`` with h-wide neighbor strips along ``grid_axis``.

    Neighbor ``i-1``'s trailing strip becomes our leading halo and vice
    versa.  Non-periodic: the outermost shards receive zeros (cleaned up by
    the bounds re-imposition).  Periodic: the ring wraps around the mesh —
    shard 0's leading halo is shard n-1's trailing strip, which IS the
    global periodic neighbor (no true-edge handling left to do locally).
    """
    fault_point(FP_EXCHANGE, {"axis": grid_axis, "halo": h,
                              "periodic": periodic})
    n = _axis_total(axis_names)
    lead = jax.lax.slice_in_dim(x, 0, h, axis=grid_axis)
    trail = jax.lax.slice_in_dim(x, x.shape[grid_axis] - h,
                                 x.shape[grid_axis], axis=grid_axis)
    perm_lo = [(j, (j + 1) % n) for j in range(n)] if periodic else \
        [(j, j + 1) for j in range(n - 1)]
    perm_hi = [(j, (j - 1) % n) for j in range(n)] if periodic else \
        [(j, j - 1) for j in range(1, n)]
    halo_lo = jax.lax.ppermute(trail, axis_names, perm_lo)
    halo_hi = jax.lax.ppermute(lead, axis_names, perm_hi)
    return jnp.concatenate([halo_lo, x, halo_hi], axis=grid_axis)


def partition_spec(axis_map) -> P:
    return P(*[names if names else None for names in axis_map])


def shard_extents(dims, axis_map, mesh: Mesh):
    """Per-shard local extents; raises unless evenly divisible (the launcher
    pads the grid to make it so)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for ax, (d, names) in enumerate(zip(dims, axis_map)):
        n = math.prod(sizes[a] for a in names) if names else 1
        if d % n:
            raise ValueError(f"grid axis {ax} (extent {d}) not divisible by "
                             f"its {n} mesh shards")
        out.append(d // n)
    return tuple(out)


def _superstep_stub(stencil: Stencil, geom: BlockGeometry, ext, coeffs,
                    steps, aux_ext, bounds, bc=None):
    """Custom-call stand-in for the Pallas streaming kernel (dry-run billing).

    Per-shard (already inside shard_map, so GSPMD sees sharded operands):
    lowers to one opaque custom-call whose operands+result are the kernel's
    HBM DMA footprint — grid in, aux in, grid out. The kernel's true DMA
    schedule adds halo re-reads (+3-8%, `kernels.ops.dma_traffic_bytes`;
    Table 4's traffic-accuracy column quantifies the gap). Executable on
    host via the pure-JAX engine, so tests can run this path end-to-end.
    """
    import numpy as np
    nb = len(bounds)
    ext_arr, keep = ext                  # (extended grid, interior slices)

    def host(ext_h, aux_h, steps_h, bounds_h, *coeff_vals):
        cf = {k: jnp.asarray(v) for k, v in zip(stencil.coeff_names,
                                                coeff_vals)}
        bd = tuple((jnp.asarray(bounds_h[i, 0]), jnp.asarray(bounds_h[i, 1]))
                   for i in range(nb))
        out = blocked_superstep(stencil, geom, jnp.asarray(ext_h), cf,
                                jnp.asarray(steps_h),
                                jnp.asarray(aux_h) if stencil.has_aux
                                else None, bounds=bd, bc=bc)
        return np.asarray(out[keep])

    bounds_arr = jnp.stack([jnp.stack([jnp.asarray(lo, jnp.int32),
                                       jnp.asarray(hi, jnp.int32)])
                            for lo, hi in bounds])
    coeff_vals = [coeffs[k] for k in stencil.coeff_names]
    aux_in = aux_ext if aux_ext is not None else jnp.zeros((), jnp.float32)
    out_shape = tuple(len(range(*k.indices(s)))
                      for k, s in zip(keep, ext_arr.shape))
    return jax.pure_callback(
        host, jax.ShapeDtypeStruct(out_shape, ext_arr.dtype), ext_arr,
        aux_in, steps, bounds_arr, *coeff_vals, vmap_method="sequential")


def build_distributed_fn(stencil: Stencil, dims, iters: Optional[int],
                         par_time: int, bsize, mesh: Mesh,
                         axis_map: Sequence[Optional[Tuple[str, ...]]],
                         kernel_stub: bool = False, *,
                         batch: bool = False, aux_batched: bool = False,
                         trace_hook=None, bc=None, stages=None, dag=None):
    """Build the jitted multi-device runner ``fn(grid, aux, coeffs) -> grid``.

    Used both for real execution (tests/examples) and for the dry-run
    (``fn.lower(ShapeDtypeStruct...)``).  ``axis_map[d]``: mesh axis names
    sharding grid axis ``d`` (or None). 2D on a (pod, data, model) mesh:
    ``axis_map = (("pod", "data"), ("model",))``. ``kernel_stub=True``
    routes each shard's super-step through the Pallas-kernel stand-in
    (billing/dry-run; see ``_superstep_stub``).

    Throughput extensions (the serving path — see ``repro.api.backends``):
      * ``iters=None`` builds a *dynamic-iteration* runner
        ``fn(grid, aux, coeffs, iters)``: the super-step count is computed
        from the traced ``iters`` scalar, so one shard_map program serves
        every iteration count (this generalizes the old per-``iters``
        compiled-program dict).
      * ``batch=True`` expects a leading batch axis on ``grid`` (replicated
        over the mesh, sharded only in the grid axes): each super-step
        exchanges ONE aggregated halo per mesh axis for the whole batch —
        temporal blocking already divides the number of ICI latency events
        by ``par_time``; batching divides the per-problem count by ``B``
        again — then updates all batch members via a vmapped engine
        super-step.  ``aux_batched`` selects whether the aux (power) grid
        carries a matching batch axis or is shared by the whole batch.
      * ``trace_hook`` (if given) is called each time the local program is
        (re)traced — the executable cache's trace counter.
      * ``bc`` (``core.boundary.BoundaryCondition``; None = clamp): per-axis
        boundary condition.  Periodic axes that are mesh-sharded exchange
        halos on a wrap-around ring and are *localized* to no-op bounds (a
        shard never sees a physical edge there); every other kind keeps its
        rule and ``bounds`` distinguishes internal from physical edges.
      * ``stages`` (multi-stage programs — see ``repro.programs``): the
        static ``((stencil, bc), ...)`` chain.  The halo width becomes
        ``sum(stage radii) * par_time`` (one exchange still covers the whole
        fused chain per super-step), each stage's BC is localized per the
        rule above (per-axis periodicity is uniform across stages, so the
        ring topology is well-defined), and each shard runs the fused
        chain super-step locally.  ``coeffs`` then is one dict per stage;
        ``bc`` must be the program's structural (stage-0) BC.
      * ``dag`` (general stage DAGs — see ``repro.programs``): the resolved
        static :class:`~repro.programs.DagSpec`.  The halo width becomes the
        DAG's *critical-path* radius × ``par_time``; per-stage BCs localize
        like ``stages``; a multi-field program's state carries a leading
        ``(F, ...)`` field axis that is never mesh-sharded — ONE halo
        exchange per sharded grid axis still covers all fields (the strips
        stack along the field axis), so temporal blocking's
        latency-aggregation win extends unchanged to multi-field DAGs.
    """
    if isinstance(bsize, int):
        bsize = (bsize,) * (len(dims) - 1)
    axis_map = tuple(tuple(a) if a else None for a in axis_map)
    from repro.core import boundary
    kinds = boundary.kinds_of(bc, len(dims))
    # Localize the BC for the per-shard engine: a sharded periodic axis has
    # no physical edge locally (the wrapped halo arrives by ppermute), so its
    # local kind degrades to clamp under full-extent bounds (a no-op) — a
    # local wrap-pad would wrap the *shard*, not the grid.  Unsharded axes
    # keep their kind: the shard owns the full global extent there.
    local_kinds = tuple(
        "clamp" if (names and kind == "periodic") else kind
        for names, kind in zip(axis_map, kinds))
    bc_local = None if bc is None else dataclasses.replace(
        bc, kinds=local_kinds)
    def localize(bc_s):
        return dataclasses.replace(bc_s, kinds=tuple(
            "clamp" if (names and k == "periodic") else k
            for names, k in zip(axis_map, bc_s.kinds)))

    local_dag = None
    n_fields = 1
    if dag is not None:
        if kernel_stub:
            raise NotImplementedError(
                "kernel_stub supports single-stage problems only")
        # the exchange must cover the DAG's deepest dependency path per
        # iteration, not the sum over stages (branches run in parallel)
        rad = dag_radius(dag)
        has_aux = any(st.has_aux for st, _, _ in dag.stages)
        n_fields = dag.n_fields
        # localize every stage's BC the same way (sharded periodic axes
        # degrade to clamp under no-op bounds — the wrapped halo is exact)
        local_dag = DagSpec(
            stages=tuple((st, localize(bc_s), refs)
                         for st, bc_s, refs in dag.stages),
            n_fields=dag.n_fields, updates=dag.updates, topo=dag.topo)
        local_stages = None
    elif stages is not None:
        if kernel_stub:
            raise NotImplementedError(
                "kernel_stub supports single-stage problems only")
        rad = sum(st.radius for st, _ in stages)
        has_aux = any(st.has_aux for st, _ in stages)
        local_stages = tuple((st, localize(bc_s)) for st, bc_s in stages)
    else:
        rad = stencil.radius
        has_aux = stencil.has_aux
        local_stages = None
    h = rad * par_time
    local_dims = shard_extents(dims, axis_map, mesh)
    ext_dims = tuple(ld + (2 * h if names else 0)
                     for ld, names in zip(local_dims, axis_map))
    geom = BlockGeometry(len(dims), ext_dims, rad, par_time,
                         tuple(bsize))
    spec = partition_spec(axis_map)
    if kernel_stub and batch:
        raise NotImplementedError("kernel_stub has no batched variant")
    # leading batch and/or field axes are never sharded; grid axes shift
    # right by one per leading axis
    off = (1 if batch else 0) + (1 if n_fields > 1 else 0)

    def local_impl(g, aux_l, coeffs_l, iters_l):
        if trace_hook is not None:
            trace_hook()
        n_super = (iters_l + par_time - 1) // par_time
        bounds = []
        for names, ld, kind in zip(axis_map, local_dims, kinds):
            if names is None:
                bounds.append((0, ld - 1))
                continue
            if kind == "periodic":
                # wrap-around ring: every shard edge is internal — bounds
                # span the whole halo-extended shard (re-imposition no-op)
                bounds.append((0, ld + 2 * h - 1))
                continue
            i = _linear_index(names)
            n = _axis_total(names)
            lo = jnp.where(i == 0, h, 0)
            hi = jnp.where(i == n - 1, h + ld - 1, ld + 2 * h - 1)
            bounds.append((lo, hi))
        bounds = tuple(bounds)

        keep = (slice(None),) * off + tuple(
            slice(h, h + ld) if names else slice(None)
            for names, ld in zip(axis_map, local_dims))
        # aux (power) grid is read-only: exchange its halo once, not per
        # super-step (hoisted out of the fori_loop)
        aux_ext = aux_l
        if has_aux:
            aux_off = 1 if (batch and aux_batched) else 0
            for ax, names in enumerate(axis_map):
                if names:
                    aux_ext = _exchange_halo(aux_ext, ax + aux_off, names, h,
                                             periodic=kinds[ax] == "periodic")

        def one_superstep(ext, steps):
            """Per-shard super-step on the halo-extended local grid."""
            if kernel_stub:
                return _superstep_stub(stencil, geom, (ext, keep), coeffs_l,
                                       steps, aux_ext if has_aux else None,
                                       bounds, bc_local)
            if local_dag is not None:
                cf_dag = (coeffs_l if isinstance(coeffs_l, tuple)
                          else (coeffs_l,))

                def step_local(e, a):
                    return blocked_superstep_dag(local_dag, geom, e, cf_dag,
                                                 steps, a, bounds)
            elif local_stages is not None:
                def step_local(e, a):
                    return blocked_superstep_chain(local_stages, geom, e,
                                                   coeffs_l, steps, a, bounds)
            else:
                def step_local(e, a):
                    return blocked_superstep(stencil, geom, e, coeffs_l,
                                             steps, a, bounds, bc_local)
            if batch:
                aux_ax = (0 if aux_batched else None) if has_aux else None
                upd = jax.vmap(step_local, in_axes=(0, aux_ax))(
                    ext, aux_ext if has_aux else None)
            else:
                upd = step_local(ext, aux_ext if has_aux else None)
            return upd[keep]

        def superstep(s, gl):
            steps = jnp.minimum(par_time, iters_l - s * par_time)
            ext = gl
            for ax, names in enumerate(axis_map):
                if names:
                    # one aggregated exchange per axis for the whole batch
                    ext = _exchange_halo(ext, ax + off, names, h,
                                         periodic=kinds[ax] == "periodic")
            return one_superstep(ext, steps)

        return jax.lax.fori_loop(0, n_super, superstep, g)

    aux_spec = P() if not has_aux else (
        P(None, *spec) if (batch and aux_batched) else spec)
    grid_spec = P(*((None,) * off), *spec) if off else spec
    if iters is None:
        # dynamic iters: the runner takes the count as a replicated scalar —
        # fn(grid, aux, coeffs, iters)
        local_run, in_specs = local_impl, (grid_spec, aux_spec, P(), P())
    else:
        # legacy static-iters arity (keeps .lower(grid, aux, coeffs) working
        # for the dry-run/HLO paths)
        def local_run(g, aux_l, coeffs_l):
            return local_impl(g, aux_l, coeffs_l, iters)
        in_specs = (grid_spec, aux_spec, P())
    shmapped = jax.shard_map(local_run, mesh=mesh, in_specs=in_specs,
                                out_specs=grid_spec, check_vma=False)
    return jax.jit(shmapped,
                   in_shardings=(NamedSharding(mesh, grid_spec),
                                 NamedSharding(mesh, aux_spec),
                                 None) + ((None,) if iters is None else ()),
                   out_shardings=NamedSharding(mesh, grid_spec))


def distributed_run(stencil: Stencil, grid: jnp.ndarray, coeffs: dict,
                    iters: int, par_time: int, bsize, mesh: Mesh,
                    axis_map, aux: jnp.ndarray | None = None, *,
                    bc=None) -> jnp.ndarray:
    """Run ``iters`` steps of ``stencil`` on a grid sharded over ``mesh``."""
    fn = build_distributed_fn(stencil, grid.shape, iters, par_time, bsize,
                              mesh, axis_map, bc=bc)
    aux_in = aux if aux is not None else jnp.zeros((), jnp.float32)
    return fn(grid, aux_in, coeffs)
