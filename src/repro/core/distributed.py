"""Multi-device spatial distribution of the streaming stencil kernel.

This implements the paper's stated future work (§8: "spatial distribution of
large stencils on multiple FPGAs") on a TPU mesh: the grid is domain-
decomposed over mesh axes via ``shard_map``; each device runs the *same*
fused super-step loop as one chip (``kernels/ops.fused_loop``, the Pallas
streaming kernel ``superstep_chain``) on its block;
halos of width ``h = rad * par_time`` are exchanged with ``lax.ppermute``
**once per super-step** — temporal blocking divides the number of exchanges
(and thus ICI latency events) by ``par_time``.  That communication
aggregation is the distributed-optimization payoff of the paper's technique.

Layout.  Along each sharded grid axis a shard carries an *extended block*
of ``ld + 2h`` cells (``ld`` its own), in the kernel's padded layout for
the whole loop, with its real cells placed so that every physical edge of
the grid lies on the block's own edge:

  * the low shard of a non-periodic axis holds
    ``[real ld | halo h | spare h]``,
  * the high shard ``[spare h | halo h | real ld]``,
  * an interior shard, and every shard of a periodic axis,
    ``[halo h | real ld | halo h]``.

The kernel re-imposes the boundary condition at its block's ends at every
fused sub-step, which is then the true edge where the grid has one; where
it does not, the wrong values it sees there (a spare strip, the kernel's
own edge clamp) creep inwards by ``rad`` per sub-step and stop, after
``par_time`` sub-steps, exactly at the real cells — the overlapped-blocking
argument one level up.  A sharded periodic axis exchanges on a wrap-around
ring and its local kind degrades to clamp: the wrapped halo is an exact
translated copy, so the shard never sees a physical edge there.  Unsharded
axes (and mesh axes of one device) keep their kind and the whole extent.

Between two kernels only padding strips are rewritten (no pass over the
whole array): per grid axis in order, a sharded axis sends the ``h`` cells
at each end of its real cells to its neighbours and writes the two strips
it gets back beside them (:func:`_exchange_strips`, scope
``stencil.halo_exchange``; a later axis' strips carry the corners an
earlier one exchanged); the kernel layout's own padding outside the block
is refreshed from the block's edge cells as on one chip
(``kernels/ops._refresh_strips``).  The aux field is exchanged once per
run.  Elasticity: the decomposition is a pure function of (mesh, grid
shape); restarting on a different mesh re-shards automatically.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.blocking import BlockGeometry
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
    versa.  Non-periodic: the outermost shards receive zeros where they
    have no neighbour.  Periodic: the ring wraps around the mesh — shard
    0's leading halo is shard n-1's trailing strip, which IS the global
    periodic neighbor (no true-edge handling left to do locally).  The one
    place that calls ``ppermute``: the kernel path calls it on the ``2h``
    pair of a shard's edge strips (:func:`_exchange_strips`), so only
    strips cross the interconnect.
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


def _real_offsets(axis_map, sharded, periodic, h: int) -> list:
    """Where the shard's real cells start inside its extended block, per
    grid axis: 0 on the low shard of a non-periodic sharded axis (its
    physical edge at the block's edge), ``2h`` on the high one, ``h`` on
    interior shards and periodic axes, 0 on unsharded axes.  Depends on the
    shard's mesh position, so it is traced on non-periodic sharded axes."""
    out = []
    for names, s, per in zip(axis_map, sharded, periodic):
        if not s or per:
            out.append(h if s else 0)
            continue
        i, n = _linear_index(names), _axis_total(names)
        out.append(jnp.where(i == 0, 0, jnp.where(i == n - 1, 2 * h, h)))
    return out


def _exchange_strips(gp: jnp.ndarray, axis: int, base: int, off, ld: int,
                     h: int, names: Tuple[str, ...],
                     periodic: bool) -> jnp.ndarray:
    """Refresh the ``h``-wide halos beside the shard's real cells along
    ``axis``: the extended block of ``ld + 2h`` cells starts at ``base``,
    the real cells ``off`` into it.  The pair ``[lead h | trail h]`` of the
    shard's own edge strips goes through :func:`_exchange_halo`, which
    returns ``[nbr trail | lead | trail | nbr lead]``; the neighbours' two
    strips are written ``h`` before and right after the real cells, modulo
    the block, so the zero strip a physical edge gets lands in the spare
    strip.  Only strips are sliced, sent and written."""
    ext = ld + 2 * h
    at = base + off
    with jax.named_scope("stencil.halo_exchange"):
        lead = lax.dynamic_slice_in_dim(gp, at, h, axis)
        trail = lax.dynamic_slice_in_dim(gp, at + ld - h, h, axis)
        got = _exchange_halo(jnp.concatenate([lead, trail], axis), axis,
                             names, h, periodic)
        gp = lax.dynamic_update_slice_in_dim(
            gp, lax.slice_in_dim(got, 0, h, axis=axis),
            base + (off - h) % ext, axis)
        return lax.dynamic_update_slice_in_dim(
            gp, lax.slice_in_dim(got, 3 * h, 4 * h, axis=axis),
            base + (off + ld) % ext, axis)


def build_distributed_fn(dag: DagSpec, dims, iters: Optional[int],
                         par_time: int, bsize, mesh: Mesh,
                         axis_map: Sequence[Optional[Tuple[str, ...]]], *,
                         batch: bool = False, aux_batched: bool = False,
                         trace_hook=None, par_vec: int = 1,
                         align: Tuple[int, ...] = (),
                         block_parallel: bool = False):
    """Build the jitted multi-device runner ``fn(grid, aux, coeffs) -> grid``
    of the stage DAG ``dag`` (:class:`~repro.programs.DagSpec`; a single
    stencil is ``chain_dag(((stencil, bc),))``).

    Used both for real execution (the ``distributed`` backend, tests,
    examples) and for the dry-run (``fn.lower(ShapeDtypeStruct...)``).
    ``axis_map[d]``: mesh axis names sharding grid axis ``d`` (or None). 2D
    on a (pod, data, model) mesh: ``axis_map = (("pod", "data"),
    ("model",))``.  Every shard runs the fused Pallas super-step loop on its
    extended block (module docstring); ``par_vec``, ``align`` and
    ``block_parallel`` are the kernel's.  The kernel is compiled on a mesh
    of TPUs and runs in the Pallas interpreter on any other.

    ``coeffs`` is one coefficient dict per stage.  The halo is
    ``h = dag_radius(dag) * par_time``, the DAG's critical-path radius, so
    one exchange covers the whole program per super-step; stage 0's BC
    (the structural one) sizes the padding and picks, per axis, a ring
    exchange where it is periodic.  A multi-field program's state carries
    a leading ``(F, ...)`` field axis that is never sharded: one exchange
    per sharded axis carries every field's strips.

    Throughput extensions (the serving path — see ``repro.api.backends``):
      * ``iters=None`` builds a *dynamic-iteration* runner
        ``fn(grid, aux, coeffs, iters)``: the super-step count is computed
        from the traced ``iters`` scalar, so one shard_map program serves
        every iteration count.
      * ``batch=True`` expects a leading batch axis on ``grid`` (replicated
        over the mesh, sharded only in the grid axes): each super-step runs
        the kernel on every member, then exchanges ONE aggregated halo per
        sharded axis for the whole batch — temporal blocking already
        divides the number of ICI latency events by ``par_time``; batching
        divides the per-problem count by ``B`` again.  ``aux_batched``
        selects whether the aux (power) grid carries a matching batch axis
        or is shared by the whole batch.
      * ``trace_hook`` (if given) is called each time the local program is
        (re)traced — the executable cache's trace counter.
    """
    if isinstance(bsize, int):
        bsize = (bsize,) * (len(dims) - 1)
    axis_map = tuple(tuple(a) if a else None for a in axis_map)
    from repro.core import boundary
    from repro.kernels import ops
    ndim = len(dims)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sharded = tuple(bool(names) and math.prod(sizes[a] for a in names) > 1
                    for names in axis_map)
    kinds = boundary.kinds_of(dag.stages[0][1], ndim)
    periodic = tuple(k == "periodic" for k in kinds)

    def localize(bc_s):
        # a sharded periodic axis has no physical edge locally (the wrapped
        # halo arrives by ppermute): clamp at the block's ends is then only
        # garbage creep; a local wrap would wrap the *shard*, not the grid
        if bc_s is None:
            return None
        return dataclasses.replace(bc_s, kinds=tuple(
            "clamp" if (s and k == "periodic") else k
            for s, k in zip(sharded, bc_s.kinds)))

    radius = dag_radius(dag)
    h = radius * par_time
    local_dims = shard_extents(dims, axis_map, mesh)
    for ax, (s, ld) in enumerate(zip(sharded, local_dims)):
        if s and ld < h:
            raise ValueError(
                f"grid axis {ax}: a shard of {ld} cells cannot feed a halo "
                f"of {h} (radius {radius} x par_time {par_time}); "
                f"lower par_time")
    geom = BlockGeometry(ndim, tuple(ld + 2 * h if s else ld
                                     for ld, s in zip(local_dims, sharded)),
                         radius, par_time, tuple(bsize), par_vec,
                         tuple(align))
    interpret = mesh.devices.flat[0].platform != "tpu"
    local_dag = dataclasses.replace(dag, stages=tuple(
        (st, localize(bc_s), refs) for st, bc_s, refs in dag.stages))
    has_aux = any(st.has_aux for st, _, _ in dag.stages)
    bc0 = local_dag.stages[0][1]

    sext = ops._stream_ext(geom, bc0)
    padded = ((geom.stream_slabs(geom.stream_dim + 2 * sext) * par_vec,)
              + geom.padded_dims)
    # where the extended block starts in the kernel's padded layout
    base = (sext,) + geom.pad

    def local_impl(g, aux_l, cpk, iters_l):
        if trace_hook is not None:
            trace_hook()
        offs = _real_offsets(axis_map, sharded, periodic, h)
        at = [b + o for b, o in zip(base, offs)]

        def starts(x):
            return (0,) * (x.ndim - ndim) + tuple(at)

        def place(x):
            lead = x.shape[:x.ndim - ndim]
            return lax.dynamic_update_slice(
                jnp.zeros(lead + padded, x.dtype), x, starts(x))

        def unpad(gp):
            return lax.dynamic_slice(gp, starts(gp),
                                     gp.shape[:gp.ndim - ndim] + local_dims)

        def exchange(gp, axis, ax):
            if not sharded[ax]:
                return gp
            return _exchange_strips(gp, axis, base[ax], offs[ax],
                                    local_dims[ax], h, axis_map[ax],
                                    periodic[ax])

        def refresh(gp):
            return ops._reclamp_padded(gp, geom, bc0, exchange)

        with jax.named_scope("stencil.pad"):
            gp = refresh(place(g))
            aux_p = refresh(place(aux_l)) if has_aux else None
        return ops.fused_loop(local_dag, geom, gp, cpk, iters_l, aux_p,
                              interpret, block_parallel, refresh=refresh,
                              unpad=unpad)

    spec = partition_spec(axis_map)
    off = (1 if batch else 0) + (1 if dag.n_fields > 1 else 0)
    aux_spec = P() if not has_aux else (
        P(None, *spec) if (batch and aux_batched) else spec)
    grid_spec = P(*((None,) * off), *spec) if off else spec
    shmapped = jax.shard_map(local_impl, mesh=mesh,
                             in_specs=(grid_spec, aux_spec, P(), P()),
                             out_specs=grid_spec, check_vma=False)
    if iters is None:
        def run(g, aux, coeffs, iters_l):
            return shmapped(g, aux, ops.pack_coeffs(dag, coeffs), iters_l)
    else:
        # static-iters arity: fn(grid, aux, coeffs) (the dry-run/HLO paths)
        def run(g, aux, coeffs):
            return shmapped(g, aux, ops.pack_coeffs(dag, coeffs),
                            jnp.int32(iters))
    return jax.jit(run,
                   in_shardings=(NamedSharding(mesh, grid_spec),
                                 NamedSharding(mesh, aux_spec),
                                 None) + ((None,) if iters is None else ()),
                   out_shardings=NamedSharding(mesh, grid_spec))
