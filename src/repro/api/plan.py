"""``plan(problem, config) -> StencilPlan`` — the single public entry point.

Mirrors the paper's two-phase workflow: the performance model prunes the
(bsize, par_time) design space *offline* (§4, §5.3), then a fixed
configuration executes many iterations.  A ``StencilPlan`` is that fixed
configuration: reusable across calls and iteration counts, and introspectable
(``predicted()``, ``traffic_report()``, ``describe()``) without running
anything.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.api import schedule_cache, tuner
from repro.api.backends import (ExecuteFn, as_program, check_pallas_dtype,
                                get_backend, resolve_axis_map)
from repro.api.config import RunConfig
from repro.api.problem import StencilProblem
from repro.core import perf_model
from repro.core.blocking import (BlockGeometry, extended_geometry,
                                 superstep_traffic_bytes, tpu_tiles)
from repro.core.perf_model import Device, Prediction


def _chip_layout(problem: StencilProblem, config: RunConfig):
    """(n_chips, chip_grid) for the perf model; (1, None) off-mesh."""
    if config.backend != "distributed" or config.mesh is None:
        return 1, None
    mesh = config.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axis_map = resolve_axis_map(problem, config)
    chip_grid = tuple(
        math.prod(sizes[a] for a in names) if names else 1
        for names in axis_map)
    return math.prod(chip_grid), chip_grid


#: backends whose executables realize ``par_vec`` (the streaming Pallas
#: kernels, on one chip or on every shard of a mesh).  The others
#: (engine/reference) run scalar-tick code, so sweeping V for them would
#: only distort the (bsize, par_time) ranking and fill measured-tuning
#: shortlists with V-duplicates.
PAR_VEC_BACKENDS = ("pallas", "pallas_interpret", "distributed")

#: built-in backends that execute scalar ticks: a *pinned* ``par_vec > 1``
#: there would silently report (and price) a vector width the executable
#: never realizes, so ``plan()`` rejects it.  Custom registered backends
#: are unrestricted — they may well wrap the vectorized kernels.
SCALAR_TICK_BACKENDS = ("engine", "reference")

#: backends compiled by Mosaic for the chip: their geometry is tile-aligned
#: (``BlockGeometry.align``) so every HBM DMA window starts on a tile.
#: ``distributed`` is, too, when its mesh is made of TPUs (:func:`_aligned`)
ALIGNED_BACKENDS = ("pallas",)


def _aligned(config: RunConfig) -> bool:
    """Whether the plan's kernels are compiled by Mosaic: the ``pallas``
    backend, or ``distributed`` on a mesh of TPUs (elsewhere its kernels
    run interpreted, on any geometry)."""
    if config.backend == "distributed" and config.mesh is not None:
        device = config.mesh.devices.flat[0]
        return getattr(device, "platform", None) == "tpu"
    return config.backend in ALIGNED_BACKENDS


def _tiles(problem: StencilProblem, config: RunConfig):
    """``(stream_tile, align)`` the backend's kernels need (``(1, ())`` for
    backends that run any geometry)."""
    if not _aligned(config):
        return 1, ()
    return tpu_tiles(problem.ndim, config.resolved_cell_bytes(problem.dtype))


def _candidate_shortlist(problem: StencilProblem, config: RunConfig,
                         device: Device, n_chips: int, chip_grid,
                         top_k: Optional[int] = None):
    """Model-ranked predictions (§5.3 pruning), best first.

    A pinned ``par_time``, ``bsize`` or ``par_vec`` constrains the sweep to
    exactly that value (the paper's tuned depths, e.g. 36, need not be
    powers of two); the free dimension(s) are enumerated, pruned by the
    VMEM budget and by geometric feasibility, and ranked by predicted run
    time.  ``par_vec`` is only swept for backends that realize it
    (:data:`PAR_VEC_BACKENDS`); elsewhere an unpinned V stays 1.  ``top_k``
    truncates to the shortlist the measured tuner times."""
    par_vec = config.par_vec
    if par_vec is None and config.backend not in PAR_VEC_BACKENDS:
        par_vec = 1
    cands = perf_model.autotune(
        problem.stencil, problem.shape, config.iters_hint, device,
        config.resolved_cell_bytes(problem.dtype),
        _par_time_max(problem, config), n_chips, chip_grid,
        par_time=config.par_time,
        bsize=config.normalized_bsize(problem.ndim),
        par_vec=par_vec, top_k=top_k,
        bc=problem.structural_bc,
        aligned=_aligned(config))
    if not cands:
        raise ValueError(
            f"no VMEM-feasible (bsize, par_time, par_vec) for "
            f"{problem.stencil.name} "
            f"on {problem.shape} under {device.name} "
            f"(par_time={config.par_time}, bsize={config.bsize}, "
            f"par_vec={config.par_vec}, "
            f"par_time_max={config.par_time_max})")
    return cands


def _resolve_schedule(problem: StencilProblem, config: RunConfig,
                      device: Device, n_chips: int, chip_grid):
    """Pick (par_time, bsize, par_vec): explicit, or perf-model autotuned
    (§5.3).  An unpinned ``par_vec`` on a fully pinned schedule defaults to
    1 (today's scalar tick) rather than triggering a sweep."""
    par_time = config.par_time
    bsize = config.normalized_bsize(problem.ndim)
    if not config.autotune and par_time is not None and bsize is not None:
        par_vec = config.par_vec or _tiles(problem, config)[0]
        return par_time, bsize, par_vec, ()
    cands = _candidate_shortlist(problem, config, device, n_chips, chip_grid)
    best = cands[0].geom
    return best.par_time, best.bsize, best.par_vec, tuple(cands)


def _resolve_measured(problem: StencilProblem, config: RunConfig,
                      device: Device, n_chips: int, chip_grid):
    """autotune="measure": serve the schedule from the persistent cache, or
    time the model's shortlist on the real backend and persist the winner.

    Returns ``(par_time, bsize, par_vec, candidates, from_cache)`` where
    candidates are :class:`~repro.api.tuner.TunedCandidate`, measured-best
    first.
    """
    cache = schedule_cache.ScheduleCache.resolve(config.cache)
    key = schedule_cache.schedule_key(problem, config, device,
                                      n_chips, chip_grid)
    if cache is not None:
        entry = cache.get(key)
        if entry is not None:
            # The cache file is documented as hand-editable JSON: a mangled
            # or future-layout entry is a miss (re-tune), never a crash.
            try:
                par_time = int(entry["par_time"])
                bsize = tuple(int(b) for b in entry["bsize"])
                # pre-par_vec entries (or hand-written ones) mean V=1
                par_vec = int(entry.get("par_vec", 1))
                measured_s = float(entry["measured_s"])
                accuracy = float(entry["model_accuracy"])
                if (par_time < 1 or par_vec < 1
                        or len(bsize) != problem.ndim - 1
                        or any(b < 1 for b in bsize) or measured_s <= 0):
                    raise ValueError("mangled schedule-cache entry")
                pred = perf_model.predict(
                    problem.stencil, problem.shape, config.iters_hint, bsize,
                    par_time, device,
                    config.resolved_cell_bytes(problem.dtype),
                    n_chips, chip_grid,
                    bc=problem.structural_bc, par_vec=par_vec,
                    aligned=_aligned(config))
            except (KeyError, TypeError, ValueError):
                entry = None
            else:
                cand = tuner.TunedCandidate(
                    prediction=pred, measured_s=measured_s,
                    measured_run_time=measured_s * pred.n_super,
                    model_accuracy=accuracy, from_cache=True)
                return par_time, bsize, par_vec, (cand,), True
    shortlist = _candidate_shortlist(problem, config, device,
                                     n_chips, chip_grid,
                                     top_k=config.tune_top_k)
    tuned = tuner.measure_candidates(problem, config, shortlist)
    best = tuned[0]
    if cache is not None:
        cache.put(key, {
            "stencil": problem.stencil.name,
            "par_time": best.geom.par_time, "bsize": list(best.geom.bsize),
            "par_vec": best.geom.par_vec,
            "measured_s": best.measured_s,
            "model_accuracy": best.model_accuracy,
        })
    return best.geom.par_time, best.geom.bsize, best.geom.par_vec, tuned, False


def _shard_par_time_max(problem: StencilProblem,
                        config: RunConfig) -> Optional[int]:
    """The deepest ``par_time`` whose halo (radius x ``par_time``) every
    shard of a sharded axis can feed from its own cells; ``None`` off-mesh.
    Raises at plan time (not first ``run()``) when the mesh cannot shard
    the grid evenly — ``predict`` ceil-divides, so only this catches it."""
    if config.backend != "distributed" or config.mesh is None:
        return None
    from repro.core.distributed import shard_extents
    axis_map = resolve_axis_map(problem, config)
    local = shard_extents(problem.shape, axis_map, config.mesh)
    sizes = dict(zip(config.mesh.axis_names, config.mesh.devices.shape))
    rad = problem.stencil.radius
    caps = [ld // rad for ld, names in zip(local, axis_map)
            if names and rad and math.prod(sizes[a] for a in names) > 1]
    return min(caps) if caps else None


def _par_time_max(problem: StencilProblem, config: RunConfig) -> int:
    cap = _shard_par_time_max(problem, config)
    return config.par_time_max if cap is None else min(cap,
                                                       config.par_time_max)


def _validate_distributed(problem: StencilProblem, config: RunConfig) -> None:
    """Fail at plan time when the mesh cannot shard the grid evenly, or a
    pinned ``par_time`` asks for a halo wider than a shard."""
    cap = _shard_par_time_max(problem, config)
    if cap is not None and (config.par_time or 0) > cap:
        raise ValueError(
            f"par_time={config.par_time} needs a halo of "
            f"{problem.stencil.radius * config.par_time} cells; the "
            f"narrowest shard of this mesh has {cap * problem.stencil.radius}"
            f" — pin par_time <= {cap}")


@tracing.span("stencil.plan")
def plan(problem: StencilProblem, config: Optional[RunConfig] = None,
         ) -> "StencilPlan":
    """Compile ``problem`` under ``config`` into a reusable ``StencilPlan``."""
    if config is None:
        config = RunConfig()
    factory = get_backend(config.backend)       # fail fast on unknown names
    if config.backend in PAR_VEC_BACKENDS:
        check_pallas_dtype(problem)
    _validate_distributed(problem, config)
    device = config.resolved_device()
    n_chips, chip_grid = _chip_layout(problem, config)
    # The unblocked oracle ignores (bsize, par_time): an unresolvable or
    # invalid schedule degrades a 'reference' plan to geometry-less instead
    # of failing (legacy stencil_run never validated the oracle's schedule).
    geom, cands, from_cache = None, (), False
    try:
        if (config.par_vec is not None and config.par_vec > 1
                and config.backend in SCALAR_TICK_BACKENDS):
            # inside the try block: the reference oracle degrades schedule
            # errors to a geometry-less plan (legacy), the others raise
            raise ValueError(
                f"par_vec={config.par_vec} is a Pallas streaming-kernel "
                f"knob; backend={config.backend!r} executes scalar ticks "
                f"and cannot honor it — pin par_vec only for "
                f"{list(PAR_VEC_BACKENDS)} (or leave it unset)")
        with tracing.span("stencil.plan.autotune"):
            if config.autotune == "measure":
                par_time, bsize, par_vec, cands, from_cache = \
                    _resolve_measured(problem, config, device, n_chips,
                                      chip_grid)
            else:
                par_time, bsize, par_vec, cands = _resolve_schedule(
                    problem, config, device, n_chips, chip_grid)
        stream_tile, align = _tiles(problem, config)
        if par_vec % stream_tile:
            raise ValueError(
                f"par_vec={par_vec} is not a multiple of {stream_tile}: the "
                f"{config.backend!r} kernels move (par_vec, ...) slabs that "
                f"must fill whole {problem.dtype} tiles")
        # on a mesh, the block one chip's kernel streams: its shard
        # extended by the halo on each sharded side
        geom = BlockGeometry(problem.ndim, perf_model.block_dims(
            problem.stencil, problem.shape, par_time, n_chips, chip_grid),
            problem.stencil.radius, par_time, tuple(bsize), par_vec, align)
    except ValueError:
        if config.backend != "reference":
            raise
    with tracing.span("stencil.plan.build"):
        program = as_program(factory(problem, config, geom))
    return StencilPlan(problem=problem, config=config, geometry=geom,
                       backend=config.backend, device=device,
                       n_chips=n_chips, chip_grid=chip_grid,
                       candidates=cands, _execute=program.execute,
                       _execute_batch=program.execute_batch,
                       _lower=program.lower, tuned_from_cache=from_cache)


@dataclasses.dataclass
class StencilPlan:
    """A compiled, reusable executable for one (problem, config) pair."""
    problem: StencilProblem
    config: RunConfig
    geometry: Optional[BlockGeometry]
    backend: str
    device: Device
    n_chips: int
    chip_grid: Optional[tuple]
    #: autotuner candidates ranked best-first (empty when the schedule was
    #: pinned explicitly) — candidates[0] is the compiled schedule.  Model
    #: autotuning yields :class:`~repro.core.perf_model.Prediction`s;
    #: measured autotuning yields :class:`~repro.api.tuner.TunedCandidate`s
    #: carrying measured seconds and model accuracy per candidate.
    candidates: tuple
    _execute: ExecuteFn = dataclasses.field(repr=False)
    #: batched entry point (None for backends without one — ``run_batch``
    #: then falls back to a per-element loop)
    _execute_batch: Optional[ExecuteFn] = dataclasses.field(
        default=None, repr=False)
    #: lowering entry point (None for backends without one)
    _lower: Optional[object] = dataclasses.field(default=None, repr=False)
    #: True when the measured schedule was served by the persistent cache
    #: (no candidate was re-timed for this plan)
    tuned_from_cache: bool = False

    # --- execution ----------------------------------------------------------
    @tracing.span("stencil.run")
    def run(self, grid, iters: int, coeffs=None, *,
            aux=None, checkpoint_every: Optional[int] = None,
            checkpoint_dir: Optional[str] = None) -> jnp.ndarray:
        """Advance ``grid`` by ``iters`` time-steps (program iterations —
        each applies every stage in order).

        ``coeffs`` defaults to :func:`~repro.core.stencils.default_coeffs`
        overlaid with any per-stage overrides; pass a dict (single-stage
        problems) or a sequence of per-stage dicts/None (programs) to
        override at run time.  ``aux`` is the Hotspot ``power`` grid
        (required iff any stage has an aux stream).  Multi-field programs
        take (and return) the ``(n_fields, *shape)`` field stack —
        ``problem.state_shape`` — fields in declaration order.  The plan is
        reusable: call ``run`` any number of times, with any ``iters``.

        ``checkpoint_every`` + ``checkpoint_dir`` make the run restartable
        (:func:`repro.resilience.run_checkpointed`): state is persisted
        atomically every (super-step-aligned) ``checkpoint_every``
        iterations, and a killed process that calls ``run`` again with the
        same directory resumes from the last complete step — the final grid
        is bit-identical to an uninterrupted run, even when the resume
        happens on a different mesh (the grid re-shards on entry)."""
        if (checkpoint_every is None) != (checkpoint_dir is None):
            raise ValueError("checkpoint_every and checkpoint_dir go "
                             "together — pass both or neither")
        if checkpoint_dir is not None:
            from repro.resilience.checkpoint_run import run_checkpointed
            return run_checkpointed(
                self, grid, iters, coeffs, aux=aux,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir).grid
        grid = jnp.asarray(grid, self.problem.jnp_dtype)
        if tuple(grid.shape) != self.problem.state_shape:
            raise ValueError(f"grid shape {grid.shape} != problem state "
                             f"shape {self.problem.state_shape}")
        iters = int(iters)
        if iters < 0:
            raise ValueError(f"iters must be >= 0, got {iters}")
        coeffs = self._coeff_payload(coeffs)
        if self.problem.needs_aux:
            if aux is None:
                raise ValueError(f"{self.problem.stencil.name} needs an aux "
                                 "(power) grid")
            aux = jnp.asarray(aux, self.problem.jnp_dtype)
            if tuple(aux.shape) != self.problem.shape:
                raise ValueError(f"aux shape {aux.shape} != problem shape "
                                 f"{self.problem.shape}")
        elif aux is not None:
            raise ValueError(f"{self.problem.stencil.name} takes no aux grid")
        if iters == 0:
            return grid
        return self._execute(grid, coeffs, iters, aux)

    def _coeff_payload(self, coeffs):
        """Resolve run-time coefficients into the backend payload: a plain
        dict for single-stage problems (the legacy custom-backend contract),
        a tuple of per-stage dicts for programs.  The no-override payload is
        resolved once and memoized — it is the common case on the serving
        hot path, and re-resolving materializes fresh jnp scalars per call."""
        # coefficients are resolved in the ACCUMULATION dtype, not storage:
        # bf16 grids multiply f32 coefficients inside the f32 PE arithmetic
        # (repro.core.precision); for f32 problems the two dtypes coincide
        dtype = self.problem.accum_dtype
        if coeffs is None:
            cached = getattr(self, "_default_payload", None)
            if cached is None:
                resolved = self.problem.resolve_coeffs(None, dtype=dtype)
                cached = (resolved[0] if self.problem.n_stages == 1
                          else resolved)
                object.__setattr__(self, "_default_payload", cached)
            return cached
        resolved = self.problem.resolve_coeffs(coeffs, dtype=dtype)
        return resolved[0] if self.problem.n_stages == 1 else resolved

    @tracing.span("stencil.run_batch")
    def run_batch(self, grids, iters: int, coeffs=None, *,
                  aux=None) -> jnp.ndarray:
        """Advance a batch of grids ``(B, *shape)`` by ``iters`` time-steps
        through ONE compiled executable (the serving path).

        Unlike a Python loop of :meth:`run` calls — B dispatches, B sets of
        host round-trips — the whole batch advances in a single fused
        program: reference/engine vmap the super-step loop, pallas maps the
        batch inside one executable, distributed aggregates all members'
        halos into one exchange per mesh axis per super-step.  Results are
        bit-identical to the sequential loop.

        ``aux`` (Hotspot ``power``): one grid of ``shape`` shared by the
        whole batch, or a matching batch ``(B, *shape)``.  Backends without
        a batched entry point fall back to a per-element loop (correct, not
        fast)."""
        grids = jnp.asarray(grids, self.problem.jnp_dtype)
        shape = self.problem.state_shape
        if grids.ndim != len(shape) + 1 \
                or tuple(grids.shape[1:]) != shape:
            raise ValueError(f"run_batch needs grids of shape (B, *{shape}); "
                             f"got {tuple(grids.shape)}")
        if grids.shape[0] < 1:
            raise ValueError("run_batch needs a batch of at least 1 grid")
        iters = int(iters)
        if iters < 0:
            raise ValueError(f"iters must be >= 0, got {iters}")
        coeffs = self._coeff_payload(coeffs)
        if self.problem.needs_aux:
            if aux is None:
                raise ValueError(f"{self.problem.stencil.name} needs an aux "
                                 "(power) grid")
            aux = jnp.asarray(aux, self.problem.jnp_dtype)
            aux_ok = (self.problem.shape,
                      (grids.shape[0],) + self.problem.shape)
            if tuple(aux.shape) not in aux_ok:
                raise ValueError(
                    f"aux shape {tuple(aux.shape)} must be {aux_ok[0]} "
                    f"(shared) or {aux_ok[1]} (per-batch)")
        elif aux is not None:
            raise ValueError(f"{self.problem.stencil.name} takes no aux grid")
        if iters == 0:
            return grids
        if self._execute_batch is None:
            outs = [self._execute(
                grids[b], coeffs, iters,
                aux if aux is None or aux.ndim == self.problem.ndim
                else aux[b]) for b in range(grids.shape[0])]
            return jnp.stack(outs)
        return self._execute_batch(grids, coeffs, iters, aux)

    def prewarm(self, batch_sizes=(1,), *, iters: int = 1, coeffs=None,
                single: bool = True) -> dict:
        """Compile (and warm) the executables this plan will need, before
        traffic arrives.

        Until now warm-up was an undocumented side effect of the first
        ``run``/``run_batch`` call — the first request of every batch size
        paid the trace+compile cost.  ``prewarm`` makes it explicit: it
        pushes zero grids through ``run_batch`` for every size in
        ``batch_sizes`` (and through ``run`` when ``single=True``), which
        populates the process-level executable cache, so same-key plans —
        including this one — serve every listed batch size with zero new
        traces.  ``iters=1`` keeps each warming run to a single super-step.

        Aux-taking stencils warm the *per-batch* aux mode — each batch
        member carrying its own aux grid — because that is the mode the
        serving path uses (per-request aux grids stacked); a shared-aux
        ``run_batch`` call compiles its own executable on first use.

        Returns ``{"single": seconds} | {B: seconds}`` per warmed entry
        (compile + one warm execution each)."""
        import time as _time
        if int(iters) < 1:
            raise ValueError(f"prewarm iters must be >= 1, got {iters}")
        zeros = jnp.zeros(self.problem.state_shape, self.problem.jnp_dtype)
        aux = (jnp.zeros(self.problem.shape, self.problem.jnp_dtype)
               if self.problem.needs_aux else None)
        timings: dict = {}
        if single:
            t0 = _time.perf_counter()
            jax.block_until_ready(self.run(zeros, iters, coeffs, aux=aux))
            timings["single"] = _time.perf_counter() - t0
        for b in sorted({int(b) for b in batch_sizes}):
            if b < 1:
                raise ValueError(f"batch sizes must be >= 1, got {b}")
            aux_b = (jnp.zeros((b,) + self.problem.shape,
                               self.problem.jnp_dtype)
                     if self.problem.needs_aux else None)
            t0 = _time.perf_counter()
            jax.block_until_ready(self.run_batch(
                jnp.zeros((b,) + self.problem.state_shape,
                          self.problem.jnp_dtype),
                iters, coeffs, aux=aux_b))
            timings[b] = _time.perf_counter() - t0
        return timings

    # --- introspection ------------------------------------------------------
    def lower(self, grid, iters: int = 1, coeffs=None, *, aux=None,
              batch: bool = False):
        """The ``jax.stages.Lowered`` executable :meth:`run` (or, with
        ``batch``, :meth:`run_batch`) dispatches to for ``grid``, which
        may be an array or a ``jax.ShapeDtypeStruct``.  Its ``compile()``
        is what the chip's compiler makes of the plan; give the specs a
        described TPU's sharding to compile for a chip that is not
        attached."""
        if self._lower is None:
            raise ValueError(f"backend {self.backend!r} has no lowering "
                             "entry point")
        return self._lower(grid, self._coeff_payload(coeffs), iters, aux,
                           batch)

    def predicted(self, iters: Optional[int] = None,
                  device: Optional[Device] = None,
                  batch: int = 1) -> Prediction:
        """Performance-model :class:`Prediction` for this plan (paper §4).

        ``batch > 1`` models :meth:`run_batch`: per-problem traffic and
        compute scale with the batch, while the read-only aux stream (and
        the scalar coefficients) are loaded once for the whole batch."""
        geom = self._require_geometry("predicted()")
        return perf_model.predict(
            self.problem.stencil, self.problem.shape,
            iters if iters is not None else self.config.iters_hint,
            geom.bsize, geom.par_time, device or self.device,
            self.config.resolved_cell_bytes(self.problem.dtype),
            self.n_chips, self.chip_grid,
            batch=batch, bc=self.problem.structural_bc, par_vec=geom.par_vec,
            aligned=bool(geom.align))

    def traffic_report(self, iters: Optional[int] = None) -> dict:
        """Model traffic (paper Eq. 7/8) vs. the Pallas kernels' exact DMA
        schedule — the hardware-free 'model accuracy' of Table 4."""
        from repro.kernels.ops import dma_traffic_bytes
        geom = self._require_geometry("traffic_report()")
        st = self.problem.stencil
        cb = self.config.resolved_cell_bytes(self.problem.dtype)
        bc = self.problem.structural_bc
        # a periodic streaming axis is billed on the extended stream the
        # kernels actually move (the materialized wrap), matching predict()
        geom_t = extended_geometry(geom, bc)
        model = superstep_traffic_bytes(geom_t, st.num_read, st.num_write, cb)
        kernel = dma_traffic_bytes(st, geom, cb, bc=bc)
        report = {
            "model_bytes_per_superstep": model,
            "kernel_dma_bytes_per_superstep": kernel,
            "traffic_accuracy": model / kernel,
            "redundancy": geom.redundancy,
            "par_vec": geom.par_vec,
            "vmem_bytes": geom.vmem_bytes(
                cb, st.has_aux,
                stage_radii=getattr(st, "stage_radii", None),
                dag_info=(st.dag_vmem_info(geom.par_time, geom.par_vec)
                          if hasattr(st, "dag_vmem_info") else None)),
        }
        n_stages = self.problem.n_stages
        if n_stages > 1:
            # fusion accounting: the chained stages' intermediates live only
            # in the rolling VMEM windows — zero HBM round-trip bytes —
            # where S sequential single-stage plans would write and re-read
            # every intermediate once per program iteration
            cells = math.prod(self.problem.shape)
            report["stages"] = [
                {"name": s.name, "radius": s.stencil.radius,
                 "flop_pcu": s.stencil.flop_pcu, "bc": s.boundary.token()}
                for s in self.problem.stages]
            report["intermediate_hbm_bytes_per_superstep"] = 0
            report["unfused_intermediate_bytes_per_superstep"] = (
                2 * (n_stages - 1) * cells * cb * geom.par_time)
        if iters is not None:
            n_super = math.ceil(iters / geom.par_time)
            report["n_super"] = n_super
            report["model_bytes_total"] = model * n_super
            report["kernel_dma_bytes_total"] = kernel * n_super
        return report

    def describe(self) -> str:
        st = self.problem.stencil
        lines = [f"StencilPlan[{self.backend}] {st.name} "
                 f"{self.problem.shape} {self.problem.dtype} "
                 f"bc={self.problem.bc.token()}"]
        if self.problem.n_stages > 1:
            for i, s in enumerate(self.problem.stages):
                lines.append(f"  stage {i}: {s.name} rad={s.stencil.radius} "
                             f"flop_pcu={s.stencil.flop_pcu} "
                             f"bc={s.boundary.token()}")
        if self.geometry is not None:
            g = self.geometry
            lines.append(f"  schedule: bsize={g.bsize} par_time={g.par_time} "
                         f"par_vec={g.par_vec} "
                         f"csize={g.csize} bnum={g.bnum} "
                         f"redundancy={g.redundancy:.3f}")
            lines.append("  predicted: " + self.predicted().describe())
            if self.candidates and isinstance(self.candidates[0],
                                              tuner.TunedCandidate):
                lines.append("  measured:  " + self.candidates[0].describe())
        else:
            lines.append("  schedule: none (unblocked oracle)")
        if self.n_chips > 1:
            lines.append(f"  mesh: {self.n_chips} chips, "
                         f"chip_grid={self.chip_grid}")
        return "\n".join(lines)

    def _require_geometry(self, what: str) -> BlockGeometry:
        if self.geometry is None:
            raise ValueError(f"{what} needs a block geometry; this "
                             f"'{self.backend}' plan was built without a "
                             "feasible (bsize, par_time)")
        return self.geometry
