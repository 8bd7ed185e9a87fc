"""Backend protocol + registry + process-level executable cache.

A *backend* is one executable implementation of the combined spatial/temporal
blocked computation.  It is registered as a factory::

    register_backend(name, factory)
    factory(problem: StencilProblem, config: RunConfig,
            geom: BlockGeometry | None) -> ExecuteFn | BackendProgram
    ExecuteFn(grid, coeffs, iters, aux) -> grid

``plan()`` resolves the name through the registry, so adding a backend (GPU
Pallas, batched ensembles, ...) is one ``register_backend`` call — no
if/elif dispatch chain to edit.  A factory may return a bare ``ExecuteFn``
(legacy/custom backends) or a :class:`BackendProgram` that additionally
carries a batched entry point; ``plan()`` normalizes via :func:`as_program`.
The built-ins registered below:

  ``reference``         unblocked oracle (kernels/ref.py) — ground truth
  ``engine``            pure-JAX blocked engine (core/engine.py)
  ``pallas``            Pallas kernels compiled for TPU (kernels/stencil*.py)
  ``pallas_interpret``  same kernels, interpret mode (CPU-correctness)
  ``distributed``       the pallas kernels on every shard of
                        ``config.mesh`` (core/distributed.py), halo strips
                        exchanged once per super-step; compiled on TPU
                        meshes, interpreted elsewhere

Throughput subsystem (the ROADMAP's serving path)
-------------------------------------------------
Every built-in compiles through a **process-level executable cache**: one
compiled program per

    (kind, stencil fingerprint, shape, dtype, geometry, iters-shape class,
     batch size, aux mode, backend specifics)

key, shared by every plan in the process.  ``iters`` is always passed into
the executable as a *dynamic* scalar (iters class ``"dyn"``): the super-step
trip count is computed in-trace, so repeated ``plan().run()`` calls with
different iteration counts — the serving pattern — never re-trace.  This
generalizes the distributed backend's old per-``iters`` compiled dict to all
backends.  ``RunConfig.exec_cache=False`` opts a plan out (it gets private
executables); ``clear_exec_cache()`` resets the process.

Tracing is observable: each cached program bumps ``TRACE_COUNTS[tag]`` when
its Python body is (re)traced, so tests — and operators — can verify that a
cache hit really skipped a trace.

Batched execution (``StencilPlan.run_batch``) compiles ONE executable over a
leading batch axis:

  * reference/engine vmap the fused super-step loop (the blocked update is
    data-parallel across batch members);
  * pallas runs one super-step loop over the batch's padded carry, each
    super-step mapping the kernel over the members *sequentially*
    (``lax.map``, ``kernels/ops._per_member``) — ``vmap`` over the
    manual-DMA kernels silently corrupts the per-block DMA offsets
    (verified), and sequential mapping preserves each kernel instance's
    exact DMA schedule while still amortizing dispatch and compile across
    the batch;
  * distributed replicates the batch axis over the mesh, runs the kernel
    on every member in turn (as pallas does) and aggregates all members'
    halos into one exchange per mesh axis per super-step.

Buffer donation (``RunConfig.donate``): the pallas backends stage an
edge-padded copy of the grid, run the whole super-step loop on it, and slice
once at the end — the padded carry is backend-owned, so it is donated to XLA
(``donate_argnums``) and reused in place across the loop.  Caller arrays are
never donated: a plan stays reusable and ``run``/``run_batch`` never
invalidate their inputs.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, Optional, Protocol, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.blocking import BlockGeometry
from repro.api.config import RunConfig
from repro.api.problem import StencilProblem
from repro.resilience.faults import (corrupt_point, fault_point,
                                     register_point)

#: (grid, coeffs, iters, aux) -> final grid
ExecuteFn = Callable[..., jnp.ndarray]

# --- fault-injection seams (repro.resilience; no-ops with no plan active) ----
FP_EXECUTE = register_point(
    "backend.execute", "before any backend's single-grid execute")
FP_EXECUTE_RESULT = register_point(
    "backend.execute.result", "a single-grid result passes through "
    "(action='nan' poisons it)")
FP_EXECUTE_BATCH = register_point(
    "backend.execute_batch", "before any backend's batched execute")
FP_EXECUTE_BATCH_RESULT = register_point(
    "backend.execute_batch.result", "a batched result passes through "
    "(action='nan' + member=i poisons one member)")
FP_EXEC_CACHE = register_point(
    "exec_cache.get", "on every process-level executable-cache lookup")

#: dtypes the Pallas streaming kernels support (plan-time validation):
#: f32, and bf16 storage with f32 accumulation inside the PE chain — see
#: ``repro.core.precision`` for the policy and ``kernels/builder.py`` for
#: the window-read / output-DMA casts that implement it
PALLAS_SUPPORTED_DTYPES = ("float32", "bfloat16")


class Backend(Protocol):
    """Factory protocol every registered backend implements."""

    def __call__(self, problem: StencilProblem, config: RunConfig,
                 geom: Optional[BlockGeometry]
                 ) -> Union[ExecuteFn, "BackendProgram"]:
        ...


@dataclasses.dataclass
class BackendProgram:
    """What a backend factory hands ``plan()``: the unbatched entry point,
    plus (optionally) a batched one.

    ``execute_batch(grids, coeffs, iters, aux)`` takes grids with a leading
    batch axis ``(B, *shape)``; ``aux`` may be ``None``, one shared grid of
    ``shape``, or a batch of ``(B, *shape)``.  Backends that do not provide
    it (``execute_batch=None``) still serve ``StencilPlan.run_batch`` via a
    per-element fallback loop.

    ``lower(grid, coeffs, iters, aux, batch)`` (optional) lowers the
    executable ``execute`` (or, with ``batch``, ``execute_batch``) would
    dispatch to, for arrays or ``jax.ShapeDtypeStruct`` specs — the
    ``jax.stages.Lowered`` whose ``compile()`` shows the chip's HLO."""
    execute: ExecuteFn
    execute_batch: Optional[ExecuteFn] = None
    lower: Optional[Callable] = None


def as_program(obj: Union[ExecuteFn, BackendProgram]) -> BackendProgram:
    """Normalize a factory's return value (bare callable or program), and
    thread the resilience seams through it: every backend — built-in or
    custom-registered — gets the ``backend.execute*`` injection points for
    free, so the whole failure matrix is testable against any of them."""
    if isinstance(obj, BackendProgram):
        program = obj
    elif callable(obj):
        program = BackendProgram(execute=obj)
    else:
        raise TypeError(f"backend factory returned {type(obj).__name__}; "
                        "expected a callable or BackendProgram")
    return _instrument(program)


def _instrument(program: BackendProgram) -> BackendProgram:
    """Wrap the entry points with their fault seams (idempotent)."""
    if getattr(program.execute, "_fault_instrumented", False):
        return program
    inner, inner_batch = program.execute, program.execute_batch

    def execute(grid, coeffs, iters, aux=None):
        fault_point(FP_EXECUTE)
        return corrupt_point(FP_EXECUTE_RESULT,
                             inner(grid, coeffs, iters, aux))
    execute._fault_instrumented = True

    execute_batch = None
    if inner_batch is not None:
        def execute_batch(grids, coeffs, iters, aux=None):
            fault_point(FP_EXECUTE_BATCH, {"batch": grids.shape[0]})
            return corrupt_point(FP_EXECUTE_BATCH_RESULT,
                                 inner_batch(grids, coeffs, iters, aux),
                                 {"batch": grids.shape[0]})
        execute_batch._fault_instrumented = True

    return BackendProgram(execute=execute, execute_batch=execute_batch,
                          lower=program.lower)


_REGISTRY: Dict[str, Backend] = {}


def register_backend(name: str, factory: Backend, *,
                     overwrite: bool = False) -> None:
    """Register ``factory`` under ``name`` for use as ``RunConfig.backend``."""
    if not callable(factory):
        raise TypeError(f"backend factory for {name!r} is not callable")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[name] = factory


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; "
                         f"registered: {list_backends()}") from None


def list_backends() -> list:
    return sorted(_REGISTRY)


# --- process-level executable cache ------------------------------------------

_EXEC_CACHE: Dict[tuple, Callable] = {}
_EXEC_STATS = {"hits": 0, "misses": 0}
#: per-key hit/miss breakdown — the global totals cannot distinguish "one
#: hot executable" from "N executables each compiled once" (batch-fill vs
#: cache-thrash); this can, and the serving metrics snapshot exports it
_EXEC_KEY_STATS: Dict[tuple, Dict[str, int]] = {}

#: how many times each cached program's Python body was (re)traced — the
#: observable proof that an executable-cache hit skipped a re-trace
TRACE_COUNTS: "collections.Counter[str]" = collections.Counter()


def _note_trace(tag: str) -> None:
    """Called from *inside* a to-be-jitted body: runs once per trace, never
    per execution, so it counts exactly the re-traces."""
    TRACE_COUNTS[tag] += 1


def _key_str(key: tuple) -> str:
    """Human-scannable rendering of an executable-cache key for reports
    (the raw tuple mixes nested tuples and tagged strings)."""
    return " ".join(str(part) for part in key)


def exec_cache_stats() -> dict:
    """Executable-cache observability: entry count, hit/miss totals, the
    per-backend trace counts, and the per-key hit/miss breakdown
    (``by_key``) — so a metrics snapshot can tell a saturated hot program
    from a thrashing key population."""
    return {"size": len(_EXEC_CACHE), "hits": _EXEC_STATS["hits"],
            "misses": _EXEC_STATS["misses"], "traces": dict(TRACE_COUNTS),
            "by_key": {_key_str(k): dict(v)
                       for k, v in _EXEC_KEY_STATS.items()}}


def clear_exec_cache() -> None:
    """Drop every cached executable and reset the counters (tests; or to
    release compiled programs in a long-lived process)."""
    _EXEC_CACHE.clear()
    _EXEC_STATS["hits"] = 0
    _EXEC_STATS["misses"] = 0
    _EXEC_KEY_STATS.clear()
    TRACE_COUNTS.clear()


def _program_cache(use_cache: bool) -> Callable:
    """Program lookup for one factory: the process-level cache when enabled,
    else a private per-plan dict — an opted-out plan gets executables no
    other plan can see, but must still never rebuild (re-trace) one on every
    call."""
    if use_cache:
        def get(key, build):
            fault_point(FP_EXEC_CACHE, {"key": key})
            per_key = _EXEC_KEY_STATS.setdefault(
                key, {"hits": 0, "misses": 0})
            fn = _EXEC_CACHE.get(key)
            if fn is None:
                _EXEC_STATS["misses"] += 1
                per_key["misses"] += 1
                fn = _EXEC_CACHE[key] = build()
            else:
                _EXEC_STATS["hits"] += 1
                per_key["hits"] += 1
            return fn
    else:
        local: Dict[tuple, Callable] = {}

        def get(key, build):
            fn = local.get(key)
            if fn is None:
                fn = local[key] = build()
            return fn
    return get


def _exec_key(kind: str, problem: StencilProblem,
              geom: Optional[BlockGeometry], *,
              batch=None, aux_mode=None, extra: Tuple = ()) -> tuple:
    """Cache key: everything that determines the compiled program.

    ``iters`` never appears — every program takes it as a dynamic scalar
    (iters-shape class ``"dyn"``), which is exactly what makes the cache
    worth having for serving loops."""
    from repro.api.schedule_cache import stencil_fingerprint
    # par_vec changes the compiled kernel's window layout, DMA schedule and
    # stream padding: a V=8 executable must never serve a V=1 plan
    gsig = (None if geom is None
            else (geom.par_time, geom.bsize, geom.par_vec))
    # the BC changes the compiled program (pad modes, re-imposition tables,
    # the periodic stream extension): it MUST split the cache key, or a
    # clamp-compiled program would serve a periodic plan
    return (kind, problem.stencil.name, stencil_fingerprint(problem.stencil),
            problem.shape, problem.dtype, f"bc={problem.bc.token()}", gsig,
            "iters=dyn", batch, aux_mode, *extra)


def _aux_mode(problem: StencilProblem, aux) -> Optional[str]:
    """``None`` (no aux) | ``"shared"`` (one grid) | ``"batched"`` (B grids).
    The plan validates shapes before execution; this only classifies."""
    if aux is None:
        return None
    return "batched" if aux.ndim == problem.ndim + 1 else "shared"


def _donate_ok(config: RunConfig) -> bool:
    """Donation is requested AND the platform implements it (CPU does not —
    donating there only emits warnings)."""
    return config.donate and jax.default_backend() in ("tpu", "gpu")


# --- built-in backends -------------------------------------------------------

def _vmapped_program(kind: str, problem, config, key_geom,
                     body: Callable) -> BackendProgram:
    """Shared scaffolding for backends whose batched form is a vmap of the
    single-grid ``body(grid, coeffs, iters, aux)``: reference (unblocked
    oracle) and engine (fused blocked loop)."""
    get = _program_cache(config.exec_cache)
    single = get(_exec_key(kind, problem, key_geom), lambda: jax.jit(body))

    def execute(grid, coeffs, iters, aux=None):
        return single(grid, coeffs, jnp.asarray(iters, jnp.int32), aux)

    def execute_batch(grids, coeffs, iters, aux=None):
        mode = _aux_mode(problem, aux)
        key = _exec_key(kind, problem, key_geom,
                        batch=grids.shape[0], aux_mode=mode)
        fn = get(key, lambda: jax.jit(jax.vmap(
            body, in_axes=(0, None, None, 0 if mode == "batched" else None))))
        return fn(grids, coeffs, jnp.asarray(iters, jnp.int32), aux)

    return BackendProgram(execute, execute_batch)


def _dag_coeffs(coeffs):
    """Normalize the plan's coefficient payload for the DAG executors: a
    single-stage DAG program gets a bare dict from ``_coeff_payload`` (the
    legacy contract) — the executors always take one dict per stage."""
    return coeffs if isinstance(coeffs, tuple) else (coeffs,)


def _reference_backend(problem, config, geom):
    if problem.is_dag:
        from repro.kernels.ref import oracle_dag_run
        dag = problem.exec_dag

        def body(grid, coeffs, iters, aux):
            _note_trace("reference")
            return oracle_dag_run(dag, grid, _dag_coeffs(coeffs), iters, aux)
    elif problem.n_stages > 1:
        from repro.kernels.ref import oracle_program_run
        stages = problem.exec_stages

        def body(grid, coeffs, iters, aux):
            _note_trace("reference")
            return oracle_program_run(stages, grid, coeffs, iters, aux)
    else:
        from repro.kernels.ref import oracle_run
        st, bc = problem.exec_stages[0]

        def body(grid, coeffs, iters, aux):
            _note_trace("reference")
            return oracle_run(st, grid, coeffs, iters, aux, bc=bc)

    # the oracle ignores blocking: key by problem only, not geometry
    return _vmapped_program("reference", problem, config, None, body)


def _engine_backend(problem, config, geom):
    if problem.is_dag:
        from repro.core.engine import superstep_loop_dag
        dag = problem.exec_dag

        def body(grid, coeffs, iters, aux):
            _note_trace("engine")
            return superstep_loop_dag(dag, geom, grid, _dag_coeffs(coeffs),
                                      iters, aux)
    elif problem.n_stages > 1:
        from repro.core.engine import superstep_loop_chain
        stages = problem.exec_stages

        def body(grid, coeffs, iters, aux):
            _note_trace("engine")
            return superstep_loop_chain(stages, geom, grid, coeffs, iters,
                                        aux)
    else:
        from repro.core.engine import superstep_loop
        st, bc = problem.exec_stages[0]

        def body(grid, coeffs, iters, aux):
            _note_trace("engine")
            return superstep_loop(st, geom, grid, coeffs, iters, aux, bc=bc)

    return _vmapped_program("engine", problem, config, geom, body)


def check_pallas_dtype(problem: StencilProblem) -> None:
    """Plan-time validation: fail before any execute (or any geometry is
    sized for the dtype's tiles), and say what IS supported."""
    if problem.dtype not in PALLAS_SUPPORTED_DTYPES:
        raise ValueError(
            f"the Pallas kernels support dtypes "
            f"{list(PALLAS_SUPPORTED_DTYPES)}; "
            f"got problem.dtype={problem.dtype!r} — use the 'engine' or "
            f"'reference' backend for other dtypes")


def _make_pallas_backend(force_interpret: bool):
    def factory(problem, config, geom):
        from repro.kernels.ops import (fused_chain_loop, fused_dag_loop,
                                       fused_superstep_loop, pack_coeffs,
                                       pack_dag_coeffs, pack_program_coeffs,
                                       _pad_blocked)
        bc = problem.structural_bc   # sizes padding + the stream extension
        interpret = force_interpret
        tag = "pallas_interpret" if interpret else "pallas"
        get = _program_cache(config.exec_cache)
        donate = _donate_ok(config)
        # Megacore opt-in recompiles the kernel grid's dimension semantics:
        # it must split the executable cache alongside donation
        mc = config.block_parallel
        extra = ("donate", donate, "mc", mc)

        if problem.is_dag:
            dag = problem.exec_dag

            def run_loop(gp, coeffs_packed, iters, aux_p):
                return fused_dag_loop(dag, geom, gp, coeffs_packed,
                                      iters, aux_p, interpret,
                                      block_parallel=mc)

            def pack(coeffs):
                return pack_dag_coeffs(dag, _dag_coeffs(coeffs))
        elif problem.n_stages > 1:
            stages = problem.exec_stages

            def run_loop(gp, coeffs_packed, iters, aux_p):
                return fused_chain_loop(stages, geom, gp, coeffs_packed,
                                        iters, aux_p, interpret,
                                        block_parallel=mc)

            def pack(coeffs):
                return pack_program_coeffs(stages, coeffs)
        else:
            st, bc1 = problem.exec_stages[0]

            def run_loop(gp, coeffs_packed, iters, aux_p):
                return fused_superstep_loop(st, geom, gp, coeffs_packed,
                                            iters, aux_p, interpret, bc1,
                                            block_parallel=mc)

            def pack(coeffs):
                return pack_coeffs(st, coeffs)

        def loop_body(gp, coeffs_packed, iters, aux_p):
            # gp is the backend-owned padded carry: safe to donate
            _note_trace(tag)
            return run_loop(gp, coeffs_packed, iters, aux_p)

        def build_single():
            return jax.jit(loop_body,
                           donate_argnums=(0,) if donate else ())

        single = get(_exec_key(tag, problem, geom, extra=extra),
                     build_single)

        def execute(grid, coeffs, iters, aux=None):
            gp = _pad_blocked(grid, geom, bc)
            aux_p = _pad_blocked(aux, geom, bc) if aux is not None else None
            return single(gp, pack(coeffs),
                          jnp.asarray(iters, jnp.int32), aux_p)

        def execute_batch(grids, coeffs, iters, aux=None):
            mode = _aux_mode(problem, aux)
            key = _exec_key(tag, problem, geom, batch=grids.shape[0],
                            aux_mode=mode, extra=extra)
            # one loop over the batch's padded carry: each super-step runs
            # the kernel on every member in turn and refreshes all members'
            # strips at once
            fn = get(key, build_single)
            gps = _pad_blocked(grids, geom, bc)
            aux_p = _pad_blocked(aux, geom, bc) if aux is not None else None
            return fn(gps, pack(coeffs),
                      jnp.asarray(iters, jnp.int32), aux_p)

        def lower(grid, coeffs, iters, aux=None, batch=False):
            # shapes only, all placed where ``grid`` is (a described chip's
            # sharding lowers for that chip without one attached)
            where = getattr(grid, "sharding", None)

            def spec(x, f=None):
                out = jax.eval_shape(f, x) if f else x
                return jax.ShapeDtypeStruct(out.shape, out.dtype,
                                            sharding=where)
            pad = lambda x: _pad_blocked(x, geom, bc)  # noqa: E731
            if batch:
                mode = _aux_mode(problem, aux)
                fn = get(_exec_key(tag, problem, geom, batch=grid.shape[0],
                                   aux_mode=mode, extra=extra),
                         build_single)
            else:
                fn = single
            return fn.lower(spec(grid, pad), spec(pack(coeffs)),
                            jax.ShapeDtypeStruct((), jnp.int32,
                                                 sharding=where),
                            None if aux is None else spec(aux, pad))

        return BackendProgram(execute, execute_batch, lower)
    return factory


def resolve_axis_map(problem: StencilProblem, config: RunConfig):
    """The grid-axis -> mesh-axes decomposition the distributed backend uses.

    Default when ``config.axis_map`` is unset: shard the streaming axis over
    every mesh axis, replicate the blocked axes."""
    if config.mesh is None:
        raise ValueError("backend='distributed' needs config.mesh "
                         "(and optionally config.axis_map)")
    if config.axis_map is not None:
        if len(config.axis_map) != problem.ndim:
            raise ValueError(f"axis_map {config.axis_map} must have one entry "
                             f"per grid axis ({problem.ndim})")
        return config.axis_map
    return (tuple(config.mesh.axis_names),) + (None,) * (problem.ndim - 1)


def _mesh_sig(mesh) -> tuple:
    """Mesh identity for the executable cache.  Structure alone is not enough
    (two same-shape meshes over different devices need different programs),
    so the object id is included — at worst an id reuse costs a re-build,
    never a wrong-mesh program, because the id is paired with structure."""
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape), id(mesh))


def _distributed_backend(problem, config, geom):
    from repro.core.distributed import build_distributed_fn
    st = problem.stencil
    mesh = config.mesh
    axis_map = resolve_axis_map(problem, config)
    get = _program_cache(config.exec_cache)
    mc = config.block_parallel
    base_key = ("mesh", _mesh_sig(mesh), "amap", axis_map, "mc", mc)

    def build(batch, aux_batched):
        return build_distributed_fn(
            st, problem.shape, None, geom.par_time, geom.bsize, mesh,
            axis_map, batch=batch, aux_batched=aux_batched,
            trace_hook=lambda: _note_trace("distributed"),
            bc=problem.structural_bc,
            stages=(problem.exec_stages
                    if problem.n_stages > 1 and not problem.is_dag else None),
            dag=problem.exec_dag if problem.is_dag else None,
            par_vec=geom.par_vec, align=geom.align, block_parallel=mc)

    def program(batch, aux):
        # built lazily on first call (not at plan time): plan() must stay
        # executable-free for the distributed backend so schedulers can plan
        # against a mesh description without touching real devices
        if not batch:
            return get(_exec_key("distributed", problem, geom,
                                 extra=base_key),
                       lambda: build(False, False))
        mode = _aux_mode(problem, aux)
        return get(_exec_key("distributed", problem, geom, batch=batch,
                             aux_mode=mode, extra=base_key),
                   lambda: build(True, mode == "batched"))

    def args(grid, coeffs, iters, aux):
        aux_in = aux if aux is not None else jnp.zeros((), jnp.float32)
        return grid, aux_in, coeffs, jnp.asarray(iters, jnp.int32)

    def execute(grid, coeffs, iters, aux=None):
        return program(None, aux)(*args(grid, coeffs, iters, aux))

    def execute_batch(grids, coeffs, iters, aux=None):
        return program(grids.shape[0], aux)(*args(grids, coeffs, iters, aux))

    def lower(grid, coeffs, iters, aux=None, batch=False):
        fn = program(grid.shape[0] if batch else None, aux)
        return fn.lower(*args(grid, coeffs, iters, aux))

    return BackendProgram(execute, execute_batch, lower)


register_backend("reference", _reference_backend)
register_backend("engine", _engine_backend)
register_backend("pallas", _make_pallas_backend(force_interpret=False))
register_backend("pallas_interpret", _make_pallas_backend(force_interpret=True))
register_backend("distributed", _distributed_backend)
