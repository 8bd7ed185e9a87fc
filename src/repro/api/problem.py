"""Declarative problem description — what to compute, not how.

``StencilProblem`` is the immutable front half of the two-phase workflow the
paper prescribes (§4, §5.3): describe the computation once, then let
``repro.api.plan`` pair it with a :class:`~repro.api.config.RunConfig` to
produce an executable :class:`~repro.api.plan.StencilPlan`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax.numpy as jnp

from repro.core import precision
from repro.core.boundary import BCSpec, BoundaryCondition
from repro.core.stencils import STENCILS, Stencil, default_coeffs
from repro.programs import StencilProgram, StencilStage

#: Supported boundary-condition kinds (per axis, mixable).  The paper (§5.1)
#: clamps every out-of-bound neighbor to the boundary cell (edge
#: replication); the other kinds open the ROADMAP's PDE/wave/periodic-domain
#: workloads — see ``repro.core.boundary``.
BOUNDARIES = ("clamp", "periodic", "reflect", "constant")


@dataclasses.dataclass(frozen=True)
class StencilProblem:
    """An iterated-stencil computation on a fixed grid.

    Parameters
    ----------
    stencil:
        A :class:`~repro.core.stencils.Stencil`, the name of one of the
        registered paper stencils (``"diffusion2d"``, ``"hotspot3d"``, ...),
        or a multi-stage program: a
        :class:`~repro.programs.StencilProgram`, a
        :class:`~repro.programs.StencilStage`, or a sequence of
        stage-likes.  A program's stages run in order each iteration; the
        fused backends keep every intermediate on-chip.  For a single plain
        stage this field normalizes to the bare ``Stencil`` (legacy
        behavior); for programs it holds the resolved
        ``StencilProgram``, which duck-types the ``Stencil`` bookkeeping
        (``radius`` = sum of stage radii, etc.).  :attr:`program` always
        exposes the resolved program form.
    shape:
        Grid extents, streaming axis first (``(ny, nx)`` / ``(nz, ny, nx)``).
    dtype:
        Cell dtype (normalized to a canonical string; f32 is the paper's).
    boundary:
        Boundary condition: a kind name applied to every axis (``"clamp"``,
        ``"periodic"``, ``"reflect"``, ``"constant"`` / ``"constant:VALUE"``),
        a per-axis sequence mixing kinds (streaming axis first, e.g.
        ``("clamp", "periodic")``), or a
        :class:`~repro.core.boundary.BoundaryCondition`.  Normalized to a
        ``BoundaryCondition`` (also exposed as :attr:`bc`).  Default: the
        paper's clamp (§5.1).
    aux:
        Auxiliary-input spec: ``None`` inherits ``stencil.has_aux`` (Hotspot's
        ``power`` grid); an explicit bool must agree with the stencil.
    """
    stencil: Union[Stencil, str, StencilProgram, StencilStage, Sequence]
    shape: Tuple[int, ...]
    dtype: str = "float32"
    boundary: BCSpec = "clamp"
    aux: Optional[bool] = None

    def __post_init__(self):
        st = self.stencil
        if isinstance(st, str):
            if st not in STENCILS:
                raise ValueError(f"unknown stencil {st!r}; "
                                 f"registered: {sorted(STENCILS)}")
            st = STENCILS[st]
        elif not isinstance(st, Stencil):
            # program forms: StencilProgram | StencilStage | sequence
            st = StencilProgram.make(st)
        shape = tuple(int(d) for d in self.shape)
        object.__setattr__(self, "shape", shape)
        if len(shape) != st.ndim:
            raise ValueError(f"{st.name} is {st.ndim}D but shape={shape}")
        if any(d < 1 for d in shape):
            raise ValueError(f"non-positive grid extent in {shape}")
        bc = BoundaryCondition.make(self.boundary, st.ndim)
        bc.validate_shape(shape)
        object.__setattr__(self, "boundary", bc)
        if isinstance(st, StencilProgram):
            # resolve per-stage BCs against the problem default + shape
            program = st.resolved(bc, shape)
            if (len(program) == 1 and program.is_linear
                    and program.stages[0].coeffs is None
                    and program.stages[0].boundary == bc):
                # a plain single stage IS the legacy problem — normalize
                # `stencil` back to the bare Stencil (exact old behavior,
                # cache keys included)
                st = program.stages[0].stencil
            else:
                st = program
        else:
            program = StencilProgram((StencilStage(st, boundary=bc),))
        object.__setattr__(self, "stencil", st)
        object.__setattr__(self, "_program", program)
        # accept np.dtype / jnp.bfloat16 / "bf16" / string forms uniformly
        object.__setattr__(self, "dtype", precision.normalize_dtype(self.dtype))
        if self.aux is not None and bool(self.aux) != st.has_aux:
            raise ValueError(
                f"aux={self.aux} conflicts with {st.name} "
                f"(stencil.has_aux={st.has_aux})")

    @property
    def bc(self) -> BoundaryCondition:
        """The normalized per-axis boundary condition (the problem-level
        default; stages may override the local kinds — see
        :attr:`structural_bc`)."""
        return self.boundary

    @property
    def program(self) -> StencilProgram:
        """The resolved program form: every problem is a (possibly
        single-stage) chain with per-stage ``BoundaryCondition``s."""
        return self._program

    @property
    def stages(self) -> Tuple[StencilStage, ...]:
        return self._program.stages

    @property
    def n_stages(self) -> int:
        return len(self._program)

    @property
    def is_program(self) -> bool:
        """True when the problem carries more than the legacy bare stencil:
        multiple stages, or a single stage with coeff/BC overrides."""
        return isinstance(self.stencil, StencilProgram)

    @property
    def exec_stages(self) -> Tuple[Tuple[Stencil, BoundaryCondition], ...]:
        """The static ``((stencil, bc), ...)`` tuple of the stages, in
        authored order; for a linear program :attr:`exec_dag` is
        ``chain_dag(exec_stages)``."""
        return tuple((s.stencil, s.boundary) for s in self.stages)

    @property
    def structural_bc(self) -> BoundaryCondition:
        """Stage 0's BC — what sizes padding, the periodic stream extension
        and the halo exchange (per-axis periodicity is uniform across
        stages; equals :attr:`bc` for non-program problems)."""
        return self.stages[0].boundary

    @property
    def is_dag(self) -> bool:
        """True when the program is a general DAG (multi-field state, fan-in/
        fan-out, or non-default wiring) rather than a linear chain.  Every
        backend runs both as :attr:`exec_dag`; the kernel builder unrolls a
        chain with per-entry PE-forwarding selects and a general DAG with
        per-field state nodes."""
        return not self._program.is_linear

    @property
    def fields(self) -> Tuple[str, ...]:
        """The program's external field names (``("u",)`` for plain
        problems)."""
        return self._program.fields

    @property
    def state_shape(self) -> Tuple[int, ...]:
        """Shape of the array ``run()`` takes: the plain grid ``shape`` for
        single-field problems, ``(n_fields, *shape)`` for multi-field
        programs (field axis leading, fields in declaration order)."""
        F = len(self._program.fields)
        return ((F,) + self.shape) if F > 1 else self.shape

    @property
    def exec_dag(self):
        """The resolved program's static :class:`~repro.programs.DagSpec` —
        what every executor (oracle / engine / kernel builder /
        distributed) takes."""
        return self._program.dag

    def resolve_coeffs(self, coeffs=None, dtype=None) -> Tuple[dict, ...]:
        """Per-stage coefficient dicts: stencil defaults, overlaid with each
        stage's static overrides, overlaid with run-time ``coeffs`` —
        a single dict (applied to the only stage) for single-stage problems,
        or a sequence of per-stage dicts/None for programs.  Unknown names
        are rejected."""
        if coeffs is None:
            per_stage = (None,) * self.n_stages
        elif isinstance(coeffs, dict):
            if self.n_stages > 1:
                raise ValueError(
                    f"{self.stencil.name} has {self.n_stages} stages: pass "
                    "coeffs as a sequence of per-stage dicts (None entries "
                    "keep that stage's defaults), not a single dict")
            per_stage = (coeffs,)
        else:
            per_stage = tuple(coeffs)
            if len(per_stage) != self.n_stages:
                raise ValueError(
                    f"got {len(per_stage)} coefficient dicts for "
                    f"{self.n_stages} stages")
        out = []
        for stage, run_c in zip(self.stages, per_stage):
            merged = dict(default_coeffs(stage.stencil, dtype)
                          if dtype is not None
                          else default_coeffs(stage.stencil))
            if stage.coeffs:
                merged.update(stage.coeffs)
            if run_c:
                unknown = [k for k in run_c if k not in merged]
                if unknown:
                    raise ValueError(
                        f"unknown coefficients {unknown} for stage "
                        f"{stage.name} (has {list(stage.stencil.coeff_names)})")
                merged.update(run_c)
            out.append(merged)
        return tuple(out)

    @property
    def ndim(self) -> int:
        return self.stencil.ndim

    @property
    def needs_aux(self) -> bool:
        return self.stencil.has_aux if self.aux is None else bool(self.aux)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def cell_bytes(self) -> int:
        """Storage bytes per cell — what HBM/halo traffic scales with
        (2 for bf16, 4 for f32)."""
        return precision.cell_bytes(self.dtype)

    @property
    def accum_dtype(self):
        """The dtype stage arithmetic runs in: f32 for sub-32-bit float
        storage (bf16), the storage dtype itself otherwise.  See
        ``repro.core.precision``."""
        return precision.accum_dtype(self.dtype)
