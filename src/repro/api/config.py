"""Execution configuration — how to run a :class:`StencilProblem`.

``RunConfig`` carries everything the planner needs that is *not* part of the
problem statement: which backend, the (bsize, par_time) schedule (or
``autotune="model"``/``"measure"`` to let the tuner choose), the device model
used for prediction/pruning, the measured-tuning knobs and schedule-cache
location, and the mesh/sharding spec for the distributed backend.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

from repro.core import precision
from repro.core.perf_model import DEVICES, Device, attached_device

#: Accepted ``RunConfig.autotune`` modes (``False`` disables; the legacy
#: booleans are aliases: ``True`` -> ``"model"``).
AUTOTUNE_MODES = ("model", "measure")


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Backend + schedule + placement for one plan.

    ``par_time``/``bsize`` left as ``None`` (or ``autotune`` set) hands the
    choice to the tuner.  ``autotune="model"`` (alias ``True``) ranks
    candidates by the performance model alone (paper §5.3);
    ``autotune="measure"`` takes the model's ``tune_top_k`` shortlist, times
    each candidate on the selected backend (``repro.api.tuner``) and compiles
    the measured winner — consulting/filling the persistent schedule cache
    (``repro.api.schedule_cache``) so the timing cost is paid once per
    (problem, backend, device) key.  Specifying only one of
    ``par_time``/``bsize`` constrains the tuner to configurations matching
    it.

    ``cache``: ``None`` uses the default cache location (the
    ``REPRO_SCHEDULE_CACHE`` env var, else ``~/.cache/repro/schedules.json``);
    a path string overrides it; ``False`` disables persistence entirely.
    """
    backend: str = "engine"
    par_time: Optional[int] = None
    bsize: Optional[Union[int, Tuple[int, ...]]] = None
    #: stream-axis vector width V (rows/planes per kernel tick, paper §3.3).
    #: ``None`` hands the choice to the tuner (sweeping
    #: ``perf_model.PAR_VEC_CANDIDATES``) when autotuning, else defaults to 1.
    par_vec: Optional[int] = None
    autotune: Union[bool, str] = False
    #: the chip the model prices against: ``None`` resolves the attached
    #: chip's ``device_kind`` (:func:`~repro.core.perf_model.attached_device`)
    device: Union[Device, str, None] = None
    #: storage bytes per cell used for traffic/VMEM pricing. ``None`` (the
    #: default) derives it from the problem's storage dtype via
    #: :func:`repro.core.precision.cell_bytes` (4 for f32, 2 for bf16); an
    #: explicit int overrides — see :meth:`resolved_cell_bytes`.
    cell_bytes: Optional[int] = None
    par_time_max: int = 64
    iters_hint: int = 100        # iteration count used for ranking/prediction
    mesh: Optional[object] = None          # jax.sharding.Mesh (distributed)
    axis_map: Optional[Tuple] = None       # grid axis -> mesh axis names
    # --- throughput knobs (serving path) ------------------------------------
    #: let backends donate the *internal* padded super-step carry to XLA
    #: (donate_argnums on the padded grid — never on a caller-visible array,
    #: so plans stay reusable).  Only takes effect on platforms that
    #: implement donation (TPU/GPU); a no-op on CPU.
    donate: bool = True
    #: consult/populate the process-level executable cache
    #: (``repro.api.backends``): plans with the same (stencil fingerprint,
    #: geometry, batch, backend) key share one compiled program instead of
    #: re-tracing.  Disable to force a private executable per plan.
    exec_cache: bool = True
    #: opt-in Megacore parallelism (pallas backends): compile the kernel
    #: grid's block dimension(s) with ``"parallel"`` instead of
    #: ``"arbitrary"`` semantics.  Blocks are independent by construction
    #: (halos are redundantly computed; every block writes a disjoint
    #: compute region), so Mosaic may split them across TensorCores;
    #: results are bit-identical to the sequential grid.
    block_parallel: bool = False
    # --- measured-tuning knobs (autotune="measure") -------------------------
    cache: Union[None, bool, str] = None   # schedule-cache path / False = off
    tune_top_k: int = 4          # model candidates the tuner times
    tune_warmup: int = 1         # untimed runs per candidate (compile+warm)
    tune_repeats: int = 3        # timed runs per candidate (min is kept)
    tune_iters: Optional[int] = None  # iters per timed run (None: 1 super-step)

    def __post_init__(self):
        if isinstance(self.autotune, bool):
            object.__setattr__(self, "autotune",
                               "model" if self.autotune else False)
        elif self.autotune not in AUTOTUNE_MODES:
            raise ValueError(f"autotune must be a bool or one of "
                             f"{AUTOTUNE_MODES}, got {self.autotune!r}")
        if self.tune_top_k < 1:
            raise ValueError(f"tune_top_k must be >= 1, got {self.tune_top_k}")
        if self.tune_warmup < 0 or self.tune_repeats < 1:
            raise ValueError("need tune_warmup >= 0 and tune_repeats >= 1, "
                             f"got {self.tune_warmup}/{self.tune_repeats}")
        if self.tune_iters is not None and self.tune_iters < 1:
            raise ValueError(f"tune_iters must be >= 1, got {self.tune_iters}")
        if self.par_time is not None and self.par_time < 1:
            raise ValueError(f"par_time must be >= 1, got {self.par_time}")
        if self.par_vec is not None and self.par_vec < 1:
            raise ValueError(f"par_vec must be >= 1, got {self.par_vec}")
        if self.bsize is not None and not isinstance(self.bsize, int):
            object.__setattr__(self, "bsize",
                               tuple(int(b) for b in self.bsize))
        if self.axis_map is not None:
            # a bare string is one axis name, not a sequence of characters
            object.__setattr__(
                self, "axis_map",
                tuple((a,) if isinstance(a, str) else tuple(a) if a else None
                      for a in self.axis_map))

    def resolved_cell_bytes(self, dtype="float32") -> int:
        """The cell bytes traffic/VMEM pricing and cache keys use: the
        explicit override when set, else the storage dtype's itemsize."""
        if self.cell_bytes is not None:
            return int(self.cell_bytes)
        return precision.cell_bytes(dtype)

    def resolved_device(self) -> Device:
        if isinstance(self.device, Device):
            return self.device
        if self.device is None:
            return attached_device()
        if self.device not in DEVICES:
            raise ValueError(f"unknown device {self.device!r}; "
                             f"have: {sorted(DEVICES)}")
        return DEVICES[self.device]

    def normalized_bsize(self, ndim: int) -> Optional[Tuple[int, ...]]:
        """bsize as a per-blocked-dim tuple (``ndim - 1`` entries)."""
        if self.bsize is None:
            return None
        if isinstance(self.bsize, int):
            return (self.bsize,) * (ndim - 1)
        if len(self.bsize) != ndim - 1:
            raise ValueError(f"bsize {self.bsize} has {len(self.bsize)} "
                             f"entries; a {ndim}D grid blocks {ndim - 1} dims")
        return self.bsize
