"""Measured autotuning — time the model's shortlist, compile the winner.

The paper tunes ``(bsize, par_time)`` from its performance model alone
(§4, §5.3).  That ranking is only as good as the model, so
``RunConfig(autotune="measure")`` closes the loop the way Table 4 does for
the FPGA boards: take the model's ``tune_top_k`` best candidates, run each on
the *selected backend* with a small warm-up + timed-repeat harness, and keep
the one that is actually fastest.  Each candidate records its measured
seconds and the paper's "model accuracy" (estimated/measured time, §6.2) —
``StencilPlan.candidates`` then reads like a Table 4 row.

Timing protocol (per candidate): ``tune_warmup`` untimed executions absorb
compilation and cache warming, then ``tune_repeats`` timed executions of
``tune_iters`` iterations (rounded up to whole super-steps — a partial
super-step costs the same as a full one and would skew deep-``par_time``
candidates cheap) and the *minimum* is kept — the standard low-noise
estimator for a deterministic kernel.  Measurements are normalized to
seconds per super-step; candidates are ranked by *amortized per-iteration*
time (``measured_s / par_time``), the steady-state metric that does not
depend on any particular run's iteration count — which is what lets the
schedule cache serve one winner to runs of every length.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional, Sequence, Tuple

import jax

from repro.api.backends import as_program, get_backend
from repro.api.config import RunConfig
from repro.api.problem import StencilProblem
from repro.core import perf_model
from repro.core.perf_model import Prediction
from repro.data import make_stencil_inputs


@dataclasses.dataclass(frozen=True)
class TunedCandidate:
    """One measured schedule: the model's view plus the stopwatch's."""
    prediction: Prediction
    measured_s: float          # seconds per super-step (min over repeats)
    measured_run_time: float   # extrapolated seconds at iters_hint
    model_accuracy: float      # paper §6.2: estimated / measured time
    from_cache: bool = False   # True when served by the schedule cache

    @property
    def geom(self):
        return self.prediction.geom

    @property
    def s_per_iter(self) -> float:
        """Amortized seconds per time-step — the (iters-independent) metric
        candidates are ranked by."""
        return self.measured_s / self.geom.par_time

    def describe(self) -> str:
        src = "cache" if self.from_cache else "measured"
        return (f"bsize={self.geom.bsize} par_time={self.geom.par_time} "
                f"-> {self.measured_s * 1e3:.3f} ms/super ({src}, "
                f"model_accuracy={self.model_accuracy:.3g})")


def _time_once(execute, grid, coeffs, iters: int, aux) -> float:
    t0 = time.perf_counter()
    out = execute(grid, coeffs, iters, aux)
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def measure_candidate(problem: StencilProblem, config: RunConfig,
                      prediction: Prediction, grid, coeffs, aux) -> TunedCandidate:
    """Time one candidate schedule on the configured backend."""
    geom = prediction.geom
    factory = get_backend(config.backend)
    execute = as_program(factory(problem, config, geom)).execute
    # time whole super-steps: a partial one costs the same as a full one
    # (PE forwarding) and would under-bill deep-par_time candidates
    n_super = math.ceil((config.tune_iters or 1) / geom.par_time)
    iters = n_super * geom.par_time
    for _ in range(config.tune_warmup):
        _time_once(execute, grid, coeffs, iters, aux)
    best = min(_time_once(execute, grid, coeffs, iters, aux)
               for _ in range(config.tune_repeats))
    per_super = best / n_super
    run_time = per_super * prediction.n_super
    return TunedCandidate(
        prediction=prediction, measured_s=per_super,
        measured_run_time=run_time,
        model_accuracy=perf_model.model_accuracy(run_time, prediction))


def measure_candidates(problem: StencilProblem, config: RunConfig,
                       predictions: Sequence[Prediction],
                       ) -> Tuple[TunedCandidate, ...]:
    """Time every candidate; return them ranked by amortized per-iteration
    measured time (steady-state fastest first)."""
    # the payload the backends take: one coefficient dict per stage
    coeffs = problem.resolve_coeffs(dtype=problem.jnp_dtype)
    grid, aux = make_stencil_inputs(jax.random.PRNGKey(0), problem.shape,
                                    problem.needs_aux)
    grid = grid.astype(problem.jnp_dtype)
    if aux is not None:
        aux = aux.astype(problem.jnp_dtype)
    tuned = [measure_candidate(problem, config, p, grid, coeffs, aux)
             for p in predictions]
    tuned.sort(key=lambda c: c.s_per_iter)
    return tuple(tuned)


def tune(problem: StencilProblem, config: Optional[RunConfig] = None,
         **overrides) -> "repro.api.plan.StencilPlan":  # noqa: F821
    """Measured-autotune ``problem`` and return the compiled plan.

    Sugar for ``plan(problem, replace(config, autotune="measure"))``: the
    returned ``StencilPlan.candidates`` carry per-candidate measured seconds
    and model accuracy (the paper's Table 4 columns), and the winner is
    persisted to the schedule cache unless ``cache=False``.
    """
    from repro.api.plan import plan    # circular at module load, not at call
    overrides.pop("autotune", None)    # redundant autotune= kwarg is harmless
    config = dataclasses.replace(config or RunConfig(),
                                 autotune="measure", **overrides)
    return plan(problem, config)
