"""The program's device scopes in a trace of a cell on a mesh.

As ``perfbench/scopes.py`` does for one chip: the chip's trace carries no
``op_name``, so the cell's mesh program (``backend="distributed"``) is
lowered and compiled again for the chips the window ran on, which gives the
same instructions under the same names, and each instruction's ``op_name``
is read from that HLO.

A program that names no ``stencil.halo_exchange`` (one whose exchange
carries no scope) gives no reading: :func:`loop_scope_us` returns ``None``.
"""
from __future__ import annotations

import sys
import traceback

from perfbench.scopes import KERNEL, LOOP, RELOWER, hlo_op_names, scope
from perfbench.trace import base_name, clip, short_name


def program_op_names(cell) -> dict:
    """Instruction name -> ``op_name`` of the cell's mesh program, lowered
    and compiled again for the mesh the window ran on (as the solve driver
    builds it)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.api import RunConfig, StencilProblem, plan
    cfg = cell.config
    m = cfg["mesh"]
    shape = tuple(int(d) for d in cell.traffic["grid"])
    problem = StencilProblem(cfg["stencil"], shape, dtype=cfg["dtype"],
                             boundary=cfg["boundary"])
    mesh = jax.make_mesh(tuple(m["shape"]), tuple(m["axes"]),
                         devices=jax.devices()[:cfg["chips"]],
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(m["axes"]))
    where = NamedSharding(mesh, PartitionSpec(*m["axes"]))

    def spec(s):
        return jax.ShapeDtypeStruct(s, problem.jnp_dtype, sharding=where)
    try:
        from repro.tracing import span
    except ImportError:
        from contextlib import nullcontext as span
    with span(RELOWER):
        p = plan(problem, RunConfig(
            backend=cfg["backend"], autotune=cfg["autotune"], mesh=mesh,
            axis_map=tuple(tuple(a) for a in m["axis_map"])))
        lowered = p.lower(spec(problem.state_shape),
                          aux=spec(shape) if problem.needs_aux else None)
        return hlo_op_names(lowered.compile().as_text())


def _op_names(cell) -> dict:
    """:func:`program_op_names`, once per cell; empty where the program
    cannot be compiled again."""
    names = getattr(cell, "mesh_op_names", None)
    if names is None:
        try:
            names = program_op_names(cell)
        except Exception:                     # noqa: BLE001 — a reader
            # must not fail the run; say why it reads nothing
            traceback.print_exc(file=sys.stderr)
            names = {}
        cell.mesh_op_names = names
    return names


def loop_scope_us(cell, name: str):
    """Device time per super-step, in us, of the super-step loop's
    operations (those inside the ``while``) whose innermost program scope is
    ``name``: summed over the traced chips, over the kernel executions of
    all of them in the traced window — the mean over the chips of each
    chip's time per super-step, where every chip runs one kernel per
    super-step.

    ``None`` without a trace or kernels, and for a program that names no
    such scope."""
    trace = cell.trace_data
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.window()
    kernels = sum(1 for d in trace.ops for op, s, _ in trace.ops[d]
                  if base_name(op) == KERNEL and lo <= s < hi)
    if not kernels:
        return None
    names = _op_names(cell)
    if not any(scope(p) == name for p in names.values()):
        return None
    total = 0
    for dev, ops in trace.ops.items():
        loops = clip([(s, e) for op, s, e in ops if base_name(op) == LOOP],
                     lo, hi)
        for op, s, e in trace.leaf_ops(dev):
            cut = clip([(s, e)], lo, hi)
            if (cut and any(ls <= cut[0][0] < le for ls, le in loops)
                    and scope(names.get(short_name(op), "")) == name):
                total += cut[0][1] - cut[0][0]
    return total / kernels / 1e3
