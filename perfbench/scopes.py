"""The program's device scopes in a trace: which ``stencil.*`` phase of the
super-step loop each operation belongs to.

The program names its phases with ``jax.named_scope`` (``stencil.pad``,
``stencil.superstep``, ``stencil.halo_refresh``, ``stencil.unpad``), and XLA
keeps the JAX name stack in each instruction's ``op_name`` metadata.  The
chip's trace does not carry it: an ``XLA Ops`` event holds the instruction's
text without its metadata, and its stats are only device offset, duration
and time scale (trace probe, TPU v5e).  So the cell's program is lowered and
compiled again for the chip the window ran on, which gives the same
instructions under the same names, and each instruction's ``op_name`` is
read from that HLO.  XLA drops the metadata of some instructions it adds
itself, such as the layout copies into the kernel's layout: those belong to
no scope.

A program that names no phase (one older than its scopes) gives no
reading: :func:`loop_split_us` returns ``None``.
"""
from __future__ import annotations

import re
import sys
import traceback

from perfbench.trace import base_name, clip, short_name

#: the streaming kernel's instruction, as the other readers match it
KERNEL = "superstep_chain"
LOOP = "while"
#: the span the program's span record holds around the compile again
RELOWER = "perfbench.relower"
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = [^\n]*$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope(path: str):
    """The innermost ``stencil.*`` component of an ``op_name`` path, or
    ``None``."""
    for part in reversed(path.split("/")):
        if part.startswith("stencil."):
            return part
    return None


def hlo_op_names(hlo: str) -> dict:
    """Instruction name -> ``op_name`` ("" without one) of an HLO text."""
    out = {}
    for m in _INSTR.finditer(hlo):
        on = _OP_NAME.search(m.group(0))
        out[m.group(1)] = on.group(1) if on else ""
    return out


def program_op_names(cell) -> dict:
    """Instruction name -> ``op_name`` of the cell's one-chip program, lowered
    and compiled again for the chip the window ran on."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.api import RunConfig, StencilProblem, plan
    cfg = cell.config
    shape = tuple(int(d) for d in cell.traffic["grid"])
    problem = StencilProblem(cfg["stencil"], shape, dtype=cfg["dtype"],
                             boundary=cfg["boundary"])
    where = SingleDeviceSharding(jax.devices()[0])

    def spec(s):
        return jax.ShapeDtypeStruct(s, problem.jnp_dtype, sharding=where)
    try:
        from repro.tracing import span
    except ImportError:
        from contextlib import nullcontext as span
    with span(RELOWER):
        p = plan(problem, RunConfig(backend=cfg["backend"],
                                    autotune=cfg["autotune"]))
        lowered = p.lower(spec(problem.state_shape),
                          aux=spec(shape) if problem.needs_aux else None)
        return hlo_op_names(lowered.compile().as_text())


def _op_names(cell) -> dict:
    """:func:`program_op_names`, once per cell; empty where the program
    cannot be compiled again."""
    names = getattr(cell, "program_op_names", None)
    if names is None:
        try:
            names = program_op_names(cell)
        except Exception:                     # noqa: BLE001 — a reader
            # must not fail the run; say why it reads nothing
            traceback.print_exc(file=sys.stderr)
            names = {}
        cell.program_op_names = names
    return names


def loop_split_us(cell, phase: str):
    """Device time per super-step, in us, of one phase of the super-step
    loop's operations, those that run inside the loop (the ``while``):
    ``halo_refresh``, under ``stencil.halo_refresh``, or ``relayout``, every
    other one but the kernel (the layout copies around the kernel, whether
    XLA left them in the kernel's scope or in none, and the loop's scalar
    work).

    A super-step is one kernel execution in the traced window, counted as
    ``loop.launch_gap_us`` counts them.  ``None`` for a trace of more than
    one device or without kernels, and for a program that names no
    phase."""
    trace = cell.trace_data
    if trace is None or len(trace.ops) != 1:
        return None
    (dev,) = trace.ops
    lo, hi = trace.window()
    ops = trace.ops[dev]
    kernels = sum(1 for op, s, _ in ops
                  if base_name(op) == KERNEL and lo <= s < hi)
    if not kernels:
        return None
    names = _op_names(cell)
    if not any(scope(p) for p in names.values()):
        return None
    loops = clip([(s, e) for op, s, e in ops if base_name(op) == LOOP],
                 lo, hi)
    total = 0
    for op, s, e in trace.leaf_ops(dev):
        cut = clip([(s, e)], lo, hi)
        if not cut or not any(ls <= cut[0][0] < le for ls, le in loops):
            continue
        where = scope(names.get(short_name(op), ""))
        if phase == "halo_refresh":
            hit = where == "stencil.halo_refresh"
        else:
            hit = base_name(op) != KERNEL and where not in (
                "stencil.halo_refresh", "stencil.pad", "stencil.unpad")
        if hit:
            total += cut[0][1] - cut[0][0]
    return total / kernels / 1e3
