"""Stencil zoo — the four paper benchmarks (Table 2) plus a generic star stencil.

A stencil is described by:
  * its neighborhood (radius + offsets used),
  * an ``apply`` function written against an abstract neighbor *getter*, so the
    same arithmetic is reused by the unblocked oracle (kernels/ref.py), the
    pure-JAX blocked engine (core/engine.py) and the Pallas kernel
    (kernels/builder.py),
  * bookkeeping constants matching the paper's Table 2 (FLOP and bytes per
    cell update, external reads/writes per cell update).

Boundary condition (paper §5.1): "all out-of-bound neighbors of grid cells on
the grid boundaries fall back on the boundary cell itself" — i.e. index clamp
/ edge replication, re-imposed at *every* time-step.  That clamp is only the
*default* here: ``repro.core.boundary`` makes the BC a per-axis parameter
(clamp / periodic / reflect / constant) honored by every backend.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence

import jax.numpy as jnp

# Neighbor getter: maps an offset tuple (dy, dx) or (dz, dy, dx) to the
# (shifted) array of that neighbor for every cell being updated.
Getter = Callable[[Sequence[int]], jnp.ndarray]

TEMP_AMB = 80.0  # Hotspot ambient temperature — compile-time constant (paper §5.1)


def _star_offsets(ndim: int, radius: int) -> tuple:
    """Axis-aligned (star) neighborhood: center + ±1..radius on each axis."""
    offs = []
    for axis in range(ndim):
        for d in range(-radius, radius + 1):
            off = [0] * ndim
            off[axis] = d
            offs.append(tuple(off))
    return tuple(dict.fromkeys(offs))  # dedup center


@dataclasses.dataclass(frozen=True)
class Stencil:
    name: str
    ndim: int                     # 1, 2 or 3
    radius: int
    flop_pcu: int                 # FLOPs per cell update      (Table 2)
    num_read: int                 # external reads per update  (Table 2)
    num_write: int                # external writes per update (Table 2)
    has_aux: bool                 # second input stream (Hotspot `power`)
    coeff_names: tuple            # scalar coefficients, passed at run time
    apply: Callable               # (get, coeffs, aux_center) -> updated center
    #: Neighbor offsets ``apply`` actually touches, stored at construction so
    #: non-star shapes (``make_box`` diagonals) report their true footprint.
    #: Defaults to the axis-aligned star — correct for every builtin.
    offsets: tuple = ()
    #: Number of input grids ``apply`` reads.  ``arity == 1`` (every classic
    #: stencil) gets a single neighbor getter; ``arity > 1`` (fan-in combine
    #: stages in a program DAG) gets a *tuple* of getters, one per input.
    arity: int = 1

    def __post_init__(self):
        if self.arity < 1:
            raise ValueError(f"{self.name}: arity must be >= 1")
        offs = self.offsets or _star_offsets(self.ndim, self.radius)
        object.__setattr__(self, "offsets",
                           tuple(tuple(int(d) for d in o) for o in offs))
        if any(len(o) != self.ndim for o in self.offsets):
            raise ValueError(f"{self.name}: offsets must be {self.ndim}-D")
        span = max((abs(d) for o in self.offsets for d in o), default=0)
        if span > self.radius:
            raise ValueError(
                f"{self.name}: offset span {span} exceeds radius "
                f"{self.radius} — halo sizing (rad*par_time) would be wrong")

    @property
    def bytes_pcu(self) -> int:
        """Bytes per cell update with full spatial-locality optimization."""
        return 4 * (self.num_read + self.num_write)

    @property
    def bytes_per_flop(self) -> float:
        return self.bytes_pcu / self.flop_pcu


def _diffusion2d(get: Getter, c: Mapping[str, jnp.ndarray], aux=None):
    # c_c*val_c + c_w*val_w + c_e*val_e + c_s*val_s + c_n*val_n  (9 FLOPs)
    return (c["cc"] * get((0, 0)) + c["cw"] * get((0, -1)) + c["ce"] * get((0, 1))
            + c["cs"] * get((1, 0)) + c["cn"] * get((-1, 0)))


def _diffusion3d(get: Getter, c: Mapping[str, jnp.ndarray], aux=None):
    # 7-point star (13 FLOPs); b(elow)/a(bove) are the z-neighbors.
    return (c["cc"] * get((0, 0, 0))
            + c["cw"] * get((0, 0, -1)) + c["ce"] * get((0, 0, 1))
            + c["cs"] * get((0, 1, 0)) + c["cn"] * get((0, -1, 0))
            + c["cb"] * get((-1, 0, 0)) + c["ca"] * get((1, 0, 0)))


def _hotspot2d(get: Getter, c: Mapping[str, jnp.ndarray], aux=None):
    # val_c + sdc*(power_c + (n+s-2c)*Ry1 + (e+w-2c)*Rx1 + (AMB-c)*Rz1)  (15 FLOPs)
    v = get((0, 0))
    return v + c["sdc"] * (
        aux
        + (get((-1, 0)) + get((1, 0)) - 2.0 * v) * c["ry1"]
        + (get((0, 1)) + get((0, -1)) - 2.0 * v) * c["rx1"]
        + (TEMP_AMB - v) * c["rz1"])


def _hotspot3d(get: Getter, c: Mapping[str, jnp.ndarray], aux=None):
    # val_c*cc + n*cn + s*cs + e*ce + w*cw + a*ca + b*cb + sdc*power + ca*AMB (17 FLOPs)
    return (get((0, 0, 0)) * c["cc"]
            + get((0, -1, 0)) * c["cn"] + get((0, 1, 0)) * c["cs"]
            + get((0, 0, 1)) * c["ce"] + get((0, 0, -1)) * c["cw"]
            + get((1, 0, 0)) * c["ca"] + get((-1, 0, 0)) * c["cb"]
            + c["sdc"] * aux + c["ca"] * TEMP_AMB)


DIFFUSION2D = Stencil("diffusion2d", 2, 1, 9, 1, 1, False,
                      ("cc", "cw", "ce", "cs", "cn"), _diffusion2d)
DIFFUSION3D = Stencil("diffusion3d", 3, 1, 13, 1, 1, False,
                      ("cc", "cw", "ce", "cs", "cn", "cb", "ca"), _diffusion3d)
HOTSPOT2D = Stencil("hotspot2d", 2, 1, 15, 2, 1, True,
                    ("sdc", "rx1", "ry1", "rz1"), _hotspot2d)
HOTSPOT3D = Stencil("hotspot3d", 3, 1, 17, 2, 1, True,
                    ("cc", "cn", "cs", "ce", "cw", "ca", "cb", "sdc"), _hotspot3d)

STENCILS = {s.name: s for s in (DIFFUSION2D, DIFFUSION3D, HOTSPOT2D, HOTSPOT3D)}


def make_combine(ndim: int, arity: int) -> Stencil:
    """Radius-0 elementwise combine — the fan-in node of a program DAG:

        out = w0*x0 + w1*x1 + ... + w_{n-1}*x_{n-1}

    With appropriate weights this expresses residuals (``r = f - A@u`` via
    ``combine(f, Au; w=(1,-1))``), time integrators (the wave equation's
    ``2u - u_prev + c*lap``), damping, and axis splitting — StencilFlow's
    "arithmetic nodes" (arXiv:2010.15218 §3).  ``apply`` receives a tuple of
    neighbor getters, one per input (``arity > 1``)."""
    if arity < 2:
        raise ValueError("make_combine needs arity >= 2 (use make_star(nd, 0)"
                         " for a single-input scale)")
    names = tuple(f"w{i}" for i in range(arity))
    center = tuple([0] * ndim)

    def _apply(gets, c, aux=None):
        out = c["w0"] * gets[0](center)
        for i in range(1, arity):
            out = out + c[f"w{i}"] * gets[i](center)
        return out

    return Stencil(f"combine{ndim}d_x{arity}", ndim, 0, 2 * arity - 1,
                   arity, 1, False, names, _apply, offsets=(center,),
                   arity=arity)


def make_star(ndim: int, radius: int) -> Stencil:
    """Generic star stencil of arbitrary radius (paper §8 future-work: high-order).

    u' = c0*u + sum_{axis,offset!=0} c_{axis,offset} * u[offset on axis]
    Coefficient names: ``c0`` and ``c_{axis}_{offset}``.
    """
    names = ["c0"]
    offs = []
    for axis in range(ndim):
        for d in range(-radius, radius + 1):
            if d == 0:
                continue
            names.append(f"c_{axis}_{d}")
            off = [0] * ndim
            off[axis] = d
            offs.append((f"c_{axis}_{d}", tuple(off)))
    n_neighbors = len(offs)
    flops = 2 * (n_neighbors + 1) - 1

    def _apply(get, c, aux=None, _offs=tuple(offs)):
        out = c["c0"] * get(tuple([0] * ndim))
        for cname, off in _offs:
            out = out + c[cname] * get(off)
        return out

    return Stencil(f"star{ndim}d_r{radius}", ndim, radius, flops, 1, 1, False,
                   tuple(names), _apply,
                   offsets=(tuple([0] * ndim),) + tuple(o for _, o in offs))


# 1D star stencils (stream axis only, no blocked dims) — the 1D kernel entry
# point: registered so `plan()` accepts 1D problems on every backend.
STAR1D_R1 = make_star(1, 1)
STAR1D_R2 = make_star(1, 2)
STENCILS[STAR1D_R1.name] = STAR1D_R1
STENCILS[STAR1D_R2.name] = STAR1D_R2


def make_box(ndim: int, radius: int) -> Stencil:
    """Generic box (dense-neighborhood) stencil: every cell within the
    L-inf ball of ``radius`` contributes (the paper's §6.4 "differently-
    shaped stencils" portability claim — a box is the densest same-order
    shape). (2r+1)^ndim coefficients named ``b_{offsets joined by _}``.
    """
    import itertools
    names = []
    offs = []
    for off in itertools.product(range(-radius, radius + 1), repeat=ndim):
        name = "b_" + "_".join(str(d) for d in off)
        names.append(name)
        offs.append((name, tuple(off)))
    flops = 2 * len(offs) - 1

    def _apply(get, c, aux=None, _offs=tuple(offs)):
        first, rest = _offs[0], _offs[1:]
        out = c[first[0]] * get(first[1])
        for cname, off in rest:
            out = out + c[cname] * get(off)
        return out

    return Stencil(f"box{ndim}d_r{radius}", ndim, radius, flops, 1, 1, False,
                   tuple(names), _apply, offsets=tuple(o for _, o in offs))


def default_coeffs(stencil: Stencil, dtype=jnp.float32) -> dict:
    """Reasonable physically-plausible coefficients (sum-preserving diffusion)."""
    if stencil.name == "diffusion2d":
        k = 0.125
        return {"cc": jnp.asarray(1 - 4 * k, dtype), "cw": jnp.asarray(k, dtype),
                "ce": jnp.asarray(k, dtype), "cs": jnp.asarray(k, dtype),
                "cn": jnp.asarray(k, dtype)}
    if stencil.name == "diffusion3d":
        k = 0.0833
        return {"cc": jnp.asarray(1 - 6 * k, dtype), "cw": jnp.asarray(k, dtype),
                "ce": jnp.asarray(k, dtype), "cs": jnp.asarray(k, dtype),
                "cn": jnp.asarray(k, dtype), "cb": jnp.asarray(k, dtype),
                "ca": jnp.asarray(k, dtype)}
    if stencil.name == "hotspot2d":
        return {"sdc": jnp.asarray(0.054, dtype), "rx1": jnp.asarray(0.1, dtype),
                "ry1": jnp.asarray(0.1, dtype), "rz1": jnp.asarray(0.0137, dtype)}
    if stencil.name == "hotspot3d":
        k = 0.07
        return {"cc": jnp.asarray(1 - 6 * k - 0.01, dtype),
                "cn": jnp.asarray(k, dtype), "cs": jnp.asarray(k, dtype),
                "ce": jnp.asarray(k, dtype), "cw": jnp.asarray(k, dtype),
                "ca": jnp.asarray(k, dtype), "cb": jnp.asarray(k, dtype),
                "sdc": jnp.asarray(0.054, dtype)}
    if stencil.name.startswith("combine"):
        # uniform convex combination (stable: weights sum to 1)
        n = len(stencil.coeff_names)
        return {name: jnp.asarray(1.0 / n, dtype)
                for name in stencil.coeff_names}
    if stencil.name.startswith("box"):
        # uniform averaging kernel (stable: coefficients sum to 1)
        n = len(stencil.coeff_names)
        return {name: jnp.asarray(1.0 / n, dtype)
                for name in stencil.coeff_names}
    # generic star: diffusion-like, stable
    n = len(stencil.coeff_names) - 1
    k = 0.5 / max(n, 1)
    out = {"c0": jnp.asarray(0.5, dtype)}
    for name in stencil.coeff_names[1:]:
        out[name] = jnp.asarray(k, dtype)
    return out
