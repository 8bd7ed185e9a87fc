"""The plain reference: each configuration's published update, written
straight from its equation in ``jax.numpy``, with clamp edges re-imposed
every step.  It imports nothing of the system under test and takes
nothing it made.

``dtype`` is the precision the reference computes in: the configuration's
own (float32) for the check, bfloat16 for the control that the check must
refuse.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _taps(x):
    """Clamp-padded neighbours of every cell: (dz,) dy, dx offsets -> array."""
    p = jnp.pad(x, 1, mode="edge")
    if x.ndim == 2:
        return {"C": x, "N": p[:-2, 1:-1], "S": p[2:, 1:-1],
                "W": p[1:-1, :-2], "E": p[1:-1, 2:]}
    return {"C": x,
            "B": p[:-2, 1:-1, 1:-1], "A": p[2:, 1:-1, 1:-1],
            "N": p[1:-1, :-2, 1:-1], "S": p[1:-1, 2:, 1:-1],
            "W": p[1:-1, 1:-1, :-2], "E": p[1:-1, 1:-1, 2:]}


def _hotspot2d(t, P, c, amb):
    T = t["C"]
    return T + c["sdc"] * (P + (t["N"] + t["S"] - 2.0 * T) * c["ry1"]
                           + (t["E"] + t["W"] - 2.0 * T) * c["rx1"]
                           + (amb - T) * c["rz1"])


def _diffusion2d(t, P, c, amb):
    return (c["cc"] * t["C"] + c["cw"] * t["W"] + c["ce"] * t["E"]
            + c["cs"] * t["S"] + c["cn"] * t["N"])


def _diffusion3d(t, P, c, amb):
    return (c["cc"] * t["C"] + c["cw"] * t["W"] + c["ce"] * t["E"]
            + c["cs"] * t["S"] + c["cn"] * t["N"] + c["cb"] * t["B"]
            + c["ca"] * t["A"])


UPDATES = {"hotspot2d": _hotspot2d, "diffusion2d": _diffusion2d,
           "diffusion3d": _diffusion3d}


@partial(jax.jit, static_argnames=("stencil", "names", "dtype", "amb"))
def _run(x, aux, coeffs, iters, *, stencil, names, dtype, amb):
    dt = jnp.dtype(dtype)
    c = {n: v.astype(dt) for n, v in zip(names, coeffs)}
    update = UPDATES[stencil]
    x = x.astype(dt)
    P = None if aux is None else aux.astype(dt)
    amb = jnp.asarray(amb, dt)
    out = jax.lax.fori_loop(0, iters, lambda _, g: update(_taps(g), P, c, amb),
                            x)
    return out.astype(jnp.float32)


def run(config: dict, x, iters: int, aux=None, dtype: str = "float32"):
    """``iters`` steps of ``config``'s update from ``x`` (float32 out)."""
    names = tuple(sorted(config["coefficients"]))
    coeffs = tuple(jnp.float32(config["coefficients"][n]) for n in names)
    return _run(x, aux, coeffs, jnp.int32(iters), stencil=config["stencil"],
                names=names, dtype=dtype,
                amb=float(config.get("amb_temp", 0.0)))


@jax.jit
def _gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want)),
            jnp.all(jnp.isfinite(got)))


def gap(got, want) -> tuple:
    """``(max |got - want|, max |want|)``; a non-finite ``got`` reads inf."""
    diff, mag, finite = (x.item() for x in _gap(got, want))
    return (diff if finite else float("inf")), mag


def _blocks(x_host, sharding, iters):
    """Per device of ``sharding``: the block of the grid
    it needs (the shard plus ``iters`` cells of halo on every side, the
    reach of ``iters`` radius-1 steps, cut at the grid's edges) and where
    the shard lies inside that block."""
    for device, index in sharding.addressable_devices_indices_map(
            x_host.shape).items():
        cut, crop = [], []
        for s, n in zip(index, x_host.shape):
            a, b = s.start or 0, n if s.stop is None else s.stop
            lo, hi = max(0, a - iters), min(n, b + iters)
            cut.append(slice(lo, hi))
            crop.append(slice(a - lo, b - lo))
        yield device, tuple(cut), tuple(crop)


def run_by_blocks(config: dict, x, iters: int, aux=None,
                  dtype: str = "float32"):
    """``run`` of a sharded ``x`` without any chip holding the grid: each
    shard's block is cut from the host copy, run on the shard's own
    device and cropped to the shard, whose cells are then exact."""
    x_host = np.asarray(x)
    aux_host = None if aux is None else np.asarray(aux)
    pieces = []
    for device, cut, crop in _blocks(x_host, x.sharding, iters):
        ab = (None if aux_host is None
              else jax.device_put(aux_host[cut], device))
        pieces.append(run(config, jax.device_put(x_host[cut], device), iters,
                          ab, dtype)[crop])
    return jax.make_array_from_single_device_arrays(x.shape, x.sharding,
                                                    pieces)


def gap_by_blocks(config: dict, x_host, out, iters: int, aux_host=None,
                  dtype: str = "float32") -> tuple:
    """``gap`` of a sharded ``out`` against the reference run from the host
    array ``x_host``, one shard at a time on the shard's own device."""
    diff = mag = 0.0
    data = {s.device: s.data for s in out.addressable_shards}
    for device, cut, crop in _blocks(x_host, out.sharding, iters):
        ab = (None if aux_host is None
              else jax.device_put(aux_host[cut], device))
        want = run(config, jax.device_put(x_host[cut], device), iters, ab,
                   dtype)[crop]
        d, m = gap(data[device], want)
        diff, mag = max(diff, d), max(mag, m)
        del want, ab
    return diff, mag
