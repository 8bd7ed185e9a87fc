"""The one traffic generator: a mix's parameters and a seed in, a schedule
out.

Every seed gets the same work: the same number of requests, the same
multiset of iteration counts and the same multiset of inter-arrival gaps
(exponential quantiles, so the offered load is Poisson in law), only in
another order.  Runs with different seeds then differ by arrangement, not
by how much they ask of the system.
"""
from __future__ import annotations

import numpy as np


def host_rng(seed: int, salt: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any non-negative int, 64-bit too)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), salt]))


def jax_key(seed: int):
    """A JAX key from a seed wider than 32 bits."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def open_loop(traffic: dict, seed: int, seconds: float,
              rate: float | None = None) -> dict:
    """An open-loop schedule of ``rate * seconds`` requests.

    ``due_s``: send time of each request from the window's start;
    ``iters``: its iteration count (the mix's values in equal shares);
    ``pool``: which seeded input it carries;
    ``sample``: the requests whose answers are compared, stratified by
    iteration count (``sample_per_iters`` of each, the longest included).
    """
    rate = float(rate if rate is not None else traffic["rate_per_s"])
    n = max(len(traffic["iters"]), int(round(rate * seconds)))
    r = host_rng(seed, 1)
    q = (np.arange(n) + 0.5) / n
    gaps = r.permutation(-np.log1p(-q) / rate)
    due = np.cumsum(gaps) - gaps[0]
    mix = np.asarray(traffic["iters"], np.int64)
    iters = r.permutation(np.resize(mix, n))
    pool = r.permutation(np.arange(n) % int(traffic["pool"]))
    k = int(traffic["sample_per_iters"])
    sample = np.sort(np.concatenate([
        r.choice(np.flatnonzero(iters == it), size=min(k, int(np.sum(
            iters == it))), replace=False) for it in mix]))
    return {"due_s": due, "iters": iters, "pool": pool, "sample": sample,
            "rate_per_s": rate}
