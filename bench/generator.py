"""The traffic generator: arrival times and prompts of one mix, from a seed.

An open loop: each request is due at its time whether or not earlier ones
have finished. Arrivals are a Poisson process conditioned on its count:
the window holds ``round(integral of the rate)`` requests, placed by the
rate's shape (uniformly for a fixed rate), so every seed brings the same
amount of work in another order. This follows the program's
``core.trace.arrivals_from_rates`` (Poisson arrivals at per-second rates)
except that the count is fixed.

A mix's ``arrivals`` is ``{"kind": "poisson", "rate_rps": r}`` or
``{"kind": "shape", "points": [[t_s, rps], ...]}`` (piecewise linear in
time, held at its last point). With ``"schedule_seed": k`` the arrival
times are drawn from ``k``, the same in every run, and the run's seed
draws only the prompts and weights: for a mix whose tail, over the few
requests one window holds, would swing with where the seed's Poisson
pattern puts its bursts.
"""
from __future__ import annotations

import numpy as np

STREAMS = {"arrivals": 1, "prompts": 2, "weights": 3, "sample": 4}


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & (2 ** 64 - 1), STREAMS[stream]]))


def jax_seed(seed: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` drawn from ``seed``."""
    return int(rng(seed, "weights").integers(0, 2 ** 31 - 1))


def rate_at(arrivals: dict, t: np.ndarray) -> np.ndarray:
    if arrivals["kind"] == "poisson":
        return np.full_like(np.asarray(t, np.float64), arrivals["rate_rps"])
    if arrivals["kind"] == "shape":
        pts = np.asarray(arrivals["points"], np.float64)
        return np.interp(t, pts[:, 0], pts[:, 1])
    raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")


def arrival_times(arrivals: dict, seconds: float, seed: int) -> np.ndarray:
    """Sorted due times in [0, seconds)."""
    grid = np.linspace(0.0, seconds, int(seconds * 1000) + 1)
    lam = rate_at(arrivals, grid)
    cum = np.concatenate([[0.0], np.cumsum(0.5 * (lam[1:] + lam[:-1])
                                           * np.diff(grid))])
    n = int(round(cum[-1]))
    u = np.sort(rng(arrivals.get("schedule_seed", seed), "arrivals")
                .uniform(0.0, cum[-1], n))
    return np.minimum(np.interp(u, cum, grid), np.nextafter(seconds, 0.0))


def prompts(n: int, length: int, vocab: int, seed: int) -> np.ndarray:
    return rng(seed, "prompts").integers(0, vocab, (n, length), np.int32)
