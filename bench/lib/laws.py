"""Sizes and arrival times of a serving mix, from the laws its traffic file
names.

Every seed gets the same set of sizes and gaps: ``n`` quantiles of each
law.  The seed only orders them, in blocks that each hold one value of
every stratum, so any stretch of whole blocks carries nearly the same
work whatever the seed.  (Drawn freely, or shuffled without blocks, the
sizes that fell into a window moved its output rate by 14% between seeds
on one TPU v5e.)

Lengths follow ``{"law": "lognormal", "median": ..., "sigma": ...}``,
clipped to ``min`` (default 1) and ``cap``.

Arrival laws (``{"law": ..., "rate": requests/s}``, the mean over the
window):

    poisson     exponential gaps
    onoff       exponential gaps while on, none while off: on_s, off_s
"""
from __future__ import annotations

import math
import statistics

import numpy as np

BLOCK = 8          # values per block: one from each of 8 strata


def _normal_ppf(q):
    return np.asarray([statistics.NormalDist().inv_cdf(float(x)) for x in q])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a length law, ascending."""
    if law["law"] != "lognormal":
        raise ValueError(f"no length law {law['law']!r}")
    x = np.round(law["median"] * np.exp(law["sigma"]
                                        * _normal_ppf(_quantiles(n))))
    return np.clip(x, law.get("min", 1), law.get("cap", math.inf)).astype(
        np.int64)


def blocked(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` (ascending) in a seeded order of blocks, each block one
    value of every stratum, shuffled inside."""
    nb = -(-len(values) // BLOCK)
    blocks = [rng.permutation(values[b::nb]) for b in range(nb)]
    return np.concatenate([blocks[b] for b in rng.permutation(nb)])


def _on_time(t: np.ndarray, law: dict) -> np.ndarray:
    """Time spent on -> wall time, for an on/off law."""
    return t + np.floor(t / law["on_s"]) * law["off_s"]


def arrivals(law: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times in [0, seconds) of ``round(rate * seconds)`` requests."""
    n = round(law["rate"] * seconds)
    if n == 0:
        return np.zeros(0)
    gaps = blocked(-np.log1p(-_quantiles(n)), rng)
    t = np.cumsum(gaps)
    if law["law"] == "poisson":
        span = seconds
    elif law["law"] == "onoff":
        cycle = law["on_s"] + law["off_s"]
        span = law["on_s"] * (seconds // cycle) + min(seconds % cycle,
                                                      law["on_s"])
    else:
        raise ValueError(f"no arrival law {law['law']!r}")
    t = t * (span * (n - 0.5) / n) / t[-1]
    return t if law["law"] == "poisson" else _on_time(t, law)
