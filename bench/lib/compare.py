"""The numbers that decide ``correct`` for a training cell.

Both the program and the reference are reduced to the same observations
of the first three DmSGD steps:

* ``losses``   -- each step's loss, the mean over nodes;
* ``grad``     -- per node and leaf, the norm of the momentum after one
  step, ``m1 = W0 g0``: the first gradient as the optimizer got it;
* ``change``   -- per node and leaf, the norm of ``x3 - x0``;
* ``consensus`` -- ``sqrt(sum_i |x3_i - mean x3|^2)`` over
  ``sqrt(sum_i |x3_i - x0|^2)``: with the one-peer exponential graph
  ``x3`` is the exact average of the nodes (eq. 7 averages exactly after
  log2(n) rounds), so this reads rounding only;
* ``g0``       -- the reference's first gradient, per node and leaf, for
  the rule that leaves out leaves which do not move.

``numbers`` turns a pair of observations into the compared numbers; each
is a gap scaled to the reference, so 0 is exact agreement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

# a leaf whose first reference gradient is below this share of the median
# leaf's moves by round-off alone and is left out of the change
STILL_LEAF = 1e-3


@jax.jit
def node_leaf_norms(flat: dict) -> dict:
    """{name: (n, ...)} -> {name: (n,)} Euclidean norms per node."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                                axis=tuple(range(1, v.ndim))))
            for k, v in flat.items()}


def change_obs(flat_x3: dict, dm, wkey) -> dict:
    """Norms of ``x3 - x0`` per node and leaf, and the consensus share;
    ``x0`` is drawn again from the weights' key inside the same program."""

    @jax.jit
    def obs(x3, wkey):
        x0 = weights.draw(dm, wkey, dtype=jnp.float32)
        out, spread, total = {}, 0.0, 0.0
        for k, v in x3.items():
            v = v.astype(jnp.float32)
            d = v - x0[k][None]
            axes = tuple(range(1, v.ndim))
            sq = jnp.sum(jnp.square(d), axis=axes)
            out[k] = jnp.sqrt(sq)
            total = total + jnp.sum(sq)
            spread = spread + jnp.sum(jnp.square(v - v.mean(0, keepdims=True)))
        return out, jnp.sqrt(spread), jnp.sqrt(total)

    change, spread, total = obs(flat_x3, wkey)
    return {"change": {k: np.asarray(v) for k, v in change.items()},
            "consensus": float(spread) / max(float(total), 1e-30)}


def _worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """max over nodes and leaves of |p - r| / max(r, median leaf of r)."""
    names = sorted(ref)
    r = np.stack([np.asarray(ref[k], np.float64) for k in names], 1)
    p = np.stack([np.asarray(prog[k], np.float64) for k in names], 1)
    med = np.median(r, axis=1, keepdims=True)
    gap = np.abs(p - r) / np.maximum(r, med)
    if keep is not None:
        gap = np.where(np.stack([keep[k] for k in names], 1), gap, 0.0)
    return float(gap.max())


def _moving(ref: dict) -> dict:
    """Per leaf and node, whether the reference's first gradient moves it."""
    g0 = ref["g0"]
    med = np.median(np.stack([np.asarray(g0[k]) for k in sorted(g0)], 1),
                    axis=1)
    return {k: np.asarray(v) >= STILL_LEAF * med for k, v in g0.items()}


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers a training cell can compare (its limits file names
    those it does): the worst step's loss, and the worst leaf's first
    gradient and change."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out = {"loss": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "grad": _worst_leaf(prog["grad"], ref["grad"]),
           "change": _worst_leaf(prog["change"], ref["change"],
                                 _moving(ref))}
    if np.asarray(next(iter(ref["g0"].values()))).shape[0] > 1:
        out["consensus"] = float(prog["consensus"])
    return out


def still_leaves(ref: dict) -> list:
    """Names of the leaves the change leaves out (for the report)."""
    return sorted(k for k, v in _moving(ref).items() if not np.all(v))
