"""Seeded weights, made on the device in one jitted call.

The benchmark draws the weights itself, so the reference never reads
anything the program made: both start from ``draw(dims, key(seed))``.
Weights live in a flat ``{name: array}`` dict over the architecture's
table (``spec``); ``to_program`` nests them the way the program's model
stores them, ``from_program`` undoes it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .spec import arch_of


def seed32(seed: int, salt: int = 0) -> int:
    """Any whole number (the driver's seeds pass 2**31) -> a 32-bit seed."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 63, int(seed) >> 63, salt])
    return int(ss.generate_state(1)[0])


def key(seed: int):
    """The weights' PRNG key for a run's ``--seed``; pass it into jitted
    code as an argument, so that one compiled program serves every seed."""
    return jax.random.key(seed32(seed, salt=1))


def draw(dm, wkey, dtype=None, *, lo=0, count: int | None = None,
         names=None) -> dict:
    """The flat weights drawn from ``wkey`` (traceable: call in a jit).

    Each layer of a stacked leaf has a key of its own, so ``count`` layers
    from ``lo`` (which may be traced) are drawn alone -- the same values
    the whole stack holds there -- and ``names`` limits the leaves drawn:
    a reference that does not fit the chip whole draws its weights block
    by block."""
    arch = arch_of(dm)
    dtype = dtype or getattr(jnp, dm.param_dtype)
    count = dm.n_layers if count is None else count
    out = {}
    for i, (name, (shape, scale)) in enumerate(sorted(arch.shapes(dm).items())):
        if names is not None and name not in names:
            continue
        k = jax.random.fold_in(wkey, i)
        if name in arch.LAYERED:
            w = jax.vmap(lambda layer: jax.random.normal(
                jax.random.fold_in(k, layer), shape[1:], jnp.float32))(
                    lo + jnp.arange(count))
        else:
            w = jax.random.normal(k, shape, jnp.float32)
        out[name] = (w * scale).astype(
            jnp.float32 if name in arch.F32 else dtype)
    return out


def to_program(dm, flat: dict) -> dict:
    paths = arch_of(dm).PATHS
    tree: dict = {}
    for name, w in flat.items():
        node = tree
        path = paths[name]
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = w
    return tree


def from_program(dm, tree: dict) -> dict:
    out = {}
    for name, path in arch_of(dm).PATHS.items():
        node = tree
        for k in path:
            if not isinstance(node, dict) or k not in node:
                break
            node = node[k]
        else:
            out[name] = node
    return out
