"""Seeded weights, made on the device in one jitted call.

The benchmark draws the weights itself, so the reference never reads
anything the program made: both start from ``make(dims, seed)``.  Weights
live in a flat ``{name: array}`` dict; ``to_program`` nests them the way
the program's model stores them, ``from_program`` undoes it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .spec import Dims

# name -> path in the program's parameter tree
_LAYER_PATHS = {
    "attn_norm": ("layers", "ln1", "scale"),
    "wq": ("layers", "attn", "wq"),
    "wk": ("layers", "attn", "wk"),
    "wv": ("layers", "attn", "wv"),
    "wo": ("layers", "attn", "wo"),
    "q_norm": ("layers", "attn", "q_norm", "scale"),
    "k_norm": ("layers", "attn", "k_norm", "scale"),
    "mlp_norm": ("layers", "ln2", "scale"),
    "w_gate": ("layers", "mlp", "w_gate"),
    "w_up": ("layers", "mlp", "w_up"),
    "w_down": ("layers", "mlp", "w_down"),
    "router": ("layers", "moe", "router"),
    "we_gate": ("layers", "moe", "w_gate"),
    "we_up": ("layers", "moe", "w_up"),
    "we_down": ("layers", "moe", "w_down"),
}
_TOP_PATHS = {"embed": ("embed",), "lm_head": ("lm_head",),
              "final_norm": ("final_norm", "scale")}
PATHS = {**_TOP_PATHS, **_LAYER_PATHS}
NORMS = ("attn_norm", "q_norm", "k_norm", "mlp_norm", "final_norm")
LAYERED = tuple(_LAYER_PATHS)   # leaves stacked over layers


def shapes(dm: Dims) -> dict:
    """name -> (shape, init scale); norms draw their (1 + scale) offset."""
    L, d, f = dm.n_layers, dm.d_model, dm.d_ff
    hq, hkv = dm.n_heads * dm.head_dim, dm.n_kv * dm.head_dim
    out = {"embed": ((dm.vocab, d), dm.embed_init_scale * d ** -0.5),
           "final_norm": ((d,), 0.1),
           "attn_norm": ((L, d), 0.1), "mlp_norm": ((L, d), 0.1),
           "wq": ((L, d, hq), d ** -0.5), "wk": ((L, d, hkv), d ** -0.5),
           "wv": ((L, d, hkv), d ** -0.5), "wo": ((L, hq, d), hq ** -0.5)}
    if not dm.tied:
        out["lm_head"] = ((d, dm.vocab), d ** -0.5)
    if dm.qk_norm:
        out["q_norm"] = ((L, dm.head_dim), 0.1)
        out["k_norm"] = ((L, dm.head_dim), 0.1)
    if dm.n_experts:
        E = dm.n_experts
        out.update(router=((L, d, E), d ** -0.5),
                   we_gate=((L, E, d, f), d ** -0.5),
                   we_up=((L, E, d, f), d ** -0.5),
                   we_down=((L, E, f, d), f ** -0.5))
    else:
        out.update(w_gate=((L, d, f), d ** -0.5), w_up=((L, d, f), d ** -0.5),
                   w_down=((L, f, d), f ** -0.5))
    return out


def seed32(seed: int, salt: int = 0) -> int:
    """Any whole number (the driver's seeds pass 2**31) -> a 32-bit seed."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 63, int(seed) >> 63, salt])
    return int(ss.generate_state(1)[0])


def key(seed: int):
    """The weights' PRNG key for a run's ``--seed``; pass it into jitted
    code as an argument, so that one compiled program serves every seed."""
    return jax.random.key(seed32(seed, salt=1))


def draw(dm: Dims, wkey, dtype=None, *, lo=0, count: int | None = None,
         names=None) -> dict:
    """The flat weights drawn from ``wkey`` (traceable: call in a jit).

    Each layer of a stacked leaf has a key of its own, so ``count`` layers
    from ``lo`` (which may be traced) are drawn alone -- the same values
    the whole stack holds there -- and ``names`` limits the leaves drawn:
    a reference that does not fit the chip whole draws its weights block
    by block."""
    dtype = dtype or getattr(jnp, dm.param_dtype)
    count = dm.n_layers if count is None else count
    out = {}
    for i, (name, (shape, scale)) in enumerate(sorted(shapes(dm).items())):
        if names is not None and name not in names:
            continue
        k = jax.random.fold_in(wkey, i)
        if name in LAYERED:
            w = jax.vmap(lambda layer: jax.random.normal(
                jax.random.fold_in(k, layer), shape[1:], jnp.float32))(
                    lo + jnp.arange(count))
        else:
            w = jax.random.normal(k, shape, jnp.float32)
        # the router is kept in f32 by the program whatever the param dtype
        out[name] = (w * scale).astype(
            jnp.float32 if name == "router" else dtype)
    return out


def to_program(flat: dict) -> dict:
    tree: dict = {}
    for name, w in flat.items():
        node = tree
        path = PATHS[name]
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = w
    return tree


def from_program(tree: dict) -> dict:
    out = {}
    for name, path in PATHS.items():
        node = tree
        for k in path:
            if not isinstance(node, dict) or k not in node:
                break
            node = node[k]
        else:
            out[name] = node
    return out
