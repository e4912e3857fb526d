"""Training traffic: per-node low-rank bigram language models, on the
device.

The same model as the program's ``SyntheticLM`` (rank-8 tables ``u``
(V, 8) and ``w`` (8, V), next-token logits ``u[tok] @ w / sqrt(8)`` at
inverse temperature 2, and ``hetero`` mixing one shared table pair with
node-specific ones), sampled with JAX on the device so that set-up makes
every batch of a run in one call instead of walking positions in numpy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

MODES = 8
INV_TEMP = 2.0


@functools.partial(jax.jit, static_argnames=("vocab", "n_nodes", "n_batches",
                                             "batch", "seq", "hetero"))
def batches(key, *, vocab: int, n_nodes: int, n_batches: int, batch: int,
            seq: int, hetero: float):
    """int32 tokens (n_batches, n_nodes, batch, seq); every row differs."""
    k_shared, k_nodes, k_first, k_walk = jax.random.split(key, 4)
    ku, kw = jax.random.split(k_shared)
    su = jax.random.normal(ku, (vocab, MODES))
    sw = jax.random.normal(kw, (MODES, vocab))
    node_keys = jax.random.split(k_nodes, n_nodes)
    own_u = jax.vmap(lambda k: jax.random.normal(
        jax.random.fold_in(k, 0), (vocab, MODES)))(node_keys)
    own_w = jax.vmap(lambda k: jax.random.normal(
        jax.random.fold_in(k, 1), (MODES, vocab)))(node_keys)
    u = (1 - hetero) * su + hetero * own_u          # (n, V, M)
    w = (1 - hetero) * sw + hetero * own_w          # (n, M, V)
    tok0 = jax.random.randint(k_first, (n_batches, n_nodes, batch), 0, vocab)

    def walk(tok, k):
        # u[i][tok[:, i]] for every node i: (nb, n, B, M)
        emb = jax.vmap(lambda un, t: un[t], in_axes=(0, 1), out_axes=1)(
            u, tok)
        logits = jnp.einsum("abcm,bmv->abcv", emb, w)
        logits = logits * (INV_TEMP / math.sqrt(MODES))
        nxt = jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)
        return nxt, tok

    _, toks = jax.lax.scan(walk, tok0, jax.random.split(k_walk, seq))
    return jnp.moveaxis(toks, 0, -1)                 # (nb, n, B, S)
