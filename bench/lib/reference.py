"""Plain float32 references: the primitives each architecture's forward
pass is built from (``bench/arch/``), the next-token loss, and DmSGD over
the one-peer exponential graph.

Written from the published model descriptions and the paper's Algorithm 1
in straightforward ``jax.numpy``; nothing here or in an architecture's
reference imports the program.  The departures from the published model
that the program makes on purpose are followed there too and listed in
each configuration's file.

``mode`` selects the arithmetic of the weight matrix products:

* ``"f32"``  -- float32 at ``Precision.HIGHEST`` (the reference);
* ``"fp8"``  -- the control: both operands of every weight product
  rounded to float8_e4m3fn under one per-tensor scale, the precision
  below the bfloat16 that the configurations compute in.

``fault`` plants one of the faults that the correctness check must catch
(for its tests and for reading the limits): ``"half"`` takes the mean
loss over the first half of each row only, ``"no_exchange"`` leaves out
the gossip between nodes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8_e4m3fn


@jax.custom_vjp
def _fp8_round(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_fp8_round.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (g,))


def mm(eq, a, w, mode):
    """A weight product, in ``mode``'s arithmetic."""
    if mode == "fp8":
        a, w = _fp8_round(a), _fp8_round(w)
    return jnp.einsum(eq, a, w, precision=HIGHEST)


def rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * (1.0 + scale)


def rope(x, pos, theta):
    """x (B, S, heads, hd): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def next_token_loss(logits, tokens, fault: str | None = None):
    """Mean next-token cross-entropy; labels are the tokens rolled left by
    one, as the program takes them."""
    labels = jnp.roll(tokens, -1, axis=1)
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    if fault == "half":
        ce = ce[:, : ce.shape[1] // 2]
    return jnp.mean(ce)


# ---------------------------------------------------------------------------
# DmSGD (Algorithm 1) over the one-peer exponential graph
# ---------------------------------------------------------------------------

def peer_shift(step: int, n: int) -> int:
    """Eq. (7): at step k node i averages with node i + 2^(k mod tau)."""
    tau = max(1, math.ceil(math.log2(n)))
    return 2 ** (step % tau) % n


class DmSGD:
    """``m' = W(beta m + g)``, ``x' = W(x - lr m)`` on node-stacked flat
    weights, one node per device of ``mesh`` (axis ``"node"``)."""

    def __init__(self, loss, mesh, *, beta: float, lr: float,
                 mode: str = "f32", fault: str | None = None):
        """``loss(w, tokens, mode=, fault=)``: one node's loss."""
        self.mesh, self.n = mesh, mesh.shape["node"]
        self.beta, self.lr, self.mode, self.fault = beta, lr, mode, fault
        node = P("node")

        def grads(x, toks):
            return jax.vmap(jax.value_and_grad(functools.partial(
                loss, mode=mode, fault=fault)))(x, toks)

        self.grads = jax.jit(jax.shard_map(
            grads, mesh=mesh, in_specs=(node, node), out_specs=(node, node)))
        self._updates = {}

    def update(self, step: int):
        n, beta, lr = self.n, self.beta, self.lr
        shift = peer_shift(step, n)
        exchange = n > 1 and shift and self.fault != "no_exchange"

        def mix(z):
            if not exchange:
                return z
            recv = jax.lax.ppermute(z, "node", [((i + shift) % n, i)
                                                for i in range(n)])
            return 0.5 * z + 0.5 * recv

        def upd(x, m, g):
            x_next = jax.tree.map(lambda a, b: a - lr * b, x, m)
            m_next = jax.tree.map(lambda a, b: beta * a + b, m, g)
            return jax.tree.map(mix, x_next), jax.tree.map(mix, m_next)

        key = bool(exchange) and shift
        if key not in self._updates:
            node = P("node")
            self._updates[key] = jax.jit(jax.shard_map(
                upd, mesh=self.mesh, in_specs=(node, node, node),
                out_specs=(node, node)), donate_argnums=(0, 1, 2))
        return self._updates[key]
