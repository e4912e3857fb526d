"""Plain float32 references: the decoder's forward pass and loss, and
DmSGD over the one-peer exponential graph.

Written from the published model descriptions and the paper's Algorithm 1
in straightforward ``jax.numpy``; nothing here imports the program.  The
departures from the published model that the program makes on purpose are
followed here too and listed in each configuration's file.

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

from .spec import Dims

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8_e4m3fn


@jax.custom_vjp
def _fp8_round(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_fp8_round.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (g,))


def _mm(eq, a, w, mode):
    if mode == "fp8":
        a, w = _fp8_round(a), _fp8_round(w)
    return jnp.einsum(eq, a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _rope(x, pos, theta):
    """x (B, S, heads, hd): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(h, lw, dm: Dims, pos, mode):
    B, S, _ = h.shape
    H, Kv, hd = dm.n_heads, dm.n_kv, dm.head_dim
    q = _mm("bsd,dh->bsh", h, lw["wq"], mode).reshape(B, S, H, hd)
    k = _mm("bsd,dh->bsh", h, lw["wk"], mode).reshape(B, S, Kv, hd)
    v = _mm("bsd,dh->bsh", h, lw["wv"], mode).reshape(B, S, Kv, hd)
    if dm.qk_norm:
        q = _rms(q, lw["q_norm"], dm.eps)
        k = _rms(k, lw["k_norm"], dm.eps)
    q, k = _rope(q, pos, dm.rope_theta), _rope(k, pos, dm.rope_theta)
    # grouped-query attention: query head j reads key/value head j // G
    k = jnp.repeat(k, H // Kv, axis=2)
    v = jnp.repeat(v, H // Kv, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = pos[:, None, :, None] >= pos[:, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return _mm("bsh,hd->bsd", o.reshape(B, S, H * hd), lw["wo"], mode)


def _moe(h, lw, dm: Dims, mode):
    """Every token through its top-k experts, gates renormalised over them
    (softmax over the k largest router logits)."""
    logits = jnp.einsum("bsd,de->bse", h, lw["router"], precision=HIGHEST)
    top, idx = jax.lax.top_k(logits, dm.top_k)
    gates = jax.nn.softmax(top, -1)
    weight = jnp.sum(jax.nn.one_hot(idx, dm.n_experts) * gates[..., None], -2)

    def expert(out, e):
        wg, wu, wd, we = e
        g = _mm("bsd,df->bsf", h, wg, mode)
        u = _mm("bsd,df->bsf", h, wu, mode)
        y = _mm("bsf,fd->bsd", jax.nn.silu(g) * u, wd, mode)
        return out + we[..., None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        lw["we_gate"], lw["we_up"], lw["we_down"], jnp.moveaxis(weight, -1, 0)))
    return out


def _mlp(h, lw, mode):
    g = _mm("bsd,df->bsf", h, lw["w_gate"], mode)
    u = _mm("bsd,df->bsf", h, lw["w_up"], mode)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"], mode)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "mlp_norm", "w_gate", "w_up", "w_down", "router", "we_gate",
              "we_up", "we_down")


def hidden(w: dict, tokens, dm: Dims, mode: str = "f32", *, lo: int = 0,
           hi: int | None = None, x=None, pos=None):
    """The residual stream after layers ``lo`` to ``hi`` (all by default),
    starting from the embeddings of ``tokens`` or from ``x``."""
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    if x is None:
        # the program scales embeddings by sqrt(d) (see the config's
        # departures)
        x = w["embed"][tokens].astype(jnp.float32) * math.sqrt(dm.d_model)
    stack = {k: w[k][lo:hi].astype(jnp.float32)
             for k in LAYER_KEYS if k in w}

    def layer(x, lw):
        x = x + _attention(_rms(x, lw["attn_norm"], dm.eps), lw, dm, pos,
                           mode)
        h = _rms(x, lw["mlp_norm"], dm.eps)
        return x + (_moe(h, lw, dm, mode) if dm.n_experts
                    else _mlp(h, lw, mode)), None

    x, _ = jax.lax.scan(layer, x, stack)
    return x


def head(w: dict, x, dm: Dims, mode: str = "f32"):
    x = _rms(x, w["final_norm"].astype(jnp.float32), dm.eps)
    if dm.tied:
        return _mm("bsd,vd->bsv", x, w["embed"].astype(jnp.float32), mode)
    return _mm("bsd,dv->bsv", x, w["lm_head"].astype(jnp.float32), mode)


def loss(w: dict, tokens, dm: Dims, mode: str = "f32", fault: str | None = None):
    """Mean next-token cross-entropy; labels are the tokens rolled left by
    one, as the program takes them."""
    logits = head(w, hidden(w, tokens, dm, mode), dm, mode)
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

    def __init__(self, dm: Dims, mesh, *, beta: float, lr: float,
                 mode: str = "f32", fault: str | None = None):
        self.dm, self.mesh, self.n = dm, mesh, mesh.shape["node"]
        self.beta, self.lr, self.mode, self.fault = beta, lr, mode, fault
        node = P("node")

        def grads(x, toks):
            return jax.vmap(jax.value_and_grad(functools.partial(
                loss, dm=dm, mode=mode, fault=fault)))(x, toks)

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
