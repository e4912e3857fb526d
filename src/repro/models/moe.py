"""Mixture-of-Experts FFN with top-k routing: dropless + capacity dispatch.

Two algebraically distinct dispatch modes:

* ``dropless=True`` (inference default): every token is processed by ALL of
  its top-k experts via a scan over the experts --
  ``y_t = sum_k gate_tk * FFN_{e_tk}(x_t)``.  Each token's output depends
  only on that token, so the path is **batch-invariant and causal**:
  token-by-token decode reproduces full-sequence prefill bit-for-bit.
  Compute is E/k times the active-parameter FLOPs, memory stays at one
  dense FFN's activations (the scan carries only the (T, d) accumulator).
  The expert weights are read in place: the model's layer loop hands the
  whole ``[L, E, ...]`` stacks and the layer's index, and each step of the
  expert scan slices ``(layer, expert)`` straight into its matmul.  Were
  the layer loop to slice the layer's ``[E, ...]`` slab instead, that slab
  would be the operand of the nested expert loop, and XLA copies such an
  operand whole before the loop starts: at granite's widths 189 MB a
  layer a step, a third of a serving decode step on TPU v5e.

* ``dropless=False`` (training): GShard/Switch-style sort-based grouped
  dispatch with a fixed per-expert ``capacity``; overflow tokens are
  dropped.  Compute is proportional to *active* parameters
  (top_k / n_experts of the dense-equivalent), which keeps the roofline's
  MODEL_FLOPS = 6 * N_active * D meaningful.  NOTE: which tokens overflow
  depends on every other token in the batchxsequence, so this path is
  neither causal nor batch-invariant -- it must never serve decode (a
  token's logits would depend on its co-batched requests).

Expert weights are stacked on a leading expert axis -- sharded over the
``model`` mesh axis (expert parallelism); the capacity dispatch
gather/scatter lowers to all-to-all under GSPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import dense_init

__all__ = ["EXPERT_WEIGHTS", "moe_init", "moe_apply"]

EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_init(key, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d_model, n_experts), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (n_experts, d_model, d_ff), dtype=dtype),
        "w_up": dense_init(ks[2], (n_experts, d_model, d_ff), dtype=dtype),
        "w_down": dense_init(ks[3], (n_experts, d_ff, d_model), dtype=dtype),
    }


def _route(params, xf, n_experts: int, top_k: int):
    """Shared router: per-token top-k gates + Switch load-balance loss."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)        # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    density = jnp.mean(
        jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32).sum(1), 0)
    mean_probs = probs.mean(axis=0)
    aux_loss = n_experts * jnp.sum(density / top_k * mean_probs)
    return gate_vals, expert_idx, aux_loss


def _expert(w, layer, e):
    """Expert ``e`` of layer ``layer`` of a ``[L, E, a, b]`` stack, as an
    ``[a, b]`` slice that fuses into the matmul reading it."""
    _, _, a, b = w.shape
    return jax.lax.dynamic_slice(w, (layer, e, 0, 0), (1, 1, a, b))[0, 0]


def _moe_dropless(params, xf, dt, *, n_experts: int, top_k: int, layer):
    """Exact per-token mixture: scan over experts, accumulate gated FFN.

    ``params``' expert weights are the whole ``[L, E, ...]`` stacks; each
    step reads expert ``e`` of layer ``layer`` in place, so no layer's
    ``[E, ...]`` slab is ever an operand of the expert loop (XLA would copy
    it whole before the loop).  Peak activation memory is one expert's
    (T, d_ff) intermediate -- the same as a dense FFN -- at E/k times the
    active FLOPs.  Used for serving, where batch-invariance is a
    correctness requirement."""
    T, d = xf.shape
    gate_vals, expert_idx, aux_loss = _route(params, xf, n_experts, top_k)
    # (T, E) combine weights: gate mass of each expert for each token
    combine = jnp.zeros((T, n_experts), jnp.float32)
    combine = combine.at[jnp.arange(T)[:, None], expert_idx].add(gate_vals)

    def body(acc, per_expert):
        e, ce = per_expert                     # expert index, (T,)
        wg, wu, wd = (_expert(params[n], layer, e) for n in EXPERT_WEIGHTS)
        g = xf @ wg.astype(dt)
        u = xf @ wu.astype(dt)
        ye = (jax.nn.silu(g) * u) @ wd.astype(dt)
        return acc + ce[:, None] * ye.astype(jnp.float32), None

    acc0 = jnp.zeros((T, d), jnp.float32)
    y, _ = jax.lax.scan(body, acc0, (jnp.arange(n_experts), combine.T))
    return y, aux_loss


def moe_apply(params, x, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25, dropless: bool = True,
              layer=0):
    """x: (B, S, d) -> (B, S, d), plus auxiliary load-balance loss.

    Returns (y, aux_loss).  See module docstring for the two dispatch
    modes; ``dropless=True`` is the batch-invariant serving path,
    ``dropless=False`` the capacity-bounded training path.  The dropless
    path takes the expert weights as whole ``[L, E, ...]`` stacks and
    reads layer ``layer`` of them (one layer's weights go in as ``w[None]``
    at layer 0); the capacity path takes one layer's ``[E, ...]``."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    dt = x.dtype

    if dropless:
        y, aux_loss = _moe_dropless(params, xf, dt, n_experts=n_experts,
                                    top_k=top_k, layer=layer)
        return y.reshape(B, S, d).astype(dt), aux_loss

    gate_vals, expert_idx, aux_loss = _route(params, xf, n_experts, top_k)

    # --- capacity-bounded grouped dispatch ----------------------------------
    A = T * top_k
    capacity = int(max(1, -(-A * capacity_factor // n_experts)))  # ceil
    flat_expert = expert_idx.reshape(A)              # (A,)
    flat_gate = gate_vals.reshape(A)
    flat_token = jnp.repeat(jnp.arange(T), top_k)

    order = jnp.argsort(flat_expert, stable=True)    # group by expert
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    # position within the expert group
    pos_in_group = jnp.arange(A) - jnp.searchsorted(
        sorted_expert, sorted_expert, side="left")
    keep = pos_in_group < capacity                   # drop overflow
    slot = sorted_expert * capacity + jnp.minimum(pos_in_group, capacity - 1)

    # gather tokens into (E*C, d); dropped tokens scatter out-of-bounds
    gathered = jnp.zeros((n_experts * capacity, d), dt)
    src = jnp.where(keep, slot, n_experts * capacity)  # OOB => dropped
    contrib = xf[sorted_token].astype(dt)
    gathered = gathered.at[src].set(contrib, mode="drop")
    xe = gathered.reshape(n_experts, capacity, d)

    # --- expert FFN (stacked einsum) ----------------------------------------
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(dt))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                    params["w_down"].astype(dt))
    yf = ye.reshape(n_experts * capacity, d)

    # --- weighted scatter back ----------------------------------------------
    out = jnp.zeros((T, d), jnp.float32)
    vals = jnp.where(keep[:, None], yf[slot].astype(jnp.float32)
                     * sorted_gate[:, None], 0.0)
    out = out.at[sorted_token].add(vals, mode="drop")
    return out.reshape(B, S, d).astype(dt), aux_loss
