"""Flash attention Pallas TPU kernel (online-softmax, VMEM-tiled).

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * Tiling is driven by BlockSpec: the grid walks (batch*kv_head, q_blocks,
    kv_blocks) with q/k/v tiles staged HBM->VMEM by pallas; the MXU sees
    (BLOCK_Q x D) @ (D x BLOCK_K) matmuls with D and block sizes multiples of
    128 (MXU systolic dims).
  * The softmax running state (m, l, acc) lives in VMEM scratch across the
    kv-block loop (innermost grid dim), exploiting pallas' sequential-grid
    guarantee on TPU -- the analogue of keeping it in registers/SMEM on GPU.
  * Causality/window are handled by skipping fully-masked kv blocks via
    jnp.where on the block index (grid is static; masked blocks still run but
    contribute zero -- the ops.py wrapper trims the grid for the causal case
    by capping kv blocks at the diagonal).

Supports GQA (query-head groups share one kv head), sliding windows and
gemma-2 soft-capping.  float32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_k: int, causal: bool,
                 window: int | None, attn_cap: float | None,
                 kv_blocks: int, sm_scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (block_q, D)
    k = k_ref[0].astype(jnp.float32)            # (block_k, D)
    v = v_ref[0].astype(jnp.float32)            # (block_k, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s *= sm_scale
    if attn_cap is not None:
        s = attn_cap * jnp.tanh(s / attn_cap)

    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                       # (block_q, block_k)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)          # fully-masked row guard
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           attn_cap: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (BH, S, D) with matching kv head already selected/broadcast;
    k, v: (BH, T, D). Returns (BH, S, D).

    The ops.py wrapper handles the GQA head plumbing and shape padding.
    """
    BH, S, D = q.shape
    T = k.shape[1]
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    q_blocks = S // block_q
    kv_blocks = T // block_k
    sm_scale = D ** -0.5

    grid = (BH, q_blocks, kv_blocks)
    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, attn_cap=attn_cap, kv_blocks=kv_blocks,
        sm_scale=sm_scale)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
