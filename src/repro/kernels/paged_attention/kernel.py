"""Paged-attention decode Pallas TPU kernel (page-table gather, online
softmax).

The serving engine stores KV in fixed-size pages of a shared pool; each
sequence owns a list of page indices (its page table row).  Decode
attention is one query token per sequence over the sequence's live pages.

TPU adaptation notes:
  * The page gather is driven by BlockSpec index maps over a SCALAR-
    PREFETCHED page table (``pltpu.PrefetchScalarGridSpec``): the grid
    walks (batch, kv_head, page) and the k/v index maps read
    ``page_table[b, p]`` to stage exactly that pool page HBM->VMEM --
    a block-indexed gather, no dense copy of the pool.  The kv-head axis
    is folded into the page axis (flat row ``h * n_pages + page``) so the
    lookup is a single dynamic block index.
  * The softmax running state (m, l, acc) lives in VMEM scratch across the
    page loop (innermost grid dim), same online-softmax recurrence as the
    flash_attention kernel.
  * Pages past a sequence's length are masked to NEG_INF rather than
    skipped (static grid); page 0 of every live sequence holds >= 1 valid
    token, so the running max is finite from the first iteration and the
    fully-masked tail contributes exactly zero.

Supports GQA (G = H // Kv query rows per kv head), a static sliding
window and gemma-2 soft-capping.  float32 accumulation throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, pages_max: int,
                  window: int | None, attn_cap: float | None,
                  sm_scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0].astype(jnp.float32)             # (page_size, D)
    v = v_ref[0].astype(jnp.float32)             # (page_size, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s *= sm_scale
    if attn_cap is not None:
        s = attn_cap * jnp.tanh(s / attn_cap)

    G = s.shape[0]
    length = len_ref[b]
    cols = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (G, page_size), 1)
    mask = cols < length
    if window is not None:
        # query position is length - 1: token j visible iff j > i - window
        mask &= cols > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (G, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    pr = jnp.exp(s - m_new)                      # (G, page_size)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(pr, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == pages_max - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)          # fully-masked row guard
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, page_table, lengths, *,
                           window: int | None = None,
                           attn_cap: float | None = None,
                           interpret: bool = False):
    """q: (B, Kv, G, D) queries grouped per kv head;
    k_pages, v_pages: (Kv, n_pages, page_size, D) shared pool;
    page_table: (B, Pmax) int32; lengths: (B,) int32.
    Returns (B, Kv, G, D).

    The ops.py wrapper handles head grouping and dtype plumbing.
    """
    B, Kv, G, D = q.shape
    n_pages, page_size = k_pages.shape[1], k_pages.shape[2]
    Pmax = page_table.shape[1]
    sm_scale = D ** -0.5

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, pages_max=Pmax, window=window,
        attn_cap=attn_cap, sm_scale=sm_scale)

    def kv_index(b, h, p, pt, ln):
        return (h * n_pages + pt[b, p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # page_table, lengths
        grid=(B, Kv, Pmax),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, page_size, D), kv_index),
            pl.BlockSpec((1, page_size, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),     # running max m
            pltpu.VMEM((G, 1), jnp.float32),     # running denom l
            pltpu.VMEM((G, D), jnp.float32),     # output accumulator
        ],
    )
    kp = k_pages.reshape(Kv * n_pages, page_size, D)
    vp = v_pages.reshape(Kv * n_pages, page_size, D)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table, lengths, q, kp, vp)
