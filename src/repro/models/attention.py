"""Attention: GQA/MQA, qk-norm, soft-capping, sliding windows, cross-attn,
ring-buffer KV caches for decode.

Pure jnp by default; the Pallas flash kernel (repro.kernels.flash_attention)
is a drop-in for the train/prefill path via ``impl='pallas'``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .layers import dense_init, rms_norm, rms_norm_init, rope

__all__ = ["attn_init", "attn_apply", "attn_decode", "attn_decode_paged",
           "cross_attn_apply", "KVCache", "init_kv_cache"]

NEG_INF = -2.0 ** 30


class KVCache(NamedTuple):
    """Ring-buffer KV cache.

    k, v: (batch, n_kv, cache_len, head_dim). Slot ``s`` holds token
    ``t(s) = idx - mod(idx - s, cache_len)`` -- for full caches
    (cache_len >= max_seq) this is simply position ``s``.
    Keys are stored *rotated* (RoPE applied at absolute position at write
    time), which is valid because RoPE is relative.
    """
    k: jax.Array
    v: jax.Array


def init_kv_cache(batch: int, n_kv: int, cache_len: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, n_kv, cache_len, head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qk_norm: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads * head_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d_model, n_kv * head_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d_model, n_kv * head_dim), dtype=dtype),
        "wo": dense_init(ks[3], (n_heads * head_dim, d_model), dtype=dtype),
    }
    if qk_norm:
        p["q_norm"] = rms_norm_init(head_dim, dtype)
        p["k_norm"] = rms_norm_init(head_dim, dtype)
    return p


def _project_qkv(params, x, n_heads, n_kv, head_dim, qk_norm, positions,
                 rope_theta):
    dt = x.dtype
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(dt))
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv, head_dim)
    v = v.reshape(B, S, n_kv, head_dim)
    if qk_norm:
        q = rms_norm(params["q_norm"], q)
        k = rms_norm(params["k_norm"], k)
    if rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, attn_cap=None, gqa_layout="grouped"):
    """q: (B,S,H,hd); k,v: (B,T,Kv,hd); mask: (B,1,S,T) or (1,1,S,T).

    gqa_layout:
      'grouped' -- scores shaped (B, Kv, G, S, T): GSPMD can shard at most
        max(Kv, G)-way over the model axis (baseline).
      'flat'    -- K/V repeated to H heads, scores (B, H, S, T): the full
        head count shards over the model axis (a §Perf iteration -- halves
        per-chip score bytes when Kv < model_axis <= H).
    """
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    G = H // Kv
    if gqa_layout == "flat":
        kf = jnp.repeat(k, G, axis=2)       # (B,T,H,hd)
        vf = jnp.repeat(v, G, axis=2)
        logits = jnp.einsum("bshd,bthd->bhst", q, kf).astype(jnp.float32)
        logits *= hd ** -0.5
        if attn_cap is not None:
            logits = attn_cap * jnp.tanh(logits / attn_cap)
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, vf)
        return out
    qg = q.reshape(B, S, Kv, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    logits *= hd ** -0.5
    if attn_cap is not None:
        logits = attn_cap * jnp.tanh(logits / attn_cap)
    logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, hd)


def attn_apply(params, x, *, n_heads, n_kv, head_dim, positions,
               rope_theta=10000.0, qk_norm=False, window=None,
               attn_cap=None, impl="jnp", gqa_layout="grouped",
               return_kv=False):
    """Causal self-attention on a full sequence (train / prefill).

    window: if set, token i attends to (i-window, i] (sliding window).
    return_kv: also return the (rotated, normed) k, v as (B, S, Kv, hd) --
      exactly what a decode cache stores -- so a serving prefill can fill
      KV pages from one full-sequence forward.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv, head_dim, qk_norm,
                           positions, rope_theta)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(
            q, k, v, causal=True, window=window, attn_cap=attn_cap)
    else:
        i = positions[:, :, None]   # (B,S,1)
        j = positions[:, None, :]   # (B,1,T)
        mask = j <= i
        if window is not None:
            mask &= j > i - window
        out = _sdpa(q, k, v, mask[:, None], attn_cap, gqa_layout)
    dt = x.dtype
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, n_heads * head_dim),
                   params["wo"].astype(dt))
    if return_kv:
        return y, k, v
    return y


def attn_decode(params, x, cache: KVCache, idx, *, n_heads, n_kv, head_dim,
                rope_theta=10000.0, qk_norm=False, window=None,
                attn_cap=None):
    """One-token decode. x: (B, 1, d); idx: scalar int32 absolute position.

    Writes (k, v) for position idx into ring slot ``idx % cache_len`` and
    attends over all valid cache slots.
    """
    B = x.shape[0]
    cache_len = cache.k.shape[2]
    pos = jnp.full((B, 1), idx, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, n_heads, n_kv, head_dim,
                                   qk_norm, pos, rope_theta)
    slot = jnp.mod(idx, cache_len)
    k = jax.lax.dynamic_update_slice(
        cache.k, k_new.transpose(0, 2, 1, 3).astype(cache.k.dtype),
        (0, 0, slot, 0))
    v = jax.lax.dynamic_update_slice(
        cache.v, v_new.transpose(0, 2, 1, 3).astype(cache.v.dtype),
        (0, 0, slot, 0))
    # slot s holds token t(s) = idx - mod(idx - s, cache_len)
    s = jnp.arange(cache_len, dtype=jnp.int32)
    t = idx - jnp.mod(idx - s, cache_len)
    valid = t >= 0
    if window is not None:
        valid &= t > idx - window
    mask = valid[None, None, None, :]  # (1,1,1,T)

    H, hd, Kv = n_heads, head_dim, n_kv
    G = H // Kv
    qg = q.reshape(B, 1, Kv, G, hd)
    logits = jnp.einsum("bskgh,bkth->bkgst", qg,
                        k.astype(q.dtype)).astype(jnp.float32)
    logits *= hd ** -0.5
    if attn_cap is not None:
        logits = attn_cap * jnp.tanh(logits / attn_cap)
    logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,bkth->bskgh", probs, v.astype(q.dtype))
    out = out.reshape(B, 1, H * hd)
    dt = x.dtype
    y = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dt))
    return y, KVCache(k, v)


def attn_decode_paged(params, x, k_pages, v_pages, page_table, positions, *,
                      page_size, n_heads, n_kv, head_dim,
                      rope_theta=10000.0, qk_norm=False, window=None,
                      attn_cap=None, impl="jnp"):
    """One-token decode over a PAGED KV cache (continuous batching).

    x: (B, 1, d); positions: (B,) int32 -- per-sequence absolute position
    of the new token (continuous batching: every sequence is at its own
    position).  k_pages, v_pages: (Kv, n_pages, page_size, hd) shared
    pools; page_table: (B, Pmax) int32, row b's p-th entry names the pool
    page holding tokens [p*page_size, (p+1)*page_size) of sequence b.

    Writes (k, v) for position[b] into page ``page_table[b, pos//page_size]``
    slot ``pos % page_size`` (the engine guarantees that page is allocated)
    and attends over the first ``positions + 1`` tokens.  Returns
    (y, k_pages, v_pages).

    impl='pallas' uses the paged-attention kernel when the window is
    static (None or int); a traced window (gemma-2's scanned local/global
    flag) falls back to the pure-jnp gather, which handles traced masks.
    """
    B = x.shape[0]
    pos2 = positions[:, None]                    # (B, 1)
    q, k_new, v_new = _project_qkv(params, x, n_heads, n_kv, head_dim,
                                   qk_norm, pos2, rope_theta)
    pages = jnp.take_along_axis(
        page_table, (positions // page_size)[:, None], axis=1)[:, 0]
    slots = positions % page_size
    kn = k_new[:, 0].transpose(1, 0, 2)          # (Kv, B, hd)
    vn = v_new[:, 0].transpose(1, 0, 2)
    with jax.named_scope("kv_write"):
        k_pages = k_pages.at[:, pages, slots].set(kn.astype(k_pages.dtype))
        v_pages = v_pages.at[:, pages, slots].set(vn.astype(v_pages.dtype))
    lengths = positions + 1

    static_window = window is None or isinstance(window, int)
    if impl == "pallas" and static_window:
        from repro.kernels.paged_attention import ops as paged_ops
        out = paged_ops.paged_attention(
            q[:, 0], k_pages, v_pages, page_table, lengths,
            window=window, attn_cap=attn_cap)
    else:
        from repro.kernels.paged_attention import ref as paged_ref
        out = paged_ref.paged_attention_ref(
            q[:, 0], k_pages, v_pages, page_table, lengths,
            window=window, attn_cap=attn_cap)
    dt = x.dtype
    y = jnp.einsum("bh,hd->bd", out.reshape(B, n_heads * head_dim),
                   params["wo"].astype(dt))[:, None]
    return y, k_pages, v_pages


def cross_attn_init(key, d_model: int, n_heads: int, n_kv: int,
                    head_dim: int, dtype=jnp.float32):
    p = attn_init(key, d_model, n_heads, n_kv, head_dim, qk_norm=True,
                  dtype=dtype)
    p["gate"] = jnp.zeros((), dtype)  # llama-3.2-vision tanh gating
    return p


def cross_attn_apply(params, x, kv_src, *, n_heads, n_kv, head_dim):
    """Cross attention: queries from x (B,S,d), keys/values from kv_src
    (B,T,d) -- the (stubbed) vision/audio embeddings. No RoPE, no causality.
    """
    dt = x.dtype
    B, S, _ = x.shape
    T = kv_src.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dh->bth", kv_src.astype(dt), params["wk"].astype(dt))
    v = jnp.einsum("btd,dh->bth", kv_src.astype(dt), params["wv"].astype(dt))
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, T, n_kv, head_dim)
    v = v.reshape(B, T, n_kv, head_dim)
    q = rms_norm(params["q_norm"], q)
    k = rms_norm(params["k_norm"], k)
    mask = jnp.ones((B, 1, S, T), bool)
    out = _sdpa(q, k, v, mask)
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, n_heads * head_dim),
                   params["wo"].astype(dt))
    return jnp.tanh(params["gate"].astype(jnp.float32)).astype(dt) * y
