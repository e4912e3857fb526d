"""Unified decoder-only model composer.

Covers the six assigned families through a block-pattern abstraction:
  dense   -- [attn + mlp] x L                      (llama/qwen/gemma/deepseek)
  moe     -- [attn + moe_ffn] x L                  (granite-moe, dbrx)
  ssm     -- [mamba2] x L                          (mamba2)
  hybrid  -- mamba2 x L with a SHARED attn block every k layers (zamba2)
  vlm     -- dense with cross-attn layers every k  (llama-3.2-vision)
  audio   -- dense over summed codebook embeddings, K lm heads (musicgen)

Layer stacks are `jax.lax.scan`s over stacked parameters so the HLO (and
compile time) stays O(1) in depth; per-layer behaviour flags (e.g. gemma-2
local/global alternation) ride along as scanned arrays.

Three entry points:
  forward(params, cfg, tokens, ...)      -> logits  (train / prefill)
  decode_step(params, cfg, token, cache, idx) -> logits, cache
  init(cfg, key) / init_cache(cfg, batch, cache_len)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba2 as m2
from . import moe as moe_mod
from .layers import dense_init, mlp_apply, mlp_init, rms_norm, rms_norm_init, softcap

PyTree = Any

__all__ = ["ModelConfig", "init", "forward", "forward_prefill",
           "decode_step", "decode_step_paged", "init_cache",
           "param_count", "active_param_count"]

# families whose decode state is a uniform per-layer self-attention KV --
# the ones the paged serving plane (repro.serve) supports natively
PAGED_FAMILIES = ("dense", "moe", "audio")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # attention behaviour
    qk_norm: bool = False
    attn_softcap: float | None = None
    final_softcap: float | None = None
    sliding_window: int | None = None      # static window for ALL attn layers
    local_global: bool = False             # gemma2: even layers use window
    rope_theta: float = 10000.0
    mlp_kind: str = "swiglu"
    tie_embeddings: bool = False
    # moe
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # dropless=True: exact batch-invariant expert mixture (serving; decode
    # matches prefill bit-for-bit).  The train step flips this off to use
    # the GShard capacity dispatch (active-param FLOPs, overflow drops).
    # Governs forward() only: decode_step is ALWAYS dropless by design --
    # capacity drops depend on co-batched tokens, so a capacity decode
    # would be non-deterministic per request and can never reproduce any
    # prefill; with moe_dropless=False, forward() is the (drop-lossy)
    # training objective and decode intentionally diverges from it.
    moe_dropless: bool = True
    # ssm / hybrid
    d_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    d_conv: int = 4
    ssm_n_groups: int = 1
    shared_attn_every: int = 0             # zamba2
    # vlm
    cross_attn_every: int = 0              # llama-3.2-vision
    n_image_tokens: int = 1024
    # audio
    n_codebooks: int = 0                   # musicgen
    # numerics
    norm_eps: float = 1e-6
    param_dtype: Any = jnp.float32
    activation_dtype: Any = jnp.bfloat16
    ssd_chunk: int = 128
    attention_impl: str = "jnp"            # jnp | pallas
    remat: bool = True
    # training-shape override for long-context (see DESIGN long_500k)
    attention_override_window: int | None = None
    # perf knob (§Perf iteration): positions as (1, S) so the causal mask is
    # (1,1,S,T) instead of per-batch (B,1,S,T) -- identical semantics for
    # unpacked sequences, B-fold smaller mask working set.
    broadcast_positions: bool = False
    # perf knob: 'flat' repeats K/V to full heads so attention scores shard
    # H-way (not max(Kv,G)-way) over the model axis. Identical math.
    gqa_layout: str = "grouped"

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def window_for(self, layer_flag_local: bool) -> int | None:
        if self.attention_override_window is not None:
            return self.attention_override_window
        if self.local_global:
            return self.sliding_window if layer_flag_local else None
        return self.sliding_window


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stacked(init_one, n, key, *args, **kw):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_one(k, *args, **kw))(keys)


def _dense_layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": rms_norm_init(cfg.d_model, cfg.param_dtype),
        "attn": attn.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.qk_norm, cfg.param_dtype),
        "ln2": rms_norm_init(cfg.d_model, cfg.param_dtype),
    }
    if cfg.family == "moe" or (cfg.n_experts and cfg.top_k):
        p["moe"] = moe_mod.moe_init(k2, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                    cfg.param_dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                            cfg.param_dtype)
    return p


def _mamba_layer_init(key, cfg: ModelConfig):
    return {
        "ln": rms_norm_init(cfg.d_model, cfg.param_dtype),
        "mixer": m2.mamba2_init(key, cfg.d_model, d_state=cfg.d_state,
                                head_dim=cfg.ssm_head_dim,
                                expand=cfg.ssm_expand, d_conv=cfg.d_conv,
                                n_groups=cfg.ssm_n_groups,
                                dtype=cfg.param_dtype),
    }


def _cross_layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rms_norm_init(cfg.d_model, cfg.param_dtype),
        "xattn": attn.cross_attn_init(k1, cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.head_dim,
                                      cfg.param_dtype),
        "ln2": rms_norm_init(cfg.d_model, cfg.param_dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                        cfg.param_dtype),
    }


def init(cfg: ModelConfig, key) -> PyTree:
    ks = jax.random.split(key, 8)
    emb_scale = cfg.d_model ** -0.5
    params: dict = {"final_norm": rms_norm_init(cfg.d_model, cfg.param_dtype)}

    if cfg.family == "audio":
        params["embed"] = dense_init(
            ks[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model),
            scale=emb_scale, dtype=cfg.param_dtype)
        params["lm_head"] = dense_init(
            ks[1], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size),
            dtype=cfg.param_dtype)
    else:
        params["embed"] = dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                     scale=emb_scale, dtype=cfg.param_dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(
                ks[1], (cfg.d_model, cfg.vocab_size), dtype=cfg.param_dtype)

    fam = cfg.family
    if fam in ("dense", "moe", "audio"):
        params["layers"] = _stacked(_dense_layer_init, cfg.n_layers, ks[2], cfg)
    elif fam == "ssm":
        params["layers"] = _stacked(_mamba_layer_init, cfg.n_layers, ks[2], cfg)
    elif fam == "hybrid":
        params["layers"] = _stacked(_mamba_layer_init, cfg.n_layers, ks[2], cfg)
        shared = _dense_layer_init(ks[3], cfg)
        # zamba2: shared block consumes concat(hidden, embedding) -> project
        k_in = jax.random.split(ks[4])[0]
        shared["in_proj"] = dense_init(k_in, (2 * cfg.d_model, cfg.d_model),
                                       dtype=cfg.param_dtype)
        params["shared_attn"] = shared
    elif fam == "vlm":
        every = cfg.cross_attn_every
        n_groups = cfg.n_layers // every
        n_self = every - 1
        ksg = jax.random.split(ks[2], n_groups)
        params["layers"] = jax.vmap(
            lambda k: _stacked(_dense_layer_init, n_self, k, cfg))(ksg)
        params["cross_layers"] = _stacked(_cross_layer_init, n_groups, ks[3],
                                          cfg)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _effective_window(cfg: ModelConfig, is_local):
    """Window as int, traced scalar, or None.

    For gemma-2 local/global alternation the flag is a *traced* per-layer
    boolean riding through the scan, so the window becomes a traced scalar:
    the mask `j > i - window` handles both variants with one attention
    compute (global layers just get a 2^30 window)."""
    if cfg.attention_override_window is not None:
        return cfg.attention_override_window
    if cfg.local_global:
        return jnp.where(is_local, cfg.sliding_window, 2 ** 30)
    return cfg.sliding_window


def _split_experts(layers: PyTree, n_layers: int, dropless: bool):
    """Take a dropless mixture's expert stacks out of the scanned layers.

    Returns ``(scanned, experts, layer_ids)``.  ``experts`` holds the whole
    ``[L, E, ...]`` expert stacks, which the layer scan closes over and
    ``moe.moe_apply`` reads in place at ``(layer, expert)``; ``layer_ids``
    (``arange(L)``) rides the scan to name the layer.  Were the scan to
    slice each layer's ``[E, ...]`` slab, the slab would be an operand of
    the nested expert loop, which XLA copies whole every step.  Layers
    without a dropless mixture scan as they are: ``experts`` and
    ``layer_ids`` are None (an empty scan input)."""
    if not (dropless and "moe" in layers):
        return layers, None, None
    moe_p = dict(layers["moe"])
    experts = {n: moe_p.pop(n) for n in moe_mod.EXPERT_WEIGHTS}
    return {**layers, "moe": moe_p}, experts, jnp.arange(n_layers)


def _moe_params(p, experts):
    """The mixture's parameters for one step of the layer scan: the
    layer's router, plus the closed-over expert stacks where they were
    split out by :func:`_split_experts`."""
    return p["moe"] if experts is None else {**p["moe"], **experts}


def _dense_block(cfg: ModelConfig, p, x, positions, is_local, aux,
                 collect_kv=False, experts=None, layer=None):
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        out = attn.attn_apply(
            p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, positions=positions,
            rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            window=_effective_window(cfg, is_local),
            attn_cap=cfg.attn_softcap, impl=cfg.attention_impl,
            gqa_layout=cfg.gqa_layout, return_kv=collect_kv)
    h, kv = (out[0], out[1:]) if collect_kv else (out, None)
    x = x + h
    h = rms_norm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        with jax.named_scope("moe"):
            h, aux_l = moe_mod.moe_apply(
                _moe_params(p, experts), h, n_experts=cfg.n_experts,
                top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                dropless=cfg.moe_dropless, layer=layer)
        aux = aux + aux_l
    else:
        h = mlp_apply(p["mlp"], h, cfg.mlp_kind)
    if collect_kv:
        return x + h, aux, kv
    return x + h, aux


def _mamba_block(cfg: ModelConfig, p, x):
    h = rms_norm(p["ln"], x, cfg.norm_eps)
    h = m2.mamba2_apply(p["mixer"], h, d_state=cfg.d_state,
                        head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                        d_conv=cfg.d_conv, n_groups=cfg.ssm_n_groups,
                        chunk=cfg.ssd_chunk, impl=cfg.attention_impl
                        if cfg.attention_impl == "pallas" else "jnp")
    return x + h


def _embed_tokens(params: PyTree, cfg: ModelConfig, tokens):
    """tokens: (B, S) int32 (audio: (B, S, K)) -> activations (B, S, d)."""
    adt = cfg.activation_dtype
    if cfg.family == "audio":
        x = sum(params["embed"][k].astype(adt)[tokens[:, :, k]]
                for k in range(cfg.n_codebooks))
    else:
        x = params["embed"].astype(adt)[tokens]
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)  # gemma-style scaling
    return x


def forward(params: PyTree, cfg: ModelConfig, tokens, *, image_embeds=None,
            positions=None):
    """tokens: (B, S) int32 — or (B, S, K) for audio.  Returns logits
    (B, S, V) (audio: (B, S, K, V)) plus scalar aux loss."""
    adt = cfg.activation_dtype
    B, S = tokens.shape[0], tokens.shape[1]
    x = _embed_tokens(params, cfg, tokens)
    if positions is None:
        rows = 1 if cfg.broadcast_positions else B
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (rows, S))
    aux0 = jnp.zeros((), jnp.float32)

    fam = cfg.family
    if fam in ("dense", "moe", "audio"):
        local_flags = _local_flags(cfg)
        layers, experts, layer_ids = _split_experts(
            params["layers"], cfg.n_layers, cfg.moe_dropless)

        def body(carry, inp):
            x, aux = carry
            p, flag, l = inp
            x, aux = _dense_block(cfg, p, x, positions, flag, aux,
                                  experts=experts, layer=l)
            return (x, aux), None

        body = _maybe_remat(body, cfg)
        with jax.named_scope("layers"):
            (x, aux), _ = jax.lax.scan(body, (x, aux0),
                                       (layers, local_flags, layer_ids))
    elif fam == "ssm":
        def body(carry, p):
            return _mamba_block(cfg, p, carry), None

        body = _maybe_remat(body, cfg)
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = aux0
    elif fam == "hybrid":
        x, aux = _hybrid_forward(params, cfg, x, positions, aux0)
    elif fam == "vlm":
        assert image_embeds is not None, "vlm requires image_embeds"
        img = image_embeds.astype(adt)
        local_flags = _local_flags(cfg, cfg.n_layers // cfg.cross_attn_every
                                   * (cfg.cross_attn_every - 1))
        n_groups = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.cross_attn_every - 1
        flags_g = local_flags[: n_groups * n_self].reshape(n_groups, n_self)

        def group(carry, inp):
            x, aux = carry
            p_self, p_cross, flags = inp

            def inner(c, i):
                xx, a = c
                pp, f = i
                xx, a = _dense_block(cfg, pp, xx, positions, f, a)
                return (xx, a), None

            inner = _maybe_remat(inner, cfg)
            (x, aux), _ = jax.lax.scan(inner, (x, aux), (p_self, flags))
            h = rms_norm(p_cross["ln1"], x, cfg.norm_eps)
            h = attn.cross_attn_apply(p_cross["xattn"], h, img,
                                      n_heads=cfg.n_heads,
                                      n_kv=cfg.n_kv_heads,
                                      head_dim=cfg.head_dim)
            x = x + h
            h = rms_norm(p_cross["ln2"], x, cfg.norm_eps)
            x = x + mlp_apply(p_cross["mlp"], h, cfg.mlp_kind)
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(
            group, (x, aux0),
            (params["layers"], params["cross_layers"], flags_g))
    else:
        raise ValueError(fam)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, cfg, x)
    return logits, aux


def forward_prefill(params: PyTree, cfg: ModelConfig, tokens, *,
                    positions=None):
    """Full-sequence serving prefill: one forward pass that ALSO returns
    the per-layer decode KV, so caches (ring slots or pages) fill without
    the token-by-token demo loop.

    tokens: (B, S) int32 (audio: (B, S, K)).  Returns
    ``(logits, (k, v))`` with k, v shaped (L, B, S, Kv, hd) -- the
    rotated/normed tensors a decode cache stores.  Uniform-attention
    families only (:data:`PAGED_FAMILIES`); SSM/hybrid/vlm keep their
    own prefill paths.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"forward_prefill supports {PAGED_FAMILIES}, not {cfg.family}")
    B, S = tokens.shape[0], tokens.shape[1]
    x = _embed_tokens(params, cfg, tokens)
    if positions is None:
        rows = 1 if cfg.broadcast_positions else B
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (rows, S))
    aux0 = jnp.zeros((), jnp.float32)
    local_flags = _local_flags(cfg)
    layers, experts, layer_ids = _split_experts(
        params["layers"], cfg.n_layers, cfg.moe_dropless)

    def body(carry, inp):
        x, aux = carry
        p, flag, l = inp
        x, aux, (k, v) = _dense_block(cfg, p, x, positions, flag, aux,
                                      collect_kv=True, experts=experts,
                                      layer=l)
        return (x, aux), (k, v)

    with jax.named_scope("layers"):
        (x, _), (k_all, v_all) = jax.lax.scan(
            body, (x, aux0), (layers, local_flags, layer_ids))
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _lm_head(params, cfg, x), (k_all, v_all)


def _hybrid_forward(params, cfg, x, positions, aux):
    """zamba2: scan groups of `shared_attn_every` mamba layers, then apply the
    single SHARED attention block on concat(hidden, residual_stream_input)."""
    every = cfg.shared_attn_every
    L = cfg.n_layers
    n_groups, rem = divmod(L, every)
    x0 = x  # original embedding stream (zamba2 concatenates it)
    shared = params["shared_attn"]
    layers = params["layers"]
    head = jax.tree.map(lambda a: a[: n_groups * every].reshape(
        (n_groups, every) + a.shape[1:]), layers)
    tail = jax.tree.map(lambda a: a[n_groups * every:], layers)

    def mamba_body(c, p):
        return _mamba_block(cfg, p, c), None

    mamba_body = _maybe_remat(mamba_body, cfg)

    def group(carry, p_group):
        x, aux = carry
        x, _ = jax.lax.scan(mamba_body, x, p_group)
        h = jnp.concatenate([x, x0], axis=-1)
        h = jnp.einsum("bsd,dk->bsk", h, shared["in_proj"].astype(x.dtype))
        h2 = rms_norm(shared["ln1"], h, cfg.norm_eps)
        h2 = attn.attn_apply(
            shared["attn"], h2, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, positions=positions,
            rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            window=cfg.window_for(True), attn_cap=cfg.attn_softcap,
            impl=cfg.attention_impl)
        h = h + h2
        h2 = rms_norm(shared["ln2"], h, cfg.norm_eps)
        h = h + mlp_apply(shared["mlp"], h2, cfg.mlp_kind)
        return (x + h, aux), None

    (x, aux), _ = jax.lax.scan(group, (x, aux), head)
    if rem:
        x, _ = jax.lax.scan(mamba_body, x, tail)
    return x, aux


def _lm_head(params, cfg, x):
    with jax.named_scope("lm_head"):
        if cfg.family == "audio":
            return jnp.einsum("bsd,kdv->bskv", x,
                              params["lm_head"].astype(x.dtype))
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(x.dtype))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(x.dtype))
        return softcap(logits, cfg.final_softcap)


def _local_flags(cfg: ModelConfig, n: int | None = None):
    n = cfg.n_layers if n is None else n
    if cfg.local_global:
        return jnp.arange(n) % 2 == 0  # even layers local (gemma2)
    return jnp.zeros((n,), bool)


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16) -> PyTree:
    """Stacked (per-scanned-layer) decode caches."""
    fam = cfg.family

    def kv(n):
        return jax.vmap(lambda _: attn.init_kv_cache(
            batch, cfg.n_kv_heads, cache_len, cfg.head_dim, dtype))(
                jnp.arange(n))

    def ssm(n):
        d_inner = cfg.ssm_expand * cfg.d_model
        conv_dim = d_inner + 2 * cfg.ssm_n_groups * cfg.d_state
        nh = d_inner // cfg.ssm_head_dim
        return jax.vmap(lambda _: m2.init_ssm_cache(
            batch, cfg.d_conv, conv_dim, nh, cfg.ssm_head_dim, cfg.d_state,
            dtype))(jnp.arange(n))

    if fam in ("dense", "moe", "audio"):
        return {"kv": kv(cfg.n_layers)}
    if fam == "ssm":
        return {"ssm": ssm(cfg.n_layers)}
    if fam == "hybrid":
        n_groups = cfg.n_layers // cfg.shared_attn_every
        return {"ssm": ssm(cfg.n_layers), "shared_kv": kv(n_groups)}
    if fam == "vlm":
        n_groups = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.cross_attn_every - 1
        kvs = jax.vmap(lambda _: attn.init_kv_cache(
            batch, cfg.n_kv_heads, cache_len, cfg.head_dim, dtype))(
                jnp.arange(n_groups * n_self))
        kvs = jax.tree.map(lambda a: a.reshape(
            (n_groups, n_self) + a.shape[1:]), kvs)
        return {"kv": kvs}
    raise ValueError(fam)


def decode_step(params: PyTree, cfg: ModelConfig, token, cache: PyTree, idx,
                *, image_embeds=None):
    """One-token decode. token: (B,1) int32 (audio: (B,1,K)); idx scalar.
    Returns (logits, new_cache)."""
    x = _embed_tokens(params, cfg, token)
    fam = cfg.family

    def dense_decode(p, x, kvc, is_local, experts=None, layer=None):
        h = rms_norm(p["ln1"], x, cfg.norm_eps)
        window = _effective_window(cfg, is_local)
        with jax.named_scope("attention"):
            h, kvc = attn.attn_decode(
                p["attn"], h, kvc, idx, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                window=window, attn_cap=cfg.attn_softcap)
        x = x + h
        h = rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            # decode is always dropless: a capacity drop here would make a
            # token's logits depend on co-batched requests (and diverge
            # from prefill).
            with jax.named_scope("moe"):
                h, _ = moe_mod.moe_apply(_moe_params(p, experts), h,
                                         n_experts=cfg.n_experts,
                                         top_k=cfg.top_k, dropless=True,
                                         layer=layer)
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_kind)
        return x + h, kvc

    if fam in ("dense", "moe", "audio"):
        flags = _local_flags(cfg)
        layers, experts, layer_ids = _split_experts(
            params["layers"], cfg.n_layers, dropless=True)

        def body(x, inp):
            p, kvc, flag, l = inp
            x, kvc = dense_decode(p, x, attn.KVCache(*kvc), flag,
                                  experts=experts, layer=l)
            return x, (kvc.k, kvc.v)

        with jax.named_scope("layers"):
            x, new_kv = jax.lax.scan(
                body, x, (layers, (cache["kv"].k, cache["kv"].v), flags,
                          layer_ids))
        new_cache = {"kv": attn.KVCache(*new_kv)}
    elif fam == "ssm":
        def body(x, inp):
            p, c = inp
            h = rms_norm(p["ln"], x, cfg.norm_eps)
            h, c2 = m2.mamba2_decode(p["mixer"], h, m2.SSMCache(*c),
                                     d_state=cfg.d_state,
                                     head_dim=cfg.ssm_head_dim,
                                     expand=cfg.ssm_expand,
                                     d_conv=cfg.d_conv,
                                     n_groups=cfg.ssm_n_groups)
            return x + h, (c2.conv, c2.state)

        x, new_ssm = jax.lax.scan(
            body, x, (params["layers"],
                      (cache["ssm"].conv, cache["ssm"].state)))
        new_cache = {"ssm": m2.SSMCache(*new_ssm)}
    elif fam == "hybrid":
        x, new_cache = _hybrid_decode(params, cfg, x, cache, idx)
    elif fam == "vlm":
        assert image_embeds is not None
        img = image_embeds.astype(cfg.activation_dtype)
        n_groups = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.cross_attn_every - 1
        flags = _local_flags(cfg, n_groups * n_self).reshape(n_groups, n_self)

        def group(x, inp):
            p_self, p_cross, kvc, fl = inp

            def inner(x, i):
                pp, c, f = i
                x, c2 = dense_decode(pp, x, attn.KVCache(*c), f)
                return x, (c2.k, c2.v)

            x, kv2 = jax.lax.scan(inner, x, (p_self, kvc, fl))
            h = rms_norm(p_cross["ln1"], x, cfg.norm_eps)
            h = attn.cross_attn_apply(p_cross["xattn"], h, img,
                                      n_heads=cfg.n_heads,
                                      n_kv=cfg.n_kv_heads,
                                      head_dim=cfg.head_dim)
            x = x + h
            h = rms_norm(p_cross["ln2"], x, cfg.norm_eps)
            x = x + mlp_apply(p_cross["mlp"], h, cfg.mlp_kind)
            return x, kv2

        x, new_kv = jax.lax.scan(
            group, x, (params["layers"], params["cross_layers"],
                       (cache["kv"].k, cache["kv"].v), flags))
        new_cache = {"kv": attn.KVCache(*new_kv)}
    else:
        raise ValueError(fam)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, cfg, x)
    return logits, new_cache


def decode_step_paged(params: PyTree, cfg: ModelConfig, token, pool,
                      page_table, positions, *, page_size: int):
    """One-token decode over a PAGED KV pool (continuous batching).

    token: (B, 1) int32 (audio: (B, 1, K)); positions: (B,) int32 -- each
    sequence decodes at its OWN absolute position.  pool: ``{"k", "v"}``
    shaped (L, Kv, n_pages, page_size, hd); page_table: (B, Pmax) int32.
    Returns (logits, new_pool).  Uniform-attention families only
    (:data:`PAGED_FAMILIES`).
    """
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"decode_step_paged supports {PAGED_FAMILIES}, not {cfg.family}")
    x = _embed_tokens(params, cfg, token)
    flags = _local_flags(cfg)
    layers, experts, layer_ids = _split_experts(
        params["layers"], cfg.n_layers, dropless=True)

    def body(x, inp):
        p, kp, vp, flag, l = inp
        h = rms_norm(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention"):
            h, kp, vp = attn.attn_decode_paged(
                p["attn"], h, kp, vp, page_table, positions,
                page_size=page_size, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                window=_effective_window(cfg, flag),
                attn_cap=cfg.attn_softcap, impl=cfg.attention_impl)
        x = x + h
        h = rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            # decode is always dropless (see decode_step)
            with jax.named_scope("moe"):
                h, _ = moe_mod.moe_apply(_moe_params(p, experts), h,
                                         n_experts=cfg.n_experts,
                                         top_k=cfg.top_k, dropless=True,
                                         layer=l)
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_kind)
        return x + h, (kp, vp)

    with jax.named_scope("layers"):
        x, (k_all, v_all) = jax.lax.scan(
            body, x, (layers, pool["k"], pool["v"], flags, layer_ids))
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, cfg, x)
    return logits, {"k": k_all, "v": v_all}


def _hybrid_decode(params, cfg, x, cache, idx):
    every = cfg.shared_attn_every
    L = cfg.n_layers
    n_groups, rem = divmod(L, every)
    x0 = x
    shared = params["shared_attn"]
    layers = params["layers"]
    head = jax.tree.map(lambda a: a[: n_groups * every].reshape(
        (n_groups, every) + a.shape[1:]), layers)
    tail = jax.tree.map(lambda a: a[n_groups * every:], layers)
    ssm_all = cache["ssm"]
    ssm_head = jax.tree.map(lambda a: a[: n_groups * every].reshape(
        (n_groups, every) + a.shape[1:]), ssm_all)
    ssm_tail = jax.tree.map(lambda a: a[n_groups * every:], ssm_all)

    def mamba_body(x, inp):
        p, c = inp
        h = rms_norm(p["ln"], x, cfg.norm_eps)
        h, c2 = m2.mamba2_decode(p["mixer"], h, m2.SSMCache(*c),
                                 d_state=cfg.d_state,
                                 head_dim=cfg.ssm_head_dim,
                                 expand=cfg.ssm_expand, d_conv=cfg.d_conv,
                                 n_groups=cfg.ssm_n_groups)
        return x + h, (c2.conv, c2.state)

    def group(x, inp):
        p_group, ssm_c, kv_c = inp
        x, ssm2 = jax.lax.scan(mamba_body, x, (p_group,
                                               (ssm_c.conv, ssm_c.state)))
        h = jnp.concatenate([x, x0], axis=-1)
        h = jnp.einsum("bsd,dk->bsk", h, shared["in_proj"].astype(x.dtype))
        h2 = rms_norm(shared["ln1"], h, cfg.norm_eps)
        h2, kv2 = attn.attn_decode(
            shared["attn"], h2, attn.KVCache(*kv_c), idx,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            window=cfg.window_for(True), attn_cap=cfg.attn_softcap)
        h = h + h2
        h2 = rms_norm(shared["ln2"], h, cfg.norm_eps)
        h = h + mlp_apply(shared["mlp"], h2, cfg.mlp_kind)
        return x + h, (ssm2, (kv2.k, kv2.v))

    x, (new_ssm, new_kv) = jax.lax.scan(
        group, x, (head, ssm_head, (cache["shared_kv"].k,
                                    cache["shared_kv"].v)))
    if rem:
        x, new_tail = jax.lax.scan(mamba_body, x,
                                   (tail, (ssm_tail.conv, ssm_tail.state)))
    else:
        new_tail = (ssm_tail.conv, ssm_tail.state)
    conv = jnp.concatenate([new_ssm[0].reshape((-1,) + new_ssm[0].shape[2:]),
                            new_tail[0]], axis=0)
    state = jnp.concatenate([new_ssm[1].reshape((-1,) + new_ssm[1].shape[2:]),
                             new_tail[1]], axis=0)
    return x, {"ssm": m2.SSMCache(conv, state),
               "shared_kv": attn.KVCache(*new_kv)}


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------

def param_count(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def active_param_count(params: PyTree, cfg: ModelConfig) -> int:
    """MoE: count only top_k/n_experts of expert params (for MODEL_FLOPS)."""
    total = param_count(params)
    if not cfg.n_experts:
        return total

    def expert_size(p):
        if isinstance(p, dict) and "w_gate" in p and p["w_gate"].ndim == 4:
            pass
        return 0

    # stacked layers: moe expert tensors have shape (L, E, ., .)
    inactive = 0
    layers = params.get("layers", {})
    moe_p = layers.get("moe") if isinstance(layers, dict) else None
    if moe_p:
        for name in ("w_gate", "w_up", "w_down"):
            t = moe_p[name]
            inactive += int(t.size) * (cfg.n_experts - cfg.top_k) // cfg.n_experts
    return total - inactive
