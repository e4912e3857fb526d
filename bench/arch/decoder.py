"""The decoder-only transformer: RoPE attention with grouped queries (and
optionally normalised queries and keys), then a SwiGLU MLP or a top-k
mixture of SwiGLU experts, in pre-norm residual layers.

Everything the benchmark knows of this architecture: its dimensions, the
program's ``ModelConfig`` for them, the table of seeded weights, the plain
float32 reference and the counts the FLOP formulas need.  A configuration
whose file says ``"architecture": "decoder"`` is run through this module.

The file holds the published ``config.json`` keys; ``assumed`` holds the
sizes and dtypes the source leaves open, and ``qk_norm`` says whether the
architecture normalises queries and keys (its ``model_type`` implies it;
no published key says it).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.lib.reference import HIGHEST, mm, next_token_loss, rms, rope


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the reference needs of a decoder-only transformer."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    qk_norm: bool
    tied: bool
    n_experts: int = 0
    top_k: int = 0
    param_dtype: str = "float32"
    activation_dtype: str = "bfloat16"
    embed_init_scale: float = 1.0


def dims(spec: dict) -> Dims:
    assumed = spec.get("assumed", {})
    d = spec["hidden_size"]
    heads = spec["num_attention_heads"]
    return Dims(
        n_layers=spec["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv=spec["num_key_value_heads"],
        head_dim=spec.get("head_dim", d // heads),
        d_ff=spec["intermediate_size"], vocab=spec["vocab_size"],
        rope_theta=float(spec["rope_theta"]), eps=float(spec["rms_norm_eps"]),
        qk_norm=bool(spec.get("qk_norm", False)), tied=bool(spec["tie_word_embeddings"]),
        n_experts=spec.get("num_local_experts", 0),
        top_k=spec.get("num_experts_per_tok", 0),
        param_dtype=assumed.get("param_dtype", "float32"),
        activation_dtype=assumed.get("activation_dtype", "bfloat16"),
        embed_init_scale=assumed.get("embed_init_scale", 1.0))


def program_config(dm: Dims, name: str, *, remat: bool = True,
                   attention_impl: str = "jnp"):
    """The program's ``ModelConfig`` for these dimensions."""
    from repro.models.model import ModelConfig

    return ModelConfig(
        name=name, family="moe" if dm.n_experts else "dense",
        n_layers=dm.n_layers, d_model=dm.d_model, n_heads=dm.n_heads,
        n_kv_heads=dm.n_kv, head_dim=dm.head_dim, d_ff=dm.d_ff,
        vocab_size=dm.vocab, qk_norm=dm.qk_norm, rope_theta=dm.rope_theta,
        tie_embeddings=dm.tied, n_experts=dm.n_experts, top_k=dm.top_k,
        norm_eps=dm.eps, param_dtype=getattr(jnp, dm.param_dtype),
        activation_dtype=getattr(jnp, dm.activation_dtype), remat=remat,
        attention_impl=attention_impl)


# ---------------------------------------------------------------------------
# the seeded weights
# ---------------------------------------------------------------------------

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
LAYERED = tuple(_LAYER_PATHS)   # leaves stacked over layers
F32 = ("router",)   # kept in f32 by the program whatever the param dtype


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


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------

def _attention(h, lw, dm: Dims, pos, mode):
    B, S, _ = h.shape
    H, Kv, hd = dm.n_heads, dm.n_kv, dm.head_dim
    q = mm("bsd,dh->bsh", h, lw["wq"], mode).reshape(B, S, H, hd)
    k = mm("bsd,dh->bsh", h, lw["wk"], mode).reshape(B, S, Kv, hd)
    v = mm("bsd,dh->bsh", h, lw["wv"], mode).reshape(B, S, Kv, hd)
    if dm.qk_norm:
        q = rms(q, lw["q_norm"], dm.eps)
        k = rms(k, lw["k_norm"], dm.eps)
    q, k = rope(q, pos, dm.rope_theta), rope(k, pos, dm.rope_theta)
    # grouped-query attention: query head j reads key/value head j // G
    k = jnp.repeat(k, H // Kv, axis=2)
    v = jnp.repeat(v, H // Kv, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = pos[:, None, :, None] >= pos[:, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return mm("bsh,hd->bsd", o.reshape(B, S, H * hd), lw["wo"], mode)


def _moe(h, lw, dm: Dims, mode):
    """Every token through its top-k experts, gates renormalised over them
    (softmax over the k largest router logits)."""
    logits = jnp.einsum("bsd,de->bse", h, lw["router"], precision=HIGHEST)
    top, idx = jax.lax.top_k(logits, dm.top_k)
    gates = jax.nn.softmax(top, -1)
    weight = jnp.sum(jax.nn.one_hot(idx, dm.n_experts) * gates[..., None], -2)

    def expert(out, e):
        wg, wu, wd, we = e
        g = mm("bsd,df->bsf", h, wg, mode)
        u = mm("bsd,df->bsf", h, wu, mode)
        y = mm("bsf,fd->bsd", jax.nn.silu(g) * u, wd, mode)
        return out + we[..., None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        lw["we_gate"], lw["we_up"], lw["we_down"], jnp.moveaxis(weight, -1, 0)))
    return out


def _mlp(h, lw, mode):
    g = mm("bsd,df->bsf", h, lw["w_gate"], mode)
    u = mm("bsd,df->bsf", h, lw["w_up"], mode)
    return mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"], mode)


def embed(w: dict, tokens, dm: Dims):
    """The residual stream's start: the program scales embeddings by
    sqrt(d) (see the config's departures)."""
    return w["embed"][tokens].astype(jnp.float32) * math.sqrt(dm.d_model)


def hidden(w: dict, tokens, dm: Dims, mode: str = "f32", *, lo: int = 0,
           hi: int | None = None, x=None, pos=None):
    """The residual stream after layers ``lo`` to ``hi`` (all by default),
    starting from the embeddings of ``tokens`` or from ``x``."""
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    if x is None:
        x = embed(w, tokens, dm)
    stack = {k: w[k][lo:hi].astype(jnp.float32) for k in LAYERED if k in w}

    def layer(x, lw):
        x = x + _attention(rms(x, lw["attn_norm"], dm.eps), lw, dm, pos,
                           mode)
        h = rms(x, lw["mlp_norm"], dm.eps)
        return x + (_moe(h, lw, dm, mode) if dm.n_experts
                    else _mlp(h, lw, mode)), None

    x, _ = jax.lax.scan(layer, x, stack)
    return x


def head(w: dict, x, dm: Dims, mode: str = "f32"):
    x = rms(x, w["final_norm"].astype(jnp.float32), dm.eps)
    if dm.tied:
        return mm("bsd,vd->bsv", x, w["embed"].astype(jnp.float32), mode)
    return mm("bsd,dv->bsv", x, w["lm_head"].astype(jnp.float32), mode)


def loss(w: dict, tokens, dm: Dims, mode: str = "f32", fault: str | None = None):
    return next_token_loss(head(w, hidden(w, tokens, dm, mode), dm, mode),
                           tokens, fault)


# ---------------------------------------------------------------------------
# the counts the FLOP formulas need
# ---------------------------------------------------------------------------

def active_params(dm: Dims, name: str, size: int) -> int:
    """Of a leaf's ``size`` parameters, those one token uses: the routed
    experts count top_k / n_experts of their size."""
    if name.startswith("we_"):
        return size * dm.top_k // dm.n_experts
    return size


def matmul_params_per_token(dm: Dims) -> int:
    """Weights one token multiplies by in a forward pass (active experts
    only; the output head once, tied or not)."""
    hq, hkv = dm.n_heads * dm.head_dim, dm.n_kv * dm.head_dim
    d = dm.d_model
    attn = d * hq + 2 * d * hkv + hq * d
    if dm.n_experts:
        ffn = d * dm.n_experts + dm.top_k * 3 * d * dm.d_ff
    else:
        ffn = 3 * d * dm.d_ff
    return dm.n_layers * (attn + ffn) + d * dm.vocab


def attention_flops(dm: Dims, context: float) -> float:
    """QK^T and PV for one query token over ``context`` keys."""
    return 4.0 * dm.n_layers * dm.n_heads * dm.head_dim * context
