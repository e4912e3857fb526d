"""Model configurations: the JSON file under ``bench/configs/`` -> the
program's ``ModelConfig`` and the plain dimensions the reference, the
weight generator and the FLOP counts read.

The file holds the published ``config.json`` keys; ``assumed`` holds the
sizes and dtypes the source leaves open, and ``qk_norm`` says whether the
architecture normalises queries and keys (its ``model_type`` implies it;
no published key says it).  Any decoder-only model with these keys maps.
"""
from __future__ import annotations

import dataclasses
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def load(name: str, base: str = BENCH) -> dict:
    path = os.path.join(base, "configs", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"{path} names itself {spec.get('name')!r}")
    return spec


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


def shrink(dm: Dims, **kw) -> Dims:
    """A smaller copy for the CPU tests (never used on the chip)."""
    return dataclasses.replace(dm, **kw)


def program_config(dm: Dims, name: str, *, remat: bool = True,
                   attention_impl: str = "jnp"):
    """The program's ``ModelConfig`` for these dimensions."""
    import jax.numpy as jnp

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
