"""Operations and bytes computed from shapes, for MFU and roofline shares.

Counts are of the work the mathematics needs: recomputation under remat
is not counted, causal attention counts the attended half, and a mixture
of experts counts the experts each token is routed to.
"""
from __future__ import annotations

from .spec import Dims
from .weights import shapes


def param_counts(dm: Dims) -> tuple[int, int]:
    """(all parameters, those a token uses); for a mixture of experts the
    routed experts count top_k / n_experts of their size."""
    total = active = 0
    for name, (shape, _) in shapes(dm).items():
        size = 1
        for s in shape:
            size *= s
        total += size
        if name.startswith("we_"):
            size = size * dm.top_k // dm.n_experts
        active += size
    return total, active


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


def forward_flops_per_token(dm: Dims, seq: int) -> float:
    """Mean forward FLOPs per token of a causal sequence of ``seq``."""
    return (2.0 * matmul_params_per_token(dm)
            + attention_flops(dm, (seq + 1) / 2))


def train_flops_per_token(dm: Dims, seq: int) -> float:
    """Forward and backward (backward = twice forward)."""
    return 3.0 * forward_flops_per_token(dm, seq)


def prefill_flops(dm: Dims, n_tokens: int) -> float:
    """A prompt of ``n_tokens`` processed in one pass."""
    return (2.0 * matmul_params_per_token(dm) * n_tokens
            + attention_flops(dm, 1.0) * n_tokens * (n_tokens + 1) / 2)


def decode_flops(dm: Dims, context: int) -> float:
    """One token decoded after ``context`` earlier tokens."""
    return 2.0 * matmul_params_per_token(dm) + attention_flops(dm, context + 1)

