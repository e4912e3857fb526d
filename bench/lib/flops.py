"""Operations and bytes computed from shapes, for MFU and roofline shares.

Counts are of the work the mathematics needs: recomputation under remat
is not counted, causal attention counts the attended half, and a mixture
of experts counts the experts each token is routed to.  The architecture
(``spec``) gives the weights a token multiplies by and the attention's
FLOPs per query token, affine in the context; the formulas here are
built on those.
"""
from __future__ import annotations

from .spec import arch_of


def param_counts(dm) -> tuple[int, int]:
    """(all parameters, those a token uses)."""
    arch = arch_of(dm)
    total = active = 0
    for name, (shape, _) in arch.shapes(dm).items():
        size = 1
        for s in shape:
            size *= s
        total += size
        active += arch.active_params(dm, name, size)
    return total, active


def forward_flops_per_token(dm, seq: int) -> float:
    """Mean forward FLOPs per token of a causal sequence of ``seq``."""
    arch = arch_of(dm)
    return (2.0 * arch.matmul_params_per_token(dm)
            + arch.attention_flops(dm, (seq + 1) / 2))


def train_flops_per_token(dm, seq: int) -> float:
    """Forward and backward (backward = twice forward)."""
    return 3.0 * forward_flops_per_token(dm, seq)


def prefill_flops(dm, n_tokens: int) -> float:
    """A prompt of ``n_tokens`` processed in one pass."""
    arch = arch_of(dm)
    return (2.0 * arch.matmul_params_per_token(dm) * n_tokens
            + arch.attention_flops(dm, (n_tokens + 1) / 2) * n_tokens)


def decode_flops(dm, context: int) -> float:
    """One token decoded after ``context`` earlier tokens."""
    arch = arch_of(dm)
    return (2.0 * arch.matmul_params_per_token(dm)
            + arch.attention_flops(dm, context + 1))
