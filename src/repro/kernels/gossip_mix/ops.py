"""jit'd wrapper: flatten/pad arbitrary buffers into kernel tiles."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import kernel as K


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("w_self", "ws", "interpret"))
def gossip_mix(x, recvs, *, w_self: float, ws: tuple,
               interpret: bool | None = None):
    """out = w_self * x + sum_d ws[d] * recvs[d]; any shape/dtype."""
    if interpret is None:
        interpret = not _on_tpu()
    recvs = list(recvs)
    if (x.ndim == 2 and x.shape[1] % K.TILE_COLS == 0
            and (x.shape[0] < K.TILE_ROWS or x.shape[0] % K.TILE_ROWS == 0)):
        # a packed (rows, B) gossip buffer is tile-aligned as it stands.
        # Reshaping it to (rows * B / 1024, 1024) is no bitcast on TPU: the
        # two shapes tile differently in HBM, so each operand and the
        # result would be copied (three payload-sized temporaries).
        return K.gossip_mix_kernel(x, recvs, w_self, tuple(ws),
                                   interpret=interpret)
    shape, dtype = x.shape, x.dtype
    n = x.size
    cols = min(K.TILE_COLS, max(n, 1))
    rows_needed = -(-n // cols)
    rows = -(-rows_needed // K.TILE_ROWS) * K.TILE_ROWS if rows_needed > 1 \
        else 1
    pad = rows * cols - n

    def prep(a):
        f = a.reshape(-1)
        if pad:
            f = jnp.pad(f, (0, pad))
        return f.reshape(rows, cols)

    out = K.gossip_mix_kernel(prep(x), [prep(r) for r in recvs],
                              w_self, tuple(ws), interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)
