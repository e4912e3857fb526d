"""Gossip-mix Pallas TPU kernel: fused weighted averaging of the local buffer
with received neighbor buffers (the compute half of neighbor_allreduce).

After the ppermute delivers neighbor shards, the mixing
  out = w_self * x + sum_d w_d * recv_d
is a pure-bandwidth elementwise pass over every parameter/momentum byte.
Fusing all (1 + degree) reads and the f32 upcast into one VMEM-tiled kernel
keeps it a single HBM sweep (XLA would otherwise materialize the f32
intermediates for mixed-dtype buffers).  Blocks hold 8 * 1024 elements
(32 KiB of f32) -- (8, 1024), or (1, 8192) for a single node's row -- and
the grid walks the buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_ROWS = 8
TILE_COLS = 1024


def _mix_kernel(*refs, w_self: float, ws: tuple):
    x_ref = refs[0]
    recv_refs = refs[1:-1]
    o_ref = refs[-1]
    acc = w_self * x_ref[...].astype(jnp.float32)
    for w, r in zip(ws, recv_refs):
        acc += w * r[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def gossip_mix_kernel(x, recvs, w_self: float, ws: tuple,
                      interpret: bool = False):
    """x, recvs[i]: (R, C) same shape/dtype, R < TILE_ROWS or a multiple of
    it, C a multiple of TILE_COLS or smaller (ops.py arranges this).

    A block holds TILE_ROWS * TILE_COLS elements: fewer rows buy wider
    blocks, so a packed (1, B) node buffer streams in (1, 8192) blocks."""
    R, C = x.shape
    tr = min(TILE_ROWS, R)
    tc = min(TILE_COLS * (TILE_ROWS // tr), C)
    if C % tc:
        tc = min(TILE_COLS, C)
    assert R % tr == 0 and C % tc == 0, (R, C, tr, tc)
    grid = (R // tr, C // tc)
    spec = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    kernel = functools.partial(_mix_kernel, w_self=w_self, ws=tuple(ws))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * (1 + len(recvs)),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        # the result overwrites x (each block is read before it is
        # written): a gossip round then holds two payload-sized buffers
        # (local + received), not three; XLA copies x first where it is
        # still needed afterwards
        input_output_aliases={0: 0},
        interpret=interpret,
        name="gossip_mix",
    )(x, *recvs)
