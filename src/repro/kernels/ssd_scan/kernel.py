"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060, GPU Triton original):
  * Grid = (batch*heads, chunks) with the chunk axis innermost: pallas TPU
    executes the grid sequentially, so the inter-chunk SSM state lives in a
    VMEM scratch accumulator carried across chunk iterations -- the TPU
    equivalent of the GPU kernel's cross-CTA state passing (which needs
    grid-sync / multi-kernel on CUDA; on TPU the sequential grid gives it
    for free).
  * Intra-chunk work is three MXU matmuls: scores = C B^T (L x L), the
    masked-decay weighted y_intra = M (dt x), and the state outer-product
    update -- L (chunk) and N (d_state) chosen as multiples of the 128-wide
    MXU systolic array; P (head_dim 64) rides the lane dimension.
  * All accumulation in f32 VMEM regardless of input dtype.

Inputs are pre-arranged per head by ops.py: x (BH, S, P), dt (BH, S, 1)
(already softplus'ed), dA = dt * A (BH, S, 1), B, C (BH, S, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, hT_ref,
                state_ref, *, chunk: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)      # (L, P)
    dt = dt_ref[0].astype(jnp.float32)    # (L, 1)
    dA = da_ref[0].astype(jnp.float32)    # (L, 1)
    B = b_ref[0].astype(jnp.float32)      # (L, N)
    C = c_ref[0].astype(jnp.float32)      # (L, N)

    cum = jnp.cumsum(dA, axis=0)          # (L, 1)
    # intra-chunk: y[t] = sum_{u<=t} (C_t . B_u) exp(cum_t - cum_u) dt_u x_u
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    diff = cum - cum.T                     # (L, L): cum_t - cum_u
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    decay = jnp.exp(jnp.where(tri, diff, -1e30))
    M = scores * decay
    y = jax.lax.dot_general(M, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y[t] += exp(cum_t) C_t . H_in  ;  H_in = state (N, P)
    y += jnp.exp(cum) * jax.lax.dot_general(
        C, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: H = exp(cum_end) H + sum_u exp(cum_end - cum_u) dt_u B_u x_u^T
    cum_end = cum[chunk - 1:chunk]         # (1, 1)
    w = jnp.exp(cum_end - cum) * dt        # (L, 1)
    state_ref[...] = (state_ref[...] * jnp.exp(cum_end)
                      + jax.lax.dot_general(
                          B * w, x, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        hT_ref[0] = state_ref[...].astype(hT_ref.dtype)


def ssd_scan_kernel(x, dt, dA, B, C, *, chunk: int = 128,
                    interpret: bool = False):
    """x: (BH, S, P); dt, dA: (BH, S, 1); B, C: (BH, S, N).
    Returns (y (BH, S, P), h_final (BH, N, P))."""
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    grid = (BH, nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, dA, B, C)
    return y, hT
