"""Pallas kernel validation: shape/dtype sweeps + hypothesis, interpret=True
against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.gossip_mix import ops as gm_ops, ref as gm_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref
from repro.models import mamba2 as m2

TOL = dict(rtol=2e-2, atol=2e-2)
TOL32 = dict(rtol=2e-4, atol=2e-4)


def _tol(dtype):
    return TOL if dtype == jnp.bfloat16 else TOL32


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,Kv,D", [
    (1, 128, 128, 4, 4, 64),     # MHA
    (2, 256, 256, 4, 2, 64),     # GQA
    (1, 128, 128, 8, 1, 128),    # MQA, fat head_dim
    (1, 192, 192, 2, 2, 64),     # non-pow2 seq (padding path)
    (1, 64, 64, 2, 1, 32),       # tiny blocks
])
def test_flash_attention_shapes(B, S, T, H, Kv, D, dtype):
    k = jax.random.key(hash((B, S, H)) % 2**31)
    q = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, D), dtype)
    kk = jax.random.normal(jax.random.fold_in(k, 2), (B, T, Kv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(k, 3), (B, T, Kv, D), dtype)
    got = fa_ops.flash_attention(q, kk, v, causal=True, interpret=True)
    want = fa_ref.attention_ref(q, kk, v, causal=True)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 128, None])
@pytest.mark.parametrize("attn_cap", [None, 50.0])
def test_flash_attention_window_softcap(window, attn_cap):
    B, S, H, Kv, D = 1, 256, 4, 2, 64
    k = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, D))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (B, S, Kv, D))
    v = jax.random.normal(jax.random.fold_in(k, 3), (B, S, Kv, D))
    got = fa_ops.flash_attention(q, kk, v, causal=True, window=window,
                                 attn_cap=attn_cap, interpret=True)
    want = fa_ref.attention_ref(q, kk, v, causal=True, window=window,
                                attn_cap=attn_cap)
    np.testing.assert_allclose(got, want, **TOL32)


def test_flash_attention_matches_model_attention():
    """Kernel path == model's jnp attention path (positions = arange)."""
    from repro.models import attention as A
    B, S, H, Kv, D, d_model = 2, 128, 4, 2, 64, 96
    k = jax.random.key(7)
    params = A.attn_init(k, d_model, H, Kv, D)
    x = jax.random.normal(jax.random.fold_in(k, 1), (B, S, d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    y_jnp = A.attn_apply(params, x, n_heads=H, n_kv=Kv, head_dim=D,
                         positions=pos, impl="jnp")
    y_pal = A.attn_apply(params, x, n_heads=H, n_kv=Kv, head_dim=D,
                         positions=pos, impl="pallas")
    np.testing.assert_allclose(y_pal, y_jnp, rtol=1e-3, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(
    B=st.integers(1, 2),
    s_pow=st.integers(5, 8),
    H=st.sampled_from([2, 4]),
    D=st.sampled_from([32, 64]),
    causal=st.booleans(),
)
def test_flash_attention_property(B, s_pow, H, D, causal):
    S = 2 ** s_pow
    k = jax.random.key(s_pow * 7 + B)
    q = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, D))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(k, 3), (B, S, H, D))
    got = fa_ops.flash_attention(q, kk, v, causal=causal, interpret=True)
    want = fa_ref.attention_ref(q, kk, v, causal=causal)
    np.testing.assert_allclose(got, want, **TOL32)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 128, 2, 64, 1, 64, 64),
    (2, 256, 4, 32, 2, 32, 128),
    (1, 64, 2, 64, 1, 128, 32),
    (1, 512, 2, 64, 1, 64, 128),
])
def test_ssd_scan_shapes(b, s, h, p, g, n, chunk, dtype):
    k = jax.random.key(s + h)
    x = jax.random.normal(jax.random.fold_in(k, 1), (b, s, h, p), dtype)
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(k, 2), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(k, 4), (b, s, g, n), dtype)
    C = jax.random.normal(jax.random.fold_in(k, 5), (b, s, g, n), dtype)
    y, hT = ssd_ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, h_ref = ssd_ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y, y_ref, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(hT, h_ref, rtol=1e-3, atol=1e-3)


def test_model_chunked_matches_naive_recurrence():
    """The model's pure-jnp chunked SSD == naive recurrence oracle."""
    b, s, h, p, g, n = 2, 256, 4, 32, 1, 64
    k = jax.random.key(3)
    x = jax.random.normal(jax.random.fold_in(k, 1), (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 2), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(k, 4), (b, s, g, n))
    C = jax.random.normal(jax.random.fold_in(k, 5), (b, s, g, n))
    y1, h1 = m2.ssd_chunked(x, dt, A, B, C, chunk=64)
    y2, h2 = ssd_ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y1, y2, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(h1, h2, rtol=1e-3, atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(
    s_pow=st.integers(6, 9),
    h=st.sampled_from([1, 2, 4]),
    chunk_pow=st.integers(5, 7),
)
def test_ssd_scan_property(s_pow, h, chunk_pow):
    b, p, g, n = 1, 32, 1, 32
    s, chunk = 2 ** s_pow, 2 ** chunk_pow
    k = jax.random.key(s_pow * 31 + h)
    x = jax.random.normal(jax.random.fold_in(k, 1), (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 2), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(k, 4), (b, s, g, n))
    C = jax.random.normal(jax.random.fold_in(k, 5), (b, s, g, n))
    y, hT = ssd_ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, h_ref = ssd_ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y, y_ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(hT, h_ref, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# gossip mix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 1024), (3, 5, 7), (1000,), (17,),
                                   (128, 4096)])
@pytest.mark.parametrize("degree", [1, 3])
def test_gossip_mix(shape, degree, dtype):
    k = jax.random.key(sum(shape) + degree)
    x = jax.random.normal(jax.random.fold_in(k, 0), shape, dtype)
    recvs = [jax.random.normal(jax.random.fold_in(k, i + 1), shape, dtype)
             for i in range(degree)]
    w_self = 1.0 / (degree + 1)
    ws = tuple([w_self] * degree)
    got = gm_ops.gossip_mix(x, recvs, w_self=w_self, ws=ws, interpret=True)
    want = gm_ref.gossip_mix_ref(x, recvs, w_self, ws)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), **_tol(dtype))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 5000), degree=st.integers(1, 4))
def test_gossip_mix_property(n, degree):
    k = jax.random.key(n * 13 + degree)
    x = jax.random.normal(jax.random.fold_in(k, 0), (n,))
    recvs = [jax.random.normal(jax.random.fold_in(k, i + 1), (n,))
             for i in range(degree)]
    ws = tuple(float(w) for w in
               np.random.default_rng(n).dirichlet(np.ones(degree + 1))[1:])
    w_self = 1.0 - sum(ws)
    got = gm_ops.gossip_mix(x, recvs, w_self=w_self, ws=ws, interpret=True)
    want = gm_ref.gossip_mix_ref(x, recvs, w_self, ws)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gqa_flat_layout_matches_grouped():
    """The 'flat' GQA score layout (a §Perf sharding iteration) is exactly
    the same math as the grouped baseline."""
    from repro.models import attention as A
    B, S, H, Kv, D, d_model = 2, 64, 8, 2, 32, 96
    k = jax.random.key(11)
    params = A.attn_init(k, d_model, H, Kv, D)
    x = jax.random.normal(jax.random.fold_in(k, 1), (B, S, d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    y1 = A.attn_apply(params, x, n_heads=H, n_kv=Kv, head_dim=D,
                      positions=pos, gqa_layout="grouped")
    y2 = A.attn_apply(params, x, n_heads=H, n_kv=Kv, head_dim=D,
                      positions=pos, gqa_layout="flat")
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
