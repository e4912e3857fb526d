"""Ring-buffer KVCache property tests.

The documented slot invariant: after decoding token ``idx``, slot ``s``
holds token ``t(s) = idx - mod(idx - s, cache_len)``.  Consequence: a
wrapped ring of size ``cl`` attends to EXACTLY the last ``cl`` positions
-- i.e. it is equivalent to a full (never-wrapping) cache with a sliding
window of ``cl``.
"""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import attention as A

B, H, KV, HD = 2, 4, 2, 16


def _params(seed=0):
    return A.attn_init(jax.random.key(seed), d_model=32, n_heads=H,
                       n_kv=KV, head_dim=HD)


def _decode_seq(params, xs, cache_len, window=None):
    """Decode xs (B, N, d) token-by-token; return per-step outputs and the
    final cache."""
    N = xs.shape[1]
    cache = A.init_kv_cache(B, KV, cache_len, HD, jnp.float32)
    ys = []
    for t in range(N):
        y, cache = A.attn_decode(params, xs[:, t:t + 1], cache,
                                 jnp.asarray(t, jnp.int32), n_heads=H,
                                 n_kv=KV, head_dim=HD, window=window)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), cache


@settings(max_examples=12, deadline=None)
@given(cl=st.integers(2, 9),
       n=st.integers(1, 24))
def test_ring_slot_invariant(cl, n):
    """Slot s of a ring cache == slot t(s) of a full cache (same tokens)."""
    params = _params()
    xs = jax.random.normal(jax.random.key(1), (B, n, 32), jnp.float32)
    _, ring = _decode_seq(params, xs, cache_len=cl)
    _, full = _decode_seq(params, xs, cache_len=max(n, cl))
    idx = n - 1
    s = np.arange(cl)
    t = idx - np.mod(idx - s, cl)
    valid = t >= 0
    np.testing.assert_allclose(
        np.asarray(ring.k)[:, :, s[valid]],
        np.asarray(full.k)[:, :, t[valid]], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ring.v)[:, :, s[valid]],
        np.asarray(full.v)[:, :, t[valid]], rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(cl=st.integers(2, 8),
       n=st.integers(9, 20))
def test_wrapped_ring_equals_windowed_full_cache(cl, n):
    """A wrapped ring of size cl == a full cache with window=cl: the ring
    attends to exactly the last cl positions, nothing more, nothing less."""
    params = _params()
    xs = jax.random.normal(jax.random.key(2), (B, n, 32), jnp.float32)
    y_ring, _ = _decode_seq(params, xs, cache_len=cl)
    y_full, _ = _decode_seq(params, xs, cache_len=n, window=cl)
    np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_full),
                               rtol=1e-5, atol=1e-5)


def test_unwrapped_ring_equals_full_cache():
    """cache_len >= n: the ring never wraps and must match an oversized
    cache exactly (every slot s holds token s)."""
    params = _params()
    n = 7
    xs = jax.random.normal(jax.random.key(3), (B, n, 32), jnp.float32)
    y_a, cache = _decode_seq(params, xs, cache_len=n)
    y_b, _ = _decode_seq(params, xs, cache_len=3 * n)
    np.testing.assert_allclose(np.asarray(y_a), np.asarray(y_b),
                               rtol=1e-6, atol=1e-6)
    # slots 0..n-1 hold tokens 0..n-1 in order
    k = np.asarray(cache.k)
    assert k.shape[2] == n and np.isfinite(k).all()
