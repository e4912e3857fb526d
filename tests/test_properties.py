"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import gossip, optim, spectral, topology

TOPS = ["ring", "star", "grid", "torus", "static_exp", "full"]


@settings(max_examples=40, deadline=None)
@given(
    name=st.sampled_from(TOPS),
    n=st.integers(3, 33),
    seed=st.integers(0, 10),
)
def test_doubly_stochastic_all_sizes(name, n, seed):
    W = topology.get_topology(name, n).weights(0)
    assert np.allclose(W.sum(0), 1.0, atol=1e-10)
    assert np.allclose(W.sum(1), 1.0, atol=1e-10)
    assert (W >= -1e-12).all()


@settings(max_examples=30, deadline=None)
@given(
    name=st.sampled_from(TOPS + ["one_peer_exp"]),
    n=st.sampled_from([4, 8, 16]),
    step=st.integers(0, 7),
    seed=st.integers(0, 5),
)
def test_gossip_preserves_mean(name, n, step, seed):
    """Double stochasticity => node-mean invariance for ANY pytree."""
    k = jax.random.key(seed)
    tree = {"a": jax.random.normal(jax.random.fold_in(k, 0), (n, 3, 7)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (n, 11))}
    out = gossip.mix(tree, topology.get_topology(name, n), step)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_allclose(a.mean(0), b.mean(0), rtol=1e-4,
                                   atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    name=st.sampled_from(["ring", "grid", "torus", "static_exp", "star"]),
    n=st.sampled_from([8, 16, 24]),
    seed=st.integers(0, 8),
)
def test_mixing_contraction(name, n, seed):
    """||W x - x_bar|| <= rho ||x - x_bar|| for symmetric/normal W; for the
    (non-symmetric) static exp graph Prop. 1 gives ||W - J||_2 = rho, so the
    same contraction bound holds."""
    top = topology.get_topology(name, n)
    W = top.weights(0)
    rho = spectral.residual_norm(W)  # ||W - J||_2 is the exact operator norm
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 5))
    xb = x.mean(0, keepdims=True)
    lhs = np.linalg.norm(W @ x - xb)
    assert lhs <= rho * np.linalg.norm(x - xb) + 1e-9


@settings(max_examples=15, deadline=None)
@given(
    n_pow=st.integers(1, 5),
    k0=st.integers(0, 9),
)
def test_one_peer_exactness_any_offset(n_pow, k0):
    """Lemma 1 for all power-of-two sizes and arbitrary start offsets."""
    n = 2 ** n_pow
    top = topology.one_peer_exponential(n)
    P = np.eye(n)
    for k in range(k0, k0 + n_pow):
        P = top.weights(k) @ P
    np.testing.assert_allclose(P, np.ones((n, n)) / n, atol=1e-12)


@settings(max_examples=10, deadline=None)
@given(
    beta=st.floats(0.0, 0.95),
    lr=st.floats(1e-3, 0.2),
    seed=st.integers(0, 5),
)
def test_dmsgd_average_recursion_invariant(beta, lr, seed):
    """Eqs. (50)-(51): the node-average trajectory of DmSGD follows the
    centralized momentum recursion EXACTLY, for any topology/beta/lr."""
    n, d = 8, 6
    top = topology.one_peer_exponential(n)
    opt = optim.dmsgd(top, beta=beta)
    rng = np.random.default_rng(seed)
    params = {"x": jnp.asarray(rng.standard_normal((n, d)), jnp.float32)}
    state = opt.init(params)
    xbar = np.asarray(params["x"]).mean(0)
    mbar = np.zeros(d)
    for k in range(6):
        g = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        params, state = opt.update(params, state, {"x": g}, k, lr)
        gbar = np.asarray(g).mean(0)
        xbar = xbar - lr * mbar
        mbar = beta * mbar + gbar
        np.testing.assert_allclose(np.asarray(params["x"]).mean(0), xbar,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(state.momentum["x"]).mean(0),
                                   mbar, rtol=2e-4, atol=2e-5)
