"""The benchmark's yardstick on the CPU: seeded weights in the program's
layout, FLOP and parameter counts against the program's own counts, and
the plain reference against the program's forward pass at float32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtest import tiny
from bench.lib import bigram, flops, reference, spec, weights
from repro.models import model as M

DENSE = dict(config="qwen3-0.6b")
MOE = dict(config="qwen3-0.6b", n_experts=4, top_k=2, qk_norm=False,
           tied=False)


def _cfg(dm, **kw):
    return spec.program_config(dm, "t", remat=False, **kw)


@pytest.mark.parametrize("kind", [DENSE, MOE], ids=["dense", "moe"])
def test_weights_have_the_program_layout(kind):
    dm = tiny(**kind)
    want = jax.eval_shape(lambda: M.init(_cfg(dm), jax.random.key(0)))
    got = jax.eval_shape(lambda k: weights.to_program(weights.draw(dm, k)),
                         weights.key(3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    flat = jax.eval_shape(lambda k: weights.draw(dm, k), weights.key(3))
    assert weights.from_program(got).keys() == flat.keys()


@pytest.mark.parametrize("kind", [DENSE, MOE], ids=["dense", "moe"])
def test_param_counts_match_the_program(kind):
    dm = tiny(**kind)
    cfg = _cfg(dm)
    params = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    total, active = flops.param_counts(dm)
    assert total == M.param_count(params)
    assert active == M.active_param_count(params, cfg)


@pytest.mark.parametrize("kind", [DENSE, MOE], ids=["dense", "moe"])
def test_matmul_params_are_active_params_less_norms_and_gathers(kind):
    dm = tiny(**kind)
    _, active = flops.param_counts(dm)
    norms = dm.d_model * (1 + 2 * dm.n_layers) + (
        2 * dm.n_layers * dm.head_dim if dm.qk_norm else 0)
    gathered = 0 if dm.tied else dm.vocab * dm.d_model
    assert flops.matmul_params_per_token(dm) == active - norms - gathered
    seq = 64
    assert flops.train_flops_per_token(dm, seq) == pytest.approx(
        3 * (2 * flops.matmul_params_per_token(dm)
             + 4 * dm.n_layers * dm.n_heads * dm.head_dim * (seq + 1) / 2))


def test_real_qwen3_sizes():
    dm = spec.dims(spec.load("qwen3-0.6b"))
    assert flops.param_counts(dm) == (596049920, 596049920)
    assert spec.load("qwen3-0.6b")["sizing"]["params"] == 596049920


@pytest.mark.parametrize("kind", [DENSE, MOE], ids=["dense", "moe"])
def test_reference_matches_the_program_forward_in_f32(kind):
    """With the program's activations in float32 the two agree to
    rounding: the reference computes the same mathematics."""
    dm = dataclasses.replace(tiny(**kind), activation_dtype="float32")
    cfg = _cfg(dm)
    flat = jax.jit(lambda k: weights.draw(dm, k))(weights.key(5))
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, dm.vocab)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, t: M.forward(p, cfg, t))(
            weights.to_program(flat), toks)
    got = reference.head(flat, reference.hidden(flat, toks, dm), dm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_reference_layers_compose():
    """Layer blocks run one after another give the whole stack."""
    dm = tiny()
    flat = weights.draw(dm, weights.key(2))
    toks = jax.random.randint(jax.random.key(1), (1, 16), 0, dm.vocab)
    whole = reference.hidden(flat, toks, dm)
    x = reference.hidden(flat, toks, dm, hi=1)
    x = reference.hidden(flat, toks, dm, lo=1, x=x)
    np.testing.assert_allclose(np.asarray(x), np.asarray(whole), rtol=1e-6,
                               atol=1e-6)


def test_bigram_batches_are_seeded_and_distinct():
    kw = dict(vocab=97, n_nodes=2, n_batches=3, batch=2, seq=16, hetero=0.5)
    a = bigram.batches(jax.random.key(4), **kw)
    b = bigram.batches(jax.random.key(4), **kw)
    c = bigram.batches(jax.random.key(5), **kw)
    assert a.shape == (3, 2, 2, 16) and a.dtype == jnp.int32
    assert (np.asarray(a) == np.asarray(b)).all()
    assert not (np.asarray(a) == np.asarray(c)).all()
    rows = np.asarray(a).reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert rows.min() >= 0 and rows.max() < 97


def test_seed32_takes_large_seeds():
    s = {weights.seed32(x) for x in (0, 1, 2 ** 31 + 5, 2 ** 40, 2 ** 64 + 3)}
    assert len(s) == 5 and all(0 <= x < 2 ** 32 for x in s)


def test_peer_shift_follows_eq7():
    assert [reference.peer_shift(k, 4) for k in range(4)] == [1, 2, 1, 2]
    assert reference.peer_shift(0, 1) == 0
    assert [reference.peer_shift(k, 8) for k in range(3)] == [1, 2, 4]


def test_layer_blocks_draw_the_same_weights():
    dm = tiny(**MOE)
    whole = weights.draw(dm, weights.key(9))
    part = jax.jit(lambda k, lo: weights.draw(
        dm, k, lo=lo, count=1, names=("we_up", "wq")))(weights.key(9), 1)
    assert set(part) == {"we_up", "wq"}
    for k in part:
        np.testing.assert_array_equal(np.asarray(part[k]),
                                      np.asarray(whole[k][1:2]))


@pytest.mark.parametrize("law", [
    {"law": "lognormal", "median": 512, "sigma": 0.7, "cap": 2048},
    {"law": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "cap": 12},
], ids=["capped", "clipped"])
def test_lengths_are_the_same_set_for_every_seed(law):
    from bench.lib import laws

    x = laws.lengths(law, 40)
    a = laws.blocked(x, np.random.default_rng(1))
    b = laws.blocked(x, np.random.default_rng(2 ** 33))
    assert sorted(a) == sorted(b) == list(x)
    assert x.max() <= law["cap"] and x.min() >= law.get("min", 1)
    assert x.max() == law["cap"]


def test_blocks_hold_one_value_of_every_stratum():
    from bench.lib import laws

    n = 6 * laws.BLOCK
    out = laws.blocked(np.arange(n), np.random.default_rng(5))
    for b in range(0, n, laws.BLOCK):
        strata = sorted(v * laws.BLOCK // n for v in out[b:b + laws.BLOCK])
        assert strata == list(range(laws.BLOCK))


@pytest.mark.parametrize("law", [
    {"law": "poisson", "rate": 3.0},
    {"law": "onoff", "rate": 3.0, "on_s": 2.0, "off_s": 3.0},
], ids=["poisson", "onoff"])
def test_arrivals_keep_the_rate_inside_the_window(law):
    from bench.lib import laws

    t = laws.arrivals(law, 20.0, np.random.default_rng(9))
    assert len(t) == 60 and np.all(np.diff(t) > 0)
    assert 0 < t[0] and t[-1] < 20.0
    if law["law"] == "onoff":
        assert np.all(t % 5.0 < 2.0)
