"""The benchmark's yardstick on the CPU: seeded weights in the program's
layout, FLOP and parameter counts against the program's own counts, and
the plain reference against the program's forward pass at float32."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtest import tiny
from bench.lib import bigram, flops, reference, spec, weights
from repro.models import model as M

DECODER = spec.architecture("decoder")
DENSE = dict(config="qwen3-0.6b")
MOE = dict(config="qwen3-0.6b", n_experts=4, top_k=2, qk_norm=False,
           tied=False)


def _cfg(dm, **kw):
    return DECODER.program_config(dm, "t", remat=False, **kw)


@pytest.mark.parametrize("kind", [DENSE, MOE], ids=["dense", "moe"])
def test_weights_have_the_program_layout(kind):
    dm = tiny(**kind)
    want = jax.eval_shape(lambda: M.init(_cfg(dm), jax.random.key(0)))
    got = jax.eval_shape(
        lambda k: weights.to_program(dm, weights.draw(dm, k)), weights.key(3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    flat = jax.eval_shape(lambda k: weights.draw(dm, k), weights.key(3))
    assert weights.from_program(dm, got).keys() == flat.keys()


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
    assert DECODER.matmul_params_per_token(dm) == active - norms - gathered
    seq = 64
    assert flops.train_flops_per_token(dm, seq) == pytest.approx(
        3 * (2 * DECODER.matmul_params_per_token(dm)
             + 4 * dm.n_layers * dm.n_heads * dm.head_dim * (seq + 1) / 2))


def test_real_qwen3_sizes():
    dm = spec.dims(spec.load("qwen3-0.6b"))
    assert flops.param_counts(dm) == (596049920, 596049920)
    assert spec.load("qwen3-0.6b")["sizing"]["params"] == 596049920


# The decoder's counts at the published widths, its seeded weights and its
# reference's loss, held fixed: a change to any of them changes what every
# cell reads (``train.mfu``, ``serve.mfu``, the weights of a seed, the
# compared numbers), and a cell's ledger line would no longer be comparable
# with the last.
COUNTS = {
    "qwen3-0.6b": {
        "matmul_params_per_token": 595984384,
        "param_counts": (596049920, 596049920),
        "train_flops_per_token(512)": 3752411136.0,
        "decode_flops(1000)": 1421574144.0,
        "prefill_flops(700)": 890655539200.0},
    "granite-moe-3b-a800m": {
        "matmul_params_per_token": 882774528,
        "param_counts": (3298793472, 882874368),
        "train_flops_per_token(512)": 5447937024.0,
        "decode_flops(1000)": 1962353664.0,
        "prefill_flops(700)": 1284122112000.0},
}
DRAW_SHA256 = {
    "qwen3-0.6b":
        "5aa316f09da0dc71ddbc2e29e8d4e26875fbed4e317fa3cd40d24be7c2680454",
    "granite-moe-3b-a800m":
        "e3d97c5bbf42d09e66f89978b56ac088181b9372e18a239be795b6028f44f344",
}
LOSS = {("qwen3-0.6b", "f32", None): 6.675437927246094,
        ("qwen3-0.6b", "fp8", None): 6.6689581871032715,
        ("qwen3-0.6b", "f32", "half"): 6.734808921813965,
        ("granite-moe-3b-a800m", "f32", None): 6.1260881423950195,
        ("granite-moe-3b-a800m", "fp8", None): 6.110877513885498,
        ("granite-moe-3b-a800m", "f32", "half"): 6.134368419647217}


@pytest.mark.parametrize("config", sorted(COUNTS))
def test_decoder_counts_at_published_widths(config):
    dm = spec.dims(spec.load(config))
    assert spec.arch_of(dm) is DECODER
    assert {"matmul_params_per_token": DECODER.matmul_params_per_token(dm),
            "param_counts": flops.param_counts(dm),
            "train_flops_per_token(512)": flops.train_flops_per_token(dm, 512),
            "decode_flops(1000)": flops.decode_flops(dm, 1000),
            "prefill_flops(700)": flops.prefill_flops(dm, 700),
            } == COUNTS[config]


@pytest.mark.parametrize("config", sorted(DRAW_SHA256))
def test_decoder_draws_the_same_weights(config):
    flat = weights.draw(tiny(config), weights.key(3))
    h = hashlib.sha256()
    for name in sorted(flat):
        a = np.asarray(flat[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DRAW_SHA256[config]


@pytest.mark.parametrize("config,mode,fault", sorted(LOSS, key=str))
def test_decoder_reference_loss_is_unchanged(config, mode, fault):
    """On one fixed batch.  The tolerance, about 13 float32 ulps, admits
    the last two ulps that the CPU's threaded contractions move with the
    number of threads."""
    dm = tiny(config)
    flat = weights.draw(dm, weights.key(3), dtype=jnp.float32)
    toks = jnp.asarray((np.arange(48).reshape(2, 24) * 37 + 5) % dm.vocab,
                       jnp.int32)
    got = float(DECODER.loss(flat, toks, dm, mode, fault))
    assert got == pytest.approx(LOSS[config, mode, fault], rel=1e-6)


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
            weights.to_program(dm, flat), toks)
    got = DECODER.head(flat, DECODER.hidden(flat, toks, dm), dm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_reference_layers_compose():
    """Layer blocks run one after another give the whole stack."""
    dm = tiny()
    flat = weights.draw(dm, weights.key(2))
    toks = jax.random.randint(jax.random.key(1), (1, 16), 0, dm.vocab)
    whole = DECODER.hidden(flat, toks, dm)
    x = DECODER.hidden(flat, toks, dm, hi=1)
    x = DECODER.hidden(flat, toks, dm, lo=1, x=x)
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
