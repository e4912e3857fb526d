"""Serving plane: allocator/scheduler units, engine end-to-end parity vs
the dense-cache decode path, preemption, and legacy-generate satellites
(fast prefill parity, audio per-codebook sampling)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import serve as serve_mod
from repro.models import model as M
from repro.serve import (PageAllocator, Request, Scheduler, ServeEngine,
                         pages_needed)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = configs.reduced_config(configs.get_config("qwen3-0.6b"))
    return cfg, M.init(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def audio_setup():
    cfg = configs.reduced_config(configs.get_config("musicgen-large"))
    return cfg, M.init(cfg, jax.random.key(0))


def _prompts(cfg, rng, lens):
    if cfg.family == "audio":
        return [rng.integers(0, cfg.vocab_size, (p, cfg.n_codebooks))
                for p in lens]
    return [rng.integers(0, cfg.vocab_size, (p,)) for p in lens]


def _greedy_dense(cfg, params, prompt, max_new, cache_len=64):
    """Dense ring-cache greedy reference, one request at a time."""
    dec = serve_mod._decode_fn(cfg)
    cache = M.init_cache(cfg, batch=1, cache_len=cache_len, dtype=jnp.float32)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    plen = toks.shape[1]
    logits = None
    for t in range(plen):
        logits, cache = dec(params, toks[:, t:t + 1], cache,
                            jnp.asarray(t, jnp.int32), None)
    out = []
    for t in range(plen, plen + max_new):
        cur = jnp.argmax(logits[:, -1], -1)
        out.append(np.asarray(cur[0]))
        logits, cache = dec(params, cur[:, None], cache,
                            jnp.asarray(t, jnp.int32), None)
    return out


# ---------------------------------------------------------------------------
# allocator / scheduler units
# ---------------------------------------------------------------------------

def test_allocator_all_or_nothing():
    a = PageAllocator(6)            # 5 usable (page 0 reserved)
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and 0 not in got
    assert a.alloc(3) is None       # only 2 left: no partial grant
    assert a.free_pages == 2
    a.free(got)
    assert a.free_pages == 5 and a.peak_used == 3


def test_allocator_rejects_bad_free():
    a = PageAllocator(4)
    with pytest.raises(ValueError):
        a.free([0])                 # reserved trash page
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(RuntimeError):
        a.free(got)                 # double free overflows the pool


def test_pages_needed():
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2


def test_scheduler_admission_budget():
    a = PageAllocator(64)
    s = Scheduler(a, page_size=4, max_batch=8, prefill_token_budget=10)
    for rid, p in enumerate((8, 8, 3)):
        s.submit(Request(rid=rid, prompt=np.zeros(p, np.int32), max_new=4))
    plan = s.plan()
    # first always admitted; second would blow the 10-token budget; third
    # arrives after second, FIFO admission never skips ahead
    assert [r.rid for r in plan.prefill] == [0]
    assert s.plan().prefill[0].rid == 1


def test_scheduler_lifo_preemption_and_resume():
    a = PageAllocator(7)            # 6 usable pages
    s = Scheduler(a, page_size=2, max_batch=4, prefill_token_budget=64)
    r0 = Request(rid=0, prompt=np.zeros(4, np.int32), max_new=8)
    r1 = Request(rid=1, prompt=np.zeros(4, np.int32), max_new=8)
    s.submit(r0)
    s.submit(r1)
    plan = s.plan()                 # both admitted: 2+2 pages
    assert len(plan.prefill) == 2
    r0.generated.append(1)
    r1.generated.append(1)
    # burn the rest of the pool so the next boundary alloc must preempt
    held = a.alloc(a.free_pages)
    for _ in range(2):              # decode to both requests' page boundary
        plan = s.plan()
        for r in plan.decode:
            r.generated.append(1)
    assert r1.state == "waiting" and r1.pages == []   # LIFO victim
    assert r0.state == "running"                      # oldest kept
    assert s.waiting[0] is r1       # resumes ahead of fresh arrivals
    a.free(held)
    plan = s.plan()
    assert plan.prefill == [r1]     # re-admitted with its history
    assert r1.prefill_tokens().shape[0] == 4 + len(r1.generated) - 1


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def _assert_engine_greedy_matches_dense(cfg, params):
    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, params, n_pages=64, page_size=4, max_seq=64,
                      max_batch=4, prefill_token_budget=32,
                      temperature=0.0, pool_dtype=jnp.float32)
    prompts = _prompts(cfg, rng, (5, 9, 3, 12))
    reqs = [eng.submit(p, max_new=5) for p in prompts]
    eng.run()
    assert len(eng.finished) == 4
    for r in reqs:
        want = [int(x) for x in _greedy_dense(cfg, params, r.prompt, 5)]
        assert [int(g) for g in r.generated] == want, r.rid


def test_engine_greedy_matches_dense(dense_setup):
    _assert_engine_greedy_matches_dense(*dense_setup)


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "dbrx-132b"])
def test_engine_greedy_matches_dense_moe(arch):
    """The engine's prefill and paged decode read each expert in place out
    of the stacked weights; the ring-cache decode reads them the same way
    through another layer scan: both give the same greedy tokens.  In
    float32: with bf16 activations the batched prefill and the one-token
    steps round apart, and a random router's near-ties then pick other
    experts."""
    cfg = dataclasses.replace(
        configs.reduced_config(configs.get_config(arch), n_layers=3),
        activation_dtype=jnp.float32)
    _assert_engine_greedy_matches_dense(cfg, M.init(cfg, jax.random.key(0)))


def test_engine_preemption_parity(dense_setup):
    """A pool too small for the working set must preempt -- and still
    produce exactly the unpreempted greedy continuations."""
    cfg, params = dense_setup
    rng = np.random.default_rng(1)
    small = ServeEngine(cfg, params, n_pages=9, page_size=4, max_seq=32,
                        max_batch=4, prefill_token_budget=64,
                        temperature=0.0, pool_dtype=jnp.float32)
    prompts = _prompts(cfg, rng, (6, 7, 5))
    reqs = [small.submit(p, max_new=8) for p in prompts]
    small.run(max_steps=300)
    assert small.stats()["preemptions"] > 0
    big = ServeEngine(cfg, params, n_pages=64, page_size=4, max_seq=32,
                      max_batch=4, prefill_token_budget=64,
                      temperature=0.0, pool_dtype=jnp.float32)
    reqs2 = [big.submit(p, max_new=8) for p in prompts]
    big.run()
    for a, b in zip(reqs, reqs2):
        assert [int(x) for x in a.generated] == [int(x) for x in b.generated]


def test_engine_page_accounting(dense_setup):
    cfg, params = dense_setup
    eng = ServeEngine(cfg, params, n_pages=32, page_size=4, max_seq=32,
                      temperature=0.0, pool_dtype=jnp.float32)
    eng.submit(np.arange(6) % cfg.vocab_size, max_new=4)
    eng.run()
    st = eng.stats()
    # 6 prompt + 4 new - 1 (last token never cached) = 9 tokens -> 3 pages
    assert st["peak_pages"] == pages_needed(9, 4)
    assert st["used_pages"] == 0 and st["free_pages"] == 31
    assert st["peak_kv_bytes"] > 0


def test_engine_compile_cache_bounded(dense_setup):
    """Bucketed shapes: many ragged requests, a handful of executables --
    and a second identical run is all hits."""
    cfg, params = dense_setup
    rng = np.random.default_rng(2)
    eng = ServeEngine(cfg, params, n_pages=128, page_size=4, max_seq=64,
                      max_batch=8, prefill_token_budget=64,
                      temperature=0.0, pool_dtype=jnp.float32)
    for p in _prompts(cfg, rng, (3, 5, 7, 9, 11, 4, 6, 8)):
        eng.submit(p, max_new=3)
    eng.run()
    cc = eng.compile_cache.stats()
    assert cc["entries"] <= 8
    misses0 = cc["misses"]
    for p in _prompts(cfg, rng, (3, 5, 7, 9, 11, 4, 6, 8)):
        eng.submit(p, max_new=3)
    eng.run()
    assert eng.compile_cache.stats()["misses"] == misses0


def test_engine_rejects_oversized_request(dense_setup):
    cfg, params = dense_setup
    eng = ServeEngine(cfg, params, n_pages=16, page_size=4, max_seq=16)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(14, np.int32), max_new=8)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(4, np.int32), max_new=0)


def test_engine_audio_family(audio_setup):
    """Audio (multi-codebook) requests serve end-to-end; greedy matches
    the dense decode loop per codebook."""
    cfg, params = audio_setup
    rng = np.random.default_rng(3)
    eng = ServeEngine(cfg, params, n_pages=64, page_size=4, max_seq=32,
                      temperature=0.0, pool_dtype=jnp.float32)
    reqs = [eng.submit(p, max_new=3) for p in _prompts(cfg, rng, (4, 6))]
    eng.run()
    for r in reqs:
        want = _greedy_dense(cfg, params, r.prompt, 3, cache_len=32)
        got = np.stack(r.generated)
        np.testing.assert_array_equal(got, np.stack(want))


def test_engine_sampled_stream_batch_invariant(dense_setup):
    """temperature>0: a request's sample stream depends only on (seed,
    rid, step) -- co-batching/batch size must not change its tokens."""
    cfg, params = dense_setup
    rng = np.random.default_rng(4)
    prompts = _prompts(cfg, rng, (5, 8))
    solo = ServeEngine(cfg, params, n_pages=64, page_size=4, max_seq=32,
                       temperature=0.8, seed=7, pool_dtype=jnp.float32)
    r_solo = solo.submit(prompts[0], max_new=4)
    solo.run()
    both = ServeEngine(cfg, params, n_pages=64, page_size=4, max_seq=32,
                       temperature=0.8, seed=7, pool_dtype=jnp.float32)
    r_both = both.submit(prompts[0], max_new=4)
    both.submit(prompts[1], max_new=4)
    both.run()
    assert [int(x) for x in r_solo.generated] == \
           [int(x) for x in r_both.generated]


# ---------------------------------------------------------------------------
# legacy generate() satellites
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plen,cache_len", [(10, 64), (20, 16)])
def test_generate_fast_prefill_parity(dense_setup, plen, cache_len):
    """One-shot forward_prefill == token-by-token loop prefill, including
    a prompt longer than the ring (wrap case)."""
    cfg, params = dense_setup
    prompts = jax.random.randint(jax.random.key(1), (2, plen), 0,
                                 cfg.vocab_size)
    a = serve_mod.generate(cfg, params, prompts, max_new=5,
                           cache_len=cache_len, temperature=0.7, seed=3,
                           prefill="auto")
    b = serve_mod.generate(cfg, params, prompts, max_new=5,
                           cache_len=cache_len, temperature=0.7, seed=3,
                           prefill="loop")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generate_fast_prefill_parity_audio(audio_setup):
    cfg, params = audio_setup
    prompts = jax.random.randint(jax.random.key(2),
                                 (2, 8, cfg.n_codebooks), 0, cfg.vocab_size)
    a = serve_mod.generate(cfg, params, prompts, max_new=4, temperature=0.7,
                           seed=3, prefill="auto")
    b = serve_mod.generate(cfg, params, prompts, max_new=4, temperature=0.7,
                           seed=3, prefill="loop")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_audio_codebooks_sample_independently(audio_setup):
    """Regression: one PRNG key reused across the K codebook categoricals
    made identical logits sample IDENTICAL codes in every codebook.  With
    per-codebook key splits the draws are independent."""
    cfg, _ = audio_setup
    K = cfg.n_codebooks
    assert K >= 2
    # same (uniform-ish) logits in every codebook: correlated sampling
    # would emit one repeated code across the K streams
    logits = jnp.broadcast_to(
        jax.random.normal(jax.random.key(0), (1, 1, 64)), (4, K, 64))
    toks = serve_mod.sample_tokens(cfg, jax.random.key(1), logits,
                                   temperature=1.0)   # (B, 1, K)
    toks = np.asarray(toks)[:, 0]
    assert any(len(set(row.tolist())) > 1 for row in toks), \
        "codebook draws are perfectly correlated"
