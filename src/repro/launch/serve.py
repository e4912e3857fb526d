"""Serving CLI: continuous-batching engine over a paged KV pool, driven
by a Poisson arrival trace.

``main`` builds a :class:`repro.serve.ServeEngine` and feeds it requests
as their (virtual) arrival times pass, printing per-request latency
percentiles, throughput, and page/compile-cache statistics.  ``--full``
serves the published widths; the default ``--reduced`` keeps the block
structure at tiny dims (the CPU demo).

The legacy :func:`generate` (one fixed batch, dense ring cache) is kept
as the serving baseline ``bench_serve`` compares against.  Its prefill
runs as ONE full-sequence :func:`repro.models.model.forward_prefill`
whose returned per-layer KV fills the ring cache directly (``prefill=
'loop'`` forces the old token-by-token path; non-uniform-attention
families always loop).  Executables are cached in a
:class:`repro.core.cache.CompileCache` keyed per config, so repeated
calls reuse one jit wrapper.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.cache import enable_persistent_cache
from repro.core.plan import CompileCache
from repro.models import model as M
from repro.models.attention import KVCache
from repro.serve import ServeEngine

_DECODE_CACHE = CompileCache()


def _decode_fn(cfg):
    """One jitted decode step per config (ModelConfig is hashable)."""
    return _DECODE_CACHE.get(
        ("decode", cfg),
        lambda: jax.jit(lambda p, t, c, i, img: M.decode_step(
            p, cfg, t, c, i, image_embeds=img)))


def _ring_fill(k_all, v_all, cache_len: int, dtype):
    """Fill a ring KVCache from full-sequence prefill KV.

    k_all, v_all: (L, B, S, Kv, hd).  Ring slot ``s`` must hold token
    ``t(s) = (S-1) - mod(S-1-s, cache_len)`` (the newest token whose
    position is congruent to s), so for S > cache_len only the last
    cache_len tokens survive -- exactly the state the token-by-token
    loop would have left.
    """
    S = k_all.shape[2]
    s = jnp.arange(cache_len, dtype=jnp.int32)
    t_s = (S - 1) - jnp.mod(S - 1 - s, cache_len)
    valid = (t_s >= 0)[None, None, None, :, None]
    tc = jnp.clip(t_s, 0)

    def take(a):
        a = a.transpose(0, 1, 3, 2, 4).astype(dtype)  # (L,B,Kv,S,hd)
        return jnp.where(valid, a[:, :, :, tc], 0)

    return KVCache(take(k_all), take(v_all))


def _prefill_fn(cfg, cache_len: int):
    def build():
        def fn(params, prompts):
            logits, (k, v) = M.forward_prefill(params, cfg, prompts)
            return logits, {"kv": _ring_fill(k, v, cache_len, jnp.float32)}

        return jax.jit(fn)

    return _DECODE_CACHE.get(("prefill", cfg, cache_len), build)


def sample_tokens(cfg, key, logits, temperature: float):
    """Sample one token per row.  logits: (B, V) -- audio: (B, K, V).
    Returns (B, 1) (audio: (B, 1, K)).

    Audio splits the step key per codebook: K INDEPENDENT sample streams.
    (Reusing one key across the K categorical draws correlates codebooks
    -- identical logits would always sample identical codes.)
    """
    lg = logits / max(temperature, 1e-4)
    if cfg.family == "audio":
        cb_keys = jax.random.split(key, cfg.n_codebooks)
        cur = jax.vmap(jax.random.categorical,
                       in_axes=(0, 1), out_axes=1)(cb_keys, lg)
        return cur[:, None, :]  # (B,1,K)
    return jax.random.categorical(key, lg)[:, None]  # (B,1)


def generate(cfg, params, prompts, *, max_new: int = 32, cache_len: int = 128,
             temperature: float = 1.0, seed: int = 0, image_embeds=None,
             prefill: str = "auto"):
    """prompts: (B, P) int32 (audio: (B, P, K)). Returns (B, P+max_new[, K]).

    prefill='auto': one full-sequence forward fills the ring cache
    (uniform-attention families); 'loop' forces the token-by-token path
    (always used for ssm/hybrid/vlm).
    """
    B = prompts.shape[0]
    plen = prompts.shape[1]
    decode = _decode_fn(cfg)
    toks = prompts

    fast = (prefill == "auto" and cfg.family in M.PAGED_FAMILIES
            and image_embeds is None)
    if fast:
        logits, cache = _prefill_fn(cfg, cache_len)(params, toks)
        logits = logits[:, -1:]
    else:
        cache = M.init_cache(cfg, batch=B, cache_len=cache_len,
                             dtype=jnp.float32)
        logits = None
        for t in range(plen):
            logits, cache = decode(params, toks[:, t:t + 1], cache,
                                   jnp.asarray(t, jnp.int32), image_embeds)

    key = jax.random.key(seed)
    out = [toks]
    for t in range(plen, plen + max_new):
        key, sub = jax.random.split(key)
        cur = sample_tokens(cfg, sub, logits[:, -1], temperature)
        out.append(cur)
        logits, cache = decode(params, cur, cache,
                               jnp.asarray(t, jnp.int32), image_embeds)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Poisson-trace serving driver
# ---------------------------------------------------------------------------

def poisson_trace(n: int, rate: float, mean_prompt: int, max_new: int,
                  vocab: int, seed: int, n_codebooks: int = 0):
    """[(arrival_s, prompt, max_new)] with exponential inter-arrivals."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    trace = []
    for a in arrivals:
        plen = max(1, int(rng.poisson(mean_prompt)))
        shape = (plen, n_codebooks) if n_codebooks else (plen,)
        prompt = rng.integers(0, vocab, shape, dtype=np.int64)
        trace.append((float(a), prompt, max_new))
    return trace


def serve_trace(engine: ServeEngine, trace, *, realtime: bool = False):
    """Feed a trace through the engine.  ``realtime=False`` runs a virtual
    clock that jumps to the next arrival whenever the engine goes idle --
    the standard replay mode for benchmarks and tests."""
    pending = sorted(trace, key=lambda r: r[0])
    t0 = time.perf_counter()
    now = 0.0
    i = 0
    while i < len(pending) or engine.sched.waiting or engine.sched.running:
        if realtime:
            now = time.perf_counter() - t0
        while i < len(pending) and pending[i][0] <= now:
            _, prompt, max_new = pending[i]
            engine.submit(prompt, max_new)
            i += 1
        worked = engine.step()
        if not realtime:
            now = time.perf_counter() - t0
        if not worked and not engine.sched.waiting and not engine.sched.running:
            if i < len(pending):
                now = max(now, pending[i][0])   # idle: jump to next arrival
            else:
                break
    return now


def latency_summary(finished):
    """Seconds from each request's submission to its first and its last
    token on the host (the engine's own ``perf_counter`` stamps)."""
    first = np.array([r.t_first_token - r.t_submit for r in finished])
    total = np.array([r.t_finish - r.t_submit for r in finished])

    def pct(a, q):
        return float(np.percentile(a, q)) if len(a) else float("nan")

    return {
        "first_token_p50_s": pct(first, 50), "first_token_p99_s": pct(first, 99),
        "total_p50_s": pct(total, 50), "total_p99_s": pct(total, 99),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--mean-prompt", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_persistent_cache()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced_config(cfg)
    params = M.init(cfg, jax.random.key(args.seed))
    engine = ServeEngine(cfg, params, n_pages=args.pages,
                         page_size=args.page_size, max_seq=args.max_seq,
                         max_batch=args.max_batch,
                         temperature=args.temperature, seed=args.seed)
    trace = poisson_trace(args.n_requests, args.rate, args.mean_prompt,
                          args.max_new, cfg.vocab_size, args.seed,
                          n_codebooks=cfg.n_codebooks)
    wall = serve_trace(engine, trace)
    st = engine.stats()
    lat = latency_summary(engine.finished)
    new_tokens = sum(len(r.generated) for r in engine.finished)
    print(f"arch={cfg.name} served {len(engine.finished)} requests, "
          f"{new_tokens} new tokens in {wall:.2f}s "
          f"({new_tokens / max(wall, 1e-9):.1f} tok/s)")
    print(f"latency: first-token p50={lat['first_token_p50_s']:.3f}s "
          f"p99={lat['first_token_p99_s']:.3f}s | total "
          f"p50={lat['total_p50_s']:.3f}s p99={lat['total_p99_s']:.3f}s")
    print(f"pages: peak={st['peak_pages']}/{args.pages} "
          f"(peak KV {st['peak_kv_bytes'] / 1e6:.2f} MB), "
          f"preemptions={st['preemptions']}")
    cc = st["compile_cache"]
    print(f"compile cache: {cc['entries']} executables, {cc['hits']} hits / "
          f"{cc['misses']} misses / {cc['evictions']} evictions")


if __name__ == "__main__":
    main()
