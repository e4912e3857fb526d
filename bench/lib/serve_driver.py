"""The serving driver: an open loop of requests into the program's
continuous-batching engine.

The traffic file fixes the laws of arrivals and lengths (``laws``), the
backlog due when the window opens, and the engine's limits.  Requests are
due on that schedule whatever the engine does; every token is stamped on
the host clock after the ``engine.step`` that made it returns.  The
backlog is submitted in set-up, which steps the engine until the backlog
fills its batch, so a window above capacity opens in steady work and not
on an empty engine.  After the window the driver steps on until every
request that arrived in it has its first token (where the mix awaits
them) and enough requests have finished, then checks a seeded sample of
the finished requests, the longest among them, against the plain float32
reference.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import flops, laws, peaks, reference, spec, weights
from . import trace as tracing
from .harness import ROOT, peak_memory, settle_heap

REF_BLOCK = 4          # layers the reference holds at once, in float32


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """[(due s, prompt tokens, max_new)]: the mix's backlog, due when the
    window opens, then its arrivals in the window.  Every seed gets the
    same sizes and gaps in its own order (``laws``); the seed also draws
    the prompt tokens."""
    order = np.random.default_rng(weights.seed32(seed, salt=5))
    due = np.concatenate([np.zeros(mix.get("backlog", 0)),
                          laws.arrivals(mix["arrivals"], seconds, order)])
    n = len(due)
    plen = laws.blocked(laws.lengths(mix["prompt"], n), order)
    olen = laws.blocked(laws.lengths(mix["output"], n), order)
    rng = np.random.default_rng(weights.seed32(seed, salt=3))
    return [(float(d), rng.integers(0, vocab, size=int(p)).astype(np.int32),
             int(o)) for d, p, o in zip(due, plen, olen)]


def pages_at_most(mix: dict, sched) -> int:
    """Pool pages the schedule can hold at once, however the seed pairs
    prompts with answers: the pages of its ``max_batch`` longest prompts
    and of its ``max_batch`` longest answers, and the engine's trash
    page."""
    def top(xs):
        pages = sorted(-(-x // mix["page_size"]) for x in xs)
        return sum(pages[-mix["max_batch"]:])
    return 1 + top(len(p) for _, p, _ in sched) + top(o for *_, o in sched)


def buckets(mix: dict, min_prompt: int, max_prompt: int):
    """Every (rows, length) prefill bucket and decode bucket the engine can
    form under the cell's limits (its buckets are powers of two)."""
    from repro.serve.engine import _bucket

    budget, page = mix["prefill_token_budget"], mix["page_size"]
    max_rows = _bucket(mix["max_batch"])
    lengths = []
    L = _bucket(min_prompt, lo=page)
    while L <= _bucket(max_prompt, lo=page):
        lengths.append(L)
        L *= 2
    prefill = []
    for L in lengths:
        longest = max(L // 2 + 1, min_prompt)
        B = 1
        while B <= max_rows:
            # fewest requests that land in bucket B, the longest landing
            # in bucket L, all but the first inside the token budget
            fewest = 1 if B == 1 else B // 2 + 1
            if B == 1 or (fewest <= mix["max_batch"] and
                          longest + (fewest - 1) * min_prompt <= budget):
                prefill.append((B, L))
            B *= 2
    decode = []
    B = 1
    while B <= max_rows:
        decode.append(B)
        B *= 2
    return prefill, decode


def warm(eng, prefill, decode) -> None:
    """Run each bucketed executable once on the engine's trash page."""
    from repro.serve.pages import TRASH_PAGE

    for B, L in prefill:
        exe = eng._prefill_exe(B, L)
        toks = np.zeros((B, L), np.int32)
        pos = np.broadcast_to(np.arange(L, dtype=np.int32), (B, L))
        page = np.full((B, L), TRASH_PAGE, np.int32)
        slot = np.broadcast_to(np.arange(L, dtype=np.int32) % eng.page_size,
                               (B, L))
        logits, eng.pool = exe(eng.params, toks, pos, eng.pool, page, slot,
                               np.zeros((B,), np.int32))
        np.asarray(logits)
    for B in decode:
        exe = eng._decode_exe(B)
        logits, eng.pool = exe(
            eng.params, np.zeros((B, 1), np.int32), eng.pool,
            np.full((B, eng.pmax), TRASH_PAGE, np.int32),
            np.zeros((B,), np.int32))
        np.asarray(logits)


# ---------------------------------------------------------------------------
# the reference: served tokens judged by the float32 forward pass
# ---------------------------------------------------------------------------

class Judge:
    """Runs sequences through the reference in blocks of layers (the whole
    model in float32 does not fit beside anything) and reads, at each
    judged position, how far a token's logit lies below the best."""

    def __init__(self, dm, wkey, pad: int, mode: str = "f32"):
        self.dm, self.wkey, self.pad, self.mode = dm, wkey, pad, mode
        self.layer_names = tuple(n for n in reference.LAYER_KEYS
                                 if n in weights.shapes(dm))
        pdt = getattr(jnp, dm.param_dtype)

        def block(wkey, lo, x, pos):
            w = weights.draw(dm, wkey, pdt, lo=lo, count=REF_BLOCK,
                             names=self.layer_names)
            return reference.hidden(w, None, dm, mode, x=x, pos=pos)

        def embed(wkey, toks):
            w = weights.draw(dm, wkey, pdt, names=("embed",))
            return w["embed"][toks].astype(jnp.float32) * math.sqrt(
                dm.d_model)

        def logits(wkey, x):
            names = ("final_norm", "embed" if dm.tied else "lm_head")
            return reference.head(weights.draw(dm, wkey, pdt, names=names),
                                  x, dm, mode)[0]

        self._block = jax.jit(block)
        self._embed = jax.jit(embed)
        self._logits = jax.jit(logits)

    def logits(self, seqs):
        """Final logits (pad, V) of each sequence, each padded at its end."""
        if self.dm.n_layers % REF_BLOCK:
            raise ValueError(f"{self.dm.n_layers} layers in blocks of "
                             f"{REF_BLOCK}")
        toks = [np.pad(s, (0, self.pad - len(s)))[None] for s in seqs]
        pos = jnp.arange(self.pad)[None]
        xs = [self._embed(self.wkey, t) for t in toks]
        for lo in range(0, self.dm.n_layers, REF_BLOCK):
            xs = [self._block(self.wkey, lo, x, pos) for x in xs]
        for x in xs:
            yield self._logits(self.wkey, x)


def served_gaps(judge: Judge, sample) -> list:
    """For each (tokens, prompt length): the gap, under ``judge``'s logits,
    of each served token below the best token at its position."""
    out = []
    for (seq, plen), lg in zip(sample, judge.logits([s for s, _ in sample])):
        served = seq[plen:]
        at = lg[plen - 1: plen - 1 + len(served)]
        got = jnp.take_along_axis(at, jnp.asarray(served)[:, None], 1)[:, 0]
        out.append(np.asarray(jnp.max(at, -1) - got))
    return out


def first_choices(judge: Judge, sample) -> list:
    """The token ``judge`` puts first at each served position."""
    return [np.asarray(jnp.argmax(lg[plen - 1: len(seq) - 1], -1))
            for (seq, plen), lg in zip(sample,
                                       judge.logits([s for s, _ in sample]))]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(*, workload, config, traffic, limits, devs, seed, seconds, trace,
        t_start, dims=None, keep_sample=False):
    from repro.serve import ServeEngine

    mix = traffic
    cfg_spec = spec.load(config)
    dm = dims or spec.dims(cfg_spec)
    cfg = spec.program_config(dm, config, remat=False)
    if trace:
        seconds = min(seconds, mix["trace_seconds"])
    sched = schedule(mix, seed, seconds, dm.vocab)
    if pages_at_most(mix, sched) > mix["n_pages"]:
        raise ValueError(
            f"{mix['n_pages']} pages cannot hold the {mix['max_batch']} "
            f"largest requests ({pages_at_most(mix, sched)}): the engine "
            f"would preempt, and the work would depend on the seed")
    wkey = weights.key(seed)
    with jax.default_device(devs[0]):
        params = jax.jit(lambda k: weights.to_program(
            weights.draw(dm, k)))(wkey)
        eng = ServeEngine(cfg, params, n_pages=mix["n_pages"],
                          page_size=mix["page_size"], max_seq=mix["max_seq"],
                          max_batch=mix["max_batch"],
                          prefill_token_budget=mix["prefill_token_budget"],
                          temperature=0.0)
        plens = [len(p) for _, p, _ in sched]
        prefill_b, decode_b = buckets(mix, min(plens), max(plens))
        warm(eng, prefill_b, decode_b)
        reqs, due, stamps = [], [], []
        i = 0
        while i < len(sched) and sched[i][0] <= 0.0:
            reqs.append(eng.submit(sched[i][1], sched[i][2]))
            due.append(0.0)
            stamps.append([])
            i += 1
        backlog = i
        while (eng.sched.waiting and len(eng.sched.running)
               < mix["max_batch"]) or any(not r.generated
                                          for r in eng.sched.running):
            eng.step()
        jax.block_until_ready(eng.pool)
        settle_heap()
        compiled = eng.compile_cache.stats()["misses"]
        setup_s = time.perf_counter() - t_start

        trace_dir = os.path.join(ROOT, ".bench_trace")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        live = [j for j, r in enumerate(reqs) if not r.done]
        step_s, step_flops, decode_steps, decode_rows = 0.0, 0.0, 0, 0
        late = 0.0
        t0 = time.perf_counter()
        with tracing.span("bench.window", trace):
            while True:
                now = time.perf_counter() - t0
                with tracing.span("bench.submit", trace):
                    while i < len(sched) and sched[i][0] <= now:
                        d, prompt, max_new = sched[i]
                        late = max(late, now - d)
                        reqs.append(eng.submit(prompt, max_new))
                        due.append(d)
                        stamps.append([])
                        live.append(len(reqs) - 1)
                        i += 1
                if now >= seconds:
                    break
                if not live:
                    nxt = sched[i][0] if i < len(sched) else seconds
                    with tracing.span("bench.idle_until_due", trace):
                        time.sleep(max(0.0, min(nxt, seconds) - now))
                    continue
                before = [(j, len(reqs[j].generated)) for j in live]
                rows = eng.decoded_tokens
                ts = time.perf_counter()
                with tracing.span("bench.engine_step", trace):
                    eng.step()
                t = time.perf_counter() - t0
                step_s += t - (ts - t0)
                if eng.decoded_tokens > rows:
                    decode_steps += 1
                    decode_rows += eng.decoded_tokens - rows
                for j, n in before:
                    r = reqs[j]
                    new = len(r.generated) - n
                    if new <= 0:
                        continue
                    if n == 0:
                        step_flops += flops.prefill_flops(dm, r.prompt_len)
                    else:
                        step_flops += new * flops.decode_flops(
                            dm, r.cache_len())
                    stamps[j].extend([t] * new)
                live = [j for j in live if not reqs[j].done]
        t_close = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        # a late first token is late, not missing: where the mix awaits
        # them, step on until each request that arrived in the window has
        # its first token; and until enough requests have finished for the
        # check
        arrived = range(backlog, len(reqs))
        await_first = mix.get("await_first_tokens", True)
        deadline = time.perf_counter() + mix["drain_seconds"]
        while live and ((await_first and any(not stamps[j] for j in arrived))
                        or sum(r.done for r in reqs) < mix["check_requests"]
                        ) and time.perf_counter() < deadline:
            before = [(j, len(reqs[j].generated)) for j in live]
            eng.step()
            t = time.perf_counter() - t0
            for j, n in before:
                stamps[j].extend([t] * (len(reqs[j].generated) - n))
            live = [j for j in live if not reqs[j].done]
        jax.block_until_ready(eng.pool)
        summary = None
        if trace:
            summary = tracing.reduce(tracing.extract(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        stats = eng.stats()
        in_window = compiled != eng.compile_cache.stats()["misses"]
        memory_peak = peak_memory(devs)

        # -- metrics -------------------------------------------------------
        missing = sum(1 for j in arrived if not stamps[j]) if await_first \
            else 0
        ttft = [(stamps[j][0] - due[j]) if stamps[j] else math.inf
                for j in arrived]
        itl = [b - a for s in stamps for a, b in zip(s, s[1:])
               if b <= t_close]
        out_tokens = sum(1 for s in stamps for x in s if x <= t_close)
        queued = sum(1 for r in reqs if not r.generated)

        def p95_ms(xs):
            return 1e3 * float(np.percentile(xs, 95)) if xs else math.inf

        print(f"serve: {backlog} requests of backlog, {len(reqs) - backlog} "
              f"arrived in the window, {queued} without a token at the end, "
              f"{out_tokens} tokens in the window, generator late by at "
              f"most {late * 1e3:.3f} ms, {int(in_window)} compiles in the "
              f"window, TTFT p95 {p95_ms(ttft):.1f} ms over {len(ttft)} "
              f"arrivals, ITL p95 {p95_ms(itl):.1f} ms over {len(itl)} "
              f"gaps, pool {mix['n_pages']} pages, at most "
              f"{stats['peak_kv_bytes'] / 2 ** 20:.0f} MiB of it used, "
              f"engine {stats}", file=sys.stderr)

        # -- the check: a seeded sample of finished requests ---------------
        finished = [r for r in reqs if r.done]
        rng = np.random.default_rng(weights.seed32(seed, salt=4))
        pick = []
        if finished:
            longest = max(finished, key=lambda r: len(r.prompt)
                          + len(r.generated))
            others = [r for r in finished if r is not longest]
            k = min(len(others), mix["check_requests"] - 1)
            pick = [longest] + [others[j] for j in
                                rng.choice(len(others), k, replace=False)]
        sample = [(np.concatenate([r.prompt, np.asarray(r.generated,
                                                        np.int32)]),
                   r.prompt_len) for r in pick]
        del eng, params, reqs, finished, pick
        gc.unfreeze()
        gc.collect()
        judge = Judge(dm, wkey, pad=mix["max_seq"])
        gaps = served_gaps(judge, sample)
    served = sum(len(g) for g in gaps)
    widest = float(max((g.max() for g in gaps), default=math.inf))
    checks = {"served_gap": {"value": widest,
                             "limit": limits["served_gap"]}}
    print(f"serve check: {len(sample)} requests, {served} served tokens "
          f"compared", file=sys.stderr)

    out = {"ok": missing == 0 and served > 0 and not in_window,
           "attempted": len(due), "failed": missing,
           "checks": checks, "memory_peak": memory_peak,
           "end_to_end": {
               "serve_out_tok_s": out_tokens / t_close,
               "serve_ttft_p95_ms": p95_ms(ttft),
               "serve_itl_p95_ms": p95_ms(itl),
               "setup_s": setup_s}}
    if keep_sample:
        out["sample"] = sample
    if trace:
        out["trace"] = summary
        out["reader_ctx"] = {
            "kind": "serve", "summary": summary, "steps": stats["steps"],
            "window_s": t_close, "chips": len(devs),
            "step_s": step_s, "step_flops": step_flops,
            "decode_rows": decode_rows, "decode_steps": decode_steps,
            "itl_p95_ms": p95_ms(itl) if itl else None,
            "peaks": peaks.peaks(devs[0].device_kind)
            if devs[0].platform == "tpu" else None}
    return out
