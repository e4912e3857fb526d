"""Continuous-batching serve engine: scheduler plans -> bucketed
executables over a paged KV pool.

Each :meth:`ServeEngine.step` runs at most one batched prefill (all
admissions this step padded into one ``(Bb, Lb)`` call of
:func:`repro.models.model.forward_prefill`, whose returned per-layer KV is
scattered straight into the page pool) and one batched decode
(:func:`repro.models.model.decode_step_paged` over every running request,
each at its OWN absolute position).  Batch and sequence dims are bucketed
to powers of two so the whole serving run compiles a handful of
executables, cached in a :class:`repro.core.cache.CompileCache` keyed on
the bucketed shapes -- the same keyed-compile engine GossipPlan uses.

Padded rows of a bucket point their page tables at the TRASH page and
their logits are dropped, so they never touch a live request's state.

Sampling is per-request: the PRNG key is ``fold_in(fold_in(base, rid),
n_generated)`` so a request's sample stream is reproducible regardless of
how it was co-batched, preempted, or resumed.  Audio configs split that
step key once more per codebook -- K independent streams, not one key
reused K times.  ``temperature=0`` is greedy argmax (exactly reproducible
against a dense-cache decode of the same request).

Measurement, all free unless a profiler session is open:

* Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock
  with the device ops): ``repro.serve.step`` wraps a step;
  ``repro.serve.plan`` the scheduler; for each of ``decode`` and
  ``prefill``, ``repro.serve.<phase>.inputs`` (tokens, page tables,
  positions), ``.dispatch`` (the executable call) and ``.fetch`` (the
  logits to the host: where the host waits on the device); and
  ``repro.serve.sample``.
* Executables are named ``serve_prefill`` and ``serve_decode``; inside
  them the model's device scopes (``layers``, the layer loop with its
  slicing of the stacked weights and pool; ``attention``, ``moe``,
  ``lm_head``) and ``kv_write`` (the pool scatter) name the ops.
* Counters (:meth:`ServeEngine.stats`): ``decoded_tokens``,
  ``prefill_tokens`` (real tokens prefilled) and ``prefill_slots`` (rows
  times length of the bucketed prefill calls).
* Each request's ``t_submit``, ``t_admit``, ``t_first_token`` and
  ``t_finish`` on ``time.perf_counter``; a token is stamped once it is on
  the host.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.cache import CompileCache
from repro.models import model as M

from .pages import TRASH_PAGE, PageAllocator, init_page_pool, page_bytes, \
    pages_needed
from .scheduler import Request, Scheduler

__all__ = ["ServeEngine"]


def _bucket(n: int, lo: int = 1) -> int:
    """Next power of two >= n (floored at lo) -- the executable shape."""
    b = lo
    while b < n:
        b *= 2
    return b


class ServeEngine:
    """Step-loop serving over a paged KV pool (continuous batching)."""

    def __init__(self, cfg: M.ModelConfig, params, *, n_pages: int,
                 page_size: int = 16, max_seq: int = 256,
                 max_batch: int = 8, prefill_token_budget: int = 256,
                 temperature: float = 0.0, seed: int = 0,
                 pool_dtype=jnp.bfloat16, max_cached_executables: int = 32,
                 compile_cache: CompileCache | None = None):
        if cfg.family not in M.PAGED_FAMILIES:
            raise NotImplementedError(
                f"serving supports {M.PAGED_FAMILIES}, not {cfg.family}")
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_seq = max_seq
        self.pmax = pages_needed(max_seq, page_size)
        self.pool = init_page_pool(cfg, n_pages=n_pages, page_size=page_size,
                                   dtype=pool_dtype)
        self.pool_dtype = pool_dtype
        self.alloc = PageAllocator(n_pages)
        self.sched = Scheduler(self.alloc, page_size=page_size,
                               max_batch=max_batch,
                               prefill_token_budget=prefill_token_budget)
        self.temperature = temperature
        self._base_key = jax.random.key(seed)
        # pass a shared cache to reuse executables across engines (the
        # benchmark warms one engine, then times a fresh one steady-state)
        self.compile_cache = compile_cache if compile_cache is not None \
            else CompileCache(max_entries=max_cached_executables)
        self.finished: list[Request] = []
        self._next_rid = 0
        self.n_steps = 0
        self.decoded_tokens = 0
        self.prefill_tokens = 0
        self.prefill_slots = 0

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, max_new: int) -> Request:
        prompt = np.asarray(prompt, np.int32)
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if prompt.shape[0] + max_new > self.max_seq:
            raise ValueError(
                f"request needs {prompt.shape[0] + max_new} tokens > "
                f"max_seq={self.max_seq}")
        req = Request(rid=self._next_rid, prompt=prompt, max_new=max_new,
                      t_submit=time.perf_counter())
        self._next_rid += 1
        self.sched.submit(req)
        return req

    # -- bucketed executables ---------------------------------------------

    def _prefill_exe(self, Bb: int, Lb: int):
        cfg = self.cfg

        def build():
            def serve_prefill(params, tokens, positions, pool, page_idx,
                              slot_idx, last_idx):
                logits, (k, v) = M.forward_prefill(params, cfg, tokens,
                                                   positions=positions)
                # (L, B, S, Kv, hd) -> (L, Kv, B, S, hd) to match the pool's
                # advanced-index result layout at dims (pages, slots)
                with jax.named_scope("kv_write"):
                    k = k.transpose(0, 3, 1, 2, 4)
                    v = v.transpose(0, 3, 1, 2, 4)
                    kp = pool["k"].at[:, :, page_idx, slot_idx].set(
                        k.astype(pool["k"].dtype))
                    vp = pool["v"].at[:, :, page_idx, slot_idx].set(
                        v.astype(pool["v"].dtype))
                idx = last_idx.reshape((-1,) + (1,) * (logits.ndim - 1))
                last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
                return last, {"k": kp, "v": vp}

            return jax.jit(serve_prefill)

        return self.compile_cache.get(("prefill", Bb, Lb), build)

    def _decode_exe(self, Bb: int):
        cfg, page_size = self.cfg, self.page_size

        def build():
            def serve_decode(params, token, pool, page_table, positions):
                return M.decode_step_paged(params, cfg, token, pool,
                                           page_table, positions,
                                           page_size=page_size)

            return jax.jit(serve_decode)

        return self.compile_cache.get(("decode", Bb), build)

    # -- sampling ----------------------------------------------------------

    def _sample(self, logits_row, req: Request):
        """logits_row: (V,) -- audio: (K, V).  Greedy at temperature 0;
        otherwise a per-(request, step) key, split per codebook for audio."""
        if self.temperature == 0.0:
            tok = np.argmax(np.asarray(logits_row, np.float32), axis=-1)
        else:
            key = jax.random.fold_in(
                jax.random.fold_in(self._base_key, req.rid),
                len(req.generated))
            lg = logits_row / self.temperature
            if self.cfg.family == "audio":
                keys = jax.random.split(key, self.cfg.n_codebooks)
                tok = jax.vmap(jax.random.categorical)(keys, lg)
            else:
                tok = jax.random.categorical(key, lg)
            tok = np.asarray(tok)
        if self.cfg.family == "audio":
            return tok.astype(np.int32)          # (K,)
        return int(tok)

    # -- step loop ---------------------------------------------------------

    def _token_shape(self, *lead):
        if self.cfg.family == "audio":
            return lead + (self.cfg.n_codebooks,)
        return lead

    def _run_prefill(self, reqs: list[Request]) -> None:
        with TraceAnnotation("repro.serve.prefill.inputs"):
            toks = [r.prefill_tokens() for r in reqs]
            Bb = _bucket(len(reqs))
            Lb = _bucket(max(t.shape[0] for t in toks), lo=self.page_size)
            tokens = np.zeros(self._token_shape(Bb, Lb), np.int32)
            page_idx = np.full((Bb, Lb), TRASH_PAGE, np.int32)
            slot_idx = np.broadcast_to(
                np.arange(Lb, dtype=np.int32) % self.page_size,
                (Bb, Lb)).copy()
            last_idx = np.zeros((Bb,), np.int32)
            for i, (r, t) in enumerate(zip(reqs, toks)):
                n = t.shape[0]
                tokens[i, :n] = t
                pages = np.asarray(r.pages, np.int32)
                page_idx[i, :n] = pages[np.arange(n) // self.page_size]
                last_idx[i] = n - 1
            positions = np.broadcast_to(np.arange(Lb, dtype=np.int32),
                                        (Bb, Lb))
            exe = self._prefill_exe(Bb, Lb)
        self.prefill_tokens += sum(t.shape[0] for t in toks)
        self.prefill_slots += Bb * Lb
        with TraceAnnotation("repro.serve.prefill.dispatch"):
            last_logits, self.pool = exe(self.params, tokens, positions,
                                         self.pool, page_idx, slot_idx,
                                         last_idx)
        with TraceAnnotation("repro.serve.prefill.fetch"):
            last_logits = np.asarray(last_logits, np.float32)
        with TraceAnnotation("repro.serve.sample"):
            for i, r in enumerate(reqs):
                if not r.generated:      # fresh: sample the first token
                    self._append(r, self._sample(last_logits[i], r))
                # resumed requests re-filled their pages; logits are dropped

    def decode_inputs(self, reqs: list[Request]):
        """``(exe, args)`` of one batched decode step over ``reqs``: the
        bucketed executable and its arguments (params, last tokens, pool,
        page table, positions).  ``exe(*args)`` returns (logits, pool)."""
        Bb = _bucket(len(reqs))
        tokens = np.zeros(self._token_shape(Bb, 1), np.int32)
        positions = np.zeros((Bb,), np.int32)
        page_table = np.full((Bb, self.pmax), TRASH_PAGE, np.int32)
        for i, r in enumerate(reqs):
            tokens[i, 0] = r.generated[-1]
            positions[i] = r.cache_len()
            page_table[i, :len(r.pages)] = r.pages
        return self._decode_exe(Bb), (self.params, tokens, self.pool,
                                      page_table, positions)

    def _run_decode(self, reqs: list[Request]) -> None:
        with TraceAnnotation("repro.serve.decode.inputs"):
            exe, args = self.decode_inputs(reqs)
        with TraceAnnotation("repro.serve.decode.dispatch"):
            logits, self.pool = exe(*args)
        with TraceAnnotation("repro.serve.decode.fetch"):
            logits = np.asarray(logits[:, 0], np.float32)
        with TraceAnnotation("repro.serve.sample"):
            for i, r in enumerate(reqs):
                self._append(r, self._sample(logits[i], r))
                self.decoded_tokens += 1

    def _append(self, req: Request, token) -> None:
        """A sampled token, on the host: stamp it and finish the request
        if it is its last."""
        req.generated.append(token)
        now = time.perf_counter()
        if req.t_first_token is None:
            req.t_first_token = now
        if req.done:
            req.t_finish = now
            self.sched.finish(req)
            self.finished.append(req)

    def step(self) -> bool:
        """One engine step.  Returns True if any work ran."""
        with TraceAnnotation("repro.serve.step"):
            with TraceAnnotation("repro.serve.plan"):
                plan = self.sched.plan()
                now = time.perf_counter()
                for r in plan.prefill:
                    if r.t_admit is None:
                        r.t_admit = now
            if plan.decode:
                self._run_decode(plan.decode)
            if plan.prefill:
                self._run_prefill(plan.prefill)
            if not plan.empty:
                self.n_steps += 1
            return not plan.empty

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Drive steps until every submitted request finishes."""
        for _ in range(max_steps):
            if not self.step():
                if not (self.sched.waiting or self.sched.running):
                    return self.finished
                raise RuntimeError(
                    f"stalled: {self.sched.stats()} -- pool too small for "
                    f"even one request?")
        raise RuntimeError(f"no convergence in {max_steps} steps")

    # -- introspection -----------------------------------------------------

    def peak_kv_bytes(self) -> int:
        return self.alloc.peak_used * page_bytes(self.cfg, self.page_size,
                                                 self.pool_dtype)

    def stats(self) -> dict:
        s = self.sched.stats()
        s.update(steps=self.n_steps, decoded_tokens=self.decoded_tokens,
                 prefill_tokens=self.prefill_tokens,
                 prefill_slots=self.prefill_slots,
                 finished=len(self.finished),
                 peak_kv_bytes=self.peak_kv_bytes(),
                 compile_cache=self.compile_cache.stats())
        return s
