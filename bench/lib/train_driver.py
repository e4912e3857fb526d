"""The training driver: DmSGD through the program's own trainer.

Set-up builds one object -- ``build_trainer``'s compiled step with its
node-stacked parameters and optimizer state, donated as ``train.run``
donates them -- drives it through its first steps on distinct batches (the
observations the correctness check compares come from those steps, and
they compile every gossip realization), and hands the same object to the
window.  The traffic file's ``trainer`` object is passed to
``build_trainer`` as keyword arguments.  The window cycles the set-up's
batches and ends in ``block_until_ready``.  After the window, with the program's state freed,
the plain float32 reference runs the same first steps from the same seed.
"""
from __future__ import annotations

import functools
import gc
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import bigram, compare, flops, peaks, reference, spec, weights
from . import trace as tracing
from .harness import ROOT, peak_memory, settle_heap

FIRST_STEPS = 3


def _on_nodes(mesh, n):
    """out_shardings that put every leaf with a leading node axis one node
    per device (the trainer's layout), the rest replicated."""
    def shard(tree_shapes):
        return jax.tree.map(lambda s: NamedSharding(
            mesh, P("node") if s.ndim and s.shape[0] == n else P()),
            tree_shapes)
    return shard


def _program_observations(step_for, params, state, batches, lr, dm, wkey,
                          n_first):
    """Drive the trainer through its first steps; returns the observations
    and the state handed on to the window."""
    losses, grad = [], None
    for k in range(n_first):
        params, state, loss = step_for(k)(params, state,
                                          {"tokens": batches[k]}, lr)
        losses.append(loss)
        if k == 0:
            grad = compare.node_leaf_norms(weights.from_program(
                dm, state.momentum))
        if k == FIRST_STEPS - 1:
            obs = compare.change_obs(weights.from_program(dm, params), dm,
                                     wkey)
    obs.update(losses=[float(x) for x in losses[:FIRST_STEPS]],
               grad={k: np.asarray(v) for k, v in grad.items()})
    return obs, params, state


def _reference_observations(dm, devs, n, batches, lr, beta, wkey,
                            mode="f32", fault=None):
    mesh = Mesh(np.array(devs), ("node",))
    node = NamedSharding(mesh, P("node"))
    ref = reference.DmSGD(functools.partial(spec.arch_of(dm).loss, dm=dm),
                          mesh, beta=beta, lr=lr, mode=mode, fault=fault)

    def start(wkey):
        flat = weights.draw(dm, wkey, dtype=jnp.float32)
        x = {k: jnp.broadcast_to(v, (n,) + v.shape) for k, v in flat.items()}
        return x, jax.tree.map(jnp.zeros_like, x)

    x, m = jax.jit(start, out_shardings=node)(wkey)
    losses = []
    for k in range(FIRST_STEPS):
        loss, g = ref.grads(x, jax.device_put(batches[k], node))
        losses.append(float(jnp.mean(loss)))
        if k == 0:
            g0 = compare.node_leaf_norms(g)
        x, m = ref.update(k)(x, m, g)
        if k == 0:
            grad = compare.node_leaf_norms(m)
    del m, g
    obs = compare.change_obs(x, dm, wkey)
    obs.update(losses=losses,
               grad={k: np.asarray(v) for k, v in grad.items()},
               g0={k: np.asarray(v) for k, v in g0.items()})
    return obs


def run(*, workload, config, traffic, limits, devs, seed, seconds, trace,
        t_start, dims=None):
    from repro.core import topology as topo_mod
    from repro.launch import train as train_mod

    cfg_spec = spec.load(config)
    dm = dims or spec.dims(cfg_spec)
    remat = cfg_spec.get("assumed", {}).get("remat", True)
    cfg = spec.arch_of(dm).program_config(dm, config, remat=remat)
    n = traffic["nodes"]
    if len(devs) != n:
        raise ValueError(f"{n} nodes need {n} chips, got {len(devs)}")
    B, S = traffic["per_node_batch"], traffic["seq"]
    beta, lr_value = traffic["beta"], traffic["lr"]

    mesh = Mesh(np.array(devs), ("node",)) if n > 1 else None
    top = topo_mod.get_topology(traffic["topology"], n)
    opt, step_for = train_mod.build_trainer(cfg, top, traffic["optimizer"],
                                            beta, mesh=mesh, donate=True,
                                            **traffic.get("trainer", {}))
    n_first = max(FIRST_STEPS, len(top.realizations or ()) or 1)

    wkey = weights.key(seed)
    bkey = jax.random.key(weights.seed32(seed, salt=2))

    def stacked(wkey):
        tree = weights.to_program(dm, weights.draw(dm, wkey))
        return jax.tree.map(lambda p: jnp.broadcast_to(p, (n,) + p.shape),
                            tree)

    place = _on_nodes(mesh, n) if mesh is not None else (lambda s: None)
    params = jax.jit(stacked, out_shardings=place(
        jax.eval_shape(stacked, wkey)))(wkey)
    state = jax.jit(opt.init, out_shardings=place(
        jax.eval_shape(opt.init, params)))(params)
    toks = bigram.batches(bkey, vocab=dm.vocab, n_nodes=n,
                          n_batches=traffic["n_batches"], batch=B, seq=S,
                          hetero=traffic["hetero"])
    toks = np.asarray(toks)
    batch_sharding = (NamedSharding(mesh, P("node")) if mesh is not None
                      else devs[0])
    batches = [jax.device_put(t, batch_sharding) for t in toks]
    lr = jnp.asarray(lr_value, jnp.float32)

    prog_obs, params, state = _program_observations(
        step_for, params, state, batches, lr, dm, wkey, n_first)
    jax.block_until_ready((params, state))
    settle_heap()
    setup_s = time.perf_counter() - t_start

    # -- the window ----------------------------------------------------------
    tokens_per_step = n * B * S
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, traffic["trace_seconds"])
        jax.profiler.start_trace(trace_dir)
    losses = []
    step = n_first
    t0 = time.perf_counter()
    with tracing.span("bench.window", trace):
        while time.perf_counter() - t0 < seconds:
            with tracing.span("bench.step_dispatch", trace):
                params, state, loss = step_for(step)(
                    params, state,
                    {"tokens": batches[step % len(batches)]}, lr)
            losses.append(loss)
            step += 1
            if len(losses) > 1:
                with tracing.span("bench.wait_previous_step", trace):
                    losses[-2].block_until_ready()
        with tracing.span("bench.wait_last_step", trace):
            jax.block_until_ready((params, state))
    window_s = time.perf_counter() - t0
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = tracing.reduce(tracing.extract(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        kernels = sorted({k[:200] for k in summary["op_s"]
                          if " custom-call " in k})
        print(f"bench: custom calls in the trace: {kernels}", file=sys.stderr)
    steps = len(losses)
    loss_values = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(loss_values)))
    memory_peak = peak_memory(devs)

    # -- the reference, with the program's state freed ------------------------
    del params, state, losses, batches
    gc.unfreeze()
    gc.collect()
    ref_obs = _reference_observations(dm, devs, n, toks, lr_value, beta, wkey)
    nums = compare.numbers(prog_obs, ref_obs)
    print(f"train numbers: {nums}", file=sys.stderr)
    checks = {k: {"value": nums[k], "limit": v} for k, v in limits.items()}

    tok_s_chip = steps * tokens_per_step / window_s / len(devs)
    out = {"ok": steps > 0 and failed == 0, "attempted": steps,
           "failed": failed, "checks": checks, "memory_peak": memory_peak,
           "end_to_end": {"train_tok_s_per_chip": tok_s_chip,
                          "setup_s": setup_s}}
    if trace:
        kind = devs[0].device_kind
        out["trace"] = summary
        out["reader_ctx"] = {
            "kind": "train", "summary": summary, "steps": steps,
            "window_s": window_s, "chips": len(devs), "nodes": n,
            "tokens_per_s_per_chip": tok_s_chip,
            "flops_per_token": flops.train_flops_per_token(dm, S),
            "peaks": peaks.peaks(kind) if devs[0].platform == "tpu" else None,
        }
    return out
