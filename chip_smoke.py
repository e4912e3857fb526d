"""Drive the system's main paths once on a TPU, at qwen3-0.6b's published
widths (28 layers, d_model 1024, vocab 151936; random weights from a seed).

    python3 chip_smoke.py              # one chip: train, serve, gossip kernel
    python3 chip_smoke.py --four-chip  # four chips: the decentralized trainer

One chip runs three phases in this one process, through the normal entry
points:

* ``train``  -- ``repro.launch.train.run`` with one node and DmSGD; every
  loss is finite, and the loss on the batches it trained on falls.
* ``serve``  -- ``ServeEngine`` fed a ``poisson_trace`` through
  ``serve_trace``, once with the Pallas paged-attention kernel and once
  with the jnp gather; the program must contain the kernel, and the greedy
  tokens must match or the logits of one decode step on identical inputs
  must agree within ``LOGIT_RTOL``.
* ``gossip`` -- the compiled ``gossip_mix`` kernel on one node's packed f32
  parameters against ``gossip_mix_ref``.

``--four-chip`` runs only the trainer with one node per chip over
``one_peer_exp``: exact averaging after log2(4) = 2 rounds at lr 0, a
falling loss at lr > 0, one collective-permute per dtype group plus the
``gossip_mix`` kernel in the step, and each node's arrays on its own chip.

Each phase prints one line of results.  The last line of stdout is the
device summary ``{"ok": true, "device": {...}}``; any failed check raises
and the script exits non-zero.  Without a TPU it exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import types

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "qwen3-0.6b"
# decode logits, Pallas kernel vs jnp gather on identical inputs: both
# attend in f32 over the same bf16 pool, but the online softmax sums in
# another order and the bf16 attention output can round the other way.
LOGIT_RTOL = 2e-2


class SmokeError(AssertionError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _train_args(**kw) -> types.SimpleNamespace:
    base = dict(arch=ARCH, reduced=False, nodes=1, topology="one_peer_exp",
                optimizer="dmsgd", beta=0.9, steps=6, batch=1, seq=512,
                lr=0.3, warmup=0, hetero=0.0, micro_batch=None, seed=0,
                desync=False, log_every=1, ckpt_dir=None, ckpt_every=1)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _step_times(history: list) -> dict:
    """Wall seconds of the first logged step (compile + run) and the mean
    of the later ones (each includes host data generation and the log)."""
    secs = [h["seconds"] for h in history]
    later = (secs[-1] - secs[0]) / (len(secs) - 1) if len(secs) > 1 else None
    return {"first_step_s": secs[0], "later_step_wall_s": later}


def _train_and_check(**kw) -> tuple[dict, dict]:
    """``train.run`` with ``_train_args(**kw)``; every logged loss is finite
    and the loss falls.

    A few steps at published widths barely move the loss on the next,
    unseen batch (per-batch spread hides it), so the fall is measured on
    the batches the run trained on: the mean loss of the trained
    parameters over them against that of the initial parameters, per
    node, through the trainer's own loss.  Returns (results, run output).
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import configs
    from repro.data import SyntheticLM
    from repro.launch import mesh as mesh_mod
    from repro.launch import steps as steps_mod
    from repro.launch import train
    from repro.models import model as M

    args = _train_args(**kw)
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced_config(cfg)
    data = SyntheticLM(cfg.vocab_size, args.nodes, hetero=args.hetero,
                       seed=args.seed)
    tokens = np.stack([data.sample(t, args.batch, args.seq)
                       for t in range(args.steps)])   # (steps, n, B, S)
    mesh = mesh_mod.node_mesh(args.nodes)
    if mesh is not None:
        tokens = jax.device_put(tokens, NamedSharding(mesh, P(None, "node")))

    def seen_loss(params, stacked: bool) -> float:
        per_node = jax.vmap(lambda p, t: steps_mod.train_loss_fn(p, cfg, t),
                            in_axes=(0 if stacked else None, 0))
        per_step = jax.jit(lambda p, t: jax.lax.map(
            lambda tt: per_node(p, tt), t))
        return float(np.mean(np.asarray(per_step(params, tokens))))

    before = seen_loss(M.init(cfg, jax.random.key(args.seed)), False)
    out = train.run(args)
    losses = [h["loss"] for h in out["history"]]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    after = seen_loss(out["params"], True)
    check(math.isfinite(after) and after < before,
          f"the loss on the trained batches did not fall: {before} -> "
          f"{after} (losses while training: {losses})")
    return ({"losses": losses, "seen_loss_before": before,
             "seen_loss_after": after, **_step_times(out["history"])}, out)


def phase_train(*, reduced: bool = False, steps: int = 6,
                seq: int = 512) -> dict:
    """One DmSGD node on one device: finite, falling loss."""
    res, _ = _train_and_check(reduced=reduced, steps=steps, seq=seq)
    return res


def phase_serve(*, reduced: bool = False, n_requests: int = 4,
                max_new: int = 8) -> dict:
    """Greedy serving, Pallas paged kernel vs the jnp gather."""
    import jax
    import numpy as np

    from repro import configs
    from repro.launch.serve import poisson_trace, serve_trace
    from repro.models import model as M
    from repro.serve import ServeEngine

    cfg = configs.get_config(ARCH)
    if reduced:
        cfg = configs.reduced_config(cfg)
    params = M.init(cfg, jax.random.key(0))
    # every request arrives at once, so the run compiles one prefill and
    # one decode bucket per attention implementation
    trace = [(0.0, p, m) for _, p, m in poisson_trace(
        n_requests, rate=1.0, mean_prompt=12, max_new=max_new,
        vocab=cfg.vocab_size, seed=0)]

    caches: dict = {}

    def engine(impl):
        eng = ServeEngine(dataclasses.replace(cfg, attention_impl=impl),
                          params, n_pages=64, page_size=16, max_seq=64,
                          max_batch=n_requests, compile_cache=caches.get(impl))
        caches[impl] = eng.compile_cache
        return eng

    res: dict = {}
    tokens: dict = {}
    for impl in ("pallas", "jnp"):
        for run in ("cold", "warm"):
            eng = engine(impl)
            t0 = time.perf_counter()
            serve_trace(eng, trace)
            res[f"{impl}_{run}_s"] = time.perf_counter() - t0
            got = {r.rid: [int(t) for t in r.generated] for r in eng.finished}
            check(len(got) == n_requests and all(
                len(t) == max_new for t in got.values()),
                f"{impl}: finished {len(got)} of {n_requests} requests")
            check(tokens.setdefault(impl, got) == got,
                  f"{impl}: the warm run generated other tokens")
        res[f"{impl}_executables"] = caches[impl].stats()["entries"]

    # one decode step of each implementation on identical inputs: the jnp
    # engine's pool and page tables right after the prefill
    ref = engine("jnp")
    for _, prompt, mx in trace:
        ref.submit(prompt, mx)
    ref.step()
    running = list(ref.sched.running)
    exe_j, args = ref.decode_inputs(running)
    exe_p, _ = engine("pallas").decode_inputs(running)
    check("pallas_call" in str(jax.make_jaxpr(exe_p)(*args)),
          "the pallas engine's decode step does not reach the kernel")
    check("pallas_call" not in str(jax.make_jaxpr(exe_j)(*args)),
          "the jnp engine's decode step holds a Pallas kernel")
    rows = len(running)
    lp = np.asarray(exe_p(*args)[0], np.float32)[:rows]
    lj = np.asarray(exe_j(*args)[0], np.float32)[:rows]
    check(bool(np.isfinite(lp).all() and np.isfinite(lj).all()),
          "non-finite decode logits")
    rel = float(np.abs(lp - lj).max() / np.abs(lj).max())
    match = sum(tokens["pallas"][r] == tokens["jnp"][r] for r in tokens["jnp"])
    res.update(tokens_matching=f"{match}/{n_requests}",
               decode_logits_rel_err=rel, logit_rtol=LOGIT_RTOL)
    check(match == n_requests or rel <= LOGIT_RTOL,
          f"pallas and jnp disagree: {match}/{n_requests} token sequences "
          f"match and decode logits differ by {rel:.3e} of their max")
    return res


def phase_gossip(*, reduced: bool = False) -> dict:
    """``gossip_mix`` on one node's packed f32 parameters vs the ref."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core import flatbuf
    from repro.kernels.gossip_mix import ops as gm_ops
    from repro.kernels.gossip_mix.ref import gossip_mix_ref
    from repro.models import model as M

    cfg = configs.get_config(ARCH)
    if reduced:
        cfg = configs.reduced_config(cfg)
    shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    node = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), shapes)
    (group,) = flatbuf.layout_of(node).groups
    check(group.dtype == jnp.float32, f"payload dtype {group.dtype}")
    kx, kr = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, (1, group.padded), jnp.float32)
    r = jax.random.normal(kr, (1, group.padded), jnp.float32)

    def mix():
        return gm_ops.gossip_mix(x, [r], w_self=0.5, ws=(0.5,))

    t0 = time.perf_counter()
    mix().block_until_ready()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = mix().block_until_ready()
    again = time.perf_counter() - t0
    want = jax.jit(gossip_mix_ref, static_argnums=(2, 3))(x, [r], 0.5, (0.5,))
    err = float(jnp.max(jnp.abs(out - want)))
    scale = float(jnp.max(jnp.abs(want)))
    check(math.isfinite(err) and err <= 1e-6 * scale,
          f"gossip_mix differs from its reference by {err:.3e}")
    moved = 3 * group.padded * 4          # read x and r, write out
    return {"elements": group.padded, "first_call_s": first,
            "call_s": again, "bytes_per_s": moved / again,
            "max_abs_err": err, "ref_max_abs": scale}


def phase_four_chip(*, reduced: bool = False, steps: int = 4,
                    seq: int = 512) -> dict:
    """One node per device over ``one_peer_exp``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import flatbuf
    from repro.launch import train
    from repro.launch.hlo_cost import analyze_hlo

    n = jax.device_count()
    check(n == 4, f"the four-chip phase needs 4 devices, sees {n}")
    res: dict = {}

    # finite-time exact averaging: 2 rounds at lr 0 from desynced nodes
    out = train.run(_train_args(reduced=reduced, nodes=n, steps=2, lr=0.0,
                                desync=True, seq=seq))
    cons = [h["consensus"] for h in out["history"]]
    res["consensus_lr0"] = cons
    check(cons[0] > 0 and cons[1] <= 1e-4 * cons[0],
          f"no exact average after 2 rounds: consensus {cons}")
    res.update({f"lr0_{k}": v for k, v in _step_times(out["history"]).items()})
    del out

    trained, out = _train_and_check(reduced=reduced, nodes=n, steps=steps,
                                    seq=seq)
    res.update(trained)
    params, state, plan = out["params"], out["state"], out["plan"]
    del out

    devices = set()
    for leaf in jax.tree.leaves((params, state.momentum)):
        shards = leaf.addressable_shards
        check(len(shards) == n and all(s.data.shape[0] == 1 for s in shards),
              f"a leaf of shape {leaf.shape} is not one node per device")
        devices |= {s.device for s in shards}
        check(len({s.device for s in shards}) == n,
              f"a leaf of shape {leaf.shape} shares a device between nodes")
    res["devices"] = sorted(d.id for d in devices)

    node = NamedSharding(plan.mesh, P("node"))
    batch = {"tokens": jax.ShapeDtypeStruct((n, 1, seq), jnp.int32,
                                            sharding=node)}
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    step = plan.step_fn(1)
    check("pallas_call" in str(step.trace(params, state, batch, lr).jaxpr),
          "the train step does not reach the gossip_mix kernel")
    text = step.lower(params, state, batch, lr).compile().as_text()
    permutes = analyze_hlo(text).collective_counts.get("collective-permute", 0)
    groups = len(flatbuf.layout_of(params).groups)
    kernels = text.count('custom_call_target="tpu_custom_call"')
    res.update(collective_permutes=permutes, dtype_groups=groups,
               tpu_custom_calls=kernels)
    check(permutes == groups,
          f"{permutes} collective-permutes for {groups} dtype groups")
    # the CPU tests run this phase with the kernel in interpret mode, where
    # it lowers to plain HLO; on the chip it must be a Mosaic custom call
    check(jax.default_backend() != "tpu" or kernels >= 1,
          "the compiled train step holds no Pallas kernel")
    return res


def _peak_bytes() -> int | None:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip decentralized trainer")
    opts = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    from repro.core.cache import enable_persistent_cache

    print(f"compile cache: {enable_persistent_cache()}", flush=True)
    phases = ([("four_chip", phase_four_chip)] if opts.four_chip else
              [("train", phase_train), ("serve", phase_serve),
               ("gossip", phase_gossip)])
    for name, fn in phases:
        t0 = time.perf_counter()
        res = fn()
        res.update(phase_s=time.perf_counter() - t0,
                   peak_bytes_in_use=_peak_bytes())
        print(f"[{name}] {json.dumps(res)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
