"""End-to-end decentralized training driver.

Runs DmSGD (or any variant) over any topology on any assigned architecture.
With one visible device every node is stacked on it along the leading
axis; with several, one node sits on each device (a ("node",) mesh) and
every gossip round runs shard-natively.  ``--full`` trains the published
widths; the default ``--reduced`` keeps the block structure at tiny dims.

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --nodes 8 --topology one_peer_exp --optimizer dmsgd --steps 100
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import checkpoint, configs
from repro.core import flatbuf
from repro.core import optim as optim_mod
from repro.core import schedule
from repro.core import topology as topo_mod
from repro.core.cache import enable_persistent_cache
from repro.core.plan import GossipPlan
from repro.data import SyntheticLM
from repro.launch import mesh as mesh_mod
from repro.launch import sharding as sharding_mod
from repro.launch import steps as steps_mod


def build_trainer(cfg, topology, optimizer_name: str, beta: float,
                  micro_batch=None, momentum_dtype=None, warmup_steps=0,
                  mesh=None, payload_specs=None, overlap=False,
                  loss_aware=False, deadline=False, donate=False):
    """Returns (opt, step_for) where ``step_for(step)`` is the compiled
    train-step callable for that step's gossip realization (the plan
    itself rides along as ``step_for.plan`` -- checkpoint flushes and
    introspection go through it).

    All schedule handling (realization-IR classification -- Shifts /
    Matching / Dense / Identity -- warm-up phase keying, realization-keyed
    compile cache) lives in :class:`repro.core.plan.GossipPlan`; this is
    just optimizer + step function + plan wiring.  Pass a ``mesh`` whose
    ``node`` axis matches the node count to run every Shifts/Matching round
    shard-natively (one explicit-pairs collective-permute per dtype group,
    each device moving only its local shard); on a multi-axis mesh
    ``payload_specs`` carries the payload's PartitionSpecs -- by default
    the full ("node", "fsdp", "model") logical mesh reuses the parameter
    placement rules (:func:`repro.launch.sharding.gossip_payload_spec_fn`)
    so inner-dim shardings pass through the gossip untouched.

    ``overlap=True`` builds the one-step-delayed pipelined trainer: the
    gossip permute for step t's payload is issued at the top of step t+1
    (hidden under that step's backward), the packed payload rides the
    optimizer state as a double buffer, and params + state are DONATED to
    the executable so the buffer rotates in place instead of being copied.
    ``donate=True`` donates params + state to the synchronous step too: the
    new iterates then reuse the old ones' memory, which a loop that never
    reads a step's inputs again (``run``) can afford at published widths.
    """
    opt = optim_mod.make_optimizer(optimizer_name, topology, beta=beta,
                                   momentum_dtype=momentum_dtype,
                                   overlap=overlap, loss_aware=loss_aware,
                                   deadline=deadline)
    if warmup_steps:
        from repro.core.transforms import allreduce_warmup
        opt = allreduce_warmup(warmup_steps)(opt)
    if (payload_specs is None and mesh is not None
            and "node" in mesh.axis_names and len(mesh.axis_names) > 1):
        # multi-axis mesh: any default spec would declare the payload's
        # inner dims replicated and GSPMD would reshard fsdp/model-sharded
        # leaves at the shard_map boundary -- the bug the engine fixes
        payload_specs = sharding_mod.gossip_payload_spec_fn(mesh)
    step_fn = steps_mod.make_train_step(cfg, opt, micro_batch=micro_batch)
    plan = GossipPlan.for_optimizer(opt, fn=step_fn, mesh=mesh,
                                    specs=payload_specs,
                                    donate_argnums=(0, 1) if overlap or donate
                                    else ())

    def step_for(step, **kw):
        return plan.step_fn(step, **kw)

    step_for.plan = plan
    return opt, step_for


@jax.jit
def _consensus_sq(params) -> jax.Array:
    """sum_i ||x_i - x_bar||^2 over the packed flat buffers (one jitted
    reduction per tree structure; padding columns are zeros on every node,
    so they contribute exactly 0)."""
    _, bufs = flatbuf.pack(params)
    total = jnp.zeros((), jnp.float32)
    for buf in bufs:
        b32 = buf.astype(jnp.float32)
        total += jnp.sum(jnp.square(b32 - b32.mean(axis=0, keepdims=True)))
    return total


def consensus_distance(params) -> float:
    """||x_i - x_bar|| aggregated over the pytree (paper's consensus metric).

    Vectorized via the flat-buffer pack: one compiled reduction and a
    single host sync, instead of a python loop with a ``float()`` sync per
    leaf."""
    return float(jnp.sqrt(_consensus_sq(params)))


def _born_on_nodes(fn, mesh, *args):
    """``jax.jit(fn)(*args)`` with every output leaf that has a leading node
    axis sharded over the mesh's ``node`` axis (the rest replicated), so
    node-stacked params and optimizer state are created in place, one
    node per device, and never assembled on a single device first."""
    if mesh is None:
        return jax.jit(fn)(*args)
    n = mesh.shape["node"]
    shapes = jax.eval_shape(fn, *args)
    shard = jax.tree.map(
        lambda s: NamedSharding(mesh, P("node") if s.ndim and s.shape[0] == n
                                else P()), shapes)
    return jax.jit(fn, out_shardings=shard)(*args)


def run(args) -> dict:
    """Train per ``args`` (the CLI namespace).  Returns the logged
    ``history`` (step, loss, consensus, lr, wall seconds since the first
    step began), the final node-stacked ``params`` and ``state``, the
    ``config``, and the ``plan`` the steps ran on."""
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced_config(cfg)
    n = args.nodes
    top = topo_mod.get_topology(args.topology, n)
    # momentum dtype comes from the arch's layout config (e.g. dbrx-132b
    # keeps momentum in bf16 for the HBM fit) -- an explicit argument, not
    # a process-global knob.
    layout = configs.get_layout(args.arch)
    mom_dtype = {"bfloat16": jnp.bfloat16,
                 "float32": jnp.float32}.get(layout.get("momentum_dtype"))
    overlap = getattr(args, "overlap", False)
    loss_aware = getattr(args, "loss_aware", False)
    deadline = getattr(args, "deadline_skip", False)
    straggler_prob = getattr(args, "straggler_prob", 0.0)
    if straggler_prob and not deadline:
        raise ValueError("--straggler-prob simulates missed deadlines; "
                         "pair it with --deadline-skip")
    mesh = mesh_mod.node_mesh(n)
    opt, step_for = build_trainer(cfg, top, args.optimizer, args.beta,
                                  args.micro_batch, momentum_dtype=mom_dtype,
                                  mesh=mesh, overlap=overlap,
                                  loss_aware=loss_aware, deadline=deadline,
                                  donate=True)
    plan = step_for.plan
    on_nodes = ((lambda x: jnp.asarray(x)) if mesh is None else
                (lambda x: jax.device_put(x, NamedSharding(mesh, P("node")))))

    from repro.models import model as M
    params = M.init(cfg, jax.random.key(args.seed))
    desync = args.optimizer != "parallel_msgd" and args.desync

    def stack(params):
        stacked = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (n,) + p.shape), params)
        if desync:
            # start nodes desynchronized to exercise consensus
            stacked = jax.tree.map(
                lambda p: p + 0.01 * jax.random.normal(
                    jax.random.key(1), p.shape, jnp.float32).astype(p.dtype),
                stacked)
        return stacked

    stacked = _born_on_nodes(stack, mesh, params)
    del params
    state = _born_on_nodes(opt.init, mesh, stacked)

    data = SyntheticLM(cfg.vocab_size, n, hetero=args.hetero, seed=args.seed)
    lr_fn = schedule.warmup_step_decay(
        args.lr, args.warmup, [int(args.steps * 0.6), int(args.steps * 0.85)])

    history = []
    t0 = time.time()
    for step in range(args.steps):
        batch_np = data.sample(step, args.batch, args.seq,
                               cfg.n_codebooks if cfg.family == "audio" else 0)
        batch = {"tokens": on_nodes(batch_np)}
        if cfg.family == "vlm":
            batch["image_embeds"] = on_nodes(jax.random.normal(
                jax.random.key(step), (n, args.batch, cfg.n_image_tokens,
                                       cfg.d_model), jnp.float32))
        if deadline:
            # simulated stragglers: each node independently misses the
            # round's deadline with prob p; the gossip drops it per node
            # (both directions) and renormalizes the surviving weights
            alive = jax.random.uniform(
                jax.random.key(2**20 + step), (n,)) >= straggler_prob
            batch["alive"] = on_nodes(alive)
        lr = lr_fn(step)
        stacked, state, loss = step_for(step)(stacked, state, batch, lr)
        if step % args.log_every == 0 or step == args.steps - 1:
            # the pipelined iterate is pre-mix; metrics read the FLUSHED
            # view (what the synchronous recursion would hold) without
            # disturbing the live buffer -- flush is pure
            ev_params, _ = plan.flush_step_fn(step + 1)(stacked, state)
            cd = consensus_distance(ev_params)
            history.append(dict(step=step, loss=float(loss), consensus=cd,
                                lr=float(lr), seconds=time.time() - t0))
            print(f"step {step:5d}  loss {float(loss):.4f}  "
                  f"consensus {cd:.3e}  lr {float(lr):.2e}  "
                  f"({time.time() - t0:.1f}s)")
        if args.ckpt_dir and step and step % args.ckpt_every == 0:
            if overlap and getattr(args, "ckpt_flush", False):
                # flush-on-save: persist the mixed iterates, no buffer;
                # resume re-primes the pipeline (step_for(k, prime=True))
                fp, fs = plan.flush_step_fn(step + 1)(stacked, state)
                payload = {"params": fp, "momentum": fs.momentum}
            else:
                # carry-buffer: the in-flight payload checkpoints with the
                # state, so resume is bit-identical to never stopping
                payload = {"params": stacked, "momentum": state.momentum}
                if state.buf is not None:
                    payload["gossip_buf"] = state.buf
            checkpoint.save(args.ckpt_dir, step, payload)
    if overlap:
        stacked, state = plan.flush_step_fn(args.steps)(stacked, state)
    return {"history": history, "params": stacked, "state": state,
            "config": cfg, "plan": plan}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--topology", default="one_peer_exp",
                    choices=sorted(topo_mod.TOPOLOGIES),
                    help="gossip graph; base_k/ceca are the finite-time "
                         "families (Takezawa 23 / cf. Ding 23)")
    ap.add_argument("--optimizer", default="dmsgd")
    ap.add_argument("--overlap", action="store_true",
                    help="one-step-delayed (overlapped) gossip: the permute "
                         "for step t's payload is issued at the top of step "
                         "t+1 and hides under that step's backward")
    ap.add_argument("--ckpt-flush", action="store_true",
                    help="flush the in-flight overlap buffer into the "
                         "checkpoint (smaller artifact, resume re-primes) "
                         "instead of carrying it (bit-identical resume)")
    ap.add_argument("--loss-aware", action="store_true",
                    help="AL-DSGD adjacent-leader weights: pull harder from "
                         "better-loss neighbors; the per-node losses ride "
                         "the existing gossip permute (zero extra "
                         "collectives)")
    ap.add_argument("--deadline-skip", action="store_true",
                    help="per-node straggler tolerance: nodes whose alive "
                         "flag is False drop out of the round (skipped "
                         "edges renormalize into the self weight)")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="per-step probability each node misses the gossip "
                         "deadline (simulated; needs --deadline-skip)")
    ap.add_argument("--beta", type=float, default=0.9)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4, help="per-node batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--hetero", type=float, default=0.0)
    ap.add_argument("--micro-batch", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--desync", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    enable_persistent_cache()
    run(args)


if __name__ == "__main__":
    main()
