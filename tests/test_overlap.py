"""Overlapped (delayed-mix) gossip pipeline.

The pipelined executable must be BIT-identical to a sequential reference
of the same one-step-delayed recursion (mix step t-1's payload, update
locally with grads at the pre-mix iterate, emit step t's payload), keep
exactly one collective-permute per dtype group in HLO, still exactly
average over a finite-time family's period after the final flush, and
survive checkpoint/restore mid-pipeline -- flush-on-save and carry-buffer
both bit-exactly.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.core import optim, topology, transforms
from repro.core.plan import GossipPlan, OverlapIO

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _eq(a, b, tag=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=tag)


def _params(n=4, d=12, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)}


def _grads(params, T, seed=100):
    return [jax.tree.map(lambda x: jnp.asarray(
        np.random.default_rng(seed + t).standard_normal(x.shape),
        jnp.float32), params) for t in range(T)]


def _sequential_delayed_step(opt_s, sync_plan, t, lr):
    """ONE jitted program of the delayed recursion's step ``t``, built
    only from the SYNCHRONOUS public pieces: apply step t-1's mix to the
    carried payload, run the chain with an identity mix, emit the fresh
    payload (= this step's pre-mix where-tensors)."""
    names = opt_s.gossip_where
    mix = sync_plan.mix(t - 1) if t > 0 else None

    def fn(p, s, g, pay):
        if mix is not None:
            mixed = mix(pay)
            vals = (mixed,) if len(names) == 1 else tuple(mixed)
            slots = dict(opt_s._slots_of(s))
            for w, v in zip(names, vals):
                if w == "x_next":
                    p = jax.tree.map(lambda a, b: a.astype(b.dtype), v, p)
                else:
                    slots[w[:-5]] = jax.tree.map(
                        lambda a, b: a.astype(b.dtype), v, slots[w[:-5]])
            s = opt_s._state_of(slots, s.count)
        p2, s2 = opt_s.update_with_mix(p, s, g, lr, lambda t_: t_)
        slots2 = dict(opt_s._slots_of(s2))
        parts = tuple((p2 if w == "x_next" else slots2[w[:-5]])
                      for w in names)
        return p2, s2, parts[0] if len(parts) == 1 else parts

    return jax.jit(fn)


def _run_pipelined(opt_o, plan, params, grads, lr, start=0, state=None):
    p = params
    s = opt_o.init(params) if state is None else state
    hist = []
    for i, g in enumerate(grads):
        t = start + i
        p, s = plan.step_fn(t, prime=(s.buf is None and t > 0))(p, s, g)
        hist.append((p, s))
    return p, s, hist


@pytest.mark.parametrize("name", ["dmsgd", "dsgd", "vanilla_dmsgd",
                                  "d_adamw"])
def test_pipelined_bit_identical_to_sequential_delayed(name):
    """Acceptance: the pipelined executable == the sequential delayed-mix
    reference, params AND state, every step, plus the final flush."""
    n, T, lr = 4, 9, 0.1
    top = topology.one_peer_exponential(n)
    params = _params(n)
    grads = _grads(params, T)
    opt_o = optim.make_optimizer(name, top, beta=0.9, overlap=True)
    opt_s = optim.make_optimizer(name, top, beta=0.9)
    assert opt_o.overlap and not opt_s.overlap

    plan = GossipPlan.for_optimizer(
        opt_o, fn=lambda io, p, s, g: opt_o.update_pipelined(p, s, g, lr, io))
    pf, sf, hist = _run_pipelined(opt_o, plan, params, grads, lr)
    pf, sf = plan.flush_step_fn(T)(pf, sf)
    assert sf.buf is None

    sync_plan = GossipPlan.for_optimizer(opt_s)
    p, s, pay = params, opt_s.init(params), None
    for t in range(T):
        p, s, pay = _sequential_delayed_step(opt_s, sync_plan, t, lr)(
            p, s, grads[t], pay)
        _eq(p, hist[t][0], f"{name} params @ step {t}")
        _eq(s.momentum, hist[t][1].momentum, f"{name} momentum @ step {t}")
    # flush == one final synchronous mix of the in-flight payload
    mixed = jax.jit(sync_plan.mix(T - 1))(pay)
    vals = (mixed,) if len(opt_s.gossip_where) == 1 else tuple(mixed)
    for w, v in zip(opt_s.gossip_where, vals):
        if w == "x_next":
            _eq(v, pf, f"{name} flushed params")


def test_pipelined_int8_and_every_and_warmup():
    """The overlap pipeline composes with the rest of the transform
    algebra: int8 wire compression, gossip(every=k) Identity off-steps,
    and the Corollary-3 all-reduce warm-up phase -- each matching the
    sequential delayed reference built from the sync executors.

    To f32 rounding, not bit for bit: with every=2 or int8 the two programs
    fuse the combine with different neighbours, and on a CPU with FMA
    instructions LLVM contracts a multiply-add in one fusion and not the
    other (1 ulp on one element under jax 0.9.0).  Held to SSE4.2
    (``XLA_FLAGS=--xla_cpu_max_isa=SSE4_2``, no FMA) they agree bit for bit.
    The plain every=1 pipeline keeps its bit-exact test above."""
    n, T, lr = 4, 8, 0.05
    top = topology.one_peer_exponential(n)
    params = _params(n, seed=3)
    grads = _grads(params, T, seed=50)
    for kw in ({"compression": "int8"}, {}):
        for every, warmup in ((1, 2), (2, 0)):
            def build(overlap):
                o = transforms.chain(
                    transforms.trace_momentum(0.9),
                    transforms.scale_by_lr("m"),
                    transforms.quantize_int8() if kw else None,
                    transforms.gossip(where=("m_next", "x_next"),
                                      every=every, overlap=overlap),
                    topology=top, name="t", beta=0.9)
                if warmup:
                    o = transforms.allreduce_warmup(warmup)(o)
                return o

            opt_o, opt_s = build(True), build(False)
            plan = GossipPlan.for_optimizer(
                opt_o,
                fn=lambda io, p, s, g: opt_o.update_pipelined(p, s, g, lr,
                                                              io))
            pf, sf, hist = _run_pipelined(opt_o, plan, params, grads, lr)
            sync_plan = GossipPlan.for_optimizer(opt_s)
            p, s, pay = params, opt_s.init(params), None
            for t in range(T):
                p, s, pay = _sequential_delayed_step(
                    opt_s, sync_plan, t, lr)(p, s, grads[t], pay)
                for x, y in zip(jax.tree.leaves(p),
                                jax.tree.leaves(hist[t][0])):
                    np.testing.assert_allclose(
                        np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7,
                        err_msg=f"int8={bool(kw)} every={every} "
                                f"warmup={warmup} step {t}")


def test_delayed_exact_average_over_period():
    """Consensus property: with zero gradients, the delayed one-peer
    pipeline still reaches the EXACT average after one period + flush
    (the mixes compose identically, just one step late)."""
    for top in (topology.one_peer_exponential(8),
                topology.one_peer_hypercube(8),
                topology.ceca(6),
                topology.bipartite_random_match(6, pool=2)):
        n = top.n
        params = _params(n, d=7, seed=9)
        zero = [jax.tree.map(jnp.zeros_like, params)] * (top.period or 8)
        opt = optim.dsgd(top, overlap=True)
        plan = GossipPlan.for_optimizer(
            opt, fn=lambda io, p, s, g: opt.update_pipelined(p, s, g, 0.0,
                                                             io))
        p, s, _ = _run_pipelined(opt, plan, params, zero, 0.0)
        p, _ = plan.flush_step_fn(len(zero))(p, s)
        if top.name in ("one_peer_exp", "one_peer_hypercube", "ceca"):
            # finite-time families: exact average after one period
            for k, x in p.items():
                want = np.broadcast_to(
                    np.asarray(params[k]).mean(0, keepdims=True), x.shape)
                np.testing.assert_allclose(np.asarray(x), want, atol=1e-6)
        # every family: the global mean is preserved exactly
        for k, x in p.items():
            np.testing.assert_allclose(np.asarray(x).mean(0),
                                       np.asarray(params[k]).mean(0),
                                       atol=1e-6)


def test_checkpoint_carry_buffer_resumes_bit_identically(tmp_path):
    """Save/restore THROUGH checkpoint/ckpt.py with a live overlap buffer:
    carrying the in-flight buffer resumes bit-identically to never having
    stopped."""
    n, T, k, lr = 4, 8, 3, 0.1
    top = topology.one_peer_exponential(n)
    params = _params(n)
    grads = _grads(params, T)
    opt = optim.dmsgd(top, beta=0.9, overlap=True)
    plan = GossipPlan.for_optimizer(
        opt, fn=lambda io, p, s, g: opt.update_pipelined(p, s, g, lr, io))

    # uninterrupted run
    pu, su, hist = _run_pipelined(opt, plan, params, grads, lr)

    # run to step k, checkpoint WITH the live buffer, restore, resume
    p, s, _ = _run_pipelined(opt, plan, params, grads[:k], lr)
    assert s.buf is not None
    ckpt.save(str(tmp_path), k, {"params": p, "momentum": s.momentum,
                                 "count": s.count, "buf": s.buf})
    like = {"params": p, "momentum": s.momentum, "count": s.count,
            "buf": s.buf}
    rest = ckpt.restore(str(tmp_path), k, like)
    state = optim.OptState(rest["momentum"], rest["count"],
                           tuple(rest["buf"]))
    pr, sr, _ = _run_pipelined(opt, plan, rest["params"], grads[k:], lr,
                               start=k, state=state)
    _eq(pr, pu, "carry-buffer resumed params")
    _eq(sr.momentum, su.momentum, "carry-buffer resumed momentum")
    _eq(sr.buf, su.buf, "carry-buffer resumed in-flight buffer")


def test_checkpoint_flush_on_save_resumes_bit_identically(tmp_path):
    """Flush-on-save: the checkpoint holds the MIXED iterates and no
    buffer; resume re-primes the pipeline (step_fn(k, prime=True)).  The
    disk round trip must be bit-identical to the same flush + re-prime
    performed in memory."""
    n, T, k, lr = 4, 8, 3, 0.1
    top = topology.one_peer_exponential(n)
    params = _params(n)
    grads = _grads(params, T)
    opt = optim.dmsgd(top, beta=0.9, overlap=True)
    plan = GossipPlan.for_optimizer(
        opt, fn=lambda io, p, s, g: opt.update_pipelined(p, s, g, lr, io))

    p, s, _ = _run_pipelined(opt, plan, params, grads[:k], lr)
    fp, fs = plan.flush_step_fn(k)(p, s)
    assert fs.buf is None

    # in-memory reference: continue from the flushed state (re-prime)
    pm, sm, _ = _run_pipelined(opt, plan, fp, grads[k:], lr, start=k,
                               state=fs)

    # disk round trip of the flushed state
    ckpt.save(str(tmp_path), k, {"params": fp, "momentum": fs.momentum,
                                 "count": fs.count})
    rest = ckpt.restore(str(tmp_path), k,
                        {"params": fp, "momentum": fs.momentum,
                         "count": fs.count})
    state = optim.OptState(rest["momentum"], rest["count"], None)
    pr, sr, _ = _run_pipelined(opt, plan, rest["params"], grads[k:], lr,
                               start=k, state=state)
    _eq(pr, pm, "flush-on-save resumed params")
    _eq(sr.momentum, sm.momentum, "flush-on-save resumed momentum")
    # flushing drained exactly the pending realization: one more flush at
    # the same step is the identity
    fp2, fs2 = plan.flush_step_fn(k)(fp, fs)
    _eq(fp2, fp, "flush is idempotent")
    assert fs2.buf is None


def test_overlap_state_buffer_is_donated():
    """The double buffer rotates in place: with donate_argnums=(0, 1) the
    previous step's params/state buffers are consumed by the executable
    (accessing them afterwards raises)."""
    n, lr = 4, 0.1
    top = topology.one_peer_exponential(n)
    params = _params(n)
    opt = optim.dmsgd(top, beta=0.9, overlap=True)
    plan = GossipPlan.for_optimizer(
        opt, fn=lambda io, p, s, g: opt.update_pipelined(p, s, g, lr, io),
        donate_argnums=(0, 1))
    g = jax.tree.map(jnp.ones_like, params)
    p, s = plan.step_fn(0)(params, opt.init(params), g)
    old_buf = s.buf
    p, s = plan.step_fn(1)(p, s, g)
    with pytest.raises(RuntimeError):
        np.asarray(old_buf[0])   # donated to the step-1 executable


def test_overlap_compile_keys_and_prime():
    """Compile keys carry the overlap phase; the same in-flight
    realization reuses ONE executable across the whole run; prime and
    flush executables are keyed separately."""
    top = topology.one_peer_exponential(4)   # period 2
    opt = optim.dmsgd(top, overlap=True)
    plan = GossipPlan.for_optimizer(
        opt, fn=lambda io, p, s, g: opt.update_pipelined(p, s, g, 0.1, io))
    assert plan.realization_key(0) == ("overlap", "prime")
    assert plan.realization_key(1)[0] == "overlap"
    assert plan.realization_key(1) == plan.realization_key(3)
    assert plan.realization_key(1) != plan.realization_key(2)
    params = _params(4)
    g = jax.tree.map(jnp.zeros_like, params)
    p, s = params, opt.init(params)
    for t in range(8):
        p, s = plan.step_fn(t)(p, s, g)
    # prime + 2 realizations
    assert plan.num_compiled == 3
    plan.flush_step_fn(8)(p, s)
    assert plan.num_compiled == 4
    io = plan.overlap_io(0)
    assert io.prime
    with pytest.raises(ValueError, match="priming"):
        io.delayed(params, ())


def test_overlap_composition_is_validated():
    """chain()-time validation: overlapped gossip must be the chain's last
    applied transform (qg_dmsgd has no delayed formulation), one gossip
    per chain, known where-names, and no mixing of sync + overlap."""
    top = topology.one_peer_exponential(4)
    with pytest.raises(ValueError, match="AFTER the"):
        optim.qg_dmsgd(top, overlap=True)
    with pytest.raises(ValueError, match="no gossip payload"):
        optim.make_optimizer("parallel_msgd", top, overlap=True)
    with pytest.raises(ValueError, match="mixes overlapped and sync"):
        transforms.chain(
            transforms.trace_momentum(0.9),
            transforms.gossip(where=("m_next",), overlap=True),
            transforms.scale_by_lr("m"),
            transforms.gossip(where=("x_next",)),
            topology=top, name="bad")
    with pytest.raises(ValueError, match="neither"):
        transforms.chain(
            transforms.trace_momentum(0.9),
            transforms.scale_by_lr("m"),
            transforms.gossip(where=("qq",), overlap=True),
            topology=top, name="bad2")
    # time-varying dense realizations have no overlap pipeline
    with pytest.raises(ValueError, match="time-varying dense"):
        GossipPlan(topology.base_k(12, 2), overlap=True)


_HLO_OVERLAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.core import optim, topology
    from repro.core.plan import GossipPlan
    from repro.launch import sharding, steps as steps_mod
    from repro.launch.hlo_cost import analyze_hlo
    from repro.models import model as M

    nodes, fsdp = 4, 2
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(nodes, fsdp, 1),
                ("node", "fsdp", "model"))
    sh0 = NamedSharding(mesh, P())
    cfg = configs.reduced_config(configs.get_config("qwen3-0.6b"))
    params = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((nodes,) + x.shape, x.dtype), params)
    p_specs = sharding.param_specs(stacked, mesh, node_axis=True)
    p_shard = sharding.named(p_specs, mesh)
    stacked = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        stacked, p_shard)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (nodes, 1, 16), jnp.int32, sharding=NamedSharding(mesh, P("node")))}
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=sh0)

    top = topology.one_peer_exponential(nodes)
    opt = optim.dmsgd(top, beta=0.9, overlap=True)
    state0 = optim.OptState(
        momentum=stacked,
        count=jax.ShapeDtypeStruct((), jnp.int32, sharding=sh0))
    step_fn = steps_mod.make_train_step(cfg, opt)
    spec_fn = sharding.gossip_payload_spec_fn(mesh)
    plan = GossipPlan.for_optimizer(opt, fn=step_fn, mesh=mesh,
                                    specs=spec_fn)
    # every=2: step 1's in-flight realization is the one-peer Shifts
    # round, step 2's is Identity (zero communication) -- the no-gossip
    # BASELINE with an otherwise identical pipelined executable.
    plan = dataclasses.replace(plan, every=2)

    # the in-flight buffer's struct comes from abstractly evaluating the
    # priming step (shardings via gossip._buffer_specs on the full mesh)
    from repro.core import gossip as gossip_mod
    out = jax.eval_shape(plan.step_fn(0), stacked, state0, batch, lr)
    buf_structs = out[1].buf
    bspecs = gossip_mod._buffer_specs(mesh, "node", len(buf_structs))
    buf = tuple(jax.ShapeDtypeStruct(
        b.shape, b.dtype, sharding=NamedSharding(mesh, sp))
        for b, sp in zip(buf_structs, bspecs))
    state = optim.OptState(momentum=stacked,
                           count=jax.ShapeDtypeStruct((), jnp.int32,
                                                      sharding=sh0),
                           buf=buf)

    def counts(step, st):
        txt = plan.lowered(step, stacked, st, batch, lr) \\
                  .compile().as_text()
        return analyze_hlo(txt).collective_counts

    prime_c = counts(0, state0)      # priming: pack only, no mix
    gossip_c = counts(1, state)      # in flight: one-peer Shifts
    ident_c = counts(2, state)       # in flight: Identity (no comm)

    # the pipelined gossip step adds exactly ONE collective-permute (the
    # single fused f32 payload group) over the identical Identity
    # executable, and NOTHING else -- a reshard of the in-flight buffer
    # or payload would show up as extra collectives
    for kind in ("all-gather", "all-to-all", "all-reduce",
                 "reduce-scatter"):
        assert gossip_c.get(kind, 0) == ident_c.get(kind, 0), \\
            (kind, dict(gossip_c), dict(ident_c))
        assert prime_c.get(kind, 0) == ident_c.get(kind, 0), \\
            (kind, dict(prime_c), dict(ident_c))
    got = gossip_c.get("collective-permute", 0) \\
        - ident_c.get("collective-permute", 0)
    assert got == 1, (dict(gossip_c), dict(ident_c))
    assert prime_c.get("collective-permute", 0) == \\
        ident_c.get("collective-permute", 0), (dict(prime_c), dict(ident_c))
    print("HLO-OVERLAP-OK")
""")


@pytest.mark.slow
def test_hlo_pipelined_train_step_one_permute(tmp_path):
    """Acceptance: the FULL pipelined train step on a (node, fsdp) mesh
    keeps exactly one collective-permute per dtype group -- the in-flight
    payload's -- and adds zero reshard collectives vs the identical
    Identity-in-flight executable; the priming step communicates nothing."""
    script = tmp_path / "hlo_overlap.py"
    script.write_text(_HLO_OVERLAP_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "HLO-OVERLAP-OK" in r.stdout


def test_overlap_io_shard_native_roundtrip():
    """OverlapIO.pack / .delayed on a real 2-axis mesh inside one jit:
    the delayed combine of the packed payload equals the synchronous mix
    (single-process smoke; the 8-device variants live in the HLO script
    and test_shard_native)."""
    n = 4
    top = topology.one_peer_exponential(n)
    params = _params(n, d=8, seed=2)
    io = OverlapIO(top.realization(0))
    bufs = jax.jit(io.pack)(params)
    out = jax.jit(lambda b: io.delayed(params, b))(bufs)
    from repro.core import gossip
    _eq(out, gossip.mix_realization(params, top.realization(0)),
        "OverlapIO roundtrip == sync mix")
