"""Flat-buffer gossip engine: layout/pack/unpack, bit-exact equivalence with
the historical per-leaf path, collective-count HLO inspection, and the
aperiodic-schedule regression (random_match must not freeze)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import flatbuf, gossip, topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(n, seed=0):
    k = jax.random.key(seed)
    return {
        "w": jax.random.normal(jax.random.fold_in(k, 0), (n, 8, 16)),
        "b": jax.random.normal(jax.random.fold_in(k, 1), (n, 4)),
        "h": jax.random.normal(jax.random.fold_in(k, 2),
                               (n, 3, 5)).astype(jnp.bfloat16),
        "nested": {"v": jax.random.normal(jax.random.fold_in(k, 3),
                                          (n, 2, 3, 2))},
    }


# --- layout / pack / unpack -------------------------------------------------

def test_pack_unpack_roundtrip():
    tree = _tree(8)
    layout, bufs = flatbuf.pack(tree)
    assert len(bufs) == 2  # f32 group + bf16 group
    for g, buf in zip(layout.groups, bufs):
        assert buf.shape == (8, g.padded)
        assert buf.dtype == g.dtype
        assert g.padded % flatbuf.PAD_MULTIPLE == 0
    out = flatbuf.unpack(layout, bufs)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_layout_cached_and_rejects_mismatched_node_axis():
    t1, t2 = _tree(8, 0), _tree(8, 1)
    assert flatbuf.layout_of(t1) is flatbuf.layout_of(t2)  # structure-keyed
    bad = {"a": jnp.zeros((4, 3)), "b": jnp.zeros((5, 3))}
    with pytest.raises(ValueError):
        flatbuf.layout_of(bad)


def test_pallas_tile_grid_padding():
    """Padded group width always reshapes into whole (8, 1024) kernel tiles,
    so ops.gossip_mix never re-pads the packed buffer."""
    from repro.kernels.gossip_mix import kernel as K
    for n in (2, 6, 8):
        layout = flatbuf.layout_of(_tree(n))
        for g in layout.groups:
            total = n * g.padded
            assert total % K.TILE_COLS == 0
            assert (total // K.TILE_COLS) % K.TILE_ROWS == 0


# --- flat path == per-leaf path, bit for bit --------------------------------

SCHED_TOPS = [("ring", {}), ("static_exp", {}), ("one_peer_exp", {}),
              ("one_peer_exp", {"schedule": "random_perm"}),
              ("one_peer_exp", {"schedule": "uniform"})]


@pytest.mark.parametrize("name,kw", SCHED_TOPS)
@pytest.mark.parametrize("compression", [None, "int8"])
def test_flat_mix_bit_identical_to_per_leaf(name, kw, compression, n=8):
    """pack -> roll -> fused combine -> unpack is BIT-identical to the
    historical one-roll-per-leaf path, for every neighbor-schedule topology
    and for the quantized payload (per-leaf scales preserved)."""
    top = topology.get_topology(name, n, **kw)
    assert top.realization_types() == frozenset({topology.Shifts})
    tree = _tree(n, seed=5)
    for step in range(5):
        r = top.realization(step)
        self_w, shifts = r.self_w, list(r.shifts)
        got = gossip.mix_shifts(tree, self_w, shifts, compression)
        want = gossip.mix_shifts_per_leaf(tree, self_w, shifts, compression)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


@settings(max_examples=20, deadline=None)
@given(
    name=st.sampled_from([t for t, _ in SCHED_TOPS]),
    n=st.sampled_from([4, 6, 8, 16]),
    step=st.integers(0, 9),
    seed=st.integers(0, 7),
)
def test_flat_mix_bit_identical_property(name, n, step, seed):
    top = topology.get_topology(name, n)
    r = top.realization(step)
    if not isinstance(r, topology.Shifts):
        return
    tree = _tree(n, seed=seed)
    self_w, shifts = r.self_w, list(r.shifts)
    got = gossip.mix_shifts(tree, self_w, shifts)
    want = gossip.mix_shifts_per_leaf(tree, self_w, shifts)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_mix_dense_matches_flat_for_dense_topologies():
    # The packed path contracts the same n products per element as the
    # per-leaf reference, but through a dot of another shape ((n, B) vs the
    # leaf's own), and XLA's CPU backend orders that reduction per shape: the
    # two agree to f32 rounding, not bit for bit (1 ulp on 7 of 32 elements
    # under jax 0.9.0).  A 1-ulp f32 difference can then round across a bf16
    # boundary when the result is cast back, hence the per-dtype tolerance.
    for name in ("star", "grid", "random_match", "full"):
        top = topology.get_topology(name, 8)
        tree = _tree(8, seed=3)
        W = jnp.asarray(top.weights(0))
        got = gossip.mix_dense(tree, W)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
            ref = jnp.einsum("ij,j...->i...", W.astype(jnp.float32),
                             b.astype(jnp.float32)).astype(b.dtype)
            tol = 1e-2 if b.dtype == jnp.bfloat16 else 1e-6
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(ref, np.float32),
                                       rtol=tol, atol=tol)


# --- gossip_spec packed accounting ------------------------------------------

def test_gossip_spec_packed_accounting():
    tree = _tree(8)
    layout = flatbuf.layout_of(tree)
    spec = gossip.gossip_spec(topology.one_peer_exponential(8), 0,
                              layout=layout)
    assert spec["dtype_groups"] == 2
    assert spec["collectives_per_step"] == 1 * 2   # 1 shift x 2 dtype groups
    f32b, bf16b = [g.padded * jnp.dtype(g.dtype).itemsize
                   for g in layout.groups]
    assert spec["bytes_per_node_per_step"] == f32b + bf16b
    # layout=None keeps the structural dict (consumed by == asserts)
    legacy = gossip.gossip_spec(topology.one_peer_exponential(8), 0)
    assert legacy == {"kind": "ppermute", "rounds": 1, "shifts": [-1],
                      "wire_multiplier": 1}
    # matchings report true 1-permute bytes; dense all-gathers O(n)
    match = gossip.gossip_spec(topology.bipartite_random_match(8), 0,
                               layout=layout)
    assert match["bytes_per_node_per_step"] == f32b + bf16b
    assert match["collectives_per_step"] == 2        # 1 permute x 2 groups
    dense = gossip.gossip_spec(topology.star(8), 0, layout=layout)
    assert dense["bytes_per_node_per_step"] == (f32b + bf16b) * 7


# --- HLO inspection: one collective-permute per shift per dtype group -------

_HLO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import gossip, optim, topology
    from repro.launch.hlo_cost import analyze_hlo

    n = 8
    mesh = Mesh(jax.devices()[:n], ("node",))
    sh = NamedSharding(mesh, P("node"))
    # 4 leaves, TWO dtype groups (f32 + bf16)
    tree = {"a": jax.ShapeDtypeStruct((n, 17), jnp.float32),
            "b": jax.ShapeDtypeStruct((n, 3, 5), jnp.float32),
            "c": jax.ShapeDtypeStruct((n, 2, 2), jnp.float32),
            "d": jax.ShapeDtypeStruct((n, 9), jnp.bfloat16)}
    shard = jax.tree.map(lambda _: sh, tree)
    for name in ("one_peer_exp", "static_exp"):
        top = topology.get_topology(name, n)
        shifts = top.realization(0).shifts
        f = jax.jit(lambda t: gossip.mix(t, top, 0),
                    in_shardings=(shard,), out_shardings=shard)
        txt = f.lower(tree).compile().as_text()
        got = analyze_hlo(txt).collective_counts.get("collective-permute", 0)
        want = len(shifts) * 2          # per shift per DTYPE GROUP, not leaf
        assert got == want, (name, got, want)

    # ANY matching (arbitrary pairing, not just circulants) is ONE
    # explicit-pairs collective-permute per dtype group -- and NO all-gather
    # of the packed buffer (the old dense route paid O(n) bytes here).
    for name in ("one_peer_hypercube", "random_match"):
        top = topology.get_topology(name, n)
        for step in (0, 1):
            f = jax.jit(lambda t, _s=step: gossip.mix(t, top, _s, mesh=mesh),
                        in_shardings=(shard,), out_shardings=shard)
            cost = analyze_hlo(f.lower(tree).compile().as_text())
            got = cost.collective_counts.get("collective-permute", 0)
            assert got == 2, (name, step, got)     # 1 per dtype group
            assert cost.collective_counts.get("all-gather", 0) == 0, name

    # full DmSGD update: the fused (beta m + g, x - gamma m) payload is one
    # f32 buffer => one-peer exponential costs EXACTLY ONE permute per step.
    top = topology.get_topology("one_peer_exp", n)
    opt = optim.dmsgd(top, beta=0.9)
    params = {"w": jax.ShapeDtypeStruct((n, 40, 3), jnp.float32),
              "b": jax.ShapeDtypeStruct((n, 7), jnp.float32)}
    pshard = jax.tree.map(lambda _: sh, params)
    state = optim.OptState(momentum=params,
                           count=jax.ShapeDtypeStruct((), jnp.int32))
    sshard = optim.OptState(momentum=pshard, count=NamedSharding(mesh, P()))
    f = jax.jit(lambda p, s, g: opt.update(p, s, g, 0, 0.1),
                in_shardings=(pshard, sshard, pshard),
                out_shardings=(pshard, sshard))
    txt = f.lower(params, state, params).compile().as_text()
    got = analyze_hlo(txt).collective_counts.get("collective-permute", 0)
    assert got == 1, got

    # the same guarantee through GossipPlan.lowered: shardings ride on the
    # ShapeDtypeStructs, the plan owns the jit.
    from repro.core.plan import GossipPlan
    sharded = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), params)
    sstate = optim.OptState(
        momentum=sharded,
        count=jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P())))
    plan = GossipPlan.for_optimizer(
        opt, fn=lambda mix, p, s, g: opt.update_with_mix(p, s, g, 0.1, mix))
    txt = plan.lowered(0, sharded, sstate, sharded).compile().as_text()
    got = analyze_hlo(txt).collective_counts.get("collective-permute", 0)
    assert got == 1, ("plan", got)

    # d_adamw gossips (mu, nu, x) as ONE f32 payload: still one permute.
    opt2 = optim.d_adamw(top)
    st2 = optim.OptState(momentum={"mu": sharded, "nu": sharded},
                         count=jax.ShapeDtypeStruct(
                             (), jnp.int32,
                             sharding=NamedSharding(mesh, P())))
    plan2 = GossipPlan.for_optimizer(
        opt2, fn=lambda mix, p, s, g: opt2.update_with_mix(p, s, g, 0.1, mix))
    txt = plan2.lowered(0, sharded, st2, sharded).compile().as_text()
    got = analyze_hlo(txt).collective_counts.get("collective-permute", 0)
    assert got == 1, ("d_adamw", got)
    print("HLO-OK")
""")


def test_hlo_one_permute_per_shift_per_dtype_group(tmp_path):
    """Needs its own process: XLA's host device count locks at first init."""
    script = tmp_path / "hlo_inspect.py"
    script.write_text(_HLO_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "HLO-OK" in r.stdout


# --- regression: aperiodic schedules must not freeze ------------------------

def test_random_match_consecutive_steps_use_different_matchings():
    """build_trainer used to fold period >= 64 down to a single compiled
    phase, replaying the step-0 matching forever."""
    from repro import configs
    from repro.launch.train import build_trainer
    from repro.models import model as M

    top = topology.bipartite_random_match(4, seed=0)
    # sanity: the schedule itself draws distinct matchings at steps 0/1
    assert not np.array_equal(top.weights(0), top.weights(1))

    cfg = configs.reduced_config(configs.get_config("qwen3-0.6b"))
    opt, step_for = build_trainer(cfg, top, "dmsgd", 0.9)
    params = M.init(cfg, jax.random.key(0))
    n = 4
    stacked = jax.tree.map(
        lambda p: jnp.broadcast_to(p, (n,) + p.shape)
        * (1.0 + 0.05 * jnp.arange(n, dtype=jnp.float32).reshape(
            (n,) + (1,) * p.ndim)).astype(p.dtype), params)
    state = opt.init(stacked)
    batch = {"tokens": jnp.zeros((n, 1, 8), jnp.int32)}
    p0, _, _ = step_for(0)(stacked, state, batch, 0.1)
    p1, _, _ = step_for(1)(stacked, state, batch, 0.1)
    diffs = [float(jnp.abs(a.astype(jnp.float32)
                           - b.astype(jnp.float32)).max())
             for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))]
    assert max(diffs) > 0.0


def test_mix_switch_rejects_aperiodic_schedules():
    top = topology.bipartite_random_match(8, seed=0)
    tree = {"x": jnp.zeros((8, 4))}
    with pytest.raises(ValueError, match="periodic"):
        gossip.mix_switch(tree, top, jnp.asarray(0))


def test_warmup_supersedes_dense_schedule():
    """Corollary-3 warm-up on a dense aperiodic topology (random_match):
    warm-up steps mix with exact global averaging -- NOT the realized
    pairwise matching -- and post-warm-up steps honor W^{(k)}.  The plan
    keys the two phases to separate executables."""
    from repro.core import optim
    from repro.core.plan import GossipPlan
    from repro.core.transforms import allreduce_warmup

    n, d = 8, 5
    top = topology.bipartite_random_match(n, seed=0)
    opt = allreduce_warmup(2)(optim.dmsgd(top, beta=0.0))
    assert opt.warmup_steps == 2
    plan = GossipPlan.for_optimizer(
        opt, fn=lambda mix, p, s, g: opt.update_with_mix(p, s, g, 0.1, mix))
    assert plan.realization_key(0) == ("warmup",)
    assert plan.realization_key(1) == ("warmup",)
    assert plan.realization_key(2) != plan.realization_key(0)

    rng = np.random.default_rng(0)
    params = {"x": jnp.asarray(rng.standard_normal((n, d)), jnp.float32)}
    state = opt.init(params)
    g = {"x": jnp.zeros((n, d), jnp.float32)}
    p1, s1 = plan.step_fn(0)(params, state, g)
    # warm-up step: exact consensus despite the (pairwise-matching) W^{(0)}
    np.testing.assert_allclose(
        np.asarray(p1["x"]), np.asarray(p1["x"]).mean(0, keepdims=True)
        .repeat(n, 0), rtol=1e-6, atol=1e-6)
    plan.step_fn(1)(params, state, g)     # same warm-up executable
    assert plan.num_compiled == 1
    plan.step_fn(2)(p1, s1, g)            # dense-traced executable
    assert plan.num_compiled == 2
    # after warm-up the realized W^{(k)} applies (lr=0 isolates the mix)
    plan0 = GossipPlan(top, fn=lambda mix, p, s, g: opt.update_with_mix(
        p, s, g, 0.0, mix))
    params2 = {"x": jnp.asarray(rng.standard_normal((n, d)), jnp.float32)}
    p2, _ = plan0.step_fn(2)(params2, opt.init(params2), g)
    W2 = jnp.asarray(top.weights(2), jnp.float32)
    want = gossip.mix_dense(params2, W2)
    np.testing.assert_allclose(np.asarray(p2["x"]), np.asarray(want["x"]),
                               rtol=1e-6, atol=1e-6)
