"""Shard-native gossip engine: multi-axis-mesh HLO assertions (no payload
reshard, one permute per dtype group down to the full train step),
multi-device ref-vs-Pallas parity for the shard_map-wrapped combine, the
int8 fixed-point invariant, the layout-cache LRU bound, and the int8 wire
accounting split."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flatbuf, gossip, topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- satellite (a): int8 fixed points keep their value EXACTLY --------------

def test_int8_fixed_points_keep_value_exactly():
    """mix_matching(compression='int8') used to blend a fixed point from
    its own QUANTIZED buffer, violating the documented 'fixed points keep
    their value exactly' invariant; they now blend from the full-precision
    local buffer."""
    partner = (1, 0, 2, 3)        # imperfect matching: nodes 2, 3 are fixed
    rng = np.random.default_rng(3)
    tree = {"w": jnp.asarray(rng.standard_normal((4, 9)) * 2.7, jnp.float32),
            "b": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32)}
    out = gossip.mix_matching(tree, partner, 0.5, compression="int8")
    for k in tree:
        # fixed points: bit-exact (quantization error would be ~|x|/127)
        np.testing.assert_array_equal(np.asarray(out[k][2:]),
                                      np.asarray(tree[k][2:]))
        # paired nodes really were quantized (error present but bounded)
        err = np.abs(np.asarray(out[k][:2])
                     - np.asarray(gossip.mix_matching(tree, partner, 0.5)[k][:2]))
        assert err.max() > 0.0
        step = float(jnp.max(jnp.abs(tree[k]))) / 127.0
        assert err.max() <= step * 0.51 + 1e-6


def test_matching_realization_int8_through_ir():
    """Same invariant through mix_realization (the GossipPlan route) --
    including w_self != 0.5, where the blend w_self*x + (1-w_self)*x is
    NOT exact in f32 and only the output mask preserves bit-exactness."""
    for w_self in (0.5, 0.3, 0.45):
        m = topology.Matching((2, 1, 0, 4, 3), w_self)   # node 1 fixed
        tree = {"x": jnp.asarray(
            np.random.default_rng(0).standard_normal((5, 7)), jnp.float32)}
        for comp in (None, "int8"):
            out = gossip.mix_realization(tree, m, compression=comp)
            np.testing.assert_array_equal(np.asarray(out["x"][1]),
                                          np.asarray(tree["x"][1]))


# --- satellite (b): int8 wire accounting (scales ride a second permute) -----

def test_gossip_spec_int8_splits_payload_and_scales():
    tree = {"w": jnp.zeros((8, 130), jnp.float32),
            "b": jnp.zeros((8, 6), jnp.float32),
            "h": jnp.zeros((8, 10), jnp.bfloat16)}
    layout = flatbuf.layout_of(tree)
    top = topology.one_peer_exponential(8)

    plain = gossip.gossip_spec(top, 0, layout=layout)
    assert plain["collectives_per_step"] == 1 * 2       # 1 shift x 2 groups
    assert plain["scale_bytes_per_node_per_step"] == 0
    assert plain["bytes_per_node_per_step"] == \
        plain["payload_bytes_per_node_per_step"]

    quant = gossip.gossip_spec(top, 0, layout=layout, compression="int8")
    # int8 rounds move TWO permutes per dtype group: payload + scale row
    assert quant["collectives_per_step"] == 1 * 2 * 2
    f32g = layout.group_for(jnp.float32)
    bf16g = layout.group_for(jnp.bfloat16)
    assert quant["payload_bytes_per_node_per_step"] == \
        f32g.padded + bf16g.padded                       # 1 byte / element
    # one f32 scale per leaf segment (+ padding segment) per group
    assert quant["scale_bytes_per_node_per_step"] == \
        4 * ((len(f32g.slots) + 1) + (len(bf16g.slots) + 1))
    assert quant["bytes_per_node_per_step"] == (
        quant["payload_bytes_per_node_per_step"]
        + quant["scale_bytes_per_node_per_step"])

    # static_exp: 3 shifts at n=8 -> 3x the collectives and bytes
    se = gossip.gossip_spec(topology.static_exponential(8), 0, layout=layout,
                            compression="int8")
    assert se["collectives_per_step"] == 3 * 2 * 2
    assert se["bytes_per_node_per_step"] == 3 * quant["bytes_per_node_per_step"]


# --- satellite (c): layout cache is LRU-bounded -----------------------------

def test_payload_spec_fn_degrades_on_partial_meshes():
    """gossip_payload_spec_fn works on meshes lacking some logical axes
    (never emitting the missing names) and build_trainer auto-wires it for
    any multi-axis node mesh -- a bare (node, fsdp) mesh must NOT fall back
    to replicated-inner-dim specs (that reintroduces the payload
    reshard)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.launch import sharding

    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("node", "fsdp"))
    spec_fn = sharding.gossip_payload_spec_fn(mesh)
    payload = ({"wq": jnp.zeros((1, 16, 8)), "scale": jnp.zeros((1, 6))},) * 2
    specs = spec_fn(payload)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all("model" not in str(s) for s in flat)
    assert any("fsdp" in str(s) for s in flat)
    with pytest.raises(ValueError, match="node"):
        sharding.gossip_payload_spec_fn(Mesh(dev, ("data", "fsdp")))


def test_layout_cache_lru_bounded():
    cap = flatbuf._LAYOUT_CACHE.max_entries
    assert cap is not None
    for i in range(cap + 50):
        flatbuf.layout_of({"x": jnp.zeros((2, 3 + i), jnp.float32)})
    assert len(flatbuf._LAYOUT_CACHE) <= cap
    # and caching still works (same structure -> same object)
    t = {"x": jnp.zeros((2, 5), jnp.float32)}
    assert flatbuf.layout_of(t) is flatbuf.layout_of(t)


def test_layout_pad_multiple_one_for_per_shard_pack():
    """The shard-native path packs local shards without tile padding
    (ops.gossip_mix pads per shard); the two granularities are cached as
    distinct layouts."""
    t = {"w": jnp.zeros((1, 37), jnp.float32), "b": jnp.zeros((1, 5))}
    tight = flatbuf.layout_of(t, pad_multiple=1)
    assert tight.groups[0].padded == tight.groups[0].size == 42
    padded = flatbuf.layout_of(t)
    assert padded.groups[0].padded == flatbuf.PAD_MULTIPLE
    assert tight is not padded
    layout, bufs = flatbuf.pack(t, tight)
    assert bufs[0].shape == (1, 42)
    out = flatbuf.unpack(layout, bufs)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- HLO: multi-axis mesh, no payload reshard, per-shard permutes -----------

_HLO_2AX_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import gossip, topology, flatbuf
    from repro.launch.hlo_cost import analyze_hlo

    nodes, fsdp = 4, 2
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(nodes, fsdp),
                ("node", "fsdp"))
    tree = {"w": jax.ShapeDtypeStruct((nodes, 16, 8), jnp.float32),
            "b": jax.ShapeDtypeStruct((nodes, 6), jnp.float32),
            "h": jax.ShapeDtypeStruct((nodes, 8, 4), jnp.bfloat16)}
    specs = {"w": P("node", "fsdp"), "b": P("node"), "h": P("node", "fsdp")}
    shard = {k: NamedSharding(mesh, specs[k]) for k in tree}
    top = topology.one_peer_exponential(nodes)
    r = top.realization(0)

    def counts(fn):
        f = jax.jit(fn, in_shardings=(shard,), out_shardings=shard)
        return analyze_hlo(f.lower(tree).compile().as_text())

    # one-peer step: exactly ONE collective-permute per dtype group, and
    # NO all-gather / all-to-all anywhere (= no GSPMD reshard of the
    # payload; a reshard would show up as extra collectives).
    cost = counts(lambda t: gossip.mix_shifts(
        t, r.self_w, list(r.shifts), mesh=mesh, specs=specs))
    c = cost.collective_counts
    assert c.get("collective-permute", 0) == 2, c     # f32 + bf16 group
    assert c.get("all-gather", 0) == 0, c
    assert c.get("all-to-all", 0) == 0, c
    assert c.get("all-reduce", 0) == 0, c

    # ... and the permute moves exactly the LOCAL shard's bytes (f32-only
    # payload: the CPU ref combine lets XLA hoist bf16->f32 converts
    # through the permute, which would muddy a mixed-dtype byte count)
    f32_tree = {k: tree[k] for k in ("w", "b")}
    f32_specs = {k: specs[k] for k in ("w", "b")}
    f32_shard = {k: shard[k] for k in ("w", "b")}
    f = jax.jit(lambda t: gossip.mix_shifts(
        t, r.self_w, list(r.shifts), mesh=mesh, specs=f32_specs),
        in_shardings=(f32_shard,), out_shardings=f32_shard)
    cost = analyze_hlo(f.lower(f32_tree).compile().as_text())
    local_f32 = (16 * 8) // fsdp + 6      # w sharded over fsdp, b replicated
    want_bytes = 4 * local_f32
    got_bytes = cost.collective_bytes.get("collective-permute", 0)
    assert got_bytes == want_bytes, (got_bytes, want_bytes)

    # matching realization on the same mesh: same guarantee
    m = topology.one_peer_hypercube(nodes).realization(0)
    cost = counts(lambda t: gossip.mix_matching(
        t, m.partner, m.w_self, mesh=mesh, specs=specs))
    c = cost.collective_counts
    assert c.get("collective-permute", 0) == 2, c
    assert c.get("all-gather", 0) == 0 and c.get("all-to-all", 0) == 0, c

    # int8: payload permute + scale-row permute per dtype group, matching
    # gossip_spec's accounting (dry-run rooflines == HLO)
    cost = counts(lambda t: gossip.mix_shifts(
        t, r.self_w, list(r.shifts), "int8", mesh=mesh, specs=specs))
    c = cost.collective_counts
    spec = gossip.gossip_spec(top, 0, layout=flatbuf.layout_of(
        jax.tree.map(jnp.zeros_like, tree)), compression="int8")
    assert c.get("collective-permute", 0) == spec["collectives_per_step"] \\
        == 4, (c, spec)
    assert c.get("all-gather", 0) == 0, c

    # Dense realizations route through shard_map too: grid's W has 4
    # nonzero circulant distance classes at n=4 ({1, 2, 3} after merging)
    # -> explicit-pairs permutes per dtype group and ZERO added reshards
    # (the old route einsum'd the packed buffer = an all-gather + the
    # payload reshard on this mesh)
    gridW = topology.grid_2d(nodes).realization(0)
    cost = counts(lambda t: gossip.mix_realization(
        t, gridW, mesh=mesh, specs=specs))
    c = cost.collective_counts
    assert c.get("all-gather", 0) == 0, c
    assert c.get("all-to-all", 0) == 0, c
    assert c.get("all-reduce", 0) == 0, c
    assert 0 < c.get("collective-permute", 0) <= 2 * (nodes - 1), c

    # exact averaging (uniform rows) collapses to ONE psum per group:
    # all-reduce only, no permutes, no gathers.  XLA's all-reduce combiner
    # may merge the two groups' psums into one tuple all-reduce, so count
    # the all-reduce OPERANDS: one per dtype group (f32 + bf16).
    fullW = topology.full_averaging(nodes).realization(0)
    f = jax.jit(lambda t: gossip.mix_realization(
        t, fullW, mesh=mesh, specs=specs),
        in_shardings=(shard,), out_shardings=shard)
    txt = f.lower(tree).compile().as_text()
    cost = analyze_hlo(txt)
    c = cost.collective_counts
    operands = sum(line.split(" all-reduce(", 1)[1].split(")", 1)[0]
                   .count("%") for line in txt.splitlines()
                   if " all-reduce(" in line)
    assert operands == 2, (operands, c)
    assert 1 <= c.get("all-reduce", 0) <= 2, c
    assert c.get("all-gather", 0) == 0, c
    assert c.get("collective-permute", 0) == 0, c
    print("HLO-2AX-OK")
""")


_HLO_2AX_TRAIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
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
    # the payload really is fsdp-sharded (not just node-sharded): at least
    # one spec must carry the fsdp axis for the assertion to mean anything
    assert any("fsdp" in str(s) for s in jax.tree.leaves(
        p_specs, is_leaf=lambda x: isinstance(x, P)))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (nodes, 1, 16), jnp.int32, sharding=NamedSharding(mesh, P("node")))}
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=sh0)

    top = topology.one_peer_exponential(nodes)
    opt = optim.dmsgd(top, beta=0.9)
    state = optim.OptState(
        momentum=stacked,
        count=jax.ShapeDtypeStruct((), jnp.int32, sharding=sh0))
    step_fn = steps_mod.make_train_step(cfg, opt)
    spec_fn = sharding.gossip_payload_spec_fn(mesh)
    # every=2: step 0 realizes the one-peer Shifts round, step 1 realizes
    # Identity (zero communication) -- the no-gossip BASELINE with an
    # otherwise identical executable.  The model forward itself contains
    # fsdp/TP collectives, so the payload assertion is DIFFERENTIAL: the
    # gossip round must add exactly one collective-permute (single fused
    # f32 payload) and NOTHING else -- any GSPMD reshard/all-gather of the
    # packed payload would show up as extra collectives at step 0.
    plan = GossipPlan.for_optimizer(opt, fn=step_fn, mesh=mesh,
                                    specs=spec_fn)
    plan = __import__("dataclasses").replace(plan, every=2)

    def counts(step):
        txt = plan.lowered(step, stacked, state, batch, lr) \\
                  .compile().as_text()
        return analyze_hlo(txt).collective_counts

    gossip_c = counts(0)
    base_c = counts(1)
    for kind in ("all-gather", "all-to-all", "all-reduce",
                 "reduce-scatter"):
        assert gossip_c.get(kind, 0) == base_c.get(kind, 0), \\
            (kind, dict(gossip_c), dict(base_c))
    got = gossip_c.get("collective-permute", 0) \\
        - base_c.get("collective-permute", 0)
    assert got == 1, (dict(gossip_c), dict(base_c))
    print("HLO-2AX-TRAIN-OK")
""")


_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import gossip, topology

    nodes, fsdp = 4, 2
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(nodes, fsdp),
                ("node", "fsdp"))
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.standard_normal((nodes, 16, 8)),
                             jnp.float32),
            "b": jnp.asarray(rng.standard_normal((nodes, 6)), jnp.float32),
            "h": jnp.asarray(rng.standard_normal((nodes, 8, 4)),
                             jnp.float32).astype(jnp.bfloat16)}
    specs = {"w": P("node", "fsdp"), "b": P("node"), "h": P("node", "fsdp")}
    shard = {k: NamedSharding(mesh, specs[k]) for k in tree}
    tree_s = {k: jax.device_put(v, shard[k]) for k, v in tree.items()}

    def eq(a, b):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))

    top = topology.one_peer_exponential(nodes)
    r = top.realization(0)
    m = topology.Matching((1, 0, 2, 3))     # fixed points on a 2-axis mesh

    def run_all():
        outs = [gossip.mix_shifts(tree_s, r.self_w, list(r.shifts),
                                  mesh=mesh, specs=specs),
                gossip.mix_matching(tree_s, m.partner, 0.5,
                                    mesh=mesh, specs=specs),
                gossip.mix_matching(tree_s, m.partner, 0.5, "int8",
                                    mesh=mesh, specs=specs)]
        return outs

    # the shard_map-wrapped Pallas combine (interpret mode: ref semantics
    # of the KERNEL, exercised on 8 devices) vs the jnp ref combine
    gossip.set_pallas_mode("interpret")
    kernel_outs = run_all()
    gossip.set_pallas_mode("off")
    ref_outs = run_all()
    gossip.set_pallas_mode("auto")
    for a, b in zip(kernel_outs, ref_outs):
        eq(a, b)

    # shard-native == single-process global path, bit for bit
    eq(kernel_outs[0], gossip.mix_shifts(tree, r.self_w, list(r.shifts)))
    eq(kernel_outs[1], gossip.mix_matching(tree, m.partner, 0.5))
    eq(kernel_outs[2], gossip.mix_matching(tree, m.partner, 0.5, "int8"))

    # dense shard-native (permute route + psum route) vs the global
    # einsum: allclose, not bit-equal -- the summation ORDER differs (and
    # a 1-ulp f32 difference can round across a bf16 boundary at commit)
    def close(a, b):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            tol = 1e-2 if x.dtype == jnp.bfloat16 else 1e-5
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32),
                                       rtol=tol, atol=tol * 1e-1)

    for topname in ("grid", "full"):
        W = topology.get_topology(topname, nodes).realization(0).dense(nodes)
        close(gossip.mix_dense(tree_s, W, mesh=mesh, specs=specs),
              gossip.mix_dense(tree, W))

    # the delayed (overlapped) halves: pack_payload -> delayed_mix on the
    # 2-axis mesh is bit-identical to the synchronous shard-native mix
    gossip.set_pallas_mode("off")
    for real in (r, m, topology.Identity(),
                 topology.Dense(topology.grid_2d(nodes).realization(0)
                                .dense(nodes))):
        bufs = gossip.pack_payload(tree_s, mesh=mesh, specs=specs)
        eq(gossip.delayed_mix(tree_s, bufs, real, mesh=mesh, specs=specs),
           gossip.mix_realization(tree_s, real, mesh=mesh, specs=specs))
    bufs = gossip.pack_payload(tree_s, mesh=mesh, specs=specs)
    eq(gossip.delayed_mix(tree_s, bufs, m, compression="int8", mesh=mesh,
                          specs=specs),
       gossip.mix_realization(tree_s, m, compression="int8", mesh=mesh,
                              specs=specs))
    gossip.set_pallas_mode("auto")
    # ... and fixed points survived int8 bit-exactly on the sharded path
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(kernel_outs[2][k][2:], np.float32),
            np.asarray(tree[k][2:], np.float32))
    print("PARITY-OK")
""")


def _run_script(tmp_path, name: str, body: str, marker: str):
    script = tmp_path / name
    script.write_text(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert marker in r.stdout


def test_hlo_two_axis_mix_no_reshard(tmp_path):
    """Acceptance: on a (node, fsdp) mesh the shard-native mix is exactly
    one collective-permute per dtype group moving per-shard bytes, with no
    all-gather/reshard of the payload; int8 doubles the permutes (payload +
    scales) exactly as gossip_spec accounts.  Own process: XLA's host
    device count locks at first init."""
    _run_script(tmp_path, "hlo_2ax.py", _HLO_2AX_SCRIPT, "HLO-2AX-OK")


@pytest.mark.slow
def test_hlo_two_axis_train_step_no_payload_reshard(tmp_path):
    """Acceptance: the FULL train step on a (node, fsdp) mesh adds exactly
    one collective-permute for the one-peer gossip round versus the
    identical no-gossip executable -- zero additional all-gathers,
    all-to-alls, all-reduces or reduce-scatters, i.e. GSPMD never reshards
    the packed payload."""
    _run_script(tmp_path, "hlo_2ax_train.py", _HLO_2AX_TRAIN_SCRIPT,
                "HLO-2AX-TRAIN-OK")


def test_multi_device_pallas_parity(tmp_path):
    """The shard_map-wrapped gossip_mix combine (Pallas kernel in interpret
    mode) is bit-identical to the jnp ref combine on 8 devices over a
    2-axis mesh, and both match the single-process global path."""
    _run_script(tmp_path, "parity.py", _PARITY_SCRIPT, "PARITY-OK")
