import os
# These lines MUST run before any jax import: jax locks the backend and the
# device count on first initialization.  The dry-run only compiles, on 512
# host devices that model 2 pods x 256 chips, so it is pinned to the CPU and
# never takes an accelerator; the device-count flag is appended to any
# XLA_FLAGS already set.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512"]))

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import gossip as gossip_mod  # noqa: E402
from repro.core import optim as optim_mod  # noqa: E402
from repro.core import plan as plan_mod  # noqa: E402
from repro.core import topology as topo_mod  # noqa: E402
from repro.launch import hlo_cost, sharding, steps  # noqa: E402
from repro.launch.mesh import HW, make_production_mesh, to_logical_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination with production shardings; print memory_analysis() and
cost_analysis(); dump roofline terms to JSON.

No arrays are ever allocated: parameters, optimizer state, caches and
batches are jax.ShapeDtypeStruct stand-ins.
"""

ARCH_IDS = [
    "mamba2-1.3b", "granite-34b", "musicgen-large", "gemma2-27b",
    "llama-3.2-vision-90b", "zamba2-1.2b", "qwen3-0.6b",
    "granite-moe-3b-a800m", "deepseek-67b", "dbrx-132b",
]
SHAPE_IDS = list(steps.SHAPES)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, None: None}


def _struct_tree(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _stack_node_axis(tree, n):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), tree)


def _retype(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if jnp.issubdtype(x.dtype, jnp.floating)
            else x.dtype), tree)


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool,
                  topology: str = "one_peer_exp", optimizer: str = "dmsgd",
                  gossip_phase: int = 0, knobs: dict | None = None):
    """Lower one (arch, shape, mesh) combination. Returns (lowered, meta)."""
    knobs = dict(knobs or {})
    layout = configs.get_layout(arch)
    layout.update({k: v for k, v in knobs.items() if k in layout})
    cfg = configs.get_config(arch)
    cfg = steps.shape_cfg(cfg, shape_name)
    if layout.get("param_dtype"):
        cfg = dataclasses.replace(cfg,
                                  param_dtype=_DTYPES[layout["param_dtype"]])
    if knobs.get("remat") is not None:
        cfg = dataclasses.replace(cfg, remat=bool(knobs["remat"]))
    if knobs.get("broadcast_positions"):
        cfg = dataclasses.replace(cfg, broadcast_positions=True)
    if knobs.get("attention_impl"):
        cfg = dataclasses.replace(cfg,
                                  attention_impl=knobs["attention_impl"])
    if knobs.get("gqa_layout"):
        cfg = dataclasses.replace(cfg, gqa_layout=knobs["gqa_layout"])

    prod_mesh = make_production_mesh(multi_pod=multi_pod)
    nodes = layout["nodes"] * (2 if multi_pod else 1)
    fsdp = layout["fsdp"]
    model_axis = layout.get("model", 16)
    if nodes * fsdp * model_axis != prod_mesh.devices.size:
        # layout overrides may re-factorize only part of the mesh; scale
        # nodes to absorb the remainder (keeps global batch divisible)
        rem = prod_mesh.devices.size // (fsdp * model_axis)
        nodes = rem
    mesh = to_logical_mesh(prod_mesh, nodes, fsdp, model_axis)
    info = steps.SHAPES[shape_name]
    kind = info["kind"]

    params = _struct_tree(jax.eval_shape(partial(M.init, cfg),
                                         jax.random.key(0)))
    meta = dict(arch=arch, shape=shape_name, kind=kind,
                multi_pod=multi_pod, nodes=nodes, fsdp=fsdp,
                model_axis=sharding.axis_size(mesh, "model"),
                topology=topology, optimizer=optimizer, knobs=knobs,
                n_params=int(sum(x.size for x in jax.tree.leaves(params))))

    if kind == "train":
        top = topo_mod.get_topology(topology, nodes)
        # momentum dtype is threaded from the arch layout (dbrx-132b: bf16
        # momentum for the HBM fit) as an explicit optimizer argument.
        opt = optim_mod.make_optimizer(
            optimizer, top, beta=0.9,
            momentum_dtype=_DTYPES[layout.get("momentum_dtype")],
            compression=knobs.get("compression"))
        stacked = _stack_node_axis(params, nodes)
        p_specs = sharding.param_specs(stacked, mesh, node_axis=True,
                                       fsdp_params=knobs.get("fsdp_params",
                                                             True))
        mom = _retype(stacked, _DTYPES[layout.get("momentum_dtype")])
        state = optim_mod.OptState(momentum=mom,
                                   count=jax.ShapeDtypeStruct((), jnp.int32))
        state_specs = optim_mod.OptState(momentum=p_specs, count=P())
        batch = steps.input_specs(cfg, shape_name, nodes=nodes)
        bspec = {}
        for k, v in batch.items():
            inner = sharding.batch_spec(mesh, node_axis=True,
                                        batch_dim_size=v.shape[1])
            bspec[k] = P(*(inner + (None,) * (v.ndim - len(inner))))
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        grads_dtype = _DTYPES[layout.get("grads_dtype")] or jnp.float32
        step_fn = steps.make_train_step(cfg, opt,
                                        micro_batch=layout.get("micro"),
                                        grads_dtype=grads_dtype)
        # GossipPlan resolves the phase's realization into a mixing
        # executor running shard-natively over the full logical mesh (one
        # explicit-pairs permute per dtype group, payload specs reusing the
        # parameter placement rules so nothing is resharded); the plan also
        # owns the jit contract -- donation + in/out shardings -- so the
        # dry-run lowers via ``plan.lowered`` like every other path.
        spec_fn = sharding.gossip_payload_spec_fn(
            mesh, fsdp_params=knobs.get("fsdp_params", True))
        in_shardings = (p_specs, state_specs, bspec, P())
        out_shardings = (p_specs, state_specs, P())
        plan = plan_mod.GossipPlan.for_optimizer(
            opt, fn=step_fn, mesh=mesh, specs=spec_fn,
            donate_argnums=(0, 1),
            in_shardings=sharding.named(in_shardings, mesh),
            out_shardings=sharding.named(out_shardings, mesh))
        # roofline wire accounting straight off the realization IR: what
        # this phase's round SHOULD cost per node, before looking at HLO.
        ir = gossip_mod.gossip_spec(top, gossip_phase,
                                    compression=opt.compression)
        bytes_per_elem = 1 if opt.compression == "int8" else 4
        ir["payload_bytes_per_node"] = int(
            bytes_per_elem * meta["n_params"] * max(len(opt.gossip_where), 1)
            * ir["wire_multiplier"])
        # shard-native engine: each chip permutes only its node's LOCAL
        # shard -- the per-chip wire term the roofline compares against the
        # (per-partition) HLO collective bytes.
        inner_shards = fsdp * meta["model_axis"]
        ir["inner_shards"] = inner_shards
        ir["payload_bytes_per_shard"] = (
            ir["payload_bytes_per_node"] // inner_shards)
        meta["gossip_ir"] = ir
        with mesh:
            lowered = plan.lowered(gossip_phase, stacked, state, batch, lr)
        meta["compile_cache"] = plan.cache_stats()
        return lowered, meta

    # serving paths: single replica sharded over (fsdp, model); batch on node
    p_specs = sharding.param_specs(params, mesh, node_axis=False)
    batch = steps.input_specs(cfg, shape_name, nodes=1)
    gb = info["global_batch"]
    bspec = {}
    for k, v in batch.items():
        if v.ndim == 0:
            bspec[k] = P()
        else:
            inner = sharding.batch_spec(mesh, node_axis=False,
                                        batch_dim_size=v.shape[0])
            bspec[k] = P(*(inner + (None,) * (v.ndim - len(inner))))
    if kind == "prefill":
        fn = steps.make_prefill_step(cfg)
        jitted = jax.jit(fn,
                         in_shardings=sharding.named((p_specs, bspec), mesh),
                         out_shardings=None)
        with mesh:
            lowered = jitted.lower(params, batch)
        return lowered, meta

    cache = steps.cache_struct(cfg, shape_name)
    c_specs = sharding.cache_specs(cache, mesh, gb)
    fn = steps.make_serve_step(cfg)
    jitted = jax.jit(
        fn,
        in_shardings=sharding.named((p_specs, c_specs, bspec), mesh),
        out_shardings=(None, sharding.named(c_specs, mesh)),
        donate_argnums=(1,))
    with mesh:
        lowered = jitted.lower(params, cache, batch)
    return lowered, meta


def roofline_terms(cost: hlo_cost.HloCost, n_chips: int, meta: dict) -> dict:
    """Three roofline terms in seconds (per chip / per link).

    The HLO cost is per-partition already (SPMD module), so no division by
    chips: flops/hbm/collective bytes are what ONE chip executes.
    """
    t_compute = cost.flops / HW["peak_flops_bf16"]
    t_memory = cost.hbm_bytes / HW["hbm_bw"]
    t_coll = cost.total_collective_bytes / HW["ici_bw"]
    dom = max((t_compute, "compute"), (t_memory, "memory"),
              (t_coll, "collective"))
    return {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_coll,
        "dominant": dom[1],
        "n_chips": n_chips,
    }


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            out_dir: str | None = None, verbose: bool = True,
            **kw) -> dict:
    t0 = time.time()
    lowered, meta = build_lowered(arch, shape_name, multi_pod=multi_pod, **kw)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # newer jaxlib: one dict per program
        ca = ca[0] if ca else {}
    txt = compiled.as_text()
    model_axis = meta["model_axis"]
    cost = hlo_cost.analyze_hlo(txt, default_group=model_axis)
    n_chips = 512 if multi_pod else 256
    rec = dict(
        meta,
        ok=True,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory_analysis=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        ),
        xla_cost_analysis={k: ca.get(k) for k in ("flops", "bytes accessed")},
        hlo_cost=cost.to_dict(),
        roofline=roofline_terms(cost, n_chips, meta),
    )
    if verbose:
        print(f"== {arch} x {shape_name} x "
              f"{'2-pod(512)' if multi_pod else '1-pod(256)'} ==")
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops=%s bytes=%s" %
              (ca.get("flops"), ca.get("bytes accessed")))
        print("  hlo_cost: flops=%.3e hbm=%.3e coll=%.3e  %s" %
              (cost.flops, cost.hbm_bytes, cost.total_collective_bytes,
               dict(cost.collective_counts)))
        r = rec["roofline"]
        print("  roofline: compute=%.3fms memory=%.3fms collective=%.3fms"
              " dominant=%s" % (1e3 * r["compute_s"], 1e3 * r["memory_s"],
                                1e3 * r["collective_s"], r["dominant"]))
        print("  lower=%.1fs compile=%.1fs" % (t_lower, t_compile))
        if "compile_cache" in meta:
            print("  compile_cache:", meta["compile_cache"])
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = "2pod" if multi_pod else "1pod"
        extra = ""
        if kw.get("topology", "one_peer_exp") != "one_peer_exp":
            extra += f"_{kw['topology']}"
        if kw.get("optimizer", "dmsgd") != "dmsgd":
            extra += f"_{kw['optimizer']}"
        if kw.get("knobs"):
            extra += "_" + "-".join(f"{k}{v}" for k, v in
                                    sorted(kw["knobs"].items()))
        path = os.path.join(out_dir,
                            f"dryrun_{arch}_{shape_name}_{tag}{extra}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape id or 'all'")
    ap.add_argument("--mesh", default="1pod", choices=["1pod", "2pod", "both"])
    ap.add_argument("--topology", default="one_peer_exp")
    ap.add_argument("--optimizer", default="dmsgd")
    ap.add_argument("--gossip-phase", type=int, default=0)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--knob", action="append", default=[],
                    help="k=v hillclimb knobs (micro, fsdp_params, remat...)")
    args = ap.parse_args()

    knobs = {}
    for kv in args.knob:
        k, v = kv.split("=", 1)
        try:
            knobs[k] = json.loads(v)
        except json.JSONDecodeError:
            knobs[k] = v

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = SHAPE_IDS if args.shape == "all" else [args.shape]
    meshes = {"1pod": [False], "2pod": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        for shp in shapes:
            for mp in meshes:
                try:
                    run_one(arch, shp, multi_pod=mp, out_dir=args.out,
                            topology=args.topology, optimizer=args.optimizer,
                            gossip_phase=args.gossip_phase, knobs=knobs)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shp, mp, repr(e)))
                    print(f"!! FAILED {arch} x {shp} x mp={mp}: {e!r}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("ALL DRY-RUNS OK")


if __name__ == "__main__":
    main()
