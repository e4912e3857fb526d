"""Production meshes.

``make_production_mesh`` builds the physical v5e mesh exactly as specified:
one pod = (16, 16) chips with axes ("data", "model"); two pods =
(2, 16, 16) with axes ("pod", "data", "model").

``to_logical_mesh`` refines the same device array into the decentralized
layout ("node", "fsdp", "model"): the gossip graph lives on the ``node``
axis, each node's replica is sharded FSDP x TP inside.  For multi-pod meshes
the pod axis is absorbed into the node count (pod-major), so exponential-
graph hops cross the pod boundary.

Functions, not module-level constants: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "to_logical_mesh", "node_mesh", "HW"]

# TPU v5e hardware constants used by the roofline analysis (per chip).
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16e9,
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def to_logical_mesh(mesh: Mesh, nodes: int, fsdp: int,
                    model: int | None = None) -> Mesh:
    """Reshape a production mesh's devices into ("node", "fsdp", "model").

    Default keeps the physical model axis (16) as the logical model axis,
    with node*fsdp = data extent.  Passing ``model`` explicitly allows ANY
    factorization of the full device count (a §Perf lever: e.g. small models
    prefer model=1 with 16-way fsdp, or more gossip nodes) — device order is
    row-major over the physical (pod, data, model) axes so model groups stay
    on physically adjacent chips.

    Multi pod: the pod axis is folded node-major, so gossip shifts of
    +-2^t cross the pod boundary for large t.
    """
    devs = mesh.devices
    total = devs.size
    if model is None:
        model = devs.shape[-1]
    if nodes * fsdp * model != total:
        raise ValueError(
            f"nodes*fsdp*model ({nodes}*{fsdp}*{model}) != {total} devices")
    return Mesh(devs.reshape(nodes, fsdp, model), ("node", "fsdp", "model"))


def node_mesh(nodes: int) -> Mesh | None:
    """One decentralized node per visible device: a 1-axis ("node",) mesh.

    ``None`` when a single device is visible -- every node then lives on
    it, stacked along the leading axis -- or for a single node, which needs
    no mesh.  Otherwise the node count must equal the device count:
    anything else would either pile nodes onto one device or leave devices
    idle."""
    devs = jax.devices()
    if len(devs) == 1 or nodes == 1:
        return None
    if nodes != len(devs):
        raise ValueError(
            f"--nodes {nodes} != {len(devs)} visible devices: the trainer "
            f"lays one node per device")
    return Mesh(np.array(devs), ("node",))
