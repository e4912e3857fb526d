"""Partial averaging (gossip) over the node axis — shard-native fused engine.

State layout: every decentralized quantity (params, momentum, grads) is a
pytree whose leaves carry a **leading node axis** of size ``n``.  On the
production mesh that axis is sharded over the ``node`` mesh axis, so each
device block holds exactly its node's replica (itself sharded over
``fsdp``/``model``).

Every mixing path first packs the pytree into one contiguous ``(n, B)``
buffer per dtype (:mod:`repro.core.flatbuf`), so the collective cost is
independent of the leaf count.  One lowering per realization-IR node
(:mod:`repro.core.topology`):

* ``Shifts``   -> :func:`mix_shifts`: a weighted sum of circulant node-axis
  permutes -- one ``collective-permute`` per shift **per dtype group** (NOT
  per leaf): one-peer exponential = ONE collective-permute per iteration
  (the paper's Omega(1) claim), static exponential = ceil(log2 n) permutes.
* ``Matching`` -> :func:`mix_matching`: an arbitrary pairing is ONE
  explicit-pairs ``collective-permute`` per dtype group -- random matchings
  and the one-peer hypercube never fall to the dense all-gather route.
* ``Dense``    -> :func:`mix_dense`: shard-native with a mesh -- one
  ``psum`` for uniform-row ``W`` (exact averaging), else the self term +
  one explicit-pairs permute per nonzero circulant distance class, so the
  payload is never resharded; the no-mesh / traced-``W`` route is one
  ``einsum('ij,jb->ib')`` per dtype group (an all-gather: O(n) bytes).
* ``Identity`` -> no-op (skipped round, ``gossip(every=k)`` off-steps).

The **overlapped pipeline** splits every one of these into send/combine
halves: :func:`pack_payload` produces the wire buffers at the end of step
t (carried as optimizer state), :func:`delayed_mix` permutes + combines
them at the top of step t+1 -- with no data dependency on that step's
forward/backward, so XLA's scheduler hides the collective under the next
microbatch's compute (one-step-delayed mixing; see
:class:`repro.core.plan.OverlapIO`).

**Shard-native path** (pass ``mesh=`` whose node axis matches ``n``, plus
optional per-leaf ``specs=``): packing, the permutes, the int8 quantizer and
the weighted combine all run *inside* ``shard_map`` over the FULL mesh.
Each device packs only its local block of every leaf (``flatbuf`` with
``pad_multiple=1``), ``lax.ppermute`` over the node axis moves exactly the
local shard's bytes, and inner-dim (fsdp/model) shardings are never
disturbed -- no GSPMD reshard or all-gather of the payload appears anywhere
in the train step.  The fused ``gossip_mix`` Pallas kernel runs per device
shard on TPU meshes of ANY size (the old single-chip gate is gone); the
algebraically identical ``ref`` path serves other backends, and
:func:`set_pallas_mode` can force the kernel (interpret mode) or the ref
path for parity tests.  Without a mesh the historical global path packs the
full ``(n, B)`` buffer and relies on GSPMD to lower rolls to permutes --
correct everywhere, but on a multi-axis mesh it reshards the payload; the
shard-native path is the production route.

All paths preserve the global mean exactly (double stochasticity), which
the property tests assert; the flat path is bit-identical to the historical
per-leaf path (kept as ``mix_shifts_per_leaf`` for tests/benchmarks), the
shard-native path is bit-identical to the global path, and the matching
path is bit-identical to ``mix_dense`` of the realized W.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import flatbuf
from .topology import (
    AperiodicScheduleError,
    Dense,
    Gated,
    Identity,
    Matching,
    Shifts,
    Topology,
    _is_static_value,
)

PyTree = Any

__all__ = ["mix_dense", "mix_shifts", "mix_matching", "mix_realization",
           "mix", "mix_switch", "mix_scheduled", "gossip_spec",
           "mix_shifts_per_leaf", "pack_payload", "delayed_mix",
           "set_pallas_mode", "AperiodicScheduleError"]


# "auto": fused Pallas combine on TPU (per-shard inside shard_map on any
# mesh size; whole-buffer on a single chip), jnp ref elsewhere.
# "interpret": force the kernel in interpret mode (CPU parity tests).
# "off": force the ref combine everywhere.
_PALLAS_MODE = os.environ.get("REPRO_GOSSIP_PALLAS", "auto")


def set_pallas_mode(mode: str) -> None:
    """Select the combine backend: ``"auto"`` | ``"interpret"`` | ``"off"``."""
    global _PALLAS_MODE
    if mode not in ("auto", "interpret", "off"):
        raise ValueError(f"unknown pallas mode {mode!r}")
    _PALLAS_MODE = mode


def _use_pallas(local: bool) -> bool:
    # ``local=True`` means we are inside shard_map operating on one device's
    # shard: pallas_call is then a plain per-device custom call and needs no
    # GSPMD partitioning rule, so the kernel is safe on ANY mesh size.  The
    # only remaining auto-gate is the global (no-mesh) path on multi-device
    # jit, where XLA would replicate the node-sharded buffer around the
    # custom call.
    if _PALLAS_MODE == "off":
        return False
    if _PALLAS_MODE == "interpret":
        return True
    if jax.default_backend() != "tpu":
        return False
    return local or jax.device_count() == 1


# Device scopes of a round's three phases (HLO ``op_name`` metadata only,
# free at run time; the device trace names every op by them):
# ``gossip/pack`` packs into and unpacks from the flat wire buffers (and
# quantizes them), ``gossip/permute`` is the wire (ppermute, psum, or the
# global path's roll and gather), ``gossip/combine`` the weighted sum.
def _scope(phase: str):
    return jax.named_scope(f"gossip/{phase}")


def _pack(tree: PyTree, layout: flatbuf.FlatLayout | None = None):
    with _scope("pack"):
        return flatbuf.pack(tree, layout)


def _unpack(layout: flatbuf.FlatLayout, bufs) -> PyTree:
    with _scope("pack"):
        return flatbuf.unpack(layout, bufs)


def _ppermute(x, axis_name: str, pairs):
    with _scope("permute"):
        return jax.lax.ppermute(x, axis_name, perm=pairs)


def _combine(x, recvs, w_self: float, ws: tuple, local: bool = False):
    """out = w_self*x + sum_d ws[d]*recvs[d] over packed buffers."""
    with _scope("combine"):
        if _use_pallas(local):
            from repro.kernels.gossip_mix import ops as gm_ops
            interpret = True if _PALLAS_MODE == "interpret" else None
            return gm_ops.gossip_mix(x, recvs, w_self=float(w_self),
                                     ws=tuple(float(w) for w in ws),
                                     interpret=interpret)
        from repro.kernels.gossip_mix import ref as gm_ref
        return gm_ref.gossip_mix_ref(x, recvs, float(w_self), ws)


def mix_dense(tree: PyTree, W, *, mesh=None, axis_name: str = "node",
              specs=None) -> PyTree:
    """x_i <- sum_j W[i, j] x_j  over the leading node axis of every leaf.

    With a ``mesh`` whose node axis matches ``n`` (and a concrete, untraced
    ``W``), the round runs shard-natively inside ``shard_map`` -- the self
    term plus one explicit-pairs ``lax.ppermute`` per nonzero circulant
    distance class of ``W`` (a single ``psum`` when every row of ``W`` is
    identical, i.e. exact averaging) -- so static-exp/grid-style dense
    realizations no longer force GSPMD to reshard the payload on multi-axis
    meshes.  Without a mesh (or with a traced ``W``, the time-varying dense
    executable), one ``einsum('ij,jb->ib')`` per dtype group on the packed
    buffer: exact for any doubly-stochastic ``W`` but an all-gather over
    the node axis."""
    n = _node_count(tree)
    if (not isinstance(W, jax.core.Tracer)
            and np.asarray(W).shape[0] == n
            and _shard_native(mesh, axis_name, n)):
        Wnp = np.asarray(W, np.float64)
        spec_tree = _resolve_specs(tree, specs, axis_name)
        return jax.shard_map(
            lambda t: _local_dense(t, Wnp, axis_name), mesh=mesh,
            in_specs=(spec_tree,), out_specs=spec_tree,
            check_vma=False)(tree)
    layout, bufs = _pack(tree)
    Wl = jnp.asarray(W).astype(jnp.float32)
    with _scope("combine"):
        out = [jnp.einsum("ij,jb->ib", Wl,
                          b.astype(jnp.float32)).astype(b.dtype)
               for b in bufs]
    return _unpack(layout, out)


def _scale_columns(leaves, layout: flatbuf.FlatLayout, inner_axes: tuple = ()):
    """Per-(node, leaf) int8 scales, grouped to match the packed buffers.

    Returns one (n, L_g + 1) f32 matrix per group; the trailing column is
    the padding segment's scale (1.0, so padded zeros quantize to zero).
    Matches the historical per-leaf path bit-for-bit: scale_l = max|x_l| /
    127 along each node's slice.  Inside shard_map (``inner_axes`` = the
    mesh axes the inner dims are sharded over) each device reduces its
    local block and a ``pmax`` over the inner axes completes the exact
    per-leaf max -- one scalar per leaf on the wire, nothing else."""
    outs = []
    for g in layout.groups:
        cols = []
        for s in g.slots:
            x32 = leaves[s.leaf_index].astype(jnp.float32).reshape(
                layout.n, -1)
            m = jnp.max(jnp.abs(x32), axis=1)
            if inner_axes:
                m = jax.lax.pmax(m, inner_axes)
            cols.append(m / 127.0 + 1e-30)
        cols.append(jnp.ones((layout.n,), jnp.float32))
        outs.append(jnp.stack(cols, axis=1))
    return outs


def _leaf_scales(tree: PyTree, layout: flatbuf.FlatLayout):
    return _scale_columns(jax.tree.leaves(tree), layout)


# ---------------------------------------------------------------------------
# Shard-native engine
# ---------------------------------------------------------------------------

def _node_count(tree: PyTree) -> int:
    leaves = jax.tree.leaves(tree)
    return int(leaves[0].shape[0]) if leaves and leaves[0].ndim else 0


def _shard_native(mesh, axis_name: str, n: int) -> bool:
    return mesh is not None and dict(mesh.shape).get(axis_name) == n


def _resolve_specs(tree: PyTree, specs, axis_name: str):
    """Per-leaf PartitionSpecs for the shard_map boundary.

    ``specs`` may be a pytree of PartitionSpec matching ``tree``, a callable
    ``tree -> spec pytree`` (e.g. ``launch.sharding.gossip_payload_spec_fn``
    reapplying the parameter placement rules), or None -- node-sharded
    leading axis, replicated inner dims (the 1-axis-mesh default)."""
    from jax.sharding import PartitionSpec as P
    if specs is None:
        return jax.tree.map(
            lambda x: P(axis_name, *([None] * (x.ndim - 1))), tree)
    if callable(specs):
        return specs(tree)
    return specs


def _local_round(t: PyTree, *, rounds: list, self_w: float,
                 compression: str | None, fixed_arr, axis_name: str,
                 inner_axes: tuple) -> PyTree:
    """One Shifts/Matching gossip round on a device's LOCAL shard (runs
    inside ``shard_map``): pack the local block of every leaf
    (``pad_multiple=1`` -- per-shard tile padding happens inside
    ``ops.gossip_mix``), permute only those bytes over the node axis,
    combine, and unpack to the same local shapes.  ``fixed_arr`` is an
    optional (n,) bool mask of matching fixed points whose nodes must keep
    their value bit-exactly."""
    ws = tuple(w for _, w in rounds)
    layout = flatbuf.layout_of(t, pad_multiple=1)
    layout, bufs = _pack(t, layout)
    keep = (None if fixed_arr is None
            else fixed_arr[jax.lax.axis_index(axis_name)])
    out = []
    if compression == "int8":
        with _scope("pack"):
            scales = _scale_columns(jax.tree.leaves(t), layout, inner_axes)
        for g, buf, sc in zip(layout.groups, bufs, scales):
            seg = jnp.asarray(g.seg_ids)
            x32 = buf.astype(jnp.float32)
            with _scope("pack"):
                q = jnp.round(x32 / sc[:, seg]).astype(jnp.int8)
            with _scope("combine"):
                acc = (self_w * x32) if self_w else None
            for pairs, w in rounds:
                rq = _ppermute(q, axis_name, pairs)
                rs = _ppermute(sc, axis_name, pairs)
                with _scope("combine"):
                    r = w * (rq.astype(jnp.float32) * rs[:, seg])
                    acc = r if acc is None else acc + r
            with _scope("combine"):
                if keep is not None:
                    # fixed points keep their FULL-PRECISION buffer (never
                    # the quantized image, and never the w_self*x +
                    # w_peer*x blend, which is only exact for w_self=0.5)
                    acc = jnp.where(keep, x32, acc)
                out.append(acc.astype(buf.dtype))
    else:
        for buf in bufs:
            recvs = [_ppermute(buf, axis_name, pairs) for pairs, _ in rounds]
            o = _combine(buf, recvs, self_w, ws, local=True)
            if keep is not None:
                with _scope("combine"):
                    o = jnp.where(keep, buf, o)
            out.append(o)
    return _unpack(layout, out)


def _local_dense(t: PyTree, W: np.ndarray, axis_name: str) -> PyTree:
    """One dense round on a device's LOCAL shard (inside ``shard_map``).

    Uniform-row ``W`` (exact averaging, the all-reduce warm-up) is ONE
    ``psum`` over the node axis; any other ``W`` is the self term plus one
    explicit-pairs permute per nonzero circulant distance class ``s``
    (``W[i, (i-s) % n] != 0`` for some ``i``), each receive weighted by
    the receiving node's own matrix entry.  Same wire bytes as the
    all-gather in the worst case, but inner-dim shardings are untouched:
    no GSPMD reshard of the payload on multi-axis meshes."""
    n = W.shape[0]
    layout = flatbuf.layout_of(t, pad_multiple=1)
    layout, bufs = _pack(t, layout)
    i = jax.lax.axis_index(axis_name)
    out = []
    if np.allclose(W, W[0:1, :]):
        row = jnp.asarray(W[0], jnp.float32)
        for buf in bufs:
            with _scope("permute"):
                o = jax.lax.psum(row[i] * buf.astype(jnp.float32), axis_name)
            out.append(o.astype(buf.dtype))
        return _unpack(layout, out)
    diag = jnp.asarray(np.ascontiguousarray(np.diagonal(W)), jnp.float32)
    shifts = []
    for s in range(1, n):
        col = np.array([W[j, (j - s) % n] for j in range(n)])
        if np.any(col):
            shifts.append((s, jnp.asarray(col, jnp.float32)))
    for buf in bufs:
        with _scope("combine"):
            acc = diag[i] * buf.astype(jnp.float32)
        for s, col in shifts:
            recv = _ppermute(buf, axis_name, _shift_pairs(n, s))
            with _scope("combine"):
                acc = acc + col[i] * recv.astype(jnp.float32)
        with _scope("combine"):
            out.append(acc.astype(buf.dtype))
    return _unpack(layout, out)


def _mix_sharded(tree: PyTree, *, mesh, specs, axis_name: str, rounds: list,
                 self_w: float, compression: str | None,
                 fixed=None) -> PyTree:
    """One gossip round entirely inside ``shard_map`` over the full mesh.

    ``rounds`` is ``[(ppermute send pairs, weight), ...]``; the per-shard
    body is :func:`_local_round` -- the payload is never resharded and
    inner-dim (fsdp/model) shardings pass through untouched."""
    spec_tree = _resolve_specs(tree, specs, axis_name)
    inner_axes = tuple(a for a in mesh.axis_names if a != axis_name)
    fixed_arr = None if fixed is None else jnp.asarray(fixed)

    def local_fn(t):
        return _local_round(t, rounds=rounds, self_w=self_w,
                            compression=compression, fixed_arr=fixed_arr,
                            axis_name=axis_name, inner_axes=inner_axes)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec_tree,),
                         out_specs=spec_tree, check_vma=False)(tree)


def _shift_pairs(n: int, shift: int) -> list:
    """Send pairs for a circulant +shift: node i sends to (i + s) mod n,
    i.e. receives from (i - s) mod n == jnp.roll(x, s, axis=0) semantics."""
    return [(i, (i + shift) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# Runtime-valued rounds: traced weights, metadata piggyback, node gating
# ---------------------------------------------------------------------------
#
# A round is RUNTIME-valued when any of its weights is a traced jax value,
# or when it carries per-node metadata (``meta=``), loss-aware edge weights
# (``edge_weight=``) or a straggler gate (``node_gate=``).  The wire
# structure stays exactly the static path's -- the same permutes are always
# issued (a gated-off edge still moves its bytes; no collective ever sits
# inside a ``lax.cond``) -- but the combine runs in plain jnp f32 (the
# Pallas kernel wants static float weights) with weights that are traced
# operands.  Metadata rides as EXTRA COLUMNS concatenated onto the f32
# dtype group's packed buffer before its permute: the receiver learns the
# sender's (loss, grad-norm, deadline) row through the collective it was
# already paying for -- zero additional collectives, ``4 * meta_cols``
# extra bytes per payload copy (counted by :func:`gossip_spec`).
#
# Weight semantics: ``edge_weight(own_meta, recv_meta, base_w) -> w`` gives
# the RECEIVING node's weight for that edge (elementwise over nodes, so the
# same callable serves the global (n, .) and per-shard (1, .) layouts).
# Under gating or edge_weight the self weight is always derived as
# ``1 - sum_d w_d`` per node, so every realized row stays stochastic (the
# mass of a dropped edge returns to self).  Directed Shifts rounds are then
# row- but not column-stochastic -- exact mean preservation holds for
# symmetric Matchings (both endpoints drop the pair or neither does) and
# for symmetric weight choices, measured rather than assumed elsewhere.

def _assemble_meta(meta, node_gate):
    """Stack user metadata and the alive flag into one (n, M) f32 matrix.

    Returns ``(meta_mat | None, n_user_cols, has_gate)``; the gate flag is
    always the LAST column so both ends of an edge can read it after the
    permute."""
    cols = []
    n_user = 0
    if meta is not None:
        m = jnp.asarray(meta, jnp.float32)
        if m.ndim == 1:
            m = m[:, None]
        n_user = m.shape[1]
        cols.append(m)
    if node_gate is not None:
        g = jnp.asarray(node_gate)
        cols.append(g.astype(jnp.float32)[:, None])
    if not cols:
        return None, 0, False
    return jnp.concatenate(cols, axis=1), n_user, node_gate is not None


def _f32_group_index(layout: flatbuf.FlatLayout) -> int:
    """The dtype group the metadata columns ride on (f32 if present)."""
    for i, g in enumerate(layout.groups):
        if jnp.dtype(g.dtype) == jnp.dtype(jnp.float32):
            return i
    return 0


def _wcol(w):
    """Broadcast a per-node weight against an (n, B) buffer."""
    w = jnp.asarray(w, jnp.float32)
    return w[:, None] if w.ndim == 1 else w


def _runtime_combine(bufs: list, layout: flatbuf.FlatLayout, permute,
                     base_ws: list, self_w, meta_mat, n_user: int,
                     has_gate: bool, edge_weight, keep) -> list:
    """Weighted combine with traced weights / piggybacked metadata.

    ``permute(arr, d)`` returns edge ``d``'s received array (roll, take, or
    ppermute -- the caller picks the wire primitive, so this one body
    serves the global and the shard-native paths).  ``keep`` is an optional
    broadcastable mask of rows that keep their value bit-exactly (matching
    fixed points)."""
    D = len(base_ws)
    gi = _f32_group_index(layout)
    recvs: list = [[None] * D for _ in bufs]
    recv_meta: list = [None] * D
    for d in range(D):
        for j, buf in enumerate(bufs):
            if j == gi and meta_mat is not None:
                with _scope("pack"):
                    aug = jnp.concatenate(
                        [buf, meta_mat.astype(buf.dtype)], axis=1)
                with _scope("permute"):
                    r = permute(aug, d)
                recvs[j][d] = r[:, :buf.shape[1]]
                recv_meta[d] = r[:, buf.shape[1]:].astype(jnp.float32)
            else:
                with _scope("permute"):
                    recvs[j][d] = permute(buf, d)
    with _scope("combine"):
        own_user = meta_mat[:, :n_user] if n_user else None
        own_alive = meta_mat[:, -1] > 0.5 if has_gate else None
        eff = []
        for d in range(D):
            w = base_ws[d]
            if edge_weight is not None:
                w = edge_weight(own_user, recv_meta[d][:, :n_user]
                                if n_user else None, w)
            w = jnp.asarray(w, jnp.float32)
            if has_gate:
                both = jnp.logical_and(own_alive, recv_meta[d][:, -1] > 0.5)
                w = jnp.where(both, w, jnp.zeros_like(w))
            eff.append(w)
        if self_w is None or has_gate or edge_weight is not None:
            # dropped-edge mass returns to self: rows stay stochastic
            self_col = 1.0 - sum(_wcol(w) for w in eff)
        else:
            self_col = _wcol(self_w)
        outs = []
        for j, buf in enumerate(bufs):
            x32 = buf.astype(jnp.float32)
            acc = self_col * x32
            for d in range(D):
                acc = acc + _wcol(eff[d]) * recvs[j][d].astype(jnp.float32)
            if keep is not None:
                acc = jnp.where(keep, x32, acc)
            outs.append(acc.astype(buf.dtype))
        return outs


def _runtime_operands(n: int, self_w, base_ws: list, meta_mat):
    """Normalize runtime values to per-node arrays so ONE pytree (with one
    spec tree) carries them across the shard_map boundary."""
    def pernode(w):
        if w is None:
            return None
        w = jnp.asarray(w, jnp.float32)
        return jnp.broadcast_to(w, (n,)) if w.ndim == 0 else w
    return {"self": pernode(self_w), "ws": tuple(pernode(w) for w in base_ws),
            "meta": meta_mat}


def _runtime_mix(tree: PyTree, *, rounds: list, base_ws: list, self_w,
                 meta, node_gate, edge_weight, fixed_mask, mesh, axis_name,
                 specs) -> PyTree:
    """Runtime-valued Shifts/Matching round: global or shard-native.

    ``rounds[d]`` is edge ``d``'s ppermute send-pairs (the global path
    derives its gather index from them); ``base_ws[d]`` its base weight
    (float, traced scalar, or per-node array; ``edge_weight`` may override).
    """
    n = _node_count(tree)
    meta_mat, n_user, has_gate = _assemble_meta(meta, node_gate)

    if _shard_native(mesh, axis_name, n):
        from jax.sharding import PartitionSpec as P

        spec_tree = _resolve_specs(tree, specs, axis_name)
        rt = _runtime_operands(n, self_w, base_ws, meta_mat)
        rt_specs = jax.tree.map(lambda x: P(axis_name), rt)
        fixed_arr = None if fixed_mask is None else jnp.asarray(fixed_mask)

        def local_fn(t, rt):
            layout = flatbuf.layout_of(t, pad_multiple=1)
            layout, bufs = _pack(t, layout)
            keep = (None if fixed_arr is None
                    else fixed_arr[jax.lax.axis_index(axis_name)])
            outs = _runtime_combine(
                bufs, layout,
                lambda arr, d: jax.lax.ppermute(arr, axis_name,
                                                perm=rounds[d]),
                list(rt["ws"]), rt["self"], rt["meta"], n_user, has_gate,
                edge_weight, keep)
            return _unpack(layout, outs)

        return jax.shard_map(local_fn, mesh=mesh,
                             in_specs=(spec_tree, rt_specs),
                             out_specs=spec_tree, check_vma=False)(tree, rt)

    layout, bufs = _pack(tree)
    # receive index: node i receives from the node that SENDS to i
    idxs = []
    for pairs in rounds:
        src = [0] * n
        for s, dst in pairs:
            src[dst] = s
        idxs.append(jnp.asarray(src))
    keep = (None if fixed_mask is None
            else jnp.asarray(fixed_mask)[:, None])
    outs = _runtime_combine(
        bufs, layout, lambda arr, d: jnp.take(arr, idxs[d], axis=0),
        base_ws, self_w, meta_mat, n_user, has_gate, edge_weight, keep)
    return _unpack(layout, outs)


# ---------------------------------------------------------------------------
# Overlapped (delayed-mix) pipeline: send / combine halves
# ---------------------------------------------------------------------------
#
# The synchronous paths above pack, permute and combine in one call.  The
# overlapped pipeline splits that: :func:`pack_payload` produces the wire
# buffers at the END of step t (the payload rides in the optimizer state),
# and :func:`delayed_mix` at the TOP of step t+1 issues the permutes on
# those buffers and applies the weighted combine -- the permutes have no
# data dependency on step t+1's forward/backward, so XLA's scheduler can
# run them concurrently with the next microbatch's compute.

def _buffer_specs(mesh, axis_name: str, n_groups: int) -> tuple:
    """PartitionSpecs for the in-flight packed buffers: node-sharded rows,
    flat columns sharded over EVERY inner mesh axis (each device's local
    block is its per-shard pack, so the assembled global buffer is just the
    concatenation -- only ever consumed by the matching ``shard_map``)."""
    from jax.sharding import PartitionSpec as P
    inner = tuple(a for a in mesh.axis_names if a != axis_name)
    spec = P(axis_name, inner) if inner else P(axis_name)
    return tuple(spec for _ in range(n_groups))


def _local_template(template: PyTree, spec_tree: PyTree, mesh,
                    axis_name: str) -> PyTree:
    """ShapeDtypeStructs of each leaf's per-device block under
    ``spec_tree`` (static -- used to recover the per-shard flat layout
    when only the packed buffers cross the ``shard_map`` boundary)."""
    sizes = dict(mesh.shape)

    def one(x, spec):
        shape = list(x.shape)
        for d, ax in enumerate(tuple(spec)):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shape[d] //= sizes.get(a, 1)
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype)

    return jax.tree.map(one, template, spec_tree)


def pack_payload(tree: PyTree, *, mesh=None, axis_name: str = "node",
                 specs=None) -> tuple:
    """SEND half of the overlapped pipeline: pack ``tree`` into its wire
    buffers (one ``(n, B)`` buffer per dtype group) WITHOUT mixing.

    Shard-native (mesh whose node axis matches ``n``): each device packs
    only its local block (``pad_multiple=1``) inside ``shard_map``, so the
    buffer is born with the payload's shardings and the next step's
    :func:`delayed_mix` permutes it without any reshard.  Without a mesh,
    the global tile-padded pack of :mod:`repro.core.flatbuf` -- in both
    cases the SAME granularity the synchronous mix of that path uses, so
    delayed mixing is bit-identical to it."""
    n = _node_count(tree)
    if not _shard_native(mesh, axis_name, n):
        _, bufs = _pack(tree)
        return tuple(bufs)
    spec_tree = _resolve_specs(tree, specs, axis_name)
    ltpl = _local_template(tree, spec_tree, mesh, axis_name)
    n_groups = len(flatbuf.layout_of(ltpl, pad_multiple=1).groups)

    def local_fn(t):
        layout = flatbuf.layout_of(t, pad_multiple=1)
        _, bufs = _pack(t, layout)
        return tuple(bufs)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec_tree,),
                         out_specs=_buffer_specs(mesh, axis_name, n_groups),
                         check_vma=False)(tree)


def delayed_mix(template: PyTree, bufs, realization, *,
                compression: str | None = None, mesh=None,
                axis_name: str = "node", specs=None) -> PyTree:
    """COMBINE half of the overlapped pipeline: apply ``realization`` to
    the in-flight packed buffers and unpack to ``template``'s structure.

    ``template`` is a pytree of arrays or ``ShapeDtypeStruct``s with the
    payload's global shapes/dtypes (it is never read, only its structure);
    ``bufs`` must come from :func:`pack_payload` with the same mesh/specs.
    The permutes depend only on ``bufs`` -- never on anything computed in
    the current step -- which is the whole point: XLA schedules them under
    the step's forward/backward.  Every realization kind is supported
    (``Identity`` just unpacks; ``Dense`` runs the shard-native dense round
    when a mesh is given), and each path is bit-identical to packing +
    synchronously mixing the same payload."""
    bufs = tuple(bufs)
    leaves = jax.tree.leaves(template)
    n = int(leaves[0].shape[0])
    if not _shard_native(mesh, axis_name, n):
        layout = flatbuf.layout_of(template)
        return mix_realization(_unpack(layout, bufs), realization,
                               compression=compression)
    spec_tree = _resolve_specs(template, specs, axis_name)
    ltpl = _local_template(template, spec_tree, mesh, axis_name)
    local_layout = flatbuf.layout_of(ltpl, pad_multiple=1)
    inner_axes = tuple(a for a in mesh.axis_names if a != axis_name)

    if isinstance(realization, Identity):
        def local_fn(bs):
            return _unpack(local_layout, list(bs))
    elif isinstance(realization, Dense):
        if compression is not None:
            raise ValueError(
                f"compression={compression!r} has no dense-matrix wire "
                f"format; only Shifts/Matching realizations quantize")
        Wnp = np.asarray(realization.W, np.float64)

        def local_fn(bs):
            return _local_dense(_unpack(local_layout, list(bs)),
                                Wnp, axis_name)
    elif isinstance(realization, (Shifts, Matching)):
        if isinstance(realization, Shifts):
            rounds = [(_shift_pairs(n, s), w) for s, w in realization.shifts]
            self_w, fixed_arr = realization.self_w, None
        else:
            pairs = [(src, dst) for dst, src in enumerate(realization.partner)]
            rounds = [(pairs, 1.0 - realization.w_self)]
            self_w = realization.w_self
            fixed = np.fromiter(
                (j == i for i, j in enumerate(realization.partner)),
                dtype=bool, count=n)
            fixed_arr = jnp.asarray(fixed) if fixed.any() else None

        def local_fn(bs):
            t = _unpack(local_layout, list(bs))
            return _local_round(t, rounds=rounds, self_w=self_w,
                                compression=compression,
                                fixed_arr=fixed_arr, axis_name=axis_name,
                                inner_axes=inner_axes)
    else:
        raise TypeError(f"not a realization IR node: {realization!r}")

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(_buffer_specs(mesh, axis_name, len(local_layout.groups)),),
        out_specs=spec_tree, check_vma=False)(bufs)


def _is_runtime_round(self_w, ws, meta, edge_weight, node_gate) -> bool:
    """True when the round needs the traced-weight combine path (any traced
    weight, derived self weight, metadata, loss-aware weights, or gating).
    A plain static round MUST return False so it takes the byte-identical
    legacy path."""
    return (meta is not None or edge_weight is not None
            or node_gate is not None
            or not _is_static_value(self_w)
            or any(not _is_static_value(w) for w in ws))


def mix_shifts(tree: PyTree, self_weight: float,
               shifts: list[tuple[int, float]],
               compression: str | None = None, *, mesh=None,
               axis_name: str = "node", specs=None, meta=None,
               edge_weight=None, node_gate=None) -> PyTree:
    """x_i <- self_weight * x_i + sum_d w_d * x_{(i - s_d) mod n}.

    Each (s_d, w_d) descriptor means node i *sends* its buffer to node
    (i + s_d) mod n.

    With a ``mesh`` whose ``axis_name`` axis has one node per device block,
    the whole round runs shard-natively (see :func:`_mix_sharded`): ONE
    explicit-pairs ``lax.ppermute`` per shift per dtype group moving only
    each device's local shard bytes.  Without a mesh, the global path packs
    the full ``(n, B)`` buffer and rolls it (GSPMD lowers each static roll
    on a node-sharded axis to one collective-permute).

    compression='int8': QSGD-style quantized payload (beyond-paper, cf. the
    paper's related work [2, 24, 26]): the SENT buffer is symmetric-int8
    quantized with a per-(node, leaf-segment) scale (identical to the
    historical per-leaf quantizer), so each shift moves 1 byte/element plus
    one f32 scale per leaf (the scale row rides a second, tiny permute per
    dtype group); the local term stays full precision.  Biased (~0.4% of
    per-leaf max); exact-averaging of Lemma 1 becomes approximate --
    measured in tests.

    Runtime-valued rounds (traced weights, ``meta=``/``edge_weight=``/
    ``node_gate=``) take the traced combine path (see the runtime section
    above); the wire structure is unchanged, ``compression`` is refused.
    """
    n = _node_count(tree)
    ws_list = [w for _, w in shifts]
    if _is_runtime_round(self_weight, ws_list, meta, edge_weight, node_gate):
        if compression is not None:
            raise ValueError(
                "compression is not supported on runtime-valued rounds "
                "(traced weights / metadata / gating); drop compression= "
                "or use static weights")
        return _runtime_mix(
            tree, rounds=[_shift_pairs(n, s) for s, _ in shifts],
            base_ws=ws_list, self_w=self_weight, meta=meta,
            node_gate=node_gate, edge_weight=edge_weight, fixed_mask=None,
            mesh=mesh, axis_name=axis_name, specs=specs)
    if _shard_native(mesh, axis_name, n):
        rounds = [(_shift_pairs(n, s), w) for s, w in shifts]
        return _mix_sharded(tree, mesh=mesh, specs=specs,
                            axis_name=axis_name, rounds=rounds,
                            self_w=self_weight, compression=compression)

    layout, bufs = _pack(tree)
    ws = tuple(w for _, w in shifts)

    if compression == "int8":
        with _scope("pack"):
            scales = _leaf_scales(tree, layout)
        out = []
        for g, buf, sc in zip(layout.groups, bufs, scales):
            seg = jnp.asarray(g.seg_ids)
            x32 = buf.astype(jnp.float32)
            with _scope("pack"):
                q = jnp.round(x32 / sc[:, seg]).astype(jnp.int8)
            with _scope("combine"):
                acc = (self_weight * x32) if self_weight else None
            for s, w in shifts:
                with _scope("permute"):
                    rq = jnp.roll(q, s, axis=0)    # int8 over the wire
                    rs = jnp.roll(sc, s, axis=0)   # tiny per-leaf scales
                with _scope("combine"):
                    r = w * (rq.astype(jnp.float32) * rs[:, seg])
                    acc = r if acc is None else acc + r
            with _scope("combine"):
                out.append(acc.astype(buf.dtype))
        return _unpack(layout, out)

    out = []
    for buf in bufs:
        with _scope("permute"):
            recvs = [jnp.roll(buf, s, axis=0) for s, _ in shifts]
        out.append(_combine(buf, recvs, self_weight, ws))
    return _unpack(layout, out)


def mix_matching(tree: PyTree, partner: tuple, w_self: float = 0.5,
                 compression: str | None = None, mesh=None,
                 axis_name: str = "node", specs=None, meta=None,
                 edge_weight=None, node_gate=None) -> PyTree:
    """Pairwise gossip: x_i <- w_self * x_i + (1 - w_self) * x_{partner[i]}.

    ``partner`` is an involution; fixed points keep their value EXACTLY
    (bit-for-bit, enforced with a mask -- under int8 compression their
    blend reads the full-precision local buffer, never its quantized
    image).  One explicit-pairs collective-permute per dtype group: the
    shard-native path when ``mesh`` carries the node axis (see
    :func:`_mix_sharded`), a local static gather without one.

    compression='int8' quantizes the permuted payload exactly like
    :func:`mix_shifts` (per-leaf-segment scales ride along as a second,
    tiny permute).

    Runtime-valued rounds (traced ``w_self``, ``meta=``/``edge_weight=``/
    ``node_gate=``) take the traced combine path; fixed points still keep
    their value bit-exactly, and under a per-node gate the pair averages
    only when BOTH endpoints are alive (the symmetric drop that keeps a
    matching round exactly mean-preserving).
    """
    n = len(partner)
    fixed = np.fromiter((j == i for i, j in enumerate(partner)),
                        dtype=bool, count=n)
    fixed_mask = fixed if fixed.any() else None

    if _is_runtime_round(w_self, (), meta, edge_weight, node_gate):
        if compression is not None:
            raise ValueError(
                "compression is not supported on runtime-valued rounds "
                "(traced weights / metadata / gating); drop compression= "
                "or use static weights")
        pairs = [(src, dst) for dst, src in enumerate(partner)]
        base = (0.5 if w_self is None
                else 1.0 - jnp.asarray(w_self, jnp.float32))
        # paired nodes carry the peer weight; fixed points contribute 0 so
        # the derived self weight stays 1 there (keep mask then makes the
        # row bit-exact, not just algebraically e_i)
        base = jnp.where(jnp.asarray(fixed), 0.0,
                         jnp.broadcast_to(base, (n,)))
        return _runtime_mix(
            tree, rounds=[pairs], base_ws=[base],
            self_w=None if (node_gate is not None or edge_weight is not None
                            or w_self is None) else w_self,
            meta=meta, node_gate=node_gate, edge_weight=edge_weight,
            fixed_mask=fixed_mask, mesh=mesh, axis_name=axis_name,
            specs=specs)
    w_peer = 1.0 - w_self

    if _shard_native(mesh, axis_name, n):
        pairs = [(src, dst) for dst, src in enumerate(partner)]
        return _mix_sharded(tree, mesh=mesh, specs=specs,
                            axis_name=axis_name, rounds=[(pairs, w_peer)],
                            self_w=w_self, compression=compression,
                            fixed=fixed_mask)

    layout, bufs = _pack(tree)
    idx = jnp.asarray(partner)

    if compression == "int8":
        with _scope("pack"):
            scales = _leaf_scales(tree, layout)
        out = []
        for g, buf, sc in zip(layout.groups, bufs, scales):
            seg = jnp.asarray(g.seg_ids)
            x32 = buf.astype(jnp.float32)
            with _scope("pack"):
                q = jnp.round(x32 / sc[:, seg]).astype(jnp.int8)
            with _scope("permute"):
                rq = jnp.take(q, idx, axis=0)
                rs = jnp.take(sc, idx, axis=0)
            with _scope("combine"):
                acc = w_self * x32 + w_peer * (rq.astype(jnp.float32)
                                               * rs[:, seg])
                if fixed_mask is not None:
                    # fixed points keep their full-precision buffer
                    # bit-exactly (for ANY w_self, not just 0.5)
                    acc = jnp.where(jnp.asarray(fixed_mask)[:, None], x32,
                                    acc)
                out.append(acc.astype(buf.dtype))
        return _unpack(layout, out)

    out = []
    for buf in bufs:
        with _scope("permute"):
            recv = jnp.take(buf, idx, axis=0)
        o = _combine(buf, [recv], w_self, (w_peer,))
        if fixed_mask is not None:
            with _scope("combine"):
                o = jnp.where(jnp.asarray(fixed_mask)[:, None], buf, o)
        out.append(o)
    return _unpack(layout, out)


def mix_shifts_per_leaf(tree: PyTree, self_weight: float,
                        shifts: list[tuple[int, float]],
                        compression: str | None = None) -> PyTree:
    """Historical reference path: one roll PER LEAF per shift.

    Algebraically (and bit-) identical to :func:`mix_shifts`; kept for the
    pack->mix->unpack equivalence tests and the bench_comm comparison."""

    def _leaf(x):
        x32 = x.astype(jnp.float32)
        acc = (self_weight * x32) if self_weight else None
        if compression == "int8":
            red_axes = tuple(range(1, x.ndim))
            scale = (jnp.max(jnp.abs(x32), axis=red_axes, keepdims=True)
                     / 127.0 + 1e-30)
            q = jnp.round(x32 / scale).astype(jnp.int8)
            for s, w in shifts:
                rq = jnp.roll(q, s, axis=0)
                rs = jnp.roll(scale, s, axis=0)
                r = w * (rq.astype(jnp.float32) * rs)
                acc = r if acc is None else acc + r
            return acc.astype(x.dtype)
        for s, w in shifts:
            r = w * jnp.roll(x, s, axis=0).astype(jnp.float32)
            acc = r if acc is None else acc + r
        return acc.astype(x.dtype)

    return jax.tree.map(_leaf, tree)


def mix_realization(tree: PyTree, realization, *,
                    compression: str | None = None, mesh=None,
                    axis_name: str = "node", specs=None, meta=None,
                    edge_weight=None, node_gate=None) -> PyTree:
    """Lower one realization-IR node onto its wire path.

    ``meta``/``edge_weight``/``node_gate`` flow through to the runtime
    combine of Shifts/Matching rounds (see :func:`mix_shifts`); a
    :class:`Gated` node realizes its inner round or Identity from its
    traced gate -- the wire is ALWAYS issued, only the combine is gated."""
    if isinstance(realization, Identity):
        return tree
    if isinstance(realization, Gated):
        gate = realization.gate
        if getattr(gate, "ndim", 0) == 0:
            # whole-round gate: run the round unconditionally (the permute
            # must not sit under a cond), select the result per element
            mixed = mix_realization(
                tree, realization.inner, compression=compression, mesh=mesh,
                axis_name=axis_name, specs=specs, meta=meta,
                edge_weight=edge_weight, node_gate=node_gate)
            return jax.tree.map(
                lambda m, t: jnp.where(gate, m, t), mixed, tree)
        if node_gate is not None:
            raise ValueError("Gated realization with an explicit node_gate=;"
                             " pass one or the other")
        if isinstance(realization.inner, Dense):
            raise ValueError(
                "per-node gating of a Dense round is not supported; gate "
                "Shifts/Matching rounds (or use a scalar whole-round gate)")
        return mix_realization(
            tree, realization.inner, compression=compression, mesh=mesh,
            axis_name=axis_name, specs=specs, meta=meta,
            edge_weight=edge_weight, node_gate=gate)
    if isinstance(realization, Shifts):
        return mix_shifts(tree, realization.self_w, list(realization.shifts),
                          compression, mesh=mesh, axis_name=axis_name,
                          specs=specs, meta=meta, edge_weight=edge_weight,
                          node_gate=node_gate)
    if isinstance(realization, Matching):
        return mix_matching(tree, realization.partner, realization.w_self,
                            compression, mesh, axis_name, specs, meta=meta,
                            edge_weight=edge_weight, node_gate=node_gate)
    if isinstance(realization, Dense):
        if compression is not None:
            raise ValueError(
                f"compression={compression!r} has no dense-matrix wire "
                f"format; only Shifts/Matching realizations quantize")
        if meta is not None or edge_weight is not None or node_gate is not None:
            raise ValueError(
                "metadata piggyback / loss-aware weights / gating need a "
                "permute wire (Shifts or Matching); Dense rounds all-gather")
        return mix_dense(tree, realization.W, mesh=mesh,
                         axis_name=axis_name, specs=specs)
    raise TypeError(f"not a realization IR node: {realization!r}")


def mix(tree: PyTree, topology: Topology, step: int,
        compression: str | None = None, mesh=None, specs=None) -> PyTree:
    """Apply W^(step) of ``topology`` to ``tree``; ``step`` must be a Python
    int (static).  Dispatches on the realization IR node type."""
    return mix_realization(tree, topology.realization(step),
                           compression=compression, mesh=mesh, specs=specs)


def mix_switch(tree: PyTree, topology: Topology, step: jax.Array,
               mesh=None, specs=None) -> PyTree:
    """Traced-step variant: lax.switch over the topology's period so one
    compiled function serves the whole schedule (each branch keeps its own
    static-shift / static-pairs collective-permute; pass ``mesh`` so every
    branch takes the shard-native one-permute path instead of the gather
    fallback).

    Only valid for periodic schedules (``Static``/``Cyclic``): aperiodic
    schedules (``RandomPerm``/``Aperiodic`` -- random matchings, random
    one-peer orders) have no step -> realization map a traced switch can
    enumerate; silently folding them mod a cap would freeze the schedule to
    its first few realizations (the bug this guard replaces).  NB the
    executable carries one branch per period step -- a schedule's period is
    naturally O(log n) for every family here."""
    if not topology.schedule.is_periodic:
        raise AperiodicScheduleError(
            f"mix_switch needs a periodic schedule, but {topology.name!r} "
            f"carries {topology.schedule!r}; aperiodic schedules must use "
            "the static-step path (GossipPlan compiles one executable per "
            "realization)")
    period = topology.schedule.period
    branches = [partial(_mix_static, topology=topology, k=k, mesh=mesh,
                        specs=specs)
                for k in range(period)]
    return jax.lax.switch(step % period, branches, tree)


def _mix_static(tree: PyTree, *, topology: Topology, k: int,
                mesh=None, specs=None) -> PyTree:
    return mix(tree, topology, k, mesh=mesh, specs=specs)


def mix_scheduled(tree: PyTree, topology: Topology, pos, gate=None, *,
                  compression: str | None = None, mesh=None, specs=None,
                  meta=None, edge_weight=None, node_gate=None) -> PyTree:
    """Traced-POSITION variant: the schedule position ``pos`` is a traced
    int32 scalar living in optimizer state, advanced only on rounds that
    actually communicate (``pos_next = pos + gate``) -- the data-dependent
    generalization of ``gossip(every=k)``.  Realization ``pos % period`` is
    selected by ``lax.switch``; an optional traced scalar ``gate`` selects
    between the mixed result and the unmixed tree WITHOUT skipping the
    wire (every branch issues its permutes unconditionally, so a gated-off
    round still moves its bytes and no collective sits under a data-
    dependent cond -- SPMD-safe because ``pos``/``gate`` are replicated).

    Exactness: because ``pos`` only advances on communicating rounds, a
    finite-time family (one_peer_exp / base_k / ceca) still exactly
    averages once ``period`` COMMUNICATING rounds complete, however many
    skipped rounds interleave -- the property test asserts this.

    Periodic schedules only (same restriction and reasoning as
    :func:`mix_switch`)."""
    if not topology.schedule.is_periodic:
        raise AperiodicScheduleError(
            f"mix_scheduled needs a periodic schedule, but "
            f"{topology.name!r} carries {topology.schedule!r}")
    period = topology.schedule.period

    def branch(k):
        def f(t):
            return mix_realization(
                t, topology.realization(k), compression=compression,
                mesh=mesh, specs=specs, meta=meta, edge_weight=edge_weight,
                node_gate=node_gate)
        return f

    mixed = jax.lax.switch(pos % period, [branch(k) for k in range(period)],
                           tree)
    if gate is None:
        return mixed
    return jax.tree.map(lambda m, t: jnp.where(gate, m, t), mixed, tree)


def gossip_spec(topology: Topology, step: int,
                layout: flatbuf.FlatLayout | None = None,
                compression: str | None = None,
                meta_cols: int = 0) -> dict:
    """Structural description of one gossip round, read straight off the
    realization IR (for roofline accounting).

    ``wire_multiplier`` is the number of per-node payload copies the round
    moves: one per shift for ``Shifts``, exactly 1 for any ``Matching``,
    ``n - 1`` for ``Dense`` (the packed buffer is all-gathered -- O(n)
    bytes per node REGARDLESS of the realization's fan-in), 0 for
    ``Identity``.  With a ``layout`` (from :func:`flatbuf.layout_of`), adds
    the packed-path byte accounting: collectives per step (int8 rounds move
    TWO permutes per dtype group -- payload plus the per-leaf scale row)
    and bytes sent per node, split payload vs. scales so dry-run rooflines
    match the HLO.

    ``meta_cols`` counts the piggybacked per-node metadata columns (loss,
    grad-norm, deadline flag -- INCLUDING the gate column when present):
    they ride the f32 group's existing permute, so they add ZERO
    collectives but ``4 * meta_cols`` bytes per payload copy, reported as
    a separate ``meta_bytes_per_node_per_step`` split (mirroring the int8
    scale-row split) so :mod:`benchmarks.check_comm_regression` gates the
    new bytes honestly."""
    r = topology.realization(step)
    n = topology.n
    gated = isinstance(r, Gated)
    if gated:
        r = r.inner          # the wire structure is always issued
    mult = r.wire_multiplier(n)
    if isinstance(r, Shifts):
        spec = {"kind": "ppermute", "rounds": len(r.shifts),
                "shifts": [s for s, _ in r.shifts]}
        rounds = len(r.shifts)
    elif isinstance(r, Matching):
        paired = sum(1 for i, j in enumerate(r.partner) if j != i)
        spec = {"kind": "matching", "rounds": 1, "paired_nodes": paired}
        rounds = 1
    elif isinstance(r, Identity):
        spec = {"kind": "identity", "rounds": 0}
        rounds = 0
    else:
        spec = {"kind": "dense", "rounds": 1, "fanin": r.max_degree}
        rounds = 1
    spec["wire_multiplier"] = mult
    if gated:
        spec["gated"] = True
    if meta_cols:
        spec["meta_cols"] = meta_cols
    if layout is not None:
        split = flatbuf.wire_bytes_split(layout, compression)
        quantized = (compression == "int8"
                     and spec["kind"] in ("ppermute", "matching"))
        spec["dtype_groups"] = len(layout.groups)
        # int8 rounds ride a second permute per dtype group for the
        # per-leaf scale payload (the old accounting missed it).
        spec["collectives_per_step"] = (
            rounds * len(layout.groups) * (2 if quantized else 1))
        # piggybacked metadata rides the f32 group's EXISTING permute: zero
        # extra collectives, 4 bytes per column per payload copy.
        meta_bytes = 4 * meta_cols * mult
        spec["payload_bytes_per_node_per_step"] = split["payload"] * mult
        spec["scale_bytes_per_node_per_step"] = split["scales"] * mult
        spec["meta_bytes_per_node_per_step"] = meta_bytes
        spec["bytes_per_node_per_step"] = (
            (split["payload"] + split["scales"]) * mult + meta_bytes)
    return spec
