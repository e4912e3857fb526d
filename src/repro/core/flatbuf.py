"""Flat-buffer packing: one contiguous (n, B) gossip payload per dtype.

The gossip state is a pytree whose leaves all carry a leading node axis of
size ``n``.  Mixing leaf-by-leaf issues one roll (=> one collective-permute
under GSPMD) **per leaf per shift** -- a transformer with ~100 leaves pays
~100 tiny collectives per iteration, burying the paper's Omega(1)
communication claim in launch overhead.  This module packs all leaves of a
common dtype into ONE contiguous ``(n, B)`` buffer so the production path in
:mod:`repro.core.gossip` rolls each dtype group exactly once per shift,
regardless of leaf count, and feeds the fused ``gossip_mix`` Pallas kernel
directly.

The pack runs at TWO granularities:

* **global** (``pad_multiple=PAD_MULTIPLE``, the default): every node's full
  leaf row is flattened into the group buffer, padded so the flattened
  ``(n * B)`` buffer tiles the kernel's (8, 1024) f32 grid.  This is the
  single-process / no-mesh path.
* **per-shard** (``pad_multiple=1``): used *inside* ``shard_map`` by the
  shard-native engine -- each device packs only its local block of every
  leaf (e.g. ``(1, B_shard)`` on a ``node x fsdp`` mesh), so packing never
  moves bytes across devices and inner-dim shardings are untouched.  Tile
  padding happens per shard inside ``ops.gossip_mix`` instead of globally.

The layout (group membership, per-leaf offsets/shapes, padding, segment ids
for per-leaf quantization scales) depends only on the tree *structure* (and
the pad granularity), so it is computed once per structure and kept in an
LRU-bounded process cache; ``pack``/``unpack`` inside a jit trace are pure
reshape/concat/slice -- XLA fuses them into the surrounding computation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gossip_mix import kernel as _gm_kernel

from .cache import CompileCache

PyTree = Any

__all__ = ["FlatLayout", "GroupLayout", "LeafSlot", "layout_of", "pack",
           "unpack", "wire_bytes_per_round", "wire_bytes_split",
           "PAD_MULTIPLE"]

# Pad each group's flat width to this multiple: with TILE_COLS lanes the
# flattened (n * B) buffer then reshapes to a whole number of TILE_ROWS-row
# tiles for any n, so ops.gossip_mix takes its zero-copy path.
PAD_MULTIPLE = _gm_kernel.TILE_ROWS * _gm_kernel.TILE_COLS


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One leaf's strip inside its dtype group's flat buffer."""

    leaf_index: int        # position in jax.tree.leaves order
    offset: int            # start column in the (n, B) group buffer
    size: int              # number of elements per node (prod(shape[1:]))
    shape: tuple           # full leaf shape, including the node axis


@dataclasses.dataclass(frozen=True, eq=False)
class GroupLayout:
    dtype: Any             # jnp dtype of every leaf in the group
    slots: tuple           # tuple[LeafSlot, ...] in leaf order
    size: int              # used columns (sum of slot sizes)
    padded: int            # allocated columns (size rounded up to tile grid)

    @functools.cached_property
    def seg_ids(self) -> np.ndarray:
        """(padded,) int32: element -> slot position within this group;
        padding elements map to len(slots).  Consumed only by the per-leaf
        int8 scale expansion, so it is built on first use: at a published
        width it is one int32 per payload element (GBs of host memory)."""
        seg = np.full((self.padded,), len(self.slots), np.int32)
        for pos, s in enumerate(self.slots):
            seg[s.offset:s.offset + s.size] = pos
        return seg


@dataclasses.dataclass(frozen=True, eq=False)
class FlatLayout:
    treedef: Any
    n: int                 # node-axis size shared by every leaf
    groups: tuple          # tuple[GroupLayout, ...]
    n_leaves: int

    def group_for(self, dtype) -> GroupLayout:
        dt = jnp.dtype(dtype)
        for g in self.groups:
            if g.dtype == dt:
                return g
        raise KeyError(f"no group with dtype {dtype}")


# LRU-bounded: one entry per (tree structure, shapes, pad granularity).  A
# long-lived multi-model process (serve + train + benchmarks) visits a fresh
# structure per model; an unbounded dict would leak layouts (plus their
# seg_ids arrays) for the whole process lifetime.
_LAYOUT_CACHE = CompileCache(max_entries=256)


def _pad_up(size: int, multiple: int) -> int:
    return max(-(-size // multiple) * multiple, multiple)


def layout_of(tree: PyTree, pad_multiple: int = PAD_MULTIPLE) -> FlatLayout:
    """Compute (or fetch) the packing layout for ``tree``'s structure.

    ``pad_multiple=1`` (the shard-native per-shard pack) allocates exactly
    the used columns; the default pads each group's width to the Pallas
    tile grid so the single-process kernel path never re-pads."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot pack an empty pytree")
    key = (treedef,
           tuple((jnp.dtype(x.dtype).name, tuple(x.shape)) for x in leaves),
           int(pad_multiple))

    n = leaves[0].shape[0] if leaves[0].ndim else None
    for leaf in leaves:
        if leaf.ndim == 0 or leaf.shape[0] != n:
            raise ValueError(
                "every gossip leaf needs the same leading node axis; got "
                f"shapes {[tuple(x.shape) for x in leaves]}")

    def build() -> FlatLayout:
        by_dtype: dict = {}
        for i, leaf in enumerate(leaves):
            by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)

        groups = []
        for dt, idxs in by_dtype.items():
            slots, off = [], 0
            for i in idxs:
                size = int(np.prod(leaves[i].shape[1:], dtype=np.int64))
                slots.append(LeafSlot(i, off, size, tuple(leaves[i].shape)))
                off += size
            groups.append(GroupLayout(dt, tuple(slots), off,
                                      _pad_up(off, pad_multiple)))

        return FlatLayout(treedef, int(n), tuple(groups), len(leaves))

    return _LAYOUT_CACHE.get(key, build)


def pack(tree: PyTree, layout: FlatLayout | None = None):
    """tree -> (layout, [(n, padded) buffer per dtype group])."""
    if layout is None:
        layout = layout_of(tree)
    leaves = jax.tree.leaves(tree)
    n = layout.n
    bufs = []
    for g in layout.groups:
        strips = [leaves[s.leaf_index].reshape(n, -1) for s in g.slots]
        buf = strips[0] if len(strips) == 1 else jnp.concatenate(strips, 1)
        if g.padded != g.size:
            buf = jnp.pad(buf, ((0, 0), (0, g.padded - g.size)))
        bufs.append(buf)
    return layout, bufs


def unpack(layout: FlatLayout, bufs) -> PyTree:
    """Inverse of :func:`pack` (padding is discarded)."""
    leaves = [None] * layout.n_leaves
    for g, buf in zip(layout.groups, bufs):
        for s in g.slots:
            leaves[s.leaf_index] = (
                buf[:, s.offset:s.offset + s.size].reshape(s.shape))
    return jax.tree.unflatten(layout.treedef, leaves)


def wire_bytes_split(layout: FlatLayout,
                     compression: str | None = None) -> dict:
    """Per-round wire bytes one node sends, split by collective.

    Returns ``{"payload": ..., "scales": ...}``: the main payload buffers
    (all dtype groups) and -- under int8 compression -- the per-leaf-segment
    f32 scale rows that ride a SECOND, tiny collective-permute per dtype
    group (``scales == 0`` uncompressed)."""
    payload = scales = 0
    for g in layout.groups:
        if compression == "int8":
            payload += g.padded                       # 1 byte / element
            scales += 4 * (len(g.slots) + 1)          # f32 per leaf + pad seg
        else:
            payload += g.padded * jnp.dtype(g.dtype).itemsize
    return {"payload": payload, "scales": scales}


def wire_bytes_per_round(layout: FlatLayout,
                         compression: str | None = None) -> int:
    """Total bytes one node sends per gossip round (payload + scales)."""
    split = wire_bytes_split(layout, compression)
    return split["payload"] + split["scales"]
