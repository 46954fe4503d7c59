"""Distributed cutout over a device mesh (paper §4.1 C3, TPU-native).

The paper shards large datasets by partitioning the Morton curve across
database nodes, with application-level request routing. The TPU-native
analogue: the volume lives device-resident as a *cuboid-major* array of
shape ``(n_cells, *cuboid_shape)`` sharded along axis 0 over the mesh
``data`` axis — each device owns one contiguous curve segment (== one
paper "database node"). A cutout is then:

  1. (host, static) box -> Morton runs -> cell indices -> owning devices,
  2. (device, shard_map) each device gathers its local cells,
  3. all_gather + static permutation assembles the dense cutout.

Collective cost is proportional to the cutout, not the volume: only the
touched cells move. This module is also the substrate for the training
data pipeline (`repro.data`): a global batch is a cutout of the token grid.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import morton
from .cuboid import CuboidGrid


def pack_to_cuboids(volume: np.ndarray, grid: CuboidGrid) -> np.ndarray:
    """Dense volume -> (n_cells, *cuboid_shape), rows in Morton order.

    Out-of-volume cells (pow2 padding) are zero — they exist so the row
    index IS the Morton index (lazy cuboids, paper §3.2).
    """
    cs = grid.cuboid_shape
    out = np.zeros((grid.n_cells,) + tuple(cs), dtype=volume.dtype)
    for m in range(grid.n_cells):
        origin = grid.cuboid_origin(m)
        if any(o >= v for o, v in zip(origin, grid.volume_shape)):
            continue
        sl = tuple(slice(o, min(o + c, v))
                   for o, c, v in zip(origin, cs, grid.volume_shape))
        src = volume[sl]
        out[m][tuple(slice(0, s) for s in src.shape)] = src
    return out


def unpack_from_cuboids(packed: np.ndarray, grid: CuboidGrid) -> np.ndarray:
    vol = np.zeros(grid.volume_shape, dtype=packed.dtype)
    cs = grid.cuboid_shape
    for m in range(grid.n_cells):
        origin = grid.cuboid_origin(m)
        if any(o >= v for o, v in zip(origin, grid.volume_shape)):
            continue
        sl = tuple(slice(o, min(o + c, v))
                   for o, c, v in zip(origin, cs, grid.volume_shape))
        vol[sl] = packed[m][tuple(slice(0, h - o) for o, h in
                                  zip(origin, [s.stop for s in sl]))]
    return vol


def shard_cuboids(packed: jax.Array, mesh: Mesh,
                  axis: str = "data") -> jax.Array:
    """Place the cuboid-major array with curve-partitioned ownership."""
    spec = P(axis, *([None] * (packed.ndim - 1)))
    return jax.device_put(packed, NamedSharding(mesh, spec))


def _cutout_plan(grid: CuboidGrid, lo, hi, n_devices: int):
    """Static plan: per-device padded cell lists + assembly permutation."""
    cs = grid.cuboid_shape
    glo = [l // c for l, c in zip(lo, cs)]
    ghi = [-(-h // c) for h, c in zip(hi, cs)]
    gshape = tuple(h - l for l, h in zip(glo, ghi))
    # cells in box-grid order (row-major over the sub-grid)
    mesh_idx = np.meshgrid(*[np.arange(l, h) for l, h in zip(glo, ghi)],
                           indexing="ij")
    coords = np.stack([g.ravel() for g in mesh_idx], axis=-1)
    cells = morton.morton_encode(coords, grid.bits)          # (n_box,)
    n_box = len(cells)

    seg = morton.partition_curve(grid.n_cells, n_devices)
    owner = morton.owner_of(cells, grid.n_cells, n_devices)  # (n_box,)
    counts = np.bincount(owner, minlength=n_devices)
    max_k = max(1, int(counts.max()))
    local_idx = np.zeros((n_devices, max_k), dtype=np.int32)
    slot_of = np.zeros(n_box, dtype=np.int64)  # flat (dev*max_k+slot) per cell
    fill = [0] * n_devices
    for i, (c, o) in enumerate(zip(cells, owner)):
        s = fill[o]
        local_idx[o, s] = c - seg[o][0]     # row within the device's shard
        slot_of[i] = o * max_k + s
        fill[o] += 1
    return gshape, local_idx, slot_of, max_k


def distributed_cutout(packed: jax.Array, grid: CuboidGrid,
                       lo: Sequence[int], hi: Sequence[int],
                       mesh: Mesh, axis: str = "data") -> jax.Array:
    """Dense cutout of [lo, hi) from a curve-sharded cuboid array.

    ``lo``/``hi`` are static (trace-time) — like the paper's URL-specified
    ranges. Assembly (gather + transpose-merge + trim) happens on device.
    """
    lo = tuple(int(x) for x in lo)
    hi = tuple(int(x) for x in hi)
    n_dev = mesh.shape[axis]
    gshape, local_idx, slot_of, max_k = _cutout_plan(grid, lo, hi, n_dev)
    cs = grid.cuboid_shape
    local_idx_j = jnp.asarray(local_idx)                  # (n_dev, max_k)

    ndim_tail = packed.ndim - 1
    in_specs = (jax.sharding.PartitionSpec(axis, *([None] * ndim_tail)),
                jax.sharding.PartitionSpec())
    out_specs = jax.sharding.PartitionSpec()

    def gather_local(shard, idx_table):
        me = jax.lax.axis_index(axis)
        mine = idx_table[me]                               # (max_k,)
        picked = jnp.take(shard, mine, axis=0)             # (max_k, *cs)
        return jax.lax.all_gather(picked, axis)            # (n_dev,max_k,*cs)

    gathered = jax.jit(
        jax.shard_map(gather_local, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    )(packed, local_idx_j)                                 # replicated

    flat = gathered.reshape((n_dev * max_k,) + tuple(cs))
    ordered = jnp.take(flat, jnp.asarray(slot_of), axis=0)  # box-grid order
    return merge_blocks(ordered.reshape(tuple(gshape) + tuple(cs)), lo, hi)


def merge_blocks(blocks: jax.Array, lo: Sequence[int],
                 hi: Sequence[int]) -> jax.Array:
    """Cuboids in box-grid order, shaped (*gshape, *cuboid_shape) -> the
    dense [lo, hi) box. Interleaves grid and intra-cuboid axes
    (g0, c0, g1, c1, ...), merges each pair, then trims to the box."""
    rank = blocks.ndim // 2
    gshape, cs = blocks.shape[:rank], blocks.shape[rank:]
    perm = [i for d in range(rank) for i in (d, rank + d)]
    merged = blocks.transpose(perm).reshape(
        tuple(g * c for g, c in zip(gshape, cs)))
    trim = tuple(slice(l % c, l % c + h - l) for l, h, c in zip(lo, hi, cs))
    return merged[trim]


def distributed_write_cutout(packed: jax.Array, grid: CuboidGrid,
                             lo: Sequence[int], data: jax.Array,
                             mesh: Mesh, axis: str = "data") -> jax.Array:
    """Functional distributed write: returns updated cuboid array.

    Analogue of the paper's write path; each device applies updates only to
    its own curve segment (no cross-device write traffic — the update is
    broadcast and masked locally, writes stay node-local as in §4.1).
    """
    lo = tuple(int(x) for x in lo)
    hi = tuple(l + s for l, s in zip(lo, data.shape))
    n_dev = mesh.shape[axis]
    cs = grid.cuboid_shape
    glo = [l // c for l, c in zip(lo, cs)]
    ghi = [-(-h // c) for h, c in zip(hi, cs)]
    gshape = tuple(h - l for l, h in zip(glo, ghi))
    # pad data out to the cuboid-aligned box; an explicit mask marks which
    # voxels the write covers (numeric data overwrites fully inside the box)
    alo = [g * c for g, c in zip(glo, cs)]
    pad_widths = []
    for l, h, a, gl, g, c in zip(lo, hi, alo, glo, gshape, cs):
        before = l - a
        after = (gl + g) * c - h
        pad_widths.append((before, after))
    dpad = jnp.pad(data, pad_widths)
    mpad = jnp.pad(jnp.ones(data.shape, dtype=bool), pad_widths)
    # split into blocks: reshape to (g0,c0,g1,c1,...) -> (n_box, *cs)
    rank = len(cs)
    shape_i = []
    for g, c in zip(gshape, cs):
        shape_i += [g, c]
    perm = list(range(0, 2 * rank, 2)) + list(range(1, 2 * rank, 2))
    dblocks = dpad.reshape(shape_i).transpose(perm).reshape(
        (-1,) + tuple(cs))
    mblocks = mpad.reshape(shape_i).transpose(perm).reshape(
        (-1,) + tuple(cs))

    mesh_idx = np.meshgrid(*[np.arange(l, h) for l, h in zip(glo, ghi)],
                           indexing="ij")
    coords = np.stack([g.ravel() for g in mesh_idx], axis=-1)
    cells = morton.morton_encode(coords, grid.bits)
    seg = morton.partition_curve(grid.n_cells, n_dev)
    seg_starts = jnp.asarray(np.array([a for a, _ in seg], dtype=np.int32))
    cells_j = jnp.asarray(cells.astype(np.int32))

    ndim_tail = packed.ndim - 1
    pspec = jax.sharding.PartitionSpec(axis, *([None] * ndim_tail))
    rep = jax.sharding.PartitionSpec()

    def apply_local(shard, dblk, mblk, cells_, seg_starts_):
        me = jax.lax.axis_index(axis)
        start = seg_starts_[me]
        n_local = shard.shape[0]

        def body(i, acc):
            cell = cells_[i]
            row = cell - start
            in_range = (row >= 0) & (row < n_local)
            row_c = jnp.clip(row, 0, n_local - 1)
            cur = acc[row_c]
            new = jnp.where(mblk[i], dblk[i].astype(acc.dtype), cur)
            new = jnp.where(in_range, new, cur)
            return acc.at[row_c].set(new)

        return jax.lax.fori_loop(0, dblk.shape[0], body, shard)

    updated = jax.jit(
        jax.shard_map(apply_local, mesh=mesh,
                      in_specs=(pspec, rep, rep, rep, rep),
                      out_specs=pspec, check_vma=False)
    )(packed, dblocks, mblocks, cells_j, seg_starts)
    return updated
