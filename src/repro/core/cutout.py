"""The cutout engine (paper §4.2, C2): arbitrary sub-volume read/write.

A *cutout* specifies a resolution and a range in every dimension; the engine
decomposes the box into Morton runs of cuboids (few long sequential reads),
assembles the dense array in memory, and returns it. Unaligned requests are
rounded up to cuboid boundaries and trimmed (the paper measures exactly this
cost in Fig 10). Writes apply a conflict discipline per voxel (paper §3.2):
``overwrite`` / ``preserve`` / ``exception``.

Both directions are *planned*: :func:`plan_cutout` computes every
(cuboid, destination-slice) pair up front with one vectorized Morton decode,
and the read direction is a *pipeline* (§5: throughput is assembly-bound,
not I/O-bound).  The store's ``fetch_blocks`` drives the whole cold path —
blobs fetched in `DecodePolicy.chunk`-sized ``get_many`` batches so one
chunk's backend I/O overlaps another's decompression, the next curve
segments prefetching into the hot-cuboid cache while the current one
decodes (``read_stats.seeks`` still counts *run boundaries*, the paper's
spatial-discontiguity metric, not these temporal batches) — and each
decoded block is
assembled **directly into the shared output buffer** by the worker that
decoded it, through the plan's precomputed disjoint ``buf_slices`` (no
intermediate per-key dict, no second pass; disjointness makes the
concurrent writes race-free).  Absent (lazy-zero) cuboids skip both
decompression and assembly.  :func:`cutout_loop` preserves the original
per-cuboid loop as the reference implementation and correctness oracle.

Lower-dimensional projections (§3.3 tiles) are cutouts with singleton dims.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import trace
from . import morton
from .cuboid import CuboidGrid
from .store import CuboidStore, decompress

Box = Tuple[Sequence[int], Sequence[int]]  # (lo, hi) half-open


@dataclasses.dataclass
class CutoutStats:
    cuboids_read: int = 0
    runs: int = 0
    bytes_assembled: int = 0
    bytes_discarded: int = 0   # read-and-discarded due to misalignment
    zero_copy: int = 0         # aligned requests returned without a copy


def _aligned_box(grid: CuboidGrid, lo, hi):
    alo = [l - l % c for l, c in zip(lo, grid.cuboid_shape)]
    ahi = [min(-(-h // c) * c, g * c) for h, c, g in
           zip(hi, grid.cuboid_shape, grid.grid_shape)]
    return alo, ahi


@dataclasses.dataclass(frozen=True)
class CutoutPlan:
    """Everything a batch cutout needs, computed before any I/O.

    ``cells[i]`` is assembled into ``buf[buf_slices[i]]`` from the leading
    ``keep_shapes[i]`` corner of its cuboid.  ``runs`` is the I/O schedule
    (contiguous Morton runs, the paper's few-sequential-reads property);
    cells outside the volume (pow2 padding) or outside the box (run
    coarsening) are already excluded.
    """
    r: int
    lo: Tuple[int, ...]
    hi: Tuple[int, ...]
    alo: Tuple[int, ...]              # cuboid-aligned box lo
    buf_shape: Tuple[int, ...]
    runs: morton.Runs
    cells: np.ndarray                 # (n,) int64 morton indices to assemble
    origins: np.ndarray               # (n, rank) voxel origin per cell
    buf_slices: List[Tuple[slice, ...]]
    keep_shapes: List[Tuple[int, ...]]

    @property
    def trim(self) -> Tuple[slice, ...]:
        return tuple(slice(l - a, h - a)
                     for l, h, a in zip(self.lo, self.hi, self.alo))


def plan_cutout(grid: CuboidGrid, r: int, lo: Sequence[int],
                hi: Sequence[int],
                max_runs: Optional[int] = None) -> CutoutPlan:
    """Plan the batch assembly of clamped box [lo, hi) — no I/O, no loops
    over cuboid *contents*: cell origins come from one vectorized decode."""
    runs = grid.box_to_runs(lo, hi, max_runs=max_runs)
    alo, ahi = _aligned_box(grid, lo, hi)
    cs = np.asarray(grid.cuboid_shape)
    cells = morton.runs_to_indices(runs)
    origins = morton.morton_decode(cells, grid.bits) * cs  # (n, rank)
    vol = np.asarray(grid.volume_shape)
    # runs may cover cells outside the box (coarsening) or outside the
    # volume (pow2 padding): mask those out of the assembly.
    keep = ((origins < vol).all(axis=1)
            & (origins + cs > np.asarray(alo)).all(axis=1)
            & (origins < np.asarray(ahi)).all(axis=1))
    cells, origins = cells[keep], origins[keep]
    buf_shape = tuple(h - l for l, h in zip(alo, ahi))
    rel = origins - np.asarray(alo)
    ends = np.minimum(rel + cs, np.asarray(buf_shape))
    buf_slices = [tuple(slice(int(a), int(b)) for a, b in zip(row_lo, row_hi))
                  for row_lo, row_hi in zip(rel, ends)]
    keep_shapes = [tuple(int(x) for x in row) for row in (ends - rel)]
    return CutoutPlan(r=r, lo=tuple(lo), hi=tuple(hi), alo=tuple(alo),
                      buf_shape=buf_shape, runs=runs, cells=cells,
                      origins=origins, buf_slices=buf_slices,
                      keep_shapes=keep_shapes)


def cutout(store: CuboidStore, r: int, lo: Sequence[int], hi: Sequence[int],
           channel: int = 0, stats: Optional[CutoutStats] = None,
           max_runs: Optional[int] = None) -> np.ndarray:
    """Read the dense sub-volume [lo, hi) at resolution ``r`` (planned)."""
    grid = store.spec.grid(r)
    lo, hi = grid.clamp_box(lo, hi)
    dtype = np.dtype(store.spec.dtype)
    if any(l >= h for l, h in zip(lo, hi)):
        return np.zeros([max(0, h - l) for l, h in zip(lo, hi)], dtype=dtype)
    with trace.span("plan", r=r):
        plan = plan_cutout(grid, r, lo, hi, max_runs=max_runs)
    buf = np.zeros(plan.buf_shape, dtype=dtype)
    targets = {int(m): (sl, keep) for m, sl, keep in
               zip(plan.cells, plan.buf_slices, plan.keep_shapes)}

    def assemble(m: int, block: Optional[np.ndarray]) -> None:
        # Called from decode workers / node fan-out threads: buf_slices
        # are pairwise disjoint, so concurrent writes never race.
        if block is None:
            return  # lazy cuboid: buffer is already zeros
        t = targets.get(m)
        if t is None:
            return  # outside box/volume (run coarsening / pow2 padding)
        sl, keep = t
        buf[sl] = block[tuple(slice(0, s) for s in keep)]

    # One span covers fetch + decode + assembly — the whole pipelined
    # read (per-node fetch and decode spans nest inside it).
    with trace.span("assemble", cuboids=len(plan.cells), runs=len(plan.runs)):
        store.fetch_blocks(r, plan.runs, channel, sink=assemble)
    # Cuboid-aligned requests assemble the answer exactly: hand the buffer
    # over as-is instead of copying the whole volume through a no-op trim.
    aligned = (plan.lo == plan.alo
               and plan.buf_shape == tuple(h - l for l, h
                                           in zip(plan.lo, plan.hi)))
    out = buf if aligned else np.ascontiguousarray(buf[plan.trim])
    if stats is not None:
        stats.cuboids_read += len(plan.cells)
        stats.runs += len(plan.runs)
        stats.bytes_assembled += out.nbytes
        stats.bytes_discarded += buf.nbytes - out.nbytes
        stats.zero_copy += int(aligned)
    return out


def cutout_loop(store: CuboidStore, r: int, lo: Sequence[int],
                hi: Sequence[int], channel: int = 0,
                stats: Optional[CutoutStats] = None,
                max_runs: Optional[int] = None) -> np.ndarray:
    """Reference cutout: the original per-cuboid Python loop.

    Kept as the correctness oracle for the planned path and as the baseline
    the benchmark suite measures the planned speedup against.
    """
    grid = store.spec.grid(r)
    lo, hi = grid.clamp_box(lo, hi)
    if any(l >= h for l, h in zip(lo, hi)):
        return np.zeros([max(0, h - l) for l, h in zip(lo, hi)],
                        dtype=np.dtype(store.spec.dtype))
    runs = grid.box_to_runs(lo, hi, max_runs=max_runs)
    alo, ahi = _aligned_box(grid, lo, hi)
    buf = np.zeros([h - l for l, h in zip(alo, ahi)],
                   dtype=np.dtype(store.spec.dtype))
    cs = grid.cuboid_shape
    n_read = 0
    for start, stop in runs:
        blocks = store.read_run(r, start, stop, channel)
        for m, block in zip(range(start, stop), blocks):
            origin = grid.cuboid_origin(m)
            # runs may cover morton cells outside the box (coarsening) or
            # outside the volume (pow2 padding): skip those.
            if any(o >= v for o, v in zip(origin, grid.volume_shape)):
                continue
            if any(o + c <= l or o >= h
                   for o, c, l, h in zip(origin, cs, alo, ahi)):
                continue
            sl = tuple(slice(o - a, o - a + c)
                       for o, a, c in zip(origin, alo, cs))
            view_shape = buf[sl].shape
            buf[sl] = block[tuple(slice(0, s) for s in view_shape)]
            n_read += 1
    trim = tuple(slice(l - a, h - a) for l, h, a in zip(lo, hi, alo))
    out = buf[trim]
    if stats is not None:
        stats.cuboids_read += n_read
        stats.runs += len(runs)
        stats.bytes_assembled += out.nbytes
        stats.bytes_discarded += buf.nbytes - out.nbytes
    return np.ascontiguousarray(out)


WriteDiscipline = str  # 'overwrite' | 'preserve' | 'exception'


def write_cutout(store: CuboidStore, r: int, lo: Sequence[int],
                 data: np.ndarray, channel: int = 0,
                 discipline: WriteDiscipline = "overwrite",
                 on_conflict: Optional[Callable[[int, Tuple[int, ...],
                                                 np.ndarray, np.ndarray],
                                                None]] = None) -> None:
    """Write dense ``data`` at offset ``lo`` (read-modify-write per cuboid).

    Mirrors the paper's annotation upload path (§5/Fig 12): (1) read prior
    cuboids, (2) resolve per-voxel conflicts by ``discipline``, (3) write
    back.  ``on_conflict(morton, origin, old_block, new_block)`` is invoked
    for ``exception`` discipline so the annotation layer can record
    multi-label exceptions (paper §3.2).
    """
    if discipline not in ("overwrite", "preserve", "exception"):
        raise ValueError(f"unknown discipline {discipline!r}")
    grid = store.spec.grid(r)
    hi = [l + s for l, s in zip(lo, data.shape)]
    clo, chi = grid.clamp_box(lo, hi)
    if any(l >= h for l, h in zip(clo, chi)):
        return
    cs = grid.cuboid_shape
    dtype = np.dtype(store.spec.dtype)
    plan = plan_cutout(grid, r, clo, chi)
    # read-modify-write, planned: ONE batch fetch of all prior blobs
    # (compressed, cheap to hold), merge per cuboid, batch write-back in
    # bounded chunks so peak decompressed memory stays O(chunk) rather
    # than O(region) — bulk ingest routes whole volumes through here.
    with trace.span("write.fetch", runs=len(plan.runs)):
        blobs = store.fetch_runs(r, plan.runs, channel)
    flush_every = 64  # ~16 MB of 256K-voxel uint8 cuboids per chunk
    cells = list(zip(plan.cells, plan.origins))
    for c0 in range(0, len(cells), flush_every):
        chunk = cells[c0:c0 + flush_every]
        out_blocks: Dict[int, np.ndarray] = {}
        with trace.span("write.merge") as counts:
            voxels = 0
            for cell, origin in chunk:
                m = int(cell)
                blob = blobs.get(m)
                block = (np.zeros(cs, dtype=dtype) if blob is None
                         else decompress(blob, cs, dtype).copy())
                # overlap of this cuboid with the data box, in both frames
                b_lo = [max(0, l - int(o)) for l, o in zip(clo, origin)]
                b_hi = [min(c, h - int(o)) for c, h, o in zip(cs, chi, origin)]
                d_lo = [int(o) + bl - l for o, bl, l in zip(origin, b_lo, lo)]
                d_hi = [int(o) + bh - l for o, bh, l in zip(origin, b_hi, lo)]
                bsl = tuple(slice(a, b) for a, b in zip(b_lo, b_hi))
                dsl = tuple(slice(a, b) for a, b in zip(d_lo, d_hi))
                new = data[dsl]
                old = block[bsl]
                voxels += new.size
                if discipline == "overwrite":
                    merged = np.where(new != 0, new, old)
                elif discipline == "preserve":
                    merged = np.where(old != 0, old, new)
                else:  # exception
                    merged = np.where(old != 0, old, new)
                    if on_conflict is not None:
                        conflict = (old != 0) & (new != 0) & (old != new)
                        if conflict.any():
                            # report in full-cuboid frame so flat voxel
                            # offsets are stable keys for the exceptions
                            # list (§3.2)
                            old_full = np.zeros(cs, dtype=block.dtype)
                            new_full = np.zeros(cs, dtype=block.dtype)
                            old_full[bsl] = old * conflict
                            new_full[bsl] = new * conflict
                            on_conflict(m, tuple(int(o) for o in origin),
                                        old_full, new_full)
                block[bsl] = merged.astype(block.dtype)
                out_blocks[m] = block
            if counts is not None:
                counts["voxels"] = voxels
        with trace.span("write.store", cuboids=len(out_blocks)):
            store.store_cuboids(r, out_blocks, channel)


def project(store: CuboidStore, r: int, lo: Sequence[int],
            hi: Sequence[int], axis: int, reduce: str = "slice",
            channel: int = 0) -> np.ndarray:
    """Lower-dimensional projection (paper §3.3: dynamic tile building).

    ``slice`` takes the first plane along ``axis`` (a tile request);
    ``max``/``mean`` reduce along it (e.g. MIP renderings). The engine reads
    3-d cuboid runs and discards what the projection does not need — this is
    exactly the read-amplification trade the paper accepts to avoid storing
    redundant tile stacks.
    """
    vol = cutout(store, r, lo, hi, channel)
    if reduce == "slice":
        return np.take(vol, 0, axis=axis)
    if reduce == "max":
        return vol.max(axis=axis)
    if reduce == "mean":
        return vol.mean(axis=axis).astype(vol.dtype)
    raise ValueError(f"unknown reduce {reduce!r}")


def batch_cutout(store: CuboidStore, r: int,
                 boxes: List[Box], channel: int = 0) -> List[np.ndarray]:
    """Batch interface (paper §4.2): amortize fixed costs over requests.

    Over a cluster the boxes *overlap*: each box's plan, node fan-out, and
    decode chunks run as one job on the cluster's request-level pool, so
    box B's I/O pipelines with box A's assembly instead of queuing behind
    it.  Results keep request order.  Stores without a ``run_batch``
    (single `CuboidStore`) execute serially, as before.
    """
    jobs = [functools.partial(cutout, store, r, lo, hi, channel)
            for lo, hi in boxes]
    runner = getattr(store, "run_batch", None)
    if runner is None:
        return [job() for job in jobs]
    return list(runner(jobs))


def ingest(store: CuboidStore, r: int, volume: np.ndarray,
           channel: int = 0, offset: Optional[Sequence[int]] = None) -> None:
    """Bulk-load a dense volume (instrument → store ingest path)."""
    off = list(offset or [0] * volume.ndim)
    write_cutout(store, r, off, volume, channel, discipline="overwrite")


def build_hierarchy(store: CuboidStore, channel: int = 0,
                    labels: bool = False) -> None:
    """Propagate level r -> r+1 for the whole dataset (background job, §3.2).

    Image data average-pools the scaled dims; label data stride-samples so
    identifiers survive (no blending of ids).
    """
    from .cuboid import downsample_block, downsample_labels
    spec = store.spec
    for r in range(spec.n_resolutions - 1):
        src, dst = spec.grid(r), spec.grid(r + 1)
        # iterate destination cuboids; pull the source region for each
        for m in range(dst.n_cells):
            origin = dst.cuboid_origin(m)
            if any(o >= v for o, v in zip(origin, dst.volume_shape)):
                continue
            dhi = [min(o + c, v) for o, c, v in
                   zip(origin, dst.cuboid_shape, dst.volume_shape)]
            # source box: scale up the scaled dims by 2
            slo = [o * 2 if d in spec.scaled_dims else o
                   for d, o in enumerate(origin)]
            shi = [h * 2 if d in spec.scaled_dims else h
                   for d, h in enumerate(dhi)]
            block = cutout(store, r, slo, shi, channel)
            if not block.any():
                continue
            down = (downsample_labels(block, spec.scaled_dims) if labels
                    else downsample_block(block, spec.scaled_dims))
            write_cutout(store, r + 1, list(origin), down, channel)
