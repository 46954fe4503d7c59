"""Annotation projects: RAMON-like metadata + spatial labels (paper §3.2).

An :class:`AnnotationProject` pairs
  * a metadata table implementing a small RAMON-like ontology
    (synapse / seed / segment / neuron / organelle + user KV pairs), with
    predicate queries (equality on ints/enums/strings, range on floats), and
  * a spatial label database: a uint32 CuboidStore registered to an image
    dataset, with lazy cuboids, per-cuboid exception lists for multiply
    labeled voxels, write disciplines, and deferred resolution-hierarchy
    propagation (paper: consistency traded for write throughput).
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import trace
from .cuboid import DatasetSpec
from .cutout import cutout, write_cutout, build_hierarchy
from .spatial_index import ObjectIndex
from .store import Backend, CuboidStore

# --- RAMON-ish metadata ------------------------------------------------

RAMON_TYPES = ("generic", "seed", "synapse", "segment", "neuron", "organelle")


@dataclasses.dataclass
class Annotation:
    ann_id: int
    ann_type: str = "generic"
    confidence: float = 1.0
    status: int = 0
    author: str = ""
    kv: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # synapse-specific (paper's driving application):
    synapse_type: int = 0
    weight: float = 0.0
    segments: Tuple[int, ...] = ()      # linked segment ids
    # segment/neuron-specific:
    neuron: int = 0
    parent_seed: int = 0

    def matches(self, field: str, op: str, value) -> bool:
        v = self.kv.get(field) if field in self.kv else getattr(
            self, field, None)
        if v is None:
            return False
        if op == "eq":
            return str(v) == str(value) if isinstance(v, str) else v == value
        x, y = float(v), float(value)
        return {"lt": x < y, "leq": x <= y, "gt": x > y,
                "geq": x >= y}[op]


class MetadataTable:
    """Key/value predicate queries over annotation metadata (paper §4.2)."""

    def __init__(self):
        self._rows: Dict[int, Annotation] = {}
        self._next_id = itertools.count(1)
        self._lock = threading.Lock()

    def create(self, ann: Optional[Annotation] = None, **kwargs) -> Annotation:
        with self._lock:
            if ann is None:
                ann_id = kwargs.pop("ann_id", None) or next(self._next_id)
                ann = Annotation(ann_id=ann_id, **kwargs)
            elif ann.ann_id in (0, None):
                ann.ann_id = next(self._next_id)
            if ann.ann_type not in RAMON_TYPES:
                raise ValueError(f"unknown RAMON type {ann.ann_type!r}")
            self._rows[ann.ann_id] = ann
            # keep auto-ids ahead of explicit ids
            self._next_id = itertools.count(max(self._rows) + 1)
            return ann

    def get(self, ann_id: int) -> Optional[Annotation]:
        return self._rows.get(int(ann_id))

    def update(self, ann_id: int, **fields) -> Annotation:
        ann = self._rows[int(ann_id)]
        for k, v in fields.items():
            if hasattr(ann, k):
                setattr(ann, k, v)
            else:
                ann.kv[k] = v
        return ann

    def delete(self, ann_id: int) -> None:
        self._rows.pop(int(ann_id), None)

    def query(self, *predicates: Tuple[str, str, Any]) -> List[int]:
        """Conjunctive predicates: [(field, op, value), ...] -> ids.

        Paper example: ``objects/type/synapse/confidence/geq/0.99``.
        """
        out = []
        for ann_id, ann in self._rows.items():
            if all(ann.matches(f, op, v) for f, op, v in predicates):
                out.append(ann_id)
        return sorted(out)

    def __len__(self):
        return len(self._rows)


# --- the spatial annotation database ------------------------------------


class AnnotationProject:
    """One annotation database registered to an image dataset (paper §3.2).

    ``enable_exceptions`` activates per-cuboid exception tracking: every
    read then pays a small check cost (the paper notes this), and conflicting
    writes with the ``exception`` discipline are preserved per voxel.
    """

    def __init__(self, name: str, image_spec: DatasetSpec,
                 enable_exceptions: bool = False,
                 readonly: bool = False,
                 backend: Optional[Backend] = None,
                 write_path_backend: Optional[Backend] = None,
                 store_factory: Optional[Callable[[DatasetSpec], Any]] = None):
        """``store_factory(spec)`` overrides the default single-node store —
        pass e.g. ``lambda s: ClusterStore(s, n_nodes=4)`` to hold the label
        database sharded across the cluster (paper §4.1: annotation projects
        are distributed exactly like image datasets)."""
        self.name = name
        spec = dataclasses.replace(
            image_spec, name=f"{image_spec.name}/{name}",
            dtype="uint32", n_channels=1)
        self.spec = spec
        if store_factory is not None:
            self.store = store_factory(spec)
        else:
            self.store = CuboidStore(spec, backend=backend,
                                     write_path_backend=write_path_backend,
                                     compression_level=1)
        self.meta = MetadataTable()
        self.index = ObjectIndex()
        self.enable_exceptions = enable_exceptions
        self.readonly = readonly
        # (resolution, morton) -> list of (flat_voxel_offset, label)
        self._exceptions: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
        self._dirty_levels: set[int] = set()

    # -- write -------------------------------------------------------------
    def write(self, r: int, lo: Sequence[int], labels: np.ndarray,
              discipline: str = "overwrite",
              update_index: bool = True) -> None:
        """Write a labeled volume with a conflict discipline (paper §3.2).

        Annotations become visible at resolution ``r`` immediately; other
        levels are stale until :meth:`propagate` runs (deferred-consistency
        design, paper §3.2).
        """
        if self.readonly:
            raise PermissionError(f"project {self.name} is readonly")
        if discipline == "exception" and not self.enable_exceptions:
            raise ValueError("exceptions not enabled for this project")
        labels = labels.astype(np.uint32)

        exc_sink = None
        if discipline == "exception":
            def exc_sink(m, origin, old_block, new_block):
                lst = self._exceptions.setdefault((r, m), [])
                flat_new = new_block.ravel()
                for off in np.flatnonzero(flat_new):
                    lst.append((int(off), int(flat_new[off])))

        write_cutout(self.store, r, lo, labels, discipline=discipline,
                     on_conflict=exc_sink)
        self._dirty_levels.add(r)
        if update_index:
            self._update_index(r, lo, labels)

    def _update_index(self, r: int, lo: Sequence[int],
                      labels: np.ndarray) -> None:
        """Record, for every id in ``labels``, the cuboids it touches."""
        with trace.span("annotate.index"):
            grid = self.spec.grid(r)
            hi = [l + s for l, s in zip(lo, labels.shape)]
            clo, chi = grid.clamp_box(lo, hi)
            updates: Dict[int, set] = {}
            for start, stop in grid.box_to_runs(clo, chi):
                for m in range(start, stop):
                    origin = grid.cuboid_origin(m)
                    if any(o >= v for o, v in
                           zip(origin, grid.volume_shape)):
                        continue
                    b_lo = [max(0, l - o) for l, o in zip(clo, origin)]
                    b_hi = [min(c, h - o) for c, h, o in
                            zip(grid.cuboid_shape, chi, origin)]
                    if any(a >= b for a, b in zip(b_lo, b_hi)):
                        continue
                    d_lo = [o + bl - l for o, bl, l in zip(origin, b_lo, lo)]
                    d_hi = [o + bh - l for o, bh, l in zip(origin, b_hi, lo)]
                    sub = labels[tuple(slice(a, b)
                                       for a, b in zip(d_lo, d_hi))]
                    for ann_id in np.unique(sub):
                        if ann_id:
                            updates.setdefault(int(ann_id), set()).add(m)
            if updates:
                self.index.append_batch(updates)

    # -- read ---------------------------------------------------------------
    def read(self, r: int, lo: Sequence[int], hi: Sequence[int],
             with_exceptions: bool = False) -> np.ndarray:
        out = cutout(self.store, r, lo, hi)
        if self.enable_exceptions and with_exceptions:
            # exception check happens on every read once enabled (paper).
            pass  # dense array holds primary labels; exceptions via getter
        return out

    def exceptions_at(self, r: int, m: int) -> List[Tuple[int, int]]:
        return list(self._exceptions.get((r, m), ()))

    def voxel_labels(self, r: int, voxel: Sequence[int]) -> List[int]:
        """All labels at one voxel: primary + exceptions (paper §3.2)."""
        grid = self.spec.grid(r)
        m = grid.cuboid_of_voxel(voxel)
        block = self.store.read_cuboid(r, m)
        origin = grid.cuboid_origin(m)
        local = tuple(v - o for v, o in zip(voxel, origin))
        labels = []
        primary = int(block[local])
        if primary:
            labels.append(primary)
        flat = int(np.ravel_multi_index(local, grid.cuboid_shape))
        for off, lab in self._exceptions.get((r, m), ()):
            if off == flat and lab not in labels:
                labels.append(lab)
        return labels

    # -- object-level queries (paper §4.2) -----------------------------------
    def object_cutout(self, ann_id: int, r: int,
                      box: Optional[Tuple[Sequence[int], Sequence[int]]] = None
                      ) -> Tuple[List[int], np.ndarray]:
        """Dense array of one object within its bbox (others filtered out)."""
        bbox = (box or self.index.bounding_box(ann_id, self.spec.grid(r)))
        if bbox is None:
            return [0] * self.spec.spatial_rank, np.zeros(
                (0,) * self.spec.spatial_rank, np.uint32)
        lo, hi = bbox
        dense = self.read(r, lo, hi)
        mask = dense == np.uint32(ann_id)
        return list(lo), np.where(mask, dense, 0).astype(np.uint32)

    def voxel_list(self, ann_id: int, r: int) -> np.ndarray:
        """Sparse (N, rank) voxel coordinates — better for skinny objects.

        Reads the object's cuboids in one morton-sorted pass via the index
        (paper Fig 9), not a bbox cutout: for long skinny neurites the bbox
        is pathologically larger than the object.
        """
        grid = self.spec.grid(r)
        coords = []
        for start, stop in self.index.runs(ann_id):
            blocks = self.store.read_run(r, start, stop)
            for m, block in zip(range(start, stop), blocks):
                where = np.argwhere(block == np.uint32(ann_id))
                if where.size:
                    origin = np.array(grid.cuboid_origin(m))
                    coords.append(where + origin)
        if not coords:
            return np.zeros((0, grid.rank), dtype=np.int64)
        return np.concatenate(coords, axis=0)

    def objects_in_region(self, r: int, lo, hi) -> List[int]:
        """What objects are in a region? cutout + unique (paper §4.2)."""
        dense = self.read(r, lo, hi)
        ids = np.unique(dense)
        return [int(i) for i in ids if i]

    def bounding_box(self, ann_id: int, r: int):
        return self.index.bounding_box(ann_id, self.spec.grid(r))

    # -- batch interface (paper §4.2) ---------------------------------------
    def batch_write_objects(
            self, r: int,
            objects: List[Tuple[Annotation, Sequence[int], np.ndarray]],
            discipline: str = "overwrite") -> List[int]:
        """Write many (metadata, offset, labeled-volume) at once.

        The paper doubled synapse-finder throughput batching 40 writes; the
        batch path shares one index append transaction across objects.
        """
        ids = []
        with trace.span("annotate.batch") as counts:
            for ann, lo, vol in objects:
                ann = self.meta.create(ann)
                ids.append(ann.ann_id)
                vol = np.where(vol != 0, np.uint32(ann.ann_id), 0)
                self.write(r, lo, vol, discipline=discipline)
            if counts is not None:
                counts["object_voxels"] = sum(
                    int(np.count_nonzero(vol)) for _, _, vol in objects)
        return ids

    def batch_read_objects(self, ann_ids: Sequence[int], r: int):
        return {i: self.object_cutout(i, r) for i in ann_ids}

    # -- hierarchy (deferred consistency) ------------------------------------
    def propagate(self) -> None:
        """Background batch job building the annotation resolution hierarchy
        (paper §3.2: annotations visible only at write resolution until
        propagation runs)."""
        build_hierarchy(self.store, labels=True)
        self._dirty_levels.clear()

    @property
    def pending_propagation(self) -> bool:
        return bool(self._dirty_levels) and self.spec.n_resolutions > 1

    # -- spatial analysis helpers (paper §2 kasthuri11 use case) -------------
    def centroid(self, ann_id: int, r: int) -> Optional[np.ndarray]:
        vox = self.voxel_list(ann_id, r)
        return vox.mean(axis=0) if len(vox) else None

    def distance(self, a: int, b: int, r: int) -> float:
        """Min voxel-to-voxel distance between two objects (e.g. synapse to
        dendrite backbone, paper §2)."""
        va, vb = self.voxel_list(a, r), self.voxel_list(b, r)
        if not len(va) or not len(vb):
            return float("inf")
        # chunked pairwise min to bound memory
        best = np.inf
        for i in range(0, len(va), 4096):
            d = np.linalg.norm(va[i:i + 4096, None, :] - vb[None], axis=-1)
            best = min(best, float(d.min()))
        return best
