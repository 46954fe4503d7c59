"""The paper's detect-and-annotate job over the EM deployment.

Set-up builds the deployment from its configuration: the seeded volume at
resolution 0 and its 2x mean in x and y at resolution 1, loaded into a
replicated ``ClusterStore`` through the store's batch write, and an
``AnnotationProject`` whose label store is a ``ClusterStore`` of the same
layout. It computes the exclusion mask from resolution 1 as
``run_parallel_detection`` does, warms the device programs on one tile, and
starts the workers.

The workers run a copy of ``run_parallel_detection``'s per-tile work
(cutout -> ``detect_synapses`` -> one ``labels == i + 1`` object per
detection -> ``batch_write_objects`` in batches), tile by tile, so that the
window can end. Tiles are the whole-volume tiling in one fixed order
(``order_seed``), taken again from the first once all are done. The window opens once every worker has finished a tile, and
``detect_mvox_s`` counts the voxels of the tiles that finished inside it.

While a worker detects, a tap on ``synapse_mask`` and
``connected_components`` keeps what ``detect_synapses`` computed on the
way: the z-scored DoG response, the thresholded mask and the component
labels. The comparison samples, from the seed, tiles that finished in the
window, and holds each stage of the timed path against the plain reference
given that stage's input: the cutout against the volume; the response
against the float32 reference's, and the mask against the reference's own
(its z-score, threshold and exclusion), both from the volume; the labels
against an independent labelling of the timed mask; the detections against
the reference's size filter of it; and the annotations, read back through
the project and from every member of each cuboid's replica set, against
the objects written. Set-up's exclusion mask is held against the
reference's over the whole low resolution.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import traceback
import zlib
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..lib import xtrace
from ..lib.harness import Check, Window, log
from ..lib.volume import load, make_volume
from ..refs import vision as ref

Box = Tuple[Tuple[int, ...], Tuple[int, ...]]


@dataclasses.dataclass
class TileRecord:
    box: Box
    t_done: float = 0.0
    crc: int = 0
    labels: Optional[np.ndarray] = None     # detect_synapses' out_labels
    resp: Optional[np.ndarray] = None       # the tap's z-scored response
    mask: Optional[object] = None           # the tap's mask (on the device)
    cc: Optional[np.ndarray] = None         # the tap's component labels
    ids: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    spans: List[Dict] = dataclasses.field(default_factory=list)


class Tap:
    """Keeps, for each worker thread, what ``detect_synapses`` computed on
    its way: the response and mask that ``synapse_mask`` returned and the
    labels of ``connected_components``. It passes every value through."""

    NAMES = ("synapse_mask", "connected_components")

    def __init__(self, module):
        self.module, self.local = module, threading.local()
        self.orig = {n: getattr(module, n) for n in self.NAMES}

        def synapse_mask(*args, **kwargs):
            resp, mask = self.orig["synapse_mask"](*args, **kwargs)
            self.local.resp, self.local.mask = resp, mask
            return resp, mask

        def connected_components(*args, **kwargs):
            self.local.cc = self.orig["connected_components"](*args, **kwargs)
            return self.local.cc
        module.synapse_mask = synapse_mask
        module.connected_components = connected_components

    def take(self) -> Dict[str, object]:
        got = {k: getattr(self.local, k, None) for k in ("resp", "mask", "cc")}
        self.local.__dict__.clear()
        return got

    def remove(self) -> None:
        for n, f in self.orig.items():
            setattr(self.module, n, f)


def replica_mismatch(store, r: int, lo, hi, expected: np.ndarray) -> int:
    """Voxels of the box that some member of a cuboid's replica set holds
    otherwise than ``expected``: every member is read on its own."""
    grid = store.spec.grid(r)
    bad = 0
    for start, stop in grid.box_to_runs(lo, hi):
        for m in range(start, stop):
            o = grid.cuboid_origin(m)
            a = [max(x, y) for x, y in zip(lo, o)]
            b = [min(x, y + c) for x, y, c in zip(hi, o, grid.cuboid_shape)]
            if any(p >= q for p, q in zip(a, b)):
                continue
            want = expected[tuple(slice(p - x, q - x)
                                  for p, q, x in zip(a, b, lo))]
            for idx in store.router.replica_set(r, m):
                block = store.nodes[idx].read_cuboid(r, m)
                got = block[tuple(slice(p - x, q - x)
                                  for p, q, x in zip(a, b, o))]
                bad += int((got != want).sum())
    return bad


def tiling(volume: Tuple[int, ...], tile: Tuple[int, ...]) -> List[Box]:
    """Every tile of the volume, in x, y, z order (as
    ``run_parallel_detection`` builds them)."""
    out = []
    for x0 in range(0, volume[0], tile[0]):
        for y0 in range(0, volume[1], tile[1]):
            for z0 in range(0, volume[2], tile[2]):
                lo = (x0, y0, z0)
                hi = tuple(min(v, o + s) for v, o, s in zip(volume, lo, tile))
                out.append((lo, hi))
    return out


class Cell:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.vision = config["vision"]
        self.records: List[TileRecord] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.traced = False
        self.window_bounds = (0.0, 0.0)

    # ------------------------------------------------------------ set-up ----
    def setup(self) -> None:
        from repro.cluster import ClusterStore
        from repro.core.annotations import AnnotationProject
        from repro.core.cuboid import DatasetSpec
        from repro.core.cutout import cutout
        from repro.vision import synapse_detector as sd

        cfg, tr = self.config, self.traffic
        shape = tuple(cfg["volume_shape"])
        t0 = time.perf_counter()
        self.vol, self.low = make_volume(shape, self.seed,
                                         cfg["layout_seed"])
        t1 = time.perf_counter()
        spec = DatasetSpec(name=cfg["dataset"], volume_shape=shape,
                           dtype=cfg["dtype"],
                           n_resolutions=cfg["n_resolutions"],
                           base_cuboid=tuple(cfg["cuboid"]))

        def cluster(s):
            return ClusterStore(s, n_nodes=cfg["n_nodes"],
                                replication=cfg["replication"],
                                cache_bytes=cfg["cache_bytes"])

        self.store = cluster(spec)
        load(self.store, 0, self.vol)
        load(self.store, 1, self.low)
        t2 = time.perf_counter()
        self.project = AnnotationProject(tr["project"], spec,
                                         store_factory=cluster)
        self.r = tr["resolution"]
        self.lowres = tr["lowres_level"]
        lg = spec.grid(self.lowres)
        lowread = cutout(self.store, self.lowres, (0,) * 3, lg.volume_shape)
        v = self.vision
        self.excl_full = np.asarray(sd.large_structure_mask(
            jnp.asarray(lowread, jnp.float32),
            sigma=tuple(v["exclusion_sigma"]), radius=v["exclusion_radius"],
            quantile=v["exclusion_quantile"]))
        tiles = tiling(spec.grid(self.r).volume_shape, tuple(tr["tile"]))
        # one order for every seed, so that every run's window meets the
        # same tiles (the volume's content comes from the seed)
        order = np.random.default_rng(tr["order_seed"]).permutation(
            len(tiles))
        self.tiles = [tiles[i] for i in order]
        self._next = itertools.count()
        self.tap = Tap(sd)
        lo, hi = self.tiles[0]
        sd.detect_synapses(cutout(self.store, self.r, lo, hi),
                           threshold=self.vision["threshold"],
                           min_voxels=self.vision["min_voxels"],
                           max_voxels=self.vision["max_voxels"],
                           exclusion_mask=self.scale_mask(lo, hi))
        self.tap.take()
        t3 = time.perf_counter()
        log(f"em.detect set-up: volume {t1 - t0:.2f} s, load {t2 - t1:.2f} s,"
            f" exclusion + warm-up {t3 - t2:.2f} s")
        for i in range(tr["workers"]):
            th = threading.Thread(target=self._worker, name=f"detect-{i}",
                                  daemon=True)
            th.start()
            self._threads.append(th)
        # pre-roll: the window opens on a steady pipeline, once as many
        # tiles as there are workers have finished
        while self._done_count() < tr["workers"]:
            if not any(th.is_alive() for th in self._threads):
                raise RuntimeError("every detection worker stopped")
            time.sleep(0.05)
        log(f"em.detect pre-roll {time.perf_counter() - t3:.2f} s")

    def scale_mask(self, lo, hi):
        """Copy of ``run_parallel_detection``'s exclusion-mask scaling."""
        f = 1 << (self.lowres - self.r)
        sub = self.excl_full[lo[0] // f:max(lo[0] // f + 1, -(-hi[0] // f)),
                             lo[1] // f:max(lo[1] // f + 1, -(-hi[1] // f)),
                             lo[2]:hi[2]]
        out = np.repeat(np.repeat(sub, f, axis=0), f, axis=1)
        return out[:hi[0] - lo[0], :hi[1] - lo[1], :hi[2] - lo[2]]

    def _done_count(self) -> int:
        with self._lock:
            return sum(1 for rec in self.records if rec.t_done)

    # ----------------------------------------------------------- workers ----
    def _take(self) -> Optional[int]:
        with self._lock:
            if self._stop.is_set():
                return None
            # past the last tile the job starts again from the first
            return next(self._next) % len(self.tiles)

    def _worker(self) -> None:
        from repro.obs import trace as obs_trace
        while True:
            i = self._take()
            if i is None:
                return
            rec = TileRecord(self.tiles[i])
            with self._lock:
                self.records.append(rec)
            try:
                if self.traced:
                    ring = obs_trace.SpanRing(1 << 14)
                    ctx = obs_trace.TraceContext(f"tile{i}", ring)
                    with obs_trace.activate(ctx):
                        self._work(rec)
                    rec.spans = ring.spans_for(ctx.trace_id)
                else:
                    self._work(rec)
            except Exception:  # a failed tile is counted, and the run goes on
                rec.error = traceback.format_exc()
                log(f"tile {rec.box} failed:\n{rec.error}")
            rec.t_done = time.perf_counter()

    def _work(self, rec: TileRecord) -> None:
        """Copy of ``run_parallel_detection``'s per-tile ``work``."""
        from repro.core.annotations import Annotation
        from repro.core.cutout import cutout
        from repro.vision import synapse_detector as sd

        lo, hi = rec.box
        v = self.vision
        with xtrace.span("cutout"):
            vol = cutout(self.store, self.r, lo, hi)
        rec.crc = zlib.crc32(np.ascontiguousarray(vol))
        with xtrace.span("detect"):
            dets, labels = sd.detect_synapses(
                vol, threshold=v["threshold"], min_voxels=v["min_voxels"],
                max_voxels=v["max_voxels"],
                exclusion_mask=self.scale_mask(lo, hi))
        rec.labels = labels
        got = self.tap.take()
        # the host copies detect_synapses made; the mask stays on the device
        rec.resp = None if got["resp"] is None else np.asarray(got["resp"])
        rec.cc = None if got["cc"] is None else np.asarray(got["cc"])
        rec.mask = got["mask"]
        if not dets:
            return
        with xtrace.span("write"):
            objs = []
            for i, d in enumerate(dets):
                sub = (labels == i + 1).astype(np.uint32)
                objs.append((Annotation(0, ann_type="synapse",
                                        confidence=d.confidence,
                                        kv={"n_voxels": d.n_voxels}),
                             lo, sub))
            bs = self.traffic["batch_size"]
            for i in range(0, len(objs), bs):
                rec.ids.extend(self.project.batch_write_objects(
                    self.r, objs[i:i + bs]))

    # ------------------------------------------------------------ window ----
    def window(self, seconds: float, traced: bool) -> Window:
        self.traced = traced
        with self._lock:  # tiles done before the window are never checked
            for rec in self.records:
                if rec.t_done:
                    rec.resp = rec.mask = rec.cc = None
        t0 = time.perf_counter()
        time.sleep(seconds)
        t1 = time.perf_counter()
        self._stop.set()
        self.window_bounds = (t0, t1)
        inside = self.window_tiles()
        vox = sum(int(np.prod([b - a for a, b in zip(*rec.box)]))
                  for rec in inside if rec.error is None)
        failed = sum(1 for rec in inside if rec.error is not None)
        return Window(metrics={"detect_mvox_s": vox / (t1 - t0) / 1e6},
                      attempted=len(inside), failed=failed)

    def window_tiles(self) -> List[TileRecord]:
        """Tiles that finished inside the window."""
        t0, t1 = self.window_bounds
        return [rec for rec in self.records if t0 <= rec.t_done <= t1]

    def release(self) -> None:
        """Lets the tiles in flight at the window's close finish (nothing
        of the program lives on the device between tiles)."""
        for th in self._threads:
            th.join(timeout=120)
        if any(th.is_alive() for th in self._threads):
            raise RuntimeError("detection workers did not finish within "
                               "120 s of the window's close")

    # ------------------------------------------------------------- check ----
    def sample(self) -> List[TileRecord]:
        """Tiles that finished in the window, drawn from the seed, among
        those whose annotations no later pass over the tile rewrote."""
        latest = {}
        for rec in self.records:
            if rec.t_done >= latest.get(rec.box, rec).t_done:
                latest[rec.box] = rec
        inside = sorted((rec for rec in self.window_tiles()
                         if latest[rec.box] is rec), key=lambda rec: rec.box)
        k = min(self.traffic["check_tiles"], len(inside))
        rng = np.random.default_rng([self.seed, 1])
        return [inside[i] for i in sorted(rng.choice(len(inside), k,
                                                     replace=False))]

    def checks(self, precision: str = "f32") -> List[Check]:
        """The comparison. ``precision="fp8"`` puts the float8 reference in
        the program's place (the control): its response, mask and
        exclusion are compared; the stages after the mask are the
        reference's own there, and read 0."""
        v, limits = self.vision, self.traffic["limits"]
        thr, f = v["threshold"], 1 << (self.lowres - self.r)
        args = (tuple(v["sigma1"]), tuple(v["sigma2"]), v["radius"])
        ex = (tuple(v["exclusion_sigma"]), v["exclusion_radius"],
              v["exclusion_quantile"])
        control = precision != "f32"
        low = jnp.asarray(self.low, jnp.float32)
        smooth, q = ref.exclusion_smooth(low, *ex, "f32")
        excl = self.excl_full
        if control:
            c_smooth, c_q = ref.exclusion_smooth(low, *ex, precision)
            excl = np.asarray(c_smooth >= c_q)
            del c_smooth
        excl_edge = float(ref.edge_gap(excl, smooth, q))
        del low
        sample = self.sample()
        n = {"tile_wrong": 0, "label_wrong": 0, "det_wrong": 0,
             "ann_wrong": 0}
        resp_gap = mask_edge = 0.0
        missing = 0
        for rec in sample:
            lo, hi = rec.box
            want = self.vol[tuple(slice(a, b) for a, b in zip(lo, hi))]
            n["tile_wrong"] += int(rec.crc != zlib.crc32(
                np.ascontiguousarray(want)))
            x = jnp.asarray(want, jnp.float32)
            r_ref = ref.zscore(ref.dog_jit(x, *args, "f32"))
            e_ref = ref.at_res0(smooth, lo, hi, f) >= q
            m_ref = (r_ref > thr) & ~e_ref
            if control:
                e_got = ref.at_res0(excl, lo, hi, f)
                r_got = ref.zscore(ref.dog_jit(x, *args, precision))
                m_got = (r_got > thr) & ~e_got
            elif rec.resp is None or rec.mask is None or rec.cc is None:
                missing += 1
                continue
            else:
                e_got = self.scale_mask(lo, hi)
                r_got, m_got = rec.resp, np.asarray(rec.mask)
            resp_gap = max(resp_gap, ref.relative_gap(r_got, r_ref))
            mask_edge = max(mask_edge, ref.flip_edge(m_got, m_ref, r_ref, thr,
                                                     e_got == e_ref))
            if control:
                continue
            comps = ref.label(m_got)
            n["label_wrong"] += ref.partition_mismatch(rec.cc, comps)
            kept = ref.size_filter(comps, v["min_voxels"], v["max_voxels"])
            n["det_wrong"] += ref.partition_mismatch(rec.labels, kept)
            expected = np.zeros(want.shape, np.uint32)
            for k, ann_id in enumerate(rec.ids):
                expected[rec.labels == k + 1] = ann_id
            got = self.project.read(self.r, lo, hi)
            n["ann_wrong"] += int((got != expected).sum()) + replica_mismatch(
                self.project.store, self.r, lo, hi, expected)
        failed = sum(1 for rec in self.window_tiles() if rec.error)
        return ([Check("no_tile_checked", 0 if sample else 1, 0),
                 Check("tiles_failed", failed, 0),
                 Check("response_missing", missing, 0)]
                + [Check(k, val, limits[k]) for k, val in n.items()]
                + [Check(k, val, limits[k]) for k, val in
                   (("resp_gap", resp_gap), ("mask_edge", mask_edge),
                    ("excl_edge", excl_edge))])

    def calibration(self, control: bool = True) -> Dict:
        """The program's numbers and, with ``control``, the control's (the
        float8 reference in the program's place)."""
        out = {"program": {c.name: c.value for c in self.checks()}}
        if control:
            out["control"] = {c.name: c.value for c in self.checks("fp8")}
        return out

    def close(self) -> None:
        self._stop.set()
        for th in self._threads:
            th.join(timeout=120)
        if getattr(self, "tap", None) is not None:
            self.tap.remove()
        for s in (getattr(self, "store", None),
                  getattr(getattr(self, "project", None), "store", None)):
            if s is not None:
                s.close()

