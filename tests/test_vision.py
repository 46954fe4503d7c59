"""Synapse-detection pipeline tests (paper §2 application)."""
import numpy as np
import jax.numpy as jnp

from repro.core.annotations import AnnotationProject
from repro.core.cuboid import DatasetSpec
from repro.core.cutout import ingest
from repro.core.store import CuboidStore, MemoryBackend
from repro.vision import (connected_components, detect_synapses,
                          gaussian_blur, run_parallel_detection)
from repro.vision.synapse_detector import synapse_mask


def test_gaussian_blur_preserves_mean():
    rng = np.random.default_rng(0)
    vol = rng.random((16, 16, 8), dtype=np.float32)
    out = np.asarray(gaussian_blur(jnp.asarray(vol), (1.0, 1.0, 0.5)))
    assert out.shape == vol.shape
    assert abs(out.mean() - vol.mean()) < 0.02


def test_connected_components_two_blobs():
    mask = np.zeros((12, 12, 4), dtype=bool)
    mask[1:4, 1:4, 1:3] = True
    mask[8:11, 8:11, 1:3] = True
    lab = np.asarray(connected_components(jnp.asarray(mask)))
    ids = set(np.unique(lab)) - {0}
    assert len(ids) == 2
    a = lab[2, 2, 1]
    b = lab[9, 9, 1]
    assert a != b
    assert (lab[1:4, 1:4, 1:3] == a).all()
    assert (lab[8:11, 8:11, 1:3] == b).all()


def test_connected_components_diagonal_not_connected():
    mask = np.zeros((6, 6, 2), dtype=bool)
    mask[0, 0, 0] = True
    mask[1, 1, 0] = True  # diagonal neighbor: 6-connectivity keeps separate
    lab = np.asarray(connected_components(jnp.asarray(mask)))
    assert lab[0, 0, 0] != lab[1, 1, 0]


def synthetic_volume(shape=(48, 48, 16), n_blobs=5, seed=3):
    rng = np.random.default_rng(seed)
    vol = rng.normal(100, 3, size=shape).astype(np.float32)
    centers = []
    for _ in range(n_blobs):
        c = [rng.integers(6, s - 6) for s in shape]
        centers.append(c)
        xx, yy, zz = np.ogrid[:shape[0], :shape[1], :shape[2]]
        d2 = ((xx - c[0]) ** 2 + (yy - c[1]) ** 2 + ((zz - c[2]) * 2) ** 2)
        vol += 80.0 * np.exp(-d2 / 8.0)
    return vol, centers


def test_detect_synapses_finds_planted_blobs():
    vol, centers = synthetic_volume()
    dets, labels = detect_synapses(vol, threshold=2.0, min_voxels=4)
    assert len(dets) >= len(centers) - 1  # allow one merge/miss
    # every detection is near a planted center
    for d in dets:
        dist = min(np.linalg.norm(np.array(d.centroid) - np.array(c))
                   for c in centers)
        assert dist < 6.0
    assert labels.max() == len(dets)


def test_parallel_detection_end_to_end():
    vol, centers = synthetic_volume(shape=(64, 64, 16), n_blobs=6)
    spec = DatasetSpec(name="em", volume_shape=vol.shape, dtype="float32",
                       base_cuboid=(16, 16, 8))
    store = CuboidStore(spec)
    ingest(store, 0, vol)
    proj = AnnotationProject("syn", spec,
                             write_path_backend=MemoryBackend())
    n = run_parallel_detection(store, proj, r=0, tile=(32, 32, 16),
                               n_workers=3, threshold=2.0, min_voxels=4)
    assert n >= 4
    # written through the write path (SSD node), queryable by predicate
    ids = proj.meta.query(("ann_type", "eq", "synapse"))
    assert len(ids) == n
    hi_conf = proj.meta.query(("ann_type", "eq", "synapse"),
                              ("confidence", "geq", 0.5))
    assert set(hi_conf) <= set(ids)
    # spatial index lets us pull each object back
    some = ids[0]
    vox = proj.voxel_list(some, 0)
    assert len(vox) >= 4


def _detect_per_label_scan(vol, min_voxels=8, max_voxels=512):
    """Reference: one whole-volume scan per label."""
    resp, mask = synapse_mask(vol)
    labels = np.asarray(connected_components(mask))
    resp_np = np.asarray(resp)
    dets, out, next_id = [], np.zeros_like(labels), 1
    for lab in np.unique(labels):
        if lab == 0:
            continue
        where = np.argwhere(labels == lab)
        n = len(where)
        if not (min_voxels <= n <= max_voxels):
            continue
        conf = float(1.0 / (1.0 + np.exp(-resp_np[tuple(where.T)].mean())))
        dets.append((tuple(where.mean(axis=0)), n,
                     tuple(int(v) for v in where.min(axis=0)),
                     tuple(int(v) for v in where.max(axis=0) + 1), conf))
        out[tuple(where.T)] = next_id
        next_id += 1
    return dets, out


def test_detect_synapses_matches_per_label_scan():
    rng = np.random.default_rng(5)
    vol = rng.normal(100, 4, size=(48, 40, 12)).astype(np.float32)
    xx, yy, zz = np.ogrid[:48, :40, :12]
    for c in rng.integers((4, 4, 2), (44, 36, 10), size=(10, 3)):
        d2 = (xx - c[0]) ** 2 + (yy - c[1]) ** 2 + ((zz - c[2]) * 2) ** 2
        vol += 90.0 * np.exp(-d2 / 9.0)
    dets, labels = detect_synapses(vol)
    want, want_labels = _detect_per_label_scan(vol)
    assert len(want) >= 5
    assert [(d.centroid, d.n_voxels, d.bbox_lo, d.bbox_hi, d.confidence)
            for d in dets] == want
    np.testing.assert_array_equal(labels, want_labels)
