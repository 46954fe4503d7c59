"""Parallel synapse detection — the paper's driving application (§2, Fig 1).

The paper extracted 19M synapse detections from the bock11 volume with 20
parallel workers reading cutouts and issuing small annotation writes. We
reproduce the *pipeline shape* in JAX:

  workers ->  cutout (read path)  ->  DoG blob filter + threshold
          ->  connected components (label propagation, jax.lax loop)
          ->  size filter (synapses span tens of voxels, §3.1)
          ->  large-structure false-positive mask from a LOW resolution
              level (paper: blood vessels/cell bodies at res 5)
          ->  batch annotation writes (write path / SSD node)

Everything numeric is jittable; workers are host threads, matching the
paper's concurrency model (parallel Web-service requests).
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.annotations import Annotation, AnnotationProject
from ..core.cutout import cutout
from ..core.store import CuboidStore
from ..obs import trace


def _gauss_kernel(sigma: float, radius: int) -> jnp.ndarray:
    x = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (x / max(sigma, 1e-6)) ** 2)
    return k / k.sum()


@functools.partial(jax.jit, static_argnames=("sigmas", "radius"))
def gaussian_blur(vol: jnp.ndarray, sigmas: Tuple[float, ...],
                  radius: int = 4) -> jnp.ndarray:
    """Separable anisotropic Gaussian blur (sigma per dim; EM Z is coarse)."""
    out = vol.astype(jnp.float32)
    for d, s in enumerate(sigmas):
        if s <= 0:
            continue
        k = _gauss_kernel(s, radius)
        moved = jnp.moveaxis(out, d, -1)
        flat = moved.reshape(-1, moved.shape[-1])
        pad = jnp.pad(flat, ((0, 0), (radius, radius)), mode="edge")
        conv = jax.vmap(lambda row: jnp.convolve(row, k, mode="valid"))(pad)
        out = jnp.moveaxis(conv.reshape(moved.shape), -1, d)
    return out


@functools.partial(jax.jit, static_argnames=("sigma1", "sigma2", "radius"))
def difference_of_gaussians(vol, sigma1=(1.0, 1.0, 0.5),
                            sigma2=(3.0, 3.0, 1.5), radius=4):
    """Band-pass blob response; synapses are bright compact blobs."""
    return gaussian_blur(vol, sigma1, radius) - gaussian_blur(
        vol, sigma2, radius)


@functools.partial(jax.jit, static_argnames=("connectivity",))
def _connected_components_sweeps(mask: jnp.ndarray, connectivity: int = 6):
    """Label 3-d connected components by iterative min-label propagation.

    Each foreground voxel starts with its flat index + 1; every sweep takes
    the min over face neighbors; a `lax.while_loop` runs to fixpoint. On TPU
    this is embarrassingly vectorizable (shifts + minimum) — the adaptation
    of a classically pointer-chasing CPU algorithm to SIMD hardware.
    Returns the labels and the loop's sweep count (the sweep that finds
    nothing changed included, the first propagation before the loop not).
    """
    fg = mask != 0
    init = jnp.where(
        fg, jnp.arange(1, mask.size + 1,
                       dtype=jnp.int32).reshape(mask.shape), 0)
    big = jnp.int32(mask.size + 2)

    def neighbor_min(lab):
        padded = jnp.where(fg, lab, big)
        best = padded
        for d in range(mask.ndim):
            for shift in (1, -1):
                rolled = jnp.roll(padded, shift, axis=d)
                # zero-pad the wrap-around plane
                idx = 0 if shift == 1 else -1
                rolled = _set_plane(rolled, d, idx, big)
                best = jnp.minimum(best, rolled)
        return jnp.where(fg, jnp.minimum(lab, best), 0)

    def cond(state):
        lab, prev, it = state
        return jnp.logical_and(jnp.any(lab != prev), it < mask.size)

    def body(state):
        lab, _, it = state
        return neighbor_min(lab), lab, it + 1

    lab, _, it = jax.lax.while_loop(
        cond, body, (neighbor_min(init), init, jnp.int32(0)))
    return lab, it


def connected_components(mask: jnp.ndarray,
                         connectivity: int = 6) -> jnp.ndarray:
    """Component labels of ``mask`` (see `_connected_components_sweeps`).

    A sampled trace records the sweep count as ``sweeps`` on the enclosing
    span; untraced, the count is never read back."""
    lab, sweeps = _connected_components_sweeps(mask, connectivity)
    if trace.current() is not None:
        trace.annotate(sweeps=int(sweeps))
    return lab


def _set_plane(arr, axis, idx, value):
    sl = [slice(None)] * arr.ndim
    sl[axis] = idx
    return arr.at[tuple(sl)].set(value)


@functools.partial(jax.jit, static_argnames=("sigma", "radius", "quantile"))
def large_structure_mask(lowres_vol, sigma=(6.0, 6.0, 3.0), radius=8,
                         quantile=0.9):
    """Mask of large bright structures (vessels, somata) at low resolution.

    Paper §3.1: computed at res 5 where 'structures are large and detectable
    at low resolution and the computation requires all data in memory'.
    The heavy blur is what makes this selective for LARGE structures:
    synapse-scale blobs wash out, vessel/soma-scale structures persist.
    """
    smooth = gaussian_blur(lowres_vol, sigma, radius)
    thr = jnp.quantile(smooth, quantile)
    return smooth >= thr


@dataclasses.dataclass
class Detection:
    centroid: Tuple[float, ...]
    n_voxels: int
    bbox_lo: Tuple[int, ...]
    bbox_hi: Tuple[int, ...]
    confidence: float


def synapse_mask(vol, threshold: float = 2.0,
                 exclusion_mask: Optional[np.ndarray] = None):
    """DoG response z-scored over the cutout, and its thresholded mask."""
    x = jnp.asarray(vol, dtype=jnp.float32)
    resp = difference_of_gaussians(x)
    resp = (resp - resp.mean()) / (resp.std() + 1e-6)
    mask = resp > threshold
    if exclusion_mask is not None:
        mask = jnp.logical_and(mask, ~jnp.asarray(exclusion_mask))
    return resp, mask


def detect_synapses(vol: np.ndarray, threshold: float = 2.0,
                    min_voxels: int = 8, max_voxels: int = 512,
                    exclusion_mask: Optional[np.ndarray] = None
                    ) -> Tuple[List[Detection], np.ndarray]:
    """Detect synapse-like blobs in one cutout. Returns detections + labels."""
    with trace.span("detect.device"):
        resp, mask = synapse_mask(vol, threshold, exclusion_mask)
        labels = np.asarray(connected_components(mask))
        resp_np = np.asarray(resp)
    dets: List[Detection] = []
    out_labels = np.zeros_like(labels)
    next_id = 1
    with trace.span("detect.group"):
        # group foreground voxels by label with one stable sort: each group
        # lists its voxels in C order, as np.argwhere(labels == lab) would,
        # without a whole-volume scan per label
        fg = np.flatnonzero(labels)
        order = np.argsort(labels.ravel()[fg], kind="stable")
        labs, starts, sizes = np.unique(labels.ravel()[fg][order],
                                        return_index=True, return_counts=True)
        for lab, start, n in zip(labs, starts, sizes):
            if not (min_voxels <= n <= max_voxels):
                continue  # too small = noise; too big = not a synapse (§3.1)
            where = np.stack(np.unravel_index(fg[order[start:start + n]],
                                              labels.shape), axis=1)
            lo = where.min(axis=0)
            hi = where.max(axis=0) + 1
            conf = float(1.0 / (1.0 + np.exp(
                -resp_np[tuple(where.T)].mean())))
            dets.append(Detection(tuple(where.mean(axis=0)), int(n),
                                  tuple(int(v) for v in lo),
                                  tuple(int(v) for v in hi), conf))
            out_labels[tuple(where.T)] = next_id
            next_id += 1
    return dets, out_labels


def run_parallel_detection(image_store: CuboidStore,
                           project: AnnotationProject,
                           r: int, tile: Sequence[int],
                           n_workers: int = 4,
                           threshold: float = 2.0,
                           min_voxels: int = 8,
                           batch_size: int = 40,
                           lowres_level: Optional[int] = None) -> int:
    """The full paper workflow: parallel workers over a tiling of the volume.

    Each worker: cutout -> detect -> batch-write annotations (batch of 40,
    the size the paper found doubled synapse-finder throughput).
    Returns number of synapses written.
    """
    grid = image_store.spec.grid(r)
    vol_shape = grid.volume_shape
    tiles = []
    t = list(tile)
    for x0 in range(0, vol_shape[0], t[0]):
        for y0 in range(0, vol_shape[1], t[1]):
            for z0 in range(0, vol_shape[2], t[2]):
                lo = (x0, y0, z0)
                hi = tuple(min(v, o + s)
                           for v, o, s in zip(vol_shape, lo, t))
                tiles.append((lo, hi))

    excl_full = None
    if lowres_level is not None and lowres_level < image_store.spec.n_resolutions:
        lg = image_store.spec.grid(lowres_level)
        low = cutout(image_store, lowres_level, (0,) * 3, lg.volume_shape)
        excl_full = np.asarray(large_structure_mask(
            jnp.asarray(low, jnp.float32)))

    def scale_mask(lo, hi):
        if excl_full is None:
            return None
        f = 1 << (lowres_level - r)
        sub = excl_full[lo[0] // f:max(lo[0] // f + 1, -(-hi[0] // f)),
                        lo[1] // f:max(lo[1] // f + 1, -(-hi[1] // f)),
                        lo[2]:hi[2]]
        out = np.repeat(np.repeat(sub, f, axis=0), f, axis=1)
        return out[:hi[0] - lo[0], :hi[1] - lo[1], :hi[2] - lo[2]]

    total = 0

    def work(box):
        nonlocal total
        lo, hi = box
        vol = cutout(image_store, r, lo, hi)
        dets, labels = detect_synapses(
            vol, threshold=threshold, min_voxels=min_voxels,
            exclusion_mask=scale_mask(lo, hi))
        if not dets:
            return 0
        # batch writes of `batch_size` objects (paper §4.2)
        objs = []
        for i, d in enumerate(dets):
            sub = (labels == i + 1).astype(np.uint32)
            objs.append((Annotation(0, ann_type="synapse",
                                    confidence=d.confidence,
                                    kv={"n_voxels": d.n_voxels}),
                         lo, sub))
        written = 0
        for i in range(0, len(objs), batch_size):
            ids = project.batch_write_objects(r, objs[i:i + batch_size])
            written += len(ids)
        return written

    with cf.ThreadPoolExecutor(max_workers=n_workers) as ex:
        for n in ex.map(work, tiles):
            total += n
    return total
