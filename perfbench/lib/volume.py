"""The seeded EM-like volume, its resolution pyramid, and the bulk load.

``make_volume`` follows ``chip_smoke.py``'s recipe, kept here so that the
data does not move with the program; its GiB of integer work runs on the
device, which costs a run less set-up than the host's loops.
"""
from __future__ import annotations

import concurrent.futures as cf
import functools
import statistics
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def make_volume(shape: Tuple[int, int, int], seed: int, layout_seed: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """EM-like uint8 volume and its next pyramid level: grey noise from
    ``seed``; synapse-sized bright blobs placed from ``layout_seed``; one
    large bright structure (a vessel). The recipe is ``chip_smoke.py``'s
    ``make_volume``. The grey
    noise (a GiB of lookups) and the level below (the mean of each 2x2
    block in x and y, rounded down; EM keeps its z resolution) are integer
    work done on the device; the blobs are stamped on the host, as in the
    recipe."""
    from .lm_data import key_from_seed
    X, Y, Z = shape
    vol = np.array(_grey(tuple(int(s) for s in shape),
                         key_from_seed(seed, 7)))
    rng = np.random.default_rng([layout_seed, 7])
    # one blob per 64x64x16 voxels; each a 9x9x5 anisotropic Gaussian stamp
    n = max(4, X * Y * Z // (64 * 64 * 16))
    r = np.array([4, 4, 2])
    centers = rng.integers(r, np.array(shape) - r, size=(n, 3))
    off = np.stack(np.meshgrid(*[np.arange(-k, k + 1) for k in r],
                               indexing="ij"), -1).reshape(-1, 3)
    stamp = 90.0 * np.exp(-(off[:, 0] ** 2 + off[:, 1] ** 2
                            + (2 * off[:, 2]) ** 2) / 9.0)
    flat_idx = np.ravel_multi_index(
        (centers[:, None, :] + off[None]).reshape(-1, 3).T, shape)
    cells, inverse = np.unique(flat_idx, return_inverse=True)
    added = np.bincount(inverse, weights=np.tile(stamp, n))
    flat = vol.reshape(-1)
    flat[cells] = np.clip(flat[cells] + added, 0, 255).astype(np.uint8)
    x0, x1 = int(X * 0.31), int(X * 0.70)
    y0, y1 = int(Y * 0.31), int(Y * 0.39)
    sub = vol[x0:x1, y0:y1]
    np.minimum(sub, 195, out=sub)
    sub += 60
    return vol, np.asarray(_halve_xy(vol))


@functools.partial(jax.jit, static_argnums=0)
def _grey(shape, key):
    """N(100, 4) grey noise by inverse-CDF lookup of uniform bytes."""
    ppf = statistics.NormalDist(100.0, 4.0).inv_cdf
    grey = jnp.asarray([round(ppf((u + 0.5) / 256)) for u in range(256)],
                       jnp.uint8)
    return grey[jax.random.bits(key, shape, jnp.uint8)]


@jax.jit
def _halve_xy(vol):
    X, Y, Z = vol.shape
    acc = vol.reshape(X // 2, 2, Y // 2, 2, Z).astype(jnp.uint16).sum((1, 3))
    return (acc // 4).astype(jnp.uint8)


def load(store, r: int, vol: np.ndarray, workers: int = 8,
         chunk: int = 64) -> None:
    """Write ``vol`` at resolution ``r`` through the store's batch write
    (``store_cuboids``), ``chunk`` cuboids a call, ``workers`` calls at a
    time: the whole curve in Morton order."""
    grid = store.spec.grid(r)
    cells = [m for m in range(grid.n_cells)
             if all(o < v for o, v in zip(grid.cuboid_origin(m),
                                          grid.volume_shape))]

    def write(ms: Sequence[int]) -> None:
        blocks = {}
        for m in ms:
            o = grid.cuboid_origin(m)
            blocks[m] = np.ascontiguousarray(vol[tuple(
                slice(a, a + c) for a, c in zip(o, grid.cuboid_shape))])
        store.store_cuboids(r, blocks)

    with cf.ThreadPoolExecutor(max_workers=workers) as ex:
        for f in [ex.submit(write, cells[i:i + chunk])
                  for i in range(0, len(cells), chunk)]:
            f.result()
