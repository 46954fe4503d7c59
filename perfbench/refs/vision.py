"""Plain reference for the detection pipeline's numbers.

Independent of the program: a float32 difference of Gaussians by shifted
multiply-adds (elementwise float32 on every backend, no matrix unit), its
z-score over the tile in float64, the exclusion of large structures (a
float32 blur of the low resolution and its quantile), each resolution-0
voxel taking its low-resolution voxel, an independent connected-component
labelling (``scipy.ndimage.label``), and the size filter.
``precision="fp8"`` is the control: every pass's operand and kernel weights
rounded to float8 (e4m3, one scale per tensor), the step below the
program's bfloat16 products.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from scipy import ndimage

F32 = jnp.float32
# largest finite float8_e4m3fn
_E4M3_MAX = 448.0


def gauss_kernel(sigma: float, radius: int) -> np.ndarray:
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (x / max(sigma, 1e-6)) ** 2)
    return (k / k.sum()).astype(np.float32)


def to_fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def blur(x, sigmas: Sequence[float], radius: int, precision: str = "f32"):
    out = jnp.asarray(x, F32)
    for d, s in enumerate(sigmas):
        if s <= 0:
            continue
        k = jnp.asarray(gauss_kernel(s, radius))
        if precision == "fp8":
            out, k = to_fp8(out), to_fp8(k)
        pad = [(0, 0)] * out.ndim
        pad[d] = (radius, radius)
        xp = jnp.pad(out, pad, mode="edge")
        n = out.shape[d]
        acc = jnp.zeros_like(out)
        for j in range(2 * radius + 1):
            acc = acc + k[j] * jax.lax.slice_in_dim(xp, j, j + n, axis=d)
        out = acc
    return out


def dog(x, sigma1: Sequence[float], sigma2: Sequence[float], radius: int,
        precision: str = "f32"):
    """Difference of Gaussians in float32 (or the float8 control)."""
    return (blur(x, sigma1, radius, precision)
            - blur(x, sigma2, radius, precision))


dog_jit = jax.jit(dog, static_argnums=(1, 2, 3, 4))


def zscore(d) -> np.ndarray:
    """The response over its tile's mean and standard deviation."""
    d = np.asarray(d, np.float64)
    return (d - d.mean()) / (d.std() + 1e-6)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def exclusion_smooth(low, sigma: Sequence[float], radius: int,
                     quantile: float, precision: str = "f32"):
    """The blurred low-resolution volume and its ``quantile``: voxels at or
    above it are large structures, excluded from detection."""
    smooth = blur(low, sigma, radius, precision)
    return smooth, jnp.quantile(smooth, quantile)


@jax.jit
def edge_gap(got, smooth, q):
    """Largest |smooth - q| over the voxels where the exclusion ``got``
    differs from ``smooth >= q`` (0 where none does), over max |smooth|."""
    off = got != (smooth >= q)
    return (jnp.max(jnp.where(off, jnp.abs(smooth - q), 0.0))
            / jnp.max(jnp.abs(smooth)))


def at_res0(low, lo: Sequence[int], hi: Sequence[int], f: int) -> np.ndarray:
    """Voxels ``lo``..``hi`` of resolution 0 of a volume given ``f`` times
    coarser in x and y: each voxel takes the low voxel it lies in."""
    idx = [np.arange(lo[0], hi[0]) // f, np.arange(lo[1], hi[1]) // f,
           np.arange(lo[2], hi[2])]
    block = np.asarray(low[tuple(slice(i[0], i[-1] + 1) for i in idx)])
    return block[np.ix_(*(i - i[0] for i in idx))]


def flip_edge(got, want, resp, threshold: float, where) -> float:
    """Largest |resp - threshold| over the voxels, among ``where``, on
    which the masks ``got`` and ``want`` differ (0 where none does), over
    max |resp|. Where ``got`` thresholds a response within g max |resp| of
    ``resp``, it reads at most g."""
    off = (np.asarray(got, bool) != np.asarray(want, bool)) & where
    if not off.any():
        return 0.0
    return float(np.abs(resp[off] - threshold).max()
                 / max(np.abs(resp).max(), 1e-30))


def label(mask: np.ndarray) -> np.ndarray:
    """Face-connected (6-neighbour in 3-d) components, labels 1..n."""
    structure = ndimage.generate_binary_structure(mask.ndim, 1)
    labels, _ = ndimage.label(np.asarray(mask, bool), structure=structure)
    return labels


def size_filter(labels: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Keep components with lo <= voxels <= hi; others become 0."""
    sizes = np.bincount(labels.ravel())
    keep = (sizes >= lo) & (sizes <= hi)
    keep[0] = False
    return np.where(keep[labels], labels, 0)


def partition_mismatch(a: np.ndarray, b: np.ndarray) -> int:
    """Voxels on which two labellings (0 = background) do not describe the
    same partition: a voxel counts unless both label it and its two labels
    correspond one to one."""
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    fg = (a > 0) | (b > 0)
    pa, pb = a[fg].astype(np.int64), b[fg].astype(np.int64)
    if pa.size == 0:
        return 0
    pairs = np.unique(np.stack([pa, pb], 1), axis=0)
    a_ids, a_deg = np.unique(pairs[:, 0], return_counts=True)
    b_ids, b_deg = np.unique(pairs[:, 1], return_counts=True)
    a_one = dict(zip(a_ids.tolist(), (a_deg == 1).tolist()))
    b_one = dict(zip(b_ids.tolist(), (b_deg == 1).tolist()))
    good_pair = {(x, y) for x, y in pairs.tolist()
                 if x > 0 and y > 0 and a_one[x] and b_one[y]}
    if not good_pair:
        return int(pa.size)
    key = pa * (int(pb.max()) + 1) + pb
    good_keys = np.array([x * (int(pb.max()) + 1) + y for x, y in good_pair])
    return int(pa.size - np.isin(key, good_keys).sum())


def relative_gap(got, want) -> float:
    """max |got - want| over max |want|."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
