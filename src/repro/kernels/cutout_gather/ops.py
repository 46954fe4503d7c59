"""jit'd cutout wrapper: box -> Morton plan -> gather kernel -> merge."""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core import morton
from ...core.cuboid import CuboidGrid
from ...core.distributed import merge_blocks
from .kernel import cutout_gather_kernel


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def build_plan(grid: CuboidGrid, lo: Sequence[int], hi: Sequence[int]):
    """Static part of a cutout: box-grid shape + Morton cell per position."""
    cs = grid.cuboid_shape
    glo = [l // c for l, c in zip(lo, cs)]
    ghi = [-(-h // c) for h, c in zip(hi, cs)]
    gshape = tuple(h - l for l, h in zip(glo, ghi))
    mesh_idx = np.meshgrid(*[np.arange(l, h) for l, h in zip(glo, ghi)],
                           indexing="ij")
    coords = np.stack([g.ravel() for g in mesh_idx], axis=-1)
    cells = morton.morton_encode(coords, grid.bits).astype(np.int32)
    return gshape, cells, [g * c for g, c in zip(glo, cs)]


@functools.partial(jax.jit, static_argnames=("gshape", "lo", "hi",
                                             "interpret"))
def _gather(packed, cells, *, gshape, lo, hi, interpret):
    picked = cutout_gather_kernel(packed, cells, interpret=interpret)
    return merge_blocks(picked.reshape(gshape + packed.shape[1:]), lo, hi)


def cutout_gather(packed, grid: CuboidGrid, lo, hi, *, interpret=None):
    """Dense cutout [lo, hi) from a cuboid-major device array."""
    lo = tuple(int(x) for x in lo)
    hi = tuple(int(x) for x in hi)
    interpret = _interpret_default() if interpret is None else interpret
    gshape, cells, alo = build_plan(grid, lo, hi)
    # only the box's offset within its first cuboid shapes the program
    return _gather(packed, jnp.asarray(cells), gshape=gshape,
                   lo=tuple(l - a for l, a in zip(lo, alo)),
                   hi=tuple(h - a for h, a in zip(hi, alo)),
                   interpret=interpret)
