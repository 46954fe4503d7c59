"""Cuboid->cutout assembly as a Pallas gather kernel (paper C2/C8).

The paper's §5 finding is that cutout *assembly* — not disk I/O — bounds
throughput, and that unaligned assembly (cache-hostile byte shuffles) is 2x
slower than aligned. The TPU translation: assembly = a sequence of
HBM->VMEM block copies whose source row comes from the Morton plan. The
plan (cell index per box-grid position) is a *scalar-prefetched* operand
(pltpu.PrefetchScalarGridSpec), i.e. it is available to the BlockSpec
index_map before the DMA is issued — exactly a database fetching the block
list from its spatial index (C7) and then streaming blocks.

The kernel writes whole cuboids out cuboid-major, one block per grid step;
interleaving them into the dense box is left to XLA (ops.py), as
``distributed_cutout`` does. Blocks are copied in the (cx, cz, cy) view of
each cuboid: with the paper's 128x128x16 cuboids the short z extent would
otherwise sit on the 128-wide lane axis. XLA already stores the
cuboid-major array with y minor, so the view is a bitcast, not a copy.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def cutout_gather_kernel(packed, plan, interpret: bool = False):
    """packed: (n_cells, cx, cy, cz); plan: (n_box,) int32 cell per box-grid
    position. Returns the picked cuboids, (n_box, cx, cy, cz)."""
    n_cells, cx, cy, cz = packed.shape
    (n_box,) = plan.shape
    block = (1, cx, cz, cy)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_box,),
        in_specs=[pl.BlockSpec(block, lambda g, plan_ref: (plan_ref[g], 0, 0,
                                                           0))],
        out_specs=pl.BlockSpec(block, lambda g, plan_ref: (g, 0, 0, 0)),
    )

    def _kern(plan_ref, packed_ref, out_ref):
        del plan_ref  # consumed by the index maps
        out_ref[...] = packed_ref[...]

    picked = pl.pallas_call(
        _kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_box, cx, cz, cy), packed.dtype),
        interpret=interpret,
    )(plan, packed.transpose(0, 1, 3, 2))
    return picked.transpose(0, 1, 3, 2)
