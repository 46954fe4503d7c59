"""Pallas TPU flash-decode: one-token GQA attention against a KV cache.

The §Perf Cell B analysis showed optimized decode is bound by cache reads
plus fp32 staging of scores/softmax in HBM. This kernel streams the cache
through VMEM in blocks with the online-softmax state (m, l, acc) resident
in VMEM scratch — the only HBM traffic is one pass over K and V plus the
(G, D) output, the read floor.

Grid: (B, K, nkv) — cache blocks innermost and sequential. The current
cache length arrives via scalar prefetch (SMEM) so masking is dynamic
without retracing per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG = -1e30


def _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, block_kv: int, n_kv: int, skv: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    G = q_ref.shape[2]
    D = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(F32) * scale                  # (G, D)
    kb = k_ref[0, 0].astype(F32)                         # (Bkv, D)
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)  # (G, Bkv)

    n_valid = lens_ref[b]
    pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (G, block_kv),
                                                  1)
    s = jnp.where((pos < n_valid) & (pos < skv), s, NEG)

    m_prev = m_sc[...]                                   # (G, 1)
    l_prev = l_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (G, Bkv)
    corr = jnp.exp(m_prev - m_new)                       # (G, 1)
    l_sc[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    m_sc[...] = m_new
    vb = v_ref[0, 0]                                     # (Bkv, D)
    # the MXU accumulates in f32 whatever the operand dtype
    pv = jax.lax.dot_general(p.astype(vb.dtype), vb,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=F32)   # (G, D)
    acc_sc[...] = acc_sc[...] * corr + pv

    @pl.when(j == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)


def flash_decode_kernel(q, k, v, lens, *, scale: float,
                        block_kv: int = 512, interpret: bool = False):
    """q: (B, K, G, D); k, v: (B, K, Skv, D); lens: (B,) int32.

    Returns (B, K, G, D) attention output in q.dtype.
    """
    B, K, G, D = q.shape
    Skv = k.shape[2]
    block_kv = min(block_kv, Skv)
    nkv = -(-Skv // block_kv)
    pad = nkv * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))

    kern = functools.partial(_kernel, scale=scale, block_kv=block_kv,
                             n_kv=nkv, skv=Skv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, j, lens: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, j, lens: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), F32),
            pltpu.VMEM((G, 1), F32),
            pltpu.VMEM((G, D), F32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(lens, q, k, v)
