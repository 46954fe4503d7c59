"""Pallas TPU flash attention (GQA, causal/window) with explicit VMEM tiling.

Grid: (B, K, nq, nkv) — kv innermost so the online-softmax state for one
query tile lives in VMEM scratch across kv steps (classic Pallas flash
layout). Query tiles carry the G grouped heads with them (GQA: each KV head
serves G query heads), so the MXU sees (G*Bq, D) x (D, Bkv) matmuls.

Causal/window tiles that are fully masked are skipped with ``pl.when`` —
the locality analogue at the schedule level: never touch blocks the query
tile cannot see.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_kv: int, n_kv: int, sq: int, skv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    G = q_ref.shape[2]
    D = q_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_pos = (skv - sq) + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = kj * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)

    live = None
    if causal:
        # tile live unless its newest q row precedes its oldest k col
        live = ((skv - sq) + (qi + 1) * block_q - 1) >= kj * block_kv
    if window is not None:
        # tile dead when even its oldest q row is past the window
        live_w = ((skv - sq) + qi * block_q) - (
            (kj + 1) * block_kv - 1) < window
        live = live_w if live is None else jnp.logical_and(live, live_w)
    if live is None:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].reshape(G * block_q, D)          # (G*Bq, D)
        k = k_ref[0, 0]                                  # (Bkv, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32) * scale, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(G, block_q, block_kv)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        mask &= k_pos < skv                              # kv padding
        s = jnp.where(mask[None], s, NEG)

        m_prev = m_sc[...]                               # (G, Bq)
        l_prev = l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + p.sum(axis=-1)
        m_sc[...] = m_new
        # the MXU accumulates in f32 whatever the operand dtype
        pv = jax.lax.dot_general(
            p.reshape(G * block_q, block_kv).astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(G, block_q, D)
        acc_sc[...] = acc_sc[...] * corr[..., None] + pv

    @pl.when(kj == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool, scale: float,
                           window: Optional[int] = None,
                           block_q: int = 256, block_kv: int = 512,
                           interpret: bool = False):
    """q: (B, K, G, Sq, D); k, v: (B, K, Skv, D) -> (B, K, G, Sq, D)."""
    B, K, G, Sq, D = q.shape
    Skv = k.shape[2]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = -(-Sq // block_q)
    nkv = -(-Skv // block_kv)
    pad_q = nq * block_q - Sq
    pad_kv = nkv * block_kv - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))

    grid = (B, K, nq, nkv)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, n_kv=nkv, sq=Sq, skv=Skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, block_q, D),
                         lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, block_q, D),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (B, K, G, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :, :Sq]
