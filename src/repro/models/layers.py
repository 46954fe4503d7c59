"""Shared neural building blocks: norms, rotary, MLPs, blockwise attention.

Attention is implemented *blockwise* (flash-style online softmax in pure
jnp, `lax.map` over query blocks x `lax.scan` over KV blocks) so that memory
stays sub-O(S^2) on every backend; the Pallas kernel in
``repro.kernels.flash_attention`` computes the same math with explicit VMEM
tiling for TPU and is validated against the jnp oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import ParamSpec

F32 = jnp.float32
NEG = -1e30  # large-negative mask value (avoids -inf - -inf = nan)

# ---------------------------------------------------------------- norms ----


def maybe_remat(body, remat: str):
    """Apply the configured activation-checkpoint policy to a scan body.

    none  — no rematerialization: lowest FLOPs, highest activation HBM.
    block — jax.checkpoint on the whole block: bwd recomputes everything,
            activations O(1) per layer (the FSDP-at-405B default).
    dots  — checkpoint_dots_with_no_batch_dims: matmul OUTPUTS are saved,
            elementwise ops recompute. Cuts the bwd recompute FLOPs of
            `block` while keeping activation memory far below `none`
            (the §Perf hillclimb variant).
    """
    if remat == "block":
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if remat == "none":
        return body
    raise ValueError(f"unknown remat policy {remat!r}")


def rms_norm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), ("embed",), init="ones", dtype="float32")


def rms_norm(x, w, eps=1e-6):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


# --------------------------------------------------------------- rotary ----


def rotary(x, positions, theta: float):
    """x: (..., S, H, D). positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    angles = positions[..., :, None].astype(F32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]   # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ MLP ----


def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ff"), dtype=cfg.dtype),
        "w_up": ParamSpec((d, f), ("embed", "ff"), dtype=cfg.dtype),
        "w_down": ParamSpec((f, d), ("ff", "embed"), dtype=cfg.dtype),
    }


def mlp(p, x, act: str):
    g = x @ p["w_gate"]
    u = x @ p["w_up"]
    if act == "swiglu":
        h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    elif act == "geglu":
        h = jax.nn.gelu(g.astype(F32), approximate=True).astype(x.dtype) * u
    else:
        raise ValueError(act)
    return h @ p["w_down"]


# ---------------------------------------------------- blockwise attention ----


def _block_attn_update(q, k, v, m, l, acc, mask):
    """One online-softmax update. q:(...,Bq,D) k/v:(...,Bkv,D)
    mask:(...,Bq,Bkv) additive; m,l:(...,Bq); acc:(...,Bq,Dv)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(F32) + mask
    # The running max only keeps exp() in range: the result does not depend
    # on it, so no gradient flows through it. Differentiating the max would
    # divide by the count of scores equal to it, which the TPU can make 0
    # (bf16 scores recomputed with another rounding): NaN gradients.
    m_new = jax.lax.stop_gradient(jnp.maximum(m, s.max(axis=-1)))
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v.dtype), v).astype(F32)
    return m_new, l_new, acc_new


def _unrolled_attention(q, k, v, *, causal, scale, block_q, block_kv,
                        window):
    """Python-unrolled flash-style attention with STATIC causal/window
    skipping (dead tiles never traced). Exactly the work a TPU flash
    kernel performs — used by the dry-run cost variants because XLA's
    cost_analysis counts scan/map bodies once regardless of trip count."""
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = -(-Sq // block_q)
    nkv = -(-Skv // block_kv)
    pad_q = nq * block_q - Sq
    pad_kv = nkv * block_kv - Skv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    qb = qp.reshape(B, nq, block_q, K, G, D) * scale
    kb = kp.reshape(B, nkv, block_kv, K, D)
    vb = vp.reshape(B, nkv, block_kv, K, D)
    q_start = Skv - Sq
    outs = []
    for qi in range(nq):
        qt = jnp.moveaxis(qb[:, qi], 1, 3)              # (B,K,G,Bq,D)
        m = jnp.full((B, K, G, block_q), NEG, F32)
        l = jnp.zeros((B, K, G, block_q), F32)
        acc = jnp.zeros((B, K, G, block_q, D), F32)
        q_lo = q_start + qi * block_q
        q_hi = q_lo + block_q - 1
        for kj in range(nkv):
            k_lo, k_hi = kj * block_kv, (kj + 1) * block_kv - 1
            if causal and q_hi < k_lo:
                continue                                 # static skip
            if window is not None and q_lo - k_hi >= window:
                continue
            q_pos = q_lo + jnp.arange(block_q)
            k_pos = k_lo + jnp.arange(block_kv)
            msk = jnp.zeros((block_q, block_kv), F32)
            if causal:
                msk = jnp.where(q_pos[:, None] >= k_pos[None, :], msk, NEG)
            if window is not None:
                msk = jnp.where(q_pos[:, None] - k_pos[None, :] < window,
                                msk, NEG)
            msk = jnp.where(k_pos[None, :] < Skv, msk, NEG)
            kt = jnp.moveaxis(kb[:, kj], 1, 2)[:, :, None]
            vt = jnp.moveaxis(vb[:, kj], 1, 2)[:, :, None]
            m, l, acc = _block_attn_update(qt, kt, vt, m, l, acc,
                                           msk[None, None, None])
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.moveaxis(out, 3, 1))             # (B,Bq,K,G,D)
    out = jnp.concatenate(outs, axis=1)
    return out[:, :Sq].reshape(B, Sq, H, D).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, scale: float,
                        block_q: int = 512, block_kv: int = 1024,
                        window: Optional[int] = None,
                        skip_masked_blocks: bool = True,
                        unroll: bool = False):
    if unroll:
        return _unrolled_attention(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_kv=block_kv,
                                   window=window)
    """Flash-style attention, GQA-aware.

    q: (B, Sq, H, D); k, v: (B, Skv, K, D) with H = K * G.
    Returns (B, Sq, H, D). ``window`` = sliding local attention width.

    ``skip_masked_blocks``: wrap each KV block in ``lax.cond`` so blocks
    fully outside the causal/window band are never computed. This is the
    §Perf iteration documented in EXPERIMENTS.md (baseline computes all
    blocks and masks — 2x FLOP waste for causal).
    """
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = -(-Sq // block_q)
    nkv = -(-Skv // block_kv)
    pad_q = nq * block_q - Sq
    pad_kv = nkv * block_kv - Skv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    # (B, nq, Bq, K, G, D) — group GQA heads with their KV head
    qb = qp.reshape(B, nq, block_q, K, G, D) * scale
    kb = kp.reshape(B, nkv, block_kv, K, D)
    vb = vp.reshape(B, nkv, block_kv, K, D)
    # offset of query positions relative to the END of kv (decode: q at end)
    q_start = Skv - Sq

    def per_q_block(args):
        qi, qblk = args           # qblk: (B, Bq, K, G, D)
        q_pos = q_start + qi * block_q + jnp.arange(block_q)
        qt = jnp.moveaxis(qblk, 1, 3)                   # (B,K,G,Bq,D)

        def kv_step(carry, args2):
            m, l, acc = carry
            kj, kblk, vblk = args2
            k_pos = kj * block_kv + jnp.arange(block_kv)
            # additive mask: causal, window, kv padding
            msk = jnp.zeros((block_q, block_kv), F32)
            if causal:
                msk = jnp.where(q_pos[:, None] >= k_pos[None, :], msk, NEG)
            if window is not None:
                msk = jnp.where(q_pos[:, None] - k_pos[None, :] < window,
                                msk, NEG)
            msk = jnp.where(k_pos[None, :] < Skv, msk, NEG)

            def compute(operands):
                m_, l_, a_, kb_, vb_, msk_ = operands
                kt = jnp.moveaxis(kb_, 1, 2)[:, :, None]  # (B,K,1,Bkv,D)
                vt = jnp.moveaxis(vb_, 1, 2)[:, :, None]
                return _block_attn_update(qt, kt, vt, m_, l_, a_,
                                          msk_[None, None, None])

            def skip(operands):
                m_, l_, a_, *_ = operands
                return m_, l_, a_

            operands = (m, l, acc, kblk, vblk, msk)
            if skip_masked_blocks and (causal or window is not None):
                block_live = jnp.any(msk > NEG / 2)
                m, l, acc = jax.lax.cond(block_live, compute, skip, operands)
            else:
                m, l, acc = compute(operands)
            return (m, l, acc), None

        m0 = jnp.full((B, K, G, block_q), NEG, F32)
        l0 = jnp.zeros((B, K, G, block_q), F32)
        a0 = jnp.zeros((B, K, G, block_q, D), F32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nkv), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,K,G,Bq,D)
        return jnp.moveaxis(out, 3, 1)                  # (B,Bq,K,G,D)

    outs = jax.lax.map(per_q_block,
                       (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * block_q, K, G, D)
    return out[:, :Sq].reshape(B, Sq, H, D).astype(q.dtype)


def per_seq_positions(index, B: int):
    """Decode position(s) -> (B, 1) int32. ``index`` may be a scalar (all
    sequences at the same position) or (B,) (continuous batching: every
    slot at its own position)."""
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        return jnp.full((B, 1), idx, jnp.int32)
    return idx.reshape(B, 1)


def cache_insert(cache, new, index):
    """Insert one token of K or V at per-sequence positions.

    cache: (B, S, K, D); new: (B, 1, K, D); index scalar or (B,).
    Scalar keeps the cheap dynamic_update_slice; per-sequence uses a
    batched scatter (one row per sequence).
    """
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), idx, axis=1)
    B = cache.shape[0]
    return cache.at[jnp.arange(B), idx].set(new[:, 0].astype(cache.dtype))


def decode_attention(q, k_cache, v_cache, cache_len, *, scale: float,
                     window: Optional[int] = None):
    """Single-token attention against a KV cache.

    q: (B, 1, H, D); caches: (B, S, K, D); cache_len: scalar or (B,) current
    length (positions >= cache_len masked out).
    """
    B, _, H, D = q.shape
    _, S, K, _ = k_cache.shape
    G = H // K
    qg = (q.reshape(B, K, G, D) * scale)
    # preferred_element_type: f32 MXU accumulation WITHOUT materializing an
    # f32 copy of the (B,S,K,D) cache (decode is cache-read-bound)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=F32)
    pos = jnp.arange(S)
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))  # (B,S) or (1,S)
    if window is not None:
        valid = valid & (pos[None, :] >=
                         jnp.reshape(cache_len, (-1, 1)) - window)
    s = jnp.where(valid[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=F32)
    return out.reshape(B, 1, H, D).astype(q.dtype)
