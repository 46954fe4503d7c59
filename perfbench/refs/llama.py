"""Plain float32 reference of a Llama-architecture decoder (SmolLM).

Follows the published description: RMSNorm before attention and MLP,
rotary position embedding on the two halves of each head (Llama's
``rotate_half``), grouped-query causal attention, SwiGLU MLP, tied input
and output embedding, next-token cross-entropy averaged over every
position. AdamW as the configuration states it (warmup, then cosine decay;
global-norm clipping; decoupled weight decay on every leaf).

Every product runs in float32 at ``precision="highest"``; with
``precision="fp8"`` every product's operands are first rounded to float8
(e4m3, one scale per tensor): the control. Imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_E4M3_MAX = 448.0


def _round_fp8(x):
    """float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    """Rounds a product's operand; its gradient passes straight through."""
    return _round_fp8(x)


_fp8_operand.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity; rounds the gradient that flows back into the products."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_round_fp8(g),))


def _mm(precision: str, spec: str, a, b):
    """A product in float32 at "highest", or with ``precision="fp8"`` the
    usual float8 recipe: operands of the forward and of both backward
    products rounded to scaled float8, accumulation in float32."""
    if precision == "fp8":
        a, b = _fp8_operand(a), _fp8_operand(b)
    y = jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=F32)
    return _fp8_cotangent(y) if precision == "fp8" else y


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (S, H, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * inv                     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(cfg: Dict, precision: str, p: Dict, x, pos):
    """One decoder layer on one sequence: x (S, d) -> (S, d)."""
    S = x.shape[0]
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = functools.partial(_mm, precision)
    h = rms_norm(x, p["ln1"], eps)
    q = rope(mm("sd,de->se", h, p["attn"]["w_q"]).reshape(S, H, hd), pos,
             theta)
    k = rope(mm("sd,de->se", h, p["attn"]["w_k"]).reshape(S, K, hd), pos,
             theta)
    v = mm("sd,de->se", h, p["attn"]["w_v"]).reshape(S, K, hd)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    scores = mm("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    o = mm("hqk,khd->qhd", att, v).reshape(S, H * hd)
    x = x + mm("se,ed->sd", o, p["attn"]["w_o"])
    h = rms_norm(x, p["ln2"], eps)
    g = mm("sd,df->sf", h, p["mlp"]["w_gate"])
    u = mm("sd,df->sf", h, p["mlp"]["w_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["mlp"]["w_down"])


def logits(cfg: Dict, precision: str, params: Dict, tokens):
    """tokens (S,) int -> logits (S, vocab), float32."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    body = jax.checkpoint(functools.partial(layer, cfg, precision))

    def step(x, p):
        return body(p, x, pos), None

    x, _ = jax.lax.scan(step, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return _mm(precision, "sd,vd->sv", x, params["embed"])


def row_loss(cfg: Dict, precision: str, params: Dict, tokens, labels):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits(cfg, precision, params, tokens)
    ll = jnp.take_along_axis(jax.nn.log_softmax(lg, -1), labels[:, None],
                             -1)[:, 0]
    return -jnp.mean(ll)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _row_value_and_grad(cfg_items, precision, params, tokens, labels):
    return jax.value_and_grad(
        lambda p: row_loss(dict(cfg_items), precision, p, tokens, labels))(
            params)


def loss_and_grad(cfg: Dict, params: Dict, tokens: np.ndarray,
                  labels: np.ndarray, precision: str = "f32"):
    """Batch-mean loss and gradient, one sequence at a time (so that a
    published-width batch fits beside nothing else)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    loss, grads = 0.0, None
    B = tokens.shape[0]
    for b in range(B):
        lv, g = _row_value_and_grad(items, precision, params,
                                    jnp.asarray(tokens[b]),
                                    jnp.asarray(labels[b]))
        loss += float(lv) / B
        grads = (jax.tree.map(lambda a: a / B, g) if grads is None
                 else jax.tree.map(lambda a, c: a + c / B, grads, g))
    return loss, grads


def lr_at(opt: Dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr_peak"] * warm * 0.5 * (1 + np.cos(np.pi * t))


def adamw(opt: Dict, state: Dict, grads: Dict, step: int) -> Tuple[Dict, Dict]:
    """One AdamW step at ``step`` (1-based) on float32 params. Returns the
    new state and the gradient as the update used it (after clipping)."""
    leaves = jax.tree.leaves(grads)
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in leaves)))
    scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    lr = lr_at(opt, step)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    g = jax.tree.map(lambda x: x * scale, grads)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, state["mu"], g)
    nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x, state["nu"], g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                  + wd * p),
        state["params"], mu, nu)
    return {"params": params, "mu": mu, "nu": nu}, g


def train(cfg: Dict, opt: Dict, params: Dict, batches, precision="f32"):
    """Follow ``len(batches)`` steps from float32 ``params``. Returns the
    losses, the first step's clipped gradient and the final params."""
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = {"params": params, "mu": zeros, "nu": zeros}
    losses, first_grad = [], None
    for i, (tokens, labels) in enumerate(batches):
        loss, grads = loss_and_grad(cfg, state["params"], tokens, labels,
                                    precision)
        losses.append(loss)
        state, g = adamw(opt, state, grads, i + 1)
        if first_grad is None:
            first_grad = g
    return losses, first_grad, state["params"]
