"""Seeded inputs of the model cells: weights and token corpora.

The weights are made on the device in one jitted call from the seed, in the
type they are served in, and laid out as the program's parameter tree (its
shapes come from the cell's architecture, ``perfbench/archs/``). The
reference regenerates them from the same seed; it takes nothing the
program made. The corpus is Zipf-distributed token ids (p(i) ~ 1/(i+1)),
drawn on the host by inverse CDF.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any non-negative seed (wider than 32 bits)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def unflatten(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def flatten(tree: Dict, prefix: str = "") -> Dict[str, jax.Array]:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(shapes, key, dtype):
    out = {}
    for i, (path, (shape, kind)) in enumerate(shapes):
        if kind == "norm":
            out[path] = jnp.ones(shape, jnp.float32)
        else:
            out[path] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32)
                         * INIT_STD).astype(dtype)
    return out


def make_params(shapes: Dict[str, Tuple[Tuple[int, ...], str]], dtype: str,
                seed: int) -> Dict:
    """The parameter tree of flat ``{path: (shape, kind)}`` (an
    architecture's ``param_shapes``) from ``seed``: "matrix" leaves drawn
    N(0, 0.02) in float32 and rounded to ``dtype``, "norm" leaves ones in
    float32."""
    return unflatten(_make(tuple(sorted(shapes.items())), key_from_seed(seed),
                           jnp.dtype(dtype).name))


def zipf_tokens(seed: int, shape: Tuple[int, ...], vocab: int,
                stream: int = 1) -> np.ndarray:
    """Token ids with p(i) proportional to 1 / (i + 1), int32."""
    p = 1.0 / np.arange(1, vocab + 1)
    cdf = np.cumsum(p / p.sum())
    u = np.random.default_rng([seed, stream]).random(int(np.prod(shape)))
    return np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(
        np.int32).reshape(shape)
