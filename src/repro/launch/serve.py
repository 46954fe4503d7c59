"""Batched serving driver: prefill + decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --batch 8 --prompt-len 128 --gen 32

The mesh is built from the devices present (1x1 on one chip, 2x2 on four);
``--smoke`` selects the reduced per-arch config.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, get_smoke_config
from ..models import build_model, init_params
from ..models.params import ParamSpec
from ..serve import make_serve_step
from ..train import make_plan, use_plan
from ..train.sharding import resolve_shardings
from .cache import enable_compile_cache
from .mesh import make_device_mesh


def zero_cache(model, cfg, B, cache_len):
    if cfg.family == "encdec":
        specs = model.cache_specs(B, cache_len, enc_len=cache_len)
    else:
        specs = model.cache_specs(B, cache_len)
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def _device_label() -> str:
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


def main(argv=None):
    """Returns ``{"tokens", "logits"}`` for the batched run (generated
    tokens (B, gen) and the last step's logits (B, 1, V)), or
    ``{"finished", "occupancy"}`` with ``--continuous``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching engine")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(
        args.arch)
    mesh = make_device_mesh()
    plan = make_plan(mesh)
    model = build_model(cfg)
    specs = model.specs()
    params = jax.device_put(init_params(specs, jax.random.key(0)),
                            resolve_shardings(specs, plan))
    serve_step = jax.jit(make_serve_step(model, cfg))

    B = args.batch
    cache_len = args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    if args.continuous:
        from ..serve import ContinuousBatcher, Request
        eng = ContinuousBatcher(model, cfg, params, n_slots=B,
                                cache_len=cache_len)
        n_req = 2 * B + 1           # backlog > slots: slots must recycle
        with use_plan(plan):
            t0 = time.perf_counter()
            for rid in range(n_req):
                plen = int(rng.integers(4, args.prompt_len + 1))
                eng.submit(Request(rid, rng.integers(
                    0, cfg.vocab, size=plen).tolist(), args.gen))
            done = eng.run()
            dt = time.perf_counter() - t0
        total = sum(len(v) for v in done.values())
        print(f"continuous batching: {len(done)} requests over {B} slots")
        print(f"occupancy {eng.occupancy:.2f}, "
              f"{total / dt:.1f} gen tok/s ({_device_label()}, "
              f"compile included)")
        return {"finished": done, "occupancy": eng.occupancy}
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       size=(B, args.prompt_len)),
                          jnp.int32)
    cache = zero_cache(model, cfg, B, cache_len)
    with use_plan(plan):
        # prefill by stepping the prompt (batched requests share steps)
        t0 = time.perf_counter()
        for i in range(args.prompt_len):
            nxt, logits, cache = serve_step(params, cache,
                                            prompts[:, i:i + 1],
                                            jnp.int32(i))
        generated = [nxt]
        for j in range(args.gen - 1):
            nxt, logits, cache = serve_step(
                params, cache, generated[-1],
                jnp.int32(args.prompt_len + j))
            generated.append(nxt)
        jax.block_until_ready(generated[-1])
        dt = time.perf_counter() - t0
    out = jnp.concatenate(generated, axis=1)
    total_tokens = B * (args.prompt_len + args.gen - 1)
    print(f"served {B} sequences, {args.gen} new tokens each")
    print(f"throughput {total_tokens / dt:.1f} tok/s ({_device_label()}, "
          f"compile included)")
    print("sample:", np.asarray(out[0])[:12].tolist())
    return {"tokens": out, "logits": logits}


if __name__ == "__main__":
    main()
