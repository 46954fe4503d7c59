"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 5 --batch 8 --seq-len 2048

The mesh is built from the devices present (1x1 on one chip, 2x2 on four)
and parameters, optimizer state and batches are placed by the sharding
plan resolved against it; ``--smoke`` selects the reduced per-arch config.

The driver wires every substrate together: Morton-sharded data pipeline ->
jit'd train_step under the sharding plan -> async cuboid-chunked
checkpoints -> supervisor (failure recovery + straggler monitor).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..configs import get_config, get_smoke_config
from ..data import DataPipeline, PipelineConfig, TokenStore
from ..ft import FailureInjector, StragglerMonitor, TrainingSupervisor
from ..models import build_model, init_params
from ..optim import AdamWConfig, adamw_init_specs
from ..train import batch_pspec, make_train_step, use_plan, make_plan
from ..train.sharding import resolve_shardings
from .cache import enable_compile_cache
from .mesh import make_device_mesh


def init_opt_state(model_specs, rng):
    from ..models.params import init_params as ip
    specs = adamw_init_specs(model_specs)
    # master starts as a copy of params; mu/nu zeros
    return ip(specs, rng)


def build_state(cfg, seed: int = 0):
    model = build_model(cfg)
    specs = model.specs()
    params = init_params(specs, jax.random.key(seed))
    opt = init_opt_state(specs, jax.random.key(seed + 1))
    opt["master"] = jax.tree.map(
        lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params)
    return model, params, opt


def synthetic_corpus(cfg, n_docs=256, doc_len=1024, seed=0) -> TokenStore:
    """A Zipf-ish synthetic corpus through the Morton token store."""
    rng = np.random.default_rng(seed)
    store = TokenStore(n_docs, doc_len, cuboid=(16, min(4096, doc_len)))
    ranks = np.arange(1, cfg.vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    toks = rng.choice(cfg.vocab, size=(n_docs, doc_len), p=probs)
    store.ingest_corpus(toks)
    return store


def main(argv=None, mesh=None) -> Dict:
    """``mesh`` overrides the mesh built from the devices present."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(
        args.arch)
    mesh = make_device_mesh() if mesh is None else mesh
    plan = make_plan(mesh)
    model, params, opt = build_state(cfg)
    specs = model.specs()
    params = jax.device_put(params, resolve_shardings(specs, plan))
    opt = jax.device_put(opt, resolve_shardings(adamw_init_specs(specs),
                                                plan))
    opt_cfg = AdamWConfig(lr_peak=args.lr, warmup_steps=5,
                          total_steps=args.steps,
                          grad_compression=args.grad_compression)
    step_fn_raw = make_train_step(model, cfg, opt_cfg,
                                  n_microbatches=args.microbatches)
    jit_step = jax.jit(step_fn_raw, donate_argnums=(0, 1))

    store = synthetic_corpus(cfg, doc_len=args.seq_len + 1 + 64)
    pipe = DataPipeline(store, PipelineConfig(
        seq_len=args.seq_len, global_batch=args.batch))

    losses = []
    monitor = StragglerMonitor(n_workers=1)

    def one_step(state, step):
        params, opt = state
        t0 = time.perf_counter()
        batch = pipe.get_batch(step)
        batch = {k: jax.device_put(v, NamedSharding(
            mesh, batch_pspec(plan, v.ndim, v.shape[0])))
            for k, v in batch.items()}
        if cfg.frontend == "patch_stub":
            B = batch["tokens"].shape[0]
            rng = np.random.default_rng(step)
            batch["embeds"] = jnp.asarray(rng.normal(size=(
                B, cfg.n_frontend_tokens, cfg.d_model)), jnp.bfloat16)
        if cfg.family == "encdec":
            B, S = batch["tokens"].shape
            rng = np.random.default_rng(step)
            batch["frames"] = jnp.asarray(
                rng.normal(size=(B, S, cfg.d_model)), jnp.bfloat16)
        with use_plan(plan):
            params, opt, metrics = jit_step(params, opt, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        monitor.record(0, time.perf_counter() - t0)
        if step % 5 == 0:
            print(f"step {step:4d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        return (params, opt)

    state = (params, opt)
    if args.ckpt_dir:
        injector = None
        if args.inject_failure_at is not None:
            injector = FailureInjector({args.inject_failure_at: 0})
        sup = TrainingSupervisor(args.ckpt_dir,
                                 ckpt_every=args.ckpt_every,
                                 injector=injector)
        state = sup.run(
            state, one_step, args.steps,
            state_to_tree=lambda s: {"params": s[0], "opt": s[1]},
            tree_to_state=lambda t, s: (
                jax.tree.map(jnp.asarray, t["params"]),
                jax.tree.map(jnp.asarray, t["opt"])))
        if sup.recovery_log:
            print("recoveries:", sup.recovery_log)
    else:
        for s in range(args.steps):
            state = one_step(state, s)
    pipe.stop()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return {"losses": losses}


if __name__ == "__main__":
    main()
