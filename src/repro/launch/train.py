"""Multi-device training launcher.

Builds the largest valid mesh from the live device count (elastic), shards
params/optimizer with the production rules, and runs the fault-tolerant
training loop (async checkpoints, deterministic resume, straggler monitor).

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
      --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import all_archs
from ..dist.elastic import StragglerMonitor, current_mesh_shape
from ..dist.sharding import make_param_shardings, token_sharding
from ..models.transformer import init_model
from ..training import checkpoint as ckpt
from ..training.data import DataConfig, TokenStream
from ..training.optimizer import AdamWConfig, adamw_init
from ..training.train_loop import TrainConfig, make_train_step
from .mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    arch = all_archs()[args.arch]
    cfg = arch.reduced() if args.reduced else arch.model

    n_dev = len(jax.devices())
    if n_dev == 1:
        mesh = make_mesh((1, 1), ("data", "model"))
    else:
        shape = current_mesh_shape(n_dev)
        mesh = make_mesh(shape, ("pod", "data", "model"))
    print(f"[train] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    key = jax.random.PRNGKey(args.seed)
    params = init_model(key, cfg)
    opt = adamw_init(params)
    p_shard = make_param_shardings(mesh, params)
    params = jax.tree.map(jax.device_put, params, p_shard)
    opt = {
        "mu": jax.tree.map(jax.device_put, opt["mu"], p_shard),
        "nu": jax.tree.map(jax.device_put, opt["nu"], p_shard),
        "step": opt["step"],
    }

    tcfg = TrainConfig(
        microbatches=args.microbatches,
        compress_grads=args.compress_grads,
        opt=AdamWConfig(lr=3e-4, warmup_steps=10, total_steps=args.steps),
    )
    step_fn = jax.jit(make_train_step(cfg, tcfg, mesh=mesh))

    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                    global_batch=args.global_batch, seed=args.seed)
    stream = TokenStream(dc)
    start = 0
    residual = None
    if args.ckpt_dir and (latest := ckpt.latest_step(args.ckpt_dir)):
        restored, extra = ckpt.restore(args.ckpt_dir, latest,
                                       {"params": params, "opt": opt})
        params, opt = restored["params"], restored["opt"]
        stream.restore(extra["data_step"])
        start = latest
        print(f"[train] resumed from step {latest}")

    tok_sh = token_sharding(mesh, args.global_batch)
    mon = StragglerMonitor()
    for step in range(start, args.steps):
        tokens = jax.device_put(jnp.asarray(next(stream)), tok_sh)
        t0 = time.perf_counter()
        if tcfg.compress_grads:
            params, opt, stats, residual = step_fn(params, opt, tokens,
                                                   residual)
        else:
            params, opt, stats = step_fn(params, opt, tokens)
        jax.block_until_ready(stats["loss"])
        slow = mon.step(time.perf_counter() - t0)
        print(f"step {step:4d} loss {float(stats['loss']):.4f} "
              f"lr {float(stats['lr']):.2e}"
              + ("  [straggler]" if slow else ""))
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(args.ckpt_dir, step + 1,
                            {"params": params, "opt": opt},
                            extra={"data_step": stream.state()})
    ckpt.wait_pending()
    print(f"[train] done; straggler steps: {mon.slow_steps}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
