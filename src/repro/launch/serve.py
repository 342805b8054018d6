"""Serving launcher: run the engine against a synthetic request stream under
any of the three schedulers.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --scheduler chunked_prefill --requests 8
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import all_archs
from ..models.transformer import init_model, encode
from ..serving import SCHEDULERS, ServeRequest, ServingEngine, summarize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--scheduler", default="orca",
                    choices=list(SCHEDULERS.keys()))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=160)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    arch = all_archs()[args.arch]
    cfg = arch.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = init_model(key, cfg)

    enc_out = None
    if cfg.encoder_layers > 0:
        frames = jax.random.normal(
            key, (args.max_batch, cfg.encoder_len, cfg.d_model)) * 0.02
        enc_out = encode(params, cfg, frames)

    rng = np.random.default_rng(args.seed)
    reqs = [
        ServeRequest(i, rng.integers(0, cfg.vocab,
                                     size=int(rng.integers(8, 64))).tolist(),
                     args.max_new)
        for i in range(args.requests)
    ]
    sched = (SCHEDULERS[args.scheduler](chunk=args.chunk)
             if args.scheduler == "chunked_prefill"
             else SCHEDULERS[args.scheduler]())
    eng = ServingEngine(params, cfg, max_batch=args.max_batch,
                        max_len=args.max_len, enc_out=enc_out)
    finished, stats = eng.run(reqs, sched)
    print(json.dumps(summarize(finished, stats), indent=1))
    for r in finished[:3]:
        print(f"req {r.rid}: prompt[:8]={r.prompt[:8]} -> {r.generated}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
