"""Serving driver: continuous-batching engine over a registry arch.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
        --requests 16 --slots 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.configs.base import ParallelConfig
from repro.models import transformer as tfm
from repro.serve import Request, ServeConfig, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen3-32b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    pcfg = ParallelConfig(model_axis=1, remat="none", attn_chunk=64)
    params, _ = tfm.init_params(cfg, pcfg, jax.random.PRNGKey(args.seed))
    engine = ServingEngine(cfg, pcfg, params,
                           ServeConfig(batch_slots=args.slots, max_seq=args.max_seq))
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(2, 12))
        reqs.append(Request(prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                            max_new_tokens=args.max_new))
        engine.submit(reqs[-1])
    t0 = time.monotonic()
    engine.run_to_completion()
    dt = time.monotonic() - t0
    toks = sum(len(r.generated) for r in reqs)
    assert all(r.done for r in reqs)
    print(f"served {len(reqs)} requests / {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, {args.slots} slots, continuous batching)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
