"""End-to-end training driver (the paper-kind-appropriate e2e example).

On this CPU container it trains a ~100M-parameter model for a few hundred
steps under the fault-tolerant supervisor; on a real cluster the same driver
runs any registry arch on the production mesh (--mesh single|multi).

    PYTHONPATH=src python -m repro.launch.train \
        --arch mamba2-130m --steps 300 --batch 8 --seq 256 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, make_batch
from repro.ckpt import CheckpointManager, latest_step
from repro.ft import FaultInjector, FaultPlan, Supervisor, SupervisorConfig
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import TrainConfig, init_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="mamba2-130m")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opt-dtype", choices=["fp32", "bf16", "int8"], default="fp32")
    ap.add_argument("--compress", action="store_true", help="int8+EF grad compression")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-faults", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--moe-dispatch", choices=["iru_sorted", "iru_hash", "dense"],
                    default=None,
                    help="override MoEConfig.dispatch (MoE archs only)")
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.moe_dispatch is not None:
        if cfg.moe is None:
            ap.error(f"--moe-dispatch set but arch {cfg.name!r} has no MoE layers")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=args.moe_dispatch))
    pcfg = ParallelConfig(model_axis=1, remat="full", microbatches=args.microbatches,
                          attn_chunk=min(256, args.seq))
    tc = TrainConfig(
        adam=AdamWConfig(lr=args.lr, state_dtype=args.opt_dtype),
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        grad_compression="int8_ef" if args.compress else None,
    )
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    mgr = CheckpointManager(args.ckpt, keep=3)
    start = latest_step(args.ckpt) or 0
    if start:
        print(f"resuming from checkpoint step {start}")
        dummy = init_state(cfg, pcfg, tc, jax.random.PRNGKey(args.seed))
        target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), dummy)
        state = mgr.restore_latest(target)
    else:
        state = init_state(cfg, pcfg, tc, jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M opt={args.opt_dtype} "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    step_fn = jax.jit(make_train_step(cfg, pcfg, tc), donate_argnums=(0,))
    injector = FaultInjector(FaultPlan(die_at=(args.steps // 3,),
                                       nan_at=(2 * args.steps // 3,))) if args.inject_faults else None
    sup = Supervisor(mgr, SupervisorConfig(ckpt_every=args.ckpt_every), injector=injector)

    t0 = time.monotonic()
    logged = {"n": 0}

    orig_append = sup.history.append

    def log_append(rec):
        orig_append(rec)
        if rec["step"] % args.log_every == 0:
            dt = time.monotonic() - t0
            extra = ""
            dr = rec.get("moe_drop_rate")
            if dr is not None and len(dr):
                # per-layer drop rates from the planned dispatch's stats,
                # threaded through the layer scan (moe_load_imbalance rides
                # alongside in the supervisor history)
                extra = (f" moe_drop {float(dr.mean()):.3f}"
                         f"/max {float(dr.max()):.3f}")
            print(f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                  f"({rec['dt']*1e3:.0f} ms/step, {dt:.0f}s total){extra}")
        logged["n"] += 1

    sup.history = type("L", (list,), {"append": lambda self, r: log_append(r)})()
    state, last = sup.run(state, step_fn, lambda s: make_batch(cfg, shape, s), start, args.steps - start)
    mgr.wait()
    print(f"done at step {last}; restarts={sup.restarts} straggles={sup.straggles} "
          f"nan_events={sup.nan_events}")
    with open(os.path.join(args.ckpt, "train_summary.json"), "w") as f:
        json.dump({"arch": cfg.name, "steps": last, "restarts": sup.restarts,
                   "nan_events": sup.nan_events}, f)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
