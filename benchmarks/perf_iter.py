"""§Perf hillclimb harness: re-lower one cell under modified knobs and diff
the three roofline terms against the recorded baseline.

    PYTHONPATH=src python -m benchmarks.perf_iter --arch mamba2-130m \
        --shape train_4k --rules ffn= ssm_heads= --label pure-dp

Knobs: --rules name=axis1+axis2 (empty = replicate), --attn-chunk, --micro,
--remat, --opt-dtype.  Results append to results/perf_iters.jsonl.
"""

import argparse
import dataclasses
import json
import os

from repro.configs import LM_SHAPES, get_config
from repro.configs.base import ParallelConfig
from repro.dist.sharding import override_rules
from repro.launch.dryrun import RESULTS_DIR, default_pcfg, run_cell
from repro.launch.mesh import make_production_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(LM_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--rules", nargs="*", default=[],
                    help="name=axis+axis or name= (replicate)")
    ap.add_argument("--attn-chunk", type=int)
    ap.add_argument("--micro", type=int)
    ap.add_argument("--remat", choices=["full", "none"])
    ap.add_argument("--ssd-chunk", type=int, help="override MambaConfig.chunk")
    ap.add_argument("--ssd-bf16", action="store_true", help="bf16 SSD einsums")
    ap.add_argument("--capacity-factor", type=float, help="override MoE capacity factor")
    ap.add_argument("--no-constraints", action="store_true",
                    help="pure SPMD propagation (no activation constraints)")
    ap.add_argument("--label", default="iter")
    ap.add_argument("--save-baseline", action="store_true",
                    help="overwrite the cell's baseline record with this run")
    args = ap.parse_args()

    base_path = os.path.join(RESULTS_DIR, f"{args.arch}__{args.shape}__{args.mesh}.json")
    baseline = None
    if os.path.exists(base_path):
        with open(base_path) as f:
            baseline = json.load(f)

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    pcfg = default_pcfg(get_config(args.arch), LM_SHAPES[args.shape], mesh)
    upd = {}
    if args.attn_chunk:
        upd["attn_chunk"] = args.attn_chunk
    if args.micro:
        upd["microbatches"] = args.micro
    if args.remat:
        upd["remat"] = args.remat
    if upd:
        pcfg = dataclasses.replace(pcfg, **upd)

    rules = {}
    for r in args.rules:
        name, _, axes = r.partition("=")
        rules[name] = tuple(a for a in axes.split("+") if a)

    def mutate(cfg):
        if args.ssd_chunk and cfg.mamba is not None:
            cfg = dataclasses.replace(
                cfg, mamba=dataclasses.replace(cfg.mamba, chunk=args.ssd_chunk))
        if args.ssd_bf16 and cfg.mamba is not None:
            cfg = dataclasses.replace(
                cfg, mamba=dataclasses.replace(cfg.mamba, ssd_dtype="bf16"))
        if args.capacity_factor and cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=args.capacity_factor))
        return cfg

    import contextlib

    from repro.dist.sharding import constraints_disabled

    ctx = constraints_disabled() if args.no_constraints else contextlib.nullcontext()
    with override_rules(**rules), ctx:
        rec = run_cell(args.arch, args.shape, args.mesh, pcfg=pcfg,
                       save=args.save_baseline, mutate_cfg=mutate)
    if rec["status"] != "ok":
        print(json.dumps(rec, indent=1)[:2000])
        raise SystemExit(1)

    def show(name, r):
        ra = r["roofline"]
        print(f"{name:10s} tc={ra['t_compute_s']:.3e} tm={ra['t_memory_s']:.3e} "
              f"tx={ra['t_collective_s']:.3e} bound={ra['bottleneck']} "
              f"useful={r.get('useful_flops_ratio'):.3f}")

    if baseline and baseline.get("status") == "ok":
        show("baseline", baseline)
    show(args.label, rec)
    if baseline and baseline.get("status") == "ok":
        for k in ("t_compute_s", "t_memory_s", "t_collective_s"):
            b, n = baseline["roofline"][k], rec["roofline"][k]
            print(f"  {k}: {b:.3e} -> {n:.3e}  ({(n/b - 1) * 100 if b else 0:+.1f}%)")
    entry = {"label": args.label, "arch": args.arch, "shape": args.shape,
             "mesh": args.mesh, "rules": {k: list(v) for k, v in rules.items()},
             "pcfg": dataclasses.asdict(pcfg), "roofline": rec["roofline"],
             "useful": rec.get("useful_flops_ratio"),
             "collectives": rec["collectives"]["counts"]}
    with open(os.path.join(RESULTS_DIR, "..", "perf_iters.jsonl"), "a") as f:
        f.write(json.dumps(entry) + "\n")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    # forced host devices for the production meshes, set only by the CLI
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    enable_compile_cache()
    main()
