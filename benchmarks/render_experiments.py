"""Render §Dry-run and §Roofline markdown tables from results/dryrun."""
from __future__ import annotations

import json

from benchmarks.roofline import load_records


def fmt(x, n=3):
    return f"{x:.{n}e}"


def dryrun_table(mesh: str) -> str:
    rows = ["| arch | shape | status | compile_s | collectives (count) | wire GB/dev | fits 16GB |",
            "|---|---|---|---|---|---|---|"]
    for r in load_records(mesh):
        if r["status"] != "ok":
            reason = r.get("reason", r.get("error", ""))[:60]
            rows.append(f"| {r['arch']} | {r['shape']} | {r['status']} | | {reason} | | |")
            continue
        cc = r["collectives"]["counts"]
        cstr = " ".join(f"{k.replace('all-','a')}:{v}" for k, v in sorted(cc.items()))
        wire = r["collectives"]["wire_bytes_per_device"] / 2**30
        fits = r.get("analytic_memory", {}).get("fits_16gb")
        rows.append(f"| {r['arch']} | {r['shape']} | ok | {r['compile_s']:.0f} | "
                    f"{cstr} | {wire:.2f} | {fits} |")
    return "\n".join(rows)


def roofline_table(mesh: str) -> str:
    rows = ["| arch | shape | t_compute | t_memory | t_collective | bound | MODEL/HLO | what moves the bound |",
            "|---|---|---|---|---|---|---|---|"]
    hints = {
        ("memory", "train"): "less remat recompute / fused attn kernel",
        ("memory", "decode"): "physics: weights+cache per token; batch or quantize cache",
        ("memory", "prefill"): "fused blockwise attention (fewer materialized tiles)",
        ("collective", "train"): "sharding: cut resharding / dispatch collectives",
        ("collective", "prefill"): "overlap a2a with expert compute; bigger chunks",
        ("collective", "decode"): "replicate small tensors; avoid per-step gathers",
        ("compute", "train"): "drop masked-block waste; tighter capacity factor",
    }
    for r in load_records(mesh):
        if r["status"] != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | {r.get('reason','failed')[:50]} | | | | | |")
            continue
        ra = r["roofline"]
        hint = hints.get((ra["bottleneck"], r["kind"]), "")
        rows.append(
            f"| {r['arch']} | {r['shape']} | {fmt(ra['t_compute_s'])} | {fmt(ra['t_memory_s'])} | "
            f"{fmt(ra['t_collective_s'])} | {ra['bottleneck']} | "
            f"{(r.get('useful_flops_ratio') or 0):.3f} | {hint} |")
    return "\n".join(rows)


def main():
    print("### Dry-run (single-pod 16x16)\n")
    print(dryrun_table("single"))
    print("\n### Dry-run (multi-pod 2x16x16)\n")
    print(dryrun_table("multi"))
    print("\n### Roofline (single-pod)\n")
    print(roofline_table("single"))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
