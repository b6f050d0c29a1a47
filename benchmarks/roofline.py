"""Roofline table from the dry-run records (EXPERIMENTS.md §Roofline).

Reads results/dryrun/<arch>__<shape>__<mesh>.json and prints the three-term
table: compute / memory / collective seconds per device, dominant bottleneck,
MODEL_FLOPS/HLO_FLOPs, and the HBM-fit estimate."""
from __future__ import annotations

import glob
import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def load_records(mesh: str | None = None) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if mesh and r.get("mesh") != mesh:
            continue
        out.append(r)
    return out


def run(mesh: str = "single"):
    rows = []
    for r in load_records(mesh):
        if r["status"] == "skipped":
            rows.append({"arch": r["arch"], "shape": r["shape"], "status": "skipped"})
            continue
        if r["status"] != "ok":
            rows.append({"arch": r["arch"], "shape": r["shape"], "status": "FAILED"})
            continue
        roof = r["roofline"]
        dom = max(roof["t_compute_s"], roof["t_memory_s"], roof["t_collective_s"])
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "status": "ok",
            "t_compute_s": f"{roof['t_compute_s']:.3e}",
            "t_memory_s": f"{roof['t_memory_s']:.3e}",
            "t_collective_s": f"{roof['t_collective_s']:.3e}",
            "bottleneck": roof["bottleneck"],
            "roofline_frac": round(roof["t_compute_s"] / dom, 4) if dom else 0.0,
            "useful_ratio": round(r.get("useful_flops_ratio") or 0.0, 3),
            "fits_16gb": r.get("analytic_memory", {}).get("fits_16gb"),
        })
    return rows


def main():
    for mesh in ("single", "multi"):
        rows = run(mesh)
        if not rows:
            continue
        print(f"# mesh={mesh}")
        print("arch,shape,status,t_compute_s,t_memory_s,t_collective_s,"
              "bottleneck,roofline_frac,useful_ratio,fits_16gb")
        for r in rows:
            if r["status"] != "ok":
                print(f"{r['arch']},{r['shape']},{r['status']},,,,,,,")
                continue
            print(f"{r['arch']},{r['shape']},ok,{r['t_compute_s']},{r['t_memory_s']},"
                  f"{r['t_collective_s']},{r['bottleneck']},{r['roofline_frac']},"
                  f"{r['useful_ratio']},{r['fits_16gb']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
