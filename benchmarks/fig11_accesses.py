"""Figure 11: normalized L1/L2 accesses, IRU vs baseline (paper: 67%/56%)."""
from __future__ import annotations

from benchmarks.common import ALGOS, DATASET_KW, all_cells, geomean


def run(force: bool = False):
    rows = []
    for cell in all_cells(force):
        r = cell["report"]
        rows.append({
            "algo": cell["algo"], "dataset": cell["dataset"],
            "l1_ratio": round(r["l1_ratio"], 3),
            "l2_ratio": round(r["l2_ratio"], 3),
        })
    rows.append({
        "algo": "MEAN", "dataset": "-",
        "l1_ratio": round(geomean([r["l1_ratio"] for r in rows]), 3),
        "l2_ratio": round(geomean([r["l2_ratio"] for r in rows]), 3),
    })
    return rows


def main():
    print("algo,dataset,l1_ratio,l2_ratio")
    for r in run():
        print(f"{r['algo']},{r['dataset']},{r['l1_ratio']},{r['l2_ratio']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
