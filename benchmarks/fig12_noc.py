"""Figure 12: normalized SM<->MP interconnect traffic (paper mean: 54%)."""
from __future__ import annotations

from benchmarks.common import all_cells, geomean


def run(force: bool = False):
    rows = []
    for cell in all_cells(force):
        rows.append({
            "algo": cell["algo"], "dataset": cell["dataset"],
            "noc_ratio": round(cell["report"]["noc_ratio"], 3),
        })
    rows.append({"algo": "MEAN", "dataset": "-",
                 "noc_ratio": round(geomean([r["noc_ratio"] for r in rows]), 3)})
    return rows


def main():
    print("algo,dataset,noc_ratio")
    for r in run():
        print(f"{r['algo']},{r['dataset']},{r['noc_ratio']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
