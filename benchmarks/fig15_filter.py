"""Figure 15: fraction of elements filtered/merged by the IRU
(paper average: 48.5% over SSSP + PR).

Filtering happens inside the streaming reorder (``reorder_frontier``): the
merge datapath only coalesces duplicates that meet within one lookahead
window, so these fractions are window-bounded exactly like the hardware's.
``--quick`` caps frontier sizes for CI runs.
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks import common
from benchmarks.common import DATASET_KW, geomean, run_pair


def run(force: bool = False, quick: bool = False):
    if quick:
        common.set_quick(True)
    rows = []
    for algo in ("sssp", "pr"):        # filtering applies to SSSP + PR (§6.2)
        for ds in DATASET_KW:
            cell = run_pair(algo, ds, force=force)
            rows.append({"algo": algo, "dataset": ds,
                         "filtered_frac": round(cell.get("filtered_frac", 0.0), 3)})
    rows.append({"algo": "MEAN", "dataset": "-",
                 "filtered_frac": round(float(np.mean([r["filtered_frac"] for r in rows])), 3)})
    return rows


def main(quick: bool = False, force: bool = False):
    print("algo,dataset,filtered_frac")
    for r in run(force=force, quick=quick):
        print(f"{r['algo']},{r['dataset']},{r['filtered_frac']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--force", action="store_true")
    a = ap.parse_args()
    main(quick=a.quick, force=a.force)
