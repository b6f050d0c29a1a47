"""The control of each cell's check, read at the cell's own size.

    python bench/control.py --workload <name> --seeds 1,2,3 [--count 8]

For each seed it makes the cell's graph on the device and draws the run's
traffic as ``bench/run.py`` does, puts the reference one step below the
stated precision in the program's place (``reference.control``), and
prints the numbers the cell's check compares, over ``--count`` traversals:
the upper readings that the limits in ``PERF.md`` are set from.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import reference, run, trafficgen  # noqa: E402
from bench.graphgen import make_graph  # noqa: E402


def readings(spec: dict, seed: int, count: int) -> dict:
    import numpy as np

    cfg, mix = spec["config"], spec["mix"]
    (row_ptr, col_idx, weights), _ = make_graph(cfg)
    row_ptr, col_idx, weights = map(np.asarray, (row_ptr, col_idx, weights))
    degrees = np.diff(row_ptr)[:-1]
    roots = trafficgen.generate(mix, seed, degrees)[int(mix["warmup"]):]
    kind = cfg["app"]
    results = [(root, reference.control(kind, row_ptr, col_idx, weights,
                                        root)) for root in roots[:count]]
    return spec["driver"].compare(kind, row_ptr, col_idx, weights, results,
                                  run.log)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--count", type=int, default=8)
    args = ap.parse_args()
    bench = run.load_benchmark()
    spec = run.resolve(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        checks = readings(spec, seed, args.count)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": checks,
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
