"""Graph analytics end-to-end on the FrontierPipeline: BFS / SSSP / PageRank
on Table-3-like graphs, baseline vs IRU, with the GPU-analogue traffic model
(the paper's evaluation loop in miniature).

All three apps and both modes run through ONE code path — the pipeline's
instrumented driver — instead of three per-app host loops: the same compiled
expand → reorder → filter/merge → update step produces both the results and
the irregular-access traces the cost model replays.

    PYTHONPATH=src python examples/graph_analytics.py [--dataset kron]
                                                      [--mode hash|sort]
"""
import argparse

import numpy as np

from repro.apps.bfs import BFS_APP, bfs
from repro.apps.pagerank import pagerank, pagerank_app
from repro.apps.sssp import SSSP_APP, sssp
from repro.apps.trace import TraceRecorder
from repro.core import CapacityPolicy, IRUConfig
from repro.core.costmodel import Comparison, simulate_trace
from repro.core.pipeline import FrontierPipeline
from repro.graphs.generators import make_dataset
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--dataset", default="kron",
                choices=["ca", "cond", "delaunay", "human", "kron", "msdoor"])
ap.add_argument("--mode", default="hash", choices=["hash", "sort"],
                help="IRU engine for the reorder stage")
args = ap.parse_args()

kw = {"ca": dict(scale=48), "cond": dict(n=4000), "delaunay": dict(scale=48),
      "human": dict(n=1200), "kron": dict(scale=11), "msdoor": dict(scale=12)}
g = make_dataset(args.dataset, **kw[args.dataset])
source = int(np.argmax(np.asarray(g.degrees())))
print(f"dataset={args.dataset}: {g.n_nodes} nodes, {g.n_edges} edges, "
      f"avg degree {g.avg_degree():.1f}")

# the paper's 4x2 banked geometry; the same config drives every app
iru_cfg = IRUConfig(num_sets=1024, slots=32, n_partitions=4, n_banks=2,
                    round_cap=64)
# capacity ladder: sparse BFS/SSSP levels dispatch to bucket-sized step
# executables (PageRank's all-nodes frontier always predicts the top bucket)
policy = CapacityPolicy(n_buckets=3, min_capacity=2048, growth=8)
PR_ITERS = 5
apps = {
    "bfs": (BFS_APP, None, lambda: bfs(g, source)),
    "sssp": (SSSP_APP, None, lambda: sssp(g, source)),
    "pr": (pagerank_app(iters=PR_ITERS), PR_ITERS,
           lambda: pagerank(g, iters=PR_ITERS)),
}

print(f"\n{'algo':6s} {'L1 acc':>8s} {'L2 acc':>8s} {'NoC':>8s} "
      f"{'speedup':>8s} {'energy':>8s}")
for name, (app, max_iters, host_oracle) in apps.items():
    counts, results = {}, {}
    for mode in ("baseline", args.mode):
        pipe = FrontierPipeline(g, app, mode=mode,
                                iru_config=None if mode == "baseline" else iru_cfg,
                                capacity_policy=policy, max_iters=max_iters)
        rec = TraceRecorder()
        results[mode] = pipe.run_instrumented(source, recorder=rec)
        counts[mode] = simulate_trace(rec.events,
                                      iru_processed=rec.iru_elements)
    # correctness: both modes identical, and both match the host oracle
    np.testing.assert_allclose(np.asarray(results["baseline"], np.float64),
                               np.asarray(results[args.mode], np.float64),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(results["baseline"], np.float64),
                               np.asarray(host_oracle(), np.float64),
                               rtol=1e-4)
    rep = Comparison(name, counts["baseline"], counts[args.mode]).report()
    print(f"{name:6s} {rep['l1_ratio']:8.3f} {rep['l2_ratio']:8.3f} "
          f"{rep['noc_ratio']:8.3f} {rep['speedup']:8.3f} "
          f"{rep['energy_ratio']:8.3f}")
print("\n(ratios < 1 are reductions vs baseline; one pipeline code path "
      "produced results, traces and parity for every mode)")
