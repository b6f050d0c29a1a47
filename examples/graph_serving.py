"""Multi-tenant graph query serving walkthrough (fused tagged-lane engine).

Mixed BFS / SSSP / PPR queries from different "users" multiplex into ONE
compiled bucketed step over a query-replica composite view
(``tile_csr`` → ``GraphView``): query ``q``'s node ``v`` rides as composite
id ``q * n + v``, so queries join and retire mid-flight exactly like
requests in the continuous-batching LM engine (``examples/serve_lm.py``).
With ``fused=True`` (the default) BOTH merge families — min (BFS/SSSP) and
add (PPR) — advance in the SAME dispatch per tick: the composite app tags
each lane with its slot's family and the tagged datapath folds min and add
lanes in one pass, so a mixed workload compiles at most ``n_buckets`` step
executables TOTAL.

The walkthrough exercises the whole robustness surface:

1. a mixed workload admitted under the degree-sum capacity gate

       degsum(new query's initial frontier) + Σ degsum(running frontiers)
           <= the serving edge budget

   (the exact predictor the bucketed pipeline already dispatches on — a
   tenant can never push the merged frontier past the largest compiled
   capacity);
2. an injected capacity overflow (``QueryFaultPlan``): the engine evicts
   the largest predicted contributor into quarantine and retries it solo
   after exponential backoff, while every co-tenant's result stays
   bit-identical to a solo run;
3. deadline supervision: a pathological tenant burns its per-query tick
   budget and is cancelled loudly — the engine never hangs and
   ``run_to_completion`` names stuck queries instead of returning quietly;
4. partitioned serving: the SAME engine API over the fully composed view
   ``partition_csr(tile_csr(g, Q), P)`` runs every tick shard_map-
   partitioned across P devices with the tagged boundary exchange — run

       XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
           PYTHONPATH=src python examples/graph_serving.py --devices 2

   to serve on two forced host devices and check parity against the
   single-device engine (BFS/SSSP bit-identical, PPR allclose).

    PYTHONPATH=src python examples/graph_serving.py [--dataset kron]
"""
import argparse

import numpy as np

from repro.core import CapacityPolicy
from repro.ft import QueryFaultPlan
from repro.graphs.csr import partition_csr, tile_csr
from repro.graphs.generators import make_dataset
from repro.serve import GraphQuery, GraphServeConfig, GraphServingEngine
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--dataset", default="kron", choices=["kron", "delaunay"])
ap.add_argument("--devices", type=int, default=1,
                help="serve over a partition_csr(tile_csr(g, Q), P) view; "
                     "needs P real or XLA-forced host devices")
args = ap.parse_args()

kw = {"kron": dict(scale=9), "delaunay": dict(scale=64)}
g = make_dataset(args.dataset, **kw[args.dataset])
rng = np.random.default_rng(0)
print(f"dataset={args.dataset}: {g.n_nodes} nodes, {g.n_edges} edges")

# -- 1. a mixed workload through one fused engine ---------------------------
# 10 queries, 4 slots: more tenants than lanes, so admission is continuous —
# finished queries free their slot and the queue drains under the gate.
# Both families share ONE tagged-lane runtime ticked in one dispatch.
plan = QueryFaultPlan(overflow_at=(4,))   # ...with one scripted fault (2.)
policy = CapacityPolicy(n_buckets=3, min_capacity=1024, growth=8)
eng = GraphServingEngine(
    g,
    GraphServeConfig(query_slots=4, backoff_base_s=0.001,
                     capacity_policy=policy),
    fault_plan=plan)

kinds = ["bfs", "sssp", "ppr"]
queries = [GraphQuery(kinds[i % 3], int(rng.integers(0, g.n_nodes)), iters=6)
           for i in range(10)]
# ...plus one pathological tenant with a tiny deadline (3.)
doomed = GraphQuery("ppr", 0, iters=400, tick_budget=5)
for q in queries + [doomed]:
    eng.submit(q)

eng.run_to_completion(10_000)

n_exec = sum(fn._cache_size() for fn in eng._pipes["fused"]._step_b)
print(f"\nserved {len(queries) + 1} queries in {eng.tick_no} engine ticks "
      f"({eng.quarantines} quarantine(s), {eng.overflow_events} overflow "
      f"event(s), {eng.admission_blocked} admission-blocked tick(s))")
print(f"fused datapath: {list(eng._pipes)} runtime(s), {n_exec} compiled "
      f"step executable(s) total for all three kinds "
      f"(<= n_buckets={policy.n_buckets})")

# -- 2. the injected overflow was recovered, not absorbed -------------------
assert ("overflow", 4) in eng.injector.fired
victims = [q for q in queries if q.retries > 0]
print(f"injected overflow at tick 4 evicted "
      f"{[f'q{q.qid}({q.kind})' for q in victims]} into quarantine; "
      f"solo retry completed {'them' if len(victims) != 1 else 'it'}")

# every surviving tenant — including the quarantined ones — is bit-identical
# to a single-tenant FrontierPipeline run of the same query
for q in queries:
    assert q.done, (q.qid, q.status, q.error)
    np.testing.assert_array_equal(np.asarray(q.result), eng.solo_reference(q))
print("all 10 workload results bit-identical to solo FrontierPipeline runs")

# -- 3. the pathological tenant was cancelled loudly ------------------------
assert doomed.status == "cancelled", (doomed.status, doomed.error)
print(f"pathological tenant q{doomed.qid}: {doomed.status!r} — "
      f"{doomed.error}")

# peek at two results
bfs_q = next(q for q in queries if q.kind == "bfs")
ppr_q = next(q for q in queries if q.kind == "ppr")
hops = bfs_q.result[bfs_q.result < np.iinfo(np.int32).max]
print(f"\nq{bfs_q.qid}: BFS from {bfs_q.source} reached {hops.size} nodes, "
      f"max depth {hops.max()}")
top = np.argsort(ppr_q.result)[::-1][:5]
print(f"q{ppr_q.qid}: PPR seed {ppr_q.source} top-5 nodes {top.tolist()} "
      f"(seed rank {ppr_q.result[ppr_q.source]:.3f})")

# -- 4. partitioned serving over the composed view --------------------------
if args.devices > 1:
    import jax

    avail = jax.device_count()
    if avail < args.devices:
        raise SystemExit(
            f"--devices {args.devices} but only {avail} JAX device(s) "
            f"visible; relaunch with XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={args.devices}")
    Q = 4
    pview = partition_csr(tile_csr(g, Q), args.devices)
    print(f"\npartitioned serving: {pview.n_parts} shards x "
          f"{pview.part.local_nodes} local nodes over the {Q}-tenant "
          f"composite ({pview.n_nodes} composite nodes)")
    peng = GraphServingEngine(
        pview, GraphServeConfig(query_slots=Q, capacity_policy=policy))
    pqs = [GraphQuery(kinds[i % 3], int(rng.integers(0, g.n_nodes)),
                      iters=6) for i in range(6)]
    for q in pqs:
        peng.submit(q)
    peng.run_to_completion(10_000)
    for q in pqs:
        assert q.done, (q.qid, q.status, q.error)
        ref = peng.solo_reference(q)
        if q.kind == "ppr":
            np.testing.assert_allclose(q.result, ref, rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(q.result, ref)
    print(f"served {len(pqs)} queries shard_map-partitioned on "
          f"{args.devices} devices: BFS/SSSP bit-identical, PPR allclose "
          f"to single-device solo runs")
