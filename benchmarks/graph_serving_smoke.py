"""CI smoke: multi-tenant graph serving through the interpret-mode pipeline.

Default leg (``make smoke-graph-serving``): eight mixed BFS/SSSP/PPR queries
share a 4-slot ``GraphServingEngine`` whose composite step expands through
the Pallas block-reuse gather (interpret mode on CPU), with one scripted
capacity overflow mid-flight.  Asserts the acceptance contract end-to-end at
a size CI can afford:

* every query completes despite the injected overflow (the victim finishes
  via quarantine + solo retry);
* every per-query result is bit-identical to its solo ``FrontierPipeline``
  run (min family everywhere; the add family is exact too in this baseline
  reorder mode);
* the scripted fault actually fired and was counted — no silent recovery,
  no silent truncation.

Fused leg (``make smoke-serving-fused``, ``--fused``): pins the tagged-lane
family-fusion contract —

* one fused mixed-family tick advances BOTH merge families in ONE compiled
  bucketed dispatch (a single ``_pipes`` runtime, at most ``n_buckets``
  step executables TOTAL); and
* a subprocess with FOUR forced host devices serves the same workload on a
  composed ``partition_csr(tile_csr(g, Q), 4)`` view and matches the
  single-device engine (BFS/SSSP bit-identical, PPR allclose).

    PYTHONPATH=src python -m benchmarks.graph_serving_smoke [--fused]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.core.pipeline import CapacityPolicy
from repro.ft import QueryFaultPlan
from repro.graphs.generators import make_dataset
from repro.serve import GraphQuery, GraphServeConfig, GraphServingEngine


def main() -> None:
    g = make_dataset("kron", scale=7)
    rng = np.random.default_rng(11)
    kinds = ["bfs", "sssp", "ppr"]
    queries = [GraphQuery(kinds[i % 3], int(rng.integers(0, g.n_nodes)),
                          iters=4) for i in range(8)]

    plan = QueryFaultPlan(overflow_at=(3,))
    eng = GraphServingEngine(
        g,
        GraphServeConfig(
            query_slots=4, gather="pallas", backoff_base_s=0.001,
            capacity_policy=CapacityPolicy(n_buckets=2, min_capacity=512,
                                           growth=32)),
        fault_plan=plan)
    for q in queries:
        eng.submit(q)
    eng.run_to_completion(5_000)

    assert ("overflow", 3) in eng.injector.fired, \
        "the scripted overflow must actually fire"
    assert eng.quarantines >= 1, "the overflow must quarantine a tenant"
    for q in queries:
        assert q.done, (q.qid, q.status, q.error)
        np.testing.assert_array_equal(
            np.asarray(q.result), eng.solo_reference(q),
            err_msg=f"query {q.qid} ({q.kind} from {q.source}) diverged "
                    f"from its solo run")
    retried = sum(q.retries > 0 for q in queries)
    print(f"graph-serving smoke OK: {len(queries)} mixed queries, "
          f"{eng.tick_no} ticks, {eng.quarantines} quarantine(s), "
          f"{retried} solo retr{'y' if retried == 1 else 'ies'}, "
          f"all results bit-identical to solo runs")


_PARTITIONED_CHILD = textwrap.dedent("""
    import numpy as np
    from repro.core.pipeline import CapacityPolicy
    from repro.graphs.csr import partition_csr, tile_csr
    from repro.graphs.generators import make_dataset
    from repro.serve import GraphQuery, GraphServeConfig, GraphServingEngine

    g = make_dataset("kron", scale=6, edge_factor=8, seed=4)
    pol = CapacityPolicy(n_buckets=2, min_capacity=256, growth=16)
    Q = 4

    def queries():
        rng = np.random.default_rng(3)
        kinds = ["bfs", "sssp", "ppr"]
        return [GraphQuery(kinds[i % 3], int(rng.integers(0, g.n_nodes)),
                           iters=4) for i in range(6)]

    pview = partition_csr(tile_csr(g, Q), 4)
    assert pview.n_parts == 4 and pview.n_tenants == Q
    part_eng = GraphServingEngine(
        pview, GraphServeConfig(query_slots=Q, capacity_policy=pol))
    pqs = queries()
    for q in pqs:
        part_eng.submit(q)
    part_eng.run_to_completion(5_000)

    solo_eng = GraphServingEngine(
        g, GraphServeConfig(query_slots=Q, capacity_policy=pol))
    sqs = queries()
    for q in sqs:
        solo_eng.submit(q)
    solo_eng.run_to_completion(5_000)

    for a, b in zip(pqs, sqs):
        assert a.done and b.done, (a.status, b.status)
        if a.kind == "ppr":
            np.testing.assert_allclose(a.result, b.result,
                                       rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(a.result, b.result)
    print("PARTITIONED-SERVING-PARITY-OK", len(pqs), "queries on",
          pview.n_parts, "devices")
""")


def fused_main() -> None:
    # leg 1: one fused mixed-family tick == one compiled bucketed dispatch
    g = make_dataset("kron", scale=7)
    pol = CapacityPolicy(n_buckets=2, min_capacity=512, growth=32)
    eng = GraphServingEngine(
        g, GraphServeConfig(query_slots=4, capacity_policy=pol))
    mixed = [GraphQuery("bfs", 1), GraphQuery("ppr", 2, iters=4),
             GraphQuery("sssp", 3), GraphQuery("ppr", 5, iters=4)]
    for q in mixed:
        eng.submit(q)
    eng.tick()
    assert list(eng._pipes) == ["fused"], \
        f"mixed families must share ONE runtime, got {list(eng._pipes)}"
    eng.run_to_completion(5_000)
    n_exec = sum(fn._cache_size() for fn in eng._pipes["fused"]._step_b)
    assert n_exec <= pol.n_buckets, \
        (f"mixed BFS+SSSP+PPR workload compiled {n_exec} step executables; "
         f"the fused datapath allows at most n_buckets={pol.n_buckets} TOTAL")
    for q in mixed:
        assert q.done, (q.qid, q.status, q.error)
        np.testing.assert_array_equal(np.asarray(q.result),
                                      eng.solo_reference(q))
    print(f"fused-tick smoke OK: {len(mixed)} mixed-family queries, "
          f"{n_exec} step executable(s) total (<= {pol.n_buckets} buckets), "
          f"results bit-identical to solo runs")

    # leg 2: partitioned serving parity on 4 forced host devices.  The child
    # exists only for forced CPU devices: on a chip this process already
    # holds the device, and a child cannot share it
    if jax.default_backend() == "tpu":
        raise SystemExit(
            "graph_serving_smoke --fused: the partitioned leg runs in a child "
            "on 4 forced CPU host devices; on a TPU run the partitioned path "
            "in one process over jax.devices() (chip_smoke.py --chips 4)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _PARTITIONED_CHILD],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    if proc.returncode != 0:
        print(proc.stdout)
        print(proc.stderr, file=sys.stderr)
        raise SystemExit("partitioned-serving parity child failed")
    assert "PARTITIONED-SERVING-PARITY-OK" in proc.stdout, proc.stdout
    print("partitioned-serving smoke OK: composed "
          "partition_csr(tile_csr(g, 4), 4) view matches the single-device "
          "engine on 4 forced host devices (min bit-identical, add allclose)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fused", action="store_true",
                    help="fused mixed-family tick + 4-forced-device "
                         "partitioned-serving parity legs")
    args = ap.parse_args()
    fused_main() if args.fused else main()
