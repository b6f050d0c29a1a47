"""CI smoke: one FrontierPipeline BFS iteration on a small rmat graph with
the Pallas expansion gather in interpret mode, plus a capacity-bucketed
whole run that forces a bucket hop.

Exercises the full device-resident step — expand (Pallas block-reuse
gather) → banked hash reorder → min-merge → scatter update — at a size CI
can afford, the whole-run while_loop driver for parity, the bucketed
dispatch path (small-bucket levels, a host-side hop to a larger bucket,
``n_traces <= n_buckets``) so capacity bucketing is exercised in CI, not
just in tests, and the ragged (live-prefix) path on a sparse delaunay
frontier forcing < 10% bucket occupancy.

    PYTHONPATH=src python -m benchmarks.pipeline_smoke
"""
from __future__ import annotations

import numpy as np

from repro.apps.bfs import BFS_APP, bfs
from repro.core import CapacityPolicy, IRUConfig
from repro.core.pipeline import FrontierPipeline
from repro.graphs.generators import make_dataset


def main() -> None:
    g = make_dataset("kron", scale=7)
    source = int(np.argmax(np.asarray(g.degrees())))
    cfg = IRUConfig(num_sets=64, slots=8, n_partitions=4, n_banks=2,
                    round_cap=64)

    # one instrumented step through the Pallas interpret gather
    pipe = FrontierPipeline(g, BFS_APP, mode="hash", iru_config=cfg,
                            gather="pallas")
    state, mask = pipe.init(source)
    state, mask, idx, act, real, n_edges, overflow, _ = pipe._step(
        g, state, mask, pipe._counts)
    assert int(n_edges) == int(np.asarray(g.degrees())[source]), \
        "first expansion must cover the source's out-edges"
    assert int(np.asarray(act).sum()) > 0
    assert not bool(overflow), "full-capacity expansion can never overflow"

    # the claim in this smoke's name must be true: the monotone offset
    # stream of a CSR expansion satisfies the gather's window contract,
    # so the Pallas kernel (not the fallback) serviced the gather
    from repro.graphs.csr import expand_frontier, frontier_from_mask
    from repro.kernels.coalesced_gather.coalesced_gather import (
        window_contract_ok)

    _, init_mask = pipe.init(source)
    ef = expand_frontier(g, frontier_from_mask(init_mask))
    assert bool(window_contract_ok(ef.eids)), \
        "expansion offsets must hold the block-reuse window contract"

    # whole-run driver (XLA gather) stays bit-identical to the host oracle
    fast = FrontierPipeline(g, BFS_APP, mode="hash", iru_config=cfg)
    np.testing.assert_array_equal(np.asarray(fast.run(source)),
                                  bfs(g, source))
    assert fast.n_traces == 1

    # capacity-bucketed run: min_capacity below the source degree forces at
    # least one host-side hop out of the smallest bucket mid-traversal
    policy = CapacityPolicy(n_buckets=3, min_capacity=32, growth=16)
    bucketed = FrontierPipeline(g, BFS_APP, mode="hash", iru_config=cfg,
                                capacity_policy=policy)
    assert len(bucketed.buckets) > 1, bucketed.buckets
    np.testing.assert_array_equal(np.asarray(bucketed.run(source)),
                                  bfs(g, source))
    assert 1 < bucketed.n_traces <= len(bucketed.buckets), (
        bucketed.n_traces, bucketed.buckets)
    np.testing.assert_array_equal(np.asarray(bucketed.run(0)), bfs(g, 0))
    assert bucketed.n_traces <= len(bucketed.buckets)  # executables reused

    # ragged path: a sparse delaunay frontier filling < 10% of its bucket —
    # live-prefix execution must stay bit-identical to both the padded
    # bucketed run and the host oracle, without any extra compile
    gd = make_dataset("delaunay", scale=24)
    source_d = int(np.argmax(np.asarray(gd.degrees())))
    # one big bucket (>= 10x the max frontier degree sum of a planar
    # graph's BFS levels) forces low occupancy on EVERY level
    sparse_policy = CapacityPolicy(n_buckets=1,
                                   min_capacity=max(gd.n_edges, 1), growth=8)
    rag = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=cfg,
                           capacity_policy=sparse_policy, ragged=True)
    pad = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=cfg,
                           capacity_policy=sparse_policy, ragged=False)
    deg = np.asarray(gd.degrees())
    occ = float(deg[source_d]) / rag.buckets[-1][0]
    assert occ < 0.1, (occ, rag.buckets)
    got = np.asarray(rag.run(source_d))
    np.testing.assert_array_equal(got, np.asarray(pad.run(source_d)))
    np.testing.assert_array_equal(got, bfs(gd, source_d))
    assert rag.n_traces <= len(rag.buckets), (rag.n_traces, rag.buckets)

    print(f"pipeline smoke ok: kron scale 7 ({g.n_nodes} nodes, "
          f"{g.n_edges} edges), first step expanded {int(n_edges)} edges "
          f"through the interpret-mode Pallas gather; whole run matches "
          f"the host oracle in 1 compile; bucketed run (ladder "
          f"{[b[0] for b in bucketed.buckets]}) hopped buckets and matched "
          f"in {bucketed.n_traces} compiles; ragged delaunay run at "
          f"{occ:.1%} source-level bucket occupancy matched padded + host "
          f"in {rag.n_traces} compiles")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
