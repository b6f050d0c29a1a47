"""CI smoke: the edge-partitioned frontier pipeline on 4 forced host devices.

Forces the device count BEFORE jax initializes (jax pins it at first init),
then runs the full partitioned machinery at a size CI can afford:

  * partition a small kron graph into 4 halo'd shards and check the edge
    multiset survives the relabeling,
  * one compressed partitioned BFS superstep through ``shard_map`` (flag
    codec over the int8 all-to-all) — the frontier after step one must be
    exactly the source's out-neighbors,
  * whole-run parity: compressed partitioned BFS bit-identical and
    compressed partitioned PageRank allclose vs the single-device
    pipelines,
  * the static traffic accounting reports the flag codec's exact 4x.

    PYTHONPATH=src python -m benchmarks.dist_smoke
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", "")).strip()

import numpy as np


def main() -> None:
    import jax

    from repro.apps import bfs_pipeline, pagerank_pipeline
    from repro.dist.graph_partition import (
        PartitionedFrontierPipeline, partitioned_bfs_app,
        partitioned_pagerank_app)
    from repro.graphs.csr import partition_csr
    from repro.graphs.generators import kron

    assert jax.device_count() == 4, jax.devices()
    g = kron(scale=7, edge_factor=8, seed=4)
    part = partition_csr(g, 4)
    assert int(np.sum(np.asarray(part.n_local_edges))) == g.n_edges
    print(f"[ok] partition: {part.n_parts} shards, block={part.block}, "
          f"ghost_cap={part.ghost_cap}, lane_cap={part.lane_cap}")

    pipe = PartitionedFrontierPipeline(
        part, partitioned_bfs_app(part), mode="hash", compress=True)
    state, mask = pipe.papp.init(part, 0)
    ef = np.zeros((4, 4, max(part.lane_cap, 1)), np.float32)
    state, mask, ef, cont, ovf = pipe._step_b[0](part, state, mask, ef)
    assert int(cont) > 0 and int(ovf) == 0
    # after one superstep the global frontier is exactly source 0's
    # out-neighborhood (minus the source itself)
    got = np.flatnonzero(np.asarray(mask)[:, :part.block].reshape(-1)[:g.n_nodes])
    rp = np.asarray(g.row_ptr)
    want = np.unique(np.asarray(g.col_idx)[rp[0]:rp[1]])
    np.testing.assert_array_equal(got, np.setdiff1d(want, [0]))
    print(f"[ok] superstep 1: frontier == source out-neighbors "
          f"({len(got)} vertices)")

    ref = np.asarray(bfs_pipeline(g, 0))
    full = PartitionedFrontierPipeline(
        part, partitioned_bfs_app(part), mode="hash", compress=True)
    assert (np.asarray(full.run(0)) == ref).all()
    t = full.boundary_traffic()
    assert t["codec"] == "flag" and t["reduction"] == 4.0
    print(f"[ok] BFS parity on 4 shards ({full.supersteps} supersteps, "
          f"flag codec {t['reduction']:.0f}x)")

    pr = PartitionedFrontierPipeline(
        part, partitioned_pagerank_app(part, iters=3), compress=True,
        max_iters=3)
    ref_p = np.asarray(pagerank_pipeline(g, iters=3))
    assert np.allclose(np.asarray(pr.run(0)), ref_p, rtol=2e-3, atol=2e-3)
    tp = pr.boundary_traffic()
    assert tp["codec"] == "int8_ef" and tp["reduction"] >= 3.0
    print(f"[ok] PageRank parity on 4 shards (int8+EF codec "
          f"{tp['reduction']:.2f}x)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
