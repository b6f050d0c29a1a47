"""Quickstart: the IRU in five minutes.

1. the raw reorder primitive and the coalescing win it buys (Figs. 8-10);
2. the device-resident ``FrontierPipeline``: a whole BFS as ONE compiled
   ``lax.while_loop`` — expand → reorder → filter/merge → update with zero
   host work between iterations, reused across sources without recompiling;
3. ``CapacityPolicy`` bucketing: sparse frontiers on high-diameter graphs
   dispatch to ladder-sized step executables instead of paying the
   worst-case ``n_edges`` expansion every level.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.apps.bfs import BFS_APP, bfs
from repro.core import (
    CapacityPolicy,
    FrontierPipeline,
    IRUConfig,
    coalescing_improvement,
    iru_reorder,
    iru_scatter_add,
    iru_scatter_min,
    mean_accesses_per_group,
)
from repro.graphs.generators import make_dataset
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(0)

# An irregular index stream: the edge frontier of a graph exploration —
# duplicate-heavy, no block locality (the paper's Fig. 2 pattern).
frontier = jnp.asarray(rng.integers(0, 16384, 8192), jnp.int32)

print("== The reorder primitive (Fig. 8 pattern) ==")
base_acc = float(mean_accesses_per_group(frontier))
stream = iru_reorder(frontier, config=IRUConfig(mode="sort"))
sort_acc = float(mean_accesses_per_group(stream.indices))
print(f"accesses/warp: baseline {base_acc:.2f} -> sorted {sort_acc:.2f} "
      f"({float(coalescing_improvement(frontier, stream.indices)):.2f}x coalescing)")
assert bool(jnp.all(frontier[stream.positions] == stream.indices))

print("\n== Paper-faithful bounded hash engine, banked 4x2 geometry ==")
banked = IRUConfig(mode="hash", num_sets=1024, slots=32,
                   n_partitions=4, n_banks=2, round_cap=64)
stream_h = iru_reorder(frontier, config=banked)
print(f"hash accesses/warp: "
      f"{float(mean_accesses_per_group(stream_h.indices, stream_h.active)):.2f} "
      f"({banked.bank_parallelism} parallel insert lanes; round_cap guards "
      f"adversarial streams; IRUConfig(bank_map='vmap') batches the bank "
      f"rows instead of lax.map)")

print("\n== Merged atomics (Figs. 9-10): scatter-min / scatter-add ==")
cand = jnp.asarray(rng.random(8192), jnp.float32)
dist = iru_scatter_min(jnp.full((16384,), jnp.inf, jnp.float32), frontier, cand)
expect_min = np.full(16384, np.inf, np.float32)
np.minimum.at(expect_min, np.asarray(frontier), np.asarray(cand))
assert np.allclose(np.asarray(dist), expect_min)
contrib = jnp.asarray(rng.random(8192), jnp.float32)
acc = iru_scatter_add(jnp.zeros((16384,), jnp.float32), frontier, contrib)
expect_add = np.zeros(16384, np.float32)
np.add.at(expect_add, np.asarray(frontier), np.asarray(contrib))
assert np.allclose(np.asarray(acc), expect_add, rtol=1e-4, atol=1e-6)
print("merged scatter-min/add == per-element atomicMin/Add oracles [ok]")

print("\n== FrontierPipeline: the whole traversal on-device ==")
g = make_dataset("kron", scale=11)
source = int(np.argmax(np.asarray(g.degrees())))
pipe = FrontierPipeline(g, BFS_APP, mode="hash", iru_config=banked)
labels = np.asarray(pipe.run(source))          # compiles here, once
labels2 = np.asarray(pipe.run(0))              # new source: same executable
assert pipe.n_traces == 1, "whole-run pipeline must compile exactly once"
np.testing.assert_array_equal(labels, bfs(g, source))   # host parity oracle
reached = int((labels != np.iinfo(np.int32).max).sum())
print(f"kron scale 11 ({g.n_nodes} nodes, {g.n_edges} edges): "
      f"BFS reached {reached} nodes, depth {labels[labels < 1 << 30].max()}; "
      f"1 compile, 2 runs, zero host numpy between iterations [ok]")

print("\n== CapacityPolicy: bucketed capacities for sparse frontiers ==")
# a high-diameter graph: each BFS level touches O(frontier) edges, so the
# fixed n_edges expansion above would pay the full graph EVERY level.  A
# geometric capacity ladder dispatches each level to the smallest compiled
# bucket its predicted degree sum fits (n_traces <= n_buckets).
gd = make_dataset("delaunay", scale=48)
sd = int(np.argmax(np.asarray(gd.degrees())))
policy = CapacityPolicy(n_buckets=3, min_capacity=1024, growth=8)
bucketed = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=banked,
                            capacity_policy=policy)
labels_b = np.asarray(bucketed.run(sd))
np.testing.assert_array_equal(labels_b, bfs(gd, sd))  # host parity oracle
assert bucketed.n_traces <= len(bucketed.buckets)
print(f"delaunay scale 48 ({gd.n_nodes} nodes, {gd.n_edges} edges), "
      f"depth {labels_b[labels_b < 1 << 30].max()}: capacity ladder "
      f"{[c for c, _ in bucketed.buckets]} serviced the whole run in "
      f"{bucketed.n_traces} compiles; sparse levels ran at bucket size, "
      f"not n_edges [ok]")
