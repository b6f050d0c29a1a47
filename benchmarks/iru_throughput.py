"""IRU reorder-engine throughput (elements/sec) across frontier sizes.

Tracks the perf trajectory of the repo's hottest path: the reorder engines of
``core.iru``.  Engine rows:

  sort          — stable-sort engine (XLA argsort), jit steady-state
  hash          — batch-parallel hash engine (kernels/iru_reorder/batched.py)
  hash_w{w}     — windowed sweep: same engine through w-element lookahead
                  windows (w in 2048 / 8192 / 32768)
  hash_filter   — filter mode (merge-on-duplicate, ``filter_op="add"``) on a
                  duplicate-heavy stream; sort_filter / hash_ref_filter are
                  the comparison points
  hash_p{P}     — partition sweep (P in 1/2/4/8) of the banked engine
                  (kernels/iru_reorder/banked.py) on a hot-set graph frontier
                  (uniform background + one burst of distinct blocks hashing
                  to a single set, filter mode).  Partition-local occupancy
                  rounds mean the round-peeling loop of the cold partitions
                  stops early and the hot partition peels over ~n/P lanes
                  instead of n — the banking win the paper's 4x2 geometry
                  buys.  hash_p4_cap64 adds the round-cap hybrid fallback on
                  the same stream.
  adv_*         — adversarial single-set stream (every element a distinct
                  block of ONE hash set): adv_sort is the sort engine,
                  adv_hash_cap64 the banked engine with the round cap armed
                  (capacity bypass -> flat -> dense fallback), and
                  adv_hash_uncapped (small sizes only) documents the
                  n/slots-round blowup the cap exists to prevent.
  hash_p4_vmap  — the same 4-partition banked run with ``bank_map="vmap"``
                  (jax.vmap over bank rows instead of lax.map; ROADMAP open
                  item — the notes record which wins on this backend)
  {kron,delaunay}_frontier_*
                — real-graph frontier replay: the concatenated BFS edge
                  frontiers of a Table-3-like graph (the paper's actual
                  index streams, hub-skewed for kron / planar-local for
                  delaunay) through sort / hash / banked-hash engines
  app_{bfs,sssp,pr}_{host,pipe}
                — whole-app wall clock (edges relaxed per second): the host
                  per-iteration loop (hash_ref oracle reorder) vs the
                  device-resident FrontierPipeline (one compiled
                  lax.while_loop, banked hash engine) on a kron graph
  app_*_pipe_bucketed / app_bfs_del_*
                — capacity-bucketed pipeline rows (CapacityPolicy ladder
                  dispatch) on kron, and the high-diameter delaunay BFS
                  rows the bucketing exists for: _del_pipe is the
                  fixed-capacity pipeline paying O(n_edges) per sparse
                  level, _del_pipe_bucketed the ladder dispatch (the
                  headline speedup_bucketed_vs_fixed_bfs_delaunay must
                  stay >= 3)
  hash_ref      — vectorized numpy oracle (host fast path)
  seed_ref      — seed element-sequential numpy oracle   (capped size)
  seed_pallas   — seed element-sequential Pallas interpret (capped size)

seed_pallas collapses superlinearly with n (2.0k el/s at 100k vs 33k at 1k in
earlier runs).  That is an INTERPRET-MODE ARTIFACT, not a kernel regression:
under CPU interpretation every ``pl.store`` into the [n]-sized output refs is
a functional whole-buffer update, so per-element cost grows ~O(n) (measured
steady-state: ~99us/elem at 4k -> ~313us/elem at 32k), plus ~2s of trace
overhead at small n.  On TPU silicon the same stores are in-place VMEM
writes.  The row is kept (capped) as the honest seed baseline; the JSON
carries this note so the number is not misread.

Writes ``BENCH_iru.json`` at the repo root so the numbers are versioned with
the code.  Headline metrics: ``speedup_hash_vs_seed_pallas_100k``,
``partition_sweep_1m`` (the 1->8 scaling curve) and
``adv_cap64_vs_sort_100k`` (the adversarial stream with the cap armed must
stay within 2x of the sort engine).

    PYTHONPATH=src python -m benchmarks.iru_throughput            # full sweep
    PYTHONPATH=src python -m benchmarks.iru_throughput --quick    # CI-sized
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.iru import IRUConfig, iru_reorder, reorder_frontier
from repro.kernels.iru_reorder.ref import hash_reorder_ref, hash_set

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_iru.json")

GEOM = dict(num_sets=1024, slots=32)
SIZES = (1_000, 10_000, 100_000, 1_000_000)
QUICK_SIZES = (1_000, 10_000)
WINDOW_SWEEP = (2_048, 8_192, 32_768)
PART_SWEEP = (1, 2, 4, 8)
# partition-sweep stream: hot burst of this many distinct blocks into one
# set (~200 occupancy rounds at 32 slots) over a uniform background
HOT_BURST = 6_400
# element-sequential seed paths: one element at a time; keep sizes honest but
# bounded so the sweep terminates
SEED_CAP = 100_000
SEED_PALLAS_CAP = 100_000
ADV_UNCAPPED_CAP = 10_000

SEED_PALLAS_NOTE = (
    "seed_pallas throughput collapses superlinearly with n (interpret-mode "
    "artifact, NOT a kernel regression): under CPU interpretation each "
    "pl.store into the [n]-sized output refs is a functional whole-buffer "
    "update, so per-element cost grows ~O(n) — measured ~99us/elem at 4k vs "
    "~313us/elem at 32k steady-state. On TPU silicon the same stores are "
    "in-place VMEM writes.")

APP_ROWS_NOTE = (
    "app_* rows compare realizations of the same traversal at the paper "
    "4x2 geometry: _host = host loop + numpy-oracle reorder (hash_ref), "
    "_hostdev = host loop + the device hash engine (one device round trip "
    "per iteration), _pipe = FrontierPipeline (same device engine, "
    "compiled lax.while_loop, zero host work between iterations), "
    "_pipe_bucketed = the same pipeline under a CapacityPolicy ladder "
    "(capacities dispatched per predicted frontier degree sum; "
    "n_traces <= n_buckets), _pipe_ragged = the bucketed pipeline with "
    "ragged (live-prefix) execution ON: the frontier's exact live count "
    "rides the pipeline as a runtime operand, every reorder/filter/merge "
    "stage runs against the live prefix only, fully-dead streaming windows "
    "skip the engine outright (the window is sized to the ladder's bottom "
    "rung so buckets are whole numbers of windows), and live windows whose "
    "sets stay within two occupancy generations — the common case for "
    "block-clustered wavefronts, whose raw counts blow past the slot depth "
    "on duplicates alone — take the closed-form direct path: generation-"
    "aware dedup off one index sort plus computed emission positions, one "
    "scatter in place of the presorted round machinery. "
    "The bucketing closed the former sparse-frontier CAPACITY tax "
    "(O(n_edges) lanes expanded per sparse level -> O(bucket); "
    "speedup_bucketed_vs_fixed_bfs_delaunay, ~10-25x on CPU); ragged "
    "execution removes the residue the ladder could not: a level that "
    "fills 3% of its bucket no longer pays bucket-sized occupancy rounds "
    "(speedup_ragged_vs_padded_bfs_delaunay; the padded_vs_ragged block "
    "carries the engine-level occupancy sweep). Legacy _pipe/_pipe_bucketed "
    "rows pin ragged=False so their history stays comparable. What remains "
    "of the _pipe vs _host(dev) gap on this CPU backend is the numpy-oracle "
    "artifact (seed_pallas note) — on accelerators the removed "
    "per-iteration dispatch+transfer dominates instead. Dense all-edges "
    "apps (PageRank) predict the top bucket at full occupancy every "
    "iteration, so neither bucketing nor raggedness moves them (noise-level "
    "on these single-rep rows).")

MOE_ROWS_NOTE = (
    "moe_* rows: one MoE FFN layer forward (E=16 experts, top_k=2, "
    "d_model=512, d_ff=1024, cf=1.25; benchmarks/moe_dispatch.py "
    "geometry) at a token sweep, tokens/s best-of-reps. dense is the "
    "GShard one-hot-einsum baseline: it pays O(T*E*C*D) dispatch/combine "
    "einsum FLOPs and materializes the (T, E, C) dispatch tensor, so it "
    "is measured only up to T=4096 on this CPU backend and its tokens/s "
    "collapses with T by construction. iru_sorted (sort-engine emission "
    "ordering) and iru_hash (the occupancy planner — capacity ranks and "
    "drop accounting straight from the hash engine's set-residency "
    "machinery, no emission sort) pay O(T*k*D) gather/scatter. On CPU "
    "all three share the identical expert matmuls, which dominate at "
    "small T, so wall-clock separation is modest; the "
    "moe_dense_vs_hash_{flops,bytes}_* ratios are deterministic "
    "compiled-HLO ratios and carry the accelerator-relevant story (the "
    "dense dispatch tensor is the HBM cliff — see "
    "benchmarks/moe_dispatch.py for the full sweep with extrapolation).")

DIST_ROWS_NOTE = (
    "dist_* rows: edge-partitioned multi-device frontier pipeline "
    "(dist.graph_partition) on forced host devices, one subprocess per "
    "shard count (jax pins the device count at first init). Weak scaling: "
    "delaunay side grows with sqrt(P) so per-shard work is ~constant; "
    "eps is whole-BFS edges/s (compressed exchange, hash reorder), "
    "parity_ok asserts BFS bit-identical + compressed PageRank allclose "
    "vs the single-device pipelines inside each child. Forced host "
    "devices time-slice the same CPU cores, so weak-scaling efficiency "
    "(eps_P / eps_1) is far below 1 here by construction — the rows "
    "track partitioning overhead, not real scaling. "
    "dist_boundary_traffic_reduction is the MEASURED worst-case codec "
    "win at the largest shard count: min over the flag codec (BFS, "
    "exactly 4x: int8 presence flags vs int32 depths) and the "
    "blockwise-int8+EF codec (PageRank rank mass, K + 4*ceil(K/128) "
    "bytes vs 4K); tests/test_graph_partition.py pins it >= 3.")


def _time(fn, *, min_time: float = 0.2, max_reps: int = 50,
          warmup: bool = True) -> float:
    """Best-of-reps steady state (min is robust to the bursty background
    contention of shared CI boxes; the mean of 2 reps is not)."""
    if warmup:
        fn()  # jit compile / caches
    reps, total, best = 0, 0.0, float("inf")
    while reps == 0 or (total < min_time and reps < max_reps):
        t0 = time.monotonic()
        fn()
        dt = time.monotonic() - t0
        total += dt
        best = min(best, dt)
        reps += 1
    return best


def _same_set_indices(k: int, *, num_sets: int, target: int = 3,
                      epb: int = 32) -> np.ndarray:
    """k distinct int32 indices whose blocks all hash to one set.

    Packs up to ``epb`` distinct indices per matching block so the stream
    stays inside int32 for any k (a block id only needs to clear
    ``k / (epb * num_sets)`` on average, far below ``2**31 / epb``)."""
    blocks_needed = -(-k // epb)
    out, start = [], 0
    got = 0
    while got < blocks_needed:
        blocks = np.arange(start, start + 4_000_000, dtype=np.int64)
        hit = blocks[hash_set(blocks, num_sets) == target]
        out.append(hit)
        got += hit.shape[0]
        start += 4_000_000
    blocks = np.concatenate(out)[:blocks_needed]
    assert blocks[-1] * epb + epb - 1 < 2**31, "indices would overflow int32"
    idx = (blocks[:, None] * epb + np.arange(epb)[None, :]).reshape(-1)[:k]
    return idx.astype(np.int32)


def _hotset_stream(n: int, rng: np.random.Generator) -> np.ndarray:
    """Uniform frontier with a single-set burst: the round-skew workload the
    partition sweep measures (hot vertices in a power-law graph frontier)."""
    burst = min(HOT_BURST, max(n // 32, 2))
    idx = rng.integers(0, n, n).astype(np.int32)
    idx[rng.choice(n, burst, replace=False)] = _same_set_indices(
        burst, num_sets=GEOM["num_sets"])
    return idx


def _rows(n: int, quick: bool):
    """Yield (row_name, thunk, timing_kwargs) benchmark rows for size n."""
    rng = np.random.default_rng(n)
    idx_np = rng.integers(0, max(n, 2), n).astype(np.int32)
    idx = jnp.asarray(idx_np)
    dup_np = rng.integers(0, max(n // 4, 2), n).astype(np.int32)
    dup = jnp.asarray(dup_np)
    vals = jnp.asarray(rng.random(n).astype(np.float32))
    one = {}
    slow = dict(min_time=0.0, max_reps=1)

    def jit_row(cfg, i=idx, v=None):
        if v is None:
            return lambda: iru_reorder(i, config=cfg).indices.block_until_ready()
        return lambda: iru_reorder(i, v, config=cfg).indices.block_until_ready()

    yield "sort", jit_row(IRUConfig(mode="sort")), one
    yield "hash", jit_row(IRUConfig(mode="hash", **GEOM)), one
    for w in WINDOW_SWEEP:
        if n > w:
            yield (f"hash_w{w}",
                   jit_row(IRUConfig(mode="hash", window_elems=w, **GEOM)),
                   one)

    # filter-mode rows: duplicate-heavy stream, merge-on-duplicate
    yield ("hash_filter",
           jit_row(IRUConfig(mode="hash", filter_op="add", **GEOM), dup, vals),
           slow if n >= 1_000_000 else one)
    yield ("sort_filter",
           jit_row(IRUConfig(mode="sort", filter_op="add"), dup, vals), one)
    ref_filter_cfg = IRUConfig(mode="hash_ref", filter_op="add", **GEOM)
    yield ("hash_ref_filter",
           lambda: reorder_frontier(dup_np, np.asarray(vals),
                                    config=ref_filter_cfg), one)

    # partition sweep: banked engine on the hot-set frontier
    if not (quick and n > 10_000):
        hot_np = _hotset_stream(n, rng)
        hot = jnp.asarray(hot_np)
        for p in PART_SWEEP:
            cfg = IRUConfig(mode="hash", filter_op="add", n_partitions=p,
                            n_banks=2, **GEOM)
            yield f"hash_p{p}", jit_row(cfg, hot, vals), slow
        cap_cfg = IRUConfig(mode="hash", filter_op="add", n_partitions=4,
                            n_banks=2, round_cap=64, **GEOM)
        yield "hash_p4_cap64", jit_row(cap_cfg, hot, vals), slow
        vmap_cfg = IRUConfig(mode="hash", filter_op="add", n_partitions=4,
                             n_banks=2, bank_map="vmap", **GEOM)
        yield "hash_p4_vmap", jit_row(vmap_cfg, hot, vals), slow

    # adversarial single-set stream (round-count worst case)
    if n <= SEED_CAP:
        adv_np = rng.permutation(_same_set_indices(
            n, num_sets=GEOM["num_sets"]))
        adv = jnp.asarray(adv_np)
        # several reps: the headline adv_cap64_vs_sort ratio should compare
        # steady states, not whichever rep a noisy neighbor landed on
        stable = dict(min_time=0.5)
        yield ("adv_sort",
               jit_row(IRUConfig(mode="sort", filter_op="add"), adv, vals),
               stable)
        yield ("adv_hash_cap64",
               jit_row(IRUConfig(mode="hash", filter_op="add", n_partitions=4,
                                 n_banks=2, round_cap=64, **GEOM), adv, vals),
               stable)
        if n <= ADV_UNCAPPED_CAP:
            yield ("adv_hash_uncapped",
                   jit_row(IRUConfig(mode="hash", filter_op="add", **GEOM),
                           adv, vals),
                   slow)

    ref_cfg = IRUConfig(mode="hash_ref", **GEOM)
    yield "hash_ref", lambda: reorder_frontier(idx_np, config=ref_cfg), one
    if n <= SEED_CAP and not (quick and n > 10_000):
        # one timed rep, no warmup double-run: the first call carries
        # jit compile for seed_pallas but is dwarfed by the loop itself
        seedkw = dict(min_time=0.0, max_reps=1, warmup=False)
        yield ("seed_ref",
               lambda: hash_reorder_ref(idx_np, np.zeros(n, np.float32),
                                        **GEOM), seedkw)
        from repro.kernels.iru_reorder.ops import hash_reorder

        yield ("seed_pallas",
               lambda: hash_reorder(idx, engine="pallas",
                                    **GEOM).indices.block_until_ready(),
               seedkw)


def _bfs_edge_frontiers(g) -> np.ndarray:
    """Concatenated per-level BFS edge frontiers from the max-degree source
    — the traversal's actual irregular index stream (paper Fig. 2), exactly
    as the app itself records it through the TraceRecorder hook."""
    from repro.apps.bfs import bfs
    from repro.apps.trace import TraceRecorder

    source = int(np.argmax(np.asarray(g.degrees())))
    rec = TraceRecorder()
    bfs(g, source, recorder=rec)
    return np.concatenate([idx for idx, _, _ in rec.events]).astype(np.int32)


def frontier_rows(results: dict, quick: bool) -> None:
    """Real-graph frontier replay: engine throughput on BFS edge streams."""
    from repro.graphs.generators import make_dataset

    graphs = {
        "kron": dict(scale=10) if quick else dict(scale=13),
        "delaunay": dict(scale=32) if quick else dict(scale=96),
    }
    banked = IRUConfig(mode="hash", n_partitions=4, n_banks=2, **GEOM)
    engines = {
        "sort": IRUConfig(mode="sort"),
        "hash": IRUConfig(mode="hash", **GEOM),
        "hash_banked": banked,
        "hash_w8192": IRUConfig(mode="hash", window_elems=8192, **GEOM),
    }
    for gname, kw in graphs.items():
        stream = jnp.asarray(_bfs_edge_frontiers(make_dataset(gname, **kw)))
        n = stream.shape[0]
        for ename, cfg in engines.items():
            fn = (lambda s=stream, c=cfg:
                  iru_reorder(s, config=c).indices.block_until_ready())
            sec = _time(fn, min_time=0.0, max_reps=3)
            eps = n / sec if sec > 0 else float("inf")
            row = f"{gname}_frontier_{ename}"
            results.setdefault(row, {})[str(n)] = round(eps, 1)
            print(f"n={n:>9,}  {row:<24} {sec*1e3:10.2f} ms   "
                  f"{eps:14,.0f} elem/s")


def app_rows(results: dict, quick: bool) -> None:
    """Whole-app pipeline-vs-host rows (edges relaxed per second)."""
    from repro.apps.bfs import bfs
    from repro.apps.pagerank import pagerank
    from repro.apps.sssp import sssp
    from repro.graphs.generators import make_dataset

    g = make_dataset("kron", **(dict(scale=10) if quick else dict(scale=13)))
    deg = np.asarray(g.degrees())
    source = int(np.argmax(deg))
    iters = 5
    # same paper 4x2 geometry on both sides: the host loop reorders through
    # the hash_ref oracle per iteration, the pipeline through the banked
    # device engine inside one compiled while_loop.  The streaming window is
    # sized to the capacity ladder's bottom rung (1024) so every bucket is a
    # whole number of windows — the granularity at which ragged execution
    # skips fully-dead windows; padded rows run the identical geometry
    geom = dict(n_partitions=4, n_banks=2, round_cap=64, window_elems=1024,
                **GEOM)
    host_cfg = {
        "bfs": IRUConfig(mode="hash_ref", **geom),
        "sssp": IRUConfig(mode="hash_ref", filter_op="min", **geom),
        "pr": IRUConfig(mode="hash_ref", filter_op="add", **geom),
    }
    pipe_cfg = IRUConfig(mode="hash", **geom)
    # pipelines build (and compile) ONCE; the timed thunk is the steady-state
    # whole-run executable — exactly what a service would amortize
    from repro.apps.bfs import BFS_APP
    from repro.apps.pagerank import pagerank_app
    from repro.apps.sssp import SSSP_APP
    from repro.core.pipeline import CapacityPolicy, FrontierPipeline

    # legacy rows pin ragged=False: their history predates live-prefix
    # execution and the ragged rows (ragged_rows) measure the delta
    bfs_p = FrontierPipeline(g, BFS_APP, mode="hash", iru_config=pipe_cfg,
                             ragged=False)
    sssp_p = FrontierPipeline(g, SSSP_APP, mode="hash", iru_config=pipe_cfg,
                              ragged=False)
    pr_p = FrontierPipeline(g, pagerank_app(iters), mode="hash",
                            iru_config=pipe_cfg, max_iters=iters,
                            ragged=False)
    # capacity-bucketed twins: same engine/geometry, ladder-dispatched
    # capacities (the sparse-frontier-tax fix)
    policy = CapacityPolicy(n_buckets=4, min_capacity=1024, growth=8)
    bfs_pb = FrontierPipeline(g, BFS_APP, mode="hash", iru_config=pipe_cfg,
                              capacity_policy=policy, ragged=False)
    sssp_pb = FrontierPipeline(g, SSSP_APP, mode="hash", iru_config=pipe_cfg,
                               capacity_policy=policy, ragged=False)
    pr_pb = FrontierPipeline(g, pagerank_app(iters), mode="hash",
                             iru_config=pipe_cfg, max_iters=iters,
                             capacity_policy=policy, ragged=False)
    # the high-diameter graph the capacity tax actually bites on: delaunay
    # BFS pays O(n_edges) per O(frontier)-sized level without bucketing
    gd = make_dataset("delaunay", **(dict(scale=32) if quick
                                     else dict(scale=96)))
    source_d = int(np.argmax(np.asarray(gd.degrees())))
    bfs_d = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=pipe_cfg,
                             ragged=False)
    bfs_db = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=pipe_cfg,
                              capacity_policy=policy, ragged=False)
    # per app: host loop + numpy-oracle reorder (hash_ref), host loop + the
    # DEVICE hash engine (one device round trip per iteration — what the
    # pipeline exists to remove), the fixed-capacity pipeline (one compiled
    # while_loop for the whole run) and its capacity-bucketed twin
    hostdev_cfg = {k: dataclasses.replace(c, mode="hash")
                   for k, c in host_cfg.items()}
    rows = {
        "app_bfs_host": (g.n_edges, lambda: bfs(
            g, source, mode="iru", iru_config=host_cfg["bfs"])),
        "app_bfs_hostdev": (g.n_edges, lambda: bfs(
            g, source, mode="iru", iru_config=hostdev_cfg["bfs"])),
        "app_bfs_pipe": (g.n_edges,
                         lambda: np.asarray(bfs_p.run(source))),
        "app_bfs_pipe_bucketed": (g.n_edges,
                                  lambda: np.asarray(bfs_pb.run(source))),
        "app_sssp_host": (g.n_edges, lambda: sssp(
            g, source, mode="iru", iru_config=host_cfg["sssp"])),
        "app_sssp_hostdev": (g.n_edges, lambda: sssp(
            g, source, mode="iru", iru_config=hostdev_cfg["sssp"])),
        "app_sssp_pipe": (g.n_edges,
                          lambda: np.asarray(sssp_p.run(source))),
        "app_sssp_pipe_bucketed": (g.n_edges,
                                   lambda: np.asarray(sssp_pb.run(source))),
        "app_pr_host": (g.n_edges * iters, lambda: pagerank(
            g, iters=iters, mode="iru", iru_config=host_cfg["pr"])),
        "app_pr_hostdev": (g.n_edges * iters, lambda: pagerank(
            g, iters=iters, mode="iru", iru_config=hostdev_cfg["pr"])),
        "app_pr_pipe": (g.n_edges * iters,
                        lambda: np.asarray(pr_p.run())),
        "app_pr_pipe_bucketed": (g.n_edges * iters,
                                 lambda: np.asarray(pr_pb.run())),
        "app_bfs_del_host": (gd.n_edges, lambda: bfs(
            gd, source_d, mode="iru", iru_config=host_cfg["bfs"])),
        "app_bfs_del_hostdev": (gd.n_edges, lambda: bfs(
            gd, source_d, mode="iru", iru_config=hostdev_cfg["bfs"])),
        "app_bfs_del_pipe": (gd.n_edges,
                             lambda: np.asarray(bfs_d.run(source_d))),
        "app_bfs_del_pipe_bucketed": (
            gd.n_edges, lambda: np.asarray(bfs_db.run(source_d))),
    }
    for name, (edges, fn) in rows.items():
        sec = _time(fn, min_time=0.2, max_reps=5)
        eps = edges / sec if sec > 0 else float("inf")
        results.setdefault(name, {})[str(edges)] = round(eps, 1)
        print(f"n={edges:>9,}  {name:<28} {sec*1e3:10.2f} ms   "
              f"{eps:14,.0f} edge/s")


def ragged_rows(out: dict, quick: bool = False) -> None:
    """Padded-vs-ragged rows: the occupancy residue live-prefix execution
    removes.

    Engine level: the duplicate-heavy ``hash_filter`` stream at ONE padded
    size with the live prefix swept from 1% to 100% occupancy.  The padded
    engine pays multi-round peeling sized by the buffer; the ragged run
    keys dead lanes out of every sort/scan and its round structure follows
    the live prefix — streams whose sets stay within two occupancy
    generations take the closed-form direct path with computed emission
    positions.  The ``padded_vs_ragged`` block records the sweep
    (``padded_cost_ratio`` = ragged wall clock / padded wall clock at the
    same buffer size; << 1 at low occupancy is the point).

    App level: high-diameter delaunay BFS through the bucketed pipeline,
    ``ragged=False`` vs ``ragged=True`` (identical ladder, geometry and
    result).  Sparse levels fill a few percent of their bucket, so this is
    where the residue bit hardest — ``speedup_ragged_vs_padded_bfs_delaunay``
    is the headline and tests/test_iru_ragged.py pins its floor (>= 1.5) on
    the checked-in JSON.
    """
    from repro.apps.bfs import BFS_APP
    from repro.core.pipeline import CapacityPolicy, FrontierPipeline
    from repro.graphs.generators import make_dataset

    results = out.setdefault("results", {})
    # --- engine occupancy sweep ------------------------------------------
    n = 100_000 if quick else 1_000_000
    rng = np.random.default_rng(n)
    dup = jnp.asarray(rng.integers(0, max(n // 4, 2), n).astype(np.int32))
    vals = jnp.asarray(rng.random(n).astype(np.float32))
    cfg = IRUConfig(mode="hash", filter_op="add", **GEOM)
    sec_pad = _time(lambda: iru_reorder(
        dup, vals, config=cfg).indices.block_until_ready(),
        min_time=0.0, max_reps=2)
    sweep = {}
    for frac in (0.01, 0.1, 0.5, 1.0):
        m = max(int(n * frac), 1)
        sec = _time(lambda m=m: iru_reorder(
            dup, vals, config=cfg,
            n_live=jnp.int32(m)).indices.block_until_ready(),
            min_time=0.0, max_reps=2)
        sweep[str(frac)] = {
            "live": m,
            "ragged_elem_per_s": round(m / sec, 1) if sec > 0 else None,
            "padded_cost_ratio": round(sec / sec_pad, 3),
        }
        print(f"n={n:>9,}  ragged hash_filter occ={frac:<5} "
              f"{sec*1e3:10.2f} ms   cost vs padded: "
              f"{sweep[str(frac)]['padded_cost_ratio']}x")
    out["padded_vs_ragged"] = {
        "engine": "hash_filter",
        "padded_size": n,
        "padded_elem_per_s": round(n / sec_pad, 1),
        "occupancy": sweep,
    }
    # --- whole-app: delaunay BFS, bucketed ladder, padded vs ragged ------
    gd = make_dataset("delaunay", **(dict(scale=32) if quick
                                     else dict(scale=96)))
    source_d = int(np.argmax(np.asarray(gd.degrees())))
    # identical geometry to app_rows (window = the ladder's bottom rung, so
    # buckets are whole numbers of windows): the twins differ ONLY in the
    # ragged flag
    geom = dict(n_partitions=4, n_banks=2, round_cap=64, window_elems=1024,
                **GEOM)
    pipe_cfg = IRUConfig(mode="hash", **geom)
    policy = CapacityPolicy(n_buckets=4, min_capacity=1024, growth=8)
    padded = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=pipe_cfg,
                              capacity_policy=policy, ragged=False)
    ragged = FrontierPipeline(gd, BFS_APP, mode="hash", iru_config=pipe_cfg,
                              capacity_policy=policy, ragged=True)
    # a whole-graph run outlasts the default min_time, which would collapse
    # best-of-reps to best-of-ONE right after the 1M-element sweep above —
    # give the headline ratio a real sample of reps to take the min over
    sec_p = _time(lambda: np.asarray(padded.run(source_d)),
                  min_time=1.0, max_reps=5)
    sec_r = _time(lambda: np.asarray(ragged.run(source_d)),
                  min_time=1.0, max_reps=5)
    for name, sec in (("app_bfs_del_pipe_bucketed", sec_p),
                      ("app_bfs_del_pipe_ragged", sec_r)):
        eps = gd.n_edges / sec if sec > 0 else float("inf")
        results.setdefault(name, {})[str(gd.n_edges)] = round(eps, 1)
        print(f"n={gd.n_edges:>9,}  {name:<28} {sec*1e3:10.2f} ms   "
              f"{eps:14,.0f} edge/s")
    ratio = round(sec_p / sec_r, 2)
    out["speedup_ragged_vs_padded_bfs_delaunay"] = ratio
    floor = "" if quick else (" (>= 1.5x required at this scale: the "
                              "padded-size residue must stay gone)")
    print(f"ragged vs padded bucketed pipeline, delaunay BFS: "
          f"{ratio}x{floor}")
    if not quick and ratio < 1.5:
        # tests/test_iru_ragged.py pins this floor on the checked-in JSON:
        # committing a refresh below it fails tier-1
        print("WARNING: ragged delaunay BFS below the 1.5x floor — do not "
              "commit this refresh without investigating", file=sys.stderr)


def serving_rows(out: dict, quick: bool = False) -> None:
    """Multi-tenant graph serving throughput (queries/s) — the ROADMAP's
    multi-query serving column.

    ``serving_queries_per_s``: N mixed BFS/SSSP/PPR queries through ONE
    ``GraphServingEngine`` on the fused tagged-lane datapath (steady-state:
    engine + compiled step built once, timed run is submissions +
    run_to_completion).
    ``serving_vs_sequential_solo``: the same query list as back-to-back solo
    ``FrontierPipeline`` runs (also steady-state) — the multiplexing ratio.
    ``serving_fused_vs_split``: the same workload through the split
    per-family engine (``fused=False``, one batched step per family per
    tick) over the fused engine — the family-fusion win;
    ``tests/test_graph_serving.py`` pins a >= 1.0 floor (fusing may never
    lose to splitting).
    ``serving_ragged_vs_padded``: the same workload with occupancy-aware
    ragged steps disabled (``ragged=False``) over the ragged default — the
    serving-side padded-size residue.
    On this CPU backend the ratio sits BELOW 1: the composite step's cost
    scales with the merged frontier across all replicas, and CPU execution
    is serial, so multiplexing buys nothing over back-to-back solo runs
    here.  The row exists for the accelerator story (one dispatch serving
    every tenant vs one dispatch per query per iteration) and to keep the
    absolute queries/s floor pinned; the regression test guards
    ``serving_queries_per_s``, not the ratio.
    """
    from repro.core.pipeline import CapacityPolicy
    from repro.graphs.generators import make_dataset
    from repro.serve.graph_engine import (GraphQuery, GraphServeConfig,
                                          GraphServingEngine)

    g = make_dataset("kron", scale=9 if quick else 11)
    n_q = 8 if quick else 16
    kinds = ["bfs", "sssp", "ppr"]

    def queries():
        rng = np.random.default_rng(7)  # identical workload for every leg
        return [GraphQuery(kinds[i % 3], int(rng.integers(0, g.n_nodes)),
                           iters=5) for i in range(n_q)]

    def make_engine(**kw):
        return GraphServingEngine(g, GraphServeConfig(
            query_slots=8, capacity_policy=CapacityPolicy(
                n_buckets=2, min_capacity=4096, growth=32), **kw))

    def serve_on(eng):
        def serve():
            qs = queries()
            for q in qs:
                eng.submit(q)
            eng.run_to_completion(50_000)
            assert all(q.done for q in qs)
        return serve

    eng = make_engine()  # fused tagged-lane datapath (the default)
    solo = {k: eng._solo_pipe(GraphQuery(k, 0, iters=5)) for k in kinds}

    def sequential():
        for q in queries():
            np.asarray(solo[q.kind].run(q.source))

    sec_serve = _time(serve_on(eng), min_time=0.2, max_reps=3)
    sec_solo = _time(sequential, min_time=0.2, max_reps=3)
    sec_split = _time(serve_on(make_engine(fused=False)),
                      min_time=0.2, max_reps=3)
    sec_padded = _time(serve_on(make_engine(ragged=False)),
                       min_time=0.2, max_reps=3)
    qps = n_q / sec_serve
    out["serving_queries_per_s"] = round(qps, 2)
    out["serving_vs_sequential_solo"] = round(sec_solo / sec_serve, 2)
    out["serving_fused_vs_split"] = round(sec_split / sec_serve, 2)
    out["serving_ragged_vs_padded"] = round(sec_padded / sec_serve, 2)
    if out["serving_fused_vs_split"] < 1.0:
        # tests/test_graph_serving.py pins this floor on the checked-in
        # JSON: committing a refresh below it fails tier-1
        print("WARNING: fused serving slower than the split engine — do "
              "not commit this refresh without investigating",
              file=sys.stderr)
    out.setdefault("notes", {})["serving"] = (
        f"{n_q} mixed bfs/sssp/ppr queries, 8 slots, kron scale "
        f"{9 if quick else 11}, fused tagged-lane datapath; "
        f"tests/test_graph_serving.py pins the queries_per_s floor and the "
        f">= 1.0 fused_vs_split floor. The vs-sequential ratio is < 1 on "
        f"CPU by construction (composite-step cost scales with the merged "
        f"replica frontier and CPU execution is serial); the multiplexing "
        f"win is dispatch amortization on accelerators. ragged_vs_padded "
        f"is the serving-side occupancy residue (ragged=False twin).")
    print(f"serving: {qps:,.1f} queries/s   "
          f"({out['serving_vs_sequential_solo']}x vs sequential solo runs, "
          f"{out['serving_fused_vs_split']}x vs split engine, "
          f"{out['serving_ragged_vs_padded']}x vs padded steps)")


def moe_rows(out: dict, quick: bool = False) -> None:
    """MoE dispatch throughput (tokens/s) — the ROADMAP's MoE column.

    One MoE FFN layer forward per engine at a token sweep (geometry from
    ``benchmarks/moe_dispatch.py``), plus the deterministic compiled-HLO
    dense-vs-hash FLOP/byte ratios.  ``tests/test_moe_dispatch.py`` pins a
    floor on ``moe_tokens_per_s["iru_hash"]`` and on the FLOP ratio in the
    checked-in JSON.
    """
    from benchmarks import moe_dispatch as md
    from repro.models import moe as moe_mod

    params, moe = md._params()
    results = out.setdefault("results", {})
    sizes = (1024,) if quick else (1024, 4096, 16384)
    dense_cap = 4096  # dense @16384 is ~0.7 TFLOP of einsum — CPU-hostile
    tokens: dict[str, dict[str, float]] = {}
    for dispatch in md.DISPATCHES:
        col: dict[str, float] = {}
        for T in sizes:
            if dispatch == "dense" and T > dense_cap:
                continue

            def fn(p, xx, _d=dispatch):
                y, _ = moe_mod.moe_ffn(p, xx, moe, "swiglu", dispatch=_d)
                return y

            f = jax.jit(fn)
            xr = jax.random.normal(jax.random.PRNGKey(1), (T, md.D),
                                   jnp.float32)
            sec = _time(lambda: f(params, xr).block_until_ready(),
                        min_time=0.2, max_reps=10)
            tps = round(T / sec, 1) if sec > 0 else float("inf")
            col[str(T)] = tps
            results.setdefault(f"moe_{dispatch}", {})[str(T)] = tps
            print(f"T={T:>6,}  moe_{dispatch:<11} {sec*1e3:10.2f} ms   "
                  f"{tps:14,.0f} tok/s")
        tokens[dispatch] = col
    out["moe_tokens_per_s"] = tokens
    # deterministic dense-vs-hash compiled-HLO cost ratios (no wall clock;
    # quick mode never writes JSON, so skip the extra dense compiles there)
    for T in () if quick else (1024, 4096):
        d = md.measure(T, "dense", params, moe, wall=False)
        h = md.measure(T, "iru_hash", params, moe, wall=False)
        out[f"moe_dense_vs_hash_flops_{T}"] = round(
            d["hlo_flops"] / max(h["hlo_flops"], 1), 2)
        out[f"moe_dense_vs_hash_bytes_{T}"] = round(
            d["hlo_bytes"] / max(h["hlo_bytes"], 1), 2)
        print(f"dense vs hash @T={T}: "
              f"{out[f'moe_dense_vs_hash_flops_{T}']}x HLO flops, "
              f"{out[f'moe_dense_vs_hash_bytes_{T}']}x HLO bytes")
    out.setdefault("notes", {})["moe_rows"] = MOE_ROWS_NOTE


def dist_rows(out: dict, quick: bool = False) -> None:
    """Partitioned-pipeline rows — one ``dist_bench`` child per shard count.

    The children exist only for forced CPU host devices: they get
    ``JAX_PLATFORMS=cpu`` and a REPLACED ``XLA_FLAGS`` (bench.sh pins one
    host device for the single-device rows; the children need P of them).
    On a TPU this process holds the chip, so the rows refuse to run there.
    Writes the weak-scaling table, its efficiency column, the measured
    boundary compression headline, and the all-children parity flag.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "dist rows spawn children on forced CPU host devices; on a TPU "
            "run the partitioned path in one process over jax.devices() "
            "(chip_smoke.py --chips 4)")
    base = 32 if quick else 64
    weak: dict[str, dict] = {}
    parity = True
    reduction = None
    for p_n in (1, 2, 4):
        scale = round(base * p_n ** 0.5)
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p_n}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.dist_bench",
             "--parts", str(p_n), "--scale", str(scale)],
            capture_output=True, text=True, env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(f"dist_bench P={p_n} failed:\n{r.stderr[-2000:]}")
        row = json.loads(r.stdout.splitlines()[-1])
        parity = parity and row["parity_ok"]
        weak[str(p_n)] = {k: row[k] for k in
                          ("scale", "n", "m", "lane_cap", "supersteps",
                           "bfs_sec", "eps", "parity_ok")}
        if p_n > 1:
            # worst codec at this shard count: flag (BFS) vs int8+EF (PR)
            red = min(row["traffic_bfs"]["reduction"],
                      row["traffic_pr"]["reduction"])
            reduction = red if reduction is None else min(reduction, red)
            weak[str(p_n)]["traffic_reduction"] = round(red, 2)
        print(f"P={p_n}  delaunay scale={scale:>3}  n={row['n']:>6,}  "
              f"{row['eps']:>12,.0f} edges/s  parity={row['parity_ok']}")
    eff = {p: round(weak[p]["eps"] / (int(p) * weak["1"]["eps"]), 3)
           for p in weak}
    out["dist_weak_scaling"] = weak
    out["dist_weak_scaling_efficiency"] = eff
    out["dist_boundary_traffic_reduction"] = round(reduction, 2)
    out["dist_parity_ok"] = parity
    out.setdefault("notes", {})["dist_rows"] = DIST_ROWS_NOTE
    print(f"dist: boundary traffic reduction {reduction:.2f}x "
          f"(floor 3.0), parity_ok={parity}")


def run(quick: bool = False, apps_only: bool = False) -> dict:
    sizes = QUICK_SIZES if quick else SIZES
    results: dict[str, dict[str, float]] = {}
    if apps_only:
        app_rows(results, quick)
        return {
            "metric": "elements_per_second",
            "backend": jax.default_backend(),
            "results": results,
        }
    for n in sizes:
        for name, fn, tkw in _rows(n, quick):
            sec = _time(fn, **tkw)
            eps = n / sec if sec > 0 else float("inf")
            results.setdefault(name, {})[str(n)] = round(eps, 1)
            print(f"n={n:>9,}  {name:<16} {sec*1e3:10.2f} ms   "
                  f"{eps:14,.0f} elem/s")
    frontier_rows(results, quick)
    app_rows(results, quick)
    out = {
        "metric": "elements_per_second",
        "backend": jax.default_backend(),
        "geometry": dict(GEOM, n_partitions_sweep=list(PART_SWEEP), n_banks=2),
        "sizes": list(sizes),
        "results": results,
        "notes": {"seed_pallas": SEED_PALLAS_NOTE, "app_rows": APP_ROWS_NOTE},
    }
    serving_rows(out, quick)
    ragged_rows(out, quick)
    moe_rows(out, quick)
    dist_rows(out, quick)
    key = str(100_000)
    if key in results.get("hash", {}) and key in results.get("seed_pallas", {}):
        out["speedup_hash_vs_seed_pallas_100k"] = round(
            results["hash"][key] / results["seed_pallas"][key], 1)
        out["speedup_hash_ref_vs_seed_ref_100k"] = round(
            results["hash_ref"][key] / results["seed_ref"][key], 1)
        print(f"\nhash vs seed_pallas @100k: "
              f"{out['speedup_hash_vs_seed_pallas_100k']}x   "
              f"({SEED_PALLAS_NOTE.splitlines()[0]}...)")
        print(f"hash_ref vs seed_ref @100k: "
              f"{out['speedup_hash_ref_vs_seed_ref_100k']}x")
    mkey = str(1_000_000)
    if mkey in results.get("hash_p1", {}):
        sweep = {str(p): results[f"hash_p{p}"][mkey] for p in PART_SWEEP}
        out["partition_sweep_1m"] = sweep
        curve = [sweep[str(p)] for p in PART_SWEEP]
        out["partition_sweep_1m_monotone"] = bool(
            all(a <= b for a, b in zip(curve, curve[1:])))
        print(f"partition sweep @1M (el/s): {sweep}  "
              f"monotone={out['partition_sweep_1m_monotone']}")
    if mkey in results.get("hash_p4_vmap", {}):
        r = round(results["hash_p4_vmap"][mkey] / results["hash_p4"][mkey], 2)
        out["bank_vmap_vs_map_1m"] = r
        winner = "vmap" if r > 1 else "lax.map"
        out["notes"] = dict(out.get("notes", {}), bank_map=(
            f"vmap-over-bank-rows vs lax.map at 1M hot-set stream: "
            f"{r}x — {winner} wins on this backend (ROADMAP open item)"))
        print(f"bank rows vmap vs lax.map @1M: {r}x ({winner} wins)")
    for app in ("bfs", "sssp", "pr", "bfs_del"):
        hk, dk, pk = (f"app_{app}_host", f"app_{app}_hostdev",
                      f"app_{app}_pipe")
        if hk in results and pk in results:
            (ek, hv), = results[hk].items()
            pv = results[pk][ek]
            out[f"speedup_pipeline_vs_host_{app}"] = round(pv / hv, 2)
            line = f"pipeline vs host(oracle) {app}: {round(pv / hv, 2)}x"
            if dk in results:
                dv = results[dk][ek]
                out[f"speedup_pipeline_vs_hostdev_{app}"] = round(pv / dv, 2)
                line += f"   vs host(device engine): {round(pv / dv, 2)}x"
            bk = f"app_{app}_pipe_bucketed"
            if bk in results:
                bv = results[bk][ek]
                out[f"speedup_bucketed_vs_fixed_{app}"] = round(bv / pv, 2)
                line += f"   bucketed vs fixed: {round(bv / pv, 2)}x"
                if dk in results:
                    out[f"speedup_bucketed_vs_hostdev_{app}"] = round(
                        bv / dv, 2)
            print(line)
    if "speedup_bucketed_vs_fixed_bfs_del" in out:
        # the headline the bucketing PR is accountable for: the former
        # sparse-frontier capacity tax on high-diameter graphs
        out["speedup_bucketed_vs_fixed_bfs_delaunay"] = out[
            "speedup_bucketed_vs_fixed_bfs_del"]
        floor = ("" if quick else
                 " (>= 3x required at this scale: the capacity tax must "
                 "stay gone)")
        print(f"bucketed vs fixed-capacity pipeline, delaunay BFS: "
              f"{out['speedup_bucketed_vs_fixed_bfs_del']}x{floor}")
        if not quick and out["speedup_bucketed_vs_fixed_bfs_del"] < 3.0:
            # tests/test_capacity.py pins this floor on the checked-in
            # JSON: committing a refresh below it fails tier-1
            print("WARNING: bucketed delaunay BFS below the 3x floor — "
                  "do not commit this refresh without investigating",
                  file=sys.stderr)
    if key in results.get("adv_sort", {}):
        ratio = round(results["adv_hash_cap64"][key]
                      / results["adv_sort"][key], 2)
        out["adv_cap64_vs_sort_100k"] = ratio
        print(f"adversarial capped hash vs sort @100k: {ratio}x "
              f"(>0.5 means within 2x of the sort engine)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--no-write", action="store_true")
    ap.add_argument("--apps-only", action="store_true",
                    help="only the app-level pipeline-vs-host rows "
                         "(what `make bench-apps-quick` runs)")
    ap.add_argument("--serving-only", action="store_true",
                    help="only the multi-tenant serving rows, merged into "
                         "the existing BENCH_iru.json (no full re-sweep)")
    ap.add_argument("--ragged-only", action="store_true",
                    help="only the padded-vs-ragged rows (engine occupancy "
                         "sweep + delaunay BFS app twins), merged into the "
                         "existing BENCH_iru.json (no full re-sweep)")
    ap.add_argument("--moe-only", action="store_true",
                    help="only the MoE dispatch tokens/s + HLO-ratio rows, "
                         "merged into the existing BENCH_iru.json (no full "
                         "re-sweep)")
    ap.add_argument("--dist-only", action="store_true",
                    help="only the partitioned-pipeline weak-scaling + "
                         "boundary-compression rows (subprocesses with "
                         "forced host devices), merged into the existing "
                         "BENCH_iru.json (no full re-sweep)")
    args = ap.parse_args()
    if args.serving_only or args.ragged_only or args.moe_only or args.dist_only:
        out = json.load(open(OUT_PATH)) if os.path.exists(OUT_PATH) else {}
        out.setdefault("notes", {})
        if args.serving_only:
            serving_rows(out, quick=args.quick)
        if args.ragged_only:
            out["notes"]["app_rows"] = APP_ROWS_NOTE
            ragged_rows(out, quick=args.quick)
        if args.moe_only:
            moe_rows(out, quick=args.quick)
        if args.dist_only:
            dist_rows(out, quick=args.quick)
        if not args.no_write and not args.quick:
            with open(OUT_PATH, "w") as f:
                json.dump(out, f, indent=1)
            print(f"wrote {os.path.normpath(OUT_PATH)}")
        return
    out = run(quick=args.quick, apps_only=args.apps_only)
    if not args.no_write and not args.quick and not args.apps_only:
        with open(OUT_PATH, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {os.path.normpath(OUT_PATH)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
