"""Graph500 BFS over several chips: whole traversals through
``PartitionedFrontierPipeline.run``.

The configuration names the app (``bfs``: Graph500 kernel 2, hop depths),
the shard count (``n_parts``, one contiguous vertex block per chip), the
reorder engine, the boundary codec (``compress``) and the capacity ladder,
whose rungs are over a shard's local edge capacity.  The graph is made on
the first chip as the one-chip cells make theirs, copied to the host for
the reference, then partitioned on the chips by the program
(``graphs.csr.partition_csr``: each shard built on its own chip).  Roots,
the window and ``teps`` are the one-chip cells' (``bench/drivers/
analytics.py``), and the check is theirs: every depth of every traversal
against the plain reference.

``measured()`` adds the partitioned pipeline's own counts over the window
(``stats()`` read before and after it): supersteps, host syncs, the lanes
the boundary all-to-all shipped and those that carried a value, and the
bytes it put on the wire.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import trafficgen
from bench.drivers import analytics
from bench.drivers.analytics import compare  # noqa: F401 (bench/control.py)
from bench.graphgen import make_graph

# the window's counts: host counters, then per-rung device counters summed
HOST_COUNTS = ("supersteps", "host_syncs")
RUNG_COUNTS = ("live_lanes", "compiled_lanes", "boundary_lanes",
               "boundary_live_lanes", "wire_bytes")


def window_counts(before: dict, after: dict) -> dict:
    """``stats()`` counts of the window alone, rungs summed."""
    out = {k: after[k] - before[k] for k in HOST_COUNTS}
    for k in RUNG_COUNTS:
        out[k] = sum(a[k] - b[k] for a, b in zip(after["rungs"],
                                                  before["rungs"]))
    return out


class Cell(analytics.Cell):
    """``analytics.Cell`` with the partitioned program: its own set-up,
    the same window, roots and check, and the window's counts."""

    def setup(self) -> None:
        # first, before any device work: a program whose partitioned
        # pipeline counts nothing cannot run this cell
        from repro.dist import graph_partition as gp

        if not hasattr(gp.PartitionedFrontierPipeline, "stats"):
            raise RuntimeError(
                "PartitionedFrontierPipeline has no stats(): this program "
                "cannot run a partitioned cell")
        from repro.core import CapacityPolicy, IRUConfig
        from repro.graphs.csr import CSRGraph, partition_csr

        cfg = self.cfg
        if cfg["app"] != "bfs":
            raise ValueError(f"the partitioned driver runs bfs, not "
                             f"{cfg['app']!r}")
        t0 = time.monotonic()
        (row_ptr, col_idx, weights), self.n_edges = make_graph(cfg)
        # the reference's copy; BFS reads no weights
        self.row_ptr = np.asarray(row_ptr)
        self.col_idx, self.weights = np.asarray(col_idx), None
        self.log(f"graph: {self.n_edges} directed edges, "
                 f"{time.monotonic() - t0:.2f} s (with the host copy)")
        # the pad vertex (the last row) is never a root
        self.degrees = np.diff(self.row_ptr)[:-1]
        t0 = time.monotonic()
        part = jax.block_until_ready(partition_csr(
            CSRGraph(row_ptr=row_ptr, col_idx=col_idx, weights=weights),
            cfg["n_parts"]))
        self.partition_s = time.monotonic() - t0
        del row_ptr, col_idx, weights
        self.log(f"partition: {self.partition_s:.3f} s, block {part.block}, "
                 f"edge_cap {part.edge_cap}, ghost_cap {part.ghost_cap}, "
                 f"lane_cap {part.lane_cap}, local edges "
                 f"{np.asarray(part.n_local_edges).tolist()}, ghosts "
                 f"{np.asarray(part.n_ghosts).tolist()}")
        eng = cfg["engine"]
        ladder = eng["capacity_ladder"]
        policy = CapacityPolicy(
            n_buckets=ladder["n_buckets"],
            min_capacity=max(part.edge_cap // ladder["min_divisor"], 1))
        iru = IRUConfig(**eng["iru"]) if eng.get("iru") else None
        self.pipe = gp.PartitionedFrontierPipeline(
            part, gp.partitioned_bfs_app(part), mode=eng["mode"],
            iru_config=iru, capacity_policy=policy,
            compress=cfg["compress"])
        del part
        roots = trafficgen.generate(self.mix, self.seed, self.degrees)
        n_warm = int(self.mix["warmup"])
        self.roots = roots[n_warm:]
        # as the one-chip cells warm up: until every rung is traced and one
        # traversal lowers nothing new
        for i, root in enumerate(roots[:n_warm]):
            before = self.compiles.n
            jax.block_until_ready(self.pipe.run(root))
            if (self.compiles.n == before
                    and self.pipe.n_traces >= len(self.pipe.buckets)):
                break
        self.log(f"warm after {i + 1} traversal(s); rungs "
                 f"{self.pipe.buckets}")

    def window(self) -> None:
        before = self.pipe.stats()
        super().window()
        self.counts = window_counts(before, self.pipe.stats())
        self.log(f"window counts: {self.counts}")

    def release(self) -> None:
        """Log each chip's peak memory; free the program."""
        for d in self.pipe.mesh.devices.flat:
            stats = d.memory_stats() or {}
            self.log(f"chip {d.id}: peak {stats.get('peak_bytes_in_use')} "
                     f"of {stats.get('bytes_limit')} bytes")
        del self.pipe

    def measured(self) -> dict:
        return dict(super().measured(), partition_s=self.partition_s,
                    **self.counts)
