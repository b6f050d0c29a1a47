"""Graph500 analytics: whole traversals through ``FrontierPipeline.run``.

The configuration names the app (``bfs``: Graph500 kernel 2, hop depths;
``sssp``: kernel 3, float32 distances), the reorder engine and its
geometry, and the capacity ladder.  The traffic gives roots; the window runs
traversals back to back until ``seconds`` have passed, finishing the one in
flight, and ``teps`` is the edges of every traversal over the window's wall
time.  A traversal's edges are the degree sum of the vertices it reached:
every directed CSR edge it crossed, twice Graph500's undirected count.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from bench import reference, trafficgen
from bench.graphgen import make_graph

class Cell:
    def __init__(self, cfg: dict, mix: dict, *, seed: int, seconds: float,
                 compiles, log):
        self.cfg, self.mix, self.seed, self.seconds = cfg, mix, seed, seconds
        self.compiles, self.log = compiles, log

    def setup(self) -> None:
        from repro.apps.bfs import BFS_APP
        from repro.apps.sssp import SSSP_APP
        from repro.core import CapacityPolicy, IRUConfig
        from repro.core.pipeline import FrontierPipeline
        from repro.graphs.csr import CSRGraph

        cfg = self.cfg
        t0 = time.monotonic()
        (row_ptr, col_idx, weights), self.n_edges = make_graph(cfg)
        self.graph = CSRGraph(row_ptr=row_ptr, col_idx=col_idx,
                              weights=weights)
        self.row_ptr = np.asarray(row_ptr)
        self.log(f"graph: {self.n_edges} directed edges, "
                 f"{time.monotonic() - t0:.2f} s")
        # the pad vertex (the last row) is never a root
        self.degrees = np.diff(self.row_ptr)[:-1]
        eng = cfg["engine"]
        ladder = eng["capacity_ladder"]
        policy = CapacityPolicy(
            n_buckets=ladder["n_buckets"],
            min_capacity=max(self.graph.n_edges // ladder["min_divisor"], 1))
        iru = IRUConfig(**eng["iru"]) if eng.get("iru") else None
        app = {"bfs": BFS_APP, "sssp": SSSP_APP}[cfg["app"]]
        self.pipe = FrontierPipeline(self.graph, app, mode=eng["mode"],
                                     iru_config=iru, capacity_policy=policy)
        roots = trafficgen.generate(self.mix, self.seed, self.degrees)
        n_warm = int(self.mix["warmup"])
        self.roots = roots[n_warm:]
        # one warm traversal at a time until every rung is traced and one
        # traversal lowers nothing new: every program the window's
        # traversals can run is then compiled or loaded
        for i, root in enumerate(roots[:n_warm]):
            before = self.compiles.n
            jax.block_until_ready(self.pipe.run(root))
            if (self.compiles.n == before
                    and self.pipe.n_traces >= len(self.pipe.buckets)):
                break
        self.log(f"warm after {i + 1} traversal(s); rungs "
                 f"{self.pipe.buckets}")

    def window(self) -> None:
        pipe, results = self.pipe, []
        hops0 = pipe.n_hops
        t0 = time.monotonic()
        for root in self.roots:
            with jax.profiler.TraceAnnotation("bench.run"):
                out = pipe.run(root)
                out.block_until_ready()
            results.append((root, out))
            if time.monotonic() - t0 >= self.seconds:
                break
        self.window_s = time.monotonic() - t0
        self.hops = pipe.n_hops - hops0
        if len(results) == len(self.roots):
            raise RuntimeError("the traffic ran out of roots inside the "
                               "window: raise max_requests")
        self.results = [(root, np.asarray(out)) for root, out in results]
        self.log(f"window: {len(results)} traversals in {self.window_s:.3f} "
                 f"s, {self.hops} bucket hops")

    def release(self) -> None:
        """Copy what the reference needs to the host; free the program."""
        self.col_idx = np.asarray(self.graph.col_idx)
        self.weights = np.asarray(self.graph.weights)
        del self.pipe, self.graph

    def _reached(self, out: np.ndarray) -> np.ndarray:
        if self.cfg["app"] == "bfs":
            return out[:-1] != reference.UNVISITED
        return np.isfinite(out[:-1])

    def measured(self) -> dict:
        edges = sum(int(self.degrees[self._reached(out)].sum())
                    for _, out in self.results)
        n = len(self.results)
        return {"teps": edges / self.window_s, "attempted": n, "failed": 0,
                "traversals": n, "edges": edges, "window_s": self.window_s,
                "bucket_hops": self.hops}

    def check(self) -> dict:
        return compare(self.cfg["app"], self.row_ptr, self.col_idx,
                       self.weights, self.results, self.log)


def compare(app: str, row_ptr, col_idx, weights, results, log) -> dict:
    """Every value of every traversal against the plain reference: the
    count of vertices that differ, limit 0 (exact).  ``results`` is
    ``[(root, values)]``."""
    if app == "bfs":
        def ref(root):
            return reference.bfs_depths(row_ptr, col_idx, root)
    else:
        def ref(root):
            return reference.sssp_f32(row_ptr, col_idx, weights, root)
    t0 = time.monotonic()
    with ThreadPoolExecutor(4) as pool:
        refs = list(pool.map(ref, [root for root, _ in results]))
    bad = sum(int(np.sum(out != r)) for (_, out), r in zip(results, refs))
    log(f"reference: {len(refs)} traversals, {time.monotonic() - t0:.2f} s")
    name = "depth_mismatches" if app == "bfs" else "dist_mismatches"
    return {name: {"value": bad, "limit": 0}}
