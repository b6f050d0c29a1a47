"""Chip smoke: the frontier pipeline and graph serving on TPU, Graph500 graphs.

    python chip_smoke.py [--seed N]     # one chip: every phase below
    python chip_smoke.py --chips 4      # four chips: the partitioned phase only

One process drives the chip(s) through the repo's normal entry points and
checks every result against the host numpy oracles:

* BFS and SSSP (``bfs_pipeline`` semantics through ``FrontierPipeline``) on
  Graph500 Kronecker graphs (A/B/C .57/.19/.19, edge factor 16, uniform
  [0, 1) SSSP weights) in ``baseline`` mode at scale 20, with a two-rung
  capacity ladder, and BFS in ``hash`` reorder mode at scale 18 with one
  rung.  Each top rung is checked against the chip by
  ``compiled.memory_analysis()``; each scale up to Graph500's 22 that a
  mode does not run at is logged with its cut.  Results are bit-identical to
  ``apps.bfs.bfs`` / ``apps.sssp.sssp``.
* Multi-tenant serving: a mixed BFS/SSSP/PPR query set through
  ``GraphServingEngine``'s fused tick, checked against the host oracles
  (PPR: its solo run).
* The compiled (not interpreted) Pallas kernels: the block-reuse gather on
  one real-size expansion level against the XLA gather, and the segment
  merge against its reference.
* ``--chips 4``: ``bfs_partitioned`` / ``sssp_partitioned`` semantics
  through ``PartitionedFrontierPipeline`` (their default ``baseline`` mode)
  on ``partition_csr(g, 4)`` of a scale-18 graph.

Times are smoke output, not benchmark results.  Without a TPU the script
exits non-zero before any phase.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Scales are cut from Graph500's 22 to what one cold run holds in its
# 1200 s: on a v5e a top-rung step costs about 50 ns per edge lane, the
# scale-22 graph takes about 2 minutes to build on the host and its SSSP
# oracle longer, and one hash executable compiles for about 4 minutes.
GRAPH500_SCALE = 22
MAX_SCALE = 20          # baseline BFS and SSSP, two sources each
HASH_SCALE = 18         # hash BFS: a one-rung step sorts every edge lane
EDGE_FACTOR = 16        # Graph500 specification (generators.kron defaults to 8)
SERVE_SCALE = 14        # tile_csr(g, 8) of scale 14 is 3.4M composite edges
SERVE_SLOTS = 8
PART_SCALE = 18         # --chips 4: one quarter of the edges per chip
HBM_HEADROOM = 0.9      # share of bytes_limit one executable may claim
T0 = time.monotonic()


def log(phase: str, **kv) -> None:
    print(f"[{time.monotonic() - T0:7.1f}s] {phase}",
          " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def build_graph(scale: int, seed: int):
    """(device graph, host graph): kron edges, seeded uniform weights."""
    from repro.graphs.csr import CSRGraph
    from repro.graphs.generators import kron

    t0 = time.monotonic()
    g = kron(scale, edge_factor=EDGE_FACTOR, seed=seed)
    w = np.random.default_rng(seed + scale).random(g.n_edges,
                                                   dtype=np.float32)
    dev = CSRGraph(row_ptr=g.row_ptr, col_idx=g.col_idx,
                   weights=jnp.asarray(w))
    host = CSRGraph(row_ptr=np.asarray(g.row_ptr),
                    col_idx=np.asarray(g.col_idx), weights=w)
    log("graph", scale=scale, nodes=g.n_nodes, edges=g.n_edges,
        build_s=round(time.monotonic() - t0, 1))
    return dev, host


def hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def compile_timed(fn, *args):
    """(executable, seconds): a later call of ``fn`` with the same
    arguments reuses the executable."""
    t0 = time.monotonic()
    compiled = fn.lower(*args).compile()
    return compiled, time.monotonic() - t0


def compile_async(pool, label: str, fn, *args):
    """Trace ``fn`` here and compile it on ``pool``: compiles release the
    GIL, so several run at once beside the host's numpy work.  Returns a
    future of (executable, seconds); a later call of ``fn`` with the same
    arguments reuses the executable."""
    lowered = fn.lower(*args)
    log("compile start", what=label)

    def job():
        t0 = time.monotonic()
        compiled = lowered.compile()
        secs = time.monotonic() - t0
        log("compile done", what=label, compile_s=round(secs, 1))
        return compiled, secs

    return pool.submit(job)


def pipeline(g, app, mode: str):
    from benchmarks.common import IRU_HASH
    from repro.core import CapacityPolicy, IRUConfig
    from repro.core.pipeline import FrontierPipeline

    # the paper's banked 4x2 geometry; the reorder scope is the whole
    # frontier (window_elems models the GPU's in-flight window, not a
    # TPU constraint)
    cfg = IRUConfig(**{k: v for k, v in IRU_HASH.items()
                       if k != "window_elems"})
    # baseline: two rungs, sparse levels run a 1/16-capacity step, so bucket
    # hops happen on the chip.  hash: one rung, since each hash executable
    # takes minutes to compile
    policy = CapacityPolicy(n_buckets=2 if mode == "baseline" else 1,
                            min_capacity=max(g.n_edges // 16, 1))
    return FrontierPipeline(g, app, mode=mode, iru_config=cfg,
                            capacity_policy=policy)


def run_args(pipe, source: int = 0):
    state, mask = pipe.init(source)
    return pipe.graph, state, mask, jnp.int32(0), pipe._counts


def check_fit(label, compiled, host, cap) -> float:
    """Log a top rung's bytes against the chip; returns its HBM bytes per
    edge.  Raises when it does not fit."""
    ma = compiled.memory_analysis()
    need = hbm_bytes(compiled)
    fits = need <= HBM_HEADROOM * cap
    log(f"size {label}", nodes=host.n_nodes, edges=host.n_edges,
        argument_bytes=ma.argument_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        temp_bytes_per_edge=round(ma.temp_size_in_bytes / host.n_edges, 1),
        need_bytes=need, limit_bytes=int(HBM_HEADROOM * cap), fits=fits)
    if not fits:
        raise RuntimeError(f"{label}: top rung needs {need} B, over "
                           f"{HBM_HEADROOM} x bytes_limit")
    return need / host.n_edges


def log_cuts(mode: str, scale: int, per_edge: float, edges: int,
             cap: int) -> None:
    """Log each scale above ``scale`` up to Graph500's 22 with the bytes
    its top rung would need (edges double per scale) and why it is cut."""
    for s in range(scale + 1, GRAPH500_SCALE + 1):
        est = int(per_edge * edges * 2 ** (s - scale))
        fits = est <= HBM_HEADROOM * cap
        log(f"size {mode} scale={s}", need_bytes_est=est,
            limit_bytes=int(HBM_HEADROOM * cap), fits=fits,
            cut="a cold run's time" if fits else "over the chip's memory")


def check_traversals(label, pipes, host, sources, compile_s) -> None:
    """Every (app, source) through ``FrontierPipeline.run`` vs the host
    oracle, bit for bit.  The rung executables are compiled ahead; the
    small host-dispatch programs (init, convergence test, bucket
    prediction) are warmed first, so every timed run is warm."""
    from repro.apps.bfs import UNVISITED

    deg = np.diff(host.row_ptr)
    for name, pipe in pipes.items():
        state, mask = pipe.init(sources[0])
        jax.block_until_ready((pipe.app.cond(state, mask),
                               pipe._predict(pipe.graph, mask)))
        for src in sources:
            expect = oracle(host, name, src)
            reach = (expect != UNVISITED) if name == "bfs" else np.isfinite(
                expect)
            hops0 = pipe.n_hops
            t0 = time.monotonic()
            got = pipe.run(src).block_until_ready()
            dt = time.monotonic() - t0
            got = np.asarray(got)
            if not np.array_equal(got, expect):
                raise AssertionError(
                    f"{label} {name} source {src}: "
                    f"{int(np.sum(got != expect))} of {got.size} "
                    f"entries differ from the host oracle")
            edges = int(deg[reach].sum())
            log(f"run {label} {name}", source=src,
                compile_s=round(compile_s[name], 1), warm_run_s=round(dt, 3),
                edges_traversed=edges, teps=f"{edges / dt:.3e}",
                n_traces=pipe.n_traces, n_hops=pipe.n_hops - hops0,
                match="bit-identical")


ORACLES: dict = {}


def oracle(host, name: str, src: int) -> np.ndarray:
    """Host numpy result of ``name`` from ``src`` (computed once)."""
    from repro.apps.bfs import bfs
    from repro.apps.sssp import sssp

    key = (host.n_nodes, name, src)
    if key not in ORACLES:
        ORACLES[key] = (bfs if name == "bfs" else sssp)(host, src)
    return ORACLES[key]


def pick_sources(host, seed: int) -> list[int]:
    """The highest-degree vertex and a seeded vertex of its component."""
    from repro.apps.bfs import UNVISITED

    hub = int(np.argmax(np.diff(host.row_ptr)))
    reach = np.nonzero(oracle(host, "bfs", hub) != UNVISITED)[0]
    other = int(np.random.default_rng(seed).choice(reach[reach != hub]))
    return [hub, other]


def single_chip(seed: int, cap: int) -> None:
    """Every one-chip phase.  The whole-traversal executables compile on
    threads from the start (the ``hash`` one takes minutes) while the host
    builds the graphs and computes the oracles; every later phase compiles
    in the main thread."""
    from repro.apps.bfs import BFS_APP
    from repro.apps.sssp import SSSP_APP

    apps = {"bfs": BFS_APP, "sssp": SSSP_APP}
    # not a with-block: a failing phase must not wait for a compile
    pool = ThreadPoolExecutor(5)
    g_hash, host_hash = build_graph(HASH_SCALE, seed)
    hash_pipe = pipeline(g_hash, BFS_APP, "hash")
    hash_job = compile_async(pool, f"hash bfs scale={HASH_SCALE} rung 0",
                             hash_pipe._run_b[0], *run_args(hash_pipe))

    g, host = build_graph(MAX_SCALE, seed)
    pipes = {n: pipeline(g, a, "baseline") for n, a in apps.items()}
    jobs = {n: [compile_async(pool, f"baseline {n} scale={MAX_SCALE} "
                              f"rung {b}", fn, *run_args(p))
                for b, fn in enumerate(p._run_b)]
            for n, p in pipes.items()}
    # the host oracles while those compile
    for src in pick_sources(host, seed):
        for name in apps:
            oracle(host, name, src)
    for src in pick_sources(host_hash, seed):
        oracle(host_hash, "bfs", src)
    log("oracles", scales=[MAX_SCALE, HASH_SCALE], sources_per_scale=2)

    secs = {}
    for name, p in pipes.items():
        rungs = [job.result() for job in jobs[name]]
        secs[name] = sum(t for _, t in rungs)
        log(f"compile baseline {name}", scale=MAX_SCALE,
            rungs=[b[0] for b in p.buckets],
            rung_compile_s=[round(t, 1) for _, t in rungs])
    # SSSP's top rung also carries the edge weights: it bounds BFS's
    per_edge = check_fit(f"baseline scale={MAX_SCALE}", rungs[-1][0], host,
                         cap)
    log_cuts("baseline", MAX_SCALE, per_edge, host.n_edges, cap)
    del rungs, jobs
    check_traversals(f"baseline@{MAX_SCALE}", pipes, host,
                     pick_sources(host, seed), secs)
    del pipes
    check_kernels(g, host)
    del g, host
    check_serving(seed)

    compiled, secs = hash_job.result()
    pool.shutdown()
    log("compile hash bfs", scale=HASH_SCALE, rungs=[hash_pipe.buckets[0][0]],
        rung_compile_s=[round(secs, 1)])
    per_edge = check_fit(f"hash scale={HASH_SCALE}", compiled, host_hash, cap)
    log_cuts("hash", HASH_SCALE, per_edge, host_hash.n_edges, cap)
    del compiled
    check_traversals(f"hash@{HASH_SCALE}", {"bfs": hash_pipe}, host_hash,
                     pick_sources(host_hash, seed), {"bfs": secs})


def check_kernels(g, host) -> None:
    """The compiled Pallas kernels at real size against their references."""
    log("kernels", step="start")
    from repro.graphs.csr import expand_frontier
    from repro.kernels.coalesced_gather.coalesced_gather import (
        window_contract_ok)
    from repro.core.filter import merge_sorted
    from repro.kernels.iru_reorder.ops import resolve_interpret
    from repro.kernels.segment_merge.ops import segment_merge

    # every vertex at once, a PageRank level: a BFS level of a Kronecker
    # graph skips hubs between its vertices, and a skipped hub's edge range
    # breaks the window contract (the XLA fallback would serve it)
    nodes = jnp.arange(host.n_nodes, dtype=jnp.int32)

    def expand(gather):
        return jax.jit(functools.partial(
            expand_frontier, edge_capacity=host.n_edges, gather=gather,
            with_weights=True))(g, nodes)

    ref = expand("xla")
    if not bool(window_contract_ok(ref.eids)):
        raise AssertionError("expansion offsets break the window contract")
    t0 = time.monotonic()
    got = jax.block_until_ready(expand("pallas"))
    dt = time.monotonic() - t0
    for field in ("dsts", "weights", "eids", "valid"):
        if not np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(ref, field))):
            raise AssertionError(f"pallas gather {field} differs from xla")
    log("gather", level="all_vertices", lanes=int(ref.eids.shape[0]),
        window_contract=True, interpret=resolve_interpret(None),
        first_call_s=round(dt, 2), match="bit-identical")
    del got, ref

    # traversals run gather="xla" by default; with gather="pallas" a level
    # reaches the kernel only where the contract holds, so count the real
    # BFS levels from the hub that it holds on
    from repro.apps.bfs import UNVISITED
    from repro.graphs.csr import frontier_from_mask

    depth = oracle(host, "bfs", int(np.argmax(np.diff(host.row_ptr))))
    contract = jax.jit(lambda g, m: window_contract_ok(expand_frontier(
        g, frontier_from_mask(m), edge_capacity=host.n_edges).eids))
    held = [bool(contract(g, jnp.asarray(depth == d)))
            for d in range(int(depth[depth != UNVISITED].max()) + 1)]
    log("gather", level="bfs_from_hub", levels=len(held),
        levels_contract_held=sum(held), held=held)

    rng = np.random.default_rng(0)
    n = 1 << 18     # 32 grid steps of the default chunk: the carry crosses
    idx = jnp.asarray(np.sort(rng.integers(0, n // 4, n)), jnp.int32)
    val = jnp.asarray(rng.random(n, dtype=np.float32))
    tags = idx % 3 == 0
    for op in ("min", "add", "tagged"):
        t = tags if op == "tagged" else None
        m, surv = segment_merge(idx, val, op=op, tags=t)
        mr, sr = merge_sorted(idx, val, op, tags=t)
        s = np.asarray(sr)
        exact = s & ~np.asarray(tags) if op == "tagged" else (
            s if op == "min" else np.zeros_like(s))
        if not (np.array_equal(np.asarray(surv), s)
                and np.array_equal(np.asarray(m)[exact], np.asarray(mr)[exact])
                and np.allclose(np.asarray(m)[s], np.asarray(mr)[s],
                                rtol=1e-5)):
            raise AssertionError(f"segment_merge {op} differs from merge_sorted")
        log("segment_merge", op=op, lanes=n,
            interpret=resolve_interpret(None),
            match="survivors bit-identical")


def check_serving(seed: int) -> None:
    from repro.serve import GraphQuery, GraphServeConfig, GraphServingEngine

    log("serve", step="start")
    g, host = build_graph(SERVE_SCALE, seed)
    sources = pick_sources(host, seed)
    rng = np.random.default_rng(seed)
    deg = np.diff(host.row_ptr)
    pool = np.nonzero(deg > 0)[0]
    kinds = ("bfs", "sssp", "ppr")
    queries = [GraphQuery(kinds[i % 3],
                          sources[0] if i < 3 else int(rng.choice(pool)))
               for i in range(3 * SERVE_SLOTS)]
    # one rung: each rung is one more executable per kind to compile
    eng = GraphServingEngine(g, GraphServeConfig(query_slots=SERVE_SLOTS))
    log("serve", scale=SERVE_SCALE, slots=SERVE_SLOTS,
        composite_edges=eng.cgraph.n_edges, queries=len(queries),
        fused=eng.cfg.fused, mode=eng.cfg.mode)
    for q in queries:
        eng.submit(q)
    t0 = time.monotonic()
    eng.run_to_completion()
    dt = time.monotonic() - t0
    for q in queries:
        if not q.done:
            raise AssertionError(f"query {q.qid} ended {q.status}: {q.error}")
        if q.kind == "ppr":
            # fused PPR reassociates fp adds across tenants (not bit-exact)
            ok = np.allclose(q.result, eng.solo_reference(q), rtol=1e-4,
                             atol=1e-9)
        else:
            # the solo runs are bit-identical to the host oracles (parity
            # tests), and each solo kind would be one more compile
            ok = np.array_equal(q.result, oracle(host, q.kind, q.source))
        if not ok:
            raise AssertionError(f"query {q.qid} ({q.kind}) differs from "
                                 f"its reference")
    n_exec = sum(fn._cache_size() for fn in eng._pipes["fused"]._step_b)
    log("serve", done=len(queries), ticks=eng.tick_no,
        wall_s=round(dt, 2), queries_per_s=round(len(queries) / dt, 2),
        step_executables=n_exec, quarantines=eng.quarantines,
        match="bfs/sssp bit-identical to the host oracles, "
              "ppr allclose to its solo run")


def four_chips(seed: int) -> None:
    """``bfs_partitioned`` / ``sssp_partitioned`` semantics on
    ``partition_csr(g, 4)`` of the PART_SCALE graph, one shard per chip, in
    the wrappers' default (``baseline``) mode: a ``hash`` partitioned step
    takes minutes to compile, which four chips would sit out."""
    from repro.dist.graph_partition import (PartitionedFrontierPipeline,
                                            partitioned_bfs_app,
                                            partitioned_sssp_app)
    from repro.graphs.csr import partition_csr
    from repro.launch.mesh import make_graph_mesh

    g, host = build_graph(PART_SCALE, seed)
    hub = int(np.argmax(np.diff(host.row_ptr)))
    # the host oracles run beside the partition, the compiles and the runs
    pool = ThreadPoolExecutor(1)
    expected = {name: pool.submit(oracle, host, name, hub)
                for name in ("bfs", "sssp")}
    t0 = time.monotonic()
    part = partition_csr(g, 4)
    log("partition", shards=4, block=part.block, edge_cap=part.edge_cap,
        ghost_cap=part.ghost_cap, lane_cap=part.lane_cap,
        local_edges=np.asarray(part.n_local_edges).tolist(),
        partition_s=round(time.monotonic() - t0, 1))
    del g
    mesh = make_graph_mesh(4)
    makers = {"bfs": partitioned_bfs_app, "sssp": partitioned_sssp_app}
    pipes = {name: PartitionedFrontierPipeline(part, make(part), mesh=mesh)
             for name, make in makers.items()}
    del part
    for name, p in pipes.items():
        compiled, secs = compile_timed(p._step_b[0], p.part, *p.init(0))
        log(f"compile partitioned {p.mode} {name}", compile_s=round(secs, 1),
            step_bytes_per_device=hbm_bytes(compiled))
        del compiled
        t0 = time.monotonic()
        got = np.asarray(p.run(hub))
        dt = time.monotonic() - t0
        expect = expected[name].result()
        if not np.array_equal(got, expect):
            raise AssertionError(
                f"partitioned {name}: {int(np.sum(got != expect))} "
                f"entries differ from the host oracle")
        log(f"run partitioned {p.mode} {name}", source=hub,
            run_s=round(dt, 3), supersteps=p.supersteps,
            n_traces=p.n_traces, match="bit-identical")
    pool.shutdown()
    shards = {s.device.id: s.data.shape
              for s in pipes["bfs"].part.col_idx.addressable_shards}
    for d in jax.devices()[:4]:
        st = d.memory_stats() or {}
        log("device_memory", device=d.id, col_idx_shard=shards.get(d.id),
            bytes_in_use=st.get("bytes_in_use"),
            peak_bytes_in_use=st.get("peak_bytes_in_use"),
            bytes_limit=st.get("bytes_limit"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    from repro.kernels.iru_reorder.ops import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    t0 = time.monotonic()
    cache = enable_compile_cache()
    interpret = resolve_interpret(None)
    cap = int(devices[0].memory_stats()["bytes_limit"])
    log("platform", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), count=len(devices),
        resolve_interpret=interpret, bytes_limit=cap, cache=cache,
        seed=args.seed)
    if interpret:
        raise AssertionError("Pallas kernels would run interpreted on TPU")
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            single_chip(args.seed, cap)
    except BaseException:
        # fail now: compile threads still running would hold the exit
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    log("total", wall_s=round(time.monotonic() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
