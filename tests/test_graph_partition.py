"""Edge-partitioned multi-device frontier pipeline (dist.graph_partition).

Coverage:

  * ``partition_csr`` against its numpy oracle (``partition_oracle``),
    bit for bit on every field, over kron, grid, star (hub-skewed
    ``edge_cap``), ghost-free and empty shards and a ``tile_csr`` view;
    with a device per shard, each shard is built on its own device.
  * ``partition_csr`` invariants — every global edge lands in exactly one
    shard (owned by its source), ghost renumbering round-trips, ghost rows
    have degree 0, and the static send/recv boundary maps are transposes of
    each other (what shard p gathers for owner o is exactly what o scatters
    back into its owned rows).
  * codec plumbing — blockwise int8 row quantization round-trip, wire-size
    accounting, and the exact/flag/int8_ef byte ratios the bench reports.
  * single-device (P=1) parity in-process: the partitioned wrappers reduce
    to the plain pipelines bit-for-bit when there is nothing to exchange.
  * multi-device parity in subprocesses (jax pins the device count at first
    init, so forced host devices need a child process — the
    test_distributed.py pattern): BFS/SSSP bit-identical and PageRank
    allclose to single-device on 2 and 4 shards, compressed and exact,
    including under a multi-rung CapacityPolicy with bucket hops + ragged.
  * the checked-in BENCH_iru.json dist rows keep their floors
    (boundary-traffic reduction >= 3x, weak-scaling parity).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs.csr import (CSRGraph, GraphPartition, PartitionedGraphView,
                              from_edges, partition_csr, suggest_partitions,
                              tile_csr)
from repro.graphs.generators import delaunay, kron, make_dataset
from repro.dist.graph_partition import (
    _wire_bytes, bfs_partitioned, dequantize_rows_i8, pagerank_partitioned,
    quantize_rows_i8, sssp_partitioned, PartitionedFrontierPipeline,
    partitioned_bfs_app)
from repro.apps import bfs_pipeline, pagerank_pipeline, sssp_pipeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# the device partition against the host oracle, field by field
# ---------------------------------------------------------------------------

def partition_oracle(graph: CSRGraph, n_parts: int, *, edge_align: int = 8
                     ) -> dict:
    """``partition_csr``'s contract in plain numpy: every field of the
    ``GraphPartition`` it must return, by name."""
    rp = np.asarray(graph.row_ptr, np.int64)
    col = np.asarray(graph.col_idx, np.int64)
    w = np.asarray(graph.weights, np.float32)
    n = int(graph.n_nodes)
    m = int(graph.n_edges)
    block = -(-n // n_parts) if n else 1

    segs = []
    for p in range(n_parts):
        lo = min(p * block, n)
        hi = min(lo + block, n)
        e0, e1 = int(rp[lo]), int(rp[hi])
        seg_dst = col[e0:e1]
        owned = (seg_dst >= lo) & (seg_dst < hi)
        ghosts = np.unique(seg_dst[~owned])  # sorted: owner groups contiguous
        segs.append((lo, hi, seg_dst, w[e0:e1], owned, ghosts))

    ghost_cap = max((len(s[5]) for s in segs), default=0)
    edge_cap = max((len(s[2]) for s in segs), default=0)
    edge_cap = max(edge_align, -(-max(edge_cap, 1) // edge_align) * edge_align)
    lane_cap = 0
    for lo, hi, seg_dst, seg_w, owned, ghosts in segs:
        if len(ghosts):
            counts = np.bincount(ghosts // block, minlength=n_parts)
            lane_cap = max(lane_cap, int(counts.max()))

    local_nodes = block + ghost_cap
    row_ptr_l = np.zeros((n_parts, local_nodes + 1), np.int32)
    col_l = np.full((n_parts, edge_cap), local_nodes, np.int32)
    w_l = np.zeros((n_parts, edge_cap), np.float32)
    ghost_ids = np.full((n_parts, ghost_cap), -1, np.int32)
    n_ghosts = np.zeros((n_parts,), np.int32)
    n_local_edges = np.zeros((n_parts,), np.int32)
    send_slot = np.full((n_parts, n_parts, lane_cap), local_nodes, np.int32)
    send_mask = np.zeros((n_parts, n_parts, lane_cap), bool)
    recv_id = np.full((n_parts, n_parts, lane_cap), block, np.int32)
    recv_mask = np.zeros((n_parts, n_parts, lane_cap), bool)

    for p, (lo, hi, seg_dst, seg_w, owned, ghosts) in enumerate(segs):
        deg = rp[lo + 1:hi + 1] - rp[lo:hi]
        cum = np.concatenate([[0], np.cumsum(deg)])
        row_ptr_l[p, :hi - lo + 1] = cum
        row_ptr_l[p, hi - lo + 1:] = cum[-1]  # padding + ghost rows degree-0
        k = len(seg_dst)
        col_l[p, :k] = np.where(
            owned, seg_dst - lo,
            block + np.searchsorted(ghosts, seg_dst) if len(ghosts)
            else seg_dst - lo)
        w_l[p, :k] = seg_w
        g = len(ghosts)
        ghost_ids[p, :g] = ghosts
        n_ghosts[p] = g
        n_local_edges[p] = k
        if g:
            owner = ghosts // block
            for o in np.unique(owner):
                idx = np.nonzero(owner == o)[0]
                send_slot[p, o, :len(idx)] = block + idx
                send_mask[p, o, :len(idx)] = True
                recv_id[o, p, :len(idx)] = ghosts[idx] - o * block
                recv_mask[o, p, :len(idx)] = True

    return dict(
        row_ptr=row_ptr_l, col_idx=col_l, weights=w_l, ghost_ids=ghost_ids,
        n_ghosts=n_ghosts, n_local_edges=n_local_edges, send_slot=send_slot,
        send_mask=send_mask, recv_id=recv_id, recv_mask=recv_mask,
        n_nodes=n, n_edges=m, n_parts=n_parts, block=block,
        ghost_cap=ghost_cap, lane_cap=lane_cap, edge_cap=edge_cap)


def _star(n: int = 40) -> CSRGraph:
    """A hub joined to every vertex: its shard holds almost every edge."""
    leaves = np.arange(1, n)
    return from_edges(np.zeros(n - 1, np.int64), leaves, n, symmetrize=True)


def _islands() -> CSRGraph:
    """Edges inside vertex blocks only, save one pair between the last two
    blocks of four: shards 0 and 1 have no ghosts."""
    src = np.array([0, 1, 2, 4, 5, 8, 9, 10, 12, 13, 11])
    dst = np.array([1, 2, 3, 5, 7, 9, 10, 11, 13, 15, 12])
    return from_edges(src, dst, 16, symmetrize=True,
                      weights=np.linspace(0.1, 1.1, src.size))


def _single_row_blocks() -> CSRGraph:
    """Nine vertices over eight shards: blocks of two, the last three
    shards empty."""
    src = np.arange(8)
    return from_edges(src, src + 1, 9, symmetrize=True)


PARTITION_GRAPHS = {
    "kron": lambda: kron(scale=7, edge_factor=8, seed=4),
    "grid": lambda: make_dataset("ca", scale=12),
    "star": _star,
    "islands": _islands,
    "tiny_blocks": _single_row_blocks,
}


@pytest.mark.parametrize("n_parts", [1, 2, 4, 8])
@pytest.mark.parametrize("gname", sorted(PARTITION_GRAPHS) + ["kron_tiled"])
def test_device_partition_matches_the_numpy_oracle(gname, n_parts):
    if gname == "kron_tiled":
        view = tile_csr(kron(scale=6, edge_factor=8, seed=4), 3)
        got = partition_csr(view, n_parts)
        assert isinstance(got, PartitionedGraphView) and got.view is view
        got, g = got.part, view
    else:
        g = PARTITION_GRAPHS[gname]()
        got = partition_csr(g, n_parts)
    want = partition_oracle(g, n_parts)
    for field, value in want.items():
        have = np.asarray(getattr(got, field))
        value = np.asarray(value)
        assert have.dtype == value.dtype, (field, have.dtype, value.dtype)
        np.testing.assert_array_equal(have, value, err_msg=field)
    if gname == "star" and n_parts > 1:
        # the hub's shard sets the common edge capacity
        local = want["n_local_edges"]
        assert local[0] > 2 * local[1:].max() and want["edge_cap"] >= local[0]
    if gname == "islands" and n_parts == 4:
        assert list(want["n_ghosts"][:2]) == [0, 0] and want["ghost_cap"] > 0
    if gname == "tiny_blocks" and n_parts == 8:
        assert list(want["n_local_edges"][5:]) == [0, 0, 0]


def test_device_partition_places_one_shard_per_device():
    """With a device per shard, each shard's arrays are built and kept on
    its own device, as ``make_graph_mesh`` lays the shards out."""
    out = run_py("""
        import jax
        from repro.graphs.csr import partition_csr
        from repro.graphs.generators import kron
        from repro.launch.mesh import make_graph_mesh
        part = partition_csr(kron(scale=7, edge_factor=8, seed=4), 4)
        want = list(make_graph_mesh(4).devices.flat)
        for name in ("row_ptr", "col_idx", "weights", "ghost_ids",
                     "send_slot", "recv_id", "recv_mask"):
            shards = getattr(part, name).addressable_shards
            assert [s.device for s in shards] == want, name
            assert all(s.data.shape[0] == 1 for s in shards), name
        print("PLACED OK")
    """, devices=4)
    assert "PLACED OK" in out


# ---------------------------------------------------------------------------
# partitioner invariants
# ---------------------------------------------------------------------------

def _global_edges(g: CSRGraph):
    rp = np.asarray(g.row_ptr)
    src = np.repeat(np.arange(g.n_nodes), np.diff(rp))
    dst = np.asarray(g.col_idx)[: g.n_edges]
    w = np.asarray(g.weights)[: g.n_edges]
    return src, dst, w


def _shard_edges_global(part: GraphPartition, p: int):
    """Shard p's edge list mapped back to global vertex ids."""
    B, L = part.block, part.local_nodes
    rp = np.asarray(part.row_ptr[p])
    ne = int(part.n_local_edges[p])
    src_l = np.repeat(np.arange(L), np.diff(rp))
    dst_l = np.asarray(part.col_idx[p])[:ne]
    w = np.asarray(part.weights[p])[:ne]
    ng = int(part.n_ghosts[p])
    ghosts = np.asarray(part.ghost_ids[p])[:ng]
    src_g = src_l + p * B
    is_ghost = dst_l >= B
    slot = np.clip(dst_l - B, 0, max(ng - 1, 0))
    dst_g = np.where(is_ghost, ghosts[slot] if ng else 0, dst_l + p * B)
    return src_g, dst_g, w, is_ghost


@pytest.mark.parametrize("gname", ["kron", "delaunay"])
@pytest.mark.parametrize("n_parts", [1, 2, 3, 4])
def test_partition_covers_every_edge_once(gname, n_parts):
    g = kron(scale=7, edge_factor=8, seed=4) if gname == "kron" else delaunay(scale=16)
    part = partition_csr(g, n_parts)
    gs, gd, gw = _global_edges(g)
    ss, ds, ws = [], [], []
    for p in range(n_parts):
        src_g, dst_g, w, _ = _shard_edges_global(part, p)
        # ownership: every edge lives on its source's shard
        assert (src_g // part.block == p).all()
        ss.append(src_g); ds.append(dst_g); ws.append(w)
    ss, ds, ws = map(np.concatenate, (ss, ds, ws))
    assert len(ss) == g.n_edges == int(np.sum(np.asarray(part.n_local_edges)))
    want = sorted(zip(gs.tolist(), gd.tolist(), gw.tolist()))
    got = sorted(zip(ss.tolist(), ds.tolist(), ws.tolist()))
    assert want == got


@pytest.mark.parametrize("n_parts", [2, 4])
def test_partition_ghost_rows_and_boundary_maps(n_parts):
    g = kron(scale=7, edge_factor=8, seed=4)
    part = partition_csr(g, n_parts)
    B, L = part.block, part.local_nodes
    for p in range(n_parts):
        rp = np.asarray(part.row_ptr[p])
        assert int(rp[-1]) == int(part.n_local_edges[p])
        assert (np.diff(rp)[B:] == 0).all()  # ghost rows never expand
        ng = int(part.n_ghosts[p])
        ghosts = np.asarray(part.ghost_ids[p])[:ng]
        assert (np.sort(ghosts) == ghosts).all()  # sorted => owner-contiguous
        assert (ghosts // B != p).all()  # a ghost is never locally owned
        # every edge dst is a valid local id (pad never appears inside rows)
        _, _, _, is_ghost = _shard_edges_global(part, p)
        dst_l = np.asarray(part.col_idx[p])[: int(part.n_local_edges[p])]
        assert (dst_l[is_ghost] < B + ng).all()
    # send/recv transpose consistency: the ghost slot shard p gathers for
    # owner o holds exactly the owner-local vertex o receives on that lane
    send_slot = np.asarray(part.send_slot)
    send_mask = np.asarray(part.send_mask)
    recv_id = np.asarray(part.recv_id)
    recv_mask = np.asarray(part.recv_mask)
    for p in range(n_parts):
        ng = int(part.n_ghosts[p])
        ghosts = np.asarray(part.ghost_ids[p])[:ng]
        for o in range(n_parts):
            np.testing.assert_array_equal(send_mask[p, o], recv_mask[o, p])
            lanes = np.flatnonzero(send_mask[p, o])
            slots = send_slot[p, o, lanes]
            assert ((slots >= B) & (slots < B + ng)).all()
            gids = ghosts[slots - B]
            assert (gids // B == o).all()  # gathered for their true owner
            np.testing.assert_array_equal(gids - o * B, recv_id[o, p, lanes])
            # padding lanes carry the documented sentinels
            pad = np.flatnonzero(~send_mask[p, o])
            assert (send_slot[p, o, pad] == L).all()
            assert (recv_id[o, p, pad] == B).all()


def test_partition_single_shard_is_trivial():
    g = delaunay(scale=12)
    part = partition_csr(g, 1)
    assert part.n_parts == 1 and part.ghost_cap == 0 and part.lane_cap == 0
    sub = part.shard_graph(0)
    np.testing.assert_array_equal(np.asarray(sub.row_ptr)[: g.n_nodes + 1],
                                  np.asarray(g.row_ptr))
    np.testing.assert_array_equal(np.asarray(sub.col_idx)[: g.n_edges],
                                  np.asarray(g.col_idx))


def test_partition_validation():
    g = delaunay(scale=8)
    with pytest.raises(ValueError):
        partition_csr(g, 0)
    with pytest.raises(ValueError):
        partition_csr(g, g.n_nodes + 1)


def test_suggest_partitions_scales_with_vmem():
    g = kron(scale=9, edge_factor=8, seed=4)
    p_small = suggest_partitions(g, vmem_bytes=1 << 16)
    p_big = suggest_partitions(g, vmem_bytes=1 << 30)
    assert p_big == 1
    assert p_small >= p_big
    assert p_small & (p_small - 1) == 0  # power of two
    assert p_small <= 256


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

def test_int8_row_quantization_roundtrip():
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(6, 200)).astype(np.float32) * 10)
    q, s = quantize_rows_i8(y)
    assert q.shape == y.shape and q.dtype == jnp.int8
    assert s.shape == (6, 2)  # ceil(200/128) fp32 scales per row
    back = dequantize_rows_i8(q, s)
    assert back.shape == y.shape
    err = np.abs(np.asarray(back) - np.asarray(y))
    # per-block abs-max scaling bounds the error by scale/2 per lane
    bound = np.repeat(np.asarray(s), 128, axis=1)[:, :200] / 2 + 1e-6
    assert (err <= bound).all()
    # zero rows stay exactly zero (no NaN from a 0 scale)
    qz, sz = quantize_rows_i8(jnp.zeros((2, 64)))
    np.testing.assert_array_equal(np.asarray(dequantize_rows_i8(qz, sz)), 0.0)


def test_wire_bytes_ratios():
    k = 256
    raw = _wire_bytes("exact", k, 4)
    assert raw == k * 4
    assert raw / _wire_bytes("flag", k, 4) == 4.0
    # int8 payload + one fp32 scale per 128 lanes
    assert _wire_bytes("int8_ef", k, 4) == k + 4 * 2
    assert raw / _wire_bytes("int8_ef", k, 4) > 3.8


# ---------------------------------------------------------------------------
# P=1 parity in-process (single device; nothing crosses a wire)
# ---------------------------------------------------------------------------

def test_single_shard_bfs_sssp_parity():
    g = kron(scale=7, edge_factor=8, seed=4)
    ref_b = np.asarray(bfs_pipeline(g, 0))
    ref_s = np.asarray(sssp_pipeline(g, 0))
    np.testing.assert_array_equal(bfs_partitioned(g, 0, n_parts=1), ref_b)
    np.testing.assert_array_equal(
        bfs_partitioned(g, 0, n_parts=1, compress=True), ref_b)
    np.testing.assert_array_equal(sssp_partitioned(g, 0, n_parts=1), ref_s)


def test_single_shard_pagerank_parity():
    g = delaunay(scale=12)
    ref = np.asarray(pagerank_pipeline(g, iters=5))
    got = pagerank_partitioned(g, n_parts=1, iters=5)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)


def test_boundary_traffic_accounting_single_shard():
    g = delaunay(scale=8)
    part = partition_csr(g, 1)
    pipe = PartitionedFrontierPipeline(part, partitioned_bfs_app(part))
    pipe.run(0)
    t = pipe.boundary_traffic()
    assert t["codec"] == "exact"
    assert t["raw_bytes_per_superstep"] == 0  # no off-diagonal rows
    assert t["supersteps"] == pipe.supersteps > 0


def test_mesh_too_small_raises():
    if len(jax.devices()) >= 2:
        pytest.skip("needs a single-device environment")
    g = delaunay(scale=8)
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        bfs_partitioned(g, 0, n_parts=2)


# ---------------------------------------------------------------------------
# multi-device parity (subprocesses with forced host devices)
# ---------------------------------------------------------------------------

def test_partitioned_parity_2_and_4_shards():
    """BFS/SSSP bit-identical, PageRank allclose, compressed and exact."""
    out = run_py("""
        import numpy as np
        from repro.graphs.generators import kron
        from repro.apps import bfs_pipeline, pagerank_pipeline, sssp_pipeline
        from repro.dist.graph_partition import (
            bfs_partitioned, pagerank_partitioned, sssp_partitioned)
        g = kron(scale=7, edge_factor=8, seed=4)
        ref_b = np.asarray(bfs_pipeline(g, 0))
        ref_s = np.asarray(sssp_pipeline(g, 0))
        ref_p = np.asarray(pagerank_pipeline(g, iters=5))
        for P in (2, 4):
            for compress in (False, True):
                b = bfs_partitioned(g, 0, n_parts=P, compress=compress)
                assert (b == ref_b).all(), (P, compress, "bfs")
                s = sssp_partitioned(g, 0, n_parts=P, compress=compress)
                assert (s == ref_s).all(), (P, compress, "sssp")
                tol = 2e-3 if compress else 1e-4
                p = pagerank_partitioned(g, n_parts=P, iters=5,
                                         compress=compress)
                assert np.allclose(p, ref_p, rtol=tol, atol=tol), (P, compress)
        print("PARITY OK")
    """, devices=4)
    assert "PARITY OK" in out


def test_partitioned_bucketed_ragged_compressed_hops():
    """Compressed BFS under a multi-rung ladder + ragged + hash reorder stays
    bit-identical while actually hopping buckets."""
    out = run_py("""
        import numpy as np
        from repro.core import CapacityPolicy
        from repro.graphs.csr import partition_csr
        from repro.graphs.generators import delaunay
        from repro.apps import bfs_pipeline
        from repro.dist.graph_partition import (
            PartitionedFrontierPipeline, partitioned_bfs_app)
        g = delaunay(scale=16)
        ref = np.asarray(bfs_pipeline(g, 0))
        part = partition_csr(g, 4)
        pipe = PartitionedFrontierPipeline(
            part, partitioned_bfs_app(part), mode="hash", compress=True,
            ragged=True,
            capacity_policy=CapacityPolicy(n_buckets=3, min_capacity=64))
        got = np.asarray(pipe.run(0))
        assert (got == ref).all()
        assert pipe.n_hops > 1, pipe.n_hops  # the ladder was exercised
        t = pipe.boundary_traffic()
        assert t["codec"] == "flag" and t["reduction"] == 4.0
        print("BUCKETED OK hops=", pipe.n_hops)
    """, devices=4)
    assert "BUCKETED OK" in out


# ---------------------------------------------------------------------------
# checked-in bench floors (refreshed by `make bench-dist`)
# ---------------------------------------------------------------------------

def test_checked_in_bench_keeps_dist_floors():
    """BENCH_iru.json's distributed rows: compressed boundary traffic stays
    >=3x under raw, weak scaling keeps parity on every device count (the
    test_capacity.py / test_moe_dispatch.py floor pattern)."""
    bench = json.load(open(os.path.join(ROOT, "BENCH_iru.json")))
    assert bench["dist_boundary_traffic_reduction"] >= 3.0
    assert bench["dist_parity_ok"] is True
    weak = bench["dist_weak_scaling"]
    assert {"1", "2", "4"} <= set(weak)
    for row in weak.values():
        assert row["parity_ok"] is True
        assert row["eps"] > 0
