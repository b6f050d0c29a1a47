"""Capacity bucketing + expansion/compaction edge cases.

Covers the bucketed-dispatch contract of ``core.pipeline``:

* ``CapacityPolicy`` ladder construction (default = one full-capacity
  bucket, geometric rungs, dedupe at the top);
* bucketed BFS/SSSP parity with the host oracles on kron and delaunay,
  with ``n_traces <= n_buckets`` asserted and the default policy
  bit-identical to the fixed-capacity pipeline;
* overflow detection and re-dispatch (``EdgeFrontier.overflow``), including
  the host-path RuntimeError when even the top bucket cannot fit;

and the expansion-layer regressions this PR fixes:

* ``expand_frontier`` on a zero-length frontier array (F=0) — crashed with
  a gather-slice TypeError;
* ``CSRGraph.edge_sources`` under ``jit`` — crashed with
  TracerArrayConversionError;
* empty graph (0 edges), empty mask, single-node frontiers, exact bucket
  boundaries, and ``_merge_identity`` on unsigned dtypes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.bfs import BFS_APP, bfs, bfs_pipeline
from repro.apps.sssp import SSSP_APP, sssp, sssp_pipeline
from repro.apps.trace import TraceRecorder
from repro.core import CapacityPolicy, IRUConfig
from repro.core.pipeline import FrontierPipeline, _merge_identity
from repro.graphs.csr import (
    expand_frontier,
    from_edges,
    frontier_degree_sum,
    frontier_from_mask,
)
from repro.graphs.generators import make_dataset

BANKED = IRUConfig(num_sets=64, slots=8, n_partitions=4, n_banks=2,
                   round_cap=64)
POLICY = CapacityPolicy(n_buckets=4, min_capacity=256, growth=8)


@pytest.fixture(scope="module", params=["kron", "delaunay"])
def graph(request):
    kw = {"kron": dict(scale=9), "delaunay": dict(scale=16)}[request.param]
    g = make_dataset(request.param, **kw)
    g.source = int(np.argmax(np.asarray(g.degrees())))
    return g


def _tiny():
    """3-cycle plus an isolated node (degree-0 tail)."""
    return from_edges(np.array([0, 1, 2]), np.array([1, 2, 0]), 4)


# ---------------------------------------------------------------------------
# CapacityPolicy ladder
# ---------------------------------------------------------------------------

def test_default_policy_is_one_full_bucket():
    assert CapacityPolicy().ladder(110_908, 8_192) == ((110_908, 8_192),)


def test_ladder_geometric_rungs_and_node_compaction():
    pol = CapacityPolicy(n_buckets=4, min_capacity=2_048, growth=8)
    # growth runs past the capacity after two rungs: dedupe to three
    assert pol.ladder(110_908, 8_192) == (
        (2_048, 2_048), (16_384, 8_192), (110_908, 8_192))
    # top rung always carries the full node frontier
    assert pol.ladder(1_000, 300) == ((1_000, 300),)
    assert pol.ladder(0, 3) == ((0, 3),)


def test_policy_validation():
    with pytest.raises(ValueError):
        CapacityPolicy(n_buckets=0)
    with pytest.raises(ValueError):
        CapacityPolicy(min_capacity=0)
    with pytest.raises(ValueError):
        CapacityPolicy(growth=1)


# ---------------------------------------------------------------------------
# expansion-layer regressions
# ---------------------------------------------------------------------------

def test_expand_frontier_zero_length_frontier():
    """F=0 regression: cum[F-1]/clip(...,0,F-1) were ill-formed at F=0."""
    g = _tiny()
    for cap in (None, 2):
        ef = expand_frontier(g, jnp.zeros((0,), jnp.int32),
                             edge_capacity=cap, with_weights=True)
        assert ef.valid.shape == (g.n_edges if cap is None else cap,)
        assert int(ef.valid.sum()) == 0
        assert not bool(ef.overflow)
        assert np.all(np.asarray(ef.srcs) == g.n_nodes)
        assert np.all(np.asarray(ef.dsts) == g.n_nodes)
        assert ef.weights.shape == ef.valid.shape


def test_edge_sources_under_jit():
    """jit regression: np.asarray(self.degrees()) on a traced array."""
    g = make_dataset("kron", scale=8)
    got = jax.jit(lambda gg: gg.edge_sources())(g)
    expect = np.repeat(np.arange(g.n_nodes), np.asarray(g.degrees()))
    np.testing.assert_array_equal(np.asarray(got), expect)
    # degree-0 nodes (isolated tail) are skipped, not mis-assigned
    gt = _tiny()
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda gg: gg.edge_sources())(gt)), [0, 1, 2])


def test_expand_frontier_empty_graph():
    g = from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 3)
    ef = expand_frontier(g, jnp.array([0, 1], jnp.int32))
    assert ef.valid.shape == (0,)
    assert not bool(ef.overflow)
    assert int(frontier_degree_sum(g, jnp.ones((3,), bool))) == 0


def test_expand_frontier_empty_mask_and_single_node(graph):
    n = graph.n_nodes
    ef = expand_frontier(graph, frontier_from_mask(
        jnp.zeros((n,), bool), size=16), edge_capacity=16)
    assert int(ef.valid.sum()) == 0 and not bool(ef.overflow)
    deg = np.asarray(graph.degrees())
    node = int(np.argmin(np.where(deg > 0, deg, deg.max() + 1)))
    mask = jnp.zeros((n,), bool).at[node].set(True)
    cap = int(deg[node])
    ef = expand_frontier(graph, frontier_from_mask(mask, size=1),
                         edge_capacity=cap)
    assert int(ef.valid.sum()) == cap and not bool(ef.overflow)
    np.testing.assert_array_equal(
        np.asarray(ef.dsts),
        np.asarray(graph.col_idx)[deg[:node].sum():deg[:node].sum() + cap])


def test_expansion_at_exact_bucket_boundary():
    """Degree sum == capacity fits (no overflow); one more edge overflows."""
    g = _tiny()
    f = jnp.array([0, 1, 2], jnp.int32)  # degree sum exactly 3
    ef = expand_frontier(g, f, edge_capacity=3)
    assert int(ef.valid.sum()) == 3 and not bool(ef.overflow)
    ef = expand_frontier(g, f, edge_capacity=2)
    assert int(ef.valid.sum()) == 2 and bool(ef.overflow)
    # duplicated ids inflate the degree sum past the default n_edges bound
    ef = expand_frontier(g, jnp.array([0, 0, 1, 2], jnp.int32))
    assert bool(ef.overflow)


@pytest.mark.parametrize("order,cap_share", [("ascending", 1.0),
                                             ("shuffled", 1.0),
                                             ("shuffled", 0.5)])
def test_expansion_lanes_match_host_csr_walk(order, cap_share):
    """Every lane's (src, eid, dst) is the host CSR walk of the frontier in
    its given order: degree-0 nodes, sentinels and stray negative ids
    expand to nothing, and a truncated capacity keeps the lane prefix."""
    g = make_dataset("kron", scale=9)
    rp, ci = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    deg = np.diff(rp)
    rng = np.random.default_rng(5)
    nodes = rng.choice(g.n_nodes, 200, replace=False)
    nodes = np.concatenate([nodes, np.nonzero(deg == 0)[0][:5]])
    nodes = np.sort(nodes) if order == "ascending" else rng.permutation(nodes)
    f = np.concatenate([nodes, [g.n_nodes, -1, g.n_nodes]]).astype(np.int32)
    want_eid = np.concatenate([np.arange(rp[v], rp[v + 1]) for v in nodes])
    want_src = np.repeat(nodes, deg[nodes])
    cap = int(want_eid.size * cap_share)
    ef = expand_frontier(g, jnp.asarray(f), edge_capacity=cap)
    assert bool(ef.overflow) == (cap < want_eid.size)
    assert int(ef.n_valid) == cap
    np.testing.assert_array_equal(np.asarray(ef.eids), want_eid[:cap])
    np.testing.assert_array_equal(np.asarray(ef.srcs), want_src[:cap])
    np.testing.assert_array_equal(np.asarray(ef.dsts), ci[want_eid[:cap]])


def test_frontier_degree_sum_forms_agree(graph):
    rng = np.random.default_rng(3)
    mask = jnp.asarray(rng.random(graph.n_nodes) < 0.2)
    want = int(np.asarray(graph.degrees())[np.asarray(mask)].sum())
    assert int(frontier_degree_sum(graph, mask)) == want
    assert int(frontier_degree_sum(graph, frontier_from_mask(mask))) == want
    ef = expand_frontier(graph, frontier_from_mask(mask))
    assert int(ef.valid.sum()) == want


def test_frontier_from_mask_size_bound():
    mask = jnp.array([True, False, True, True])
    np.testing.assert_array_equal(
        np.asarray(frontier_from_mask(mask, size=3)), [0, 2, 3])
    out = frontier_from_mask(mask, size=6)
    np.testing.assert_array_equal(np.asarray(out), [0, 2, 3, 4, 4, 4])


@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.uint8, jnp.int32,
                                   jnp.float32])
def test_merge_identity_is_neutral(dtype):
    """max identity must be the dtype minimum — unsigned included (the old
    ``-big - 1`` relied on wraparound for uintN)."""
    for op, red in (("min", jnp.minimum), ("max", jnp.maximum),
                    ("add", jnp.add)):
        ident = _merge_identity(op, dtype)
        assert ident.dtype == jnp.dtype(dtype)
        x = jnp.array([0, 1, 5], dtype)
        np.testing.assert_array_equal(np.asarray(red(x, ident)),
                                      np.asarray(x))
    assert int(_merge_identity("max", jnp.uint32)) == 0
    assert int(_merge_identity("min", jnp.uint32)) == 2**32 - 1


# ---------------------------------------------------------------------------
# bucketed pipeline: parity + compile bound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,cfg", [
    pytest.param("baseline", None, id="baseline"),
    pytest.param("hash", BANKED, id="hash_banked4x2"),
])
def test_bucketed_bfs_parity_and_trace_bound(graph, mode, cfg):
    base = bfs(graph, graph.source)
    pipe = FrontierPipeline(graph, BFS_APP, mode=mode, iru_config=cfg,
                            capacity_policy=POLICY)
    assert len(pipe.buckets) > 1
    np.testing.assert_array_equal(np.asarray(pipe.run(graph.source)), base)
    np.testing.assert_array_equal(np.asarray(pipe.run(graph.source)), base)
    np.testing.assert_array_equal(np.asarray(pipe.run(0)), bfs(graph, 0))
    assert pipe.n_traces <= len(pipe.buckets), (pipe.n_traces, pipe.buckets)


def test_bucketed_sssp_parity(graph):
    base = sssp(graph, graph.source)
    got = sssp_pipeline(graph, graph.source, mode="hash", iru_config=BANKED,
                        capacity_policy=POLICY)
    np.testing.assert_array_equal(base, got)


def test_default_policy_matches_fixed_pipeline(graph):
    """Default policy (one bucket at n_edges) = today's pipeline exactly."""
    fixed = FrontierPipeline(graph, BFS_APP, mode="hash", iru_config=BANKED)
    default = FrontierPipeline(graph, BFS_APP, mode="hash", iru_config=BANKED,
                               capacity_policy=CapacityPolicy())
    assert default.buckets == ((graph.n_edges, graph.n_nodes),)
    a = np.asarray(fixed.run(graph.source))
    b = np.asarray(default.run(graph.source))
    np.testing.assert_array_equal(a, b)
    assert fixed.n_traces == 1 and default.n_traces == 1


def test_bucketed_instrumented_matches_host_trace(graph):
    cfg = IRUConfig(num_sets=64, slots=8)
    pipe = FrontierPipeline(graph, BFS_APP, mode="hash", iru_config=cfg,
                            capacity_policy=POLICY)
    rec = TraceRecorder()
    got = pipe.run_instrumented(graph.source, recorder=rec)
    np.testing.assert_array_equal(np.asarray(got), bfs(graph, graph.source))
    host_rec = TraceRecorder()
    bfs(graph, graph.source, mode="iru",
        iru_config=IRUConfig(mode="hash", num_sets=64, slots=8),
        recorder=host_rec)
    # bucketed capacities change lane padding, never the recorded accesses
    assert len(rec.events) == len(host_rec.events)
    assert rec.iru_elements == host_rec.iru_elements


def test_boundary_hovering_frontier_does_not_pingpong():
    """Down-hop hysteresis: a frontier whose degree sum alternates across a
    rung boundary (within the 2x margin) must stay in the larger bucket,
    not pay one host dispatch per level."""
    # chain v_i -> v_{i+1} plus back-edges to long-visited nodes: the
    # frontier is always the single chain node (count=1) but its degree
    # sum alternates 3/6 around the bottom rung capacity of 4
    L = 46
    src, dst = list(range(L)), list(range(1, L + 1))
    for i in range(7, L):
        for k in range(2 if i % 2 == 0 else 5):
            src.append(i), dst.append(i - 2 - k)
    g = from_edges(np.array(src), np.array(dst), L + 1, dedup=False)
    pipe = FrontierPipeline(g, BFS_APP, mode="baseline",
                            capacity_policy=CapacityPolicy(
                                n_buckets=3, min_capacity=4, growth=8))
    labels = np.asarray(pipe.run(0))
    np.testing.assert_array_equal(labels, bfs(g, 0))
    assert int(labels[L]) == L  # the traversal really went L levels deep
    assert pipe.n_hops <= 3, (
        f"{pipe.n_hops} host dispatches for {L} levels: the boundary "
        f"oscillation the hysteresis exists to prevent")


def test_checked_in_bench_keeps_bucketed_floor():
    """The BENCH_iru.json headline this PR is accountable for: delaunay
    BFS bucketed >= 3x the fixed-capacity pipeline.  Guards the committed
    numbers — a bench refresh that regresses the dispatch fails tier-1."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_iru.json")
    bench = json.load(open(path))
    assert bench["speedup_bucketed_vs_fixed_bfs_delaunay"] >= 3.0, bench[
        "speedup_bucketed_vs_fixed_bfs_delaunay"]


def test_bucketed_forced_hop_via_small_min_capacity(graph):
    """min_capacity below the source degree forces >= 1 bucket hop."""
    deg = int(np.asarray(graph.degrees())[graph.source])
    pol = CapacityPolicy(n_buckets=3, min_capacity=max(deg // 4, 1),
                         growth=64)
    pipe = FrontierPipeline(graph, BFS_APP, mode="baseline",
                            capacity_policy=pol)
    np.testing.assert_array_equal(np.asarray(pipe.run(graph.source)),
                                  bfs(graph, graph.source))
    assert 1 < pipe.n_traces <= len(pipe.buckets)


# ---------------------------------------------------------------------------
# overflow re-dispatch
# ---------------------------------------------------------------------------

def test_step_dispatch_walks_up_on_overflow(graph, monkeypatch):
    """A lying predictor is corrected by step()'s overflow walk-up, not
    ignored."""
    pipe = FrontierPipeline(graph, BFS_APP, mode="baseline",
                            capacity_policy=CapacityPolicy(
                                n_buckets=4, min_capacity=8, growth=8))
    state, mask = pipe.init(graph.source)
    # step until the frontier outgrows the smallest bucket (a max-degree
    # source guarantees it within the first couple of levels)
    for _ in range(graph.n_nodes):
        if int(frontier_degree_sum(graph, mask)) > pipe.buckets[0][0]:
            break
        r = pipe.step(state, mask)
        state, mask = r.state, r.mask
    need = int(frontier_degree_sum(graph, mask))
    assert need > pipe.buckets[0][0], "frontier never outgrew bucket 0"
    # force dispatch to always start at bucket 0: the step overflows there
    # and step() must walk up to a fitting rung
    monkeypatch.setattr(pipe, "_host_bucket", lambda need, count: 0)
    out_small = pipe._step_b[0](pipe.graph, state, mask, pipe._counts)
    assert bool(out_small[6])  # overflowed at the small bucket
    steps = [r["steps"] for r in pipe.stats()["rungs"]]
    r = pipe.step(state, mask)
    assert r.bucket > 0 and not r.overflow
    assert int(r.n_edges) == need  # n_edges: nothing truncated
    # only the step that fitted is counted, at the rung it ran on
    steps[r.bucket] += 1
    assert [x["steps"] for x in pipe.stats()["rungs"]] == steps


def test_overflow_at_top_bucket_raises():
    """Caller-shrunk edge_capacity: detected, not silently truncated."""
    src = np.zeros(8, np.int64)
    dst = np.arange(1, 9)
    g = from_edges(src, dst, 9)  # star: source degree 8
    pipe = FrontierPipeline(g, BFS_APP, mode="baseline", edge_capacity=4)
    with pytest.raises(RuntimeError, match="overflow"):
        pipe.run_instrumented(0)
    with pytest.raises(RuntimeError, match="overflow"):
        pipe.run(0)
