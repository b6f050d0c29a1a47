"""Ragged (occupancy-aware) execution: live-prefix semantics end to end.

The contract under test (``ref.ragged_oracle`` is its executable spec):
``n_live`` is a *runtime operand* — never a shape — that restricts every
engine to the first ``n_live`` lanes of the padded buffer.  The output is
the padded-size buffer laid out as

    [0, s)        survivors of the live prefix, in engine emission order
    [s, n - t)    dead lanes, stream order, ``active=False``, original values
    [n - t, n)    the live prefix's filtered tail

which is exactly ``oracle(live prefix)`` with the dead lanes spliced between
survivors and filtered tail.  Covers:

* flat / banked / sort engines vs the composed oracle, all filter ops,
  round caps, ``n_live`` in {0, 1, n//3, n-1, n};
* the exactly-``slots`` flush edge (pads used to occupy hash slots and
  perturb flush timing — ragged execution must flush on live elements only);
* banked bank-capacity bypass decided on the *live* count, not the padded
  size;
* the two-generation route sized to the live stream: around the prefix
  width, past the round cap and past the bank capacity, the arm and the
  plan are those of the full-width plan;
* windowed streams: window ``i`` sees ``clip(n_live - i*w, 0, w)`` live lanes;
* ``n_live == n`` bit-identical to padded execution (no behaviour fork);
* ``EdgeFrontier.n_valid``: always ``sum(valid)`` and never above the
  compacted capacity, including the overflow/shrink path (regression for
  the ``frontier_from_mask(size=)`` interaction);
* pipeline ragged-vs-padded parity on kron + delaunay for BFS / SSSP
  (bit-identical; min is idempotent under pad-induced regrouping) and
  PageRank (allclose; fp-add grouping may differ);
* the compile bound: ragged execution adds ZERO traces — ``n_traces`` per
  bucket is unchanged because the live count is an operand;
* the checked-in BENCH_iru.json ragged-vs-padded floor.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.bfs import BFS_APP, bfs
from repro.apps.pagerank import pagerank_pipeline
from repro.apps.sssp import sssp, sssp_pipeline
from repro.core import CapacityPolicy, IRUConfig
from repro.core.iru import iru_reorder
from repro.core.pipeline import FrontierPipeline
from repro.graphs.csr import expand_frontier, from_edges, frontier_from_mask
from repro.graphs.generators import make_dataset
from repro.kernels.iru_reorder import ref
from repro.kernels.iru_reorder.batched import (
    _two_gen_plan,
    _two_gen_route,
    route_prefix_width,
)
from repro.kernels.iru_reorder.iru_reorder import _hash_set
from repro.kernels.iru_reorder.ops import hash_reorder

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _lives(n):
    return sorted({0, 1, n // 3, n - 1, n})


def _assert_stream(stream, ref_tuple, rtol=None):
    ri, rs, rp, ra = ref_tuple
    np.testing.assert_array_equal(ri, np.asarray(stream.indices))
    np.testing.assert_array_equal(rp, np.asarray(stream.positions))
    np.testing.assert_array_equal(ra, np.asarray(stream.active))
    if rtol is None:
        np.testing.assert_array_equal(rs, np.asarray(stream.secondary))
    else:
        np.testing.assert_allclose(rs, np.asarray(stream.secondary), rtol=rtol)


def _stream_tuple(stream):
    return (np.asarray(stream.indices), np.asarray(stream.secondary),
            np.asarray(stream.positions), np.asarray(stream.active))


# ---------------------------------------------------------------------------
# flat engine vs composed oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_sets,slots", [(16, 4), (8, 2)])
@pytest.mark.parametrize("filter_op", [None, "min", "add"])
@pytest.mark.parametrize("round_cap", [None, 2])
def test_flat_ragged_matches_composed_oracle(num_sets, slots, filter_op,
                                             round_cap):
    rng = np.random.default_rng(num_sets * 31 + slots)
    n = 193
    idx = rng.integers(0, 160, n).astype(np.int32)
    sec = rng.random(n).astype(np.float32)
    for m in _lives(n):
        got = hash_reorder(jnp.asarray(idx), jnp.asarray(sec),
                           num_sets=num_sets, slots=slots,
                           filter_op=filter_op, round_cap=round_cap,
                           n_live=jnp.int32(m))
        want = ref.ragged_oracle(
            ref.hash_reorder_ref_flat, idx, sec, m, num_sets=num_sets,
            slots=slots, filter_op=filter_op, round_cap=round_cap)
        _assert_stream(got, want)


def test_exact_slots_flush_is_decided_on_live_elements():
    """A set whose live prefix holds exactly ``slots`` distinct blocks must
    flush — and a padded run over the same buffer (pads landing in that set)
    must NOT leak the pads into the flush decision under ragged execution."""
    num_sets, slots, epb = 8, 4, 32  # epb = block_bytes // elem_bytes
    # find `slots` block ids all hashing to one set, plus pad-tail block ids
    # hashing to the SAME set: the ragged run must ignore them
    blocks = [b for b in range(4096)
              if int(ref.hash_set(np.array([b]), num_sets)[0]) == 3]
    live_blk, pad_blk = blocks[:slots], blocks[slots:slots + 3]
    idx = np.array([b * epb for b in live_blk + pad_blk], np.int32)
    sec = np.arange(idx.shape[0], dtype=np.float32)
    m = slots  # live prefix = exactly one full set
    got = hash_reorder(jnp.asarray(idx), jnp.asarray(sec), num_sets=num_sets,
                       slots=slots, filter_op="min", n_live=jnp.int32(m))
    want = ref.ragged_oracle(ref.hash_reorder_ref_flat, idx, sec, m,
                             num_sets=num_sets, slots=slots, filter_op="min")
    _assert_stream(got, want)
    # the live prefix really is a flush (all kept, full set): all active,
    # emitted in stream order
    act = np.asarray(got.active)
    assert act[:m].all() and not act[m:].any()
    np.testing.assert_array_equal(np.asarray(got.positions)[:m], np.arange(m))


# ---------------------------------------------------------------------------
# banked engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("filter_op", [None, "min"])
@pytest.mark.parametrize("round_cap", [None, 4])
def test_banked_ragged_matches_composed_oracle(filter_op, round_cap):
    rng = np.random.default_rng(7)
    n = 257
    idx = rng.integers(0, 500, n).astype(np.int32)
    sec = rng.random(n).astype(np.float32)
    for m in _lives(n):
        got = hash_reorder(jnp.asarray(idx), jnp.asarray(sec), num_sets=16,
                           slots=4, filter_op=filter_op, round_cap=round_cap,
                           n_partitions=4, n_live=jnp.int32(m))
        want = ref.ragged_oracle(
            ref.hash_reorder_ref_banked, idx, sec, m, num_sets=16, slots=4,
            filter_op=filter_op, round_cap=round_cap, n_partitions=4)
        _assert_stream(got, want)


def test_banked_capacity_bypass_decided_on_live_count():
    """All-one-partition stream: the padded size would trip the bank-capacity
    bypass, but the decision must follow ``partition_capacity`` of the LIVE
    count — the oracle composition encodes both sides of the threshold."""
    n = 400
    idx = np.full(n, 128, np.int32)  # one block -> one set -> one partition
    idx[200:] = np.arange(200, dtype=np.int32) * 32  # pads spread out
    sec = np.arange(n, dtype=np.float32)
    for m in (32, 150, 200, n):
        got = hash_reorder(jnp.asarray(idx), jnp.asarray(sec), num_sets=16,
                           slots=8, filter_op="min", n_partitions=4,
                           n_live=jnp.int32(m))
        want = ref.ragged_oracle(
            ref.hash_reorder_ref_banked, idx, sec, m, num_sets=16, slots=8,
            filter_op="min", n_partitions=4)
        _assert_stream(got, want)


# ---------------------------------------------------------------------------
# the two-generation route: plan width from the live count
# ---------------------------------------------------------------------------

ROUTE_N, ROUTE_SETS, ROUTE_SLOTS, ROUTE_CAP = 4096, 1024, 32, 4
ROUTE_W = route_prefix_width(ROUTE_N, ROUTE_SETS)


def _route_stream(kind, seed):
    rng = np.random.default_rng(seed)
    n = ROUTE_N
    # half the lanes from a narrow range (duplicates to merge, a filtered
    # tail), half from a wide one (sets stay under two generations)
    idx = np.where(rng.random(n) < 0.5, rng.integers(0, 2000, n),
                   rng.integers(0, 200000, n)).astype(np.int32)
    if kind == "round_cap":
        # one block 200 times: 7 raw rounds of one set, over the cap of 4,
        # while every partition stays within its bank capacity
        idx[rng.choice(n, 200, replace=False)] = 4096
    elif kind == "wrap":
        # three blocks of one set, every index once, inside the prefix
        # width: 96 insertions wrap the set's 32 slots a third time.  They
        # replace lanes of the set's own partition, so no bank overflows
        blocks = np.arange(10000, 30000)
        hot = blocks[ref.hash_set(blocks, ROUTE_SETS) == 5][:3]
        same = np.flatnonzero(
            ref.hash_set(idx[:ROUTE_W] // 32, ROUTE_SETS) % 4 == 5 % 4)
        lanes = rng.choice(same, 96, replace=False)
        idx[lanes] = (hot[:, None] * 32 + np.arange(32)).reshape(-1)
    elif kind == "overflow":
        idx[:] = 128  # every lane in one set, so in one partition
    return idx, rng.random(n).astype(np.float32)


def _parent_arm(idx, m, n_partitions, ok_full):
    """The arm the full-width plan picked: 0 bank overflow, 1 the banked
    rows (or the flat engine's presorted path), 2 the two-generation
    emission."""
    sets = ref.hash_set(idx[:m] // 32, ROUTE_SETS)
    part = np.bincount(sets % n_partitions, minlength=n_partitions)
    if n_partitions > 1 and part.max(initial=0) > ref.partition_capacity(
            m, n_partitions):
        return 0
    r_raw = -(-np.bincount(sets, minlength=ROUTE_SETS).max(initial=0)
              // ROUTE_SLOTS)
    return 2 if ok_full and r_raw <= ROUTE_CAP else 1


ROUTE_CASES = [(p, kind, m, arm)
               for p in (4, 1)
               for kind, m, arm in [
                   ("mixed", 0, 2), ("mixed", 1, 2),
                   ("mixed", ROUTE_W - 1, 2), ("mixed", ROUTE_W, 2),
                   ("mixed", ROUTE_W + 1, 2), ("mixed", ROUTE_N, 2),
                   ("wrap", ROUTE_W, 1), ("round_cap", ROUTE_N, 1)]]
ROUTE_CASES.append((4, "overflow", ROUTE_N, 0))


@pytest.mark.parametrize(
    "n_partitions,kind,m,arm", ROUTE_CASES,
    ids=[f"{'banked' if p > 1 else 'flat'}-{k}-{m}"
         for p, k, m, _ in ROUTE_CASES])
def test_two_gen_route_matches_full_width_plan(n_partitions, kind, m, arm):
    """The route builds no plan, a prefix plan or the full plan from the
    live count and the per-set counts; the arm it picks, the plan it hands
    that arm and the stream it emits are the full-width plan's, and the
    stream is the oracle's on the live prefix.  ``wrap`` is a prefix plan
    whose own guard declines the direct path."""
    assert 0 < ROUTE_W < ROUTE_N
    idx, sec = _route_stream(kind, seed=m + n_partitions)
    geo = dict(num_sets=ROUTE_SETS, slots=ROUTE_SLOTS, filter_op="min",
               round_cap=ROUTE_CAP)
    got = hash_reorder(jnp.asarray(idx), jnp.asarray(sec),
                       n_partitions=n_partitions, n_live=jnp.int32(m), **geo)
    oracle = (ref.hash_reorder_ref_banked if n_partitions > 1
              else ref.hash_reorder_ref_flat)
    kw = dict(n_partitions=n_partitions) if n_partitions > 1 else {}
    _assert_stream(got, ref.ragged_oracle(oracle, idx, sec, m, **geo, **kw))

    @jax.jit
    def plans(i, s, m_live):
        sets = _hash_set(i // 32, ROUTE_SETS)
        live = jnp.arange(ROUTE_N) < m_live
        part = jnp.where(live, sets % n_partitions, n_partitions)
        cnt = jnp.zeros((n_partitions,), jnp.int32).at[part].add(1)
        overflow = (jnp.max(cnt) > ref.partition_capacity(m, n_partitions)
                    if n_partitions > 1 else None)
        plan_kw = dict(n_partitions=n_partitions, num_sets=ROUTE_SETS,
                       slots=ROUTE_SLOTS, filter_op="min")
        return (_two_gen_plan(i, s, live, sets, **plan_kw),
                _two_gen_route(i, s, live, m_live, sets, round_cap=ROUTE_CAP,
                               overflow=overflow, **plan_kw))

    (ok_full, full), (ok, plan) = plans(jnp.asarray(idx), jnp.asarray(sec),
                                        jnp.int32(m))
    assert _parent_arm(idx, m, n_partitions, bool(ok_full)) == arm
    assert bool(ok) == (arm == 2)
    if arm == 2:
        for a, b in zip(full, plan):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sort engine + windowed streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("filter_op", [None, "min", "add"])
@pytest.mark.parametrize("compact", [False, True])
def test_sort_ragged_is_prefix_sort_plus_dead_tail(filter_op, compact):
    rng = np.random.default_rng(11)
    n = 150
    idx = rng.integers(0, 90, n).astype(np.int32)
    sec = rng.random(n).astype(np.float32)
    cfg = IRUConfig(mode="sort", filter_op=filter_op, compact=compact)
    nocompact = dataclasses.replace(cfg, compact=False)
    for m in _lives(n):
        got = iru_reorder(jnp.asarray(idx), jnp.asarray(sec), config=cfg,
                          n_live=jnp.int32(m))
        # expected: sort of the live prefix, dead lanes passed through at
        # the tail (inactive, original values), then compact() if enabled
        pre = iru_reorder(jnp.asarray(idx[:m]), jnp.asarray(sec[:m]),
                          config=nocompact)
        ei = np.concatenate([np.asarray(pre.indices), idx[m:]])
        es = np.concatenate([np.asarray(pre.secondary), sec[m:]])
        ep = np.concatenate([np.asarray(pre.positions),
                             np.arange(m, n, dtype=np.int32)])
        ea = np.concatenate([np.asarray(pre.active), np.zeros(n - m, bool)])
        if compact and filter_op is not None:
            order = np.argsort(~ea, kind="stable")
            ei, es, ep, ea = ei[order], es[order], ep[order], ea[order]
        _assert_stream(got, (ei, es, ep, ea))


@pytest.mark.parametrize("w,n", [(64, 256), (64, 250), (33, 100)])
def test_windowed_ragged_matches_host_oracle(w, n):
    """Window ``i`` gets ``clip(n_live - i*w, 0, w)`` live lanes; the host
    oracle path (``hash_ref``) composes the same contract per window."""
    rng = np.random.default_rng(w * n)
    idx = rng.integers(0, 300, n).astype(np.int32)
    sec = rng.random(n).astype(np.float32)
    dev = IRUConfig(mode="hash", filter_op="add", num_sets=32, slots=8,
                    window_elems=w)
    host = dataclasses.replace(dev, mode="hash_ref")
    for m in _lives(n):
        got = iru_reorder(jnp.asarray(idx), jnp.asarray(sec), config=dev,
                          n_live=jnp.int32(m))
        want = iru_reorder(jnp.asarray(idx), jnp.asarray(sec), config=host,
                           n_live=m)
        _assert_stream(got, _stream_tuple(want), rtol=1e-6)


@pytest.mark.parametrize("engine_kw", [
    pytest.param(dict(), id="flat"),
    pytest.param(dict(n_partitions=4), id="banked"),
])
def test_full_live_count_is_bit_identical_to_padded(engine_kw):
    rng = np.random.default_rng(5)
    n = 200
    idx = jnp.asarray(rng.integers(0, 300, n).astype(np.int32))
    sec = jnp.asarray(rng.random(n).astype(np.float32))
    base = hash_reorder(idx, sec, num_sets=16, slots=4, filter_op="min",
                        **engine_kw)
    got = hash_reorder(idx, sec, num_sets=16, slots=4, filter_op="min",
                       n_live=jnp.int32(n), **engine_kw)
    _assert_stream(got, _stream_tuple(base))


def test_full_live_count_sort_is_bit_identical_to_padded():
    rng = np.random.default_rng(5)
    n = 200
    idx = jnp.asarray(rng.integers(0, 300, n).astype(np.int32))
    sec = jnp.asarray(rng.random(n).astype(np.float32))
    cfg = IRUConfig(mode="sort", filter_op="min")
    base = iru_reorder(idx, sec, config=cfg)
    got = iru_reorder(idx, sec, config=cfg, n_live=jnp.int32(n))
    _assert_stream(got, _stream_tuple(base))


def test_ragged_under_jit_is_operand_not_shape():
    """Two different live counts through ONE jitted callable: results match
    eager, and the callable compiles once (n_live is an operand)."""
    rng = np.random.default_rng(3)
    n = 128
    idx = jnp.asarray(rng.integers(0, 200, n).astype(np.int32))
    sec = jnp.asarray(rng.random(n).astype(np.float32))

    @jax.jit
    def f(i, s, m):
        st = hash_reorder(i, s, num_sets=16, slots=4, filter_op="min",
                          n_live=m)
        return st.indices, st.secondary, st.positions, st.active

    for m in (0, 40, 97, n):
        jt = f(idx, sec, jnp.int32(m))
        eg = hash_reorder(idx, sec, num_sets=16, slots=4, filter_op="min",
                          n_live=jnp.int32(m))
        _assert_stream(eg, tuple(np.asarray(x) for x in jt))
    if hasattr(f, "_cache_size"):
        assert f._cache_size() == 1, f._cache_size()


# ---------------------------------------------------------------------------
# EdgeFrontier.n_valid (satellite: overflow/shrink interaction)
# ---------------------------------------------------------------------------

def _star(deg):
    return from_edges(np.zeros(deg, np.int64), np.arange(1, deg + 1), deg + 1)


def test_n_valid_always_equals_compacted_live_count():
    g = _star(8)
    # fits: n_valid == degree sum
    ef = expand_frontier(g, jnp.array([0], jnp.int32), edge_capacity=8)
    assert int(ef.n_valid) == 8 == int(ef.valid.sum())
    assert not bool(ef.overflow)
    # overflow shrink path: n_valid must report the COMPACTED size (4), not
    # the pre-truncation degree sum (8) — the regression this test pins
    ef = expand_frontier(g, jnp.array([0], jnp.int32), edge_capacity=4)
    assert bool(ef.overflow)
    assert int(ef.n_valid) == 4 == int(ef.valid.sum())
    assert int(ef.n_valid) <= ef.valid.shape[0]
    # F=0 / empty-mask degenerate paths report 0
    ef = expand_frontier(g, jnp.zeros((0,), jnp.int32), edge_capacity=4)
    assert int(ef.n_valid) == 0
    ef = expand_frontier(g, frontier_from_mask(
        jnp.zeros((g.n_nodes,), bool), size=4), edge_capacity=4)
    assert int(ef.n_valid) == 0 == int(ef.valid.sum())


def test_n_valid_with_truncated_frontier_from_mask():
    """frontier_from_mask(size=) silently truncates the node list; the edge
    expansion of the truncated frontier must still satisfy
    n_valid == sum(valid) <= capacity."""
    g = _star(8)
    mask = jnp.ones((g.n_nodes,), bool)  # 9 nodes, only 0 has out-edges
    f = frontier_from_mask(mask, size=2)  # truncates to nodes {0, 1}
    ef = expand_frontier(g, f, edge_capacity=6)
    assert int(ef.n_valid) == int(ef.valid.sum()) <= 6
    ef = expand_frontier(g, f, edge_capacity=16)
    assert int(ef.n_valid) == int(ef.valid.sum()) == 8


# ---------------------------------------------------------------------------
# pipeline: ragged vs padded parity + compile bound
# ---------------------------------------------------------------------------

BANKED = IRUConfig(num_sets=64, slots=8, n_partitions=4, n_banks=2,
                   round_cap=64)
POLICY = CapacityPolicy(n_buckets=4, min_capacity=256, growth=8)


@pytest.fixture(scope="module", params=["kron", "delaunay"])
def graph(request):
    kw = {"kron": dict(scale=9), "delaunay": dict(scale=16)}[request.param]
    g = make_dataset(request.param, **kw)
    g.source = int(np.argmax(np.asarray(g.degrees())))
    return g


def test_pipeline_ragged_matches_padded_bfs(graph):
    want = bfs(graph, graph.source)
    pads = FrontierPipeline(graph, BFS_APP, mode="hash", iru_config=BANKED,
                            capacity_policy=POLICY, ragged=False)
    rag = FrontierPipeline(graph, BFS_APP, mode="hash", iru_config=BANKED,
                           capacity_policy=POLICY, ragged=True)
    a = np.asarray(pads.run(graph.source))
    b = np.asarray(rag.run(graph.source))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(b, np.asarray(want))
    # ragged adds zero traces: the live count is an operand, not a shape
    assert rag.n_traces <= len(rag.buckets), (rag.n_traces, rag.buckets)
    np.testing.assert_array_equal(np.asarray(rag.run(0)),
                                  np.asarray(bfs(graph, 0)))
    assert rag.n_traces <= len(rag.buckets)


def test_pipeline_ragged_matches_padded_sssp(graph):
    base = np.asarray(sssp(graph, graph.source))
    got = np.asarray(sssp_pipeline(graph, graph.source, mode="hash",
                                   iru_config=BANKED, capacity_policy=POLICY,
                                   ragged=True))
    np.testing.assert_array_equal(base, got)


def test_pipeline_ragged_pagerank_allclose(graph):
    """fp-add grouping may differ between ragged and padded execution (pads
    no longer share hash slots with live elements) — allclose, not equal."""
    pads = np.asarray(pagerank_pipeline(graph, iters=8, mode="hash",
                                        iru_config=BANKED, ragged=False))
    rag = np.asarray(pagerank_pipeline(graph, iters=8, mode="hash",
                                       iru_config=BANKED, ragged=True))
    np.testing.assert_allclose(pads, rag, rtol=1e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# checked-in bench floor
# ---------------------------------------------------------------------------

def test_checked_in_bench_keeps_ragged_floor():
    """The headline this PR is accountable for: ragged delaunay BFS at least
    1.5x the padded bucketed pipeline, pinned on the committed numbers."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_iru.json")
    bench = json.load(open(path))
    assert bench["speedup_ragged_vs_padded_bfs_delaunay"] >= 1.5, bench[
        "speedup_ragged_vs_padded_bfs_delaunay"]
    assert "app_bfs_del_pipe_ragged" in bench["results"]
    assert "padded_vs_ragged" in bench
