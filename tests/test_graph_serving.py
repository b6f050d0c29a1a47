"""Multi-tenant graph query serving: admission, quarantine, deadlines.

The engine's acceptance contract: under EVERY scripted ``QueryFaultPlan``
fault, each surviving query's result is bit-identical to its solo
``FrontierPipeline`` run, no co-tenant is lost, and nothing ever truncates
silently (failures are loud statuses/exceptions naming the query).
"""
import numpy as np
import pytest

from repro.core.pipeline import CapacityPolicy
from repro.ft import (
    QueryFaultInjector,
    QueryFaultPlan,
    StragglerClock,
    backoff_delay,
)
from repro.graphs.csr import tile_csr
from repro.graphs.generators import delaunay, kron
from repro.serve import (
    AdmissionError,
    GraphQuery,
    GraphServeConfig,
    GraphServingEngine,
    QueueFullError,
)

SMALL = CapacityPolicy(n_buckets=2, min_capacity=256, growth=16)


@pytest.fixture(scope="module")
def gk():
    return kron(scale=7, edge_factor=8, seed=4)  # hub-skewed, 128 nodes


@pytest.fixture(scope="module")
def gd():
    return delaunay(scale=48, seed=2)  # planar, high diameter


def _mixed(sources=(0, 3, 9, 17)):
    s = list(sources)
    return [GraphQuery("bfs", s[0]), GraphQuery("sssp", s[1]),
            GraphQuery("ppr", s[2], iters=8), GraphQuery("bfs", s[3]),
            GraphQuery("ppr", s[0], iters=5), GraphQuery("sssp", s[2])]


def _assert_parity(eng, queries):
    for q in queries:
        assert q.status == "done", (q.qid, q.status, q.error)
        ref = eng.solo_reference(q)
        assert q.result.dtype == ref.dtype
        np.testing.assert_array_equal(q.result, ref, err_msg=str(
            (q.qid, q.kind, q.source)))


# ---------------------------------------------------------------------------
# multiplexing parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["bfs", "sssp", "ppr"])
def test_single_query_matches_solo(gk, kind):
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2,
                                                  capacity_policy=SMALL))
    q = GraphQuery(kind, 5, iters=6)
    eng.submit(q)
    eng.run_to_completion(500)
    _assert_parity(eng, [q])


@pytest.mark.parametrize("gname", ["gk", "gd"])
def test_mixed_queries_bit_identical_to_solo(gname, request):
    g = request.getfixturevalue(gname)
    eng = GraphServingEngine(g, GraphServeConfig(query_slots=4,
                                                 capacity_policy=SMALL))
    qs = _mixed()
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    _assert_parity(eng, qs)


def test_more_queries_than_slots_all_complete(gk):
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2,
                                                  capacity_policy=SMALL))
    qs = [GraphQuery("bfs", i * 7 % gk.n_nodes) for i in range(9)]
    qs += [GraphQuery("ppr", 3, iters=4)]
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    _assert_parity(eng, qs)


def test_random_query_mixes_match_solo(gk, gd):
    """Fixed-seed random mixes on both graph shapes (the in-container twin
    of the hypothesis property in test_graph_serving_prop.py)."""
    rng = np.random.default_rng(0)
    for g in (gk, gd):
        kinds = rng.choice(["bfs", "sssp", "ppr"], size=7)
        srcs = rng.integers(0, g.n_nodes, size=7)
        qs = [GraphQuery(str(k), int(s), iters=int(rng.integers(2, 7)))
              for k, s in zip(kinds, srcs)]
        eng = GraphServingEngine(g, GraphServeConfig(query_slots=3,
                                                     capacity_policy=SMALL))
        for q in qs:
            eng.submit(q)
        eng.run_to_completion(3000)
        _assert_parity(eng, qs)


def test_same_source_tenants_do_not_cross_dedupe(gk):
    """Two identical BFS queries in flight together: duplicate filtering
    must collapse lanes only WITHIN a query — if it deduped across tenants
    the second query's frontier would be starved and its labels wrong."""
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2,
                                                  capacity_policy=SMALL))
    qa, qb = GraphQuery("bfs", 0), GraphQuery("bfs", 0)
    eng.submit(qa)
    eng.submit(qb)
    eng.run_to_completion(500)
    _assert_parity(eng, [qa, qb])
    np.testing.assert_array_equal(qa.result, qb.result)


def test_step_executables_reused_across_tenants_and_ticks(gk):
    """One compiled step per (family, bucket), shared by every tenant and
    tick — the serving engine must not recompile as queries join/retire."""
    eng = GraphServingEngine(gk, GraphServeConfig(
        query_slots=4, capacity_policy=CapacityPolicy(
            n_buckets=3, min_capacity=512, growth=8)))
    qs = _mixed() + [GraphQuery("bfs", 11), GraphQuery("sssp", 23)]
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    _assert_parity(eng, qs)
    for fam, pipe in eng._pipes.items():
        assert len(pipe.buckets) <= 3
        for b, fn in enumerate(pipe._step_b):
            assert fn._cache_size() <= 1, (
                f"{fam} bucket {b} compiled {fn._cache_size()}x")


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_submit_rejects_invalid_queries_loudly(gk):
    eng = GraphServingEngine(gk)
    with pytest.raises(AdmissionError, match="unknown query kind"):
        eng.submit(GraphQuery("wcc", 0))
    with pytest.raises(AdmissionError, match="outside"):
        eng.submit(GraphQuery("bfs", -1))
    with pytest.raises(AdmissionError, match="outside"):
        eng.submit(GraphQuery("bfs", gk.n_nodes))


def test_submit_rejects_query_that_can_never_fit(gk):
    """A query whose solo footprint exceeds the top bucket is refused at
    submit time, not left to starve in the queue."""
    eng = GraphServingEngine(gk, GraphServeConfig(
        query_slots=2, edge_capacity=gk.n_edges // 2,
        capacity_policy=SMALL))
    with pytest.raises(AdmissionError, match="edge lanes solo"):
        eng.submit(GraphQuery("ppr", 0))  # ppr always needs all n_edges


def test_bounded_queue_overflows_loudly(gk):
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=1, max_queue=2))
    eng.submit(GraphQuery("bfs", 0))
    eng.submit(GraphQuery("bfs", 1))
    with pytest.raises(QueueFullError, match="shed load"):
        eng.submit(GraphQuery("bfs", 2))


def test_admission_gate_delays_join_until_capacity_frees(gk):
    """Two PPR tenants against a budget that holds ~1.5 of them: the second
    must wait (admission_blocked ticks counted), then complete with parity —
    the gate delays, it never drops."""
    eng = GraphServingEngine(gk, GraphServeConfig(
        query_slots=2, edge_capacity=int(1.5 * gk.n_edges),
        capacity_policy=SMALL))
    qa = GraphQuery("ppr", 0, iters=6)
    qb = GraphQuery("ppr", 5, iters=6)
    eng.submit(qa)
    eng.submit(qb)
    eng.run_to_completion(2000)
    assert eng.admission_blocked > 0
    assert qb.admitted_tick > qa.admitted_tick
    _assert_parity(eng, [qa, qb])


# ---------------------------------------------------------------------------
# overflow quarantine
# ---------------------------------------------------------------------------

def test_injected_overflow_quarantines_largest_and_preserves_cotenants(gk):
    plan = QueryFaultPlan(overflow_at=(3,))
    eng = GraphServingEngine(
        gk, GraphServeConfig(query_slots=4, backoff_base_s=0.001,
                             capacity_policy=SMALL),
        fault_plan=plan)
    qs = _mixed()
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    assert ("overflow", 3) in eng.injector.fired
    assert eng.quarantines >= 1
    assert any(q.retries > 0 for q in qs)
    _assert_parity(eng, qs)  # including the quarantined tenant: solo retry


def test_capacity_pressure_evicts_and_recovers_bit_identical(gk):
    """Real (non-injected) pressure: a shrunk edge budget the merged BFS
    frontiers genuinely outgrow mid-flight.  The largest contributor is
    evicted to solo retry; nobody is truncated, everybody matches solo."""
    eng = GraphServingEngine(gk, GraphServeConfig(
        query_slots=4, edge_capacity=int(1.3 * gk.n_edges),
        backoff_base_s=0.001,
        capacity_policy=CapacityPolicy(n_buckets=3, min_capacity=64,
                                       growth=8)))
    qs = [GraphQuery("bfs", s) for s in (0, 3, 9, 17, 33, 64)]
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    assert eng.overflow_events > 0 and eng.quarantines > 0
    _assert_parity(eng, qs)


def test_step_overflow_flag_quarantines_without_committing(gk, monkeypatch):
    """The belt-and-braces path: if the pre-step gate is wrong (here: a
    monkeypatched predictor that lies), the step's own ``EdgeFrontier.
    overflow`` flag still catches it — the truncated outputs are discarded
    (StepResult carries the unchanged inputs), a tenant is quarantined, and
    every query still ends bit-identical to solo."""
    eng = GraphServingEngine(gk, GraphServeConfig(
        query_slots=4, edge_capacity=int(1.2 * gk.n_edges),
        backoff_base_s=0.001,
        capacity_policy=CapacityPolicy(n_buckets=2, min_capacity=64,
                                       growth=8)))
    real_load = eng._family_load
    monkeypatch.setattr(
        eng, "_family_load",
        lambda fam: np.minimum(real_load(fam), 1))  # lies: "everyone fits"
    qs = [GraphQuery("bfs", s) for s in (0, 3, 9, 17)]
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    assert eng.overflow_events > 0, "the lying gate must have let one slip"
    _assert_parity(eng, qs)


def test_quarantine_retries_are_bounded_and_fail_loudly(gk):
    """A query that cannot finish inside its tick budget even solo burns its
    bounded retries and lands in status 'failed' with a loud error — the
    supervisor-style giving-up path, never an infinite retry loop."""
    plan = QueryFaultPlan(overflow_at=(1,))
    # zero backoff: each retry is due on the next tick, so the tick cap
    # below bounds retries, not how many idle ticks fit in a wall-clock
    # backoff (about a microsecond each, which made the test flaky)
    eng = GraphServingEngine(
        gk, GraphServeConfig(query_slots=1, backoff_base_s=0.0,
                             max_retries=2, capacity_policy=SMALL),
        fault_plan=plan)
    q = GraphQuery("ppr", 0, iters=50, tick_budget=2)
    eng.submit(q)
    eng.run_to_completion(2000)
    assert q.status == "failed"
    assert "exhausted 2 quarantine retries" in q.error
    assert q.retries > 2


def test_backoff_delay_is_exponential():
    assert backoff_delay(0.1, 1) == pytest.approx(0.1)
    assert backoff_delay(0.1, 3) == pytest.approx(0.4)
    assert backoff_delay(0.1, 0) == pytest.approx(0.1)  # clamped floor


# ---------------------------------------------------------------------------
# poisoned sources, cancellation, deadlines
# ---------------------------------------------------------------------------

def test_poisoned_source_rejected_at_admission_never_expanded(gk):
    plan = QueryFaultPlan(poison_source=(1,), poison_value=-7)
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2),
                             fault_plan=plan)
    qa, qb = GraphQuery("bfs", 0), GraphQuery("sssp", 3)
    eng.submit(qa)
    eng.submit(qb)  # qid 1: poisoned between submit and admission
    eng.run_to_completion(500)
    assert qb.status == "rejected"
    assert "poisoned source id -7" in qb.error
    assert qb.result is None
    _assert_parity(eng, [qa])  # co-tenant untouched


def test_mid_flight_cancellation_spares_cotenants(gk):
    plan = QueryFaultPlan(cancel_at=((0, 2),))
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2,
                                                  capacity_policy=SMALL),
                             fault_plan=plan)
    qa, qb = GraphQuery("ppr", 0, iters=20), GraphQuery("sssp", 3)
    eng.submit(qa)
    eng.submit(qb)
    eng.run_to_completion(500)
    assert qa.status == "cancelled" and "tick 2" in qa.error
    assert ("cancel", 0) in eng.injector.fired
    _assert_parity(eng, [qb])


def test_tick_budget_cancels_pathological_query(gk):
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2,
                                                  capacity_policy=SMALL))
    qa = GraphQuery("ppr", 0, iters=500, tick_budget=4)
    qb = GraphQuery("bfs", 3)
    eng.submit(qa)
    eng.submit(qb)
    eng.run_to_completion(2000)
    assert qa.status == "cancelled" and "tick budget 4" in qa.error
    _assert_parity(eng, [qb])


def test_straggler_deadline_cancels_stalling_query(gk):
    """EWMA wall-clock supervision: quick co-tenants set the completion
    EWMA; a tenant stalled far past factor*avg is cancelled as a straggler
    (hang injected via the fault plan, attributed to that query)."""
    plan = QueryFaultPlan(hang_at=tuple((0, t) for t in range(2, 40)),
                          hang_seconds=0.05)
    eng = GraphServingEngine(
        gk, GraphServeConfig(query_slots=3, straggler_factor=1.5,
                             straggler_min_s=0.0, capacity_policy=SMALL),
        fault_plan=plan)
    slow = GraphQuery("ppr", 0, iters=500)
    quick = [GraphQuery("bfs", 3), GraphQuery("bfs", 9)]
    eng.submit(slow)
    for q in quick:
        eng.submit(q)
    eng.run_to_completion(2000)
    assert slow.status == "cancelled", (slow.status, slow.error)
    assert "straggler deadline" in slow.error
    _assert_parity(eng, quick)


def test_straggler_clock_observe_then_compare():
    clk = StragglerClock(factor=3.0, ewma=0.9)
    assert clk.deadline() is None
    assert not clk.observe(1.0)       # first sample never a straggler
    assert clk.observe(100.0)         # two orders past the EWMA
    assert clk.deadline(0.0) == pytest.approx(3.0 * clk.avg)
    assert clk.deadline(1e9) == 1e9   # floor wins while avg is small


# ---------------------------------------------------------------------------
# fault-plan validation + loud completion timeout
# ---------------------------------------------------------------------------

def test_query_fault_plan_validates_at_construction():
    with pytest.raises(ValueError, match="overflow_at"):
        QueryFaultPlan(overflow_at=(-1,))
    with pytest.raises(ValueError, match="cancel_at"):
        QueryFaultPlan(cancel_at=((0, -2),))
    with pytest.raises(ValueError, match="hang_seconds"):
        QueryFaultPlan(hang_seconds=-0.1)


def test_query_fault_injector_fires_each_entry_once():
    inj = QueryFaultInjector(QueryFaultPlan(overflow_at=(2,),
                                            cancel_at=((1, 3),)))
    assert inj.force_overflow(2) and not inj.force_overflow(2)
    assert not inj.should_cancel(1, 2)
    assert inj.should_cancel(1, 3) and not inj.should_cancel(1, 3)
    assert inj.fired == {("overflow", 2), ("cancel", 1)}


def test_run_to_completion_raises_naming_stuck_queries(gk):
    eng = GraphServingEngine(gk, GraphServeConfig(query_slots=2,
                                                  capacity_policy=SMALL))
    eng.submit(GraphQuery("ppr", 0, iters=100))
    eng.submit(GraphQuery("ppr", 1, iters=100))
    with pytest.raises(TimeoutError, match=r"qids=\[0, 1\]"):
        eng.run_to_completion(max_ticks=3)


# ---------------------------------------------------------------------------
# tile_csr (the composite replica substrate)
# ---------------------------------------------------------------------------

def test_tile_csr_builds_disjoint_replicas(gk):
    Q = 3
    cg = tile_csr(gk, Q)
    n, m = gk.n_nodes, gk.n_edges
    assert cg.n_nodes == Q * n and cg.n_edges == Q * m
    base_deg = np.asarray(gk.degrees())
    np.testing.assert_array_equal(np.asarray(cg.degrees()),
                                  np.tile(base_deg, Q))
    col = np.asarray(cg.col_idx)
    for q in range(Q):
        seg = col[q * m:(q + 1) * m]
        assert seg.min() >= q * n and seg.max() < (q + 1) * n
        np.testing.assert_array_equal(seg, np.asarray(gk.col_idx) + q * n)
    np.testing.assert_array_equal(np.asarray(cg.weights),
                                  np.tile(np.asarray(gk.weights), Q))


def test_tile_csr_rejects_bad_copies(gk):
    with pytest.raises(ValueError):
        tile_csr(gk, 0)
    with pytest.raises(ValueError, match="int32"):
        tile_csr(gk, 2**31 // gk.n_nodes + 1)


def test_tile_csr_overflow_error_names_geometry(gk):
    """The query-id high-bit packing overflow must be loud and actionable:
    the message names the requested copies, the base node count, and the id
    dtype it overflows (regression: the old check silently wrapped when the
    EDGE space overflowed before the node space)."""
    bad = 2**31 // gk.n_edges + 1  # edge offsets overflow before node ids
    assert bad * gk.n_nodes < 2**31  # node space alone would have passed
    with pytest.raises(ValueError) as ei:
        tile_csr(gk, bad)
    msg = str(ei.value)
    assert f"copies={bad}" in msg
    assert f"n={gk.n_nodes}" in msg
    assert "int32" in msg


def test_composed_view_composition_metadata(gk):
    """partition_csr(tile_csr(g, Q), P): closed transforms whose composite
    carries the id-space metadata (tenant count, base geometry) through."""
    from repro.graphs.csr import GraphView, PartitionedGraphView, partition_csr

    Q = 3
    view = tile_csr(gk, Q)
    assert isinstance(view, GraphView)
    assert view.n_tenants == Q and view.base_nodes == gk.n_nodes
    np.testing.assert_array_equal(np.asarray(view.base.col_idx),
                                  np.asarray(gk.col_idx))
    retiled = tile_csr(view, 2)  # composition: tenants multiply
    assert retiled.n_tenants == 2 * Q
    assert retiled.base_nodes == gk.n_nodes
    pview = partition_csr(view, 2)
    assert isinstance(pview, PartitionedGraphView)
    assert pview.n_parts == 2 and pview.n_tenants == Q
    assert pview.base_nodes == gk.n_nodes and pview.n_nodes == view.n_nodes


# ---------------------------------------------------------------------------
# the fused tagged-lane datapath (min + add families in ONE dispatch)
# ---------------------------------------------------------------------------

def _fused_vs_split(g, queries_fn):
    out = []
    for fused in (True, False):
        eng = GraphServingEngine(g, GraphServeConfig(
            query_slots=4, capacity_policy=SMALL, fused=fused))
        qs = queries_fn()
        for q in qs:
            eng.submit(q)
        eng.run_to_completion(3000)
        out.append((eng, qs))
    return out


@pytest.mark.parametrize("gname", ["gk", "gd"])
def test_fused_matches_split_engine(gname, request):
    """The fused tick's parity contract vs the split per-family engine on a
    mixed min+add workload: min-family results bit-identical, add-family
    allclose (exact here too — baseline mode preserves add-lane order)."""
    g = request.getfixturevalue(gname)
    (ef, fq), (es, sq) = _fused_vs_split(g, _mixed)
    for a, b in zip(fq, sq):
        assert a.done and b.done, (a.status, b.status)
        if a.kind == "ppr":
            np.testing.assert_allclose(a.result, b.result,
                                       rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(a.result, b.result)
    _assert_parity(ef, fq)  # and min stays bit-identical to SOLO runs


def test_fused_mixed_workload_compiles_n_buckets_total(gk):
    """Acceptance: a mixed BFS+SSSP+PPR workload compiles at most n_buckets
    step executables TOTAL — not per family — because both families share
    the single tagged-lane runtime."""
    pol = CapacityPolicy(n_buckets=3, min_capacity=512, growth=8)
    eng = GraphServingEngine(gk, GraphServeConfig(
        query_slots=4, capacity_policy=pol))
    qs = _mixed()
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    _assert_parity(eng, qs)
    assert list(eng._pipes) == ["fused"], list(eng._pipes)
    total = sum(fn._cache_size() for fn in eng._pipes["fused"]._step_b)
    assert total <= pol.n_buckets, (
        f"{total} step executables for a mixed workload; the fused "
        f"datapath allows at most n_buckets={pol.n_buckets} TOTAL")


def test_fused_injected_overflow_quarantines_and_recovers(gk):
    """Forced overflow under the fused datapath: a victim is evicted from
    the SHARED tick (either family is eligible), co-tenants keep advancing,
    and every query still lands bit-identical to its solo run."""
    plan = QueryFaultPlan(overflow_at=(3,))
    eng = GraphServingEngine(
        gk, GraphServeConfig(query_slots=4, backoff_base_s=0.001,
                             capacity_policy=SMALL),
        fault_plan=plan)
    qs = _mixed()
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(3000)
    assert ("overflow", 3) in eng.injector.fired
    assert eng.quarantines >= 1 and eng.overflow_events >= 1
    _assert_parity(eng, qs)


def test_fused_mid_flight_cancel_spares_cotenants(gk):
    """Cancelling one tenant mid-tick under the fused datapath clears ONLY
    its lane (reset to the idle min row); survivors of BOTH families stay
    bit-identical to solo runs."""
    plan = QueryFaultPlan(cancel_at=((0, 2),))
    eng = GraphServingEngine(
        gk, GraphServeConfig(query_slots=4, capacity_policy=SMALL),
        fault_plan=plan)
    qs = _mixed()
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(3000)
    cancelled = [q for q in qs if q.status == "cancelled"]
    assert len(cancelled) == 1 and cancelled[0].qid == 0
    _assert_parity(eng, [q for q in qs if q.status == "done"])
    assert sum(q.status == "done" for q in qs) == len(qs) - 1


def test_fused_engine_accepts_composed_view(gk):
    """A pre-composed GraphView serves identically to letting the engine
    tile; a tenant-count mismatch is rejected loudly at construction."""
    Q = 4
    view = tile_csr(gk, Q)
    eng = GraphServingEngine(view, GraphServeConfig(query_slots=Q,
                                                    capacity_policy=SMALL))
    qs = _mixed()
    for q in qs:
        eng.submit(q)
    eng.run_to_completion(2000)
    _assert_parity(eng, qs)
    with pytest.raises(ValueError, match="n_tenants"):
        GraphServingEngine(view, GraphServeConfig(query_slots=Q + 1,
                                                  capacity_policy=SMALL))


def test_split_engine_rejects_partitioned_view(gk):
    from repro.graphs.csr import partition_csr

    pview = partition_csr(tile_csr(gk, 2), 1)
    with pytest.raises(ValueError, match="fused"):
        GraphServingEngine(pview, GraphServeConfig(
            query_slots=2, capacity_policy=SMALL, fused=False))


# ---------------------------------------------------------------------------
# checked-in serving throughput floor
# ---------------------------------------------------------------------------

def test_checked_in_bench_keeps_serving_floor():
    """BENCH_iru.json's multi-tenant serving row: a refresh that tanks the
    engine (or drops the row) fails tier-1, same pattern as the bucketed
    delaunay-BFS floor in test_capacity.py."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_iru.json")
    bench = json.load(open(path))
    assert bench["serving_queries_per_s"] >= 2.0, bench[
        "serving_queries_per_s"]
    # family fusion may never LOSE to the split engine: one tagged dispatch
    # replaces two per-family dispatches per tick
    assert bench["serving_fused_vs_split"] >= 1.0, bench[
        "serving_fused_vs_split"]
