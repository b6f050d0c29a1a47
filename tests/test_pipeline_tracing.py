"""FrontierPipeline's own tracing: named scopes, host spans and counters.

* every gather, scatter, sort and fusion of the compiled rung executables
  runs under a ``frontier.*`` stage scope, and the hash engine names its
  arms (``iru.flat`` / ``iru.banked`` / ``iru.two_gen``) and the banked
  arm's stages;
* the executables' modules carry stable names;
* ``stats()`` counts exactly: live lanes are the host reference's
  per-level frontier degree sums, merged lanes the ``hash_ref`` oracle's
  inactive live lanes, prefix-route steps the levels whose degree sums fit
  the hash engine's prefix plan width, and the counter words carry past
  2**30;
* the hash engine's route names its three plan branches
  (``iru.plan_none`` / ``iru.plan_prefix`` / ``iru.plan_full``);
* under a profiler trace the ``pipeline.*`` host spans nest inside the run;
* ``hlo_texts()`` neither traces nor compiles again.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.apps.bfs import BFS_APP, bfs
from repro.apps.sssp import SSSP_APP
from repro.core import CapacityPolicy, IRUConfig
from repro.core.iru import _hash_ref_host
from repro.core.pipeline import FrontierPipeline, _merge_identity
from repro.graphs.csr import expand_frontier, frontier_from_mask
from repro.graphs.generators import make_dataset
from repro.kernels.iru_reorder.batched import route_prefix_width
from repro.launch.hlo_stats import hlo_instructions, scope_path

BANKED = IRUConfig(num_sets=64, slots=8, n_partitions=4, n_banks=2,
                   round_cap=64)
DEVICE_OPS = ("gather", "scatter", "sort", "fusion")
CASES = {
    "bfs_baseline": (BFS_APP, "baseline", None),
    "sssp_baseline": (SSSP_APP, "baseline", None),
    "bfs_hash": (BFS_APP, "hash", BANKED),
}


@pytest.fixture(scope="module")
def graph():
    g = make_dataset("kron", scale=8)
    deg = np.asarray(g.degrees())
    # a low-degree source: the traversal starts on the small rung, grows
    # into the top one and comes back down
    g.source = int(np.flatnonzero(deg == deg[deg > 0].min())[0])
    return g


def _ladder(g):
    return CapacityPolicy(n_buckets=2, min_capacity=g.n_edges // 16)


@pytest.fixture(scope="module")
def ran(graph):
    """One traversal through each case's two-rung pipeline, and its texts."""
    out = {}
    for name, (app, mode, cfg) in CASES.items():
        pipe = FrontierPipeline(graph, app, mode=mode, iru_config=cfg,
                                capacity_policy=_ladder(graph))
        pipe.run(graph.source)
        out[name] = (pipe, pipe.hlo_texts())
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_device_op_runs_under_a_stage_scope(ran, case):
    _, texts = ran[case]
    assert {"jit_frontier_run_r0", "jit_frontier_run_r1"} <= set(texts)
    seen = set()
    for name in ("jit_frontier_run_r0", "jit_frontier_run_r1"):
        for ins, (opcode, op_name) in hlo_instructions(texts[name]).items():
            if opcode not in DEVICE_OPS:
                continue
            path = scope_path(op_name, "frontier.", "iru.")
            assert path, f"{name}: {ins} ({opcode}) is unscoped: {op_name!r}"
            seen.add(path)
    stages = {p[0] for p in seen}
    assert {"frontier.expand", "frontier.scatter", "frontier.update",
            "frontier.predict", "frontier.count"} <= stages
    if case == "bfs_hash":
        assert {("frontier.reorder", "iru.flat"),
                ("frontier.reorder", "iru.two_gen"),
                ("frontier.reorder", "iru.route"),
                ("frontier.reorder", "iru.banked", "iru.sort"),
                ("frontier.reorder", "iru.banked", "iru.rows"),
                ("frontier.reorder", "iru.banked", "iru.emit")} <= seen
    else:
        assert "frontier.reorder" not in stages


def test_route_names_its_plan_branches(ran):
    pipe, texts = ran["bfs_hash"]
    assert pipe.n_traces == len(pipe.buckets) == 2
    paths = {scope_path(op_name, "frontier.", "iru.")
             for _, op_name in hlo_instructions(
                 texts["jit_frontier_run_r1"]).values()}
    for branch in ("iru.plan_none", "iru.plan_prefix", "iru.plan_full"):
        assert ("frontier.reorder", "iru.route", branch) in paths, branch
    # the low rung is below the prefix width's tile: no prefix plan there
    low = {scope_path(op_name, "frontier.", "iru.")
           for _, op_name in hlo_instructions(
               texts["jit_frontier_run_r0"]).values()}
    assert ("frontier.reorder", "iru.route", "iru.plan_full") in low
    assert ("frontier.reorder", "iru.route", "iru.plan_prefix") not in low


def test_executables_carry_stable_module_names(ran):
    pipe, texts = ran["bfs_baseline"]
    assert sorted(texts) == ["jit_frontier_predict", "jit_frontier_run_r0",
                             "jit_frontier_run_r1"]
    for name, text in texts.items():
        assert text.startswith(f"HloModule {name},")
    state, mask = pipe.init(0)
    pipe.step(state, mask)
    assert "jit_frontier_step_r0" in pipe.hlo_texts()


def _level_degree_sums(g, source):
    depth = bfs(g, source)
    deg = np.asarray(g.degrees())
    reached = depth[depth != np.iinfo(np.int32).max]
    return [int(deg[depth == d].sum()) for d in range(int(reached.max()) + 1)]


@pytest.mark.parametrize("mode", ["baseline", "hash"])
def test_live_lanes_are_the_host_level_degree_sums(graph, mode):
    sums = _level_degree_sums(graph, graph.source)
    cfg = BANKED if mode == "hash" else None
    pipe = FrontierPipeline(graph, BFS_APP, mode=mode, iru_config=cfg,
                            capacity_policy=_ladder(graph))
    pipe.run(graph.source)
    rungs = pipe.stats()["rungs"]
    assert sum(r["steps"] for r in rungs) == len(sums)
    assert sum(r["live_lanes"] for r in rungs) == sum(sums)
    assert all(r["steps"] > 0 for r in rungs), rungs  # both rungs ran
    for r, (e_cap, _) in zip(rungs, pipe.buckets):
        assert r["compiled_lanes"] == r["steps"] * e_cap
    # level by level through step(): each step adds its level's sum
    state, mask = pipe.init(graph.source)
    for want in sums:
        before = pipe.stats()["rungs"]
        r = pipe.step(state, mask)
        state, mask = r.state, r.mask
        after = pipe.stats()["rungs"][r.bucket]
        assert after["live_lanes"] - before[r.bucket]["live_lanes"] == want
    assert not bool(jnp.any(mask))


def test_merged_lanes_are_the_oracle_inactive_live_lanes(graph):
    pipe = FrontierPipeline(graph, BFS_APP, mode="hash", iru_config=BANKED)
    (e_cap, f_cap), = pipe.buckets
    state, mask = pipe.init(graph.source)
    merged = []
    while bool(jnp.any(mask)):
        # the stream the step hands its reorder, through the host oracle
        ef = expand_frontier(graph, frontier_from_mask(mask, size=f_cap),
                             edge_capacity=e_cap)
        vals = jnp.where(ef.valid, BFS_APP.candidate(state, graph, ef),
                         _merge_identity("min", jnp.int32))
        n_live = int(ef.n_valid)
        _, _, pos, act = _hash_ref_host(np.asarray(ef.dsts),
                                        np.asarray(vals), pipe.iru_config,
                                        n_live=n_live)
        want = int(np.sum((pos < n_live) & ~act))
        before = pipe.stats()["rungs"][0]["merged_lanes"]
        r = pipe.step(state, mask)
        state, mask = r.state, r.mask
        got = pipe.stats()["rungs"][0]["merged_lanes"] - before
        assert got == want
        merged.append(got)
    assert sum(merged) > 0  # the graph's duplicates did merge


@pytest.mark.parametrize("mode", ["baseline", "hash"])
def test_prefix_route_steps_count_the_levels_that_fit_the_width(graph,
                                                                mode):
    sums = _level_degree_sums(graph, graph.source)
    cfg = BANKED if mode == "hash" else None
    # one rung, through run()
    pipe = FrontierPipeline(graph, BFS_APP, mode=mode, iru_config=cfg)
    pipe.run(graph.source)
    (rung,) = pipe.stats()["rungs"]
    w = route_prefix_width(rung["edge_capacity"], BANKED.num_sets)
    want = sum(s <= w for s in sums) if mode == "hash" else 0
    assert rung["prefix_route_steps"] == want
    if mode == "hash":
        assert 0 < want < len(sums)  # levels on both sides of the width
    # two rungs, step by step: only a rung with a prefix plan counts
    pipe = FrontierPipeline(graph, BFS_APP, mode=mode, iru_config=cfg,
                            capacity_policy=_ladder(graph))
    widths = [route_prefix_width(e_cap, BANKED.num_sets) if cfg else 0
              for e_cap, _ in pipe.buckets]
    state, mask = pipe.init(graph.source)
    want = [0] * len(widths)
    while bool(jnp.any(mask)):
        r = pipe.step(state, mask)
        state, mask = r.state, r.mask
        want[r.bucket] += int(0 < int(r.n_edges) <= widths[r.bucket])
    assert [r["prefix_route_steps"]
            for r in pipe.stats()["rungs"]] == want
    assert pipe.n_traces == 0  # step() runs the step executables only
    if mode == "hash":
        assert widths[0] == 0 < widths[1] and want[1] > 0


def test_counter_words_carry_past_2_to_the_30(graph):
    pipe = FrontierPipeline(graph, BFS_APP)
    top = (1 << 30) - 1
    pipe._counts = pipe._counts.at[0, :, 0].set(top)
    state, mask = pipe.init(graph.source)
    r = pipe.step(state, mask)
    got = pipe.stats()["rungs"][0]
    assert got["steps"] == top + 1
    assert got["live_lanes"] == top + int(r.n_edges)


def test_pipeline_spans_nest_inside_one_run(graph, tmp_path):
    pipe = FrontierPipeline(graph, BFS_APP, capacity_policy=_ladder(graph))
    pipe.run(graph.source)  # compile outside the trace
    hops = pipe.n_hops
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("test.run"):
        jax.block_until_ready(pipe.run(graph.source))
    jax.profiler.stop_trace()
    hops = pipe.n_hops - hops
    pd = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    spans = sorted(((ev.start_ns, ev.end_ns, ev.name)
                    for plane in pd.planes if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith(("pipeline.", "test.run"))))
    (r0, r1, _), = [s for s in spans if s[2] == "test.run"]
    inner = [s for s in spans if s[2] != "test.run"]
    assert all(r0 <= s0 and s1 <= r1 for s0, s1, _ in inner)
    names = [n for _, _, n in inner]
    assert names[0] == "pipeline.init" and names[-1] == "pipeline.result"
    assert names.count("pipeline.dispatch") == hops > 1
    # one hop span before each dispatch, and the last that ends the run
    assert names.count("pipeline.hop") == hops + 1
    # spans of the same run never overlap one another
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_hlo_texts_trace_and_compile_nothing(ran):
    pipe, texts = ran["bfs_hash"]
    compiles = []

    def listen(event, secs, **_):
        compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    traces = pipe.n_traces
    try:
        again = pipe.hlo_texts()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert again == texts and pipe.n_traces == traces
    assert "/jax/core/compile/backend_compile_duration" not in compiles
    assert "/jax/core/compile/jaxpr_to_mlir_module_duration" not in compiles
