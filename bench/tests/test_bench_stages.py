"""The stage reduction (``bench/stages.py``) on a small synthetic XSpace, and
that the harness's own numbers read the same with its new events in."""
import pytest
from jax.profiler import ProfileData

from bench import run, stages, trace

# device ops (name, start ns, end ns); modules and spans likewise
OPS = [
    ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop, calls=%f1",
     0, 1000),
    ("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %fusion.1), kind=kCustom, "
     "calls=%f2", 1000, 3000),
    ("%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t), "
     "condition=%c, body=%b", 0, 4000),
    # outside every module
    ("%fusion.9 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop, calls=%f9",
     4200, 4500),
    # the same instruction name as the first op, in the other rung
    ("%fusion.1 = s32[16]{0} fusion(s32[16]{0} %p), kind=kLoop, calls=%f1",
     5000, 6000),
    # no scope in its module's text
    ("%copy.3 = s32[16]{0} copy(s32[16]{0} %fusion.1)", 6000, 7000),
    # a module with no text
    ("%iota.0 = s32[4]{0} iota(), iota_dimension=0", 8500, 8600),
]
MODULES = [("jit_frontier_run_r0(11)", 0, 4000),
           ("jit_frontier_run_r1(22)", 5000, 8000), ("jit_iota(33)", 8500, 9000)]
SPANS = [("bench.window", 0, 10000), ("bench.run", 0, 9500)]
PIPELINE_SPANS = [("pipeline.dispatch", 2900, 3500),
                  ("pipeline.hop", 3500, 4300),
                  ("pipeline.result", 6900, 8600)]

SCOPE = "jit(frontier_run_r{})/frontier.loop/while/body/"
TEXTS = {
    "jit_frontier_run_r0": f"""HloModule jit_frontier_run_r0, is_scheduled=true

ENTRY %main.1 (p: s32[8]) -> s32[8] {{
  %p = s32[8]{{0}} parameter(0)
  %fusion.1 = s32[8]{{0}} fusion(%p), kind=kLoop, calls=%f1, metadata={{op_name="{SCOPE.format(0)}frontier.expand/gather"}}
  ROOT %fusion.2 = s32[8]{{0}} fusion(%fusion.1), kind=kCustom, calls=%f2, metadata={{op_name="{SCOPE.format(0)}frontier.reorder/jit(hash_reorder_banked)/cond/branch_1_fun/iru.banked/iru.rows/add"}}
}}
""",
    "jit_frontier_run_r1": f"""HloModule jit_frontier_run_r1, is_scheduled=true

ENTRY %main.2 (p: s32[16]) -> s32[16] {{
  %p = s32[16]{{0}} parameter(0)
  %fusion.1 = s32[16]{{0}} fusion(%p), kind=kLoop, calls=%f1, metadata={{op_name="{SCOPE.format(1)}frontier.scatter/scatter-add"}}
  ROOT %copy.3 = s32[16]{{0}} copy(%fusion.1)
}}
""",
}


def _events(events, ids):
    return "\n".join(
        f"events {{ metadata_id: {ids[name]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}" for name, s, e in events)


def _metadata(ids):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{name}" }} }}' for name, i in ids.items())


def xspace(rich: bool) -> ProfileData:
    """The trace as the harness reads it; ``rich`` adds the module line and
    the ``pipeline.*`` spans."""
    dev = {name: i + 1 for i, name in enumerate(
        dict.fromkeys(n for n, _, _ in OPS + MODULES))}
    spans = SPANS + (PIPELINE_SPANS if rich else [])
    host = {name: i + 1 for i, name in enumerate(n for n, _, _ in spans)}
    modules = (f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n'
               f'{_events(MODULES, dev)} }}' if rich else "")
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events(OPS, dev)} }}
  {modules}
{_metadata(dev)}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events(spans, host)} }}
{_metadata(host)}
}}
"""
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_stages_by_module_and_scope():
    tr, modules = stages.from_profile(xspace(rich=True))
    assert [m.name for m in modules["/device:TPU:0"]] == [
        "jit_frontier_run_r0", "jit_frontier_run_r1", "jit_iota"]
    got = stages.stage_seconds(tr, modules, TEXTS)
    assert got["stage_s"] == pytest.approx({
        "frontier.expand": 1e-6, "frontier.reorder": 2e-6,
        "frontier.scatter": 1e-6, stages.UNSCOPED: 1e-6,
        stages.NO_MODULE: 0.3e-6, "jit_iota": 0.1e-6})
    assert got["scope_s"]["frontier.reorder/iru.banked/iru.rows"] == (
        pytest.approx(2e-6))
    share = stages.shares(got["stage_s"])
    assert share["stage"] == pytest.approx(4 / 5.4 * 100)
    assert share["unscoped"] == pytest.approx(1 / 5.4 * 100)
    # idle gaps under the innermost span, pipeline spans included:
    # [3000, 4200] (mid 3600) in pipeline.hop, [7000, 8500] in
    # pipeline.result, [4500, 5000] and [8600, 10000] in bench.run
    gaps = dict(map(tuple, trace.reduce(tr)["breakdown"]["idle_gaps"]))
    assert gaps == pytest.approx({"pipeline.hop": 1.2e-6,
                                  "pipeline.result": 1.5e-6,
                                  "bench.run": 1.9e-6})


def test_existing_metrics_read_the_same_with_modules_and_spans():
    counters = {"edges": 1000, "traversals": 2, "bucket_hops": 6}
    plain = trace.reduce(trace.from_profile(xspace(rich=False)))
    rich = trace.reduce(trace.from_profile(xspace(rich=True)))
    assert rich == plain
    labelled = trace.reduce(stages.from_profile(xspace(rich=True))[0])
    for key in ("window_s", "busy_s", "idle_share", "class_s"):
        assert labelled[key] == plain[key]
    assert (labelled["breakdown"]["device_ops"]
            == plain["breakdown"]["device_ops"])
    bench = run.load_benchmark()
    for m in bench["per_layer"]:
        read = run.load_module("metrics", m["name"]).read
        assert (read({"trace": rich, "counters": counters})
                == read({"trace": plain, "counters": counters})), m["name"]


def test_window_counts_and_stage_metrics():
    before = {"rungs": [
        {"edge_capacity": 8, "steps": 2, "live_lanes": 10,
         "merged_lanes": 1, "compiled_lanes": 16},
        {"edge_capacity": 64, "steps": 1, "live_lanes": 40,
         "merged_lanes": 5, "compiled_lanes": 64}]}
    after = {"rungs": [
        {"edge_capacity": 8, "steps": 6, "live_lanes": 30,
         "merged_lanes": 4, "compiled_lanes": 48},
        {"edge_capacity": 64, "steps": 3, "live_lanes": 140,
         "merged_lanes": 25, "compiled_lanes": 192}]}
    rungs = stages.window_counts(before, after)
    assert rungs == [
        {"edge_capacity": 8, "steps": 4, "live_lanes": 20,
         "merged_lanes": 3, "compiled_lanes": 32},
        {"edge_capacity": 64, "steps": 2, "live_lanes": 100,
         "merged_lanes": 20, "compiled_lanes": 128}]
    stage_s = {"frontier.expand": 2e-6, "frontier.scatter": 1e-6,
               "frontier.update": 0.5e-6, stages.UNSCOPED: 1e-6}
    got = stages.stage_metrics("iru", stage_s, rungs, edges=1000)
    assert got == pytest.approx({
        "expand_ns_per_edge.iru": 2.0, "update_ns_per_edge.iru": 1.5,
        "live_lane_share.iru": 120 / 160 * 100,
        "iru_merged_share.iru": 23 / 120 * 100})
    # baseline rungs count no merges; no reorder stage, no reorder metric
    for r in rungs:
        del r["merged_lanes"]
    assert set(stages.stage_metrics("bfs", stage_s, rungs, edges=1000)) == {
        "expand_ns_per_edge.bfs", "update_ns_per_edge.bfs",
        "live_lane_share.bfs"}
