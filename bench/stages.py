"""Device time of a traced window by pipeline stage, and the pipeline's counts.

    python3 bench/stages.py --workload <name> --seed <n> --seconds <s>

runs one cell of ``BENCHMARK.json`` the way ``bench/run.py --trace 1`` does
(set-up, then the window under the profiler) and prints one JSON line with
what the harness's own line does not carry: device seconds per stage, the
per-layer numbers read from them and from ``FrontierPipeline.stats()``, and
the idle gaps labelled by the innermost ``bench.*`` or ``pipeline.*`` span.
It skips the check against the reference; ``bench/run.py`` makes it.

An op's stage: its module is the event of the device plane's ``XLA Modules``
line that holds the op's start; its instruction name is looked up in that
module's compiled HLO text (``FrontierPipeline.hlo_texts()``, fetched after
the window), whose ``op_name`` metadata carries the program's named scopes.
The stage is the innermost ``frontier.*`` scope (``frontier.expand``,
``frontier.reorder``, ...) and the scope path adds the ``iru.*`` scopes
under it (``frontier.reorder/iru.banked/iru.rows``).  An op of a module
with no text counts under the module's name (``jit_iota``); an op with no
stage scope under ``(unscoped)``; an op outside every module under
``(no module)``.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run, trace  # noqa: E402
from repro.launch.hlo_stats import hlo_instructions, scope_path  # noqa: E402

MODULE_LINE = "XLA Modules"
PIPELINE_SPAN = "pipeline."
STAGE, WITHIN = "frontier.", "iru."
UNSCOPED, NO_MODULE = "(unscoped)", "(no module)"
TRACE_DIR = os.path.join(ROOT, ".bench_trace_stages")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Module:
    name: str          # ``jit_frontier_run_r0``, the fingerprint cut off
    start_ns: float
    end_ns: float


def module_key(event_name: str) -> str:
    """``jit_frontier_run_r0(1234)`` -> ``jit_frontier_run_r0``."""
    return re.sub(r"\(\d*\)$", "", event_name)


def from_profile(pd) -> tuple[trace.Trace, dict[str, list[Module]]]:
    """The harness's ``Trace`` with the ``pipeline.*`` spans added, and the
    module events of each device plane in time order."""
    base = trace.from_profile(pd)
    spans = list(base.spans)
    modules: dict[str, list[Module]] = {}
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name == MODULE_LINE:
                modules.setdefault(plane.name, []).extend(
                    Module(module_key(ev.name), ev.start_ns, ev.end_ns)
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans += [trace.Span(ev.name, ev.start_ns, ev.end_ns)
                          for ev in line.events
                          if ev.name.startswith(PIPELINE_SPAN)]
    for mods in modules.values():
        mods.sort(key=lambda m: m.start_ns)
    return trace.Trace(base.ops, spans), modules


def load(logdir: str):
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(trace.latest_xplane(logdir)))


def _instruction(op_name: str) -> str:
    """``%fusion.41 = s32[8]{0} fusion(...)`` -> ``fusion.41``."""
    return op_name.partition(" = ")[0].strip().lstrip("%")


def stage_seconds(tr: trace.Trace, modules: dict[str, list[Module]],
                  texts: dict[str, str]) -> dict:
    """Device seconds in the window by stage and by scope path, summed over
    chips as ``trace.reduce``'s ``class_s`` is; loop and branch ops that
    enclose others count for nothing, as there."""
    w0, w1 = trace.window_of(tr)
    tables = {name: hlo_instructions(text) for name, text in texts.items()}
    stage_ns: collections.Counter = collections.Counter()
    scope_ns: collections.Counter = collections.Counter()
    for plane, ops in tr.ops.items():
        mods = modules.get(plane, [])
        starts = [m.start_ns for m in mods]
        for op in ops:
            s, e = max(op.start_ns, w0), min(op.end_ns, w1)
            if e <= s or trace.classify(op.name) == "control":
                continue
            i = bisect.bisect_right(starts, op.start_ns) - 1
            if i < 0 or op.start_ns >= mods[i].end_ns:
                stage = scope = NO_MODULE
            elif mods[i].name not in tables:
                stage = scope = mods[i].name
            else:
                ins = tables[mods[i].name].get(_instruction(op.name))
                path = scope_path(ins[1] if ins else None, STAGE, WITHIN)
                stage = path[0] if path else UNSCOPED
                scope = "/".join(path) if path else UNSCOPED
            stage_ns[stage] += e - s
            scope_ns[scope] += e - s
    return {"stage_s": {k: v * 1e-9 for k, v in stage_ns.most_common()},
            "scope_s": {k: v * 1e-9 for k, v in scope_ns.most_common()}}


def window_counts(before: dict, after: dict) -> list[dict]:
    """Per-rung ``FrontierPipeline.stats()`` counts of the window alone."""
    out = []
    for b, a in zip(before["rungs"], after["rungs"]):
        out.append({k: (v - b[k] if k != "edge_capacity" else v)
                    for k, v in a.items()})
    return out


def stage_metrics(suffix: str, stage_s: dict, rungs: list[dict],
                  edges: int) -> dict:
    """The per-layer numbers read from the stage seconds (ns per edge
    counted for ``teps``) and from the window's counts (%)."""
    out = {}
    if edges > 0:
        def ns(*stages):
            secs = sum(stage_s.get(s, 0.0) for s in stages)
            return secs / edges * 1e9 if secs > 0 else None

        out[f"expand_ns_per_edge.{suffix}"] = ns("frontier.expand")
        out[f"reorder_ns_per_edge.{suffix}"] = ns("frontier.reorder")
        out[f"update_ns_per_edge.{suffix}"] = ns("frontier.scatter",
                                                 "frontier.update")
    live = sum(r["live_lanes"] for r in rungs)
    compiled = sum(r["compiled_lanes"] for r in rungs)
    if compiled:
        out[f"live_lane_share.{suffix}"] = live / compiled * 100.0
    if live and all("merged_lanes" in r for r in rungs):
        out[f"iru_merged_share.{suffix}"] = (
            sum(r["merged_lanes"] for r in rungs) / live * 100.0)
    return {k: v for k, v in out.items() if v is not None}


def shares(stage_s: dict) -> dict:
    """Shares (%) of the device time: under a ``frontier.*`` stage, and
    unscoped."""
    total = sum(stage_s.values())
    if total <= 0:
        return {}
    staged = sum(v for k, v in stage_s.items() if k.startswith(STAGE))
    return {"stage": staged / total * 100.0,
            "unscoped": stage_s.get(UNSCOPED, 0.0) / total * 100.0}


class _BackendCompiles:
    """Counts backend compiles (cold ones: a persistent-cache load makes
    none)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


def measure(spec: dict, *, seed: int, seconds: float,
            logdir: str = TRACE_DIR) -> dict:
    """Set-up, the traced window, then the stage report as a dict."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    lowered, cold = run.CompileCounter(), _BackendCompiles()
    cell = spec["driver"].Cell(spec["config"], spec["mix"], seed=seed,
                               seconds=seconds, compiles=lowered, log=run.log)
    cell.setup()
    pipe = cell.pipe
    before = pipe.stats()
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    lowered0 = lowered.n
    with jax.profiler.TraceAnnotation("bench.window"):
        cell.window()
    jax.profiler.stop_trace()
    in_window = lowered.n - lowered0
    after = pipe.stats()
    # the executables' texts, after the window: a jit cache hit lowers and
    # compiles nothing, and nothing is traced again
    lowered0, cold0 = lowered.n, cold.n
    texts = pipe.hlo_texts()
    fetch = {"modules": sorted(texts), "lowered": lowered.n - lowered0,
             "compiled": cold.n - cold0,
             "n_traces": [after["n_traces"], pipe.n_traces]}
    run.log(f"texts of {fetch['modules']}: lowered {fetch['lowered']}, "
            f"compiled {fetch['compiled']}, n_traces {fetch['n_traces']}")

    rich, modules = load(logdir)
    shutil.rmtree(logdir, ignore_errors=True)
    base = trace.Trace(rich.ops, [s for s in rich.spans
                                  if s.name.startswith(trace.SPAN_PREFIX)])
    measured = cell.measured()
    ctx = {"trace": trace.reduce(base), "counters": measured}
    metrics = {m["name"]: spec["readers"][m["name"]](ctx)
               for m in spec["per_layer"]}
    suffix = next(m["name"].split(".", 1)[1] for m in spec["end_to_end"]
                  if m["name"] != "setup_s")
    staged = stage_seconds(rich, modules, texts)
    rungs = window_counts(before, after)
    metrics.update(stage_metrics(suffix, staged["stage_s"], rungs,
                                 measured["edges"]))
    labelled = trace.reduce(rich)
    return {
        "workload": spec["cell"]["name"], "seed": seed,
        "traversals": measured["traversals"], "edges": measured["edges"],
        "window_s": labelled["window_s"], "busy_s": labelled["busy_s"],
        "metrics": metrics, "stage_share": shares(staged["stage_s"]),
        **staged, "rungs": rungs,
        "breakdown": {
            "stages": [[k, v] for k, v in staged["stage_s"].items()],
            "idle_gaps": labelled["breakdown"]["idle_gaps"],
            "device_ops": labelled["breakdown"]["device_ops"]},
        "programs_lowered_in_window": in_window, "texts": fetch,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = run.resolve(run.load_benchmark(), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        run.log(f"no TPU: JAX platform is {jax.devices()[0].platform!r}")
        return 2
    line = measure(spec, seed=args.seed, seconds=args.seconds)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
