"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``: ``bench/configs/<config>.json`` names the
driver (``bench/drivers/<driver>.py``) that builds the system under test,
warms it up and drives the window; ``bench/traffic/<mix>.json`` is read by
``bench/trafficgen.py``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell, mix or metric is new files and
new entries, with no edit here.

A run: set-up (graph made on the device from the seed, the program built
and every executable the cell uses warmed up, from the compile cache in the
checkout after the first run), then the window of ``--seconds``, then the
device's peak memory, then the check of every answer the window produced
against the plain reference (``bench/reference.py``).  With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer metrics
and a breakdown; with ``--trace 0`` it carries the end-to-end metrics.  The
numbers the check compared, each with its limit, are the last lines on
stderr and the last key of the result line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, mix and metrics, by name, with the
    files that hold them: a name that resolves to no file raises."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{cell['traffic']}.json")) as f:
        mix = json.load(f)

    def applies(metric: dict, reported: set | None = None) -> bool:
        # a metric with no cell list goes wherever what it moves is reported
        if "workloads" in metric:
            return workload in metric["workloads"]
        return reported is None or metric["moves"] in reported

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if applies(m, names)]
    driver = load_module("drivers", cfg["driver"], root)
    readers = {m["name"]: load_module("metrics", m["name"], root).read
               for m in layer}
    return {"cell": cell, "config": cfg, "mix": mix, "end_to_end": e2e,
            "per_layer": layer, "driver": driver, "readers": readers}


class CompileCounter:
    """Counts programs lowered (each a compile or a cache load) while on."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, chips: int, before_window=None) -> dict:
    """Set-up, window, peak memory, check: the result line as a dict.
    ``before_window`` (tests only) is called between set-up and window."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # small programs too: a warm run loads every program it uses
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    cell = spec["driver"].Cell(spec["config"], spec["mix"], seed=seed,
                               seconds=seconds, compiles=compiles, log=log)
    cell.setup()
    setup_s = time.monotonic() - t_start
    log(f"setup_s {setup_s:.3f} (programs lowered: {compiles.n})")
    if before_window is not None:
        before_window()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    lowered0 = compiles.n
    with jax.profiler.TraceAnnotation("bench.window"):
        cell.window()
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.n - lowered0
    log(f"window done: programs lowered inside it: {in_window}")
    devices = jax.devices()[:chips]
    peak = memory_peak(devices)
    cell.release()
    checks = cell.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    measured = cell.measured()
    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {}
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(tr.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"trace": reduced, "counters": measured}
        for m in spec["per_layer"]:
            value = spec["readers"][m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    else:
        measured = dict(measured, setup_s=setup_s)
        for m in spec["end_to_end"]:
            # ``teps.bfs`` is the cell driver's ``teps``, named for its cells
            key = m["name"].split(".")[0]
            if key not in measured:
                raise KeyError(f"driver reported no {key}")
            metrics[m["name"]] = {"value": measured[key], "unit": m["unit"]}
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    line = {"correct": correct, "attempted": measured["attempted"],
            "failed": measured["failed"], "metrics": metrics,
            "device": device, "programs_lowered_in_window": in_window}
    line.update(out)
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = resolve(load_benchmark(), args.workload)
    import jax

    devices = jax.devices()
    chips = int(spec["cell"]["chips"])
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX platform is {devices[0].platform!r}")
        return 2
    if len(devices) < chips:
        log(f"the cell needs {chips} chips; JAX sees {len(devices)}")
        return 2
    from bench.trace import peaks

    peaks(devices[0].device_kind)
    line = run_cell(spec, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=T_START, chips=chips)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
