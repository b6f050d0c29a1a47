"""A cell of ``BENCHMARK.json`` cut to a size a CPU test run can hold."""
from __future__ import annotations

import time

from bench import run

TINY_SCALE = 7


def spec(workload: str, **mix_overrides) -> dict:
    s = run.resolve(run.load_benchmark(), workload)
    cfg = dict(s["config"], scale=TINY_SCALE)
    cfg["edge_capacity"] = 2 * cfg["edge_factor"] << cfg["scale"]
    mix = dict(s["mix"], **mix_overrides)
    return dict(s, config=cfg, mix=mix)


def run_tiny(workload: str, *, seed: int = 3, seconds: float = 0.5,
             trace: bool = False, before_window=None, **mix_overrides) -> dict:
    return run.run_cell(spec(workload, **mix_overrides), seed=seed,
                        seconds=seconds, trace=trace,
                        t_start=time.monotonic(), chips=1,
                        before_window=before_window)
