"""Benchmark driver: one section per paper table/figure + beyond-paper.

    PYTHONPATH=src python -m benchmarks.run            # everything cached
    PYTHONPATH=src python -m benchmarks.run --force    # re-simulate
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized frontiers

Sections:
  fig14  coalescing (accesses/warp)        paper: 3.9 -> ~3, 1.32x
  fig11  L1/L2 access reduction            paper: 67% / 56%
  fig12  NoC traffic                       paper: 54%
  fig15  filter effectiveness              paper: 48.5%
  fig13  speedup / energy                  paper: 1.33x / -13%
  fig4   IRU service overhead              paper: overhead < win
  moe    IRU (sorted/hash) vs dense MoE dispatch  beyond-paper
  roofline  dry-run three-term table       EXPERIMENTS §Roofline
"""
from __future__ import annotations

import argparse
import time


def _section(title, mod, *args, **kw):
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)))
    t0 = time.monotonic()
    mod.main(*args, **kw)
    print(f"# ({time.monotonic() - t0:.1f}s)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-moe", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="cap frontier sizes so the full suite fits CI time")
    args = ap.parse_args()

    from benchmarks import (common, fig4_overhead, fig11_accesses, fig12_noc,
                            fig13_perf_energy, fig14_coalescing, fig15_filter,
                            moe_dispatch, roofline)

    if args.quick:
        common.set_quick(True)

    if args.force:
        from benchmarks.common import all_cells
        print("re-simulating all (algo, dataset) cells ...")
        list(all_cells(force=True))

    _section("Fig 14 — memory coalescing (accesses per warp)", fig14_coalescing)
    _section("Fig 11 — normalized L1/L2 accesses", fig11_accesses)
    _section("Fig 12 — normalized NoC traffic", fig12_noc)
    _section("Fig 15 — IRU filter effectiveness", fig15_filter)
    _section("Fig 13 — speedup / energy", fig13_perf_energy)
    _section("Fig 4 — IRU service overhead vs win", fig4_overhead)
    if not args.skip_moe:
        _section("Beyond-paper — MoE dispatch (IRU sorted/hash vs dense)", moe_dispatch)
    _section("Roofline (from dry-run artifacts)", roofline)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
