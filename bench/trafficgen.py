"""The one traffic generator: a mix file under ``bench/traffic/`` plus a seed
and a graph give the run's roots.

A mix is data (``bench/traffic/<mix>.json``), so a later change adds traffic
without code.  Its keys:

* ``arrivals``: ``"back_to_back"`` (closed loop: each traversal starts when
  the one before it has finished), the only kind a cell uses so far;
* ``sources``: ``"uniform"`` over the vertices of degree >= 1 (Graph500's
  root rule), the only rule a cell uses so far;
* ``max_requests``: how many roots to draw; a run uses as many as its
  window holds;
* ``warmup``: how many of them warm the executables and are never timed.

Everything is drawn from the seed with numpy on the host, before the window.
"""
from __future__ import annotations

import numpy as np


def generate(mix: dict, seed: int, degrees: np.ndarray) -> list[int]:
    """The run's roots in order.  ``degrees`` covers the vertices a root may
    be drawn from (the pad vertex left out)."""
    if mix["arrivals"] != "back_to_back":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    if mix["sources"] != "uniform":
        raise ValueError(f"unknown source rule {mix['sources']!r}")
    rng = np.random.default_rng([int(seed), 0x7EAFF1C])
    pool = np.nonzero(degrees > 0)[0]
    return [int(r) for r in rng.choice(pool, int(mix["max_requests"]))]
