"""Reduce a profiler trace of the measured window to the benchmark's numbers.

``jax.profiler`` writes an XSpace (``*.xplane.pb``); ``ProfileData`` reads it.
Device planes are ``/device:TPU:<i>``, and on each the line ``XLA Ops``
holds one event per executed HLO op, on the host's clock.  The host plane
``/host:CPU`` holds the harness's own spans (``TraceAnnotation`` names that
start with ``bench.``).  From those:

* busy: the union of the op intervals inside the window, per chip, then
  averaged over the chips used; the idle share is ``1 - busy / window``;
* op classes: device time of the gathers, scatters and sorts
  (``classify``), for the per-edge metrics;
* breakdown: the ops that took most device time, and the device's idle time
  attributed to the innermost harness span open at each gap's midpoint.

The window is the ``bench.window`` span.  An op that straddles its edges
counts for the part inside.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"

# The TPU trace names each op by its HLO instruction text
# (``%fusion.41 = s32[33554432]{...} fusion(s32[33554432]{...} %a, s32[...]
# %b), kind=kCustom, calls=...``) and carries no other stat that says what
# it does.  The class is read from that text (looked at by hand in the
# traces of every cell on a v5e chip):
# * ``sort``: an op whose opcode or name is a sort;
# * ``gather``: a gather, or a ``kCustom`` fusion (the TPU's gather and
#   scatter emitters) whose output has the length of its second operand,
#   the indices of ``table[indices]``;
# * ``scatter``: a scatter, or another ``kCustom`` fusion whose output has
#   the length of its first operand, the target of
#   ``target.at[indices].op(updates)`` (updates that are constants or iotas
#   are fused in, so a scatter may show two operands);
# * ``control``: ``while``, ``conditional`` and ``call``, which enclose
#   other ops: left out of busy time and of every class, which the ops
#   inside them already count;
# * ``other``: the rest, with fusions whose target is fused in too.
# Lengths are compared after rounding up to a whole tile of 1024 lanes:
# XLA pads an index operand to the tile (``s32[31418368]`` indices for
# ``s32[31417472]`` output).
CONTROL_OPS = ("while", "conditional", "call")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9_\-]*)\(")
_DIMS = re.compile(r"\[([0-9,]*)\]")


def _top_level_operands(text: str) -> list[str]:
    """The comma-separated operands inside the first parenthesis group."""
    depth, cur, out = 0, [], []
    for ch in text:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return [o.strip() for o in out if o.strip()]


def classify(name: str) -> str:
    """The op class of one trace event, from its HLO instruction text."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return "other"
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    if opcode in CONTROL_OPS:
        return "control"
    label = head.lstrip("%")
    for cls in ("sort", "scatter", "gather"):
        if opcode == cls or label.startswith(cls):
            return cls
    if opcode != "fusion" or "kind=kCustom" not in rest:
        return "other"
    out = _DIMS.search(rest[:m.start() + 1])
    ops = [_DIMS.search(o) for o in _top_level_operands(rest[m.end():])]
    if out is None or any(o is None for o in ops):
        return "other"
    out_len, lens = _tiles(out.group(1)), [_tiles(o.group(1)) for o in ops]
    if len(lens) >= 2 and out_len == lens[1]:
        return "gather"
    if len(lens) >= 2 and out_len == lens[0]:
        return "scatter"
    return "other"


def _tiles(dims: str) -> tuple:
    """A shape's dims with the last rounded up to whole 1024-lane tiles."""
    d = [int(x) for x in dims.split(",") if x]
    if d:
        d[-1] = -(-d[-1] // 1024)
    return tuple(d)


@dataclasses.dataclass
class Op:
    name: str          # the HLO instruction text
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Op]]    # device plane name -> its ops
    spans: list[Span]


def latest_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return found[-1]


def from_profile(pd) -> Trace:
    """``Trace`` from a ``jax.profiler.ProfileData``."""
    ops: dict[str, list[Op]] = {}
    spans: list[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                ops.setdefault(plane.name, []).extend(
                    Op(ev.name, ev.start_ns, ev.end_ns) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns))
    return Trace(ops, spans)


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(latest_xplane(logdir)))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(trace: Trace) -> tuple[float, float]:
    wins = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return wins[0].start_ns, wins[0].end_ns


def reduce(trace: Trace, top: int = 10) -> dict:
    """The window's busy and idle time, op-class seconds and breakdown.

    Returns ``window_s``, ``busy_s`` (mean over chips), ``idle_share``,
    ``class_s`` ({class: device seconds, summed over chips}), and
    ``breakdown`` (``device_ops`` and ``idle_gaps``: ``[name, seconds]``)."""
    w0, w1 = window_of(trace)
    window = w1 - w0
    if window <= 0 or not trace.ops:
        raise ValueError("empty trace window or no device ops")
    busy_total = 0.0
    class_ns: dict[str, float] = collections.Counter()
    op_ns: dict[str, float] = collections.Counter()
    gaps: list[tuple[float, float]] = []
    for plane, ops in sorted(trace.ops.items()):
        clipped = []
        for op in ops:
            s, e = max(op.start_ns, w0), min(op.end_ns, w1)
            cls = classify(op.name)
            if e <= s or cls == "control":
                continue
            clipped.append((s, e))
            class_ns[cls] += e - s
            op_ns[op.name] += e - s
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(trace.ops)
    busy_s = busy_total / n_dev * 1e-9
    idle_by_span = _attribute(gaps, trace.spans)
    return {
        "window_s": window * 1e-9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window * 1e-9),
        "class_s": {k: v * 1e-9 for k, v in class_ns.items()},
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in op_ns.most_common(top)],
            "idle_gaps": [[k, v * 1e-9 / n_dev]
                          for k, v in idle_by_span.most_common(top)],
        },
    }


def _attribute(gaps, spans: list[Span]) -> collections.Counter:
    """Idle nanoseconds by the innermost harness span open at each gap's
    midpoint: spans are laid on the gaps longest first, so a shorter span
    nested inside a longer one overwrites it."""
    gaps = sorted(gaps)
    mids = [(g0 + g1) / 2 for g0, g1 in gaps]
    label = ["(no span)"] * len(gaps)
    for sp in sorted(spans, key=lambda s: s.start_ns - s.end_ns):
        i0 = bisect.bisect_left(mids, sp.start_ns)
        i1 = bisect.bisect_right(mids, sp.end_ns)
        label[i0:i1] = [sp.name] * (i1 - i0)
    out: collections.Counter = collections.Counter()
    for (g0, g1), name in zip(gaps, label):
        out[name] += g1 - g0
    return out


def peaks(device_kind: str) -> dict:
    """The chip's published peaks (``bench/peaks.json``); an unknown device
    is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


# Readers shared by the per-layer metric files (``bench/metrics/``).

def idle_percent(ctx: dict) -> float:
    return ctx["trace"]["idle_share"] * 100.0


def hops_per_traversal(ctx: dict):
    c = ctx["counters"]
    return c["bucket_hops"] / c["traversals"] if c.get("traversals") else None


def per_edge_ns(ctx: dict, cls: str):
    """Device ns of one op class per edge counted for ``teps``; nothing
    when the class or the edges are absent."""
    secs = ctx["trace"]["class_s"].get(cls, 0.0)
    edges = ctx["counters"].get("edges", 0)
    return secs / edges * 1e9 if secs > 0 and edges > 0 else None
