"""Device-resident frontier pipeline: one compiled step per (graph, app).

The paper's IRU wins come from keeping the graph-analytics inner loop —
expand → reorder → filter/merge → update — on-device (Figs. 8-10).  The host
apps (``apps.bfs`` / ``apps.sssp`` / ``apps.pagerank``) re-implement that
loop in numpy per app, paying a host↔device round trip per iteration.  This
module is the shared runtime that composes the loop out of the repo's
device-resident pieces instead, Gunrock-style (frontier operators as the
unifying abstraction; locality transforms inside the shared runtime):

* **expand** — ``graphs.csr.expand_frontier``: capacity-padded CSR
  edge-frontier expansion, optionally through the block-reuse gather kernel
  (``kernels/coalesced_gather``);
* **reorder** — ``core.iru.iru_reorder``: the sort engine or the
  batched/banked hash engines (the paper's 4x2 partition geometry,
  ``round_cap`` hybrid, streaming windows — everything ``IRUConfig`` can
  express except the host-only ``hash_ref``);
* **filter/merge** — the engine's merge datapath (``core.filter``
  add/min), surfaced as the stream's ``active`` mask;
* **update** — the app's scatter + frontier rule (a ``FrontierApp``).

``FrontierPipeline.run`` drives the traversal through jitted
``lax.while_loop`` executables: zero host numpy between iterations, a
BOUNDED number of compiles per (graph shape, app) — re-running with a
different source, or running again, reuses the executables (``n_traces``
counts compiles; tests assert the bound).

**Capacity bucketing** (``CapacityPolicy``) is how sparse frontiers stop
paying the worst-case allocation: instead of one step compiled at
``edge_capacity = n_edges``, the runtime compiles the SAME step at a small
geometric ladder of capacities, predicts each iteration's edge count from
the frontier's degree sum (``graphs.csr.frontier_degree_sum`` — a cheap
device reduction), and dispatches to the smallest bucket that fits
(Gunrock / GraphCage: frontier runtimes live or die on sized frontier
buffers, not worst-case allocation).  Inside ``run`` the ``while_loop``
stays within one bucket; only when the predicted size outgrows the bucket
— or shrinks below the rung beneath with a hysteresis margin
(``CapacityPolicy.hysteresis``), so a frontier jittering at a rung
boundary never ping-pongs — does control
return to the host to hop executables (``n_hops`` counts dispatches).  So
``n_traces <= n_buckets`` and a deep sparse traversal (high-diameter BFS)
does O(frontier)-sized work per level instead of O(n_edges).  The node
frontier compacts with the same ladder (``frontier_from_mask(size=...)``),
and ``EdgeFrontier.overflow`` turns bucket misprediction into a detected,
re-dispatched event instead of silent truncation.

``FrontierPipeline.run_instrumented`` steps the SAME compiled step from the
host, dispatching per step, to feed a ``TraceRecorder`` — baseline / sort /
hash modes are measured from one code path instead of three per-app
reimplementations.

The pipeline names its own work for the profiler: ``frontier.*`` scopes on
every stage of the step, ``pipeline.*`` host spans around ``run`` / ``step``,
and per-rung lane counters read by ``FrontierPipeline.stats()``.

Apps declare themselves as ``FrontierApp`` records: an init rule, a
per-edge candidate value, a scatter target + merge op, and an update /
convergence predicate.  See ``apps.bfs.BFS_APP`` etc. for the three paper
apps; anything frontier-shaped (k-core, connected components, label
propagation) slots in the same way.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.iru import IRUConfig, iru_reorder
from repro.graphs.csr import (
    CSRGraph,
    expand_frontier,
    frontier_degree_sum,
    frontier_from_mask,
)
from repro.kernels.iru_reorder.batched import route_prefix_width

State = Any  # pytree of arrays (dict); app-defined


def _merge_identity(op: str, dtype) -> jax.Array:
    """Neutral element of a merge op at a payload dtype (inert lanes).

    ``"tagged"`` takes the min identity: by the tag-table contract every
    sentinel/padding index carries tag False (the min family), so inert
    lanes always land in min territory.
    """
    if op == "add":
        return jnp.zeros((), dtype)
    if op not in ("min", "max", "tagged"):
        raise ValueError(f"unknown merge op {op!r}")
    if op == "tagged":
        op = "min"
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        # iinfo.min is exact for signed AND unsigned dtypes (0 for uintN —
        # the old ``-max - 1`` relied on wraparound there)
        return jnp.array(info.max if op == "min" else info.min, dtype)
    return jnp.array(jnp.inf if op == "min" else -jnp.inf, dtype)


def _scatter(target: jax.Array, idx: jax.Array, val: jax.Array,
             act: jax.Array, op: str,
             tags: Optional[jax.Array] = None) -> jax.Array:
    """Merged scatter: inactive lanes retarget out of range and drop.

    ``op="tagged"`` is the fused-family scatter — each lane folds under its
    family (``tags``: False = min, True = add).  Min and add destinations
    are disjoint (a destination index has exactly one family), so the two
    drop-scatters compose without interference and each family's update
    stream is identical to what its solo scatter would apply.
    """
    dest = jnp.where(act, idx, target.shape[0])
    if op == "tagged":
        if tags is None:
            raise ValueError("op='tagged' requires per-lane tags")
        oob = jnp.int32(target.shape[0])
        d_min = jnp.where(tags, oob, dest)
        d_add = jnp.where(tags, dest, oob)
        return target.at[d_min].min(val, mode="drop").at[d_add].add(
            val, mode="drop")
    if op == "add":
        return target.at[dest].add(val, mode="drop")
    if op == "min":
        return target.at[dest].min(val, mode="drop")
    if op == "max":
        return target.at[dest].max(val, mode="drop")
    raise ValueError(f"unknown merge op {op!r}")


# Device counters (``FrontierPipeline.stats``) are int32 words: ``lo`` keeps
# the low ``_COUNT_BITS`` bits and ``hi`` the carries, so a count reaches
# 2**61 while each step adds under 2**30 (a step's lanes, at most its rung).
_COUNT_BITS = 30
_COUNT_FIELDS = ("steps", "live_lanes", "merged_lanes", "prefix_route_steps")


def _route_width(cfg: Optional[IRUConfig], e_cap: int, ragged: bool) -> int:
    """Lanes of the hash engine's prefix plan in a rung of ``e_cap`` lanes
    (``route_prefix_width``); 0 where the rung's reorder has none: no hash
    engine, a padded stream, or a stream split into windows."""
    if (cfg is None or cfg.mode != "hash" or cfg.engine != "batched"
            or not ragged
            or (cfg.window_elems is not None and e_cap > cfg.window_elems)):
        return 0
    return route_prefix_width(e_cap, cfg.num_sets)


def _named(name: str, fn: Callable, **kw) -> Callable:
    """``functools.partial(fn, **kw)`` named ``name``: ``jax.jit`` of it
    compiles a module called ``jit_<name>`` (``jit__unknown`` otherwise)."""
    f = functools.partial(fn, **kw)
    f.__name__ = name
    return f


def _arg_struct(x) -> jax.ShapeDtypeStruct:
    # no sharding: lowering from it then hits the executable the call
    # compiled (an explicit single-device sharding lowers another module)
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                weak_type=getattr(x, "weak_type", False))


@dataclasses.dataclass(frozen=True)
class CapacityPolicy:
    """Geometric ladder of compiled step capacities (the bucketing knob).

    The pipeline compiles its step once per rung; each rung ``c`` expands
    into ``c`` edge lanes and compacts the node frontier to
    ``min(c, n_nodes)`` lanes.  Rungs ascend geometrically from
    ``min_capacity`` by ``growth`` and the top rung is always the full
    ``edge_capacity`` (node frontier ``n_nodes``) so every frontier fits
    somewhere.  The default is ONE bucket at full capacity — exactly the
    pre-bucketing pipeline.

    More buckets = tighter working sets for sparse frontiers but more
    compiles (``n_traces <= n_buckets``) and more host boundary hops; 3-4
    buckets with growth 8-16 covers high-diameter traversals well.

    ``hysteresis`` is the down-hop margin: the compiled loop leaves its
    rung for a smaller one only once the frontier fits the rung below with
    this factor to spare, so a frontier jittering around a rung boundary
    does not pay one host dispatch per iteration.  1.0 = pure best-fit
    (hop the moment the rung below fits — minimal padding, more hops);
    larger values trade padding for fewer host syncs.  Host dispatch is
    cheap on CPU and expensive on accelerators, so tune accordingly.
    """

    n_buckets: int = 1
    min_capacity: int = 4096
    growth: int = 8
    hysteresis: float = 1.5

    def __post_init__(self):
        if self.n_buckets < 1:
            raise ValueError(f"n_buckets must be >= 1, got {self.n_buckets}")
        if self.min_capacity < 1:
            raise ValueError(
                f"min_capacity must be >= 1, got {self.min_capacity}")
        if self.growth < 2:
            raise ValueError(f"growth must be >= 2, got {self.growth}")
        if self.hysteresis < 1.0:
            raise ValueError(
                f"hysteresis must be >= 1.0, got {self.hysteresis}")

    def ladder(self, edge_capacity: int, n_nodes: int) -> tuple[
            tuple[int, int], ...]:
        """Ascending ``(edge_cap, node_cap)`` rungs, top = full capacity."""
        caps: list[int] = []
        c = self.min_capacity
        for _ in range(self.n_buckets - 1):
            if c >= edge_capacity:
                break
            caps.append(int(c))
            c *= self.growth
        caps.append(int(edge_capacity))
        return tuple(
            (ec, n_nodes if ec == edge_capacity else min(ec, n_nodes))
            for ec in caps)


def frontier_step(
    g: CSRGraph,
    app: "FrontierApp",
    state: State,
    mask: jax.Array,
    *,
    e_cap: int,
    f_cap: int,
    iru_config: Optional[IRUConfig] = None,
    gather: str = "xla",
    ragged: bool = True,
    exchange: Optional[Callable[[jax.Array, State], jax.Array]] = None,
):
    """One expand → candidate → reorder → merge-scatter → update iteration.

    This is the pipeline step as a pure function of ``(graph, app, state,
    mask)`` at one compiled capacity rung ``(e_cap, f_cap)`` — what
    ``FrontierPipeline._step_impl`` jits per bucket, and what the
    edge-partitioned multi-device driver (``dist.graph_partition``) runs
    per shard under ``shard_map`` with the SAME bucketing/ragged semantics.

    ``exchange``, when given, is called as ``exchange(new_target, state)``
    between the merged scatter and ``app.update`` and must return the
    (possibly rewritten) target array.  The partitioned driver uses it to
    ship ghost-slot contributions to their owning shards (the boundary
    all-to-all) before the app commits the superstep; single-device
    execution passes ``None`` and is bit-identical to the historical step.

    Apps with ``filter_op == "tagged"`` (the fused min+add datapath) must
    declare a ``tag_table`` rule; the table is built ONCE per step and rides
    the reorder engines as a lookup operand — lane tags re-derive from each
    engine frame's own index array, so the tag is always a pure function of
    the destination index and every duplicate run is uniform-tag.

    Each stage runs under a ``jax.named_scope`` (``frontier.expand``,
    ``frontier.reorder``, ``frontier.scatter``, ``frontier.exchange``,
    ``frontier.update``): compile-time metadata that names every HLO op's
    stage in the compiled text and in device traces, at no run-time cost.

    Returns ``(state, mask, idx, act, real, n_edges, overflow)``.
    """
    n = g.n_nodes
    with jax.named_scope("frontier.expand"):
        tag_tab = None
        if app.filter_op == "tagged":
            if app.tag_table is None:
                raise ValueError(f"app {app.name!r} has filter_op='tagged' "
                                 "but no tag_table")
            tag_tab = app.tag_table(state, g)
        nodes = frontier_from_mask(mask, size=f_cap)
        ef = expand_frontier(g, nodes, edge_capacity=e_cap, gather=gather,
                             with_weights=app.needs_weights)
        vals = app.candidate(state, g, ef)
        ident = _merge_identity(app.filter_op, vals.dtype)
        if tag_tab is None:
            vals = jnp.where(ef.valid, vals, ident)
        else:
            # per-lane identity: dead lanes in the ADD family must carry the
            # add identity (0), not +inf — their family's fold would
            # otherwise poison the destination through the drop-protected
            # scatter of an overflowed engine round.  Dead lanes with the
            # sentinel index n map to tag False and take the min identity.
            lane_tag = tag_tab[jnp.clip(ef.dsts, 0, tag_tab.shape[0] - 1)]
            ident_add = _merge_identity("add", vals.dtype)
            vals = jnp.where(ef.valid, vals,
                             jnp.where(lane_tag, ident_add, ident))
    # the expansion already counted its live lanes (clamped to the
    # bucket) — no O(capacity) reduction to recover it
    n_edges = ef.n_valid
    if iru_config is None:
        idx, svals, act = ef.dsts, vals, ef.valid
        real = ef.valid
    else:
        # padding lanes carry the sentinel index n: they ride through
        # the reorder as ordinary elements (merging only with each
        # other) and drop at the scatter — stream shape stays static.
        # Under ragged execution the engines instead treat them as dead
        # lanes: sorts/scans/rounds see the live prefix only, and the
        # pads come back inactive without ever entering a hash set.
        with jax.named_scope("frontier.reorder"):
            stream = iru_reorder(ef.dsts, vals, config=iru_config,
                                 n_live=ef.n_valid if ragged else None,
                                 tag_table=tag_tab)
            idx, svals = stream.indices, stream.secondary
            act = stream.active & (stream.indices < n)
            # expansion emits valid lanes front-packed, so a lane is a real
            # element iff its original position is below the valid count —
            # what the instrumented driver crops traces to (padding lanes
            # issue no memory access and must not count in the cost model)
            real = stream.positions < n_edges
    with jax.named_scope("frontier.scatter"):
        lane_tags = (None if tag_tab is None
                     else tag_tab[jnp.clip(idx, 0, tag_tab.shape[0] - 1)])
        new_target = _scatter(state[app.target], idx, svals, act,
                              app.filter_op, tags=lane_tags)
    if exchange is not None:
        with jax.named_scope("frontier.exchange"):
            new_target = exchange(new_target, state)
    with jax.named_scope("frontier.update"):
        state, mask = app.update(state, new_target, g)
    return state, mask, idx, act, real, n_edges, ef.overflow


class StepResult(NamedTuple):
    """One dispatched pipeline step (see :meth:`FrontierPipeline.step`).

    On ``overflow=True`` (only reachable with ``raise_on_overflow=False``)
    ``state``/``mask`` are the UNCHANGED inputs — the overflowed step's
    outputs were truncated and must be discarded; the caller decides how to
    shed load (the serving engine quarantines a tenant and retries).
    """

    state: Any
    mask: jax.Array
    idx: jax.Array
    act: jax.Array
    real: jax.Array
    n_edges: jax.Array
    overflow: bool
    bucket: int


@dataclasses.dataclass(frozen=True)
class FrontierApp:
    """Declarative frontier app: what varies between BFS / SSSP / PageRank.

    The pipeline owns expansion, reorder, merge and the scatter; the app
    owns only its state, its per-edge candidate value, and its frontier /
    convergence rule.

    * ``init(graph, source)`` -> ``(state, mask)``: initial state pytree and
      dense bool[n_nodes] frontier mask.
    * ``candidate(state, graph, ef)`` -> per-lane payload [edge_capacity]
      (``ef`` is a ``graphs.csr.EdgeFrontier``; invalid lanes are
      overwritten with the merge identity by the pipeline).
    * ``target``: state key the merged stream scatters into (``filter_op``
      is both the IRU merge op and the scatter op — the paper couples them
      the same way: the merge datapath mirrors the atomic).
    * ``update(state, new_target, graph)`` -> ``(state, mask)``: commit the
      scattered target, advance counters, emit the next frontier mask.
    * ``cond(state, mask)`` -> bool scalar: keep iterating?
    * ``result(state)`` -> the app's output array.
    * ``atomic``: whether the recorded irregular access is an atomic
      (SSSP/PR scatters) or a plain load (BFS label lookups) — trace
      bookkeeping only.
    * ``needs_weights``: expansion co-gathers edge weights into
      ``ef.weights`` (through the same kernel pass on the pallas path).
    * ``tag_table(state, graph)`` (required iff ``filter_op == "tagged"``)
      -> bool[n_nodes + 1]: each destination index's merge family (False =
      min, True = add; the trailing entry covers the padding sentinel and
      must be False).  Built once per step and passed to the reorder
      engines, which re-derive per-lane tags from their own index frames —
      the tag is a pure function of the index, so equal indices always
      share a family and duplicate runs stay uniform-tag.
    """

    name: str
    filter_op: str
    target: str
    init: Callable[[CSRGraph, int], tuple[State, jax.Array]]
    candidate: Callable[[State, CSRGraph, Any], jax.Array]
    update: Callable[[State, jax.Array, CSRGraph], tuple[State, jax.Array]]
    cond: Callable[[State, jax.Array], jax.Array]
    result: Callable[[State], jax.Array]
    atomic: bool = True
    needs_weights: bool = False
    tag_table: Optional[Callable[[State, CSRGraph], jax.Array]] = None


class FrontierPipeline:
    """Bucketed single-compile frontier runtime over one (graph, app) pair.

    ``mode`` selects the reorder stage from one code path:

    * ``"baseline"`` — no reorder; the raw expansion stream scatters
      directly (duplicate lanes resolved by the scatter op itself);
    * ``"sort"``     — the stable-sort engine (infinite-patience bound);
    * ``"hash"``     — the paper's bounded hash engine; the full
      ``IRUConfig`` geometry applies (banked partitions, ``round_cap``,
      ``window_elems``, ``bank_map``...).

    ``iru_config`` carries the geometry; its ``mode``/``filter_op`` are
    overridden by ``mode`` and the app's op (``hash_ref`` is host-only and
    rejected — the pipeline is the device path).

    ``capacity_policy`` buckets the compiled capacities (see
    ``CapacityPolicy``); the default single bucket at ``edge_capacity``
    reproduces the fixed-capacity pipeline exactly.

    ``ragged`` (default True) threads the expansion's live lane count
    (``EdgeFrontier.n_valid``) into the reorder engines as ``n_live``, so
    sorts, segment scans and occupancy rounds run against the live prefix
    of the padded bucket instead of its full extent — the padded-size
    residue the capacity ladder cannot remove (a bucket is still 1-growthx
    oversized on average, and the top bucket dwarfs sparse frontiers).
    Results are unchanged: the ragged stream is bit-identical on indices /
    positions / active to the padded one (engine parity suites +
    ``tests/test_iru_ragged.py``), with payload fp grouping differing only
    within the documented reduction-order freedom.  The live count is a
    runtime operand, never a shape — bucket executables and trace counts
    are identical to padded execution.  ``ragged=False`` restores padded
    execution exactly (the benchmark's padded-vs-ragged rows pin the
    difference).
    """

    def __init__(
        self,
        graph: CSRGraph,
        app: FrontierApp,
        *,
        mode: str = "baseline",
        iru_config: Optional[IRUConfig] = None,
        max_iters: Optional[int] = None,
        edge_capacity: Optional[int] = None,
        capacity_policy: Optional[CapacityPolicy] = None,
        gather: str = "xla",
        ragged: bool = True,
    ):
        if mode not in ("baseline", "sort", "hash"):
            raise ValueError(
                f"mode must be baseline|sort|hash, got {mode!r} "
                "(hash_ref is the host oracle; use apps.* host paths)")
        self.graph = graph
        self.app = app
        self.mode = mode
        self.max_iters = graph.n_nodes if max_iters is None else max_iters
        self.edge_capacity = (graph.n_edges if edge_capacity is None
                              else edge_capacity)
        self.gather = gather
        if mode == "baseline":
            self.iru_config = None
        else:
            self.iru_config = dataclasses.replace(
                iru_config or IRUConfig(), mode=mode, filter_op=app.filter_op)
        self.ragged = ragged
        self.capacity_policy = capacity_policy or CapacityPolicy()
        # ascending (edge_cap, node_cap) rungs; top rung == full capacity
        self.buckets = self.capacity_policy.ladder(
            self.edge_capacity, graph.n_nodes)
        # per rung: the live count up to which the hash engine's route
        # plans over its prefix width (0: no prefix plan)
        self._route_w = tuple(_route_width(self.iru_config, e_cap, ragged)
                              for e_cap, _ in self.buckets)
        self.n_traces = 0  # whole-run compiles (tests assert <= n_buckets)
        self.n_hops = 0    # host bucket dispatches across run() calls
        # per-rung device counters (see stats()): [rung, field, (lo, hi)]
        # words, updated inside the executables and read only by stats()
        self._counts = jnp.zeros((len(self.buckets), len(_COUNT_FIELDS), 2),
                                 jnp.int32)
        # module name -> (jitted fn, argument structs of its first call):
        # what hlo_texts() looks the compiled executables up by
        self._signatures: dict[str, tuple[Callable, Any]] = {}
        # whole-run executables donate (state, mask, it, counts): the
        # while_loop carry rewrites every buffer each level anyway, so the
        # caller's copies are dead the moment the call is dispatched —
        # donation lets XLA reuse them instead of allocating a fresh
        # frontier/state set per run/hop.  run() rebinds all of them from
        # the outputs before any further use.  The per-step executables
        # (_step_b) must NOT donate: step(raise_on_overflow=False) hands the
        # UNCHANGED inputs back on overflow and the serving engine
        # re-dispatches them rung by rung.
        self._run_b = tuple(
            jax.jit(_named(f"frontier_run_r{b}", self._run_impl, bucket=b),
                    donate_argnums=(1, 2, 3, 4))
            for b in range(len(self.buckets)))
        self._step_b = tuple(
            jax.jit(_named(f"frontier_step_r{b}", self._step_impl, bucket=b))
            for b in range(len(self.buckets)))
        # the top-bucket step is the historical fixed-capacity step
        self._step = self._step_b[-1]
        self._predict = jax.jit(_named("frontier_predict", self._predict_impl))

    # -- bucket dispatch ---------------------------------------------------
    def _predict_impl(self, g, mask):
        """Next iteration's exact working set: (degree sum, node count)."""
        with jax.named_scope("frontier.predict"):
            return (frontier_degree_sum(g, mask),
                    jnp.sum(mask.astype(jnp.int32)))

    def _host_bucket(self, need: int, count: int) -> int:
        for i, (e_cap, f_cap) in enumerate(self.buckets):
            if need <= e_cap and count <= f_cap:
                return i
        return len(self.buckets) - 1

    def _dispatch(self, fn, *args):
        """``fn(*args)``, remembering the first call's argument structs."""
        name = fn.__name__
        if name not in self._signatures:
            self._signatures[name] = (fn, jax.tree.map(_arg_struct, args))
        return fn(*args)

    # -- one pipeline iteration (expand → reorder → merge → update) --------
    def _step_impl(self, g, state, mask, counts, bucket: int):
        # ``g`` rides as a jit argument (CSRGraph is a pytree), not a baked
        # closure constant: the executable is reusable across same-shape
        # graphs and the HLO carries no giant literals.  ``bucket`` is a
        # static Python int — one executable per rung.  ``counts`` comes
        # back with this step added to the rung's row.
        e_cap, f_cap = self.buckets[bucket]
        out = frontier_step(g, self.app, state, mask, e_cap=e_cap,
                            f_cap=f_cap, iru_config=self.iru_config,
                            gather=self.gather, ragged=self.ragged)
        _, _, _, act, _, n_edges, _ = out
        with jax.named_scope("frontier.count"):
            # live lanes the reorder merged away (baseline merges none):
            # ``real & ~act`` counted as ``n_edges - sum(act)``, since only
            # real lanes are active — reading ``real`` would keep the
            # engines' position outputs alive, which the loop never reads
            merged = (jnp.int32(0) if self.iru_config is None
                      else n_edges.astype(jnp.int32)
                      - jnp.sum(act.astype(jnp.int32)))
            # a scalar compare: the step's live count fits the route's
            # prefix width
            w = self._route_w[bucket]
            prefix = ((n_edges <= w).astype(jnp.int32) if w
                      else jnp.int32(0))
            lo = counts[bucket, :, 0] + jnp.stack(
                [jnp.int32(1), n_edges.astype(jnp.int32), merged, prefix])
            hi = counts[bucket, :, 1] + (lo >> _COUNT_BITS)
            lo = lo & jnp.int32((1 << _COUNT_BITS) - 1)
            counts = counts.at[bucket].set(jnp.stack([lo, hi], axis=-1))
        return (*out, counts)

    def _run_impl(self, g, state, mask, it, counts, bucket: int):
        self.n_traces += 1  # python body: executes per trace, not per call
        top = len(self.buckets) - 1

        # a caller-shrunk edge_capacity (< n_edges) makes even the top rung
        # overflowable; guard it in the loop condition so control returns to
        # the host (which raises) instead of silently truncating.  The
        # default full-capacity single bucket compiles exactly the
        # pre-bucketing loop (no fit test at all).
        shrunk = self.edge_capacity < self.graph.n_edges

        def cond(carry):
            s, m, i, _ = carry
            with jax.named_scope("frontier.predict"):
                return fits(s, m, i)

        def fits(s, m, i):
            ok = self.app.cond(s, m) & (i < self.max_iters)
            if top > 0 or shrunk:
                need, count = self._predict_impl(g, m)
                if bucket < top or shrunk:
                    # the next frontier must still FIT this rung (exceeding
                    # it returns to the host, which hops up)
                    e_cap, f_cap = self.buckets[bucket]
                    ok &= (need <= e_cap) & (count <= f_cap)
                if bucket > 0:
                    # down-hop hysteresis: leave for a smaller rung only
                    # once the frontier fits the rung below with margin —
                    # a frontier jittering around a rung boundary must not
                    # degenerate to one host round trip per iteration (a
                    # wide margin would instead trap smooth decaying
                    # frontiers a rung too high; CapacityPolicy.hysteresis
                    # picks the tradeoff).  Entry guarantee: the host
                    # dispatches the smallest FITTING rung, so at loop
                    # entry either need or count exceeds the rung below
                    # (hence the static threshold, <= pe_cap) and this
                    # term is True — the loop always makes >= 1 iteration
                    # of progress.
                    pe_cap, pf_cap = self.buckets[bucket - 1]
                    h = self.capacity_policy.hysteresis
                    # same margin on both axes: a node count jittering
                    # around the rung-below node cap must not ping-pong
                    # any more than a degree sum around its edge cap
                    ok &= ((need > int(pe_cap / h))
                           | (count > int(pf_cap / h)))
            return ok

        def body(carry):
            s, m, i, c = carry
            s, m, *_, c = self._step_impl(g, s, m, c, bucket)
            with jax.named_scope("frontier.count"):
                return s, m, i + 1, c

        # the loop's own control, and whatever the compiler makes of it
        with jax.named_scope("frontier.loop"):
            return jax.lax.while_loop(cond, body, (state, mask, it, counts))

    # -- public drivers ----------------------------------------------------
    def init(self, source: int = 0) -> tuple[State, jax.Array]:
        return self.app.init(self.graph, source)

    def run(self, source: int = 0) -> jax.Array:
        """Whole traversal through the compiled bucket executables.

        Single-bucket policies make ONE device call (zero host work
        inside); multi-bucket policies hop executables on the host only
        when the predicted frontier crosses a bucket boundary.  Either
        way ``n_traces <= n_buckets``.

        Host spans (``jax.profiler.TraceAnnotation``, inert unless a
        profiler trace is on) name the host work: ``pipeline.init`` (the
        app's init and the donation copies), ``pipeline.hop`` (the loop
        test, the predict, its host sync and the rung choice),
        ``pipeline.dispatch`` (enqueueing one rung executable) and
        ``pipeline.result``.
        """
        span = jax.profiler.TraceAnnotation
        with span("pipeline.init"):
            state, mask = self.init(source)
            # the run executables donate (state, mask, it, counts); donation
            # rejects one buffer arriving as two leaves (XLA: "donate the
            # same buffer twice"), and apps may seed several state entries
            # from one array (ppr's rank/src) — or, worse, reference a
            # graph array, which must never be given away.  Copy-break
            # duplicates once per run — later hops pass executable
            # outputs, which are distinct buffers.
            seen: set[int] = {
                id(x) for x in jax.tree_util.tree_leaves(self.graph)}

            def _unalias(x):
                if id(x) in seen:
                    return jnp.array(x, copy=True)
                seen.add(id(x))
                return x

            state, mask = jax.tree_util.tree_map(_unalias, (state, mask))
            it = jnp.int32(0)
        shrunk = self.edge_capacity < self.graph.n_edges
        if len(self.buckets) == 1 and not shrunk:
            with span("pipeline.dispatch"):
                state, _, _, self._counts = self._dispatch(
                    self._run_b[0], self.graph, state, mask, it, self._counts)
        else:
            while True:
                with span("pipeline.hop"):
                    if not (int(it) < self.max_iters
                            and bool(self.app.cond(state, mask))):
                        break
                    need, count = self._dispatch(self._predict, self.graph,
                                                 mask)
                    if shrunk and int(need) > self.buckets[-1][0]:
                        raise RuntimeError(
                            f"frontier degree sum {int(need)} overflows the "
                            f"shrunk edge_capacity={self.edge_capacity}: "
                            f"edges would be dropped — raise edge_capacity")
                    b = self._host_bucket(int(need), int(count))
                self.n_hops += 1
                with span("pipeline.dispatch"):
                    state, mask, it, self._counts = self._dispatch(
                        self._run_b[b], self.graph, state, mask, it,
                        self._counts)
        assert self.n_traces <= len(self.buckets), (
            f"pipeline traced {self.n_traces}x for "
            f"{len(self.buckets)} buckets — executables not reused")
        with span("pipeline.result"):
            return self.app.result(state)

    def step(self, state, mask, *, raise_on_overflow: bool = True
             ) -> StepResult:
        """One step at the smallest fitting bucket, re-dispatched upward on
        overflow (misprediction can only come from a caller-shrunk
        ``edge_capacity``; the predictor itself is exact).

        This is the host-dispatched public step — what external drivers
        that join/retire work between iterations (the multi-tenant
        ``serve.graph_engine``) build on, and what ``run_instrumented``
        steps.  With ``raise_on_overflow=False`` a top-bucket overflow is
        returned as ``StepResult(overflow=True)`` carrying the UNCHANGED
        input state/mask (the truncated outputs are discarded, and the
        step is not counted in ``stats()``) instead of raising, so a
        serving loop can shed load and retry rather than die.  Host spans
        as in :meth:`run`.
        """
        span = jax.profiler.TraceAnnotation
        if len(self.buckets) == 1 and self.edge_capacity >= self.graph.n_edges:
            # default full-capacity single bucket: the choice is forced and
            # a mask-derived frontier cannot overflow n_edges — skip the
            # predict round trip (the pre-bucketing step path exactly)
            with span("pipeline.dispatch"):
                *out, self._counts = self._dispatch(
                    self._step_b[0], self.graph, state, mask, self._counts)
            return StepResult(*out, 0)
        with span("pipeline.hop"):
            need, count = self._dispatch(self._predict, self.graph, mask)
            b = self._host_bucket(int(need), int(count))
        while True:
            with span("pipeline.dispatch"):
                *out, counts = self._dispatch(
                    self._step_b[b], self.graph, state, mask, self._counts)
            with span("pipeline.hop"):
                if not bool(out[-1]):  # overflow flag
                    self._counts = counts
                    return StepResult(*out[:-1], False, b)
                if b == len(self.buckets) - 1:
                    if raise_on_overflow:
                        raise RuntimeError(
                            f"expansion overflowed the top bucket "
                            f"(edge_capacity={self.edge_capacity}): the "
                            f"frontier's degree sum exceeds the compiled "
                            f"capacity — raise edge_capacity (duplicated "
                            f"frontier ids can also inflate the degree sum)")
                    return StepResult(state, mask, out[2], out[3], out[4],
                                      out[5], True, b)
                b += 1

    def run_instrumented(self, source: int = 0, *, recorder=None) -> jax.Array:
        """Host-stepped traversal over the same compiled steps, feeding a
        ``apps.trace.TraceRecorder`` per iteration — the single
        instrumentation point for baseline/sort/hash measurement.  Buckets
        dispatch per step; an overflowed step (possible only with a
        caller-shrunk ``edge_capacity``) is re-dispatched one rung up
        instead of silently truncating."""
        state, mask = self.init(source)
        it = 0
        while it < self.max_iters and bool(np.asarray(self.app.cond(state, mask))):
            r = self.step(state, mask)
            state, mask = r.state, r.mask
            it += 1
            if recorder is not None:
                if self.mode != "baseline":
                    recorder.processed(int(r.n_edges))
                # crop to real-element lanes: recorded streams carry exactly
                # the accesses the traversal issues, same element counts as
                # the host apps' ragged traces (capacity padding is free)
                sel = np.asarray(r.real)
                recorder.access(np.asarray(r.idx)[sel], np.asarray(r.act)[sel],
                                atomic=self.app.atomic)
        return self.app.result(state)

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        """Cumulative counts since construction, as Python ints.

        ``n_traces`` and ``n_hops``, and per rung (ascending, as
        ``buckets``): ``edge_capacity``, ``steps`` run, ``live_lanes`` (the
        expansion's live edge lanes, ``EdgeFrontier.n_valid`` summed over
        the steps), ``compiled_lanes`` (``steps * edge_capacity``) and, in
        the reorder modes, ``merged_lanes`` (live lanes the reorder left
        inactive: merged into another lane of the same index), and
        ``prefix_route_steps`` (steps whose live lanes fit the hash
        engine's prefix plan width, ``route_prefix_width``; 0 where the
        rung has no such plan).  Steps count in ``run`` and in ``step``;
        an overflowed step does not.
        Reads the device counters: one transfer that waits for the work
        in flight, so call it outside timed work.
        """
        words = np.asarray(self._counts).astype(np.int64)
        totals = (words[..., 0] + (words[..., 1] << _COUNT_BITS)).tolist()
        rungs = []
        for (e_cap, _), row in zip(self.buckets, totals):
            r = {"edge_capacity": e_cap, **dict(zip(_COUNT_FIELDS, row))}
            r["compiled_lanes"] = r["steps"] * e_cap
            if self.iru_config is None:
                del r["merged_lanes"]
            rungs.append(r)
        return {"n_traces": self.n_traces, "n_hops": self.n_hops,
                "rungs": rungs}

    def hlo_texts(self) -> dict[str, str]:
        """Compiled HLO text of every executable this pipeline has called,
        by module name (``jit_frontier_run_r<b>``, ``jit_frontier_step_r<b>``,
        ``jit_frontier_predict``).  Each instruction's ``op_name`` metadata
        carries its ``frontier.*`` / ``iru.*`` scopes.

        The executables are looked up from the jit caches by the argument
        structs of their first call: nothing is traced again (``n_traces``
        is unchanged).  A cache miss would lower and compile again, which
        ``jax.monitoring``'s compile events show to a caller that listens.
        """
        traces = self.n_traces
        texts = {f"jit_{name}": fn.lower(*args).compile().as_text()
                 for name, (fn, args) in self._signatures.items()}
        assert self.n_traces == traces, "hlo_texts() traced a rung again"
        return texts
