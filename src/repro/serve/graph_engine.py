"""Fault-tolerant multi-tenant graph query serving on the fused datapath.

``GraphServingEngine`` is the graph twin of the slot-leased continuous
batching ``ServingEngine`` (``serve.engine``): many concurrent traversal
queries — BFS / SSSP / PPR, different source nodes, different users — are
multiplexed into ONE compiled bucketed ``FrontierPipeline`` step, and
queries join and retire mid-flight exactly like decode requests joining a
batch slot.

**The query-id lane.**  The engine leases ``query_slots`` lanes over a
composite replica view (``graphs.csr.tile_csr`` → ``GraphView``): query
``q``'s node ``v`` is composite node ``q * n_nodes + v``, so the merged
frontier is a single stream of ``(query, node)`` ids the existing runtime
consumes unchanged — expansion, degree-sum prediction, the capacity ladder,
IRU reorder and the merge datapath all see ordinary node ids.  Because
composite ids never collide across replicas, duplicate filtering and
merging combine lanes only WITHIN a query — the per-tenant isolation
invariant the property tests pin.  The engine accepts a plain ``CSRGraph``
(and tiles it itself), a pre-built ``GraphView`` whose ``n_tenants``
matches ``query_slots``, or a ``PartitionedGraphView``
(``partition_csr(tile_csr(g, Q), P)``) — the last runs every tick
``shard_map``-partitioned across ``P`` devices with the PR-9 boundary
exchange stitching shard results per superstep.

**Merge families — the tagged-lane fused datapath.**  BFS and SSSP share
the ``min`` family (BFS runs as unit-weight shortest paths in f32,
converted back to int32 hop labels on retirement — exact for any graph
that fits memory); PPR is the ``add`` family.  With ``fused=True`` (the
default) BOTH families advance in ONE compiled bucketed dispatch per tick:
the composite app declares ``filter_op="tagged"`` and a per-step tag table
(tag of composite id = family of its slot), so every reorder/merge/scatter
stage folds each lane under its own family in a single pass — one
``CapacityPolicy`` ladder, at most ``n_buckets`` step executables TOTAL
for a mixed BFS+SSSP+PPR workload, reused across ticks and tenants.
``fused=False`` retains the split per-family engine (one batched step per
family per tick, ``n_traces <= n_buckets`` per family) — the parity
oracle the fused suite compares against.

**Robustness model** (the serving-side analogue of ``ft.supervisor``):

* *Admission control* — a query is admitted only if

      degsum(init_frontier_new) + Σ_running degsum(frontier_q)  <=  E_top

  where ``degsum`` is ``graphs.csr.frontier_degree_sum`` and ``E_top`` the
  top rung of the family's ``CapacityPolicy`` ladder (the engine's edge
  budget, default ``query_slots * n_edges``): a new tenant can never push
  the merged frontier past the largest compiled bucket.  The wait queue is
  bounded (``max_queue``) and overflows loudly (``QueueFullError``); a
  query that could never fit even alone is rejected at submit
  (``AdmissionError``).
* *Overflow quarantine* — frontiers grow mid-flight, so the per-tick
  dispatch re-checks the predicted degree sum; if the merged frontier
  outgrows the top bucket (or a step reports ``EdgeFrontier.overflow``, or
  a fault plan forces one) the engine evicts the query with the LARGEST
  predicted contribution and retries it solo — a fresh single-tenant
  ``FrontierPipeline`` run at full base-graph capacity — after exponential
  backoff (``ft.supervisor.backoff_delay``), bounded by ``max_retries``.
  Co-tenants never see truncated results: an overflowed step's outputs are
  discarded wholesale (``FrontierPipeline.step(raise_on_overflow=False)``).
* *Deadline supervision* — per-query tick budgets plus an EWMA wall-clock
  straggler deadline (``ft.supervisor.StragglerClock`` over completed-query
  durations): a pathological query degrades to loud cancellation, never a
  hung engine.  ``run_to_completion`` raises ``TimeoutError`` naming the
  stuck query ids instead of returning silently.
* *Fault injection* — a ``ft.failures.QueryFaultPlan`` scripts forced
  overflows, poisoned source ids (rejected at admission, never expanded),
  mid-flight cancellations and attributed stalls; tests drive the engine
  through each and assert surviving queries stay bit-identical to their
  solo ``FrontierPipeline`` runs.

Determinism note: ``min``-family results are bit-identical to solo runs in
every reorder mode and under both the fused and split datapaths (min is
merge-grouping independent — equal indices share a tag, so the tagged fold
applies the identical min over the identical lane set).  ``add``-family
(PPR) results are bit-identical in single-device ``baseline`` mode (the
composite scatter accumulates each replica's lanes in the same relative
order as the solo run, and the fused tagged scatter preserves that order —
min lanes drop out of the add pass without reordering it); under ``hash``
reorder or shard-partitioned execution the merge grouping depends on
co-tenant occupancy / shard boundaries, so sums may reassociate within fp
tolerance — the same caveat as hardware fp atomics.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.bfs import BFS_APP, UNVISITED
from repro.apps.ppr import ppr_app
from repro.apps.sssp import SSSP_APP
from repro.core.iru import IRUConfig
from repro.core.pipeline import (CapacityPolicy, FrontierApp,
                                 FrontierPipeline, StepResult, frontier_step)
from repro.dist.graph_partition import AXIS as _AXIS
from repro.ft.failures import QueryFaultInjector, QueryFaultPlan
from repro.ft.supervisor import StragglerClock, backoff_delay
from repro.graphs.csr import (CSRGraph, GraphView, PartitionedGraphView,
                              frontier_degree_sum, tile_csr)


class AdmissionError(RuntimeError):
    """Query can never be admitted (invalid or over-capacity solo)."""


class QueueFullError(AdmissionError):
    """Bounded wait queue overflow — shed load upstream."""


@dataclasses.dataclass(frozen=True)
class _KindSpec:
    family: str        # "min" | "add"
    unit_weight: bool  # min family: traverse with unit edge weights (BFS)


KINDS = {
    "bfs": _KindSpec("min", True),
    "sssp": _KindSpec("min", False),
    "ppr": _KindSpec("add", False),
}


@dataclasses.dataclass
class GraphQuery:
    """One tenant's traversal query (the graph analogue of ``Request``)."""

    kind: str                 # "bfs" | "sssp" | "ppr"
    source: int
    iters: int = 20           # ppr power iterations
    damping: float = 0.85     # ppr damping
    tick_budget: Optional[int] = None  # per-query deadline in engine ticks
    # filled by the engine
    qid: int = -1
    status: str = "new"       # queued|running|quarantined|done|rejected|
    #                           cancelled|failed
    result: Optional[np.ndarray] = None
    error: Optional[str] = None
    slot: int = -1
    ticks: int = 0            # batched + solo steps consumed
    retries: int = 0          # quarantine retry attempts
    admitted_tick: int = -1
    admitted_time: float = 0.0

    @property
    def done(self) -> bool:
        return self.status == "done"


@dataclasses.dataclass(frozen=True)
class GraphServeConfig:
    """Engine knobs (capacity ladder sized GraphCage-style: buckets are the
    cache/VMEM-sized working sets the merged frontier is dispatched to)."""

    query_slots: int = 8
    max_queue: int = 64
    fused: bool = True                   # tagged-lane fused datapath (one
    #                                      compiled step advances BOTH merge
    #                                      families); False = split engine
    mode: str = "baseline"               # reorder stage: baseline|sort|hash
    iru_config: Optional[IRUConfig] = None
    gather: str = "xla"
    ragged: bool = True                  # occupancy-aware steps; False pins
    #                                      padded execution (benchmark leg)
    edge_capacity: Optional[int] = None  # serving edge budget per family
    #                                      step; None = query_slots * n_edges
    capacity_policy: CapacityPolicy = CapacityPolicy(
        n_buckets=4, min_capacity=4096, growth=8)
    default_tick_budget: int = 10_000
    max_retries: int = 3
    backoff_base_s: float = 0.01
    straggler_factor: float = 10.0
    straggler_min_s: float = 30.0        # deadline floor (generous default)
    ewma: float = 0.9


# ---------------------------------------------------------------------------
# composite (multi-query) frontier apps
# ---------------------------------------------------------------------------

def _min_family_app(Q: int, n: int) -> FrontierApp:
    """BFS+SSSP composite app over the Q-replica graph: f32 distances with a
    per-slot unit-weight flag (BFS lanes relax with weight 1.0)."""

    def init(graph: CSRGraph, source: int):
        dist = jnp.full((Q * n,), jnp.inf, jnp.float32).at[source].set(0.0)
        mask = jnp.zeros((Q * n,), jnp.bool_).at[source].set(True)
        return {"dist": dist, "unit": jnp.zeros((Q,), jnp.bool_)}, mask

    def candidate(state, graph: CSRGraph, ef):
        srcs = jnp.clip(ef.srcs, 0, Q * n - 1)  # padding lanes carry Q*n
        w = jnp.where(state["unit"][srcs // n], jnp.float32(1.0), ef.weights)
        return state["dist"][srcs] + w

    def update(state, new_dist, graph: CSRGraph):
        mask = new_dist < state["dist"]
        return {"dist": new_dist, "unit": state["unit"]}, mask

    return FrontierApp(
        name="mq_min", filter_op="min", target="dist",
        init=init, candidate=candidate, update=update,
        cond=lambda state, mask: jnp.any(mask),
        result=lambda state: state["dist"],
        atomic=True, needs_weights=True)


def _add_family_app(Q: int, n: int) -> FrontierApp:
    """PPR composite app: per-slot personalized teleport/restart, all-nodes
    frontier on live slots, merged fp-add contribution scatter."""

    def init(graph: CSRGraph, source: int):
        zeros = jnp.zeros((Q * n,), jnp.float32)
        state = {"rank": zeros, "src": zeros,
                 "acc": zeros,
                 "live": jnp.zeros((Q,), jnp.bool_),
                 "damp": jnp.zeros((Q,), jnp.float32)}
        return state, jnp.zeros((Q * n,), jnp.bool_)

    def candidate(state, graph: CSRGraph, ef):
        deg = jnp.maximum(graph.degrees(), 1).astype(jnp.float32)
        return (state["rank"] / deg)[ef.srcs]

    def update(state, acc, graph: CSRGraph):
        live_row = jnp.repeat(state["live"], n)
        d = jnp.repeat(state["damp"], n)
        dangling = graph.degrees() == 0
        leak = jnp.repeat(jnp.sum(
            jnp.where(dangling, state["rank"], 0.0).reshape(Q, n), axis=1), n)
        new_rank = ((1 - d) * state["src"] + d * acc
                    + d * leak * state["src"]).astype(jnp.float32)
        rank = jnp.where(live_row, new_rank, state["rank"])
        state = {"rank": rank, "src": state["src"],
                 "acc": jnp.zeros_like(acc),
                 "live": state["live"], "damp": state["damp"]}
        return state, live_row

    return FrontierApp(
        name="mq_add", filter_op="add", target="acc",
        init=init, candidate=candidate, update=update,
        cond=lambda state, mask: jnp.any(mask),
        result=lambda state: state["rank"],
        atomic=True)


def _fused_family_app(Q: int, n: int) -> FrontierApp:
    """Both merge families in ONE tagged composite app.

    Per-slot ``tag`` (False = min family, True = add) makes the tag a pure
    function of the composite node id (``tag[id // n]``) — the tag-table
    contract of the fused datapath: equal indices share a tag, every
    duplicate run is uniform-tag, and the reorder/merge/scatter stages fold
    each lane under its own family in one pass.

    One state array does double duty: ``val`` is the min family's distance
    AND the add family's rank; ``tgt`` is the shared scatter target — min
    rows mirror ``val`` (the ``.min`` fold relaxes in place, exactly the
    split app's contract) while add rows reset to 0 each step (a fresh
    accumulator, exactly the split app's ``acc``).  ``update`` commits each
    family's rows from the same merged target and re-establishes the
    invariant.
    """

    def init(graph: CSRGraph, source: int):
        inf = jnp.full((Q * n,), jnp.inf, jnp.float32)
        state = {"val": inf, "tgt": inf,
                 "src": jnp.zeros((Q * n,), jnp.float32),
                 "tag": jnp.zeros((Q,), jnp.bool_),
                 "unit": jnp.zeros((Q,), jnp.bool_),
                 "live": jnp.zeros((Q,), jnp.bool_),
                 "damp": jnp.zeros((Q,), jnp.float32)}
        return state, jnp.zeros((Q * n,), jnp.bool_)

    def tag_table(state, graph: CSRGraph):
        # bool[Q*n + 1]: tag per composite id; the expansion's padding
        # sentinel (== Q*n) maps to False (min) per the datapath contract
        return jnp.concatenate(
            [jnp.repeat(state["tag"], n), jnp.zeros((1,), jnp.bool_)])

    def candidate(state, graph: CSRGraph, ef):
        srcs = jnp.clip(ef.srcs, 0, Q * n - 1)  # padding lanes carry Q*n
        row = srcs // n
        trow = state["tag"][row]
        w = jnp.where(state["unit"][row], jnp.float32(1.0), ef.weights)
        deg = jnp.maximum(graph.degrees(), 1).astype(jnp.float32)
        return jnp.where(trow, (state["val"] / deg)[srcs],
                         state["val"][srcs] + w)

    def update(state, new_tgt, graph: CSRGraph):
        trow = jnp.repeat(state["tag"], n)
        live_row = jnp.repeat(state["live"], n)
        d = jnp.repeat(state["damp"], n)
        dangling = graph.degrees() == 0
        # per-slot dangling mass (min rows' sums are garbage — inf dist —
        # but feed only their own rows' discarded new_rank lanes)
        leak = jnp.repeat(jnp.sum(
            jnp.where(dangling, state["val"], 0.0).reshape(Q, n), axis=1), n)
        new_rank = ((1 - d) * state["src"] + d * new_tgt
                    + d * leak * state["src"]).astype(jnp.float32)
        val = jnp.where(trow, jnp.where(live_row, new_rank, state["val"]),
                        new_tgt)
        mask = jnp.where(trow, live_row, new_tgt < state["val"])
        state = {"val": val, "tgt": jnp.where(trow, 0.0, val),
                 "src": state["src"], "tag": state["tag"],
                 "unit": state["unit"], "live": state["live"],
                 "damp": state["damp"]}
        return state, mask

    return FrontierApp(
        name="mq_fused", filter_op="tagged", target="tgt",
        init=init, candidate=candidate, update=update,
        cond=lambda state, mask: jnp.any(mask),
        result=lambda state: state["val"],
        atomic=True, needs_weights=True, tag_table=tag_table)


# ---------------------------------------------------------------------------
# shard_map-partitioned fused runtime
# ---------------------------------------------------------------------------

def _partitioned_fused_app(Q: int) -> FrontierApp:
    """The fused composite app restated over ONE shard's local node space.

    Local geometry rides in the state itself: ``slot`` (int32[local_nodes],
    slot index of each local node — owned AND ghost; padding rows carry Q)
    and ``own`` (bool[local_nodes], owned REAL composite lanes).  Per-slot
    scalars are replicated across shards.  The PPR dangling leak is a
    per-slot ``segment_sum`` over owned lanes ``psum``-ed across shards —
    the partition-aware restatement of the single-device per-row reduction.
    """

    def init(graph, source):
        raise TypeError(
            "partitioned fused app: state is laid out by the runtime")

    def _tag1(state):
        return jnp.concatenate(
            [state["tag"], jnp.zeros((1,), jnp.bool_)])

    def tag_table(state, graph: CSRGraph):
        # bool[local_nodes + 1]: family per LOCAL node (ghosts carry their
        # composite id's family); trailing entry = the padding sentinel
        return jnp.concatenate([_tag1(state)[state["slot"]],
                                jnp.zeros((1,), jnp.bool_)])

    def candidate(state, graph: CSRGraph, ef):
        ln = state["slot"].shape[0]
        srcs = jnp.clip(ef.srcs, 0, ln - 1)
        slot_row = state["slot"][srcs]
        unit1 = jnp.concatenate(
            [state["unit"], jnp.zeros((1,), jnp.bool_)])
        trow = _tag1(state)[slot_row]
        w = jnp.where(unit1[slot_row], jnp.float32(1.0), ef.weights)
        deg = jnp.maximum(graph.degrees(), 1).astype(jnp.float32)
        return jnp.where(trow, (state["val"] / deg)[srcs],
                         state["val"][srcs] + w)

    def update(state, new_tgt, graph: CSRGraph):
        slot, own = state["slot"], state["own"]
        trow = _tag1(state)[slot]
        live1 = jnp.concatenate(
            [state["live"], jnp.zeros((1,), jnp.bool_)])
        damp1 = jnp.concatenate(
            [state["damp"], jnp.zeros((1,), jnp.float32)])
        live_row = live1[slot] & own
        d = damp1[slot]
        # owned degrees equal global degrees (a shard owns all its block's
        # out-edges), so the dangling test is exact on owned lanes
        dangling = own & (graph.degrees() == 0)
        leak_q = jax.ops.segment_sum(
            jnp.where(dangling, state["val"], 0.0), slot,
            num_segments=Q + 1)[:Q]
        leak_q = jax.lax.psum(leak_q, _AXIS)
        leak = jnp.concatenate([leak_q, jnp.zeros((1,), jnp.float32)])[slot]
        new_rank = ((1 - d) * state["src"] + d * new_tgt
                    + d * leak * state["src"]).astype(jnp.float32)
        val = jnp.where(trow, jnp.where(live_row, new_rank, state["val"]),
                        new_tgt)
        mask = jnp.where(trow, live_row, new_tgt < state["val"])
        state = {"val": val, "tgt": jnp.where(trow, 0.0, val),
                 "src": state["src"], "tag": state["tag"],
                 "unit": state["unit"], "live": state["live"],
                 "damp": state["damp"], "slot": slot, "own": own}
        return state, mask

    return FrontierApp(
        name="mq_fused_part", filter_op="tagged", target="tgt",
        init=init, candidate=candidate, update=update,
        cond=lambda state, mask: jnp.any(mask),
        result=lambda state: state["val"],
        atomic=True, needs_weights=True, tag_table=tag_table)


class _PartitionedFusedRuntime:
    """Duck-typed ``FrontierPipeline`` twin: the fused tick, shard_map-
    partitioned over a ``PartitionedGraphView``.

    The engine keeps its fused state in the GLOBAL single-device layout
    (placement, eviction, extraction, load prediction are untouched); this
    runtime relays global ↔ stacked per step: scatter the global arrays
    onto the per-shard local node spaces (owned block + ghost slots at
    their per-family identities), run one ``frontier_step`` per shard with
    the tagged boundary exchange spliced in (exact codec — the fused
    parity contract), and gather the owned blocks back.  Step executables
    are NON-donating: the engine re-dispatches unchanged inputs rung by
    rung and discards overflowed outputs wholesale.
    """

    def __init__(self, pview: PartitionedGraphView, app: FrontierApp, *,
                 mode: str, iru_config: Optional[IRUConfig], gather: str,
                 capacity_policy: Optional[CapacityPolicy],
                 ragged: bool = True):
        import functools

        from jax.sharding import PartitionSpec
        from repro.launch.mesh import make_graph_mesh

        part = pview.part
        self.part = part
        self.Q, self.n = pview.n_tenants, pview.base_nodes
        self.app = _partitioned_fused_app(self.Q)
        self.mesh = make_graph_mesh(part.n_parts)
        if mode == "baseline":
            self.iru_config = None
        else:
            self.iru_config = dataclasses.replace(
                iru_config or IRUConfig(), mode=mode, filter_op="tagged")
        self.gather = gather
        self.ragged = ragged
        self.capacity_policy = capacity_policy or CapacityPolicy()
        # per-shard rungs over the LOCAL capacities; the top rung holds any
        # shard's full edge set, so prediction-dispatched steps never
        # overflow at the top
        self.buckets = self.capacity_policy.ladder(
            max(part.edge_cap, 1), part.local_nodes)

        # host-built id-space maps ([P, local_nodes]): global composite id,
        # slot index (ghosts carry theirs; padding -> Q), owned-real mask
        P_, block, ln = part.n_parts, part.block, part.local_nodes
        Qn = self.Q * self.n
        gid = np.full((P_, ln), -1, np.int64)
        for p in range(P_):
            owned = np.arange(block, dtype=np.int64) + p * block
            gid[p, :block] = np.where(owned < Qn, owned, -1)
            gid[p, block:] = np.asarray(part.ghost_ids[p], np.int64)
        slot = np.where(gid >= 0, gid // max(self.n, 1), self.Q)
        own = np.zeros((P_, ln), bool)
        own[:, :block] = gid[:, :block] >= 0
        self._gid = jnp.asarray(np.clip(gid, 0, max(Qn - 1, 0)), jnp.int32)
        self._slot = jnp.asarray(slot, jnp.int32)
        self._own = jnp.asarray(own)

        spec = PartitionSpec(_AXIS)
        rep = PartitionSpec()
        self._step_b = tuple(
            jax.jit(jax.shard_map(
                functools.partial(self._superstep, bucket=b),
                mesh=self.mesh, in_specs=(spec, spec, spec),
                out_specs=(spec, spec, rep), check_vma=False))
            for b in range(len(self.buckets)))
        self._predict = jax.jit(jax.shard_map(
            self._predict_impl, mesh=self.mesh, in_specs=(spec, spec),
            out_specs=(rep, rep), check_vma=False))
        self._to_stacked = jax.jit(self._to_stacked_impl)
        self._from_stacked = jax.jit(self._from_stacked_impl)

    # -- global <-> stacked relayout ---------------------------------------
    def _to_stacked_impl(self, state_g, mask_g):
        gid, own, slot = self._gid, self._own, self._slot
        tag1 = jnp.concatenate(
            [state_g["tag"], jnp.zeros((1,), jnp.bool_)])
        ident = jnp.where(tag1[slot], jnp.float32(0.0), jnp.inf)
        P_ = own.shape[0]
        rep = lambda a: jnp.broadcast_to(a[None], (P_,) + a.shape)
        state = {"val": jnp.where(own, state_g["val"][gid], jnp.inf),
                 "tgt": jnp.where(own, state_g["tgt"][gid], ident),
                 "src": jnp.where(own, state_g["src"][gid], 0.0),
                 "tag": rep(state_g["tag"]), "unit": rep(state_g["unit"]),
                 "live": rep(state_g["live"]), "damp": rep(state_g["damp"]),
                 "slot": slot, "own": own}
        return state, own & mask_g[gid]

    def _from_stacked_impl(self, state_st, mask_st):
        Qn, block = self.Q * self.n, self.part.block
        take = lambda a: a[:, :block].reshape(-1)[:Qn]
        state = {"val": take(state_st["val"]), "tgt": take(state_st["tgt"]),
                 "src": take(state_st["src"]), "tag": state_st["tag"][0],
                 "unit": state_st["unit"][0], "live": state_st["live"][0],
                 "damp": state_st["damp"][0]}
        return state, take(mask_st)

    # -- compiled bodies (run per shard inside shard_map) ------------------
    def _local_graph(self, part) -> CSRGraph:
        return CSRGraph(row_ptr=part.row_ptr[0], col_idx=part.col_idx[0],
                        weights=part.weights[0])

    def _predict_impl(self, part, mask):
        g = self._local_graph(part)
        m = mask[0]
        return (jax.lax.pmax(frontier_degree_sum(g, m), _AXIS),
                jax.lax.pmax(jnp.sum(m.astype(jnp.int32)), _AXIS))

    def _superstep(self, part, state, mask, *, bucket: int):
        from repro.dist.graph_partition import _boundary_exchange

        g = self._local_graph(part)
        state = jax.tree.map(lambda a: a[0], state)
        mask = mask[0]
        e_cap, f_cap = self.buckets[bucket]

        exchange = None
        if self.part.n_parts > 1 and self.part.lane_cap > 0:
            def exchange(new_target, st):
                tag1 = jnp.concatenate(
                    [st["tag"], jnp.zeros((1,), jnp.bool_)])
                out, _, _ = _boundary_exchange(
                    new_target, jnp.float32(0.0),
                    send_slot=part.send_slot[0], send_mask=part.send_mask[0],
                    recv_id=part.recv_id[0], recv_mask=part.recv_mask[0],
                    block=self.part.block, op="tagged", codec="exact",
                    payload=None, tags=tag1[st["slot"]])
                return out

        state, mask, _, _, _, _, overflow = frontier_step(
            g, self.app, state, mask, e_cap=e_cap, f_cap=f_cap,
            iru_config=self.iru_config, gather=self.gather,
            ragged=self.ragged, exchange=exchange)
        ovf = jax.lax.psum(overflow.astype(jnp.int32), _AXIS)
        ex = lambda t: jax.tree.map(lambda a: a[None], t)
        return ex(state), mask[None], ovf

    # -- the host-dispatched step (the engine's pipe.step contract) --------
    def _host_bucket(self, need: int, count: int) -> int:
        for i, (e_cap, f_cap) in enumerate(self.buckets):
            if need <= e_cap and count <= f_cap:
                return i
        return len(self.buckets) - 1

    def step(self, state, mask, *, raise_on_overflow: bool = True
             ) -> StepResult:
        st, mk = self._to_stacked(state, mask)
        if len(self.buckets) > 1:
            need, count = self._predict(self.part, mk)
            b = self._host_bucket(int(need), int(count))
        else:
            b = 0
        none = jnp.zeros((0,), jnp.int32)
        while True:
            out_state, out_mask, ovf = self._step_b[b](self.part, st, mk)
            if not int(ovf):
                gs, gm = self._from_stacked(out_state, out_mask)
                return StepResult(gs, gm, none, none, none, jnp.int32(0),
                                  False, b)
            if b == len(self.buckets) - 1:
                if raise_on_overflow:
                    raise RuntimeError(
                        "partitioned fused step overflowed the top bucket "
                        f"{self.buckets[b]} — raise edge capacities")
                return StepResult(state, mask, none, none, none,
                                  jnp.int32(0), True, b)
            b += 1


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class GraphServingEngine:
    def __init__(
        self,
        graph,
        config: Optional[GraphServeConfig] = None,
        *,
        fault_plan: Optional[QueryFaultPlan] = None,
    ):
        self.cfg = cfg = config or GraphServeConfig()
        if cfg.query_slots < 1:
            raise ValueError(f"query_slots must be >= 1, got {cfg.query_slots}")
        # ``graph`` is a plain CSRGraph (tiled here), a pre-composed
        # GraphView, or a PartitionedGraphView (sharded multi-tenant
        # composite — runs the fused tick shard_map-partitioned)
        self.part_view: Optional[PartitionedGraphView] = None
        view: Optional[GraphView] = None
        if isinstance(graph, PartitionedGraphView):
            if not cfg.fused:
                raise ValueError(
                    "PartitionedGraphView serving requires fused=True "
                    "(the split per-family engine is single-device only)")
            self.part_view = graph
            view = graph.view
        elif isinstance(graph, GraphView):
            view = graph
        if view is not None:
            if view.n_tenants != cfg.query_slots:
                raise ValueError(
                    f"composed view has n_tenants={view.n_tenants} but the "
                    f"engine leases query_slots={cfg.query_slots} lanes — "
                    f"tile with tile_csr(g, {cfg.query_slots})")
            base = view.base
        else:
            base = graph
        self.graph = base
        self.Q, self.n, self.m = cfg.query_slots, base.n_nodes, base.n_edges
        self.cgraph = view if view is not None else tile_csr(base, self.Q)
        self.injector = (QueryFaultInjector(fault_plan)
                         if fault_plan is not None else None)
        self.queue: deque[GraphQuery] = deque()
        self.slots: list[Optional[GraphQuery]] = [None] * self.Q
        self.quarantined: list[tuple[GraphQuery, float]] = []  # (q, retry_at)
        self.completed: list[GraphQuery] = []
        self.tick_no = 0
        self.clock = StragglerClock(cfg.straggler_factor, cfg.ewma)
        self._next_qid = 0
        # telemetry
        self.overflow_events = 0
        self.quarantines = 0
        self.admission_blocked = 0
        # family runtimes (composite pipelines share one edge budget each)
        self._edge_budget = (cfg.edge_capacity if cfg.edge_capacity is not None
                             else self.Q * self.m)
        Q, n = self.Q, self.n
        self._pipes: dict[str, FrontierPipeline] = {}
        self._states: dict[str, dict] = {}
        self._masks: dict[str, jax.Array] = {}
        self._apps = {"min": _min_family_app(Q, n),
                      "add": _add_family_app(Q, n)}
        deg_dev = base.degrees()
        self._needs_fn = jax.jit(lambda mask: jnp.sum(jnp.where(
            mask.reshape(Q, n), deg_dev[None, :], 0), axis=1))
        self._solo_pipes: dict[tuple, FrontierPipeline] = {}
        # fused-datapath state (one composite state for BOTH families)
        self._fstate: Optional[dict] = None
        self._fmask: Optional[jax.Array] = None

    # -- family runtimes (built lazily: a BFS/SSSP-only workload never
    #    compiles the add family and vice versa) ---------------------------
    def _family(self, fam: str) -> FrontierPipeline:
        if fam not in self._pipes:
            cfg = self.cfg
            self._pipes[fam] = FrontierPipeline(
                self.cgraph, self._apps[fam], mode=cfg.mode,
                iru_config=cfg.iru_config, gather=cfg.gather,
                edge_capacity=self._edge_budget,
                capacity_policy=cfg.capacity_policy, ragged=cfg.ragged)
            state, mask = self._apps[fam].init(self.cgraph, 0)
            if fam == "min":  # init seeds composite node 0; engine owns lanes
                state = {"dist": jnp.full((self.Q * self.n,), jnp.inf,
                                          jnp.float32),
                         "unit": state["unit"]}
                mask = jnp.zeros_like(mask)
            self._states[fam] = state
            self._masks[fam] = mask
        return self._pipes[fam]

    def _fused_pipe(self):
        """The single tagged-datapath runtime (lazily built, shared by both
        families): a ``FrontierPipeline`` over the composite view, or the
        shard_map-partitioned twin when serving a ``PartitionedGraphView``.
        Registered in ``_pipes`` so executable-reuse assertions see it."""
        if "fused" not in self._pipes:
            cfg = self.cfg
            app = _fused_family_app(self.Q, self.n)
            if self.part_view is not None:
                pipe = _PartitionedFusedRuntime(
                    self.part_view, app, mode=cfg.mode,
                    iru_config=cfg.iru_config, gather=cfg.gather,
                    capacity_policy=cfg.capacity_policy, ragged=cfg.ragged)
            else:
                pipe = FrontierPipeline(
                    self.cgraph, app, mode=cfg.mode,
                    iru_config=cfg.iru_config, gather=cfg.gather,
                    edge_capacity=self._edge_budget,
                    capacity_policy=cfg.capacity_policy, ragged=cfg.ragged)
            self._pipes["fused"] = pipe
            self._fstate, self._fmask = app.init(self.cgraph, 0)
        return self._pipes["fused"]

    def _family_top_cap(self, fam: str) -> int:
        if self.cfg.fused:
            # one shared edge budget gates both families (always the top
            # rung of the fused ladder; the partitioned runtime's rungs are
            # per-shard, so the GLOBAL budget is the correct gate there)
            return self._edge_budget
        return self._family(fam).buckets[-1][0]

    # -- submission / admission -------------------------------------------
    def _initial_need(self, kind: str, source: int) -> int:
        if KINDS[kind].family == "add":
            return self.m  # all-nodes frontier: every replica edge, always
        return int(frontier_degree_sum(
            self.graph, jnp.asarray([source], jnp.int32)))

    def submit(self, query: GraphQuery) -> int:
        """Queue a query; loud rejection when it can never be served."""
        if query.kind not in KINDS:
            raise AdmissionError(
                f"unknown query kind {query.kind!r}; have {sorted(KINDS)}")
        if not (0 <= query.source < self.n):
            raise AdmissionError(
                f"source id {query.source} outside [0, {self.n})")
        need = self._initial_need(query.kind, query.source)
        top = self._family_top_cap(KINDS[query.kind].family)
        if need > top:
            raise AdmissionError(
                f"query (kind={query.kind}, source={query.source}) needs "
                f"{need} edge lanes solo but the top "
                f"{KINDS[query.kind].family}-family bucket holds {top}: "
                f"raise edge_capacity")
        if len(self.queue) >= self.cfg.max_queue:
            raise QueueFullError(
                f"wait queue full ({self.cfg.max_queue} queries): shed load")
        query.qid = self._next_qid
        self._next_qid += 1
        query.status = "queued"
        self.queue.append(query)
        return query.qid

    def _running(self, fam: Optional[str] = None) -> list[GraphQuery]:
        return [q for q in self.slots if q is not None
                and (fam is None or KINDS[q.kind].family == fam)]

    def _family_load(self, fam: str) -> np.ndarray:
        """Per-slot predicted next-step edge-lane contribution."""
        if self.cfg.fused:
            if self._fmask is None or not self._running(fam):
                return np.zeros(self.Q, np.int64)
            per_slot = np.asarray(self._needs_fn(self._fmask), np.int64)
            needs = np.zeros(self.Q, np.int64)
            for q in self._running(fam):
                needs[q.slot] = per_slot[q.slot]
            return needs
        if fam == "add":
            needs = np.zeros(self.Q, np.int64)
            for q in self._running("add"):
                needs[q.slot] = self.m
            return needs
        if "min" not in self._pipes or not self._running("min"):
            return np.zeros(self.Q, np.int64)
        return np.asarray(self._needs_fn(self._masks["min"]), np.int64)

    def _admit(self) -> None:
        """FIFO admission under the capacity gate (head-of-line order keeps
        starvation impossible; a blocked head blocks the queue, counted)."""
        while self.queue:
            free = [s for s, q in enumerate(self.slots) if q is None]
            if not free:
                break
            query = self.queue[0]
            src = query.source
            if self.injector is not None:
                src = self.injector.admitted_source(query.qid, src)
            if not (0 <= src < self.n):
                # poisoned in flight: reject loudly, never expand it
                self.queue.popleft()
                query.status = "rejected"
                query.error = (f"poisoned source id {src} detected at "
                               f"admission (query {query.qid})")
                self.completed.append(query)
                continue
            fam = KINDS[query.kind].family
            need = self._initial_need(query.kind, src)
            load = int(self._family_load(fam).sum())
            if load + need > self._family_top_cap(fam):
                self.admission_blocked += 1
                break  # cannot join yet: wait for tenants to shrink/retire
            self.queue.popleft()
            self._place(query, src, free[0])

    def _place(self, query: GraphQuery, src: int, slot: int) -> None:
        n, fam = self.n, KINDS[query.kind].family
        lo = slot * n
        if self.cfg.fused:
            self._fused_pipe()  # ensure runtime + fused state exist
            st = self._fstate
            if fam == "min":
                val = st["val"].at[lo:lo + n].set(jnp.inf).at[lo + src].set(0.0)
                self._fstate = {
                    "val": val,
                    "tgt": st["tgt"].at[lo:lo + n].set(
                        jnp.inf).at[lo + src].set(0.0),
                    "src": st["src"].at[lo:lo + n].set(0.0),
                    "tag": st["tag"].at[slot].set(False),
                    "unit": st["unit"].at[slot].set(
                        KINDS[query.kind].unit_weight),
                    "live": st["live"].at[slot].set(False),
                    "damp": st["damp"].at[slot].set(0.0)}
                self._fmask = (self._fmask.at[lo:lo + n].set(False)
                               .at[lo + src].set(True))
            else:
                row = jnp.zeros((n,), jnp.float32).at[src].set(1.0)
                self._fstate = {
                    "val": st["val"].at[lo:lo + n].set(row),
                    "tgt": st["tgt"].at[lo:lo + n].set(0.0),
                    "src": st["src"].at[lo:lo + n].set(row),
                    "tag": st["tag"].at[slot].set(True),
                    "unit": st["unit"].at[slot].set(False),
                    "live": st["live"].at[slot].set(True),
                    "damp": st["damp"].at[slot].set(query.damping)}
                self._fmask = self._fmask.at[lo:lo + n].set(True)
            query.slot = slot
            query.status = "running"
            query.ticks = 0
            query.admitted_tick = self.tick_no
            query.admitted_time = time.monotonic()
            self.slots[slot] = query
            return
        self._family(fam)  # ensure runtime exists
        if fam == "min":
            st = self._states["min"]
            dist = st["dist"].at[lo:lo + n].set(jnp.inf).at[lo + src].set(0.0)
            unit = st["unit"].at[slot].set(KINDS[query.kind].unit_weight)
            self._states["min"] = {"dist": dist, "unit": unit}
            self._masks["min"] = (self._masks["min"]
                                  .at[lo:lo + n].set(False)
                                  .at[lo + src].set(True))
        else:
            st = self._states["add"]
            row = jnp.zeros((n,), jnp.float32).at[src].set(1.0)
            self._states["add"] = {
                "rank": st["rank"].at[lo:lo + n].set(row),
                "src": st["src"].at[lo:lo + n].set(row),
                "acc": st["acc"],
                "live": st["live"].at[slot].set(True),
                "damp": st["damp"].at[slot].set(query.damping)}
            self._masks["add"] = self._masks["add"].at[lo:lo + n].set(True)
        query.slot = slot
        query.status = "running"
        query.ticks = 0
        query.admitted_tick = self.tick_no
        query.admitted_time = time.monotonic()
        self.slots[slot] = query

    def _clear_lane(self, query: GraphQuery) -> None:
        n, lo, fam = self.n, query.slot * self.n, KINDS[query.kind].family
        if self.cfg.fused:
            # an empty lane is an idle min row: +inf val/tgt, no frontier
            st = self._fstate
            self._fstate = {
                "val": st["val"].at[lo:lo + n].set(jnp.inf),
                "tgt": st["tgt"].at[lo:lo + n].set(jnp.inf),
                "src": st["src"].at[lo:lo + n].set(0.0),
                "tag": st["tag"].at[query.slot].set(False),
                "unit": st["unit"].at[query.slot].set(False),
                "live": st["live"].at[query.slot].set(False),
                "damp": st["damp"].at[query.slot].set(0.0)}
            self._fmask = self._fmask.at[lo:lo + n].set(False)
            self.slots[query.slot] = None
            query.slot = -1
            return
        if fam == "min":
            st = self._states["min"]
            self._states["min"] = {
                "dist": st["dist"].at[lo:lo + n].set(jnp.inf),
                "unit": st["unit"]}
            self._masks["min"] = self._masks["min"].at[lo:lo + n].set(False)
        else:
            st = self._states["add"]
            zeros = jnp.zeros((n,), jnp.float32)
            self._states["add"] = {
                "rank": st["rank"].at[lo:lo + n].set(zeros),
                "src": st["src"].at[lo:lo + n].set(zeros),
                "acc": st["acc"],
                "live": st["live"].at[query.slot].set(False),
                "damp": st["damp"]}
            self._masks["add"] = self._masks["add"].at[lo:lo + n].set(False)
        self.slots[query.slot] = None
        query.slot = -1

    # -- results -----------------------------------------------------------
    def _extract(self, query: GraphQuery, state) -> np.ndarray:
        n, lo = self.n, query.slot * self.n
        fam = KINDS[query.kind].family
        if self.cfg.fused:
            row = np.asarray(state["val"][lo:lo + n])
        else:
            key = "rank" if fam == "add" else "dist"
            row = np.asarray(state[key][lo:lo + n])
        if fam == "add" or query.kind == "sssp":
            return row
        lab = np.full(n, UNVISITED, np.int32)
        fin = np.isfinite(row)
        lab[fin] = row[fin].astype(np.int32)
        return lab

    def _finish(self, query: GraphQuery, result: np.ndarray) -> None:
        query.result = result
        query.status = "done"
        if query.slot >= 0:
            self._clear_lane(query)
        self.clock.observe(time.monotonic() - query.admitted_time)
        self.completed.append(query)

    def _cancel(self, query: GraphQuery, reason: str) -> None:
        query.status = "cancelled"
        query.error = reason
        if query.slot >= 0:
            self._clear_lane(query)
        self.completed.append(query)

    # -- overflow quarantine ----------------------------------------------
    def _quarantine_victim(self, fam: Optional[str],
                           needs: np.ndarray) -> GraphQuery:
        running = self._running(fam)
        # largest predicted contribution; ties break to the newest tenant
        # (evicting the latecomer is the least disruptive choice)
        return max(running,
                   key=lambda q: (int(needs[q.slot]), q.admitted_tick))

    def _quarantine(self, query: GraphQuery, why: str) -> None:
        self.quarantines += 1
        query.retries += 1
        self._clear_lane(query)
        if query.retries > self.cfg.max_retries:
            query.status = "failed"
            query.error = (f"query {query.qid} exhausted {self.cfg.max_retries}"
                           f" quarantine retries ({why})")
            self.completed.append(query)
            return
        query.status = "quarantined"
        query.error = why
        retry_at = time.monotonic() + backoff_delay(
            self.cfg.backoff_base_s, query.retries)
        self.quarantined.append((query, retry_at))

    def _solo_pipe(self, query: GraphQuery) -> FrontierPipeline:
        key = ((query.kind,) if KINDS[query.kind].family == "min"
               else (query.kind, query.iters, query.damping))
        if key not in self._solo_pipes:
            app = {"bfs": BFS_APP, "sssp": SSSP_APP}.get(query.kind) \
                or ppr_app(query.iters, query.damping)
            self._solo_pipes[key] = FrontierPipeline(
                self.graph, app, mode=self.cfg.mode,
                iru_config=self.cfg.iru_config, gather=self.cfg.gather,
                capacity_policy=self.cfg.capacity_policy,
                ragged=self.cfg.ragged)
        return self._solo_pipes[key]

    def _retry_solo(self, query: GraphQuery) -> None:
        """Quarantined query degrades to a single-tenant run at full
        base-graph capacity — bit-identical to a solo ``FrontierPipeline``
        run because it IS one, just host-stepped under the tick budget."""
        pipe = self._solo_pipe(query)
        state, mask = pipe.init(query.source)
        budget = query.tick_budget or self.cfg.default_tick_budget
        used = 0
        t0 = time.monotonic()
        while used < budget - query.ticks and bool(
                np.asarray(pipe.app.cond(state, mask))):
            res = pipe.step(state, mask)
            state, mask = res.state, res.mask
            used += 1
        query.ticks += used
        if bool(np.asarray(pipe.app.cond(state, mask))):
            self._quarantine_retry_failed(query, budget)
            return
        query.result = np.asarray(pipe.app.result(state))
        query.status = "done"
        self.clock.observe(time.monotonic() - t0)
        self.completed.append(query)

    def _quarantine_retry_failed(self, query: GraphQuery, budget: int) -> None:
        query.retries += 1
        why = (f"solo retry exceeded the {budget}-tick budget")
        if query.retries > self.cfg.max_retries:
            query.status = "failed"
            query.error = (f"query {query.qid} exhausted "
                           f"{self.cfg.max_retries} quarantine retries "
                           f"({why})")
            self.completed.append(query)
            return
        query.status = "quarantined"
        query.error = why
        self.quarantined.append((query, time.monotonic() + backoff_delay(
            self.cfg.backoff_base_s, query.retries)))

    def _drain_quarantine(self) -> None:
        now = time.monotonic()
        due = [(q, t) for q, t in self.quarantined if t <= now]
        self.quarantined = [(q, t) for q, t in self.quarantined if t > now]
        for q, _ in due:
            self._retry_solo(q)

    # -- the tick ----------------------------------------------------------
    def _fused_tick(self) -> None:
        """One fused step: BOTH families advance in one compiled bucketed
        dispatch.  Gate/quarantine/overflow semantics mirror the split
        ``_family_tick`` with the shared edge budget as the single gate."""
        pipe = self._fused_pipe()
        needs = self._family_load("min") + self._family_load("add")
        top = self._family_top_cap("min")  # shared budget, fam-independent
        forced = (self.injector is not None
                  and self.injector.force_overflow(self.tick_no))
        if forced:
            self.overflow_events += 1
            self._quarantine(
                self._quarantine_victim(None, needs),
                f"injected capacity overflow at tick {self.tick_no}")
            return  # the overflowed step's outputs would have been garbage
        while int(needs.sum()) > top:
            self.overflow_events += 1
            victim = self._quarantine_victim(None, needs)
            self._quarantine(
                victim,
                f"merged frontier degree sum {int(needs.sum())} exceeds the "
                f"serving edge budget {top} at tick {self.tick_no}")
            needs = self._family_load("min") + self._family_load("add")
        if not self._running():
            return
        res = pipe.step(self._fstate, self._fmask, raise_on_overflow=False)
        if bool(res.overflow):
            self.overflow_events += 1
            self._quarantine(
                self._quarantine_victim(None, needs),
                f"step overflow at tick {self.tick_no}")
            return
        self._fstate, self._fmask = res.state, res.mask
        for q in self._running():
            q.ticks += 1
        alive = np.asarray(self._fmask.reshape(self.Q, self.n).any(axis=1))
        for q in self._running("min"):
            if not alive[q.slot]:
                self._finish(q, self._extract(q, self._fstate))
        for q in self._running("add"):
            if q.ticks >= q.iters:
                self._finish(q, self._extract(q, self._fstate))

    def _family_tick(self, fam: str) -> None:
        pipe = self._family(fam)
        needs = self._family_load(fam)
        top = self._family_top_cap(fam)
        forced = (self.injector is not None
                  and self.injector.force_overflow(self.tick_no))
        if forced:
            self.overflow_events += 1
            self._quarantine(
                self._quarantine_victim(fam, needs),
                f"injected capacity overflow at tick {self.tick_no}")
            return  # the overflowed step's outputs would have been garbage
        # pre-dispatch gate: frontiers grow mid-flight; shed the largest
        # tenants until the merged frontier fits the top bucket again
        while int(needs.sum()) > top:
            self.overflow_events += 1
            victim = self._quarantine_victim(fam, needs)
            self._quarantine(
                victim,
                f"merged frontier degree sum {int(needs.sum())} exceeds the "
                f"top bucket capacity {top} at tick {self.tick_no}")
            needs = self._family_load(fam)
        if not self._running(fam):
            return
        res = pipe.step(self._states[fam], self._masks[fam],
                        raise_on_overflow=False)
        if bool(res.overflow):
            # belt-and-braces: the predictor is exact, so this is only
            # reachable through an adversarial graph mutation — still no
            # silent truncation, still no co-tenant poisoning
            self.overflow_events += 1
            self._quarantine(
                self._quarantine_victim(fam, needs),
                f"step overflow at tick {self.tick_no}")
            return
        self._states[fam], self._masks[fam] = res.state, res.mask
        for q in self._running(fam):
            q.ticks += 1
        self._retire(fam)

    def _retire(self, fam: str) -> None:
        state = self._states[fam]
        if fam == "min":
            alive = np.asarray(
                self._masks["min"].reshape(self.Q, self.n).any(axis=1))
            for q in self._running("min"):
                if not alive[q.slot]:
                    self._finish(q, self._extract(q, state))
        else:
            for q in self._running("add"):
                if q.ticks >= q.iters:
                    self._finish(q, self._extract(q, state))

    def _supervise(self) -> None:
        now = time.monotonic()
        deadline = self.clock.deadline(self.cfg.straggler_min_s)
        for q in self._running():
            if self.injector is not None:
                self.injector.stall(q.qid, self.tick_no)
                if self.injector.should_cancel(q.qid, self.tick_no):
                    self._cancel(q, f"cancelled mid-flight at tick "
                                    f"{self.tick_no}")
                    continue
            budget = q.tick_budget or self.cfg.default_tick_budget
            if q.ticks >= budget:
                self._cancel(q, f"tick budget {budget} exhausted")
                continue
            age = time.monotonic() - q.admitted_time
            if deadline is not None and age > deadline:
                self._cancel(
                    q, f"straggler deadline exceeded ({age:.3f}s > "
                       f"{deadline:.3f}s EWMA wall-clock bound)")

    def tick(self) -> int:
        """One engine tick: drain quarantine, admit, one batched step per
        active family, supervise deadlines.  Returns in-flight count."""
        self.tick_no += 1
        self._drain_quarantine()
        self._admit()
        if self.cfg.fused:
            if self._running():
                self._fused_tick()
        else:
            for fam in ("min", "add"):
                if self._running(fam):
                    self._family_tick(fam)
        self._supervise()
        return (sum(q is not None for q in self.slots) + len(self.queue)
                + len(self.quarantined))

    def run_to_completion(self, max_ticks: int = 10_000) -> list[GraphQuery]:
        """Drive until every query resolves; loud on a stuck engine (the
        same contract as ``ServingEngine.run_to_completion``)."""
        for _ in range(max_ticks):
            if self.tick() == 0:
                return self.completed
        stuck = sorted(
            [q.qid for q in self.slots if q is not None]
            + [q.qid for q in self.queue]
            + [q.qid for q, _ in self.quarantined])
        raise TimeoutError(
            f"graph engine exhausted max_ticks={max_ticks} with queries "
            f"still in flight: qids={stuck}")

    # -- convenience -------------------------------------------------------
    def solo_reference(self, query: GraphQuery) -> np.ndarray:
        """The solo ``FrontierPipeline`` result this query's engine result
        must match (the parity oracle the fault tests compare against)."""
        return np.asarray(self._solo_pipe(query).run(query.source))
