"""Compressed Sparse Row graph container (paper §2.1: CSR is the standard
GPGPU graph layout; the IRU consumes its edge frontiers).

Arrays live as jax arrays so apps can jit over them; builders accept numpy.
:func:`expand_frontier` is the device-resident edge-frontier expansion the
``core.pipeline`` runtime drives every iteration: fixed ``edge_capacity``
output shapes (padding lanes carry ``valid=False``) make it legal inside
``lax.while_loop`` — no host round trip, no retracing across iterations.
:func:`frontier_degree_sum` predicts the exact lane count an expansion will
emit (the dispatch reduction of the pipeline's capacity bucketing), and a
truncated expansion reports itself through ``EdgeFrontier.overflow``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CSRGraph:
    row_ptr: jax.Array   # int32[n_nodes + 1]
    col_idx: jax.Array   # int32[n_edges]  (destination node per edge)
    weights: jax.Array   # float32[n_edges]

    @property
    def n_nodes(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def n_edges(self) -> int:
        return self.col_idx.shape[0]

    def degrees(self) -> jax.Array:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    def edge_sources(self) -> jax.Array:
        """int32[n_edges] source node of each edge (expanded row_ptr).

        Pure-jnp (``searchsorted`` over ``row_ptr``), so it is legal
        under ``jit``: edge ``e`` belongs to the last node whose CSR
        range starts at or before ``e`` (degree-0 nodes contribute repeated
        ``row_ptr`` entries and are skipped by ``side="right"``).
        """
        e = jnp.arange(self.n_edges, dtype=self.row_ptr.dtype)
        return (jnp.searchsorted(self.row_ptr, e, side="right") - 1).astype(
            jnp.int32)

    def avg_degree(self) -> float:
        return self.n_edges / max(self.n_nodes, 1)


class EdgeFrontier(NamedTuple):
    """Capacity-padded edge frontier (all arrays ``[edge_capacity]``)."""

    srcs: jax.Array    # int32 source node per edge lane (n_nodes on padding)
    dsts: jax.Array    # int32 destination node per lane (n_nodes on padding)
    eids: jax.Array    # int32 CSR edge offset per lane (padding repeats the
    #                    last real offset, keeping the stream monotone so
    #                    the block-reuse gather's window contract survives)
    valid: jax.Array   # bool  True on real edge lanes
    weights: jax.Array | None = None  # f32 edge weight per lane (on request)
    overflow: jax.Array | None = None  # bool scalar: the frontier's degree
    #                    sum exceeded edge_capacity, so edges were DROPPED —
    #                    the consumer must re-dispatch at a larger capacity
    #                    (what core.pipeline's bucketed dispatch does)
    n_valid: jax.Array | None = None  # int32 scalar: live lane count — the
    #                    real edges occupy lanes [0, n_valid).  CLAMPED to
    #                    the capacity: on overflow it reports the lanes that
    #                    actually exist, never the degree sum that did not
    #                    fit (the ragged engines trust it as a prefix bound).
    #                    Always sum(valid); carried so consumers never pay an
    #                    O(capacity) reduction to recover it.


def frontier_from_mask(mask: jax.Array, *, size: int | None = None) -> jax.Array:
    """Dense frontier mask -> capacity-padded ascending node list.

    Returns int32[size] (default ``n_nodes``); tail lanes past the frontier
    size carry the sentinel ``n_nodes`` (which :func:`expand_frontier`
    expands to nothing).  Ascending order matters: it makes the CSR offsets
    of the expansion monotone, which is what the block-reuse gather kernel
    exploits.

    ``size`` bounds the output — the frontier-compaction knob of the
    capacity-bucketed pipeline (``core.pipeline.CapacityPolicy``): a sparse
    frontier no longer drags ``n_nodes`` lanes through expansion.  Like
    ``jnp.nonzero(size=...)``, a mask with MORE than ``size`` set bits is
    silently truncated; callers shrinking it take on the same obligation as
    :func:`expand_frontier`'s ``edge_capacity`` — bound the popcount
    themselves (the pipeline predicts it per iteration).
    """
    n = mask.shape[0]
    return jnp.nonzero(mask, size=n if size is None else size,
                       fill_value=n)[0].astype(jnp.int32)


def _frontier_counts(
    graph: CSRGraph, frontier: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-node (clipped ids, CSR starts, degree counts) of a node list.

    Out-of-range ids (the ``>= n_nodes`` sentinel of
    :func:`frontier_from_mask`, but also any stray negative id — the banked
    engine's other padding convention) count zero edges.
    """
    n = graph.n_nodes
    f = frontier.astype(jnp.int32)
    in_range = (f >= 0) & (f < n)
    fc = jnp.clip(f, 0, max(n - 1, 0))
    starts = graph.row_ptr[fc]
    counts = jnp.where(in_range, graph.row_ptr[fc + 1] - starts, 0)
    return fc, starts, counts


def frontier_degree_sum(graph: CSRGraph, frontier: jax.Array) -> jax.Array:
    """Exact lane count :func:`expand_frontier` will emit (int32 scalar).

    ``frontier`` is either a dense bool[n_nodes] mask or a padded int32 node
    list (both frontier representations the pipeline carries).  This is the
    cheap device reduction the capacity-bucketed dispatch predicts each
    iteration's working set from — O(F) adds against an O(capacity)
    expansion.
    """
    if frontier.dtype == jnp.bool_:
        return jnp.sum(
            jnp.where(frontier, graph.degrees(), 0)).astype(jnp.int32)
    _, _, counts = _frontier_counts(graph, frontier)
    return jnp.sum(counts).astype(jnp.int32)


def expand_frontier(
    graph: CSRGraph,
    frontier: jax.Array,
    *,
    edge_capacity: int | None = None,
    gather: str = "xla",
    with_weights: bool = False,
) -> EdgeFrontier:
    """Device-resident CSR edge-frontier expansion (fixed output shapes).

    ``frontier`` is int32[F] node ids, padded with sentinels ``>= n_nodes``
    (what :func:`frontier_from_mask` emits).  Each valid node contributes its
    full CSR range; lanes are laid out node-major in frontier order — the
    Gunrock "advance" operator as a shape-stable gather, legal under
    ``jit``/``lax.while_loop``.  Work per lane is the load-balanced-search
    form without the search: each frontier slot marks the first lane of its
    range in the degree prefix sum, and a running max over lanes gives every
    output lane its owning node.

    ``gather`` selects how ``col_idx`` is serviced: ``"xla"`` (native take)
    or ``"pallas"`` (the block-reuse kernel of ``kernels/coalesced_gather``
    — ascending frontiers make the offsets monotone, exactly its window
    contract; it falls back to the native gather when violated).

    PRECONDITION: frontier node ids must be UNIQUE (what
    :func:`frontier_from_mask` produces by construction).  The expansion
    emits at most ``edge_capacity`` lanes; past it edges are DROPPED (static
    shapes leave no way to raise under jit), but the truncation is no longer
    silent: the returned ``overflow`` flag is True whenever the frontier's
    degree sum exceeded the capacity, so callers shrinking ``edge_capacity``
    below ``n_edges`` (or feeding duplicated ids, which inflate the degree
    sum past the default ``n_edges`` bound) can detect the miss and
    re-dispatch at a larger capacity — what ``core.pipeline``'s bucketed
    dispatch does.  :func:`frontier_degree_sum` is the matching predictor.
    """
    n = graph.n_nodes
    cap = graph.n_edges if edge_capacity is None else edge_capacity
    f = frontier.astype(jnp.int32)
    F = f.shape[0]
    fc, starts, counts = _frontier_counts(graph, f)

    if F == 0 or cap == 0:
        # degenerate shapes: cum[k]/counts[k] gathers are ill-formed at F=0
        # and the pad-offset max has no identity at cap=0 — both collapse to
        # an all-padding frontier (cap=0 can still overflow: edges exist but
        # zero lanes were compiled for them)
        return EdgeFrontier(
            srcs=jnp.full((cap,), n, jnp.int32),
            dsts=jnp.full((cap,), n, jnp.int32),
            eids=jnp.zeros((cap,), jnp.int32),
            valid=jnp.zeros((cap,), jnp.bool_),
            weights=jnp.zeros((cap,), graph.weights.dtype) if with_weights
            else None,
            overflow=jnp.sum(counts).astype(jnp.int32) > cap,
            n_valid=jnp.int32(0))

    cum = jnp.cumsum(counts)
    total = cum[F - 1]
    lane = jnp.arange(cap, dtype=jnp.int32)
    valid = lane < total
    # owner slot of every lane: each slot marks its first lane and a running
    # max carries it across its range (a degree-0 slot shares its first lane
    # with the next slot and loses the max to it).  A per-lane binary search
    # over ``cum`` is ~log2(F) dependent gathers per lane: on a TPU v5e it
    # made the expansion about 97% of a BFS step
    first = cum - counts
    k = jax.lax.cummax(jnp.zeros((cap,), jnp.int32).at[first].max(
        jnp.arange(F, dtype=jnp.int32), mode="drop"))
    raw = (starts - first)[k] + lane
    # padding repeats the LAST real offset (not 0): the offset stream stays
    # monotone non-decreasing end to end, so a trailing partial group does
    # not break the gather kernel's two-window contract
    pad_eid = jnp.max(jnp.where(valid, raw, 0))
    eids = jnp.where(valid, raw, pad_eid).astype(jnp.int32)
    srcs = jnp.where(valid, fc[k], n).astype(jnp.int32)
    weights = None
    if gather == "pallas":
        from repro.kernels.coalesced_gather.ops import csr_edge_gather

        if with_weights:
            # one kernel pass stages each HBM window once for both arrays
            dsts, weights = csr_edge_gather(graph.col_idx, eids,
                                            graph.weights)
        else:
            dsts = csr_edge_gather(graph.col_idx, eids)
    elif gather == "xla":
        dsts = graph.col_idx[eids]
        if with_weights:
            weights = graph.weights[eids]
    else:
        raise ValueError(f"unknown gather backend {gather!r}")
    dsts = jnp.where(valid, dsts, n).astype(jnp.int32)
    # n_valid clamps to the capacity: a truncated expansion (overflow, or a
    # caller-shrunk frontier_from_mask(size=) that compacted lanes away) must
    # never advertise more live lanes than the buffer holds — the ragged
    # engines treat n_valid as a trusted prefix bound
    return EdgeFrontier(srcs, dsts, eids, valid, weights, total > cap,
                        jnp.minimum(total, jnp.int32(cap)))


@dataclasses.dataclass
class GraphView(CSRGraph):
    """A composite ``CSRGraph`` carrying its id-space metadata.

    The composition layer of the graph-view transforms: :func:`tile_csr`
    emits ``GraphView`` instead of a bare ``CSRGraph``, so the fact that
    composite node ``c`` decomposes as ``(tenant, local) = divmod(c,
    base_nodes)`` travels WITH the arrays instead of being a side channel
    the serving engine re-derives.  ``GraphView`` IS a ``CSRGraph`` (the
    whole pipeline machinery — expansion, prediction, reorder, scatter —
    applies unchanged); the metadata rides as static pytree leaves, so a
    jitted step traced on a view retraces only when the tenant GEOMETRY
    changes, never per call.

    Closed under the view transforms: tiling a view multiplies
    ``n_tenants`` (the base stays the ORIGINAL base graph), and
    :func:`partition_csr` of a view yields a
    :class:`PartitionedGraphView` — the sharded multi-tenant composite the
    partitioned serving runtime consumes.
    """

    n_tenants: int = 1
    base_nodes: int = 0
    base_edges: int = 0

    @property
    def base(self) -> CSRGraph:
        """The single-tenant base graph — exact prefix slices (tenant 0's
        composite ids coincide with base ids, so no renumbering)."""
        return CSRGraph(row_ptr=self.row_ptr[:self.base_nodes + 1],
                        col_idx=self.col_idx[:self.base_edges],
                        weights=self.weights[:self.base_edges])

    def tenant_of(self, composite_ids):
        """Tenant index of each composite node id (high 'bits' of the id)."""
        return composite_ids // self.base_nodes

    def local_of(self, composite_ids):
        """Base-graph node id of each composite node id."""
        return composite_ids % self.base_nodes


jax.tree_util.register_dataclass(
    GraphView,
    data_fields=["row_ptr", "col_idx", "weights"],
    meta_fields=["n_tenants", "base_nodes", "base_edges"],
)


def tile_csr(graph: CSRGraph, copies: int) -> GraphView:
    """``copies`` disjoint replicas of ``graph`` as ONE composite CSR view.

    Replica ``q``'s node ``v`` becomes composite node ``q * n_nodes + v``;
    its edges shift likewise, so the replicas are disconnected components
    sharing one ``row_ptr`` / ``col_idx``.  This is the graph twin of
    slot-leased continuous batching (``serve.engine``): a multi-query
    frontier over the replicas is a single frontier of composite
    ``(query, node)`` ids — the query id rides in the high bits of the node
    id — so the whole bucketed ``FrontierPipeline`` machinery (expansion,
    degree-sum prediction, capacity ladder, reorder/merge) applies
    unchanged, and duplicate filtering / merging can only ever combine
    lanes WITHIN one query (composite ids never collide across replicas).

    Returns a :class:`GraphView` carrying the tenant geometry; tiling a
    view again composes (``n_tenants`` multiplies, the base stays the
    original base graph).

    Memory is ``copies``x the base graph — the serving engine's slot count
    is the knob, exactly as a decode engine's batch slots size its KV cache.
    """
    if copies < 1:
        raise ValueError(f"copies must be >= 1, got {copies}")
    n, m = graph.n_nodes, graph.n_edges
    # composite ids pack the tenant index into the high bits of the node id
    # (and edge offsets shift by q*m): validate copies*n / copies*m against
    # the id dtype BEFORE building anything — a silent wraparound would
    # alias tenants onto each other
    info = np.iinfo(graph.col_idx.dtype)
    if copies * max(int(n), 1) > info.max or copies * max(int(m), 1) > info.max:
        raise ValueError(
            f"tile_csr: copies={copies} tenants over a base of n={n} nodes"
            f" / {m} edges needs composite ids up to "
            f"{max(copies * max(int(n), 1), copies * max(int(m), 1))}, which"
            f" overflows the {info.dtype.name} id space "
            f"(max {info.max}); int32 ids cap copies at "
            f"{info.max // max(int(n), int(m), 1)} for this base graph")
    if isinstance(graph, GraphView):
        base_n, base_m = graph.base_nodes, graph.base_edges
        tenants = graph.n_tenants * copies
    else:
        base_n, base_m = int(n), int(m)
        tenants = copies
    q = jnp.arange(copies, dtype=jnp.int32)
    # composite row_ptr[c*n + v] = c*m + row_ptr[v]; interior replica
    # boundaries coincide ((c-1)*m + row_ptr[n] == c*m + row_ptr[0]), so
    # tiling the tail row_ptr[1:] per replica and re-prepending 0 is exact
    row_ptr = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        (graph.row_ptr[None, 1:] + q[:, None] * m).reshape(-1),
    ]).astype(jnp.int32)
    col_idx = (graph.col_idx[None, :] + q[:, None] * n).reshape(-1).astype(
        jnp.int32)
    return GraphView(row_ptr=row_ptr, col_idx=col_idx,
                     weights=jnp.tile(graph.weights, copies),
                     n_tenants=tenants, base_nodes=base_n, base_edges=base_m)


def from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    n_nodes: int,
    weights: np.ndarray | None = None,
    *,
    dedup: bool = True,
    symmetrize: bool = False,
) -> CSRGraph:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if weights is None:
        weights = np.ones(src.shape[0], np.float32)
    weights = np.asarray(weights, np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        weights = np.concatenate([weights, weights])
    keep = (src != dst) & (src >= 0) & (dst >= 0) & (src < n_nodes) & (dst < n_nodes)
    src, dst, weights = src[keep], dst[keep], weights[keep]
    if dedup:
        # unique keys come out ascending in (src, dst): the CSR order, so no
        # second sort (Graph500-scale builds are host set-up time)
        key, first = np.unique(src * n_nodes + dst, return_index=True)
        src, dst, weights = key // n_nodes, key % n_nodes, weights[first]
    else:
        order = np.lexsort((dst, src))
        src, dst, weights = src[order], dst[order], weights[order]
    row_ptr = np.concatenate(
        [[0], np.cumsum(np.bincount(src, minlength=n_nodes))])
    return CSRGraph(
        row_ptr=jnp.asarray(row_ptr, jnp.int32),
        col_idx=jnp.asarray(dst, jnp.int32),
        weights=jnp.asarray(weights),
    )


# -- edge-partitioned multi-device layout ----------------------------------
#
# A 1-D block vertex partition with halo (ghost) slots, the Dehne/GraphCage
# recipe restated for shard_map: shard ``p`` owns the contiguous vertex
# block [p*block, (p+1)*block) and ALL edges sourced there, so its local
# CSR slice is an exact row-range crop of the global one.  Remote
# destinations are renumbered into ghost slots appended after the owned
# block: local node space is [0, block) owned ++ [block, block+ghost_cap)
# ghosts, and the expansion's padding sentinel (== local n_nodes) lands
# PAST the ghosts, so no remote id can collide with padding.  The ghost
# region of the scatter target starts every superstep at the merge identity
# and accumulates only outbound candidates; the boundary exchange ships
# those VALUES along static (slot, owner-local id) maps built once here —
# ids never cross the wire at runtime, which is what makes the payload
# compressible (dist.graph_partition).


@dataclasses.dataclass(frozen=True)
class GraphPartition:
    """Stacked per-shard CSR slices + static boundary maps ([P, ...])."""

    # per-shard local CSR (leading dim = shard)
    row_ptr: jax.Array    # int32[P, local_nodes + 1] (ghost rows degree-0)
    col_idx: jax.Array    # int32[P, edge_cap] local-space dsts; pad == local_nodes
    weights: jax.Array    # float32[P, edge_cap]
    # ghost directory
    ghost_ids: jax.Array  # int32[P, ghost_cap] global id per ghost slot; pad -1
    n_ghosts: jax.Array   # int32[P]
    n_local_edges: jax.Array  # int32[P] true (unpadded) local edge count
    # boundary maps: lane k of the (shard, owner) pair
    send_slot: jax.Array  # int32[P, P, lane_cap] local ghost slot to gather; pad local_nodes
    send_mask: jax.Array  # bool[P, P, lane_cap]
    recv_id: jax.Array    # int32[P, P, lane_cap] owner-local id (< block); pad block
    recv_mask: jax.Array  # bool[P, P, lane_cap]
    # static geometry
    n_nodes: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_edges: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_parts: int = dataclasses.field(metadata=dict(static=True), default=1)
    block: int = dataclasses.field(metadata=dict(static=True), default=0)
    ghost_cap: int = dataclasses.field(metadata=dict(static=True), default=0)
    lane_cap: int = dataclasses.field(metadata=dict(static=True), default=0)
    edge_cap: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def local_nodes(self) -> int:
        """Per-shard local node-space size (owned block + ghost slots)."""
        return self.block + self.ghost_cap

    def shard_graph(self, p: int) -> CSRGraph:
        """Local CSRGraph view of shard ``p`` (host-side convenience)."""
        return CSRGraph(row_ptr=self.row_ptr[p], col_idx=self.col_idx[p],
                        weights=self.weights[p])


jax.tree_util.register_dataclass(
    GraphPartition,
    data_fields=["row_ptr", "col_idx", "weights", "ghost_ids", "n_ghosts",
                 "n_local_edges", "send_slot", "send_mask", "recv_id",
                 "recv_mask"],
    meta_fields=["n_nodes", "n_edges", "n_parts", "block", "ghost_cap",
                 "lane_cap", "edge_cap"],
)


@dataclasses.dataclass(frozen=True)
class PartitionedGraphView:
    """A sharded multi-tenant composite: ``partition_csr(tile_csr(g, Q), P)``.

    Host-side handle (NOT a pytree — the runtime feeds ``part`` to
    ``shard_map`` and keeps ``view`` for id-space arithmetic): ``part`` is
    the ordinary halo'd :class:`GraphPartition` of the composite id space —
    boundary maps are built over composite ids, so ghost dedupe happens
    per tenant for free (composite ids never collide across tenants) and
    the send/recv maps stay transpose-consistent exactly as in the
    single-tenant partition — and ``view`` carries the tenant geometry the
    partition flattened away.
    """

    part: GraphPartition
    view: GraphView

    @property
    def n_nodes(self) -> int:
        return self.part.n_nodes

    @property
    def n_edges(self) -> int:
        return self.part.n_edges

    @property
    def n_parts(self) -> int:
        return self.part.n_parts

    @property
    def n_tenants(self) -> int:
        return self.view.n_tenants

    @property
    def base_nodes(self) -> int:
        return self.view.base_nodes


def partition_csr(graph: CSRGraph, n_parts: int, *, edge_align: int = 8):
    """Block-partition ``graph`` into ``n_parts`` halo'd CSR slices.

    Every edge lands exactly once, on the shard owning its SOURCE vertex;
    destinations outside the owned block are renumbered into sorted ghost
    slots.  All shards are padded to common capacities (max local edges,
    max ghosts, max boundary lanes per (shard, owner) pair) so the result
    stacks into the [P, ...] arrays ``shard_map`` wants.  Pure numpy — runs
    once per (graph, P) at partition time.

    Closed over the view transforms: a :class:`GraphView` input (a
    :func:`tile_csr` composite) returns a :class:`PartitionedGraphView` —
    the same partition over the composite id space, plus the tenant
    geometry — so ``partition_csr(tile_csr(g, Q), P)`` is the sharded
    multi-tenant composite the partitioned serving runtime consumes.  A
    plain ``CSRGraph`` returns the bare :class:`GraphPartition` as before.
    """
    if isinstance(graph, GraphView):
        base = CSRGraph(row_ptr=graph.row_ptr, col_idx=graph.col_idx,
                        weights=graph.weights)
        return PartitionedGraphView(
            part=partition_csr(base, n_parts, edge_align=edge_align),
            view=graph)
    n_parts = int(n_parts)
    if n_parts < 1:
        raise ValueError(f"partition_csr: n_parts must be >= 1, got {n_parts}")
    if n_parts > max(int(graph.n_nodes), 1):
        raise ValueError(
            f"partition_csr: n_parts={n_parts} exceeds n_nodes="
            f"{int(graph.n_nodes)} — shards would own no vertices")
    rp = np.asarray(graph.row_ptr, np.int64)
    col = np.asarray(graph.col_idx, np.int64)
    w = np.asarray(graph.weights, np.float32)
    n = int(graph.n_nodes)
    m = int(graph.n_edges)
    block = -(-n // n_parts) if n else 1

    segs = []
    for p in range(n_parts):
        lo = min(p * block, n)
        hi = min(lo + block, n)
        e0, e1 = int(rp[lo]), int(rp[hi])
        seg_dst = col[e0:e1]
        owned = (seg_dst >= lo) & (seg_dst < hi)
        ghosts = np.unique(seg_dst[~owned])  # sorted: owner groups contiguous
        segs.append((lo, hi, seg_dst, w[e0:e1], owned, ghosts))

    ghost_cap = max((len(s[5]) for s in segs), default=0)
    edge_cap = max((len(s[2]) for s in segs), default=0)
    edge_cap = max(edge_align, -(-max(edge_cap, 1) // edge_align) * edge_align)
    lane_cap = 0
    for lo, hi, seg_dst, seg_w, owned, ghosts in segs:
        if len(ghosts):
            counts = np.bincount(ghosts // block, minlength=n_parts)
            lane_cap = max(lane_cap, int(counts.max()))

    local_nodes = block + ghost_cap
    row_ptr_l = np.zeros((n_parts, local_nodes + 1), np.int32)
    col_l = np.full((n_parts, edge_cap), local_nodes, np.int32)
    w_l = np.zeros((n_parts, edge_cap), np.float32)
    ghost_ids = np.full((n_parts, ghost_cap), -1, np.int32)
    n_ghosts = np.zeros((n_parts,), np.int32)
    n_local_edges = np.zeros((n_parts,), np.int32)
    send_slot = np.full((n_parts, n_parts, lane_cap), local_nodes, np.int32)
    send_mask = np.zeros((n_parts, n_parts, lane_cap), bool)
    recv_id = np.full((n_parts, n_parts, lane_cap), block, np.int32)
    recv_mask = np.zeros((n_parts, n_parts, lane_cap), bool)

    for p, (lo, hi, seg_dst, seg_w, owned, ghosts) in enumerate(segs):
        deg = rp[lo + 1:hi + 1] - rp[lo:hi]
        cum = np.concatenate([[0], np.cumsum(deg)])
        row_ptr_l[p, :hi - lo + 1] = cum
        row_ptr_l[p, hi - lo + 1:] = cum[-1]  # padding + ghost rows degree-0
        k = len(seg_dst)
        col_l[p, :k] = np.where(
            owned, seg_dst - lo,
            block + np.searchsorted(ghosts, seg_dst) if len(ghosts)
            else seg_dst - lo)
        w_l[p, :k] = seg_w
        g = len(ghosts)
        ghost_ids[p, :g] = ghosts
        n_ghosts[p] = g
        n_local_edges[p] = k
        if g:
            owner = ghosts // block
            for o in np.unique(owner):
                idx = np.nonzero(owner == o)[0]
                send_slot[p, o, :len(idx)] = block + idx
                send_mask[p, o, :len(idx)] = True
                recv_id[o, p, :len(idx)] = ghosts[idx] - o * block
                recv_mask[o, p, :len(idx)] = True

    return GraphPartition(
        row_ptr=jnp.asarray(row_ptr_l), col_idx=jnp.asarray(col_l),
        weights=jnp.asarray(w_l), ghost_ids=jnp.asarray(ghost_ids),
        n_ghosts=jnp.asarray(n_ghosts),
        n_local_edges=jnp.asarray(n_local_edges),
        send_slot=jnp.asarray(send_slot), send_mask=jnp.asarray(send_mask),
        recv_id=jnp.asarray(recv_id), recv_mask=jnp.asarray(recv_mask),
        n_nodes=n, n_edges=m, n_parts=n_parts, block=block,
        ghost_cap=ghost_cap, lane_cap=lane_cap, edge_cap=edge_cap)


def suggest_partitions(graph: CSRGraph, *, vmem_bytes: int = 16 * 2 ** 20,
                       state_arrays: int = 2, max_parts: int = 256) -> int:
    """Smallest power-of-two shard count whose working set fits ``vmem_bytes``.

    GraphCage's segment-size-to-cache rule reinterpreted for VMEM: a
    shard's resident set is its CSR slice (row_ptr + col_idx + weights),
    ``state_arrays`` node-payload arrays over the local node space, and one
    edge-frontier lane set (ids + payload).  Ghosts are bounded above by
    min(local edges, remote nodes) — the estimate errs conservative so the
    suggested P fits without rebuilding.
    """
    n, m = graph.n_nodes, graph.n_edges
    p = 1
    while p < max_parts:
        b = -(-n // p)
        m_p = -(-m // p)
        ghost = min(m_p, max(n - b, 0))
        local = b + ghost
        bytes_p = ((local + 1) * 4          # row_ptr slice
                   + m_p * 8                # col_idx + weights
                   + local * 4 * state_arrays
                   + m_p * 8)               # expansion lanes (ids + payload)
        if bytes_p <= vmem_bytes:
            break
        p *= 2
    return p
