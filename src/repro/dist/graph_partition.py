"""Edge-partitioned multi-device frontier pipeline with compressed boundary
exchange.

The single-device ``core.pipeline.FrontierPipeline`` keeps the whole graph on
one device; ``shard_map`` so far only sharded the reorder engine's bank rows.
This module shards the GRAPH: ``graphs.csr.partition_csr`` splits the CSR
into per-device halo'd slices (owned vertex block + ghost slots for remote
destinations, sized to VMEM by ``suggest_partitions`` — GraphCage's
segment-to-cache rule), and :class:`PartitionedFrontierPipeline` runs the
SAME ``frontier_step`` per shard under ``shard_map`` — same
``CapacityPolicy`` bucketing, same ragged ``n_live`` path — stitching shards
together with one boundary all-to-all per superstep.

The exchange is value-only: the partitioner froze the (ghost slot → owner
local id) maps at partition time, so each superstep ships just the app
payload per boundary lane (BFS depth / SSSP dist / PR rank mass), never ids.
That makes the payload compressible (``compress=True``):

* ``flag``   — BFS: the candidate is the same ``depth+1`` scalar on every
  shard (supersteps advance in lockstep), so one int8 presence flag per lane
  reconstructs the payload EXACTLY on the receiver — 4x less traffic and
  still bit-identical.
* ``int8_ef`` — PageRank: rank mass quantizes to blockwise int8 (one fp32
  scale per 128 lanes, the ``optim.adamw`` quantizer geometry) with a
  per-lane error-feedback buffer carried across supersteps, the
  ``dist.collectives`` recipe applied to the boundary instead of gradients —
  ~3.9x less traffic, results allclose.
* SSSP payloads are true f32 distances with no exact small encoding, so SSSP
  stays on the ``exact`` codec even under ``compress=True`` (the parity
  guarantee — BFS/SSSP bit-identical to single-device — is absolute).

Everything is measurable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (one graph shard per
forced host device over the ``gpart`` mesh axis).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.iru import IRUConfig
from repro.core.pipeline import (CapacityPolicy, FrontierApp, _merge_identity,
                                 _scatter, frontier_step)
from repro.graphs.csr import (CSRGraph, GraphPartition, frontier_degree_sum,
                              partition_csr)

AXIS = "gpart"  # the graph-shard mesh axis (launch.mesh.make_graph_mesh)

_QBLOCK = 128  # int8 codec block (one fp32 scale per 128 lanes, adamw rule)


# -- boundary payload codecs ------------------------------------------------

def quantize_rows_i8(y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Blockwise-int8 quantize each row of ``y`` [R, K] independently.

    Rows stay separable because each row of the send buffer goes to a
    different device in the all-to-all; blocks of ``_QBLOCK`` consecutive
    lanes share one fp32 scale.  Returns ``(q int8 [R, K], scale f32
    [R, ceil(K/128)])`` — the wire payload is K + 4*ceil(K/128) bytes per
    row against 4K raw.
    """
    r, k = y.shape
    nb = -(-k // _QBLOCK)
    yb = jnp.pad(y, ((0, 0), (0, nb * _QBLOCK - k))).reshape(r, nb, _QBLOCK)
    scale = jnp.max(jnp.abs(yb), axis=-1, keepdims=True) / 127.0
    q = jnp.round(yb / jnp.maximum(scale, 1e-20)).astype(jnp.int8)
    return q.reshape(r, nb * _QBLOCK)[:, :k], scale[..., 0]


def dequantize_rows_i8(q: jax.Array, scale: jax.Array) -> jax.Array:
    r, k = q.shape
    nb = scale.shape[1]
    qb = jnp.pad(q, ((0, 0), (0, nb * _QBLOCK - k)))
    y = qb.reshape(r, nb, _QBLOCK).astype(jnp.float32) * scale[..., None]
    return y.reshape(r, nb * _QBLOCK)[:, :k]


def _encode(codec: str, send: jax.Array, ef: jax.Array, ident) -> tuple[dict, jax.Array]:
    """Send buffer [P, K] -> wire pytree (+ new error-feedback buffer)."""
    if codec == "exact":
        return {"v": send}, ef
    if codec == "flag":
        return {"f": (send != ident).astype(jnp.int8)}, ef
    if codec == "int8_ef":
        y = send.astype(jnp.float32) + ef
        q, scale = quantize_rows_i8(y)
        return {"q": q, "s": scale}, y - dequantize_rows_i8(q, scale)
    raise ValueError(f"unknown boundary codec {codec!r}")


def _decode(codec: str, wire: dict, ident, dtype, payload) -> jax.Array:
    if codec == "exact":
        return wire["v"]
    if codec == "flag":
        # the payload scalar is reconstructed from the RECEIVER's state —
        # exact because partitioned supersteps advance in lockstep
        return jnp.where(wire["f"] != 0, jnp.asarray(payload, dtype),
                         jnp.asarray(ident, dtype))
    return dequantize_rows_i8(wire["q"], wire["s"]).astype(dtype)


def _wire_bytes(codec: str, lanes: int, itemsize: int) -> int:
    """Wire bytes for ``lanes`` boundary lanes of one (shard, peer) row."""
    if codec == "flag":
        return lanes
    if codec == "int8_ef":
        return lanes + 4 * -(-lanes // _QBLOCK)
    return lanes * itemsize


def _boundary_exchange(new_target, ef_buf, *, send_slot, send_mask, recv_id,
                       recv_mask, block, op, codec, payload, tags=None):
    """One all-to-all of boundary values; returns (merged target, new ef).

    Runs inside ``shard_map`` per shard.  ``new_target`` is the post-scatter
    local target [local_nodes]: the ghost region [block:] holds this shard's
    outbound contributions (it started the superstep at the merge identity).
    Gather them along the static send map, codec-encode, all-to-all, decode,
    merge into the owned region along the static recv map, and reset the
    ghost region to the identity for the next superstep.

    ``op="tagged"`` is the fused-family exchange: ``tags`` is this shard's
    LOCAL tag table (bool[local_nodes], False = min family, True = add) —
    the tag is a pure function of the composite id, so the sender's ghost
    slot and the receiver's owned slot for the same id agree on the family.
    Identities become per-slot (min lanes idle at +inf, add lanes at 0) and
    the receive merge is the tagged scatter; only the ``exact`` codec
    applies (the fused serving runtime's contract).
    """
    local_nodes = new_target.shape[0]
    if (op == "tagged") != (tags is not None):
        raise ValueError("op='tagged' and a local tag table go together")
    if op == "tagged" and codec != "exact":
        raise ValueError(
            f"tagged boundary exchange supports only the exact codec, "
            f"got {codec!r}")
    ident = _merge_identity(op, new_target.dtype)
    if op == "tagged":
        # per-slot identity vector: each slot idles at ITS family's identity
        slot_ident = jnp.where(tags, _merge_identity("add", new_target.dtype),
                               ident)
        ss = jnp.minimum(send_slot, local_nodes - 1)
        send = jnp.where(send_mask, new_target[ss], slot_ident[ss])
        wire = jax.tree.map(
            lambda a: jax.lax.all_to_all(a, AXIS, 0, 0, tiled=True),
            {"v": send})
        rid = recv_id.reshape(-1)
        rtags = tags[jnp.clip(rid, 0, local_nodes - 1)]
        owned = _scatter(new_target[:block], rid, wire["v"].reshape(-1),
                         recv_mask.reshape(-1), op, tags=rtags)
        return jnp.concatenate([owned, slot_ident[block:]]), ef_buf
    # masked lanes carry the identity so every codec ships a no-op for them
    send = jnp.where(send_mask,
                     new_target[jnp.minimum(send_slot, local_nodes - 1)],
                     ident)
    wire, new_ef = _encode(codec, send, ef_buf, ident)
    wire = jax.tree.map(
        lambda a: jax.lax.all_to_all(a, AXIS, 0, 0, tiled=True), wire)
    recv = _decode(codec, wire, ident, new_target.dtype, payload)
    owned = _scatter(new_target[:block], recv_id.reshape(-1),
                     recv.reshape(-1), recv_mask.reshape(-1), op)
    ghost = jnp.full((local_nodes - block,), ident, new_target.dtype)
    return jnp.concatenate([owned, ghost]), new_ef


# -- partition-aware apps ---------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartitionedApp:
    """A ``FrontierApp`` restated over one shard's local node space.

    * ``app`` — the per-shard app ``frontier_step`` runs under ``shard_map``
      (BFS/SSSP reuse the single-device candidate/update verbatim: ghost
      entries sit at the merge identity, so their update is a no-op; PR
      carries a partition-aware update that ``psum``s the dangling leak).
    * ``codec`` — the compressed-exchange codec ``compress=True`` selects
      ("exact" = no compression even when asked, the SSSP case).
    * ``payload(state)`` — scalar the ``flag`` codec reconstructs lanes
      from (BFS: ``depth + 1``); None otherwise.
    * ``init(part, source)`` — stacked initial ``(state [P, ...],
      mask [P, local_nodes])``; every node-space leaf is [P, local_nodes],
      per-shard scalars are [P].
    """

    app: FrontierApp
    codec: str
    init: Callable[[GraphPartition, int], tuple[Any, jax.Array]]
    payload: Optional[Callable[[Any], jax.Array]] = None


def _stacked_point_mask(part: GraphPartition, source: int):
    """bool[P, local_nodes] with only the owner-local bit of ``source``."""
    mask = np.zeros((part.n_parts, part.local_nodes), bool)
    owner = source // part.block
    mask[owner, source - owner * part.block] = True
    return mask, owner


def partitioned_bfs_app(part: GraphPartition) -> PartitionedApp:
    from repro.apps.bfs import BFS_APP, UNVISITED

    def init(part: GraphPartition, source: int):
        mask, owner = _stacked_point_mask(part, source)
        label = np.full((part.n_parts, part.local_nodes), UNVISITED, np.int32)
        label[owner, source - owner * part.block] = 0
        state = {"label": jnp.asarray(label),
                 "depth": jnp.zeros((part.n_parts,), jnp.int32)}
        return state, jnp.asarray(mask)

    return PartitionedApp(app=BFS_APP, codec="flag", init=init,
                          payload=lambda state: state["depth"] + 1)


def partitioned_sssp_app(part: GraphPartition) -> PartitionedApp:
    from repro.apps.sssp import SSSP_APP

    def init(part: GraphPartition, source: int):
        mask, owner = _stacked_point_mask(part, source)
        dist = np.full((part.n_parts, part.local_nodes), np.inf, np.float32)
        dist[owner, source - owner * part.block] = 0.0
        return {"dist": jnp.asarray(dist)}, jnp.asarray(mask)

    # f32 distances have no exact sub-word encoding; parity wins over bytes
    return PartitionedApp(app=SSSP_APP, codec="exact", init=init)


def _owned_real_mask(part: GraphPartition) -> np.ndarray:
    """bool[P, local_nodes]: owned slots holding a REAL global vertex.

    Excludes ghost slots and the last shard's padding rows (global id >=
    n_nodes) — the entries partitioned PageRank must not count as dangling
    nor hand (1-d)/n base mass.
    """
    own = np.zeros((part.n_parts, part.local_nodes), bool)
    for p in range(part.n_parts):
        lo = min(p * part.block, part.n_nodes)
        hi = min(lo + part.block, part.n_nodes)
        own[p, :hi - lo] = True
    return own


def partitioned_pagerank_app(part: GraphPartition, *, iters: int = 20,
                             damping: float = 0.85) -> PartitionedApp:
    """PR with a partition-aware update: the dangling leak and the base
    mass use the GLOBAL vertex count, with the leak summed across shards by
    ``psum`` — owned degrees equal global degrees (a shard owns all its
    block's out-edges), so the candidate is the single-device one."""
    n = part.n_nodes

    def init(part: GraphPartition, source: int):
        own = _owned_real_mask(part)
        state = {"rank": jnp.asarray(np.where(own, 1.0 / n, 0.0).astype(np.float32)),
                 "acc": jnp.zeros((part.n_parts, part.local_nodes), jnp.float32),
                 "it": jnp.zeros((part.n_parts,), jnp.int32),
                 "own": jnp.asarray(own)}
        return state, jnp.asarray(own)

    def candidate(state, graph: CSRGraph, ef):
        deg = jnp.maximum(graph.degrees(), 1).astype(jnp.float32)
        return (state["rank"] / deg)[ef.srcs]

    def update(state, acc, graph: CSRGraph):
        own = state["own"]
        dangling = own & (graph.degrees() == 0)
        leak = jax.lax.psum(
            jnp.sum(jnp.where(dangling, state["rank"], 0.0)), AXIS)
        rank = jnp.where(
            own, (1.0 - damping) / n + damping * (acc + leak / n),
            0.0).astype(jnp.float32)
        state = {"rank": rank, "acc": jnp.zeros_like(acc),
                 "it": state["it"] + 1, "own": own}
        return state, own

    app = FrontierApp(
        name="pagerank_part", filter_op="add", target="acc",
        init=lambda graph, source: (_ for _ in ()).throw(
            TypeError("partitioned app: use PartitionedApp.init")),
        candidate=candidate, update=update,
        cond=lambda state, mask: state["it"] < iters,
        result=lambda state: state["rank"], atomic=True)
    return PartitionedApp(app=app, codec="int8_ef", init=init)


# -- the partitioned driver -------------------------------------------------

class PartitionedFrontierPipeline:
    """Bucketed frontier runtime over an edge-partitioned graph.

    One ``frontier_step`` per shard per superstep under ``shard_map`` on a
    ``gpart`` mesh (one shard per device), with the boundary exchange
    spliced in through the step's ``exchange`` hook — between the merged
    scatter (which parked outbound contributions in the ghost slots) and
    ``app.update`` (which therefore sees exactly the values a single-device
    step would have scattered).  Convergence is a ``psum`` of per-shard
    frontier occupancy checked on the host each superstep; bucket choice is
    a ``pmax`` of per-shard working sets so every shard runs the same
    executable.  ``compress=True`` switches the exchange to the app's codec
    (see module docstring); ``compress=False`` is the exact parity path.
    """

    def __init__(
        self,
        part: GraphPartition,
        papp: PartitionedApp,
        *,
        mesh=None,
        mode: str = "baseline",
        iru_config: Optional[IRUConfig] = None,
        capacity_policy: Optional[CapacityPolicy] = None,
        max_iters: Optional[int] = None,
        gather: str = "xla",
        ragged: bool = True,
        compress: bool = False,
    ):
        if mesh is None:
            from repro.launch.mesh import make_graph_mesh
            mesh = make_graph_mesh(part.n_parts)
        if mesh.shape.get(AXIS) != part.n_parts:
            raise ValueError(
                f"mesh axis {AXIS!r} has size {mesh.shape.get(AXIS)}, "
                f"partition has {part.n_parts} shards")
        # one shard per device, placed once: an unplaced partition would sit
        # whole on the default device and be re-split on every superstep
        self.part = jax.device_put(part, NamedSharding(mesh, P(AXIS)))
        self.papp = papp
        self.mesh = mesh
        self.mode = mode
        if mode == "baseline":
            self.iru_config = None
        else:
            self.iru_config = dataclasses.replace(
                iru_config or IRUConfig(), mode=mode,
                filter_op=papp.app.filter_op)
        self.gather = gather
        self.ragged = ragged
        self.compress = compress
        self.codec = papp.codec if compress else "exact"
        self.max_iters = part.n_nodes if max_iters is None else max_iters
        self.capacity_policy = capacity_policy or CapacityPolicy()
        # per-shard ladder over the LOCAL capacities: the top rung holds any
        # shard's full edge set, so a pmax-dispatched bucket never overflows
        self.buckets = self.capacity_policy.ladder(
            max(part.edge_cap, 1), part.local_nodes)
        self.n_traces = 0
        self.n_hops = 0
        self.supersteps = 0
        self._state = None

        spec = P(AXIS)
        rep = P()
        self._step_b = tuple(
            jax.jit(jax.shard_map(
                functools.partial(self._superstep, bucket=b),
                mesh=mesh, in_specs=(spec, spec, spec, spec),
                out_specs=(spec, spec, spec, rep, rep), check_vma=False),
                donate_argnums=(1, 2, 3))
            for b in range(len(self.buckets)))
        self._predict = jax.jit(jax.shard_map(
            self._predict_impl, mesh=mesh, in_specs=(spec, spec),
            out_specs=(rep, rep), check_vma=False))

    # -- compiled bodies (run per shard inside shard_map) ------------------
    def _local_graph(self, part: GraphPartition) -> CSRGraph:
        return CSRGraph(row_ptr=part.row_ptr[0], col_idx=part.col_idx[0],
                        weights=part.weights[0])

    def _predict_impl(self, part, mask):
        g = self._local_graph(part)
        m = mask[0]
        need = frontier_degree_sum(g, m)
        count = jnp.sum(m.astype(jnp.int32))
        return jax.lax.pmax(need, AXIS), jax.lax.pmax(count, AXIS)

    def _superstep(self, part, state, mask, ef_buf, *, bucket: int):
        self.n_traces += 1  # python body: executes per trace, not per call
        g = self._local_graph(part)
        state = jax.tree.map(lambda a: a[0], state)
        mask, ef_local = mask[0], ef_buf[0]
        app = self.papp.app
        e_cap, f_cap = self.buckets[bucket]

        exchange = None
        cell = {"ef": ef_local}
        if self.part.n_parts > 1 and self.part.lane_cap > 0:
            def exchange(new_target, st):
                payload = (None if self.papp.payload is None
                           else self.papp.payload(st))
                new_target, cell["ef"] = _boundary_exchange(
                    new_target, cell["ef"],
                    send_slot=part.send_slot[0], send_mask=part.send_mask[0],
                    recv_id=part.recv_id[0], recv_mask=part.recv_mask[0],
                    block=self.part.block, op=app.filter_op,
                    codec=self.codec, payload=payload)
                return new_target

        state, mask, _, _, _, _, overflow = frontier_step(
            g, app, state, mask, e_cap=e_cap, f_cap=f_cap,
            iru_config=self.iru_config, gather=self.gather,
            ragged=self.ragged, exchange=exchange)
        cont = jax.lax.psum(jnp.any(mask).astype(jnp.int32), AXIS)
        ovf = jax.lax.psum(overflow.astype(jnp.int32), AXIS)
        ex = lambda t: jax.tree.map(lambda a: a[None], t)
        return ex(state), mask[None], cell["ef"][None], cont, ovf

    # -- host superstep loop ----------------------------------------------
    def _host_bucket(self, need: int, count: int) -> int:
        for i, (e_cap, f_cap) in enumerate(self.buckets):
            if need <= e_cap and count <= f_cap:
                return i
        return len(self.buckets) - 1

    def init(self, source: int = 0):
        """Initial ``(state, mask, ef_buf)``, placed one shard per device:
        the first superstep then sees the same input shardings as every
        later one, so the step compiles once."""
        part = self.part
        state, mask = self.papp.init(part, source)
        ef_buf = jnp.zeros(
            (part.n_parts, part.n_parts, max(part.lane_cap, 1)), jnp.float32)
        return jax.device_put((state, mask, ef_buf),
                              NamedSharding(self.mesh, P(AXIS)))

    def run(self, source: int = 0) -> jax.Array:
        part = self.part
        state, mask, ef_buf = self.init(source)
        self.supersteps = 0
        last_b = None
        it, cont = 0, True
        multi = len(self.buckets) > 1
        while cont and it < self.max_iters:
            if multi:
                need, count = self._predict(part, mask)
                b = self._host_bucket(int(need), int(count))
            else:
                b = 0
            if b != last_b:
                self.n_hops += 1
                last_b = b
            state, mask, ef_buf, cont_i, ovf = self._step_b[b](
                part, state, mask, ef_buf)
            if int(ovf):
                raise RuntimeError(
                    f"partitioned superstep overflowed bucket {b} "
                    f"{self.buckets[b]} — dispatch predicted wrong")
            cont = int(cont_i) > 0
            it += 1
        self.supersteps = it
        self._state = state
        return self.gather_result(state)

    def gather_result(self, state=None) -> jax.Array:
        """Assemble the global [n_nodes] result from the stacked state."""
        if state is None:
            state = self._state
        stacked = self.papp.app.result(state)  # [P, local_nodes]
        owned = stacked[:, :self.part.block]
        return owned.reshape(-1)[:self.part.n_nodes]

    # -- boundary-traffic accounting (static: maps are frozen) -------------
    @property
    def payload_itemsize(self) -> int:
        return 4  # int32 depth / f32 dist / f32 mass

    def boundary_traffic(self) -> dict:
        """Cross-device boundary bytes per superstep, raw vs on-the-wire.

        Counts only lanes whose all-to-all row leaves the device (the
        diagonal row stays local); ``raw`` is what the exact codec ships,
        ``wire`` what the active codec ships.  Static because the maps are:
        the exchange runs every superstep at full lane capacity.
        """
        p_n, k = self.part.n_parts, self.part.lane_cap
        rows = p_n * (p_n - 1)  # off-diagonal (shard, peer) rows
        raw = rows * k * self.payload_itemsize
        wire = rows * _wire_bytes(self.codec, k, self.payload_itemsize)
        return {
            "codec": self.codec,
            "raw_bytes_per_superstep": raw,
            "wire_bytes_per_superstep": wire,
            "reduction": raw / wire if wire else 1.0,
            "supersteps": self.supersteps,
            "raw_bytes_total": raw * self.supersteps,
            "wire_bytes_total": wire * self.supersteps,
        }


# -- one-call wrappers (mirror apps.bfs_pipeline & co.) ---------------------

def _as_partition(graph, n_parts: Optional[int]) -> GraphPartition:
    from repro.graphs.csr import PartitionedGraphView

    if isinstance(graph, PartitionedGraphView):
        return graph.part
    if isinstance(graph, GraphPartition):
        return graph
    part = partition_csr(graph, n_parts or 1)
    return part.part if isinstance(part, PartitionedGraphView) else part


def bfs_partitioned(graph, source: int = 0, *, n_parts: Optional[int] = None,
                    compress: bool = False, **kw) -> np.ndarray:
    """Multi-device BFS; bit-identical to ``apps.bfs_pipeline`` (also with
    ``compress=True`` — the flag codec is exact)."""
    part = _as_partition(graph, n_parts)
    pipe = PartitionedFrontierPipeline(
        part, partitioned_bfs_app(part), compress=compress, **kw)
    return np.asarray(pipe.run(source))


def sssp_partitioned(graph, source: int = 0, *, n_parts: Optional[int] = None,
                     compress: bool = False, **kw) -> np.ndarray:
    """Multi-device SSSP; bit-identical to ``apps.sssp_pipeline`` (fp-min
    is reduction-order independent; the codec stays exact by design)."""
    part = _as_partition(graph, n_parts)
    pipe = PartitionedFrontierPipeline(
        part, partitioned_sssp_app(part), compress=compress, **kw)
    return np.asarray(pipe.run(source))


def pagerank_partitioned(graph, *, n_parts: Optional[int] = None,
                         iters: int = 20, damping: float = 0.85,
                         compress: bool = False, **kw) -> np.ndarray:
    """Multi-device push PageRank; allclose to ``apps.pagerank_pipeline``
    (fp-add regrouping across shards; int8+EF quantization when
    ``compress=True``)."""
    part = _as_partition(graph, n_parts)
    pipe = PartitionedFrontierPipeline(
        part, partitioned_pagerank_app(part, iters=iters, damping=damping),
        compress=compress, max_iters=iters, **kw)
    return np.asarray(pipe.run(0))
