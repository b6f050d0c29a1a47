"""Multi-partition banked IRU hash engine (paper §3.2: 4 partitions x 2 banks).

The hardware IRU is not one monolithic hash: sets are striped across
partitions (``partition = set % n_partitions``) and each partition reorders
its share of the stream independently, in parallel banks.  This engine
models that geometry on top of the flat batch-parallel machinery of
``batched.py``:

* one stable sort by ``(partition, set, stream order)`` buckets the stream
  partition-major (the set-major sort the flat engine pays anyway, just on a
  composite key);
* elements scatter into a ``[n_partitions, capacity]`` bank buffer —
  per-partition rows, already set-sorted, padded with inert lanes;
* ``lax.map`` runs the per-partition reorder row by row, so the filter
  path's occupancy-round loop trips only as many times as *that partition's*
  max round count — a hot partition no longer stalls the cold ones, and each
  partition applies its own ``round_cap`` fallback (``batched.py``) to the
  dense merge path;
* survivors re-emit partition-major: partition fronts first, filtered tails
  last, matching ``ref.hash_reorder_ref_banked`` bit for bit.

Two escape hatches keep the semantics total (both mirrored by the oracle):
a stream whose partition counts exceed ``ref.partition_capacity`` (bank
overflow — e.g. every element hashing to one set) bypasses banking through
the flat engine via ``lax.cond``, and ``n_partitions=1`` *is* the flat
engine.

Multi-device: pass a mesh (see ``launch.mesh.make_iru_mesh``) and the row
stage runs under ``shard_map`` with partitions sharded over the mesh axis —
each device reorders its resident partitions only; the cheap partition-major
combine stays global.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.iru_reorder.batched import (
    _assemble,
    _lane_tags,
    _reorder_presorted,
    _two_gen_emit,
    _two_gen_fits,
    _two_gen_route,
    hash_reorder_batched,
)
from repro.kernels.iru_reorder.iru_reorder import _hash_set
from repro.kernels.iru_reorder.ref import partition_capacity

_INT32_MAX = np.int32(np.iinfo(np.int32).max)


def _row_reorder(row, *, num_sets: int, slots: int,
                 filter_op: Optional[str], round_cap: Optional[int],
                 tag_table: Optional[jax.Array] = None):
    """Reorder one partition's (padded, set-sorted) bank row.

    Tags re-derive from the row's own index frame (``_lane_tags``): padding
    lanes carry index ``-1``, which clips into the table but is never
    consumed — padding never leads nor folds.
    """
    I, V, Pos, S, valid = row
    filtered, band, key, acc = _reorder_presorted(
        I, V, Pos, S, valid,
        num_sets=num_sets, slots=slots, filter_op=filter_op,
        round_cap=round_cap, tags=_lane_tags(tag_table, I))
    oi, osec, opos, oact = _assemble(I, V, Pos, valid, filtered, band, key, acc)
    n_filt = jnp.sum(filtered.astype(jnp.int32))
    n_surv = jnp.sum((~filtered & valid).astype(jnp.int32))
    return oi, osec, opos, oact, n_surv, n_filt


@functools.partial(
    jax.jit,
    static_argnames=("num_sets", "slots", "elem_bytes", "block_bytes",
                     "filter_op", "n_partitions", "round_cap", "mesh",
                     "bank_map"),
)
def hash_reorder_banked(
    indices: jax.Array,
    secondary: jax.Array,
    *,
    num_sets: int = 1024,
    slots: int = 32,
    elem_bytes: int = 4,
    block_bytes: int = 128,
    filter_op: Optional[str] = None,
    n_partitions: int = 4,
    round_cap: Optional[int] = None,
    mesh=None,
    bank_map: str = "map",
    n_live: Optional[jax.Array] = None,
    tag_table: Optional[jax.Array] = None,
):
    """Banked hash reorder; stream-identical to ``ref.hash_reorder_ref_banked``.

    ``filter_op="tagged"`` + ``tag_table`` is the fused-family datapath of
    ``hash_reorder_batched``: the (replicated) table rides into every bank
    row and each duplicate group folds under its index's family.

    ``n_live`` (runtime operand) makes the stream ragged: the result is the
    banked oracle applied to the live prefix — partition fronts, then the
    dead lanes in stream order (``active=False``, original values), then the
    partition tails.  Dead lanes take a sentinel partition so the bank
    counts, the capacity-bypass decision (``partition_capacity`` evaluated
    on the *live* count) and every per-row round bound see only the prefix.

    Returns ``(out_idx, out_sec, out_pos, out_act)`` arrays.
    """
    indices = indices.astype(jnp.int32)
    n = indices.shape[0]
    if mesh is not None and n_partitions <= 1:
        raise ValueError(
            "mesh sharding requires n_partitions > 1 (the mesh shards bank "
            "rows; a single partition has nothing to shard)")
    if (filter_op == "tagged") != (tag_table is not None):
        raise ValueError("filter_op='tagged' and tag_table go together")
    if n_partitions <= 1:
        return hash_reorder_batched(
            indices, secondary, num_sets=num_sets, slots=slots,
            elem_bytes=elem_bytes, block_bytes=block_bytes,
            filter_op=filter_op, round_cap=round_cap, n_live=n_live,
            tag_table=tag_table)
    if num_sets % n_partitions != 0:
        raise ValueError(
            f"num_sets={num_sets} must divide evenly into "
            f"n_partitions={n_partitions}")
    if n == 0:
        return (indices, secondary, jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,), jnp.bool_))

    nP = n_partitions
    C = partition_capacity(n, nP)
    epb = block_bytes // elem_bytes
    payload = secondary.shape[1:]

    with jax.named_scope("iru.route"):
        sets = _hash_set(indices // jnp.int32(epb), num_sets)
        if n_live is None:
            live = None
            part = sets % jnp.int32(nP)
            cap_eff = jnp.int32(C)
        else:
            m_live = jnp.clip(jnp.asarray(n_live, jnp.int32), 0, n)
            live = jnp.arange(n, dtype=jnp.int32) < m_live
            # sentinel partition: dead lanes never land in a bank row and
            # drop out of the partition counts (out-of-range scatter
            # indices drop)
            part = jnp.where(live, sets % jnp.int32(nP), jnp.int32(nP))
            # the bypass decision the oracle makes on the live prefix:
            # partition_capacity(m_live, nP), traced (static row width C
            # only bounds the buffer; capacity is monotone in n so
            # C >= cap_eff)
            per = (m_live + jnp.int32(nP) - 1) // jnp.int32(nP)
            cap_eff = jnp.minimum(m_live, per + jnp.maximum(jnp.int32(64),
                                                            per // 4))
        cnt = jnp.zeros((nP,), jnp.int32).at[part].add(1)
        overflow = jnp.max(cnt) > cap_eff

    if bank_map not in ("map", "vmap"):
        raise ValueError(f"bank_map must be 'map' or 'vmap', got {bank_map!r}")

    def rows_stage(rI, rV, rPos, rS, rValid, tt=None):
        # "map": sequential rows, each partition's round loop trips its own
        # count.  "vmap": one batched program over rows — every partition
        # pays the max round count, but the work vectorizes across the bank
        # dimension (BENCH_iru.json hash_p4_vmap row tracks which wins).
        # ``tt`` (the fused-family tag table) is unbatched: every row reads
        # the same replicated table.
        row_fn = functools.partial(
            _row_reorder, num_sets=num_sets, slots=slots,
            filter_op=filter_op, round_cap=round_cap, tag_table=tt)
        if bank_map == "vmap":
            return jax.vmap(lambda row: row_fn(row))((rI, rV, rPos, rS,
                                                      rValid))
        return jax.lax.map(row_fn, (rI, rV, rPos, rS, rValid))

    def sort_stage():
        # composite key: partition-major, set-minor, stream-stable — the one
        # big sort of the engine (the flat engine's set sort on a fused key).
        # Built inside the branch so the capacity bypass never pays for it.
        # Dead lanes share one maximal key so they sink as a stream-ordered
        # block behind every partition.
        skey = part * jnp.int32(num_sets) + (
            sets if live is None else jnp.where(live, sets,
                                                jnp.int32(num_sets)))
        order = jnp.argsort(skey, stable=True)
        S = sets[order]
        I = indices[order]
        V = jnp.take(secondary, order, axis=0)
        Pos = order.astype(jnp.int32)
        Pa = part[order]
        part_start = jnp.cumsum(cnt) - cnt
        col = jnp.arange(n, dtype=jnp.int32) - part_start[Pa]
        return order, S, I, V, Pos, Pa, col

    def bank_rows(S, I, V, Pos, Pa, col):
        # bank buffers: per-partition rows, set-sorted, inert padding at tail
        rc = (Pa, col)
        rI = jnp.full((nP, C), -1, jnp.int32).at[rc].set(I, mode="drop")
        rV = jnp.zeros((nP, C) + payload, secondary.dtype).at[rc].set(
            V, mode="drop")
        rPos = jnp.full((nP, C), _INT32_MAX).at[rc].set(Pos, mode="drop")
        rS = jnp.full((nP, C), num_sets, jnp.int32).at[rc].set(S, mode="drop")
        rValid = jnp.zeros((nP, C), jnp.bool_).at[rc].set(
            jnp.ones((n,), jnp.bool_), mode="drop")
        if mesh is None:
            return rows_stage(rI, rV, rPos, rS, rValid, tag_table)
        else:
            from repro.launch.shardings import iru_partition_axis

            axis = iru_partition_axis(mesh)
            # the tag table (when present) is replicated across the mesh —
            # every shard's rows consult the same index → family map
            extra = () if tag_table is None else (P(),)
            sharded = jax.shard_map(
                rows_stage, mesh=mesh,
                in_specs=(P(axis), P(axis), P(axis), P(axis),
                          P(axis)) + extra,
                out_specs=(P(axis), P(axis), P(axis), P(axis),
                           P(axis), P(axis)),
                check_vma=False,
            )
            args = (rI, rV, rPos, rS, rValid)
            if tag_table is not None:
                args = args + (tag_table,)
            return sharded(*args)

    def emit_stage(order, I, V, Pos, oi, osec, opos, oact, m, f):
        # partition-major combine: fronts [0, sum m), tails [n - sum f, n)
        front_off = jnp.cumsum(m) - m
        tail_off = jnp.cumsum(f) - f
        F = jnp.sum(f)
        cols = jnp.arange(C, dtype=jnp.int32)[None, :]
        in_front = cols < m[:, None]
        in_tail = cols >= jnp.int32(C) - f[:, None]
        g = jnp.where(
            in_front, front_off[:, None] + cols,
            jnp.where(in_tail,
                      (jnp.int32(n) - F) + tail_off[:, None]
                      + (cols - (jnp.int32(C) - f[:, None])),
                      jnp.int32(n)))  # padding lanes scatter out of range
        g = g.reshape(-1)
        out_idx = jnp.zeros((n,), jnp.int32).at[g].set(
            oi.reshape(-1), mode="drop")
        out_sec = jnp.zeros((n,) + payload, secondary.dtype).at[g].set(
            osec.reshape((nP * C,) + payload), mode="drop")
        out_pos = jnp.zeros((n,), jnp.int32).at[g].set(
            opos.reshape(-1), mode="drop")
        out_act = jnp.zeros((n,), jnp.bool_).at[g].set(
            oact.reshape(-1), mode="drop")
        if live is not None:
            # dead lanes never entered a bank row; they fill the gap between
            # the partition fronts and the filtered tails, in stream order,
            # carrying their original values (active stays False)
            live_s = live[order]
            dead_rank = jnp.cumsum((~live_s).astype(jnp.int32)) - 1
            gd = jnp.where(live_s, jnp.int32(n), jnp.sum(m) + dead_rank)
            out_idx = out_idx.at[gd].set(I, mode="drop")
            out_sec = out_sec.at[gd].set(V, mode="drop")
            out_pos = out_pos.at[gd].set(Pos, mode="drop")
        return out_idx, out_sec, out_pos, out_act

    # each arm of the engine, and each stage of the banked arm, runs under
    # a named scope (``iru.*``): the compiled ops say which arm ran
    def banked_fn(_):
        with jax.named_scope("iru.banked"):
            with jax.named_scope("iru.sort"):
                order, S, I, V, Pos, Pa, col = sort_stage()
            with jax.named_scope("iru.rows"):
                rows = bank_rows(S, I, V, Pos, Pa, col)
            with jax.named_scope("iru.emit"):
                return emit_stage(order, I, V, Pos, *rows)

    def flat_fn(_):
        # bank capacity exceeded (adversarially skewed stream): bypass
        # banking entirely — same rule as the oracle
        with jax.named_scope("iru.flat"):
            return hash_reorder_batched(
                indices, secondary, num_sets=num_sets, slots=slots,
                elem_bytes=elem_bytes, block_bytes=block_bytes,
                filter_op=filter_op, round_cap=round_cap, n_live=n_live,
                tag_table=tag_table)

    def two_gen_fn(plan):
        with jax.named_scope("iru.two_gen"):
            return _two_gen_emit(indices, secondary, plan)

    if live is not None and _two_gen_fits(n, num_sets):
        # ragged fast path: when every live set stays within two occupancy
        # generations (and no partition trips the round-cap fallback), the
        # whole banked reorder is the two-generation closed form with
        # partition-major computed emission — no bank scatter, no per-row
        # stage.  Same partition sharding (set % P), same capacity bypass
        # (the ``overflow`` arm), so this is exactly
        # ``hash_reorder_ref_banked`` on the live prefix.  The global raw
        # round bound the route checks implies every per-partition bound,
        # so no partition the oracle would dense-fallback takes this arm.
        # The route builds the plan only where counts leave it a chance
        # (no overflow, raw rounds within the cap), and over the live
        # prefix's width where the live lanes fit it.
        with jax.named_scope("iru.route"):
            ok, plan = _two_gen_route(
                indices, secondary, live, m_live, sets, n_partitions=nP,
                num_sets=num_sets, slots=slots, filter_op=filter_op,
                round_cap=round_cap, tag_table=tag_table, overflow=overflow)
            branch = jnp.where(overflow, jnp.int32(0),
                               jnp.where(ok, jnp.int32(2), jnp.int32(1)))
        return jax.lax.switch(branch, [flat_fn, banked_fn, two_gen_fn],
                              plan)
    return jax.lax.cond(overflow, flat_fn, banked_fn, None)
