"""Batch-parallel IRU hash-reorder engine (pure JAX, jit/vmap-safe).

The hardware IRU inserts one element per cycle per partition; the seed Pallas
kernel mirrors that with an element-sequential ``fori_loop`` — faithful, but
latency-bound (tens of microseconds per element under CPU interpretation).
This engine produces the exact same stream with *batch-parallel dataflow*:

* block keys and hash sets are computed for the whole stream at once;
* one stable sort buckets elements per hash set (stream order preserved
  inside each bucket);
* each set's life is a sequence of *occupancy rounds* — residency periods
  between flushes.  A round ends when its ``slots``-th surviving element
  arrives (flush, emitted at that trigger's stream position) or at
  end-of-stream (drain, emitted in set order after every flush).  Without a
  filter op round boundaries are the closed form ``rank // slots`` and the
  whole reorder is sorts + cumsums + one scatter.  With a filter op, an
  element is filtered exactly when a same-index element already landed in
  the *current* round, so rounds are peeled by a ``lax.while_loop`` whose
  body is fully vectorized across all sets — the sequential dimension is the
  (small) maximum occupancy-round count, never the element count;
* duplicates resolve with segment ops: one surviving leader per
  (set, index, round) group carries the segment reduction of the group's
  payloads (scatter-add/min/max keyed by group leader).

``round_cap`` (the hybrid fallback, ROADMAP "round-peeling worst case"):
adversarial streams that hammer one set degrade the filter path to
``n / slots`` sequential passes.  With a cap, the engine bounds the round
count up front — each full round consumes at least ``slots`` elements of its
set, so ``max_set ceil(n_set / slots)`` bounds the trip count — and when
that bound exceeds the cap it switches (``lax.cond``, so only the taken
branch executes) to the *dense merge* path: stable sort by index, one
survivor per unique index carrying the segment-reduced payload, duplicates
filtered at detection.  The switch is a deterministic function of the input
(mirrored by ``ref.hash_reorder_ref_flat``), never a heuristic.

The module is factored so the multi-partition banked engine (``banked.py``)
can reuse the per-stream machinery on pre-sorted, possibly padded rows:

* :func:`_reorder_presorted` — the round/merge decomposition over a stream
  that is already set-major sorted, with a ``valid`` lane mask (padding
  lanes are inert and emit last);
* :func:`_assemble` — the shared emission layout: survivors at the front
  grouped by (band, key) — flushes by trigger stream position, then drains
  by set id, then padding — and filtered elements closing the tail in
  reverse detection order.

Output layout matches ``ref.hash_reorder_ref`` exactly: survivors at the
front in emission order, filtered elements at the tail in reverse detection
order; ``indices``/``positions``/``active`` are bit-identical, payloads agree
up to fp reduction order.  Payloads may be ``[n]`` or ``[n, k]``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.iru_reorder.iru_reorder import _hash_set

# emission bands: front groups order by (band, local_key, stream pos)
BAND_FLUSH = np.int32(0)   # key = stream position of the flush trigger
BAND_DRAIN = np.int32(1)   # key = set id (dense path: index value)
BAND_PAD = np.int32(2)     # padding lanes of banked rows; dropped by caller
_BAND_FILTERED = np.int32(3)  # assembly-internal: filtered close the tail

_INT32_MAX = np.int32(np.iinfo(np.int32).max)


def _pex(mask: jax.Array, ref: jax.Array) -> jax.Array:
    """Broadcast a lane mask across trailing payload dims of ``ref``."""
    return mask.reshape(mask.shape + (1,) * (ref.ndim - mask.ndim))


def _seg_scatter(seg_id: jax.Array, values: jax.Array, n: int) -> jax.Array:
    """Sum ``values`` per segment into an [n]-sized per-segment array."""
    return jnp.zeros((n,), values.dtype).at[seg_id].add(values)


def _scatter_merge(V: jax.Array, tgt: jax.Array, filter_op: str,
                   tags: Optional[jax.Array] = None) -> jax.Array:
    """Fold every lane of ``V`` into ``V[tgt]`` with the filter op
    (out-of-range targets drop — the idiom for 'only filtered lanes fold').

    ``filter_op="tagged"`` is the fused-family datapath: ``tags`` marks each
    lane's merge family (False = min, True = add).  A lane and its leader
    always share an index, hence a tag, so the two per-family folds hit
    disjoint target sets and compose as two drop-scatters.
    """
    if filter_op == "tagged":
        if tags is None:
            raise ValueError("filter_op='tagged' requires per-lane tags")
        n = V.shape[0]
        t_min = jnp.where(tags, jnp.int32(n), tgt)
        t_add = jnp.where(tags, tgt, jnp.int32(n))
        return V.at[t_min].min(V, mode="drop").at[t_add].add(V, mode="drop")
    if filter_op == "add":
        return V.at[tgt].add(V, mode="drop")
    if filter_op == "min":
        return V.at[tgt].min(V, mode="drop")
    if filter_op == "max":
        return V.at[tgt].max(V, mode="drop")
    raise ValueError(filter_op)


def _lane_tags(tag_table: Optional[jax.Array],
               I: jax.Array) -> Optional[jax.Array]:
    """Per-lane family tags recomputed from an index frame.

    The tag is a pure function of the index, so any permutation of the
    stream can re-derive its lane tags from the (replicated) table instead
    of threading a permuted tag array through every frame.  Out-of-range
    lanes (sort sentinels, bank padding ``-1``) clip into the table; their
    tag is never consumed — such lanes always scatter to the drop target.
    """
    if tag_table is None:
        return None
    return tag_table[jnp.clip(I, 0, tag_table.shape[0] - 1)]


def _segment_fields(S: jax.Array):
    """Per-set segment bookkeeping over a set-major sorted stream."""
    n = S.shape[0]
    ar = jnp.arange(n, dtype=jnp.int32)
    new_seg = jnp.concatenate([jnp.ones((1,), jnp.bool_), S[1:] != S[:-1]])
    seg_id = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    seg_start = jax.lax.cummax(jnp.where(new_seg, ar, 0))
    rank = ar - seg_start                        # within-set arrival rank
    # per-segment arrays live in [n]-sized slots indexed by seg_id
    seg_len = _seg_scatter(seg_id, jnp.ones((n,), jnp.int32), n)
    seg_set = _seg_scatter(seg_id, jnp.where(new_seg, S, 0), n)
    seg_startA = _seg_scatter(seg_id, jnp.where(new_seg, ar, 0), n)
    return ar, new_seg, seg_id, rank, seg_len, seg_set, seg_startA


def _keys_nofilter(S, Pos, ar, new_seg, rank, *, slots: int):
    """Closed-form round boundaries: every ``slots`` arrivals flush."""
    n = S.shape[0]
    g_new = new_seg | (rank % slots == 0)
    gid = jnp.cumsum(g_new.astype(jnp.int32)) - 1
    g_size = _seg_scatter(gid, jnp.ones((n,), jnp.int32), n)
    g_startA = _seg_scatter(gid, jnp.where(g_new, ar, 0), n)
    g_last = jnp.clip(g_startA + g_size - 1, 0, n - 1)
    full = g_size == slots
    g_band = jnp.where(full, BAND_FLUSH, BAND_DRAIN)
    g_key = jnp.where(full, Pos[g_last],
                      _seg_scatter(gid, jnp.where(g_new, S, 0), n))
    filtered = jnp.zeros((n,), jnp.bool_)
    return filtered, g_band[gid], g_key[gid]


def _keys_hash_filter(I, Pos, valid, seg_fields, psr, *, slots: int):
    """Round peeling: one vectorized pass over all sets per round generation.

    ``psr[i]`` is the within-set rank of the previous same-(set, index)
    element (−1 if none / padding); an element is filtered exactly when that
    rank falls inside the current round.
    """
    n = I.shape[0]
    ar, new_seg, seg_id, rank, seg_len, seg_set, seg_startA = seg_fields
    BIG = jnp.int32(n + 1)

    def cond(state):
        return jnp.any(state[1])

    def body(state):
        cur, seg_active, round_of, filtered, band, key, r = state
        un = round_of < 0
        dup = un & (psr >= cur[seg_id])
        keep = un & ~dup
        kc = jnp.cumsum(keep.astype(jnp.int32))
        kcb = kc - keep.astype(jnp.int32)    # keeps strictly before pos
        base = kcb[jnp.clip(seg_startA + cur, 0, n - 1)]  # per segment
        local = kc - base[seg_id]            # keep count within round
        trig_mask = keep & (local == slots)
        trigR = jnp.full((n,), BIG, jnp.int32).at[seg_id].min(
            jnp.where(trig_mask, rank, BIG))
        flushed = seg_active & (trigR < BIG)
        lim = jnp.where(flushed, trigR, BIG)[seg_id]
        take = un & seg_active[seg_id] & (rank <= lim)
        round_of = jnp.where(take, r, round_of)
        filtered = filtered | (take & dup)
        tpos = jnp.clip(seg_startA + trigR, 0, n - 1)
        bandA = jnp.where(flushed, BAND_FLUSH, BAND_DRAIN)
        keyA = jnp.where(flushed, Pos[tpos], seg_set)
        band = jnp.where(take & keep, bandA[seg_id], band)
        key = jnp.where(take & keep, keyA[seg_id], key)
        cur = jnp.where(flushed, trigR + 1, cur)
        seg_active = flushed & (cur < seg_len)
        return cur, seg_active, round_of, filtered, band, key, r + 1

    state = (jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), jnp.bool_).at[seg_id].set(valid),
             jnp.where(valid, jnp.int32(-1), jnp.int32(0)),
             jnp.zeros((n,), jnp.bool_),
             jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), jnp.int32),
             jnp.int32(0))
    _, _, round_of, filtered, band, key, _ = jax.lax.while_loop(
        cond, body, state)
    return filtered, band, key, round_of


def _keys_single_round(I, V, Pos, S, valid, seg_fields, *, slots: int,
                       filter_op: str, tags: Optional[jax.Array] = None):
    """Closed form for streams whose round bound collapses to one round
    (every live set's raw count fits in ``slots`` — the common case for
    sparse ragged frontiers, where most sets see a handful of elements).

    With at most one round per set the peeling semantics are static:

    * an element is filtered exactly when ANY same-(set, index)
      predecessor exists (the whole segment is round 0);
    * a set flushes exactly when its raw count is ``slots`` with zero
      duplicates (only then does the ``slots``-th *kept* element arrive),
      and the trigger is the segment's last element; every other set
      drains;
    * the payload merge is one (set, index)-run segment reduction (the
      round id never splits a run).

    One lexsort plus a few scatters replace the round-peeling
    ``while_loop``, its psr precomputation and the 4-key merge lexsort.
    """
    n = I.shape[0]
    _, _, seg_id, rank, seg_len, seg_set, _ = seg_fields
    o2 = jnp.lexsort((rank, I, S))
    run_new = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (S[o2][1:] != S[o2][:-1]) | (I[o2][1:] != I[o2][:-1])])
    run_new = run_new | ~valid[o2]      # padding lanes never join runs
    rid = jnp.cumsum(run_new.astype(jnp.int32)) - 1
    lead_pos = _seg_scatter(rid, jnp.where(run_new, o2, 0), n)
    leader_of = jnp.zeros((n,), jnp.int32).at[o2].set(lead_pos[rid])
    first = jnp.zeros((n,), jnp.bool_).at[o2].set(run_new)
    filtered = valid & ~first
    acc = _scatter_merge(V, jnp.where(filtered, leader_of, n), filter_op,
                         tags)
    kept = _seg_scatter(seg_id, (~filtered & valid).astype(jnp.int32), n)
    flush_seg = (seg_len == slots) & (kept == slots)
    trig_pos = jnp.zeros((n,), jnp.int32).at[seg_id].max(Pos)
    band = jnp.where(flush_seg, BAND_FLUSH, BAND_DRAIN)[seg_id]
    key = jnp.where(flush_seg, trig_pos, seg_set)[seg_id]
    return filtered, band, key, acc


def _two_gen_fits(n: int, num_sets: int) -> bool:
    """Static guard: the packed ``set * n + lane`` key of the direct path's
    set-major value sort must fit int32 (x64 stays off).  Beyond it the
    presorted pipeline handles the stream."""
    return (num_sets + 1) * max(n, 1) <= 2**31


def _two_gen_plan(indices, secondary, live, sets, *, n_partitions: int,
                  num_sets: int, slots: int, filter_op: Optional[str],
                  tag_table: Optional[jax.Array] = None):
    """Closed-form analysis of a ragged stream under the *two-generation*
    specialization of the hash oracle, and the exactness guard for it.

    A hash set lives through at most two generations when its occupancy
    reaches ``slots`` at most once: generation 1 runs until the ``slots``-th
    insertion (= the ``slots``-th first occurrence, position ``T`` — the
    flush trigger), everything after ``T`` re-inserts into the emptied set
    and drains at end of stream.  Duplicates merge only against *resident*
    entries, so dedup is per (index run, generation): one stable index sort
    finds global first occurrences, and a segmented rank over the same sort
    finds each run's first post-``T`` element (the generation-2 re-insert).
    Sparse frontiers live here: block-clustered wavefronts routinely push a
    set's *raw* count past ``slots`` on duplicates alone while its resident
    occupancy never wraps twice.

    Everything else is counting, not sorting: per-set insertion ranks come
    from segmented cumsums over a set-major order obtained with one *packed
    value sort* (``set * n + lane`` — single-key sorts avoid XLA's variadic
    comparator), flush ranks from a cumsum over trigger positions, and every
    element's output slot is computed directly — partition fronts (flushes
    by trigger time, then drains by set id, insertion order within each),
    dead lanes in stream order, partition filtered tails in reverse
    detection order — so emission is one scatter instead of an O(n log n)
    stable argsort.

    Exactness guard (``ok``): no set may start a third generation or flush
    twice (per-set kept count under ``2 * slots`` whenever it flushed).  The
    round-cap rule is not checked here: :func:`_two_gen_route` decides it
    before it builds a plan at all.

    Returns ``(ok, (outpos, kept, acc))`` — feed to :func:`_two_gen_emit`
    inside the branch ``ok`` selects.
    """
    n = indices.shape[0]
    nP = n_partitions
    i32 = jnp.int32
    ar = jnp.arange(n, dtype=i32)
    # dead lanes take the sentinel set so every scatter drops them
    sets_l = jnp.where(live, sets, i32(num_sets))

    # ---- global first occurrences (generation-1 insertions) ---------------
    if filter_op is not None:
        Ik = jnp.where(live, indices, _INT32_MAX)
        o = jnp.argsort(Ik, stable=True)
        run_new = jnp.concatenate([
            jnp.ones((1,), jnp.bool_), Ik[o][1:] != Ik[o][:-1]])
        run_new = run_new | ~live[o]    # dead lanes never join runs
        rid = jnp.cumsum(run_new.astype(i32)) - 1
        first = jnp.zeros((n,), jnp.bool_).at[o].set(run_new) & live
    else:
        first = live                    # no merging: every live lane inserts

    # ---- set-major position order (one packed value sort) -----------------
    so = jnp.sort(sets_l * i32(n) + ar)
    o_s = so % i32(n)                   # lanes, position-ordered per set
    S_s = so // i32(n)
    seg_new = jnp.concatenate([
        jnp.ones((1,), jnp.bool_), S_s[1:] != S_s[:-1]])

    def seg_rank(flags):
        # inclusive rank of flagged lanes within their set segment
        c = jnp.cumsum(flags.astype(i32))
        base = jax.lax.cummax(jnp.where(seg_new, c - flags.astype(i32), 0))
        return c - base

    # flush trigger T = position of the slots-th insertion (or n: never)
    f_s = first[o_s]
    trig_slot = f_s & (seg_rank(f_s) == i32(slots))
    T = jnp.full((num_sets + 1,), i32(n)).at[
        jnp.where(trig_slot, S_s, i32(num_sets))].min(o_s)
    gen2 = live & (ar > T[sets_l])

    # ---- generation-aware dedup and payload merge -------------------------
    if filter_op is not None:
        g2o = gen2[o]
        c2 = jnp.cumsum(g2o.astype(i32))
        base2 = jax.lax.cummax(jnp.where(run_new, c2 - g2o.astype(i32), 0))
        first2 = g2o & ((c2 - base2) == 1)   # run's gen-2 re-insert
        lead1 = _seg_scatter(rid, jnp.where(run_new, o, 0), n)
        lead2 = _seg_scatter(rid, jnp.where(first2, o, 0), n)
        kept = jnp.zeros((n,), jnp.bool_).at[o].set(run_new | first2) & live
        filtered = live & ~kept
        leader_of = jnp.zeros((n,), i32).at[o].set(
            jnp.where(g2o, lead2[rid], lead1[rid]))
        acc = _scatter_merge(secondary, jnp.where(filtered, leader_of, n),
                             filter_op, _lane_tags(tag_table, indices))
    else:
        kept = live
        filtered = jnp.zeros((n,), jnp.bool_)
        acc = secondary

    # ---- per-set layout counts and the exactness guard --------------------
    kept_s = jnp.zeros((num_sets,), i32).at[sets_l].add(kept.astype(i32))
    flush_s = T[:num_sets] < i32(n)
    ok = jnp.all(jnp.where(flush_s, kept_s < i32(2 * slots), True))
    drain_s = kept_s - jnp.where(flush_s, i32(slots), 0)

    # ---- output positions: partition fronts / dead lanes / tails ----------
    set_ar = jnp.arange(num_sets, dtype=i32)
    p_set = set_ar % i32(nP)
    nflush_p = jnp.zeros((nP,), i32).at[p_set].add(
        jnp.where(flush_s, i32(slots), 0))
    ndrain_p = jnp.zeros((nP,), i32).at[p_set].add(drain_s)
    front_p = nflush_p + ndrain_p
    front_base = jnp.cumsum(front_p) - front_p
    s_total = jnp.sum(front_p)

    # flushed-set rank within its partition, by trigger time: triggers are
    # distinct stream positions, so a cumsum over the position axis ranks
    # them without a sort
    rank_f = jnp.zeros((num_sets,), i32)
    t_cl = jnp.clip(T[:num_sets], 0, max(n - 1, 0))
    for p in range(nP):
        mark = jnp.zeros((n,), i32).at[
            jnp.where(flush_s & (p_set == p), t_cl, i32(n))].add(
                1, mode="drop")
        rank_f = jnp.where(flush_s & (p_set == p),
                           jnp.cumsum(mark)[t_cl] - 1, rank_f)

    # per-set drain offset: exclusive prefix over the (partition, set) grid
    dd = jnp.zeros((nP * num_sets,), i32).at[
        p_set * i32(num_sets) + set_ar].set(drain_s)
    d_ex = jnp.cumsum(dd) - dd
    drain_off = (d_ex[p_set * i32(num_sets) + set_ar]
                 - d_ex[jnp.arange(nP, dtype=i32) * i32(num_sets)][p_set])

    # per-element insertion ranks (0-based), element-aligned
    k_s = kept[o_s]
    g2_s = gen2[o_s]
    rank1 = jnp.zeros((n,), i32).at[o_s].set(seg_rank(k_s & ~g2_s)) - 1
    rank2 = jnp.zeros((n,), i32).at[o_s].set(seg_rank(k_s & g2_s)) - 1

    sc = jnp.clip(sets_l, 0, max(num_sets - 1, 0))
    p_e = p_set[sc]
    flush_e = flush_s[sc]
    is_flush = kept & ~gen2 & flush_e
    pos_flush = front_base[p_e] + rank_f[sc] * i32(slots) + rank1
    pos_drain = (front_base[p_e] + nflush_p[p_e] + drain_off[sc]
                 + jnp.where(flush_e, rank2, rank1))

    t_p = jnp.zeros((nP,), i32).at[jnp.where(filtered, p_e, i32(nP))].add(
        1, mode="drop")
    tail_base = i32(n) - jnp.sum(t_p) + (jnp.cumsum(t_p) - t_p)
    rfil = jnp.zeros((n,), i32)
    for p in range(nP):
        fp = filtered & (p_e == p)
        rfil = jnp.where(fp, jnp.cumsum(fp.astype(i32)) - 1, rfil)
    pos_filt = tail_base[p_e] + (t_p[p_e] - 1 - rfil)
    pos_dead = s_total + (ar - jnp.sum(live.astype(i32)))

    outpos = jnp.where(is_flush, pos_flush,
             jnp.where(kept, pos_drain,
             jnp.where(filtered, pos_filt, pos_dead)))
    return ok, (outpos, kept, acc)


def route_prefix_width(n: int, num_sets: int) -> int:
    """Static lane width of the two-generation route's prefix plan for an
    ``n``-lane ragged stream: one sixteenth of ``n`` (the low rung of the
    capacity ladders), rounded up to a whole 1024-lane tile.  0 where the
    stream has no prefix plan: the width would not be below ``n``, or the
    plan's packed sort key would not fit int32."""
    w = -(-(n // 16) // 1024) * 1024
    return w if 0 < w < n and _two_gen_fits(n, num_sets) else 0


def _two_gen_route(indices, secondary, live, m_live, sets, *,
                   n_partitions: int, num_sets: int, slots: int,
                   filter_op: Optional[str], round_cap: Optional[int],
                   tag_table: Optional[jax.Array] = None, overflow=None):
    """``(ok, plan)`` of :func:`_two_gen_plan` for a ragged stream, built
    only over the lanes it needs (``lax.switch``, one branch runs):

    * ``iru.plan_none`` — no plan when counts rule the direct path out: the
      banks overflow (``overflow``), or with a filter op under a round cap
      the raw per-set round bound ``max ceil(cnt_s / slots)`` of the live
      lanes exceeds the cap (the oracle's dense-fallback rule, one
      histogram).  ``ok`` is False and the plan inert;
    * ``iru.plan_prefix`` — the live count fits :func:`route_prefix_width`
      ``W``: the plan of the first ``W`` lanes, lifted to ``n``.  Prefix
      fronts and dead lanes keep their slots, the filtered tail moves up by
      ``n - W``, and lanes from ``W`` on (all dead) follow the in-prefix
      dead lanes;
    * ``iru.plan_full`` — every other stream: the plan over all ``n`` lanes.

    Every branch gives the ``ok`` and, where ``ok``, the plan of the full
    width, bit for bit: the same arm runs and emits the same stream.
    """
    n = indices.shape[0]
    i32 = jnp.int32
    kw = dict(n_partitions=n_partitions, num_sets=num_sets, slots=slots,
              filter_op=filter_op, tag_table=tag_table)
    ruled_out = jnp.bool_(False) if overflow is None else overflow
    if filter_op is not None and round_cap is not None:
        cnt_s = jnp.zeros((num_sets + 1,), i32).at[
            jnp.where(live, sets, i32(num_sets))].add(1)
        r_raw = jnp.max((cnt_s[:num_sets] + i32(slots) - 1) // i32(slots))
        ruled_out = ruled_out | (r_raw > i32(round_cap))

    def none_fn(_):
        with jax.named_scope("iru.plan_none"):
            # inert: every lane in its own slot, none kept; never emitted
            return jnp.bool_(False), (jnp.arange(n, dtype=i32),
                                      jnp.zeros((n,), jnp.bool_), secondary)

    def full_fn(_):
        with jax.named_scope("iru.plan_full"):
            return _two_gen_plan(indices, secondary, live, sets, **kw)

    W = route_prefix_width(n, num_sets)
    if not W:
        return jax.lax.cond(ruled_out, none_fn, full_fn, None)

    def prefix_fn(_):
        with jax.named_scope("iru.plan_prefix"):
            ok, (pos_w, kept_w, acc_w) = _two_gen_plan(
                indices[:W], secondary[:W], live[:W], sets[:W], **kw)
            n_filt = m_live - jnp.sum(kept_w.astype(i32))
            pos_w = jnp.where(pos_w >= i32(W) - n_filt, pos_w + i32(n - W),
                              pos_w)
            outpos = jnp.concatenate(
                [pos_w, jnp.arange(W, n, dtype=i32) - n_filt])
            kept = jnp.concatenate([kept_w, jnp.zeros((n - W,), jnp.bool_)])
            acc = jnp.concatenate([acc_w, secondary[W:]])
            return ok, (outpos, kept, acc)

    branch = jnp.where(ruled_out, i32(0),
                       jnp.where(m_live <= i32(W), i32(1), i32(2)))
    return jax.lax.switch(branch, [none_fn, prefix_fn, full_fn], None)


def _two_gen_emit(indices, secondary, plan):
    """Place every lane at its precomputed output slot — four scatters, the
    whole emission of the direct two-generation path."""
    outpos, kept, acc = plan
    n = indices.shape[0]
    ar = jnp.arange(n, dtype=jnp.int32)
    out_idx = jnp.zeros((n,), jnp.int32).at[outpos].set(indices)
    out_sec = jnp.zeros_like(acc).at[outpos].set(acc)
    out_pos = jnp.zeros((n,), jnp.int32).at[outpos].set(ar)
    out_act = jnp.zeros((n,), jnp.bool_).at[outpos].set(kept)
    return out_idx, out_sec, out_pos, out_act


def _merge_payloads(I, V, S, rank, round_of, filtered, filter_op: str,
                    tags: Optional[jax.Array] = None):
    """Fold each filtered element into the surviving leader of its
    (set, index, round) group — a segment reduction."""
    n = I.shape[0]
    ar = jnp.arange(n, dtype=jnp.int32)
    o3 = jnp.lexsort((rank, round_of, I, S))
    S3, I3, R3 = S[o3], I[o3], round_of[o3]
    lead_new = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (S3[1:] != S3[:-1]) | (I3[1:] != I3[:-1]) | (R3[1:] != R3[:-1])])
    g3 = jnp.cumsum(lead_new.astype(jnp.int32)) - 1
    lead_pos = _seg_scatter(g3, jnp.where(lead_new, o3, 0), n)
    leader_of = jnp.zeros((n,), jnp.int32).at[o3].set(lead_pos[g3])
    return _scatter_merge(V, jnp.where(filtered, leader_of, n), filter_op,
                          tags)


def _keys_dense_merge(I, V, Pos, valid, filter_op: str,
                      tags: Optional[jax.Array] = None):
    """Dense fallback: one survivor per unique index, sorted by index value.

    The "infinite-patience" reorder of the sub-stream — what the sort engine
    would do — expressed in the hash engine's output conventions: survivors
    at the front ordered by (index, arrival), duplicates filtered at
    detection and folded into their survivor by a segment reduction.
    """
    n = I.shape[0]
    # padding lanes sort last and never form duplicate runs
    Ik = jnp.where(valid, I, _INT32_MAX)
    o2 = jnp.lexsort((Pos, Ik))
    run_new = jnp.concatenate([
        jnp.ones((1,), jnp.bool_), (Ik[o2][1:] != Ik[o2][:-1])])
    run_new = run_new | ~valid[o2]
    rid = jnp.cumsum(run_new.astype(jnp.int32)) - 1
    lead_pos = _seg_scatter(rid, jnp.where(run_new, o2, 0), n)
    leader_of = jnp.zeros((n,), jnp.int32).at[o2].set(lead_pos[rid])
    first = jnp.zeros((n,), jnp.bool_).at[o2].set(run_new)
    filtered = valid & ~first
    acc = _scatter_merge(V, jnp.where(filtered, leader_of, n), filter_op,
                         tags)
    band = jnp.full((n,), BAND_FLUSH)
    key = Ik
    # round_of is unused downstream for the dense path; return zeros
    return filtered, band, key, acc


def _reorder_presorted(
    I: jax.Array,
    V: jax.Array,
    Pos: jax.Array,
    S: jax.Array,
    valid: jax.Array,
    *,
    num_sets: int,
    slots: int,
    filter_op: Optional[str],
    round_cap: Optional[int] = None,
    tags: Optional[jax.Array] = None,
):
    """Round/merge decomposition over one set-major sorted (padded) stream.

    ``S`` must be non-decreasing with padding lanes (``valid=False``) at the
    tail carrying ``S = num_sets``.  Returns per-lane ``(filtered, band,
    local_key, acc)`` for :func:`_assemble`; padding lanes come back with
    ``band == BAND_PAD`` and ``filtered == False``.
    """
    seg_fields = _segment_fields(S)
    ar, new_seg, seg_id, rank, seg_len, seg_set, _ = seg_fields

    if filter_op is None:
        filtered, band, key = _keys_nofilter(
            S, Pos, ar, new_seg, rank, slots=slots)
        acc = V
    else:
        n = I.shape[0]

        def hash_path(_):
            # psr[i] = within-set rank of previous same-(set, index) element
            # (computed inside the branch: the dense path never needs it)
            o2 = jnp.lexsort((rank, I, S))
            o2_prev = jnp.concatenate([o2[:1], o2[:-1]])
            run_new = jnp.concatenate([
                jnp.ones((1,), jnp.bool_),
                (S[o2][1:] != S[o2][:-1]) | (I[o2][1:] != I[o2][:-1])])
            psr = jnp.zeros((n,), jnp.int32).at[o2].set(
                jnp.where(run_new, -1, rank[o2_prev]))
            psr = jnp.where(valid, psr, -1)
            filtered, band, key, round_of = _keys_hash_filter(
                I, Pos, valid, seg_fields, psr, slots=slots)
            acc = _merge_payloads(I, V, S, rank, round_of, filtered,
                                  filter_op, tags)
            return filtered, band, key, acc

        def single_path(_):
            return _keys_single_round(
                I, V, Pos, S, valid, seg_fields, slots=slots,
                filter_op=filter_op, tags=tags)

        # each full round consumes >= slots elements of its set, so the
        # per-set ceil(len / slots) bounds the trip count a priori; a bound
        # of one means the peeling loop is statically a single iteration and
        # the closed form replaces it (only the taken branch executes)
        seg_rounds = jnp.where(seg_set < num_sets,
                               (seg_len + slots - 1) // slots, 0)
        r_ub = jnp.max(seg_rounds) if n else jnp.int32(0)
        if round_cap is None:
            filtered, band, key, acc = jax.lax.cond(
                r_ub <= 1, single_path, hash_path, None)
        else:
            branch = jnp.where(
                r_ub > round_cap, jnp.int32(2),
                jnp.where(r_ub <= 1, jnp.int32(0), jnp.int32(1)))
            filtered, band, key, acc = jax.lax.switch(
                branch,
                [single_path, hash_path,
                 lambda _: _keys_dense_merge(I, V, Pos, valid, filter_op,
                                             tags)],
                None)
    band = jnp.where(valid, band, BAND_PAD)
    # padding keys collapse to 0 so pads order purely by stream position —
    # the ragged flat path emits dead lanes between survivors and the
    # filtered tail, and the contract wants them in stream order
    key = jnp.where(valid, key, 0)
    filtered = filtered & valid
    return filtered, band, key, acc


def _assemble(I, V, Pos, valid, filtered, band, key, acc):
    """Shared emission layout over one (padded) stream of length L.

    Survivors occupy the front ordered by (band, key, stream position) —
    flushes by trigger position, drains by set id, padding last among the
    non-filtered; filtered lanes close the tail in reverse detection order.
    Returns ``(out_idx, out_sec, out_pos, out_act)`` plus the filtered
    count so banked callers can split front/tail regions.
    """
    L = I.shape[0]
    ar = jnp.arange(L, dtype=jnp.int32)
    band_eff = jnp.where(filtered, _BAND_FILTERED, band)
    em = jnp.lexsort((Pos, key, band_eff))
    front_pos = jnp.zeros((L,), jnp.int32).at[em].set(ar)
    fo = jnp.lexsort((jnp.where(filtered, Pos, _INT32_MAX),))
    frank = jnp.zeros((L,), jnp.int32).at[fo].set(ar)
    out_position = jnp.where(filtered, L - 1 - frank, front_pos)

    out_idx = jnp.zeros((L,), jnp.int32).at[out_position].set(I)
    out_sec = jnp.zeros((L,) + V.shape[1:], V.dtype).at[out_position].set(
        jnp.where(_pex(filtered, V), V, acc))
    out_pos = jnp.zeros((L,), jnp.int32).at[out_position].set(Pos)
    out_act = jnp.zeros((L,), jnp.bool_).at[out_position].set(
        ~filtered & valid)
    return out_idx, out_sec, out_pos, out_act


def _dense_merge_flat(indices: jax.Array, secondary: jax.Array,
                      filter_op: str, tags: Optional[jax.Array] = None):
    """Whole-stream dense fallback, direct form (one argsort, no emission
    sorts): the output positions of ``dense_merge_ref`` are closed-form —
    survivors take their rank among survivors in (index, arrival) order,
    duplicates take the tail in reverse detection (stream) order."""
    n = indices.shape[0]
    ar = jnp.arange(n, dtype=jnp.int32)
    o = jnp.argsort(indices, stable=True)
    I2 = indices[o]
    run_new = jnp.concatenate([jnp.ones((1,), jnp.bool_), I2[1:] != I2[:-1]])
    rid = jnp.cumsum(run_new.astype(jnp.int32)) - 1
    lead_pos = _seg_scatter(rid, jnp.where(run_new, o, 0), n)
    leader_of = jnp.zeros((n,), jnp.int32).at[o].set(lead_pos[rid])
    first = jnp.zeros((n,), jnp.bool_).at[o].set(run_new)
    filtered = ~first
    acc = _scatter_merge(secondary, jnp.where(filtered, leader_of, n),
                         filter_op, tags)
    surv_rank = jnp.cumsum(run_new.astype(jnp.int32)) - 1    # per sorted pos
    pos_of = jnp.zeros((n,), jnp.int32).at[o].set(surv_rank)
    frank = jnp.cumsum(filtered.astype(jnp.int32)) - 1       # stream order
    out_position = jnp.where(filtered, n - 1 - frank, pos_of)
    out_idx = jnp.zeros((n,), jnp.int32).at[out_position].set(indices)
    out_sec = jnp.zeros_like(secondary).at[out_position].set(
        jnp.where(_pex(filtered, secondary), secondary, acc))
    out_pos = jnp.zeros((n,), jnp.int32).at[out_position].set(ar)
    out_act = jnp.zeros((n,), jnp.bool_).at[out_position].set(~filtered)
    return out_idx, out_sec, out_pos, out_act


@functools.partial(
    jax.jit,
    static_argnames=("num_sets", "slots", "elem_bytes", "block_bytes",
                     "filter_op", "round_cap"),
)
def hash_reorder_batched(
    indices: jax.Array,
    secondary: jax.Array,
    *,
    num_sets: int = 1024,
    slots: int = 32,
    elem_bytes: int = 4,
    block_bytes: int = 128,
    filter_op: Optional[str] = None,
    round_cap: Optional[int] = None,
    n_live: Optional[jax.Array] = None,
    tag_table: Optional[jax.Array] = None,
):
    """Batch-parallel hash reorder; stream-identical to ``hash_reorder_ref``
    (``ref.hash_reorder_ref_flat`` when ``round_cap`` is set).

    ``filter_op="tagged"`` fuses the min and add merge families into one
    pass: ``tag_table`` (a runtime bool operand of size ``max_index + 2``,
    True = add) maps every index to its family, and each duplicate group
    merges under its own family's op.  Binning, rounds, flush/drain layout
    and dedup decisions are all tag-independent — equal indices share a tag
    by construction, so only the payload folds consult it.

    ``n_live`` (a runtime operand, never a shape) makes the stream ragged:
    only the first ``n_live`` lanes are real.  The result is then the oracle
    applied to the live prefix, laid out in the same padded buffer —
    survivors at the front, the ``n - n_live`` dead lanes in the middle in
    stream order (``active=False``, original index/payload/position), and
    the filtered tail closing the buffer.  Dead lanes hash to a sentinel
    set, so every count, round bound and cap decision sees the live prefix
    only and the round loop trips on the *live* occupancy bound.

    Returns ``(out_idx, out_sec, out_pos, out_act)`` arrays.
    """
    indices = indices.astype(jnp.int32)
    if (filter_op == "tagged") != (tag_table is not None):
        raise ValueError("filter_op='tagged' and tag_table go together")
    n = indices.shape[0]
    epb = block_bytes // elem_bytes
    if n == 0:
        return (indices, secondary, jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,), jnp.bool_))

    sets = _hash_set(indices // jnp.int32(epb), num_sets)
    if n_live is None:
        live = None
    else:
        m_live = jnp.clip(jnp.asarray(n_live, jnp.int32), 0, n)
        live = jnp.arange(n, dtype=jnp.int32) < m_live
        # sentinel set: dead lanes sort to the tail as inert padding and
        # drop out of every bincount (out-of-range scatter indices drop)
        sets = jnp.where(live, sets, jnp.int32(num_sets))

    def hash_fn(_):
        order = jnp.argsort(sets, stable=True)   # set-major, stream order kept
        S = sets[order]
        I = indices[order]
        V = jnp.take(secondary, order, axis=0)
        Pos = order.astype(jnp.int32)
        valid = jnp.ones((n,), jnp.bool_) if live is None else live[order]
        filtered, band, key, acc = _reorder_presorted(
            I, V, Pos, S, valid,
            num_sets=num_sets, slots=slots, filter_op=filter_op,
            # padded streams decide the cap below, before paying the sort;
            # ragged streams decide inside the sorted layout where the
            # live-only segment lengths are already on hand
            round_cap=(round_cap if live is not None else None),
            tags=_lane_tags(tag_table, I))
        return _assemble(I, V, Pos, valid, filtered, band, key, acc)

    if live is not None and _two_gen_fits(n, num_sets):
        # ragged fast path: analyze the live prefix under the two-generation
        # closed form (real sparse frontiers live there — raw set counts
        # blow past ``slots`` on block-clustered duplicates while resident
        # occupancy wraps at most once); when exact, emission is computed
        # output positions plus one scatter — cheaper than even the padded
        # dense fallback, which is what makes sparse-frontier raggedness a
        # win rather than a wash.  The route sizes the plan to the live
        # lanes, and builds none where the round cap rules it out
        with jax.named_scope("iru.route"):
            ok, plan = _two_gen_route(
                indices, secondary, live, m_live, sets, n_partitions=1,
                num_sets=num_sets, slots=slots, filter_op=filter_op,
                round_cap=round_cap, tag_table=tag_table)
        return jax.lax.cond(
            ok,
            lambda _: _two_gen_emit(indices, secondary, plan),
            hash_fn, None)
    if filter_op is None or round_cap is None or live is not None:
        return hash_fn(None)
    # round-cap hybrid: the trip-count bound is one bincount away, so decide
    # before paying the set sort — the dense fallback needs neither it nor
    # any emission sort
    counts = jnp.zeros((num_sets,), jnp.int32).at[sets].add(1)
    r_ub = jnp.max((counts + slots - 1) // slots)
    return jax.lax.cond(
        r_ub > round_cap,
        lambda _: _dense_merge_flat(indices, secondary, filter_op,
                                    _lane_tags(tag_table, indices)),
        hash_fn,
        None)
