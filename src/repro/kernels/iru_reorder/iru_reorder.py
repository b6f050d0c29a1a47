"""Pallas kernel: the IRU reordering hash (behavioural twin of §3.2-3.3).

The hardware is a direct-mapped, multi-banked SRAM hash that elements stream
through at one element/cycle/partition.  This package realizes that unit
twice, sharing one output spec (``ref.hash_reorder_ref``):

* **This kernel** is the cycle-level twin: all state (set tags, payloads,
  positions, occupancy) lives in VMEM/SMEM scratch — the TPU analogue of the
  80 KB/partition SRAM — and the element stream is consumed by a sequential
  ``fori_loop``, flushing full sets to the output stream exactly like the
  Data Replier services full entries to warps.  One element per iteration:
  the most literal transcription, used to validate TPU lowering and as the
  seed of the throughput benchmark (``benchmarks/iru_throughput.py``).
* **``batched.py``** is the production dataflow (the default engine): block
  keys and hash sets for the whole stream are computed at once, each set's
  stream is decomposed into occupancy *rounds* (the residency periods
  between flushes), duplicates are resolved with segment reductions, and
  the reordered stream is materialized by one scatter — batch-parallel
  work in place of the per-element recurrence, identical output stream.

Selection happens in ``ops.hash_reorder(engine=...)``; ``interpret`` mode
auto-detection also lives there (``resolve_interpret``), so nothing here
hardcodes CPU vs TPU.  The pallas_call carries real BlockSpecs so this
kernel lowers for TPU unchanged.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIX = 2654435761  # Knuth multiplicative hash constant (shared with ref.py)


def _hash_set(key: jax.Array, num_sets: int) -> jax.Array:
    h = (key.astype(jnp.uint32) * jnp.asarray(_MIX, jnp.uint32)).astype(jnp.uint32)
    h = h ^ (h >> jnp.asarray(16, jnp.uint32))
    return (h % jnp.asarray(num_sets, jnp.uint32)).astype(jnp.int32)


def _store1(ref, i, val):
    ref[pl.ds(i, 1)] = val.reshape(1)


def _store_cell(ref, s, j, val):
    ref[pl.ds(s, 1), pl.ds(j, 1)] = val.reshape(1, 1)


def _load_cell(ref, s, j):
    return ref[pl.ds(s, 1), pl.ds(j, 1)].reshape(())


def _load_row(ref, s):
    return ref[pl.ds(s, 1), :].reshape(-1)


def _kernel(
    idx_ref,
    sec_ref,
    out_idx_ref,
    out_sec_ref,
    out_pos_ref,
    out_act_ref,
    tbl_idx,
    tbl_sec,
    tbl_pos,
    cnt,
    *,
    num_sets: int,
    slots: int,
    epb: int,
    filter_op: Optional[str],
):
    n = idx_ref.shape[0]
    out_act_ref[...] = jnp.zeros((n,), jnp.int32)
    out_idx_ref[...] = jnp.zeros((n,), out_idx_ref.dtype)
    out_sec_ref[...] = jnp.zeros((n,), out_sec_ref.dtype)
    out_pos_ref[...] = jnp.zeros((n,), jnp.int32)
    tbl_idx[...] = jnp.zeros((num_sets, slots), jnp.int32)
    tbl_sec[...] = jnp.zeros((num_sets, slots), tbl_sec.dtype)
    tbl_pos[...] = jnp.zeros((num_sets, slots), jnp.int32)
    cnt[...] = jnp.zeros((num_sets,), jnp.int32)

    def flush(s, head, count):
        """Emit ``count`` residents of set ``s`` (insertion order) at ``head``."""
        row_i = _load_row(tbl_idx, s)
        row_v = _load_row(tbl_sec, s)
        row_p = _load_row(tbl_pos, s)

        def emit(j, head):
            @pl.when(j < count)
            def _():
                _store1(out_idx_ref, head + j, row_i[j])
                _store1(out_sec_ref, head + j, row_v[j])
                _store1(out_pos_ref, head + j, row_p[j])
                _store1(out_act_ref, head + j, jnp.int32(1))
            return head

        jax.lax.fori_loop(0, slots, emit, head)
        cnt[s] = jnp.int32(0)
        return head + count

    def step(i, carry):
        head, tail = carry
        idx = idx_ref[pl.ds(i, 1)].reshape(())
        sec = sec_ref[pl.ds(i, 1)].reshape(())
        key = idx // epb
        s = _hash_set(key, num_sets)
        c = cnt[s]

        merged = jnp.bool_(False)
        if filter_op is not None:
            row = _load_row(tbl_idx, s)
            lane = jax.lax.iota(jnp.int32, slots)
            eq = (row == idx) & (lane < c)
            merged = jnp.any(eq)
            j = jnp.argmax(eq).astype(jnp.int32)

            @pl.when(merged)
            def _():
                old = _load_cell(tbl_sec, s, j)
                if filter_op == "add":
                    new = old + sec
                elif filter_op == "min":
                    new = jnp.minimum(old, sec)
                elif filter_op == "max":
                    new = jnp.maximum(old, sec)
                else:  # pragma: no cover
                    raise ValueError(filter_op)
                _store_cell(tbl_sec, s, j, new)
                # filtered element parks at the tail (reverse detection order)
                p = n - (tail + 1)
                _store1(out_idx_ref, p, idx)
                _store1(out_sec_ref, p, sec)
                _store1(out_pos_ref, p, i)
                _store1(out_act_ref, p, jnp.int32(0))

        def insert(head):
            _store_cell(tbl_idx, s, c, idx)
            _store_cell(tbl_sec, s, c, sec)
            _store_cell(tbl_pos, s, c, i)
            cnt[s] = c + 1
            return jax.lax.cond(
                c + 1 == slots, lambda h: flush(s, h, jnp.int32(slots)), lambda h: h, head
            )

        head = jax.lax.cond(merged, lambda h: h, insert, head)
        tail = tail + merged.astype(jnp.int32)
        return head, tail

    head, tail = jax.lax.fori_loop(0, n, step, (jnp.int32(0), jnp.int32(0)))

    def drain(s, head):
        c = cnt[s]
        return jax.lax.cond(c > 0, lambda h: flush(s, h, c), lambda h: h, head)

    jax.lax.fori_loop(0, num_sets, drain, head)


@functools.partial(
    jax.jit,
    static_argnames=("num_sets", "slots", "elem_bytes", "block_bytes", "filter_op", "interpret"),
)
def hash_reorder_pallas(
    indices: jax.Array,
    secondary: jax.Array,
    *,
    num_sets: int = 1024,
    slots: int = 32,
    elem_bytes: int = 4,
    block_bytes: int = 128,
    filter_op: Optional[str] = None,
    interpret: bool = True,
):
    n = indices.shape[0]
    epb = block_bytes // elem_bytes
    kernel = functools.partial(
        _kernel, num_sets=num_sets, slots=slots, epb=epb, filter_op=filter_op
    )
    out_idx, out_sec, out_pos, out_act = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), secondary.dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((num_sets, slots), jnp.int32),
            pltpu.VMEM((num_sets, slots), secondary.dtype),
            pltpu.VMEM((num_sets, slots), jnp.int32),
            pltpu.SMEM((num_sets,), jnp.int32),
        ],
        interpret=interpret,
    )(indices.astype(jnp.int32), secondary)
    return out_idx, out_sec, out_pos, out_act.astype(jnp.bool_)
