"""Pallas kernel: duplicate merge over a sorted index stream (IRU filter unit).

After the IRU bins a stream, duplicate indices are adjacent; the hardware
merges them with fp-add / int-min comparators at hash-insert time.  The TPU
formulation is a segmented suffix reduction over the sorted stream: the first
lane of each run (the survivor) receives the full merged payload, all other
lanes are deactivated.

Kernel structure: the stream is laid out lane-dense as ``[rows, 128]`` and
the grid walks ``chunk``-element blocks of it in REVERSE order; a carry
(index, value) pair in VMEM threads the reduction of a run that crosses the
block boundary.  Within a block the reduction is a log-step (Hillis-Steele)
suffix scan in flat order: step ``s`` folds lane ``i + s`` into lane ``i``
when both hold the same index.  Sortedness makes index equality the whole
segment test (equal indices ``s`` apart bracket a single run), and the
shifted operand is built from ``pltpu.roll`` along lanes and sublanes, which
Mosaic lowers natively.

Contract (matches ref.segment_merge_ref):
  merged[i]    — full segment reduction, valid where survivor[i]
  survivor[i]  — True iff i is the first lane of its run
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8  # one (8, 128) tile: the block granule of 32-bit data

_IDENTITY = {
    "add": lambda dt: jnp.zeros((), dt),
    "min": lambda dt: jnp.asarray(jnp.iinfo(dt).max if jnp.issubdtype(dt, jnp.integer) else jnp.inf, dt),
    "max": lambda dt: jnp.asarray(jnp.iinfo(dt).min if jnp.issubdtype(dt, jnp.integer) else -jnp.inf, dt),
    # tagged padding lanes carry tag 0 (the min family), so the min identity
    # is the inert payload for them
    "tagged": lambda dt: jnp.asarray(jnp.iinfo(dt).max if jnp.issubdtype(dt, jnp.integer) else jnp.inf, dt),
}


def _combine(op: str, a, b, tag):
    """Fold ``b`` into ``a``.  ``tagged`` selects by the lane's own tag: a
    fold only ever joins lanes of one run, and a run is uniform-tag."""
    if op == "add":
        return a + b
    if op == "min":
        return jnp.minimum(a, b)
    if op == "max":
        return jnp.maximum(a, b)
    return jnp.where(tag != 0, a + b, jnp.minimum(a, b))


def _kernel(*refs, op: str):
    if op == "tagged":
        (idx_ref, prev_ref, val_ref, tag_ref, merged_ref, surv_ref,
         carry_idx, carry_val) = refs
        tag = tag_ref[...]
    else:
        (idx_ref, prev_ref, val_ref, merged_ref, surv_ref,
         carry_idx, carry_val) = refs
        tag = None
    idx = idx_ref[...]
    val = val_ref[...]
    rows = idx.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)

    def ahead(x, s):
        """``x`` at flat position ``i + s`` (garbage past the block end)."""
        if s < _LANES:
            same_row = pltpu.roll(x, _LANES - s, 1)
            next_row = pltpu.roll(same_row, rows - 1, 0)
            return jnp.where(lane + s < _LANES, same_row, next_row)
        return pltpu.roll(x, rows - s // _LANES, 0)

    flat = row * _LANES + lane
    s = 1
    while s < rows * _LANES:  # static unroll: log2(chunk) steps
        fold = (flat + s < rows * _LANES) & (ahead(idx, s) == idx)
        val = jnp.where(fold, _combine(op, val, ahead(val, s), tag), val)
        s *= 2

    # the run continuing into the block to our right (processed previously)
    has_carry = pl.program_id(0) > 0
    cidx = jnp.broadcast_to(carry_idx[...], idx.shape)
    cval = jnp.broadcast_to(carry_val[...], idx.shape)
    val = jnp.where(has_carry & (idx == cidx), _combine(op, val, cval, tag),
                    val)

    merged_ref[...] = val
    surv_ref[...] = (idx != prev_ref[...]).astype(jnp.int32)

    # the next block (to the left) continues the run that starts this one:
    # carry lane 0 out as a (1, 1) reduction (every other lane masked to the
    # min identity)
    first = (row == 0) & (lane == 0)
    carry_idx[...] = jnp.min(
        jnp.where(first, idx, _IDENTITY["min"](idx.dtype)), keepdims=True)
    carry_val[...] = jnp.min(
        jnp.where(first, val, _IDENTITY["min"](val.dtype)), keepdims=True)


@functools.partial(jax.jit, static_argnames=("op", "chunk", "interpret"))
def segment_merge_pallas(
    sorted_indices: jax.Array,
    values: jax.Array,
    tags: jax.Array | None = None,
    *,
    op: str = "add",
    chunk: int = 8192,
    interpret: bool = True,
):
    """``chunk`` elements per grid step; a multiple of one (8, 128) tile."""
    if (op == "tagged") != (tags is not None):
        raise ValueError("op='tagged' and tags go together")
    if chunk % (_SUBLANES * _LANES) != 0:
        raise ValueError(
            f"chunk={chunk} must be a multiple of {_SUBLANES * _LANES} "
            f"(whole ({_SUBLANES}, {_LANES}) tiles)")
    n = sorted_indices.shape[0]
    dt = values.dtype
    ident = _IDENTITY[op](dt)
    pad = (-n) % chunk
    idx = jnp.concatenate([sorted_indices.astype(jnp.int32), jnp.full((pad,), jnp.iinfo(jnp.int32).max, jnp.int32)])
    val = jnp.concatenate([values, jnp.full((pad,), ident, dt)])
    prev = jnp.concatenate([idx[:1] - 1, idx[:-1]])
    m = idx.shape[0]
    grid = m // chunk
    rows = chunk // _LANES
    lanes2d = lambda x: x.reshape(m // _LANES, _LANES)
    inputs = [idx, prev, val]
    if op == "tagged":
        # padding lanes tag 0: the min family, matching the pad identity
        inputs.append(jnp.concatenate([tags.astype(jnp.int32),
                                       jnp.zeros((pad,), jnp.int32)]))
    block = pl.BlockSpec((rows, _LANES), lambda g: (grid - 1 - g, 0))

    merged, surv = pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=(grid,),
        in_specs=[block] * len(inputs),
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((m // _LANES, _LANES), dt),
            jax.ShapeDtypeStruct((m // _LANES, _LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.int32),
            pltpu.VMEM((1, 1), dt),
        ],
        # the carry threads the blocks in order: the grid axis is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*map(lanes2d, inputs))
    return merged.reshape(-1)[:n], surv.reshape(-1)[:n].astype(jnp.bool_)
