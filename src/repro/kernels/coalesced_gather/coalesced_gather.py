"""Pallas kernel: block-reuse gather for IRU-binned index streams.

The GPU coalescer's win is that 32 binned indices touch one 128 B line → one
L1 request.  The TPU analogue: once the IRU bins a stream, each group of
consecutive output lanes reads table entries inside a narrow, aligned
window.  The kernel stages that window HBM→VMEM once per group (two adjacent
window tiles, so runs crossing a window boundary stay legal) and services
every lane of the group from VMEM — each HBM block is fetched once, exactly
the hardware's block-reuse.

TPU geometry: tables and streams are laid out lane-dense as ``[rows, 128]``
views, so a group is one (8, 128) tile of indices (``TILE`` = 1024 lanes)
and a window is one (8, 128) tile of table entries.  Inside the kernel each
lane picks its entry with a lane gather (``take_along_axis``) from the
window row its offset falls in.  A ``[V, d]`` table is served one column at
a time: a narrow minor dimension would otherwise be padded to 128 lanes in
HBM.

Contract: for every group g of ``TILE`` indices,
    max(idx) < (min(idx) // TILE + 2) * TILE
ops.py verifies this and falls back to ``jnp.take`` when violated — the
software analogue of the IRU timeout (trades coalescing for progress, never
correctness).

Scalar prefetch feeds the per-group window anchor to the BlockSpec index_map
(classic Pallas sparse-access pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
TILE = _LANES * _SUBLANES  # lanes per group == table entries per window
# groups per pallas_call: bounds the scalar-prefetched anchors (4 B each) to
# 256 KiB of the 1 MiB SMEM
_MAX_GROUPS = 1 << 16


def _kernel(base_ref, idx_ref, *refs, n_tables: int):
    wins, outs = refs[:2 * n_tables], refs[2 * n_tables:]
    off = jnp.clip(idx_ref[...] - base_ref[pl.program_id(0)] * TILE,
                   0, 2 * TILE - 1)
    row, lane = off // _LANES, off % _LANES
    for t, out_ref in enumerate(outs):
        out = jnp.zeros(out_ref.shape, out_ref.dtype)
        for v in range(2 * _SUBLANES):  # static unroll over the window rows
            src = wins[2 * t + v // _SUBLANES][pl.ds(v % _SUBLANES, 1), :]
            cand = jnp.take_along_axis(
                jnp.broadcast_to(src, out.shape), lane, axis=1,
                mode="promise_in_bounds")
            out = jnp.where(row == v, cand, out)
        out_ref[...] = out


def _pad_groups(indices: jax.Array) -> jax.Array:
    """Pad to whole groups with the last index: the tail group's span, and
    so the contract, is the real lanes' span."""
    n = indices.shape[0]
    idx = indices.astype(jnp.int32)
    return jnp.concatenate(
        [idx, jnp.broadcast_to(idx[n - 1:], ((-n) % TILE,))])


def _gather_call(tables, idx, interpret: bool):
    """One pallas_call over whole groups ``idx`` ([groups * TILE])."""
    n_blocks = tables[0].shape[0] // _SUBLANES  # window tiles per table
    groups = idx.shape[0] // TILE
    base = jnp.min(idx.reshape(groups, TILE), axis=1) // TILE
    base = jnp.minimum(base, n_blocks - 2)  # keep the second window in range
    tile = (_SUBLANES, _LANES)
    win = [pl.BlockSpec(tile, lambda g, b: (b[g], 0)),
           pl.BlockSpec(tile, lambda g, b: (b[g] + 1, 0))]
    return pl.pallas_call(
        functools.partial(_kernel, n_tables=len(tables)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups,),
            in_specs=[pl.BlockSpec(tile, lambda g, b: (g, 0))]
            + win * len(tables),
            out_specs=[pl.BlockSpec(tile, lambda g, b: (g, 0))] * len(tables),
        ),
        out_shape=[jax.ShapeDtypeStruct((groups * _SUBLANES, _LANES), t.dtype)
                   for t in tables],
        interpret=interpret,
    )(base, idx.reshape(-1, _LANES), *[t for t in tables for _ in (0, 1)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def coalesced_gather_pallas(tables: tuple[jax.Array, ...],
                            indices: jax.Array, *, interpret: bool = True):
    """Gather ``t[indices]`` for each 1-D 32-bit table ``t`` in ``tables``
    (all the same length, one kernel pass), assuming the window contract
    holds.  Returns a tuple of ``[n]`` arrays."""
    n = indices.shape[0]
    if n == 0:
        return tuple(jnp.zeros((0,), t.dtype) for t in tables)
    v = tables[0].shape[0]
    n_blocks = max(2, -(-v // TILE))
    views = tuple(jnp.pad(t, (0, n_blocks * TILE - v)).reshape(-1, _LANES)
                  for t in tables)
    idx = _pad_groups(indices)
    step = _MAX_GROUPS * TILE
    parts = [_gather_call(views, idx[s:s + step], interpret)
             for s in range(0, idx.shape[0], step)]
    return tuple(jnp.concatenate([p[i] for p in parts]).reshape(-1)[:n]
                 for i in range(len(tables)))


def window_contract_ok(indices: jax.Array) -> jax.Array:
    """True iff every group of ``TILE`` lanes spans < 2 aligned windows
    (kernel usable)."""
    g = _pad_groups(indices).reshape(-1, TILE)
    lo = jnp.min(g, axis=1) // TILE
    hi = jnp.max(g, axis=1)
    return jnp.all(hi < (lo + 2) * TILE)
