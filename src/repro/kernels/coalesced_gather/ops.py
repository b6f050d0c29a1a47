"""jit'd wrapper: binned-gather fast path with timeout-style fallback.

Mirrors the IRU Data Replier: if the stream is well binned (window contract
holds) the block-reuse kernel services it; otherwise we fall back to the
baseline gather — worse coalescing, never a stall (paper §3.2.2 timeout).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.coalesced_gather.coalesced_gather import (
    coalesced_gather_pallas,
    window_contract_ok,
)
from repro.kernels.coalesced_gather.ref import coalesced_gather_ref
from repro.kernels.iru_reorder.ops import resolve_interpret


def _gather_columns(columns, indices, interpret):
    """``c[indices]`` for every 1-D column, through the kernel when the
    window contract holds and through ``jnp.take`` otherwise."""
    return jax.lax.cond(
        window_contract_ok(indices),
        lambda cs, i: coalesced_gather_pallas(cs, i, interpret=interpret),
        lambda cs, i: tuple(coalesced_gather_ref(c, i) for c in cs),
        tuple(columns),
        indices,
    )


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def coalesced_gather(
    table: jax.Array,
    indices: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``table[indices]`` for a ``[V]`` table of 32-bit values."""
    if not use_pallas:
        return coalesced_gather_ref(table, indices)
    return _gather_columns((table,), indices, resolve_interpret(interpret))[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def csr_edge_gather(
    col_idx: jax.Array,
    offsets: jax.Array,
    weights: Optional[jax.Array] = None,
    *,
    interpret: Optional[bool] = None,
):
    """Edge-array gather ``col_idx[offsets]`` (and optionally
    ``weights[offsets]``) through the block-reuse kernel.

    This is the expansion path of ``graphs.csr.expand_frontier``: an
    ascending node frontier makes CSR offsets monotone non-decreasing, so
    consecutive lanes read inside narrow aligned windows — the kernel's
    exact contract (violations fall back to the native gather, trading
    coalescing for progress, never correctness).  When ``weights`` is
    given, both edge arrays ride ONE kernel pass over the same windows.
    """
    interpret = resolve_interpret(interpret)
    if weights is None:
        return _gather_columns((col_idx.astype(jnp.int32),), offsets,
                               interpret)[0]
    return _gather_columns(
        (col_idx.astype(jnp.int32), weights.astype(jnp.float32)), offsets,
        interpret)
