"""jit'd public wrapper for the segment-merge kernel with CPU fallback."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.iru_reorder.ops import resolve_interpret
from repro.kernels.segment_merge.ref import segment_merge_ref
from repro.kernels.segment_merge.segment_merge import segment_merge_pallas


def segment_merge(
    sorted_indices: jax.Array,
    values: jax.Array,
    *,
    op: str = "add",
    chunk: int = 8192,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    tags: Optional[jax.Array] = None,
):
    """Merge duplicate adjacent indices; returns ``(merged, survivor_mask)``.

    ``op="tagged"`` fuses the min and add merge families in one kernel pass:
    ``tags`` marks each lane's family (False = min, True = add); equal
    indices always share a tag, so runs are uniform-tag by construction.
    """
    if (op == "tagged") != (tags is not None):
        raise ValueError("op='tagged' and tags go together")
    if not use_pallas:
        return segment_merge_ref(sorted_indices, values, op, tags=tags)
    return segment_merge_pallas(sorted_indices, values, tags, op=op,
                                chunk=chunk,
                                interpret=resolve_interpret(interpret))
