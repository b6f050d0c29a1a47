"""Expert-parallel execution of a hash-engine dispatch plan.

The banked engine's geometry carries over verbatim: experts stripe across
partitions as ``expert % n_partitions`` (the banked ``set % nP`` rule), the
capacity buffer is laid out partition-major ``[nP, E/nP, C, D]`` — the
engine's bank rows — and the row stage runs under ``shard_map`` over
``iru_partition_axis(mesh)`` (``launch/mesh.make_iru_mesh`` builds the
mesh; a device owns ``nP / n_devices`` partitions, and the degenerate
1-device mesh exercises the identical program on a single host).

Each device runs its experts' FFN and combines *its own* lanes into a
per-device partial ``(T, D)`` output; the cross-device combine is the sum
of those partials, carried by the int8-compressed all-reduce from
``dist/collectives.py`` (``compress=False`` selects an exact fp32 sum —
the parity-test path).  Expert weights shard the same partition-major way,
so each device holds only its ``E/nP`` experts' parameters inside the
sharded region.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.dist.collectives import allreduce_int8
from repro.dist.sharding import resolve_spec
from repro.launch.shardings import iru_partition_axis
from repro.moe.dispatch import _experts_ffn, _route, capacity, plan_dispatch


def moe_hash_ep(params: dict, x: jax.Array, moe: MoEConfig, ffn_type: str,
                mesh, *, n_partitions: Optional[int] = None,
                n_live: Optional[jax.Array] = None, compress: bool = True):
    """x: (T, D) -> (T, D). Hash-planned dispatch, experts sharded over mesh.

    ``n_partitions`` defaults to the mesh's partition-axis size; it may
    exceed it (banked convention: a device then owns a block of
    ``nP / n_devices`` partitions) but must be divisible by it, and must
    divide ``n_experts``.
    """
    T, D = x.shape
    E = moe.n_experts
    C = capacity(T, moe)
    axis = iru_partition_axis(mesh)
    d = mesh.shape[axis]
    nP = n_partitions if n_partitions is not None else d
    if E % nP != 0:
        raise ValueError(f"n_experts={E} must split across {nP} partitions")
    if nP % d != 0:
        raise ValueError(
            f"n_partitions={nP} must be divisible by mesh axis "
            f"{axis!r} size {d}")
    Eper = E // nP           # experts per partition
    B = nP // d              # partitions per device (banked block)

    gates, experts, aux = _route(params, x, moe, n_live=n_live)
    plan = plan_dispatch(experts, gates, C, E, n_partitions=nP, n_live=n_live)

    # partition-major expert permutation: expert e lives in partition e%nP
    # (the banked set%nP stripe); perm lists experts partition-major, prow
    # maps expert id -> its row in that layout.
    perm = jnp.argsort(jnp.arange(E, dtype=jnp.int32) % nP, stable=True)
    prow = jnp.zeros((E,), jnp.int32).at[perm].set(jnp.arange(E, dtype=jnp.int32))
    slot_p = jnp.where(plan.keep, prow[plan.expert] * C + plan.rank, E * C)

    # bank rows: scatter token payloads into the partition-major capacity
    # buffer, then view as [nP, E/nP, C, D] for the shard_map row stage
    rows = jnp.zeros((E * C, D), x.dtype)
    rows = rows.at[slot_p].set(jnp.take(x, plan.src_tok, axis=0), mode="drop")
    rows = rows.reshape(nP, Eper, C, D)
    row_spec = resolve_spec(("iru_part", None, None, None), rows.shape, mesh)

    weights = [params["wi"][perm].reshape(nP, Eper, D, -1)]
    if ffn_type == "swiglu":
        weights.append(params["wg"][perm].reshape(nP, Eper, D, -1))
    weights.append(params["wo"][perm].reshape(nP, Eper, -1, D))

    def row_stage(rows_l, slot_l, keep_l, part_l, src_l, gate_l, *w_l):
        blk = jax.lax.axis_index(axis)                  # this device's block
        pl = {"wi": w_l[0].reshape(B * Eper, D, -1),
              "wo": w_l[-1].reshape(B * Eper, -1, D)}
        if len(w_l) == 3:
            pl["wg"] = w_l[1].reshape(B * Eper, D, -1)
        out = _experts_ffn(pl, rows_l.reshape(B * Eper, C, D), ffn_type)
        out = out.reshape(B * Eper * C, D)
        # combine only the lanes whose expert lives on this device's block
        local = keep_l & (part_l // B == blk)
        loc = jnp.clip(slot_l - blk * (B * Eper * C), 0, B * Eper * C - 1)
        gathered = jnp.where(local[:, None], jnp.take(out, loc, axis=0), 0)
        y = jnp.zeros((T, D), jnp.float32).at[src_l].add(
            gathered.astype(jnp.float32) * gate_l[:, None], mode="drop")
        return y[None]                                  # [1, T, D] per device

    lane_spec = P()                                     # lane arrays replicated
    y_parts = jax.shard_map(
        row_stage, mesh=mesh,
        in_specs=(row_spec, lane_spec, lane_spec, lane_spec, lane_spec,
                  lane_spec) + (P(axis, None, None, None),) * len(weights),
        out_specs=P(axis, None, None),
        check_vma=False,
    )(rows, slot_p, plan.keep, plan.partition, plan.src_tok, plan.gate,
      *weights)                                         # [d, T, D] partials

    if compress and d > 1:
        y = allreduce_int8(y_parts, mesh, axis)         # int8-compressed combine
    else:
        y = jnp.sum(y_parts, axis=0)
    return y.astype(x.dtype), aux
