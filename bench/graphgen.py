"""Graph500 Kronecker graphs and their CSR, built on the device from a seed.

The benchmark makes its graphs itself, so a later change to the program
cannot move them, and so no run pays a host build in its set-up (a host
build of a scale-20 graph takes about 20 s).

* ``kron_edges``: Graph500's R-MAT generator (initiator A/B/C, edge factor,
  one random bit pair per level per edge), then a seeded permutation of the
  vertex labels, and an optional uniform [0, 1) weight per generated edge
  (Graph500 kernel 3).  The edges, their weights and the labels all come
  from the configuration's ``structure_seed``: every run of a configuration
  traverses one graph, as a Graph500 run does, and the run's seed draws only
  the roots.  Drawn per seed, different structures spread a scale-20 BFS's
  TEPS by 9% from seed to seed, different weights a scale-17 SSSP's by 7%,
  and different labels a scale-16 ``hash`` BFS's by 18% (quartile spreads,
  one v5e chip), far more than two runs of one seed differ.
* ``csr_from_edges``: the rules of ``repro.graphs.csr.from_edges(...,
  symmetrize=True)``: both directions of every edge, self-loops dropped,
  duplicates dropped keeping the first occurrence's weight, rows sorted by
  destination.  x64 is off, so the sort is on two int32 keys, not on
  ``src * n + dst``.

The CSR has a static edge capacity from the configuration, so the arrays'
shapes, and with them the program's executables, are fixed by the
configuration alone.  The edges left over past the real ones belong to one
extra pad vertex (id ``2**scale``) as self-loops: nothing reaches it, no
root is drawn from it, and the real vertices' rows are exactly
``from_edges``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """The seed as two uint32 words: seeds past 32 bits stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def seed_key(words: jax.Array) -> jax.Array:
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


def kron_edges(key: jax.Array, scale: int, edge_factor: int,
               initiator: tuple[float, float, float], weighted: bool):
    """(src, dst, weights) int32/int32/f32[edge_factor * 2**scale]: edges,
    weights and vertex labels, all from ``key``."""
    a, b, c = initiator
    n = 1 << scale
    m = edge_factor * n
    k_bits = key
    k_w, k_perm = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)

    def level(i, carry):
        src, dst = carry
        r1, r2 = jax.random.uniform(jax.random.fold_in(k_bits, i), (2, m))
        s_bit = r1 >= a + b
        d_bit = jnp.where(s_bit, r2 >= c / (1 - a - b), r2 >= a / (a + b))
        return (src * 2 + s_bit.astype(jnp.int32),
                dst * 2 + d_bit.astype(jnp.int32))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    w = (jax.random.uniform(k_w, (m,), jnp.float32) if weighted
         else jnp.ones((m,), jnp.float32))
    return perm[src], perm[dst], w


def csr_from_edges(src: jax.Array, dst: jax.Array, w: jax.Array, n: int,
                   edge_capacity: int):
    """Symmetrised, deduplicated CSR with ``n + 1`` rows (the last is the
    pad vertex).  Returns ``(row_ptr, col_idx, weights, n_edges)``, where
    ``n_edges`` is the count of real directed edges; the caller checks it
    against ``edge_capacity``."""
    s = jnp.concatenate([src, dst])
    d = jnp.concatenate([dst, src])
    ww = jnp.concatenate([w, w])
    loop = s == d
    # self-loops sort past every real edge and are dropped below
    s = jnp.where(loop, n, s)
    d = jnp.where(loop, n, d)
    # stable: among equal (src, dst) the first occurrence stays first
    s, d, ww = jax.lax.sort((s, d, ww), num_keys=2, is_stable=True)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    keep = first & (s < n)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    n_edges = pos[-1] + 1
    slot = jnp.where(keep, pos, edge_capacity)
    col_idx = jnp.full((edge_capacity,), n, jnp.int32).at[slot].set(
        d, mode="drop")
    weights = jnp.ones((edge_capacity,), jnp.float32).at[slot].set(
        ww, mode="drop")
    counts = jnp.zeros((n + 1,), jnp.int32).at[jnp.where(keep, s, n + 1)].add(
        1, mode="drop")
    counts = counts.at[n].set(edge_capacity - jnp.minimum(n_edges,
                                                          edge_capacity))
    row_ptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    return row_ptr, col_idx, weights, n_edges


@functools.partial(jax.jit, static_argnames=(
    "scale", "edge_factor", "initiator", "weighted", "edge_capacity"))
def kron_csr(words: jax.Array, *, scale: int, edge_factor: int,
             initiator: tuple[float, float, float], weighted: bool,
             edge_capacity: int):
    """One device program: seed words -> padded CSR arrays and the real
    edge count."""
    src, dst, w = kron_edges(seed_key(words), scale, edge_factor, initiator,
                             weighted)
    return csr_from_edges(src, dst, w, 1 << scale, edge_capacity)


def make_graph(cfg: dict):
    """(row_ptr, col_idx, weights) device arrays of the configuration's
    graph, and the real edge count.  Raises when the graph has more edges
    than the configuration's ``edge_capacity``."""
    row_ptr, col_idx, weights, n_edges = kron_csr(
        jnp.asarray(seed_words(cfg["structure_seed"])), scale=cfg["scale"],
        edge_factor=cfg["edge_factor"], initiator=tuple(cfg["initiator"]),
        weighted=cfg["weighted"], edge_capacity=cfg["edge_capacity"])
    n_edges = int(n_edges)
    if n_edges > cfg["edge_capacity"]:
        raise RuntimeError(
            f"{n_edges} directed edges exceed the configured "
            f"edge_capacity {cfg['edge_capacity']}")
    return (row_ptr, col_idx, weights), n_edges
