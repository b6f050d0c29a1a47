"""Plain references for the benchmark's answers, on the host, in numpy.

They import nothing of the program and take only the benchmark's own CSR
arrays (``bench.graphgen``) and sources:

* ``bfs_depths``: hop depth of every vertex from a root (``UNVISITED`` where
  unreached), from scipy's breadth-first order and its predecessor tree.
* ``sssp_f32``: shortest-path distances where a path's length is the float32
  left fold of its weights, the semantics of a float32 min-plus relaxation.
  A label-correcting (Bellman-Ford) sweep over the changed vertices reaches
  the one fixpoint: ``fl(x + w)`` is monotone in ``x``, so the fixpoint is the
  least fold over all paths whatever the order of relaxations.

Each takes ``dtype`` for the controls (``bench/control.py``): the same
computation held in a lower precision than the configuration states.
"""
from __future__ import annotations

import numpy as np

UNVISITED = np.iinfo(np.int32).max


def bfs_depths(row_ptr: np.ndarray, col_idx: np.ndarray, root: int, *,
               max_depth: int | None = None) -> np.ndarray:
    """int32 hop depths; ``max_depth`` stops the traversal early (the BFS
    control: a level left out)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import breadth_first_order

    n = row_ptr.shape[0] - 1
    adj = csr_matrix((np.ones(col_idx.shape[0], np.int8), col_idx, row_ptr),
                     shape=(n, n))
    order, pred = breadth_first_order(adj, root, directed=True,
                                      return_predecessors=True)
    depth = np.full(n, UNVISITED, np.int64)
    depth[root] = 0
    rest = order[1:]
    # BFS order lists every level after the one before it, so one pass in
    # order settles each vertex from its settled predecessor; done in
    # level-sized vectorised passes
    while rest.size:
        ready = depth[pred[rest]] != UNVISITED
        depth[rest[ready]] = depth[pred[rest[ready]]] + 1
        rest = rest[~ready]
    if max_depth is not None:
        depth[depth > max_depth] = UNVISITED
    return depth.astype(np.int32)


def sssp_f32(row_ptr: np.ndarray, col_idx: np.ndarray, weights: np.ndarray,
             root: int, *, dtype=np.float32) -> np.ndarray:
    """float32 distances (``inf`` where unreached), kept in ``dtype``."""
    n = row_ptr.shape[0] - 1
    w = weights.astype(dtype)
    dist = np.full(n, np.inf, dtype)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    while frontier.size:
        starts = row_ptr[frontier]
        counts = row_ptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        offs = np.repeat(starts - np.cumsum(counts) + counts, counts) + \
            np.arange(total)
        src = np.repeat(frontier, counts)
        dst = col_idx[offs]
        cand = (dist[src] + w[offs]).astype(dtype)
        better = cand < dist[dst]
        dst, cand = dst[better], cand[better]
        old = dist.copy()
        np.minimum.at(dist, dst, cand)
        frontier = np.nonzero(dist < old)[0]
    return dist


def control(kind: str, row_ptr: np.ndarray, col_idx: np.ndarray,
            weights: np.ndarray, source: int) -> np.ndarray:
    """The reference one step below what the configuration states, put in
    the program's place to show that the check fails it: SSSP held in
    bfloat16 (float32 is stated); BFS, which states no precision, breaks its
    guarantee of exact depths by leaving out the last level."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    if kind == "bfs":
        full = bfs_depths(row_ptr, col_idx, source)
        deepest = int(full[full != UNVISITED].max())
        return bfs_depths(row_ptr, col_idx, source,
                          max_depth=max(deepest - 1, 0))
    if kind == "sssp":
        return sssp_f32(row_ptr, col_idx, weights, source,
                        dtype=bf16).astype(np.float32)
    raise ValueError(f"no control for {kind!r}")
