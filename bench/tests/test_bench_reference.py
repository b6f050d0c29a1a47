"""The benchmark's plain references on known graphs and against the host
oracles of the repository."""
import ml_dtypes
import numpy as np

from bench import reference as R


def path_graph():
    # 0 -> 1 -> 2 -> 3 (both ways), and 4 isolated; weights per direction
    src = np.array([0, 1, 1, 2, 2, 3])
    dst = np.array([1, 0, 2, 1, 3, 2])
    w = np.array([0.5, 0.5, 0.25, 0.25, 0.125, 0.125], np.float32)
    order = np.lexsort((dst, src))
    row_ptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=5))])
    return row_ptr, dst[order].astype(np.int32), w[order]


def test_known_answers():
    rp, ci, w = path_graph()
    np.testing.assert_array_equal(R.bfs_depths(rp, ci, 0),
                                  [0, 1, 2, 3, R.UNVISITED])
    np.testing.assert_array_equal(R.sssp_f32(rp, ci, w, 0),
                                  np.float32([0, 0.5, 0.75, 0.875, np.inf]))


def test_references_match_the_host_oracles():
    from bench.graphgen import make_graph
    from repro.apps.bfs import bfs
    from repro.apps.sssp import sssp
    from repro.graphs.csr import CSRGraph

    cfg = {"scale": 9, "structure_seed": 3, "edge_factor": 16,
           "initiator": [0.57, 0.19, 0.19], "weighted": True,
           "edge_capacity": 2 * (16 << 9)}
    (rp, ci, w), _ = make_graph(cfg)
    rp, ci, w = map(np.asarray, (rp, ci, w))
    g = CSRGraph(row_ptr=rp, col_idx=ci, weights=w)
    for root in np.nonzero(np.diff(rp)[:-1] > 0)[0][:3]:
        root = int(root)
        np.testing.assert_array_equal(R.bfs_depths(rp, ci, root), bfs(g, root))
        np.testing.assert_array_equal(R.sssp_f32(rp, ci, w, root),
                                      sssp(g, root))
        # each control reads far off the reference
        for kind in ("bfs", "sssp"):
            c = R.control(kind, rp, ci, w, root)
            exact = {"bfs": R.bfs_depths(rp, ci, root),
                     "sssp": R.sssp_f32(rp, ci, w, root)}[kind]
            assert np.sum(c != exact) > 0
    assert R.sssp_f32(rp, ci, w, 0, dtype=ml_dtypes.bfloat16).dtype == \
        ml_dtypes.bfloat16
