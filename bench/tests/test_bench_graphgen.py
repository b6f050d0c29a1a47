"""The device Kronecker CSR against ``from_edges`` on the same edge list."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import graphgen
from repro.graphs.csr import from_edges

INIT = (0.57, 0.19, 0.19)


@pytest.mark.parametrize("scale,seed,weighted", [
    (7, 1, False), (9, 2 ** 33 + 5, True)])
def test_device_csr_equals_from_edges(scale, seed, weighted):
    n, m = 1 << scale, 16 << scale
    key = graphgen.seed_key(jnp.asarray(graphgen.seed_words(seed)))
    src, dst, w = jax.jit(graphgen.kron_edges, static_argnums=(1, 2, 3, 4))(
        key, scale, 16, INIT, weighted)
    ref = from_edges(np.asarray(src), np.asarray(dst), n, np.asarray(w),
                     symmetrize=True)
    cap = 2 * m
    row_ptr, col_idx, weights, n_edges = jax.jit(
        graphgen.csr_from_edges, static_argnums=(3, 4))(src, dst, w, n, cap)
    e = int(n_edges)
    assert e == ref.n_edges
    np.testing.assert_array_equal(np.asarray(row_ptr)[:n + 1], ref.row_ptr)
    np.testing.assert_array_equal(np.asarray(col_idx)[:e], ref.col_idx)
    np.testing.assert_array_equal(np.asarray(weights)[:e], ref.weights)
    # the spare capacity is the pad vertex's self-loops
    assert int(row_ptr[-1]) == cap
    assert np.all(np.asarray(col_idx)[e:] == n)


def test_make_graph_is_fixed_by_the_config():
    cfg = {"scale": 8, "structure_seed": 5, "edge_factor": 16,
           "initiator": list(INIT), "weighted": True,
           "edge_capacity": 2 * (16 << 8)}
    (a, ca, wa), ea = graphgen.make_graph(cfg)
    (b, cb, wb), eb = graphgen.make_graph(cfg)
    assert ea == eb and np.array_equal(a, b) and np.array_equal(ca, cb)
    assert np.array_equal(wa, wb)
    # another structure seed is another graph, seeds past 32 bits too
    (c, _, _), _ = graphgen.make_graph(dict(cfg, structure_seed=5 + 2 ** 32))
    assert sorted(np.diff(a)) != sorted(np.diff(c))
    with pytest.raises(RuntimeError, match="exceed"):
        graphgen.make_graph(dict(cfg, edge_capacity=ea - 1))
