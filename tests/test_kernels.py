"""Per-kernel validation: Pallas (interpret=True) vs pure ref oracles,
swept over shapes, dtypes and configuration points."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.iru_reorder.ref import hash_reorder_ref
from repro.kernels.iru_reorder.ops import hash_reorder
from repro.kernels.segment_merge.ops import segment_merge
from repro.kernels.segment_merge.segment_merge import segment_merge_pallas
from repro.kernels.coalesced_gather.ops import coalesced_gather
from repro.kernels.coalesced_gather.coalesced_gather import (
    coalesced_gather_pallas,
    window_contract_ok,
)
from repro.core.filter import merge_sorted


# ---------------------------------------------------------------------------
# IRU reordering hash kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 64, 513, 2048])
@pytest.mark.parametrize("num_sets,slots", [(16, 4), (64, 8), (128, 32)])
def test_hash_reorder_matches_ref(n, num_sets, slots):
    rng = np.random.default_rng(n * 1000 + num_sets)
    idx = rng.integers(0, 4 * n + 1, n).astype(np.int32)
    sec = rng.random(n).astype(np.float32)
    ri, rs, rp, ra = hash_reorder_ref(idx, sec, num_sets=num_sets, slots=slots)
    st = hash_reorder(jnp.asarray(idx), jnp.asarray(sec), num_sets=num_sets, slots=slots)
    np.testing.assert_array_equal(ri, np.asarray(st.indices))
    np.testing.assert_array_equal(rp, np.asarray(st.positions))
    np.testing.assert_array_equal(ra, np.asarray(st.active))
    np.testing.assert_allclose(rs, np.asarray(st.secondary), rtol=1e-6)


@pytest.mark.parametrize("filter_op", ["add", "min", "max"])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_hash_reorder_filter_ops(filter_op, dtype):
    rng = np.random.default_rng(42)
    n = 777
    idx = rng.integers(0, 100, n).astype(np.int32)  # heavy duplication
    if dtype == np.float32:
        sec = rng.random(n).astype(dtype)
    else:
        sec = rng.integers(0, 1000, n).astype(dtype)
    ri, rs, rp, ra = hash_reorder_ref(idx, sec, num_sets=32, slots=8, filter_op=filter_op)
    st = hash_reorder(jnp.asarray(idx), jnp.asarray(sec), num_sets=32, slots=8,
                      filter_op=filter_op)
    np.testing.assert_array_equal(ri, np.asarray(st.indices))
    np.testing.assert_array_equal(ra, np.asarray(st.active))
    np.testing.assert_allclose(rs, np.asarray(st.secondary), rtol=1e-5, atol=1e-5)


def test_hash_reorder_is_permutation():
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 512, 1000).astype(np.int32)
    st = hash_reorder(jnp.asarray(idx), None, num_sets=64, slots=8)
    # (index, position) pairs are a permutation of the input
    np.testing.assert_array_equal(np.sort(np.asarray(st.positions)), np.arange(1000))
    np.testing.assert_array_equal(idx[np.asarray(st.positions)], np.asarray(st.indices))


@pytest.mark.parametrize("n,num_sets,slots", [(257, 16, 4), (400, 64, 8)])
@pytest.mark.parametrize("filter_op", [None, "add"])
def test_hash_reorder_pallas_engine_matches_ref(n, num_sets, slots, filter_op):
    """The element-sequential Pallas behavioural twin stays validated even
    though the default engine is the batch-parallel one."""
    rng = np.random.default_rng(n + slots)
    idx = rng.integers(0, 2 * n, n).astype(np.int32)
    sec = rng.random(n).astype(np.float32)
    ri, rs, rp, ra = hash_reorder_ref(idx, sec, num_sets=num_sets, slots=slots,
                                      filter_op=filter_op)
    st = hash_reorder(jnp.asarray(idx), jnp.asarray(sec), num_sets=num_sets,
                      slots=slots, filter_op=filter_op, engine="pallas")
    np.testing.assert_array_equal(ri, np.asarray(st.indices))
    np.testing.assert_array_equal(rp, np.asarray(st.positions))
    np.testing.assert_array_equal(ra, np.asarray(st.active))
    np.testing.assert_allclose(rs, np.asarray(st.secondary), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Segment merge kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 512, 1000, 4096])
@pytest.mark.parametrize("op", ["add", "min", "max"])
@pytest.mark.parametrize("chunk", [1024, 2048])
def test_segment_merge_matches_ref(n, op, chunk):
    rng = np.random.default_rng(n + len(op))
    idx = np.sort(rng.integers(0, max(n // 4, 2), n)).astype(np.int32)
    val = rng.random(n).astype(np.float32)
    m, surv = segment_merge_pallas(jnp.asarray(idx), jnp.asarray(val), op=op,
                                   chunk=chunk, interpret=True)
    mr, sr = merge_sorted(jnp.asarray(idx), jnp.asarray(val), op)
    np.testing.assert_array_equal(np.asarray(surv), np.asarray(sr))
    np.testing.assert_allclose(np.asarray(m)[np.asarray(surv)],
                               np.asarray(mr)[np.asarray(sr)], rtol=1e-5)


@pytest.mark.parametrize("n", [5, 1000, 4096])
def test_segment_merge_tagged_matches_ref(n):
    """Fused min|add families in one pass; a run's tag is its index's."""
    rng = np.random.default_rng(n)
    idx = np.sort(rng.integers(0, max(n // 4, 2), n)).astype(np.int32)
    val = rng.random(n).astype(np.float32)
    tags = (idx % 3 == 0)
    m, surv = segment_merge(jnp.asarray(idx), jnp.asarray(val), op="tagged",
                            chunk=1024, tags=jnp.asarray(tags))
    mr, sr = merge_sorted(jnp.asarray(idx), jnp.asarray(val), "tagged",
                          tags=jnp.asarray(tags))
    np.testing.assert_array_equal(np.asarray(surv), np.asarray(sr))
    s = np.asarray(sr)
    np.testing.assert_array_equal(np.asarray(m)[s & ~tags],
                                  np.asarray(mr)[s & ~tags])
    np.testing.assert_allclose(np.asarray(m)[s], np.asarray(mr)[s], rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_merge_dtypes(dtype):
    idx = jnp.asarray(np.sort(np.random.default_rng(1).integers(0, 30, 256)), jnp.int32)
    val = jnp.arange(256).astype(dtype)
    m, surv = segment_merge(idx, val, op="min", chunk=1024)
    mr, sr = merge_sorted(idx, val, "min")
    np.testing.assert_allclose(np.asarray(m)[np.asarray(surv)],
                               np.asarray(mr)[np.asarray(sr)])


# ---------------------------------------------------------------------------
# Coalesced gather kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [256, 1024, 4096])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_coalesced_gather_sorted_streams(rows, dtype):
    rng = np.random.default_rng(rows)
    table = (rng.random(rows) * 100).astype(dtype)
    idx = np.sort(rng.integers(0, rows, 512)).astype(np.int32)
    out = coalesced_gather(jnp.asarray(table), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(out), table[idx])


def test_coalesced_gather_fallback_on_scattered_stream():
    """Scattered streams violate the window contract -> baseline gather path."""
    rng = np.random.default_rng(3)
    table = rng.random(4096).astype(np.float32)
    idx = rng.integers(0, 4096, 256).astype(np.int32)  # unsorted, wide spread
    assert not bool(window_contract_ok(jnp.asarray(idx)))
    out = coalesced_gather(jnp.asarray(table), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(out), table[idx])


def test_coalesced_gather_pallas_direct():
    rng = np.random.default_rng(4)
    table = rng.random((1024, 8)).astype(np.float32)
    idx = np.sort(rng.integers(0, 1024, 128)).astype(np.int32)
    assert bool(window_contract_ok(jnp.asarray(idx)))
    cols = coalesced_gather_pallas(tuple(jnp.asarray(table).T),
                                   jnp.asarray(idx), interpret=True)
    np.testing.assert_array_equal(np.stack(cols, axis=1), table[idx])
