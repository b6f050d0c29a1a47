"""Ahead-of-time compiles of the main-path kernels for a TPU v5e chip.

Interpret mode cannot show what the chip's compiler refuses (tile-misaligned
blocks, SMEM/VMEM overflow, unsupported primitives, HBM relayouts), so these
tests compile for a described ``v5e:2x2`` topology with ``interpret=False``
at real widths.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.bfs import BFS_APP
from repro.core.pipeline import CapacityPolicy, FrontierPipeline
from repro.graphs.generators import kron
from repro.kernels.coalesced_gather import ops as gather_ops
from repro.kernels.segment_merge.segment_merge import segment_merge_pallas

ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("op", ["min", "add", "tagged"])
def test_segment_merge_compiles_for_v5e(sds, op):
    tags = sds((ROWS,), jnp.bool_) if op == "tagged" else None
    compiled = jax.jit(
        lambda i, v, t: segment_merge_pallas(i, v, t, op=op,
                                             interpret=False)).lower(
        sds((ROWS,), jnp.int32), sds((ROWS,), jnp.float32), tags).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_coalesced_gather_compiles_for_v5e(sds):
    """The co-gather of CSR columns and weights (the expansion's pallas
    path) compiles, and its tables stay lane-dense in HBM: a [rows, 2]
    layout would be padded to 128 lanes, 512 B per row."""
    compiled = jax.jit(
        lambda c, o, w: gather_ops.csr_edge_gather(c, o, w,
                                                   interpret=False)).lower(
        sds((ROWS,), jnp.int32), sds((ROWS,), jnp.int32),
        sds((ROWS,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * ROWS


@pytest.mark.parametrize("gather", ["xla", "pallas"])
def test_bfs_baseline_top_rung_compiles_for_v5e(sds, gather, monkeypatch):
    """The whole-traversal executable of the top capacity rung at kron
    scale 16 (Graph500 edge factor 16) fits one chip; with the pallas
    gather the kernel is compiled, not interpreted, inside the loop."""
    # default_backend() is the CPU here: steer the auto-detection to the
    # compiled kernel the chip would resolve to
    monkeypatch.setattr(gather_ops, "resolve_interpret",
                        lambda flag: False if flag is None else flag)
    g = kron(16, edge_factor=16)
    pipe = FrontierPipeline(g, BFS_APP, mode="baseline", gather=gather,
                            capacity_policy=CapacityPolicy(
                                n_buckets=2, min_capacity=1 << 16))
    state, mask = pipe.init(0)
    args = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                        (g, state, mask, jnp.int32(0), pipe._counts))
    compiled = pipe._run_b[-1].lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (gather == "pallas")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
