"""Expert-dispatch subsystem tests (repro.moe + kernels dispatch planner).

Contracts covered:
  * dense / iru_sorted / iru_hash produce the same MoE layer output
    (allclose — fp scatter-add regrouping differs) and the same aux loss,
    at non-binding AND binding capacity (binding parity only holds when
    the drop sets agree, so it doubles as an integer drop-set check);
  * the planner's ranks / keep mask / load counts / drop counts are
    bit-identical to the numpy oracle (``ref.moe_dispatch_ref``) across
    shapes, skew, and capacity regimes;
  * ragged ``n_live`` microbatches: dead tokens contribute nothing, live
    prefix matches the truncated run, counts see live lanes only, and
    varying ``n_live`` re-uses one trace (runtime operand, never a shape);
  * the expert-parallel executor (``repro.moe.ep``) matches the planner
    on the degenerate 1-device mesh exactly and on a real 4-device mesh
    (subprocess), with the int8-compressed combine within quantization
    tolerance;
  * gradients flow through the planned dispatch;
  * the checked-in BENCH_iru.json keeps the MoE throughput + HLO-ratio
    floors (the test_capacity / test_iru_ragged pattern).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import MoEConfig
from repro.kernels.iru_reorder.dispatch import hash_dispatch
from repro.kernels.iru_reorder.ref import moe_dispatch_ref
from repro.models.common import Initializer
from repro.models.moe import init_moe, moe_ffn
from repro.moe import (DispatchPlan, capacity, dispatch_stats, format_stats,
                       moe_dense, moe_hash, moe_hash_ep, moe_sorted,
                       plan_dispatch)
from repro.moe.dispatch import _route

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _toy(key, T, D, E, k, F, cf, ffn_type="swiglu", dtype=jnp.float32):
    moe = MoEConfig(n_experts=E, top_k=k, d_ff=F, capacity_factor=cf)
    it = Initializer(key, dtype)
    init_moe(it, D, moe, ffn_type)
    x = jax.random.normal(jax.random.fold_in(key, 1), (T, D), dtype)
    return it.params, moe, x


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ffn_type", ["swiglu", "gelu"])
def test_three_engine_parity_no_drops(ffn_type):
    params, moe, x = _toy(jax.random.PRNGKey(0), 96, 32, 8, 2, 48, 8.0,
                          ffn_type)
    yh, ah = moe_ffn(params, x, moe, ffn_type, dispatch="iru_hash")
    ys, as_ = moe_ffn(params, x, moe, ffn_type, dispatch="iru_sorted")
    yd, ad = moe_ffn(params, x, moe, ffn_type, dispatch="dense")
    np.testing.assert_allclose(np.asarray(yh), np.asarray(ys),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(yh), np.asarray(yd),
                               rtol=1e-4, atol=1e-5)
    assert float(ah) == float(as_) == float(ad)


def test_three_engine_parity_binding_capacity():
    """cf=0.25 forces real drops; parity then REQUIRES bit-identical drop
    sets (a lane dropped by one engine but kept by another would shift
    whole token rows)."""
    params, moe, x = _toy(jax.random.PRNGKey(3), 256, 16, 4, 2, 24, 0.5)
    C = capacity(x.shape[0], moe)
    gates, experts, _ = _route(params, x, moe)
    _, keep, counts, dropped = moe_dispatch_ref(np.asarray(experts), C,
                                                moe.n_experts)
    assert dropped.sum() > 0, "capacity must actually bind in this test"
    yh, _ = moe_ffn(params, x, moe, "swiglu", dispatch="iru_hash")
    ys, _ = moe_ffn(params, x, moe, "swiglu", dispatch="iru_sorted")
    yd, _ = moe_ffn(params, x, moe, "swiglu", dispatch="dense")
    np.testing.assert_allclose(np.asarray(yh), np.asarray(ys),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(yh), np.asarray(yd),
                               rtol=1e-4, atol=1e-5)
    plan = plan_dispatch(experts, gates, C, moe.n_experts)
    np.testing.assert_array_equal(np.asarray(plan.keep), keep)


def test_moe_ffn_rejects_n_live_on_unplanned_engines():
    params, moe, x = _toy(jax.random.PRNGKey(4), 32, 16, 4, 2, 24, 4.0)
    with pytest.raises(ValueError, match="iru_hash"):
        moe_ffn(params, x, moe, "swiglu", dispatch="iru_sorted",
                n_live=jnp.int32(16))


# ---------------------------------------------------------------------------
# planner vs numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,E,k,cap", [
    (64, 8, 2, 128),      # nothing drops
    (256, 4, 2, 16),      # uniform, binding
    (128, 16, 4, 8),      # many experts, deep k
    (500, 3, 1, 4),       # non-power-of-two everything
])
def test_plan_matches_oracle(T, E, k, cap):
    rng = np.random.default_rng(T * E + k)
    # zipf-ish skew so some experts overflow hard and some never fill
    p = 1.0 / np.arange(1, E + 1)
    experts = rng.choice(E, size=(T, k), p=p / p.sum()).astype(np.int32)
    gates = np.ones((T, k), np.float32) / k
    plan = plan_dispatch(jnp.asarray(experts), jnp.asarray(gates), cap, E)
    rank, keep, counts, dropped = moe_dispatch_ref(experts, cap, E)
    np.testing.assert_array_equal(np.asarray(plan.rank), rank)
    np.testing.assert_array_equal(np.asarray(plan.keep), keep)
    np.testing.assert_array_equal(np.asarray(plan.counts), counts)
    np.testing.assert_array_equal(np.asarray(plan.dropped), dropped)
    np.testing.assert_array_equal(np.asarray(plan.kept),
                                  np.minimum(counts, cap))
    # slot layout: expert-major segments, rank as the in-segment offset
    slot = np.asarray(plan.slot)
    flat_e = experts.reshape(-1)
    np.testing.assert_array_equal(slot[keep],
                                  (flat_e * cap + rank)[keep])
    assert (slot[~keep] == E * cap).all()
    # every kept lane owns a distinct capacity-buffer row
    assert len(np.unique(slot[keep])) == keep.sum()


def test_planner_generation_is_occupancy_round():
    """generation == rank // slots: the hash engine's flush round id."""
    sets = jnp.asarray(np.zeros(40, np.int32))
    rank, gen, live, counts = hash_dispatch(sets, num_sets=2, slots=8)
    np.testing.assert_array_equal(np.asarray(rank), np.arange(40))
    np.testing.assert_array_equal(np.asarray(gen), np.arange(40) // 8)
    assert np.asarray(live).all()
    np.testing.assert_array_equal(np.asarray(counts), [40, 0])


# ---------------------------------------------------------------------------
# ragged n_live
# ---------------------------------------------------------------------------

def test_ragged_prefix_matches_truncated_run():
    T, m = 128, 80
    params, moe, x = _toy(jax.random.PRNGKey(5), T, 32, 8, 2, 48, 8.0)
    yr, ar = moe_hash(params, x, moe, "swiglu", n_live=jnp.int32(m))
    # dead tokens must contribute nothing
    np.testing.assert_array_equal(np.asarray(yr[m:]), 0)
    # live prefix: same routing at fixed padded capacity -> same output
    C = capacity(T, moe)
    gates, experts, aux_small = _route(params, x[:m], moe)
    plan_small = plan_dispatch(experts, gates, C, moe.n_experts)
    from repro.moe.dispatch import execute_plan
    y_small = execute_plan(params, x[:m], plan_small, C, "swiglu")
    np.testing.assert_allclose(np.asarray(yr[:m]), np.asarray(y_small),
                               rtol=1e-5, atol=1e-6)
    # aux loss sees the live prefix only
    np.testing.assert_allclose(float(ar), float(aux_small), rtol=1e-6)


def test_ragged_plan_counts_live_only():
    T, E, k, cap, m = 100, 8, 2, 16, 37
    rng = np.random.default_rng(9)
    experts = rng.integers(0, E, (T, k)).astype(np.int32)
    gates = np.ones((T, k), np.float32) / k
    plan = plan_dispatch(jnp.asarray(experts), jnp.asarray(gates), cap, E,
                         n_live=jnp.int32(m))
    rank, keep, counts, dropped = moe_dispatch_ref(experts, cap, E, n_live=m)
    live = np.asarray(plan.live)
    assert live[:m * k].all() and not live[m * k:].any()
    np.testing.assert_array_equal(np.asarray(plan.keep), keep)
    np.testing.assert_array_equal(np.asarray(plan.counts), counts)
    np.testing.assert_array_equal(np.asarray(plan.dropped), dropped)
    # dead-lane ranks are sentinel-segment bookkeeping; compare live only
    np.testing.assert_array_equal(np.asarray(plan.rank)[:m * k],
                                  rank[:m * k])


def test_ragged_n_live_is_runtime_operand_one_trace():
    params, moe, x = _toy(jax.random.PRNGKey(6), 64, 16, 4, 2, 24, 4.0)

    @jax.jit
    def f(p, xx, m):
        y, aux = moe_hash(p, xx, moe, "swiglu", n_live=m)
        return y

    outs = [f(params, x, jnp.int32(m)) for m in (64, 40, 17, 0)]
    assert f._cache_size() == 1, "n_live must not retrace"
    np.testing.assert_array_equal(np.asarray(outs[-1]), 0)


# ---------------------------------------------------------------------------
# expert-parallel executor
# ---------------------------------------------------------------------------

def test_ep_degenerate_mesh_matches_planner():
    from repro.launch.mesh import make_iru_mesh

    params, moe, x = _toy(jax.random.PRNGKey(7), 64, 32, 8, 2, 48, 8.0)
    mesh = make_iru_mesh(4)
    y, aux = moe_hash(params, x, moe, "swiglu")
    for nP in (None, 2, 8):
        yep, auxep = moe_hash_ep(params, x, moe, "swiglu", mesh,
                                 n_partitions=nP, compress=False)
        np.testing.assert_allclose(np.asarray(yep), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)
        assert float(auxep) == float(aux)


def test_ep_validates_geometry():
    from repro.launch.mesh import make_iru_mesh

    params, moe, x = _toy(jax.random.PRNGKey(8), 32, 16, 8, 2, 24, 4.0)
    mesh = make_iru_mesh(1)
    with pytest.raises(ValueError, match="partitions"):
        moe_hash_ep(params, x, moe, "swiglu", mesh, n_partitions=3)


def test_ep_shard_map_multi_device_parity():
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_iru_mesh
        from repro.models.common import Initializer
        from repro.models.moe import init_moe
        from repro.moe import moe_hash, moe_hash_ep
        assert len(jax.devices()) == 4, jax.devices()
        mesh = make_iru_mesh(4)
        assert mesh.shape["part"] == 4
        T, D, E, k, F = 128, 32, 8, 2, 48
        moe = MoEConfig(n_experts=E, top_k=k, d_ff=F, capacity_factor=2.0)
        it = Initializer(jax.random.PRNGKey(0), jnp.float32)
        init_moe(it, D, moe, "swiglu")
        params = it.params
        x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
        y, aux = moe_hash(params, x, moe, "swiglu")
        # exact combine across 4 real devices (fp32 partial sums)
        ye, auxe = moe_hash_ep(params, x, moe, "swiglu", mesh,
                               n_partitions=8, compress=False)
        np.testing.assert_allclose(np.asarray(ye), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)
        assert float(auxe) == float(aux)
        # int8-compressed combine: within blockwise quantization tolerance
        yc, _ = moe_hash_ep(params, x, moe, "swiglu", mesh, compress=True)
        err = np.abs(np.asarray(yc) - np.asarray(y)).max()
        scale = np.abs(np.asarray(y)).max()
        assert err <= 0.05 * scale + 1e-3, (err, scale)
        # ragged through the sharded path
        yr, _ = moe_hash_ep(params, x, moe, "swiglu", mesh,
                            n_live=jnp.int32(70), compress=False)
        assert np.asarray(yr)[70:].max() == 0
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# training path
# ---------------------------------------------------------------------------

def test_grad_flows_through_hash_dispatch():
    params, moe, x = _toy(jax.random.PRNGKey(10), 64, 16, 4, 2, 24, 4.0)

    def loss(p):
        y, aux = moe_ffn(p, x, moe, "swiglu", dispatch="iru_hash")
        return jnp.sum(y ** 2) + 0.01 * aux

    grads = jax.grad(loss)(params)
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # the expert weights and the router must both receive signal
    assert float(jnp.abs(grads["wi"]).max()) > 0
    assert float(jnp.abs(grads["router"]).max()) > 0


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_dispatch_stats_accounting():
    T, E, k, cap = 64, 4, 2, 8
    rng = np.random.default_rng(2)
    experts = rng.integers(0, E, (T, k)).astype(np.int32)
    gates = np.ones((T, k), np.float32) / k
    plan = plan_dispatch(jnp.asarray(experts), jnp.asarray(gates), cap, E)
    probs = jnp.asarray(rng.random((T, E)).astype(np.float32))
    st = dispatch_stats(plan, probs=probs)
    assert int(st.n_routed) == T * k
    assert int(st.n_dropped) == int(np.asarray(plan.dropped).sum())
    np.testing.assert_allclose(float(st.drop_rate),
                               int(st.n_dropped) / (T * k), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(st.expert_load),
                                  np.asarray(plan.counts))
    np.testing.assert_allclose(np.asarray(st.load_fraction).sum(), 1.0,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(st.mean_prob),
                               np.asarray(probs).mean(0), rtol=1e-6)
    line = format_stats(st)
    assert "drop_rate" in line and "routed" in line
    # stats are a pytree: they cross jit boundaries like any activation
    leaves = jax.tree.leaves(st)
    assert all(isinstance(l, jax.Array) for l in leaves)


def test_moe_hash_return_stats():
    params, moe, x = _toy(jax.random.PRNGKey(11), 64, 16, 4, 2, 24, 4.0)
    y, aux, st = moe_hash(params, x, moe, "swiglu", return_stats=True)
    assert int(st.n_routed) == x.shape[0] * moe.top_k
    assert np.isfinite(float(st.drop_rate))


# ---------------------------------------------------------------------------
# benchmark plumbing + checked-in floors
# ---------------------------------------------------------------------------

def test_checked_in_bench_keeps_moe_floors():
    """MoE rows must exist in the committed BENCH_iru.json and stay above
    the floors: the planned engine's absolute throughput, and the
    deterministic dense-vs-hash HLO FLOP ratio (the accelerator story)."""
    bench = json.load(open(os.path.join(ROOT, "BENCH_iru.json")))
    tps = bench["moe_tokens_per_s"]
    for eng in ("dense", "iru_sorted", "iru_hash"):
        assert eng in tps and tps[eng], tps.keys()
    # generous absolute floor (CPU box variance) on the planned engine
    assert tps["iru_hash"]["4096"] >= 1_000, tps["iru_hash"]
    # dense pays the (T, E, C) dispatch/combine einsums; the ratio is a
    # compiled-HLO constant, not a timing
    assert bench["moe_dense_vs_hash_flops_4096"] >= 2.0, bench[
        "moe_dense_vs_hash_flops_4096"]
    assert bench["moe_dense_vs_hash_bytes_4096"] >= 1.0, bench[
        "moe_dense_vs_hash_bytes_4096"]
    assert "moe_rows" in bench["notes"]
