"""CI smoke: one transformer block forward + train step with hash dispatch.

Runs the deepseek smoke config (the registry's MoE arch, reduced to toy
widths) with ``MoEConfig(dispatch="iru_hash")`` through the full
plan → scatter → expert-matmul → combine path, interpret-safe on CPU:

* a transformer forward must produce finite logits and a finite aux loss;
* one ``train.make_train_step`` optimizer step must run end-to-end and
  produce a finite loss (the planned dispatch is differentiable);
* the three dispatch engines must agree on one MoE layer at the smoke
  size (allclose — fp scatter-add regrouping differs), with bit-identical
  drop accounting against the numpy oracle;
* the expert-parallel executor on the degenerate 1-device IRU mesh must
  match the single-device planner exactly (same program, mesh of one).

    PYTHONPATH=src python -m benchmarks.moe_smoke
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.configs.base import MoEConfig, ParallelConfig, ShapeConfig
from repro.data.pipeline import make_batch
from repro.kernels.iru_reorder.ref import moe_dispatch_ref
from repro.launch.mesh import make_iru_mesh
from repro.models.common import Initializer
from repro.models.moe import init_moe, moe_ffn
from repro.moe import capacity, moe_hash_ep, plan_dispatch
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import TrainConfig, init_state, make_train_step


def main() -> None:
    cfg = smoke_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="iru_hash"))
    assert cfg.moe.dispatch == "iru_hash"

    # --- one full train step through the planned dispatch ---------------
    pcfg = ParallelConfig(model_axis=1, microbatches=1, attn_chunk=64)
    tc = TrainConfig(adam=AdamWConfig(lr=1e-3), warmup_steps=1, total_steps=2)
    shape = ShapeConfig("smoke", 64, 2, "train")
    state = init_state(cfg, pcfg, tc, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, pcfg, tc))
    state, metrics = step(state, make_batch(cfg, shape, 0))
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"train-step loss not finite: {loss}"
    print(f"moe smoke: train step OK (arch={cfg.name}, dispatch=iru_hash, "
          f"loss={loss:.4f})")

    # --- 3-engine parity + oracle drop accounting on one layer -----------
    T, D, E, k, F = 64, 32, 8, 2, 48
    moe = MoEConfig(n_experts=E, top_k=k, d_ff=F, capacity_factor=8.0)
    it = Initializer(jax.random.PRNGKey(1), jnp.float32)
    init_moe(it, D, moe, "swiglu")
    params = it.params
    x = jax.random.normal(jax.random.PRNGKey(2), (T, D), jnp.float32)
    outs = {d: moe_ffn(params, x, moe, "swiglu", dispatch=d)
            for d in ("iru_hash", "iru_sorted", "dense")}
    for d in ("iru_sorted", "dense"):
        np.testing.assert_allclose(
            np.asarray(outs["iru_hash"][0]), np.asarray(outs[d][0]),
            rtol=1e-4, atol=1e-5, err_msg=f"iru_hash vs {d} diverged")
        assert float(outs["iru_hash"][1]) == float(outs[d][1]), "aux diverged"

    C = capacity(T, moe)
    from repro.moe.dispatch import _route
    gates, experts, _ = _route(params, x, moe)
    plan = plan_dispatch(experts, gates, C, E)
    rank, keep, counts, dropped = moe_dispatch_ref(np.asarray(experts), C, E)
    np.testing.assert_array_equal(np.asarray(plan.rank), rank)
    np.testing.assert_array_equal(np.asarray(plan.keep), keep)
    np.testing.assert_array_equal(np.asarray(plan.counts), counts)
    np.testing.assert_array_equal(np.asarray(plan.dropped), dropped)
    print("moe smoke: 3-engine parity OK, drop accounting bit-identical "
          "to oracle")

    # --- expert-parallel executor on the degenerate IRU mesh --------------
    mesh = make_iru_mesh(4)
    y_ep, aux_ep = moe_hash_ep(params, x, moe, "swiglu", mesh,
                               n_partitions=4, compress=False)
    np.testing.assert_allclose(
        np.asarray(y_ep), np.asarray(outs["iru_hash"][0]),
        rtol=1e-5, atol=1e-6,
        err_msg="expert-parallel executor diverged from planner")
    print(f"moe smoke OK: mesh={dict(mesh.shape)}, all engines agree")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
