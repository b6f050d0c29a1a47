"""MoE expert dispatch through the IRU, in five minutes.

Expert routing is the paper's irregular access transplanted into an LM
stack: every token issues ``expert_buffer[route[i]] <- x[i]`` — duplicate
destinations, no locality.  This walkthrough shows the expert-dispatch
subsystem (``repro.moe``) end to end:

1. plan: the (token, expert) stream routed through the hash engine's
   occupancy machinery — expert id is the set key, expert capacity is the
   per-set slot bound, so capacity ranks, overflow drops and per-expert
   segment offsets fall out of set residency (no hand-rolled scan);
2. execute: scatter → segment-contiguous expert matmuls → weighted combine
   off the plan, with drop accounting bit-identical to the numpy oracle;
3. observe: per-layer dispatch stats (drop rate, expert load histogram);
4. ragged microbatches: ``n_live`` as a runtime operand — one trace serves
   every final-microbatch length;
5. expert parallelism: the same plan executed ``shard_map``-sharded over
   the banked engine's partition geometry on an IRU mesh.

    PYTHONPATH=src python examples/moe_dispatch.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.kernels.iru_reorder.ref import moe_dispatch_ref
from repro.launch.mesh import make_iru_mesh
from repro.models.common import Initializer
from repro.models.moe import init_moe, moe_ffn
from repro.moe import (capacity, dispatch_stats, format_stats, moe_hash,
                       moe_hash_ep, plan_dispatch)
from repro.moe.dispatch import _route, execute_plan
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

T, D, E, k, F = 256, 64, 8, 2, 96
moe = MoEConfig(n_experts=E, top_k=k, d_ff=F, capacity_factor=1.0)
it = Initializer(jax.random.PRNGKey(0), jnp.float32)
init_moe(it, D, moe, "swiglu")
params = it.params
x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)

print("== 1. Plan: hash-engine occupancy as the capacity rule ==")
C = capacity(T, moe)
gates, experts, aux = _route(params, x, moe)
plan = plan_dispatch(experts, gates, C, E)
rank, keep, counts, dropped = moe_dispatch_ref(np.asarray(experts), C, E)
np.testing.assert_array_equal(np.asarray(plan.keep), keep)
np.testing.assert_array_equal(np.asarray(plan.dropped), dropped)
print(f"capacity C={C} per expert; load histogram "
      f"{np.asarray(plan.counts).tolist()}; "
      f"{int(np.asarray(plan.dropped).sum())} overflow drops "
      f"(bit-identical to the numpy oracle)")

print("\n== 2. Execute: scatter -> expert matmuls -> combine ==")
y = execute_plan(params, x, plan, C, "swiglu")
y2, aux2 = moe_ffn(params, x, moe, "swiglu", dispatch="iru_hash")
np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6)
ys, _ = moe_ffn(params, x, moe, "swiglu", dispatch="iru_sorted")
print(f"output ({y.shape}) matches the sort-engine pipeline to "
      f"{float(jnp.abs(y - ys).max()):.2e} (fp regrouping only)")

print("\n== 3. Observe: per-layer dispatch stats ==")
_, _, st = moe_hash(params, x, moe, "swiglu", return_stats=True)
print(format_stats(st))

print("\n== 4. Ragged microbatches: n_live is a runtime operand ==")
f = jax.jit(lambda p, xx, m: moe_hash(p, xx, moe, "swiglu", n_live=m)[0])
for m in (T, T // 2, 10):
    ym = f(params, x, jnp.int32(m))
    assert float(jnp.abs(ym[m:]).max() if m < T else 0.0) == 0.0
print(f"one trace, three live lengths: cache_size={f._cache_size()} "
      f"(dead tokens contribute nothing)")

print("\n== 5. Expert parallelism: the banked partition geometry ==")
mesh = make_iru_mesh(4)
yep, _ = moe_hash_ep(params, x, moe, "swiglu", mesh, n_partitions=4,
                     compress=False)
np.testing.assert_allclose(np.asarray(yep), np.asarray(y), rtol=1e-5,
                           atol=1e-6)
print(f"shard_map over {dict(mesh.shape)} (experts stripe as e % nP, the "
      f"banked set % nP rule): matches the single-device planner; "
      f"compress=True carries the combine over int8 collectives")
