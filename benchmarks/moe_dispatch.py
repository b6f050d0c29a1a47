"""Beyond-paper benchmark: IRU (sorted/hash) vs dense one-hot MoE dispatch.

The LM-side analogue of the paper's coalescing story: routing tokens to
experts is an irregular access with duplicate destinations.  The dense
(GShard-style) dispatch pays O(T*E*C*D) einsum FLOPs and materializes a
(T, E, C) tensor; the IRU dispatches pay O(T*k*D) gather/scatter work —
``iru_sorted`` through the sort engine's emission ordering, ``iru_hash``
through the occupancy planner (``repro.moe.dispatch``), which skips the
emission sort entirely.  This harness measures compiled HLO FLOPs + bytes
for all three at a sweep of token counts, plus CPU wall time at the small
end, and extrapolates where the dense tensor stops fitting HBM.

Wall-clock follows the bench-harness hygiene (`benchmarks/iru_throughput._time`
best-of-N under a min-time budget; run under ``./bench.sh`` for the pinned
env) instead of a fixed 3-rep mean.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.iru_throughput import _time
from repro.configs.base import MoEConfig
from repro.models.common import Initializer
from repro.models import moe as moe_mod

E, K, D, F = 16, 2, 512, 1024
DISPATCHES = ("iru_sorted", "iru_hash", "dense")


def _params():
    it = Initializer(jax.random.PRNGKey(0), jnp.float32)
    moe = MoEConfig(n_experts=E, top_k=K, d_ff=F, capacity_factor=1.25)
    moe_mod.init_moe(it, D, moe, "swiglu")
    return it.params, moe


def measure(T: int, dispatch: str, params, moe, *, wall: bool = True) -> dict:
    x = jax.ShapeDtypeStruct((T, D), jnp.float32)

    def fn(p, xx):
        y, aux = moe_mod.moe_ffn(p, xx, moe, "swiglu", dispatch=dispatch)
        return y

    compiled = jax.jit(fn).lower(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params), x).compile()
    cost = compiled.cost_analysis()
    out = {"T": T, "dispatch": dispatch,
           "hlo_flops": float(cost.get("flops", 0)) if cost else 0.0,
           "hlo_bytes": float(cost.get("bytes accessed", 0)) if cost else 0.0}
    C = moe_mod.capacity(T, moe)
    out["dispatch_tensor_gb"] = T * E * C * 4 / 2**30 if dispatch == "dense" else 0.0
    if wall and T <= 8192:  # wall-clock at small scale only
        xr = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
        f = jax.jit(fn)
        best = _time(lambda: f(params, xr).block_until_ready())
        out["wall_ms"] = round(best * 1e3, 1)
    return out


def run():
    params, moe = _params()
    rows = []
    for T in (1024, 4096, 16384, 65536):
        for dispatch in DISPATCHES:
            rows.append(measure(T, dispatch, params, moe))
    # pairwise ratios: dense cost over each IRU engine's
    for T in (1024, 4096, 16384, 65536):
        d = next(r for r in rows if r["T"] == T and r["dispatch"] == "dense")
        for eng, tag in (("iru_sorted", "sorted"), ("iru_hash", "hash")):
            s = next(r for r in rows if r["T"] == T and r["dispatch"] == eng)
            rows.append({"T": T, "dispatch": f"RATIO dense/{tag}",
                         "hlo_flops": round(d["hlo_flops"] / max(s["hlo_flops"], 1), 2),
                         "hlo_bytes": round(d["hlo_bytes"] / max(s["hlo_bytes"], 1), 2),
                         "dispatch_tensor_gb": d["dispatch_tensor_gb"]})
    return rows


def main():
    print("T,dispatch,hlo_flops,hlo_bytes,dispatch_tensor_gb,wall_ms")
    for r in run():
        print(f"{r['T']},{r['dispatch']},{r['hlo_flops']},{r['hlo_bytes']},"
              f"{r.get('dispatch_tensor_gb', 0):.3f},{r.get('wall_ms', '')}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
