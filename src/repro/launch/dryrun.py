DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the full program — ``train_step`` (model +
loss + AdamW) for training shapes, ``prefill`` for prefill shapes, and
``decode_step`` (one token against a full KV cache) for decode shapes — jits
it with the production in_shardings, calls ``.lower().compile()``, and
records:

  * ``memory_analysis()``  (bytes per device: argument/output/temp/peak)
  * ``cost_analysis()``    (HLO FLOPs + bytes accessed)
  * collective wire bytes  (parsed from the post-SPMD HLO, hlo_stats)
  * the derived three-term roofline (§Roofline)

Results are written incrementally to ``results/dryrun/<arch>__<shape>__<mesh>.json``
so a crashed sweep resumes where it stopped.

Usage::

    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both          # full sweep
    python -m repro.launch.dryrun --all --subprocess          # isolation
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, LM_SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.data.pipeline import batch_specs
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import default_pcfg, shard_tree, state_shardings
from repro.models import transformer as tfm
from repro.train.trainer import TrainConfig, abstract_state, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def _result_path(arch: str, shape: str, mesh_name: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_name}.json")


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_train(cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig, mesh):
    tc = TrainConfig()
    state_shapes, param_specs = abstract_state(cfg, pcfg, tc)
    st_sh = state_shardings(state_shapes, param_specs, mesh,
                            fsdp_params=pcfg.fsdp_params)
    b_shapes, b_axes = batch_specs(cfg, shape)
    b_sh = shard_tree(b_shapes, b_axes, mesh)
    step = make_train_step(cfg, pcfg, tc)
    # out state mirrors in state so the step chains (and donation aliases)
    jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None), donate_argnums=(0,))
    return jitted.lower(state_shapes, b_shapes)


def lower_prefill(cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig, mesh):
    params_shapes, param_specs = tfm.abstract_params(cfg, pcfg)
    p_sh = shard_tree(params_shapes, param_specs, mesh, zero=pcfg.fsdp_params)
    b_shapes, b_axes = batch_specs(cfg, shape)
    b_shapes.pop("labels", None)
    b_axes.pop("labels", None)
    b_sh = shard_tree(b_shapes, b_axes, mesh)
    cache_shapes = tfm.init_cache(cfg, pcfg, shape.global_batch, shape.seq_len, abstract=True)
    c_axes = _stacked_cache_axes(cfg, pcfg)
    c_sh = shard_tree(cache_shapes, c_axes, mesh)

    def fn(params, batch, cache):
        return tfm.prefill(params, cfg, pcfg, batch, cache)

    jitted = jax.jit(fn, in_shardings=(p_sh, b_sh, c_sh), donate_argnums=(2,))
    return jitted.lower(params_shapes, b_shapes, cache_shapes)


def lower_decode(cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig, mesh):
    params_shapes, param_specs = tfm.abstract_params(cfg, pcfg)
    p_sh = shard_tree(params_shapes, param_specs, mesh, zero=pcfg.fsdp_params)
    B = shape.global_batch
    cache_shapes = tfm.init_cache(cfg, pcfg, B, shape.seq_len, abstract=True)
    c_axes = _stacked_cache_axes(cfg, pcfg)
    c_sh = shard_tree(cache_shapes, c_axes, mesh)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = shard_tree(tok, ("batch", "seq"), mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, tokens, cache, pos):
        return tfm.decode_step(params, cfg, pcfg, tokens, cache, pos)

    jitted = jax.jit(fn, in_shardings=(p_sh, tok_sh, c_sh, None), donate_argnums=(2,))
    return jitted.lower(params_shapes, tok, cache_shapes, pos)


def _stacked_cache_axes(cfg: ModelConfig, pcfg: ParallelConfig):
    return tfm.cache_axes(cfg, pcfg)


LOWERERS = {"train": lower_train, "prefill": lower_prefill, "decode": lower_decode}


# ---------------------------------------------------------------------------
# Stage-depth extrapolation
#
# XLA's HloCostAnalysis visits a while-loop body ONCE — it cannot know trip
# counts — so cost/collective numbers of a scanned layer stack are
# undercounted by the repeat factor (verified: scan-of-4 matmuls reports 1/4
# the flops of the unrolled form).  The dry-run therefore lowers each cell at
# 1-unit and 2-unit stage depth (identical widths/shapes otherwise) and
# extrapolates every additive measurement linearly:
#
#     M(full) = M(1u) + (R-1) * [M(2u) - M(1u)]        per scanned stage
#
# This is exact for FLOPs/bytes/collective payloads (they are additive per
# unit) and slashes compile time for 72-88-layer archs.  Raw per-variant
# measurements are kept in the record for audit.
# ---------------------------------------------------------------------------

def _stage_geometry(cfg: ModelConfig):
    """(lead_layers, unit_len, dec_repeat, enc_repeat)."""
    lead = cfg.moe.first_dense_layers if cfg.moe else 0
    unit = 1 if lead else cfg.unit_len()
    rep = (cfg.n_layers - lead) // unit
    return lead, unit, rep, cfg.encoder_layers


def _variant(cfg: ModelConfig, dec_units: int, enc_layers: int) -> ModelConfig:
    lead, unit, _, enc = _stage_geometry(cfg)
    return dataclasses.replace(
        cfg,
        n_layers=lead + unit * dec_units,
        encoder_layers=enc_layers if enc else 0,
    )


def _measure(cfg_v: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig,
             mesh, n_dev: int, keep_hlo_path: str | None = None) -> dict:
    from repro.dist.sharding import use_mesh
    from repro.models.measure import measure_mode

    # measure with microbatches=1: the unrolled microbatch scan would
    # duplicate the whole fwd+bwd graph k times for identical per-step
    # FLOPs/bytes/collectives (accumulation is linear); activation-memory
    # effects of microbatching are covered by analytic_memory instead.
    pcfg = dataclasses.replace(pcfg, microbatches=1)
    t0 = time.monotonic()
    # use_mesh (not a bare `with mesh:`) so activation sharding constraints
    # inside the model (common.constrain) bind during lowering
    with use_mesh(mesh), measure_mode():
        lowered = LOWERERS[shape.kind](cfg_v, pcfg, shape, mesh)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        del compiled, lowered
    coll = hlo_stats.collective_stats(hlo, n_dev)
    if keep_hlo_path:
        with open(keep_hlo_path, "w") as f:
            f.write(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        "transcendentals": float(cost.get("transcendentals", 0.0)) if cost else 0.0,
        "wire_bytes": coll.wire_bytes_per_device,
        "coll_counts": coll.counts,
        "coll_result_bytes": coll.result_bytes,
        "memory_analysis": _mem_dict(mem),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }


_ADDITIVE = ("flops", "bytes_accessed", "transcendentals", "wire_bytes")


def _extrapolate(base: dict, delta_sets: list[tuple[int, dict]]) -> dict:
    """base + sum_s (rep_s - 1) * (two_s - base), per additive key."""
    out = {k: base[k] for k in _ADDITIVE}
    out["coll_counts"] = dict(base["coll_counts"])
    out["coll_result_bytes"] = dict(base["coll_result_bytes"])
    for rep, two in delta_sets:
        for k in _ADDITIVE:
            out[k] += (rep - 1) * max(two[k] - base[k], 0.0)
        for dk in ("coll_counts", "coll_result_bytes"):
            keys = set(out[dk]) | set(two[dk]) | set(base[dk])
            for kk in keys:
                d = max(two[dk].get(kk, 0) - base[dk].get(kk, 0), 0)
                out[dk][kk] = out[dk].get(kk, 0) + (rep - 1) * d
    return out


# ---------------------------------------------------------------------------
# Cell execution
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             pcfg: ParallelConfig | None = None, save: bool = True,
             keep_hlo: bool = False, mutate_cfg=None) -> dict:
    cfg = get_config(arch)
    if mutate_cfg is not None:
        cfg = mutate_cfg(cfg)
    shape = LM_SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "status": None,
    }
    if not ok:
        record.update(status="skipped", reason=why)
        if save:
            _save(record)
        return record
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_dev = mesh.size
    pcfg = pcfg or default_pcfg(cfg, shape, mesh)
    record["pcfg"] = dataclasses.asdict(pcfg)
    lead, unit, dec_rep, enc_rep = _stage_geometry(cfg)
    try:
        hlo_path = (_result_path(arch, shape_name, mesh_name) + ".hlo") if keep_hlo else None
        base = _measure(_variant(cfg, 1, min(enc_rep, 1)), pcfg, shape, mesh, n_dev,
                        keep_hlo_path=hlo_path)
        deltas: list[tuple[int, dict]] = []
        variants: dict = {"base_1unit": base}
        if dec_rep > 1:
            two = _measure(_variant(cfg, 2, min(enc_rep, 1)), pcfg, shape, mesh, n_dev)
            variants["dec_2unit"] = two
            if two["flops"] >= base["flops"]:
                deltas.append((dec_rep, two))
            else:
                # SPMD strategy flip between 1 and 2 units (observed: grok
                # prefill replicates the expert matmul at depth 1).  Anchor
                # on the stable 2-unit strategy: full = f(2u)+(R-2)[f(3u)-f(2u)]
                three = _measure(_variant(cfg, 3, min(enc_rep, 1)), pcfg, shape, mesh, n_dev)
                variants["dec_3unit"] = three
                base = two
                deltas.append((dec_rep - 1, three))
        if enc_rep > 1:
            enc2 = _measure(_variant(cfg, 1, 2), pcfg, shape, mesh, n_dev)
            deltas.append((enc_rep, enc2))
            variants["enc_2layer"] = enc2
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        record.update(status="failed", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        if save:
            _save(record)
        return record

    full = _extrapolate(base, deltas)
    roof = hlo_stats.Roofline(full["flops"], full["bytes_accessed"],
                              full["wire_bytes"], n_dev)
    mf = hlo_stats.model_flops(cfg, shape)
    record.update(
        status="ok",
        stage_geometry={"lead": lead, "unit": unit, "dec_repeat": dec_rep,
                        "enc_repeat": enc_rep},
        compile_s=sum(v["compile_s"] for v in variants.values()),
        memory_analysis=base["memory_analysis"],
        cost_analysis={"flops": full["flops"], "bytes_accessed": full["bytes_accessed"],
                       "transcendentals": full["transcendentals"]},
        collectives={"counts": full["coll_counts"],
                     "result_bytes": full["coll_result_bytes"],
                     "wire_bytes_per_device": full["wire_bytes"]},
        roofline=roof.as_dict(),
        model_flops=mf,
        useful_flops_ratio=(mf / (full["flops"] * n_dev)) if full["flops"] else None,
        analytic_memory=analytic_memory(cfg, pcfg, shape, n_dev),
        variants={k: {kk: vv for kk, vv in v.items() if kk != "memory_analysis"}
                  for k, v in variants.items()},
    )
    if keep_hlo:
        record["hlo_path"] = hlo_path
    if save:
        _save(record)
    return record


def analytic_memory(cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig,
                    n_dev: int) -> dict:
    """HBM-fit estimate per device (the CPU backend's memory_analysis does
    not run the TPU memory-assignment pipeline, so a structural estimate is
    the trustworthy signal for 16 GB/chip v5e).

    Params are TP/DP-sharded across the whole mesh for weights (model axis)
    and ZeRO-fragments for optimizer moments (all axes)."""
    n_params = cfg.params_billions() * 1e9
    model_axis = pcfg.model_axis
    denom = n_dev if pcfg.fsdp_params else model_axis  # FSDP: whole mesh
    param_bytes = n_params * 2 / denom                 # bf16 weights
    record = {"param_bytes_per_dev": param_bytes, "fsdp": pcfg.fsdp_params}
    if shape.kind == "train":
        # fp32 m+v ZeRO-sharded over the full mesh
        record["opt_bytes_per_dev"] = n_params * 8 / n_dev
        toks_per_dev = shape.global_batch * shape.seq_len / (n_dev / model_axis)
        toks_per_dev /= max(pcfg.microbatches, 1)
        # remat keeps ~2 fp32 residences of (tokens, d_model) per layer-unit
        record["act_bytes_per_dev"] = toks_per_dev * cfg.d_model * 4 * 2
    else:
        # KV cache per device
        kv_per_tok = 0.0
        for kind, i in zip(cfg.layer_kinds(), range(cfg.n_layers)):
            if kind != "attn":
                continue
            if cfg.attention == "mla":
                kv_per_tok += (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
            else:
                kv_per_tok += 2 * cfg.n_kv_heads * cfg.head_dim * 2
        cache_global = kv_per_tok * shape.seq_len * shape.global_batch
        # batch shards over data; kv_seq falls through to the (otherwise
        # idle) model axis -> the cache divides by the whole mesh
        record["cache_bytes_per_dev"] = cache_global / n_dev
    record["total_per_dev_gb"] = round(sum(v for k, v in record.items()) / 2**30, 3)
    record["fits_16gb"] = record["total_per_dev_gb"] < 16.0
    return record


def _mem_dict(mem) -> dict | None:
    if mem is None:
        return None
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes", "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out or {"repr": str(mem)}


def _save(record: dict) -> None:
    path = _result_path(record["arch"], record["shape"], record["mesh"])
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


# ---------------------------------------------------------------------------
# Sweep driver
# ---------------------------------------------------------------------------

def all_cells(mesh_names):
    for arch in ARCH_IDS:
        for shape in LM_SHAPES:
            for mesh_name in mesh_names:
                yield arch, shape, mesh_name


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(LM_SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="one subprocess per cell (memory isolation)")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        cells = list(all_cells(meshes))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape, m) for m in meshes]

    failures = 0
    for arch, shape, mesh_name in cells:
        path = _result_path(arch, shape, mesh_name)
        if not args.force and os.path.exists(path):
            with open(path) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skipped"):
                print(f"[cached] {arch} {shape} {mesh_name}: {prev['status']}")
                continue
        if args.subprocess:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_name]
            if args.force:
                cmd.append("--force")
            if args.keep_hlo:
                cmd.append("--keep-hlo")
            try:
                r = subprocess.run(cmd, cwd=os.getcwd(), timeout=2400)
                rc = r.returncode
            except subprocess.TimeoutExpired:
                rc = -1
                _save({"arch": arch, "shape": shape, "mesh": mesh_name,
                       "kind": LM_SHAPES[shape].kind, "status": "failed",
                       "error": "compile timeout (2400s)"})
                print(f"[TIMEOUT] {arch} {shape} {mesh_name}")
            if rc:
                failures += 1
            continue
        rec = run_cell(arch, shape, mesh_name, keep_hlo=args.keep_hlo)
        if rec["status"] == "ok":
            ra = rec["roofline"]
            print(f"[ok] {arch} {shape} {mesh_name}: compile={rec['compile_s']}s "
                  f"tc={ra['t_compute_s']:.3e} tm={ra['t_memory_s']:.3e} "
                  f"tx={ra['t_collective_s']:.3e} bound={ra['bottleneck']} "
                  f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)}")
        elif rec["status"] == "skipped":
            print(f"[skip] {arch} {shape} {mesh_name}: {rec['reason']}")
        else:
            failures += 1
            print(f"[FAIL] {arch} {shape} {mesh_name}: {rec['error']}")
    return 1 if failures else 0


if __name__ == "__main__":
    # the CLI lowers production meshes on forced host devices; importing
    # this module leaves the process's devices alone
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
