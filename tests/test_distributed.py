"""Multi-device tests (8 virtual CPU devices in subprocesses).

jax pins the device count at first init, so each scenario runs in a child
process with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_sharded_train_step_runs_on_2x4_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.configs.base import ParallelConfig, ShapeConfig
        from repro.data.pipeline import make_batch, batch_specs
        from repro.train.trainer import TrainConfig, init_state, make_train_step, abstract_state
        from repro.launch.mesh import make_auto_mesh
        from repro.launch.shardings import shard_tree, state_shardings

        mesh = make_auto_mesh((2, 4), ("data", "model"))
        cfg = smoke_config("qwen3-32b")
        pcfg = ParallelConfig(model_axis=4, remat="full", attn_chunk=32)
        tc = TrainConfig(warmup_steps=1, total_steps=10)
        shape = ShapeConfig("t", 64, 4, "train")
        st_shapes, param_specs = abstract_state(cfg, pcfg, tc)
        st_sh = state_shardings(st_shapes, param_specs, mesh)
        b_shapes, b_axes = batch_specs(cfg, shape)
        b_sh = shard_tree(b_shapes, b_axes, mesh)
        with mesh:
            step = jax.jit(make_train_step(cfg, pcfg, tc),
                           in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None))  # state feeds back
            state = init_state(cfg, pcfg, tc, jax.random.PRNGKey(0))
            for s in range(3):
                state, m = step(state, make_batch(cfg, shape, s))
            loss = float(m["loss"])
        assert np.isfinite(loss), loss
        # params really live distributed across the mesh
        emb = state["params"]["embed"]["tok"]
        assert len(emb.sharding.device_set) == 8
        print("OK", loss)
    """)
    assert "OK" in out


def test_elastic_restore_across_device_counts(tmp_path):
    """Save sharded on 8 devices, restore on 1 — elastic re-shard contract."""
    ckpt = str(tmp_path / "ck")
    run_py(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import save_checkpoint
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                           NamedSharding(mesh, P("data", "model")))
        save_checkpoint({ckpt!r}, 5, {{"w": w}})
        print("saved")
    """)
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.ckpt import restore_checkpoint, latest_step
        assert latest_step({ckpt!r}) == 5
        back = restore_checkpoint({ckpt!r}, {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}})
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.arange(64, dtype=np.float32).reshape(8, 8))
        print("restored OK")
    """, devices=1)
    assert "restored OK" in out


def test_int8_allreduce_shardmap():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.collectives import allreduce_int8
        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
        got = allreduce_int8(x, mesh, "data")
        expect = np.asarray(x).sum(0)
        rel = np.abs(np.asarray(got) - expect) / np.maximum(np.abs(expect), 1)
        assert rel.max() < 0.02, rel.max()   # int8 quantization tolerance
        print("OK")
    """)
    assert "OK" in out


def test_int8_allreduce_multirow_shards():
    """Shards wider than one row per device: exact local partial sum, then
    one int8 payload per device (regression: used to crash in an opaque
    reshape inside the shard_map body)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.collectives import allreduce_int8
        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.arange(24 * 16, dtype=jnp.float32).reshape(24, 16) / 7.0
        got = allreduce_int8(x, mesh, "data")  # 3 rows per device
        expect = np.asarray(x).sum(0)
        rel = np.abs(np.asarray(got) - expect) / np.maximum(np.abs(expect), 1)
        assert rel.max() < 0.02, rel.max()
        print("OK")
    """)
    assert "OK" in out


def test_int8_allreduce_indivisible_raises():
    """A leading dim that does not divide over the axis raises a loud
    ValueError naming the shape, before any tracing."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.dist.collectives import allreduce_int8
        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.zeros((12, 4), jnp.float32)
        try:
            allreduce_int8(x, mesh, "data")
        except ValueError as e:
            assert "(12, 4)" in str(e) and "'data'" in str(e), e
            print("OK raised")
        else:
            raise AssertionError("expected ValueError for 12 rows / 8 devices")
    """)
    assert "OK raised" in out


def test_dryrun_single_cell_machinery():
    """The dry-run driver end-to-end on the smallest cell (512 devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-130m",
         "--shape", "decode_32k", "--mesh", "single", "--force"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[ok]" in r.stdout, r.stdout
