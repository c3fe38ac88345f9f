"""Multi-device semantics tests (subprocess: tests must normally see 1 device,
so anything needing a real mesh runs in a child process with forced host
devices)."""
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(code: str) -> str:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu",   # never probe TPU/GPU in the child
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
           "HOME": "/tmp"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_shard_map_matches_reference():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.models import moe as moe_mod, act_sharding
        from repro.models.moe_shard_map import apply_moe_expert_parallel
        cfg = ModelConfig(name="t", family="moe", source="", num_layers=1,
                          d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
                          vocab_size=100,
                          moe=MoEConfig(num_experts=8, top_k=2,
                                        d_ff_expert=32, num_shared_experts=1,
                                        capacity_factor=8.0),
                          param_dtype="float32", compute_dtype="float32")
        p = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
        ref, _ = moe_mod._moe_dispatch(cfg, p, x)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, act_sharding.activation_mesh(mesh):
            out, _ = jax.jit(lambda p, x: apply_moe_expert_parallel(
                cfg, p, x))(p, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        print("MOE_OK", err)
    """)
    assert "MOE_OK" in out


def test_decomposed_poisson_converges():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.cfd.decomp import make_decomposed_poisson
        from repro.cfd import poisson
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ny, nx = 48, 256
        rhs = jax.random.normal(jax.random.PRNGKey(0), (ny, nx))
        solve = make_decomposed_poisson(mesh, nx, dx=0.05, dy=0.05,
                                        inner_iters=4)
        with mesh:
            sol = solve(rhs, iters=400)
        r = poisson.residual(sol, rhs, 0.05, 0.05)
        r0 = poisson.residual(jnp.zeros_like(rhs), rhs, 0.05, 0.05)
        frac = float(jnp.linalg.norm(r) / jnp.linalg.norm(r0))
        assert frac < 0.10, frac
        # the MPI-analogue message pattern: exactly 2 halo ppermutes
        with mesh:
            txt = jax.jit(lambda r: solve(r, iters=400)
                          ).lower(rhs).compile().as_text()
        n = txt.count("collective-permute(")
        assert n == 2, n
        print("POISSON_OK", frac, n)
    """)
    assert "POISSON_OK" in out


def test_data_parallel_collect_runs_each_env_slice_alone():
    """A data-only plan rolls each device's envs out as a program of its
    own: every env's trajectory is bitwise what the same env gives rolled
    out alone with its key, for the reference and the Pallas backends."""
    out = _run("""
        import jax, numpy as np
        from repro.cfd.env import CylinderEnv, EnvConfig
        from repro.cfd.grid import GridConfig
        from repro.core.plan import ParallelPlan
        from repro.drl import networks, rollout
        from repro.drl.engine import (EngineConfig, RolloutEngine,
                                      broadcast_env_state, place_env_batch)
        from repro.launch.mesh import mesh_for_plan
        ecfg = EnvConfig(grid=GridConfig(res=6, dt=0.012, poisson_iters=40),
                         steps_per_action=4, actions_per_episode=3,
                         warmup_time=1.5)
        st0, obs0 = CylinderEnv(ecfg).reset()
        st_b, obs_b = broadcast_env_state(st0, obs0, 8)
        params = networks.init_actor_critic(
            networks.PolicyConfig(obs_dim=int(obs_b.shape[-1]), act_dim=1),
            jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        keys = jax.random.split(key, 8)
        mesh = mesh_for_plan(ParallelPlan(4, 4, 1))
        for backend in ("reference", "pallas"):
            env = CylinderEnv(ecfg, backend=backend, mesh=mesh)
            eng = RolloutEngine.for_env(env, EngineConfig(n_envs=8,
                                                          horizon=3),
                                        mesh=mesh)
            _, traj = eng.collect(params, place_env_batch(mesh, st_b),
                                  place_env_batch(mesh, obs_b), key,
                                  record=False)
            assert len(traj.reward.sharding.device_set) == 4
            for dev in range(4):
                sl = slice(2 * dev, 2 * dev + 2)
                _, alone = jax.jit(lambda s, o, k: rollout.rollout_keyed(
                    env.env_step, params, s, o, k, 3))(
                    jax.tree.map(lambda a: a[sl], st_b), obs_b[sl], keys[sl])
                for f in ("obs", "act", "reward", "cd", "cl"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(traj, f))[sl],
                        np.asarray(getattr(alone, f)), err_msg=f)
        print("DP_OK")
    """)
    assert "DP_OK" in out


def test_train_step_lowers_on_multidevice_mesh():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import get_config, INPUT_SHAPES, InputShape
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("phi4-mini-3.8b").reduced()
        shape = InputShape("t", 64, 8, "train")
        with mesh:
            jitted, args = steps.lowering_for(cfg, shape, mesh)
            compiled = jitted.lower(*args).compile()
        print("LOWER_OK", compiled.memory_analysis().temp_size_in_bytes)
    """)
    assert "LOWER_OK" in out
