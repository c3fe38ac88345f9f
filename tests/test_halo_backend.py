"""The "halo" Poisson backend on a forced multi-device CPU host: parity vs
the reference solver and the Pallas kernel, mixed-scenario engine collection,
golden-physics tolerances at n_ranks=2, and the executable-plan train() path.

Subprocess pattern follows tests/test_distributed.py: the parent test run
must see 1 device, so everything needing a real mesh runs in a child with
XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")
GOLDEN = str(Path(__file__).resolve().parent / "golden" / "cyl_re100_res8.npz")


def _run(code: str, timeout: int = 420) -> str:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu",   # never probe TPU/GPU in the child
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
           "HOME": "/tmp"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_halo_rank1_exact_equivalence():
    """n_ranks=1: the decomposed path IS the reference iteration for ANY
    inner_iters — edge ghosts are live, no neighbour halos exist, exactly
    ``iters`` sweep pairs run (the last outer round masks its tail), and
    the omega / polish schedule matches sweep for sweep."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.cfd import poisson
        from repro.launch.mesh import mesh_for_plan
        rhs = jax.random.normal(jax.random.PRNGKey(3), (34, 176))
        mesh = mesh_for_plan((1, 1))
        for iters, polish, inner in ((24, 6, 1), (60, 10, 1), (7, 0, 1),
                                     (50, 10, 4), (24, 6, 3)):
            a = np.asarray(poisson.solve(rhs, 0.125, 0.12, iters=iters,
                                         polish=polish))
            b = np.asarray(poisson.solve(rhs, 0.125, 0.12, iters=iters,
                                         polish=polish, backend="halo",
                                         mesh=mesh, halo_inner=inner))
            np.testing.assert_array_equal(a, b)
        print("EXACT_OK")
    """)
    assert "EXACT_OK" in out


def test_halo_multirank_parity_vs_reference_and_pallas():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.cfd import decomp, poisson
        from repro.kernels.poisson import ops as poisson_ops
        from repro.launch.mesh import mesh_for_plan
        rhs = jax.random.normal(jax.random.PRNGKey(3), (34, 176))
        res0 = float(np.linalg.norm(np.asarray(
            poisson.residual(jnp.zeros_like(rhs), rhs, 0.125, 0.12))))
        ref = np.asarray(poisson.solve(rhs, 0.125, 0.12, iters=400))
        scale = np.abs(ref).max()
        for r in (2, 4):
            # packed halo_inner=1 exchanges the updated parity before every
            # half-sweep, so the decomposed iteration IS the monolithic
            # red-black sweep — ulp-level agreement at ANY rank count
            mesh = mesh_for_plan((1, r))
            h = np.asarray(poisson.solve(rhs, 0.125, 0.12, iters=400,
                                         backend="halo", mesh=mesh,
                                         halo_inner=1))
            res = float(np.linalg.norm(np.asarray(poisson.residual(
                jnp.asarray(h), rhs, 0.125, 0.12))))
            assert res < 0.05 * res0, (r, res / res0)
            rel = np.abs(h - ref).max() / scale
            assert rel < 1e-5, (r, rel)      # calibrated: ~4e-7 (1 ulp)
        # the legacy full-grid path keeps the old block-Jacobi semantics of
        # the Pallas slab smoother: 2 slabs, refresh every pair, no polish
        # -> near-identical iterates
        pal = np.asarray(poisson_ops.rb_sor(rhs, 0.125, 0.12, iters=200,
                                            omega=1.7, nslabs=2,
                                            inner_iters=1, interpret=True,
                                            packed=False))
        h2 = np.asarray(decomp.decomposed_solve(
            rhs, mesh=mesh_for_plan((1, 2)), dx=0.125, dy=0.12, iters=200,
            polish=0, inner_iters=1, packed=False))
        rel = np.abs(h2 - pal).max() / np.abs(pal).max()
        assert rel < 1e-4, rel               # calibrated: 2.6e-5
        # and the packed slab kernel agrees with the unpacked one
        pal_p = np.asarray(poisson_ops.rb_sor(rhs, 0.125, 0.12, iters=200,
                                              omega=1.7, nslabs=2,
                                              inner_iters=1, interpret=True))
        rel = np.abs(pal_p - pal).max() / np.abs(pal).max()
        assert rel < 1e-4, rel               # calibrated: 7.2e-7
        print("PARITY_OK")
    """)
    assert "PARITY_OK" in out


def test_halo_packed_exchange_bytes_halved():
    """Acceptance criterion: the packed halo backend's per-exchange message
    is half-width — every ppermute operand in the traced program ships
    ceil(ny/2) scalars, where the legacy full-grid path ships ny — and the
    loose-coupling (inner_iters > 1) rounds keep the full-column volume in
    ONE message pair per round."""
    out = _run("""
        import jax, numpy as np
        from repro.cfd import decomp
        from repro.launch.mesh import mesh_for_plan
        rhs = jax.random.normal(jax.random.PRNGKey(0), (34, 176))
        mesh = mesh_for_plan((1, 4))

        def shapes(**kw):
            return set(decomp.ppermute_message_shapes(
                lambda r: decomp.decomposed_solve(
                    r, mesh=mesh, dx=0.125, dy=0.12, iters=60, **kw), rhs))

        packed = shapes(inner_iters=1)
        legacy = shapes(inner_iters=1, packed=False)
        jacobi = shapes(inner_iters=4)
        assert packed == {(17, 1)}, packed       # ny//2: bytes halved
        assert legacy == {(34, 1)}, legacy       # ny: the old full column
        assert jacobi == {(34, 1)}, jacobi       # both parities, one message
        assert decomp.halo_exchange_values(34) * 2 \\
            == decomp.halo_exchange_values(34, packed=False)
        print("BYTES_OK")
    """)
    assert "BYTES_OK" in out


def test_halo_engine_mixed_scenario_batch():
    """A heterogeneous scenario batch stepped through the engine's compute
    core (vmap of env_step over the batch, halo backend, (2, 2) hybrid
    mesh, batch placed by shard_env_batch) matches the reference backend
    within solver tolerance.  Actions are a FIXED shared sequence — a
    stochastic policy would chaos-amplify the tiny solver differences into
    trajectory divergence, which is physics, not a defect."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.cfd.env import CylinderEnv, EnvConfig
        from repro.cfd.grid import GridConfig
        from repro.core.plan import ParallelPlan
        from repro.drl.engine import shard_env_batch
        from repro.launch.mesh import mesh_for_plan

        cfg = EnvConfig(grid=GridConfig(res=6, dt=0.012, poisson_iters=40),
                        steps_per_action=5, warmup_time=2.0)
        scenarios = ("cyl_re100", "cyl_re100_rotary", "cyl_re200",
                     "cyl_re100")
        actions = jnp.array([0.3, -0.2, 0.1])

        def rollout(backend, mesh, n_ranks):
            env = CylinderEnv(cfg, backend=backend, mesh=mesh)
            st_b, obs_b = env.reset_batch(scenarios, 4)
            if mesh is not None:
                st_b = shard_env_batch(mesh, st_b, n_ranks)

            def period(st_b, a):
                st_b, out = jax.vmap(env.env_step, in_axes=(0, None))(st_b,
                                                                      a)
                return st_b, out

            _, outs = jax.jit(lambda s: jax.lax.scan(period, s, actions))(
                st_b)
            return outs

        mesh = mesh_for_plan(ParallelPlan(4, 2, 2))
        o_ref = rollout(None, None, 1)
        o_halo = rollout("halo", mesh, 2)
        for f in ("reward", "cd", "cl", "obs"):
            a = np.asarray(getattr(o_ref, f))
            b = np.asarray(getattr(o_halo, f))
            assert np.isfinite(b).all(), f
            d = np.abs(a - b).max()
            assert d < 0.05, (f, d)
        print("MIXED_OK")
    """)
    assert "MIXED_OK" in out


def test_halo_golden_physics_at_two_ranks():
    """Acceptance criterion: trajectories integrated through the halo
    backend at n_ranks=2 stay inside the golden-physics tolerances
    (the golden test's tolerances, from cfd.validation)."""
    out = _run(f"""
        import numpy as np
        from repro.cfd import solver
        from repro.cfd.grid import GridConfig
        from repro.cfd.validation import (TOL_AMP, TOL_CD, TOL_ST,
                                          measure_shedding, run_uncontrolled)
        from repro.launch.mesh import mesh_for_plan

        ref = np.load({GOLDEN!r})
        cfg = GridConfig(res=int(ref["res"]), dt=float(ref["dt"]),
                         poisson_iters=int(ref["poisson_iters"]))
        state = solver.FlowState(u=ref["u"], v=ref["v"], p=ref["p"])
        mesh = mesh_for_plan((1, 2))
        _, cds, cls = run_uncontrolled(cfg, state, int(ref["meas_steps"]),
                                       backend="halo", mesh=mesh)
        stats = measure_shedding(cds, cls, cfg.dt)
        def rel(a, b):
            return abs(a - b) / abs(b)
        errs = dict(st=rel(stats["strouhal"], float(ref["strouhal"])),
                    cd=rel(stats["cd_mean"], float(ref["cd_mean"])),
                    amp=rel(stats["cl_amp"], float(ref["cl_amp"])))
        assert errs["st"] < TOL_ST, errs
        assert errs["cd"] < TOL_CD, errs
        assert errs["amp"] < TOL_AMP, errs
        print("GOLDEN_OK", errs)
    """)
    assert "GOLDEN_OK" in out


def test_train_plan_auto_measures_selects_executes():
    """Acceptance criterion: one train(TrainConfig(plan="auto")) call on a
    forced 4-device host measures, selects and EXECUTES a plan; and
    optimize_plan on the refit model keeps the paper's n_ranks=1 optimum."""
    out = _run("""
        import numpy as np
        from repro.cfd.env import EnvConfig
        from repro.cfd.grid import GridConfig
        from repro.core.autotune import autotune
        from repro.core.plan import optimize_plan
        from repro.drl.ppo import PPOConfig
        from repro.drl.train import TrainConfig, train

        logs = []
        hist, params = train(TrainConfig(
            env=EnvConfig(grid=GridConfig(res=6, dt=0.012,
                                          poisson_iters=40),
                          steps_per_action=4, actions_per_episode=4,
                          warmup_time=1.5),
            ppo=PPOConfig(epochs=2, minibatches=2),
            n_envs=4, episodes=2, plan="auto"), log_fn=logs.append)
        assert any("plan[auto]" in l for l in logs), logs
        assert len(hist["reward"]) == 2
        assert np.isfinite(hist["reward"]).all()
        print("LOG:", [l for l in logs if "plan[auto]" in l][0])

        # the refit cost model keeps the paper's headline optimum
        rp = autotune(grid=GridConfig(res=4, dt=0.01, poisson_iters=20),
                      smoke=True)
        best60 = optimize_plan(60, rp.model)
        assert best60.n_ranks == 1, best60
        assert rp.plan.n_ranks == 1, rp.plan
        print("AUTO_OK")
    """)
    assert "AUTO_OK" in out
    assert "plan[auto]" in out


def test_train_forced_hybrid_plan_runs_halo():
    """train() with an explicit hybrid ParallelPlan executes the halo
    backend (n_ranks=2) end to end with finite physics."""
    out = _run("""
        import numpy as np
        from repro.cfd.env import EnvConfig
        from repro.cfd.grid import GridConfig
        from repro.core.plan import ParallelPlan
        from repro.drl.ppo import PPOConfig
        from repro.drl.train import TrainConfig, train

        logs = []
        hist, _ = train(TrainConfig(
            env=EnvConfig(grid=GridConfig(res=6, dt=0.012,
                                          poisson_iters=40),
                          steps_per_action=4, actions_per_episode=4,
                          warmup_time=1.5),
            ppo=PPOConfig(epochs=2, minibatches=2),
            n_envs=4, episodes=2, plan=ParallelPlan(4, 2, 2)),
            log_fn=logs.append)
        plan_line = [l for l in logs if "plan[explicit]" in l][0]
        assert "'halo'" in plan_line, plan_line
        assert "2 x 2" in plan_line, plan_line
        assert np.isfinite(hist["reward"]).all()
        assert np.isfinite(hist["cd"]).all()
        print("HYBRID_OK", plan_line)
    """)
    assert "HYBRID_OK" in out
