"""Compile the main-path kernels for a described TPU v5e, with no chip.

The TPU compiler is installed with jaxlib, and it compiles for a topology
that is described rather than attached.  These compiles catch what
interpret mode cannot: a primitive the Pallas TPU lowering lacks, a layout
Mosaic refuses, a mesh program the partitioner rejects.  Nothing runs, so
they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.cfd import decomp, solver
from repro.cfd.grid import GridConfig, build_geometry
from repro.kernels.actuation import kernel as actuation_kernel
from repro.kernels.poisson import ops as poisson_ops

N_ENVS = 60          # the paper's env count, vmapped as train() runs it


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _grid(res):
    return GridConfig(res=res, dt=0.01, poisson_iters=50)


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4


@pytest.mark.parametrize("res", [8, 16])
def test_poisson_slab_kernel_compiles_vmapped(one_chip, res):
    """backend="pallas": the packed slab kernel over the env batch."""
    g = _grid(res)
    plane = _spec((N_ENVS, g.ny, g.nx // 2), one_chip)
    fn = jax.vmap(lambda r, b, rr, rb: poisson_ops.rb_sor_planes(
        r, b, rr, rb, g.dx, g.dy, iters=40, omega=g.poisson_omega,
        interpret=False))
    text = jax.jit(fn).lower(plane, plane, plane, plane).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's stable name is the instruction's, which the trace shows
    assert re.search(r"%\w*poisson_rb_sor_packed\w*\.\d+ = ", text)


@pytest.mark.parametrize("batch", [N_ENVS, 1])
def test_batched_sor_kernel_compiles(one_chip, monkeypatch, batch):
    """backend="reference" on TPU: the whole packed solve of the paper
    deployment's grid in one call whose block holds every env, vmapped as
    the rollout runs it; and the kernel alone at one env."""
    from repro.kernels.poisson import kernel as poisson_kernel
    monkeypatch.setattr(poisson_ops, "kernel_platform", lambda: "tpu")
    monkeypatch.setattr(poisson_ops, "_on_tpu", lambda: True)
    g = _grid(8)
    kw = dict(dx=g.dx, dy=g.dy, omega=g.poisson_omega, iters=g.poisson_iters,
              polish=10)
    if batch > 1:
        fn = jax.vmap(lambda *planes: poisson_ops.rb_sor_solve(*planes, **kw))
    else:
        fn = lambda *planes: poisson_kernel.rb_sor_batched(  # noqa: E731
            *planes, interpret=False, **kw)
    plane = _spec((batch, g.ny, g.nx // 2), one_chip)
    text = jax.jit(fn).lower(plane, plane, plane, plane).compile().as_text()
    # one call, its block: rows, then every env, then packed columns
    block = rf"f32\[{g.ny},{batch},{g.nx // 2}\]"
    assert len(re.findall(rf"%poisson_rb_sor_batched\.\d+ = \({block}",
                          text)) == 1


def test_actuation_megakernel_compiles_vmapped(one_chip):
    """backend="fused" on TPU: one dt of the megakernel over the env batch,
    at the paper deployment's grid."""
    g = _grid(8)
    ga = solver.geom_to_arrays(build_geometry(g))
    geom = tuple(None if a is None else _spec(a.shape, one_chip) for a in ga)
    batch = lambda *shape: _spec((N_ENVS,) + shape, one_chip)  # noqa: E731

    def one_dt(geom, u, v, red, black, jet, re, mode):
        return actuation_kernel.fused_step(
            g, solver.GeomArrays(*geom), u, v, red, black, jet, re, mode,
            interpret=False)

    fn = jax.vmap(one_dt, in_axes=(None, 0, 0, 0, 0, 0, 0, 0))
    compiled = jax.jit(fn).lower(
        geom, batch(g.ny, g.nx + 1), batch(g.ny + 1, g.nx),
        batch(g.ny, g.nx // 2), batch(g.ny, g.nx // 2),
        batch(), batch(), batch()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%\w*actuation_fused_dt\w*\.\d+ = ", text)


@pytest.mark.parametrize("backend", ["pallas", "fused"])
def test_data_parallel_collect_compiles_on_four_chips(topo, monkeypatch,
                                                      backend):
    """ParallelPlan(4, 4, 1): the engine's rollout with the env batch over
    four chips and a Pallas kernel in every env step.  The SPMD partitioner
    cannot split a Mosaic call, so each chip must run its own envs."""
    import numpy as np
    from repro.cfd.env import CylinderEnv, EnvConfig
    from repro.drl import networks
    from repro.drl.engine import (EngineConfig, RolloutEngine,
                                  broadcast_env_state)
    from repro.kernels.actuation import ops as actuation_ops
    ecfg = EnvConfig(grid=_grid(8), steps_per_action=25,
                     actions_per_episode=40, warmup_time=0.02)
    st0, obs0 = CylinderEnv(ecfg).reset()      # two dt on this host's CPU
    # trace the kernels as the chip runs them, not in interpret mode
    monkeypatch.setattr(poisson_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(actuation_ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4, 1),
                             ("data", "model"))
    env = CylinderEnv(ecfg, backend=backend, mesh=mesh)
    st_b, obs_b = jax.eval_shape(
        lambda s, o: broadcast_env_state(s, o, N_ENVS), st0, obs0)
    params = jax.eval_shape(lambda: networks.init_actor_critic(
        networks.PolicyConfig(obs_dim=obs_b.shape[-1], act_dim=1),
        jax.random.PRNGKey(0)))
    eng = RolloutEngine.for_env(
        env, EngineConfig(n_envs=N_ENVS, horizon=40), mesh=mesh)
    on = lambda tree, spec: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=NamedSharding(mesh, spec)),
        tree)
    text = eng._rollout.lower(
        on(params, P()), on(st_b, P("data")), on(obs_b, P("data")),
        on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), P())
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_halo_solve_compiles_on_2x2_mesh(topo):
    """backend="halo": x-slab decomposition over the "model" axis with the
    env batch on "data", as a ParallelPlan(4, 2, 2) places it."""
    import numpy as np
    g = _grid(16)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(2, 2),
                             ("data", "model"))
    field = _spec((2, g.ny, g.nx),
                  NamedSharding(mesh, P("data", None, "model")))
    fn = jax.vmap(lambda rhs, p0: decomp.decomposed_solve(
        rhs, p0, mesh=mesh, dx=g.dx, dy=g.dy, omega=g.poisson_omega,
        iters=g.poisson_iters, inner_iters=1))
    text = jax.jit(fn).lower(field, field).compile().as_text()
    assert "collective-permute" in text
