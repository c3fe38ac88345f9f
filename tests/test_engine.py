"""RolloutEngine: bitwise sync equivalence, async-vs-sync learning parity,
mesh path consistency, and TrajectorySink round trips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.drl import networks, rollout
from repro.drl import engine as engine_mod
from repro.drl.engine import (EngineConfig, FileSink, MemorySink,
                              RolloutEngine, SinkSpec,
                              broadcast_env_state, make_sink)
from repro.drl.gae import gae_batch
from repro.drl.ppo import Batch, PPOConfig
from repro.launch.mesh import make_debug_mesh


class _Out:
    def __init__(self, obs, reward):
        self.obs, self.reward = obs, reward
        self.cd = jnp.float32(0)
        self.cl = jnp.float32(0)


def _toy_step(st, a):
    new = st * 0.8 + jnp.array([0.5, 0.0, 0.0]) * a
    return new, _Out(new, -jnp.sum(new[:1] ** 2))


N, T = 8, 24
PCFG = networks.PolicyConfig(obs_dim=3, act_dim=1)
PPO = PPOConfig(lr=1e-3, epochs=4, minibatches=4)


def _setup():
    st0 = jnp.ones((N, 3)) * 2.0
    params = networks.init_actor_critic(PCFG, jax.random.PRNGKey(0))
    engine = RolloutEngine(_toy_step, EngineConfig(n_envs=N, horizon=T))
    return engine, params, st0


# ---------------------------------------------------------------------------
# sync mode == the reference vmap pipeline, bitwise
# ---------------------------------------------------------------------------

def test_engine_collect_matches_rollout_batch_bitwise():
    engine, params, st0 = _setup()
    key = jax.random.PRNGKey(42)
    batch, traj = engine.collect(params, st0, st0, key)

    @jax.jit
    def reference(params, st_b, obs_b, key):
        _, traj = rollout.rollout_batch(_toy_step, params, st_b, obs_b,
                                        key, T, N)
        values = networks.value(params, traj.obs)
        last_v = networks.value(params, traj.last_obs)
        adv, ret = gae_batch(traj.reward, values, last_v,
                             gamma=PPO.gamma, lam=PPO.lam)
        flat = lambda x: x.reshape((-1,) + x.shape[2:])
        return Batch(obs=flat(traj.obs), act=flat(traj.act),
                     logp_old=flat(traj.logp), adv=flat(adv),
                     ret=flat(ret)), traj

    ref_batch, ref_traj = reference(params, st0, st0, key)
    for a, b in zip(traj, ref_traj):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(batch, ref_batch):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_mesh_path_matches_plain():
    engine, params, st0 = _setup()
    mesh = make_debug_mesh(1, 1)
    sharded = RolloutEngine(_toy_step, EngineConfig(n_envs=N, horizon=T),
                            mesh=mesh)
    key = jax.random.PRNGKey(3)
    b0, t0 = engine.collect(params, st0, st0, key)
    b1, t1 = sharded.collect(params, st0, st0, key)
    np.testing.assert_allclose(np.asarray(t0.reward), np.asarray(t1.reward),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b0.adv), np.asarray(b1.adv),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# async mode: learns, and lands within noise of sync
# ---------------------------------------------------------------------------

def test_async_within_noise_of_sync():
    episodes = 25
    st0 = jnp.ones((N, 3)) * 2.0

    def run(mode):
        engine = RolloutEngine(_toy_step, EngineConfig(
            n_envs=N, horizon=T, gamma=PPO.gamma, lam=PPO.lam))
        params, optimizer, opt_state, key = engine.init(PCFG, PPO, seed=0)
        loop = engine.run_sync if mode == "sync" else engine.run_async
        _, _, returns = loop(params, opt_state, PPO, optimizer, st0, st0,
                             key, episodes)
        return returns

    sync = run("sync")
    asyn = run("async")
    # both learn ...
    assert np.mean(sync[-5:]) > np.mean(sync[:5]) + 0.1
    assert np.mean(asyn[-5:]) > np.mean(asyn[:5]) + 0.1
    # ... and the one-step staleness costs at most a noise-level gap on the
    # final performance (same seed, same number of env interactions)
    gap = abs(float(np.mean(sync[-5:]) - np.mean(asyn[-5:])))
    spread = float(np.std(sync[-10:]) + np.std(asyn[-10:])) + 0.05
    assert gap < 4 * spread, (gap, spread)


# ---------------------------------------------------------------------------
# trajectory sinks
# ---------------------------------------------------------------------------

def _collect_one():
    engine, params, st0 = _setup()
    _, traj = engine.collect(params, st0, st0, jax.random.PRNGKey(7))
    return traj


@pytest.mark.parametrize("codec", ["binary", "zstd"])
def test_file_sink_roundtrip(tmp_path, codec):
    sink = FileSink(str(tmp_path / codec), codec=codec)
    if codec == "zstd" and engine_mod.zstd is not None:
        assert sink.codec == "zstd"   # real zstd installed: no silent fallback
    traj = _collect_one()
    nb = sink.write(0, traj)
    assert nb > 0 and sink.bytes_written == nb and sink.episodes == 1
    back = sink.read(0)
    for a, b in zip(traj, back):
        if a is None or b is None:    # aux probe fields: absent both sides
            assert a is None and b is None
            continue
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6, atol=1e-7)
    with pytest.raises(KeyError):
        sink.read(99)
    sink.close()                      # close never destroys spilled data
    assert sink.read(0).obs.shape == back.obs.shape
    sink.cleanup()
    assert not sink.dir.exists()


def test_memory_sink_eviction():
    sink = MemorySink(keep=2)
    traj = _collect_one()
    for ep in range(4):
        sink.write(ep, traj)
    assert sink.episodes == 4
    with pytest.raises(KeyError):
        sink.read(0)
    np.testing.assert_array_equal(sink.read(3).obs, np.asarray(traj.obs))


def test_engine_records_to_sink():
    sink = MemorySink()
    engine = RolloutEngine(_toy_step, EngineConfig(n_envs=N, horizon=T),
                           sink=sink)
    params = networks.init_actor_critic(PCFG, jax.random.PRNGKey(0))
    st0 = jnp.ones((N, 3)) * 2.0
    engine.collect(params, st0, st0, jax.random.PRNGKey(1))
    engine.collect(params, st0, st0, jax.random.PRNGKey(2))
    assert sink.episodes == 2
    assert sink.read(1).obs.shape == (N, T, 3)


def test_run_async_spills_every_episode(tmp_path):
    """Async mode defers each spill until after the next update dispatch
    (to preserve overlap) but must still persist ALL episodes."""
    episodes = 5
    sink = FileSink(str(tmp_path), codec="binary")
    engine = RolloutEngine(_toy_step, EngineConfig(n_envs=N, horizon=T),
                           sink=sink)
    params, optimizer, opt_state, key = engine.init(PCFG, PPO, seed=0)
    st0 = jnp.ones((N, 3)) * 2.0
    engine.run_async(params, opt_state, PPO, optimizer, st0, st0, key,
                     episodes)
    assert sink.episodes == episodes
    for ep in range(episodes):
        assert sink.read(ep).obs.shape == (N, T, 3)
    sink.cleanup()


def test_make_sink_modes(tmp_path):
    assert make_sink("none") is None
    assert isinstance(make_sink("memory"), MemorySink)
    fs = make_sink("binary", str(tmp_path))
    assert isinstance(fs, FileSink)
    fs.cleanup()


def test_make_sink_unknown_mode_and_missing_root(tmp_path):
    with pytest.raises(ValueError, match="unknown sink mode"):
        make_sink("parquet", str(tmp_path))
    with pytest.raises(ValueError, match="root directory"):
        make_sink("binary")                       # file sink needs a root


def test_file_sink_unknown_codec():
    with pytest.raises(ValueError, match="unknown trajectory-sink codec"):
        FileSink("/tmp/never_created", codec="gzip")


def test_file_sink_read_before_write(tmp_path):
    sink = FileSink(str(tmp_path / "empty"))
    with pytest.raises(KeyError, match="episode 0"):
        sink.read(0)
    assert sink.episodes == 0 and sink.bytes_written == 0


def test_file_sink_cleanup_idempotent(tmp_path):
    sink = FileSink(str(tmp_path / "c"))
    sink.write(0, _collect_one())
    sink.cleanup()
    assert not sink.dir.exists()
    sink.cleanup()                                # second cleanup: no error
    with pytest.raises(KeyError):
        sink.read(0)                              # spilled data is gone


def test_memory_sink_eviction_drops_lowest_episode():
    sink = MemorySink(keep=2)
    traj = _collect_one()
    for ep in (5, 3, 7):                          # out-of-order arrivals
        sink.write(ep, traj)
    with pytest.raises(KeyError):
        sink.read(3)                              # lowest id evicted first
    assert sink.read(5).obs.shape == sink.read(7).obs.shape
    sink_one = MemorySink(keep=1)
    sink_one.write(0, traj)
    sink_one.write(1, traj)
    with pytest.raises(KeyError):
        sink_one.read(0)
    np.testing.assert_array_equal(sink_one.read(1).obs, np.asarray(traj.obs))


def test_broadcast_env_state():
    st = {"a": jnp.zeros((3,)), "b": jnp.float32(1.0)}
    obs = jnp.zeros((5,))
    st_b, obs_b = broadcast_env_state(st, obs, 4)
    assert st_b["a"].shape == (4, 3) and st_b["b"].shape == (4,)
    assert obs_b.shape == (4, 5)


# ---------------------------------------------------------------------------
# SinkSpec: the declarative sink config (make_sink's replacement)
# ---------------------------------------------------------------------------

def test_sink_spec_parse_and_build(tmp_path):
    from repro.data.trajectory_dataset import DatasetSink
    assert SinkSpec.parse(None).build() is None
    assert SinkSpec.parse("none").build() is None
    assert SinkSpec.parse("disabled").kind == "none"
    assert isinstance(SinkSpec.parse("memory").build(), MemorySink)
    fs = SinkSpec.parse(f"binary:{tmp_path}/b").build()
    assert isinstance(fs, FileSink) and fs.codec == "binary"
    ds = SinkSpec.parse(f"dataset:{tmp_path}/d").build()
    assert isinstance(ds, DatasetSink)
    assert SinkSpec(kind="memory", keep=3).build().keep == 3


def test_sink_spec_rejects_bad_specs(tmp_path):
    with pytest.raises(ValueError, match="unknown sink kind"):
        SinkSpec(kind="parquet", root=str(tmp_path)).build()
    with pytest.raises(ValueError, match="needs a root directory"):
        SinkSpec(kind="binary").build()
    with pytest.raises(ValueError, match="needs a root directory"):
        SinkSpec(kind="dataset").build()


def test_engine_builds_sink_from_config_spec(tmp_path):
    engine = RolloutEngine(
        _toy_step, EngineConfig(n_envs=N, horizon=T,
                                sink=SinkSpec(kind="memory", keep=2)))
    assert isinstance(engine.sink, MemorySink)
    # an explicit sink= always wins over the config spec
    mine = MemorySink()
    engine = RolloutEngine(
        _toy_step, EngineConfig(n_envs=N, horizon=T,
                                sink=SinkSpec(kind="memory")), sink=mine)
    assert engine.sink is mine


def test_make_sink_deprecation_blames_caller(tmp_path):
    import warnings
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        sink = make_sink("memory")
    assert isinstance(sink, MemorySink)
    assert len(w) == 1 and issubclass(w[0].category, DeprecationWarning)
    assert "SinkSpec" in str(w[0].message)
    # stacklevel walks out of the engine module: the warning names THIS file
    assert w[0].filename == __file__


def test_sink_read_errors_are_actionable(tmp_path):
    from repro.drl.engine import SinkReadError
    mem = MemorySink(keep=2)
    traj = _collect_one()
    for ep in range(3):
        mem.write(ep, traj)
    with pytest.raises(SinkReadError, match=r"keep=2"):
        mem.read(0)                         # names the retention window
    fs = FileSink(str(tmp_path), codec="binary")
    fs.write(4, traj)
    with pytest.raises(SinkReadError) as ei:
        fs.read(99)
    msg = str(ei.value)
    assert str(tmp_path) in msg and "codec" in msg and "episode 99" in msg
    fs.cleanup()

