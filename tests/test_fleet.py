"""Multi-process fleet: launcher-driven bitwise parity across fleet sizes,
elastic kill -> shrink -> resume, process-suffixed sinks, and the
distributed bootstrap helpers.

The heavyweight tests drive the REAL entry point — ``tools/launch_fleet.py``
forking runner processes into a ``jax.distributed`` (gloo) fleet — because
the bitwise contract lives in the launcher's pinned
``--xla_force_host_platform_device_count``: XLA CPU codegen differs between
forced device counts even for single-device programs, so only runs whose
runners all pin the plan's ``n_total`` are comparable.  Checkpoints written
by each fleet are compared array-for-array.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
SRC = str(ROOT / "src")
LAUNCHER = str(ROOT / "tools" / "launch_fleet.py")


def _launch(workdir, *extra, processes=1, episodes=2, timeout=600):
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": SRC,
           "PATH": "/usr/bin:/bin", "HOME": "/tmp",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    out = subprocess.run(
        [sys.executable, LAUNCHER, "--processes", str(processes),
         "--episodes", str(episodes), "--workdir", str(workdir),
         "--heartbeat-timeout", "300", *map(str, extra)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    return out.stdout


def _final_state(workdir):
    from repro.ckpt.checkpoint import latest_checkpoint
    from repro.drl.train_state import load_train_state
    path = latest_checkpoint(str(Path(workdir) / "ckpt"))
    assert path is not None, f"no checkpoint under {workdir}/ckpt"
    return load_train_state(path)


# ---------------------------------------------------------------------------
# the bitwise contract: N-process training == 1-process training
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_process_training_bitwise_matches_single(tmp_path):
    """Same plan, same seed: a 2-process fleet and a 1-process fleet write
    IDENTICAL final checkpoints (params, optimizer state, PRNG carry, env
    batch, history) — the distributed rollout + replicated-learner design
    is bitwise-invariant in the fleet size."""
    out1 = _launch(tmp_path / "p1", processes=1)
    out2 = _launch(tmp_path / "p2", processes=2)
    assert "FLEET_DONE episodes=2" in out1
    assert "FLEET_DONE episodes=2" in out2

    ts1, meta1 = _final_state(tmp_path / "p1")
    ts2, meta2 = _final_state(tmp_path / "p2")
    assert meta1["episode"] == meta2["episode"] == 2
    assert meta1["plan"]["n_processes"] == 1
    assert meta2["plan"]["n_processes"] == 2
    import jax
    l1, l2 = jax.tree.leaves(ts1.params), jax.tree.leaves(ts2.params)
    assert len(l1) == len(l2) and len(l1) > 0
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ts1.opt_state),
                    jax.tree.leaves(ts2.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ts1.key), np.asarray(ts2.key))
    for f, v in ts1.history.items():
        if f == "wall":                   # wall-clock seconds: not bitwise
            continue
        np.testing.assert_array_equal(v, ts2.history[f])


@pytest.mark.slow
def test_killed_runner_shrinks_and_resumes(tmp_path):
    """SIGKILL one runner mid-run: the supervisor detects the death, shrinks
    the fleet to the next viable size, and the relaunched fleet resumes from
    the latest checkpoint to the full episode target."""
    out = _launch(tmp_path / "elastic", "--kill-process", 1,
                  "--kill-episode", 1, processes=2, episodes=3)
    assert "FLEET_SHRINK gen=1 procs=2->1 reason=exit" in out, out
    assert "FLEET_DONE episodes=3" in out, out
    ts, meta = _final_state(tmp_path / "elastic")
    assert meta["episode"] == 3
    assert len(ts.history["reward"]) == 3


# ---------------------------------------------------------------------------
# per-process sink sharding (no cross-host write contention)
# ---------------------------------------------------------------------------

def test_file_sink_process_suffix(tmp_path):
    from repro.drl.engine import FileSink
    from repro.drl.rollout import Trajectory

    def traj(v):
        z = lambda *s: np.full(s, v, np.float32)
        return Trajectory(obs=z(2, 3, 4), act=z(2, 3, 1), logp=z(2, 3),
                          reward=z(2, 3), cd=z(2, 3), cl=z(2, 3),
                          last_obs=z(2, 4))

    s0 = FileSink(str(tmp_path), process=0)
    s1 = FileSink(str(tmp_path), process=1)
    s0.write(0, traj(0.0))
    s1.write(0, traj(1.0))
    names = sorted(p.name for p in tmp_path.glob("*.bin"))
    assert names == ["traj_000000.p000.bin", "traj_000000.p001.bin"]
    # each sink reads back its own shard only
    np.testing.assert_array_equal(s1.read(0).obs,
                                  np.full((2, 3, 4), 1.0, np.float32))
    np.testing.assert_array_equal(s0.read(0).obs,
                                  np.zeros((2, 3, 4), np.float32))
    # a process-less sink in the same dir sees no suffixed shards
    plain = FileSink(str(tmp_path))
    with pytest.raises(KeyError):
        plain.read(0)


def test_dataset_sink_process_partition(tmp_path):
    from repro.data.trajectory_dataset import DatasetSink, TrajectoryReader
    from repro.drl.rollout import Trajectory

    z = lambda *s: np.zeros(s, np.float32)
    traj = Trajectory(obs=z(2, 3, 4), act=z(2, 3, 1), logp=z(2, 3),
                      reward=z(2, 3), cd=z(2, 3), cl=z(2, 3),
                      last_obs=z(2, 4))
    for p in (0, 1):
        sink = DatasetSink(str(tmp_path), process=p)
        sink.write(0, traj)
        assert sink.metadata["process"] == p
    parts = sorted(d.name for d in tmp_path.iterdir() if d.is_dir())
    assert parts == ["part000", "part001"]
    for part in parts:
        reader = TrajectoryReader(str(tmp_path / part))
        assert reader.episodes == [0]


def test_sink_spec_process_defaults_to_jax(tmp_path):
    """Single-process: SinkSpec resolves process=None (no suffix churn for
    the historical layout); an explicit process wins."""
    from repro.drl.engine import SinkSpec
    spec = SinkSpec(kind="binary", root=str(tmp_path))
    assert spec._process() is None
    spec = SinkSpec(kind="binary", root=str(tmp_path), process=7)
    assert spec._process() == 7


# ---------------------------------------------------------------------------
# bootstrap helpers (no fleet needed)
# ---------------------------------------------------------------------------

def test_fleet_env_pins_device_count():
    from repro.launch.distributed import (ENV_COORDINATOR, ENV_FLEET,
                                          ENV_NUM_PROCESSES, ENV_PROCESS_ID,
                                          fleet_env)
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2 "
                         "--xla_dump_to=/tmp/d"}
    env = fleet_env("127.0.0.1:1234", 2, 1, n_total_devices=8, base=base)
    # the stale forced count is REPLACED (pinned to the plan), other flags kept
    assert env["XLA_FLAGS"].count("--xla_force_host_platform_device_count") \
        == 1
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "--xla_dump_to=/tmp/d" in env["XLA_FLAGS"]
    assert env[ENV_COORDINATOR] == "127.0.0.1:1234"
    assert env[ENV_NUM_PROCESSES] == "2" and env[ENV_PROCESS_ID] == "1"
    assert env[ENV_FLEET] == "1"
    # the platform is the caller's choice: nothing here selects the CPU
    assert "JAX_PLATFORMS" not in env
    cpu = fleet_env("127.0.0.1:1234", 2, 1, n_total_devices=8,
                    base={"JAX_PLATFORMS": "cpu"})
    assert cpu["JAX_PLATFORMS"] == "cpu"


def test_launch_fleet_refuses_several_runners_off_cpu(tmp_path):
    """One chip belongs to one process: several runners on one host start
    only when the caller chose the CPU, and the refusal says why."""
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, LAUNCHER, "--processes", "2",
         "--workdir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert "JAX_PLATFORMS=cpu" in out.stderr
    assert not list(tmp_path.glob("runner_*.log"))      # nothing started


def test_initialize_fleet_single_process_noop():
    from repro.launch.distributed import initialize_fleet
    info = initialize_fleet(num_processes=1)
    assert info.num_processes == 1 and info.is_coordinator


def test_heartbeats_roundtrip_and_staleness(tmp_path):
    from repro.launch.distributed import (read_heartbeats, stale_processes,
                                          write_heartbeat)
    write_heartbeat(str(tmp_path), 0, episode=3)
    write_heartbeat(str(tmp_path), 1, episode=2)
    beats = read_heartbeats(str(tmp_path))
    assert beats[0]["episode"] == 3 and beats[1]["pid"] == os.getpid()
    now = beats[1]["time"]
    assert stale_processes(str(tmp_path), 2, timeout=60, now=now) == []
    assert stale_processes(str(tmp_path), 2, timeout=60,
                           now=now + 120) == [0, 1]
    # a runner that never heartbeated is the launcher's child-exit path,
    # not a staleness signal
    assert stale_processes(str(tmp_path), 3, timeout=60,
                           now=now + 120) == [0, 1]


def test_launch_fleet_shrink_ladder():
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        from launch_fleet import _shrink
    finally:
        sys.path.pop(0)
    assert _shrink(8, 1, 4) == 2          # 3 doesn't divide 8 devices
    assert _shrink(4, 1, 4) == 2          # next divisor of 4 below 4
    assert _shrink(4, 2, 4) == 2          # 2 procs x 2-rank envs still fit
    assert _shrink(4, 4, 2) == 1
    assert _shrink(4, 1, 1) == 0          # nowhere left to shrink


def test_plan_json_roundtrip_with_processes(tmp_path):
    """run_metadata's plan dict (with n_processes) survives the checkpoint
    manifest JSON round trip the resume-compat check reads."""
    from repro.drl.train_state import run_metadata
    from repro.cfd.grid import GridConfig
    meta = run_metadata(n_envs=4, obs_dim=8, seed=0, grid=GridConfig(res=6),
                        horizon=3, steps_per_action=3, scenarios=None,
                        plan={"n_envs": 4, "n_ranks": 1, "backend": "ref",
                              "n_processes": 2})
    back = json.loads(json.dumps(meta))
    assert back["plan"]["n_processes"] == 2
