"""The comparison that decides ``correct`` passes a sound run and fails the
low-precision control and every planted fault, at a size the CPU holds."""
import copy
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness
from bench.calibrate import planted
from bench.reference import replay

CELL = "cyl_re100_jets.paper"
SEED = 2 ** 31 + 17      # beyond 32 signed bits, as a run's seed may be


def run(tiny, fault="none", records=None):
    cfg, traffic, limits = tiny
    with planted(fault):
        return harness.run_cell(CELL, SEED, 0.0, False, require_chip=False,
                                cfg=cfg, traffic=traffic, limits=limits,
                                keep_record=records)


def test_sound_run_is_correct(tiny):
    res = run(tiny)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"transitions_per_s", "episode_ms_p90",
                                   "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "reward",
                                   "batch_rows", "policy_logp",
                                   "skip_leaf"])
def test_planted_fault_is_not_correct(tiny, fault):
    res = run(tiny, fault)
    assert not res["correct"], res["checks"]
    caught_by = {"policy_logp": "logp_gap", "skip_leaf": "change_gap"}
    if fault in caught_by:
        gap = res["checks"][caught_by[fault]]
        assert gap["value"] > gap["limit"], res["checks"]


def test_per_step_readings_follow_the_programs_own_weights(tiny):
    """A learner whose weights have drifted far from the reference's own
    learner (here one that steps three times as far), but whose
    log-probabilities, advantages and returns are those of its own weights,
    reads near 0 on the per-step numbers, and ``change_gap`` still flags
    the drift."""
    cfg, traffic, limits = tiny
    records = []
    run(tiny, records=records)
    fast = copy.deepcopy(traffic)
    fast["ppo"]["lr"] *= 3
    rec = replay.control(records[0], cfg, fast, SEED, dtype=jnp.float32)
    ref = replay.view(rec, cfg, traffic, SEED)
    readings, _ = check.numbers(rec, ref, traffic)
    assert readings["logp_gap"] < 1e-4, readings
    assert readings["gae_gap"] < 1e-4, readings
    assert readings["change_gap"] > limits["change_gap"], readings
    # the same record read under the reference's own weights is far off
    free = replay.Learner(cfg, traffic, replay.Setup(
        cfg, traffic, rec["episodes"][0]["traj"]["act"].shape[0],
        jnp.float32), SEED)
    for ep in rec["episodes"][:-1]:
        free.update(ep["batch"], free.next_keys()[1])
    last = rec["episodes"][-1]["traj"]
    drift = np.max(np.abs(np.asarray(free.logp(last["obs"], last["act"]))
                          - last["logp"]))
    assert drift > 100 * max(readings["logp_gap"], 1e-7), drift


def test_policy_widths_the_program_did_not_build_are_refused(tiny):
    cfg, traffic, limits = tiny
    cfg = dict(cfg, policy=dict(cfg["policy"], hidden=64))
    with pytest.raises(harness.SetupError, match="not the configuration's"):
        harness.run_cell(CELL, SEED, 0.0, False, require_chip=False, cfg=cfg,
                         traffic=traffic, limits=limits)


def test_bfloat16_control_is_not_correct(tiny):
    cfg, traffic, limits = tiny
    records = []
    run(tiny, records=records)
    rec = replay.control(records[0], cfg, traffic, SEED, dtype=jnp.bfloat16)
    readings, _ = check.numbers(rec, replay.view(rec, cfg, traffic, SEED),
                                traffic)
    ok, checks = check.judge(readings, limits)
    assert not ok, checks


FOUR = textwrap.dedent("""
    import copy, json, sys
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    from bench import check, harness
    from bench.calibrate import planted
    W = "cyl_re100_jets_dp4.paper"
    _, cfg, traffic = harness.load_cell(W)
    cfg = dict(cfg, res=4, n_envs=8, warmup_time=0.5, poisson_iters=10)
    traffic = copy.deepcopy(traffic)
    traffic.update(steps_per_action=2, actions_per_episode=3)
    traffic["ppo"].update(epochs=2, minibatches=2)
    out = []
    for fault in ("none", "exchange"):
        with planted(fault):
            res = harness.run_cell(W, int(sys.argv[2]), 0.0, False,
                                   require_chip=False, cfg=cfg,
                                   traffic=traffic, log=lambda s: None)
        out.append(res["correct"])
    print(json.dumps(out))
""")


def test_exchange_fault_on_four_devices_is_not_correct():
    """The four-chip cell's configuration (``ParallelPlan(4, 4, 1)``) and
    limits, cut to 8 envs on four virtual CPU devices: a sound run is
    correct, and the fault of a batch that spans four chips is not: each
    chip's update reads only its share of the batch, as it does when the
    gradients' reduction across chips is left out."""
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR, str(root), str(SEED)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, fault = json.loads(p.stdout.splitlines()[-1])
    assert sound and not fault
