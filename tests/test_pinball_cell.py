"""The benchmark's pinball cell against its plain reference, on the CPU.

``pinball_re100_rotary.paper`` (three cylinders, one rotation each, 59
probes, a three-output policy) cut to res 4, 8 envs, 2 dt x 3 actions and
PPO 2 x 2, through the harness's own run and its limits: a sound run reads
``correct``, and a fault in the per-body path reads false through
``traj_gap`` (the env step against the reference's, under the recorded
actions)."""
import copy
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, harness  # noqa: E402
from bench.metrics import warmup_s  # noqa: E402
from bench.reference import replay  # noqa: E402
from repro.cfd import env as env_mod  # noqa: E402
from repro.cfd import solver  # noqa: E402
from repro.core import spans  # noqa: E402

CELL = "pinball_re100_rotary.paper"
SEED = 2 ** 31 + 29      # beyond 32 signed bits, as a run's seed may be


@pytest.fixture(scope="module")
def tiny():
    _, cfg, traffic = harness.load_cell(CELL)
    cfg = dict(cfg, res=4, n_envs=8, warmup_time=0.5, poisson_iters=10)
    traffic = copy.deepcopy(traffic)
    traffic.update(steps_per_action=2, actions_per_episode=3)
    traffic["ppo"].update(epochs=2, minibatches=2)
    setup = replay.Setup(cfg, traffic, cfg["n_envs"], jnp.float32)
    return cfg, traffic, check.load_limits(ROOT, CELL), setup


def run(tiny):
    cfg, traffic, limits, setup = tiny
    return harness.run_cell(CELL, SEED, 0.0, False, require_chip=False,
                            cfg=cfg, traffic=traffic, limits=limits,
                            setup=setup, log=lambda s: None)


def swap_back_cylinders(monkeypatch):
    """``env_step`` drives the top back cylinder with the bottom one's
    action slot and the reverse."""
    orig = env_mod.CylinderEnv.env_step

    def env_step(self, st, action):
        return orig(self, st, jnp.asarray(action)[..., jnp.array([0, 2, 1])])

    monkeypatch.setattr(env_mod.CylinderEnv, "env_step", env_step)


def lift_summed_before_abs(monkeypatch):
    """The per-body lifts reach the reward already summed, so its penalty
    is |sum_b C_L,b| and opposite lifts cancel."""
    orig = solver.step_interval

    def step_interval(*args, **kwargs):
        flow, outs = orig(*args, **kwargs)
        if outs.cl.ndim > 1:
            outs = outs._replace(cl=jnp.sum(outs.cl, axis=-1, keepdims=True))
        return flow, outs

    monkeypatch.setattr(solver, "step_interval", step_interval)


def test_sound_pinball_run_is_correct(tiny):
    res = run(tiny)
    assert res["correct"], res["checks"]
    # the harness's train() warmed up the one geometry once, and the
    # benchmark's set-up reader reads that span's seconds
    seconds = spans.setup_seconds["repro/setup.warmup"]
    assert len(seconds) == 1
    assert warmup_s.read({}) == pytest.approx(seconds[0])


def test_warmup_reader_reads_nothing_without_the_span(monkeypatch):
    monkeypatch.setattr(spans, "setup_seconds", {})
    assert warmup_s.read({}) is None


@pytest.mark.parametrize("plant", [swap_back_cylinders,
                                   lift_summed_before_abs],
                         ids=["swapped_back_cylinders", "lift_summed"])
def test_per_body_fault_fails_traj_gap(tiny, monkeypatch, plant):
    plant(monkeypatch)
    res = run(tiny)
    gap = res["checks"]["traj_gap"]
    assert not res["correct"], res["checks"]
    assert gap["value"] is None or gap["value"] > gap["limit"], res["checks"]
