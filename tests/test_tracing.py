"""Profiler spans of the training loop, and named scopes in its programs.

``train()`` under ``jax.profiler.trace`` writes one ``repro/episode`` step
span per episode, with the loop's layer spans inside it
(``repro.drl.spans``).  The compiled rollout, postprocess and update
programs carry each sub-step's ``jax.named_scope`` in their ``op_name``
metadata, which is how a device trace's fusions are traced back to them.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.cfd.env import CylinderEnv, EnvConfig
from repro.cfd.grid import GridConfig
from repro.drl import networks
from repro.drl.engine import (EngineConfig, RolloutEngine,
                              broadcast_env_state)
from repro.drl.ppo import PPOConfig
from repro.drl.train import TrainConfig, train

EPISODES = 3
N_ENVS = 2
ENV = EnvConfig(grid=GridConfig(res=6, dt=0.012, poisson_iters=10),
                steps_per_action=2, actions_per_episode=3, warmup_time=0.1)
PPO = PPOConfig(epochs=2, minibatches=2)
# the reads train()'s own loop makes each episode besides the watchdog's
# one per PPO metric: the return (run_sync), reward, C_D, C_L, quarantines
# and grad skips (on_episode)
LOOP_READS = 6


class _Identity:
    """A CFD<->DRL interface that hands the batch back unchanged."""

    def exchange(self, batch):
        return batch


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(repro spans as (name, start, end, stats), metric names per episode)
    of a 3-episode ``train()`` run traced by the profiler."""
    out = tmp_path_factory.mktemp("trace")
    seen = []
    cfg = TrainConfig(env=ENV, ppo=PPO, n_envs=N_ENVS, episodes=EPISODES,
                      seed=0)
    with jax.profiler.trace(str(out)):
        train(cfg, log_fn=None, interface=_Identity(),
              on_episode=lambda traj, metrics: seen.append(sorted(metrics)))
    path = next(out.rglob("*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in prof.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro/")]
    return spans, seen


def _episodes(spans):
    eps = sorted((s for s in spans if s[0] == "repro/episode"),
                 key=lambda s: s[1])
    assert len(eps) == EPISODES
    return eps


def test_one_episode_step_span_per_episode(traced):
    spans, _ = traced
    eps = _episodes(spans)
    assert [int(e[3]["step_num"]) for e in eps] == list(range(EPISODES))
    assert all(e0[2] <= e1[1] for e0, e1 in zip(eps, eps[1:]))
    # every span of the loop lies inside its episode
    assert all(any(_inside(s, e) for e in eps) for s in spans)


@pytest.mark.parametrize("kind", ["collect", "update", "sync",
                                  "io.interface", "caller"])
def test_layer_span_inside_each_episode(traced, kind):
    spans, _ = traced
    for ep in _episodes(spans):
        assert any(s[0] == f"repro/{kind}" and _inside(s, ep)
                   for s in spans), (kind, ep[3])


def test_sync_spans_count_every_blocking_read(traced):
    spans, seen = traced
    assert len(seen) == EPISODES
    for ep, metrics in zip(_episodes(spans), seen):
        syncs = [s for s in spans if s[0] == "repro/sync" and _inside(s, ep)]
        # the watchdog reads each PPO metric once
        assert len(syncs) == LOOP_READS + len(metrics), metrics


# -- named scopes in the compiled programs -----------------------------------

SCOPES = {"collect_traj": ("momentum", "poisson", "projection", "forces",
                           "probes", "reward", "policy"),
          "postprocess": ("values", "gae"),
          "update": ("ppo_loss", "ppo_grad", "optimizer")}


@pytest.fixture(scope="module")
def compiled_text():
    """The optimized HLO text of the engine's three programs."""
    env = CylinderEnv(ENV)
    st, obs = env.reset()
    st_b, obs_b = broadcast_env_state(st, obs, N_ENVS)
    engine = RolloutEngine.for_env(
        env, EngineConfig(n_envs=N_ENVS, horizon=ENV.actions_per_episode))
    pcfg = networks.PolicyConfig(obs_dim=int(obs.shape[-1]))
    params, optimizer, opt_state, key = engine.init(pcfg, PPO, seed=0)
    traj = jax.eval_shape(engine._rollout, params, st_b, obs_b, key)
    batch = jax.eval_shape(engine.postprocess, params, traj)
    update = engine.make_update(PPO, optimizer)
    lowered = {
        "collect_traj": engine._rollout.lower(params, st_b, obs_b, key),
        "postprocess": engine.postprocess.lower(params, traj),
        "update": update.lower(params, opt_state, batch, key, jnp.int32(0))}
    return {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, scopes in
                                           SCOPES.items() for s in scopes])
def test_named_scope_in_compiled_program(compiled_text, program, scope):
    # a scope is one component of the op_name path; under a transform it
    # reads wrapped, as in ``jvp(ppo_loss)``
    assert re.search(rf'op_name="[^"]*[/(]{re.escape(scope)}[/)]',
                     compiled_text[program]), (program, scope)
