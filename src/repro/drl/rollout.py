"""Episode rollout: lax.scan over actuation periods, vmapped over N_envs."""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.drl import networks


class Trajectory(NamedTuple):
    """The trailing aux fields default to ``None`` (jax.tree skips None
    subtrees) so sinks/readers written against the 7-field layout keep
    working; they are populated when the env exposes ``obs_aux`` — the
    probe-set side channel a set-structured policy needs to replay the
    trajectory (coords + live-slot mask are constant over an episode)."""
    obs: jnp.ndarray      # (T, obs_dim)
    act: jnp.ndarray      # (T, act_dim)
    logp: jnp.ndarray     # (T,)
    reward: jnp.ndarray   # (T,)
    cd: jnp.ndarray       # (T,)
    cl: jnp.ndarray       # (T,)
    last_obs: jnp.ndarray  # (obs_dim,)
    probe_xy: jnp.ndarray = None    # (obs_dim, 2) normalized probe coords
    probe_mask: jnp.ndarray = None  # (obs_dim,) 1 = live probe slot
    valid: jnp.ndarray = None       # (T,) 1 = healthy step (sentinel mask)


def rollout_episode(env_step_fn, params, st0, obs0, key, length: int,
                    *, obs_aux_fn=None) -> Tuple[object, Trajectory]:
    """env_step_fn: (state, action) -> (state, EnvOutput).

    ``obs_aux_fn(state) -> {"xy", "mask"}`` (optional) is evaluated ONCE on
    the initial state — the probe layout rides in the scenario params and is
    constant over an episode — and fed to every policy evaluation."""
    aux0 = None if obs_aux_fn is None else obs_aux_fn(st0)

    def step(carry, k):
        st, obs = carry
        with jax.named_scope("policy"):
            act, logp = networks.sample_action(params, obs, k, aux=aux0)
        # scalar envs take the bare amplitude (the historical program);
        # vector (multi-body) envs take the whole action vector
        a = act[0] if act.shape[0] == 1 else act
        st, out = env_step_fn(st, a)
        # toy/test envs predating the sentinel carry no ``valid`` at all;
        # None threads through lax.scan as an empty subtree either way
        return (st, out.obs), (obs, act, logp, out.reward, out.cd, out.cl,
                               getattr(out, "valid", None))

    keys = jax.random.split(key, length)
    (st, last_obs), (obs, act, logp, rew, cd, cl, valid) = jax.lax.scan(
        step, (st0, obs0), keys)
    traj = Trajectory(obs=obs, act=act, logp=logp, reward=rew,
                      cd=cd, cl=cl, last_obs=last_obs, valid=valid)
    if aux0 is not None:
        traj = traj._replace(probe_xy=aux0["xy"], probe_mask=aux0["mask"])
    return st, traj


def rollout_batch(env_step_fn, params, st0_b, obs0_b, key, length: int,
                  n_envs: int, *, obs_aux_fn=None):
    """vmapped over the environment axis (the paper's N_envs parallelism)."""
    return rollout_keyed(env_step_fn, params, st0_b, obs0_b,
                         jax.random.split(key, n_envs), length,
                         obs_aux_fn=obs_aux_fn)


def rollout_keyed(env_step_fn, params, st0_b, obs0_b, keys, length: int,
                  *, obs_aux_fn=None):
    """``rollout_batch`` with one key per env already split, so a slice of
    the batch draws exactly the actions it draws inside the whole batch."""
    # axis_name lets the fault injector address a single env via
    # ``jax.lax.axis_index("env")``; with no collectives in the program it
    # is otherwise inert
    return jax.vmap(
        lambda st, obs, k: rollout_episode(env_step_fn, params, st, obs, k,
                                           length, obs_aux_fn=obs_aux_fn),
        axis_name="env")(st0_b, obs0_b, keys)
