"""The learner of one episode: the critic's values of every step and of the
bootstrap state with GAE, then ``epochs`` passes of PPO over all samples in
``minibatches`` minibatches, each ending in an AdamW step.

FLOPs: the forward pass of actor and critic per sample per epoch, twice that
for the backward pass, the critic's forward for the values, 6 per step for
GAE, and 12 per parameter per optimizer step.  Least bytes: the batch read
once, the parameters and both Adam moments read and written once per
episode."""
from __future__ import annotations

GAE_STEP = 6
ADAM_PER_PARAM = 12


def work(spec: dict, traffic: dict, sh: dict, n_envs: int, pol) -> dict:
    ppo = traffic["ppo"]
    samples = n_envs * traffic["actions_per_episode"]
    fwd = pol.forward_flops(spec, sh)
    critic = pol.forward_flops(spec, sh, heads=("critic",))
    params = pol.param_count(spec, sh)
    flops = (ppo["epochs"] * samples * 3 * fwd
             + (samples + n_envs) * critic + samples * GAE_STEP
             + ppo["epochs"] * ppo["minibatches"] * params * ADAM_PER_PARAM)
    row = sh["obs_dim"] + sh["act_dim"] + 4
    return {"flops": flops,
            "bytes": 4 * (samples * row + 2 * 3 * params)}
