"""Work a cell's episode requires, counted from its configuration's shapes.

Each mechanism has a file of its own (``sor_solver``, ``mlp_policy``,
``attention_policy``, ``ppo_update``) that counts algorithmic FLOPs and the
least bytes any implementation must move: every input read once and every
output written once, with no intermediate traffic.  Both are lower bounds,
so a share of a peak built on them cannot pass 100% for any implementation.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(w: dict, pk: dict) -> float:
    """The larger of the compute and the memory bound."""
    return max(w["flops"] / pk["flops_bf16"], w["bytes"] / pk["hbm_bytes_per_s"])


def _policy(cfg):
    return importlib.import_module(f"bench.work.{cfg['policy']['kind']}_policy")


def shapes(cfg: dict) -> dict:
    """Observation and action widths of the (padded) env batch."""
    from bench.reference import geometry as geo
    scns = cfg["scenarios"]
    obs = max(len(geo.PROBES[s["probes"]]()) for s in scns)
    act = max(len(geo.BODIES[s["geometry"]]) if s["actuation"] == "rotary"
              else 1 for s in scns)
    return {"obs_dim": obs, "act_dim": act}


def episode_work(cfg: dict, traffic: dict, n_envs: int) -> dict:
    """-> {"rollout", "learner", "episode"}: each {"flops", "bytes"}."""
    from bench.work import ppo_update, sor_solver
    pol = _policy(cfg)
    sh = shapes(cfg)
    steps = n_envs * traffic["actions_per_episode"]
    solver = sor_solver.work(cfg, n_envs, traffic["actions_per_episode"],
                             traffic["steps_per_action"], sh["obs_dim"])
    act_fwd = pol.forward_flops(cfg["policy"], sh, heads=("actor",))
    rollout = {"flops": solver["flops"] + steps * act_fwd,
               "bytes": solver["bytes"] + pol.param_bytes(cfg["policy"], sh)
               + steps * 4 * (2 * sh["obs_dim"] + sh["act_dim"] + 5)}
    learner = ppo_update.work(cfg["policy"], traffic, sh, n_envs, pol)
    return {"rollout": rollout, "learner": learner,
            "episode": {k: rollout[k] + learner[k] for k in rollout}}
