"""The comparison that decides ``correct``.

A run records its first episodes (``traffic.check.episodes`` of them, plus
one more whose log-probabilities and advantages show the weights after the
last compared update), with its state after each compared update: the
weights, Adam's two moments and the step count.  One episode is one
training step: a rollout of every env, then a PPO update.  The plain
reference replays them (``bench.reference.replay.view``) and six numbers
measure how far the run lies from it; each is held to its limit in
``bench/limits/<cell>.json``.  Each number says under whose state the
reference reads it:

- ``traj_gap``   the env step (solver, forces, probes, reward), under the
                 recorded actions: the largest gap over obs, last obs,
                 reward, C_D and C_L between the run's trajectory and the
                 reference's, each field against its own largest magnitude;
                 and the batch the learner consumed (obs, act, logp_old,
                 valid) against the run's trajectory it was made from, env
                 by env in time order, where a copy reads 0.
- ``logp_gap``   the policy in the rollout, under the program's weights
                 that collected the episode (the reference's initial
                 weights for the first, else the run's after the update
                 before): the largest gap, in nats, of the recorded
                 actions' log-probabilities.
- ``gae_gap``    values and GAE, under the same weights: the largest gap of
                 advantages and returns, each against its own largest
                 magnitude.
- ``loss_gap``   one PPO update, from the program's state before it (the
                 initial state for the first) on the recorded batch: its
                 mean loss (clipped surrogate + value - entropy bonus),
                 relative.
- ``grad_gap``   the gradient as the optimizer gets it, under the
                 reference's own learner from the seed: Adam's first moment
                 after the first update, by the worst leaf (the gap between
                 the two norms of a leaf over the larger of the reference's
                 norm of that leaf and of the median leaf).
- ``change_gap`` the weights' change over the compared updates, the run's
                 against the reference's own learner's from the seed, by
                 the worst leaf as above; leaves whose reference gradient
                 is under a thousandth of the median leaf's move by
                 round-off alone and are left out.

The four per-step numbers are teacher-forced: two float32 learners round
differently, and a sample at the PPO clip edge turns that rounding into a
different gradient, so after a few updates two sound learners hold
different policies and a reading under the reference's own weights would
measure that divergence, not the step.  Under the program's state each
reading covers one step, and no rounding carries from one to the next.
``grad_gap`` and ``change_gap`` are not teacher-forced: they guard against
an update that is a little wrong every time, which no single step shows
but three steps add up, and against a leaf the update leaves unmoved.  A
sound run's worst leaf parts by up to 6% over three updates where a
sample crosses the clip edge on one side only; a leaf left unmoved reads
its change over the larger of that change and the median leaf's, so an
unmoved leaf far smaller than the median (a bias, the log-std) stays under
the limit.

A quarantine the reference does not share (or the reverse) reads as an
infinite ``traj_gap``.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

NAMES = ("traj_gap", "logp_gap", "gae_gap", "loss_gap", "grad_gap",
         "change_gap")
ENV_FIELDS = ("obs", "last_obs", "reward", "cd", "cl")
# batch row field <- trajectory field, the rows flattened env-major
BATCH_ROWS = {"obs": "obs", "act": "act", "logp_old": "logp",
              "valid": "valid"}
QUIET_LEAF = 1e-3


def leaves(tree) -> dict:
    """A parameter tree as {path: float32 host array}."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float32)
            for k, v in flat}


def leaf_gap(got: dict, ref: dict, keep=None) -> tuple:
    """-> (worst gap of leaf norms, its leaf).  Each leaf's gap is measured
    against the larger of the reference's norm of that leaf and of the
    median leaf; a leaf missing on one side, or of another shape, reads
    infinite.  Leaves of ``ref`` not in ``keep`` are left out."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    floor = float(np.median(list(norms.values())))
    worst, where = 0.0, "-"
    for k in sorted(set(got) | set(ref)):
        if keep is not None and k in ref and k not in keep:
            continue
        if k not in got or k not in ref or got[k].shape != ref[k].shape:
            return math.inf, k
        g = (abs(float(np.linalg.norm(got[k])) - norms[k])
             / (max(norms[k], floor) or 1.0))
        if _worse(g, worst):
            worst, where = g, k
    return worst, where


def _rel(a, b, scale):
    return float(np.max(np.abs(np.asarray(a, np.float64) - b)) / scale)


def _worse(g, cur):
    """True when reading ``g`` replaces ``cur`` as the worst (NaN sticks)."""
    return not math.isnan(cur) and (math.isnan(g) or g > cur)


def _loss(m, ppo):
    return (m["policy_loss"] + ppo["value_coef"] * m["value_loss"]
            - ppo["entropy_coef"] * m["entropy"])


def batch_gap(ep: dict) -> tuple:
    """-> (largest gap of the batch's rows from the trajectory's, its
    field).  Each field against its own largest magnitude; a field on one
    side only, or rows of another shape, read infinite."""
    worst, where = 0.0, "-"
    for f, t in BATCH_ROWS.items():
        got, src = ep["batch"].get(f), ep["traj"].get(t)
        if got is None and src is None:
            continue
        if got is None or src is None or got.size != src.size:
            return math.inf, f
        src = src.reshape(got.shape)
        g = _rel(got, src, float(np.max(np.abs(src))) or 1.0)
        if _worse(g, worst):
            worst, where = g, f
    return worst, where


def numbers(record: dict, ref: list, traffic: dict) -> tuple:
    """-> ({name: reading}, {name: where the reading came from})."""
    eps = record["episodes"]
    ppo = traffic["ppo"]
    env = [r["env"] for r in ref if "env" in r]
    worst = {}
    traj = 0.0
    for f in ENV_FIELDS:
        scale = max(float(np.max(np.abs(e[f]))) for e in env) or 1.0
        for k, e in enumerate(env):
            g = _rel(eps[k]["traj"][f], e[f], scale)
            if _worse(g, traj):
                traj, worst["traj_gap"] = g, f"{f}, episode {k + 1}"
    for k, e in enumerate(env):
        if not np.array_equal(eps[k]["traj"]["valid"] > 0.5, e["valid"] > 0.5):
            traj, worst["traj_gap"] = math.inf, f"valid, episode {k + 1}"
        g, f = batch_gap(eps[k])
        if _worse(g, traj):
            traj, worst["traj_gap"] = g, f"batch {f}, episode {k + 1}"
    out = {"traj_gap": traj, "logp_gap": 0.0, "gae_gap": 0.0,
           "loss_gap": 0.0}
    for k, (ep, r) in enumerate(zip(eps, ref)):
        g = float(np.max(np.abs(ep["traj"]["logp"] - r["logp"])))
        if _worse(g, out["logp_gap"]):
            out["logp_gap"], worst["logp_gap"] = g, f"episode {k + 1}"
        for f in ("adv", "ret"):
            scale = float(np.max(np.abs(r[f]))) or 1.0
            g = _rel(ep["batch"][f], r[f], scale)
            if _worse(g, out["gae_gap"]):
                out["gae_gap"], worst["gae_gap"] = g, f"{f}, episode {k + 1}"
        if "metrics" not in r:
            continue
        mp, mr = ep["metrics"], r["metrics"]
        g = abs(_loss(mp, ppo) - _loss(mr, ppo)) / abs(_loss(mr, ppo))
        if _worse(g, out["loss_gap"]):
            out["loss_gap"], worst["loss_gap"] = g, f"episode {k + 1}"
    m_ref = ref[0]["m"]
    out["grad_gap"], worst["grad_gap"] = leaf_gap(eps[0]["m"], m_ref)
    norms = {k: float(np.linalg.norm(v)) for k, v in m_ref.items()}
    quiet = QUIET_LEAF * float(np.median(list(norms.values())))
    moved = {k for k, n in norms.items() if n >= quiet}
    p0, last = ref[0]["p0"], len(env) - 1
    delta = lambda p: {k: v - p0[k] if k in p0 else v  # noqa: E731
                       for k, v in p.items()}
    out["change_gap"], worst["change_gap"] = leaf_gap(
        delta(eps[last]["params"]), delta(ref[last]["params"]), keep=moved)
    return out, worst


def load_limits(root: Path, workload: str) -> dict:
    return json.loads((root / "bench" / "limits" / f"{workload}.json")
                      .read_text())["limits"]


def judge(readings: dict, limits: dict) -> tuple:
    """-> (correct, {name: {"value": reading, "limit": limit}}).  A reading
    that is not a finite number fails and is reported as null."""
    checks, ok = {}, True
    for name in NAMES:
        v, lim = readings.get(name), limits[name]
        finite = v is not None and math.isfinite(v)
        ok = ok and finite and v <= lim
        checks[name] = {"value": v if finite else None, "limit": lim}
    return ok, checks
