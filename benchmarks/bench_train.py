"""End-to-end training-loop benchmark: the observability artifact.

Runs the paper's Fig. 4 loop (collect -> PPO update -> trajectory sink) on
the cylinder env and measures:

- **throughput**: environment steps (solver steps x envs) per second over
  the whole loop,
- **sink-write share**: the trajectory sink's own write time
  (``TrajectorySink.time_spent``) as a fraction of wall time,
- **golden-physics drift**: Strouhal / mean C_D / C_L amplitude re-measured
  from the checked-in golden state vs the stored reference — the dashboard
  sees solver drift next to the perf numbers that might have caused it.

Where the loop's time goes inside an episode is read from a profiler trace
of it (the ``repro/...`` spans of ``repro.drl.spans``), not from timers
here: a timer that syncs would change the loop it measures.

Writes ``artifacts/BENCH_train.json`` (``BENCH_train_smoke.json`` with
``--smoke`` — smoke artifacts never overwrite committed measurements).

    PYTHONPATH=src python benchmarks/bench_train.py [--smoke]
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from repro.cfd.env import CylinderEnv, EnvConfig
from repro.cfd.grid import GridConfig
from repro.drl import networks
from repro.drl.engine import (EngineConfig, RolloutEngine, SinkSpec,
                              broadcast_env_state)
from repro.drl.ppo import PPOConfig
from repro.drl.train_state import code_fingerprint

BENCH_SCHEMA = "repro.bench_train/v2"
GOLDEN = Path(__file__).resolve().parent.parent / "tests" / "golden" \
    / "cyl_re100_res8.npz"


def measure_training(smoke: bool) -> dict:
    """One timed training run with a dataset sink; returns the perf record."""
    # non-smoke uses the paper's 50 solver steps per actuation so the phase
    # split reflects the regime the scaling claims are about (CFD-dominated)
    res, p_iters = (6, 30) if smoke else (8, 50)
    spa = 3 if smoke else 50
    horizon = 3 if smoke else 20
    n_envs = 2 if smoke else 4
    episodes = 3 if smoke else 5
    env = CylinderEnv(EnvConfig(
        grid=GridConfig(res=res, dt=0.01, poisson_iters=p_iters),
        steps_per_action=spa, actions_per_episode=horizon,
        warmup_time=1.0 if smoke else 5.0))
    st, obs = env.reset()
    pcfg = networks.PolicyConfig(obs_dim=int(obs.shape[-1]))
    ppo = PPOConfig(epochs=2 if smoke else 6,
                    minibatches=2 if smoke else 4)

    root = tempfile.mkdtemp(prefix="bench_train_sink_")
    engine = RolloutEngine.for_env(
        env, EngineConfig(n_envs=n_envs, horizon=horizon, gamma=ppo.gamma,
                          lam=ppo.lam,
                          sink=SinkSpec(kind="dataset", root=root)))
    st_b, obs_b = broadcast_env_state(st, obs, n_envs)
    params, optimizer, opt_state, key = engine.init(pcfg, ppo, seed=0)

    # one untimed episode: compile collect + postprocess + update outside
    # the measured window (throughput, not compile latency)
    engine.run_sync(params, opt_state, ppo, optimizer, st_b, obs_b, key, 1)
    sink = engine.sink
    write0, bytes0 = sink.time_spent, sink.bytes_written

    t0 = time.perf_counter()
    engine.run_sync(params, opt_state, ppo, optimizer, st_b, obs_b, key,
                    episodes)
    wall = time.perf_counter() - t0

    sink_s = sink.time_spent - write0
    sink_bytes = sink.bytes_written - bytes0
    shutil.rmtree(root, ignore_errors=True)

    env_steps = n_envs * horizon * spa * episodes
    return {
        "config": {"res": res, "poisson_iters": p_iters, "n_envs": n_envs,
                   "horizon": horizon, "steps_per_action": spa,
                   "episodes": episodes, "smoke": smoke,
                   "ppo_epochs": ppo.epochs,
                   "ppo_minibatches": ppo.minibatches},
        "wall_s": wall,
        "env_steps": env_steps,
        "env_steps_per_s": env_steps / wall,
        "episodes_per_s": episodes / wall,
        "shares": {"sink_write": sink_s / wall},
        "sink": {"kind": "dataset", "bytes_written": sink_bytes,
                 "bytes_per_episode": sink_bytes / episodes,
                 "write_s_per_episode": sink_s / episodes,
                 "write_bandwidth": sink_bytes / sink_s if sink_s else None},
    }


def measure_golden_drift(smoke: bool) -> dict:
    """Re-measure the golden Re=100 shedding window; relative drift vs the
    checked-in reference (tools/gen_golden.py).  Mirrors
    tests/test_golden_physics.py, but reports magnitudes instead of
    asserting — the dashboard tracks drift as a trajectory."""
    from repro.cfd import solver
    from repro.cfd.validation import measure_shedding, run_uncontrolled
    if not GOLDEN.exists():
        return {"error": f"golden reference missing: {GOLDEN}"}
    ref = np.load(GOLDEN)
    cfg = GridConfig(res=int(ref["res"]), dt=float(ref["dt"]),
                     poisson_iters=int(ref["poisson_iters"]))
    steps = int(ref["meas_steps"]) // (2 if smoke else 1)
    state = solver.FlowState(u=ref["u"], v=ref["v"], p=ref["p"])
    _, cds, cls = run_uncontrolled(cfg, state, steps)
    try:
        stats = measure_shedding(cds, cls, cfg.dt)
    except ValueError as exc:           # smoke window too short for periods
        return {"error": str(exc), "window_steps": steps}
    rel = lambda k: stats[k] / float(ref[k]) - 1.0
    return {"window_steps": steps,
            "strouhal": stats["strouhal"],
            "cd_mean": stats["cd_mean"],
            "cl_amp": stats["cl_amp"],
            "strouhal_rel_drift": rel("strouhal"),
            "cd_mean_rel_drift": rel("cd_mean"),
            "cl_amp_rel_drift": rel("cl_amp")}


def run(smoke: bool = False, out: str = None) -> dict:
    record = {"schema": BENCH_SCHEMA,
              "code": code_fingerprint(),
              "jax_devices": jax.device_count()}
    record.update(measure_training(smoke))
    record["golden_drift"] = measure_golden_drift(smoke)

    root = Path(__file__).resolve().parent.parent / "artifacts"
    name = "BENCH_train_smoke.json" if smoke else "BENCH_train.json"
    path = Path(out) if out else root / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(record, indent=1, sort_keys=True))

    print(f"train: {record['env_steps_per_s']:.1f} env-steps/s "
          f"({record['wall_s']:.2f}s wall), sink writes "
          f"{record['shares']['sink_write']:.1%} of it")
    gd = record["golden_drift"]
    if "error" in gd:
        print(f"golden drift: skipped ({gd['error']})")
    else:
        print(f"golden drift: St {gd['strouhal_rel_drift']:+.3%}  "
              f"CD {gd['cd_mean_rel_drift']:+.3%}  "
              f"|CL| {gd['cl_amp_rel_drift']:+.3%}")
    print(f"artifact -> {path}")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI; writes BENCH_train_smoke.json")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
