"""Fleet scaling benchmark: env-steps/sec and parallel efficiency at
1 / 2 / 4 processes through the real launcher.

Each point shells out to ``tools/launch_fleet.py --mode bench``, which
forks that many runner processes into one ``jax.distributed`` fleet (the
"data" axis spanning processes, exactly the cluster layout) and times
distributed collects; this script parses the coordinator's ``FLEET_STATS``
line.  The pinned forced device count keeps the numerical work identical at
every fleet size, so throughput ratios compare like with like.

Efficiency is reported three ways:

- **raw**: ``tp_n / (n * tp_1)`` — the paper's definition.  On a CI box
  with fewer cores than processes this is bounded by ``cores/n`` no matter
  how good the communication layer is (the processes time-slice the cores).
- **vs_cores**: ``tp_n / (min(n, cores) * tp_1)`` — efficiency against
  ideal core scaling.  Still conflates the fleet's communication cost with
  time-slicing contention (cache/context-switch tax of co-running n full
  JAX runtimes), which p INDEPENDENT jobs on the same host would also pay.
- **comm** (the gate): ``tp_n / tp_n^(no-gather)`` — the same fleet, same
  pinned program, same process count, but with the trajectory all-gather
  disabled (``--no-gather``: each process times only its own env shard).
  The denominator is the best this host can do running the fleet's exact
  per-process compute with zero communication, so the ratio isolates the
  one thing the fleet layer adds: inter-process collectives + sync.

The gate (``gate.passed``, enforced by ``tools/bench_report.py --check``)
requires comm efficiency >= 70% at the largest fleet, reported beside the
paper's measured 78% at 60 cores (arXiv 2402.11515 Fig. 7 — measured on
dedicated cores, where raw and comm efficiency coincide).

Writes ``artifacts/BENCH_fleet.json`` (``BENCH_fleet_smoke.json`` with
``--smoke`` — smoke artifacts never overwrite committed measurements).

    PYTHONPATH=src python benchmarks/bench_fleet.py [--smoke]
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))

BENCH_SCHEMA = "repro.bench_fleet/v1"
PAPER_EFFICIENCY_60 = 0.78      # paper Fig. 7: parallel efficiency, 60 cores
GATE_EFFICIENCY = 0.70          # comm efficiency floor at the max fleet
LAUNCHER = _ROOT / "tools" / "launch_fleet.py"


def run_fleet_point(processes: int, *, plan: str, n_envs: int,
                    measure_episodes: int, res: int, dt: float,
                    poisson_iters: int, steps_per_action: int,
                    actions_per_episode: int, timeout: float,
                    no_gather: bool = False) -> dict:
    """One launcher invocation; returns the parsed FLEET_STATS record."""
    tag = f"bench_fleet_p{processes}{'_nogather' if no_gather else ''}_"
    workdir = tempfile.mkdtemp(prefix=tag)
    cmd = [sys.executable, str(LAUNCHER),
           "--processes", str(processes), "--mode", "bench",
           "--plan", plan, "--n-envs", str(n_envs),
           "--measure-episodes", str(measure_episodes),
           "--res", str(res), "--dt", str(dt),
           "--poisson-iters", str(poisson_iters),
           "--steps-per-action", str(steps_per_action),
           "--actions-per-episode", str(actions_per_episode),
           "--workdir", workdir,
           "--launch-timeout", str(timeout),
           "--heartbeat-timeout", str(timeout)]
    if no_gather:
        cmd.append("--no-gather")
    t0 = time.perf_counter()
    # several runner processes share this host, which only the CPU allows
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout + 120, cwd=str(_ROOT),
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet bench at {processes} process(es) failed "
            f"(exit {proc.returncode}); logs in {workdir}\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    stats_lines = [line for line in proc.stdout.splitlines()
                   if line.startswith("FLEET_STATS ")]
    if not stats_lines:
        raise RuntimeError(f"no FLEET_STATS line from {processes}-process "
                           f"bench:\n{proc.stdout[-2000:]}")
    stats = json.loads(stats_lines[-1].split(" ", 1)[1])
    stats["launcher_wall_s"] = wall
    return stats


def run(smoke: bool = False, out: str = None) -> dict:
    from repro.drl.train_state import code_fingerprint

    fleet_sizes = (1, 2) if smoke else (1, 2, 4)
    # non-smoke episodes use the paper's 50 solver steps per actuation so
    # each measured collect carries seconds of CFD — the regime the
    # efficiency claim is about; the per-collect fleet overhead (gloo
    # rendezvous + host gather, ~tens of ms) must amortize, not dominate
    cfg = {
        "plan": "4,4,1",
        "n_envs": 4,
        "measure_episodes": 2 if smoke else 3,
        "res": 6 if smoke else 8,
        "dt": 0.012 if smoke else 0.01,
        "poisson_iters": 30 if smoke else 50,
        "steps_per_action": 10 if smoke else 50,
        "actions_per_episode": 3 if smoke else 10,
        "timeout": 600.0 if smoke else 900.0,
    }
    cores = os.cpu_count() or 1
    points, baselines = {}, {}
    for n in fleet_sizes:
        points[n] = run_fleet_point(n, **cfg)
        if n > 1:
            # the no-comms twin: same fleet size, gather disabled
            baselines[n] = run_fleet_point(n, no_gather=True, **cfg)

    tp1 = points[fleet_sizes[0]]["env_steps_per_sec"]
    scaling = []
    for n in fleet_sizes:
        tp = points[n]["env_steps_per_sec"]
        tp_base = baselines[n]["env_steps_per_sec"] if n in baselines else tp
        scaling.append({
            "processes": n,
            "env_steps_per_sec": tp,
            "env_steps_per_sec_no_gather": tp_base,
            "elapsed_s": points[n]["elapsed_s"],
            "launcher_wall_s": points[n]["launcher_wall_s"],
            "speedup": tp / tp1,
            "efficiency_raw": tp / (n * tp1),
            "efficiency_vs_cores": tp / (min(n, cores) * tp1),
            "efficiency_comm": tp / tp_base,
        })
    top = scaling[-1]
    record = {
        "schema": BENCH_SCHEMA,
        "code": code_fingerprint(),
        "host": {"cores": cores},
        "config": dict(cfg, smoke=smoke, fleet_sizes=list(fleet_sizes)),
        "scaling": scaling,
        "paper": {"efficiency_60cores": PAPER_EFFICIENCY_60},
        "gate": {
            "metric": "efficiency_comm",
            "processes": top["processes"],
            "measured_efficiency": top["efficiency_comm"],
            "required_efficiency": GATE_EFFICIENCY,
            "passed": top["efficiency_comm"] >= GATE_EFFICIENCY,
        },
    }

    root = _ROOT / "artifacts"
    name = "BENCH_fleet_smoke.json" if smoke else "BENCH_fleet.json"
    path = Path(out) if out else root / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(record, indent=1, sort_keys=True))

    for s in scaling:
        print(f"fleet x{s['processes']}: {s['env_steps_per_sec']:.1f} "
              f"env-steps/s, speedup {s['speedup']:.2f}x, efficiency "
              f"raw {s['efficiency_raw']:.1%} / vs-cores "
              f"{s['efficiency_vs_cores']:.1%} / comm "
              f"{s['efficiency_comm']:.1%}")
    g = record["gate"]
    print(f"gate: comm efficiency {g['measured_efficiency']:.1%} at "
          f"{g['processes']} processes (requires "
          f">= {GATE_EFFICIENCY:.0%}; paper: {PAPER_EFFICIENCY_60:.0%} at "
          f"60 cores) -> {'PASS' if g['passed'] else 'FAIL'}")
    print(f"artifact -> {path}")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="1/2-process points only, tiny shapes; writes "
                         "BENCH_fleet_smoke.json")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
