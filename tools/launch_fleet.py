"""Single-command local fleet launcher with elastic shrink + resume.

Forks N runner processes on this box, each a full jax process of one
``jax.distributed`` fleet (``repro.launch.distributed``), so the engine's
"data" axis spans processes exactly as it would span hosts on a cluster.
Every runner gets the PINNED ``--xla_force_host_platform_device_count`` =
the plan's ``n_total`` (the bitwise contract: XLA CPU codegen differs by
forced device count, so the count must not change with the fleet size).

The launcher doubles as the elastic supervisor: runners heartbeat once per
episode, and when one dies (SIGKILL fast path: child exit) or hangs
(heartbeat older than ``--heartbeat-timeout``), the supervisor kills the
survivors, shrinks the fleet to the next process count that still divides
the plan, and relaunches with ``resume="auto"`` — training continues from
the latest durable checkpoint on the smaller fleet, same plan, same bits.

A chip belongs to one process, so all runners of this one-host launcher can
share it only when they run on the CPU: with more than one runner the
caller must set ``JAX_PLATFORMS=cpu`` itself, and the launcher refuses to
start otherwise.  The supervisor never touches JAX.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/launch_fleet.py \
        --processes 2 --episodes 4
    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/launch_fleet.py --smoke

Machine-readable lines on stdout (tests/bench parse these):
    FLEET_SHRINK gen=<g> procs=<old>-><new> reason=<exit|stale>
    FLEET_STATS {json}          (from process 0: bench throughput in
                                 --mode bench; train health counters —
                                 quarantines / grad_skips / rollbacks /
                                 sink_retries — in --mode train)
    FLEET_DONE episodes=<E>     (supervisor, after the fleet finishes)
"""
import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")
sys.path.insert(0, SRC)

# the test hook: a runner whose env carries this SIGKILLs ITSELF after that
# many episodes — a deterministic stand-in for a preempted/OOM-killed host
ENV_DIE_AFTER = "REPRO_TEST_DIE_AFTER"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--processes", type=int, default=2,
                    help="fleet size to start with")
    ap.add_argument("--plan", default="4,4,1", metavar="NT,NE,NR",
                    help="ParallelPlan n_total,n_envs,n_ranks (the forced "
                         "device count is pinned to n_total on EVERY runner)")
    ap.add_argument("--n-envs", type=int, default=None,
                    help="env batch size (default: the plan's n_envs)")
    ap.add_argument("--episodes", type=int, default=4)
    ap.add_argument("--mode", choices=("train", "bench"), default="train")
    ap.add_argument("--measure-episodes", type=int, default=3,
                    help="bench mode: timed collects after one warmup")
    ap.add_argument("--no-gather", action="store_true",
                    help="bench mode: time the distributed rollout WITHOUT "
                         "the trajectory all-gather — the no-comms "
                         "oversubscription baseline benchmarks divide by")
    ap.add_argument("--res", type=int, default=6, help="grid resolution")
    ap.add_argument("--dt", type=float, default=0.012)
    ap.add_argument("--poisson-iters", type=int, default=30)
    ap.add_argument("--steps-per-action", type=int, default=3)
    ap.add_argument("--actions-per-episode", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=None,
                    help="heartbeats/checkpoints/logs root (default: tmp)")
    ap.add_argument("--sink-root", default=None,
                    help="dataset sink root: each runner writes its env "
                         "shard into part{NNN}/ (trajectory_dataset)")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0,
                    help="seconds without a heartbeat before a runner "
                         "counts as hung")
    ap.add_argument("--launch-timeout", type=float, default=900.0,
                    help="hard wall-clock cap per fleet generation")
    ap.add_argument("--max-generations", type=int, default=4,
                    help="shrink-and-resume attempts before giving up")
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: tiny 2-process train, asserts "
                         "completion (overrides the knobs above)")
    ap.add_argument("--kill-process", type=int, default=None,
                    help="test hook: this runner id self-SIGKILLs ...")
    ap.add_argument("--kill-episode", type=int, default=None,
                    help="... after completing this many episodes")
    ap.add_argument("--role", choices=("supervisor", "runner"),
                    default="supervisor", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.smoke:
        args.processes = min(args.processes, 2)
        args.mode = "train"
        args.episodes = 2
    args.plan_tuple = tuple(int(x) for x in args.plan.split(","))
    if len(args.plan_tuple) != 3:
        ap.error(f"--plan must be n_total,n_envs,n_ranks (got {args.plan!r})")
    if args.n_envs is None:
        args.n_envs = args.plan_tuple[1]
    return args


# ---------------------------------------------------------------------------
# runner role — executes inside each fleet process
# ---------------------------------------------------------------------------

def run_runner(args) -> None:
    from repro.launch import distributed as dist
    from repro.launch.compile_cache import enable_compile_cache

    info = dist.initialize_fleet()       # from the REPRO_* env vars
    enable_compile_cache()

    from repro.cfd.env import EnvConfig
    from repro.cfd.grid import GridConfig
    from repro.core.plan import ParallelPlan
    from repro.drl.engine import SinkSpec
    from repro.drl.ppo import PPOConfig
    from repro.drl.train import TrainConfig, train

    die_after = int(os.environ.get(ENV_DIE_AFTER, "0"))
    hb = dist.HeartbeatReporter(info.process_id)

    def on_episode(traj, metrics):
        hb(traj, metrics)
        if die_after and hb.episodes >= die_after:
            os.kill(os.getpid(), signal.SIGKILL)    # never returns

    plan = ParallelPlan(*args.plan_tuple)
    workdir = Path(args.workdir)
    sink = None
    if args.sink_root:
        sink = SinkSpec(kind="dataset", root=args.sink_root)
    cfg = TrainConfig(
        env=EnvConfig(grid=GridConfig(res=args.res, dt=args.dt,
                                      poisson_iters=args.poisson_iters),
                      steps_per_action=args.steps_per_action,
                      actions_per_episode=args.actions_per_episode,
                      warmup_time=1.0),
        ppo=PPOConfig(epochs=2, minibatches=2),
        n_envs=args.n_envs, episodes=args.episodes, seed=args.seed,
        plan=plan, ckpt_dir=str(workdir / "ckpt"), ckpt_every=1,
        ckpt_async=False, resume="auto", sink=sink)

    if args.mode == "bench":
        run_runner_bench(args, cfg, info, on_episode)
        return
    health = {}
    hist, _ = train(cfg, log_fn=print if info.is_coordinator else None,
                    on_episode=on_episode, health=health)
    if info.is_coordinator:
        print("FLEET_STATS " + json.dumps({
            "mode": "train",
            "processes": info.num_processes,
            "episodes": len(hist["reward"]),
            "health": health,
        }), flush=True)
    print(f"RUNNER_DONE process={info.process_id} "
          f"episodes={len(hist['reward'])}", flush=True)


def run_runner_bench(args, cfg, info, on_episode) -> None:
    """Rollout-throughput probe: one warmup collect (compile), then
    ``--measure-episodes`` timed collects.  Process 0 prints FLEET_STATS."""
    import jax

    from repro.cfd.env import CylinderEnv
    from repro.drl import networks
    from repro.drl.engine import (EngineConfig, RolloutEngine,
                                  broadcast_env_state, place_env_batch)
    from repro.drl.ppo import PPOConfig

    from repro.core.autotune import resolve_plan
    resolved = resolve_plan(cfg.plan, grid=cfg.env.grid, smoke=True)
    mesh = resolved.build_mesh()
    env = CylinderEnv(cfg.env, backend=resolved.backend, mesh=mesh)
    st0, obs0 = env.reset()
    st_b, obs_b = broadcast_env_state(st0, obs0, cfg.n_envs)
    engine = RolloutEngine.for_env(
        env, EngineConfig(n_envs=cfg.n_envs,
                          horizon=cfg.env.actions_per_episode,
                          n_ranks=resolved.n_ranks, fleet=True), mesh=mesh)
    pcfg = networks.PolicyConfig(obs_dim=int(obs_b.shape[-1]))
    params, _, _, key = engine.init(pcfg, PPOConfig(), cfg.seed)
    st_b = place_env_batch(mesh, st_b, engine.cfg.n_ranks)
    obs_b = place_env_batch(mesh, obs_b, 1)

    key, kw = jax.random.split(key)
    if args.no_gather:
        engine.rollout_local(params, st_b, obs_b, kw)   # warmup: compile
    else:
        engine.collect(params, st_b, obs_b, kw)         # warmup: compile
    t0 = time.perf_counter()
    for _ in range(args.measure_episodes):
        key, kr = jax.random.split(key)
        if args.no_gather:
            traj = engine.rollout_local(params, st_b, obs_b, kr)
            on_episode(traj, None)
        else:
            batch, traj = engine.collect(params, st_b, obs_b, kr)
            on_episode(traj, None)
            jax.block_until_ready(batch)
    elapsed = time.perf_counter() - t0
    env_steps = (args.measure_episodes * cfg.n_envs
                 * cfg.env.actions_per_episode * cfg.env.steps_per_action)
    if info.is_coordinator:
        print("FLEET_STATS " + json.dumps({
            "processes": info.num_processes,
            "episodes": args.measure_episodes,
            "n_envs": cfg.n_envs,
            "gather": not args.no_gather,
            "env_steps": env_steps,
            "elapsed_s": elapsed,
            "env_steps_per_sec": env_steps / elapsed,
        }), flush=True)


# ---------------------------------------------------------------------------
# supervisor role — fork, watch, shrink, resume
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _shrink(n_total: int, n_ranks: int, procs: int) -> int:
    """Next viable fleet size below ``procs``: must divide n_total with
    each process holding whole envs (halo stays intra-host)."""
    for p in range(procs - 1, 0, -1):
        if n_total % p == 0 and (n_total // p) % n_ranks == 0:
            return p
    return 0


def _spawn(args, procs: int, gen: int, workdir: Path):
    from repro.launch.distributed import fleet_env
    port = _free_port()
    hb_dir = workdir / f"hb_gen{gen}"
    hb_dir.mkdir(parents=True, exist_ok=True)
    runner_argv = [
        sys.executable, os.path.abspath(__file__), "--role", "runner",
        "--plan", args.plan, "--n-envs", str(args.n_envs),
        "--episodes", str(args.episodes), "--mode", args.mode,
        "--measure-episodes", str(args.measure_episodes),
        "--res", str(args.res), "--dt", str(args.dt),
        "--poisson-iters", str(args.poisson_iters),
        "--steps-per-action", str(args.steps_per_action),
        "--actions-per-episode", str(args.actions_per_episode),
        "--seed", str(args.seed), "--workdir", str(workdir),
    ]
    if args.sink_root:
        runner_argv += ["--sink-root", args.sink_root]
    if args.no_gather:
        runner_argv += ["--no-gather"]
    children = []
    for pid in range(procs):
        env = fleet_env(coordinator=f"127.0.0.1:{port}",
                        num_processes=procs, process_id=pid,
                        n_total_devices=args.plan_tuple[0],
                        heartbeat_dir=str(hb_dir))
        env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
        env.setdefault("PYTHONUNBUFFERED", "1")
        if (gen == 0 and args.kill_process == pid
                and args.kill_episode is not None):
            env[ENV_DIE_AFTER] = str(args.kill_episode)
        log = open(workdir / f"runner_gen{gen}_p{pid:03d}.log", "wb")
        children.append((subprocess.Popen(
            runner_argv, env=env,
            stdout=subprocess.PIPE if pid == 0 else log,
            stderr=subprocess.STDOUT if pid == 0 else log), log))
    return children, hb_dir


def _drain_proc0(children, sink):
    """Forward process 0's buffered stdout lines (non-blockingly sized
    reads are overkill here: proc 0's pipe is drained after exit, and
    FLEET_STATS/train logs are tiny)."""
    p0 = children[0][0]
    out, _ = p0.communicate()
    for line in (out or b"").decode(errors="replace").splitlines():
        print(line, flush=True)
        sink.append(line)


def run_supervisor(args) -> int:
    import tempfile
    from repro.launch.distributed import stale_processes

    workdir = Path(args.workdir or tempfile.mkdtemp(prefix="fleet_"))
    workdir.mkdir(parents=True, exist_ok=True)
    n_total, _, n_ranks = args.plan_tuple
    procs, gen = args.processes, 0
    if procs > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"--processes {procs} would start {procs} runners on this "
                 f"host, but an accelerator belongs to one process; set "
                 f"JAX_PLATFORMS=cpu to run a multi-process fleet on the "
                 f"CPU, or use --processes 1 (one process drives every "
                 f"local chip)")
    if n_total % procs or (n_total // procs) % n_ranks:
        sys.exit(f"--processes {procs} does not divide plan {args.plan} "
                 f"with intra-host halos; viable sizes divide n_total="
                 f"{n_total} with whole envs per process")
    lines: list = []

    while True:
        print(f"fleet gen={gen}: {procs} process(es), plan {args.plan}, "
              f"mode {args.mode} -> {workdir}", flush=True)
        children, hb_dir = _spawn(args, procs, gen, workdir)
        deadline = time.time() + args.launch_timeout
        reason = None
        while reason is None:
            states = [c.poll() for c, _ in children]
            if all(s == 0 for s in states):
                break                                   # clean finish
            if any(s not in (None, 0) for s in states):
                reason = "exit"
            elif stale_processes(str(hb_dir), procs,
                                 args.heartbeat_timeout):
                reason = "stale"
            elif time.time() > deadline:
                reason = "timeout"
            else:
                time.sleep(0.2)
        if reason is None:                              # success
            _drain_proc0(children, lines)
            for _, log in children:
                log.close()
            break
        for c, log in children:                         # kill survivors
            if c.poll() is None:
                c.kill()
            c.wait()
            log.close()
        dead = [i for i, (c, _) in enumerate(children) if c.returncode != 0]
        nxt = _shrink(n_total, n_ranks, procs)
        gen += 1
        if nxt == 0 or gen >= args.max_generations or reason == "timeout":
            sys.exit(f"fleet failed (reason={reason}, dead runners {dead}) "
                     f"and cannot shrink further; logs in {workdir}")
        print(f"FLEET_SHRINK gen={gen} procs={procs}->{nxt} "
              f"reason={reason}", flush=True)
        procs = nxt
        # resume="auto" in every runner picks up the latest checkpoint

    done = [line for line in lines if line.startswith("RUNNER_DONE")]
    episodes = (int(done[-1].rsplit("=", 1)[1]) if done
                else args.episodes if args.mode == "train" else 0)
    print(f"FLEET_DONE episodes={episodes}", flush=True)
    if args.smoke:
        assert episodes >= args.episodes, (episodes, args.episodes)
        print("FLEET_SMOKE_OK", flush=True)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.role == "runner":
        run_runner(args)
        return 0
    return run_supervisor(args)


if __name__ == "__main__":
    sys.exit(main())
