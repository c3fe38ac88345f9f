"""End-to-end driver: PPO learns active flow control on the cylinder
(the paper's Fig. 5 experiment at reduced scale).

Defaults fit a single CPU core in ~20-40 min: coarse grid, short episodes.
Increase --res/--episodes to approach the paper's setup.

    PYTHONPATH=src python examples/drl_cylinder.py --episodes 60
"""
import argparse
import json
from pathlib import Path

import numpy as np

from repro.cfd.env import EnvConfig
from repro.cfd.grid import GridConfig
from repro.drl.engine import SinkSpec
from repro.drl.ppo import PPOConfig
from repro.drl.train import TrainConfig, train
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=60)
    ap.add_argument("--n-envs", type=int, default=4)
    ap.add_argument("--res", type=int, default=8)
    ap.add_argument("--actions", type=int, default=40)
    ap.add_argument("--steps-per-action", type=int, default=25)
    ap.add_argument("--warmup", type=float, default=20.0)
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario names (see "
                         "repro.cfd.scenarios.list_scenarios(), e.g. "
                         "'cyl_re100,cyl_re200,cyl_re100_rotary') assigned "
                         "round-robin over the env batch; default: the "
                         "single Re=100 jets case")
    ap.add_argument("--policy", default="mlp",
                    choices=["mlp", "attention"],
                    help="policy architecture: 'mlp' (the paper's 2x512 "
                         "tanh MLP, default) or 'attention' (permutation-"
                         "invariant set encoder over (coord, value) probe "
                         "tokens — recommended for mixed or multi-body "
                         "batches, e.g. --scenarios pinball_re100)")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print the scenario registry and exit")
    ap.add_argument("--plan", default=None,
                    help="hybrid placement: 'auto' (measure this host and "
                         "optimize, core.autotune) or 'N_ENVSxN_RANKS' "
                         "(e.g. '2x2' = 2 envs x 2 spatial CFD shards, "
                         "runs the halo Poisson backend); default: plain "
                         "single-host vmap")
    ap.add_argument("--sink", default=None,
                    help="trajectory sink spec 'kind[:root]': 'none', "
                         "'memory', 'binary:/path', 'zstd:/path' (one file "
                         "per episode, paper §IV I/O), or 'dataset:/path' "
                         "(sharded files + manifest, replayable via "
                         "tools/replay_smoke.py)")
    ap.add_argument("--spill", default=None,
                    choices=["none", "memory", "binary", "zstd"],
                    help="deprecated alias for --sink KIND:--spill-dir")
    ap.add_argument("--spill-dir", default="artifacts/traj_spill")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory: save the full TrainState "
                         "(params, optimizer, PRNG carry, env batch, "
                         "history) every --ckpt-every episodes with async "
                         "background writes; required for --resume")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retain only the newest N checkpoints")
    ap.add_argument("--resume", nargs="?", const="auto", default=None,
                    help="resume training: bare --resume restarts from the "
                         "latest valid checkpoint in --ckpt-dir (fresh run "
                         "when none exists yet); or pass an explicit .ckpt "
                         "path / checkpoint directory.  --episodes is the "
                         "TOTAL target, so an interrupted run rerun with "
                         "the same flags just continues")
    ap.add_argument("--out", default="artifacts/drl_cylinder.json")
    args = ap.parse_args()

    if args.list_scenarios:
        from repro.cfd.scenarios import get_scenario, list_scenarios
        for name in list_scenarios():
            s = get_scenario(name)
            print(f"{name:22s} Re={s.re:<6g} {s.actuation:7s} "
                  f"{s.geometry:9s} {s.probes:9s} {s.description}")
        return

    plan = args.plan
    if plan and plan != "auto":
        n_envs, n_ranks = (int(v) for v in plan.lower().split("x"))
        plan = (n_envs, n_ranks)

    if args.sink is not None and args.spill is not None:
        ap.error("--spill is a deprecated alias for --sink; pass only one")
    if args.spill is not None:
        print(f"note: --spill is deprecated; use "
              f"--sink {args.spill}:{args.spill_dir}")
        spec = SinkSpec(kind=args.spill, root=args.spill_dir
                        if args.spill in ("binary", "zstd") else None)
    else:
        spec = SinkSpec.parse(args.sink)

    cfg = TrainConfig(
        env=EnvConfig(
            grid=GridConfig(res=args.res, dt=0.01, poisson_iters=50),
            steps_per_action=args.steps_per_action,
            actions_per_episode=args.actions,
            warmup_time=args.warmup,
        ),
        ppo=PPOConfig(lr=3e-4, epochs=6, minibatches=4,
                      entropy_coef=0.005),
        n_envs=args.n_envs,
        episodes=args.episodes,
        scenarios=(tuple(s.strip() for s in args.scenarios.split(",")
                         if s.strip())
                   if args.scenarios else None),
        policy=args.policy,
        plan=plan,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        ckpt_keep=args.ckpt_keep,
        resume=args.resume,
        sink=spec,
    )
    sink = spec.build()
    enable_compile_cache()
    hist, params = train(cfg, sink=sink)
    if sink is not None:
        print(f"sink[{spec.kind}]: {sink.episodes} episodes, "
              f"{sink.bytes_written / 1e6:.2f} MB, "
              f"{sink.time_spent:.2f}s interface time")
    # report drag reduction: mean CD of last episodes vs uncontrolled CD0
    first5 = float(np.mean(hist["cd"][:5]))
    last5 = float(np.mean(hist["cd"][-5:]))
    r_first = float(np.mean(hist["reward"][:5]))
    r_last = float(np.mean(hist["reward"][-5:]))
    print(f"\nreturn: {r_first:+.2f} -> {r_last:+.2f}")
    print(f"tail CD: {first5:.3f} -> {last5:.3f} "
          f"({100*(last5-first5)/first5:+.1f}% change; paper: -8%)")
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({k: np.asarray(v).tolist()
                               for k, v in hist.items()}, indent=1))
    print(f"history -> {out}")


if __name__ == "__main__":
    main()
