"""Single-host DRL training driver: multi-env PPO on the cylinder AFC task.

This is the paper's training loop (Fig. 4): N_envs environments roll out one
episode each in parallel, trajectories are batched, and PPO updates the shared
policy.  Collection itself — the vmap/shard path, GAE and flattening — is the
``RolloutEngine``'s single implementation (drl/engine.py); this module owns
the episode loop, logging, the optional CFD<->DRL file interface hook, the
hybrid-plan resolution (``TrainConfig(plan="auto" | ParallelPlan)``, see
``repro.core.autotune``), and **fault tolerance**: with ``ckpt_dir`` set,
an ``AsyncCheckpointer`` persists the full ``TrainState`` (params, optimizer
moments, PRNG carry, PPO step, env batch, history) every ``ckpt_every``
episodes, with the disk write hidden behind the next episode's collection.
``resume=`` restarts from the latest valid checkpoint — bitwise-identically
under the same plan, and across plans by re-sharding the host-round-tripped
env batch onto the new mesh.

Fresh and resumed runs share one code path: both build a ``TrainState``
first (fresh from ``engine.init``, resumed from the checkpoint) and the loop
only ever reads that state — the PRNG key lives in the state, never
re-derived from ``cfg.seed`` mid-run.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cfd.env import CylinderEnv, EnvConfig
from repro.ckpt import checkpoint as ckpt_mod
from repro.core import spans as core_spans
from repro.drl import networks, spans
from repro.drl import train_state as ts_mod
from repro.drl.engine import (EngineConfig, RolloutEngine, SinkSpec,
                              TrajectorySink, broadcast_env_state,
                              place_env_batch)
from repro.drl.health import DivergenceError, Watchdog, WatchdogConfig
from repro.drl.ppo import PPOConfig, make_optimizer
from repro.drl.train_state import HISTORY_FIELDS, TrainState
from repro.launch import distributed as dist_mod


def resolve_watchdog(spec) -> Optional[Watchdog]:
    """TrainConfig.watchdog -> Watchdog | None (shared with train_async)."""
    if not spec:
        return None
    return Watchdog(spec if isinstance(spec, WatchdogConfig)
                    else WatchdogConfig())


@dataclass
class TrainConfig:
    env: EnvConfig = field(default_factory=EnvConfig)
    ppo: PPOConfig = field(default_factory=PPOConfig)
    n_envs: int = 4
    episodes: int = 100
    seed: int = 0
    # scenario names (repro.cfd.scenarios) assigned round-robin over the env
    # batch; None = the single case described by ``env`` (historical default)
    scenarios: Optional[Tuple[str, ...]] = None
    # policy architecture: "mlp" (the paper's 2x512 tanh MLP, historical
    # default) | "attention" (permutation-invariant set encoder over
    # (coord, value) probe tokens — serves mixed/variable sensor sets)
    policy: str = "mlp"
    # hybrid placement: None (single-host vmap, historical default),
    # "auto" (measure this host and optimize via core.autotune), a
    # core.plan.ParallelPlan / (n_envs, n_ranks) pair, or a ResolvedPlan.
    # train() builds the mesh from the resolved plan, selects the matching
    # Poisson backend, and logs the chosen split.
    plan: Any = None
    # extra kwargs for the plan="auto" measurement (core.autotune.autotune),
    # e.g. {"smoke": False, "iters": 5} for a careful median-of-5 probe.
    # Default: a quick single-iteration smoke probe.
    plan_args: Optional[Dict[str, Any]] = None
    # fault tolerance: with ckpt_dir set, the TrainState is saved every
    # ckpt_every episodes (and at the final one) via an AsyncCheckpointer
    # (keep newest ckpt_keep; background write unless ckpt_async=False).
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 10
    ckpt_keep: int = 3
    ckpt_async: bool = True
    ckpt_compress: bool = True
    # resume: None (fresh run) | True / "latest" (latest valid checkpoint in
    # ckpt_dir — error when none) | "auto" (same, but fresh when the dir has
    # none yet: the preemptible-job idiom) | an explicit path (.ckpt file or
    # a checkpoint directory).  ``episodes`` is the TOTAL target: resuming a
    # 40-episode checkpoint with episodes=100 runs 60 more.
    resume: Any = None
    # trajectory spill: one SinkSpec for every strategy ('none' | 'memory' |
    # 'binary' | 'zstd' | 'dataset'); an explicit sink= to train() wins.
    # The run fingerprint (run_metadata) is annotated into dataset manifests.
    sink: Optional[SinkSpec] = None
    # multi-process fleet mode (repro.launch.distributed): None = auto
    # (fleet when this process is part of a jax.distributed fleet or the
    # launcher exported REPRO_FLEET=1 — single-process fleets keep the same
    # engine path so runs are bitwise-comparable across fleet sizes).
    # Requires a plan; only process 0 logs and writes checkpoints.
    fleet: Optional[bool] = None
    # training-health watchdog (drl/health.py): True = default thresholds,
    # a WatchdogConfig for custom ones, False/None = off.  On a trip the
    # run rolls back to the last healthy checkpoint (fresh restart when
    # ckpt_dir is unset) and replays, bounded by max_rollbacks.
    watchdog: Any = True


def train(cfg: TrainConfig, *, log_fn: Optional[Callable] = print,
          interface=None, sink: Optional[TrajectorySink] = None,
          on_episode: Optional[Callable] = None,
          health: Optional[Dict[str, Any]] = None,
          _rollbacks: int = 0, _sink_retries0: int = 0,
          ) -> Tuple[Dict[str, np.ndarray], Any]:
    """Returns (history dict of per-episode arrays, trained params).

    ``on_episode(traj, metrics)`` is an extra per-episode hook (fleet
    runners use it for heartbeats); it fires after the built-in logging.
    ``health`` (optional dict, filled in place) receives the self-healing
    counters on return: quarantines, grad_skips, rollbacks, sink_retries —
    the same numbers stored under ``"health"`` in checkpoint metadata.
    ``_rollbacks``/``_sink_retries0`` are internal: the watchdog-rollback
    retry depth and the retries counted by pre-rollback engine sinks."""
    if not _rollbacks:
        core_spans.setup_seconds.clear()   # this run's set-up spans only
    resolved = mesh = None
    backend = None
    n_envs = cfg.n_envs
    fleet = dist_mod.fleet_active() if cfg.fleet is None else cfg.fleet
    proc0 = jax.process_index() == 0
    if fleet and cfg.plan is None:
        raise ValueError("fleet training needs a plan (TrainConfig.plan): "
                         "the process-spanning mesh is built from it")
    if fleet and not proc0:
        log_fn = None                  # one log stream: the coordinator's
    if cfg.plan is not None:
        from repro.core.autotune import resolve_plan
        resolved = resolve_plan(cfg.plan, grid=cfg.env.grid,
                                **{"smoke": True, **(cfg.plan_args or {})})
        mesh = resolved.build_mesh()
        backend = resolved.backend
        if n_envs % resolved.n_envs:
            # batch must tile the mesh "data" axis; round up, never down
            n_envs += resolved.n_envs - n_envs % resolved.n_envs
        if log_fn:
            log_fn(resolved.describe())
            if n_envs != cfg.n_envs:
                log_fn(f"n_envs {cfg.n_envs} -> {n_envs} (rounded up to a "
                       f"multiple of the mesh data axis {resolved.n_envs})")

    env = CylinderEnv(cfg.env, backend=backend, mesh=mesh)

    ts: Optional[TrainState] = None
    src = ts_mod.resolve_resume(cfg.resume, cfg.ckpt_dir)
    if src is not None:
        ts, ckpt_meta = ts_mod.load_train_state(src)

    if ts is not None:
        # resume: the checkpointed env batch IS the developed flow — no
        # warmup, no reset; arrays are host ndarrays until placed below.
        st_b, obs_b = ts.env_state, ts.obs
    elif cfg.scenarios:
        # mixed-scenario batch: per-env physics, one vmapped program
        st_b, obs_b = env.reset_batch(cfg.scenarios, n_envs)
    else:
        st0, obs0 = env.reset()       # warms up + calibrates CD0
        st_b, obs_b = broadcast_env_state(st0, obs0, n_envs)

    # the policy's obs_dim is DERIVED from the resolved batch, never assumed:
    # the PolicyConfig default (149) silently drifts from mixed-scenario
    # padding otherwise, surfacing as an opaque shape error inside jit
    obs_dim = int(obs_b.shape[-1])
    if cfg.scenarios and ts is None:
        from repro.cfd import scenarios as scn_mod
        expect = scn_mod.common_obs_dim(cfg.scenarios)
        if expect != obs_dim:
            raise ValueError(
                f"observation width mismatch: scenarios "
                f"{tuple(cfg.scenarios)} pad to common_obs_dim={expect} but "
                f"the reset batch produced obs_dim={obs_dim}; the env reset "
                f"and the policy must agree on one padded width")
    jv = st_b.jet_vel if ts is None else jnp.asarray(st_b.jet_vel)
    act_dim = int(jv.shape[-1]) if jv.ndim > 1 else 1
    pcfg = networks.PolicyConfig(obs_dim=obs_dim, act_dim=act_dim,
                                 policy=cfg.policy)

    engine = RolloutEngine.for_env(
        env, EngineConfig(n_envs=n_envs,
                          horizon=cfg.env.actions_per_episode,
                          gamma=cfg.ppo.gamma, lam=cfg.ppo.lam,
                          n_ranks=resolved.n_ranks if resolved else 1,
                          sink=cfg.sink, fleet=fleet),
        mesh=mesh, sink=sink)

    run_meta = ts_mod.run_metadata(
        n_envs=n_envs, obs_dim=pcfg.obs_dim, seed=cfg.seed,
        grid=cfg.env.grid, horizon=cfg.env.actions_per_episode,
        steps_per_action=cfg.env.steps_per_action, scenarios=cfg.scenarios,
        plan={"n_envs": resolved.n_envs, "n_ranks": resolved.n_ranks,
              "backend": resolved.backend,
              "n_processes": jax.process_count()} if resolved else None,
        policy={"policy": cfg.policy, "obs_dim": pcfg.obs_dim,
                "act_dim": pcfg.act_dim})
    if engine.sink is not None:
        # durable datasets record which run (and which code) produced them
        engine.sink.annotate(**run_meta)
    if ts is not None:
        for note in ts_mod.check_resume_compatible(ckpt_meta, run_meta):
            if log_fn:
                log_fn(note)
        if log_fn:
            log_fn(f"resume: {src} @ episode {int(ts.episode)}")

    # pre-place the batch on the mesh (see shard_env_batch's docstring).
    # For a resumed run this is the cross-plan re-sharding step.  Fleet
    # checkpoints snapshot the PRE-placement host copies: a process-spanning
    # global array cannot be pulled back to one host at save time.
    st_host = jax.tree.map(np.asarray, st_b) if fleet else None
    obs_host = np.asarray(obs_b) if fleet else None
    st_b = place_env_batch(mesh, st_b, engine.cfg.n_ranks)
    obs_b = place_env_batch(mesh, obs_b, 1)
    if log_fn and mesh is not None:
        log_fn(f"env batch: {n_envs} envs placed on "
               f"{len(st_b.flow.u.sharding.device_set)} device(s)")

    if ts is None:
        params, optimizer, opt_state, key = engine.init(pcfg, cfg.ppo,
                                                        cfg.seed)
        ts = TrainState(params=params, opt_state=opt_state, key=key,
                        step=jnp.int32(0), episode=jnp.int32(0),
                        env_state=st_b, obs=obs_b,
                        history={f: np.zeros((0,)) for f in HISTORY_FIELDS})
    else:
        optimizer = make_optimizer(cfg.ppo)
        ts = ts._replace(
            params=jax.tree.map(jnp.asarray, ts.params),
            opt_state=jax.tree.map(jnp.asarray, ts.opt_state),
            key=jnp.asarray(ts.key), env_state=st_b, obs=obs_b)

    hist = {f: [float(x) for x in np.asarray(ts.history.get(f, ()))]
            for f in HISTORY_FIELDS}
    # checkpoints written before the health counters existed (or truncated
    # by a mid-episode crash) restore with short columns: zero-pad to the
    # reward column's length — healthy episodes logged zeros anyway
    for f in HISTORY_FIELDS:
        if len(hist[f]) < len(hist["reward"]):
            hist[f] += [0.0] * (len(hist["reward"]) - len(hist[f]))
    ep0 = int(ts.episode)
    engine.episode = ep0              # sink episode ids continue, not restart
    watchdog = resolve_watchdog(cfg.watchdog)
    if health is None:
        health = {}

    def fill_health() -> Dict[str, Any]:
        health.update(
            quarantines=int(round(sum(hist["quarantines"]))),
            grad_skips=int(round(sum(hist["grad_skips"]))),
            rollbacks=int(_rollbacks),
            sink_retries=_sink_retries0 + (int(engine.sink.retries)
                                           if engine.sink else 0))
        return dict(health)

    remaining = cfg.episodes - ep0
    if remaining <= 0:
        fill_health()
        if log_fn:
            log_fn(f"checkpoint already has {ep0} episodes >= target "
                   f"{cfg.episodes}; nothing to train")
        return {k: np.asarray(v) for k, v in hist.items()}, ts.params

    ckpter = None
    if cfg.ckpt_dir and proc0:        # one writer: the coordinator
        ckpter = ckpt_mod.AsyncCheckpointer(
            cfg.ckpt_dir, keep=cfg.ckpt_keep, compress=cfg.ckpt_compress,
            background=cfg.ckpt_async)

    t_ep = [time.time()]
    ep_hook = on_episode               # the caller's hook (fleet heartbeats)

    def on_batch(batch):
        # paper's CFD<->DRL interface experiment
        if interface is None:
            return batch
        with spans.span("io.interface"):
            return interface.exchange(batch)

    def on_episode(traj, metrics):
        ep = len(hist["reward"])
        with spans.span("sync"):
            r = float(jnp.mean(jnp.sum(traj.reward, axis=1)))
        with spans.span("sync"):
            cd = float(jnp.mean(traj.cd[:, -10:]))
        with spans.span("sync"):
            cl = float(jnp.mean(jnp.abs(traj.cl[:, -10:])))
        hist["reward"].append(r)
        hist["cd"].append(cd)
        hist["cl"].append(cl)
        now = time.time()
        hist["wall"].append(now - t_ep[0])
        t_ep[0] = now
        # self-healing counters: quarantined env-steps from the sentinel
        # mask, rejected updates from the learner guard
        quar = 0.0
        if traj.valid is not None:
            with spans.span("sync"):
                quar = float(jnp.sum(1.0 - traj.valid))
        skips = 0.0
        if metrics is not None and "grad_skips" in metrics:
            skips = spans.read(metrics["grad_skips"])
        hist["quarantines"].append(quar)
        hist["grad_skips"].append(skips)
        if log_fn and (quar or skips):
            log_fn(f"ep {ep:4d}  health: {quar:.0f} env-step(s) "
                   f"quarantined, {skips:.0f} update(s) skipped")
        if log_fn and (ep % max(1, cfg.episodes // 20) == 0
                       or ep == cfg.episodes - 1):
            log_fn(f"ep {ep:4d}  return {r:+8.3f}  CD(tail) {cd:.3f}  "
                   f"|CL| {cl:.3f}  {hist['wall'][-1]:.1f}s")
        if ep_hook is not None:
            with spans.span("caller"):
                ep_hook(traj, metrics)
        if watchdog is not None:
            mf = (None if metrics is None
                  else {k: spans.read(v) for k, v in metrics.items()})
            reason = watchdog.observe(mf, episode=ep)
            if reason is not None:
                # raised BEFORE on_state fires for this episode, so the
                # anomalous state is never checkpointed — the latest
                # checkpoint on disk is by construction a healthy one
                raise DivergenceError(ep, reason)

    def on_state(carry):
        if ckpter is None:
            return
        done = len(hist["reward"])    # episodes completed, incl. resumed
        if done % max(1, cfg.ckpt_every) and done != cfg.episodes:
            return
        with spans.span("io.ckpt"):
            snap = TrainState(params=carry.params, opt_state=carry.opt_state,
                              key=carry.key, step=carry.step,
                              episode=jnp.int32(done),
                              env_state=st_host if fleet else st_b,
                              obs=obs_host if fleet else obs_b,
                              history={f: np.asarray(hist[f])
                                       for f in HISTORY_FIELDS})
            ckpter.save(done, ts_mod.to_tree(snap),
                        metadata=ts_mod.state_metadata(
                            snap, {**run_meta, "health": fill_health()}))

    divergence: Optional[DivergenceError] = None
    try:
        params, _, _ = engine.run_sync(ts.params, ts.opt_state, cfg.ppo,
                                       optimizer, ts.env_state, ts.obs,
                                       ts.key, remaining, step=ts.step,
                                       on_batch=on_batch,
                                       on_episode=on_episode,
                                       on_state=on_state)
    except DivergenceError as e:
        divergence = e
    finally:
        if ckpter is not None:
            ckpter.close()            # drain the in-flight write
            if log_fn and ckpter.saves:
                log_fn(f"checkpoints: {ckpter.saves} saves, "
                       f"{ckpter.bytes_written / 1e6:.2f} MB -> "
                       f"{cfg.ckpt_dir} ({ckpter.time_blocked:.2f}s "
                       f"caller-visible)")

    if divergence is not None:
        # roll back to the last healthy checkpoint (the anomalous episode
        # was never saved) and replay; without a ckpt_dir the retry is a
        # fresh restart.  Deterministic divergences replay identically and
        # exhaust the retry budget — the error below says so.
        max_rb = watchdog.cfg.max_rollbacks if watchdog else 0
        if _rollbacks >= max_rb:
            raise RuntimeError(
                f"training diverged and {_rollbacks} rollback(s) to the "
                f"last healthy checkpoint did not clear it ({divergence}); "
                f"a deterministic divergence replays identically — lower "
                f"the learning rate / tighten PPO clipping, or raise "
                f"WatchdogConfig.max_rollbacks if the trigger is transient"
            ) from divergence
        if log_fn:
            log_fn(f"watchdog: {divergence}; rolling back "
                   f"(retry {_rollbacks + 1}/{max_rb})")
        retry_cfg = dataclasses.replace(
            cfg, resume="auto" if cfg.ckpt_dir else None)
        # a cfg-built sink dies with this engine, so its retry count must be
        # carried forward; an explicit ``sink=`` object survives the
        # recursion and keeps its own count (no double-counting)
        prior = (0 if sink is not None
                 else _sink_retries0 + (int(engine.sink.retries)
                                        if engine.sink else 0))
        return train(retry_cfg, log_fn=log_fn, interface=interface,
                     sink=sink, on_episode=ep_hook, health=health,
                     _rollbacks=_rollbacks + 1, _sink_retries0=prior)

    fill_health()
    return {k: np.asarray(v) for k, v in hist.items()}, params
