#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload cyl_re100_jets.paper \
        --seeds 11,12,13 --control --faults unchanged,half_batch,reward

For each seed: one short run of the cell (its first episodes and one window
episode) with the six compared numbers of a sound run; with ``--control``
the same numbers for the reference computed in bfloat16 in the program's
place, under that run's actions; with ``--precisions`` the numbers of the
program run with its matrix products at a lower precision than the
configuration states (``high``: three bf16 passes, ``default``: one); with
``--faults`` the numbers of runs with a fault planted in the program:

- ``unchanged``   the PPO update returns the weights and optimizer state it
                  was given;
- ``half_batch``  the PPO update sees only the first half of the batch;
- ``reward``      every reward is off by 0.01 where the env produces it;
- ``batch_rows``  the learner's batch pairs each row's observation with the
                  action of the row before, where the batch is assembled;
- ``policy_logp`` the rollout's sampling log-probability is computed with
                  the log-std shifted by 0.01 (the action is drawn as
                  before), a slip a fused policy kernel could make;
- ``skip_leaf``   the PPO update leaves the policy's output layer weights
                  (``SKIPPED``) as they were, and moves every other leaf
                  and the optimizer state as before;
- ``exchange``    the update reads only the first chip's share of the batch,
                  as each chip does when the gradients' reduction across
                  chips is left out (cells on more than one chip).

Runs on the chip like ``bench/run.py``, through the harness's own run, so
the state each run records comes through the same checkpoint stand-in as in
a timed run.  Each run's readings go to stdout as they come, each with the
episode (or leaf) where it was worst, then a summary: the largest reading of
the sound runs (``lower``) and the smallest of each control and fault
(``<group>_min``), each with the seed and place it came from.
"""
import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SKIPPED = "['actor'][2]['w']"


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program for the duration of the block."""
    import jax
    import jax.numpy as jnp
    from repro.cfd import env as env_mod
    from repro.drl import engine, networks
    orig_update = engine.ppo_update
    orig_step = env_mod.CylinderEnv.env_step
    orig_batch = engine.Batch
    orig_sample = networks.sample_action

    def unchanged(cfg, opt, params, opt_state, batch, key, step):
        _, _, new_step, metrics = orig_update(cfg, opt, params, opt_state,
                                              batch, key, step)
        return params, opt_state, new_step, metrics

    def half_batch(cfg, opt, params, opt_state, batch, key, step):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return orig_update(cfg, opt, params, opt_state, half, key, step)

    def skip_leaf(cfg, opt, params, opt_state, batch, key, step):
        new, new_opt, new_step, metrics = orig_update(
            cfg, opt, params, opt_state, batch, key, step)
        new = jax.tree_util.tree_map_with_path(
            lambda k, a, b: b if jax.tree_util.keystr(k) == SKIPPED else a,
            new, params)
        return new, new_opt, new_step, metrics

    def exchange(cfg, opt, params, opt_state, batch, key, step):
        n = jax.device_count()
        local = jax.tree.map(lambda x: x[: x.shape[0] // n], batch)
        return orig_update(cfg, opt, params, opt_state, local, key, step)

    def reward(self, st, action):
        st2, out = orig_step(self, st, action)
        return st2, out._replace(reward=out.reward + 0.01)

    def batch_rows(**fields):
        fields["act"] = jnp.roll(fields["act"], 1, axis=0)
        return orig_batch(**fields)

    def policy_logp(params, obs, key, aux=None):
        act, _ = orig_sample(params, obs, key, aux=aux)
        mean, log_std = networks.policy_dist(params, obs, aux)
        log_std = log_std + 0.01
        logp = -0.5 * ((act - mean) ** 2 / jnp.exp(2 * log_std)
                       + 2 * log_std + jnp.log(2 * jnp.pi))
        return act, jnp.sum(logp, axis=-1)

    if fault == "unchanged":
        engine.ppo_update = unchanged
    elif fault == "half_batch":
        engine.ppo_update = half_batch
    elif fault == "skip_leaf":
        engine.ppo_update = skip_leaf
    elif fault == "exchange":
        engine.ppo_update = exchange
    elif fault == "reward":
        env_mod.CylinderEnv.env_step = reward
    elif fault == "batch_rows":
        engine.Batch = batch_rows
    elif fault == "policy_logp":
        networks.sample_action = policy_logp
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        engine.ppo_update = orig_update
        env_mod.CylinderEnv.env_step = orig_step
        engine.Batch = orig_batch
        networks.sample_action = orig_sample


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--precisions", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import check, harness
    from bench.reference import replay
    _, cfg, traffic = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    setups = {}
    out = {"workload": args.workload, "program": {}, "control": {},
           "faults": {}}
    limits = {n: float("inf") for n in check.NAMES}

    def one(seed, fault="none", precision=None):
        recs = []
        run_cfg = dict(cfg, matmul_precision=precision or
                       cfg["matmul_precision"])
        with planted(fault):
            res = harness.run_cell(args.workload, seed, 0.0, False,
                                   cfg=run_cfg,
                                   limits=limits,
                                   setup=setups.get(jnp.float32),
                                   keep_record=recs)
        return row(res["checks"], res["where"]), recs[0]

    def row(checks, where):
        """{name: [reading, where it was worst]}"""
        return {k: [checks[k]["value"], where.get(k, "-")] for k in checks}

    def show(label, r):
        print(f"{label}: " + ", ".join(f"{k} {v!r} ({w})"
                                       for k, (v, w) in r.items()),
              flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        out["program"][seed], rec = one(seed)
        n_envs = rec["episodes"][0]["traj"]["act"].shape[0]
        for dt in (jnp.float32, jnp.bfloat16):
            if dt not in setups:
                setups[dt] = replay.Setup(cfg, traffic, n_envs, dt)
        show(f"seed {seed} program", out["program"][seed])
        if args.control:
            crec = replay.control(rec, cfg, traffic, seed,
                                  setup=setups[jnp.bfloat16])
            ref = replay.view(crec, cfg, traffic, seed,
                              setup=setups[jnp.float32])
            readings, where = check.numbers(crec, ref, traffic)
            r = row(check.judge(readings, limits)[1], where)
            out["control"].setdefault("reference_bfloat16", {})[seed] = r
            show(f"seed {seed} control", r)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", flush=True)
    for prec in filter(None, args.precisions.split(",")):
        rows = out["control"].setdefault(f"program_{prec}", {})
        for seed in seeds[:args.fault_seeds]:
            rows[seed], _ = one(seed, precision=prec)
            show(f"program at {prec} seed {seed}", rows[seed])
    for fault in filter(None, args.faults.split(",")):
        rows = out["faults"].setdefault(fault, {})
        for seed in seeds[:args.fault_seeds]:
            rows[seed], _ = one(seed, fault)
            show(f"fault {fault} seed {seed}", rows[seed])

    def pick(rows, n, best):
        """-> [reading, seed, where] of the run ``best`` picks for ``n``; a
        reading that is no number fails but sets no upper end."""
        cands = [[math.inf if v is None else v, seed, w]
                 for seed, r in rows.items() for v, w in [r[n]]]
        return best(cands, key=lambda c: c[0])

    summary = {"lower": {n: pick(out["program"], n, max)
                         for n in check.NAMES}}
    for group, rows in {**out["control"], **out["faults"]}.items():
        summary[f"{group}_min"] = {n: pick(rows, n, min)
                                   for n in check.NAMES}
    for group, picks in summary.items():
        print(f"{group}: {json.dumps(picks)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
