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
- ``exchange``    the update reads only the first chip's share of the batch,
                  as each chip does when the gradients' reduction across
                  chips is left out (cells on more than one chip).

Runs on the chip like ``bench/run.py``.  Each run's readings go to stdout
as they come, then a summary: the largest reading of the sound runs
(``lower``) and the smallest of each control and fault (``<group>_min``).
"""
import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program for the duration of the block."""
    import jax
    from repro.cfd import env as env_mod
    from repro.drl import engine
    orig_update = engine.ppo_update
    orig_step = env_mod.CylinderEnv.env_step
    orig_batch = engine.Batch

    def unchanged(cfg, opt, params, opt_state, batch, key, step):
        _, _, new_step, metrics = orig_update(cfg, opt, params, opt_state,
                                              batch, key, step)
        return params, opt_state, new_step, metrics

    def half_batch(cfg, opt, params, opt_state, batch, key, step):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return orig_update(cfg, opt, params, opt_state, half, key, step)

    def exchange(cfg, opt, params, opt_state, batch, key, step):
        n = jax.device_count()
        local = jax.tree.map(lambda x: x[: x.shape[0] // n], batch)
        return orig_update(cfg, opt, params, opt_state, local, key, step)

    def reward(self, st, action):
        st2, out = orig_step(self, st, action)
        return st2, out._replace(reward=out.reward + 0.01)

    def batch_rows(**fields):
        fields["act"] = jax.numpy.roll(fields["act"], 1, axis=0)
        return orig_batch(**fields)

    if fault == "unchanged":
        engine.ppo_update = unchanged
    elif fault == "half_batch":
        engine.ppo_update = half_batch
    elif fault == "exchange":
        engine.ppo_update = exchange
    elif fault == "reward":
        env_mod.CylinderEnv.env_step = reward
    elif fault == "batch_rows":
        engine.Batch = batch_rows
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        engine.ppo_update = orig_update
        env_mod.CylinderEnv.env_step = orig_step
        engine.Batch = orig_batch


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
        return res, recs[0]

    for seed in seeds:
        t0 = time.perf_counter()
        res, rec = one(seed)
        n_envs = rec["episodes"][0]["traj"]["act"].shape[0]
        for dt in (jnp.float32, jnp.bfloat16):
            if dt not in setups:
                setups[dt] = replay.Setup(cfg, traffic, n_envs, dt)
        out["program"][seed] = {k: v["value"] for k, v in res["checks"].items()}
        if args.control:
            crec = replay.control(rec, cfg, traffic, seed,
                                  setup=setups[jnp.bfloat16])
            ref = replay.view(crec, cfg, traffic, seed,
                              setup=setups[jnp.float32])
            out["control"].setdefault("reference_bfloat16", {})[seed] = (
                check.numbers(crec, ref, traffic)[0])
        print(f"seed {seed}: program {out['program'][seed]} control "
              f"{out['control'].get('reference_bfloat16', {}).get(seed)} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    for prec in filter(None, args.precisions.split(",")):
        rows = out["control"].setdefault(f"program_{prec}", {})
        for seed in seeds[:args.fault_seeds]:
            res, _ = one(seed, precision=prec)
            rows[seed] = {k: v["value"] for k, v in res["checks"].items()}
            print(f"program at {prec} seed {seed}: {rows[seed]}", flush=True)
    for fault in filter(None, args.faults.split(",")):
        out["faults"][fault] = {}
        for seed in seeds[:args.fault_seeds]:
            res, _ = one(seed, fault)
            out["faults"][fault][seed] = {k: v["value"]
                                          for k, v in res["checks"].items()}
            print(f"fault {fault} seed {seed}: {out['faults'][fault][seed]}",
                  flush=True)
    summary = {"lower": {n: max(r[n] for r in out["program"].values())
                         for n in check.NAMES}}
    for group, rows in {**out["control"], **out["faults"]}.items():
        # a reading that is no number fails but sets no upper end
        summary[f"{group}_min"] = {
            n: min(r[n] if r[n] is not None and math.isfinite(r[n])
                   else math.inf for r in rows.values())
            for n in check.NAMES}
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
