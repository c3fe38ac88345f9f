"""Fused actuation-interval benchmark: the megakernel's gate artifact.

Measures the environment hot loop — ``CylinderEnv.env_step`` (one actuation
interval: ``steps_per_action`` solver dt's + probes/reward), jitted and
vmapped over the batch — for the reference scan and the fused interval path
(``backend="fused"``), and gates the fused end-to-end env-steps/s against
the committed PR-6 training baseline (``artifacts/BENCH_train.json``):

- **gate**: fused env-steps/s >= ``REQUIRED_SPEEDUP`` x the baseline's
  ``env_steps_per_s`` (``tools/bench_report.py --check`` fails on
  ``gate.passed == false``),
- **parity**: max |fused - reference| over the flow state and outputs after
  one interval on a *mixed* vmapped scenario batch (jets + rotary, two
  Reynolds numbers),
- **golden drift**: Strouhal / C_D / C_L re-measured from the checked-in
  golden state (reuses ``bench_train.measure_golden_drift``),
- **roofline gap**: measured interval time vs the roofline bound priced
  against this host's :class:`~repro.launch.roofline.HardwareSpec`, detected
  from the device kind; a CPU host is priced only when the caller names it
  (``REPRO_HW_SPEC=cpu_generic``), never silently at TPU numbers.

Throughput is the best of ``REPS`` timed repetitions: the artifact records
the machine's capability, not the co-tenancy noise of a shared host (each
rep is itself a full interval batch, ~0.2 s of work).

Writes ``artifacts/BENCH_megakernel.json`` (``_smoke`` variant under
``--smoke`` — smoke artifacts never overwrite committed measurements).

    REPRO_HW_SPEC=cpu_generic PYTHONPATH=src \
        python benchmarks/bench_megakernel.py [--smoke]      # on a CPU host
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from repro.cfd.env import CylinderEnv, EnvConfig
from repro.cfd.grid import GridConfig
from repro.drl.engine import broadcast_env_state
from repro.drl.train_state import code_fingerprint
from repro.launch.roofline import Roofline, hardware_spec

BENCH_SCHEMA = "repro.bench_megakernel/v1"
BASELINE = Path(__file__).resolve().parent.parent / "artifacts" \
    / "BENCH_train.json"
REQUIRED_SPEEDUP = 2.0
REPS = 7
# the mixed batch the parity check integrates: both actuation modes and two
# Reynolds numbers, vmapped into one program
PARITY_SCENARIOS = ("cyl_re100", "cyl_re200_rotary", "cyl_re100_rotary",
                    "cyl_re200")


def measure_throughput(smoke: bool) -> dict:
    """Best-of-reps env-steps/s for reference vs fused on the gate config
    (the res/iteration budget BENCH_train measured the baseline at)."""
    res, p_iters = (6, 30) if smoke else (8, 50)
    spa = 5 if smoke else 50
    n_envs = 2 if smoke else 4
    cfg = EnvConfig(grid=GridConfig(res=res, dt=0.01, poisson_iters=p_iters),
                    steps_per_action=spa, warmup_time=1.0 if smoke else 5.0)

    out = {"config": {"res": res, "poisson_iters": p_iters, "n_envs": n_envs,
                      "steps_per_action": spa, "smoke": smoke, "reps": REPS},
           "backends": {}}
    for backend in ("reference", "fused"):
        env = CylinderEnv(cfg, backend=backend)
        st, obs = env.reset()
        stb, _ = broadcast_env_state(st, obs, n_envs)
        act = jnp.zeros((n_envs,), jnp.float32)
        step = jax.jit(jax.vmap(env.env_step))
        jax.block_until_ready(step(stb, act))            # compile
        ts = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(step(stb, act))
            ts.append(time.perf_counter() - t0)
        best = min(ts)
        ts.sort()
        out["backends"][backend] = {
            "interval_s_best": best,
            "interval_s_median": ts[len(ts) // 2],
            "env_steps_per_s": n_envs * spa / best,
        }
    ref = out["backends"]["reference"]["env_steps_per_s"]
    fus = out["backends"]["fused"]["env_steps_per_s"]
    out["env_steps_per_s"] = fus                 # the dashboard headline
    out["speedup_fused_vs_reference"] = fus / ref
    return out


def measure_parity(smoke: bool) -> dict:
    """Max |fused - reference| after one env interval on the mixed batch."""
    res, p_iters = (4, 12) if smoke else (6, 30)
    cfg = EnvConfig(grid=GridConfig(res=res, dt=0.01, poisson_iters=p_iters),
                    steps_per_action=5 if smoke else 20, warmup_time=0.5)
    acts = jnp.asarray([0.3, -0.2, 0.1, 0.0][:len(PARITY_SCENARIOS)],
                       jnp.float32)
    states = {}
    for backend in ("reference", "fused"):
        env = CylinderEnv(cfg, backend=backend)
        st_b, _ = env.reset_batch(list(PARITY_SCENARIOS))
        states[backend] = jax.jit(jax.vmap(env.env_step))(st_b, acts)
    (st_r, out_r), (st_f, out_f) = states["reference"], states["fused"]
    mx = lambda a, b: float(jnp.max(jnp.abs(a - b)))
    return {"scenarios": list(PARITY_SCENARIOS),
            "u_maxabs": mx(st_f.flow.u, st_r.flow.u),
            "v_maxabs": mx(st_f.flow.v, st_r.flow.v),
            "p_maxabs": mx(st_f.flow.p, st_r.flow.p),
            "cd_maxabs": mx(out_f.cd, out_r.cd),
            "reward_maxabs": mx(out_f.reward, out_r.reward)}


def roofline_gap(throughput: dict) -> dict:
    """Measured fused interval vs the roofline bound on this host.

    Analytic per-interval work (one env), rough but stated: the packed SOR
    pair touches every cell twice per iteration (~11 flops/cell/half-sweep,
    3 reads + 1 write per cell), the momentum predictor ~60 flops over both
    staggered fields with ~10 array passes, projection/correction ~15
    flops/cell.  The bound uses this host's HardwareSpec — a CPU run must
    name ``cpu_generic`` through ``$REPRO_HW_SPEC``.
    """
    c = throughput["config"]
    grid = GridConfig(res=c["res"], dt=0.01, poisson_iters=c["poisson_iters"])
    ny, nx, spa = grid.ny, grid.nx, c["steps_per_action"]
    n_cells = ny * nx
    n_faces = ny * (nx + 1) + (ny + 1) * nx
    per_dt_flops = (grid.poisson_iters * 11 * 2 * n_cells   # SOR pair
                    + 60 * n_faces                          # momentum
                    + 15 * n_cells)                         # rhs + correction
    per_dt_bytes = 4 * (grid.poisson_iters * 4 * 2 * n_cells
                        + 10 * n_faces + 6 * n_cells)
    n_envs = c["n_envs"]
    hw = hardware_spec()
    rl = Roofline(arch="fused_interval", shape=f"res{c['res']}", mesh="1",
                  n_devices=1,
                  flops_per_dev=float(per_dt_flops) * spa * n_envs,
                  bytes_per_dev=float(per_dt_bytes) * spa * n_envs,
                  coll_bytes_per_dev=0.0,
                  model_flops=float(per_dt_flops) * spa * n_envs,
                  coll_by_kind={}, hw=hw)
    measured_s = throughput["backends"]["fused"]["interval_s_best"]
    return {"hw": hw.to_dict(),
            "bound_s": rl.bound_s,
            "compute_s": rl.compute_s,
            "memory_s": rl.memory_s,
            "dominant": rl.dominant,
            "measured_s": measured_s,
            "gap": measured_s / rl.bound_s if rl.bound_s else None,
            # these grids are cache-resident on CPU (working set ~hundreds
            # of KiB), so the memory term priced at DRAM bandwidth
            # overestimates its cost and gap-vs-bound can dip below 1;
            # the compute-term gap is the binding comparison there
            "gap_vs_compute": (measured_s / rl.compute_s
                               if rl.compute_s else None)}


def run(smoke: bool = False, out: str = None) -> dict:
    from benchmarks.bench_train import measure_golden_drift

    record = {"schema": BENCH_SCHEMA,
              "code": code_fingerprint(),
              "jax_devices": jax.device_count()}
    record.update(measure_throughput(smoke))
    record["parity"] = measure_parity(smoke)
    record["golden_drift"] = measure_golden_drift(smoke)
    record["roofline"] = roofline_gap(record)

    baseline = None
    if BASELINE.exists():
        base = json.loads(BASELINE.read_text())
        baseline = base.get("env_steps_per_s")
    speedup = (record["env_steps_per_s"] / baseline) if baseline else None
    record["gate"] = {
        "baseline_env_steps_per_s": baseline,
        "required_speedup": REQUIRED_SPEEDUP,
        "speedup_vs_baseline": speedup,
        # the gate is judged on the full-size measurement; smoke runs use
        # tiny shapes whose throughput says nothing about the baseline
        "passed": bool(smoke or (speedup is not None
                                 and speedup >= REQUIRED_SPEEDUP)),
    }

    root = Path(__file__).resolve().parent.parent / "artifacts"
    name = "BENCH_megakernel_smoke.json" if smoke else "BENCH_megakernel.json"
    path = Path(out) if out else root / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(record, indent=1, sort_keys=True))

    b = record["backends"]
    print(f"megakernel: fused {record['env_steps_per_s']:.1f} env-steps/s "
          f"(reference {b['reference']['env_steps_per_s']:.1f}, "
          f"fused/reference {record['speedup_fused_vs_reference']:.2f}x)")
    g = record["gate"]
    if g["speedup_vs_baseline"] is not None:
        print(f"gate: {g['speedup_vs_baseline']:.2f}x vs BENCH_train "
              f"baseline {g['baseline_env_steps_per_s']:.1f} "
              f"(need {g['required_speedup']:.1f}x) -> "
              f"{'PASS' if g['passed'] else 'FAIL'}")
    p = record["parity"]
    print(f"parity (mixed vmapped batch): u {p['u_maxabs']:.2e}  "
          f"p {p['p_maxabs']:.2e}  cd {p['cd_maxabs']:.2e}")
    r = record["roofline"]
    print(f"roofline[{r['hw']['name']}]: bound {r['bound_s']*1e3:.1f} ms "
          f"({r['dominant']}), measured {r['measured_s']*1e3:.1f} ms, "
          f"gap {r['gap']:.1f}x (vs compute term "
          f"{r['gap_vs_compute']:.1f}x)")
    print(f"artifact -> {path}")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI; writes "
                         "BENCH_megakernel_smoke.json")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
