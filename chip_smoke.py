#!/usr/bin/env python3
"""Bring-up smoke of the DRL x CFD trainer on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the paths that exist across 4 chips

Runs in one process and starts none.  The phases run in order, each prints
its result lines, and the first failure ends the run with a non-zero exit:

1. device: the first JAX device must be a TPU.  There is no CPU fallback.
2. golden: restart from ``tests/golden/cyl_re100_res8.npz`` and hold the
   Strouhal number, mean C_D and C_L amplitude to the golden test's
   tolerances, once per backend TPU code can choose on one chip:
   ``reference`` (packed XLA sweep at one env; first, the batched solve
   kernel that serves vmapped env batches must reproduce that sweep bit
   for bit over one 60-env solve), ``pallas`` (slab kernel) and ``fused``
   (the actuation megakernel).  Then the fluidic pinball's golden
   (``tests/golden/pinball_re100_res8.npz``) restarted as a vmapped batch
   of 60 copies with a per-body (3,) amplitude, so its window runs through
   the batched solve kernel and the solver's per-body branch; every copy
   must hold the golden to the tolerances of
   ``tests/test_golden_pinball.py`` (``validation.PINBALL_TOL``).
3. train: ``train()`` at the paper's deployment (``cyl_re100``, jets,
   ring149 probes, 60 envs, the 2x512 MLP, the grid and PPO settings of
   ``examples/drl_cylinder.py``) for 3 episodes each with ``plan=None``,
   ``plan=(1, 1)`` and ``plan="auto"``.

``--chips 4`` runs only the multi-chip paths and what they are compared
with: the golden window through ``backend="halo"`` at 2 and 4 ranks beside
the one-device reference, and ``train()`` with ``ParallelPlan(4, 2, 2)``
and ``ParallelPlan(4, 4, 1)``, whose env batch must span the four chips.

A phase that names a kernel path turns ``RuntimeWarning`` into an error, so
a fallback to the reference scan fails it instead of passing unseen.  The
times printed are smoke timings (compile cache cold or warm, as found), not
a benchmark.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import contextlib
import json
import math
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
GOLDEN = ROOT / "tests" / "golden" / "cyl_re100_res8.npz"
PINBALL_GOLDEN = ROOT / "tests" / "golden" / "pinball_re100_res8.npz"
N_ENVS = 60
SEED = 0


class SmokeFailure(Exception):
    """A phase's result is outside what it must be."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def kernel_phase():
    """A fallback warning from a kernel path is an error inside the phase."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        yield


# ---------------------------------------------------------------------------
# golden physics
# ---------------------------------------------------------------------------

def load_golden(path=GOLDEN):
    import numpy as np
    from repro.cfd import solver
    from repro.cfd.grid import GridConfig
    ref = np.load(path)
    cfg = GridConfig(res=int(ref["res"]), dt=float(ref["dt"]),
                     poisson_iters=int(ref["poisson_iters"]))
    state = solver.FlowState(u=ref["u"], v=ref["v"], p=ref["p"])
    return ref, cfg, state


def golden_stats(ref) -> dict:
    return {key: float(ref[key]) for key in ("strouhal", "cd_mean", "cl_amp")}


def hold_to(label, stats, bases, tols, *, show=True) -> dict:
    """Hold ``stats`` to each ``(name, stats)`` of ``bases`` within the
    relative tolerances ``tols`` (key -> tol); returns the largest relative
    error of each key."""
    worst = dict.fromkeys(tols, 0.0)
    for base_name, base in bases:
        for key, tol in tols.items():
            rel = abs(stats[key] - base[key]) / abs(base[key])
            worst[key] = max(worst[key], rel)
            if show:
                print(f"golden[{label}] {key} {stats[key]!r} vs {base_name} "
                      f"{base[key]!r}: rel err {rel:.3e} (tol {tol})")
            check(rel <= tol, f"golden[{label}] {key} off {base_name} by "
                              f"{rel:.3e} > {tol}")
    return worst


def golden_window(label, backend, *, mesh=None, against=None):
    """Re-measure the golden window through ``backend`` and hold it to the
    golden (and to ``against``, another run's stats, when given)."""
    import numpy as np
    from repro.cfd import validation as val
    ref, cfg, state = load_golden()
    n = int(ref["meas_steps"])
    t0 = time.perf_counter()
    _, cds, cls = val.run_uncontrolled(cfg, state, n, backend=backend,
                                       mesh=mesh)
    wall = time.perf_counter() - t0
    check(bool(np.isfinite(cds).all() and np.isfinite(cls).all()),
          f"golden[{label}]: non-finite force coefficients")
    stats = val.measure_shedding(cds, cls, cfg.dt)
    hold_to(label, stats, [("golden", golden_stats(ref))]
            + ([against] if against is not None else []), val.TOL)
    print(f"golden[{label}] ok: {n} dt in {wall:.2f} s (smoke timing, "
          f"compile included)")
    return stats, cds


def pinball_golden_batched():
    """The pinball golden window for a batch of 60 copies of its state,
    each actuated through the per-body branch with a (3,) amplitude of
    zero.  ``act_mode`` 0 penalizes the solid bodies alone, as the golden
    run did; the rotary mode's actuation band would thicken them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.cfd import solver
    from repro.cfd import validation as val
    from repro.cfd.grid import build_geometry
    ref, cfg, state = load_golden(PINBALL_GOLDEN)
    n = int(ref["meas_steps"])
    ga = solver.geom_to_arrays(build_geometry(cfg, str(ref["geometry"])))
    batch = jax.tree.map(
        lambda a: jnp.broadcast_to(jnp.asarray(a), (N_ENVS,) + a.shape),
        state)
    amp = jnp.zeros((N_ENVS, len(ga.rotb_u)), jnp.float32)
    run = jax.jit(jax.vmap(lambda s, a: solver.step_interval(
        cfg, ga, s, a, n, re=jnp.float32(cfg.re), act_mode=jnp.float32(0.0))))
    check("poisson_rb_sor_batched" in run.lower(batch, amp).compile()
          .as_text(), "golden[pinball, batched]: the compiled window holds "
                      "no batched solve kernel")
    t0 = time.perf_counter()
    _, outs = run(batch, amp)
    cds = np.asarray(outs.cd).sum(axis=-1)       # (envs, n): all bodies
    cls = np.asarray(outs.cl).sum(axis=-1)
    wall = time.perf_counter() - t0
    check(bool(np.isfinite(cds).all() and np.isfinite(cls).all()),
          "golden[pinball, batched]: non-finite force coefficients")
    worst = dict.fromkeys(val.PINBALL_TOL, 0.0)
    for e in range(N_ENVS):
        rel = hold_to(f"pinball, batched, env {e}",
                      val.measure_shedding(cds[e], cls[e], cfg.dt),
                      [("golden", golden_stats(ref))], val.PINBALL_TOL,
                      show=e == 0)
        worst = {key: max(worst[key], rel[key]) for key in worst}
    print(f"golden[pinball, batched] worst rel err over {N_ENVS} envs "
          f"{worst}")
    print(f"golden[pinball, batched] ok: {N_ENVS} envs x {n} dt in "
          f"{wall:.2f} s (smoke timing)")


def batched_solve_exact_on_chip():
    """On TPU the ``reference`` solve runs ``rb_sor_batched`` over the whole
    env batch; over one solve of the paper deployment's 60-env batch it
    must reproduce the XLA loop it replaced bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.cfd import poisson
    from repro.cfd.grid import GridConfig
    from repro.kernels.poisson import ops
    g = GridConfig(res=8, dt=0.01, poisson_iters=50)
    polish = 10
    check(ops.batched_kernel_fits(ops.kernel_platform(), g.ny, g.nx, N_ENVS),
          "batched sor: the paper deployment's batch does not take the "
          "kernel")
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    planes = [jax.random.normal(k, (N_ENVS, g.ny, g.nx // 2)) for k in keys]
    kern = jax.jit(jax.vmap(lambda *p: ops.rb_sor_solve(
        *p, dx=g.dx, dy=g.dy, omega=g.poisson_omega, iters=g.poisson_iters,
        polish=polish)))
    loop = jax.jit(jax.vmap(lambda *p: poisson.packed_sor_loop(
        *p, g.poisson_omega, dx=g.dx, dy=g.dy, iters=g.poisson_iters,
        n_sor=g.poisson_iters - min(polish, g.poisson_iters // 2))))
    check("poisson_rb_sor_batched" in kern.lower(*planes).compile().as_text(),
          "batched sor: the compiled solve holds no kernel call")
    got, want = kern(*planes), loop(*planes)
    gap = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
    ndiff = sum(int(jnp.sum(a != b)) for a, b in zip(got, want))
    print(f"batched sor: kernel vs XLA loop over one solve of {N_ENVS} envs: "
          f"largest gap {gap!r}, {ndiff} elements differ")
    check(ndiff == 0, f"batched sor: {ndiff} elements differ from the XLA "
                      f"loop (largest gap {gap!r})")


def pack_exact_on_chip():
    """The megakernel deinterleaves the checkerboard on the MXU; at fp32
    contract precision that must reproduce ``poisson.pack_checkerboard``
    bit for bit, and its inverse the grid."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from repro.cfd import poisson
    from repro.kernels.actuation.kernel import pack_mxu, unpack_mxu
    _, cfg, _ = load_golden()
    a = jax.random.normal(jax.random.PRNGKey(SEED), (cfg.ny, cfg.nx))
    plane = jax.ShapeDtypeStruct((cfg.ny, cfg.nx // 2), jnp.float32)

    def kern(a_ref, r_ref, b_ref, back_ref):
        r, b = pack_mxu(a_ref[...])
        r_ref[...], b_ref[...] = r, b
        back_ref[...] = unpack_mxu(r, b)

    red, black, back = pl.pallas_call(
        kern, out_shape=[plane, plane,
                         jax.ShapeDtypeStruct(a.shape, jnp.float32)])(a)
    want_r, want_b = poisson.pack_checkerboard(a)
    exact = (np.array_equal(np.asarray(red), np.asarray(want_r))
             and np.array_equal(np.asarray(black), np.asarray(want_b))
             and np.array_equal(np.asarray(back), np.asarray(a)))
    print(f"fused: in-kernel pack/unpack bitwise equal to the XLA layout: "
          f"{exact}")
    check(exact, "megakernel pack/unpack is not exact on the chip")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def paper_config(plan, episodes: int):
    """The paper's deployment with the settings of examples/drl_cylinder.py."""
    from repro.cfd.env import EnvConfig
    from repro.cfd.grid import GridConfig
    from repro.drl.ppo import PPOConfig
    from repro.drl.train import TrainConfig
    return TrainConfig(
        env=EnvConfig(grid=GridConfig(res=8, dt=0.01, poisson_iters=50),
                      steps_per_action=25, actions_per_episode=40,
                      warmup_time=20.0),
        ppo=PPOConfig(lr=3e-4, epochs=6, minibatches=4, entropy_coef=0.005),
        n_envs=N_ENVS, episodes=episodes, seed=SEED, plan=plan)


def train_run(label, cfg, *, want_devices=None):
    from repro.drl.train import train
    logs = []

    def log(line):
        logs.append(line)
        print(f"train[{label}] {line}")

    health = {}
    t0 = time.perf_counter()
    hist, _ = train(cfg, log_fn=log, health=health)
    total = time.perf_counter() - t0
    plan_lines = [s for s in logs if s.startswith("plan[")]
    if cfg.plan is None:
        print(f"train[{label}] plan: none (single-device vmap, poisson "
              f"backend 'reference')")
    else:
        check(len(plan_lines) == 1, f"train[{label}]: no plan line logged")
    rewards = [float(x) for x in hist["reward"]]
    cds = [float(x) for x in hist["cd"]]
    print(f"train[{label}] reward per episode {rewards}")
    print(f"train[{label}] C_D per episode {cds}")
    print(f"train[{label}] health {health}")
    check(len(rewards) == cfg.episodes,
          f"train[{label}]: {len(rewards)} of {cfg.episodes} episodes")
    check(all(math.isfinite(x) for x in rewards + cds),
          f"train[{label}]: non-finite reward or C_D")
    check(health.get("quarantines") == 0 and health.get("grad_skips") == 0,
          f"train[{label}]: unhealthy run {health}")
    if want_devices is not None:
        placed = [s for s in logs if s.startswith("env batch:")]
        check(len(placed) == 1
              and placed[0].endswith(f"on {want_devices} device(s)"),
              f"train[{label}]: env batch not on {want_devices} devices "
              f"({placed})")
    wall = [float(x) for x in hist["wall"]]
    print(f"train[{label}] smoke timing (not a benchmark): episode walls "
          f"{wall} s (the first includes its compile), last episode "
          f"{wall[-1]:.3f} s, set-up before the first episode "
          f"{total - sum(wall):.2f} s")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def one_chip() -> None:
    batched_solve_exact_on_chip()
    golden_window("reference", "reference")
    pinball_golden_batched()
    with kernel_phase():
        golden_window("pallas", "pallas")
    with kernel_phase():
        pack_exact_on_chip()
        golden_window("fused", "fused")
    train_run("plan=None", paper_config(None, 3))
    with kernel_phase():
        train_run("plan=(1, 1)", paper_config((1, 1), 3))
    with kernel_phase():
        train_run("plan=auto", paper_config("auto", 3))


def four_chips() -> None:
    from repro.core.plan import ParallelPlan
    from repro.launch.mesh import mesh_for_plan
    ref_stats, ref_cds = golden_window("reference, 1 device", "reference")
    for r in (2, 4):
        _, cds = golden_window(f"halo, {r} ranks", "halo",
                               mesh=mesh_for_plan((1, r)),
                               against=("1-device reference", ref_stats))
        print(f"golden[halo, {r} ranks] max |C_D - C_D(reference)| over the "
              f"window: {float(abs(cds - ref_cds).max())!r}")
    for plan in (ParallelPlan(4, 2, 2), ParallelPlan(4, 4, 1)):
        train_run(f"plan={plan.n_envs}x{plan.n_ranks}",
                  paper_config(plan, 2), want_devices=4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: only the paths "
                         "across four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform!r}, kind {dev.device_kind!r}, "
          f"count {len(devices)}; jax {jax.__version__}; compile cache "
          f"{cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: the first device is {dev.platform!r}, not a "
              f"TPU; this check has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
