"""Drive ``repro.drl.train.train()`` for one benchmark cell.

Set-up: the cell's configuration (``bench/configs``) and traffic mix
(``bench/traffic``) become a ``TrainConfig``; ``train()`` builds the env,
warms up the flow, and runs episodes.  The harness watches them through the
two hooks ``train()`` offers (the per-episode callback and the identity
CFD<->DRL ``interface``) and through its checkpoint writer, for which an
in-memory stand-in takes the weights of the first updates: the first
episodes are copied to the host for the correctness check, and the warm-up
lasts until an episode ran without a compile.  The window then runs until the first episode that ends after
``seconds``, and closes by raising from the hook.  The reference check runs
after the window, once the run's state is freed.
"""
from __future__ import annotations

import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class WindowClosed(Exception):
    """Raised from the episode hook when the measured window is over."""


class SetupError(Exception):
    """The cell cannot run here (missing files, no chip)."""


def load_cell(workload: str, root: Path = ROOT) -> tuple:
    """-> (manifest cell entry, configuration, traffic) read by name."""
    try:
        manifest = json.loads((root / "BENCHMARK.json").read_text())
        cell = next(w for w in manifest["workloads"] if w["name"] == workload)
        conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
        cfg = json.loads((root / conf["file"]).read_text())
        traffic = json.loads(
            (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    except (OSError, StopIteration, KeyError, ValueError) as e:
        raise SetupError(f"cannot load cell {workload!r}: {e!r}") from e
    return cell, cfg, traffic


class CompileCounter:
    """Counts executables built or loaded from the cache (one event each)."""

    def __init__(self):
        import jax
        self.count = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def train_config(cfg: dict, traffic: dict, seed: int):
    from repro.cfd.env import EnvConfig
    from repro.cfd.grid import GridConfig
    from repro.core.plan import ParallelPlan
    from repro.drl.ppo import PPOConfig
    from repro.drl.train import TrainConfig
    scns = cfg["scenarios"]
    single = len(scns) == 1
    grid = GridConfig(res=cfg["res"], dt=cfg["dt"],
                      poisson_iters=cfg["poisson_iters"],
                      re=scns[0]["re"] if single else 100.0)
    kw = dict(grid=grid, steps_per_action=traffic["steps_per_action"],
              actions_per_episode=traffic["actions_per_episode"],
              warmup_time=cfg["warmup_time"])
    if single:
        kw.update(probe_layout=scns[0]["probes"],
                  actuation=scns[0]["actuation"],
                  geometry=scns[0]["geometry"])
    plan = None if cfg["plan"] is None else ParallelPlan(*cfg["plan"])
    return TrainConfig(env=EnvConfig(**kw), ppo=PPOConfig(**traffic["ppo"]),
                       n_envs=cfg["n_envs"], episodes=10 ** 9, seed=seed,
                       scenarios=None if single else
                       tuple(s["name"] for s in scns),
                       policy=cfg["policy"]["kind"], plan=plan)


def _host(tree) -> dict:
    return {k: np.asarray(v, np.float32) for k, v in tree.items()
            if v is not None}


class Snapshots:
    """Stands in for ``train()``'s checkpoint writer during the compared
    episodes: it keeps the state that ``train()`` hands it after each of
    them (the weights, Adam's two moments and the PPO step count), on the
    host, then turns checkpointing off so that the window saves nothing.
    Nothing is written to disk."""

    saves, bytes_written, time_blocked = 0, 0, 0.0

    def __init__(self, tcfg, count: int):
        self.tcfg, self.count = tcfg, count
        self.states = {}
        tcfg.ckpt_dir, tcfg.ckpt_every = "in-memory", 1

    def __call__(self, *args, **kwargs):
        return self

    def save(self, step, tree, metadata=None):
        from bench.check import leaves
        if step <= self.count:
            self.states[step] = {"params": leaves(tree["params"]),
                                 "m": leaves(tree["opt_state"]["m"]),
                                 "v": leaves(tree["opt_state"]["v"]),
                                 "step": int(np.asarray(tree["step"]))}
        if step >= self.count:
            self.tcfg.ckpt_every = 10 ** 9

    def close(self):
        pass


class Driver:
    """The episode hook and the identity interface ``train()`` calls."""

    def __init__(self, traffic: dict, seconds: float, counter: CompileCounter,
                 trace_dir=None):
        warm = traffic["warmup"]
        self.keep = traffic["check"]["episodes"] + 1
        self.min_eps = max(warm["min_episodes"], self.keep)
        self.max_eps = warm["max_episodes"]
        self.trace_eps = traffic["trace_episodes"]
        self.seconds = seconds
        self.counter = counter
        self.trace_dir = trace_dir
        self.record = []
        self.warm_walls = []
        self.walls = []
        self.health = []
        self.episodes = 0
        self.t_start = self.t_end = None
        self.trace_span = None
        self.compiles_at_start = None
        self._batch = None
        self._compiles = counter.count
        self._t_prev = time.perf_counter()

    # the CFD<->DRL interface slot of train(): sees each batch, returns it
    def exchange(self, batch):
        if len(self.record) < self.keep:
            self._batch = batch
        return batch

    def on_episode(self, traj, metrics):
        now = time.perf_counter()
        self.episodes += 1
        if self.t_start is None:
            self._warmup_episode(traj, metrics, now)
        else:
            self._window_episode(traj, metrics, now)
        self._t_prev = now

    def _warmup_episode(self, traj, metrics, now):
        self.warm_walls.append(now - self._t_prev)
        if len(self.record) < self.keep:
            b = self._batch
            self.record.append({
                "traj": _host(traj._asdict()),
                "batch": _host({k: getattr(b, k) for k in
                                ("obs", "act", "logp_old", "adv", "ret",
                                 "valid")}),
                "metrics": {k: float(v) for k, v in metrics.items()}})
            self._batch = None
        compiled = self.counter.count != self._compiles
        self._compiles = self.counter.count
        n = self.episodes
        if (n >= self.min_eps and not compiled) or n >= self.max_eps:
            self.compiles_at_start = self.counter.count
            if self.trace_dir is not None:
                import jax
                jax.profiler.start_trace(str(self.trace_dir))
            self.t_start = time.perf_counter()
            self.trace_span = [self.t_start, None]

    def _window_episode(self, traj, metrics, now):
        self.walls.append(now - self._t_prev)
        self.health.append((traj.valid, metrics.get("grad_skips")))
        if self.trace_dir is not None and len(self.walls) == self.trace_eps:
            import jax
            self.trace_span[1] = now
            jax.profiler.stop_trace()
        if now - self.t_start >= self.seconds and (
                self.trace_dir is None or len(self.walls) >= self.trace_eps):
            self.t_end = now
            raise WindowClosed()

    def failed(self) -> int:
        bad = 0
        for valid, skips in self.health:
            bad += int((valid is not None
                        and float(np.min(np.asarray(valid))) < 0.5)
                       or (skips is not None and float(skips) > 0))
        return bad


def check_widths(cfg: dict, episode: dict) -> None:
    """Refuse a run whose policy has other shapes than the configuration's
    widths give the reference and the work counts: ``train()`` takes only
    the policy's kind and builds its own default widths.  (An initial
    log-std other than the configuration's reads in ``change_gap``.)"""
    import jax
    from bench.reference import policy as pol
    obs, act = episode["traj"]["obs"], episode["traj"]["act"]
    built = jax.eval_shape(
        lambda k: pol.init(cfg["policy"], obs.shape[-1],
                           act.shape[2] if act.ndim == 3 else 1, k),
        jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(built)
    want = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in flat}
    got = {k: tuple(v.shape) for k, v in episode["params"].items()}
    if got != want:
        diff = sorted(k for k in set(got) | set(want)
                      if got.get(k) != want.get(k))
        raise SetupError(
            f"the program's policy is not the configuration's: leaves "
            f"{diff[:4]} have shapes {[got.get(k) for k in diff[:4]]}, the "
            f"configured widths give {[want.get(k) for k in diff[:4]]}")


def device_info(devices, count: int) -> dict:
    """Platform and kind as JAX reports them, its device count, and the
    peak memory of the fullest of the cell's chips."""
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": count, "memory_peak_bytes": peak}


def p90(values):
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=10, method="inclusive")[-1]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, cfg=None, traffic=None,
             limits=None, t_process: float = None, log=None,
             setup=None, keep_record=None, keep_trace=None) -> dict:
    """One run of a cell.  Returns the result object; the caller prints it.
    ``cfg``/``traffic``/``limits`` replace the files (tests use that to run
    a cell at a size the CPU can hold); ``setup`` is a reference set-up to
    reuse, ``keep_record`` a list that receives the recorded episodes, and
    ``keep_trace`` a directory that receives a copy of the trace."""
    t0 = time.perf_counter() if t_process is None else t_process
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cell, cfg_file, traffic_file = load_cell(workload)
    cfg = cfg or cfg_file
    traffic = traffic or traffic_file
    import jax
    devices = jax.devices()
    t_devices = time.perf_counter()
    if require_chip:
        if devices[0].platform != "tpu":
            raise SetupError(f"no TPU: JAX found {devices[0].platform!r}; "
                             f"this benchmark has no CPU fallback")
        if len(devices) < cell["chips"]:
            raise SetupError(f"cell {workload} needs {cell['chips']} chips, "
                             f"JAX found {len(devices)}")
    used = devices[:cell["chips"]]
    from bench import check, work
    from bench.reference import replay
    from repro.drl.train import train
    limits = limits or check.load_limits(ROOT, workload)

    from repro.ckpt import checkpoint as ckpt_mod
    # the precision the configuration states, for every product that names
    # none (the policy and PPO; the reference names HIGHEST throughout)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    counter = CompileCounter()
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    drv = Driver(traffic, seconds, counter, trace_dir)
    tcfg = train_config(cfg, traffic, seed)
    snaps = Snapshots(tcfg, traffic["check"]["episodes"])
    writer, ckpt_mod.AsyncCheckpointer = ckpt_mod.AsyncCheckpointer, snaps
    t_train = drv._t_prev = time.perf_counter()
    try:
        train(tcfg, log_fn=None, interface=drv, on_episode=drv.on_episode)
        raise RuntimeError("train() returned before the window closed")
    except WindowClosed:
        pass
    finally:
        ckpt_mod.AsyncCheckpointer = writer
    counter.close()
    for k, state in snaps.states.items():
        drv.record[k - 1].update(state)
    check_widths(cfg, drv.record[0])
    compiles = counter.count - drv.compiles_at_start
    for name in counter.names[drv.compiles_at_start:]:
        log(f"compile inside the window: {name}")
    device = device_info(used, len(devices))
    n_envs, horizon = drv.record[0]["traj"]["act"].shape[:2]
    window = drv.t_end - drv.t_start
    attempted, failed = len(drv.walls), drv.failed()
    setup_s = drv.t_start - t0
    med = statistics.median(drv.walls)
    slow = [(i, round(w, 4)) for i, w in enumerate(drv.walls) if w > 1.2 * med]
    log(f"set-up {setup_s:.3f} s (to devices {t_devices - t0:.3f} s, to "
        f"train() {t_train - t0:.3f} s; {len(drv.warm_walls)} warm-up "
        f"episodes, walls {[round(w, 3) for w in drv.warm_walls]}); window "
        f"{window:.3f} s, {attempted} episodes of {n_envs} envs x {horizon} "
        f"actions (walls min {min(drv.walls):.4f}, median {med:.4f}, max "
        f"{max(drv.walls):.4f} s; over 1.2x the median: {slow}); "
        f"{compiles} compile(s) in the window")
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        from bench import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, chips=cell["chips"],
                                          keep_in=keep_trace)
        span = drv.trace_span[1] - drv.trace_span[0]
        ctx = {"trace": reduced, "window_s": span, "episodes": drv.trace_eps,
               "chips": cell["chips"], "compiles_in_window": compiles,
               "work": work.episode_work(cfg, traffic, n_envs),
               "peaks": work.peaks(device["kind"])}
        result["metrics"] = read_per_layer(workload, ctx)
        result["device"].update(busy_s=reduced.busy_s(), window_s=span)
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = {
            "transitions_per_s": {"value": attempted * n_envs * horizon / window,
                                  "unit": "transitions/s"},
            "episode_ms_p90": {"value": 1e3 * p90(drv.walls), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    record = {"episodes": drv.record}
    if keep_record is not None:
        keep_record.append(record)
    del drv, tcfg
    t_ref = time.perf_counter()
    ref = replay.view(record, cfg, traffic, seed, setup=setup)
    readings, where = check.numbers(record, ref, traffic)
    correct, checks = check.judge(readings, limits)
    log(f"reference check took {time.perf_counter() - t_ref:.1f} s")
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}; "
            f"worst at {where.get(name, '-')})")
    result["correct"] = correct
    result["where"] = where
    result["checks"] = checks
    return result


def read_per_layer(workload: str, ctx: dict) -> dict:
    """Each per-layer metric of ``BENCHMARK.json`` that applies to this cell
    is read by ``bench/metrics/<name>.py``; one that finds nothing is left
    out."""
    import importlib
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
