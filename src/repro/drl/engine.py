"""Unified sharded rollout engine — the single implementation of trajectory
collection shared by every training loop in the repo.

Three concerns that used to be triplicated across ``drl/train.py``,
``drl/async_train.py`` and ``core/runner.py`` live here exactly once:

  * collect -> GAE -> flatten: the vmapped N_envs episode rollout (paper
    Fig. 4), value bootstrap, advantage estimation and batch flattening.
  * mesh placement (paper §II.D): the env batch is sharded over the mesh
    "data" axis (the paper's N_envs) and each env's grid fields optionally
    over "model" (the paper's N_ranks domain decomposition).  XLA's SPMD
    partitioner inserts the halo collective-permutes; a data-only plan runs
    each device's envs as a program of its own (``shard_map``).
  * overlap: a double-buffered async mode where episode *e* is collected
    while the PPO update for episode *e-1*'s trajectories runs.  JAX async
    dispatch enqueues both computations back to back; the optimizer state is
    donated to the update (params and the stale batch are not — collect still
    reads the params concurrently), so the two in-flight programs never
    contend for the same buffers.  PPO's importance ratio r_t(theta) absorbs
    the one-step staleness (trajectories carry their behaviour-policy
    log-probs).

It also implements the paper's §IV I/O refinement for trajectory spill as a
pluggable ``TrajectorySink``: in-memory, binary (msgpack + raw fp32),
zstd-compressed binary, or the sharded on-disk dataset
(``repro.data.trajectory_dataset``), reusing the ``core.interface`` codecs
that back the measured Table II file-interface modes.  Sinks are selected
with one :class:`SinkSpec` config accepted uniformly by ``EngineConfig``,
``TrainConfig`` and ``examples/drl_cylinder.py --sink``; the old
``make_sink(mode, root)`` survives one release as a deprecated shim.
"""
from __future__ import annotations

import os
import time
import warnings
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.ckpt.io import atomic_write_bytes, retry_io
from repro.testing import faults
from repro.core import backend as backend_mod
from repro.core.interface import pack_arrays, unpack_arrays
from repro.drl import networks, rollout, spans
from repro.drl.gae import gae_batch
from repro.drl.ppo import Batch, PPOConfig, make_optimizer, ppo_update
from repro.drl.rollout import Trajectory

try:
    import zstandard as zstd
except ImportError:  # pragma: no cover - optional, gated
    zstd = None

_DRL_DIR = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# trajectory sinks — the paper's I/O strategies applied to trajectory spill
# ---------------------------------------------------------------------------

def _host_traj(traj) -> Trajectory:
    """Device trajectory -> host numpy, preserving absent (None) aux fields."""
    return Trajectory(*(None if a is None else np.asarray(a) for a in traj))


class SinkReadError(KeyError):
    """Raised when a sink is asked for an episode it does not hold.

    Subclasses ``KeyError`` so pre-SinkSpec callers that caught the old
    behaviour keep working; the message names the sink, its root/codec and
    the episode range actually present (``CheckpointError`` style)."""


class TrajectorySink:
    """Receives each collected episode's trajectories.  Base class = no-op
    (the paper's io-DISABLED upper bound); subclasses spill to memory or disk.

    Tracks ``bytes_written``/``time_spent`` so training loops and benchmarks
    can report interface cost exactly like ``core.interface``."""

    def __init__(self):
        self.episodes = 0
        self.bytes_written = 0
        self.time_spent = 0.0
        self.retries = 0      # transient write errors recovered by retry

    def write(self, episode: int, traj: Trajectory) -> int:
        t0 = time.perf_counter()
        with spans.span("io.sink"):
            n = self._write(episode, traj)
        self.bytes_written += n
        self.time_spent += time.perf_counter() - t0
        self.episodes += 1
        return n

    def _write(self, episode: int, traj: Trajectory) -> int:
        return 0

    def read(self, episode: int) -> Trajectory:
        raise SinkReadError(f"sink holds no episode {episode}: "
                            f"{type(self).__name__} does not retain episodes")

    def annotate(self, **meta) -> None:
        """Attach run-level metadata (solver fingerprint, scenario names...).

        No-op for stateless sinks; the dataset sink records it in its
        manifest so recorded trajectories outlive the writing process."""

    def close(self) -> None:
        """Flush and release handles; never destroys spilled data."""

    def cleanup(self) -> None:
        """Delete everything the sink spilled (mirrors FileInterface)."""


class MemorySink(TrajectorySink):
    """Keeps the last ``keep`` episodes on the host (replay / inspection)."""

    def __init__(self, keep: int = 8):
        super().__init__()
        self.keep = keep
        self._store: Dict[int, Trajectory] = {}

    def _write(self, episode: int, traj: Trajectory) -> int:
        host = _host_traj(traj)
        self._store[episode] = host
        while len(self._store) > self.keep:
            del self._store[min(self._store)]
        return sum(a.nbytes for a in host if a is not None)

    def read(self, episode: int) -> Trajectory:
        if episode not in self._store:
            have = (f"episodes {min(self._store)}..{max(self._store)}"
                    if self._store else "no episodes")
            raise SinkReadError(
                f"sink holds no episode {episode}: MemorySink(keep="
                f"{self.keep}) retains {have}")
        return self._store[episode]


class FileSink(TrajectorySink):
    """Spills each episode to one binary file via the ``core.interface``
    codec (paper §III.D: single binary file instead of many ASCII dumps).
    Files land via tmp + ``os.replace`` so a SIGKILL mid-spill never leaves
    a truncated episode.

    codec='binary'  msgpack + raw fp32 (the paper's optimized mode)
    codec='zstd'    the same, zstd-compressed (beyond-paper); silently
                    degrades to 'binary' when zstandard is not installed.

    ``process`` (fleet mode) suffixes every file with the writer's process
    id (``traj_000007.p002.bin``) so N concurrent runners sharing one sink
    root never contend on — or clobber — the same episode file; each
    runner spills its own env shard and reads back only its own files.
    """

    def __init__(self, root: str, codec: str = "binary",
                 process: Optional[int] = None):
        super().__init__()
        if codec not in ("binary", "zstd"):
            raise ValueError(f"unknown trajectory-sink codec {codec!r}; "
                             f"choose 'binary' or 'zstd'")
        if codec == "zstd" and zstd is None:
            codec = "binary"
        self.codec = codec
        self.process = process
        self.dir = Path(root)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._cctx = zstd.ZstdCompressor(level=1) if codec == "zstd" else None
        self._dctx = zstd.ZstdDecompressor() if codec == "zstd" else None

    def _path(self, episode: int) -> Path:
        if self.process is None:
            return self.dir / f"traj_{episode:06d}.bin"
        return self.dir / f"traj_{episode:06d}.p{self.process:03d}.bin"

    def _write(self, episode: int, traj: Trajectory) -> int:
        # optional trailing fields (probe aux) are skipped when absent, so
        # files written by either layout stay readable by both
        arrays = {f: np.asarray(a) for f, a in zip(Trajectory._fields, traj)
                  if a is not None}
        blob = pack_arrays(arrays, cctx=self._cctx)
        path = self._path(episode)

        def attempt():
            faults.maybe_fail_io(str(path))
            return atomic_write_bytes(path, blob)

        def on_retry(attempt_no, exc):
            self.retries += 1

        return retry_io(attempt, path=path,
                        what=f"trajectory spill (episode {episode})",
                        on_retry=on_retry)

    def _available(self) -> str:
        pat = "traj_*.bin" if self.process is None \
            else f"traj_*.p{self.process:03d}.bin"
        eps = sorted(int(p.name.split("_")[1].split(".")[0])
                     for p in self.dir.glob(pat))
        return (f"episodes {eps[0]}..{eps[-1]} ({len(eps)} on disk)"
                if eps else "no episodes on disk")

    def read(self, episode: int) -> Trajectory:
        path = self._path(episode)
        if not path.exists():
            raise SinkReadError(
                f"sink holds no episode {episode}: FileSink(root="
                f"{str(self.dir)!r}, codec={self.codec!r}) has "
                f"{self._available()}")
        arrays, _ = unpack_arrays(path.read_bytes(), dctx=self._dctx)
        return Trajectory(**{f: arrays[f] for f in Trajectory._fields
                             if f in arrays})

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclass(frozen=True)
class SinkSpec:
    """One declarative config for every trajectory-spill strategy.

    Replaces the stringly ``make_sink(mode, root)`` + ad-hoc constructor
    kwargs: the same spec is accepted by ``EngineConfig.sink``,
    ``TrainConfig.sink`` and ``examples/drl_cylinder.py --sink``.

      kind='none'     no spill (the paper's io-DISABLED upper bound)
      kind='memory'   MemorySink keeping the last ``keep`` episodes
      kind='binary'   FileSink, one msgpack+fp32 file per episode at ``root``
      kind='zstd'     FileSink, zstd-compressed (degrades without zstandard)
      kind='dataset'  repro.data.trajectory_dataset.DatasetSink: sharded
                      files + JSON manifest, ``codec``/``shard_max_bytes``
                      apply (the durable, replayable format)

    ``process`` makes file-backed sinks multi-process-safe: FileSink files
    get a per-process suffix and the dataset sink writes a per-process
    ``part{NNN}`` subdirectory (its own shards + manifest) under the shared
    root, so N fleet runners spilling concurrently never clobber one
    another.  The default (None) auto-detects: multi-process jax runs use
    ``jax.process_index()``, single-process runs keep the flat layout.
    """

    kind: str = "none"
    root: Optional[str] = None
    keep: int = 8                       # memory: episodes retained
    codec: str = "binary"               # dataset: payload codec
    shard_max_bytes: int = 64 * 1024 * 1024   # dataset: shard rotation
    # per-process shard suffix/subdir; None = auto (process_index when the
    # jax runtime spans processes, flat single-writer layout otherwise)
    process: Optional[int] = None

    KINDS = ("none", "memory", "binary", "zstd", "dataset")

    @classmethod
    def parse(cls, text: Optional[str]) -> "SinkSpec":
        """Parse a CLI-style ``kind[:root]`` string ('dataset:/tmp/ds')."""
        if text in (None, "", "none", "disabled"):
            return cls(kind="none")
        kind, _, root = text.partition(":")
        return cls(kind=kind, root=root or None)

    def _process(self) -> Optional[int]:
        if self.process is not None:
            return self.process
        return jax.process_index() if jax.process_count() > 1 else None

    def build(self) -> Optional[TrajectorySink]:
        if self.kind in (None, "none", "disabled"):
            return None
        if self.kind == "memory":
            return MemorySink(keep=self.keep)
        if self.kind in ("binary", "zstd"):
            if self.root is None:
                raise ValueError(f"file sink {self.kind!r} needs a root "
                                 f"directory")
            return FileSink(self.root, codec=self.kind,
                            process=self._process())
        if self.kind == "dataset":
            if self.root is None:
                raise ValueError("dataset sink needs a root directory")
            from repro.data.trajectory_dataset import DatasetSink
            return DatasetSink(self.root, codec=self.codec,
                               shard_max_bytes=self.shard_max_bytes,
                               process=self._process())
        raise ValueError(f"unknown sink kind {self.kind!r}; "
                         f"choose from {self.KINDS}")


def make_sink(mode: str, root: Optional[str] = None) -> Optional[TrajectorySink]:
    """Deprecated: pass ``SinkSpec(kind=..., root=...)`` (or
    ``SinkSpec.parse('binary:/path')``) instead.

    Kept for one release as a shim over :class:`SinkSpec`; the warning's
    stacklevel blames the caller (PR-5 ``resolve_backend`` pattern)."""
    warnings.warn("make_sink() is deprecated; pass SinkSpec(kind=..., "
                  "root=...) / SinkSpec.parse('binary:/path') instead",
                  DeprecationWarning,
                  stacklevel=backend_mod.caller_stacklevel((_DRL_DIR,)))
    if mode in (None, "none", "disabled"):
        return None
    if mode == "memory":
        return MemorySink()
    if mode not in ("binary", "zstd"):
        raise ValueError(f"unknown sink mode {mode!r}; choose 'none', "
                         f"'memory', 'binary' or 'zstd'")
    if root is None:
        raise ValueError(f"file sink {mode!r} needs a root directory")
    return SinkSpec(kind=mode, root=root).build()


# ---------------------------------------------------------------------------
# mesh placement helpers (absorbed from core/runner.py)
# ---------------------------------------------------------------------------

def env_state_specs(mesh: Mesh) -> Tuple[P, P]:
    """(batch-only spec, batch+space spec) for env pytrees.

    Grid arrays additionally shard their x (last) dim over "model" when the
    plan uses n_ranks > 1."""
    from repro.models.sharding import dp_axes
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else dp[0]
    return P(dp), P(dp, None, "model")


def is_grid_field(a, n_ranks: int = 1) -> bool:
    """Heuristic for (N, ny, nx) grid arrays vs. small per-env tables.

    Scenario batches carry (N, P, 2) probe coordinates in the env state;
    only genuine grid fields (trailing dim = nx, always >> 4) should have
    their x dim sharded over "model" — and only when that dim divides into
    the n_ranks x-slabs (staggered u fields are nx+1 wide and stay
    batch-sharded; GSPMD re-shards around them)."""
    return a.ndim == 3 and a.shape[-1] > 4 and a.shape[-1] % n_ranks == 0


def mesh_spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when the mesh's devices live on more than one jax process."""
    if mesh is None:
        return False
    return len({d.process_index for d in mesh.devices.flat}) > 1


def shard_env_batch(mesh: Mesh, st_b, n_ranks: int = 1):
    """device_put a batched env-state pytree with engine shardings.

    Placing the batch on the mesh before the first collect puts each env
    (and, for the halo backend, each x-slab of its grid fields) on its own
    device once, so every collect compiles for and runs on those shardings
    instead of a batch replicated over the "data" axis.

    On a process-spanning (fleet) mesh ``jax.device_put`` cannot place a
    host array, so each leaf is assembled with
    ``jax.make_array_from_callback`` instead — every process holds the same
    full host value (fleet training computes the batch identically
    everywhere) and contributes its local shards.  Leaves that are already
    global (non-fully-addressable) arrays pass through untouched."""
    batch, batch_space = env_state_specs(mesh)
    spans = mesh_spans_processes(mesh)

    def spec_of(a):
        if n_ranks > 1 and is_grid_field(a, n_ranks):
            return NamedSharding(mesh, batch_space)
        return NamedSharding(mesh, P(batch[0]))

    def put(a):
        if isinstance(a, jax.Array) and not a.is_fully_addressable:
            return a                       # already globally placed
        if spans:
            host = np.asarray(a)
            return jax.make_array_from_callback(
                host.shape, spec_of(a), lambda idx, h=host: h[idx])
        return jax.device_put(a, spec_of(a))

    return jax.tree.map(put, st_b)


def place_env_batch(mesh: Optional[Mesh], st_b, n_ranks: int = 1):
    """Place a (possibly host/checkpoint-restored) env batch for the engine.

    With a mesh this is ``shard_env_batch`` — the cross-plan resume path:
    a TrainState checkpointed under one ParallelPlan round-trips through
    host arrays and is re-sharded here onto whatever mesh the *current*
    plan resolved to.  Without a mesh it is a plain device transfer."""
    if mesh is not None:
        return shard_env_batch(mesh, st_b, n_ranks)
    return jax.tree.map(jnp.asarray, st_b)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class TrainCarry(NamedTuple):
    """The loop-carried tuple the training loops expose to ``on_state``
    after each episode: exactly what a checkpoint must persist for a
    bitwise resume (the env batch and history live with the caller)."""
    params: Any
    opt_state: Any
    step: jnp.ndarray     # PPO minibatch counter (Adam bias correction)
    key: jnp.ndarray      # PRNG carry BEFORE the next episode's splits


@dataclass(frozen=True)
class EngineConfig:
    n_envs: int
    horizon: int              # actuation periods per episode (the paper's T)
    gamma: float = 0.99
    lam: float = 0.95
    n_ranks: int = 1          # grid shards per env over the "model" axis
    donate: bool = True       # donate opt_state to the async-mode update
    # hybrid placement: "auto" (measure + optimize via core.autotune), a
    # core.plan.ParallelPlan / (n_envs, n_ranks) pair, a ResolvedPlan, or
    # None (explicit mesh= / single-host).  When set and no mesh is passed,
    # the engine builds its mesh from the resolved plan and adopts the
    # plan's n_ranks.
    plan: Any = None
    # trajectory spill (SinkSpec); an explicit sink= to the engine wins
    sink: Optional[SinkSpec] = None
    # multi-process fleet mode (repro.launch.distributed): the rollout runs
    # on the process-spanning mesh, trajectories are all-gathered to the
    # host, and postprocess + PPO update run as a REPLICATED local
    # single-device program on every process (the drlfoam runner/learner
    # split: the CFD fan-out is distributed, the tiny MLP learner is
    # redundantly recomputed — no gradient traffic, and training is
    # bitwise-identical at every fleet size under the pinned device count,
    # see launch/distributed.py).  Sinks spill per-process env shards.
    fleet: bool = False


class RolloutEngine:
    """One collect implementation, three consumers.

    ``collect`` is the jitted (params, st_b, obs_b, key) -> (Batch, Trajectory)
    function; ``collect_fn`` is the untraced closure (for ``.lower()`` dry-runs
    on abstract inputs).  With a mesh, inputs are constrained to the paper's
    hybrid placement; with ``mesh=None`` it is the plain single-host vmap path.
    """

    def __init__(self, env_step_fn: Callable, cfg: EngineConfig, *,
                 mesh: Optional[Mesh] = None,
                 sink: Optional[TrajectorySink] = None,
                 obs_aux_fn: Optional[Callable] = None):
        self.env_step_fn = env_step_fn
        self.obs_aux_fn = obs_aux_fn
        self.resolved_plan = None
        if cfg.plan is not None:
            from repro.core.autotune import resolve_plan
            # smoke probe: engine construction must not block on a
            # full-resolution timing sweep (ignored for explicit plans)
            self.resolved_plan = resolve_plan(cfg.plan, smoke=True)
            if mesh is None:
                mesh = self.resolved_plan.build_mesh()
            if self.resolved_plan.n_ranks != cfg.n_ranks:
                import dataclasses as _dc
                cfg = _dc.replace(cfg, n_ranks=self.resolved_plan.n_ranks)
        self.cfg = cfg
        self.mesh = mesh
        if sink is None and cfg.sink is not None:
            sink = cfg.sink.build()
        self.sink = sink
        self.episode = 0
        rollout_fn = self._build_rollout()
        postprocess_fn = self._build_postprocess()

        def collect_fused(params, st_b, obs_b, key):
            traj = rollout_fn(params, st_b, obs_b, key)
            return postprocess_fn(params, traj), traj

        # the untraced fused closure (runner/dry-run .lower() consumers)
        self.collect_fn = collect_fused
        if mesh is not None:
            batch, _ = env_state_specs(mesh)
            in_shardings = (
                NamedSharding(mesh, P()),              # params replicated
                None,                                  # st_b: as provided
                NamedSharding(mesh, P(batch[0])),      # obs batch-sharded
                NamedSharding(mesh, P()),
            )
            self._collect = jax.jit(self.collect_fn,
                                    in_shardings=in_shardings)
            self._rollout = jax.jit(rollout_fn, in_shardings=in_shardings)
        else:
            self._collect = jax.jit(self.collect_fn)
            self._rollout = jax.jit(rollout_fn)
        # values -> GAE -> flatten as its OWN jitted program, shared verbatim
        # by the live collect path and replay_sync: the record -> replay
        # bitwise gate holds because both feed the same compiled program
        self.postprocess = jax.jit(postprocess_fn)
        if cfg.fleet:
            if mesh is None:
                raise ValueError("EngineConfig(fleet=True) needs a mesh — "
                                 "pass a plan or an explicit mesh=")
            if cfg.n_envs % max(1, jax.process_count()):
                raise ValueError(
                    f"fleet mode needs n_envs = {cfg.n_envs} divisible by "
                    f"the process count {jax.process_count()} (each process "
                    f"owns an equal env shard)")
            # all-gather: every process materializes the full trajectory
            # batch (the inter-host traffic the autotuner's t_interhost
            # term models); postprocess + update then run on the host copy
            self._gather = jax.jit(lambda t: t,
                                   out_shardings=NamedSharding(mesh, P()))

    @classmethod
    def for_env(cls, env, cfg: EngineConfig, **kw) -> "RolloutEngine":
        """Bind a CylinderEnv-like object (anything with ``env_step``).

        Envs exposing ``obs_aux`` (probe coords + live-slot mask) get it
        threaded to the policy automatically."""
        kw.setdefault("obs_aux_fn", getattr(env, "obs_aux", None))
        return cls(env.env_step, cfg, **kw)

    # -- collect -> GAE -> flatten (THE single implementation) --------------

    def _build_rollout(self):
        cfg, mesh = self.cfg, self.mesh
        if mesh is not None and cfg.n_ranks == 1:
            return self._build_data_parallel_rollout()

        def collect_traj(params, st_b, obs_b, key):
            if mesh is not None:
                batch_spec, batch_space = env_state_specs(mesh)

                def constrain(a):     # halo plans: n_ranks > 1
                    if is_grid_field(a, cfg.n_ranks):
                        return jax.lax.with_sharding_constraint(
                            a, NamedSharding(mesh, batch_space))
                    return jax.lax.with_sharding_constraint(
                        a, NamedSharding(mesh, batch_spec))

                st_b = jax.tree.map(constrain, st_b)
            _, traj = rollout.rollout_batch(self.env_step_fn, params, st_b,
                                            obs_b, key, cfg.horizon,
                                            cfg.n_envs,
                                            obs_aux_fn=self.obs_aux_fn)
            return traj

        return collect_traj

    def _build_data_parallel_rollout(self):
        """Data-only plans: each device rolls out its own slice of the env
        batch as a program of its own (``shard_map`` over the "data" axis).
        Envs never exchange data, and a Pallas kernel in the env step is a
        Mosaic call the SPMD partitioner cannot split.  The per-env keys are
        split before the batch is, so every env draws what it draws in the
        unsharded rollout."""
        cfg, mesh = self.cfg, self.mesh
        batch_spec, _ = env_state_specs(mesh)

        def local(params, st_b, obs_b, keys):
            _, traj = rollout.rollout_keyed(self.env_step_fn, params, st_b,
                                            obs_b, keys, cfg.horizon,
                                            obs_aux_fn=self.obs_aux_fn)
            return traj

        run = jax.shard_map(local, mesh=mesh,
                            in_specs=(P(), batch_spec, batch_spec, batch_spec),
                            out_specs=batch_spec, check_vma=False)

        def collect_traj(params, st_b, obs_b, key):
            return run(params, st_b, obs_b,
                       jax.random.split(key, cfg.n_envs))

        return collect_traj

    def _build_postprocess(self):
        cfg = self.cfg

        def postprocess(params, traj):
            if traj.probe_mask is not None:
                # per-env probe layout, constant over the episode: insert a
                # T axis for the (N, T, P) obs, bare for the (N, P) last_obs
                aux_t = {"xy": traj.probe_xy[:, None],
                         "mask": traj.probe_mask[:, None]}
                aux_n = {"xy": traj.probe_xy, "mask": traj.probe_mask}
            else:
                aux_t = aux_n = None
            with jax.named_scope("values"):
                values = networks.value(params, traj.obs, aux_t)   # (N, T)
                last_v = networks.value(params, traj.last_obs, aux_n)  # (N,)
            with jax.named_scope("gae"):
                adv, ret = gae_batch(traj.reward, values, last_v,
                                     gamma=cfg.gamma, lam=cfg.lam,
                                     valid=traj.valid)
            flat = lambda x: x.reshape((-1,) + x.shape[2:])
            batch = Batch(obs=flat(traj.obs), act=flat(traj.act),
                          logp_old=flat(traj.logp), adv=flat(adv),
                          ret=flat(ret))
            if traj.valid is not None:
                # sentinel mask rides per-sample so PPO's shuffled
                # minibatches keep each row's validity with it
                batch = batch._replace(valid=flat(traj.valid))
            if traj.probe_mask is not None:
                # PPO minibatching permutes rows, so each sample carries its
                # own layout row (broadcast across the episode, then flat)
                N, T = traj.obs.shape[:2]
                xy = jnp.broadcast_to(traj.probe_xy[:, None],
                                      (N, T) + traj.probe_xy.shape[1:])
                m = jnp.broadcast_to(traj.probe_mask[:, None],
                                     (N, T) + traj.probe_mask.shape[1:])
                batch = batch._replace(probe_xy=flat(xy), probe_mask=flat(m))
            return batch

        return postprocess

    def collect(self, params, st_b, obs_b, key, *, record: bool = True
                ) -> Tuple[Batch, Trajectory]:
        """One episode round of all N_envs environments.

        With a mesh, the env batch is pre-placed on it (a no-op when the
        caller already did; see ``shard_env_batch``), so the engine owns the
        placement rather than trusting every caller.

        Fleet mode: params/key arrive as process-local arrays, are
        replicated onto the global mesh for the distributed rollout, and
        the collected trajectories are all-gathered back to the host —
        ``postprocess`` then compiles as a plain local program, identical
        on every process and at every fleet size (the bitwise contract).
        The returned Trajectory is the host copy (full batch)."""
        with spans.span("collect"):
            if self.mesh is not None:
                st_b = shard_env_batch(self.mesh, st_b, self.cfg.n_ranks)
            if self.cfg.fleet:
                traj = self._rollout(self._replicate(params), st_b, obs_b,
                                     self._replicate(key))
                with spans.span("sync"):
                    traj = _host_traj(self._gather(traj))
            else:
                traj = self._rollout(params, st_b, obs_b, key)
            batch = self.postprocess(params, traj)
        if record:
            self._sink_write(self.episode, traj)
        self.episode += 1
        return batch, traj

    def rollout_local(self, params, st_b, obs_b, key):
        """The no-comms twin of ``collect``: the same distributed rollout
        program, but each process blocks only on ITS env shard — no
        trajectory all-gather, no postprocess, no sink.

        Benchmarks use this as the oversubscription baseline: on a host
        with fewer cores than fleet processes, raw throughput conflates
        time-slicing contention (which p independent jobs would also pay)
        with the fleet's actual communication cost.  The ratio
        ``tp(collect) / tp(rollout_local)`` at the same fleet size isolates
        exactly the inter-process communication + sync overhead."""
        if self.mesh is not None:
            st_b = shard_env_batch(self.mesh, st_b, self.cfg.n_ranks)
        traj = self._rollout(self._replicate(params) if self.cfg.fleet
                             else params, st_b, obs_b,
                             self._replicate(key) if self.cfg.fleet else key)
        jax.block_until_ready(traj)
        return traj

    def _replicate(self, tree):
        """Place process-local (or host) arrays fully-replicated on the
        fleet mesh; leaves that are already global pass through."""
        rep = NamedSharding(self.mesh, P())

        def put(a):
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                return a
            host = np.asarray(a)
            return jax.make_array_from_callback(
                host.shape, rep, lambda idx, h=host: h[idx])

        return jax.tree.map(put, tree)

    def _sink_write(self, episode: int, traj: Trajectory) -> None:
        """Spill one episode; fleet runners write only THEIR env rows (the
        per-host shard — the sink's per-process suffix/part dir keeps
        concurrent writers from clobbering each other)."""
        if self.sink is None:
            return
        if self.cfg.fleet and jax.process_count() > 1:
            per = self.cfg.n_envs // jax.process_count()
            lo = jax.process_index() * per
            traj = Trajectory(*(None if a is None
                                else np.asarray(a)[lo:lo + per]
                                for a in traj))
        self.sink.write(episode, traj)

    # -- PPO update (donation-aware, shared by sync + async loops) -----------

    def make_update(self, ppo_cfg: PPOConfig, optimizer, *,
                    donate: bool = False):
        """jit'd (params, opt_state, batch, key, step) -> updated tuple.

        With ``donate=True`` the optimizer state is donated (it aliases the
        returned opt_state buffers), so in async mode the in-flight update
        never allocates a second moment-buffer set while collect runs.
        Params and the stale batch are NOT donated: the concurrently
        dispatched collect still reads the params, and the batch has no
        output to alias."""

        def update(params, opt_state, batch, key, step):
            return ppo_update(ppo_cfg, optimizer, params, opt_state, batch,
                              key, step)

        kw = {"donate_argnums": (1,)} if donate and self.cfg.donate else {}
        return jax.jit(update, **kw)

    # -- training loops ------------------------------------------------------

    def run_sync(self, params, opt_state, ppo_cfg: PPOConfig, optimizer,
                 st_b, obs_b, key, episodes: int, *, step=None,
                 on_batch: Optional[Callable] = None,
                 on_episode: Optional[Callable] = None,
                 on_state: Optional[Callable] = None):
        """Sequential [collect] -> [update] (the paper's Fig. 4 loop).

        ``step`` seeds the PPO minibatch counter (resume passes the stored
        one so Adam bias correction continues, fresh runs leave it None);
        ``on_state(TrainCarry)`` fires after every fully-applied episode —
        checkpointing that carry and re-entering with it reproduces the
        remaining episodes bit for bit."""
        update = self.make_update(ppo_cfg, optimizer)
        step = jnp.int32(0) if step is None else jnp.asarray(step, jnp.int32)
        returns = []
        for _ in range(episodes):
            with spans.episode(self.episode):
                with spans.span("collect"):
                    key, kr, ku = jax.random.split(key, 3)
                batch, traj = self.collect(params, st_b, obs_b, kr)
                if on_batch is not None:   # e.g. the CFD<->DRL file interface
                    batch = on_batch(batch)
                with spans.span("update"):
                    params, opt_state, step, metrics = update(
                        params, opt_state, batch, ku, step)
                with spans.span("sync"):
                    returns.append(float(jnp.mean(jnp.sum(traj.reward,
                                                          axis=1))))
                if on_episode is not None:
                    on_episode(traj, metrics)
                if on_state is not None:
                    on_state(TrainCarry(params, opt_state, step, key))
        return params, opt_state, np.asarray(returns)

    def replay_sync(self, reader, params, opt_state, ppo_cfg: PPOConfig,
                    optimizer, key, episodes: int, *, step=None, start=0,
                    on_batch: Optional[Callable] = None,
                    on_state: Optional[Callable] = None):
        """Offline PPO: drive the sync update path from recorded episodes.

        ``reader`` is anything with ``read(episode) -> Trajectory`` (a
        ``TrajectoryReader``, ``FileSink`` or ``MemorySink``).  Values and
        GAE are recomputed from the recorded observations with the CURRENT
        (evolving) params through the same jitted postprocess program the
        live collect uses, and the PRNG key discipline mirrors ``run_sync``
        exactly (the collect subkey is split and burned) — so replaying a
        just-recorded dataset from the recorded seed reproduces the live
        run's parameter updates bitwise.  With an older dataset this is the
        offline regression eval: old behaviour policy, current networks."""
        update = self.make_update(ppo_cfg, optimizer)
        step = jnp.int32(0) if step is None else jnp.asarray(step, jnp.int32)
        returns = []
        for ep in range(start, start + episodes):
            with spans.episode(ep):
                with spans.span("io.sink"):
                    recorded = reader.read(ep)
                with spans.span("collect"):
                    key, kr, ku = jax.random.split(key, 3)
                    del kr              # run_sync's collect subkey, burned
                    traj = Trajectory(*(None if a is None else jnp.asarray(a)
                                        for a in recorded))
                    batch = self.postprocess(params, traj)
                if on_batch is not None:
                    batch = on_batch(batch)
                with spans.span("update"):
                    params, opt_state, step, metrics = update(
                        params, opt_state, batch, ku, step)
                with spans.span("sync"):
                    returns.append(float(jnp.mean(jnp.sum(traj.reward,
                                                          axis=1))))
                if on_state is not None:
                    on_state(TrainCarry(params, opt_state, step, key))
        return params, opt_state, np.asarray(returns)

    def run_async(self, params, opt_state, ppo_cfg: PPOConfig, optimizer,
                  st_b, obs_b, key, episodes: int, *, step=None,
                  drain: bool = True,
                  on_episode: Optional[Callable] = None,
                  on_state: Optional[Callable] = None,
                  state_every: int = 1):
        """Double-buffered stale-gradient PPO.

        Episode *e* is collected with the params as of episode *e-1* while
        the update consuming episode *e-1*'s trajectories is dispatched; JAX
        async dispatch lets both programs be in flight together (on 1 CPU
        device they serialize — the algorithmic semantics are what the tests
        pin down; ``async_speedup`` models the systems half).

        ``on_state(TrainCarry)`` fires every ``state_every`` episodes with
        the carry as visible at that point — the one in-flight batch (the
        episode just collected, whose update has not been dispatched yet)
        is deliberately NOT part of it, so an async checkpoint never blocks
        the overlap.  A resume from such a checkpoint therefore drops that
        single in-flight update (its episode stays logged); PPO absorbs the
        gap the same way it absorbs the one-step staleness.  One final
        ``on_state`` fires after the drain — that carry has no in-flight
        work, so checkpointing it loses nothing.  Only the sync loop offers
        bitwise resume."""
        update = self.make_update(ppo_cfg, optimizer, donate=True)
        step = jnp.int32(0) if step is None else jnp.asarray(step, jnp.int32)
        pending: Optional[Batch] = None   # awaits its (overlapped) update
        spill = None                      # (episode, traj) awaiting the sink
        returns = []
        for i in range(episodes):
            ep_id = self.episode
            with spans.episode(ep_id):
                with spans.span("collect"):
                    key, kr, ku = jax.random.split(key, 3)
                # both dispatches below can execute concurrently: collect
                # uses the STALE params, and the update only touches the
                # previous episode's batch — never the buffers collect is
                # writing.  The sink (host-blocking I/O) only ever sees the
                # PREVIOUS, already-materialized episode, after the update
                # is dispatched, so spilling never serializes the two
                # in-flight programs.
                batch, traj = self.collect(params, st_b, obs_b, kr,
                                           record=False)
                if pending is not None:
                    with spans.span("update"):
                        params, opt_state, step, _ = update(
                            params, opt_state, pending, ku, step)
                if self.sink is not None and spill is not None:
                    self._sink_write(*spill)
                pending = batch
                spill = (ep_id, traj)
                with spans.span("sync"):
                    returns.append(float(jnp.mean(jnp.sum(traj.reward,
                                                          axis=1))))
                if on_episode is not None:
                    on_episode(traj, None)
                if on_state is not None and (i + 1) % max(1,
                                                          state_every) == 0:
                    on_state(TrainCarry(params, opt_state, step, key))
        if drain and pending is not None:
            key, ku = jax.random.split(key)
            with spans.span("update"):
                params, opt_state, step, _ = update(params, opt_state,
                                                    pending, ku, step)
        if self.sink is not None and spill is not None:
            self._sink_write(*spill)
        if on_state is not None and episodes > 0:
            # final carry AFTER the drain: the one state with no in-flight
            # update, so a checkpoint of it loses nothing
            on_state(TrainCarry(params, opt_state, step, key))
        return params, opt_state, np.asarray(returns)

    # -- convenience ---------------------------------------------------------

    def init(self, pcfg: networks.PolicyConfig, ppo_cfg: PPOConfig, seed: int
             ) -> Tuple[Any, Any, Any, Any]:
        """(params, optimizer, opt_state, key) for a fresh run."""
        key = jax.random.PRNGKey(seed)
        key, kp = jax.random.split(key)
        params = networks.init_actor_critic(pcfg, kp)
        optimizer = make_optimizer(ppo_cfg)
        opt_state = optimizer.init(params)
        return params, optimizer, opt_state, key


def broadcast_env_state(st, obs, n_envs: int):
    """Tile a single reset state/obs into an (N_envs, ...) batch."""
    st_b = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_envs,) + a.shape), st)
    obs_b = jnp.broadcast_to(obs, (n_envs,) + obs.shape)
    return st_b, obs_b
