"""Multi-process fleet bootstrap: span the engine's "data" axis over hosts.

One coordinator/runner shape (drlfoam's ``LocalBuffer``/``SlurmBuffer``
split, ported onto ``jax.distributed``): every runner process calls
:func:`initialize_fleet` before touching any jax device state, the
coordinator (process 0) doubles as the jax distributed-service host, and
``launch/mesh.mesh_for_plan`` then builds one global mesh whose "data" axis
crosses process boundaries while the "model" (halo) axis stays intra-host —
the paper's keep-the-outer-axis-embarrassing principle at fleet scale.

Two launch paths share this module:

* ``tools/launch_fleet.py`` — the single-command local launcher; forks N
  runner processes on one box with a **pinned**
  ``XLA_FLAGS=--xla_force_host_platform_device_count`` (see below) and
  wires the ``REPRO_*`` env vars.
* a cluster scheduler (SLURM sketch in the README) — each task exports the
  same env vars and calls the same entry point.

Bitwise-parity contract (tests/test_fleet.py): the forced host device
count must be **identical in every runner and at every fleet size** (the
plan's ``n_total``, NOT ``n_total // num_processes``).  XLA's CPU codegen
differs between forced device counts even for single-device programs, so a
1-process run with 4 local devices and a 2-process run with 2 local
devices each would disagree in the last ulp of the PPO update.  With the
count pinned, the fleet mesh simply uses the first
``n_total // num_processes`` devices of each process and training is
bitwise-identical across fleet sizes.

Heartbeats: runners touch a per-process JSON file each episode;
``tools/launch_fleet.py`` watches both child liveness (the SIGKILL fast
path) and heartbeat age (the hang path) and elastically shrinks + resumes
via the PR-4 checkpoint layer when a runner dies.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

# env vars the launcher (or a cluster scheduler) exports for every runner
ENV_COORDINATOR = "REPRO_COORDINATOR"      # host:port of process 0
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"
ENV_FLEET = "REPRO_FLEET"                  # "1": fleet engine mode, any size
ENV_HEARTBEAT_DIR = "REPRO_HEARTBEAT_DIR"

_initialized = False


@dataclass(frozen=True)
class FleetInfo:
    """The resolved fleet topology of THIS process."""
    num_processes: int
    process_id: int
    coordinator: Optional[str] = None

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def fleet_env(coordinator: str, num_processes: int, process_id: int,
              n_total_devices: int, heartbeat_dir: Optional[str] = None,
              base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The runner-process environment the launcher exports.

    Pins ``--xla_force_host_platform_device_count`` to the PLAN's total
    device count on every runner regardless of fleet size (the bitwise
    contract in the module docstring)."""
    env = dict(os.environ if base is None else base)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n_total_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_PROCESS_ID] = str(process_id)
    env[ENV_FLEET] = "1"
    if heartbeat_dir:
        env[ENV_HEARTBEAT_DIR] = heartbeat_dir
    return env


def initialize_fleet(coordinator_addr: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> FleetInfo:
    """Bootstrap this process into the fleet (idempotent).

    Arguments default to the ``REPRO_*`` env vars the launcher exports; a
    bare call outside any fleet is a harmless single-process no-op.  With
    ``num_processes > 1`` this selects the gloo CPU collectives
    implementation (cross-process computations are unimplemented on the
    default XLA CPU collectives) and calls ``jax.distributed.initialize``
    — so it MUST run before anything initializes a jax backend.
    """
    global _initialized
    import jax

    coordinator_addr = coordinator_addr or os.environ.get(ENV_COORDINATOR)
    if num_processes is None:
        num_processes = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
    if process_id is None:
        process_id = int(os.environ.get(ENV_PROCESS_ID, "0"))
    if num_processes <= 1:
        return FleetInfo(1, 0, coordinator_addr)
    if _initialized:
        return FleetInfo(num_processes, process_id, coordinator_addr)
    if coordinator_addr is None:
        raise ValueError(
            f"initialize_fleet(num_processes={num_processes}) needs a "
            f"coordinator address (pass coordinator_addr= or export "
            f"{ENV_COORDINATOR}=host:port)")
    # gloo BEFORE backend init: XLA's default CPU collectives cannot run
    # cross-process computations at all
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_addr,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    return FleetInfo(num_processes, process_id, coordinator_addr)


def fleet_info() -> FleetInfo:
    """The live topology as jax sees it (after :func:`initialize_fleet`)."""
    import jax
    return FleetInfo(jax.process_count(), jax.process_index(),
                     os.environ.get(ENV_COORDINATOR))


def fleet_active() -> bool:
    """True when the engine should run its fleet path — either this process
    is part of a real multi-process fleet, or the launcher pinned
    ``REPRO_FLEET=1`` (single-process fleets keep the same code path so a
    1-process run is bitwise-comparable to an N-process one)."""
    import jax
    return jax.process_count() > 1 or os.environ.get(ENV_FLEET) == "1"


def span_devices(n_total: int, devices: Optional[List] = None) -> List:
    """The global device list for a process-spanning mesh.

    Takes ``n_total // num_processes`` devices from EVERY process (sorted
    by process then local id) so consecutive mesh rows map to one host and
    the "data" axis tiles hosts — each host keeps any "model"/halo axis
    internal.  With one process this degrades to ``devices[:n_total]``
    (the classic ``mesh_for_plan`` behaviour)."""
    import jax
    devices = list(jax.devices()) if devices is None else list(devices)
    procs = sorted({d.process_index for d in devices})
    if n_total % len(procs):
        raise ValueError(
            f"plan needs n_total = {n_total} devices but the fleet has "
            f"{len(procs)} processes; n_total must divide evenly "
            f"(got {n_total} % {len(procs)} != 0)")
    per = n_total // len(procs)
    out: List = []
    for p in procs:
        local = sorted((d for d in devices if d.process_index == p),
                       key=lambda d: d.id)
        if len(local) < per:
            raise ValueError(
                f"process {p} has {len(local)} devices but the plan needs "
                f"{per} per process; force more with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_total} "
                f"(pinned to n_total on EVERY runner — see "
                f"repro.launch.distributed)")
        out.extend(local[:per])
    return out


# ---------------------------------------------------------------------------
# heartbeats — the liveness signal behind elastic shrink
# ---------------------------------------------------------------------------

def heartbeat_path(root: str, process_id: int) -> Path:
    return Path(root) / f"hb_{process_id:03d}.json"


def write_heartbeat(root: str, process_id: int, episode: int) -> None:
    """Atomically (tmp + replace) stamp this runner's liveness file."""
    path = heartbeat_path(root, process_id)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps({"process": process_id, "episode": episode,
                               "pid": os.getpid(), "time": time.time()}))
    os.replace(tmp, path)


def read_heartbeats(root: str) -> Dict[int, Dict]:
    out = {}
    for path in sorted(Path(root).glob("hb_*.json")):
        try:
            rec = json.loads(path.read_text())
            # supervisor-side observation of the write (its own clock) —
            # the skew-tolerant half of the staleness check below
            rec["_mtime"] = path.stat().st_mtime
            out[int(rec["process"])] = rec
        except (OSError, ValueError, KeyError):
            continue          # mid-replace or garbage: treat as absent
    return out


def stale_processes(root: str, num_processes: int, timeout: float,
                    now: Optional[float] = None) -> List[int]:
    """Process ids whose heartbeat is older than ``timeout`` seconds (a
    runner that never heartbeated at all only counts once the fleet has
    been up longer than the timeout — compile time is not a hang).

    Clock-skew tolerant: a beat's age is measured BOTH by the wall time the
    runner stamped into the payload and by the file mtime the supervisor's
    filesystem observed, and the beat is stale only when the *smaller* of
    the two exceeds the timeout.  A runner whose clock lags (payload looks
    ancient) is saved by a fresh mtime; a supervisor whose clock lags
    (mtime looks ancient, e.g. across NFS) is saved by a fresh payload — a
    truly hung runner ages on both."""
    now = time.time() if now is None else now
    beats = read_heartbeats(root)

    def age(rec) -> float:
        payload_age = now - rec["time"]
        mtime_age = now - rec.get("_mtime", rec["time"])
        return min(payload_age, mtime_age)

    return [p for p in range(num_processes)
            if p in beats and age(beats[p]) > timeout]


class HeartbeatReporter:
    """An ``on_episode``-shaped hook that stamps heartbeats; inert when the
    launcher exported no heartbeat dir."""

    def __init__(self, process_id: int, root: Optional[str] = None):
        self.root = root or os.environ.get(ENV_HEARTBEAT_DIR)
        self.process_id = process_id
        self.episodes = 0

    def __call__(self, *_args, **_kw) -> None:
        self.episodes += 1
        if self.root:
            write_heartbeat(self.root, self.process_id, self.episodes)
