"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (required for the dry-run's forced 512-device host platform).
"""
from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (GSPMD-partitioned) mode."""
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_abstract_mesh(shape, axes):
    """Device-less mesh (spec computation only)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     axis_types=(_AUTO,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the pod axis is
    pure data parallelism (params replicated across pods; only gradient
    all-reduce crosses pods, per the paper's keep-the-outer-axis-embarrassing
    principle; see README "Choosing a parallel plan")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many devices exist (tests / examples)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_for_plan(plan, devices=None, *, span_processes=None):
    """The executable form of a ``core.plan.ParallelPlan``: a ("data",
    "model") mesh shaped (n_envs, n_ranks) over the first ``n_total``
    devices.  Unlike ``jax.make_mesh`` this tolerates a plan smaller than
    the host (the remaining devices simply idle — the plan's utilization
    already accounts for them).

    Process-spanning mode (``span_processes=True``, or the default ``None``
    when ``jax.process_count() > 1``): the "data" axis crosses process
    boundaries — ``n_total // num_processes`` devices are taken from EVERY
    process (``repro.launch.distributed.span_devices``) — while each env's
    "model"/halo ranks stay on one host, the paper's
    keep-the-outer-axis-embarrassing principle at fleet scale.  Requires
    the per-process device slice to be a multiple of n_ranks so no halo
    exchange ever crosses a host boundary."""
    import numpy as np

    n_envs, n_ranks = plan.mesh_shape if hasattr(plan, "mesh_shape") \
        else tuple(plan)
    n = n_envs * n_ranks
    if span_processes is None:
        span_processes = devices is None and jax.process_count() > 1
    if span_processes:
        from repro.launch.distributed import span_devices
        devices = span_devices(n, devices)
        procs = len({d.process_index for d in devices})
        if (n // procs) % n_ranks:
            raise ValueError(
                f"plan (n_envs, n_ranks) = ({n_envs}, {n_ranks}) cannot "
                f"span {procs} processes: each process's {n // procs} "
                f"devices must hold whole envs (a multiple of n_ranks = "
                f"{n_ranks}) so halo exchanges stay intra-host")
    else:
        devices = list(jax.devices()) if devices is None else list(devices)
        if n > len(devices):
            raise ValueError(
                f"plan needs n_envs * n_ranks = {n} devices but this host "
                f"has {len(devices)}; shrink the plan or force more host "
                f"devices "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count={n})")
    arr = np.asarray(devices[:n], dtype=object).reshape(n_envs, n_ranks)
    return jax.sharding.Mesh(arr, ("data", "model"))
