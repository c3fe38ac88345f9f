"""Profiler spans at the layer boundaries of the training loop.

Each span is a ``jax.profiler.TraceAnnotation`` named ``repro/<kind>``; the
episode is a ``StepTraceAnnotation`` whose ``step_num`` is the episode
index.  They are recorded only while a profiler session runs (wrap the run
in ``jax.profiler.trace(dir)``), on the trace's own clock beside the
device's events, and cost about a microsecond each otherwise.  A span that
starts before the session or ends after it is not recorded at all.

    repro/episode      one episode's host work (the unit readers divide by)
    repro/collect      the episode's PRNG split, env-batch placement, and
                       the rollout and postprocess dispatch
    repro/update       PPO update dispatch
    repro/sync         a blocking device-to-host read
    repro/io.sink      trajectory spill (``TrajectorySink``)
    repro/io.ckpt      checkpoint snapshot and save
    repro/io.interface the CFD<->DRL interface exchange
    repro/caller       the caller's per-episode hook
    repro/setup.warmup one geometry's uncontrolled warm-up run before
                       training (``CylinderEnv._warmup_groups``): its
                       compile or cache load, the run and the C_D0 read

The set-up spans and their record of host seconds live in
``repro.core.spans``, which the env's layer imports too.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation

from repro.core.spans import PREFIX, span


def episode(index: int) -> StepTraceAnnotation:
    return StepTraceAnnotation(PREFIX + "episode", step_num=int(index))


def read(x) -> float:
    """``float(x)`` under ``repro/sync``: the host waits for the device."""
    with span("sync"):
        return float(x)
