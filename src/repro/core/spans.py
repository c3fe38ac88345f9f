"""Profiler spans named ``repro/<kind>``, for every layer of the system.

A span is a ``jax.profiler.TraceAnnotation``: recorded only while a
profiler session runs, on the trace's own clock beside the device's
events, and about a microsecond otherwise.  ``repro.drl.spans`` lists the
training loop's spans and adds the episode step span.

Set-up work (``repro/setup.<kind>``) runs once before training, usually
before any profiler session is open, so a set-up span also appends its
host seconds to ``setup_seconds`` (span name -> one entry per span).
``train()`` clears the record when it starts, so it holds the set-up of
the process's latest ``train()``.
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

PREFIX = "repro/"
setup_seconds: dict = {}


def span(kind: str) -> TraceAnnotation:
    """``with span("update"): ...`` records ``repro/update``."""
    return TraceAnnotation(PREFIX + kind)


@contextlib.contextmanager
def setup_span(kind: str):
    """``with setup_span("warmup"): ...`` records ``repro/setup.warmup``
    and appends its host seconds to ``setup_seconds``."""
    name = "setup." + kind
    t0 = time.perf_counter()
    with span(name):
        yield
    setup_seconds.setdefault(PREFIX + name, []).append(
        time.perf_counter() - t0)
