"""Runs of one named kernel inside the program's complete episodes.

A Pallas kernel's ``name`` becomes its HLO instruction's name
(``poisson_rb_sor_batched.1``), which ``trace_reduce.op_name`` gives for
each of its op events.  Only events that lie inside a complete
``repro/episode`` span count (``bench/spans.complete_episodes``), so the
warm-up episode that the trace starts in and the one it stops in add
nothing.
"""
from __future__ import annotations

import re
from typing import NamedTuple, Optional

from bench import spans, trace_reduce

SUFFIX = re.compile(r"\.\d+$")


class KernelRuns(NamedTuple):
    episodes: int     # complete episode spans
    calls: float      # op events per chip
    seconds: float    # their summed durations per chip


def runs(trace, kernel: str) -> Optional[KernelRuns]:
    """The events of ``kernel`` inside complete episodes, averaged over
    chips; ``None`` when there is no complete episode or no such event."""
    eps = spans.complete_episodes(trace)
    if not eps:
        return None
    calls, ns = 0, 0
    for chip in trace.chips:
        for e in chip.ops:
            if SUFFIX.sub("", trace_reduce.op_name(e.name)) != kernel:
                continue
            if any(ep.start <= e.start and e.end <= ep.end for ep in eps):
                calls += 1
                ns += e.end - e.start
    if not calls:
        return None
    n = len(trace.chips)
    return KernelRuns(len(eps), calls / n, ns / 1e9 / n)
