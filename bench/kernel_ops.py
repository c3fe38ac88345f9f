"""Runs of named device operations inside the program's complete episodes.

An operation's HLO instruction name (``poisson_rb_sor_batched.1``,
``all-reduce.3``) is what ``trace_reduce.op_name`` gives for each of its op
events; a Pallas kernel's ``name`` becomes that name.  Only events that lie
inside a complete ``repro/episode`` span count
(``bench/spans.complete_episodes``), so the warm-up episode that the trace
starts in and the one it stops in add nothing.  Where a program is named,
only events inside one of its runs on the same chip (its ``XLA Modules``
events, ``jit_<program>``) count.
"""
from __future__ import annotations

import re
from typing import Callable, NamedTuple, Optional

from bench import spans, trace_reduce

SUFFIX = re.compile(r"\.\d+$")


class KernelRuns(NamedTuple):
    episodes: int     # complete episode spans
    calls: float      # op events per chip
    seconds: float    # their summed durations per chip


def _inside(e, spans_) -> bool:
    return any(s.start <= e.start and e.end <= s.end for s in spans_)


def runs(trace, match: Callable[[str], bool],
         program: Optional[str] = None) -> Optional[KernelRuns]:
    """The op events whose instruction name, its ``.N`` suffix dropped,
    ``match`` accepts, inside complete episodes (and inside a run of
    ``program`` when given), averaged over chips; ``None`` when there is no
    complete episode or no such event."""
    eps = spans.complete_episodes(trace)
    if not eps:
        return None
    calls, ns = 0, 0
    for chip in trace.chips:
        mods = None if program is None else [
            m for m in chip.modules
            if trace_reduce.module_program(m.name) == program]
        for e in chip.ops:
            if not match(SUFFIX.sub("", trace_reduce.op_name(e.name))):
                continue
            if _inside(e, eps) and (mods is None or _inside(e, mods)):
                calls += 1
                ns += e.end - e.start
    if not calls:
        return None
    n = len(trace.chips)
    return KernelRuns(len(eps), calls / n, ns / 1e9 / n)
