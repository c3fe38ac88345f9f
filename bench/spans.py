"""Device idle time inside the program's episodes, named by its own spans.

The program marks each training episode with a ``repro/episode`` step span
and its layer boundaries with ``repro/<kind>`` spans (``collect``,
``update``, ``sync``, ``io.sink``, ``io.ckpt``, ``io.interface``,
``caller``), all on the profiler's clock (``repro.drl.spans``).  Only
complete episodes count: the profiler records no span that began before
``start_trace`` or ended after ``stop_trace``, and an episode span that
reaches past the trace's other events is left out as well.

Inside each complete episode every chip's idle time (the complement of the
union of its operations) goes to the innermost ``repro/`` span that covers
it, split at span boundaries; idle that no span inside the episode covers
is ``unspanned``.  So the idle seconds of all kinds add up to the idle
inside the complete episodes.  A trace with no complete episode span (as
from a program that writes none) reads ``None``.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

PREFIX = "repro/"
EPISODE = PREFIX + "episode"
UNSPANNED = "unspanned"


class Attribution(NamedTuple):
    episodes: int              # complete episode spans
    idle_s: Dict[str, float]   # kind -> idle seconds, mean over the chips
    counts: Dict[str, int]     # kind -> spans inside complete episodes

    def per_episode_ms(self, *kinds: str) -> float:
        return 1e3 * sum(self.idle_s.get(k, 0.0) for k in kinds
                         ) / self.episodes


def complete_episodes(trace) -> list:
    """The ``repro/episode`` spans that lie within the extent of every other
    event of the trace, host and device."""
    eps = [e for e in trace.host if e.name == EPISODE]
    others = [e for e in trace.host if e.name != EPISODE]
    others += [e for c in trace.chips for e in c.ops + c.modules]
    if not eps or not others:
        return []
    lo = min(e.start for e in others)
    hi = max(e.end for e in others)
    return sorted((e for e in eps if lo <= e.start and e.end <= hi),
                  key=lambda e: e.start)


def _segments(ep, children) -> List[tuple]:
    """(start, end, kind) pieces of the episode, each under one innermost
    span (the shortest that covers it; ``unspanned`` under none)."""
    cuts = sorted({ep.start, ep.end}
                  | {t for c in children for t in (c.start, c.end)
                     if ep.start < t < ep.end})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [c for c in children if c.start <= a and b <= c.end]
        kind = (min(cover, key=lambda c: (c.end - c.start, -c.start))
                .name[len(PREFIX):] if cover else UNSPANNED)
        out.append((a, b, kind))
    return out


def _idle(busy, start: int, end: int) -> List[tuple]:
    """Gaps of a sorted, disjoint busy list inside [start, end)."""
    out, t = [], start
    for s, e in busy:
        if e <= t:
            continue
        if s >= end:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return out


def attribute(trace) -> Optional[Attribution]:
    eps = complete_episodes(trace)
    if not eps:
        return None
    spans = [e for e in trace.host
             if e.name.startswith(PREFIX) and e.name != EPISODE]
    busy = [c.busy() for c in trace.chips]
    idle: Dict[str, float] = {UNSPANNED: 0.0}
    counts: Dict[str, int] = {}
    for ep in eps:
        inside = [s for s in spans if ep.start <= s.start and s.end <= ep.end]
        for s in inside:
            kind = s.name[len(PREFIX):]
            counts[kind] = counts.get(kind, 0) + 1
        children = [s for s in spans if s.start < ep.end and ep.start < s.end]
        segs = _segments(ep, children)
        for b in busy:
            gaps = _idle(b, ep.start, ep.end)
            i = 0
            for a, z, kind in segs:          # both lists in time order
                while i < len(gaps) and gaps[i][1] <= a:
                    i += 1
                j = i
                while j < len(gaps) and gaps[j][0] < z:
                    lap = min(z, gaps[j][1]) - max(a, gaps[j][0])
                    idle[kind] = idle.get(kind, 0.0) + lap / 1e9
                    j += 1
    n = len(trace.chips)
    return Attribution(len(eps), {k: v / n for k, v in idle.items()}, counts)


def read(ctx) -> Optional[Attribution]:
    """The attribution of ``ctx["trace"]``, computed once per context."""
    if "repro_spans" not in ctx:
        ctx["repro_spans"] = attribute(ctx["trace"])
    return ctx["repro_spans"]
