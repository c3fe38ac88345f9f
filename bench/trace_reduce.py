"""Reduce a JAX profiler trace (``.xplane.pb``) to per-layer quantities.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is one named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation run,
its ``XLA Modules`` line one event per program (jitted function) run.  Host
planes hold the host threads' activity on the same clock.

- busy time: the union of the operation intervals of a chip, so ops that
  overlap count once;
- program time: the summed durations of a program's module events, the
  program named as jitted (``jit_<name>`` in the trace);
- collective operations (all-reduce, all-gather, reduce-scatter,
  collective-permute, all-to-all), by their instruction names
  (``COLLECTIVE``; ``bench/metrics/collective_ms_per_ep.py``);
- idle gaps: the spaces between busy intervals, each labelled with the
  shortest host event that covers its midpoint (what the host was doing);
- operation time: each operation's duration less that of the operations
  nested in it (a loop's body runs inside the loop's own event), named by
  its HLO instruction (``fusion.12``), not by the instruction's whole text.
"""
from __future__ import annotations

import re
import shutil
from pathlib import Path
from typing import List, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.IGNORECASE)
MODULE_NAME = re.compile(r"^(?:jit_)?([A-Za-z0-9_]+)")
OP_NAME = re.compile(r"^%?([^\s=]+)\s*=")


class Event(NamedTuple):
    name: str
    start: int     # ns
    end: int       # ns


def union(intervals) -> List[tuple]:
    """Merge (start, end) intervals; overlapping or touching ones join."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_name(name: str) -> str:
    """``%fusion.4 = f32[256]{0} fusion(...), kind=...`` -> ``fusion.4``."""
    m = OP_NAME.match(name)
    return m.group(1) if m else name[:80]


def self_times(ops) -> dict:
    """{op name: seconds of its events less their nested events}."""
    out, stack = {}, []
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= e.start:
            stack.pop()
        name, d = op_name(e.name), e.end - e.start
        out[name] = out.get(name, 0) + d
        if stack:
            out[stack[-1][1]] -= d
        stack.append((e.end, name))
    return {k: v / 1e9 for k, v in out.items()}


def module_program(name: str) -> str:
    """``jit_collect_traj(12)`` -> ``collect_traj``."""
    m = MODULE_NAME.match(name)
    return m.group(1) if m else name


class Chip(NamedTuple):
    index: int
    ops: List[Event]
    modules: List[Event]

    def busy(self) -> List[tuple]:
        return union((e.start, e.end) for e in self.ops)


class Reduced:
    def __init__(self, chips: List[Chip], host: List[Event]):
        self.chips, self.host = chips, host

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(sum(e - s for s, e in c.busy()) for c in self.chips
                   ) / 1e9 / len(self.chips)

    def program_s(self, program: str) -> float:
        return sum(e.end - e.start for c in self.chips for e in c.modules
                   if module_program(e.name) == program) / 1e9 / len(self.chips)

    def idle_gaps(self) -> List[tuple]:
        """(label, seconds) of every gap between busy intervals, all chips,
        longest first."""
        gaps = []
        for c in self.chips:
            b = c.busy()
            for (_, e0), (s1, _) in zip(b, b[1:]):
                gaps.append((self.host_label((e0 + s1) // 2), (s1 - e0) / 1e9))
        return sorted(gaps, key=lambda g: -g[1])

    def host_label(self, t: int) -> str:
        cover = [e for e in self.host if e.start <= t < e.end]
        if not cover:
            return "host idle or untraced"
        return min(cover, key=lambda e: e.end - e.start).name

    def breakdown(self, top: int = 10) -> dict:
        """The operations with the most self time (seconds, mean over the
        chips) and the longest idle gaps."""
        per = {}
        for c in self.chips:
            for name, secs in self_times(c.ops).items():
                per[name] = per.get(name, 0.0) + secs / len(self.chips)
        ops = sorted(((n, s) for n, s in per.items() if s > 0),
                     key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]]}


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]


def reduce(profile, chips: int) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``; the first ``chips``
    TPU planes are the cell's."""
    devs, host = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devs.append(Chip(int(m.group(1)),
                             _events(lines[OPS_LINE]) if OPS_LINE in lines
                             else [],
                             _events(lines[MODULES_LINE])
                             if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    devs = sorted(devs, key=lambda c: c.index)[:chips]
    if not devs:
        raise ValueError("the trace holds no TPU plane")
    return Reduced(devs, host)


def load(path: Path):
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


def reduce_dir(trace_dir: Path, chips: int, keep_in: Path = None) -> Reduced:
    """Reduce the one trace written under ``trace_dir``, then delete it
    (after copying it to ``keep_in`` when given)."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = reduce(load(files[-1]), chips)
    if keep_in is not None:
        Path(keep_in).mkdir(parents=True, exist_ok=True)
        shutil.copy(files[-1], Path(keep_in) / files[-1].name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def describe(path: Path, top: int = 8) -> str:
    """Planes, lines, event counts and the most common event names."""
    out = []
    for plane in load(path).planes:
        out.append(f"plane {plane.name!r}")
        for ln in plane.lines:
            names = {}
            n = 0
            for e in ln.events:
                n += 1
                names[e.name] = names.get(e.name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"  line {ln.name!r}: {n} events; {common}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(Path(sys.argv[1])))
