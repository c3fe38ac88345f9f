"""Idle attribution to the program's ``repro/`` spans (``bench/spans.py``)
and the five metrics that read it, on hand-built traces with known gaps."""
import random
from pathlib import Path

import pytest

from bench import spans, trace_reduce as tr
from bench.metrics import (dispatch_idle_ms_per_ep, host_syncs_per_ep,
                           io_idle_ms_per_ep, sync_idle_ms_per_ep,
                           unspanned_idle_pct)

MS = 1_000_000
READERS = (sync_idle_ms_per_ep, dispatch_idle_ms_per_ep, io_idle_ms_per_ep,
           host_syncs_per_ep, unspanned_idle_pct)
SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


def _ev(name, start_ms, end_ms):
    return tr.Event(name, int(start_ms * MS), int(end_ms * MS))


def _chip(busy_ms, index=0):
    return tr.Chip(index, [_ev(f"fusion.{i}", s, e)
                           for i, (s, e) in enumerate(busy_ms)], [])


# Device busy [0,12] [22,41] [55,72] [78,88] [93,100] ms; one complete
# episode [5,95] and one cut by the trace's end [96,105].  Idle inside the
# complete episode: 12-22 under collect; 41-50 under sync, 50-55 under
# update (one gap, two spans); 72-75 under caller, 75-78 under the sync
# nested in it; 88-89 under io.ckpt nested in caller, 89-90 under caller,
# 90-93 under no span.
BUSY = [(0, 12), (22, 41), (55, 72), (78, 88), (93, 100)]
HOST = [_ev("repro/episode", 5, 95), _ev("repro/collect", 10, 25),
        _ev("repro/sync", 40, 50), _ev("repro/update", 50, 60),
        _ev("repro/caller", 70, 90), _ev("repro/sync", 75, 80),
        _ev("repro/io.ckpt", 86, 89),
        _ev("repro/episode", 96, 105), _ev("repro/sync", 97, 99),
        _ev("PjitFunction(collect_traj)", 11, 12)]


def _ctx(chips, host):
    return {"trace": tr.Reduced(chips, host)}


def test_innermost_span_takes_the_idle():
    a = spans.attribute(tr.Reduced([_chip(BUSY)], HOST))
    assert a.episodes == 1
    assert a.idle_s == pytest.approx({
        "collect": 0.010, "sync": 0.012, "update": 0.005, "caller": 0.004,
        "io.ckpt": 0.001, "unspanned": 0.003})


def test_metrics_read_the_attribution():
    ctx = _ctx([_chip(BUSY)], HOST)
    assert sync_idle_ms_per_ep.read(ctx) == pytest.approx(12.0)
    assert dispatch_idle_ms_per_ep.read(ctx) == pytest.approx(15.0)
    assert io_idle_ms_per_ep.read(ctx) == pytest.approx(1.0)
    assert host_syncs_per_ep.read(ctx) == 2
    assert unspanned_idle_pct.read(ctx) == pytest.approx(100 * 3 / 35)


def test_gap_split_across_two_spans():
    host = [_ev("repro/episode", 0, 100), _ev("repro/sync", 10, 30),
            _ev("repro/update", 30, 60)]
    a = spans.attribute(tr.Reduced([_chip([(0, 20), (50, 100)])], host))
    assert a.idle_s == pytest.approx({"sync": 0.010, "update": 0.020,
                                      "unspanned": 0.0})


def test_cut_episode_left_out():
    """The episode reaching past the trace's last event, and the sync span
    inside it, count nowhere."""
    a = spans.attribute(tr.Reduced([_chip(BUSY)], HOST))
    assert a.counts["sync"] == 2
    host = [e for e in HOST if e.start < 95 * MS]
    assert a == spans.attribute(tr.Reduced([_chip(BUSY)], host))
    started_early = [_ev("repro/episode", -3, 50), _ev("repro/sync", 20, 30)]
    assert spans.attribute(tr.Reduced([_chip(BUSY)], started_early)) is None


@pytest.mark.parametrize("host", [[], [_ev("PjitFunction(update)", 1, 2)],
                                  [_ev("repro/episode", 96, 105)]])
def test_no_complete_episode_reads_none(host):
    ctx = _ctx([_chip(BUSY)], host)
    assert all(r.read(ctx) is None for r in READERS)


def test_trace_of_a_program_without_spans_reads_none():
    ctx = {"trace": tr.reduce(tr.load(SMALL), chips=8)}
    assert all(r.read(ctx) is None for r in READERS)


def test_mean_over_chips():
    busy_all = [(0, 100)]
    a = spans.attribute(tr.Reduced([_chip(BUSY), _chip(busy_all, 1)], HOST))
    assert a.idle_s["sync"] == pytest.approx(0.006)


def _brute(busy_ms, host):
    """Per-millisecond attribution of the idle inside the episodes that
    lie within the other events' extent."""
    kids = [e for e in host if e.name != "repro/episode"]
    lo = min([s * MS for s, _ in busy_ms] + [k.start for k in kids])
    hi = max([e * MS for _, e in busy_ms] + [k.end for k in kids])
    eps = [e for e in host if e.name == "repro/episode"
           and lo <= e.start and e.end <= hi]
    out = {}
    for ep in eps:
        for t in range(ep.start // MS, ep.end // MS):
            if any(s <= t < e for s, e in busy_ms):
                continue
            cover = [k for k in kids if k.start <= t * MS and
                     (t + 1) * MS <= k.end]
            kind = (min(cover, key=lambda k: (k.end - k.start, -k.start))
                    .name[len("repro/"):] if cover else "unspanned")
            out[kind] = out.get(kind, 0) + 1e-3
    return out


def _random_trace(rng):
    busy, t = [], 0
    while t < 400:
        s = t + rng.randint(0, 8)
        e = s + rng.randint(1, 30)
        busy.append((s, min(e, 400)))
        t = e
    host, t = [], 0
    kinds = ["collect", "update", "sync", "io.sink", "io.interface", "caller"]
    while t < 380:
        s, e = t + rng.randint(0, 3), t + rng.randint(30, 90)
        e = min(e, 400)
        host.append(_ev("repro/episode", s, e))
        c = s
        while c < e - 2:
            a = c + rng.randint(0, 4)
            b = min(e, a + rng.randint(1, 20))
            host.append(_ev("repro/" + rng.choice(kinds), a, b))
            if b - a > 4:                     # a span nested in it
                host.append(_ev("repro/sync", a + 1, b - 1))
            c = b
        t = e
    return busy, host


@pytest.mark.parametrize("seed", range(6))
def test_attribution_matches_per_millisecond_count(seed):
    """Against a per-millisecond count, and the sum identity: the kinds'
    idle adds up to all the idle inside the complete episodes."""
    busy, host = _random_trace(random.Random(seed))
    a = spans.attribute(tr.Reduced([_chip(busy)], host))
    want = _brute(busy, host)
    assert a.idle_s == pytest.approx({"unspanned": 0.0, **want})
    total = sum(want.values())
    parts = (a.per_episode_ms("sync") + a.per_episode_ms("collect", "update")
             + a.per_episode_ms(*(k for k in a.idle_s if k.startswith("io.")))
             + a.per_episode_ms("caller", "unspanned"))
    assert parts * a.episodes / 1e3 == pytest.approx(total)
