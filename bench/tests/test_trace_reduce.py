"""Trace reduction: interval union, idle share, per-program attribution and
collective matching, on synthetic events and on a small trace recorded on
the chip (``bench/testdata/small.xplane.pb``, ``record_trace.py``)."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.metrics import (collective_ms_per_ep, device_idle_pct,
                           learner_ms_per_ep, rollout_ms_per_ep)

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


def _chip(ops, modules=()):
    return tr.Chip(0, [tr.Event(*o) for o in ops],
                   [tr.Event(*m) for m in modules])


def test_union_counts_overlap_once():
    assert tr.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 51)]) == [
        (0, 20), (30, 45), (50, 51)]
    assert tr.union([(0, 100), (10, 20)]) == [(0, 100)]


def test_idle_share_and_gaps():
    chip = _chip([("fusion.1", 0, 400_000_000), ("fusion.2", 300_000_000,
                  500_000_000), ("copy.3", 800_000_000, 900_000_000)])
    host = [tr.Event("train loop", 0, 1_000_000_000),
            tr.Event("block_until_ready", 600_000_000, 700_000_000)]
    red = tr.Reduced([chip], host)
    assert red.busy_s() == pytest.approx(0.6)
    ctx = {"trace": red, "window_s": 1.0}
    assert device_idle_pct.read(ctx) == pytest.approx(40.0)
    (label, secs), = red.idle_gaps()
    assert label == "block_until_ready" and secs == pytest.approx(0.3)


def test_programs_and_collectives_by_name():
    """Program time by module name; the learner's collectives by instruction
    name, only inside ``jit_update`` runs within complete episodes."""
    ms = 1_000_000
    chip = _chip([("all-reduce.7", 1 * ms, 3 * ms),
                  ("all-reduce-start.1", 4 * ms, 5 * ms),
                  ("fusion.12", 0, 9 * ms),
                  ("%fusion.13 = f32[8]{0} fusion(%all-reduce.7)",
                   6 * ms, 7 * ms),
                  ("collective-permute-done", 11 * ms, 12 * ms),
                  ("all-reduce.8", 42 * ms, 45 * ms)],
                 [("jit_collect_traj(12)", 10 * ms, 40 * ms),
                  ("jit_postprocess(3)", 0, 4 * ms),
                  ("jit_update(9)", 0, 6 * ms),
                  ("jit_update(9)", 41 * ms, 46 * ms),
                  ("jit_update_other(1)", 0, 99 * ms)])
    # one complete episode [0, 40 ms]; the one cut by the trace's end holds
    # the second update, whose all-reduce is left out
    host = [tr.Event("repro/episode", 0, 40 * ms),
            tr.Event("repro/episode", 41 * ms, 200 * ms)]
    red = tr.Reduced([chip, chip._replace(index=1)], host)
    ctx = {"trace": red, "episodes": 2}
    assert rollout_ms_per_ep.read(ctx) == pytest.approx(15.0)
    assert learner_ms_per_ep.read(ctx) == pytest.approx(7.5)
    assert collective_ms_per_ep.read(ctx) == pytest.approx(3.0)


def test_breakdown_names_ops_and_counts_self_time():
    loop = "%while.3 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, body=%b"
    chip = _chip([(loop, 0, 100), ("%fusion.1 = f32[8]{0} fusion(%p)", 10, 40),
                  ("%fusion.1 = f32[8]{0} fusion(%p)", 50, 70),
                  ("%copy.2 = f32[8]{0} copy(%q)", 200, 210)])
    ops = dict(tr.Reduced([chip], []).breakdown()["device_ops"])
    assert ops == pytest.approx({"while.3": 50e-9, "fusion.1": 50e-9,
                                 "copy.2": 10e-9})


def test_no_collectives_reads_nothing():
    red = tr.Reduced([_chip([("fusion.1", 0, 10)], [("jit_update(1)", 0, 10)])],
                     [tr.Event("repro/episode", 0, 10)])
    assert collective_ms_per_ep.read({"trace": red, "episodes": 1}) is None
    assert rollout_ms_per_ep.read({"trace": red, "episodes": 1}) is None


@pytest.fixture(scope="module")
def small():
    if not SMALL.exists():
        pytest.fail(f"missing recorded trace {SMALL}")
    return tr.reduce(tr.load(SMALL), chips=8)


def test_recorded_trace_programs(small):
    for name in ("collect_traj", "postprocess", "update"):
        assert small.program_s(name) > 0, name
    assert small.program_s("no_such_program") == 0


def test_recorded_trace_busy_is_the_union(small):
    for chip in small.chips:
        busy = chip.busy()
        assert all(s < e for s, e in busy)
        assert all(e0 <= s1 for (_, e0), (s1, _) in zip(busy, busy[1:]))
        total = sum(e.end - e.start for e in chip.ops)
        assert sum(e - s for s, e in busy) <= total
    assert 0 < small.busy_s()


def test_recorded_trace_breakdown(small):
    b = small.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 and " " not in n for n, s in b["device_ops"])
