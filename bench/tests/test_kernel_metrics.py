"""The pressure-solve kernels' calls and device time per complete episode
(``bench/kernel_ops.py``), on hand-built traces."""
import pytest

from bench import trace_reduce as tr
from bench.metrics import sor_kernel_calls_per_ep, sor_kernel_ms_per_ep

MS = 1_000_000


def _ev(name, start_ms, end_ms):
    return tr.Event(name, int(start_ms * MS), int(end_ms * MS))


def _ctx(chips, host):
    return {"trace": tr.Reduced(chips, host)}


# two complete episodes [0,50] and [50,100]; one cut by the trace's end
HOST = [_ev("repro/episode", 0, 50), _ev("repro/episode", 50, 100),
        _ev("repro/episode", 101, 200)]


def _chip(index=0, scale=1.0):
    ops = [_ev("fusion.3", 0, 1)]
    # 3 kernel calls in each complete episode, 2 ms each (times ``scale``)
    for t in (2, 10, 20, 52, 60, 70):
        ops.append(_ev(f"%poisson_rb_sor_batched.{t % 3 + 1} = (f32[34,64,"
                       f"128]) custom-call(...)", t, t + 2 * scale))
    # one before the first episode, one in the cut episode
    ops += [_ev("poisson_rb_sor_batched.1", -5, -3),
            _ev("poisson_rb_sor_batched.1", 110, 112)]
    # same prefix, another kernel
    ops.append(_ev("poisson_rb_sor_batched_v2.1", 30, 31))
    return tr.Chip(index, ops, [_ev("jit_collect_traj(1)", -6, 120)])


@pytest.mark.parametrize("kernel", ["batched", "packed"])
def test_counts_and_times_inside_complete_episodes(kernel):
    """``plan=None`` solves with ``poisson_rb_sor_batched``, the
    data-parallel plan with ``poisson_rb_sor_packed``."""
    chip = _chip()
    chip = chip._replace(ops=[e._replace(name=e.name.replace(
        "_batched", f"_{kernel}")) for e in chip.ops])
    ctx = _ctx([chip], HOST)
    assert sor_kernel_calls_per_ep.read(ctx) == pytest.approx(3.0)
    assert sor_kernel_ms_per_ep.read(ctx) == pytest.approx(6.0)


def test_averaged_over_chips():
    ctx = _ctx([_chip(0), _chip(1, scale=2.0)], HOST)
    assert sor_kernel_calls_per_ep.read(ctx) == pytest.approx(3.0)
    assert sor_kernel_ms_per_ep.read(ctx) == pytest.approx(9.0)


@pytest.mark.parametrize("case", ["no_kernel", "no_episode"])
def test_reads_none_without_kernel_or_episode(case):
    """The parent's program runs no such kernel; a trace with no complete
    episode has nothing to divide by."""
    chip = _chip()
    if case == "no_kernel":
        chip = tr.Chip(0, [e for e in chip.ops
                           if "rb_sor_batched." not in e.name], chip.modules)
        host = HOST
    else:
        host = [_ev("repro/episode", -10, 50)]
    ctx = _ctx([chip], host)
    assert sor_kernel_calls_per_ep.read(ctx) is None
    assert sor_kernel_ms_per_ep.read(ctx) is None
