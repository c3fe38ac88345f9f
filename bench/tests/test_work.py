"""The work counts of ``bench/work`` for the paper cell, by hand, and the
bound they put on every share of a peak."""
import pytest

from bench import harness, work
from bench.metrics import mfu, rollout_roofline_pct

NY, NX = 34, 176                         # res 8: 4.1 x 8 rounded up to even


@pytest.fixture(scope="module")
def cell():
    _, cfg, traffic = harness.load_cell("cyl_re100_jets.paper")
    return cfg, traffic


def test_solver_dt_by_hand(cell):
    from bench.work import sor_solver
    cfg, _ = cell
    assert sor_solver.grid(cfg) == (NY, NX)
    faces = NY * (NX + 1) + (NY + 1) * NX            # u faces + v faces
    per_face = 30 + 7 + 2 + 3     # advect-diffuse, penalise, force, project
    poisson = NY * NX * (40 * 9 + 10 * 6)   # 40 relaxed + 10 polish pairs
    assert sor_solver.dt_flops(cfg) == faces * per_face + NY * NX * 5 + poisson
    # each point of the pressure grid is updated once per pair, in its least
    # form; the packed sweep the program runs spends 10 per update
    assert poisson < NY * NX * 50 * 10


def test_episode_by_hand(cell):
    cfg, traffic = cell
    w = work.episode_work(cfg, traffic, 60)
    from bench.work import sor_solver
    mlp = (2 * 149 * 512 + 512) + (2 * 512 * 512 + 512)
    actor, critic = mlp + 2 * 512 + 1, mlp + 2 * 512 + 1
    steps = 60 * 40
    assert w["rollout"]["flops"] == steps * (25 * sor_solver.dt_flops(cfg)
                                             + 149 * 9 + actor)
    params = 2 * (149 * 512 + 512 + 512 * 512 + 512) + (512 + 1) + (512 + 1) + 1
    assert w["learner"]["flops"] == (6 * steps * 3 * (actor + critic)
                                     + (steps + 60) * critic + steps * 6
                                     + 6 * 4 * params * 12)
    # least bytes: every input read once (start state and its reset copy of
    # every env, the geometry, the weights) and the trajectory written once
    state = 4 * (NY * (NX + 1) + (NY + 1) * NX + NY * NX)
    assert w["rollout"]["bytes"] >= 60 * 2 * state
    assert w["rollout"]["bytes"] < 60 * 2 * state * 2


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def program_s(self, name):
        return self.seconds

    def busy_s(self):
        return self.seconds


@pytest.mark.parametrize("slack", [1.0, 1.5, 1e3])
def test_no_time_at_or_above_the_least_reads_over_100(cell, slack):
    cfg, traffic = cell
    w = work.episode_work(cfg, traffic, 60)
    pk = work.peaks("TPU v5 lite")
    least_roll = work.least_seconds(w["rollout"], pk)
    least_ep = work.least_seconds(w["episode"], pk)
    ctx = {"work": w, "peaks": pk, "chips": 1, "episodes": 2}
    roll = rollout_roofline_pct.read(dict(ctx, trace=_Trace(
        2 * least_roll * slack), window_s=1.0))
    util = mfu.read(dict(ctx, trace=_Trace(1.0),
                         window_s=2 * least_ep * slack))
    assert 0 < roll <= 100.0 + 1e-9
    assert 0 < util <= 100.0 + 1e-9
    if slack == 1.0:
        assert roll == pytest.approx(100.0) and util == pytest.approx(100.0)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu")
