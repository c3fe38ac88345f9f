"""Golden physics regression: pin the solver's Re=100 shedding physics.

The checked-in reference (``tests/golden/cyl_re100_res8.npz``, produced by
``tools/gen_golden.py``) stores a developed uncontrolled flow state plus the
Strouhal number, mean C_D and C_L oscillation amplitude measured over a
fixed window.  The test restarts the solver from that state, re-measures the
same window, and compares within tight tolerances — so any solver/kernel
change that shifts the physics (discretization, penalization, projection,
Poisson convergence) fails loudly instead of silently corrupting training.

If a physics change is INTENTIONAL, regenerate with
``PYTHONPATH=src python tools/gen_golden.py`` and commit the new npz with
the old -> new numbers in the message (see README).
"""
from pathlib import Path

import numpy as np
import pytest

from repro.cfd import solver
from repro.cfd.grid import GridConfig
from repro.cfd.validation import (TOL_AMP, TOL_CD, TOL_ST, measure_shedding,
                                  run_uncontrolled)

GOLDEN = Path(__file__).parent / "golden" / "cyl_re100_res8.npz"


@pytest.fixture(scope="module")
def remeasured():
    ref = np.load(GOLDEN)
    cfg = GridConfig(res=int(ref["res"]), dt=float(ref["dt"]),
                     poisson_iters=int(ref["poisson_iters"]))
    state = solver.FlowState(u=ref["u"], v=ref["v"], p=ref["p"])
    _, cds, cls = run_uncontrolled(cfg, state, int(ref["meas_steps"]))
    return ref, measure_shedding(cds, cls, cfg.dt), cds, cls


def test_strouhal_number(remeasured):
    ref, stats, _, _ = remeasured
    assert stats["strouhal"] == pytest.approx(float(ref["strouhal"]),
                                              rel=TOL_ST)


def test_mean_drag_coefficient(remeasured):
    ref, stats, _, _ = remeasured
    assert stats["cd_mean"] == pytest.approx(float(ref["cd_mean"]),
                                             rel=TOL_CD)


def test_lift_oscillation_amplitude(remeasured):
    ref, stats, _, _ = remeasured
    assert stats["cl_amp"] == pytest.approx(float(ref["cl_amp"]),
                                            rel=TOL_AMP)


def test_shedding_is_developed(remeasured):
    """The reference window must contain genuine periodic shedding — guards
    against a silently-decayed golden state after a regeneration."""
    _, stats, cds, cls = remeasured
    assert stats["n_periods"] >= 3
    assert stats["cl_amp"] > 0.1            # oscillating, not steady
    assert np.isfinite(cds).all() and np.isfinite(cls).all()
    # coarse-IB confined-cylinder ballpark (Schäfer: CD~3.2, St~0.30)
    assert 2.5 < stats["cd_mean"] < 6.0
    assert 0.15 < stats["strouhal"] < 0.40
