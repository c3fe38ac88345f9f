"""Shared physics-validation helpers: golden-reference measurement.

Used by ``tools/gen_golden.py`` (writes the checked-in reference) and
``tests/test_golden_physics.py`` (re-measures and compares) so both sides
compute Strouhal / mean C_D / C_L amplitude with byte-identical code.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cfd import solver
from repro.cfd.grid import GridConfig, build_geometry

# Relative tolerances of a golden re-measurement (tests/test_golden_physics.py
# and chip_smoke.py).  On the generating platform the re-measurement is
# bit-exact (0.0% on all three), so the slack only needs to cover
# cross-platform float drift over the ~1600-step window of a stable limit
# cycle.  Measured mutation sensitivities (development, restart window):
#   upwind_blend 0.2->0.25:  St -1.6%          -> caught by TOL_ST
#   upwind_blend 0.2->0.3:   St -3.0%, amp +2% -> caught by TOL_ST
#   effective Re off by 10%: amp +9.6%         -> caught by TOL_AMP
TOL_ST = 0.015
TOL_CD = 0.01
TOL_AMP = 0.05
TOL = {"strouhal": TOL_ST, "cd_mean": TOL_CD, "cl_amp": TOL_AMP}
# The fluidic pinball's golden (tests/test_golden_pinball.py, chip_smoke.py),
# calibrated on its own re-measure window:
#   upwind_blend 0.2->0.25:  CD +1.2%, amp -11.8%      -> cd_mean / cl_amp
#   upwind_blend 0.2->0.3:   St -1.7%, CD +2.3%        -> strouhal / cd_mean
#   effective Re off by 10%: CD -1.8%, amp +12.7%      -> cd_mean / cl_amp
# (penal_eta x2 moves nothing above 0.6%: penalization stiffness is not a
# physics knob at this resolution)
PINBALL_TOL = {"strouhal": 0.015, "cd_mean": 0.01, "cl_amp": 0.06}


def run_uncontrolled(cfg: GridConfig, state: solver.FlowState, n: int,
                     *, backend: str = None, mesh=None,
                     geometry: str = "cylinder"
                     ) -> Tuple[solver.FlowState, np.ndarray, np.ndarray]:
    """Advance ``n`` uncontrolled (jet_vel = 0) steps; returns (state, cds,
    cls) with force-coefficient time series as numpy arrays.

    ``backend``/``mesh`` select the Poisson backend (see ``cfd.poisson``),
    so the golden physics window can be re-measured through e.g. the
    ``"halo"`` domain-decomposed path; the window is one
    ``solver.step_interval``, so ``"fused"`` runs the fused interval (the
    megakernel on TPU).  ``geometry`` picks the obstacle set
    (``grid.GEOMETRIES``); forces are the total over all bodies, which is
    what the golden fixtures pin."""
    geom_arrays = solver.geom_to_arrays(build_geometry(cfg, geometry))
    state, out = jax.jit(lambda s: solver.step_interval(
        cfg, geom_arrays, s, jnp.float32(0.0), n, backend=backend,
        mesh=mesh))(state)
    return state, np.asarray(out.cd), np.asarray(out.cl)


def measure_shedding(cds: np.ndarray, cls: np.ndarray, dt: float
                     ) -> Dict[str, float]:
    """Vortex-shedding metrics over a developed window.

    Strouhal from the mean upward-zero-crossing period of the mean-removed
    C_L signal (sub-step resolution via linear interpolation); St = f D / U
    with D = U_mean = 1 in our nondimensionalization.
    """
    cl = cls - cls.mean()
    sgn = cl > 0
    idx = np.flatnonzero(~sgn[:-1] & sgn[1:])
    if len(idx) < 3:
        raise ValueError("window too short: fewer than 3 C_L zero crossings "
                         "(no developed shedding?)")
    t_cross = idx + cl[idx] / (cl[idx] - cl[idx + 1])
    period = float(np.diff(t_cross).mean()) * dt
    return {
        "strouhal": 1.0 / period,
        "cd_mean": float(cds.mean()),
        "cl_amp": float(0.5 * (cls.max() - cls.min())),
        "n_periods": float(len(idx) - 1),
    }
