"""Fused actuation-interval path: ``backend="fused"`` for the env hot loop.

The DRL environment integrates ``steps_per_action`` (50) solver dt's per
agent action; the per-step solver executes each dt as ~10 separate XLA
computations with full-grid pack/unpack round-trips of the pressure field
between them, so dispatch and memory traffic — not FLOPs — bound env-steps/s
(the paper's core claim, and ROADMAP open item 2).  This module fuses the
whole interval:

- the velocity fields and BOTH packed pressure parity planes are the scan
  carry — packed once before the interval, unpacked once after it, never
  round-tripped per dt;
- one fused per-dt body (:func:`fused_dt`) chains momentum -> packed SOR
  projection -> velocity correction, reusing ``solver._momentum`` and
  ``poisson.packed_half_sweep``/``packed_ghost_rows`` so there is exactly
  one momentum and one stencil implementation in the repo;
- on TPU the per-dt body runs as a Pallas megakernel
  (``kernel.fused_step``) that keeps every field VMEM-resident for the
  whole dt; elsewhere the same body lowers as one fused XLA scan step.

Tier selection (:func:`select_tier`) falls back to the reference scan —
warning once per grid shape, resettable via
``core.backend.reset_warning_caches`` — when the grid width is odd (no
checkerboard parity) or the fields exceed the TPU VMEM budget
(``REPRO_FUSED_VMEM_BUDGET`` bytes, default 16 MiB).
"""
from __future__ import annotations

import functools
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.cfd import poisson, solver
from repro.cfd.grid import GridConfig
from repro.core import backend as backend_mod

# VMEM the megakernel may claim per core (TPU v5e has ~16 MiB; leave the
# default at the full budget — the estimate below already over-counts by
# including double-buffered outputs)
DEFAULT_VMEM_BUDGET = 16 * 2 ** 20
VMEM_BUDGET_ENV = "REPRO_FUSED_VMEM_BUDGET"

# grid shapes already warned about for the fused -> reference fallback
# (once per shape, resettable for test isolation)
_FALLBACK_WARNED = backend_mod.warn_once_cache()


def vmem_budget() -> int:
    return int(os.environ.get(VMEM_BUDGET_ENV, DEFAULT_VMEM_BUDGET))


def vmem_bytes(cfg: GridConfig) -> int:
    """f32 bytes the fused per-dt kernel keeps resident: u/v in+out, both
    pressure parity planes in+out, the packed rhs pair, and the closed-over
    geometry fields (6 u-shaped + 6 v-shaped + the inlet profile)."""
    nu = cfg.ny * (cfg.nx + 1)
    nv = (cfg.ny + 1) * cfg.nx
    plane = cfg.ny * (cfg.nx // 2)
    fields = 2 * nu + 2 * nv + 4 * plane + 2 * plane
    geom = 6 * nu + 6 * nv + cfg.ny
    return 4 * (fields + geom)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def select_tier(cfg: GridConfig) -> str:
    """Which realization serves ``backend="fused"`` on this grid/platform.

    "pallas"     TPU: the VMEM-resident per-dt megakernel under lax.scan
    "jnp"        everywhere else: the same fused per-dt body as one XLA
                 scan step (interval fusion and packed-plane carry intact —
                 Pallas only adds explicit VMEM residency on TPU)
    "reference"  fallback (warns once per grid shape): odd grid width, or
                 the fields exceed the TPU VMEM budget
    """
    ny, nx = cfg.ny, cfg.nx
    if nx % 2:
        if ("odd_nx", ny, nx) not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(("odd_nx", ny, nx))
            warnings.warn(
                f"backend='fused' needs an even grid width for packed "
                f"checkerboard parity; grid (ny={ny}, nx={nx}) falls back "
                f"to the reference scan (this warning fires once per shape)",
                RuntimeWarning, stacklevel=3)
        return "reference"
    if _on_tpu():
        need, have = vmem_bytes(cfg), vmem_budget()
        if need > have:
            if ("vmem", ny, nx) not in _FALLBACK_WARNED:
                _FALLBACK_WARNED.add(("vmem", ny, nx))
                warnings.warn(
                    f"backend='fused' grid (ny={ny}, nx={nx}) needs "
                    f"~{need / 2**20:.1f} MiB resident fields, over the "
                    f"{have / 2**20:.1f} MiB VMEM budget "
                    f"(${VMEM_BUDGET_ENV}); falling back to the reference "
                    f"scan (this warning fires once per shape)",
                    RuntimeWarning, stacklevel=3)
            return "reference"
        return "pallas"
    return "jnp"


# ---------------------------------------------------------------------------
# the fused per-dt body (shared by the jnp tier and the Pallas kernel)
# ---------------------------------------------------------------------------

@jax.named_scope("poisson")
def packed_projection_planes(cfg: GridConfig, red, black, rhs_r, rhs_b):
    """The pressure solve of one dt entirely on packed planes: the same
    omega schedule as ``poisson.solve`` (``polish`` trailing sweeps run
    unrelaxed), built from the shared ``packed_sweep_pair`` stencil."""
    iters = cfg.poisson_iters
    n_polish = min(10, iters // 2)
    n_sor = iters - n_polish
    omega = float(cfg.poisson_omega)
    row_odd = jax.lax.broadcasted_iota(jnp.int32, (cfg.ny, 1), 0) % 2 == 1

    def body(i, planes):
        om = jnp.where(i < n_sor, omega, 1.0)
        return poisson.packed_sweep_pair(*planes, rhs_r, rhs_b, om,
                                         dx=cfg.dx, dy=cfg.dy,
                                         row_odd=row_odd)

    return jax.lax.fori_loop(0, iters, body, (red, black))


def fused_dt(cfg: GridConfig, ga: solver.GeomArrays, u, v, red, black,
             jet_vel, re, act_mode, *, pack=poisson.pack_checkerboard,
             unpack=poisson.unpack_checkerboard):
    """One dt with the pressure held packed: momentum (via the solver's own
    ``_momentum`` — one implementation) -> packed SOR projection ->
    velocity correction.  Returns ``(u, v, red, black, cd, cl)``.

    ``pack``/``unpack`` convert the rhs and the pressure between the full
    grid and the parity planes; the megakernel passes a layout that Mosaic
    lowers (``kernel.pack_mxu``), which gives the same planes."""
    u_bc, v_bc, fx, fy = solver._momentum(cfg, ga, u, v, jet_vel, re,
                                          act_mode)
    rhs = solver.divergence(u_bc, v_bc, cfg) / cfg.dt
    rhs_r, rhs_b = pack(rhs)
    red, black = packed_projection_planes(cfg, red, black, rhs_r, rhs_b)
    # the projection gradient needs full-grid adjacency; the planes stay the
    # carry — this unpack is a reshape/select XLA fuses into the correction
    u_new, v_new = solver._project(cfg, ga, u_bc, v_bc, unpack(red, black))
    cd = fx / (0.5 * cfg.u_mean ** 2)
    cl = fy / (0.5 * cfg.u_mean ** 2)
    return u_new, v_new, red, black, cd, cl


# ---------------------------------------------------------------------------
# the interval
# ---------------------------------------------------------------------------

def fused_interval(cfg: GridConfig, geom_arrays, state: solver.FlowState,
                   jet_vel, n_steps: int, *, re=None, act_mode=None,
                   tier: Optional[str] = None):
    """One actuation interval with fields resident across every dt.

    Drop-in for the ``backend="fused"`` arm of ``solver.step_interval``:
    returns ``(FlowState, StepOutputs)`` with per-dt ``(n_steps,)`` force
    coefficients.  ``tier`` forces a realization ("pallas" | "jnp" |
    "reference") — tests pin pallas-vs-jnp parity through it; the default
    asks :func:`select_tier`.
    """
    ga = solver.GeomArrays(*geom_arrays)
    if re is None:
        re = cfg.re
    # act_mode=0.0 is numerically exact vs the static jets-only branch
    # ((1-0)*jet + 0*rot multiplies through exactly in f32), and keeps the
    # per-dt body a single signature for the Pallas kernel
    if act_mode is None:
        act_mode = jnp.float32(0.0)
    tier = tier or select_tier(cfg)
    if tier == "reference":
        return solver.step_interval(cfg, geom_arrays, state, jet_vel,
                                    n_steps, re=re, act_mode=act_mode,
                                    backend="reference")

    if tier == "pallas":
        from repro.kernels.actuation import kernel as kernel_mod
        dt_fn = functools.partial(kernel_mod.fused_step, cfg, ga,
                                  interpret=not _on_tpu())
    else:
        dt_fn = functools.partial(fused_dt, cfg, ga)

    red, black = poisson.pack_checkerboard(state.p)

    def body(carry, _):
        u, v, red, black = carry
        u, v, red, black, cd, cl = dt_fn(u, v, red, black, jet_vel, re,
                                         act_mode)
        return (u, v, red, black), solver.StepOutputs(cd=cd, cl=cl)

    (u, v, red, black), outs = jax.lax.scan(
        body, (state.u, state.v, red, black), None, length=n_steps)
    return solver.FlowState(u, v, poisson.unpack_checkerboard(red, black)), \
        outs
