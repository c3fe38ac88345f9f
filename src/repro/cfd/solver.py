"""Fractional-step (Chorin projection) incompressible Navier-Stokes on a
staggered MAC grid, with volume-penalization immersed-boundary cylinder and
synthetic-jet / rotary actuation.

u: (ny, nx+1) x-velocity at x-faces      v: (ny+1, nx) y-velocity at y-faces
p: (ny, nx)   pressure at cell centers

One ``step`` advances dt: upwind advection + central diffusion -> implicit
volume penalization (cylinder + actuators) -> projection -> force outputs.

Geometry is static (closed over); the Reynolds number and actuation mode can
be *traced* per call so heterogeneous scenario batches vmap into one program
(see ``repro.cfd.scenarios``).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cfd import poisson
from repro.cfd.grid import Geometry, GridConfig
from repro.core import backend as backend_mod

# once-per-shape fallback warning for vector jet_vel on backend="fused"
# (registered so tests/conftest.py resets it between tests)
_FUSED_VECTOR_WARNED = backend_mod.warn_once_cache()

# the per-body contractions are physics: on TPU a default-precision f32 dot
# runs in bf16 passes, which would round the actuation targets and forces
_F32 = jax.lax.Precision.HIGHEST


class FlowState(NamedTuple):
    u: jnp.ndarray
    v: jnp.ndarray
    p: jnp.ndarray


class GeomArrays(NamedTuple):
    """Static geometry fields as jnp arrays (closed over by env closures).

    These are shared by every scenario on the same grid; everything that
    varies per scenario (Re, actuation mode, probe layout) is traced data so
    mixed-scenario batches vmap into one program.

    The trailing per-body fields (``rotb_*`` per-body rotary targets,
    ``own_*`` nearest-body force-ownership partition; see ``grid.Geometry``)
    default to ``None`` so eleven-field constructions predating the
    multi-body layer keep working; they are only consumed on the vector
    (per-body) actuation branch of ``_momentum``."""
    chi_u: jnp.ndarray
    chi_v: jnp.ndarray
    jet_u: jnp.ndarray
    jet_v: jnp.ndarray
    jmask_u: jnp.ndarray
    jmask_v: jnp.ndarray
    rot_u: jnp.ndarray
    rot_v: jnp.ndarray
    rmask_u: jnp.ndarray
    rmask_v: jnp.ndarray
    inlet_u: jnp.ndarray
    rotb_u: jnp.ndarray = None    # (B, ny, nx+1)
    rotb_v: jnp.ndarray = None    # (B, ny+1, nx)
    own_u: jnp.ndarray = None     # (B, ny, nx+1)
    own_v: jnp.ndarray = None     # (B, ny+1, nx)


class StepOutputs(NamedTuple):
    cd: jnp.ndarray          # drag coefficient (scalar; (B,) per body when
    cl: jnp.ndarray          # the actuation amplitude is a per-body vector)


def init_state(cfg: GridConfig, geom: Geometry) -> FlowState:
    """Start from the inlet profile everywhere (impulsive start)."""
    u = jnp.broadcast_to(jnp.asarray(geom.inlet_u)[:, None],
                         (cfg.ny, cfg.nx + 1)).astype(jnp.float32)
    u = u * (1.0 - jnp.asarray(geom.chi_u, jnp.float32))
    v = jnp.zeros((cfg.ny + 1, cfg.nx), jnp.float32)
    p = jnp.zeros((cfg.ny, cfg.nx), jnp.float32)
    return FlowState(u, v, p)


# ---------------------------------------------------------------------------
# boundary conditions (ghost-cell padding)
# ---------------------------------------------------------------------------

def _iota(a, axis):
    return jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)


def _apply_bc_u(u, inlet_u):
    """In-array BCs for u: inlet Dirichlet, outlet zero-gradient.

    Written as static slices and iota masks under ``where`` (not ``.at[]``
    scatters or integer column indexing) so the same code lowers inside
    the Pallas megakernel, whose TPU lowering has neither."""
    col = _iota(u, 1)
    u = jnp.where(col == u.shape[1] - 1, u[:, -2:-1], u)
    return jnp.where(col == 0, jnp.reshape(inlet_u, (-1, 1)), u)


def _apply_bc_v(v):
    """Inlet v = 0, outlet zero-gradient, no-slip bottom and top walls."""
    col, row = _iota(v, 1), _iota(v, 0)
    v = jnp.where(col == v.shape[1] - 1, v[:, -2:-1], v)
    wall = (col == 0) | (row == 0) | (row == v.shape[0] - 1)
    return jnp.where(wall, 0.0, v)


@jax.named_scope("projection")
def _project(cfg: GridConfig, ga: GeomArrays, u_bc, v_bc, p):
    """Velocity correction ``u -= dt grad p`` on the interior faces, then the
    boundary conditions.  Shared by ``step`` and the fused interval body."""
    dt = cfg.dt
    gx = u_bc[:, 1:-1] - dt * (p[:, 1:] - p[:, :-1]) / cfg.dx
    gy = v_bc[1:-1, :] - dt * (p[1:, :] - p[:-1, :]) / cfg.dy
    u_new = jnp.concatenate([u_bc[:, :1], gx, u_bc[:, -1:]], axis=1)
    v_new = jnp.concatenate([v_bc[:1, :], gy, v_bc[-1:, :]], axis=0)
    return _apply_bc_u(u_new, ga.inlet_u), _apply_bc_v(v_new)


def _pad_u(u):
    """Ghosts for stencils: walls no-slip (reflect), x handled in-array."""
    top = -u[:1, :]
    bot = -u[-1:, :]
    u = jnp.concatenate([top, u, bot], axis=0)          # (ny+2, nx+1)
    left = 2 * u[:, :1] - u[:, 1:2]                     # extrapolate inlet
    right = u[:, -1:]                                   # zero-gradient outlet
    return jnp.concatenate([left, u, right], axis=1)    # (ny+2, nx+3)


def _pad_v(v):
    top = v[-1:, :] * 0.0
    bot = v[:1, :] * 0.0
    v = jnp.concatenate([bot, v, top], axis=0)          # (ny+3, nx) walls
    left = -v[:, :1]                                    # inlet v=0 (reflect)
    right = v[:, -1:]                                   # outlet zero-gradient
    return jnp.concatenate([left, v, right], axis=1)    # (ny+3, nx+2)


# ---------------------------------------------------------------------------
# spatial operators
# ---------------------------------------------------------------------------

def _advect_diffuse_u(up, vp, cfg: GridConfig, re):
    """du/dt = -u du/dx - v du/dy + (1/Re) lap(u) at interior u-faces.

    ``up``/``vp`` are the padded fields from ``_pad_u``/``_pad_v`` — computed
    once per ``step`` and shared with ``_advect_diffuse_v``."""
    dx, dy = cfg.dx, cfg.dy
    uc = up[1:-1, 1:-1]                                  # == u
    # neighbors
    ul, ur = up[1:-1, :-2], up[1:-1, 2:]
    ub, ut = up[:-2, 1:-1], up[2:, 1:-1]
    # v interpolated to u-faces: average 4 surrounding v values
    # v faces adjacent to u face (j, i): v[j, i-1], v[j, i], v[j+1, i-1], v[j+1, i]
    v_at_u = 0.25 * (vp[1:-2, :-1] + vp[1:-2, 1:] + vp[2:-1, :-1] + vp[2:-1, 1:])
    # blended central/upwind advection (upwind share = cfg.upwind_blend)
    b = cfg.upwind_blend
    dudx_up = jnp.where(uc > 0, (uc - ul) / dx, (ur - uc) / dx)
    dudy_up = jnp.where(v_at_u > 0, (uc - ub) / dy, (ut - uc) / dy)
    dudx = b * dudx_up + (1 - b) * (ur - ul) / (2 * dx)
    dudy = b * dudy_up + (1 - b) * (ut - ub) / (2 * dy)
    adv = uc * dudx + v_at_u * dudy
    lap = (ul + ur - 2 * uc) / dx ** 2 + (ub + ut - 2 * uc) / dy ** 2
    return -adv + lap / re


def _advect_diffuse_v(up, vp, cfg: GridConfig, re):
    dx, dy = cfg.dx, cfg.dy
    vc = vp[1:-1, 1:-1]                                  # == v
    vl, vr = vp[1:-1, :-2], vp[1:-1, 2:]
    vb, vt = vp[:-2, 1:-1], vp[2:, 1:-1]
    # u interpolated to v-faces (j, i): u[j-1, i], u[j-1, i+1], u[j, i], u[j, i+1]
    u_at_v = 0.25 * (up[:-1, 1:-2] + up[:-1, 2:-1] + up[1:, 1:-2] + up[1:, 2:-1])
    b = cfg.upwind_blend
    dvdx_up = jnp.where(u_at_v > 0, (vc - vl) / dx, (vr - vc) / dx)
    dvdy_up = jnp.where(vc > 0, (vc - vb) / dy, (vt - vc) / dy)
    dvdx = b * dvdx_up + (1 - b) * (vr - vl) / (2 * dx)
    dvdy = b * dvdy_up + (1 - b) * (vt - vb) / (2 * dy)
    adv = u_at_v * dvdx + vc * dvdy
    lap = (vl + vr - 2 * vc) / dx ** 2 + (vb + vt - 2 * vc) / dy ** 2
    return -adv + lap / re


def divergence(u, v, cfg: GridConfig):
    return ((u[:, 1:] - u[:, :-1]) / cfg.dx
            + (v[1:, :] - v[:-1, :]) / cfg.dy)


# ---------------------------------------------------------------------------
# one time step
# ---------------------------------------------------------------------------

@jax.named_scope("momentum")
def _momentum(cfg: GridConfig, ga: GeomArrays, u, v, jet_vel, re, act_mode):
    """The momentum half of one dt: explicit advect-diffuse predictor,
    implicit volume penalization, and the fused BC/outlet-mass-correction
    pass.  Returns ``(u_bc, v_bc, fx, fy)`` — the BC'd intermediate fields
    the projection acts on, plus the body force (reaction) components.

    This is the single momentum implementation: ``step`` and the fused
    actuation-interval path (``repro.kernels.actuation``) both call it, so
    the megakernel can never drift from the per-step solver.

    Contract (pinned by tests/test_cfd.py): the body force is the momentum
    the penalization removed, measured against the *predictor* ``u_star``
    BEFORE boundary conditions are applied — the post-BC fields are
    deliberately separate names (``u_bc``/``v_bc``) so a refactor cannot
    silently change ``fx``/``fy``.

    ``jet_vel`` is either the historical scalar amplitude (both scalar
    branches below are byte-identical to the pre-multi-body solver) or a
    per-body ``(A,)`` vector of rotary surface speeds (``A >=`` the
    geometry's body count; extra padded slots are inert because the padded
    ``rotb_*`` planes are zero).  On the vector branch ``fx``/``fy`` come
    back per body ``(B,)``, split by the nearest-body ownership partition —
    their sum equals the global reaction force up to summation order.
    """
    chi_u, chi_v, inlet_u = ga.chi_u, ga.chi_v, ga.inlet_u
    dt = cfg.dt
    # 1. advection-diffusion (explicit Euler).  The padded fields are shared
    # by both momentum updates (each previously re-padded both u and v).
    up, vp = _pad_u(u), _pad_v(v)
    u_star = u + dt * _advect_diffuse_u(up, vp, cfg, re)
    v_star = v + dt * _advect_diffuse_v(up, vp, cfg, re)

    # 2. immersed boundary: implicit volume penalization toward target.
    # Penalization acts on the solid (target 0) AND the actuation band
    # (target = actuation velocity): C = max(chi, band mask).
    lam = dt / cfg.penal_eta
    jet_tgt_u = ga.jet_u[0] - ga.jet_u[1]
    jet_tgt_v = ga.jet_v[0] - ga.jet_v[1]
    per_body = jnp.ndim(jet_vel) > 0          # static: part of the trace
    if act_mode is None:                      # static jets-only path
        tgt_u = jet_vel * jet_tgt_u
        tgt_v = jet_vel * jet_tgt_v
        pen_u = jnp.maximum(chi_u, ga.jmask_u)
        pen_v = jnp.maximum(chi_v, ga.jmask_v)
    elif not per_body:                        # per-scenario traced blend
        m = act_mode
        tgt_u = jet_vel * ((1 - m) * jet_tgt_u + m * ga.rot_u)
        tgt_v = jet_vel * ((1 - m) * jet_tgt_v + m * ga.rot_v)
        pen_u = jnp.maximum(chi_u, (1 - m) * ga.jmask_u + m * ga.rmask_u)
        pen_v = jnp.maximum(chi_v, (1 - m) * ga.jmask_v + m * ga.rmask_v)
    else:                                     # per-body vector actuation
        if ga.rotb_u is None:
            raise ValueError(
                "per-body (vector) jet_vel needs the per-body geometry "
                "fields (rotb_*/own_*); rebuild GeomArrays via "
                "geom_to_arrays(build_geometry(cfg, geometry))")
        nb = ga.rotb_u.shape[0]
        av = jnp.asarray(jet_vel)
        if av.shape[0] < nb:                  # static pad to the body count
            av = jnp.pad(av, (0, nb - av.shape[0]))
        # slot 0 doubles as the jet amplitude so a jets-mode scenario rides
        # the same vector program inside a mixed multi-body batch
        a0 = av[0]
        m = act_mode
        rot_t_u = jnp.einsum("b,byx->yx", av[:nb], ga.rotb_u, precision=_F32)
        rot_t_v = jnp.einsum("b,byx->yx", av[:nb], ga.rotb_v, precision=_F32)
        tgt_u = (1 - m) * a0 * jet_tgt_u + m * rot_t_u
        tgt_v = (1 - m) * a0 * jet_tgt_v + m * rot_t_v
        pen_u = jnp.maximum(chi_u, (1 - m) * ga.jmask_u + m * ga.rmask_u)
        pen_v = jnp.maximum(chi_v, (1 - m) * ga.jmask_v + m * ga.rmask_v)
    u_pen = (u_star + lam * pen_u * tgt_u) / (1 + lam * pen_u)
    v_pen = (v_star + lam * pen_v * tgt_v) / (1 + lam * pen_v)
    # momentum exchange -> force on the body (reaction), per unit density —
    # measured from the PREDICTOR u_star/v_star, before BCs touch the fields
    with jax.named_scope("forces"):
        if per_body:
            fx = -jnp.einsum("byx,yx->b", ga.own_u, (u_pen - u_star) / dt,
                             precision=_F32) * cfg.dx * cfg.dy
            fy = -jnp.einsum("byx,yx->b", ga.own_v, (v_pen - v_star) / dt,
                             precision=_F32) * cfg.dx * cfg.dy
        else:
            fx = -jnp.sum((u_pen - u_star) / dt) * cfg.dx * cfg.dy
            fy = -jnp.sum((v_pen - v_star) / dt) * cfg.dx * cfg.dy

    # 3. boundary conditions + global outlet mass correction, fused into one
    # pass over each field: the inlet BC pins column 0 to inlet_u (so the
    # influx is just its sum), the outlet BC copies column -2, and the mass
    # correction shifts that same column — one scatter chain per field
    # instead of penalize -> BC -> correct as three.
    influx = jnp.sum(inlet_u) * cfg.dy
    outflux = jnp.sum(u_pen[:, -2:-1]) * cfg.dy
    out_col = u_pen[:, -2:-1] + (influx - outflux) / (cfg.ny * cfg.dy)
    u_bc = jnp.concatenate([jnp.reshape(inlet_u, (-1, 1)), u_pen[:, 1:-1],
                            out_col], axis=1)
    v_bc = _apply_bc_v(v_pen)
    return u_bc, v_bc, fx, fy


@functools.partial(jax.jit, static_argnames=("cfg", "backend", "use_pallas",
                                             "mesh", "halo_inner"))
def step(cfg: GridConfig, geom_arrays: GeomArrays, state: FlowState, jet_vel,
         *, re=None, act_mode=None, backend: Optional[str] = None,
         use_pallas: Optional[bool] = None, mesh=None, halo_inner: int = 1
         ) -> Tuple[FlowState, StepOutputs]:
    """Advance one dt.

    jet_vel: scalar actuation amplitude — jet velocity (jet1 = +, jet2 = -)
    in jet mode, cylinder surface speed in rotary mode.
    re: Reynolds number; traced (per-env scenario data) when given, else the
    static ``cfg.re``.
    act_mode: actuation blend in [0, 1] — 0 = synthetic jets, 1 = rotary
    cylinder control; traced when given, else jets.  Intermediate values
    blend the two target fields (only 0/1 are physical scenarios).
    backend: Poisson backend ("reference" | "packed" | "full" | "pallas" |
    "halo" | "fused"); "reference" (the default) runs the packed-checkerboard
    sweep on even-width grids and the full-grid oracle otherwise; "halo"
    needs ``mesh`` and runs the pressure solve as explicit x-slabs with
    ppermute halo exchange over the mesh "model" axis — the paper's
    N_ranks > 1 spatial decomposition; "fused" only changes behaviour at
    the interval level (``step_interval``) and solves a single step with
    the reference sweep.  ``use_pallas`` is a deprecated alias.
    halo_inner: local sweeps per halo exchange on the "halo" backend.  The
    default 1 exchanges the updated parity before every colored half-sweep
    (half-width messages — the MPI-per-iteration pattern whose cost the
    paper's Fig. 7 measures — making the decomposed iteration exactly the
    monolithic sweep); looser coupling leaves slab-boundary pressure error
    that the projection feedback amplifies over hundreds of steps.
    """
    backend = poisson.resolve_backend(backend, use_pallas)
    ga = GeomArrays(*geom_arrays)
    dt = cfg.dt
    if re is None:
        re = cfg.re

    u, v, p = state
    # 1-3. momentum: predictor + penalization (+ forces) + BC/mass pass
    u_bc, v_bc, fx, fy = _momentum(cfg, ga, u, v, jet_vel, re, act_mode)

    # 4. projection ("fused" fuses at the interval level — step_interval —
    # so a single step solves with the reference sweep)
    rhs = divergence(u_bc, v_bc, cfg) / dt
    p = poisson.solve(rhs, cfg.dx, cfg.dy, iters=cfg.poisson_iters,
                      omega=cfg.poisson_omega, p0=p,
                      backend="reference" if backend == "fused" else backend,
                      mesh=mesh, halo_inner=halo_inner)
    u_new, v_new = _project(cfg, ga, u_bc, v_bc, p)

    # force coefficients: 0.5 * rho * Ubar^2 * D = 0.5
    cd = fx / (0.5 * cfg.u_mean ** 2)
    cl = fy / (0.5 * cfg.u_mean ** 2)
    return FlowState(u_new, v_new, p), StepOutputs(cd=cd, cl=cl)


def step_interval(cfg: GridConfig, geom_arrays: GeomArrays, state: FlowState,
                  jet_vel, n_steps: int, *, re=None, act_mode=None,
                  backend: Optional[str] = None,
                  use_pallas: Optional[bool] = None, mesh=None,
                  halo_inner: int = 1) -> Tuple[FlowState, StepOutputs]:
    """Advance ``n_steps`` dt under one held actuation amplitude — one
    actuation interval, the unit the DRL environment integrates between
    agent actions.

    Returns ``(FlowState, StepOutputs)`` with per-dt ``(n_steps,)`` force
    coefficient arrays.

    ``backend="fused"`` runs the interval through
    ``repro.kernels.actuation``: the velocity fields and both packed
    pressure parity planes are carried across the whole interval (no per-dt
    pack/unpack round-trips), with the per-dt fused body executing as a
    VMEM-resident Pallas megakernel on TPU and as one fused XLA scan body
    elsewhere.  Grids the fused path cannot serve (odd width, or exceeding
    the TPU VMEM budget) fall back to the reference scan with a
    once-per-shape warning.  Every other backend scans :func:`step`.
    """
    backend = poisson.resolve_backend(backend, use_pallas)
    if backend == "fused":
        if jnp.ndim(jet_vel) > 0:
            # The megakernel's penalization body is scalar-actuation only;
            # multi-body vector amplitudes take the reference scan.
            key = ("fused_vector_jet", int(jet_vel.shape[0]))
            if key not in _FUSED_VECTOR_WARNED:
                _FUSED_VECTOR_WARNED.add(key)
                warnings.warn(
                    "backend='fused' does not support per-body (vector) "
                    "jet_vel; falling back to the reference interval scan",
                    RuntimeWarning, stacklevel=2)
            backend = "reference"
        else:
            from repro.kernels.actuation import ops as actuation_ops
            return actuation_ops.fused_interval(cfg, geom_arrays, state,
                                                jet_vel, n_steps, re=re,
                                                act_mode=act_mode)

    def body(flow, _):
        return step(cfg, geom_arrays, flow, jet_vel, re=re,
                    act_mode=act_mode, backend=backend, mesh=mesh,
                    halo_inner=halo_inner)

    return jax.lax.scan(body, state, None, length=n_steps)


def geom_to_arrays(geom: Geometry) -> GeomArrays:
    """Static geometry as a pytree of jnp arrays (closed over, never traced)."""
    as32 = lambda a: jnp.asarray(a, jnp.float32)
    opt = lambda a: None if a is None else as32(a)
    return GeomArrays(chi_u=as32(geom.chi_u), chi_v=as32(geom.chi_v),
                      jet_u=as32(geom.jet_u), jet_v=as32(geom.jet_v),
                      jmask_u=as32(geom.jmask_u), jmask_v=as32(geom.jmask_v),
                      rot_u=as32(geom.rot_u), rot_v=as32(geom.rot_v),
                      rmask_u=as32(geom.rmask_u), rmask_v=as32(geom.rmask_v),
                      inlet_u=as32(geom.inlet_u),
                      rotb_u=opt(getattr(geom, "rotb_u", None)),
                      rotb_v=opt(getattr(geom, "rotb_v", None)),
                      own_u=opt(getattr(geom, "own_u", None)),
                      own_v=opt(getattr(geom, "own_v", None)))
