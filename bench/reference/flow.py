"""Plain reference of the flow environment: fractional-step incompressible
Navier-Stokes on a staggered grid, volume-penalised bodies, jets or rotary
actuation, red-black SOR pressure solve on the full grid, bilinear pressure
probes and the reward of Rabault et al. (2019), eq. (12).

Written in straightforward ``jax.numpy`` over one environment and vmapped;
every array and constant takes the dtype it is given (float32 for the
reference, bfloat16 for the low-precision control).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import geometry as geo_mod

BETA = 0.4          # action smoothing, Rabault et al. eq. (11)
OMEGA_L = 0.1       # lift penalty of the reward, eq. (12)
VEL_LIMIT, DIV_LIMIT = 50.0, 1e3     # divergence sentinel of the trainer


class Physics(NamedTuple):
    """Per-environment data (each leaf gains a leading env axis)."""
    re: jnp.ndarray
    mode: jnp.ndarray        # 0 jets, 1 rotary
    cd0: jnp.ndarray
    probe_ij: jnp.ndarray    # (P, 2)
    probe_mask: jnp.ndarray  # (P,)
    geom: jnp.ndarray        # index into the geometry stack
    act_mask: jnp.ndarray    # (A,)


def geometry_stack(g: geo_mod.Grid, names, dtype) -> dict:
    per = [geo_mod.build(g, n) for n in names]
    return {k: jnp.asarray(np.stack([p[k] for p in per]), dtype)
            for k in per[0]}


def init_flow(g: geo_mod.Grid, geo: dict, dtype):
    u = jnp.broadcast_to(geo["inlet_u"][:, None], (g.ny, g.nx + 1))
    u = u * (1 - geo["chi_u"])
    return (u.astype(dtype), jnp.zeros((g.ny + 1, g.nx), dtype),
            jnp.zeros((g.ny, g.nx), dtype))


def _bc_u(u, inlet):
    u = u.at[:, -1].set(u[:, -2])
    return u.at[:, 0].set(inlet)


def _bc_v(v):
    v = v.at[:, -1].set(v[:, -2])
    return v.at[:, 0].set(0).at[0, :].set(0).at[-1, :].set(0)


def _pad_u(u):
    u = jnp.concatenate([-u[:1], u, -u[-1:]], axis=0)
    return jnp.concatenate([2 * u[:, :1] - u[:, 1:2], u, u[:, -1:]], axis=1)


def _pad_v(v):
    z = jnp.zeros_like(v[:1])
    v = jnp.concatenate([z, v, z], axis=0)
    return jnp.concatenate([-v[:, :1], v, v[:, -1:]], axis=1)


def _rhs_u(g, up, vp, re):
    c = up[1:-1, 1:-1]
    w, e, s, n = up[1:-1, :-2], up[1:-1, 2:], up[:-2, 1:-1], up[2:, 1:-1]
    va = 0.25 * (vp[1:-2, :-1] + vp[1:-2, 1:] + vp[2:-1, :-1] + vp[2:-1, 1:])
    return _advect(g, c, w, e, s, n, c, va, re)


def _rhs_v(g, up, vp, re):
    c = vp[1:-1, 1:-1]
    w, e, s, n = vp[1:-1, :-2], vp[1:-1, 2:], vp[:-2, 1:-1], vp[2:, 1:-1]
    ua = 0.25 * (up[:-1, 1:-2] + up[:-1, 2:-1] + up[1:, 1:-2] + up[1:, 2:-1])
    return _advect(g, c, w, e, s, n, ua, c, re)


def _advect(g, c, w, e, s, n, ux, uy, re):
    """-(ux d/dx + uy d/dy) c + lap(c) / Re, upwind share ``g.upwind``."""
    b, dx, dy = g.upwind, g.dx, g.dy
    ddx = (b * jnp.where(ux > 0, (c - w) / dx, (e - c) / dx)
           + (1 - b) * (e - w) / (2 * dx))
    ddy = (b * jnp.where(uy > 0, (c - s) / dy, (n - c) / dy)
           + (1 - b) * (n - s) / (2 * dy))
    lap = (w + e - 2 * c) / dx ** 2 + (s + n - 2 * c) / dy ** 2
    return -(ux * ddx + uy * ddy) + lap / re


def poisson(g, rhs, p):
    """``g.iters`` red-black pairs, the last ``g.polish`` unrelaxed."""
    jj, ii = np.meshgrid(np.arange(g.ny), np.arange(g.nx), indexing="ij")
    red = jnp.asarray((ii + jj) % 2 == 0)
    inv = 1.0 / (2.0 / g.dx ** 2 + 2.0 / g.dy ** 2)

    def sweep(p, mask, om):
        pp = jnp.concatenate([p[:, :1], p, -p[:, -1:]], axis=1)
        pp = jnp.concatenate([pp[:1], pp, pp[-1:]], axis=0)
        nb = ((pp[1:-1, :-2] + pp[1:-1, 2:]) / g.dx ** 2
              + (pp[:-2, 1:-1] + pp[2:, 1:-1]) / g.dy ** 2)
        return jnp.where(mask, (1 - om) * p + om * ((nb - rhs) * inv), p)

    def body(i, p):
        om = jnp.where(i < g.iters - g.polish, g.omega, 1.0).astype(p.dtype)
        return sweep(sweep(p, red, om), ~red, om)

    return jax.lax.fori_loop(0, g.iters, body, p)


def step(g, geo, flow, act, re, mode):
    """One dt.  ``act`` is a scalar amplitude or an (A,) per-body vector;
    returns the new flow and (cd, cl), per body for a vector."""
    u, v, p = flow
    dt = g.dt
    up, vp = _pad_u(u), _pad_v(v)
    us = u + dt * _rhs_u(g, up, vp, re)
    vs = v + dt * _rhs_v(g, up, vp, re)
    lam = dt / g.eta
    jet_u = geo["jet_u"][0] - geo["jet_u"][1]
    jet_v = geo["jet_v"][0] - geo["jet_v"][1]
    if jnp.ndim(act) == 0:
        tu = act * ((1 - mode) * jet_u + mode * geo["rot_u"])
        tv = act * ((1 - mode) * jet_v + mode * geo["rot_v"])
    else:
        a = jnp.pad(act, (0, geo["rotb_u"].shape[0] - act.shape[0]))
        ru = jnp.einsum("b,byx->yx", a, geo["rotb_u"],
                        precision=jax.lax.Precision.HIGHEST)
        rv = jnp.einsum("b,byx->yx", a, geo["rotb_v"],
                        precision=jax.lax.Precision.HIGHEST)
        tu = (1 - mode) * a[0] * jet_u + mode * ru
        tv = (1 - mode) * a[0] * jet_v + mode * rv
    pu = jnp.maximum(geo["chi_u"], (1 - mode) * geo["jmask_u"]
                     + mode * geo["rmask_u"])
    pv = jnp.maximum(geo["chi_v"], (1 - mode) * geo["jmask_v"]
                     + mode * geo["rmask_v"])
    upen = (us + lam * pu * tu) / (1 + lam * pu)
    vpen = (vs + lam * pv * tv) / (1 + lam * pv)
    area = g.dx * g.dy
    if jnp.ndim(act) == 0:
        fx = -jnp.sum((upen - us) / dt) * area
        fy = -jnp.sum((vpen - vs) / dt) * area
    else:
        fx = -jnp.einsum("byx,yx->b", geo["own_u"], (upen - us) / dt,
                         precision=jax.lax.Precision.HIGHEST) * area
        fy = -jnp.einsum("byx,yx->b", geo["own_v"], (vpen - vs) / dt,
                         precision=jax.lax.Precision.HIGHEST) * area
    inlet = geo["inlet_u"]
    corr = ((jnp.sum(inlet) * g.dy - jnp.sum(upen[:, -2]) * g.dy)
            / (g.ny * g.dy))
    ubc = upen.at[:, 0].set(inlet).at[:, -1].set(upen[:, -2] + corr)
    vbc = _bc_v(vpen)
    rhs = ((ubc[:, 1:] - ubc[:, :-1]) / g.dx
           + (vbc[1:, :] - vbc[:-1, :]) / g.dy) / dt
    p = poisson(g, rhs, p)
    un = ubc.at[:, 1:-1].set(ubc[:, 1:-1] - dt * (p[:, 1:] - p[:, :-1]) / g.dx)
    vn = vbc.at[1:-1, :].set(vbc[1:-1, :] - dt * (p[1:, :] - p[:-1, :]) / g.dy)
    q = 0.5 * g.u_mean ** 2
    return (_bc_u(un, inlet), _bc_v(vn), p), (fx / q, fy / q)


def warmup(g, geo, re, mode, n_steps: int, dtype):
    """Uncontrolled start-up to developed shedding; returns the flow and
    C_D0, the mean drag over the last quarter."""
    zero = jnp.zeros((), dtype)

    def body(flow, _):
        flow, (cd, _) = step(g, geo, flow, zero, re, mode)
        return flow, cd

    flow, cds = jax.lax.scan(body, init_flow(g, geo, dtype), None,
                             length=n_steps)
    return flow, jnp.mean(cds[-max(1, n_steps // 4):])


def probes(p, ij, mask):
    """Bilinear samples of cell-centred ``p`` at fractional [row, col]."""
    r, c = ij[:, 0], ij[:, 1]
    r0, c0 = jnp.floor(r), jnp.floor(c)
    wr, wc = (r - r0).astype(p.dtype), (c - c0).astype(p.dtype)
    ny, nx = p.shape

    def at(rr, cc):
        return p[jnp.clip(rr, 0, ny - 1).astype(jnp.int32),
                 jnp.clip(cc, 0, nx - 1).astype(jnp.int32)]

    val = ((1 - wr) * ((1 - wc) * at(r0, c0) + wc * at(r0, c0 + 1))
           + wr * ((1 - wc) * at(r0 + 1, c0) + wc * at(r0 + 1, c0 + 1)))
    return val * mask.astype(p.dtype)


def env_action(g, geo, phys, flow, reset, jet, action, n_steps: int):
    """One actuation period of one environment: smooth the action, hold it
    for ``n_steps`` dt, reward; a diverged env is reset and reports 0."""
    a = jnp.clip(action, -1.0, 1.0) * g.u_max
    if jnp.ndim(jet) > 0:
        a = a * phys.act_mask.astype(a.dtype)
    jet = jnp.clip(jet + BETA * (a - jet), -g.u_max, g.u_max)

    def body(f, _):
        return step(g, geo, f, jet, phys.re, phys.mode)

    new, (cds, cls) = jax.lax.scan(body, flow, None, length=n_steps)
    if cds.ndim > 1:
        cd_b, cl_b = jnp.mean(cds, axis=0), jnp.mean(cls, axis=0)
        cd, cl, pen = jnp.sum(cd_b), jnp.sum(cl_b), jnp.sum(jnp.abs(cl_b))
    else:
        cd, cl = jnp.mean(cds), jnp.mean(cls)
        pen = jnp.abs(cl)
    reward = phys.cd0 - cd - OMEGA_L * pen
    u, v, p = new
    div = ((u[:, 1:] - u[:, :-1]) / g.dx + (v[1:, :] - v[:-1, :]) / g.dy)
    ok = ((jnp.maximum(jnp.max(jnp.abs(u)), jnp.max(jnp.abs(v))) < VEL_LIMIT)
          & (jnp.max(jnp.abs(div)) < DIV_LIMIT)
          & jnp.isfinite(jnp.max(jnp.abs(p))) & jnp.isfinite(reward))
    new = jax.tree.map(lambda h, r: jnp.where(ok, h, r), new, reset)
    jet = jnp.where(ok, jet, jnp.zeros_like(jet))
    zero = jnp.zeros((), reward.dtype)
    out = {"reward": jnp.where(ok, reward, zero), "cd": jnp.where(ok, cd, zero),
           "cl": jnp.where(ok, cl, zero), "valid": ok.astype(reward.dtype),
           "obs": probes(new[2], phys.probe_ij, phys.probe_mask)}
    return new, jet, out


def episode(g, geos, phys, start, jet0, actions, n_steps: int):
    """Replay one episode of every env from ``start`` under the given
    actions (N, T, A).  Returns per-step outputs (N, T, ...) and the obs
    after the last action."""

    def one(ph, flow, jet, acts):
        geo = jax.tree.map(lambda x: x[ph.geom], geos)

        def body(carry, act):
            f, j = carry
            a = act[0] if jnp.ndim(j) == 0 else act
            f, j, out = env_action(g, geo, ph, f, flow, j, a, n_steps)
            return (f, j), out

        _, outs = jax.lax.scan(body, (flow, jet), acts)
        return outs

    return jax.vmap(one)(phys, start, jet0, actions)
