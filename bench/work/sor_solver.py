"""Flow solver per dt: momentum, packed red-black SOR pressure solve,
projection; plus the bilinear probes per action.

FLOPs per cell and dt, in the least algorithmic form (coefficients folded
once per solve, not per point):

- advection-diffusion of u and v, per face 30: 4 for the transverse
  velocity average, 6 per direction for the blended upwind/central
  derivative, 3 for the advective product, 7 for the Laplacian, 2 to
  combine with 1/Re, 2 for the explicit Euler update;
- volume penalisation, per face 7, and its reaction force, per face 2;
- divergence 5 per cell, velocity correction 3 per face;
- each point of the pressure grid updated once per sweep pair: 6 for the
  Gauss-Seidel value (two neighbour sums, two products, their sum, minus
  the scaled source) and 3 more for the over-relaxation, so 9 in a relaxed
  pair and 6 in a polish pair (omega = 1).

The probes cost 9 per probe and action (three linear interpolations).

Least bytes: the collect program reads each env's start state (velocity and
pressure, and the same again held for a quarantine reset) and the geometry
once; nothing else has to leave the chip but the trajectory.
"""
from __future__ import annotations

PER_FACE = 30 + 7 + 2 + 3
PER_CELL_DIV = 5
SOR_POINT, POLISH_POINT = 9, 6
PROBE = 9


def grid(cfg: dict) -> tuple:
    nx = int(round(22.0 * cfg["res"]))
    n = int(round(4.1 * cfg["res"]))
    return n + (n % 2), nx


def dt_flops(cfg: dict) -> int:
    """FLOPs of one dt of one env."""
    ny, nx = grid(cfg)
    faces = ny * (nx + 1) + (ny + 1) * nx
    polish = min(10, cfg["poisson_iters"] // 2)
    sor = cfg["poisson_iters"] - polish
    return (faces * PER_FACE + ny * nx * PER_CELL_DIV
            + ny * nx * (sor * SOR_POINT + polish * POLISH_POINT))


def state_bytes(cfg: dict) -> int:
    ny, nx = grid(cfg)
    return 4 * (ny * (nx + 1) + (ny + 1) * nx + ny * nx)


def geometry_bytes(cfg: dict) -> int:
    """Per geometry of the batch and face set: solid fraction, the two jet
    profiles, jet mask, rotary target and mask (6 planes), plus a rotary
    target and an ownership plane per body where actions are per body; and
    the inlet profile."""
    ny, nx = grid(cfg)
    geoms = {s["geometry"] for s in cfg["scenarios"]}
    per_body = 2 * 3 if len(cfg["scenarios"]) > 1 else 0
    planes = 6 + per_body
    return 4 * len(geoms) * (planes * (ny * (nx + 1) + (ny + 1) * nx) + ny)


def work(cfg: dict, n_envs: int, actions: int, steps_per_action: int,
         probes: int) -> dict:
    """One episode: ``actions`` actuation periods of ``n_envs`` envs."""
    return {"flops": n_envs * actions * (steps_per_action * dt_flops(cfg)
                                         + probes * PROBE),
            "bytes": n_envs * 2 * state_bytes(cfg) + geometry_bytes(cfg)}
