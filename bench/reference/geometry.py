"""Channel grid, immersed bodies, actuators and probes of the plain reference.

A copy of the published set-up the trainer integrates, written out from the
Schäfer & Turek 2D-2 channel (22D x 4.1D, cylinder offset +0.05D, parabolic
inlet of mean 1) and the Rabault et al. (2019) jet layout (two 10-degree jets
at 90 and 270 degrees), with the fluidic-pinball (Deng et al. 2020) and
tandem bodies for the multi-body deployment.  Everything is built in float64
numpy and handed to the solver in the solver's dtype; nothing here is read
from the program under test.
"""
from __future__ import annotations

import numpy as np

H = 4.1
LX = 22.0
X0 = -2.0
CYL = (0.0, 0.05)
RADIUS = 0.5
JET_CENTERS_DEG = (90.0, 270.0)
JET_WIDTH_DEG = 10.0

_BACK_X = -0.5 + 1.5 * np.sqrt(3.0) / 2.0
BODIES = {
    "cylinder": ((0.0, 0.05),),
    "pinball": ((-0.5, 0.0), (_BACK_X, 0.75), (_BACK_X, -0.75)),
    "tandem": ((0.0, 0.05), (1.5, 0.05)),
}
MAX_BODIES = max(len(b) for b in BODIES.values())


class Grid:
    """Uniform staggered MAC grid: ``res`` cells per diameter."""

    def __init__(self, res: int, dt: float, re: float, poisson_iters: int,
                 omega: float = 1.7, polish: int = 10, eta: float = 2e-4,
                 upwind: float = 0.2, u_mean: float = 1.0):
        self.res, self.dt, self.re = res, dt, re
        self.nx = int(round(LX * res))
        n = int(round(H * res))
        self.ny = n + (n % 2)
        self.dx, self.dy = LX / self.nx, H / self.ny
        self.iters, self.omega = poisson_iters, omega
        self.polish = min(polish, poisson_iters // 2)
        self.eta, self.upwind, self.u_mean = eta, upwind, u_mean
        self.u_max = 1.5 * u_mean

    @property
    def cells(self) -> int:
        return self.nx * self.ny


def _faces(g: Grid):
    xc = X0 + (np.arange(g.nx) + 0.5) * g.dx
    yc = -H / 2 + (np.arange(g.ny) + 0.5) * g.dy
    xxu, yyu = np.meshgrid(X0 + np.arange(g.nx + 1) * g.dx, yc)
    xxv, yyv = np.meshgrid(xc, -H / 2 + np.arange(g.ny + 1) * g.dy)
    return (xxu, yyu), (xxv, yyv), yc


def _solid(xx, yy, dx, cx, cy):
    r = np.sqrt((xx - cx) ** 2 + (yy - cy) ** 2)
    return np.clip(0.5 * (1 - (r - RADIUS) / (0.5 * dx)), 0.0, 1.0)


def _rotary(xx, yy, dx, cx, cy):
    rx, ry = xx - cx, yy - cy
    r = np.sqrt(rx ** 2 + ry ** 2) + 1e-12
    mask = np.clip((RADIUS + 0.75 * dx - r) / (0.5 * dx), 0.0, 1.0)
    mag = np.clip(r / RADIUS, 0.0, 1.0) * mask
    return mag * (-ry / r), mag * (rx / r), mask


def _jets(xx, yy, dx):
    """Jet arcs widened to >= 3 cells with the flux of the 10-degree jet."""
    rx, ry = xx - CYL[0], yy - CYL[1]
    r = np.sqrt(rx ** 2 + ry ** 2) + 1e-12
    theta = np.degrees(np.arctan2(ry, rx)) % 360.0
    shell = ((r - RADIUS) > -1.5 * dx) & ((r - RADIUS) < 0.75 * dx)
    width = max(JET_WIDTH_DEG, np.degrees(3.0 * dx / RADIUS))
    profiles, mask = [], np.zeros_like(r)
    for c in JET_CENTERS_DEG:
        d = np.abs((theta - c + 180.0) % 360.0 - 180.0)
        prof = np.clip(1.0 - (d / (width / 2)) ** 2, 0.0, 1.0)
        prof = prof * (d < width / 2) * shell * (JET_WIDTH_DEG / width)
        profiles.append(prof)
        mask = np.maximum(mask, (prof > 0).astype(np.float64))
    return np.stack(profiles), rx / r, ry / r, mask


def _owner(xx, yy, bodies):
    d = np.stack([np.sqrt((xx - x) ** 2 + (yy - y) ** 2) - RADIUS
                  for x, y in bodies])
    near = np.argmin(d, axis=0)
    own = np.stack([(near == i).astype(np.float64)
                    for i in range(len(bodies))])
    pad = np.zeros((MAX_BODIES - len(bodies),) + own.shape[1:])
    return np.concatenate([own, pad])


def build(g: Grid, name: str) -> dict:
    """Static fields of one body set (float64 numpy), per-body planes padded
    to ``MAX_BODIES`` so geometries stack.  ``jet_u``/``jet_v`` hold the two
    jets' signed normal profiles; jet 1 blows out as jet 2 sucks in."""
    bodies = BODIES[name]
    (xxu, yyu), (xxv, yyv), yc = _faces(g)
    out = {
        "chi_u": np.maximum.reduce([_solid(xxu, yyu, g.dx, *b)
                                    for b in bodies]),
        "chi_v": np.maximum.reduce([_solid(xxv, yyv, g.dx, *b)
                                    for b in bodies]),
    }
    if name == "cylinder":
        pu, nxu, _, mu = _jets(xxu, yyu, g.dx)
        pv, _, nyv, mv = _jets(xxv, yyv, g.dx)
        out["jet_u"], out["jet_v"] = pu * nxu[None], pv * nyv[None]
        out["jmask_u"], out["jmask_v"] = mu, mv
    else:
        out["jet_u"], out["jmask_u"] = np.zeros((2,) + xxu.shape), np.zeros(xxu.shape)
        out["jet_v"], out["jmask_v"] = np.zeros((2,) + xxv.shape), np.zeros(xxv.shape)
    ru = [_rotary(xxu, yyu, g.dx, *b) for b in bodies]
    rv = [_rotary(xxv, yyv, g.dx, *b) for b in bodies]
    pad_u = [np.zeros(xxu.shape)] * (MAX_BODIES - len(bodies))
    pad_v = [np.zeros(xxv.shape)] * (MAX_BODIES - len(bodies))
    out["rotb_u"] = np.stack([r[0] for r in ru] + pad_u)
    out["rotb_v"] = np.stack([r[1] for r in rv] + pad_v)
    out["rot_u"] = np.sum(out["rotb_u"], axis=0)
    out["rot_v"] = np.sum(out["rotb_v"], axis=0)
    out["rmask_u"] = np.maximum.reduce([r[2] for r in ru])
    out["rmask_v"] = np.maximum.reduce([r[2] for r in rv])
    out["own_u"] = _owner(xxu, yyu, bodies)
    out["own_v"] = _owner(xxv, yyv, bodies)
    out["inlet_u"] = g.u_max * (H - 2 * yc) * (H + 2 * yc) / H ** 2
    return out


# -- probe layouts ----------------------------------------------------------

def _ring(n, r, cx=CYL[0], cy=CYL[1]):
    a = 2 * np.pi * np.arange(n) / n
    return np.stack([cx + r * np.cos(a), cy + r * np.sin(a)], axis=-1)


def _ring149():
    pts = [(CYL[0] + r * np.cos(2 * np.pi * k / 24),
            CYL[1] + r * np.sin(2 * np.pi * k / 24))
           for r in (0.6, 0.8, 1.0) for k in range(24)]
    pts += [(x, y) for x in np.linspace(1.2, 9.0, 11)
            for y in np.linspace(-1.2, 1.2, 7)]
    return np.asarray(pts)


def _pinball():
    rings = np.concatenate([_ring(8, 0.8, *b) for b in BODIES["pinball"]])
    wx, wy = np.meshgrid(np.linspace(2.0, 8.0, 7), np.linspace(-1.4, 1.4, 5))
    return np.concatenate([rings, np.stack([wx.ravel(), wy.ravel()], -1)])


def _tandem():
    rings = np.concatenate([_ring(16, 0.8, *b) for b in BODIES["tandem"]])
    wake = np.stack([np.linspace(2.5, 9.0, 8), np.full(8, CYL[1])], axis=-1)
    return np.concatenate([rings, wake])


PROBES = {"ring149": _ring149, "pinball": _pinball, "tandem": _tandem}


def probe_ij(g: Grid, layout: str) -> np.ndarray:
    """(P, 2) fractional [row, col] cell-centre coordinates."""
    pts = PROBES[layout]()
    col = (pts[:, 0] - (X0 + 0.5 * g.dx)) / g.dx
    row = (pts[:, 1] - (-H / 2 + 0.5 * g.dy)) / g.dy
    return np.stack([row, col], axis=-1)
