"""Pressure Poisson solver: red-black SOR with channel boundary conditions.

BCs: Neumann (dp/dn = 0) at inlet and walls, Dirichlet (p = 0) at the outlet.
This is the CFD hot spot (the paper attributes >95% of wall time to CFD; within
our fractional-step solver the pressure solve dominates).  ``solve`` fans out
over the interchangeable backends:

  "reference"  the default: dispatches to "packed" on even-width grids and
               to "full" on odd widths — always correct, fastest jnp path
  "packed"     packed-checkerboard storage: red and black points held as two
               (ny, nx//2) planes so every sweep touches exactly the points
               it updates — no masks, no wasted update, ~half the FLOPs and
               memory traffic of the full-grid sweep.  Even nx only.  On a
               TPU, with a static omega, the whole solve runs as one
               kernels/poisson ``rb_sor_batched`` call whose block holds
               every env of a vmapped batch (bit for bit the XLA loop
               ``packed_sor_loop``), wherever that batch has two envs or
               more and fits in VMEM
               (``kernels.poisson.ops.batched_kernel_fits``); elsewhere the
               XLA loop runs
  "full"       the original full-grid masked sweep — the oracle the packed
               layout is tested against
  "pallas"     kernels/poisson's TPU slab smoother (block-Jacobi slabs,
               packed planes VMEM-resident per slab)
  "halo"       cfd/decomp's explicit x-slab domain decomposition with
               shard_map + ppermute halo exchange over a mesh axis — the
               paper's N_ranks parallelism, executable inside the vmapped
               env step; ships half-width (single-parity) halos
  "fused"      the actuation-interval megakernel (kernels/actuation via
               solver.step_interval): velocity fields and packed pressure
               parity planes stay resident across a whole actuation
               interval.  For a single ``solve`` call it is an alias for
               "reference" (there is no interval to fuse)

``use_pallas=`` is kept as a deprecated alias for backend selection.

Packed-checkerboard index map (nx even; row j, packed column k):

  red[j, k]   = p[j, 2k + j%2]          black[j, k] = p[j, 2k + 1 - j%2]

Vertical neighbours of a point land at the SAME packed index in the other
plane; horizontal neighbours are the other plane's columns (k-1, k) on one
row parity and (k, k+1) on the other, so one shifted add of the opposite
plane plus a per-row-parity select covers west+east.  The boundary ghosts
fall out of the layout: the ghost values a half-sweep needs always carry the
parity of the plane being *updated* (Neumann inlet ghost = own first column,
Dirichlet outlet ghost = negated own last column, wall ghosts = own
boundary rows), so no full-grid padding is ever materialized.
"""
from __future__ import annotations

import functools
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod

BACKENDS = ("reference", "packed", "full", "pallas", "halo", "fused")

# grid shapes already warned about for the pallas -> reference odd-width
# fallback (warn once per shape, not once per traced call site; resettable
# via core.backend.reset_warning_caches for test isolation)
_ODD_NX_WARNED = backend_mod.warn_once_cache()


def resolve_backend(backend: Optional[str] = None,
                    use_pallas: Optional[bool] = None) -> str:
    """Normalize the (backend, legacy use_pallas) pair to a BACKENDS member.

    ``use_pallas`` is a deprecated alias: True -> "pallas", False ->
    "reference".  Passing both a backend and a conflicting alias is an error.
    Delegates to :func:`repro.core.backend.resolve_backend`, skipping this
    cfd layer's frames so the DeprecationWarning blames the user's call site
    even when ``solve``/``step`` are traced under ``jax.jit``.
    """
    return backend_mod.resolve_backend(
        backend, use_pallas, backends=BACKENDS,
        skip_dirs=(os.path.dirname(__file__),), what="solver")


def _pad_pressure(p):
    """Ghost cells: Neumann left/top/bottom, Dirichlet 0 at right (outlet)."""
    left = p[:, :1]              # dp/dx = 0 at inlet
    right = -p[:, -1:]           # p = 0 at the outlet face
    p = jnp.concatenate([left, p, right], axis=1)
    top = p[:1, :]
    bot = p[-1:, :]
    return jnp.concatenate([top, p, bot], axis=0)


def residual(p, rhs, dx, dy):
    pp = _pad_pressure(p)
    lap = ((pp[1:-1, :-2] + pp[1:-1, 2:] - 2 * p) / dx ** 2
           + (pp[:-2, 1:-1] + pp[2:, 1:-1] - 2 * p) / dy ** 2)
    return lap - rhs


# ---------------------------------------------------------------------------
# packed checkerboard layout
# ---------------------------------------------------------------------------

def pack_checkerboard(a):
    """(ny, nx) full grid -> ((ny, nx//2) red, (ny, nx//2) black) planes.

    red[j, k] = a[j, 2k + j%2]; black[j, k] = a[j, 2k + 1 - j%2].
    Requires even nx (each row then holds exactly nx//2 of each color)."""
    ny, nx = a.shape
    if nx % 2:
        raise ValueError(f"packed checkerboard needs an even grid width, "
                         f"got nx={nx}")
    pairs = a.reshape(ny, nx // 2, 2)
    odd = (jnp.arange(ny) % 2 == 1)[:, None]
    red = jnp.where(odd, pairs[..., 1], pairs[..., 0])
    black = jnp.where(odd, pairs[..., 0], pairs[..., 1])
    return red, black


def unpack_checkerboard(red, black):
    """Inverse of ``pack_checkerboard``."""
    ny, w = red.shape
    odd = (jnp.arange(ny) % 2 == 1)[:, None, None]
    pairs = jnp.where(odd, jnp.stack([black, red], axis=-1),
                      jnp.stack([red, black], axis=-1))
    return pairs.reshape(ny, 2 * w)


def packed_half_sweep(active, other, rhs_a, left_g, right_g, north_g, south_g,
                      shift, om, dx2, dy2, inv_diag):
    """One colored Gauss-Seidel half-sweep entirely in packed storage.

    active/other: the plane being updated / the neighbour plane (..., ny, W).
    left_g/right_g: ghost columns (..., ny, 1) in the *update* parity
    (entries on the wrong row parity are never selected).  north_g/south_g:
    wall ghost ROWS (..., 1, W) — the strips :func:`packed_ghost_rows`
    returns; the shifted vertical-neighbour planes are assembled here from
    slices so each operand is a concat-of-slices XLA fuses into the stencil
    (on CPU this slice form measures ~1.8x faster than materializing padded
    planes, bitwise-identical results).  shift: (..., ny, 1) bool — rows
    whose horizontal neighbours sit one packed column to the right (j odd
    for red, j even for black).

    The update association is load-bearing for bitwise compatibility across
    backends: ``p_gs = (nb - rhs) * inv_diag`` first, then
    ``(1 - om) * active + om * p_gs`` — do not refactor into
    ``om * (nb - rhs) * inv_diag``.
    """
    o_west = jnp.concatenate([left_g, other[..., :, :-1]], axis=-1)
    o_east = jnp.concatenate([other[..., :, 1:], right_g], axis=-1)
    horiz = jnp.where(shift, other + o_east, o_west + other)
    north = jnp.concatenate([north_g, other[..., :-1, :]], axis=-2)
    south = jnp.concatenate([other[..., 1:, :], south_g], axis=-2)
    nb = horiz / dx2 + (north + south) / dy2
    p_gs = (nb - rhs_a) * inv_diag
    return (1 - om) * active + om * p_gs


def packed_ghost_rows(active, other):
    """Wall ghost ROW strips (..., 1, W) for the ``active`` half-sweep:
    Neumann walls mean the ghost is a copy of the active plane's own
    boundary row (a wall ghost always carries the parity of the point being
    updated).  ``other`` is accepted for call-site symmetry with the ghost
    columns; the strips themselves only need ``active``."""
    del other
    return active[..., :1, :], active[..., -1:, :]


def packed_sweep_pair(red, black, rhs_r, rhs_b, om, *, dx, dy, row_odd):
    """One red+black Gauss-Seidel pair on packed planes (single domain:
    boundary ghosts derived from the planes themselves)."""
    dx2, dy2 = dx ** 2, dy ** 2
    inv_diag = 1.0 / (2.0 / dx2 + 2.0 / dy2)
    red = packed_half_sweep(
        red, black, rhs_r,
        red[:, :1], -red[:, -1:],          # Neumann inlet / Dirichlet outlet
        *packed_ghost_rows(red, black),
        row_odd, om, dx2, dy2, inv_diag)
    black = packed_half_sweep(
        black, red, rhs_b,
        black[:, :1], -black[:, -1:],
        *packed_ghost_rows(black, red),
        ~row_odd, om, dx2, dy2, inv_diag)
    return red, black


def packed_sor_loop(red, black, rhs_r, rhs_b, omega, *, dx, dy, iters: int,
                    n_sor: int):
    """``iters`` red+black pairs on packed (ny, W) planes: omega for the
    first ``n_sor``, 1 (plain Gauss-Seidel) for the rest.  The XLA form of
    the packed solve; ``kernels.poisson.rb_sor_batched`` matches it bit for
    bit on the TPU."""
    ny = red.shape[0]
    row_odd = (jnp.arange(ny) % 2 == 1)[:, None]

    def body(i, planes):
        om = jnp.where(i < n_sor, omega, 1.0)
        return packed_sweep_pair(*planes, rhs_r, rhs_b, om,
                                 dx=dx, dy=dy, row_odd=row_odd)
    return jax.lax.fori_loop(0, iters, body, (red, black))


# ---------------------------------------------------------------------------
# solve
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dx", "dy", "iters", "omega_s",
                                             "backend", "polish", "mesh",
                                             "halo_axis", "halo_inner"))
@jax.named_scope("poisson")
def _solve_impl(rhs, p0, omega_t, dx, dy, *, iters: int, omega_s, backend: str,
                polish: int, mesh, halo_axis: str, halo_inner: int):
    # omega arrives on exactly one of two lanes: ``omega_s`` (static Python
    # float — the common case, required by the pallas kernel) or ``omega_t``
    # (traced array — kept working for the jnp backends, matching the seed
    # solver which only materialized its omega default at trace time)
    omega = omega_s if omega_t is None else omega_t
    ny, nx = rhs.shape
    p = jnp.zeros_like(rhs) if p0 is None else p0

    if backend == "halo":
        if mesh is None:
            raise ValueError(
                "backend='halo' needs a mesh with a spatial axis; pass "
                "mesh= (e.g. launch.mesh.mesh_for_plan(plan)) or choose "
                "backend='reference'")
        from repro.cfd import decomp
        return decomp.decomposed_solve(rhs, p, mesh=mesh, axis=halo_axis,
                                       dx=dx, dy=dy, omega=omega,
                                       iters=iters, inner_iters=halo_inner,
                                       polish=polish)

    n_polish = min(polish, iters // 2)
    n_sor = iters - n_polish

    if backend in ("packed", "pallas"):
        rhs_r, rhs_b = pack_checkerboard(rhs)
        red, black = pack_checkerboard(p)
        from repro.kernels.poisson import ops as poisson_ops

        if backend == "pallas":
            red, black = poisson_ops.rb_sor_planes(red, black, rhs_r, rhs_b,
                                                   dx, dy, iters=n_sor,
                                                   omega=omega_s)
            row_odd = (jnp.arange(ny) % 2 == 1)[:, None]

            def gs(_, planes):
                return packed_sweep_pair(*planes, rhs_r, rhs_b, 1.0,
                                         dx=dx, dy=dy, row_odd=row_odd)
            red, black = jax.lax.fori_loop(0, n_polish, gs, (red, black))
        elif omega_t is None and poisson_ops.batched_kernel_fits(
                poisson_ops.kernel_platform(), ny, nx, 2):
            # the whole solve in one kernel call over the vmapped env batch;
            # a batch the kernel does not serve (one env, or too many for
            # VMEM) runs packed_sor_loop
            red, black = poisson_ops.rb_sor_solve(
                red, black, rhs_r, rhs_b, dx=dx, dy=dy, omega=omega_s,
                iters=iters, polish=polish)
        else:
            red, black = packed_sor_loop(red, black, rhs_r, rhs_b, omega,
                                         dx=dx, dy=dy, iters=iters,
                                         n_sor=n_sor)
        return unpack_checkerboard(red, black)

    # backend == "full": the original masked full-grid sweep (the oracle)
    jj, ii = jnp.meshgrid(jnp.arange(ny), jnp.arange(nx), indexing="ij")
    red = ((ii + jj) % 2 == 0)
    inv_diag = 1.0 / (2.0 / dx ** 2 + 2.0 / dy ** 2)

    def sweep(p, mask, om):
        pp = _pad_pressure(p)
        nb = ((pp[1:-1, :-2] + pp[1:-1, 2:]) / dx ** 2
              + (pp[:-2, 1:-1] + pp[2:, 1:-1]) / dy ** 2)
        p_gs = (nb - rhs) * inv_diag
        return jnp.where(mask, (1 - om) * p + om * p_gs, p)

    def body(i, p):
        om = jnp.where(i < n_sor, omega, 1.0)
        p = sweep(p, red, om)
        p = sweep(p, ~red, om)
        return p

    return jax.lax.fori_loop(0, iters, body, p)


def solve(rhs, dx, dy, *, iters: int = 60, omega: float = 1.7,
          p0=None, backend: Optional[str] = None,
          use_pallas: Optional[bool] = None, polish: int = 10,
          mesh=None, halo_axis: str = "model", halo_inner: int = 4):
    """Red-black SOR.  rhs: (ny, nx).  Returns p with mean-free gauge handled
    by the outlet Dirichlet condition.

    The last ``polish`` sweeps run with omega = 1 (plain Gauss-Seidel):
    over-relaxation accelerates the smooth error modes but leaves an
    amplified high-frequency residual, which a few unrelaxed smoothing
    sweeps remove (~4x lower residual norm at equal total iterations).

    ``backend=None``/``"reference"`` picks the packed-checkerboard sweep on
    even-width grids (identical iteration to the full-grid oracle at ~half
    the FLOPs and memory traffic) and the full-grid sweep on odd widths.
    ``backend="packed"`` forces the packed layout (ValueError on odd nx);
    ``backend="full"`` forces the full-grid oracle.  ``backend="pallas"``
    requires an even nx (checkerboard parity); odd widths fall back to the
    reference path with a one-time warning naming the grid shape.
    ``backend="halo"`` runs cfd/decomp's explicit x-slab decomposition over
    ``mesh``'s ``halo_axis`` (``halo_inner`` local sweeps per halo exchange)
    and is traceable under vmap — the paper's N_ranks > 1 configuration."""
    backend = resolve_backend(backend, use_pallas)
    ny, nx = rhs.shape[-2:]
    if backend == "fused":
        # "fused" fuses an actuation INTERVAL (kernels/actuation via
        # solver.step_interval); a single pressure solve has nothing to
        # fuse across, so it runs the reference sweep
        backend = "reference"
    if backend == "pallas" and nx % 2:
        if (ny, nx) not in _ODD_NX_WARNED:
            _ODD_NX_WARNED.add((ny, nx))
            warnings.warn(
                f"backend='pallas' needs an even grid width for checkerboard "
                f"slab parity; grid (ny={ny}, nx={nx}) falls back to the "
                f"jnp reference path (this warning fires once per shape)",
                RuntimeWarning, stacklevel=2)
        backend = "reference"
    if backend == "packed" and nx % 2:
        raise ValueError(
            f"backend='packed' needs an even grid width, got nx={nx}; use "
            f"backend='reference' (it falls back to the full-grid sweep on "
            f"odd widths) or an even-nx grid")
    if backend == "reference":
        backend = "full" if nx % 2 else "packed"
    if isinstance(omega, (int, float)):
        omega_s, omega_t = float(omega), None
    elif backend == "pallas":
        raise TypeError(
            f"backend='pallas' needs a concrete Python-float omega (the "
            f"slab kernel specializes on it), got {type(omega).__name__}; "
            f"pass omega as a float or choose a jnp backend")
    else:
        omega_s, omega_t = None, omega
    return _solve_impl(rhs, p0, omega_t, dx, dy, iters=iters, omega_s=omega_s,
                       backend=backend, polish=polish, mesh=mesh,
                       halo_axis=halo_axis, halo_inner=halo_inner)
