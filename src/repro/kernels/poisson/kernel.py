"""Pallas TPU kernels: VMEM-resident red-black SOR.

``rb_sor_batched`` (the ``reference``/``packed`` solve on TPU, at the end of
this file) runs a whole packed solve for a whole env batch in one call.
The rest of the file is the ``pallas`` backend's slab smoother:

TPU-native design (DESIGN.md §5): the pressure grid is split into x-slabs;
each program instance loads its slab (plus one halo column from each
neighbour) into VMEM, runs ``inner_iters`` red-black SOR sweeps entirely
in VMEM (no HBM round-trips between sweeps), and writes the slab back.
Across slabs this is a block-Jacobi outer iteration — the outer loop (and
halo refresh) lives in ops.py.

Neighbour slabs are delivered with the 3-index-map trick: the same array is
passed three times with index maps i, i-1, i+1 (clamped), so every block
stays block-aligned (no unblocked indexing needed).  Boundary conditions
(Neumann inlet/walls, Dirichlet-0 outlet) are applied inside the kernel
based on program_id.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sweep(p, rhs, red_mask, inv_diag, omega, dx2, dy2, left, right):
    """One colored Gauss-Seidel half-sweep on the slab (with halo columns)."""
    pp = jnp.concatenate([left, p, right], axis=1)       # (ny, bx+2)
    top = pp[:1, :]
    bot = pp[-1:, :]
    pp = jnp.concatenate([top, pp, bot], axis=0)         # (ny+2, bx+2) Neumann walls
    nb = ((pp[1:-1, :-2] + pp[1:-1, 2:]) / dx2
          + (pp[:-2, 1:-1] + pp[2:, 1:-1]) / dy2)
    p_gs = (nb - rhs) * inv_diag
    return jnp.where(red_mask, (1 - omega) * p + omega * p_gs, p)


def rb_sor_slab_kernel(p_ref, p_left_ref, p_right_ref, rhs_ref, out_ref, *,
                       nslabs: int, bx: int, dx: float, dy: float,
                       omega: float, inner_iters: int):
    i = pl.program_id(0)
    p = p_ref[...]
    rhs = rhs_ref[...]
    ny = p.shape[0]
    dx2, dy2 = dx * dx, dy * dy
    inv_diag = 1.0 / (2.0 / dx2 + 2.0 / dy2)

    # halo columns (stale during inner sweeps = block-Jacobi)
    left_halo = jnp.where(i == 0, p[:, :1],              # Neumann at inlet
                          p_left_ref[...][:, -1:])
    right_halo = jnp.where(i == nslabs - 1, -p[:, -1:],  # Dirichlet-0 outlet
                           p_right_ref[...][:, :1])

    # global checkerboard parity: slab column offset = i * bx (bx is even)
    jj = jax.lax.broadcasted_iota(jnp.int32, (ny, bx), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (ny, bx), 1)
    red = ((ii + jj) % 2 == 0)

    def body(_, p):
        p = _sweep(p, rhs, red, inv_diag, omega, dx2, dy2, left_halo, right_halo)
        p = _sweep(p, rhs, ~red, inv_diag, omega, dx2, dy2, left_halo, right_halo)
        return p

    out_ref[...] = jax.lax.fori_loop(0, inner_iters, body, p)


def rb_sor_slabs(p, rhs, *, dx: float, dy: float, omega: float,
                 nslabs: int, inner_iters: int, interpret: bool = True):
    """One outer block-Jacobi iteration: all slabs smoothed in parallel."""
    ny, nx = p.shape
    assert nx % nslabs == 0, (nx, nslabs)
    bx = nx // nslabs
    assert bx % 2 == 0, "slab width must be even for checkerboard parity"

    kern = functools.partial(rb_sor_slab_kernel, nslabs=nslabs, bx=bx,
                             dx=dx, dy=dy, omega=omega,
                             inner_iters=inner_iters)
    slab = pl.BlockSpec((ny, bx), lambda i: (0, i))
    left = pl.BlockSpec((ny, bx), lambda i: (0, jnp.maximum(i - 1, 0)))
    right = pl.BlockSpec((ny, bx), lambda i: (0, jnp.minimum(i + 1, nslabs - 1)))
    return pl.pallas_call(
        kern,
        grid=(nslabs,),
        in_specs=[slab, left, right, slab],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((ny, nx), p.dtype),
        interpret=interpret,
        name="poisson_rb_sor_slabs",
    )(p, p, p, rhs)


# ---------------------------------------------------------------------------
# packed-checkerboard slab smoother
# ---------------------------------------------------------------------------
#
# Red and black points live as two (ny, nx//2) planes (layout documented in
# cfd/poisson.py: red[j, k] = p[j, 2k + j%2]).  Each program instance keeps
# BOTH planes of its slab VMEM-resident across ``inner_iters`` sweep pairs,
# touching only the points it updates — half the FLOPs and half the VMEM
# traffic of the masked full-grid sweep above.  The single-parity ghost
# columns a half-sweep needs are exactly the neighbour slab's packed edge
# columns (the entries on the unused row parity are never selected), so the
# same 3-index-map halo trick delivers half-width halos for free.  The
# half-sweep body itself is shared with the jnp backends (pure jnp, so it
# lowers inside the kernel unchanged) — one stencil implementation for
# packed reference, halo, and pallas.

def rb_sor_packed_slab_kernel(r_ref, rl_ref, rr_ref, b_ref, bl_ref, br_ref,
                              rhs_r_ref, rhs_b_ref, out_r_ref, out_b_ref, *,
                              nslabs: int, bxp: int, dx: float, dy: float,
                              omega: float, inner_iters: int):
    i = pl.program_id(0)
    red = r_ref[...]
    black = b_ref[...]
    rhs_r = rhs_r_ref[...]
    rhs_b = rhs_b_ref[...]
    ny = red.shape[0]
    dx2, dy2 = dx * dx, dy * dy
    inv_diag = 1.0 / (2.0 / dx2 + 2.0 / dy2)
    jj = jax.lax.broadcasted_iota(jnp.int32, (ny, bxp), 0)
    row_odd = (jj % 2 == 1)

    # Single-parity halo ghost columns, frozen for the call (block-Jacobi).
    # A red update's west/east neighbours are black, so its interior ghosts
    # are the neighbour's BLACK edge columns (and vice versa); at the domain
    # edges the ghost parity equals the update parity (Neumann inlet = own
    # first column, Dirichlet outlet = negated own last column).
    r_lg = jnp.where(i == 0, red[:, :1], bl_ref[...][:, -1:])
    r_rg = jnp.where(i == nslabs - 1, -red[:, -1:], br_ref[...][:, :1])
    b_lg = jnp.where(i == 0, black[:, :1], rl_ref[...][:, -1:])
    b_rg = jnp.where(i == nslabs - 1, -black[:, -1:], rr_ref[...][:, :1])

    from repro.cfd.poisson import packed_ghost_rows, packed_half_sweep

    def body(_, planes):
        red, black = planes
        red = packed_half_sweep(
            red, black, rhs_r, r_lg, r_rg, *packed_ghost_rows(red, black),
            row_odd, omega, dx2, dy2, inv_diag)
        black = packed_half_sweep(
            black, red, rhs_b, b_lg, b_rg, *packed_ghost_rows(black, red),
            ~row_odd, omega, dx2, dy2, inv_diag)
        return red, black

    out_r, out_b = jax.lax.fori_loop(0, inner_iters, body, (red, black))
    out_r_ref[...] = out_r
    out_b_ref[...] = out_b


def rb_sor_slabs_packed(red, black, rhs_r, rhs_b, *, dx: float, dy: float,
                        omega: float, nslabs: int, inner_iters: int,
                        interpret: bool = True):
    """One outer block-Jacobi iteration on packed planes, all slabs parallel.

    red/black/rhs_r/rhs_b: (ny, nx//2) planes from
    ``cfd.poisson.pack_checkerboard``.  The full-grid slab width must be
    even (so every slab starts on an even column and the packed layout
    parity is uniform across slabs)."""
    ny, w = red.shape
    assert w % nslabs == 0, (w, nslabs)
    bxp = w // nslabs           # packed slab width == full slab width // 2
    kern = functools.partial(rb_sor_packed_slab_kernel, nslabs=nslabs,
                             bxp=bxp, dx=dx, dy=dy, omega=omega,
                             inner_iters=inner_iters)
    slab = pl.BlockSpec((ny, bxp), lambda i: (0, i))
    left = pl.BlockSpec((ny, bxp), lambda i: (0, jnp.maximum(i - 1, 0)))
    right = pl.BlockSpec((ny, bxp),
                         lambda i: (0, jnp.minimum(i + 1, nslabs - 1)))
    plane = jax.ShapeDtypeStruct((ny, w), red.dtype)
    return pl.pallas_call(
        kern,
        grid=(nslabs,),
        in_specs=[slab, left, right, slab, left, right, slab, slab],
        out_specs=[slab, slab],
        out_shape=[plane, plane],
        interpret=interpret,
        name="poisson_rb_sor_packed",
    )(red, red, red, black, black, black, rhs_r, rhs_b)


# ---------------------------------------------------------------------------
# batched packed solve: the whole env batch in one block, the whole solve
# in one call
# ---------------------------------------------------------------------------
#
# The packed planes of B envs arrive as (ny, B, W): rows on the leading
# (untiled) axis, envs on the sublanes, packed columns on the lanes.  So a
# vertical neighbour is the adjacent leading index, the wall ghosts are the
# updated plane's own first and last rows, each row's parity is static (no
# per-row select), and a horizontal neighbour is the other plane shifted by
# one lane, with the updated plane's own lane 0 (Neumann inlet) or its own
# lane W-1 negated (Dirichlet outlet) shifted in.  Envs never mix.  The
# rows are unrolled: a loop over them (even over blocks of 8 rows) cost the
# chip 1.6x the solve time.  Built from lax primitives, with each row of
# the other plane loaded once, the unrolled body traces in a few tenths
# of a second.
#
# A red update reads only black and a black update only red, so each
# half-sweep writes its plane in place, row by row; both planes stay in
# VMEM through all sweep pairs.  The arithmetic is the XLA loop's
# (``cfd.poisson.packed_sor_loop``) as XLA compiles it on the TPU and the
# CPU: each ``/ dx**2`` becomes a product with the float32 reciprocal, and
# ``om * ((nb - rhs) * inv_diag)`` becomes ``(nb - rhs) * (om * inv_diag)``
# with the scalar product in float32.  Written in those forms, with the
# same order of every add, the kernel reproduces the loop bit for bit.

def _half_sweep_rows(act_ref, oth_ref, rhs_ref, *, east_parity: int, keep, gs,
                     rdx2, rdy2):
    """One colored half-sweep of ``act_ref`` in place.  Rows of parity
    ``east_parity`` take their horizontal neighbours from packed columns
    (k, k+1) of the other plane, the rest from (k-1, k)."""
    ny, _, w = act_ref.shape
    lax = jax.lax
    other = [None, oth_ref[0]]           # the other plane's rows j-1, j
    for j in range(ny):
        act = act_ref[j]
        other.append(oth_ref[j + 1] if j + 1 < ny else None)
        north, center, south = other[-3:]
        if j % 2 == east_parity:
            east = lax.concatenate(
                [lax.slice_in_dim(center, 1, w, axis=1),
                 lax.neg(lax.slice_in_dim(act, w - 1, w, axis=1))], 1)
            horiz = lax.add(center, east)
        else:
            west = lax.concatenate(
                [lax.slice_in_dim(act, 0, 1, axis=1),
                 lax.slice_in_dim(center, 0, w - 1, axis=1)], 1)
            horiz = lax.add(west, center)
        vert = lax.add(act if north is None else north,
                       act if south is None else south)
        nb = lax.add(lax.mul(horiz, rdx2), lax.mul(vert, rdy2))
        act_ref[j] = lax.add(lax.mul(keep, act),
                             lax.mul(lax.sub(nb, rhs_ref[j]), gs))


def rb_sor_batched_kernel(r_in, b_in, rhs_r_ref, rhs_b_ref, r_ref, b_ref, *,
                          n_sor: int, n_polish: int, rdx2, rdy2, sor, polish):
    r_ref[...] = r_in[...]
    b_ref[...] = b_in[...]

    def pairs(keep, gs):
        def body(_, carry):
            # red: odd rows sit at 2k+1, so their neighbours are (k, k+1)
            _half_sweep_rows(r_ref, b_ref, rhs_r_ref, east_parity=1,
                             keep=keep, gs=gs, rdx2=rdx2, rdy2=rdy2)
            _half_sweep_rows(b_ref, r_ref, rhs_b_ref, east_parity=0,
                             keep=keep, gs=gs, rdx2=rdx2, rdy2=rdy2)
            return carry
        return body

    jax.lax.fori_loop(0, n_sor, pairs(*sor), 0)
    jax.lax.fori_loop(0, n_polish, pairs(*polish), 0)


@functools.partial(jax.jit, static_argnames=("dx", "dy", "omega", "iters",
                                             "polish", "interpret"))
def rb_sor_batched(red, black, rhs_r, rhs_b, *, dx: float, dy: float,
                   omega: float, iters: int, polish: int,
                   interpret: bool = False):
    """The packed solve of ``cfd.poisson.packed_sor_loop`` for B envs in one
    call: ``iters`` red+black pairs, omega for the first
    ``iters - min(polish, iters // 2)``, 1 for the rest.

    red/black/rhs_r/rhs_b: (B, ny, W) packed planes.  Returns (red, black)
    in the same layout; the transposes to and from the kernel's (ny, B, W)
    block happen once per solve, around the call."""
    batch, ny, w = red.shape
    n_polish = min(polish, iters // 2)
    f32 = np.float32
    dx2, dy2 = dx ** 2, dy ** 2
    inv_diag = f32(1.0 / (2.0 / dx2 + 2.0 / dy2))
    om = f32(omega)

    kern = functools.partial(
        rb_sor_batched_kernel, n_sor=iters - n_polish, n_polish=n_polish,
        rdx2=f32(1) / f32(dx2), rdy2=f32(1) / f32(dy2),
        sor=(f32(1) - om, om * inv_diag), polish=(f32(0), f32(1) * inv_diag))
    plane = jax.ShapeDtypeStruct((ny, batch, w), red.dtype)
    out_r, out_b = pl.pallas_call(
        kern,
        out_shape=[plane, plane],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
        name="poisson_rb_sor_batched",
    )(*(jnp.swapaxes(a, 0, 1) for a in (red, black, rhs_r, rhs_b)))
    return jnp.swapaxes(out_r, 0, 1), jnp.swapaxes(out_b, 0, 1)
