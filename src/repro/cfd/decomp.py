"""Explicit spatial domain decomposition of the Poisson solve: shard_map +
lax.ppermute halo exchange — the literal TPU translation of OpenFOAM's MPI
ranks (the paper's N_ranks axis), as opposed to letting GSPMD auto-partition
the global stencil (core/runner.make_sharded_cfd_step).

Each device owns an x-slab of the pressure grid held in packed-checkerboard
storage (red/black planes, see cfd/poisson.py), so local sweeps touch only
the points they update.  Packing also halves the exchange volume: a colored
half-sweep needs only the *opposite*-parity entries of the neighbour's edge
column, so every ppermute ships a half-width (ceil(ny/2)) halo instead of a
full column — the per-message comm cost the paper's Fig. 7 measures, halved.

Two coupling schedules:

  ``inner_iters == 1``  exchange before EVERY colored half-sweep (two
        half-width ppermute pairs per red+black pair).  The black sweep then
        sees fresh red values across rank boundaries, which makes the
        decomposed iteration *exactly* the monolithic red-black sweep — at
        any rank count, not just n_shards == 1.  Same bytes per sweep pair
        as the old full-column exchange, half the bytes per message.
  ``inner_iters > 1``   classic block-Jacobi: one full-edge exchange (both
        parities, packed into one message pair) per outer round, halos
        frozen for ``inner_iters`` local sweep pairs — the loose-coupling
        end of the comm/convergence trade.

``decomposed_solve`` is the traceable entry point (usable inside jit / vmap /
scan — it is the ``backend="halo"`` path of ``cfd.poisson.solve`` and runs
inside the vmapped env step when a plan picks ``n_ranks > 1``);
``make_decomposed_poisson`` wraps it as a standalone jit'd solver.  Grids
whose slab width or height is odd fall back to the legacy full-grid sweeps
(``packed=False`` forces that path; it keeps the old frozen-halo semantics).

The domain-edge ghosts (Neumann at the inlet shard, Dirichlet at the outlet
shard) are recomputed from the live local planes every sweep, exactly like
the monolithic reference.
"""
from __future__ import annotations

import functools

import jax
import jax.extend.core
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.cfd import poisson


def validate_decomposition(mesh, nx: int, axis: str = "model") -> int:
    """Number of x-slabs for ``mesh``/``axis``, with actionable errors.

    Raises ``ValueError`` (not assert — asserts vanish under ``python -O``)
    when the axis is missing from the mesh or the grid width does not divide
    into equal slabs.  Works on abstract meshes too (shape-only check).
    """
    axes = tuple(mesh.shape.keys()) if hasattr(mesh.shape, "keys") \
        else tuple(mesh.axis_names)
    if axis not in axes:
        raise ValueError(
            f"mesh has no {axis!r} axis (axes: {axes}); build it with a "
            f"spatial axis — e.g. launch.mesh.mesh_for_plan(plan) or "
            f"make_debug_mesh(n_data, n_model) — or pass axis=<name>")
    n_shards = mesh.shape[axis]
    if nx % n_shards:
        lo, hi = nx - nx % n_shards, nx + (-nx) % n_shards
        raise ValueError(
            f"grid width nx={nx} does not split into {n_shards} equal "
            f"x-slabs over mesh axis {axis!r}; use a grid with "
            f"nx % n_ranks == 0 (e.g. nx={lo} or nx={hi}) or a plan whose "
            f"n_ranks divides {nx}")
    return n_shards


def halo_exchange_values(ny: int, packed: bool = True) -> int:
    """Scalars shipped per ppermute message: a full edge column for the
    legacy path, a single-parity half column for the packed path."""
    return -(-ny // 2) if packed else ny


def ppermute_message_shapes(fn, *args, **kw):
    """Trace ``fn(*args, **kw)`` and return the operand shape of every
    ``ppermute`` in the jaxpr (recursing through scans / shard_map / cond
    bodies).  The halo tests use this to pin the exchanged byte count."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    shapes = []

    def sub_jaxprs(v):
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax.extend.core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from sub_jaxprs(item)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "ppermute":
                shapes.extend(tuple(v.aval.shape) for v in eqn.invars)
            for v in eqn.params.values():
                for sub in sub_jaxprs(v):
                    walk(sub)

    walk(closed.jaxpr)
    return shapes


# ---------------------------------------------------------------------------
# legacy full-grid path (odd slab width / height fallback + oracle)
# ---------------------------------------------------------------------------

def _local_sweeps(p, rhs, left_h, right_h, *, idx, n_shards, dx, dy, omega,
                  inner_iters, sweep0, n_sor, n_pairs, col_offset):
    """``inner_iters`` full-grid red-black sweep pairs on a local slab.

    ``left_h``/``right_h`` are the exchanged neighbour halos, frozen for the
    whole call; the domain-edge ghosts come from the live local columns.
    ``sweep0`` is the global index of this call's first sweep pair — pairs
    past ``n_sor`` run un-relaxed (the reference solver's Gauss-Seidel
    polish tail), and pairs past ``n_pairs`` are masked to no-ops so the
    total sweep count matches the caller's ``iters`` exactly even when
    ``inner_iters`` does not divide it.
    """
    ny, bx = p.shape
    dx2, dy2 = dx * dx, dy * dy
    inv_diag = 1.0 / (2.0 / dx2 + 2.0 / dy2)
    jj = jax.lax.broadcasted_iota(jnp.int32, (ny, bx), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (ny, bx), 1) + col_offset
    red = ((ii + jj) % 2 == 0)

    def sweep(p, mask, om):
        left = jnp.where(idx == 0, p[:, :1], left_h)          # Neumann inlet
        right = jnp.where(idx == n_shards - 1, -p[:, -1:],    # Dirichlet out
                          right_h)
        pp = jnp.concatenate([left, p, right], axis=1)
        pp = jnp.concatenate([pp[:1], pp, pp[-1:]], axis=0)   # Neumann walls
        nb = ((pp[1:-1, :-2] + pp[1:-1, 2:]) / dx2
              + (pp[:-2, 1:-1] + pp[2:, 1:-1]) / dy2)
        p_gs = (nb - rhs) * inv_diag
        return jnp.where(mask, (1 - om) * p + om * p_gs, p)

    def body(j, p):
        om = jnp.where(sweep0 + j < n_sor, omega, 1.0)
        active = sweep0 + j < n_pairs
        p = sweep(p, red & active, om)
        return sweep(p, ~red & active, om)

    return jax.lax.fori_loop(0, inner_iters, body, p)


def _decomposed_solve_full(rhs, p0, *, mesh, axis, dx, dy, omega, iters,
                           inner_iters, polish):
    n_shards = mesh.shape[axis]
    bx = rhs.shape[-1] // n_shards
    outer = -(-iters // inner_iters)
    n_sor = iters - min(polish, iters // 2)

    def solve_local(p, rhs):
        idx = jax.lax.axis_index(axis)

        def outer_body(i, p):
            # halo exchange: my rightmost column -> right neighbour's left
            # halo, my leftmost -> left neighbour's right halo (2 ppermutes
            # per outer iteration == 2 MPI messages per rank pair)
            if n_shards > 1:
                from_left = jax.lax.ppermute(
                    p[:, -1:], axis,
                    [(k, k + 1) for k in range(n_shards - 1)])
                from_right = jax.lax.ppermute(
                    p[:, :1], axis,
                    [(k + 1, k) for k in range(n_shards - 1)])
            else:                      # single shard: edge ghosts cover both
                from_left = from_right = jnp.zeros_like(p[:, :1])
            return _local_sweeps(p, rhs, from_left, from_right, idx=idx,
                                 n_shards=n_shards, dx=dx, dy=dy, omega=omega,
                                 inner_iters=inner_iters,
                                 sweep0=i * inner_iters, n_sor=n_sor,
                                 n_pairs=iters, col_offset=idx * bx)

        return jax.lax.fori_loop(0, outer, outer_body, p)

    fn = jax.shard_map(solve_local, mesh=mesh,
                       in_specs=(P(None, axis), P(None, axis)),
                       out_specs=P(None, axis), check_vma=True)
    return fn(p0, rhs)


# ---------------------------------------------------------------------------
# packed-checkerboard path (the default)
# ---------------------------------------------------------------------------

def _decomposed_solve_packed(rhs, p0, *, mesh, axis, dx, dy, omega, iters,
                             inner_iters, polish):
    n_shards = mesh.shape[axis]
    ny = rhs.shape[-2]
    n_sor = iters - min(polish, iters // 2)
    dx2, dy2 = dx * dx, dy * dy
    inv_diag = 1.0 / (2.0 / dx2 + 2.0 / dy2)
    fwd = [(k, k + 1) for k in range(n_shards - 1)]
    bwd = [(k + 1, k) for k in range(n_shards - 1)]

    def solve_local(p, rhs):
        idx = jax.lax.axis_index(axis)
        last = n_shards - 1
        # slab width is even, so every slab starts on an even global column
        # and local packing parity equals global parity
        red, black = poisson.pack_checkerboard(p)
        rhs_r, rhs_b = poisson.pack_checkerboard(rhs)
        row_odd = (jnp.arange(ny) % 2 == 1)[:, None]

        def exchange(col, perm):
            if n_shards == 1:
                return jnp.zeros_like(col)
            return jax.lax.ppermute(col, axis, perm)

        def scatter(half, rows):
            """Half-column ghost: received single-parity values land on their
            row parity; the other rows are never selected by the sweep."""
            return jnp.zeros((ny, 1), half.dtype).at[rows::2, :].set(half)

        def red_half(red, black, lg, rg, om):
            return poisson.packed_half_sweep(
                red, black, rhs_r, lg, rg,
                *poisson.packed_ghost_rows(red, black),
                row_odd, om, dx2, dy2, inv_diag)

        def black_half(red, black, lg, rg, om):
            return poisson.packed_half_sweep(
                black, red, rhs_b, lg, rg,
                *poisson.packed_ghost_rows(black, red),
                ~row_odd, om, dx2, dy2, inv_diag)

        def edge_ghosts(recv_l, rows_l, recv_r, rows_r, own):
            lg = jnp.where(idx == 0, own[:, :1], scatter(recv_l, rows_l))
            rg = jnp.where(idx == last, -own[:, -1:], scatter(recv_r, rows_r))
            return lg, rg

        if inner_iters == 1:
            # tight coupling: half-width exchange before every half-sweep —
            # the decomposed iteration IS the monolithic red-black sweep
            def pair(i, planes):
                red, black = planes
                om = jnp.where(i < n_sor, omega, 1.0)
                # red updates sit on even rows of even columns / odd rows of
                # odd columns, so their west/east ghosts are the neighbour's
                # BLACK edge entries: even rows from the left, odd from the
                # right (and mirrored parities for the black update)
                lg, rg = edge_ghosts(exchange(black[0::2, -1:], fwd), 0,
                                     exchange(black[1::2, :1], bwd), 1, red)
                red = red_half(red, black, lg, rg, om)
                lg, rg = edge_ghosts(exchange(red[1::2, -1:], fwd), 1,
                                     exchange(red[0::2, :1], bwd), 0, black)
                black = black_half(red, black, lg, rg, om)
                return red, black

            red, black = jax.lax.fori_loop(0, iters, pair, (red, black))
        else:
            # block-Jacobi: both parities of the edge columns cross once per
            # outer round (one packed message pair), then stay frozen
            outer = -(-iters // inner_iters)
            h = ny // 2

            def outer_body(i, planes):
                red, black = planes
                from_left = exchange(
                    jnp.concatenate([black[0::2, -1:], red[1::2, -1:]],
                                    axis=0), fwd)
                from_right = exchange(
                    jnp.concatenate([black[1::2, :1], red[0::2, :1]],
                                    axis=0), bwd)

                def body(j, planes):
                    red, black = planes
                    om = jnp.where(i * inner_iters + j < n_sor, omega, 1.0)
                    active = i * inner_iters + j < iters
                    lg, rg = edge_ghosts(from_left[:h], 0,
                                         from_right[:h], 1, red)
                    red_new = red_half(red, black, lg, rg, om)
                    red = jnp.where(active, red_new, red)
                    lg, rg = edge_ghosts(from_left[h:], 1,
                                         from_right[h:], 0, black)
                    black_new = black_half(red, black, lg, rg, om)
                    black = jnp.where(active, black_new, black)
                    return red, black

                return jax.lax.fori_loop(0, inner_iters, body, (red, black))

            red, black = jax.lax.fori_loop(0, outer, outer_body, (red, black))
        return poisson.unpack_checkerboard(red, black)

    fn = jax.shard_map(solve_local, mesh=mesh,
                       in_specs=(P(None, axis), P(None, axis)),
                       out_specs=P(None, axis), check_vma=True)
    return fn(p0, rhs)


def decomposed_solve(rhs, p0=None, *, mesh: Mesh, axis: str = "model",
                     dx: float, dy: float, omega: float = 1.7,
                     iters: int = 60, inner_iters: int = 4,
                     polish: int = 10, packed: bool = None):
    """x-slab + ppermute halo-exchange pressure solve (traceable).

    Exactly ``iters`` red-black sweep pairs run (matching the reference
    solver's work at equal ``iters``); the last ``polish`` pairs run with
    omega = 1, mirroring ``poisson.solve``'s Gauss-Seidel tail.  Sweeps run
    in packed-checkerboard storage with half-width single-parity halos
    whenever the slab width and height are even (``packed=None`` auto;
    ``packed=False`` forces the legacy full-grid frozen-halo path).  See
    the module docstring for the two ``inner_iters`` coupling schedules.
    """
    n_shards = validate_decomposition(mesh, rhs.shape[-1], axis)
    ny = rhs.shape[-2]
    bx = rhs.shape[-1] // n_shards
    if packed is None:
        packed = bx % 2 == 0 and ny % 2 == 0
    elif packed and (bx % 2 or ny % 2):
        raise ValueError(
            f"packed halo sweeps need an even slab width and height, got "
            f"bx={bx}, ny={ny} (nx={rhs.shape[-1]} over {n_shards} ranks); "
            f"pass packed=False or use an even-slab grid")
    p0 = jnp.zeros_like(rhs) if p0 is None else p0
    impl = _decomposed_solve_packed if packed else _decomposed_solve_full
    return impl(rhs, p0, mesh=mesh, axis=axis, dx=dx, dy=dy, omega=omega,
                iters=iters, inner_iters=inner_iters, polish=polish)


def make_decomposed_poisson(mesh: Mesh, nx: int, *, axis: str = "model",
                            dx: float, dy: float, omega: float = 1.7,
                            inner_iters: int = 4, polish: int = 10):
    """Returns a jit'd (rhs, p0, iters is static) -> p solver where the grid
    is decomposed into x-slabs over ``axis`` with explicit halo exchange."""
    validate_decomposition(mesh, nx, axis)

    @functools.partial(jax.jit, static_argnames=("iters",))
    def solve(rhs, p0=None, *, iters: int = 60):
        return decomposed_solve(rhs, p0, mesh=mesh, axis=axis, dx=dx, dy=dy,
                                omega=omega, iters=iters,
                                inner_iters=inner_iters, polish=polish)

    return solve
