"""jit'd wrappers: full pressure solves built from the Pallas slab smoothers.

``rb_sor`` is the drop-in full-grid entry point; since the packed-
checkerboard rewrite it defaults to the packed slab kernel (both planes
VMEM-resident per slab, half the FLOPs/traffic) with ``packed=False``
keeping the original full-grid slab kernel for comparison.  ``rb_sor_planes``
is the plane-level loop ``cfd.poisson.solve`` composes with its packed
polish sweeps, so the pallas backend never round-trips through the full-grid
layout mid-solve.

``rb_sor_solve`` is the packed solve of one env that ``cfd.poisson.solve``
runs on TPU: a ``custom_vmap`` hands ``kernel.rb_sor_batched`` the whole
vmapped env batch in one block, where ``batched_kernel_fits`` says it fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.poisson.kernel import (rb_sor_batched, rb_sor_slabs,
                                          rb_sor_slabs_packed)

# Mosaic's default scoped VMEM limit on the TPU v5e
VMEM_SCOPED_LIMIT = 16 * 2 ** 20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_platform() -> str:
    """The platform the batched kernel's dispatch rule is asked about."""
    return jax.default_backend()


def batched_kernel_fits(platform: str, ny: int, nx: int, batch: int) -> bool:
    """Whether ``rb_sor_batched`` serves a packed solve of ``batch`` envs on
    an (ny, nx) float32 grid: on the TPU, for an even nx and a batch of two
    envs or more, when the kernel's six (ny, batch, nx/2) VMEM planes (four
    in, two out) fit under the scoped limit.  VMEM tiles pad the envs to
    whole groups of 8 sublanes and the packed columns to whole groups of
    128 lanes, so the footprint only grows with the batch.  One env keeps
    the XLA loop: there the kernel saves about 20 µs per solve, less than
    its trace and lowering cost in each single-env program (the flow
    warm-up, a lone ``solver.step``)."""
    if platform != "tpu" or nx % 2 or batch < 2:
        return False
    sublanes, lanes = -(-batch // 8) * 8, -(-(nx // 2) // 128) * 128
    return 6 * ny * sublanes * lanes * 4 <= VMEM_SCOPED_LIMIT


@functools.lru_cache(maxsize=None)
def _batched_solve(dx: float, dy: float, omega: float, iters: int,
                   polish: int):
    """The packed solve for planes (B, ny, W), as a ``custom_vmap`` whose
    rule folds every vmapped axis into B, so the kernel's block holds the
    whole env batch however many ``vmap`` levels built it."""
    from repro.cfd import poisson
    n_sor = iters - min(polish, iters // 2)

    @jax.custom_batching.custom_vmap
    def solve(red, black, rhs_r, rhs_b):
        batch, ny, w = red.shape
        if batched_kernel_fits(kernel_platform(), ny, 2 * w, batch):
            return rb_sor_batched(red, black, rhs_r, rhs_b, dx=dx, dy=dy,
                                  omega=omega, iters=iters, polish=polish,
                                  interpret=not _on_tpu())
        loop = functools.partial(poisson.packed_sor_loop, omega=omega, dx=dx,
                                 dy=dy, iters=iters, n_sor=n_sor)
        return jax.vmap(loop)(red, black, rhs_r, rhs_b)

    @solve.def_vmap
    def _rule(axis_size, in_batched, *planes):
        planes = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                  for a, b in zip(planes, in_batched)]
        out = solve(*(a.reshape((-1,) + a.shape[2:]) for a in planes))
        return (tuple(a.reshape((axis_size, -1) + a.shape[1:]) for a in out),
                (True, True))

    return solve


def rb_sor_solve(red, black, rhs_r, rhs_b, *, dx: float, dy: float,
                 omega: float, iters: int, polish: int):
    """``cfd.poisson.packed_sor_loop``'s solve of one env's (ny, W) planes,
    run by ``rb_sor_batched`` over the whole batch when this is vmapped and
    ``batched_kernel_fits`` takes the batch, else by the XLA loop."""
    solve = _batched_solve(float(dx), float(dy), float(omega), iters, polish)
    red, black = solve(red[None], black[None], rhs_r[None], rhs_b[None])
    return red[0], black[0]


def _pick_nslabs(nx: int) -> int:
    """Widest slab count keeping (ny, bx) around <= 512 lanes with bx even."""
    nslabs = max(1, nx // 512)
    while nx % nslabs or (nx // nslabs) % 2:
        nslabs -= 1
    return nslabs


@functools.partial(jax.jit,
                   static_argnames=("dx", "dy", "iters", "omega", "nslabs",
                                    "inner_iters", "interpret"))
def rb_sor_planes(red, black, rhs_r, rhs_b, dx, dy, *, iters: int = 60,
                  omega: float = 1.7, nslabs: int = 0, inner_iters: int = 4,
                  interpret: bool = None):
    """``iters`` SOR iterations on packed planes via the packed slab kernel.

    Planes come from ``cfd.poisson.pack_checkerboard``; global iterations map
    to outer block-Jacobi rounds of ``inner_iters`` VMEM-resident sweep pairs
    each.  Returns the smoothed (red, black) planes — callers that need the
    full grid unpack at their own boundary."""
    w = red.shape[1]
    if interpret is None:
        interpret = not _on_tpu()
    if nslabs == 0:
        nslabs = _pick_nslabs(2 * w)
    outer = -(-iters // inner_iters) if iters > 0 else 0

    def body(_, planes):
        return rb_sor_slabs_packed(*planes, rhs_r, rhs_b, dx=float(dx),
                                   dy=float(dy), omega=omega, nslabs=nslabs,
                                   inner_iters=inner_iters,
                                   interpret=interpret)

    return jax.lax.fori_loop(0, outer, body, (red, black))


@functools.partial(jax.jit,
                   static_argnames=("dx", "dy", "iters", "omega", "nslabs",
                                    "inner_iters", "interpret", "packed"))
def rb_sor(rhs, dx, dy, *, iters: int = 60, omega: float = 1.7, p0=None,
           nslabs: int = 0, inner_iters: int = 4, interpret: bool = None,
           packed: bool = True):
    """Drop-in replacement for cfd.poisson.solve backed by the Pallas kernel.

    ``iters`` global SOR iterations are mapped to outer block-Jacobi rounds of
    ``inner_iters`` VMEM-resident sweeps each.  ``packed=True`` (default)
    runs the packed-checkerboard slab kernel; ``packed=False`` keeps the
    original full-grid slab kernel (the masked-update oracle).
    """
    nx = rhs.shape[1]
    if nx % 2:
        raise ValueError(
            f"rb_sor requires an even grid width for checkerboard slab "
            f"parity, got nx={nx}; use cfd.poisson.solve (it falls back to "
            f"the jnp path for odd widths)")
    if interpret is None:
        interpret = not _on_tpu()
    if nslabs == 0:
        nslabs = _pick_nslabs(nx)
    p = jnp.zeros_like(rhs) if p0 is None else p0

    if packed:
        from repro.cfd.poisson import pack_checkerboard, unpack_checkerboard
        planes = rb_sor_planes(*pack_checkerboard(p), *pack_checkerboard(rhs),
                               dx, dy, iters=iters, omega=omega,
                               nslabs=nslabs, inner_iters=inner_iters,
                               interpret=interpret)
        return unpack_checkerboard(*planes)

    outer = -(-iters // inner_iters)

    def body(_, p):
        return rb_sor_slabs(p, rhs, dx=float(dx), dy=float(dy),
                            omega=omega, nslabs=nslabs,
                            inner_iters=inner_iters, interpret=interpret)

    return jax.lax.fori_loop(0, outer, body, p)
