"""Pallas megakernel: one fused solver dt with every field VMEM-resident.

One ``pallas_call`` per dt advances momentum (advect-diffuse + penalization
+ fused BC/outlet-mass-correction), the packed red-black SOR projection, and
the velocity correction without the fields ever leaving VMEM — ``u``, ``v``,
both packed pressure parity planes, and the closed-over geometry are kernel
operands held on-chip for the whole dt (~50 SOR sweep pairs included).
``solver.step_interval(backend="fused")`` scans this kernel over the
actuation interval, so across the 50-dt interval the only HBM traffic is
the scan carry hand-off between consecutive kernel launches.

The body is NOT re-implemented here: the kernel calls the same
``ops.fused_dt`` the jnp tier lowers (which itself calls
``solver._momentum`` and the ``poisson.packed_half_sweep`` stencil) — pure
jnp, so it traces inside the kernel unchanged.  One momentum and one
stencil implementation serve reference, packed, halo, pallas-Poisson, and
this megakernel.

On non-TPU hosts the kernel runs in interpret mode for correctness tests
(tests/test_fused_interval.py gates pallas-vs-jnp parity); the production
CPU path is the jnp tier (ops.select_tier), which carries the same fusion
structure without Pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.cfd.grid import GridConfig

# GeomArrays field order (repro.cfd.solver.GeomArrays._fields) — the kernel
# takes them as individual refs so every mask/target lives in VMEM too
_N_GEOM = 11


def _select(n: int, w: int, offset: int, transpose: bool = False):
    """0/1 matrix picking full-grid column ``2k + offset`` for packed column
    ``k``: shape (n, w), or (w, n) when ``transpose``."""
    shape = (w, n) if transpose else (n, w)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transpose else 0)
    k = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transpose else 1)
    return (i == 2 * k + offset).astype(jnp.float32)


def _dot(a, b):
    # every output sums one exact product with zeros, so at fp32 contract
    # precision the selection is exact; a bf16 pass would round the fields
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _row_odd(ny: int):
    return jax.lax.broadcasted_iota(jnp.int32, (ny, 1), 0) % 2 == 1


def pack_mxu(a):
    """``poisson.pack_checkerboard`` as two selection matmuls.  Mosaic lowers
    neither the (ny, nx//2, 2) reshape nor a lane-strided slice of an
    unaligned plane; the MXU deinterleaves the columns instead."""
    ny, nx = a.shape
    even = _dot(a, _select(nx, nx // 2, 0))
    odd = _dot(a, _select(nx, nx // 2, 1))
    row_odd = _row_odd(ny)
    return jnp.where(row_odd, odd, even), jnp.where(row_odd, even, odd)


def unpack_mxu(red, black):
    """Inverse of :func:`pack_mxu` (``poisson.unpack_checkerboard``)."""
    ny, w = red.shape
    row_odd = _row_odd(ny)
    even = jnp.where(row_odd, black, red)
    odd = jnp.where(row_odd, red, black)
    return (_dot(even, _select(2 * w, w, 0, transpose=True))
            + _dot(odd, _select(2 * w, w, 1, transpose=True)))


def _fused_dt_kernel(*refs, cfg: GridConfig):
    from repro.cfd.solver import GeomArrays
    from repro.kernels.actuation.ops import fused_dt

    (u_ref, v_ref, red_ref, black_ref), rest = refs[:4], refs[4:]
    geom_refs, rest = rest[:_N_GEOM], rest[_N_GEOM:]
    (jet_ref, re_ref, mode_ref), outs = rest[:3], rest[3:]
    u_out, v_out, red_out, black_out, cd_out, cl_out = outs

    ga = GeomArrays(*(r[...] for r in geom_refs))
    u2, v2, red2, black2, cd, cl = fused_dt(
        cfg, ga, u_ref[...], v_ref[...], red_ref[...], black_ref[...],
        jet_ref[0, 0], re_ref[0, 0], mode_ref[0, 0],
        pack=pack_mxu, unpack=unpack_mxu)
    u_out[...] = u2
    v_out[...] = v2
    red_out[...] = red2
    black_out[...] = black2
    cd_out[...] = jnp.reshape(cd, (1, 1))
    cl_out[...] = jnp.reshape(cl, (1, 1))


def fused_step(cfg: GridConfig, ga, u, v, red, black, jet_vel, re, act_mode,
               *, interpret: bool):
    """One dt through the megakernel.  Mirrors ``ops.fused_dt``'s signature
    and return ``(u, v, red, black, cd, cl)``; scalars ride as (1, 1)
    operands so the whole dt is a single launch."""
    f32 = jnp.float32
    scalar = lambda x: jnp.reshape(jnp.asarray(x, f32), (1, 1))
    # the megakernel serves the scalar-actuation path only (step_interval
    # falls back to the reference backend for per-body vector jets), so the
    # per-body rotation targets / ownership masks never ride as kernel refs;
    # the inlet profile rides as a (ny, 1) column (Mosaic wants 2-D refs)
    ga = ga._replace(rotb_u=None, rotb_v=None, own_u=None, own_v=None,
                     inlet_u=jnp.reshape(ga.inlet_u, (-1, 1)))
    geom = [g for g in ga if g is not None]
    kern = functools.partial(_fused_dt_kernel, cfg=cfg)
    out_shape = [
        jax.ShapeDtypeStruct(u.shape, u.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
        jax.ShapeDtypeStruct(red.shape, red.dtype),
        jax.ShapeDtypeStruct(black.shape, black.dtype),
        jax.ShapeDtypeStruct((1, 1), f32),
        jax.ShapeDtypeStruct((1, 1), f32),
    ]
    outs = pl.pallas_call(kern, out_shape=out_shape, interpret=interpret,
                          name="actuation_fused_dt")(
        u, v, red, black, *geom,
        scalar(jet_vel), scalar(re), scalar(act_mode))
    u2, v2, red2, black2, cd, cl = outs
    return u2, v2, red2, black2, cd[0, 0], cl[0, 0]
