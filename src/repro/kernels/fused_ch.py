"""Fused Cahn–Hilliard explicit-RHS kernels (beyond-paper optimisation).

The paper's solver builds the RHS of scheme eq. (2a) from *four* separate
stencil sweeps (two cuSten calls for the linear terms, one Fun call for the
nonlinear Laplacian, plus axpy combinations) — each reading and writing the
full field through HBM.  On TPU the whole expression

    rhs = -(2/3)(C^n - C^{n-1})
          - (2/3) dt gamma D  grad^4 (2 C^n - C^{n-1})
          + (2/3) D dt        grad^2 ((C^n)^3 - C^n)

fits in one VMEM pass over a halo-2 3x3 tile neighbourhood of C^n and
C^{n-1}: a ~4x cut in HBM traffic for the memory-bound explicit half of the
ADI step.  The oracle is :func:`repro.kernels.ref.ch_rhs_ref`.

:func:`ch_rhs_xsweep_pallas` goes one step further — the ADI hot loop's
full explicit half *plus* the implicit x-sweep in one ``pallas_call``: the
RHS tile is assembled in VMEM and immediately consumed by the row-layout
pentadiagonal substitution of :mod:`repro.kernels.penta`, Woodbury
closure included.  The RHS never round-trips through HBM and no
transpose of the field passes through HBM: the band's 128-lane chunks
are transposed in VMEM so the recurrence runs on sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.penta import (
    VMEM_LIMIT_BYTES,
    _fac_table,
    _pad,
    _smem_table_spec,
    rows_woodbury_correct,
    sweep_rows_refs,
    tpu_sweep_problem,
    woodbury_rows,
)
from repro.util import block_spec, wrap_block

_H = 2  # biharmonic halo


def _band_window(band, ty, tx):
    """Return shift(dy, dx) -> (ty, tx) view of a (ty+4, tx+4) band."""

    def shift(dyy, dxx):
        return jax.lax.slice(
            band, (_H + dyy, _H + dxx), (_H + dyy + ty, _H + dxx + tx)
        )

    return shift


def _laplacian(sh, inv_h2):
    return inv_h2 * (
        sh(-1, 0) + sh(1, 0) + sh(0, -1) + sh(0, 1) - 4.0 * sh(0, 0)
    )


def _biharmonic(sh, inv_h4):
    dx2 = sh(0, -2) - 4 * sh(0, -1) + 6 * sh(0, 0) - 4 * sh(0, 1) + sh(0, 2)
    dy2 = sh(-2, 0) - 4 * sh(-1, 0) + 6 * sh(0, 0) - 4 * sh(1, 0) + sh(2, 0)
    # delta_x delta_y: 3x3 cross term (needs the corner halos)
    dxdy = (
        sh(-1, -1) - 2 * sh(-1, 0) + sh(-1, 1)
        - 2 * (sh(0, -1) - 2 * sh(0, 0) + sh(0, 1))
        + sh(1, -1) - 2 * sh(1, 0) + sh(1, 1)
    )
    return inv_h4 * (dx2 + dy2 + 2.0 * dxdy)


def _ch_kernel(*refs, dt, D, gamma, inv_h2, inv_h4, ty, tx):
    # refs: 9 tiles of c_n, 9 tiles of c_nm1, out
    cn_tiles = [r[...] for r in refs[:9]]
    cm_tiles = [r[...] for r in refs[9:18]]
    o_ref = refs[-1]

    def assemble(tiles):
        rows = []
        for a in range(3):
            l, c, r = tiles[3 * a], tiles[3 * a + 1], tiles[3 * a + 2]
            rows.append(
                jnp.concatenate([l[:, tx - _H :], c, r[:, :_H]], axis=1)
            )
        return jnp.concatenate(
            [rows[0][ty - _H :, :], rows[1], rows[2][:_H, :]], axis=0
        )

    cn = assemble(cn_tiles)  # (ty+4, tx+4) band
    cm = assemble(cm_tiles)
    cbar = 2.0 * cn - cm
    nl = cn * cn * cn - cn  # (C^3 - C) on the band (recomputed in-halo:
    # cheap VPU flops traded for an entire HBM pass — the fusion's point)

    sh_cb = _band_window(cbar, ty, tx)
    sh_nl = _band_window(nl, ty, tx)
    sh_cn = _band_window(cn, ty, tx)
    sh_cm = _band_window(cm, ty, tx)

    lin = -(2.0 / 3.0) * (sh_cn(0, 0) - sh_cm(0, 0))
    hyper = -(2.0 / 3.0) * dt * gamma * D * _biharmonic(sh_cb, inv_h4)
    nonlin = (2.0 / 3.0) * D * dt * _laplacian(sh_nl, inv_h2)
    o_ref[...] = (lin + hyper + nonlin).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("dt", "D", "gamma", "inv_h2", "inv_h4", "ty", "tx", "interpret"),
)
def ch_rhs_pallas(
    c_n: jnp.ndarray,
    c_nm1: jnp.ndarray,
    *,
    dt: float,
    D: float,
    gamma: float,
    inv_h2: float,
    inv_h4: float,
    ty: int = 128,
    tx: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    ny, nx = c_n.shape
    if ny % ty or nx % tx:
        raise ValueError(f"tile ({ty},{tx}) must divide field ({ny},{nx})")
    gy, gx = ny // ty, nx // tx

    def spec(dj, di):
        return block_spec(
            (ty, tx), lambda j, i: (wrap_block(j + dj, gy), wrap_block(i + di, gx))
        )

    neigh = [(dj, di) for dj in (-1, 0, 1) for di in (-1, 0, 1)]
    in_specs = [spec(dj, di) for dj, di in neigh] * 2
    operands = [c_n] * 9 + [c_nm1] * 9
    return pl.pallas_call(
        functools.partial(
            _ch_kernel, dt=dt, D=D, gamma=gamma,
            inv_h2=inv_h2, inv_h4=inv_h4, ty=ty, tx=tx,
        ),
        grid=(gy, gx),
        in_specs=in_specs,
        out_specs=block_spec((ty, tx), lambda j, i: (j, i)),
        out_shape=jax.ShapeDtypeStruct((ny, nx), c_n.dtype),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Fused RHS + x-sweep: the whole eq.-(2a) explicit half and the L_x solve
# in one pallas_call (full-width row-band tiles, gx == 1)
# ---------------------------------------------------------------------------


def _ch_xsweep_kernel(
    *refs, dt, D, gamma, inv_h2, inv_h4, ty, hb, nx,
):
    # refs: c_n's hb-row halo block above, (ty, nx) row band, halo block
    #       below; the same three of c_nm1; the (5, nx) SMEM factor table;
    #       W^T (4, nx); out (ty, nx); the (nx, ty) VMEM scratch
    cn_tiles = [r[...] for r in refs[:3]]
    cm_tiles = [r[...] for r in refs[3:6]]
    f_ref, wt_ref, o_ref, t_ref = refs[6:]

    def assemble(above, mid, below):
        band = jnp.concatenate([above[hb - _H :, :], mid, below[:_H, :]], axis=0)
        return jnp.concatenate(
            [band[:, nx - _H :], band, band[:, :_H]], axis=1
        )  # periodic x wrap inside the full-width band

    cn = assemble(*cn_tiles)  # (ty+4, nx+4)
    cm = assemble(*cm_tiles)
    cbar = 2.0 * cn - cm
    nl = cn * cn * cn - cn

    sh_cb = _band_window(cbar, ty, nx)
    sh_nl = _band_window(nl, ty, nx)
    sh_cn = _band_window(cn, ty, nx)
    sh_cm = _band_window(cm, ty, nx)

    lin = -(2.0 / 3.0) * (sh_cn(0, 0) - sh_cm(0, 0))
    hyper = -(2.0 / 3.0) * dt * gamma * D * _biharmonic(sh_cb, inv_h4)
    nonlin = (2.0 / 3.0) * D * dt * _laplacian(sh_nl, inv_h2)
    o_ref[...] = (lin + hyper + nonlin).astype(o_ref.dtype)

    # Row-layout substitution in place (the RHS never leaves VMEM; its
    # lane chunks are transposed through t_ref so the recurrence walks
    # sublanes), then the Woodbury closure — both shared with
    # kernels/penta.py so the fused kernel stays in lockstep with the
    # standalone solve.
    sweep_rows_refs(f_ref, o_ref, o_ref, t_ref)
    o_ref[...] = rows_woodbury_correct(o_ref[...], wt_ref[...]).astype(
        o_ref.dtype
    )


# VMEM the fused kernel's row band may take, under VMEM_LIMIT_BYTES: the
# compiler also keeps Mosaic's own relayout copies there.  A taller band
# halves the serial chain of the x recurrence, so the budget admits the
# tallest band that AOT compiles for a v5e (ty = 128 at nx = 4096, about
# 41 MiB by this estimate; ty = 256 there, about 80 MiB, does not).
XSWEEP_VMEM_BUDGET = 48 * 2**20


def xsweep_vmem_bytes(ty: int, nx: int, itemsize: int = 4) -> int:
    """Estimated VMEM of one fused-kernel grid step: the double-buffered
    row bands and halo blocks of both fields and the output band, about
    a dozen (ty+4, nx+4) band temporaries (cn, cm, cbar, nl and the
    stencil terms), and the (nx, ty) transpose scratch of the sweep, each
    padded to the (8, 128) tile."""
    blocks = 2 * (2 * (ty + 16) + ty) * nx
    temps = 12 * _pad(ty + 2 * _H, 8) * _pad(nx + 2 * _H, 128)
    scratch = _pad(nx, 8) * _pad(ty, 128)
    return (blocks + temps + scratch) * itemsize


def xsweep_tile(ny: int, nx: int, itemsize: int = 4) -> int:
    """Largest 8-aligned row band dividing ``ny`` whose step fits
    :data:`XSWEEP_VMEM_BUDGET` (at least 8 where 8 divides ``ny``)."""
    fits = [
        t for t in (256, 128, 64, 32, 16, 8)
        if ny % t == 0 and xsweep_vmem_bytes(t, nx, itemsize) <= XSWEEP_VMEM_BUDGET
    ]
    return fits[0] if fits else (8 if ny % 8 == 0 else ny)


def xsweep_tpu_problem(ny: int, nx: int, ty: int, dtype) -> str | None:
    """Why :func:`ch_rhs_xsweep_pallas` cannot be compiled for a TPU with
    ``ty``-row bands, or ``None`` when it can: the band step, transpose
    scratch included, fits :data:`XSWEEP_VMEM_BUDGET`, and the row-layout
    sweep's own rules hold (``nx`` a multiple of 8, transposed in chunks
    of 128 lanes or of all of ``nx``)."""
    if ty < _H:
        return f"row tile {ty} is below the halo {_H}"
    if xsweep_vmem_bytes(ty, nx, jnp.dtype(dtype).itemsize) > XSWEEP_VMEM_BUDGET:
        return f"a ({ty}, {nx}) row band exceeds the VMEM budget"
    return tpu_sweep_problem(nx, ny, ty, dtype, lanes=True)


@functools.partial(
    jax.jit,
    static_argnames=(
        "dt", "D", "gamma", "inv_h2", "inv_h4", "ty", "interpret",
    ),
)
def ch_rhs_xsweep_pallas(
    c_n: jnp.ndarray,
    c_nm1: jnp.ndarray,
    fac_x,
    *,
    dt: float,
    D: float,
    gamma: float,
    inv_h2: float,
    inv_h4: float,
    ty: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """One ``pallas_call`` computing ``L_x^{-1} rhs(c_n, c_nm1)``.

    ``fac_x`` is a :class:`repro.kernels.penta.CyclicPentaFactors` of
    length ``nx``.  Tiles are full-width row bands (the x recurrence
    needs the whole x extent in VMEM, transposed there into an
    (nx, ty) scratch); the grid walks the y axis.  The
    y halos come from the 8-row blocks above and below the band (the
    whole band where ``ty`` is not a multiple of 8).
    """
    ny, nx = c_n.shape
    if ny % ty:
        raise ValueError(f"row tile {ty} must divide ny={ny}")
    if ty < _H:
        raise ValueError(f"row tile {ty} must be >= halo {_H}")
    gy = ny // ty
    hb = 8 if ty % 8 == 0 else ty  # halo block rows
    r = ty // hb  # halo blocks per band
    nh = ny // hb

    band = block_spec((ty, nx), lambda j: (j, 0))
    above = block_spec(
        (hb, nx), lambda j: (wrap_block(j * r - 1, nh), 0)
    )
    below = block_spec(
        (hb, nx), lambda j: (wrap_block(j * r + r, nh), 0)
    )
    in_specs = [above, band, below] * 2 + [
        _smem_table_spec(nx),
        block_spec((4, nx), lambda j: (0, 0)),
    ]
    operands = [c_n] * 3 + [c_nm1] * 3 + [_fac_table(fac_x.band), woodbury_rows(fac_x.w)]
    return pl.pallas_call(
        functools.partial(
            _ch_xsweep_kernel, dt=dt, D=D, gamma=gamma,
            inv_h2=inv_h2, inv_h4=inv_h4, ty=ty, hb=hb, nx=nx,
        ),
        grid=(gy,),
        in_specs=in_specs,
        out_specs=band,
        out_shape=jax.ShapeDtypeStruct((ny, nx), c_n.dtype),
        scratch_shapes=[pltpu.VMEM((nx, ty), c_n.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
