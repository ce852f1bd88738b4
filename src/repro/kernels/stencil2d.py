"""Generic 2D stencil Pallas kernel (the cuSten compute kernel, TPU-native).

CUDA cuSten stages a block + halo ring into shared memory and lets one thread
compute each output point.  The TPU equivalent implemented here:

- the grid tiles the field into ``(Ty, Tx)`` VMEM blocks via ``BlockSpec``;
- halos are obtained by passing the *same* input array several times with
  neighbouring ``index_map``s (wrap for periodic, clamp for non-periodic) —
  the Pallas analogue of cuSten's halo loads, including the 3x3 corner-halo
  neighbourhood the paper's XY kernels need;
- inside the kernel a contiguous band ``(Ty + top + bottom, Tx + left +
  right)`` is assembled in VMEM and the stencil is evaluated as whole-tile
  shifted-window FMAs on the VPU (instead of per-thread scalar loops);
- the "function pointer" mode is a traceable ``point_fn(windows, coeffs)``
  traced straight into the kernel body.

Constraints (checked by :mod:`repro.kernels.ops`, which falls back to the
jnp oracle otherwise): tile sizes must divide the field and the halo extents
must not exceed the neighbouring tile (``max(left,right) <= Tx`` etc.).
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import weighted_point_fn
from repro.util import block_spec, clamp_block, wrap_block


def _neighbour_index_map(dj: int, di: int, gy: int, gx: int, bc: str):
    """Block index map selecting the (dj, di) neighbour tile."""
    move = wrap_block if bc == "periodic" else clamp_block

    def index_map(j, i):
        jj = move(j + dj, gy) if dj else j
        ii = move(i + di, gx) if di else i
        return (jj, ii)

    return index_map


def _stencil_kernel(
    *refs,
    point_fn: Callable,
    left: int,
    right: int,
    top: int,
    bottom: int,
    hx: int,
    hy: int,
    bc: str,
    ny: int,
    nx: int,
    ty: int,
    tx: int,
    n_tiles_x: int,
    n_tiles_y: int,
):
    """Kernel body.  ``refs`` layout:

    [tile(dj,di) for dj in -1..1 for di in -1..1 if needed] + [coeffs,
    out_init?] + [out].
    The tile list is ordered row-major over the needed neighbourhood.
    """
    need_x = hx > 0
    need_y = hy > 0
    djs = (-1, 0, 1) if need_y else (0,)
    dis = (-1, 0, 1) if need_x else (0,)

    n_tiles = len(djs) * len(dis)
    tile_refs = refs[:n_tiles]
    coeffs_ref = refs[n_tiles]
    has_init = bc == "np"
    out_init_ref = refs[n_tiles + 1] if has_init else None
    out_ref = refs[-1]

    tiles = {}
    k = 0
    for dj in djs:
        for di in dis:
            tiles[(dj, di)] = tile_refs[k][...]
            k += 1

    # Assemble the halo band in VMEM.  Rows first, then columns.
    def row_band(di):
        mid = tiles[(0, di)]
        if not need_y:
            return mid
        upper = tiles[(-1, di)][ty - hy :, :]
        lower = tiles[(1, di)][:hy, :]
        return jnp.concatenate([upper, mid, lower], axis=0)

    band = row_band(0)
    if need_x:
        lband = row_band(-1)[:, tx - hx :]
        rband = row_band(1)[:, :hx]
        band = jnp.concatenate([lband, band, rband], axis=1)

    coeffs = coeffs_ref[...]

    windows = []
    for a in range(top + bottom + 1):
        r0 = hy - top + a
        for b in range(left + right + 1):
            c0 = hx - left + b
            windows.append(
                jax.lax.slice(band, (r0, c0), (r0 + ty, c0 + tx))
            )
    val = point_fn(windows, coeffs)

    if bc == "np":
        j = pl.program_id(0)
        i = pl.program_id(1)
        gj = j * ty + jax.lax.broadcasted_iota(jnp.int32, (ty, tx), 0)
        gi = i * tx + jax.lax.broadcasted_iota(jnp.int32, (ty, tx), 1)
        mask = (
            (gi >= left)
            & (gi < nx - right)
            & (gj >= top)
            & (gj < ny - bottom)
        )
        val = jnp.where(mask, val, out_init_ref[...])

    out_ref[...] = val.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "point_fn",
        "left",
        "right",
        "top",
        "bottom",
        "bc",
        "ty",
        "tx",
        "interpret",
    ),
)
def stencil2d_pallas(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = weighted_point_fn,
    left: int = 0,
    right: int = 0,
    top: int = 0,
    bottom: int = 0,
    bc: str = "periodic",
    ty: int = 128,
    tx: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Apply a 2D stencil with a Pallas kernel.

    ``data``: (ny, nx). ``coeffs``: 1D array fed to ``point_fn``.
    ``out_init``: required for ``bc='np'`` — boundary cells pass through.
    """
    ny, nx = data.shape
    if ny % ty or nx % tx:
        raise ValueError(f"tile ({ty},{tx}) must divide field ({ny},{nx})")
    hx = max(left, right)
    hy = max(top, bottom)
    if hx > tx or hy > ty:
        raise ValueError(f"halo ({hy},{hx}) exceeds tile ({ty},{tx})")
    gy, gx = ny // ty, nx // tx

    need_x = hx > 0
    need_y = hy > 0
    djs = (-1, 0, 1) if need_y else (0,)
    dis = (-1, 0, 1) if need_x else (0,)

    in_specs = []
    operands = []
    for dj in djs:
        for di in dis:
            in_specs.append(
                block_spec(
                    (ty, tx), _neighbour_index_map(dj, di, gy, gx, bc)
                )
            )
            operands.append(data)

    # coefficients: whole (small) array in VMEM for every program
    in_specs.append(block_spec(coeffs.shape, lambda j, i: (0,) * coeffs.ndim))
    operands.append(coeffs)

    if bc == "np":
        if out_init is None:
            out_init = jnp.zeros_like(data)
        in_specs.append(block_spec((ty, tx), lambda j, i: (j, i)))
        operands.append(out_init)

    kernel = functools.partial(
        _stencil_kernel,
        point_fn=point_fn,
        left=left,
        right=right,
        top=top,
        bottom=bottom,
        hx=hx,
        hy=hy,
        bc=bc,
        ny=ny,
        nx=nx,
        ty=ty,
        tx=tx,
        n_tiles_x=gx,
        n_tiles_y=gy,
    )

    return pl.pallas_call(
        kernel,
        grid=(gy, gx),
        in_specs=in_specs,
        out_specs=block_spec((ty, tx), lambda j, i: (j, i)),
        out_shape=jax.ShapeDtypeStruct((ny, nx), data.dtype),
        interpret=interpret,
    )(*operands)
