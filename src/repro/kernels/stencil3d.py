"""3D stencil Pallas kernel — the paper's §VI.A extension, built.

cuSten defers 3D because UM tile streaming needs contiguity; on TPU the
problem disappears: ``BlockSpec`` tiles the (z, y) axes (3×3 neighbour
tiles supply the z/y halos exactly like the 2D XY kernel) while each block
carries the **full x row**, so x-halos are in-VMEM rolls.  VMEM budget:
9 tiles of (Tz, Ty, nx) — for the default (4, 8, nx≤2048) f32 that is
9 × 256 KiB ≈ 2.3 MiB.

Supports arbitrary box stencils (fr/bk, tp/bt, lf/rt halos), weighted or
function mode, periodic / np boundaries; in weighted mode a static tap
set (``taps``) builds only the windows whose weight is not zero.  Oracle:
:func:`repro.kernels.ref.stencil3d_ref`.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.kernels.ref import weighted_point_fn
from repro.util import block_spec, clamp_block, wrap_block


def _kernel(
    *refs,
    point_fn: Callable,
    halos,
    hz: int,
    hy: int,
    bc: str,
    shape,
    tz: int,
    ty: int,
    taps,
):
    fr, bk, tp, bt, lf, rt = halos
    nz, ny, nx = shape
    need_z, need_y = hz > 0, hy > 0
    dzs = (-1, 0, 1) if need_z else (0,)
    dys = (-1, 0, 1) if need_y else (0,)
    n_tiles = len(dzs) * len(dys)
    tile_refs = refs[:n_tiles]
    coeffs = refs[n_tiles][...]
    has_init = bc == "np"
    out_init_ref = refs[n_tiles + 1] if has_init else None
    o_ref = refs[-1]

    tiles = {}
    k = 0
    for dz in dzs:
        for dy in dys:
            tiles[(dz, dy)] = tile_refs[k][...]
            k += 1

    def zband(dy):
        mid = tiles[(0, dy)]
        if not need_z:
            return mid
        up = tiles[(-1, dy)][tz - hz :, :, :]
        dn = tiles[(1, dy)][:hz, :, :]
        return jnp.concatenate([up, mid, dn], axis=0)

    band = zband(0)
    if need_y:
        tb = zband(-1)[:, ty - hy :, :]
        bb = zband(1)[:, :hy, :]
        band = jnp.concatenate([tb, band, bb], axis=1)

    sy, sx = tp + bt + 1, lf + rt + 1
    keep = range((fr + bk + 1) * sy * sx) if taps is None else taps
    subs = {}
    windows = []
    for k in keep:
        c, rem = divmod(k, sy * sx)
        a, b = divmod(rem, sx)
        if (c, a) not in subs:
            z0, y0 = hz - fr + c, hy - tp + a
            subs[(c, a)] = jax.lax.slice(
                band, (z0, y0, 0), (z0 + tz, y0 + ty, nx)
            )
        # x-halo via in-VMEM roll on the full row (a zero shift is no
        # roll: Mosaic refuses the empty slice it makes)
        shift = lf - b
        sub = subs[(c, a)]
        windows.append(jnp.roll(sub, shift, axis=2) if shift else sub)
    if taps is None:
        val = point_fn(windows, coeffs)
    else:
        # the all-taps sum in the same order, less its exact-zero products
        val = weighted_point_fn(windows, [coeffs[k] for k in taps])

    if bc == "np":
        zi = pl.program_id(0)
        yi = pl.program_id(1)
        gz = zi * tz + jax.lax.broadcasted_iota(jnp.int32, (tz, ty, nx), 0)
        gy = yi * ty + jax.lax.broadcasted_iota(jnp.int32, (tz, ty, nx), 1)
        gx = jax.lax.broadcasted_iota(jnp.int32, (tz, ty, nx), 2)
        mask = (
            (gz >= fr) & (gz < nz - bk)
            & (gy >= tp) & (gy < ny - bt)
            & (gx >= lf) & (gx < nx - rt)
        )
        val = jnp.where(mask, val, out_init_ref[...])
    o_ref[...] = val.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "point_fn", "halos", "bc", "tz", "ty", "taps", "interpret",
    ),
)
def stencil3d_pallas(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = weighted_point_fn,
    halos=(1, 1, 1, 1, 1, 1),  # (front, back, top, bottom, left, right)
    bc: str = "periodic",
    tz: int = 4,
    ty: int = 8,
    taps: tuple[int, ...] | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Apply a 3D box stencil on ``(nz, ny, nx)`` by ``(tz, ty)`` blocks.

    ``taps`` (weighted mode only): the ascending flat indices of the
    weights that are not zero.  The kernel then builds, rolls and sums
    those windows alone; ``None`` builds every window of the box.  The
    weights stay a runtime operand either way.
    """
    nz, ny, nx = data.shape
    fr, bk, tp, bt, lf, rt = halos
    hz, hy = max(fr, bk), max(tp, bt)
    if nz % tz or ny % ty:
        raise ValueError(f"tiles ({tz},{ty}) must divide ({nz},{ny})")
    if hz > tz or hy > ty or max(lf, rt) > nx:
        raise ValueError("halo exceeds tile")
    gz, gy = nz // tz, ny // ty
    if taps is not None:
        if point_fn is not weighted_point_fn:
            raise ValueError("taps needs the weighted point_fn")
        obs.add("stencil3d.sparse_applies", 1)
        n_win = (fr + bk + 1) * (tp + bt + 1) * (lf + rt + 1)
        obs.add("stencil3d.taps_skipped", n_win - len(taps))

    move = wrap_block if bc == "periodic" else clamp_block

    def spec(dz, dy):
        def index_map(k, j):
            kk = move(k + dz, gz) if dz else k
            jj = move(j + dy, gy) if dy else j
            return (kk, jj, 0)

        return block_spec((tz, ty, nx), index_map)

    need_z, need_y = hz > 0, hy > 0
    dzs = (-1, 0, 1) if need_z else (0,)
    dys = (-1, 0, 1) if need_y else (0,)
    in_specs = [spec(dz, dy) for dz in dzs for dy in dys]
    operands = [data] * len(in_specs)
    in_specs.append(block_spec(coeffs.shape, lambda k, j: (0,) * coeffs.ndim))
    operands.append(coeffs)
    if bc == "np":
        if out_init is None:
            out_init = jnp.zeros_like(data)
        in_specs.append(block_spec((tz, ty, nx), lambda k, j: (k, j, 0)))
        operands.append(out_init)

    return pl.pallas_call(
        functools.partial(
            _kernel, point_fn=point_fn, halos=halos, hz=hz, hy=hy,
            bc=bc, shape=(nz, ny, nx), tz=tz, ty=ty, taps=taps,
        ),
        grid=(gz, gy),
        in_specs=in_specs,
        out_specs=block_spec((tz, ty, nx), lambda k, j: (k, j, 0)),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), data.dtype),
        interpret=interpret,
    )(*operands)
