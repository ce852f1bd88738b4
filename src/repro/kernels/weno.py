"""WENO5 upwind advection kernel — the paper's ``2d_xyADVWENO_p`` variant.

The paper presents this as the "modify the source" example: the stock XY
kernel is extended with (a) extra streamed inputs (the u, v velocity fields)
and (b) a device-function WENO reconstruction replacing the weighted sum.
Here the same extension is two more operands with their own BlockSpecs and a
different traced point function — no source surgery required.

Halo width is 3 (WENO5 support); x- and y-bands are assembled from the
left/right and up/down neighbour tiles (no corner tiles needed — the scheme
is dimension-by-dimension, unlike the XY cross-derivative kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import _weno5_phi
from repro.util import block_spec, wrap_block

_H = 3  # WENO5 halo


def _weno_kernel(
    c_ref, l_ref, r_ref, up_ref, dn_ref, u_ref, v_ref, o_ref, *, dx, dy, ty, tx
):
    c = c_ref[...]
    xband = jnp.concatenate(
        [l_ref[:, tx - _H :], c, r_ref[:, :_H]], axis=1
    )  # (ty, tx + 6)
    yband = jnp.concatenate(
        [up_ref[ty - _H :, :], c, dn_ref[:_H, :]], axis=0
    )  # (ty + 6, tx)

    def diffs_x(k):  # (q_{i+k+1} - q_{i+k}) / dx  for the tile
        a = jax.lax.slice(xband, (0, _H + k + 1), (ty, _H + k + 1 + tx))
        b = jax.lax.slice(xband, (0, _H + k), (ty, _H + k + tx))
        return (a - b) / dx

    def diffs_y(k):
        a = jax.lax.slice(yband, (_H + k + 1, 0), (_H + k + 1 + ty, tx))
        b = jax.lax.slice(yband, (_H + k, 0), (_H + k + ty, tx))
        return (a - b) / dy

    dxs = [diffs_x(k) for k in range(-3, 3)]
    dys = [diffs_y(k) for k in range(-3, 3)]

    qxm = _weno5_phi(dxs[0], dxs[1], dxs[2], dxs[3], dxs[4])
    qxp = _weno5_phi(dxs[5], dxs[4], dxs[3], dxs[2], dxs[1])
    qym = _weno5_phi(dys[0], dys[1], dys[2], dys[3], dys[4])
    qyp = _weno5_phi(dys[5], dys[4], dys[3], dys[2], dys[1])

    u = u_ref[...]
    v = v_ref[...]
    qx = jnp.where(u > 0, qxm, qxp)
    qy = jnp.where(v > 0, qym, qyp)
    o_ref[...] = (-(u * qx + v * qy)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("dx", "dy", "ty", "tx", "interpret")
)
def weno5_advect_pallas(
    q: jnp.ndarray,
    u: jnp.ndarray,
    v: jnp.ndarray,
    *,
    dx: float,
    dy: float,
    ty: int = 128,
    tx: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """RHS of dq/dt = -(u q_x + v q_y), periodic, upwinded HJ-WENO5."""
    ny, nx = q.shape
    if ny % ty or nx % tx:
        raise ValueError(f"tile ({ty},{tx}) must divide field ({ny},{nx})")
    if _H > tx or _H > ty:
        raise ValueError("tile smaller than WENO halo")
    gy, gx = ny // ty, nx // tx

    specs = [
        block_spec((ty, tx), lambda j, i: (j, i)),  # centre
        block_spec((ty, tx), lambda j, i: (j, wrap_block(i - 1, gx))),  # left
        block_spec((ty, tx), lambda j, i: (j, wrap_block(i + 1, gx))),  # right
        block_spec((ty, tx), lambda j, i: (wrap_block(j - 1, gy), i)),  # up
        block_spec((ty, tx), lambda j, i: (wrap_block(j + 1, gy), i)),  # down
        block_spec((ty, tx), lambda j, i: (j, i)),  # u
        block_spec((ty, tx), lambda j, i: (j, i)),  # v
    ]
    return pl.pallas_call(
        functools.partial(_weno_kernel, dx=dx, dy=dy, ty=ty, tx=tx),
        grid=(gy, gx),
        in_specs=specs,
        out_specs=block_spec((ty, tx), lambda j, i: (j, i)),
        out_shape=jax.ShapeDtypeStruct((ny, nx), q.dtype),
        interpret=interpret,
    )(q, q, q, q, q, u, v)
