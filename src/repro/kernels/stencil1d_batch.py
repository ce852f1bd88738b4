"""Batched-1D stencil Pallas kernel (cuSten's ``1DBatch`` family, TPU-native).

cuSten's ``custenCreate1DBatch{p,np}{,Fun}`` kernels apply the *same* 1D
stencil independently to every row of a ``(B, M)`` stack — the workload of
cuPentBatch-style batched solvers (many independent lines, e.g. the
per-direction sweeps of an ADI scheme, an ensemble of 1D PDEs, or the rows /
columns of a 2D field treated directionally).

TPU mapping (following the 2D kernel in :mod:`repro.kernels.stencil2d`):

- the grid tiles the stack into ``(Tb, Tm)`` VMEM blocks via ``BlockSpec``;
  the batch axis is pure data-parallel — rows never talk to each other —
  so batch tiles need no halo and the ``M`` axis sits on the TPU lanes,
  vectorizing the stencil recurrence across the whole batch tile at once;
- halos along ``M`` are obtained by passing the same input with
  left/right-neighbour ``index_map``s (wrap for periodic, clamp for
  non-periodic), exactly the 1D slice of the 2D kernel's halo scheme;
- inside the kernel a ``(Tb, Tm + left + right)`` band is assembled in VMEM
  and the stencil is evaluated as whole-tile shifted-window FMAs on the VPU;
- the "function pointer" mode is a traceable ``point_fn(windows, coeffs)``
  traced straight into the kernel body (``Fun`` variants).

``bc='np'`` computes interior columns only: every batch row is computed, but
the ``left``/``right`` edge columns pass through from ``out_init`` — the
caller applies its own boundary conditions, the cuSten ``np`` semantics.

Constraints (checked by :mod:`repro.kernels.ops`, which falls back to the
jnp oracle otherwise): tile sizes must divide ``(B, M)`` exactly and the
halo must not exceed the neighbouring tile (``max(left, right) <= Tm``).
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import weighted_point_fn
from repro.util import block_spec, clamp_block, wrap_block


def _neighbour_index_map(di: int, gm: int, bc: str):
    """Block index map selecting the horizontal (0, di) neighbour tile."""
    move = wrap_block if bc == "periodic" else clamp_block

    def index_map(b, i):
        return (b, move(i + di, gm) if di else i)

    return index_map


def _stencil1d_kernel(
    *refs,
    point_fn: Callable,
    left: int,
    right: int,
    hm: int,
    bc: str,
    nm: int,
    tb: int,
    tm: int,
):
    """Kernel body.  ``refs`` layout:

    [tile(di) for di in (-1, 0, 1) if halo needed else (0,)] + [coeffs,
    out_init?] + [out].
    """
    dis = (-1, 0, 1) if hm > 0 else (0,)
    n_tiles = len(dis)
    tile_refs = refs[:n_tiles]
    coeffs_ref = refs[n_tiles]
    has_init = bc == "np"
    out_init_ref = refs[n_tiles + 1] if has_init else None
    out_ref = refs[-1]

    tiles = {di: tile_refs[k][...] for k, di in enumerate(dis)}

    # Assemble the halo band in VMEM: (Tb, hm + Tm + hm).
    band = tiles[0]
    if hm > 0:
        lband = tiles[-1][:, tm - hm :]
        rband = tiles[1][:, :hm]
        band = jnp.concatenate([lband, band, rband], axis=1)

    coeffs = coeffs_ref[...]

    windows = []
    for b in range(left + right + 1):
        c0 = hm - left + b
        windows.append(jax.lax.slice(band, (0, c0), (tb, c0 + tm)))
    val = point_fn(windows, coeffs)

    if bc == "np":
        i = pl.program_id(1)
        gi = i * tm + jax.lax.broadcasted_iota(jnp.int32, (tb, tm), 1)
        mask = (gi >= left) & (gi < nm - right)
        val = jnp.where(mask, val, out_init_ref[...])

    out_ref[...] = val.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("point_fn", "left", "right", "bc", "tb", "tm", "interpret"),
)
def stencil1d_batch_pallas(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = weighted_point_fn,
    left: int = 0,
    right: int = 0,
    bc: str = "periodic",
    tb: int = 8,
    tm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Apply a 1D stencil along axis 1 of a ``(B, M)`` stack.

    ``data``: (B, M).  ``coeffs``: 1D array fed to ``point_fn``.
    ``out_init``: required for ``bc='np'`` — edge columns pass through.
    """
    B, M = data.shape
    if B % tb or M % tm:
        raise ValueError(f"tile ({tb},{tm}) must divide stack ({B},{M})")
    hm = max(left, right)
    if hm > tm:
        raise ValueError(f"halo {hm} exceeds tile width {tm}")
    gb, gm = B // tb, M // tm

    dis = (-1, 0, 1) if hm > 0 else (0,)
    in_specs = [
        block_spec((tb, tm), _neighbour_index_map(di, gm, bc)) for di in dis
    ]
    operands = [data] * len(dis)

    # coefficients: whole (small) array in VMEM for every program
    in_specs.append(block_spec(coeffs.shape, lambda b, i: (0,) * coeffs.ndim))
    operands.append(coeffs)

    if bc == "np":
        if out_init is None:
            out_init = jnp.zeros_like(data)
        in_specs.append(block_spec((tb, tm), lambda b, i: (b, i)))
        operands.append(out_init)

    kernel = functools.partial(
        _stencil1d_kernel,
        point_fn=point_fn,
        left=left,
        right=right,
        hm=hm,
        bc=bc,
        nm=M,
        tb=tb,
        tm=tm,
    )

    return pl.pallas_call(
        kernel,
        grid=(gb, gm),
        in_specs=in_specs,
        out_specs=block_spec((tb, tm), lambda b, i: (b, i)),
        out_shape=jax.ShapeDtypeStruct((B, M), data.dtype),
        interpret=interpret,
    )(*operands)
