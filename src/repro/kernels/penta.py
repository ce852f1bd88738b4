"""Batched pentadiagonal solver — the cuPentBatch analogue (paper ref [13]).

The ADI scheme inverts ``L = I + (2/3) D gamma dt d_xxxx`` along each grid
direction every time step.  That matrix is pentadiagonal, symmetric positive
definite, and *constant in time*, so we split the solve exactly like
cuSten/cuPentBatch split Create/Compute:

- :func:`penta_factor` (Create-time, once): LU factorisation of the band,
  O(M) scalar work, pure-jnp scan.
- :func:`penta_solve_factored` (Compute-time, every step): forward/backward
  substitution on an (M, N) right-hand side — N independent systems solved
  in lockstep.  This is the hot path and has a Pallas kernel: the batch axis
  N lies on TPU lanes (cuPentBatch's "interleaved format": batch contiguous,
  recurrence strided) and the M-recurrence runs as an in-kernel
  ``fori_loop`` carrying two previous rows in vector registers.
- Periodic boundaries (cyclic pentadiagonal, paper refs [13, 16]) close the
  band with a **rank-4 Woodbury correction** whose dense (M, 4) auxiliary
  solves and 4x4 capacitance inverse are precomputed at Create-time:
  each Compute is then one banded substitution + two tiny matmuls.

Three substitution layouts are provided, so a full ADI step — 2D *or*
3D — moves **no transpose through HBM** (every sweep consumes Create-time
factors in its native layout):

- *column layout* (:func:`penta_solve_factored`): systems along axis 0
  (length M), batch along the rest — the y-sweep of an ``(ny, nx)``
  field and the z-sweep of an ``(nz, ny, nx)`` one, which runs on the
  field as it lies (the *lead* layout below) or on its ``(nz, ny*nx)``
  reshape.
- *row layout* (:func:`penta_solve_factored_rows`): batch along axis 0,
  recurrence along axis 1 (TPU lanes) — the x-sweep, with no
  interleaving transpose of the field.  The Pallas variant transposes
  each block's 128-lane chunks into a VMEM scratch and runs the
  recurrence there, on sublanes; the jnp variant walks the lanes with a
  ``fori_loop`` of dynamic column slices.  Reshaped to ``(nz*ny, nx)``
  it is also the 3D x-sweep.
- *plane layout* (:func:`penta_solve_factored_mid`): batch along axes 0
  and 2 of a ``(P, M, N)`` stack, recurrence along the *middle* axis —
  the y-sweep of a 3D field, where neither reshape nor transpose can
  bring the systems to an edge axis.  The carry is a full (P, N) plane.

The Pallas kernels run one of two recurrences, both on sublanes; the
dispatch picks from the shape alone (:func:`dense_sweep_tiles`):

- *dense steps* (:func:`sweep_lines_refs`): every step loads whole
  (8, 128) tiles, each holding step ``i`` of 8 x 128 independent lines,
  so one step's latency is paid for 1024 lines a tile.  It needs a
  second batch axis that fills the sublanes: a 3D z-sweep whose (ny, nx)
  planes tile into (8, 128) blocks (step ``i`` is plane ``i`` of an
  (nz, 8a, 128b) block), a plane sweep over a multiple of 8 planes at
  128-lane tiles (step ``i`` reads row ``i`` of 8 planes, sublane-
  strided, from the free (P*M, N) view), and a row sweep over a
  multiple of 1024 rows whose (1024k, M) block fits VMEM (each 128-row
  group transposed into its own M rows of the scratch).
- *one-sublane steps* (:func:`sweep_refs`): every other shape — the 2D
  column sweep (one plane), plane counts that are not a multiple of 8,
  the 2D row sweep and the fused CH kernel where 1024-row blocks exceed
  VMEM (M = 4096), and any explicit ``tn``/``tb`` tile.  Each step
  works on one sublane row of one (8, 128) vreg.

The rank-4 Woodbury correction is evaluated as four explicit outer
products (broadcast FMAs) rather than ``dot``s: the (M, 4) x (4, N)
contraction is far too small for a matmul unit and on BLAS-less XLA CPU
builds a ``dot_general`` of this shape costs more than the entire banded
substitution.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.util import block_spec, pick_tile


class PentaFactors(NamedTuple):
    """LU factors of a pentadiagonal band (all shape (M,))."""

    sub: jnp.ndarray  # e_i  = l2 (unchanged sub-sub diagonal)
    low: jnp.ndarray  # l_i  = eliminated sub diagonal
    inv_mu: jnp.ndarray  # 1/mu_i (reciprocal pivots; multiply, don't divide)
    al: jnp.ndarray  # alpha_i (first superdiagonal of U)
    be: jnp.ndarray  # beta_i  (second superdiagonal of U)


class CyclicPentaFactors(NamedTuple):
    band: PentaFactors
    z: jnp.ndarray  # (M, 4)  A^{-1} U, precomputed
    s_inv: jnp.ndarray  # (4, 4)  inv(I + V^T A^{-1} U)
    w: jnp.ndarray  # (M, 4)  Z S^{-1}, precomputed: Compute-time correction
    #                 is then 4 broadcast FMAs, x = y - W (V^T y)


def penta_factor(l2, l1, d, u1, u2) -> PentaFactors:
    """LU-factor the pentadiagonal matrix with diagonals (length M):

    ``A[i, i-2] = l2[i]``, ``A[i, i-1] = l1[i]``, ``A[i, i] = d[i]``,
    ``A[i, i+1] = u1[i]``, ``A[i, i+2] = u2[i]``.  Out-of-band entries
    (l2[0:2], l1[0], u1[-1], u2[-2:]) are ignored.

    No pivoting — intended for the SPD / diagonally-dominant operators of
    implicit time stepping.
    """
    M = d.shape[0]
    e = jnp.concatenate([jnp.zeros((2,), d.dtype), l2[2:]])
    c = jnp.concatenate([jnp.zeros((1,), d.dtype), l1[1:]])
    a = jnp.concatenate([u1[: M - 1], jnp.zeros((1,), d.dtype)])
    b = jnp.concatenate([u2[: M - 2], jnp.zeros((2,), d.dtype)])

    def step(carry, row):
        a1, a2, b1, b2 = carry  # alpha_{i-1}, alpha_{i-2}, beta_{i-1}, beta_{i-2}
        e_i, c_i, d_i, a_i, b_i = row
        l_i = c_i - e_i * a2
        mu_i = d_i - e_i * b2 - l_i * a1
        inv = 1.0 / mu_i
        al_i = (a_i - l_i * b1) * inv
        be_i = b_i * inv
        return (al_i, a1, be_i, b1), (l_i, inv, al_i, be_i)

    zero = jnp.zeros((), d.dtype)
    (_, _, _, _), (low, inv_mu, al, be) = jax.lax.scan(
        step, (zero, zero, zero, zero), (e, c, d, a, b)
    )
    return PentaFactors(sub=e, low=low, inv_mu=inv_mu, al=al, be=be)


# ---------------------------------------------------------------------------
# Substitution — jnp backend (lax.scan; production CPU path)
# ---------------------------------------------------------------------------


def _substitute_jnp(
    fac: PentaFactors, rhs: jnp.ndarray, unroll: int = 1
) -> jnp.ndarray:
    """Forward/backward substitution on (M, N) rhs via two scans.

    ``unroll`` is a tuner knob: some hosts amortise scan overhead with an
    unrolled loop body, others (notably BLAS-less CPU builds) run the
    rolled loop fastest.
    """

    def fwd(carry, row):
        z1, z2 = carry
        e_i, l_i, imu_i, r_i = row
        z = (r_i - e_i * z2 - l_i * z1) * imu_i
        return (z, z1), z

    N = rhs.shape[1]
    z0 = jnp.zeros((N,), rhs.dtype)
    _, z = jax.lax.scan(
        fwd, (z0, z0), (fac.sub, fac.low, fac.inv_mu, rhs), unroll=unroll
    )

    def bwd(carry, row):
        x1, x2 = carry
        al_i, be_i, z_i = row
        x = z_i - al_i * x1 - be_i * x2
        return (x, x1), x

    # explicit flips rather than scan(reverse=True): the reverse-scan's
    # internal index arithmetic miscompiles under the SPMD partitioner on
    # jax 0.4.37 (s64/s32 compare in the while body at 8 host devices)
    _, xr = jax.lax.scan(
        bwd, (z0, z0), (fac.al[::-1], fac.be[::-1], z[::-1]), unroll=unroll
    )
    return xr[::-1]


def _substitute_rows_jnp(
    fac: PentaFactors, rhs: jnp.ndarray, unroll: int = 1
) -> jnp.ndarray:
    """Row-layout substitution on (B, M) rhs — recurrence along axis 1.

    The transpose-free x-sweep: each row is one system, the recurrence
    walks the columns with dynamic slices and the batch stays contiguous
    on axis 0.  No transpose of the field appears anywhere.
    """
    B, M = rhs.shape
    zero = jnp.zeros((B,), rhs.dtype)
    # pack the per-column factor scalars so each iteration gathers once
    fwd_fac = jnp.stack([fac.sub, fac.low, fac.inv_mu], axis=1)  # (M, 3)
    bwd_fac = jnp.stack([fac.al, fac.be], axis=1)  # (M, 2)

    def col(arr, i):
        return jax.lax.dynamic_slice_in_dim(arr, i, 1, axis=1)[:, 0]

    # the intermediate z is stored recurrence-major (M, B): the forward
    # pass then writes contiguous rows and the backward pass reads them
    # back contiguously — only one strided access per column remains in
    # each loop (the rhs read / the x write), halving the strided traffic
    def fwd(i, carry):
        z1, z2, out = carry
        f = jax.lax.dynamic_slice_in_dim(fwd_fac, i, 1, axis=0)[0]
        z = (col(rhs, i) - f[0] * z2 - f[1] * z1) * f[2]
        out = jax.lax.dynamic_update_slice_in_dim(out, z[None, :], i, axis=0)
        return (z, z1, out)

    _, _, z_t = jax.lax.fori_loop(
        0, M, fwd, (zero, zero, jnp.zeros((M, B), rhs.dtype)), unroll=unroll
    )

    def bwd(t, carry):
        x1, x2, out = carry
        i = M - 1 - t
        f = jax.lax.dynamic_slice_in_dim(bwd_fac, i, 1, axis=0)[0]
        z = jax.lax.dynamic_slice_in_dim(z_t, i, 1, axis=0)[0]
        x = z - f[0] * x1 - f[1] * x2
        out = jax.lax.dynamic_update_slice_in_dim(out, x[:, None], i, axis=1)
        return (x, x1, out)

    _, _, x = jax.lax.fori_loop(
        0, M, bwd, (zero, zero, jnp.zeros_like(rhs)), unroll=unroll
    )
    return x


def _substitute_mid_jnp(
    fac: PentaFactors, rhs: jnp.ndarray, unroll: int = 1
) -> jnp.ndarray:
    """Plane-layout substitution on (P, M, N) rhs — recurrence along the
    *middle* axis, batch on the outer planes × lanes.

    The transpose-free y-sweep of a 3D field: each (z, :, x) line is one
    system; the recurrence walks axis 1 with dynamic slices carrying a
    full (P, N) plane, and no transpose of the field appears anywhere
    (the row-layout lane recurrence generalised to batched planes).
    """
    P, M, N = rhs.shape
    zero = jnp.zeros((P, N), rhs.dtype)
    # pack the per-plane factor scalars so each iteration gathers once
    fwd_fac = jnp.stack([fac.sub, fac.low, fac.inv_mu], axis=1)  # (M, 3)
    bwd_fac = jnp.stack([fac.al, fac.be], axis=1)  # (M, 2)

    def plane(arr, i):
        return jax.lax.dynamic_slice_in_dim(arr, i, 1, axis=1)[:, 0, :]

    def put(out, val, i):
        return jax.lax.dynamic_update_slice_in_dim(
            out, val[:, None, :], i, axis=1
        )

    def fwd(i, carry):
        z1, z2, out = carry
        f = jax.lax.dynamic_slice_in_dim(fwd_fac, i, 1, axis=0)[0]
        z = (plane(rhs, i) - f[0] * z2 - f[1] * z1) * f[2]
        return (z, z1, put(out, z, i))

    _, _, z = jax.lax.fori_loop(
        0, M, fwd, (zero, zero, jnp.zeros_like(rhs)), unroll=unroll
    )

    def bwd(t, carry):
        x1, x2, out = carry
        i = M - 1 - t
        f = jax.lax.dynamic_slice_in_dim(bwd_fac, i, 1, axis=0)[0]
        x = plane(z, i) - f[0] * x1 - f[1] * x2
        return (x, x1, put(out, x, i))

    _, _, x = jax.lax.fori_loop(
        0, M, bwd, (zero, zero, jnp.zeros_like(rhs)), unroll=unroll
    )
    return x


def mid_woodbury_correct(y: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Plane-layout Woodbury closure ``x = y - W (V^T y)`` on a (P, M, N)
    band solution, as four broadcast FMAs (``w`` is the Create-time (M, 4)
    ``Z S^{-1}``) — the plane generalisation of
    :func:`rows_woodbury_correct`."""
    M = y.shape[1]
    return y - (
        y[:, M - 2][:, None, :] * w[None, :, 0, None]
        + y[:, M - 1][:, None, :] * w[None, :, 1, None]
        + y[:, 0][:, None, :] * w[None, :, 2, None]
        + y[:, 1][:, None, :] * w[None, :, 3, None]
    )


# ---------------------------------------------------------------------------
# Substitution — Pallas kernel (TPU target; interpret mode off the chip)
# ---------------------------------------------------------------------------

# The five factor vectors travel as one (5, M) table in SMEM and are read
# as scalars: Mosaic refuses single-element dynamic loads from a 1D VMEM
# vector.  A float32 table fits SMEM up to this recurrence length (AOT
# compiles for TPU v5e pass at 8192 and fail at 32768).
SMEM_MAX_M = 8192
# Scoped VMEM the sweep kernels may use: half of a v5e core's 128 MiB (the
# compiler's default scoped limit, 16 MiB, is too small for a 4096-row
# column block at M = 8192).
VMEM_LIMIT_BYTES = 64 * 2**20


def _fac_table(fac: PentaFactors) -> jnp.ndarray:
    return jnp.stack([fac.sub, fac.low, fac.inv_mu, fac.al, fac.be])


def _chunk(M: int) -> int:
    """Recurrence steps per block: the largest divisor of ``M`` up to one
    vreg's 8 sublanes."""
    return max(c for c in range(1, min(M, 8) + 1) if M % c == 0)


def sweep_refs(f_ref, o_ref) -> None:
    """In-place banded substitution along the sublanes (the second-to-last
    axis) of the Pallas ref ``o_ref``, which holds the right-hand side on
    entry and the solution on exit; ``f_ref`` is the (5, M) SMEM factor
    table.

    The recurrence of the shapes with no batch axis that fills the
    sublanes (:func:`dense_sweep_tiles` is 0; else
    :func:`sweep_lines_refs` runs): column and plane sweeps run it on
    their (1, M, tn) blocks, the row sweep and the fused CH kernel on an
    (M, tb) VMEM scratch that :func:`sweep_rows_refs` transposes their
    rows into.  Mosaic refuses unaligned single-row accesses, so the loop
    loads and stores whole aligned blocks of :func:`_chunk` steps and runs
    the steps inside a block unrolled, on static slices: each step works
    on one sublane of a vreg and selects it back into the block.
    """
    nd = len(o_ref.shape)
    axis = nd - 2
    M = o_ref.shape[axis]
    chunk = _chunk(M)
    n = M // chunk

    def at(off):
        return tuple(
            pl.ds(off, chunk) if d == axis else slice(None) for d in range(nd)
        )

    def step(blk, j):
        return jax.lax.slice_in_dim(blk, j, j + 1, axis=axis)

    first = o_ref[at(0)]
    pos = jax.lax.broadcasted_iota(jnp.int32, first.shape, axis)
    # a zero laid out like a loaded step: Mosaic cannot carry a constant
    # (replicated-layout) zero through the loop
    zero = step(first, 0) * 0

    # every integer is int32: under x64 a Python int lowers to an int64
    # that Mosaic cannot mix with the int32 loop index
    c32 = np.int32(chunk)

    def fwd(k, carry):
        z1, z2 = carry
        off = pl.multiple_of(k * c32, chunk)
        blk = o_ref[at(off)]
        out = blk
        for j in range(chunk):
            i = off + np.int32(j)
            z = (step(blk, j) - f_ref[0, i] * z2 - f_ref[1, i] * z1) * f_ref[2, i]
            out = jnp.where(pos == np.int32(j), z, out)
            z1, z2 = z, z1
        o_ref[at(off)] = out
        return z1, z2

    def bwd(t, carry):
        x1, x2 = carry
        off = pl.multiple_of((np.int32(n - 1) - t) * c32, chunk)
        blk = o_ref[at(off)]
        out = blk
        for j in reversed(range(chunk)):
            i = off + np.int32(j)
            x = step(blk, j) - f_ref[3, i] * x1 - f_ref[4, i] * x2
            out = jnp.where(pos == np.int32(j), x, out)
            x1, x2 = x, x1
        o_ref[at(off)] = out
        return x1, x2

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), fwd, (zero, zero))
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), bwd, (zero, zero))


def sweep_rows_refs(f_ref, r_ref, o_ref, t_ref) -> None:
    """Row-layout substitution: every row of the (tb, M) ref ``r_ref`` is
    one system, and the solutions go to ``o_ref`` (which may be
    ``r_ref``).  The rows' lane chunks (one vreg's 128 lanes, or all of
    ``M`` where 128 does not divide it) are transposed into the (M, tb)
    VMEM scratch ``t_ref``, :func:`sweep_refs` walks its sublanes, and
    the chunks are transposed back: the transposes stay in VMEM, and the
    recurrence steps a whole row of the scratch at a time where stepping
    along the lanes would select one lane per step."""
    M = r_ref.shape[1]
    c = 128 if M % 128 == 0 else M

    def lanes(off):
        return slice(None), pl.ds(off, c)

    def rows(off):
        return pl.ds(off, c), slice(None)

    def move(src, src_at, dst, dst_at):
        if c == M:  # one chunk, at a static offset
            dst[...] = src[...].T
            return

        def body(k, carry):
            off = pl.multiple_of(k * np.int32(c), c)
            dst[dst_at(off)] = src[src_at(off)].T
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(M // c), body, None)

    move(r_ref, lanes, t_ref, rows)
    sweep_refs(f_ref, t_ref)
    move(t_ref, rows, o_ref, lanes)


def sweep_lines_refs(f_ref, r_ref, o_ref, at) -> None:
    """Dense-step banded substitution: each step of the recurrence works
    on whole (8, 128) tiles of independent lines.  ``at(i)`` lists the
    indexers of the tiles that hold recurrence index ``i`` in ``r_ref``
    (the right-hand side) and ``o_ref`` (the solution, which may be
    ``r_ref``); ``f_ref`` is the (5, M) SMEM factor table.

    A step loads its tiles, applies the forward substitution (the
    backward one, walking back) in the order of :func:`_substitute_jnp`,
    and stores them whole: no select, no sublane slice, and the step's
    tiles are independent work beside the carry's dependent chain.
    """
    M = f_ref.shape[1]
    # a zero laid out like a loaded tile (see sweep_refs)
    zero = tuple(r_ref[idx] * 0 for idx in at(jnp.int32(0)))

    def fwd(i, carry):
        z1, z2 = carry
        e, low, imu = f_ref[0, i], f_ref[1, i], f_ref[2, i]
        z = tuple(
            (r_ref[idx] - e * b - low * a) * imu
            for idx, a, b in zip(at(i), z1, z2)
        )
        for idx, v in zip(at(i), z):
            o_ref[idx] = v
        return z, z1

    def bwd(t, carry):
        x1, x2 = carry
        i = np.int32(M - 1) - t
        al, be = f_ref[3, i], f_ref[4, i]
        x = tuple(
            o_ref[idx] - al * a - be * b for idx, a, b in zip(at(i), x1, x2)
        )
        for idx, v in zip(at(i), x):
            o_ref[idx] = v
        return x, x1

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(M), fwd, (zero, zero))
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(M), bwd, (zero, zero))


def _strided_lines(M: int, k: int):
    """``at`` of :func:`sweep_lines_refs` on an (8k*M, 128) block of 8k
    line groups of M rows each: tile ``g`` of step ``i`` is row ``i`` of
    groups 8g..8g+7, one sublane-strided access."""

    def at(i):
        return [
            (pl.ds(i + np.int32(8 * g * M), 8, stride=M), slice(None))
            for g in range(k)
        ]

    return at


def sweep_row_lines_refs(f_ref, r_ref, o_ref, t_ref) -> None:
    """Dense row layout: the (128*8k, M) block ``r_ref`` holds 8k groups
    of 128 systems (rows).  Each group's lane chunks (of 128, or of all
    of ``M``) are transposed into rows g*M.. of the (8k*M, 128) VMEM
    scratch ``t_ref``, :func:`sweep_lines_refs` steps eight groups a
    tile, and the chunks are transposed back into ``o_ref``."""
    B, M = r_ref.shape
    c = 128 if M % 128 == 0 else M
    per = M // c

    def move(into_scratch):
        def body(j, carry):
            g, q = j // np.int32(per), j % np.int32(per)
            off = q * np.int32(c)
            lines = (
                pl.ds(pl.multiple_of(g * np.int32(128), 128), 128),
                pl.ds(pl.multiple_of(off, c), c),
            )
            rows = pl.ds(pl.multiple_of(g * np.int32(M) + off, 8), c), slice(None)
            if into_scratch:
                t_ref[rows] = r_ref[lines].T
            else:
                o_ref[lines] = t_ref[rows].T
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(B // 128 * per), body, None)

    move(True)
    sweep_lines_refs(f_ref, t_ref, t_ref, _strided_lines(M, B // 1024))
    move(False)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def tpu_sweep_problem(M, batch, tile, dtype, *, lanes: bool) -> str | None:
    """Why the Pallas sweep cannot be compiled for a TPU at this shape, or
    ``None`` when it can.  ``lanes`` says the systems lie along the lanes
    (row layout: (tile, M) blocks, transposed in lane chunks of 128, or of
    all of ``M`` where 128 does not divide it, into an (M, tile) scratch);
    ``batch``/``tile`` are the tiled batch extent and its block (rows of
    the row layout, lanes of the column/plane ones).  Every layout's
    recurrence walks sublanes, so ``M`` is a multiple of 8."""
    if jnp.dtype(dtype).itemsize != 4:
        return f"Mosaic has no {jnp.dtype(dtype).name} sweep (float32 only)"
    if batch % tile:
        return f"batch tile {tile} does not divide {batch}"
    align = 8 if lanes else 128
    if tile % align and tile != batch:
        return f"batch tile {tile} is not a multiple of {align}"
    if _chunk(M) not in (8, M):
        return f"recurrence length {M} is not a multiple of 8"
    if M > SMEM_MAX_M:
        return f"recurrence length {M} > {SMEM_MAX_M}: factors exceed SMEM"
    # in + out, double-buffered, and the row layout's transpose scratch
    scratch = M * _pad(tile, 128) if lanes else 0
    if 4 * (4 * M * tile + scratch) > VMEM_LIMIT_BYTES:
        return f"a ({M}, {tile}) block exceeds the VMEM limit"
    return None


# (8, 128) tiles of independent lines one dense recurrence step works on:
# independent vregs beside the carry's chain of dependent operations
STEP_TILES = 4


def _lead_block(k: int, B1: int, B2: int) -> tuple[int, int] | None:
    """The (8a, 128b) plane block, a * b = ``k``, that tiles (B1, B2),
    widest first; ``None`` where none does."""
    for b in range(k, 0, -1):
        if k % b == 0 and B1 % (8 * (k // b)) == 0 and B2 % (128 * b) == 0:
            return 8 * (k // b), 128 * b
    return None


def dense_sweep_tiles(layout: str, M: int, batch, dtype) -> int:
    """How many (8, 128) tiles of independent lines one dense recurrence
    step (:func:`sweep_lines_refs`) can work on at this shape, at most
    :data:`STEP_TILES`; 0 where no batch axis yields whole groups of
    8 x 128 lines within :data:`VMEM_LIMIT_BYTES`, and the one-sublane
    :func:`sweep_refs` runs.  The layouts and their blocks (k tiles):

    - ``'lead'``, an (M, B1, B2) rhs, ``batch = (B1, B2)``: (M, 8a, 128b)
      blocks, a * b = k; step ``i`` is the block's plane ``i``.
    - ``'mid'``, a (P, M, N) rhs, ``batch = (P, N)``: (8k M, 128) blocks of
      the free (P M, N) view; a tile is row ``i`` of 8 planes.
    - ``'rows'``, a (B, M) rhs, ``batch = (B,)``: (1024 k, M) blocks, whose
      groups of 128 rows are transposed into an (8k M, 128) scratch."""
    if jnp.dtype(dtype).itemsize != 4 or M > SMEM_MAX_M:
        return 0
    if layout != "lead" and M % 8:
        return 0
    for k in range(STEP_TILES, 0, -1):
        if layout == "lead":
            fits = _lead_block(k, *batch) is not None
            words = 4 * M * 1024 * k  # in + out, double-buffered
        elif layout == "mid":
            fits = batch[0] % (8 * k) == 0 and batch[1] % 128 == 0
            words = 4 * 8 * k * M * 128
        else:
            fits = batch[0] % (1024 * k) == 0
            words = 4 * 1024 * k * _pad(M, 128) + 8 * k * M * 128
        if fits and 4 * words <= VMEM_LIMIT_BYTES:
            return k
    return 0


def _smem_table_spec(M: int):
    """The whole (5, M) factor table in SMEM at every grid step."""
    return block_spec((5, M), lambda *_: (0, 0), memory_space=pltpu.SMEM)


def _solve_kernel(f_ref, r_ref, o_ref):
    o_ref[...] = r_ref[...]
    sweep_refs(f_ref, o_ref)


def _lines_kernel(f_ref, r_ref, o_ref, *, at):
    sweep_lines_refs(f_ref, r_ref, o_ref, at)


def _pallas_sweep(
    kernel, fac, rhs, *, block, index_map, grid, interpret, scratch_shapes=()
):
    """One sweep ``pallas_call`` of ``kernel`` over ``block``s of ``rhs``,
    the factor table in SMEM."""
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _smem_table_spec(fac.sub.shape[0]),
            block_spec(block, index_map),
        ],
        out_specs=block_spec(block, index_map),
        out_shape=jax.ShapeDtypeStruct(rhs.shape, rhs.dtype),
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(_fac_table(fac), rhs)


@functools.partial(jax.jit, static_argnames=("tn", "interpret", "tiles"))
def _substitute_mid_pallas(
    fac: PentaFactors, rhs: jnp.ndarray, *, tn: int, interpret: bool,
    tiles: int = 0,
) -> jnp.ndarray:
    """Plane layout on (P, M, N): one z-plane × lane tile per grid step,
    or, with ``tiles`` = k, 8k planes × 128 lanes of the free (P M, N)
    view per grid step, each recurrence step k sublane-strided tiles."""
    P, M, N = rhs.shape
    if N % tn:
        raise ValueError(f"lane tile {tn} must divide N={N}")
    if tiles:
        rows = 8 * tiles * M
        out = _pallas_sweep(
            functools.partial(_lines_kernel, at=_strided_lines(M, tiles)),
            fac, rhs.reshape(P * M, N), block=(rows, 128),
            index_map=lambda p, j: (p, j), grid=(P * M // rows, N // 128),
            interpret=interpret,
        )
        return out.reshape(P, M, N)
    return _pallas_sweep(
        _solve_kernel, fac, rhs, block=(1, M, tn),
        index_map=lambda p, i: (p, 0, i),
        grid=(P, N // tn), interpret=interpret,
    )


def _substitute_pallas(
    fac: PentaFactors, rhs: jnp.ndarray, *, tn: int, interpret: bool
) -> jnp.ndarray:
    """Column layout on (M, N): the plane layout with a single plane."""
    M, N = rhs.shape
    if N % tn:
        raise ValueError(f"batch tile {tn} must divide N={N}")
    return _substitute_mid_pallas(fac, rhs[None], tn=tn, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("tb", "interpret", "tiles"))
def _substitute_rows_pallas(
    fac: PentaFactors, rhs: jnp.ndarray, *, tb: int, interpret: bool,
    tiles: int = 0,
) -> jnp.ndarray:
    """Row layout on (B, M): each (tb, M) block is transposed in VMEM
    (:func:`sweep_rows_refs`) and the recurrence walks its sublanes; with
    ``tiles`` = k, (1024k, M) blocks go through
    :func:`sweep_row_lines_refs`."""
    B, M = rhs.shape
    if B % tb:
        raise ValueError(f"batch tile {tb} must divide B={B}")
    if tiles:
        rows = 1024 * tiles
        return _pallas_sweep(
            sweep_row_lines_refs, fac, rhs, block=(rows, M),
            index_map=lambda i: (i, 0), grid=(B // rows,),
            interpret=interpret,
            scratch_shapes=[pltpu.VMEM((8 * tiles * M, 128), rhs.dtype)],
        )
    return _pallas_sweep(
        sweep_rows_refs, fac, rhs, block=(tb, M), index_map=lambda i: (i, 0),
        grid=(B // tb,), interpret=interpret,
        scratch_shapes=[pltpu.VMEM((M, tb), rhs.dtype)],
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _substitute_lead_pallas(
    fac: PentaFactors, rhs: jnp.ndarray, *, block: tuple, interpret: bool
) -> jnp.ndarray:
    """Lead layout on (M, B1, B2), systems down the leading axis: an
    (M, *block) block per grid step, whose plane ``i`` is recurrence step
    ``i`` of 8a x 128b lines — dense in place, with no copy of the field."""
    M, B1, B2 = rhs.shape
    return _pallas_sweep(
        functools.partial(_lines_kernel, at=lambda i: [(i,)]), fac, rhs,
        block=(M, *block), index_map=lambda j, l: (0, j, l),
        grid=(B1 // block[0], B2 // block[1]), interpret=interpret,
    )


def woodbury_rows(w: jnp.ndarray) -> jnp.ndarray:
    """The Create-time (M, 4) ``Z S^{-1}`` as a (4, M) stack of its
    columns, for :func:`rows_woodbury_correct` (column slices, so the
    transpose-free paths hold no transpose)."""
    return jnp.stack([w[:, k] for k in range(4)])


def rows_woodbury_correct(y: jnp.ndarray, wt: jnp.ndarray) -> jnp.ndarray:
    """Row-layout Woodbury closure ``x = y - W (V^T y)`` on a (B, M) band
    solution, as four broadcast FMAs (``wt`` is :func:`woodbury_rows` of
    the Create-time ``Z S^{-1}``, so every slice is static).  Shared by the
    jnp solve and the fused Pallas kernel."""
    M = y.shape[1]
    return y - (
        y[:, M - 2 : M - 1] * wt[0:1]
        + y[:, M - 1 : M] * wt[1:2]
        + y[:, 0:1] * wt[2:3]
        + y[:, 1:2] * wt[3:4]
    )


_substitute_jnp_jit = jax.jit(_substitute_jnp, static_argnames=("unroll",))
_substitute_rows_jnp_jit = jax.jit(
    _substitute_rows_jnp, static_argnames=("unroll",)
)
_substitute_mid_jnp_jit = jax.jit(
    _substitute_mid_jnp, static_argnames=("unroll",)
)


def penta_solve_factored(
    fac: PentaFactors,
    rhs: jnp.ndarray,
    *,
    backend: str = "auto",
    tn: int | None = None,
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Solve ``A x = rhs`` given Create-time factors.  rhs: (M,), (M, N)
    or (M, B1, B2), the systems down axis 0.

    A 3D rhs whose (B1, B2) planes tile into (8, 128) blocks runs the
    dense lead-layout kernel on the field as it lies; any other batch is
    the column layout (a 3D one on its (M, B1 B2) reshape).  ``tn`` pins
    the column layout's lane tile."""
    from repro.kernels import ops  # cycle-free: ops imports names only

    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    shape = rhs.shape
    M, N = shape[0], math.prod(shape[1:])
    tiles = 0
    if rhs.ndim == 3 and tn is None:
        tiles = dense_sweep_tiles("lead", M, shape[1:], rhs.dtype)
    tn = tn if tn is not None else pick_tile(N)
    problem = None if tiles else tpu_sweep_problem(
        M, N, tn, rhs.dtype, lanes=False
    )
    backend = ops.checked_backend("penta sweep", problem, backend, interpret)
    if backend == "pallas" and tiles:
        obs.add("penta.dense_sweeps", 1)
        return _substitute_lead_pallas(
            fac, rhs, block=_lead_block(tiles, *shape[1:]),
            interpret=ops._should_interpret(interpret),
        )
    flat = rhs.reshape(M, N) if rhs.ndim == 3 else rhs
    if backend == "pallas":
        out = _substitute_pallas(
            fac, flat, tn=tn, interpret=ops._should_interpret(interpret)
        )
    elif backend == "jnp":
        out = _substitute_jnp_jit(fac, flat, unroll=unroll)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    if rhs.ndim == 3:
        out = out.reshape(shape)
    return out[:, 0] if squeeze else out


def penta_solve_factored_rows(
    fac: PentaFactors,
    rhs: jnp.ndarray,
    *,
    backend: str = "auto",
    tb: int | None = None,
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Row-layout solve: ``rhs`` is (B, M) (or (M,)), each *row* one system.

    The x-sweep, with no transpose of the field in HBM — same factors as
    :func:`penta_solve_factored`, recurrence along axis 1.  ``tb`` pins
    the one-sublane sweep's row tile; left ``None``, a batch of whole
    1024-row blocks runs the dense-step sweep.
    """
    from repro.kernels import ops

    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[None, :]
    B, M = rhs.shape
    tiles = dense_sweep_tiles("rows", M, (B,), rhs.dtype) if tb is None else 0
    tb = tb if tb is not None else pick_tile(B)
    problem = None if tiles else tpu_sweep_problem(
        M, B, tb, rhs.dtype, lanes=True
    )
    backend = ops.checked_backend("penta sweep", problem, backend, interpret)
    if backend == "pallas":
        if tiles:
            obs.add("penta.dense_sweeps", 1)
        out = _substitute_rows_pallas(
            fac, rhs, tb=tb, tiles=tiles,
            interpret=ops._should_interpret(interpret),
        )
    elif backend == "jnp":
        out = _substitute_rows_jnp_jit(fac, rhs, unroll=unroll)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out[0] if squeeze else out


def penta_solve_factored_mid(
    fac: PentaFactors,
    rhs: jnp.ndarray,
    *,
    backend: str = "auto",
    tn: int | None = None,
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Plane-layout solve: ``rhs`` is (P, M, N), recurrence along the
    middle axis — every (p, :, n) line one system.

    The transpose-free y-sweep of a 3D ADI step: same Create-time factors
    as :func:`penta_solve_factored`, batch on the outer planes × lanes.
    ``tn`` pins the one-sublane sweep's lane tile; left ``None``, a
    multiple of 8 planes at 128-lane tiles runs the dense-step sweep.
    """
    from repro.kernels import ops

    P, M, N = rhs.shape
    tiles = dense_sweep_tiles("mid", M, (P, N), rhs.dtype) if tn is None else 0
    tn = tn if tn is not None else pick_tile(N)
    problem = None if tiles else tpu_sweep_problem(
        M, N, tn, rhs.dtype, lanes=False
    )
    backend = ops.checked_backend("penta sweep", problem, backend, interpret)
    if backend == "pallas":
        if tiles:
            obs.add("penta.dense_sweeps", 1)
        return _substitute_mid_pallas(
            fac, rhs, tn=tn, tiles=tiles,
            interpret=ops._should_interpret(interpret),
        )
    if backend == "jnp":
        return _substitute_mid_jnp_jit(fac, rhs, unroll=unroll)
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# Cyclic (periodic) closure — Woodbury rank-4, precomputed at Create
# ---------------------------------------------------------------------------


def cyclic_penta_factor(l2, l1, d, u1, u2) -> CyclicPentaFactors:
    """Factor the cyclic pentadiagonal matrix whose row ``i`` couples columns
    ``(i-2, i-1, i, i+1, i+2) mod M`` with coefficients (l2, l1, d, u1, u2)[i].

    Requires M >= 6 so the corner blocks don't overlap the band.
    """
    M = d.shape[0]
    if M < 6:
        raise ValueError("cyclic pentadiagonal needs M >= 6")
    band = penta_factor(l2, l1, d, u1, u2)

    dt = d.dtype
    # U columns cover the corner entries; V columns are standard basis vectors
    # at rows/cols (M-2, M-1, 0, 1).
    U = jnp.zeros((M, 4), dt)
    U = U.at[0, 0].set(l2[0])  # (0, M-2)
    U = U.at[0, 1].set(l1[0])  # (0, M-1)
    U = U.at[1, 1].set(l2[1])  # (1, M-1)
    U = U.at[M - 2, 2].set(u2[M - 2])  # (M-2, 0)
    U = U.at[M - 1, 2].set(u1[M - 1])  # (M-1, 0)
    U = U.at[M - 1, 3].set(u2[M - 1])  # (M-1, 1)

    z = _substitute_jnp(band, U)  # (M, 4) = A^{-1} U
    vt_rows = jnp.stack([z[M - 2], z[M - 1], z[0], z[1]])  # V^T Z  (4, 4)
    s = jnp.eye(4, dtype=dt) + vt_rows
    s_inv = jnp.linalg.inv(s)
    # full f32 products: a TPU matmul at default precision rounds its
    # operands to bfloat16, which left a 6e-3 residual in the 2D ADI solve
    w = jnp.matmul(z, s_inv, precision=jax.lax.Precision.HIGHEST)
    return CyclicPentaFactors(band=band, z=z, s_inv=s_inv, w=w)


def cyclic_penta_solve_factored(
    fac: CyclicPentaFactors,
    rhs: jnp.ndarray,
    *,
    backend: str = "auto",
    tn: int | None = None,
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Woodbury: x = y - W V^T y with y = A^{-1} rhs, W = Z S^{-1}
    (Create-time).  The correction is four broadcast FMAs — no ``dot``."""
    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    y = penta_solve_factored(
        fac.band, rhs, backend=backend, tn=tn, interpret=interpret,
        unroll=unroll,
    )
    M = y.shape[0]
    w = fac.w if y.ndim == 2 else fac.w[:, :, None]  # (M, B1, B2): planes
    x = y - (
        w[:, 0:1] * y[M - 2][None, :]
        + w[:, 1:2] * y[M - 1][None, :]
        + w[:, 2:3] * y[0][None, :]
        + w[:, 3:4] * y[1][None, :]
    )
    return x[:, 0] if squeeze else x


def cyclic_penta_solve_factored_rows(
    fac: CyclicPentaFactors,
    rhs: jnp.ndarray,
    *,
    backend: str = "auto",
    tb: int | None = None,
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Row-layout Woodbury solve on a (B, M) rhs (each row one cyclic
    system) — the x-sweep of a periodic ADI step, with no transpose of
    the field in HBM."""
    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[None, :]
    y = penta_solve_factored_rows(
        fac.band, rhs, backend=backend, tb=tb, interpret=interpret,
        unroll=unroll,
    )
    x = rows_woodbury_correct(y, woodbury_rows(fac.w))
    return x[0] if squeeze else x


def cyclic_penta_solve_factored_mid(
    fac: CyclicPentaFactors,
    rhs: jnp.ndarray,
    *,
    backend: str = "auto",
    tn: int | None = None,
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Plane-layout Woodbury solve on a (P, M, N) rhs (each (p, :, n) line
    one cyclic system) — the transpose-free y-sweep of a periodic 3D ADI
    step."""
    y = penta_solve_factored_mid(
        fac.band, rhs, backend=backend, tn=tn, interpret=interpret,
        unroll=unroll,
    )
    return mid_woodbury_correct(y, fac.w)


def hyperdiffusion_diagonals(M: int, alpha, dtype=jnp.float64):
    """Diagonals of ``I + alpha * delta^4`` (eq. 4b of the paper): the ADI
    per-direction implicit operator with 5-point fourth difference."""
    one = jnp.ones((M,), dtype)
    return (
        alpha * one,  # l2
        -4.0 * alpha * one,  # l1
        1.0 + 6.0 * alpha * one,  # d
        -4.0 * alpha * one,  # u1
        alpha * one,  # u2
    )


def diffusion_diagonals(M: int, r, dtype=jnp.float64):
    """Diagonals of ``I - r * delta^2``: the per-direction implicit operator
    of a backward-Euler diffusion sweep (``r = D dt / h^2``), as a
    pentadiagonal band with zero outer diagonals — tridiagonal systems ride
    the same factor/substitute machinery (and Woodbury closure) unchanged."""
    one = jnp.ones((M,), dtype)
    zero = jnp.zeros((M,), dtype)
    return (
        zero,  # l2
        -r * one,  # l1
        1.0 + 2.0 * r * one,  # d
        -r * one,  # u1
        zero,  # u2
    )
