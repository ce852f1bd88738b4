"""Streamed tiled execution for oversized domains (paper §III, cuSten's
``nStreams``/``numStenTop`` machinery).

cuSten's headline feature beyond the four-function API is *streaming*: a
``(ny, nx)`` field larger than device memory is cut into horizontal
row-chunks that are loaded, computed, and stored on ``nStreams`` overlapping
CUDA streams, so the kernel never sees more than one chunk (+ halo rows) at
a time.  The JAX/TPU translation implemented here:

- the field is padded once with its halo ring (wrap for periodic, zeros for
  ``np`` — masked later), so every chunk slab is a single contiguous
  ``dynamic_slice``;
- chunk starts are grouped ``streams`` at a time; each group is gathered and
  computed under ``vmap`` so XLA's latency-hiding scheduler overlaps one
  chunk's HBM loads with another's VPU compute — the stream-overlap of the
  paper, expressed as instruction-level parallelism instead of explicit
  CUDA streams;
- groups advance under ``jax.lax.scan`` with the output buffer *donated*
  through the jit boundary, so the store of group ``k`` reuses the buffer
  while group ``k+1`` is in flight (double buffering);
- results are written back with ``dynamic_update_slice`` and match the
  monolithic path to floating-point rounding: the slab windows contain
  exactly the values the monolithic shifted-window evaluation sees, in the
  same reduction order (XLA fusion across the scan may contract FMAs
  differently, so agreement is allclose-at-epsilon, not bitwise).

Chunk geometry is driven by ``max_tile_bytes`` (the per-chunk memory
budget, cuSten's "how many rows fit on the device") and ``streams`` (chunks
in flight per pipeline stage, cuSten's ``nStreams``).  The multi-device
path (:func:`stream_stencil_apply_dist`) additionally shards each chunk's x
extent over a mesh axis via ``shard_map``, exchanging x halos with
``ppermute`` — streaming in y, domain decomposition in x.
"""

from __future__ import annotations

import functools
import math
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import weighted_point_fn
from repro.util import ceil_div


# ---------------------------------------------------------------------------
# Chunk geometry
# ---------------------------------------------------------------------------


def _divisors_desc(n: int):
    divs = []
    d = 1
    while d * d <= n:
        if n % d == 0:
            divs.append(d)
            if d != n // d:
                divs.append(n // d)
        d += 1
    return sorted(divs, reverse=True)


def slab_bytes(
    rows: int, nx: int, itemsize: int, *, top: int, bottom: int,
    left: int, right: int,
) -> int:
    """Bytes of one halo-padded chunk slab."""
    return (rows + top + bottom) * (nx + left + right) * itemsize


def choose_chunk_rows(
    ny: int,
    nx: int,
    itemsize: int,
    *,
    top: int = 0,
    bottom: int = 0,
    left: int = 0,
    right: int = 0,
    max_tile_bytes: int | None = None,
    streams: int | None = None,
) -> int:
    """Pick the row-chunk height (cuSten's per-stream tile of rows).

    The largest divisor of ``ny`` whose halo-padded slab fits the
    ``max_tile_bytes`` budget; among equally feasible heights, ones whose
    chunk count is a multiple of ``streams`` are preferred so the pipeline
    has no ragged tail group.  Falls back to single-row chunks when even
    one padded row exceeds the budget (nothing smaller exists).
    """
    budget = math.inf if max_tile_bytes is None else max_tile_bytes
    feasible = [
        r
        for r in _divisors_desc(ny)
        if slab_bytes(r, nx, itemsize, top=top, bottom=bottom,
                      left=left, right=right) <= budget
    ]
    if not feasible:
        return 1
    if streams and streams > 1:
        aligned = [r for r in feasible if (ny // r) % streams == 0]
        if aligned:
            return aligned[0]
    return feasible[0]


def _effective_streams(streams: int | None, n_chunks: int) -> int:
    """Largest group width <= ``streams`` that divides the chunk count."""
    if not streams or streams <= 1:
        return 1
    return math.gcd(min(streams, n_chunks), n_chunks)


# ---------------------------------------------------------------------------
# Halo padding + slab evaluation
# ---------------------------------------------------------------------------


def _slab_windows(
    slab: jnp.ndarray, *, top: int, bottom: int, left: int, right: int,
    rows: int, nx: int,
):
    """The stencil windows of a halo-padded slab, in the §V.B row-major
    order shared with :func:`repro.kernels.ref.stencil2d_ref` — same values,
    same reduction order, hence identical results."""
    wins = []
    for a in range(top + bottom + 1):
        for b in range(left + right + 1):
            wins.append(jax.lax.slice(slab, (a, b), (a + rows, b + nx)))
    return wins


def _slab_apply_pallas(
    slab, coeffs, *, point_fn, left, right, top, bottom, rows, nx, interpret,
):
    """Evaluate one slab with the Pallas kernel: the slab *is* a small field
    and ``bc='np'`` makes the kernel compute exactly the full-support
    interior — which is exactly the chunk."""
    from repro.kernels.stencil2d import stencil2d_pallas
    from repro.util import pick_tile_any

    sy, sx = slab.shape
    out = stencil2d_pallas(
        slab,
        coeffs,
        jnp.zeros_like(slab),
        point_fn=point_fn,
        left=left,
        right=right,
        top=top,
        bottom=bottom,
        bc="np",
        ty=pick_tile_any(sy),
        tx=pick_tile_any(sx),
        interpret=interpret,
    )
    return jax.lax.slice(out, (top, left), (top + rows, left + nx))


# ---------------------------------------------------------------------------
# The streamed executor
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "point_fn", "left", "right", "top", "bottom", "bc", "rows",
        "streams", "compute", "interpret",
    ),
    donate_argnums=(2,),
)
def _stream_exec(
    padded: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_buf: jnp.ndarray,
    out_init: jnp.ndarray | None,
    *,
    point_fn: Callable,
    left: int,
    right: int,
    top: int,
    bottom: int,
    bc: str,
    rows: int,
    streams: int,
    compute: str,
    interpret: bool,
):
    """The pipelined chunk loop.  ``out_buf`` is donated: stores reuse the
    buffer while the next group's loads are in flight (double buffering)."""
    ny, nx = out_buf.shape
    n_chunks = ny // rows
    starts = jnp.arange(n_chunks, dtype=jnp.int32) * rows
    groups = starts.reshape(n_chunks // streams, streams)

    def compute_chunk(start):
        zero = jnp.zeros_like(start)
        slab = jax.lax.dynamic_slice(
            padded, (start, zero), (rows + top + bottom, nx + left + right)
        )
        if compute == "pallas":
            val = _slab_apply_pallas(
                slab, coeffs, point_fn=point_fn, left=left, right=right,
                top=top, bottom=bottom, rows=rows, nx=nx,
                interpret=interpret,
            )
        else:
            val = point_fn(
                _slab_windows(
                    slab, top=top, bottom=bottom, left=left, right=right,
                    rows=rows, nx=nx,
                ),
                coeffs,
            )
        if bc == "np":
            gj = start + jax.lax.broadcasted_iota(jnp.int32, (rows, nx), 0)
            gi = jax.lax.broadcasted_iota(jnp.int32, (rows, nx), 1)
            mask = (
                (gi >= left) & (gi < nx - right)
                & (gj >= top) & (gj < ny - bottom)
            )
            base = jax.lax.dynamic_slice(out_init, (start, zero), (rows, nx))
            val = jnp.where(mask, val, base.astype(val.dtype))
        return val

    def body(out, group):
        vals = jax.vmap(compute_chunk)(group)  # streams chunks in flight

        def write(k, o):
            return jax.lax.dynamic_update_slice(
                o, vals[k].astype(o.dtype), (group[k], jnp.zeros_like(group[k]))
            )

        return jax.lax.fori_loop(0, streams, write, out), None

    out, _ = jax.lax.scan(body, out_buf, groups)
    return out


def stream_stencil_apply(
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
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_rows: int | None = None,
    compute: str = "jnp",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Streamed 2D stencil apply: identical contract (and results) to
    :func:`repro.kernels.ops.stencil_apply`, but the field is processed as
    halo-padded row-chunks so peak working-set is one slab, not the domain.

    ``chunk_rows`` overrides the geometry; otherwise it is derived from
    ``max_tile_bytes``.  ``compute`` selects the per-slab evaluator:
    ``'jnp'`` (shifted-window FMAs, bitwise-identical to the monolithic jnp
    path) or ``'pallas'`` (each slab through ``stencil2d_pallas``).
    """
    ny, nx = data.shape
    if bc not in ("periodic", "np"):
        raise ValueError(f"bc must be 'periodic' or 'np', got {bc!r}")
    if compute not in ("jnp", "pallas"):
        raise ValueError(f"compute must be 'jnp' or 'pallas', got {compute!r}")
    rows = chunk_rows or choose_chunk_rows(
        ny, nx, jnp.dtype(data.dtype).itemsize,
        top=top, bottom=bottom, left=left, right=right,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if ny % rows:
        raise ValueError(f"chunk_rows={rows} must divide ny={ny}")
    n_chunks = ny // rows

    if bc == "np" and out_init is None:
        out_init = jnp.zeros_like(data)

    if interpret is None:
        interpret = not ops.on_tpu()

    padded = ops.pad_field(
        data, top=top, bottom=bottom, left=left, right=right, bc=bc
    )
    out_buf = jnp.zeros_like(data)
    return _stream_exec(
        padded,
        coeffs,
        out_buf,
        out_init,
        point_fn=point_fn,
        left=left,
        right=right,
        top=top,
        bottom=bottom,
        bc=bc,
        rows=rows,
        streams=_effective_streams(streams, n_chunks),
        compute=compute,
        interpret=interpret,
    )


def _slab_windows_3d(slab: jnp.ndarray, *, halos, rows: int, ny: int, nx: int):
    """The 3D stencil windows of a fully halo-padded z-slab, in the z-major
    order shared with :func:`repro.kernels.ref.stencil3d_ref` — same
    values, same reduction order, hence identical results."""
    fr, bk, tp, bt, lf, rt = halos
    wins = []
    for c in range(fr + bk + 1):
        for a in range(tp + bt + 1):
            for b in range(lf + rt + 1):
                wins.append(
                    jax.lax.slice(
                        slab, (c, a, b), (c + rows, a + ny, b + nx)
                    )
                )
    return wins


def _slab_apply_pallas_3d(slab, coeffs, *, point_fn, halos, rows, ny, nx,
                          interpret):
    """Evaluate one fully halo-padded z-slab with the 3D Pallas kernel:
    the slab *is* a small field and ``bc='np'`` makes the kernel compute
    exactly the full-support interior — which is exactly the chunk.
    Awkward slab extents route through the alignment-padded dispatch in
    :func:`repro.kernels.ops.stencil_apply_3d`."""
    fr, bk, tp, bt, lf, rt = halos
    out = ops.stencil_apply_3d(
        slab,
        coeffs,
        jnp.zeros_like(slab),
        point_fn=point_fn,
        halos=halos,
        bc="np",
        backend="pallas",
        interpret=interpret,
    )
    return jax.lax.slice(out, (fr, tp, lf), (fr + rows, tp + ny, lf + nx))


@functools.partial(
    jax.jit,
    static_argnames=(
        "point_fn", "halos", "bc", "rows", "streams", "compute", "interpret",
    ),
    donate_argnums=(2,),
)
def _stream_exec_3d(
    padded: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_buf: jnp.ndarray,
    out_init: jnp.ndarray | None,
    *,
    point_fn: Callable,
    halos,
    bc: str,
    rows: int,
    streams: int,
    compute: str,
    interpret: bool,
):
    """The pipelined z-slab loop — :func:`_stream_exec` one axis deeper.
    ``out_buf`` is donated: stores reuse the buffer while the next group's
    loads are in flight (double buffering)."""
    fr, bk, tp, bt, lf, rt = halos
    nz, ny, nx = out_buf.shape
    n_chunks = nz // rows
    starts = jnp.arange(n_chunks, dtype=jnp.int32) * rows
    groups = starts.reshape(n_chunks // streams, streams)

    def compute_chunk(start):
        zero = jnp.zeros_like(start)
        slab = jax.lax.dynamic_slice(
            padded,
            (start, zero, zero),
            (rows + fr + bk, ny + tp + bt, nx + lf + rt),
        )
        if compute == "pallas":
            val = _slab_apply_pallas_3d(
                slab, coeffs, point_fn=point_fn, halos=halos,
                rows=rows, ny=ny, nx=nx, interpret=interpret,
            )
        else:
            val = point_fn(
                _slab_windows_3d(slab, halos=halos, rows=rows, ny=ny, nx=nx),
                coeffs,
            )
        if bc == "np":
            gk = start + jax.lax.broadcasted_iota(jnp.int32, (rows, ny, nx), 0)
            gj = jax.lax.broadcasted_iota(jnp.int32, (rows, ny, nx), 1)
            gi = jax.lax.broadcasted_iota(jnp.int32, (rows, ny, nx), 2)
            mask = (
                (gk >= fr) & (gk < nz - bk)
                & (gj >= tp) & (gj < ny - bt)
                & (gi >= lf) & (gi < nx - rt)
            )
            base = jax.lax.dynamic_slice(
                out_init, (start, zero, zero), (rows, ny, nx)
            )
            val = jnp.where(mask, val, base.astype(val.dtype))
        return val

    def body(out, group):
        vals = jax.vmap(compute_chunk)(group)  # streams chunks in flight

        def write(k, o):
            zero = jnp.zeros_like(group[k])
            return jax.lax.dynamic_update_slice(
                o, vals[k].astype(o.dtype), (group[k], zero, zero)
            )

        return jax.lax.fori_loop(0, streams, write, out), None

    out, _ = jax.lax.scan(body, out_buf, groups)
    return out


def stream_stencil3d_apply(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = weighted_point_fn,
    halos=(0, 0, 0, 0, 0, 0),
    bc: str = "periodic",
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_slabs: int | None = None,
    compute: str = "jnp",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Streamed 3D stencil apply: identical contract (and results) to
    :func:`repro.kernels.ops.stencil_apply_3d`, but the ``(nz, ny, nx)``
    field is processed as halo-padded z-slab chunks so peak working-set is
    one slab, not the domain — cuSten's row streaming lifted one axis up.

    ``chunk_slabs`` overrides the geometry (slabs of that many z-planes);
    otherwise it is derived from ``max_tile_bytes``.  ``compute`` selects
    the per-slab evaluator exactly as in :func:`stream_stencil_apply`.
    """
    nz, ny, nx = data.shape
    fr, bk, tp, bt, lf, rt = halos
    if bc not in ("periodic", "np"):
        raise ValueError(f"bc must be 'periodic' or 'np', got {bc!r}")
    if compute not in ("jnp", "pallas"):
        raise ValueError(f"compute must be 'jnp' or 'pallas', got {compute!r}")
    rows = chunk_slabs or choose_chunk_rows(
        nz, (ny + tp + bt) * (nx + lf + rt),
        jnp.dtype(data.dtype).itemsize,
        top=fr, bottom=bk,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if nz % rows:
        raise ValueError(f"chunk_slabs={rows} must divide nz={nz}")
    n_chunks = nz // rows

    if bc == "np" and out_init is None:
        out_init = jnp.zeros_like(data)

    if interpret is None:
        interpret = not ops.on_tpu()

    padded = ops.pad_field_3d(data, halos=halos, bc=bc)
    out_buf = jnp.zeros_like(data)
    return _stream_exec_3d(
        padded,
        coeffs,
        out_buf,
        out_init,
        point_fn=point_fn,
        halos=tuple(int(h) for h in halos),
        bc=bc,
        rows=rows,
        streams=_effective_streams(streams, n_chunks),
        compute=compute,
        interpret=interpret,
    )


def stream_batch1d_apply(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = weighted_point_fn,
    left: int = 0,
    right: int = 0,
    bc: str = "periodic",
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_rows: int | None = None,
    compute: str = "jnp",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Streamed batched-1D apply on a ``(B, M)`` stack.

    Rows never couple, so a batched-1D stencil is the ``top=bottom=0``
    special case of the 2D executor: chunks are groups of whole rows with no
    y halo at all — the cheapest possible streaming.  ``compute='pallas'``
    runs each chunk through the 2D kernel (an x-direction 2D stencil is
    exactly the batched-1D operation)."""
    return stream_stencil_apply(
        data,
        coeffs,
        out_init,
        point_fn=point_fn,
        left=left,
        right=right,
        top=0,
        bottom=0,
        bc=bc,
        streams=streams,
        max_tile_bytes=max_tile_bytes,
        chunk_rows=chunk_rows,
        compute=compute,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Streamed batched pentadiagonal solves (the ADI implicit half)
# ---------------------------------------------------------------------------


def choose_chunk_cols(
    M: int, N: int, itemsize: int, *, max_tile_bytes: int | None,
) -> int:
    """Column-chunk width for a batched ``(M, N)`` solve under the same
    byte budget (each chunk is ``M * cols`` values; columns are independent
    systems so any divisor of ``N`` is valid)."""
    if max_tile_bytes is None:
        return N
    feasible = [c for c in _divisors_desc(N) if M * c * itemsize <= max_tile_bytes]
    return feasible[0] if feasible else 1


def stream_penta_solve(
    fac,
    rhs: jnp.ndarray,
    *,
    cyclic: bool,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_cols: int | None = None,
    backend: str = "jnp",
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Streamed batched pentadiagonal substitution on an ``(M, N)`` RHS.

    The batch axis is cut into column chunks solved group-by-group under a
    scan with a donated output buffer — the implicit-sweep counterpart of
    :func:`stream_stencil_apply`, so a full ADI step can run tile-by-tile.
    """
    from repro.kernels.penta import (
        cyclic_penta_solve_factored,
        penta_solve_factored,
    )

    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    M, N = rhs.shape
    cols = chunk_cols or choose_chunk_cols(
        M, N, jnp.dtype(rhs.dtype).itemsize, max_tile_bytes=max_tile_bytes
    )
    if N % cols:
        raise ValueError(f"chunk_cols={cols} must divide N={N}")
    n_chunks = N // cols
    if n_chunks == 1:
        solve = cyclic_penta_solve_factored if cyclic else penta_solve_factored
        out = solve(fac, rhs, backend=backend, interpret=interpret, unroll=unroll)
        return out[:, 0] if squeeze else out

    out = _penta_stream_exec(
        fac,
        rhs,
        jnp.zeros_like(rhs),
        cols=cols,
        group=_effective_streams(streams, n_chunks),
        cyclic=cyclic,
        backend=backend,
        interpret=interpret,
        unroll=unroll,
    )
    return out[:, 0] if squeeze else out


@functools.partial(
    jax.jit,
    static_argnames=("cols", "group", "cyclic", "backend", "interpret", "unroll"),
    donate_argnums=(2,),
)
def _penta_stream_exec(
    fac, rhs, out_buf, *, cols, group, cyclic, backend, interpret, unroll=1
):
    """Module-level jit of the column-chunk pipeline (a per-call closure
    would retrace on every Compute — this is the ADI hot path)."""
    from repro.kernels.penta import (
        cyclic_penta_solve_factored,
        penta_solve_factored,
    )

    solve = cyclic_penta_solve_factored if cyclic else penta_solve_factored
    M, N = rhs.shape
    gcols = cols * group  # columns per scan step (one group-slab)
    n_steps = N // gcols
    starts = jnp.arange(n_steps, dtype=jnp.int32) * gcols

    # group chunks are one contiguous (M, group * cols) slab of independent
    # systems: the batched substitution is the group's parallelism, so the
    # whole group is a single solve (a vmap stage would re-run the
    # M-length recurrence loop per chunk for no working-set benefit)
    def body(out, start):
        chunk = jax.lax.dynamic_slice(
            rhs, (jnp.zeros_like(start), start), (M, gcols)
        )
        val = solve(
            fac, chunk, backend=backend, interpret=interpret, unroll=unroll
        )
        return jax.lax.dynamic_update_slice(
            out, val, (jnp.zeros_like(start), start)
        ), None

    out, _ = jax.lax.scan(body, out_buf, starts)
    return out


def stream_penta_solve_rows(
    fac,
    rhs: jnp.ndarray,
    *,
    cyclic: bool,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_rows: int | None = None,
    backend: str = "jnp",
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Streamed *row-layout* pentadiagonal solve on a ``(B, M)`` RHS.

    The transpose-free x-sweep counterpart of :func:`stream_penta_solve`:
    every row is one independent system (recurrence along axis 1), so the
    batch axis streams as plain row chunks with no halo at all.
    """
    from repro.kernels.penta import (
        cyclic_penta_solve_factored_rows,
        penta_solve_factored_rows,
    )

    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[None, :]
    B, M = rhs.shape
    rows = chunk_rows or choose_chunk_rows(
        B, M, jnp.dtype(rhs.dtype).itemsize,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if B % rows:
        raise ValueError(f"chunk_rows={rows} must divide B={B}")
    n_chunks = B // rows
    if n_chunks == 1:
        solve = (
            cyclic_penta_solve_factored_rows
            if cyclic
            else penta_solve_factored_rows
        )
        out = solve(fac, rhs, backend=backend, interpret=interpret, unroll=unroll)
        return out[0] if squeeze else out

    out = _penta_stream_rows_exec(
        fac,
        rhs,
        jnp.zeros_like(rhs),
        rows=rows,
        group=_effective_streams(streams, n_chunks),
        cyclic=cyclic,
        backend=backend,
        interpret=interpret,
        unroll=unroll,
    )
    return out[0] if squeeze else out


@functools.partial(
    jax.jit,
    static_argnames=("rows", "group", "cyclic", "backend", "interpret", "unroll"),
    donate_argnums=(2,),
)
def _penta_stream_rows_exec(
    fac, rhs, out_buf, *, rows, group, cyclic, backend, interpret, unroll=1
):
    """Row-chunk pipeline for the transpose-free x-sweep.

    Unlike the column pipeline there is no vmapped group stage: ``group``
    row chunks are one contiguous ``(group * rows, M)`` slab of
    independent systems, so the whole group is a *single* batched solve —
    the substitution itself is the group's parallelism (its batch axis is
    what vmap would have added, minus the gather/scatter it would cost).
    """
    from repro.kernels.penta import (
        cyclic_penta_solve_factored_rows,
        penta_solve_factored_rows,
    )

    solve = (
        cyclic_penta_solve_factored_rows
        if cyclic
        else penta_solve_factored_rows
    )
    B, M = rhs.shape
    grows = rows * group  # rows per scan step (one group-slab)
    n_steps = B // grows
    starts = jnp.arange(n_steps, dtype=jnp.int32) * grows

    def body(out, start):
        chunk = jax.lax.dynamic_slice(
            rhs, (start, jnp.zeros_like(start)), (grows, M)
        )
        val = solve(
            fac, chunk, backend=backend, interpret=interpret, unroll=unroll
        )
        return jax.lax.dynamic_update_slice(
            out, val, (start, jnp.zeros_like(start))
        ), None

    out, _ = jax.lax.scan(body, out_buf, starts)
    return out


def stream_penta_solve_mid(
    fac,
    rhs: jnp.ndarray,
    *,
    cyclic: bool,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_planes: int | None = None,
    backend: str = "jnp",
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Streamed *plane-layout* pentadiagonal solve on a ``(P, M, N)`` RHS.

    The 3D y-sweep counterpart of :func:`stream_penta_solve_rows`: every
    (p, :, n) line is one independent system (recurrence along axis 1), so
    the plane axis streams as plain z-slab chunks with no halo at all.
    """
    from repro.kernels.penta import (
        cyclic_penta_solve_factored_mid,
        penta_solve_factored_mid,
    )

    P, M, N = rhs.shape
    planes = chunk_planes or choose_chunk_rows(
        P, M * N, jnp.dtype(rhs.dtype).itemsize,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if P % planes:
        raise ValueError(f"chunk_planes={planes} must divide P={P}")
    n_chunks = P // planes
    solve = (
        cyclic_penta_solve_factored_mid if cyclic else penta_solve_factored_mid
    )
    if n_chunks == 1:
        return solve(fac, rhs, backend=backend, interpret=interpret,
                     unroll=unroll)
    return _penta_stream_mid_exec(
        fac,
        rhs,
        jnp.zeros_like(rhs),
        planes=planes,
        group=_effective_streams(streams, n_chunks),
        cyclic=cyclic,
        backend=backend,
        interpret=interpret,
        unroll=unroll,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "planes", "group", "cyclic", "backend", "interpret", "unroll",
    ),
    donate_argnums=(2,),
)
def _penta_stream_mid_exec(
    fac, rhs, out_buf, *, planes, group, cyclic, backend, interpret, unroll=1
):
    """Plane-chunk pipeline for the transpose-free 3D y-sweep.  As with the
    row pipeline, ``group`` plane chunks are one contiguous
    ``(group * planes, M, N)`` slab of independent systems, so the whole
    group is a *single* batched solve."""
    from repro.kernels.penta import (
        cyclic_penta_solve_factored_mid,
        penta_solve_factored_mid,
    )

    solve = (
        cyclic_penta_solve_factored_mid if cyclic else penta_solve_factored_mid
    )
    P, M, N = rhs.shape
    gplanes = planes * group  # planes per scan step (one group-slab)
    n_steps = P // gplanes
    starts = jnp.arange(n_steps, dtype=jnp.int32) * gplanes

    def body(out, start):
        zero = jnp.zeros_like(start)
        chunk = jax.lax.dynamic_slice(
            rhs, (start, zero, zero), (gplanes, M, N)
        )
        val = solve(
            fac, chunk, backend=backend, interpret=interpret, unroll=unroll
        )
        return jax.lax.dynamic_update_slice(
            out, val, (start, zero, zero)
        ), None

    out, _ = jax.lax.scan(body, out_buf, starts)
    return out


# ---------------------------------------------------------------------------
# Streamed fused Cahn–Hilliard RHS (halo-2, two-field slabs)
# ---------------------------------------------------------------------------


def stream_ch_rhs(
    c_n: jnp.ndarray,
    c_nm1: jnp.ndarray,
    *,
    dt: float,
    D: float,
    gamma: float,
    inv_h2: float,
    inv_h4: float,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_rows: int | None = None,
) -> jnp.ndarray:
    """Streamed fused explicit RHS of the paper's eq. (2a) (periodic,
    halo 2, two input fields per slab).  Matches
    :func:`repro.kernels.ref.ch_rhs_ref` exactly: rolls within a slab only
    corrupt the slab's own halo ring, which the interior slice discards."""
    ny, nx = c_n.shape
    h = 2  # biharmonic halo
    rows = chunk_rows or choose_chunk_rows(
        ny, nx, jnp.dtype(c_n.dtype).itemsize,
        top=h, bottom=h, left=h, right=h,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if ny % rows:
        raise ValueError(f"chunk_rows={rows} must divide ny={ny}")
    n_chunks = ny // rows

    pad = functools.partial(
        ops.pad_field, top=h, bottom=h, left=h, right=h, bc="periodic"
    )
    return _ch_rhs_stream_exec(
        pad(c_n),
        pad(c_nm1),
        jnp.zeros_like(c_n),
        rows=rows,
        group=_effective_streams(streams, n_chunks),
        dt=float(dt),
        D=float(D),
        gamma=float(gamma),
        inv_h2=float(inv_h2),
        inv_h4=float(inv_h4),
    )


@functools.partial(
    jax.jit,
    static_argnames=("rows", "group", "dt", "D", "gamma", "inv_h2", "inv_h4"),
    donate_argnums=(2,),
)
def _ch_rhs_stream_exec(
    p_n, p_nm1, out_buf, *, rows, group, dt, D, gamma, inv_h2, inv_h4
):
    """Module-level jit of the fused-RHS chunk pipeline (scalars static:
    they are compile-time constants of a fixed-dt solver)."""
    from repro.kernels.ref import ch_rhs_ref

    h = 2
    ny, nx = out_buf.shape
    n_chunks = ny // rows
    starts = jnp.arange(n_chunks, dtype=jnp.int32) * rows
    groups = starts.reshape(n_chunks // group, group)

    def one(start):
        size = (rows + 2 * h, nx + 2 * h)
        zero = jnp.zeros_like(start)
        s_n = jax.lax.dynamic_slice(p_n, (start, zero), size)
        s_m = jax.lax.dynamic_slice(p_nm1, (start, zero), size)
        val = ch_rhs_ref(
            s_n, s_m, dt=dt, D=D, gamma=gamma,
            inv_h2=inv_h2, inv_h4=inv_h4,
        )
        return jax.lax.slice(val, (h, h), (h + rows, h + nx))

    def body(out, g):
        vals = jax.vmap(one)(g)

        def write(k, o):
            return jax.lax.dynamic_update_slice(
                o, vals[k], (g[k], jnp.zeros_like(g[k]))
            )

        return jax.lax.fori_loop(0, group, write, out), None

    out, _ = jax.lax.scan(body, out_buf, groups)
    return out


# ---------------------------------------------------------------------------
# Streamed fused RHS + transpose-free x-sweep (the ADI hot loop, chunked)
# ---------------------------------------------------------------------------


def stream_ch_rhs_xsweep(
    c_n: jnp.ndarray,
    c_nm1: jnp.ndarray,
    fac_x,
    *,
    dt: float,
    D: float,
    gamma: float,
    inv_h2: float,
    inv_h4: float,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_rows: int | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    unroll: int = 1,
) -> jnp.ndarray:
    """Streamed ``L_x^{-1} rhs(c_n, c_nm1)``: each row chunk assembles its
    explicit RHS from the halo-padded slabs and feeds it *directly* into
    the row-layout x-sweep — the RHS never exists as a full-field
    intermediate, and no transpose appears anywhere.  One streamed pass
    replaces the old rhs-pass + transpose + column-solve + transpose
    chain."""
    ny, nx = c_n.shape
    h = 2  # biharmonic halo
    rows = chunk_rows or choose_chunk_rows(
        ny, nx, jnp.dtype(c_n.dtype).itemsize,
        top=h, bottom=h, left=h, right=h,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if ny % rows:
        raise ValueError(f"chunk_rows={rows} must divide ny={ny}")
    n_chunks = ny // rows

    pad = functools.partial(
        ops.pad_field, top=h, bottom=h, left=h, right=h, bc="periodic"
    )
    return _ch_xsweep_stream_exec(
        pad(c_n),
        pad(c_nm1),
        fac_x,
        jnp.zeros_like(c_n),
        rows=rows,
        group=_effective_streams(streams, n_chunks),
        dt=float(dt),
        D=float(D),
        gamma=float(gamma),
        inv_h2=float(inv_h2),
        inv_h4=float(inv_h4),
        backend=resolve_compute(backend),
        interpret=interpret,
        unroll=unroll,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "rows", "group", "dt", "D", "gamma", "inv_h2", "inv_h4",
        "backend", "interpret", "unroll",
    ),
    donate_argnums=(3,),
)
def _ch_xsweep_stream_exec(
    p_n, p_nm1, fac_x, out_buf, *, rows, group, dt, D, gamma, inv_h2,
    inv_h4, backend="jnp", interpret=None, unroll=1,
):
    """Chunk pipeline: slab -> windowed RHS into the donated buffer, then
    one in-place row-layout solve.

    Only the RHS assembly needs row-chunk streaming (its halo-2 slabs are
    the bounded working set); the x-sweep substitution that consumes the
    buffer is *inherently* streaming along the recurrence axis — each
    iteration touches one rhs column plus two carry columns — so chunking
    its batch would only multiply the sequential-loop overhead by the
    chunk count for zero working-set benefit."""
    from repro.kernels.penta import cyclic_penta_solve_factored_rows
    from repro.kernels.ref import ch_rhs_band

    h = 2
    ny, nx = out_buf.shape
    grows = rows * group  # rows per scan step (one group-slab)
    n_steps = ny // grows
    starts = jnp.arange(n_steps, dtype=jnp.int32) * grows

    def body(out, start):
        size = (grows + 2 * h, nx + 2 * h)
        zero = jnp.zeros_like(start)
        s_n = jax.lax.dynamic_slice(p_n, (start, zero), size)
        s_m = jax.lax.dynamic_slice(p_nm1, (start, zero), size)
        rhs = ch_rhs_band(
            s_n, s_m, grows, nx, dt=dt, D=D, gamma=gamma,
            inv_h2=inv_h2, inv_h4=inv_h4,
        )
        return jax.lax.dynamic_update_slice(out, rhs, (start, zero)), None

    out, _ = jax.lax.scan(body, out_buf, starts)
    return cyclic_penta_solve_factored_rows(
        fac_x, out, backend=backend, interpret=interpret, unroll=unroll
    )


# ---------------------------------------------------------------------------
# Multi-device path: streamed chunks through the dist_ch mesh via shard_map
# ---------------------------------------------------------------------------


def stream_stencil_apply_dist(
    plan,
    field: jnp.ndarray,
    dd,
    out_init: jnp.ndarray | None = None,
    *,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    chunk_rows: int | None = None,
) -> jnp.ndarray:
    """Streamed apply with each chunk sharded over the mesh.

    Streaming in y (host-side chunk loop over halo-padded slabs), domain
    decomposition in x: inside ``shard_map`` each device holds a column
    block of the current slab, exchanges its x halo with ``ppermute``
    (:func:`repro.core.domain._exchange_1d`), computes its piece, and the
    chunk reassembles under the mesh sharding — the multi-GPU streaming
    layout of paper §VI.B on top of :mod:`repro.core.dist_ch`'s mesh.

    ``plan`` is a :class:`~repro.core.stencil.Stencil2D`; ``dd`` a
    :class:`~repro.core.domain.DomainDecomposition` whose ``x_axis`` carries
    the chunk's x extent (its ``y_axis`` is ignored — y is streamed, not
    sharded).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.domain import _exchange_1d

    ny, nx = field.shape
    top, bottom, left, right = plan.top, plan.bottom, plan.left, plan.right
    bc = plan.bc
    n_x = dd.n_shards(dd.x_axis)
    if nx % n_x:
        raise ValueError(f"mesh x axis ({n_x}) must divide nx={nx}")
    rows = chunk_rows or choose_chunk_rows(
        ny, nx, jnp.dtype(field.dtype).itemsize,
        top=top, bottom=bottom, left=left, right=right,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    if ny % rows:
        raise ValueError(f"chunk_rows={rows} must divide ny={ny}")
    n_chunks = ny // rows
    nx_loc = nx // n_x

    if bc == "np" and out_init is None:
        out_init = jnp.zeros_like(field)

    # y halos gathered host-side into each slab; x halos exchanged on-mesh.
    padded = ops.pad_field(field, top=top, bottom=bottom, left=0, right=0, bc=bc)

    def local(slab_loc, init_loc, start):
        lf, rt = _exchange_1d(slab_loc, left, right, 1, dd.x_axis, n_x)
        parts = [p for p in (lf, slab_loc, rt) if p is not None]
        band = jnp.concatenate(parts, axis=1) if len(parts) > 1 else slab_loc
        val = plan.point_fn(
            _slab_windows(
                band, top=top, bottom=bottom, left=left, right=right,
                rows=rows, nx=nx_loc,
            ),
            plan.coeffs,
        )
        if bc == "np":
            ix = jax.lax.axis_index(dd.x_axis) if dd.x_axis else 0
            gj = start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, nx_loc), 0
            )
            gi = ix * nx_loc + jax.lax.broadcasted_iota(
                jnp.int32, (rows, nx_loc), 1
            )
            mask = (
                (gi >= left) & (gi < nx - right)
                & (gj >= top) & (gj < ny - bottom)
            )
            val = jnp.where(mask, val, init_loc.astype(val.dtype))
        return val

    spec = P(None, dd.x_axis)
    f = jax.shard_map(
        local,
        mesh=dd.mesh,
        in_specs=(spec, spec if bc == "np" else None, P()),
        out_specs=spec,
        check_vma=False,
    )

    chunks = []
    for k in range(n_chunks):
        slab = jax.lax.dynamic_slice(
            padded, (k * rows, 0), (rows + top + bottom, nx)
        )
        init = (
            jax.lax.dynamic_slice(out_init, (k * rows, 0), (rows, nx))
            if bc == "np"
            else None
        )
        chunks.append(f(slab, init, jnp.int32(k * rows)))
    return jnp.concatenate(chunks, axis=0)


# ---------------------------------------------------------------------------
# Streaming decision shared by the plan API
# ---------------------------------------------------------------------------


def resolve_compute(backend: str) -> str:
    """Map a plan ``backend`` to the streamed slab evaluator, mirroring the
    monolithic auto dispatch: ``pallas`` stays pallas, ``auto`` follows
    ``on_tpu()`` (so streaming never silently bypasses the kernel the
    monolithic path would have used), anything else is jnp."""
    if backend == "pallas":
        return "pallas"
    if backend == "auto":
        return "pallas" if ops.on_tpu() else "jnp"
    return "jnp"


def should_stream(
    shape: tuple[int, ...],
    itemsize: int,
    *,
    streams: int | None,
    max_tile_bytes: int | None,
) -> bool:
    """The plan routes through the streamed executor when a knob is set and
    the field actually exceeds one tile (or multiple streams are asked
    for).  A field within budget with ``streams in (None, 0, 1)`` keeps the
    monolithic path — streaming is free to decline, exactly like cuSten
    running single-stream when the domain fits."""
    nbytes = itemsize
    for s in shape:
        nbytes *= s
    if max_tile_bytes is not None and nbytes > max_tile_bytes:
        return True
    return bool(streams and streams > 1)


def n_chunks_for(
    ny: int, nx: int, itemsize: int, *, halos=(0, 0, 0, 0),
    max_tile_bytes: int | None = None, streams: int | None = None,
) -> int:
    """How many row-chunks the executor would use (introspection helper —
    tests and benchmarks use it to size '4x larger than one chunk')."""
    top, bottom, left, right = halos
    rows = choose_chunk_rows(
        ny, nx, itemsize, top=top, bottom=bottom, left=left, right=right,
        max_tile_bytes=max_tile_bytes, streams=streams,
    )
    return ceil_div(ny, rows)
