"""Public jit'd entry points for the Pallas kernels, with backend dispatch.

Every op takes ``backend ∈ {'auto', 'pallas', 'jnp'}``:

- ``pallas``  — the TPU kernel (interpret mode when no TPU is attached, so
  the same call runs on CPU; interpret mode cannot show what the TPU
  compiler refuses, which ``tests/test_tpu_compile.py`` checks);
- ``jnp``     — the pure-jnp oracle from :mod:`repro.kernels.ref`, which XLA
  fuses well and is the production CPU path;
- ``auto``    — pallas when the kernel's structural constraints (tile
  divisibility, halo <= tile, a dtype Mosaic compiles) hold on a TPU
  backend, otherwise jnp.

This mirrors cuSten's "the library picks the implementation details" design:
callers state the math, dispatch is the library's job.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.stencil1d_batch import stencil1d_batch_pallas
from repro.kernels.stencil2d import stencil2d_pallas
from repro.kernels.stencil3d import stencil3d_pallas
from repro.runtime import chaos as _chaos
from repro.util import pick_tile, pick_tile_any, pick_tile_padded


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_dispatch(kernel: str) -> None:
    """Chaos hook at the moment a Pallas path is chosen.

    Fires at *trace* time (these dispatchers run inside ``jit``), which
    is exactly when a real kernel failure (compile error, infeasible
    grid on this host) would surface — an injected ``backend_error``
    here exercises the serve engine's pallas→jnp degradation path."""
    _chaos.fire("pallas.dispatch", kernel=kernel)


def _tpu_pallas(dtype) -> bool:
    """On a TPU, ``auto`` may pick a Pallas kernel for this dtype: Mosaic
    compiles no 64-bit floats."""
    return on_tpu() and jnp.dtype(dtype).itemsize <= 4


def _should_interpret(interpret: bool | None) -> bool:
    return not on_tpu() if interpret is None else interpret


def checked_backend(kernel: str, problem: str | None, backend: str, interpret) -> str:
    """Resolve ``auto`` for a kernel whose TPU compile has a known
    ``problem`` at this shape (``None`` when it compiles), and refuse
    ``pallas`` for it on the chip."""
    if backend == "auto":
        return "pallas" if on_tpu() and problem is None else "jnp"
    if backend == "pallas" and problem is not None and not _should_interpret(interpret):
        raise ValueError(f"pallas {kernel} cannot run on this TPU: {problem}")
    return backend


def _pallas_ok(ny, nx, ty, tx, hx, hy) -> bool:
    return (ny % ty == 0) and (nx % tx == 0) and hx <= tx and hy <= ty


# public names for the plan-level grid-feasibility probes
# (repro.analysis rule `pallas_grid_feasible` via plan.grid_problems)
def pallas_grid_ok(ny, nx, ty, tx, hx, hy) -> bool:
    """Can a (ty, tx) tile grid with (hy, hx) halos cover (ny, nx)?"""
    return _pallas_ok(ny, nx, ty, tx, hx, hy)


def _aligned(t: int, align: int = 8) -> bool:
    """Sublane-aligned tile (the implicit-tile quality bar — an awkward
    extent like 127 should pad to 128, not run as one misaligned tile)."""
    return t % align == 0


def _lane_aligned(t: int, n: int) -> bool:
    """A last-axis tile Mosaic accepts: whole 128-lane vregs, or the whole
    extent."""
    return t % 128 == 0 or t == n


def pad_field(
    data: jnp.ndarray, *, top: int, bottom: int, left: int, right: int,
    bc: str,
) -> jnp.ndarray:
    """Pad the full field with its halo ring once; chunks then gather with a
    single contiguous ``dynamic_slice``.  Periodic wraps; ``np`` pads zeros
    (those windows are masked to ``out_init`` afterwards).  Shared by the
    streamed executor and the alignment-padded kernel dispatch."""
    if bc == "periodic":
        if top or bottom:
            parts = []
            if top:
                parts.append(data[-top:, :])
            parts.append(data)
            if bottom:
                parts.append(data[:bottom, :])
            data = jnp.concatenate(parts, axis=0)
        if left or right:
            parts = []
            if left:
                parts.append(data[:, -left:])
            parts.append(data)
            if right:
                parts.append(data[:, :right])
            data = jnp.concatenate(parts, axis=1)
        return data
    return jnp.pad(data, ((top, bottom), (left, right)))


def _stencil2d_pallas_padded(
    data, coeffs, out_init, *, point_fn, left, right, top, bottom, bc,
    ty, tx, py, px, interpret,
):
    """Pallas dispatch for awkward extents (prime/odd ``ny``/``nx``).

    Rather than degrading to one misaligned mega-tile (or a degenerate
    tile of 1), the field is halo-padded once (wrap or zeros by ``bc``)
    and grown with zeros to the aligned ``(py, px)`` tile multiple; the
    kernel runs in ``np`` mode — whose full-support interior is exactly
    the original domain — and the result is sliced back out.  The
    alignment zeros sit strictly beyond the halo ring, so no valid
    output ever reads them.
    """
    ny, nx = data.shape
    padded = pad_field(
        data, top=top, bottom=bottom, left=left, right=right, bc=bc
    )
    sy, sx = padded.shape
    padded = jnp.pad(padded, ((0, py - sy), (0, px - sx)))
    out = stencil2d_pallas(
        padded,
        coeffs,
        jnp.zeros_like(padded),
        point_fn=point_fn,
        left=left,
        right=right,
        top=top,
        bottom=bottom,
        bc="np",
        ty=ty,
        tx=tx,
        interpret=interpret,
    )
    out = jax.lax.slice(out, (top, left), (top + ny, left + nx))
    if bc == "np":
        if out_init is None:
            out_init = jnp.zeros_like(data)
        mask = jnp.asarray(
            _ref.interior_mask(
                (ny, nx), left=left, right=right, top=top, bottom=bottom
            )
        )
        out = jnp.where(mask, out, out_init.astype(out.dtype))
    return out


# Module-level jitted oracle entry points: a fresh jit(partial(...)) per call
# would miss jax's jit cache (keyed on function identity) and retrace every
# eager invocation.


@functools.partial(
    jax.jit,
    static_argnames=("point_fn", "left", "right", "top", "bottom", "bc"),
)
def _stencil2d_jnp(
    data, coeffs, out_init, *, point_fn, left, right, top, bottom, bc
):
    return _ref.stencil2d_ref(
        data,
        bc=bc,
        left=left,
        right=right,
        top=top,
        bottom=bottom,
        point_fn=point_fn,
        coeffs=coeffs,
        out_init=out_init,
    )


@functools.partial(
    jax.jit, static_argnames=("point_fn", "left", "right", "bc")
)
def _stencil1d_batch_jnp(data, coeffs, out_init, *, point_fn, left, right, bc):
    return _ref.stencil1d_batch_ref(
        data,
        bc=bc,
        left=left,
        right=right,
        point_fn=point_fn,
        coeffs=coeffs,
        out_init=out_init,
    )


def stencil_apply(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = _ref.weighted_point_fn,
    left: int = 0,
    right: int = 0,
    top: int = 0,
    bottom: int = 0,
    bc: str = "periodic",
    tile: tuple | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply a 2D stencil — the library's Compute primitive."""
    ny, nx = data.shape
    hx, hy = max(left, right), max(top, bottom)
    ty, tx = tile if tile is not None else (pick_tile(ny), pick_tile(nx))

    # explicit tiles keep the historical contract (divide + cover halo);
    # implicit tiles must additionally be sublane-aligned (and lane-aligned
    # along x), else the alignment-padded dispatch below takes over
    clean = _pallas_ok(ny, nx, ty, tx, hx, hy) and (
        tile is not None
        or (_aligned(ty) and _aligned(tx) and _lane_aligned(tx, nx))
    )
    if backend == "auto":
        backend = (
            "pallas"
            if _tpu_pallas(data.dtype)
            and (clean or (tile is None and hy <= ny and hx <= nx))
            else "jnp"
        )
    if backend == "pallas":
        _pallas_dispatch("stencil2d")
        if not clean:
            if tile is not None:
                raise ValueError(
                    f"pallas backend needs tile|field and halo<=tile; got "
                    f"field=({ny},{nx}) tile=({ty},{tx}) halo=({hy},{hx})"
                )
            # awkward extent (prime/odd): pad to an aligned tile multiple
            # instead of degrading to a mega-tile / tile of 1
            from repro.util import next_multiple

            sy, sx = ny + top + bottom, nx + left + right
            pty, py = pick_tile_padded(sy)
            ptx, px = pick_tile_padded(sx, align=128)
            if pty < hy:
                pty = next_multiple(hy, 8)
                py = next_multiple(sy, pty)
            if ptx < hx:
                ptx = next_multiple(hx, 8)
                px = next_multiple(sx, ptx)
            return _stencil2d_pallas_padded(
                data, coeffs, out_init,
                point_fn=point_fn, left=left, right=right, top=top,
                bottom=bottom, bc=bc, ty=pty, tx=ptx, py=py, px=px,
                interpret=_should_interpret(interpret),
            )
        return stencil2d_pallas(
            data,
            coeffs,
            out_init,
            point_fn=point_fn,
            left=left,
            right=right,
            top=top,
            bottom=bottom,
            bc=bc,
            ty=ty,
            tx=tx,
            interpret=_should_interpret(interpret),
        )
    if backend == "jnp":
        return _stencil2d_jnp(
            data, coeffs, out_init,
            point_fn=point_fn, left=left, right=right, top=top,
            bottom=bottom, bc=bc,
        )
    raise ValueError(f"unknown backend {backend!r}")


def _pallas_ok_1d(B, M, tb, tm, hm) -> bool:
    return (B % tb == 0) and (M % tm == 0) and hm <= tm


def pallas_grid_ok_1d(B, M, tb, tm, hm) -> bool:
    """Can a (tb, tm) tile grid with line halo hm cover the (B, M) stack?"""
    return _pallas_ok_1d(B, M, tb, tm, hm)


def _stencil1d_pallas_padded(
    data, coeffs, out_init, *, point_fn, left, right, bc, tb, tm, pb, pm,
    interpret,
):
    """Alignment-padded batched-1D dispatch (see
    :func:`_stencil2d_pallas_padded`): halo-pad the line axis, zero-grow
    both axes to tile multiples, run the kernel in ``np`` mode, slice the
    original stack back out.  Padded rows are junk rows that rows of the
    real stack never read (rows are independent)."""
    B, M = data.shape
    padded = pad_field(data, top=0, bottom=0, left=left, right=right, bc=bc)
    sb, sm = padded.shape
    padded = jnp.pad(padded, ((0, pb - sb), (0, pm - sm)))
    out = stencil1d_batch_pallas(
        padded,
        coeffs,
        jnp.zeros_like(padded),
        point_fn=point_fn,
        left=left,
        right=right,
        bc="np",
        tb=tb,
        tm=tm,
        interpret=interpret,
    )
    out = jax.lax.slice(out, (0, left), (B, left + M))
    if bc == "np":
        if out_init is None:
            out_init = jnp.zeros_like(data)
        cols = jnp.arange(M)
        mask = ((cols >= left) & (cols < M - right))[None, :]
        out = jnp.where(mask, out, out_init.astype(out.dtype))
    return out


def stencil_apply_batch1d(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = _ref.weighted_point_fn,
    left: int = 0,
    right: int = 0,
    bc: str = "periodic",
    tile: tuple | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply a 1D stencil along axis 1 of a ``(B, M)`` stack — the
    batched-1D Compute primitive (cuSten's ``1DBatch`` family).

    Same backend contract as :func:`stencil_apply`: ``auto`` picks the
    Pallas kernel when its structural constraints hold on a TPU (falling
    back to the jnp oracle for e.g. non-divisible batch counts), ``pallas``
    / ``jnp`` force the respective path.
    """
    B, M = data.shape
    hm = max(left, right)
    tb, tm = tile if tile is not None else (pick_tile_any(B), pick_tile_any(M))

    clean = _pallas_ok_1d(B, M, tb, tm, hm) and (
        tile is not None
        or (_aligned(tb) and _aligned(tm) and _lane_aligned(tm, M))
    )
    if backend == "auto":
        backend = (
            "pallas"
            if _tpu_pallas(data.dtype) and (clean or (tile is None and hm <= M))
            else "jnp"
        )
    if backend == "pallas":
        _pallas_dispatch("stencil1d_batch")
        if not clean:
            if tile is not None:
                raise ValueError(
                    f"pallas backend needs tile|stack and halo<=tile; got "
                    f"stack=({B},{M}) tile=({tb},{tm}) halo={hm}"
                )
            from repro.util import next_multiple

            sm = M + left + right
            ptb, pb = pick_tile_padded(B)
            ptm, pm = pick_tile_padded(sm, target=256, align=128)
            if ptm < hm:
                ptm = next_multiple(hm, 8)
                pm = next_multiple(sm, ptm)
            return _stencil1d_pallas_padded(
                data, coeffs, out_init,
                point_fn=point_fn, left=left, right=right, bc=bc,
                tb=ptb, tm=ptm, pb=pb, pm=pm,
                interpret=_should_interpret(interpret),
            )
        return stencil1d_batch_pallas(
            data,
            coeffs,
            out_init,
            point_fn=point_fn,
            left=left,
            right=right,
            bc=bc,
            tb=tb,
            tm=tm,
            interpret=_should_interpret(interpret),
        )
    if backend == "jnp":
        return _stencil1d_batch_jnp(
            data, coeffs, out_init,
            point_fn=point_fn, left=left, right=right, bc=bc,
        )
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# 3D stencils (paper §VI.A) — same dispatch contract as the 2D/1D families
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("point_fn", "halos", "bc"))
def _stencil3d_jnp(data, coeffs, out_init, *, point_fn, halos, bc):
    return _ref.stencil3d_ref(
        data,
        bc=bc,
        halos=halos,
        point_fn=point_fn,
        coeffs=coeffs,
        out_init=out_init,
    )


def _pallas_ok_3d(nz, ny, nx, tz, ty, hz, hy, hx) -> bool:
    return (
        nz % tz == 0 and ny % ty == 0 and hz <= tz and hy <= ty and hx <= nx
    )


def pallas_grid_ok_3d(nz, ny, nx, tz, ty, hz, hy, hx) -> bool:
    """Can a (tz, ty, nx) tile grid with the given halos cover the box?"""
    return _pallas_ok_3d(nz, ny, nx, tz, ty, hz, hy, hx)


def _interior_mask_3d(shape, halos):
    nz, ny, nx = shape
    fr, bk, tp, bt, lf, rt = halos
    zz = jnp.arange(nz)[:, None, None]
    yy = jnp.arange(ny)[None, :, None]
    xx = jnp.arange(nx)[None, None, :]
    return (
        (zz >= fr) & (zz < nz - bk)
        & (yy >= tp) & (yy < ny - bt)
        & (xx >= lf) & (xx < nx - rt)
    )


def _nonzero_taps(coeffs, point_fn) -> tuple[int, ...] | None:
    """The ascending flat indices of the non-zero weights, when the weights
    are known while the program is traced (a plan closed over, not passed
    into ``jit``) and some are zero; else ``None``, every window.  A
    function-mode ``point_fn`` is handed every window by position."""
    if point_fn is not _ref.weighted_point_fn or isinstance(
        coeffs, jax.core.Tracer
    ):
        return None
    taps = np.flatnonzero(np.asarray(coeffs))
    if taps.size in (0, np.size(coeffs)):
        return None
    return tuple(int(k) for k in taps)


def pad_field_3d(data: jnp.ndarray, *, halos, bc: str) -> jnp.ndarray:
    """Halo-pad an ``(nz, ny, nx)`` field on all three axes (wrap for
    periodic, zeros for ``np``) — the 3D counterpart of
    :func:`pad_field`, shared by the z-slab executor and the
    alignment-padded 3D kernel dispatch."""
    fr, bk, tp, bt, lf, rt = halos
    if bc == "periodic":
        for axis, (lo, hi) in enumerate(((fr, bk), (tp, bt), (lf, rt))):
            if lo or hi:
                parts = []
                if lo:
                    parts.append(jax.lax.slice_in_dim(
                        data, data.shape[axis] - lo, data.shape[axis], axis=axis
                    ))
                parts.append(data)
                if hi:
                    parts.append(jax.lax.slice_in_dim(data, 0, hi, axis=axis))
                data = jnp.concatenate(parts, axis=axis)
        return data
    return jnp.pad(data, ((fr, bk), (tp, bt), (lf, rt)))


def _stencil3d_pallas_padded(
    data, coeffs, out_init, *, point_fn, halos, bc, tz, ty, pz, py, taps,
    interpret,
):
    """Pallas dispatch for awkward 3D extents (prime/odd ``nz``/``ny``).

    The 2D alignment-padded trick lifted to 3D: halo-pad the field once
    (wrap or zeros by ``bc``) on all three axes, zero-grow z and y to the
    aligned ``(pz, py)`` tile multiples (x needs no growth — each block
    carries the full row), run the kernel in ``np`` mode — whose
    full-support interior is exactly the original domain — and slice the
    result back out.  The alignment zeros sit strictly beyond the halo
    ring, so no valid output ever reads them.
    """
    nz, ny, nx = data.shape
    fr, bk, tp, bt, lf, rt = halos
    padded = pad_field_3d(data, halos=halos, bc=bc)
    sz, sy, sx = padded.shape
    padded = jnp.pad(padded, ((0, pz - sz), (0, py - sy), (0, 0)))
    out = stencil3d_pallas(
        padded,
        coeffs,
        jnp.zeros_like(padded),
        point_fn=point_fn,
        halos=halos,
        bc="np",
        tz=tz,
        ty=ty,
        taps=taps,
        interpret=interpret,
    )
    out = jax.lax.slice(out, (fr, tp, lf), (fr + nz, tp + ny, lf + nx))
    if bc == "np":
        if out_init is None:
            out_init = jnp.zeros_like(data)
        mask = _interior_mask_3d(data.shape, halos)
        out = jnp.where(mask, out, out_init.astype(out.dtype))
    return out


def stencil_apply_3d(
    data: jnp.ndarray,
    coeffs: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
    *,
    point_fn: Callable = _ref.weighted_point_fn,
    halos=(0, 0, 0, 0, 0, 0),  # (front, back, top, bottom, left, right)
    bc: str = "periodic",
    tile: tuple | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply a 3D stencil on an ``(nz, ny, nx)`` field — the 3D Compute
    primitive.

    Same backend contract as :func:`stencil_apply`: ``auto`` picks the
    Pallas kernel when its structural constraints hold on a TPU (awkward
    prime/odd z/y extents route through the alignment-padded dispatch),
    otherwise the jnp oracle.  ``tile`` is the ``(tz, ty)`` block of the
    (z, y) Pallas grid; each block carries the full x row.
    """
    halos = tuple(int(h) for h in halos)  # hashable for the jit static arg
    nz, ny, nx = data.shape
    fr, bk, tp, bt, lf, rt = halos
    hz, hy, hx = max(fr, bk), max(tp, bt), max(lf, rt)
    tz, ty = (
        tile
        if tile is not None
        else (pick_tile_any(nz, target=8), pick_tile_any(ny, target=8))
    )

    clean = _pallas_ok_3d(nz, ny, nx, tz, ty, hz, hy, hx) and (
        tile is not None or (_aligned(ty) and _aligned(tz, 4))
    )
    if backend == "auto":
        backend = (
            "pallas"
            if _tpu_pallas(data.dtype)
            and (clean or (tile is None and hz <= nz and hy <= ny and hx <= nx))
            else "jnp"
        )
    if backend == "pallas":
        _pallas_dispatch("stencil3d")
        taps = _nonzero_taps(coeffs, point_fn)
        if not clean:
            if tile is not None:
                raise ValueError(
                    f"pallas backend needs tile|field and halo<=tile; got "
                    f"field=({nz},{ny},{nx}) tile=({tz},{ty}) "
                    f"halo=({hz},{hy},{hx})"
                )
            from repro.util import next_multiple

            sz, sy = nz + fr + bk, ny + tp + bt
            ptz, pz = pick_tile_padded(sz, target=8)
            pty, py = pick_tile_padded(sy, target=8)
            if ptz < hz:
                ptz = next_multiple(hz, 8)
                pz = next_multiple(sz, ptz)
            if pty < hy:
                pty = next_multiple(hy, 8)
                py = next_multiple(sy, pty)
            return _stencil3d_pallas_padded(
                data, coeffs, out_init,
                point_fn=point_fn, halos=halos, bc=bc,
                tz=ptz, ty=pty, pz=pz, py=py, taps=taps,
                interpret=_should_interpret(interpret),
            )
        return stencil3d_pallas(
            data,
            coeffs,
            out_init,
            point_fn=point_fn,
            halos=halos,
            bc=bc,
            tz=tz,
            ty=ty,
            taps=taps,
            interpret=_should_interpret(interpret),
        )
    if backend == "jnp":
        return _stencil3d_jnp(
            data, coeffs, out_init, point_fn=point_fn, halos=halos, bc=bc
        )
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# Pentadiagonal batched solves — public wrappers (kernel in kernels/penta.py)
# ---------------------------------------------------------------------------

from repro.kernels.penta import (  # noqa: E402  (import after defs is deliberate)
    penta_factor,
    penta_solve_factored,
    cyclic_penta_factor,
    cyclic_penta_solve_factored,
)


def penta_solve(
    l2, l1, d, u1, u2, rhs, *, cyclic: bool, backend: str = "auto",
    interpret: bool | None = None,
):
    """One-shot batched pentadiagonal solve: factor + substitute.

    ``rhs`` is (M,) or (M, N); diagonals are (M,).  For repeated solves with
    the same operator (the ADI hot path) use the factor/solve_factored pair —
    that split is cuSten's Create/Compute separation.
    """
    if cyclic:
        fac = cyclic_penta_factor(l2, l1, d, u1, u2)
        return cyclic_penta_solve_factored(
            fac, rhs, backend=backend, interpret=interpret
        )
    fac = penta_factor(l2, l1, d, u1, u2)
    return penta_solve_factored(fac, rhs, backend=backend, interpret=interpret)


# ---------------------------------------------------------------------------
# WENO5 advection — public wrapper (kernel in kernels/weno.py)
# ---------------------------------------------------------------------------


def weno_advect(
    q: jnp.ndarray,
    u: jnp.ndarray,
    v: jnp.ndarray,
    *,
    dx: float,
    dy: float,
    backend: str = "auto",
    tile: tuple | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """RHS of periodic 2D advection with upwinded WENO5 derivatives."""
    from repro.kernels.weno import weno5_advect_pallas

    ny, nx = q.shape
    ty, tx = tile if tile is not None else (pick_tile(ny), pick_tile(nx))
    if backend == "auto":
        backend = (
            "pallas"
            if _tpu_pallas(q.dtype) and _pallas_ok(ny, nx, ty, tx, 3, 3)
            else "jnp"
        )
    if backend == "pallas":
        _pallas_dispatch("weno5_advect")
        return weno5_advect_pallas(
            q, u, v, dx=dx, dy=dy, ty=ty, tx=tx,
            interpret=_should_interpret(interpret),
        )
    if backend == "jnp":
        return jax.jit(
            functools.partial(_ref.weno5_advect_ref, dx=dx, dy=dy)
        )(q, u, v)
    raise ValueError(f"unknown backend {backend!r}")


_ch_rhs_win_jnp = jax.jit(
    _ref.ch_rhs_win,
    static_argnames=("dt", "D", "gamma", "inv_h2", "inv_h4"),
)


def ch_rhs(
    c_n, c_nm1, *, dt, D, gamma, inv_h2, inv_h4,
    backend: str = "auto", tile: tuple | None = None,
    interpret: bool | None = None,
):
    """Fused Cahn–Hilliard explicit RHS (beyond-paper fusion kernel)."""
    from repro.kernels.fused_ch import ch_rhs_pallas

    ny, nx = c_n.shape
    ty, tx = tile if tile is not None else (pick_tile(ny), pick_tile(nx))
    if backend == "auto":
        backend = (
            "pallas"
            if _tpu_pallas(c_n.dtype) and _pallas_ok(ny, nx, ty, tx, 2, 2)
            else "jnp"
        )
    if backend == "pallas":
        _pallas_dispatch("ch_rhs")
        return ch_rhs_pallas(
            c_n, c_nm1, dt=dt, D=D, gamma=gamma, inv_h2=inv_h2, inv_h4=inv_h4,
            ty=ty, tx=tx, interpret=_should_interpret(interpret),
        )
    if backend == "jnp":
        return _ch_rhs_win_jnp(
            c_n, c_nm1, dt=float(dt), D=float(D), gamma=float(gamma),
            inv_h2=float(inv_h2), inv_h4=float(inv_h4),
        )
    raise ValueError(f"unknown backend {backend!r}")


def ch_rhs_xsweep(
    c_n, c_nm1, fac_x, *, dt, D, gamma, inv_h2, inv_h4,
    backend: str = "auto", ty: int | None = None,
    interpret: bool | None = None, unroll: int = 1,
):
    """Fused explicit RHS + implicit x-sweep:
    ``L_x^{-1} rhs(c_n, c_nm1)`` with ``fac_x`` the Create-time cyclic
    factors along x.  On TPU this is one ``pallas_call``
    (:func:`repro.kernels.fused_ch.ch_rhs_xsweep_pallas`); the jnp path
    composes the windowed RHS with the row-layout substitution — in both
    cases the RHS feeds the sweep in its native row layout with no
    transpose of the field in HBM (the Pallas kernel transposes 128-lane
    chunks in VMEM, the jnp path none).
    """
    from repro.kernels.fused_ch import (
        ch_rhs_xsweep_pallas,
        xsweep_tile,
        xsweep_tpu_problem,
    )
    from repro.kernels.penta import cyclic_penta_solve_factored_rows

    ny, nx = c_n.shape
    ty = ty if ty is not None else xsweep_tile(ny, nx, c_n.dtype.itemsize)
    backend = checked_backend(
        "ch_rhs_xsweep", xsweep_tpu_problem(ny, nx, ty, c_n.dtype), backend,
        interpret,
    )
    if backend == "pallas":
        _pallas_dispatch("ch_rhs_xsweep")
        return ch_rhs_xsweep_pallas(
            c_n, c_nm1, fac_x,
            dt=float(dt), D=float(D), gamma=float(gamma),
            inv_h2=float(inv_h2), inv_h4=float(inv_h4),
            ty=ty, interpret=_should_interpret(interpret),
        )
    if backend == "jnp":
        rhs = _ch_rhs_win_jnp(
            c_n, c_nm1, dt=float(dt), D=float(D), gamma=float(gamma),
            inv_h2=float(inv_h2), inv_h4=float(inv_h4),
        )
        return cyclic_penta_solve_factored_rows(
            fac_x, rhs, backend="jnp", unroll=unroll
        )
    raise ValueError(f"unknown backend {backend!r}")
