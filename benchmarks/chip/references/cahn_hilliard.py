"""The paper's Cahn–Hilliard ADI scheme (eqs. 2-3), written plainly.

Periodic box, ``dC/dt = D lap(C^3 - C - gamma lap C)``: the bootstrap
half-step pair of eq. (3), then the full steps of eq. (2) with
``L = I + beta delta^4`` along x and then y.  The stencils are rolls;
each implicit solve applies ``L^{-1}`` exactly, as a dense circulant
matrix whose column is worked out on the host in float64 from ``L``'s
Fourier symbol, multiplied at ``Precision.HIGHEST`` (no recurrence, no Woodbury
correction).  Nothing here comes from the library under test: the
scheme is the one ``chip_smoke.py`` checks in float64 numpy, moved onto
the device so that it can follow a whole window's steps.

``dtype="bfloat16"`` runs the same scheme with bfloat16 fields and
default-precision (bfloat16) matmuls: the benchmark's control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

D2 = (1.0, -2.0, 1.0)
D4 = (1.0, -4.0, 6.0, -4.0, 1.0)


def _d(v, w, axis):
    """Centred periodic 1D stencil ``w`` along ``axis``."""
    out = 0.0
    for i, wi in enumerate(w):
        out = out + wi * jnp.roll(v, len(w) // 2 - i, axis=axis)
    return out


def inverse_band_column(m: int, beta: float) -> np.ndarray:
    """First column of ``(I + beta delta^4)^{-1}`` on a periodic line of
    ``m`` points, in float64: the inverse transform of ``1 / symbol``."""
    th = 2 * np.pi * np.arange(m // 2 + 1) / m
    return np.fft.irfft(1.0 / (1 + beta * (2 - 2 * np.cos(th)) ** 2), n=m)


@jax.jit
def _circulant(col):
    i = jnp.arange(col.shape[0])
    return col[(i[:, None] - i[None, :]) % col.shape[0]]


@functools.partial(jax.jit, static_argnames=("co", "precision"))
def _evolve(c0, hx, hy, fx, fy, n_steps, *, co, precision):
    half_bih, half_lap, lin, bih_c, lap_c = co
    dot = functools.partial(jnp.matmul, precision=precision)
    solve_x = lambda v, m: dot(v, m)  # noqa: E731  (m is symmetric)
    solve_y = lambda v, m: dot(m, v)  # noqa: E731
    cross = lambda v: _d(_d(v, D2, 0), D2, 1)  # noqa: E731
    lap_cube = lambda v: _d(v**3 - v, D2, 0) + _d(v**3 - v, D2, 1)  # noqa: E731

    ra = c0 - half_bih * (_d(c0, D4, 0) + 2 * cross(c0)) + half_lap * lap_cube(c0)
    ch = solve_x(ra, hx)
    rb = ch - half_bih * (_d(ch, D4, 1) + 2 * cross(ch)) + half_lap * lap_cube(ch)
    c1 = solve_y(rb, hy)

    def step(_, carry):
        cn, cm = carry
        cb = 2 * cn - cm
        bih = _d(cb, D4, 0) + _d(cb, D4, 1) + 2 * cross(cb)
        rhs = lin * (cn - cm) + bih_c * bih + lap_c * lap_cube(cn)
        v = solve_y(solve_x(rhs, fx), fy)
        return 2 * cn - cm + v, cn

    return jax.lax.fori_loop(0, n_steps, step, (c1, c0))[0]


def evolve(c0, *, lx: float, D: float, gamma: float, dt: float, n_steps: int,
           dtype="float32"):
    """``c`` after the bootstrap step and ``n_steps`` full steps from
    ``c0`` (an ``(ny, nx)`` field with ``dx == dy == lx / nx``)."""
    ny, nx = c0.shape
    h = lx / nx
    i2, i4 = 1 / h**2, 1 / h**4
    b_half, b_full = 0.5 * D * gamma * dt * i4, (2 / 3) * D * gamma * dt * i4
    # coefficients folded on the host: D gamma / h^4 alone is ~1e9 at 4096^2
    co = (0.5 * dt * D * gamma * i4, 0.5 * dt * D * i2, -2 / 3,
          -(2 / 3) * dt * gamma * D * i4, (2 / 3) * D * dt * i2)
    dtype = jnp.dtype(dtype)
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    mats = [_circulant(jnp.asarray(inverse_band_column(m, b), dtype))
            for b in (b_half, b_full) for m in (nx, ny)]
    return _evolve(jnp.asarray(c0, dtype), *mats, n_steps, co=co,
                   precision=precision)
