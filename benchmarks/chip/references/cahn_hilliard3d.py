"""The 3D Cahn–Hilliard ADI scheme, written plainly.

Periodic cube, ``dC/dt = D lap(C^3 - C - gamma lap C)``: the bootstrap
step, implicit in all three directions and first order,

    (I + b d_x^4)(I + b d_y^4)(I + b d_z^4)(C^1 - C^0)
        = dt D [-gamma lap^2 C^0 + lap (C^3 - C)^0],   b = D gamma dt / h^4

then the paper's three-level step (eq. 2) with a third implicit factor:
``L_x w = rhs``, ``L_y u = w``, ``L_z v = u``, ``C^{n+1} = 2C^n - C^{n-1}
+ v``, ``L = I + b delta^4``: the factors carry the bootstrap's ``b``,
(3/2) of eq. (2)'s ``beta = (2/3) D gamma dt / h^4``, since three factors
at ``beta`` grow for ``beta`` above about 0.0137.  ``lap``
is the 7-point Laplacian by rolls and ``lap^2`` that Laplacian applied
twice.  Each implicit solve applies ``L^{-1}`` exactly along its axis, as
a dense circulant matrix whose column is worked out on the host in
float64 from ``L``'s Fourier symbol, multiplied at ``Precision.HIGHEST``
(no recurrence, no Woodbury correction).  Nothing here comes from the
library under test.

``dtype="bfloat16"`` runs the same scheme with bfloat16 fields and
default-precision (bfloat16) matmuls: the benchmark's control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references.cahn_hilliard import _circulant, inverse_band_column


def _lap(v):
    return sum(jnp.roll(v, s, axis=a) for a in range(3) for s in (1, -1)) - 6 * v


@functools.partial(jax.jit, static_argnames=("co", "precision"))
def _evolve(c0, inv, n_steps, *, co, precision):
    b4, b2, lin, bih_c, lap_c = co

    dot = functools.partial(jnp.matmul, precision=precision)

    def solve(v, mats):  # x, then y, then z; each matrix is symmetric
        mz, my, mx = mats
        v = dot(my, dot(v, mx))
        return dot(mz, v.reshape(v.shape[0], -1)).reshape(v.shape)

    c1 = c0 + solve(-b4 * _lap(_lap(c0)) + b2 * _lap(c0**3 - c0), inv)

    def step(_, carry):
        cn, cm = carry
        cb = 2 * cn - cm
        rhs = lin * (cn - cm) + bih_c * _lap(_lap(cb)) + lap_c * _lap(cn**3 - cn)
        return cb + solve(rhs, inv), cn

    return jax.lax.fori_loop(0, n_steps, step, (c1, c0))[0]


def evolve(c0, *, lx: float, D: float, gamma: float, dt: float, n_steps: int,
           dtype="float32"):
    """``C`` after the bootstrap step and ``n_steps`` full steps from
    ``c0``, an ``(nz, ny, nx)`` field with ``h = lx / nx`` on every axis."""
    h = lx / c0.shape[2]
    i2, i4 = 1 / h**2, 1 / h**4
    # coefficients folded on the host: D gamma / h^4 alone is ~3e5 at 512^3
    co = (dt * D * gamma * i4, dt * D * i2, -2 / 3,
          -(2 / 3) * dt * gamma * D * i4, (2 / 3) * D * dt * i2)
    dtype = jnp.dtype(dtype)
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    inv = tuple(_circulant(jnp.asarray(inverse_band_column(m, D * gamma * dt * i4),
                                       dtype))
                for m in c0.shape)
    return _evolve(jnp.asarray(c0, dtype), inv, n_steps, co=co, precision=precision)
