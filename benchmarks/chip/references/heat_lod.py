"""The periodic heat equation's LOD backward-Euler step, written plainly.

One step is ``c <- S_z S_y S_x c`` with ``S_a = (I - r delta_a^2)^{-1}``,
``delta_a^2`` the periodic second difference along axis ``a``.  Every
factor is diagonal in Fourier space, so ``n`` steps multiply mode ``k`` by
``prod over axes of (1 + r (2 - 2 cos(2 pi k_a / m_a)))^{-n}``:
:func:`evolve_exact` applies that in float64 on the host, with no rounding
to accumulate over the steps.  :func:`evolve_iterated` steps the scheme on
the device in any dtype, each ``S_a`` a dense circulant matrix applied
along its axis (worked out on the host in float64 from its symbol): in
bfloat16, with default-precision matmuls, it is the benchmark's control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from references.cahn_hilliard import _circulant

# contraction of each axis with a symmetric (m, m) matrix, axis order (z, y, x)
_ALONG = ("zyx,zk->kyx", "zyx,yk->zkx", "zyx,xk->zyk")


def _symbol(m: int, r: float, k) -> np.ndarray:
    return 1.0 / (1.0 + r * (2.0 - 2.0 * np.cos(2 * np.pi * k / m)))


def evolve_exact(c0: np.ndarray, r: float, n_steps: int) -> np.ndarray:
    """``n_steps`` LOD steps of ``c0``, exactly, in float64."""
    c0 = np.asarray(c0, np.float64)
    gain = 1.0
    for axis, m in enumerate(c0.shape):
        last = axis == c0.ndim - 1
        k = np.arange(m // 2 + 1 if last else m)
        shape = [1] * c0.ndim
        shape[axis] = k.size
        gain = gain * _symbol(m, r, k).reshape(shape)
    spectrum = np.fft.rfftn(c0) * gain**n_steps
    return np.fft.irfftn(spectrum, s=c0.shape, axes=tuple(range(c0.ndim)))


@functools.partial(jax.jit, static_argnames=("precision",))
def _iterate(c, mats, n_steps, *, precision):
    def step(_, c):
        for axis in (2, 1, 0):  # x, then y, then z
            c = jnp.einsum(_ALONG[axis], c, mats[axis], precision=precision)
        return c

    return jax.lax.fori_loop(0, n_steps, step, c)


def evolve_iterated(c0, r: float, n_steps: int, dtype="float32"):
    """``n_steps`` LOD steps of ``c0`` on the device in ``dtype``."""
    dtype = jnp.dtype(dtype)
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    mats = tuple(
        _circulant(jnp.asarray(np.fft.irfft(_symbol(m, r, np.arange(m // 2 + 1)), n=m),
                               dtype))
        for m in c0.shape)
    return _iterate(jnp.asarray(c0, dtype), mats, n_steps, precision=precision)
