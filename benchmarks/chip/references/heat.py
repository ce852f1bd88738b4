"""The periodic heat equation's forward-Euler step, written plainly.

One step is ``c <- c + r delta^2 c`` with the 7-point Laplacian
``delta^2`` (each axis' second difference, periodic).  The scheme is
linear and diagonal in Fourier space, so ``n`` steps multiply mode ``k``
by ``(1 + r lambda_k)^n`` with ``lambda_k = sum over axes of
(2 cos(2 pi k / m) - 2)``: :func:`evolve_exact` applies that in float64
on the host, with no rounding to accumulate over the steps.
:func:`evolve_iterated` steps the scheme on the device, in any dtype:
in bfloat16 it is the benchmark's control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def evolve_exact(c0: np.ndarray, r: float, n_steps: int) -> np.ndarray:
    """``n_steps`` forward-Euler steps of ``c0``, exactly, in float64."""
    c0 = np.asarray(c0, np.float64)
    lam = 0.0
    for axis, m in enumerate(c0.shape):
        last = axis == c0.ndim - 1
        k = np.arange(m // 2 + 1 if last else m)
        shape = [1] * c0.ndim
        shape[axis] = k.size
        lam = lam + (2 * np.cos(2 * np.pi * k / m) - 2).reshape(shape)
    spectrum = np.fft.rfftn(c0) * (1 + r * lam) ** n_steps
    return np.fft.irfftn(spectrum, s=c0.shape, axes=tuple(range(c0.ndim)))


@functools.partial(jax.jit, static_argnames=("r",))
def _iterate(c, n_steps, *, r):
    def step(_, c):
        lap = sum(jnp.roll(c, s, axis=a) for a in range(c.ndim) for s in (1, -1))
        return c + r * (lap - 2 * c.ndim * c)

    return jax.lax.fori_loop(0, n_steps, step, c)


def evolve_iterated(c0, r: float, n_steps: int, dtype="float32"):
    """``n_steps`` forward-Euler steps of ``c0`` on the device in ``dtype``."""
    return _iterate(jnp.asarray(c0, dtype), n_steps, r=float(r))
