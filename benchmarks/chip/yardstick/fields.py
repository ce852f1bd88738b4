"""Initial fields made from the seed, on the device, in one jitted call."""

from __future__ import annotations

import functools

import jax
import numpy as np


def key(seed: int):
    """A PRNG key that keeps all 64 bits of ``seed`` with x64 off."""
    seed %= 2**64
    return jax.random.wrap_key_data(np.array(divmod(seed, 2**32), np.uint32))


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _uniform(k, amp, *, shape, dtype):
    return jax.random.uniform(k, shape, dtype, -amp, amp)


def uniform(seed: int, shape, amp: float, dtype="float32"):
    """Uniform random values in ``[-amp, amp)``: the deep-quench initial
    condition of the paper's CH example, and the heat cell's field."""
    return _uniform(key(seed), amp, shape=tuple(shape), dtype=dtype)
