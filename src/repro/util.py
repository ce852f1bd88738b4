"""Small shared helpers used across the framework."""

from __future__ import annotations

import math
import os
import warnings
from collections.abc import Sequence
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


#: The compile cache's place when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed, because the path is part of the cache key.
REPO_JAX_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Called by the command-line entry points only, never on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_JAX_CACHE))
    return str(REPO_JAX_CACHE)


def block_spec(block_shape, index_map, **kwargs):
    """A Pallas ``BlockSpec`` whose index map returns int32 block indices.

    With ``jax_enable_x64`` on, the Python ints of an index map (the
    default one included) become int64, which Mosaic cannot lower; the
    TPU compile refuses the kernel."""

    def index_map32(*grid):
        return tuple(
            jax.lax.convert_element_type(i, jnp.int32) for i in index_map(*grid)
        )

    from jax.experimental import pallas as pl

    return pl.BlockSpec(block_shape, index_map32, **kwargs)


def wrap_block(i, n: int):
    """Periodic neighbour block index ``i mod n`` for ``i >= -n``, in int32
    arithmetic (``jnp.remainder`` goes through int64 under x64)."""
    return jax.lax.rem(i + np.int32(n), np.int32(n))


def clamp_block(i, n: int):
    """Clamped neighbour block index in ``[0, n)``, in int32 arithmetic."""
    return jax.lax.clamp(np.int32(0), i, np.int32(n - 1))


def warn_deprecated(old: str, new: str, *, stacklevel: int = 3) -> None:
    """Emit the one-release deprecation warning for a legacy API name.

    Every pre-facade entry point (the nine per-dimension ``stencil_*``
    functions, both ``make_adi_operator*`` factories) funnels through
    this, so the message shape — and therefore the warning filter in
    ``tests/conftest.py`` — stays in one place."""
    warnings.warn(
        f"{old} is deprecated; use repro.{new} — "
        "the unified four-function facade (repro.api)",
        DeprecationWarning,
        stacklevel=stacklevel,
    )


def deprecated_shim(old: str, new: str, impl):
    """Wrap a pre-facade entry point: warn via :func:`warn_deprecated`
    on every call, then delegate to the private implementation.  The one
    shim factory for both the ``stencil_*`` family and the
    ``make_adi_operator*`` factories, so the wrapper shape (name, doc,
    warning stacklevel) cannot drift between them."""

    def shim(*args, **kwargs):
        warn_deprecated(old, new)
        return impl(*args, **kwargs)

    shim.__name__ = shim.__qualname__ = old
    shim.__doc__ = (
        f"Deprecated alias (one release): use ``repro.{new}`` — the unified "
        f"four-function facade in :mod:`repro.api`.  Behaviour is identical "
        f"to the pre-facade ``{old}``; every call emits a "
        f"``DeprecationWarning``.  Example migration::\n\n"
        f"    import warnings, repro\n"
        f"    with warnings.catch_warnings():\n"
        f"        warnings.simplefilter('ignore', DeprecationWarning)\n"
        f"        result = repro.{old}(...)   # old spelling, still works\n"
        f"    result = repro.{new}(...)       # the facade equivalent\n\n"
        f"See the migration table in README.md ('Migrating from the "
        f"per-dimension API') for the exact argument mapping."
    )
    return shim


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pick_tile(n: int, target: int = 128) -> int:
    """Largest divisor of ``n`` that is ``<= target``.

    Used to choose Pallas block sizes that exactly tile the grid (periodic
    wrap-around at block granularity requires exact division).  Prefers
    hardware-aligned powers of two.
    """
    if n <= target:
        return n
    for cand in sorted({target, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1}, reverse=True):
        if cand <= target and n % cand == 0:
            return cand
    return math.gcd(n, target) or 1


def pick_tile_any(n: int, target: int = 256) -> int:
    """Largest divisor of ``n`` that is ``<= target`` (any divisor, not just
    powers of two).

    Used by the batched-1D kernel, where awkward extents (prime batch
    counts, non-power-of-two line lengths) are routine: a divisor like 150
    of 300 keeps the Pallas grid small where :func:`pick_tile` would fall
    back to a tiny power of two."""
    if n <= target:
        return n
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            if d <= target:
                best = max(best, d)
            if n // d <= target:
                best = max(best, n // d)
        d += 1
    return best


def next_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is ``>= n``."""
    return ceil_div(n, m) * m


def pick_tile_padded(n: int, target: int = 128, align: int = 8):
    """Tile choice with padding for awkward extents: ``(tile, n_padded)``.

    :func:`pick_tile_any` degrades on prime/odd extents — a 127-wide field
    gets a single misaligned 127 mega-tile, a 509-wide one a degenerate
    tile of 1.  Instead of accepting that, pick a hardware-aligned tile
    and report the padded extent the kernel wrapper should grow the field
    to (``n_padded == n`` means no padding needed).  Among the aligned
    candidate tiles the one wasting the least padding wins, largest tile
    on ties.
    """
    t = pick_tile_any(n, target)
    if t % align == 0:
        return t, n  # cleanly tiled and aligned as-is
    best_tile, best_pad = align, next_multiple(n, align)
    cand = align
    while cand * 2 <= target:
        cand *= 2
        padded = next_multiple(n, cand)
        if padded <= best_pad:  # ties -> larger tile
            best_tile, best_pad = cand, padded
    return best_tile, best_pad


def tile_candidates(n: int, cap: int = 256, limit: int = 3):
    """A few aligned divisor tiles of ``n`` for the autotuner's candidate
    space, largest first (shared by the plan and ADI tuners)."""
    cands = [t for t in (256, 128, 64, 32, 16, 8) if t <= cap and n % t == 0]
    return cands[:limit]


def tolerance_for(dtype, scale: float = 1.0) -> dict:
    """Sensible allclose tolerances per dtype for kernel<->oracle checks.

    ``scale`` loosens both tolerances by a factor for paths with a longer
    rounding chain (interpret-mode substitution recurrences, chunked
    pipelines) while keeping the per-dtype baseline in one place.
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float64:
        tol = dict(rtol=1e-12, atol=1e-12)
    elif dtype == jnp.float32:
        tol = dict(rtol=1e-5, atol=1e-5)
    elif dtype == jnp.bfloat16:
        tol = dict(rtol=2e-2, atol=2e-2)
    elif dtype == jnp.float16:
        tol = dict(rtol=2e-3, atol=2e-3)
    else:
        tol = dict(rtol=1e-5, atol=1e-5)
    if scale != 1.0:
        tol = {k: v * scale for k, v in tol.items()}
    return tol


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0 or unit == "PiB":
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def prod(xs: Sequence[int]) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out
