"""Wall-clock micro-timing helpers (CPU host; TPU numbers come from the
chip benchmark, ``benchmarks/chip/run.py``, not from here)."""

from __future__ import annotations

import time

import jax


def time_call(fn, *args, warmup: int = 3, repeat: int = 15) -> float:
    """Minimum microseconds per call of a jitted function.

    Min-of-repeats (the ``timeit`` convention): on shared/throttled CI
    hosts scheduler preemption inflates individual calls severalfold, so
    the minimum — not the median — estimates what the code actually
    costs; the extra repeats make hitting at least one quiet window very
    likely.
    """
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[0]
